"""Probabilistic -> deterministic plan compilation (paper §VI, Table I):
a two-stage compiler over the logical plan DAG.

A Plan is a small dataflow DAG of LOGICAL operator nodes (the zoo below).
``compile_plan`` no longer interprets it directly: it first LOWERS the
logical DAG to an explicit physical-plan IR — :mod:`repro.db.physical`,
where every node carries its execution strategy and a partitioning
property (Replicated / RowBlocked / HashPartitioned) — and then an
EXECUTOR (this module) interprets the physical plan, realising the
paper's central claim: probabilistic queries run on a *deterministic*
engine (here: XLA) once every probabilistic operator is rewritten to a
deterministic one + segment-UDA calls (:mod:`repro.core.uda`).

``compile_plan(root, mesh)`` lowers the SAME logical plan for a device
mesh and runs the whole physical plan inside ONE shard_map — no stage
keeps a replicated copy of any base table:

    ShardScan       the shard-local block of the (chunk-padded) base table
    Select / Map    embarrassingly parallel on the local block
    GatherJoin      small build side: all-gather the right relation's
                    (key, p, cols) columns, probe locally
    ShuffleJoin     build side above ``join_gather_budget`` (the
                    ``FKJoin.gather_budget`` per-node override wins):
                    hash-partition build rows AND probe keys to
                    ``key % n_shards`` owners with ``dist.shuffle_by_key``
                    (static buckets, overflow accounted), match
                    shard-locally, shuffle responses home — peak build
                    rows/device O(build/shards), no replicated fallback
    CoPartitioned-  the fused shuffle -> aggregate pipeline: when the
    Join /          downstream GROUP BY keys on the probe join key, probe
    Repartition     rows ship (p, canonical chunk id, value columns) and
                    matched rows STAY at their owner — no shuffle-home
                    round-trip (``dist.copartitioned_fk_join``);
                    ``dist.repartition_by_key`` is the no-join feed
    group ids       two-phase distributed unique (exact under overflow;
                    `db.distributed.group_ids_sharded`) — owner-local
                    over HashPartitioned blocks, same merged code table
    PartialAgg /    per-shard, per-canonical-chunk UDA Accumulate, then
    MergeAgg        ONE collective per aggregation pass assembling every
                    chunk state (`db.distributed.allgather_merge`) and the
                    replicated Finalize; group-level outputs are
                    replicated Tables
    PartitionedAgg  the HashPartitioned Accumulate: ONE compound
                    (chunk, group) pass over the exchange buffer, the
                    canonical chunk fold finished LOCALLY per owner, and
                    one psum / gather-fold Merge
                    (`db.distributed.partitioned_merge`)

    Strategy choice is the enumerate -> cost -> pick pass of
    ``physical.lower_plan`` over the explicit model in ``db/cost.py``;
    the budget knobs survive as cost overrides.

Determinism contract: every aggregation pass folds its tuples over a
fixed grid of ``canonical_chunks`` contiguous chunks and merges the chunk
states in the one fixed tree of :func:`repro.core.uda.tree_fold`
(pow2-base + sequential tail).  Each chunk is computed wholly on one
shard and ALL chunk states are gathered before the fold, so ANY shard
count — 2, 3, 4, ... — computes the SAME tree and
``compile_plan(root, mesh)`` results are BIT-IDENTICAL to
``compile_plan(root, None)`` — asserted per-plan by the mesh-equivalence
harness in tests/conftest.py, including plans that lower to ShuffleJoin.
Per-device memory is O(rows / shards) for every pipeline stage (plus
gathered small build sides and group-level state), not O(total rows).

Node zoo (Table I rows in brackets):

    Scan(name)                               [I]   R -> R^p
    Select(child, pred)                      [II]  sigma, deterministic cond
    Map(child, name, fn)                     [--]  computed column
    FKJoin(l, r, lk, rk, cols[, budget])     [IV]  join, deterministic cond
    Project(child, keys, max_groups)         [V]   GROUP BY + AtLeastOne
    GroupAgg(child, keys, agg, value, ...)   [VI]  GROUP BY + PGF UDAs
                                                   (+ `extra` riders share
                                                   ONE accumulation pass)
    ReweightGreater(child, agg_of, vs, ...)  [III] p *= P(SUM > threshold)
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from types import SimpleNamespace
from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import uda
from ..testing import faults
from . import cost as C
from . import operators as ops
from . import physical as phys
from .report import ExecutionReport, ReportBuilder, nan_count
from .table import HostTable, Table


class Node:
    pass


@dataclasses.dataclass(frozen=True)
class Scan(Node):
    name: str


@dataclasses.dataclass(frozen=True)
class Select(Node):
    child: Node
    pred: Callable[[Table], jnp.ndarray]


@dataclasses.dataclass(frozen=True)
class Map(Node):
    """Attach a computed column `name` = fn(table) to the child relation."""
    child: Node
    name: str
    fn: Callable[[Table], jnp.ndarray]


@dataclasses.dataclass(frozen=True)
class FKJoin(Node):
    """Many-to-one equijoin.  ``gather_budget`` overrides the compiler's
    global ``join_gather_budget`` for THIS join (rows of build side that
    may be all-gathered; larger builds lower to ShuffleJoin on a mesh), so
    mixed plans can gather small dimensions while shuffling large ones."""
    left: Node
    right: Node
    left_key: str
    right_key: str
    right_cols: tuple
    gather_budget: int | None = None


@dataclasses.dataclass(frozen=True)
class Project(Node):
    child: Node
    keys: tuple
    max_groups: int


@dataclasses.dataclass(frozen=True)
class GroupAgg(Node):
    """Returns a dict of per-group UDA results, not a Table (PGF-valued
    columns live outside the 1NF Table, §VI-C).

    The primary aggregate lands under "sum" / "cumulants" / "exact" /
    "minmax" (by method/agg); each `extra` entry (name, value_col, agg,
    method) rides the SAME accumulation pass and lands under its own name.
    Group confidence (AtLeastOne) is always included.  `value` == "" means
    COUNT (all-ones).

    ``method="exact"`` computes the full per-group SUM/COUNT distribution
    via the grouped log-CF UDA (Pallas-accelerated on TPU) and requires
    ``num_freq`` = max aggregate value + 1; the result is a (max_groups,
    num_freq) row-stochastic coefficient matrix.  When max_groups *
    num_freq exceeds the planner's ``cf_budget_elems``, the compiler
    accumulates the state in multiple passes over frequency slabs (each
    slab additively merged on a mesh) — see ``compile_plan``.
    """
    child: Node
    keys: tuple
    value: str            # column to aggregate ("" = COUNT)
    agg: str              # SUM | COUNT | MIN | MAX
    max_groups: int
    method: str = "normal"  # normal | cumulants | exact
    extra: tuple = ()
    kappa: int = 64       # MIN/MAX support capacity per group
    num_freq: int = 0     # exact: distribution capacity (max sum + 1)


@dataclasses.dataclass(frozen=True)
class ReweightGreater(Node):
    """sigma_{AGG(B) > C}: group child by keys, SUM(value), then keep each
    group with p = AtLeastOne * P(SUM > threshold) (Table I row III).
    The threshold is `threshold_col` (per-group column) when set, else the
    constant `threshold`; `carry_cols` are extra per-group columns kept on
    the output Table (all valid writers of a group agree)."""
    child: Node
    keys: tuple
    value: str
    threshold_col: str
    max_groups: int
    threshold: float | None = None
    carry_cols: tuple = ()


# ---------------------------------------------------- parameterized plans
@dataclasses.dataclass(frozen=True)
class Param:
    """A named scalar hole in a logical plan: the value arrives at RUN
    time (``compiled(tables, params={name: value})``) instead of being
    baked into the trace, so one compiled executable serves a whole
    family of queries — and ``jax.vmap`` over the params runs an N-point
    parameter sweep as ONE device program (see
    :class:`repro.db.serving.QueryService.sweep`).  Legal as
    :attr:`ReweightGreater.threshold` and inside :class:`Parameterized`
    predicates/column functions."""
    name: str


@dataclasses.dataclass(frozen=True)
class Parameterized:
    """A Select predicate / Map column function with lifted scalar
    parameters: ``fn(table, *values)`` receives the named params' values
    in ``params`` order.  Structurally hashable (the plan cache keys on
    the wrapped function's bytecode + the param names), and the executor
    feeds it the run's parameter environment."""
    fn: Callable
    params: tuple

    def __call__(self, t: Table, env: Dict[str, jnp.ndarray]):
        return self.fn(t, *(env[p] for p in self.params))


def plan_params(root: Node) -> frozenset:
    """The set of parameter names a logical plan needs at run time."""
    names: set = set()

    def walk(n):
        for f in ("child", "left", "right"):
            c = getattr(n, f, None)
            if isinstance(c, Node):
                walk(c)
        for f in ("pred", "fn"):
            v = getattr(n, f, None)
            if isinstance(v, Parameterized):
                names.update(v.params)
        if isinstance(getattr(n, "threshold", None), Param):
            names.add(n.threshold.name)

    walk(root)
    return frozenset(names)


def plan_key(root: Node) -> tuple:
    """Stable structural cache key of a logical plan: two separately
    constructed but identical plans (same node structure, same predicate
    bytecode and captured constants, same static knobs) produce EQUAL
    keys — the property the serving layer's plan cache and the streamed
    wave cache key executables on.  Delegates to
    :func:`repro.db.physical.structural_key`; unknown objects degrade to
    identity keys (a possible miss, never a false hit)."""
    return ("plan", phys.structural_key(root))


def mesh_fingerprint(mesh) -> tuple | None:
    """Hashable identity of a mesh for compile-cache keys (axis names,
    mesh shape and device ids — what the lowering and the collectives
    depend on); None for single-device compiles."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


class LRUCache:
    """Bounded least-recently-used mapping with hit/miss/eviction
    counters and an ``on_evict`` hook — the executable-cache primitive
    behind the streamed wave cache and :class:`repro.db.serving.
    PlanCache`.  The CPU jaxlib backend segfaults once a process
    accretes a few hundred live compiled executables (see
    docs/serving.md), so every cache holding compiled functions must
    bound its population and drop executables on eviction."""

    def __init__(self, capacity: int, on_evict: Callable | None = None):
        if capacity < 1:
            raise ValueError(f"LRUCache capacity must be >= 1, got "
                             f"{capacity}")
        self.capacity = capacity
        self.on_evict = on_evict
        self._data: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key) -> bool:
        return key in self._data

    def items(self) -> list:
        """(key, value) pairs, least recently used first; counts nothing."""
        return list(self._data.items())

    def get(self, key, default=None):
        if key in self._data:
            self._data.move_to_end(key)
            self.hits += 1
            return self._data[key]
        self.misses += 1
        return default

    def put(self, key, value) -> None:
        if key in self._data:
            self._data.move_to_end(key)
        self._data[key] = value
        self._trim()

    def _trim(self) -> None:
        while len(self._data) > self.capacity:
            _, old = self._data.popitem(last=False)
            self.evictions += 1
            if self.on_evict is not None:
                self.on_evict(old)

    def set_capacity(self, capacity: int) -> None:
        if capacity < 1:
            raise ValueError(f"LRUCache capacity must be >= 1, got "
                             f"{capacity}")
        self.capacity = capacity
        self._trim()

    def clear(self) -> None:
        """Evict everything (the on_evict hook runs for each entry)."""
        while self._data:
            _, old = self._data.popitem(last=False)
            self.evictions += 1
            if self.on_evict is not None:
                self.on_evict(old)

    def info(self) -> dict:
        return dict(size=len(self._data), capacity=self.capacity,
                    hits=self.hits, misses=self.misses,
                    evictions=self.evictions)


def _drop_executables(fns) -> None:
    """LRU eviction hook: drop a jitted callable's (or tuple of
    callables') compiled executables so the compiler footprint stays
    flat."""
    if not isinstance(fns, tuple):
        fns = (fns,)
    for f in fns:
        clear = getattr(f, "clear_cache", None)
        if clear is not None:
            clear()


#: Process-wide BOUNDED jit cache of the streamed executor's per-wave
#: functions, keyed structurally (plan + mesh + grid params) so repeated
#: compiles of the same streamed plan — including separately constructed
#: identical plans — reuse one traced wave pair, while distinct plans
#: past the capacity evict the least-recently-used executables instead
#: of accreting until the CPU backend segfaults.  Replaces the unbounded
#: per-``compile_plan`` ``_wave_cache`` dict.
_WAVE_CACHE = LRUCache(capacity=32, on_evict=_drop_executables)


def set_wave_cache_capacity(capacity: int) -> int:
    """Resize the streamed executor's bounded wave-function cache
    (evicting down to the new capacity if needed).  Returns the
    previous capacity so callers can restore it."""
    old = _WAVE_CACHE.capacity
    _WAVE_CACHE.set_capacity(capacity)
    return old


def wave_cache_info() -> dict:
    """Size/capacity/hit/miss/eviction counters of the wave cache."""
    return _WAVE_CACHE.info()


#: Process-wide streamed-transfer counters (benchmarks / tests): bytes
#: actually shipped in wave slabs (column-pruned slabs count only the
#: pruned payload), wave count, and the host-side slab-slice seconds
#: (the gather from [mmap] host arrays into the ping-pong buffers — the
#: measured bottleneck on thin hosts that pruning attacks).
_STREAM_STATS = {"slab_bytes": 0, "waves": 0, "slice_s": 0.0}


def reset_stream_stats() -> None:
    """Zero the streamed-transfer counters (call before a measured run)."""
    _STREAM_STATS.update(slab_bytes=0, waves=0, slice_s=0.0)


def stream_stats() -> dict:
    """Snapshot of the streamed-transfer counters since the last reset."""
    return dict(_STREAM_STATS)


def _agg_uda(agg: str, method: str, kappa: int, num_freq: int = 0,
             freq_lo: int = 0, freq_cnt: int | None = None) -> uda.UDA:
    if agg in ("SUM", "COUNT"):
        if method == "normal":
            return uda.SumNormal()
        if method == "cumulants":
            return uda.SumCumulants()
        if method == "exact":
            if num_freq <= 0:
                raise ValueError(
                    "GroupAgg(method='exact') needs num_freq = max "
                    "aggregate value + 1 (the static distribution capacity)")
            return uda.SumCF(num_freq, freq_lo=freq_lo, freq_cnt=freq_cnt)
        raise ValueError(
            f"GroupAgg method {method!r} is not supported by the planner "
            "(expected 'normal', 'cumulants' or 'exact')")
    if agg in ("MIN", "MAX"):
        if method == "exact":
            raise ValueError(
                "GroupAgg method 'exact' applies to SUM/COUNT only; MIN/MAX "
                "distributions come from the MinMax UDA (kappa support)")
        return uda.MinMax(kappa=kappa, sign=1.0 if agg == "MIN" else -1.0)
    raise ValueError(agg)


def _out_key(agg: str, method: str) -> str:
    if agg in ("MIN", "MAX"):
        return "minmax"
    return {"cumulants": "cumulants", "exact": "exact"}.get(method, "sum")


def _freq_slabs(num_freq: int, max_groups: int, budget: int) -> tuple:
    """Split [0, num_freq) into slabs so each (max_groups, slab) exact-CF
    state stays within ``budget`` elements; slab widths stay lane-aligned
    (multiples of 128) so the Pallas kernel's frequency padding is bounded."""
    f_slab = max(1, budget // max(1, max_groups))
    if f_slab >= num_freq:
        return ((0, num_freq),)
    if f_slab > 128:
        f_slab -= f_slab % 128
    return tuple((lo, min(f_slab, num_freq - lo))
                 for lo in range(0, num_freq, f_slab))


# ---------------------------------------------------------------------------
# Aggregation-pass plumbing shared by the resident executor (run_agg) and
# the streamed wave loop: spec/value collection, the frequency-slab
# schedule, per-slab UDA construction, slab-state concatenation and the
# kind epilogue.  One copy => the two paths cannot drift apart.
def _pass_values(specs, t: Table) -> dict:
    """Fetch each spec's value column exactly once (uda.accumulate dedups
    shared columns by identity; the raw dtype is kept so integer sources
    stay kernel-eligible)."""
    values: dict = {}
    cols: dict = {}
    for name, value, agg, _method in specs:
        if agg == "COUNT" or not value:
            values[name] = None
        else:
            if value not in cols:
                cols[value] = t[value]
            values[name] = cols[value]
    return values


def _pass_slabs(pa, cf_budget_elems: int) -> tuple:
    """(exact aggregate names, frequency-slab schedule) of one pass."""
    exact_names = [s[0] for s in pa.specs if s[3] == "exact"]
    slabs = (_freq_slabs(pa.num_freq, pa.max_groups,
                         cf_budget_elems // (2 * len(exact_names)))
             if exact_names else ((0, pa.num_freq),))
    return exact_names, slabs


def _model_freq_split(cnt: int, model_size: int) -> int | None:
    """Per-model-rank frequency width of one exact-CF slab, or None when
    the slab cannot split (no model axis, or fewer frequencies than
    ranks).  Rank r accumulates the window [lo + r*f_loc, lo + (r+1)*
    f_loc); per-frequency CF math is elementwise-independent in k, so the
    disjoint windows reassembled by ``SumCF.reduce_model``'s tiled
    all-gather reproduce the unsharded columns BIT-identically (the
    ceil-split's over-range columns are clamped in the kernel / harmless
    in the scan and sliced away after the gather)."""
    if model_size <= 1 or cnt < model_size:
        return None
    return -(-cnt // model_size)


def _slab_udas(pa, si: int, lo: int, cnt: int, values: dict,
               model: str | None = None, model_size: int = 1) -> tuple:
    """UDA dict + value dict of frequency-slab pass ``si``: pass 0 carries
    confidence and every non-exact aggregate; every pass carries the
    exact aggregates' (lo, cnt) frequency window.

    With a ``model`` axis (call inside shard_map only), each exact UDA
    accumulates just this model rank's 1/model_size frequency window of
    the slab — per-device phase work drops by the axis size; the caller
    reassembles full-width states with :func:`_gather_model_slabs`."""
    udas_i: dict = {}
    vals_i: dict = {}
    if si == 0:
        udas_i["confidence"] = uda.AtLeastOne()
        vals_i["confidence"] = None
        for name, _value, agg, method in pa.specs:
            if method != "exact":
                udas_i[name] = _agg_uda(agg, method, pa.kappa)
                vals_i[name] = values[name]
    f_loc = _model_freq_split(cnt, model_size) if model else None
    for name, _value, agg, method in pa.specs:
        if method == "exact":
            if f_loc is None:
                udas_i[name] = _agg_uda(agg, method, pa.kappa, pa.num_freq,
                                        lo, cnt)
            else:
                rank = jax.lax.axis_index(model)
                udas_i[name] = _agg_uda(agg, method, pa.kappa, pa.num_freq,
                                        lo + rank * f_loc, f_loc)
            vals_i[name] = values[name]
    return udas_i, vals_i


def _gather_model_slabs(udas_i: dict, sts: dict, exact_names, cnt: int,
                        model: str | None, model_size: int) -> dict:
    """Reassemble model-sharded exact-CF windows into the full (lo, cnt)
    slab: one tiled all-gather per exact state (``SumCF.reduce_model``),
    dropping the ceil-split's over-range columns.  Model replicas leave
    with bit-identical full-width states — the replicated-output contract
    every downstream consumer (slab append, chunk-state filing, P()
    out_specs) assumes.  No-op when :func:`_slab_udas` did not split."""
    if model is None or _model_freq_split(cnt, model_size) is None:
        return sts
    out = dict(sts)
    for name in exact_names:
        st = udas_i[name].reduce_model(sts[name], model)
        out[name] = uda.CFState(st.log_abs[..., :cnt], st.angle[..., :cnt])
    return out


def _append_slab(states: dict, udas: dict, udas_i: dict, sts: dict) -> None:
    """Fold one slab pass's merged states in: first slab registers the
    state (and its Finalize UDA), later slabs append their frequency
    window at the FOLDED level."""
    for name, st in sts.items():
        if name in states:              # append the frequency slab
            prev = states[name]
            states[name] = uda.CFState(
                jnp.concatenate([prev.log_abs, st.log_abs], -1),
                jnp.concatenate([prev.angle, st.angle], -1))
        else:
            states[name] = st
            udas[name] = udas_i[name]


def _lost_group_count(code_live, big, merged, ids):
    """Live rows whose group code was dropped past ``max_groups``: a
    dropped code can never equal ``merged[ids]`` (the table holds only
    the kept distinct codes), while every kept live code does — so the
    mismatch count is exactly the rows the group-code table lost."""
    return jnp.sum((code_live != big)
                   & (merged[ids] != code_live)).astype(jnp.int32)


def _finalize_pass(node, pa, udas: dict, states: dict, gvalid,
                   key_columns, rb=None, label: str = "", params=None):
    """The replicated epilogue of one aggregation pass, selected by
    ``node.kind``; ``key_columns(cols)`` returns the per-group
    representatives of the named columns.  With a :class:`ReportBuilder`
    the pass also files its diagnostics: NaN counts of every folded UDA
    state and the per-group §V-B.2 truncation mass of each MIN/MAX
    aggregate."""
    if rb is not None:
        for name, st in states.items():
            rb.state_nan(f"{label}.{name}", nan_count(st))
        for name, _value, agg, _method in pa.specs:
            if agg in ("MIN", "MAX"):
                rb.tail(f"{label}.{name}",
                        udas[name].tail_mass(states[name]))
    conf = udas["confidence"].finalize(states["confidence"])
    if node.kind == "project":
        gcols = key_columns(list(pa.keys))
        return Table(gcols, conf, gvalid, node.part)
    if node.kind == "reweight":
        mu, var = udas["sum"].finalize(states["sum"])
        carry = list(pa.keys) + list(node.carry_cols)
        if node.threshold_col:
            gcols = key_columns(carry + [node.threshold_col])
            thr = gcols[node.threshold_col].astype(mu.dtype)
        else:
            gcols = key_columns(carry)
            thr = node.threshold
            if isinstance(thr, Param):      # lifted constant: value at run
                thr = (params or {})[thr.name]
            thr = jnp.asarray(thr, mu.dtype)
        p_gt = ops.normal_greater(mu, var, thr)
        return Table({k: gcols[k] for k in carry}, conf * p_gt,
                     gvalid, node.part)
    out = dict(valid=gvalid, keys=key_columns(list(pa.keys)),
               confidence=conf)
    for name, _value, agg, _method in pa.specs:
        u, st = udas[name], states[name]
        if agg in ("MIN", "MAX"):
            out[name] = ops.minmax_runs(u, st)
        else:
            out[name] = u.finalize(st)
    return out


# ------------------------------------------------- streamed-plan surgery
def _iter_phys(node):
    yield node
    for f in ("child", "left", "right"):
        c = getattr(node, f, None)
        if isinstance(c, phys.PhysNode):
            yield from _iter_phys(c)


def _lowest_streamed_agg(node):
    """The LOWEST MergeAgg whose subtree contains a StreamedScan — the
    pass the wave loop executes; everything above it sees only the pass's
    replicated group-level output and runs resident."""
    if not phys._contains_streamed(node):
        return None
    for f in ("child", "left", "right"):
        c = getattr(node, f, None)
        if isinstance(c, phys.PhysNode):
            found = _lowest_streamed_agg(c)
            if found is not None:
                return found
    return node if isinstance(node, phys.MergeAgg) else None


def _swap_node(node, target, repl):
    """Rebuild the (frozen-dataclass) physical plan with ``target``
    replaced by ``repl``."""
    if node is target:
        return repl
    for f in ("child", "left", "right"):
        c = getattr(node, f, None)
        if isinstance(c, phys.PhysNode):
            new = _swap_node(c, target, repl)
            if new is not c:
                return dataclasses.replace(node, **{f: new})
    return node


#: reserved base-table name the streamed pass's replicated result is
#: re-injected under when the plan continues above it.
_STREAMED_RESULT = "\x00streamed"


def shard_capacity(capacity: int, canonical_chunks: int, shards: int) -> int:
    """The padded capacity ``compile_plan`` gives a base table: first the
    canonical chunk grid (chunk size csz = ceil(n / chunks)), then enough
    whole PADDING CHUNKS that every shard owns the same number of chunk
    slots — shards * ceil(chunks / shards) * csz rows.  For shard counts
    dividing the grid this adds nothing beyond the chunk padding; padding
    chunks hold only invalid p = 0 rows and their (identity) states are
    sliced away before the canonical fold."""
    csz = -(-capacity // canonical_chunks)
    local = -(-canonical_chunks // shards)
    return shards * local * csz


def compile_plan(root: Node, mesh=None, *,
                 data_axes: Sequence[str] = ("data",),
                 model_axis: str | None = "model",
                 cf_budget_elems: int = 1 << 22,
                 canonical_chunks: int = 8,
                 join_gather_budget: int = 1 << 20,
                 shuffle_slack: float = 4.0,
                 copartition: object = "auto",
                 agg_shuffle_budget: int | None = None,
                 cost_model=None,
                 device_row_budget: int | None = None,
                 stream_wave_chunks: int | None = None,
                 stream_double_buffer: bool = True,
                 stream_prune_columns: bool = True,
                 stats_tables: Dict[str, "Table | HostTable"] | None = None,
                 with_report: bool = False,
                 shuffle_bucket_floor: int | None = None,
                 stream_wave_retries: int = 2):
    """Emit a function tables -> result (Table or dict of arrays).

    With ``mesh``, the logical plan lowers to a sharded physical plan
    (:func:`repro.db.physical.lower_plan`) and the WHOLE plan runs inside
    one shard_map over the mesh's data axes — scans, selects, joins,
    group-id assignment and aggregation all consume shard-local row
    blocks (see module docstring for the per-operator strategies);
    results are bit-identical to the mesh=None compile for ANY data-shard
    count.  Tuples stay replicated over ``model_axis``; when that axis has
    more than one device, each exact-CF frequency slab additionally splits
    into per-model-rank windows (a traced ``freq_lo`` through the grouped
    kernel's SMEM scalar) so the O(n·F) phase work drops by the axis size,
    reassembled by ONE tiled all-gather per slab — still bit-identical to
    the unsharded run (per-frequency columns are computed independently).

    ``canonical_chunks`` (any positive count) is the fixed accumulation
    grid that makes results shard-count-invariant.  ``join_gather_budget``
    caps the rows of an FKJoin build side that may be all-gathered; larger
    build sides lower to a hash-partitioned strategy, whose static bucket
    capacities come from the concrete ``key % n_shards`` histogram when
    the key column is concrete at compile time (eager compiles; overflow
    impossible) and otherwise from ``shuffle_slack`` times the uniform
    share (overflow is counted and poisons the join output with NaN — see
    ``dist.shuffle_fk_join``).  A per-node ``FKJoin.gather_budget``
    overrides the global for that join.

    Which hash-partitioned strategy runs is a COST decision
    (``db/cost.py`` via ``physical.lower_plan``): when the downstream
    GROUP BY keys on the probe join key, the fused CoPartitionedJoin +
    PartitionedAgg pipeline (matched rows stay at their owner, zero
    shuffle-home round-trips, one psum merge) competes with ShuffleJoin +
    PartialAgg.  ``copartition`` overrides it: "auto" (default) lets the
    estimates decide, True forces the fused pipeline whenever legal and
    the join may not gather, False disables it.  ``agg_shuffle_budget``
    (default None = off) makes single-key aggregations over more input
    rows hash-exchange their tuples to per-group owners
    (``Repartition`` + PartitionedAgg) — the fused pipeline without a
    join.  ``cost_model`` replaces the knob-derived
    :class:`repro.db.cost.CostModel` wholesale.  Every strategy is
    bit-identical to every other and to mesh=None (the canonical-chunk
    fold contract extends to owner-local folds; see
    ``dist.partitioned_merge``).

    ``cf_budget_elems`` bounds the total live exact-CF state elements of a
    `GroupAgg(method="exact")` node — counting both the log-abs and angle
    (max_groups, slab) arrays of every exact aggregate on the node.  When
    the full (max_groups, num_freq) state would exceed it, the compiler
    runs multiple accumulation passes over frequency slabs (each slab
    collective-merged on a mesh) and concatenates the slab states before
    the one batched-FFT Finalize; the grouped kernel's argsort/operand
    prep is hoisted above the slab loop (:func:`repro.core.uda.
    cf_chunk_operands`).

    Out-of-core execution: ``device_row_budget`` caps the resident rows
    per device of any one base table.  A Scan over the budget lowers to
    :class:`repro.db.physical.StreamedScan` and its table stays
    HOST-side (pass a :class:`repro.db.table.HostTable`, or a device
    Table — it is pulled to host): the compiled function then runs the
    aggregation pass above that scan as a sequence of WAVES, each
    shipping one chunk-aligned slab to the mesh and folding its
    per-canonical-chunk UDA states into a cross-wave accumulator
    (:class:`repro.core.uda.ChunkStateAccumulator`); the canonical fold
    runs once after the last wave, so results are BIT-IDENTICAL to the
    fully-resident compile for any wave size.  Transfers are
    double-buffered — wave k+1's ``jax.device_put`` overlaps wave k's
    accumulate, so the device holds 2 slabs + the group-level state,
    never the table (``stream_double_buffer=False`` serialises
    ship/compute — the control benchmarks compare against).
    ``stream_wave_chunks`` pins the wave size (in global chunk slots)
    for tests.  HostTables without a budget are simply materialised.
    The streamed path executes eagerly (host wave loop): don't wrap the
    compiled function in an outer jit when streaming.
    ``stream_prune_columns`` (default on) ships only the columns the
    plan above the scan actually reads (the lowering's
    :func:`repro.db.physical.required_scan_columns` demand set) and
    widens the waves to match — fewer bytes per row, fewer transfers;
    off streams every column (the control for byte-counting
    benchmarks).  A :class:`~repro.db.table.HostTable` opened from a
    :meth:`~repro.db.table.HostTable.save` directory streams straight
    from its memory-mapped column files: only the touched row ranges of
    the touched columns are ever paged in.

    ``stats_tables`` (name -> representative Table or HostTable) feeds
    the skew-adaptive concrete-key bucket sizing when the RUNTIME
    tables are traced (the compiled function called under jit): the
    stats tables are padded exactly like the runtime ones and their
    concrete (numpy — a HostTable's columns are histogrammed directly)
    key histograms size the exchange buckets, replacing the flat
    ``shuffle_slack`` capacity (the overflow-NaN guard stays as the
    backstop for stale stats).

    Self-healing hooks (see :mod:`repro.db.report` and :func:`run_plan`):
    ``with_report=True`` makes the compiled function return
    ``(result, ExecutionReport)`` — per-exchange overflow / demand /
    capacity, group-code-table overflow, per-MIN/MAX §V-B.2 truncation
    mass, NaN counts of the folded UDA states, and (streamed) wave
    progress.  ``shuffle_bucket_floor`` raises every slack-sized exchange
    bucket to at least that many rows (the retry controller re-lowers
    with the observed peak demand).  ``stream_wave_retries`` bounds the
    IN-PLACE re-ship attempts of a faulted wave transfer before the
    fault propagates (annotated with the halved wave size for the
    controller); the wave loop always resumes from the last retired
    wave — completed waves are never re-streamed.
    """
    from . import distributed as dist

    mesh_mode = mesh is not None
    if mesh_mode:
        dist.reuse_eager_shard_map_programs()
    axes = dist._tuple_axes(mesh, data_axes) if mesh_mode else ()
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    # Model-axis frequency sharding: with a >1-sized model axis on the
    # mesh, each exact-CF slab splits into per-rank frequency windows
    # (the scalar step's split, lifted into the planner) — accumulated
    # via a traced freq_lo, reassembled bit-identically by one tiled
    # all-gather per slab (see _model_freq_split / _gather_model_slabs).
    model = (model_axis if mesh_mode and model_axis
             and model_axis in mesh.axis_names
             and mesh.shape[model_axis] > 1 else None)
    model_size = mesh.shape[model] if model else 1
    chunks = canonical_chunks
    if chunks <= 0:
        raise ValueError(f"canonical_chunks must be positive, got {chunks}")
    local_chunks = -(-chunks // shards)

    # Compile-time stand-ins for the concrete-key bucket sizing, padded
    # HERE — eagerly, host-side — not inside `compiled`: under jax.jit
    # omnistaging would turn a jnp pad of even a concrete table into
    # tracers, and the histogram sizing would silently fall back to
    # slack.  HostTable keeps the padding in numpy so the key columns
    # stay concrete no matter what trace `compiled` runs under.
    if stats_tables:
        stats_tables = {
            k: (st if isinstance(st, HostTable)
                else HostTable.from_table(st))
            .pad_to_multiple(chunks)
            .pad_to(shard_capacity(st.capacity, chunks, shards))
            for k, st in stats_tables.items()}

    # Canonical (chunk-grid-only) capacities of the base tables, set by
    # `compiled` before tracing: the shape a relational result has in the
    # mesh=None compile, before any shard-alignment padding chunks.
    canon_caps: dict = {}

    def _canonical_rows(pnode: phys.PhysNode) -> int:
        """Root output rows of a relational subtree under mesh=None padding
        (row capacity follows the probe/left lineage down to its scan)."""
        if isinstance(pnode, (phys.ShardScan, phys.StreamedScan)):
            return canon_caps[pnode.name]
        if isinstance(pnode, (phys.PhysSelect, phys.PhysMap,
                              phys.Repartition)):
            return _canonical_rows(pnode.child)
        if isinstance(pnode, (phys.GatherJoin, phys.ShuffleJoin,
                              phys.CoPartitionedJoin)):
            return _canonical_rows(pnode.left)
        if isinstance(pnode, phys.MergeAgg):
            return pnode.child.max_groups
        raise TypeError(pnode)

    def make_runner(sh_tables: Dict[str, Table],
                    rb: ReportBuilder | None = None,
                    params: dict | None = None) -> SimpleNamespace:
        """Bind the physical-plan interpreter to one dict of (shard-local)
        tables; in mesh mode the closures run inside shard_map.  The
        streamed executor binds the SAME interpreter to every wave's slab
        (the StreamedScan resolves to the slab), so resident and streamed
        execution share one code path for everything below the merge.
        ``rb`` (a :class:`ReportBuilder`) collects the run's diagnostics
        while the plan traces."""

        def sharded(t: Table) -> bool:
            return bool(axes) and isinstance(t.part, phys.RowBlocked)

        def hash_partitioned(t: Table) -> bool:
            return bool(axes) and isinstance(t.part, phys.HashPartitioned)

        def acc(udas_d, table: Table, values, ids, max_groups,
                cf_operands=None):
            """ONE canonical chunked pass over the relation's tuples for
            every UDA of the pass.  The chunk grid is the same in every
            compile: a RowBlocked pass computes its local chunk slots'
            states and allgather_merge assembles ALL chunk states so every
            shard finishes the identical fold tree; a HashPartitioned
            pass (the fused pipeline) computes EVERY canonical chunk's
            slice in one compound (chunk, group) accumulate over the
            exchange buffer — received rows arrive in global row order,
            so each (chunk, group) slot folds the same tuples in the same
            order as the RowBlocked chunk pass — and partitioned_merge
            finishes the identical fold owner-locally before one psum."""
            probs = table.masked_prob()
            if hash_partitioned(table):
                cid = jnp.clip(table[phys.CHUNK_COL].astype(jnp.int32),
                               0, chunks - 1)
                comp = cid * max_groups + ids
                flat = uda.accumulate(udas_d, probs, values, comp,
                                      max_groups=chunks * max_groups)
                parts = [{name: jax.tree.map(
                    lambda x, c=c: x[c * max_groups:(c + 1) * max_groups],
                    st) for name, st in flat.items()}
                    for c in range(chunks)]
                return dist.partitioned_merge(udas_d, parts, axes,
                                              n_shards=shards)
            if sharded(table):
                parts = uda.accumulate_chunk_states(
                    udas_d, probs, values, ids, max_groups=max_groups,
                    num_chunks=local_chunks, cf_operands=cf_operands)
                return dist.allgather_merge(udas_d, parts, axes, chunks,
                                            shards)
            return uda.accumulate_chunked(
                udas_d, probs, values, ids, max_groups=max_groups,
                num_chunks=chunks, cf_operands=cf_operands)

        def rel_group_ids(t: Table, keys, max_groups):
            if sharded(t) or hash_partitioned(t):
                return dist.group_ids_sharded(t, list(keys), max_groups,
                                              axes)
            return ops.group_ids(t, list(keys), max_groups)

        def rel_key_columns(t: Table, keys, ids, max_groups):
            if sharded(t) or hash_partitioned(t):
                return dist.group_key_columns_sharded(t, keys, ids,
                                                      max_groups, axes)
            return ops.group_key_columns(t, keys, ids, max_groups)

        def run_agg(node: phys.MergeAgg):
            """The PartialAgg/MergeAgg pair executes as one unit: group
            ids, then per frequency slab one Accumulate (per-chunk
            partials) + ONE collective Merge, then the replicated Finalize
            selected by ``kind`` — the module-level ``_pass_*`` /
            ``_finalize_pass`` helpers, which the streamed wave loop runs
            piecewise across waves."""
            pa = node.child
            t = run(pa.child)
            mg = pa.max_groups
            ids, merged, gvalid = rel_group_ids(t, pa.keys, mg)
            label = rb.begin_agg(node.kind) if rb is not None else ""
            if rb is not None:
                code_live, big = ops.live_key_codes(t, list(pa.keys))
                lost = _lost_group_count(code_live, big, merged, ids)
                if sharded(t) or hash_partitioned(t):
                    # Row-partitioned input: each shard counted its own
                    # rows.  Replicated inputs count every row on every
                    # shard — summing would multiply by the shard count.
                    lost = jax.lax.psum(lost, axes)
                rb.group_overflow(label, lost)
            values = _pass_values(pa.specs, t)
            exact_names, slabs = _pass_slabs(pa, cf_budget_elems)
            cf_operands: dict = {}
            if len(slabs) > 1 and not hash_partitioned(t):
                # Hoist the grouped kernel's argsort(gids) + operand prep
                # above the slab loop: prepared once per canonical chunk,
                # reused by every slab pass (None when the kernel would
                # not be dispatched — the scan/oracle paths sort nothing;
                # the compound pass of the fused pipeline sorts per call).
                probs_m = t.masked_prob()
                nloc = local_chunks if sharded(t) else chunks
                for name in exact_names:
                    prepared = uda.cf_chunk_operands(
                        pa.num_freq, probs_m, values[name], ids,
                        max_groups=mg, num_chunks=nloc)
                    if prepared is not None:
                        cf_operands[name] = prepared
            udas: dict = {}
            states: dict = {}
            for si, (lo, cnt) in enumerate(slabs):
                udas_i, vals_i = _slab_udas(pa, si, lo, cnt, values,
                                            model=model,
                                            model_size=model_size)
                sts = acc(udas_i, t, vals_i, ids, mg,
                          cf_operands=cf_operands or None)
                sts = _gather_model_slabs(udas_i, sts, exact_names, cnt,
                                          model, model_size)
                _append_slab(states, udas, udas_i, sts)
            for name in exact_names:            # full-range Finalize UDA
                udas[name] = _agg_uda("SUM", "exact", pa.kappa, pa.num_freq)
            return _finalize_pass(
                node, pa, udas, states, gvalid,
                lambda cols: rel_key_columns(t, cols, ids, mg),
                rb=rb, label=label, params=params)

        def run(node: phys.PhysNode):
            if isinstance(node, (phys.ShardScan, phys.StreamedScan)):
                # A StreamedScan resolves here only under the streamed
                # executor, which binds the scan's name to the current
                # wave's slab.
                return sh_tables[node.name].with_part(node.part)
            if isinstance(node, phys.PhysSelect):
                pred = node.pred
                if isinstance(pred, Parameterized):
                    return ops.select(run(node.child),
                                      lambda t: pred(t, params))
                return ops.select(run(node.child), pred)
            if isinstance(node, phys.PhysMap):
                t = run(node.child)
                fn = node.fn
                col = fn(t, params) if isinstance(fn, Parameterized) \
                    else fn(t)
                return t.with_column(node.name, col)
            if isinstance(node, phys.GatherJoin):
                lt = run(node.left)
                rt = run(node.right)
                if sharded(rt):
                    # Broadcast the small build side: all-gather only the
                    # probe key + carried columns (plus p and valid).
                    rt = dist.gather_table(
                        rt.select_columns(
                            dict.fromkeys((node.right_key,)
                                          + tuple(node.right_cols))),
                        axes)
                return ops.fk_join(lt, rt, node.left_key, node.right_key,
                                   list(node.right_cols))
            if isinstance(node, phys.ShuffleJoin):
                lt = run(node.left)
                rt = run(node.right)
                lbl = rb.begin_exchange("shuffle_join") \
                    if rb is not None else ""
                return dist.shuffle_fk_join(
                    lt, rt, node.left_key, node.right_key,
                    list(node.right_cols), axes, n_shards=shards,
                    build_bucket=node.build_bucket,
                    probe_bucket=node.probe_bucket,
                    report=rb, label=lbl)
            if isinstance(node, phys.CoPartitionedJoin):
                lt = run(node.left)
                rt = run(node.right)
                lbl = rb.begin_exchange("copart_join") \
                    if rb is not None else ""
                return dist.copartitioned_fk_join(
                    lt, rt, node.left_key, node.right_key,
                    list(node.right_cols), list(node.carry_cols), axes,
                    n_shards=shards, build_bucket=node.build_bucket,
                    probe_bucket=node.probe_bucket,
                    chunk_size=_canonical_rows(node.left) // chunks,
                    num_chunks=chunks, report=rb, label=lbl)
            if isinstance(node, phys.Repartition):
                t = run(node.child)
                lbl = rb.begin_exchange("repartition") \
                    if rb is not None else ""
                return dist.repartition_by_key(
                    t, node.key, list(node.carry_cols), axes,
                    n_shards=shards, bucket=node.bucket,
                    chunk_size=_canonical_rows(node.child) // chunks,
                    num_chunks=chunks, report=rb, label=lbl)
            if isinstance(node, phys.MergeAgg):
                return run_agg(node)
            raise TypeError(node)

        return SimpleNamespace(run=run, run_agg=run_agg, acc=acc,
                               rel_group_ids=rel_group_ids,
                               rel_key_columns=rel_key_columns,
                               sharded=sharded)

    def interpret(sh_tables: Dict[str, Table], proot: phys.PhysNode,
                  rb: ReportBuilder | None = None,
                  params: dict | None = None):
        """Interpret the physical plan end-to-end (the resident path)."""
        r = make_runner(sh_tables, rb, params)
        out = r.run(proot)
        if isinstance(out, Table):
            if r.sharded(out):
                out = dist.gather_table(out, axes)
                # Drop the whole-padding chunks appended for shard counts
                # that don't divide the grid: the caller-visible capacity
                # is the canonical (chunk-grid) one of the mesh=None
                # compile (the dropped rows are all invalid p = 0).
                n = _canonical_rows(proot)
                if n < out.capacity:
                    out = Table({k: v[:n] for k, v in out.columns.items()},
                                out.prob[:n], out.valid[:n], out.part)
            return out.with_part(phys.Replicated())
        return out

    # ------------------------------------------------- streamed execution
    def _build_wave_fns(proot, agg, sc):
        """The two per-wave device functions of the streamed executor —
        phase A (group-code discovery) and phase B (chunk-state
        accumulation) — each re-running the plan spine below ``agg`` on
        one slab, shard_mapped over the mesh and jitted.  Cached in the
        process-wide bounded ``_WAVE_CACHE`` under the plan's STRUCTURAL
        key plus everything else the traces depend on (mesh identity,
        data axes, the canonical grid and the CF slab budget), so
        separately constructed identical plans share one executable and
        distinct plans past the capacity evict instead of accreting."""
        key = ("wave", phys.structural_key(proot), mesh_fingerprint(mesh),
               axes, shards, chunks, cf_budget_elems, model)
        cached = _WAVE_CACHE.get(key)
        if cached is not None:
            return cached
        pa = agg.child
        spine = pa.child
        mg = pa.max_groups
        keys = list(pa.keys)
        kcols = list(pa.keys)
        if agg.kind == "reweight":
            kcols += [c for c in agg.carry_cols if c not in kcols]
            if agg.threshold_col and agg.threshold_col not in kcols:
                kcols.append(agg.threshold_col)
        exact_names, slabs = _pass_slabs(pa, cf_budget_elems)

        def wave_a(slab, res, pv):
            t = make_runner({**res, sc.name: slab}, params=pv).run(spine)
            code_live, _ = ops.live_key_codes(t, keys)
            local = ops.merge_group_codes(code_live, mg)
            if axes:
                gathered = jax.lax.all_gather(local, axes, axis=0,
                                              tiled=True)
                local = ops.merge_group_codes(gathered, mg)
            return local

        def wave_b(slab, res, merged, pv):
            t = make_runner({**res, sc.name: slab}, params=pv).run(spine)
            code_live, big = ops.live_key_codes(t, keys)
            ids = ops.codes_to_ids(code_live, merged)
            # The wave's group-overflow contribution is always computed
            # (one compare + sum — keeping the jit cache's trace
            # signature independent of report collection).
            lost = _lost_group_count(code_live, big, merged, ids)
            if axes:
                lost = jax.lax.psum(lost, axes)
            values = _pass_values(pa.specs, t)
            out_states = []
            for si, (lo, cnt) in enumerate(slabs):
                udas_i, vals_i = _slab_udas(pa, si, lo, cnt, values,
                                            model=model,
                                            model_size=model_size)
                parts = uda.accumulate_chunk_states(
                    udas_i, t.masked_prob(), vals_i, ids, max_groups=mg,
                    num_chunks=sc.schedule.local_chunks_per_wave)
                # Model windows gather per chunk state BEFORE the data-axis
                # gather: filed chunk states must be full-width (the
                # cross-wave accumulator and P() out_specs assume
                # model-replicated states).
                parts = [_gather_model_slabs(udas_i, p, exact_names, cnt,
                                             model, model_size)
                         for p in parts]
                out_states.append(
                    dist.gather_chunk_states(udas_i, parts, axes)
                    if axes else parts)
            gcols = ops.group_key_columns(t, kcols, ids, mg)
            if axes:
                gcols = {k: jax.lax.pmax(v, axes) for k, v in gcols.items()}
            return out_states, gcols, lost

        if axes or model:
            # (model-only meshes still need the shard_map so the traced
            # model-rank frequency windows can read axis_index.)
            wave_a = shard_map(wave_a, mesh=mesh,
                               in_specs=(P(axes), P(axes), P()),
                               out_specs=P(), check_vma=False)
            wave_b = shard_map(wave_b, mesh=mesh,
                               in_specs=(P(axes), P(axes), P(), P()),
                               out_specs=P(), check_vma=False)
        # Donating the slab lets XLA reuse wave k's buffers for wave k+2
        # (the CPU backend does not support donation — avoid the warning).
        donate = (0,) if jax.default_backend() != "cpu" else ()
        fns = (jax.jit(wave_a, donate_argnums=donate),
               jax.jit(wave_b, donate_argnums=donate))
        _WAVE_CACHE.put(key, fns)
        return fns

    def _stream(ht: HostTable, sched, wave_call, collect) -> int:
        """The double-buffered wave loop: slab w+1 is sliced and
        ``device_put`` WHILE the device works on slab w (JAX async
        dispatch — the host never blocks on the wave computation), so
        transfer and compute overlap and device residency is two slabs.
        With ``stream_double_buffer=False`` the loop blocks around every
        wave — the serialised control the streaming benchmarks compare
        against.

        Fault tolerance: each host→device transfer passes through
        ``testing.faults.on_transfer``; a :class:`~repro.testing.faults.
        TransferFault` re-ships the SAME wave up to ``stream_wave_retries``
        times.  Wave w is retired (``collect``-ed) BEFORE slab w+1 is
        prefetched, so the loop's position IS the checkpoint: a fault only
        ever re-ships waves whose states are not yet filed, and completed
        waves are never re-streamed.  A fault that survives the in-place
        retries propagates annotated with the halved wave size
        (``wave_chunks``) so :func:`run_plan` can re-lower a smaller
        schedule.  Returns the number of re-ship retries.

        Slab assembly is ZERO-ALLOC: two preallocated ping-pong host
        buffers (matching the double-buffer depth) are filled with
        ``np.copyto`` instead of per-wave fresh allocations.  Reusing
        buffer ``w % 2`` for wave w is safe because slab w+1 only ships
        after wave w-1's output is ready (the block below) — and w-1's
        compute finishing implies its input transfer (same parity
        buffer) has been consumed."""
        csz = sched.chunk_rows
        lrows = sched.local_chunks_per_wave * csz
        lslots = sched.n_waves * sched.local_chunks_per_wave
        n_retries = 0
        bufs = (ht.alloc_slab(lrows * shards), ht.alloc_slab(lrows * shards))
        wave_bytes = sum(a.nbytes for a in
                         jax.tree.leaves((bufs[0].columns, bufs[0].prob,
                                          bufs[0].valid)))

        def ship(w):
            # Wave w takes the next `lrows` rows of EVERY shard's slot
            # range — strided slices host-side, split back per device by
            # the sharded transfer.
            faults.on_transfer(w, lrows * shards)
            starts = tuple(s * lslots * csz + w * lrows
                           for s in range(shards))
            t0 = time.perf_counter()
            slab = ht.wave_slab(starts, lrows, out=bufs[w % 2])
            _STREAM_STATS["slice_s"] += time.perf_counter() - t0
            _STREAM_STATS["slab_bytes"] += wave_bytes
            _STREAM_STATS["waves"] += 1
            if mesh_mode:
                return jax.device_put(slab, NamedSharding(mesh, P(axes)))
            return jax.device_put(slab)

        def try_ship(w):
            nonlocal n_retries
            for attempt in range(stream_wave_retries + 1):
                try:
                    return ship(w)
                except faults.TransferFault as e:
                    if attempt == stream_wave_retries:
                        e.wave_chunks = C.halved_wave_chunks(sched)
                        e.at_minimum = sched.local_chunks_per_wave == 1
                        raise
                    n_retries += 1

        nxt = try_ship(0)
        prev = None
        for w in range(sched.n_waves):
            cur, nxt = nxt, None
            if not stream_double_buffer:
                jax.block_until_ready(cur)
            out = wave_call(w, cur)
            if not stream_double_buffer:
                out = jax.block_until_ready(out)
            elif prev is not None:
                # True double buffering: wave w-1 must have retired
                # before slab w+1 ships, bounding in-flight slabs to two
                # (unbounded run-ahead trades the overlap win away to
                # allocator pressure).
                jax.block_until_ready(prev)
            # Retire wave w before prefetching w+1 (collect is host
            # bookkeeping on async values — it doesn't block the
            # overlap): the fault-resume contract above.
            collect(w, out)
            if w + 1 < sched.n_waves:
                nxt = try_ship(w + 1)
            prev = out
        return n_retries

    def _streamed_exec(proot, padded, rb: ReportBuilder | None = None,
                       params: dict | None = None):
        """Run a physical plan containing a StreamedScan: the lowest
        aggregation pass above the scan executes as waves (see
        ``compile_plan``'s docstring); any plan suffix above that pass
        runs resident on the pass's replicated group-level output."""
        scans = [n for n in _iter_phys(proot)
                 if isinstance(n, phys.StreamedScan)]
        if len(scans) > 1:
            raise NotImplementedError(
                "one StreamedScan per plan: raise device_row_budget so at "
                f"most one table streams (got {[s.name for s in scans]})")
        sc = scans[0]
        agg = _lowest_streamed_agg(proot)
        if agg is None:
            raise NotImplementedError(
                "a StreamedScan must feed a grouped aggregation (Project /"
                " GroupAgg / ReweightGreater): the wave loop folds "
                "per-chunk UDA states, not raw relational output — raise "
                "device_row_budget so the table stays resident, or "
                "materialise it first via HostTable.to_table()")
        pa = agg.child
        sched = sc.schedule
        ht = padded[sc.name]
        ht = (ht if isinstance(ht, HostTable)
              else HostTable.from_table(ht)).pad_to(sched.padded_capacity)
        if sc.columns is not None:
            # Required-column pruning: wave slabs carry only the demand
            # set the lowering recorded (plus prob/valid, always).
            ht = ht.select_columns([c for c in sc.columns
                                    if c in ht.columns])
        resident = {k: (t.to_table() if isinstance(t, HostTable) else t)
                    for k, t in padded.items() if k != sc.name}
        wave_a, wave_b = _build_wave_fns(proot, agg, sc)
        pv = dict(params or {})

        # Phase A: stream once for the global group-code table — exact
        # under hierarchical merging (ops.merge_group_codes), so merging
        # the per-wave tables reproduces the resident table bit for bit.
        code_tabs = [None] * sched.n_waves
        retries = _stream(ht, sched,
                          lambda w, slab: wave_a(slab, resident, pv),
                          lambda w, out: code_tabs.__setitem__(w, out))
        mg = pa.max_groups
        merged = ops.merge_group_codes(jnp.concatenate(code_tabs), mg)
        gvalid = merged != jnp.iinfo(merged.dtype).max

        # Phase B: stream again, filing every wave's per-chunk states
        # under their global canonical chunk ids; the canonical fold runs
        # ONCE after the last wave (the bit-identical-streaming contract).
        exact_names, slabs = _pass_slabs(pa, cf_budget_elems)
        dummy_vals = {s[0]: None for s in pa.specs}
        slab_udas, accs = [], []
        for si, (lo, cnt) in enumerate(slabs):
            udas_i, _ = _slab_udas(pa, si, lo, cnt, dummy_vals)
            slab_udas.append(udas_i)
            accs.append(uda.ChunkStateAccumulator(udas_i, chunks))
        lcpw = sched.local_chunks_per_wave
        lslots = sched.n_waves * lcpw
        gcols_run: dict = {}
        lost_waves: list = []

        def collect_b(w, out):
            out_states, gcols, lost = out
            slot_ids = [s * lslots + w * lcpw + j
                        for s in range(shards) for j in range(lcpw)]
            for si, parts in enumerate(out_states):
                accs[si].add_wave(slot_ids, parts)
            lost_waves.append(lost)     # async values; summed after loop
            for k, v in gcols.items():
                # Per-group key representatives: segment_max identities
                # fill absent groups, so a max across waves is exact.
                gcols_run[k] = (v if k not in gcols_run
                                else jnp.maximum(gcols_run[k], v))

        retries += _stream(
            ht, sched, lambda w, slab: wave_b(slab, resident, merged, pv),
            collect_b)

        label = rb.begin_agg(agg.kind) if rb is not None else ""
        if rb is not None:
            rb.group_overflow(label, sum(lost_waves))
            rb.set_waves(completed=2 * sched.n_waves,
                         total=2 * sched.n_waves, retries=retries)
        udas: dict = {}
        states: dict = {}
        for si in range(len(slabs)):
            _append_slab(states, udas, slab_udas[si], accs[si].fold())
        for name in exact_names:                # full-range Finalize UDA
            udas[name] = _agg_uda("SUM", "exact", pa.kappa, pa.num_freq)
        result = _finalize_pass(
            agg, pa, udas, states, gvalid,
            lambda cols: {k: gcols_run[k] for k in cols},
            rb=rb, label=label, params=pv)
        if agg is proot:
            return (result.with_part(phys.Replicated())
                    if isinstance(result, Table) else result)
        # Plan suffix above the streamed pass: re-inject the replicated
        # group-level Table as a scan and run the rest resident.
        assert isinstance(result, Table), \
            "a non-root streamed aggregation must produce a Table"
        outer = _swap_node(proot, agg,
                           phys.ShardScan(_STREAMED_RESULT,
                                          phys.Replicated(),
                                          result.capacity))
        canon_caps[_STREAMED_RESULT] = result.capacity
        if not mesh_mode:
            return interpret({**resident, _STREAMED_RESULT: result},
                             outer, rb, pv)
        if rb is None:
            fn = shard_map(
                lambda sh, ex, p: interpret({**sh, **ex}, outer,
                                            params=p),
                mesh=mesh, in_specs=(P(axes), P(), P()),
                out_specs=P(), check_vma=False)
            return fn(resident, {_STREAMED_RESULT: result}, pv)
        # The suffix traces under shard_map, so its diagnostics must ride
        # the traced outputs: a forked builder (label counters continue
        # from the streamed pass) collects inside, its built report is
        # returned as replicated leaves, and the concrete copy is
        # absorbed back host-side.
        sub = rb.fork()
        fn = shard_map(
            lambda sh, ex, p: (interpret({**sh, **ex}, outer, sub, p),
                               sub.build()),
            mesh=mesh, in_specs=(P(axes), P(), P()), out_specs=P(),
            check_vma=False)
        out, rep = fn(resident, {_STREAMED_RESULT: result}, pv)
        rb.absorb(rep)
        return out

    needed_params = plan_params(root)

    def compiled(tables: Dict[str, Table], params: dict | None = None):
        # Lifted-parameter environment: every Param hole must be bound,
        # and only Param holes may be (a typo'd name would silently bake
        # nothing).  Values may be traced — under jax.vmap each is one
        # lane of the parameter batch (see repro.db.serving).
        env = dict(params or {})
        missing = needed_params - env.keys()
        extra = env.keys() - needed_params
        if missing or extra:
            raise ValueError(
                f"plan parameters mismatch: missing {sorted(missing)}, "
                f"unexpected {sorted(extra)} (plan needs "
                f"{sorted(needed_params)})")
        env = {k: jnp.asarray(env[k]) for k in sorted(env)}
        # Every compile pads every base table to the canonical chunk grid
        # (the chunk boundaries define the deterministic fold tree) plus
        # whole padding chunks so any shard count owns equal chunk runs.
        padded = {k: t.pad_to_multiple(chunks)
                   .pad_to(shard_capacity(t.capacity, chunks, shards))
                  for k, t in tables.items()}
        caps = {k: t.capacity for k, t in padded.items()}
        canon_caps.clear()
        canon_caps.update({k: -(-t.capacity // chunks) * chunks
                           for k, t in tables.items()})
        plan_tables = dict(padded)
        if stats_tables:
            # Substitute the pre-padded host-side stand-ins so the key %
            # n_shards histograms see a concrete row population even
            # when the runtime tables are tracers.
            for k, st in stats_tables.items():
                if k in padded:
                    plan_tables[k] = st
        proot = phys.lower_plan(root, caps, n_shards=shards,
                                sharded=mesh_mode and bool(axes),
                                join_gather_budget=join_gather_budget,
                                shuffle_slack=shuffle_slack,
                                copartition=copartition,
                                agg_shuffle_budget=agg_shuffle_budget,
                                canonical_chunks=chunks,
                                model=cost_model, tables=plan_tables,
                                device_row_budget=device_row_budget,
                                stream_wave_chunks=stream_wave_chunks,
                                stream_prune_columns=stream_prune_columns,
                                bucket_floor=shuffle_bucket_floor,
                                model_shards=model_size)
        rb = ReportBuilder() if with_report else None
        if any(isinstance(n, phys.StreamedScan) for n in _iter_phys(proot)):
            out = _streamed_exec(proot, padded, rb, env)
            return (out, rb.build()) if with_report else out
        resident = {k: (t.to_table() if isinstance(t, HostTable) else t)
                    for k, t in padded.items()}
        if not mesh_mode:
            out = interpret(resident, proot, rb, env)
            return (out, rb.build()) if with_report else out
        if not with_report:
            fn = shard_map(lambda sh, p: interpret(sh, proot, params=p),
                           mesh=mesh, in_specs=(P(axes), P()),
                           out_specs=P(), check_vma=False)
            return fn(resident, env)
        # The report's leaves are traced inside shard_map; returning the
        # built pytree alongside the result is what carries them out
        # (every recorded value is psum/pmax-replicated, honouring the
        # P() out_spec).
        fn = shard_map(
            lambda sh, p: (interpret(sh, proot, rb, p), rb.build()),
            mesh=mesh, in_specs=(P(axes), P()), out_specs=P(),
            check_vma=False)
        return fn(resident, env)

    return compiled


# ======================================================================
# the escalating retry controller
# ======================================================================
@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """How :func:`run_plan` escalates when a run's
    :class:`~repro.db.report.ExecutionReport` shows a problem.

    max_attempts   total compile+run attempts (first run included)
    tail_tol       largest acceptable per-group MIN/MAX §V-B.2
                   truncation mass; above it kappa doubles
    wave_retries   in-place re-ship attempts per faulted wave transfer
                   before the streamed executor gives the fault back to
                   the controller (which then halves the wave size)
    """
    max_attempts: int = 3
    tail_tol: float = 0.0
    wave_retries: int = 2


class RetryExhausted(RuntimeError):
    """The retry ladder ran out of attempts with issues outstanding; the
    last run's report is attached for diagnosis."""

    def __init__(self, msg: str, report=None):
        super().__init__(msg)
        self.report = report


def _scale_plan(node: Node, kappa_scale: int, groups_scale: int) -> Node:
    """Rebuild the logical DAG with every GroupAgg kappa (and, on group
    overflow, every grouped node's max_groups) scaled — the logical-level
    escalations; a scale of 1 returns the node unchanged (same object, so
    an unescalated retry reuses compile caches)."""
    reb: dict = {}
    for f in ("child", "left", "right"):
        c = getattr(node, f, None)
        if isinstance(c, Node):
            nc = _scale_plan(c, kappa_scale, groups_scale)
            if nc is not c:
                reb[f] = nc
    if isinstance(node, GroupAgg) and kappa_scale != 1:
        reb["kappa"] = node.kappa * kappa_scale
    if groups_scale != 1 and isinstance(node, (GroupAgg, Project,
                                               ReweightGreater)):
        reb["max_groups"] = node.max_groups * groups_scale
    return dataclasses.replace(node, **reb) if reb else node


def _default_compiler(root: Node, mesh=None, jit: bool = False, **opts):
    """The retry controller's default compile hook: a fresh
    ``compile_plan`` (jit-wrapped on request) per attempt.  A serving
    layer substitutes :meth:`repro.db.serving.PlanCache.compile` here, so
    every attempt's executable is cached under (plan structure, attempt
    params) — a later identical submit hits the FINAL attempt's entry
    bit-identically, and intermediate attempts never poison it."""
    fn = compile_plan(root, mesh, **opts)
    return jax.jit(fn) if jit else fn


def run_plan(root: Node, tables: Dict[str, Table], mesh=None, *,
             policy: RetryPolicy | None = None, jit: bool = False,
             params: dict | None = None, compiler=None,
             kappa_scale: int = 1, groups_scale: int = 1,
             **opts):
    """Run a logical plan under the self-healing retry loop: compile
    (``compile_plan(..., with_report=True)``), run, DIAGNOSE the
    :class:`~repro.db.report.ExecutionReport`, and re-lower with
    escalated parameters until the run is clean (or ``policy.
    max_attempts`` is spent — :class:`RetryExhausted`).  Escalations:

    * exchange overflow  -> ``shuffle_bucket_floor`` = the observed peak
      per-(sender, owner) send demand (exact, so ONE retry suffices) and
      ``shuffle_slack`` doubled (capped at n_shards, where overflow is
      impossible) as the belt-and-braces ladder;
    * truncation tail mass above ``policy.tail_tol`` -> kappa doubled;
    * group-code-table overflow -> max_groups doubled;
    * a transfer fault surviving the in-loop wave retries -> wave size
      halved (``stream_wave_chunks``).

    NaN counts WITHOUT an exchange overflow mean the NaN came in with
    the data — nothing to escalate, so the result returns as-is with the
    report flagging it.

    Returns ``(result, report)``; ``report.final_params`` records the
    final attempt's overrides and ``report.waves["attempts"]`` the
    attempt count.  Because every attempt is a fresh compile at its own
    parameters, the converged result is bit-identical to a first run
    launched with ``final_params`` — the determinism contract extended
    to the retry loop.

    ``jit=True`` wraps the compiled function in ``jax.jit`` (required to
    exercise the traced-key slack sizing: eager runs size buckets from
    concrete key histograms and cannot overflow).  Not available for
    streamed plans (the wave loop is a host loop).

    ``params`` binds the plan's lifted :class:`Param` holes (passed
    through to every attempt unchanged).  ``compiler`` replaces the
    per-attempt compile (signature ``compiler(root, mesh, jit=...,
    **opts) -> fn``) — the serving layer passes its bounded plan cache
    here, keyed on each attempt's exact (scaled plan, lowering params),
    so retries create per-attempt entries instead of poisoning the base
    one.  ``kappa_scale`` / ``groups_scale`` seed the escalation ladder
    (a service replaying a remembered ``final_params`` starts AT the
    converged point: attempt 1 is clean and its compile is a cache hit).
    """
    policy = policy or RetryPolicy()
    compiler = compiler or _default_compiler
    opts = dict(opts)
    slack = float(opts.pop("shuffle_slack", 4.0))
    floor = opts.pop("shuffle_bucket_floor", None)
    wave_chunks = opts.pop("stream_wave_chunks", None)
    n_shards = 1
    if mesh is not None:
        from . import distributed as dist
        for a in dist._tuple_axes(mesh, opts.get("data_axes", ("data",))):
            n_shards *= mesh.shape[a]

    out = report = None
    attempt = 0
    for attempt in range(1, policy.max_attempts + 1):
        fn = compiler(_scale_plan(root, kappa_scale, groups_scale),
                      mesh, jit=jit, with_report=True, shuffle_slack=slack,
                      shuffle_bucket_floor=floor,
                      stream_wave_chunks=wave_chunks,
                      stream_wave_retries=policy.wave_retries,
                      **opts)
        try:
            out, report = fn(tables, params)
        except faults.TransferFault as e:
            if (e.wave_chunks is None or e.at_minimum
                    or attempt == policy.max_attempts):
                raise
            wave_chunks = e.wave_chunks
            continue
        issues = report.issues(policy.tail_tol)
        if not any(k != "nan" for k in issues):
            break
        if attempt == policy.max_attempts:
            raise RetryExhausted(
                f"unresolved after {attempt} attempts: "
                f"{report.describe(policy.tail_tol)}", report)
        if "overflow" in issues:
            floor = max(floor or 0,
                        max(int(jnp.max(report.exchange_demand[k]))
                            for k in issues["overflow"]))
            slack = C.escalated_slack(slack, n_shards)
        if "tail" in issues:
            kappa_scale *= 2
        if "group_overflow" in issues:
            groups_scale *= 2

    report.final_params.update(
        shuffle_slack=slack, shuffle_bucket_floor=floor,
        stream_wave_chunks=wave_chunks, kappa_scale=kappa_scale,
        groups_scale=groups_scale)
    report.waves["attempts"] = attempt
    return out, report
