"""Distributed query execution: generic shard_map glue over the segment-UDA
protocol of :mod:`repro.core.uda`.

The paper scales by streaming partitions through per-core UDAs and merging
(Glade's Accumulate/Merge).  On a TPU pod the same structure is:

    Accumulate  per-shard: the ONE canonical blocked accumulation loop
                (`uda.accumulate`) over the local tuple partition
    Merge       `uda.reduce_collective`: one psum over the data axes per
                additive state (log-CF / cumulants / log(1-p) are all
                additive — DESIGN.md §2); MinMax gather-folds instead
    Finalize    replicated FFT / mixture solve epilogue

``make_uda_step`` builds that pipeline for ANY dict of registered UDAs —
the generic aggregation-only step that ``make_query_step`` specialises to
the canonical fixed query shape (confidence + normal + cumulants + exact
global CF) which launch/dryrun.py lowers for the `pgf_tpch` cell.
Tuples are sharded over ('pod','data') — the (batch-like) scale axis — and
replicated over 'model'; frequency grids of the exact CF path are sharded
over 'model' so the O(n*F) phase work splits both ways (the beyond-paper
optimization validated in §Perf).

The sharded relational frontend (`db/plans.py`, strategies lowered by
`db/physical.py`) runs the WHOLE physical plan inside one shard_map and
uses the collective helpers below instead of a per-node step:

    gather_table        broadcast a row-partitioned Table (small FK-join
                        build sides, final sharded results): one tiled
                        all-gather per column, shard-major == global row
                        order under the contiguous row partitioning
    shuffle_by_key      static-shape all_to_all exchange: each row goes to
                        shard ``key % n_shards`` through per-destination
                        send buckets of fixed capacity, with overflow
                        accounting (operators.bucket_slots)
    shuffle_fk_join     the ShuffleJoin executor: build rows hashed to
                        their key's owner shard, probe keys exchanged as
                        requests, matched shard-locally (ops.fk_join on
                        the hash bucket), responses shuffled home — peak
                        build rows/device O(build/shards), output
                        bit-identical to the gathered join
    copartitioned_fk_join   the CoPartitionedJoin executor: the same two
                        exchanges, but probe rows carry (p, canonical
                        chunk id, aggregation columns) and matched rows
                        STAY at their key's owner — no shuffle_back
                        round-trip; output is HashPartitioned(left_key)
    repartition_by_key  hash-exchange aggregation inputs to their
                        group-key owner (the no-join feed of
                        PartitionedAgg)
    partitioned_merge   the HashPartitioned Merge: every group lives
                        wholly at one owner, so each owner finishes the
                        canonical chunk tree_fold LOCALLY and ONE psum
                        combines the folded additive states (exact zeros
                        elsewhere => bit-identical to allgather_merge);
                        MinMax states gather-fold across owners
    group_ids_sharded   two-phase distributed group-id assignment —
                        per-shard jnp.unique, all-gather + merge of the
                        per-shard code tables, searchsorted against the
                        merged codes (exact vs the single-pass oracle,
                        overflow included: operators.merge_group_codes)
    allgather_merge     ONE collective Merge per aggregation pass: gather
                        every shard's per-canonical-chunk partial states
                        and fold ALL chunk states with the one fixed tree
                        (uda.tree_fold) — the bit-reproducible form of the
                        additive psum for ANY shard count (pow2 or not),
                        which also covers non-additive states (MinMax)
    group_key_columns_sharded   per-shard segment_max + one pmax (max is
                        exact, so bit-equal to the replicated reduction)
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import uda
from ..testing import faults
from . import operators as ops
from . import physical as phys
from .table import Table


def _tuple_axes(mesh: Mesh, data_axes: Sequence[str]) -> tuple:
    return tuple(a for a in ("pod",) + tuple(data_axes)
                 if a in mesh.axis_names)


#: trace-time counts of the collective exchanges issued by the sharded
#: frontend, keyed by kind ("shuffle", "shuffle_back", "gather_table",
#: "merge_psum", "merge_gather").  Incremented while a plan traces (once
#: per eager execution, once per jit trace), so tests and benchmarks can
#: assert structural properties — e.g. that a co-partitioned pipeline
#: issues ZERO shuffle_back round-trips.
COLLECTIVE_COUNTS: dict = {}


def reset_collective_counts() -> None:
    COLLECTIVE_COUNTS.clear()


def _count(kind: str) -> None:
    COLLECTIVE_COUNTS[kind] = COLLECTIVE_COUNTS.get(kind, 0) + 1


@functools.lru_cache(maxsize=4096)
def _interned(partial):
    return partial


def reuse_eager_shard_map_programs() -> None:
    """Let an EAGER shard_map reuse the programs it has compiled before.

    Outside jit, JAX (0.9) runs a shard_map body primitive by primitive,
    each as ``jax.jit(HashablePartial(prim, params, mesh, specs))``.  The
    partial is built anew per call and dies with it, and JAX's trace and
    lowering caches hold their keys weakly, so every primitive of every
    eager call is traced, lowered and compiled again — the same program
    hundreds of times over.  Interning the partials (equal partials are
    the same primitive with the same params on the same mesh) keeps the
    first one alive, so those caches hit.  The programs that run are the
    ones that ran before, op by op as before: no result changes by a bit.
    Jitted shard_maps never take this path.  Idempotent; does nothing on
    a JAX whose eager shard_map is built differently."""
    from jax._src import shard_map as sm_impl
    from jax._src.util import HashablePartial
    if getattr(sm_impl, "HashablePartial", None) is not HashablePartial:
        return

    def interned_partial(f, *args, **kwargs):
        partial = HashablePartial(f, *args, **kwargs)
        try:
            return _interned(partial)
        except TypeError:                   # unhashable params
            return partial

    sm_impl.HashablePartial = interned_partial


def data_rank(axis_names):
    """Linearized shard rank over the data axes (row-major — the order of
    the contiguous row partitioning).  Call inside shard_map."""
    r = jnp.zeros((), jnp.int32)
    for a in tuple(axis_names):
        r = r * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return r


def make_uda_step(mesh: Mesh, uda_factory: Callable[[int, object], dict], *,
                  max_groups: int, data_axes: Sequence[str] = ("data",),
                  model_axis: str | None = "model", block: int = 8192,
                  post=None):
    """Build a jit-able distributed Accumulate/Merge/Finalize step.

    uda_factory(model_size, model_rank) -> {name: UDA}; ``model_rank`` is a
    traced axis index inside shard_map (0 without a model axis), so CF UDAs
    can bind their per-shard frequency slice.

    The returned step takes (probs, values, gids) with tuples sharded over
    the data axes (values may be a dict of per-UDA columns) and returns the
    replicated finalized results — or ``post(udas, states)`` if given.
    """
    axes = _tuple_axes(mesh, data_axes)
    model = model_axis if (model_axis and model_axis in mesh.axis_names) \
        else None
    model_size = mesh.shape[model] if model else 1
    in_spec = P(axes)

    def step(probs, values, gids):
        def shard_fn(p, v, g):
            rank = jax.lax.axis_index(model) if model else 0
            udas = uda_factory(model_size, rank)
            states = uda.accumulate(udas, p, v, g, max_groups=max_groups,
                                    block=block)
            states = uda.reduce_collective(udas, states, axes, model)
            if post is not None:
                return post(udas, states)
            return uda.finalize(udas, states)

        fn = shard_map(shard_fn, mesh=mesh,
                       in_specs=(in_spec, in_spec, in_spec),
                       out_specs=P(), check_vma=False)
        return fn(probs, values, gids)

    return jax.jit(step)


def pad_for(mesh: Mesh, probs, values, gids, *, max_groups: int,
            data_axes: Sequence[str] = ("data",)):
    """Zero-pad tuple columns so the shard count divides them (p = 0 pads
    contribute nothing to any UDA; they land in the overflow group)."""
    shards = 1
    for a in _tuple_axes(mesh, data_axes):
        shards *= mesh.shape[a]
    n = probs.shape[0]
    pad = (-n) % shards
    if pad == 0:
        return probs, values, gids
    probs = jnp.pad(probs, (0, pad))
    gids = jnp.pad(gids, (0, pad), constant_values=max_groups - 1)
    if isinstance(values, dict):
        # Pad each distinct source array once so aggregates sharing a column
        # keep sharing it (uda.accumulate dedups value columns by identity).
        padded: dict = {}
        values = {k: None if v is None
                  else padded.setdefault(id(v), jnp.pad(v, (0, pad)))
                  for k, v in values.items()}
    elif values is not None:
        values = jnp.pad(values, (0, pad))
    return probs, values, gids


# ----------------------------------------------------- sharded frontend
def gather_table(t: Table, axis_names) -> Table:
    """Broadcast a row-partitioned Table (call inside shard_map): tiled
    all-gather of every column plus p and valid.  With the contiguous row
    partitioning of the sharded frontend, shard-major concatenation IS the
    original global row order, so the gathered table is bit-identical to
    the unsharded one."""
    axis_names = tuple(axis_names)
    _count("gather_table")
    g = lambda x: jax.lax.all_gather(x, axis_names, axis=0, tiled=True)
    return Table({k: g(v) for k, v in t.columns.items()},
                 g(t.prob), g(t.valid), phys.Replicated())


def shuffle_by_key(keys, cols: dict, axis_names, *, n_shards: int,
                   capacity: int, valid=None):
    """Static-shape shuffle exchange (call inside shard_map): row i goes
    to shard ``keys[i] % n_shards``.

    Every shard fills ``n_shards`` send buckets of ``capacity`` rows
    (``operators.bucket_slots`` assigns slots; ok-rows beyond a bucket's
    capacity overflow and are DROPPED but counted) and one ``all_to_all``
    transposes the buckets, so per-device exchange memory is the static
    ``n_shards * capacity`` rows regardless of skew.

    Returns ``(recv, recv_mask, slot, sent, overflow)``:
        recv       {name: (n_shards * capacity,) array} — bucket j*capacity
                   + r holds sender j's r-th row for THIS shard; empty
                   slots zero
        recv_mask  (n_shards * capacity,) bool occupancy
        slot, sent the local send-slot bookkeeping (route responses home
                   through the same buckets: ``shuffle_back`` +
                   ``operators.take_from_buckets``)
        overflow   local count of ok-rows dropped for capacity
    """
    axis_names = tuple(axis_names)
    _count("shuffle")
    faults.on_exchange()
    ok = jnp.ones(keys.shape, bool) if valid is None else valid
    dest = jnp.mod(keys.astype(jnp.int32), n_shards)
    slot, sent, overflow = ops.bucket_slots(dest, ok, n_shards, capacity)
    size = n_shards * capacity
    inv = ops.bucket_fill_index(slot, size)
    send = ops.scatter_to_buckets(cols, slot, size, inv=inv)
    mask = inv < keys.shape[0]          # slot filled by a sent row
    recv = {k: _all_to_all_rows(v, axis_names, n_shards, capacity)
            for k, v in send.items()}
    recv_mask = _all_to_all_rows(mask, axis_names, n_shards, capacity)
    return recv, recv_mask, slot, sent, overflow


def shuffle_back(cols: dict, axis_names, n_shards: int, capacity: int):
    """Return per-request responses to their origin shards: the inverse
    exchange of :func:`shuffle_by_key` (all_to_all is an involution on the
    (n_shards, capacity) bucket layout), landing each response in the send
    slot its request came from."""
    axis_names = tuple(axis_names)
    _count("shuffle_back")
    return {k: _all_to_all_rows(v, axis_names, n_shards, capacity)
            for k, v in cols.items()}


def _all_to_all_rows(x, axis_names, n_shards: int, capacity: int):
    b = x.reshape((n_shards, capacity) + x.shape[1:])
    out = jax.lax.all_to_all(b, axis_names, split_axis=0, concat_axis=0,
                             tiled=False)
    return out.reshape((n_shards * capacity,) + x.shape[1:])


# Internal exchange fields ride the same bucket dicts as the carried
# user columns; the "\x00" prefix keeps them out of any legal column
# namespace (a user column can't collide silently — it is rejected).
_KEY, _PROB = "\x00key", "\x00prob"


def _check_exchange_cols(what: str, cols) -> None:
    bad = [c for c in cols if c.startswith("\x00")]
    if bad:
        raise ValueError(f"{what} may not start with '\\x00' (reserved "
                         f"for exchange fields): {bad}")


def _send_demand(keys, valid, n_shards: int):
    """Peak per-owner send demand of one shard: the largest number of
    valid rows this shard wants to route to any single owner.  pmax'd
    across shards this is exactly the bucket capacity that makes the
    exchange overflow-free — the concrete escalation target the retry
    controller reads from ``ExecutionReport.exchange_demand``."""
    dest = jnp.mod(keys.astype(jnp.int32), n_shards)
    cnt = jnp.zeros((n_shards,), jnp.int32).at[dest].add(
        valid.astype(jnp.int32))
    return jnp.max(cnt)


def _record_leg(report, label: str, leg: str, axis_names, overflow,
                keys, valid, n_shards: int, capacity: int) -> None:
    """File one exchange leg into the ReportBuilder (costs two extra
    collectives per leg, so the joins only call this when a report was
    requested)."""
    if report is None:
        return
    report.exchange_leg(
        label, leg, jax.lax.psum(overflow, axis_names),
        jax.lax.pmax(_send_demand(keys, valid, n_shards), axis_names),
        capacity)


def _exchange_build(right: Table, right_key: str, right_cols, axis_names,
                    n_shards: int, build_bucket: int):
    """Shuffle the build side's valid rows to their ``right_key %
    n_shards`` owner: each owner holds its hash bucket of the dimension
    table, O(build/shards) rows.  Returns (bucket Table, local overflow)."""
    bcols = {_KEY: right[right_key].astype(jnp.int32), _PROB: right.prob}
    for c in right_cols:
        bcols[c] = right[c]
    brecv, bmask, _, _, b_over = shuffle_by_key(
        bcols[_KEY], bcols, axis_names, n_shards=n_shards,
        capacity=build_bucket, valid=right.valid)
    return Table({right_key: brecv[_KEY],
                  **{c: brecv[c] for c in right_cols}},
                 brecv[_PROB], bmask,
                 phys.HashPartitioned(right_key)), b_over


def _chunk_ids(capacity: int, axis_names, chunk_size: int,
               num_chunks: int):
    """Canonical-chunk id of each local row (clipped into the canonical
    grid; shard-alignment padding rows are invalid and never shipped)."""
    gid0 = data_rank(axis_names) * capacity
    return jnp.clip((gid0 + jnp.arange(capacity)) // chunk_size,
                    0, num_chunks - 1).astype(jnp.int32)


def shuffle_fk_join(left: Table, right: Table, left_key: str,
                    right_key: str, right_cols: Sequence[str], axis_names,
                    *, n_shards: int, build_bucket: int,
                    probe_bucket: int, report=None,
                    label: str = "") -> Table:
    """Hash-partitioned FK join (call inside shard_map): the ShuffleJoin
    strategy of :mod:`repro.db.physical`.

    1. Build exchange: the (row-partitioned) build side's valid rows are
       shuffled to shard ``right_key % n_shards`` — each owner holds its
       hash bucket of the dimension table, O(build/shards) rows.
    2. Probe requests: each shard shuffles its probe keys to the same
       owners.
    3. Local match: one ``ops.fk_join`` of the request rows against the
       local build bucket (requests carry p = 1, so the join returns the
       matched build probability directly, zero / zero-filled columns on
       miss).
    4. Responses shuffle home through the same static buckets and land in
       the probe rows' original positions — the output keeps the LEFT
       side's RowBlocked layout and is bit-identical to the gathered
       ``ops.fk_join`` (same matches, same float products, same
       deterministic zeros on miss).

    Overflow accounting: bucket overflows on either exchange lose rows the
    exact result needs, so the total overflow (one psum, so every shard
    agrees) POISONS the output probabilities with NaN rather than
    returning silently wrong masses.  The NaN propagates through every
    probabilistic epilogue (confidence / group_confidence / aggregate all
    consume the p column), but a purely BOOLEAN consumer of the join —
    e.g. a deterministic-mode predicate like ``p > 0.5`` — collapses NaN
    to False and can present the corruption as an empty result; validity
    flags and integer columns have no NaN to carry.  Where that matters,
    make overflow impossible instead of detectable: ``shuffle_slack >=
    n_shards`` pins every bucket at the sender's full local rows (the
    default slack 4.0 already guarantees this for meshes of up to 4 data
    shards), or keep join keys balanced mod n_shards.
    """
    axis_names = tuple(axis_names)
    right_cols = list(right_cols)
    KEY, PROB, HIT = _KEY, _PROB, "\x00hit"
    _check_exchange_cols("shuffle_fk_join right_cols", right_cols)

    # 1. build side -> hash owners
    build, b_over = _exchange_build(right, right_key, right_cols,
                                    axis_names, n_shards, build_bucket)

    # 2. probe keys -> the same owners
    lkey = left[left_key].astype(jnp.int32)
    preq, pmask, slot, sent, p_over = shuffle_by_key(
        lkey, {KEY: lkey}, axis_names, n_shards=n_shards,
        capacity=probe_bucket, valid=left.valid)

    # 3. shard-local match on the hash bucket
    req = Table({left_key: preq[KEY]},
                jnp.ones(pmask.shape, left.prob.dtype), pmask)
    matched = ops.fk_join(req, build, left_key, right_key, right_cols)

    # 4. responses home, into the probe rows' original positions
    resp = {PROB: matched.prob, HIT: matched.valid}
    for c in right_cols:
        resp[c] = matched[c]
    back = shuffle_back(resp, axis_names, n_shards, probe_bucket)
    got = ops.take_from_buckets(back, slot, sent)

    _record_leg(report, label, "build", axis_names, b_over,
                right[right_key], right.valid, n_shards, build_bucket)
    _record_leg(report, label, "probe", axis_names, p_over,
                lkey, left.valid, n_shards, probe_bucket)
    over = jax.lax.psum(b_over + p_over, axis_names)
    prob = left.prob * got[PROB]
    prob = jnp.where(over > 0, jnp.asarray(jnp.nan, prob.dtype), prob)
    cols = dict(left.columns)
    for c in right_cols:
        cols[c] = got[c]
    return Table(cols, prob, left.valid & got[HIT], left.part)


def copartitioned_fk_join(left: Table, right: Table, left_key: str,
                          right_key: str, right_cols: Sequence[str],
                          carry_cols: Sequence[str], axis_names, *,
                          n_shards: int, build_bucket: int,
                          probe_bucket: int, chunk_size: int,
                          num_chunks: int, report=None,
                          label: str = "") -> Table:
    """Hash-partitioned FK join WITHOUT the response round-trip (the
    CoPartitionedJoin strategy of :mod:`repro.db.physical`): matched rows
    STAY at their ``left_key % n_shards`` owner so a downstream GROUP BY
    on the join key aggregates in place.

    Differences from :func:`shuffle_fk_join`:

    * probe rows ship (key, p, canonical-chunk id, ``carry_cols``) — the
      columns the downstream aggregation reads — instead of the key alone;
    * the owner-local ``ops.fk_join`` consumes the REAL probe
      probabilities, so the output probability (p_l * p_r, deterministic
      zero on miss) is final at the owner;
    * there is no ``shuffle_back``: the output keeps the exchange's
      (sender-major, in-sender row order) bucket layout — which IS the
      global row order restricted to the owner — with the shipped chunk
      id under ``physical.CHUNK_COL``, and carries
      ``HashPartitioned(left_key)``.

    Overflow on either exchange is psum-accounted and NaN-poisons the
    output probabilities, exactly like :func:`shuffle_fk_join` (same
    boolean-consumer caveat; concrete-key adaptive buckets or
    ``shuffle_slack >= n_shards`` make overflow impossible).
    """
    axis_names = tuple(axis_names)
    right_cols = list(right_cols)
    carry_cols = list(carry_cols)
    _check_exchange_cols("copartitioned_fk_join columns",
                         right_cols + carry_cols)

    build, b_over = _exchange_build(right, right_key, right_cols,
                                    axis_names, n_shards, build_bucket)

    # The routing key is int32 (hash arithmetic); the key COLUMN ships in
    # its original dtype so group representatives keep their identity
    # values bit-identical to the unshuffled paths.
    lkey = left[left_key].astype(jnp.int32)
    pcols = {_KEY: left[left_key], _PROB: left.prob,
             phys.CHUNK_COL: _chunk_ids(left.capacity, axis_names,
                                        chunk_size, num_chunks)}
    for c in carry_cols:
        pcols[c] = left[c]
    precv, pmask, _, _, p_over = shuffle_by_key(
        lkey, pcols, axis_names, n_shards=n_shards,
        capacity=probe_bucket, valid=left.valid)

    probe = Table({left_key: precv[_KEY],
                   phys.CHUNK_COL: precv[phys.CHUNK_COL],
                   **{c: precv[c] for c in carry_cols}},
                  precv[_PROB], pmask, phys.HashPartitioned(left_key))
    out = ops.fk_join(probe, build, left_key, right_key, right_cols)
    _record_leg(report, label, "build", axis_names, b_over,
                right[right_key], right.valid, n_shards, build_bucket)
    _record_leg(report, label, "probe", axis_names, p_over,
                lkey, left.valid, n_shards, probe_bucket)
    over = jax.lax.psum(b_over + p_over, axis_names)
    return out.with_prob(jnp.where(
        over > 0, jnp.asarray(jnp.nan, out.prob.dtype), out.prob))


def repartition_by_key(t: Table, key: str, carry_cols: Sequence[str],
                       axis_names, *, n_shards: int, bucket: int,
                       chunk_size: int, num_chunks: int, report=None,
                       label: str = "") -> Table:
    """Hash-exchange a RowBlocked relation to its ``key % n_shards``
    owners (the Repartition strategy): the no-join feed of a
    PartitionedAgg.  Rows ship (key, p, canonical-chunk id, carry_cols);
    the output has the same bucket layout / chunk-id column /
    overflow-NaN contract as :func:`copartitioned_fk_join`."""
    axis_names = tuple(axis_names)
    carry_cols = list(carry_cols)
    _check_exchange_cols("repartition_by_key carry_cols", carry_cols)
    kcol = t[key].astype(jnp.int32)     # routing only; column ships as-is
    cols = {_KEY: t[key], _PROB: t.prob,
            phys.CHUNK_COL: _chunk_ids(t.capacity, axis_names,
                                       chunk_size, num_chunks)}
    for c in carry_cols:
        cols[c] = t[c]
    recv, mask, _, _, over = shuffle_by_key(
        kcol, cols, axis_names, n_shards=n_shards, capacity=bucket,
        valid=t.valid)
    _record_leg(report, label, "repart", axis_names, over,
                kcol, t.valid, n_shards, bucket)
    over = jax.lax.psum(over, axis_names)
    prob = jnp.where(over > 0, jnp.asarray(jnp.nan, recv[_PROB].dtype),
                     recv[_PROB])
    return Table({key: recv[_KEY], phys.CHUNK_COL: recv[phys.CHUNK_COL],
                  **{c: recv[c] for c in carry_cols}},
                 prob, mask, phys.HashPartitioned(key))


def group_ids_sharded(table: Table, keys: Sequence[str], max_groups: int,
                      axis_names):
    """Two-phase distributed group-id assignment (call inside shard_map).

    Phase 1: per-shard ``jnp.unique`` of the live key codes (size
    max_groups, sentinel fill).  Phase 2: one tiled all-gather of the
    per-shard code tables + a second unique merge, giving every shard the
    same global code table; ids come from searchsorted of the LOCAL codes
    against it.  Replaces the replicated full-table unique: per-shard
    work/memory is O(local rows + shards * max_groups), and the result is
    bit-identical to ``operators.group_ids`` (see
    ``operators.merge_group_codes`` for the overflow argument).
    """
    axis_names = tuple(axis_names)
    code_live, big = ops.live_key_codes(table, keys)
    local = ops.merge_group_codes(code_live, max_groups)
    gathered = jax.lax.all_gather(local, axis_names, axis=0, tiled=True)
    merged = ops.merge_group_codes(gathered, max_groups)
    return ops.codes_to_ids(code_live, merged), merged, merged != big


def group_key_columns_sharded(table: Table, keys: Sequence[str], ids,
                              max_groups: int, axis_names):
    """Per-group key representatives over a row-partitioned table: local
    segment_max, then one pmax over the data axes (max is exact, so this
    is bit-equal to the replicated reduction)."""
    axis_names = tuple(axis_names)
    cols = ops.group_key_columns(table, keys, ids, max_groups)
    return {k: jax.lax.pmax(v, axis_names) for k, v in cols.items()}


def allgather_merge(udas: dict, parts: list, axis_names,
                    num_chunks: int, shards: int) -> dict:
    """The sharded frontend's ONE collective Merge per aggregation pass:
    all-gather every shard's per-canonical-chunk partial states and fold
    ALL ``num_chunks`` chunk states with ``uda.tree_fold``, identically on
    every shard.

    ``parts`` is this shard's list of per-chunk state dicts
    (``uda.accumulate_chunk_states`` over its contiguous chunk run); under
    the contiguous chunk assignment the shard-major gather order IS the
    global chunk order, and slots past the canonical grid (the padding
    chunks of shard counts that don't divide ``num_chunks``) sort last and
    are sliced away before the fold.  Because the fold consumes the SAME
    chunk leaves in the SAME fixed tree as the single-device
    ``uda.accumulate_chunked``, the result is bit-identical for ANY shard
    count — power of two or not.  For additive states this computes
    exactly what a psum would; non-additive states (MinMax) ride the same
    code path.

    Bandwidth: when every shard's chunk run is an ALIGNED power-of-two
    subtree of the canonical tree (pow2 shard count dividing a pow2 grid
    — the common case), each shard pre-folds its run locally and the
    gather moves ONE state per shard; only non-dividing shard counts pay
    for gathering ceil(num_chunks / shards) chunk states each.
    """
    axis_names = tuple(axis_names)
    local = len(parts)
    aligned = (shards * local == num_chunks
               and local & (local - 1) == 0 and shards & (shards - 1) == 0)
    out = {}
    for name, u in udas.items():
        mine = [p[name] for p in parts]
        if aligned:
            mine = [uda.tree_fold(u, mine)]     # the local aligned subtree
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *mine)
        g = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis_names, axis=0, tiled=True),
            stacked)
        leaves = shards if aligned else num_chunks
        states = [jax.tree.map(lambda x, c=c: x[c], g)
                  for c in range(leaves)]
        out[name] = uda.tree_fold(u, states)
    return out


def gather_chunk_states(udas: dict, parts: list, axis_names) -> list:
    """All-gather per-chunk partial states WITHOUT folding them: the
    per-wave collective of the streamed executor.

    ``parts`` is this shard's list of per-chunk state dicts for ONE wave;
    the return value is the global list (shard-major = the wave's chunk
    slot order) of per-chunk state dicts, replicated on every shard.  The
    caller (plans.run's streamed wave loop) maps each entry to its
    canonical chunk slot and folds ONCE after the last wave, so the fold
    consumes exactly the leaves — in exactly the tree — of the resident
    ``allgather_merge`` / ``accumulate_chunked`` path."""
    axis_names = tuple(axis_names)
    _count("gather_chunks")
    states: list | None = None
    for name, u in udas.items():
        mine = [p[name] for p in parts]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *mine)
        g = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis_names, axis=0, tiled=True),
            stacked)
        total = jax.tree.leaves(g)[0].shape[0]
        if states is None:
            states = [dict() for _ in range(total)]
        for c in range(total):
            states[c][name] = jax.tree.map(lambda x, c=c: x[c], g)
    return states or []


def _scatter_sum_gather(state, axis_names, n_shards: int):
    """psum via reduce-scatter + all-gather: each leaf is split along its
    leading (group) axis, every shard sums ONLY its 1/n_shards stripe, and
    the gather reassembles the full state — (2/n_shards) x the psum's
    per-device payload.  Bit-identical to the psum here because every
    element is exact init-zero on all shards but its group's owner, so
    whatever the summation order, it adds x + 0 + ... + 0 = x."""
    def leaf(x):
        g = x.shape[0]
        pad = (-g) % n_shards
        if pad:
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        y = jax.lax.psum_scatter(x, axis_names, scatter_dimension=0,
                                 tiled=True)
        y = jax.lax.all_gather(y, axis_names, axis=0, tiled=True)
        return y[:g] if pad else y
    return jax.tree.map(leaf, state)


def partitioned_merge(udas: dict, parts: list, axis_names,
                      n_shards: int | None = None) -> dict:
    """The HashPartitioned Merge (PartitionedAgg): combine per-owner
    canonical-chunk states into the replicated final state.

    ``parts`` is this owner's list of ALL ``num_chunks`` canonical chunk
    states (the compound (chunk, group) accumulate of the fused pipeline
    computes every chunk's slice locally; a chunk's slice is nonzero only
    for groups this shard owns).  Because a group's tuples live wholly at
    its ``key % n_shards`` owner, the owner's chunk-c state for group g
    IS the global chunk-c state for g — so folding the chunks LOCALLY
    with the one fixed :func:`repro.core.uda.tree_fold` gives the exact
    canonical fold for the owned groups, and every other shard holds
    exact init-zeros there.  The cross-shard merge is then

    * additive states: ONE reduce-scatter onto the group owners + one
      all-gather of the owner stripes (``n_shards`` given; a plain psum
      else) — x + 0 + ... + 0 is bitwise x whichever shard sums it, so
      the result is BIT-IDENTICAL to the RowBlocked ``allgather_merge``
      fold (and to mesh=None), while moving O(state / n_shards) bytes
      per leg instead of the psum's O(state) — each owner only ever sums
      the stripe it is about to broadcast;
    * non-additive states (MinMax): one all-gather + the owner-order
      merge fold — ``MinMax.merge(init, x) == x`` bitwise (the run-fold
      merge preserves singleton runs exactly), so the same argument
      applies.

    The bit-identity argument needs every group wholly at one owner,
    which the group-id protocol guarantees as long as the key
    cardinality fits ``max_groups``; the overflow fill bucket (invalid
    in every path) may psum several owners' garbage together.
    """
    axis_names = tuple(axis_names)
    out = {}
    for name, u in udas.items():
        folded = uda.tree_fold(u, [p[name] for p in parts])
        _count("merge_psum" if u.additive else "merge_gather")
        if u.additive and n_shards is not None and n_shards > 1:
            out[name] = _scatter_sum_gather(folded, axis_names, n_shards)
        else:
            # reduce_data for both shapes: the additive default psums,
            # MinMax overrides it with the all-gather + merge fold.
            out[name] = u.reduce_data(folded, axis_names)
    return out


def make_query_step(mesh: Mesh, *, max_groups: int = 1024,
                    num_freq: int = 4096, orders: int = 8,
                    data_axes: Sequence[str] = ("data",),
                    model_axis: str | None = "model"):
    """The canonical distributed aggregate-query step for `mesh`.

    Inputs (sharded over data axes):
        probs  (n,) f32, values (n,) f32, gids (n,) int32
    Output (replicated): finalized per-group confidence, normal terms,
    cumulant sums, and the exact global distribution (num_freq coeffs),
    the latter accumulated over the model axis's frequency slices.
    """
    model = model_axis if (model_axis and model_axis in mesh.axis_names) \
        else None
    model_size = mesh.shape[model] if model else 1
    assert num_freq % model_size == 0
    f_loc = num_freq // model_size

    def factory(size, rank):
        cf = uda.SumCF(num_freq, freq_lo=rank * f_loc, freq_cnt=f_loc)
        cf.scalar = True          # global distribution: one group
        return dict(conf=uda.AtLeastOne(), normal=uda.SumNormal(),
                    cum=uda.SumCumulants(orders), cf=cf)

    def post(udas, states):
        confidence = udas["conf"].finalize(states["conf"])
        coeffs = udas["cf"].finalize(states["cf"])[0]
        return (confidence, states["normal"].terms, states["cum"].terms,
                coeffs)

    return make_uda_step(mesh, factory, max_groups=max_groups,
                         data_axes=data_axes, model_axis=model_axis,
                         post=post)


def shard_columns(mesh: Mesh, arrays, data_axes: Sequence[str] = ("data",)):
    """Place host arrays with tuple-sharded layout on the mesh."""
    sharding = NamedSharding(mesh, P(_tuple_axes(mesh, data_axes)))
    return tuple(jax.device_put(a, sharding) for a in arrays)


def input_specs(*, n_tuples: int, dtype=jnp.float32):
    """ShapeDtypeStruct stand-ins for the distributed query step inputs."""
    return dict(
        probs=jax.ShapeDtypeStruct((n_tuples,), dtype),
        values=jax.ShapeDtypeStruct((n_tuples,), dtype),
        gids=jax.ShapeDtypeStruct((n_tuples,), jnp.int32),
    )
