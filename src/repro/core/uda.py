"""Grouped segment-UDA subsystem: the ONE implementation of every
probabilistic aggregate (paper §VI-A, Glade Initialize/Accumulate/Merge/
Finalize).

Before this module the same UDA math lived three times (scalar classes in
``core/aggregates.py``, grouped segment reductions in ``db/operators.py``,
and inline again in ``db/distributed.py``), each copy with its own blocking
heuristics and tail handling.  Here each aggregate is defined once as

    init(max_groups, dtype)      -> pytree state, leaves lead with (G, ...)
    update(state, p, v, g)       -> state   (one tuple block; streaming UDAs)
    merge(a, b)                  -> state   (additive for streaming UDAs,
                                             hence one `psum` inside shard_map)
    finalize(state)              -> per-group device-side results

vectorised over ``max_groups`` groups — the scalar case is just
``max_groups == 1`` with all-zero group ids, which is how the thin wrappers
in :mod:`repro.core.aggregates` and the delegating helpers in
:mod:`repro.core.poisson_binomial` / :mod:`repro.core.approx` use it.

:func:`accumulate` below is the single canonical accumulation loop (the
blocked-scan tiling previously private to ``db/distributed.py``): ONE
``lax.scan`` over tuple blocks feeds every streaming UDA at once, so a
multi-aggregate query reads its tuples exactly once, and the (block, F)
phase tile of the exact-CF path is the only large live intermediate.  On
TPU the CF / cumulant accumulations dispatch to the Pallas kernels: scalar
states to :mod:`repro.kernels.pb_cf` / :mod:`repro.kernels.cumulants`,
grouped CF states to the (G, F)-tiled :mod:`repro.kernels.group_cf`
(``SumCF.accumulate_full``), with the pure-JAX oracles as CPU fallback.

Registered UDAs (paper §V / §VI / §VII):

    atleastone   P(group non-empty) = 1 - prod(1-p)        (§VI row V)
    normal       (sum v p, sum v^2 p (1-p)) terms          (§V-C.3)
    cumulants    sum v^j kappa_j(p) moment terms           (§V-C.3, Lindsay)
    cf           exact SUM/COUNT log-characteristic fn     (§V-A/C)
    min / max    top-kappa ordered (value, survival) list  (§V-B, §VII-C)

Distributed execution (``db/distributed.py``) is generic over this
protocol: Accumulate per shard, ``reduce_data`` = one psum over the tuple
sharding axes, ``reduce_model`` reassembles model-axis frequency slices,
Finalize replicated.
"""
from __future__ import annotations

import collections
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .approx import MAX_ORDER, _bernoulli_cumulant_polys
from .config import default_float

# The canonical tiling constants: bound the scan body's working set to
# ~2^23 elements so the (block, F) tile stays cache/VMEM sized regardless
# of distribution width; the floor of 64 keeps even num_freq ~ 2^20 tiles
# within budget (a higher floor would override the budget at large F).
_BLOCK_FLOOR = 64
_ELEM_BUDGET = 1 << 23


def _tiny(dtype):
    """Log-underflow guard, unified across all former copies."""
    return 1e-30 if dtype == jnp.float32 else 1e-300


def _scatter_add(acc, g, contrib):
    """acc[g] += contrib with the G == 1 (scalar) fast path."""
    if acc.shape[0] == 1:
        return acc + jnp.sum(contrib, axis=0, keepdims=True)
    return acc.at[g].add(contrib)


def masked_probs(probs, mask):
    """A masked-out tuple is exactly a p = 0 tuple for every UDA."""
    if mask is None:
        return probs
    return jnp.where(mask, probs, jnp.zeros_like(probs))


# ======================================================================
# protocol
# ======================================================================
class UDA:
    """Base grouped UDA.  Subclasses define init/update (or accumulate_full)
    /finalize; merge and the collective reductions default to the additive
    behaviour shared by every streaming UDA."""

    #: streaming UDAs accumulate block-by-block inside the canonical scan;
    #: non-streaming ones (MinMax) consume the full column at once.
    streaming: bool = True
    #: additive states merge by elementwise add (psum-able inside
    #: shard_map); non-additive ones (MinMax) must gather-fold instead.
    additive: bool = True
    #: a scalar UDA ignores group ids and keeps one global group (e.g. the
    #: exact global CF of the canonical query step).
    scalar: bool = False

    def init(self, max_groups: int, dtype=None):
        raise NotImplementedError

    def update(self, state, probs, values, gids):
        """Fold one block of (already masked) tuples into the state."""
        raise NotImplementedError

    def accumulate_full(self, state, probs, values, gids, max_groups):
        """Whole-column accumulate for non-streaming UDAs."""
        raise NotImplementedError

    def merge(self, a, b):
        """Combine two partial states; additive => psum-able."""
        return jax.tree.map(jnp.add, a, b)

    def reduce_data(self, state, axis_names):
        """Merge across the tuple-sharding mesh axes (inside shard_map)."""
        axis_names = tuple(axis_names)
        if not axis_names:
            return state
        return jax.tree.map(lambda x: jax.lax.psum(x, axis_names), state)

    def reduce_model(self, state, axis_name):
        """Reconcile model-axis replicas (tuples are replicated there)."""
        return jax.tree.map(lambda x: jax.lax.pmean(x, axis_name), state)

    def finalize(self, state):
        raise NotImplementedError

    #: per-tuple-row working-set width, used by the canonical block sizing.
    def row_budget(self) -> int:
        return 1


# ======================================================================
# AtLeastOne — group confidence (§VI row V)
# ======================================================================
class AtLeastOneState(NamedTuple):
    log_none: jnp.ndarray        # (G,) sum log(1-p) over accumulated tuples


class AtLeastOne(UDA):
    """P(at least one tuple present) per group: 1 - prod(1 - p)."""

    def init(self, max_groups: int, dtype=None) -> AtLeastOneState:
        return AtLeastOneState(
            jnp.zeros((max_groups,), dtype or default_float()))

    def update(self, state, probs, values, gids) -> AtLeastOneState:
        return AtLeastOneState(
            _scatter_add(state.log_none, gids, jnp.log1p(-probs)))

    def finalize(self, state):
        return 1.0 - jnp.exp(state.log_none)


# ======================================================================
# SumNormal — (mean, variance) terms (§V-C.3, with the variance erratum fix)
# ======================================================================
class NormalState(NamedTuple):
    terms: jnp.ndarray           # (G, 2) = (sum v p, sum v^2 p (1-p))


class SumNormal(UDA):
    def init(self, max_groups: int, dtype=None) -> NormalState:
        return NormalState(jnp.zeros((max_groups, 2),
                                     dtype or default_float()))

    def update(self, state, probs, values, gids) -> NormalState:
        mu_t = values * probs
        var_t = values * values * probs * (1.0 - probs)
        return NormalState(_scatter_add(state.terms, gids,
                                        jnp.stack([mu_t, var_t], axis=-1)))

    def finalize(self, state):
        return state.terms[:, 0], state.terms[:, 1]


# ======================================================================
# SumCumulants — moment terms for the Lindsay gamma mixture (§V-C.3)
# ======================================================================
class CumulantState(NamedTuple):
    terms: jnp.ndarray           # (G, orders) partial cumulant sums


class SumCumulants(UDA):
    """s_j[g] = sum_{i in g} v_i^j kappa_j(p_i), j = 1..orders."""

    def __init__(self, orders: int = 8):
        assert orders <= MAX_ORDER
        self.orders = int(orders)

    def init(self, max_groups: int, dtype=None) -> CumulantState:
        return CumulantState(jnp.zeros((max_groups, self.orders),
                                       dtype or default_float()))

    def update(self, state, probs, values, gids) -> CumulantState:
        dtype = probs.dtype
        # deg(kappa_j) = j <= orders, so the coefficient table and power
        # basis truncate exactly at degree `orders` — the dropped
        # high-degree columns are identically zero.
        table = jnp.asarray(
            _bernoulli_cumulant_polys()[1:self.orders + 1,
                                        :self.orders + 1], dtype)
        powers = probs[None, :] ** jnp.arange(self.orders + 1,
                                              dtype=dtype)[:, None]
        kappas = table @ powers                         # (orders, B)
        vpow = values[None, :] ** jnp.arange(1, self.orders + 1,
                                             dtype=dtype)[:, None]
        return CumulantState(_scatter_add(state.terms, gids,
                                          (kappas * vpow).T))

    def accumulate_full(self, state, probs, values, gids, max_groups,
                        use_kernel: bool | None = None,
                        operands=None) -> CumulantState:
        """Whole-column accumulate, dispatching to the fused one-pass
        scalar kernel (max_groups == 1) or the (G, O)-tiled grouped kernel
        (:mod:`repro.kernels.group_cumulants`); kernels/ops.py falls back
        to the segment-sum oracle for small or non-f32 inputs, and the
        kernels run in interpret mode on CPU backends.  ``operands`` are
        pre-sorted grouped-kernel columns (ignored on the scalar/oracle
        paths)."""
        from ..kernels import ops as kops
        if max_groups == 1:
            sums = kops.cumulant_sums(probs, values, orders=self.orders,
                                      use_kernel=use_kernel)
            return CumulantState(state.terms + sums[None])
        if gids is None:
            gids = jnp.zeros(probs.shape, jnp.int32)
        sums = kops.group_cumulant_sums(probs, values, gids, max_groups,
                                        orders=self.orders,
                                        use_kernel=use_kernel,
                                        operands=operands)
        return CumulantState(state.terms + sums)

    def finalize(self, state):
        return state.terms

    def row_budget(self) -> int:
        return self.orders + 1


# ======================================================================
# SumCF — exact SUM/COUNT via the log characteristic function (§V-A/C)
# ======================================================================
class CFState(NamedTuple):
    log_abs: jnp.ndarray         # (G, F_loc)
    angle: jnp.ndarray           # (G, F_loc)


class SumCF(UDA):
    """log Q(w^k) = sum_i log((1-p_i) + p_i w^{k v_i}), w = e^{2 pi i / N}.

    ``num_freq`` (= max_sum + 1) is the static distribution capacity.  For
    model-axis frequency sharding, ``freq_cnt`` frequencies starting at
    ``freq_lo`` are accumulated locally (``freq_lo`` may be a traced
    ``axis_index`` expression inside shard_map); ``reduce_model``
    reassembles the slices with one tiled all-gather.
    """

    def __init__(self, num_freq: int, freq_lo=0, freq_cnt: int | None = None):
        self.num_freq = int(num_freq)
        self.freq_lo = freq_lo
        self.freq_cnt = int(freq_cnt) if freq_cnt is not None else self.num_freq

    def init(self, max_groups: int, dtype=None) -> CFState:
        z = jnp.zeros((max_groups, self.freq_cnt), dtype or default_float())
        return CFState(z, z)

    def accumulate_full(self, state, probs, values, gids, max_groups,
                        use_kernel: bool | None = None,
                        operands=None) -> CFState:
        """Whole-column accumulate, dispatching to the (G, F)-tiled Pallas
        kernel (:mod:`repro.kernels.group_cf`) when eligible; the pure-JAX
        oracle handles small inputs and non-f32 dtypes, and the kernel
        itself runs in interpret mode on CPU backends.  Requires
        integer-valued ``values``; ``freq_lo`` may be a traced model-axis
        window (the kernel reads it from SMEM at run time).  ``operands``
        are pre-sorted kernel columns (:func:`cf_chunk_operands`) so the
        frequency-slab loop hoists the argsort above the slabs.
        """
        from ..kernels import ops as kops
        if max_groups == 1 and use_kernel and isinstance(self.freq_lo, int) \
                and self.freq_lo == 0 and self.freq_cnt == self.num_freq:
            la, an = kops.logcf(probs, values, self.num_freq)
            return CFState(state.log_abs + la[None], state.angle + an[None])
        if gids is None:
            gids = jnp.zeros(probs.shape, jnp.int32)
        la, an = kops.group_logcf(probs, values, gids, max_groups,
                                  self.num_freq, freq_lo=self.freq_lo,
                                  freq_cnt=self.freq_cnt,
                                  use_kernel=use_kernel, operands=operands)
        return CFState(state.log_abs + la, state.angle + an)

    def update(self, state, probs, values, gids) -> CFState:
        dtype = probs.dtype
        k = self.freq_lo + jnp.arange(self.freq_cnt, dtype=dtype)
        # (B, F_loc) phase tile — the one large live intermediate of the
        # canonical loop; mod num_freq keeps theta exact at large k*v.
        phase = (values[:, None] * k[None, :]) % self.num_freq
        theta = (2.0 * math.pi / self.num_freq) * phase
        q = 1.0 - probs[:, None]
        re = q + probs[:, None] * jnp.cos(theta)
        im = probs[:, None] * jnp.sin(theta)
        la = 0.5 * jnp.log(jnp.maximum(re * re + im * im, _tiny(dtype)))
        an = jnp.arctan2(im, re)
        return CFState(_scatter_add(state.log_abs, gids, la),
                       _scatter_add(state.angle, gids, an))

    def reduce_model(self, state, axis_name):
        return CFState(
            jax.lax.all_gather(state.log_abs, axis_name, axis=-1, tiled=True),
            jax.lax.all_gather(state.angle, axis_name, axis=-1, tiled=True))

    def finalize(self, state):
        """(G, F) summed log CF -> (G, F) coefficient rows, one batched FFT."""
        q = jnp.exp(state.log_abs) * jax.lax.complex(jnp.cos(state.angle),
                                                     jnp.sin(state.angle))
        coeffs = jnp.fft.fft(q, axis=-1).real / state.log_abs.shape[-1]
        return jnp.clip(coeffs, 0.0, None)

    def row_budget(self) -> int:
        return self.freq_cnt


def CountCF(capacity: int) -> SumCF:
    """COUNT = SUM of T_COUNT-translated all-ones values (§IV-F step 1)."""
    return SumCF(capacity + 1)


# ======================================================================
# MinMax — grouped top-kappa (value, survival) lists (§V-B, §VII-C)
# ======================================================================
class MinMaxState(NamedTuple):
    values: jnp.ndarray          # (G, kappa) sign-folded values, sorted, pad +inf
    log_none: jnp.ndarray        # (G, kappa) sum log(1-p) of tuples at value
    tail_log_none: jnp.ndarray   # (G,) log prod(1-p) over *evicted* values
    total_log_none: jnp.ndarray  # (G,) log prod(1-p) over all tuples seen


class MinMax(UDA):
    """The paper's ordered (value, AtLeastOne) list with capacity kappa, as
    fixed-shape (G, kappa) buffers: JAX needs static shapes, so the linked
    list becomes a sorted top-kappa buffer merged by row-wise sort + run
    folding.  ``sign`` = +1 for MIN (keep smallest), -1 for MAX (values
    stored negated so the merge logic is shared).

    Not additive: ``reduce_data`` all-gathers shard states and folds
    ``merge`` over the (static) shard count instead of psum-ing.
    """

    streaming = False
    additive = False

    def __init__(self, kappa: int = 64, sign: float = 1.0):
        self.kappa = int(kappa)
        self.sign = float(sign)

    def init(self, max_groups: int, dtype=None) -> MinMaxState:
        dtype = dtype or default_float()
        return MinMaxState(
            jnp.full((max_groups, self.kappa), jnp.inf, dtype),
            jnp.zeros((max_groups, self.kappa), dtype),
            jnp.zeros((max_groups,), dtype),
            jnp.zeros((max_groups,), dtype))

    def accumulate_full(self, state, probs, values, gids, max_groups):
        """``state=None`` means "fresh init" (the canonical loop's hint):
        the constructed chunk buffer is returned directly."""
        dtype = probs.dtype if state is None else state.values.dtype
        p = jnp.asarray(probs, dtype)
        v = jnp.asarray(values, dtype) * self.sign
        v = jnp.where(p > 0, v, jnp.inf)     # masked / p=0 tuples never matter
        logq = jnp.log1p(-p)
        n = p.shape[0]
        # Lexsort rows by (group, folded value): ONE stable two-key
        # lax.sort carrying the payload column — the same permutation the
        # old argsort(v)-then-argsort(gids) pair produced (stable lexsort
        # is unique), without the second sort and the three gathers.  A
        # combined float key would lose value bits to ULP at large group
        # ids, hence two keys.
        gs, vs, lqs = jax.lax.sort((gids, v, logq), dimension=0,
                                   is_stable=True, num_keys=2)

        # Fold duplicate (group, value) runs.
        head = jnp.concatenate([jnp.ones((1,), bool),
                                (gs[1:] != gs[:-1]) | (vs[1:] != vs[:-1])])
        seg = jnp.cumsum(head) - 1
        run_idx = jnp.arange(n)
        exists = run_idx < seg[-1] + 1
        run_lq = jax.ops.segment_sum(lqs, seg, num_segments=n)
        run_v = jax.ops.segment_min(vs, seg, num_segments=n)   # +inf if empty
        run_g = jnp.clip(jax.ops.segment_max(gs, seg, num_segments=n),
                         0, max_groups - 1)
        run_g = jnp.where(exists, run_g, max_groups - 1)

        # Rank of each run within its group = run index - group's first run.
        grp_first = jax.ops.segment_min(jnp.where(exists, run_idx, n), run_g,
                                        num_segments=max_groups)
        rank = run_idx - grp_first[run_g]

        keep = exists & jnp.isfinite(run_v) & (rank < self.kappa)
        col = jnp.where(keep, rank, self.kappa)      # out-of-range -> dropped
        chunk_v = jnp.full((max_groups, self.kappa), jnp.inf, dtype) \
            .at[run_g, col].set(run_v, mode="drop")
        chunk_lq = jnp.zeros((max_groups, self.kappa), dtype) \
            .at[run_g, col].add(run_lq, mode="drop")
        evicted = exists & jnp.isfinite(run_v) & (rank >= self.kappa)
        chunk_tail = jnp.zeros((max_groups,), dtype) \
            .at[run_g].add(jnp.where(evicted, run_lq, 0.0))
        chunk_total = jnp.zeros((max_groups,), dtype).at[gids].add(logq)
        chunk = MinMaxState(chunk_v, chunk_lq, chunk_tail, chunk_total)
        # A fresh-init state needs no merge: the chunk buffer already
        # satisfies the invariant (sorted, distinct, inf-padded) and
        # merge(init, x) == x bitwise — the canonical chunked path calls
        # this once per chunk with a fresh state, so skipping the merge
        # halves the chunked MinMax merge count.
        return chunk if state is None else self.merge(state, chunk)

    def merge(self, a: MinMaxState, b: MinMaxState) -> MinMaxState:
        """Bitonic two-way merge + in-network run fold + top-k truncation,
        sort-free: both inputs keep their rows sorted (the state
        invariant), so ascending(a) ++ descending(b) is bitonic and
        log2(2k) elementwise compare-exchange stages finish the merge —
        XLA CPU row sorts serialise and were the hot spot of the
        chunked/tree merge path.

        A value present in both inputs lands in two adjacent slots of the
        sorted 2k buffer; the run fold collapses each equal-value run
        into its head slot (log_none sums — the masses telescope exactly:
        exp(prefix) (1-Q_a) + exp(prefix) Q_a (1-Q_b) == the folded-run
        mass) BEFORE the top-k truncation, so duplicates never compete
        for the kappa capacity and the §V-B.2 truncation tail stays tight
        under heavy duplication — at one segment-sum on top of the
        bitonic stages."""
        k = self.kappa
        pw = 1 << (k - 1).bit_length()       # bitonic needs a 2^m half
        inf_pad = ((0, 0), (0, pw - k))
        v = jnp.concatenate(
            [jnp.pad(a.values, inf_pad, constant_values=jnp.inf),
             jnp.pad(b.values, inf_pad, constant_values=jnp.inf)[:, ::-1]],
            axis=1)
        lq = jnp.concatenate([jnp.pad(a.log_none, inf_pad),
                              jnp.pad(b.log_none, inf_pad)[:, ::-1]], axis=1)
        g = v.shape[0]
        s = pw
        while s >= 1:
            vr = v.reshape(g, -1, 2, s)
            lr = lq.reshape(g, -1, 2, s)
            swap = vr[:, :, 0] > vr[:, :, 1]
            v = jnp.stack([jnp.where(swap, vr[:, :, 1], vr[:, :, 0]),
                           jnp.where(swap, vr[:, :, 0], vr[:, :, 1])],
                          axis=2).reshape(g, -1)
            lq = jnp.stack([jnp.where(swap, lr[:, :, 1], lr[:, :, 0]),
                            jnp.where(swap, lr[:, :, 0], lr[:, :, 1])],
                           axis=2).reshape(g, -1)
            s //= 2
        # Run fold, scatter-free (XLA CPU scatters serialise): both inputs
        # hold DISTINCT values (the state invariant this fold maintains),
        # so an equal-value run in the sorted buffer spans at most TWO
        # slots and its log_none total is one pairwise add; heads then
        # compact to their run index — dense, still sorted — with a
        # batched binary search + gather.  (Empty +inf slots form one
        # trailing run; their log_none is 0, so any fold of it is exact.)
        w = v.shape[1]
        finite = jnp.isfinite(v)
        dup = jnp.concatenate([jnp.zeros_like(finite[:, :1]),
                               v[:, 1:] == v[:, :-1]], axis=1)
        head = ~dup
        absorb = jnp.concatenate([dup[:, 1:],
                                  jnp.zeros_like(dup[:, :1])], axis=1)
        lq_next = jnp.concatenate([lq[:, 1:],
                                   jnp.zeros_like(lq[:, :1])], axis=1)
        tot = lq + jnp.where(absorb, lq_next, 0.0)   # per-run log_none
        run = jnp.cumsum(head, axis=1) - 1           # run index per slot
        evicted = jnp.where(head & finite & (run >= k), tot, 0.0)
        # Branchless batched lower_bound over the k KEPT output slots only
        # (truncation discards the rest): src[g, j] = head slot of run j
        # (first position with run >= j).  XLA CPU gathers dominate this
        # epilogue, so: probe width k not 2k, one complex gather fetches
        # (v, tot) together, and run-existence is a slice compare instead
        # of another gather.
        idx = jnp.arange(k)
        pos = jnp.full((g, k), -1, jnp.int32)        # last slot with run < j
        step = w
        while step > 1:
            step //= 2
            cand = jnp.minimum(pos + step, w - 1)
            less = jnp.take_along_axis(run, cand, axis=1) < idx[None, :]
            pos = jnp.where(less, cand, pos)
        src = jnp.clip(pos + 1, 0, w - 1)            # head slot of run j
        ok = idx[None, :] <= run[:, -1:]             # run j exists
        got = jnp.take_along_axis(jax.lax.complex(v, tot), src, axis=1)
        v = jnp.where(ok, got.real, jnp.inf)
        lq = jnp.where(ok, got.imag, 0.0)
        return MinMaxState(v, lq,
                           a.tail_log_none + b.tail_log_none + evicted.sum(1),
                           a.total_log_none + b.total_log_none)

    def reduce_data(self, state, axis_names):
        axis_names = tuple(axis_names)
        if not axis_names:
            return state
        gathered = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis_names, axis=0, tiled=False),
            state)
        shards = jax.tree.leaves(gathered)[0].shape[0]   # static
        out = jax.tree.map(lambda x: x[0], gathered)
        for s in range(1, shards):
            out = self.merge(out, jax.tree.map(lambda x, s=s: x[s], gathered))
        return out

    def reduce_model(self, state, axis_name):
        return state     # tuples are replicated over the model axis

    def finalize(self, state: MinMaxState):
        """P(agg = v_j) = prod_{v_l better} Q_l * (1 - Q_j)  (§V-B.1), with
        Q_l = prod over tuples at value v_l of (1 - p).  Returns per-group
        (values, masses, p_tail): values un-folded (true MAX values for
        sign = -1); p_tail = P(aggregate beyond the kept support) — evicted
        values *or* the empty world (the paper's X^inf term plus its §V-B.2
        truncation remainder)."""
        finite = jnp.isfinite(state.values)
        lq = jnp.where(finite, state.log_none, 0.0)
        prefix = jnp.concatenate(
            [jnp.zeros_like(lq[:, :1]), jnp.cumsum(lq, axis=1)[:, :-1]],
            axis=1)
        mass = jnp.exp(prefix) * (1.0 - jnp.exp(lq)) * finite
        p_tail = jnp.exp(jnp.sum(lq, axis=1))
        return state.values * self.sign, mass, p_tail

    def p_empty(self, state: MinMaxState):
        """Exact P(aggregate undefined) = prod over all tuples of (1-p)."""
        return jnp.exp(state.total_log_none)

    def tail_mass(self, state: MinMaxState):
        """Per-group §V-B.2 truncation mass: the probability the exact
        aggregate lies STRICTLY beyond the kept kappa-support (evicted
        values present while every kept value is absent) — i.e. the
        ``p_tail`` of :meth:`finalize` minus its empty-world component.
        ``tail_log_none`` accumulates log(1-p) over exactly the evicted
        tuples, so the mass is

            prod_kept Q_j * (1 - prod_evicted (1-p))

        and is exactly 0 when kappa covered every distinct value (nothing
        evicted => tail_log_none = 0).  This is the quantity a caller (or
        the retry controller) compares against a tolerance to decide
        whether kappa must escalate."""
        finite = jnp.isfinite(state.values)
        lq = jnp.where(finite, state.log_none, 0.0)
        return jnp.exp(jnp.sum(lq, axis=1)) * -jnp.expm1(state.tail_log_none)


# ======================================================================
# registry
# ======================================================================
REGISTRY = {
    "atleastone": AtLeastOne,
    "normal": SumNormal,
    "cumulants": SumCumulants,
    "cf": SumCF,
    "count_cf": CountCF,
    "min": lambda **kw: MinMax(sign=1.0, **kw),
    "max": lambda **kw: MinMax(sign=-1.0, **kw),
}


def make(name: str, **kwargs) -> UDA:
    return REGISTRY[name](**kwargs)


# ======================================================================
# the canonical accumulation loop
# ======================================================================
def _block_size(udas, block: int, n: int) -> int:
    budget = max([1] + [u.row_budget() for u in udas.values()])
    bsz = max(_BLOCK_FLOOR, min(block, _ELEM_BUDGET // max(1, budget)))
    # Never pad past the column: a short column (e.g. one canonical chunk
    # of a chunked accumulate) runs as a single right-sized block instead
    # of being zero-padded up to the full block budget.  The floor keeps
    # bsz positive for empty columns (the scan then runs zero steps and
    # returns the init states).
    return min(bsz, max(_BLOCK_FLOOR, -(-n // _BLOCK_FLOOR) * _BLOCK_FLOOR))


def _groups_of(u: UDA, max_groups: int) -> int:
    return 1 if u.scalar else max_groups


def _use_pallas(kernel: str) -> bool:
    """The ONE backend half of the kernel-dispatch predicate, shared by
    :func:`accumulate` and :func:`cf_chunk_operands` so the operand hoist
    can never diverge from the dispatch it feeds."""
    return kernel == "pallas" or (kernel == "auto"
                                  and jax.default_backend() == "tpu")


def _integral_dtype(dtype) -> bool:
    """Does this source dtype carry exact integers (CF-kernel-eligible)?"""
    return jnp.issubdtype(dtype, jnp.integer) or dtype == jnp.bool_


def _kernel_eligible(u: UDA, max_groups: int, probs, values_integral: bool) \
        -> bool:
    """CF / cumulant accumulations can run on the Pallas kernels — only
    under the same guards as kernels/ops.py (f32, enough tuples to amortise
    block padding), and for CF only with integer-typed values and a
    frequency grid small enough for the kernel's exact int32 split-modmult
    phase (``freq_lo`` itself may be traced — the kernel reads the window
    start from SMEM).  Grouped CF states dispatch to the (G, F)-tiled
    group_cf kernel; grouped cumulants to the (G, O)-tiled group_cumulants
    kernel."""
    from ..kernels import ops as kops
    if probs.dtype != jnp.float32 or probs.shape[0] < kops.MIN_KERNEL_TUPLES:
        return False
    if isinstance(u, SumCF):
        return values_integral and u.num_freq <= kops.MAX_KERNEL_FREQ
    return isinstance(u, SumCumulants)


def _kernel_accumulate(u: UDA, state, probs, values, gids, max_groups,
                       operands=None):
    g = _groups_of(u, max_groups)
    return u.accumulate_full(state, probs, values,
                             None if g == 1 else gids, g,
                             use_kernel=True, operands=operands)


#: Scan bodies by UDA configuration (bounded LRU): see _scan_body.
_SCAN_BODIES: collections.OrderedDict = collections.OrderedDict()
_PLAIN = (int, float, bool, str, type(None))


def _scan_body(scan_udas, val_index):
    """The blocked scan's body for this set of streaming UDAs.

    ``lax.scan`` caches its trace on the body function object, and an
    eager scan compiles whatever jaxpr that trace gives.  A body closure
    built anew per call therefore re-traces and re-compiles the same scan
    at every eager accumulate.  A UDA's ``update`` depends only on its
    type and attributes, so equal configurations share one body object —
    the same program as before, compiled once.  UDAs with any attribute
    that is not a plain Python scalar (a traced ``freq_lo``) get a fresh
    body, as before."""
    attrs = [vars(u).items() for u in scan_udas.values()]
    key = None
    if all(isinstance(v, _PLAIN) for a in attrs for _, v in a):
        key = tuple((name, type(u), val_index[name],
                     tuple(sorted((k, type(v), v) for k, v in a)))
                    for (name, u), a in zip(scan_udas.items(), attrs))
        body = _SCAN_BODIES.get(key)
        if body is not None:
            _SCAN_BODIES.move_to_end(key)
            return body

    def body(carry, chunk):
        pc, gc, vc = chunk
        return {name: u.update(carry[name], pc, vc[val_index[name]], gc)
                for name, u in scan_udas.items()}, None

    if key is not None:
        _SCAN_BODIES[key] = body
        if len(_SCAN_BODIES) > 256:
            _SCAN_BODIES.popitem(last=False)
    return body


def accumulate(udas, probs, values=None, gids=None, *, max_groups: int = 1,
               states=None, block: int = 8192, kernel: str = "auto",
               cf_operands=None):
    """Accumulate every UDA in ``udas`` over one column of tuples.

    udas:    {name: UDA}.  Streaming UDAs share ONE blocked ``lax.scan``
             (each tuple block is read once and fed to every update);
             non-streaming UDAs (MinMax) consume the full column.
    probs:   (n,) tuple probabilities, already masked (invalid rows p = 0).
    values:  (n,) array shared by all UDAs, or {name: (n,) array} for
             per-aggregate value columns; None means all-ones (COUNT).
    gids:    (n,) int group ids in [0, max_groups); None = all group 0.
    states:  optional prior states to continue from (default: init).
    kernel:  'auto' | 'pallas' | 'xla' — 'auto' dispatches eligible
             accumulations (scalar and grouped CF / cumulants) to the
             Pallas kernels on TPU backends.
    cf_operands: optional {name: operands} pre-sorted grouped-CF kernel
             columns for this call's tuples (see :func:`cf_chunk_operands`)
             — used only when the named UDA actually dispatches to the
             grouped kernel, ignored otherwise.

    Returns {name: state}.
    """
    probs = jnp.asarray(probs)
    dtype = probs.dtype
    n = probs.shape[0]
    gids_full = (jnp.zeros((n,), jnp.int32) if gids is None
                 else jnp.asarray(gids))

    # Normalise values to one array per UDA, deduplicated by identity so the
    # scan carries each distinct column once.
    if not isinstance(values, dict):
        values = {name: values for name in udas}
    ones = None
    # Convert each distinct source column exactly once, keyed on the
    # caller's object (alive in `values` for the whole call, so ids are
    # stable): a column shared by several UDAs keeps one scan-carried copy
    # even when the cast to the prob dtype would otherwise fork it.  The
    # pre-cast source rides along in `val_sources` — the exact-CF kernels
    # consume integer columns directly (a float32 round-trip would corrupt
    # values above 2^24).
    casts: dict = {}
    val_arrays, val_index, val_integral, val_sources = [], {}, [], []
    for name in udas:
        v = values.get(name)
        if v is None:
            if ones is None:
                ones = jnp.ones((n,), dtype)
            v = src = ones
            integral = True        # COUNT: all-ones
        else:
            if id(v) not in casts:
                s = jnp.asarray(v)
                casts[id(v)] = (
                    s.astype(dtype) if s.dtype != dtype else s,
                    _integral_dtype(s.dtype), s)
            v, integral, src = casts[id(v)]
        for i, existing in enumerate(val_arrays):
            if existing is v:
                val_index[name] = i
                break
        else:
            val_index[name] = len(val_arrays)
            val_arrays.append(v)
            val_integral.append(integral)
            val_sources.append(src)

    if states is None:
        states = {}
    states = dict(states)
    fresh = {name for name in udas if name not in states}
    for name in fresh:
        # Fresh non-streaming states stay unmaterialized: accumulate_full
        # receives None and skips the no-op merge with the init buffer.
        if udas[name].streaming:
            states[name] = udas[name].init(
                _groups_of(udas[name], max_groups), dtype)

    use_pallas = _use_pallas(kernel)

    scan_udas, full_udas, kernel_udas = {}, {}, {}
    for name, u in udas.items():
        if not u.streaming:
            full_udas[name] = u
        elif use_pallas and _kernel_eligible(
                u, max_groups, probs, val_integral[val_index[name]]):
            kernel_udas[name] = u
        else:
            scan_udas[name] = u

    for name, u in full_udas.items():
        g_u = jnp.zeros_like(gids_full) if u.scalar else gids_full
        # A fresh init state is passed as None so non-streaming UDAs can
        # skip the no-op merge with it (MinMax: merge(init, x) == x).
        states[name] = u.accumulate_full(
            None if name in fresh else states[name], probs,
            val_arrays[val_index[name]], g_u, _groups_of(u, max_groups))
    for name, u in kernel_udas.items():
        # CF kernels take the pre-cast (integer) source; the cumulant
        # kernel computes float value powers and takes the cast column.
        i = val_index[name]
        vals = val_sources[i] if isinstance(u, SumCF) else val_arrays[i]
        ops_u = cf_operands.get(name) if cf_operands else None
        states[name] = _kernel_accumulate(u, states[name], probs, vals,
                                          gids_full, max_groups,
                                          operands=ops_u)
    if not scan_udas:
        return states

    bsz = _block_size(scan_udas, block, n)
    nfull = ((n + bsz - 1) // bsz) * bsz
    pad = nfull - n
    p = jnp.pad(probs, (0, pad))                    # p = 0: no contribution
    g = jnp.pad(gids_full, (0, pad), constant_values=max_groups - 1)
    vs = tuple(jnp.pad(v, (0, pad)) for v in val_arrays)

    body = _scan_body(scan_udas, val_index)
    init = {name: states[name] for name in scan_udas}
    chunks = (p.reshape(-1, bsz), g.reshape(-1, bsz),
              tuple(v.reshape(-1, bsz) for v in vs))
    from ..models.runmode import unroll_mode
    if unroll_mode():
        carry = init
        for i in range(nfull // bsz):
            carry, _ = body(carry, jax.tree.map(lambda c: c[i], chunks))
    else:
        carry, _ = jax.lax.scan(body, init, chunks)
    states.update(carry)
    return states


def merge(udas, a, b):
    """Merge two state dicts UDA-wise (any merge tree gives the same result)."""
    return {name: u.merge(a[name], b[name]) for name, u in udas.items()}


def tree_fold(u: UDA, parts):
    """Fold partial states with ``u.merge`` in the ONE canonical tree
    shape: a balanced pairwise tree over the largest power-of-two prefix,
    then a sequential left fold of the tail leaves.

    For a power-of-two leaf count this is exactly the balanced pairwise
    tree; the pow2-base + sequential-tail form extends the fixed shape to
    ANY chunk count.  The tree depends only on the leaf count — never on
    how leaves are distributed over shards — which is the
    bit-reproducibility contract of :func:`accumulate_chunked`: the
    sharded frontend computes every canonical chunk's state on exactly one
    shard, gathers all C chunk states, and every shard finishes this SAME
    tree (``db.distributed.allgather_merge``), so any shard count — power
    of two or not — reproduces the single-device fold bit for bit.
    """
    parts = list(parts)
    if not parts:
        raise ValueError("tree_fold needs at least one partial state")
    base_len = 1 << (len(parts).bit_length() - 1)   # largest pow2 <= len
    base, tail = parts[:base_len], parts[base_len:]
    while len(base) > 1:
        base = [u.merge(base[i], base[i + 1])
                for i in range(0, len(base), 2)]
    out = base[0]
    for t in tail:
        out = u.merge(out, t)
    return out


def accumulate_chunk_states(udas, probs, values=None, gids=None, *,
                            max_groups: int = 1, num_chunks: int = 8,
                            block: int = 8192, kernel: str = "auto",
                            cf_operands=None) -> list:
    """Per-canonical-chunk partial states: the Accumulate half of
    :func:`accumulate_chunked`, without the fold.

    The tuple column is split into ``num_chunks`` contiguous equal chunks
    (zero-padded with p = 0 rows to a chunk multiple); each chunk runs the
    ONE canonical loop (:func:`accumulate`) independently.  Returns the
    list of per-chunk ``{name: state}`` dicts in chunk order — the sharded
    frontend gathers these across shards so every shard can finish the
    identical :func:`tree_fold`.

    ``cf_operands``: optional ``{name: [per-chunk operands]}`` pre-sorted
    grouped-CF kernel operands (:func:`cf_chunk_operands`) so the exact-CF
    frequency-slab loop pays the argsort once, not once per slab.
    """
    probs = jnp.asarray(probs)
    n = probs.shape[0]
    csz = -(-n // num_chunks)
    pad = csz * num_chunks - n
    if pad:
        probs = jnp.pad(probs, (0, pad))
    if gids is not None and pad:
        gids = jnp.pad(jnp.asarray(gids), (0, pad),
                       constant_values=max_groups - 1)
    if not isinstance(values, dict):
        values = {name: values for name in udas}
    # Pad each distinct source column once so aggregates sharing a column
    # keep sharing it (accumulate dedups value columns by identity).
    cols: dict = {}
    cache: dict = {}
    for name in udas:
        v = values.get(name)
        if v is None:
            cols[name] = None
            continue
        if id(v) not in cache:
            a = jnp.asarray(v)
            cache[id(v)] = jnp.pad(a, (0, pad)) if pad else a
        cols[name] = cache[id(v)]
    parts = []
    for i in range(num_chunks):
        sl = slice(i * csz, (i + 1) * csz)
        ccache: dict = {}
        vals_i = {name: None if c is None else ccache.setdefault(id(c), c[sl])
                  for name, c in cols.items()}
        ops_i = ({name: per_chunk[i]
                  for name, per_chunk in cf_operands.items()}
                 if cf_operands else None)
        parts.append(accumulate(udas, probs[sl], vals_i,
                                None if gids is None else gids[sl],
                                max_groups=max_groups, block=block,
                                kernel=kernel, cf_operands=ops_i))
    return parts


class ChunkStateAccumulator:
    """Cross-wave chunk-state collection: the out-of-core entry point of
    the canonical chunk contract.

    The streamed executor (``db/plans.py``) computes per-canonical-chunk
    partial states one WAVE at a time — each wave covers a set of chunk
    slots and yields their state dicts via :func:`accumulate_chunk_states`
    + a cross-shard gather.  This accumulator files each wave's states
    under their global canonical chunk ids, drops padding slots (ids at or
    past ``num_chunks`` — the shard-alignment and wave-alignment chunks,
    whose states are pure identities), and :meth:`fold` finishes the ONE
    fixed :func:`tree_fold` over exactly the ``num_chunks`` canonical
    leaves.  Because each chunk's state is computed from that chunk's rows
    alone and the fold tree depends only on the leaf count, the result is
    bit-identical to :func:`accumulate_chunked` on the resident table —
    for ANY wave schedule.
    """

    def __init__(self, udas: dict, num_chunks: int):
        self.udas = udas
        self.num_chunks = num_chunks
        self._chunks: list = [None] * num_chunks

    @property
    def filed(self) -> int:
        """Canonical chunks collected so far — the wave-resume checkpoint
        marker: a retried wave must only bring chunks not yet filed."""
        return sum(st is not None for st in self._chunks)

    def add_wave(self, chunk_ids, parts: list) -> None:
        """File one wave's per-chunk state dicts under their global
        canonical chunk ids (parallel lists; waves partition the slots, so
        each canonical chunk arrives exactly once)."""
        for g, st in zip(chunk_ids, parts):
            if g < self.num_chunks:
                assert self._chunks[g] is None, f"chunk {g} seen twice"
                self._chunks[g] = st

    def fold(self) -> dict:
        """The canonical fold over all collected chunks — call after the
        last wave."""
        missing = [g for g, st in enumerate(self._chunks) if st is None]
        assert not missing, f"canonical chunks never streamed: {missing}"
        return {name: tree_fold(u, [c[name] for c in self._chunks])
                for name, u in self.udas.items()}


def accumulate_chunked(udas, probs, values=None, gids=None, *,
                       max_groups: int = 1, num_chunks: int = 8,
                       block: int = 8192, kernel: str = "auto",
                       cf_operands=None):
    """Canonical chunk-grid Accumulate + tree Merge (the sharded-frontend
    accumulation semantics).

    :func:`accumulate_chunk_states` computes one partial state per
    contiguous chunk and the partials fold in the fixed pow2-base +
    sequential-tail tree of :func:`tree_fold`.  The plan compiler uses the
    same grid in every compile: on a mesh each shard computes the states
    of its contiguous chunk run and the cross-shard Merge
    (``db.distributed.allgather_merge``) gathers ALL chunk states and
    finishes the SAME tree — which is what makes
    ``compile_plan(root, mesh)`` outputs bit-identical to the
    single-device compile for ANY shard count.
    """
    if num_chunks <= 1:
        ops_0 = ({name: per_chunk[0]
                  for name, per_chunk in cf_operands.items()}
                 if cf_operands else None)
        return accumulate(udas, probs, values, gids, max_groups=max_groups,
                          block=block, kernel=kernel, cf_operands=ops_0)
    parts = accumulate_chunk_states(udas, probs, values, gids,
                                    max_groups=max_groups,
                                    num_chunks=num_chunks, block=block,
                                    kernel=kernel, cf_operands=cf_operands)
    return {name: tree_fold(u, [p[name] for p in parts])
            for name, u in udas.items()}


def cf_chunk_operands(num_freq: int, probs, values, gids, *,
                      max_groups: int, num_chunks: int,
                      kernel: str = "auto"):
    """Pre-sorted per-chunk grouped-CF kernel operands for an exact-CF
    aggregation, or None when the Pallas kernel would not be dispatched.

    The exact-CF frequency-slab loop re-runs :func:`accumulate` once per
    slab over the SAME tuples; the grouped kernel's argsort(gids) and
    split-modmult operand prep depend only on (values, gids, num_freq) —
    not on the slab window — so the planner calls this once per
    aggregation pass and threads the result through every slab's
    ``cf_operands``.  Mirrors the dispatch guards of :func:`accumulate`
    (backend, dtype, size, integrality); a None return means the caller
    should simply not pass operands (the scan/oracle paths sort nothing).
    """
    from ..kernels import ops as kops
    probs = jnp.asarray(probs)
    n = probs.shape[0]
    if n % num_chunks:
        return None            # planner columns divide the grid exactly
    csz = n // num_chunks
    if values is None:
        vals = jnp.ones((n,), probs.dtype)
        integral = True        # COUNT: all-ones
    else:
        vals = jnp.asarray(values)
        integral = _integral_dtype(vals.dtype)
    probe = SumCF(num_freq)    # static freq_lo=0: same verdict as any slab
    if not (_use_pallas(kernel)
            and _kernel_eligible(probe, max_groups, probs[:csz], integral)):
        return None
    g = (jnp.zeros((n,), jnp.int32) if gids is None
         else jnp.asarray(gids))
    return [kops.presort_group_operands(probs[i * csz:(i + 1) * csz],
                                        vals[i * csz:(i + 1) * csz],
                                        g[i * csz:(i + 1) * csz], num_freq)
            for i in range(num_chunks)]


def reduce_collective(udas, states, data_axes, model_axis=None):
    """The distributed Merge: one psum (or gather-fold) per UDA over the
    tuple-sharding axes, then model-axis reconciliation.  Call inside
    shard_map."""
    out = {}
    for name, u in udas.items():
        st = u.reduce_data(states[name], data_axes)
        if model_axis is not None:
            st = u.reduce_model(st, model_axis)
        out[name] = st
    return out


def finalize(udas, states):
    return {name: u.finalize(states[name]) for name, u in udas.items()}
