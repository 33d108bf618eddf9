"""Pallas TPU kernel: (G, F)-tiled grouped log-characteristic-function
accumulation — the grouped exact-CF hot path.

The scalar kernel (:mod:`repro.kernels.pb_cf`) accumulates ONE summed log CF
over all tuples; grouped exact SUM/COUNT (paper §VI row VI with the §V-A
exact PGF) needs G of them, one per group:

    log_abs[g, k] = sum_{i : gid_i = g} 0.5*log|q_i + p_i w^{k a_i}|^2
    angle[g, k]   = sum_{i : gid_i = g} arg (q_i + p_i w^{k a_i})

with w = exp(2 pi i / N).  A per-group loop over the scalar kernel would
re-stream the tuple column G times; this kernel streams it once per group
*block* and scatters each tuple's contribution to its group row in-register.

TPU mapping
-----------
grid = (G_blocks, F_blocks, T_blocks); the tuple axis is the innermost
reduction axis so each (GB, FB) output tile stays resident in VMEM while
tuple blocks stream through.  Per grid step the kernel materialises one
(FB, TB) phase/log-abs/angle tile (identical math to pb_cf.py) and scatters
it to the (GB, FB) accumulators with an in-kernel segment mask:

    M[r, t]   = 1 if gid_t == gi*GB + r else 0          (GB, TB)
    acc[r, f] += sum_t M[r, t] * tile[f, t]             one MXU matmul

i.e. the scatter is a (GB, TB) x (TB, FB) matmul contracting the tuple
axis — exact (M is 0/1) and MXU-shaped, so the scatter costs 2*GB flops per
(tuple, frequency) pair on top of the ~46 VPU flops of the phase tile.

Tuples are pre-sorted by group id in the wrapper, and each tuple block's
[min gid, max gid] range rides along in SMEM: a (gi, ti) step whose group
rows don't intersect the block's range skips all vector work, so with
sorted inputs each tuple block is materialised O(1) times instead of
G_blocks times and total work stays ~n*F, not ~n*F*G/GB.

VMEM budget (defaults gb=8, fb=256, tb=512, f32):
    phase/log-abs/angle tiles  3 x (FB, TB) x 4B  = 1.5 MB
    segment mask               (GB, TB) x 4B      = 16 KB
    accumulators               2 x (GB, FB) x 4B  = 16 KB
well inside the ~16 MB v5e VMEM with double-buffering headroom.  All lane
dims are multiples of 128; GB is a multiple of the f32 sublane (8).

Frequency slabs: ``freq_lo``/``freq_cnt`` select a [freq_lo, freq_lo+cnt)
slice of the N-point DFT grid so callers can chunk the (G, F) state against
a memory budget (the planner's multi-pass slab path: each slab is one
kernel launch + one additive merge, see db/plans.py).  Phase exactness uses
the same split-modmult as pb_cf.py: k = k_hi*2^S + k_lo with
a2 = (a << S) mod N needs k_lo*a < 2^(S + b) and k_hi*a2 < 2^(2b - S)
(b = bit length of N-1) both below 2^31, which S = b//2 + 1 satisfies
exactly for N <= 2^20 — ``pb_cf.split_modmult_operands`` (shared with the
scalar kernel) asserts that bound and the ops.py / uda.py dispatch guards
route larger grids to the pure-JAX path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The segment-mask scatter must not round its f32 operand: Mosaic's
# default matmul precision feeds the MXU bf16 (2^-9 relative per term).
_EXACT = jax.lax.Precision.HIGHEST

from . import pb_cf


def _group_logcf_kernel(gmin_ref, gmax_ref, flo_ref, p_ref, a_ref, a2_ref,
                        g_ref, la_ref, an_ref, *, num_freq: int,
                        shift: int, gb: int, fb: int, tb: int):
    gi = pl.program_id(0)
    fi = pl.program_id(1)
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        la_ref[...] = jnp.zeros_like(la_ref)
        an_ref[...] = jnp.zeros_like(an_ref)

    # Skip the whole tile when this tuple block (sorted by gid) cannot touch
    # any of this block's group rows [gi*gb, gi*gb + gb).
    row_lo = gi * gb
    hit = (gmin_ref[ti] < row_lo + gb) & (gmax_ref[ti] >= row_lo)

    @pl.when(hit)
    def _acc():
        n = num_freq
        # Global frequency index for every lane of this tile: (FB, 1).
        # freq_lo rides in as a run-time scalar (SMEM) so a traced
        # model-axis window parameterises ONE compiled kernel.
        k = flo_ref[0] + fi * fb \
            + jax.lax.broadcasted_iota(jnp.int32, (fb, 1), 0)
        k = jnp.minimum(k, n - 1)       # freq padding: extra lanes discarded
        k_hi = k >> shift
        k_lo = k & ((1 << shift) - 1)

        a = a_ref[...]                  # (1, TB) int32, already mod N
        a2 = a2_ref[...]                # (1, TB) int32, (a << shift) mod N
        p = p_ref[...]                  # (1, TB)

        # (FB, TB) exact phase: ((k_hi*a2) mod N + (k_lo*a) mod N) mod N
        phase = ((k_hi * a2) % n + (k_lo * a) % n) % n
        theta = phase.astype(p.dtype) * (2.0 * math.pi / n)

        q = 1.0 - p
        re = q + p * jnp.cos(theta)     # (FB, TB)
        im = p * jnp.sin(theta)
        tiny = jnp.asarray(1e-30 if p.dtype == jnp.float32 else 1e-300,
                           p.dtype)
        la = 0.5 * jnp.log(jnp.maximum(re * re + im * im, tiny))
        an = pb_cf.atan2(im, re)

        # Segment-mask scatter: rows (GB, 1) vs gids (1, TB) -> (GB, TB)
        # 0/1 mask; one MXU matmul contracts the tuple axis into (GB, FB).
        rows = row_lo + jax.lax.broadcasted_iota(jnp.int32, (gb, 1), 0)
        m = (g_ref[...] == rows).astype(p.dtype)
        dims = (((1,), (1,)), ((), ()))
        la_ref[...] += jax.lax.dot_general(m, la, dims,
                                           preferred_element_type=p.dtype,
                                           precision=_EXACT)
        an_ref[...] += jax.lax.dot_general(m, an, dims,
                                           preferred_element_type=p.dtype,
                                           precision=_EXACT)


def presort_operands(probs: jnp.ndarray, values: jnp.ndarray,
                     gids: jnp.ndarray, num_freq: int):
    """The argsort(gids) + split-modmult operand prep of
    :func:`group_logcf`, hoisted so callers can run it ONCE and reuse it
    across frequency slabs (the prep depends only on (values, gids,
    num_freq), never on the slab window; each slab is a separately
    dispatched step, so nothing else de-duplicates the sort).

    Returns ``(p_sorted, a, a2, g_sorted)`` — pass as ``operands=`` to
    :func:`group_logcf` (directly or through ``kernels.ops.group_logcf``).
    """
    order = jnp.argsort(jnp.asarray(gids))
    a, a2, _ = pb_cf.split_modmult_operands(jnp.asarray(values)[order],
                                            num_freq)
    return (probs[order], a, a2,
            jnp.asarray(gids)[order].astype(jnp.int32))


def group_logcf(probs: jnp.ndarray, values: jnp.ndarray, gids: jnp.ndarray,
                *, num_groups: int, num_freq: int, freq_lo=0,
                freq_cnt: int | None = None, gb: int = 8, fb: int = 256,
                tb: int = 512, interpret: bool | None = None,
                operands=None):
    """(G, F)-tiled Pallas grouped log-CF accumulation.

    probs:  (n,) float tuple probabilities (p = 0 rows contribute nothing).
    values: (n,) integer tuple values (any int dtype; reduced mod num_freq).
    gids:   (n,) int group ids in [0, num_groups).
    freq_lo: window start — a static int, or a traced int scalar (e.g. a
    model-axis ``rank * f_loc`` inside shard_map); traced windows require
    an explicit ``freq_cnt`` and feed the kernel through SMEM, so every
    window shares ONE compiled kernel.
    operands: optional pre-sorted columns from :func:`presort_operands`;
    when given, probs/values/gids are ignored and the per-call sort +
    operand prep is skipped (the frequency-slab hoist).
    Returns (log_abs, angle), each (num_groups, freq_cnt) float, matching
    :func:`repro.kernels.ref.group_logcf_ref` — frequencies
    [freq_lo, freq_lo + freq_cnt) of the num_freq-point DFT grid.
    """
    if isinstance(freq_lo, int):
        if freq_cnt is None:
            freq_cnt = num_freq - freq_lo
        assert 0 <= freq_lo and freq_lo + freq_cnt <= num_freq
    elif freq_cnt is None:
        raise ValueError("traced freq_lo requires an explicit freq_cnt")
    return _group_logcf(probs, values, gids,
                        jnp.asarray(freq_lo, jnp.int32).reshape(1),
                        num_groups=num_groups, num_freq=num_freq,
                        freq_cnt=int(freq_cnt), gb=gb, fb=fb, tb=tb,
                        interpret=interpret, operands=operands)


@functools.partial(jax.jit, static_argnames=(
    "num_groups", "num_freq", "freq_cnt", "gb", "fb", "tb", "interpret"))
def _group_logcf(probs, values, gids, freq_lo, *, num_groups: int,
                 num_freq: int, freq_cnt: int, gb: int, fb: int,
                 tb: int, interpret: bool | None, operands=None):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n = num_freq
    f = freq_cnt
    dtype = probs.dtype

    nt = probs.shape[0]
    ntp = pl.cdiv(nt, tb) * tb
    if operands is None:
        # Sort tuples by group id so each block spans a narrow group range
        # and the kernel's block-range skip prunes non-intersecting
        # (gi, ti) steps.
        operands = presort_operands(probs, values, gids, n)
    p, a, a2, g = operands
    shift = pb_cf.phase_shift(n)
    # p = 0 padding contributes log(1) = 0 to both outputs (any group row).
    p = jnp.pad(p, (0, ntp - nt))
    g = jnp.pad(g, (0, ntp - nt),
                constant_values=max(0, num_groups - 1))
    a = jnp.pad(a, (0, ntp - nt))
    a2 = jnp.pad(a2, (0, ntp - nt))

    gblocks = g.reshape(-1, tb)
    gmin = gblocks.min(axis=1)                     # (T_blocks,) in SMEM
    gmax = gblocks.max(axis=1)

    ngp = pl.cdiv(num_groups, gb) * gb
    nfp = pl.cdiv(f, fb) * fb
    grid = (ngp // gb, nfp // fb, ntp // tb)

    # The block ranges and freq_lo sit whole in SMEM (a (1, 1) block per
    # step breaks the TPU's (8, 128)-or-full-dim block rule): 4 bytes per
    # tuple block each, ~47 KB apiece at 6M tuples and tb=512.
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    la, an = pl.pallas_call(
        functools.partial(_group_logcf_kernel, num_freq=n,
                          shift=shift, gb=gb, fb=fb, tb=tb),
        grid=grid,
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((1, tb), lambda g, f, t: (0, t)),
            pl.BlockSpec((1, tb), lambda g, f, t: (0, t)),
            pl.BlockSpec((1, tb), lambda g, f, t: (0, t)),
            pl.BlockSpec((1, tb), lambda g, f, t: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((gb, fb), lambda g, f, t: (g, f)),
            pl.BlockSpec((gb, fb), lambda g, f, t: (g, f)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ngp, nfp), dtype),
            jax.ShapeDtypeStruct((ngp, nfp), dtype),
        ],
        interpret=interpret,
    )(gmin, gmax, freq_lo, p.reshape(1, -1), a.reshape(1, -1),
      a2.reshape(1, -1), g.reshape(1, -1))
    return la[:num_groups, :f], an[:num_groups, :f]
