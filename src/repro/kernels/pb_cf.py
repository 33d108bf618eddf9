"""Pallas TPU kernel: blocked log-characteristic-function accumulation.

This is the hot loop of the exact COUNT/SUM path (DESIGN.md §2): for the
Poisson-binomial product  Q(X) = prod_i (q_i + p_i X^{a_i})  we accumulate

    log_abs[k] = sum_i 0.5*log|q_i + p_i w^{k a_i}|^2
    angle[k]   = sum_i arg (q_i + p_i w^{k a_i}),     w = exp(2 pi i / N)

over all tuples i for every DFT frequency k < N.  The paper's FFTW product
tree becomes this additive accumulation + one FFT at Finalize.

TPU mapping
-----------
grid = (F_blocks, T_blocks); the tuple axis is the (fast, innermost)
reduction axis so each (1, FB) output block stays resident in VMEM while all
tuple blocks stream through.  Per grid step the kernel materialises a
(FB, TB) phase tile — FB=256, TB=1024 f32 ~ 1 MB per intermediate, inside
the ~16 MB v5e VMEM budget with headroom for cos/sin/log tiles.  All lane
dims are multiples of 128.

Phase precision: theta = 2*pi*((k*a) mod N)/N must be exact; k*a overflows
f32 (and int32 for large N), so the wrapper splits k = k_hi*2^S + k_lo and
supplies a2 = (a << S) mod N.  Then

    (k*a) mod N = ((k_hi * a2) mod N + (k_lo * a) mod N) mod N

with k_lo*a < 2^(S + b) and k_hi*a2 < 2^(2b - S) (b = bit length of N-1)
both below 2^31 — S = b//2 + 1 satisfies that exactly for N <= 2^20, the
bound the ops.py dispatch guard enforces.  Integer-exact on the VPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _logcf_kernel(p_ref, a_ref, a2_ref, la_ref, an_ref, *,
                  num_freq: int, shift: int, fb: int, tb: int):
    fi = pl.program_id(0)
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        la_ref[...] = jnp.zeros_like(la_ref)
        an_ref[...] = jnp.zeros_like(an_ref)

    n = num_freq
    # Global frequency index for every lane of this output block: (FB, 1).
    k = fi * fb + jax.lax.broadcasted_iota(jnp.int32, (fb, 1), 0)
    k = jnp.minimum(k, n - 1)              # freq padding: recomputed lanes are discarded
    k_hi = k >> shift
    k_lo = k & ((1 << shift) - 1)

    a = a_ref[...]                         # (1, TB) int32, already mod N
    a2 = a2_ref[...]                       # (1, TB) int32, (a << shift) mod N
    p = p_ref[...]                         # (1, TB)

    # (FB, TB) exact phase: ((k_hi*a2) mod N + (k_lo*a) mod N) mod N
    phase = ((k_hi * a2) % n + (k_lo * a) % n) % n
    theta = phase.astype(p.dtype) * (2.0 * math.pi / n)

    q = 1.0 - p
    re = q + p * jnp.cos(theta)            # (FB, TB)
    im = p * jnp.sin(theta)
    tiny = jnp.asarray(1e-30 if p.dtype == jnp.float32 else 1e-300, p.dtype)
    la = 0.5 * jnp.log(jnp.maximum(re * re + im * im, tiny))
    an = atan2(im, re)

    la_ref[...] += la.sum(axis=1)[None, :]
    an_ref[...] += an.sum(axis=1)[None, :]


# tan(pi/8): atan2's second range-reduction threshold.
_TAN_PI_8 = 0.41421356237309503


def atan2(y, x):
    """``arctan2(y, x)`` from operations the TPU kernel lowering accepts
    (Mosaic has no atan2 or atan): reduce to t = min(|x|,|y|)/max(|x|,|y|)
    in [0, 1], fold t > tan(pi/8) onto z = (t-1)/(t+1) (+pi/4), evaluate
    the Cephes ``atanf`` minimax odd polynomial on |z| <= tan(pi/8)
    (relative error ~2e-7, one f32 ulp), then restore the octant and
    quadrant.  ``re = 1-p + p cos(theta)`` is negative for p > 1/2 near
    theta = pi, so all four quadrants occur.  The oracle
    (:func:`repro.kernels.ref.logcf_ref`) keeps ``jnp.arctan2``."""
    ax, ay = jnp.abs(x), jnp.abs(y)
    hi = jnp.maximum(ax, ay)
    t = jnp.minimum(ax, ay) / jnp.where(hi > 0, hi, 1)
    fold = t > _TAN_PI_8
    z = jnp.where(fold, (t - 1) / (t + 1), t)
    z2 = z * z
    r = ((((8.05374449538e-2 * z2 - 1.38776856032e-1) * z2
           + 1.99777106478e-1) * z2 - 3.33329491539e-1) * z2 * z + z)
    r = jnp.where(fold, r + math.pi / 4, r)
    r = jnp.where(ay > ax, math.pi / 2 - r, r)
    r = jnp.where(x < 0, math.pi - r, r)
    return jnp.where(y < 0, -r, r)


def phase_shift(num_freq: int) -> int:
    """The static split-modmult shift S for an N-point grid (k = k_hi*2^S +
    k_lo; see module docstring) — shared so callers holding precomputed
    operands recover the same S without re-running the prep."""
    return max(1, (num_freq - 1).bit_length() // 2 + 1)


def split_modmult_operands(values: jnp.ndarray, num_freq: int):
    """Exact int32 phase operands shared by the CF kernels (this module and
    :mod:`repro.kernels.group_cf`): reduce ``values`` mod N in the SOURCE
    integer dtype (a 64-bit value truncated to int32 first would wrap mod
    2^32, changing the residue for non-power-of-two N), narrow to int32,
    and precompute a2 = (a << shift) mod N by repeated doubling —
    int32-overflow-free for any N <= 2^30 (each intermediate < 2N <= 2^31).
    Returns (a, a2, shift), asserting the N <= 2^20 split-modmult
    exactness bound (see module docstring); zero-padding a/a2 afterwards
    is safe (phase 0, and p = 0 pad rows contribute log(1) = 0 anyway).
    """
    n = num_freq
    # int32 split-modmult exactness bound (see module docstring).
    assert n <= 1 << 20, f"num_freq {n} > 2^20 overflows the exact phase"
    shift = phase_shift(n)
    v = jnp.asarray(values)
    if jnp.issubdtype(v.dtype, jnp.integer) or v.dtype == jnp.bool_:
        v = v % n
    a = v.astype(jnp.int32) % n
    a2 = a
    for _ in range(shift):
        a2 = (a2 * 2) % n
    return a, a2, shift


@functools.partial(jax.jit, static_argnames=("num_freq", "fb", "tb", "interpret"))
def logcf(probs: jnp.ndarray, values: jnp.ndarray, *, num_freq: int,
          fb: int = 256, tb: int = 1024, interpret: bool | None = None):
    """Blocked Pallas log-CF accumulation.

    probs:  (n,) float tuple probabilities.
    values: (n,) integer tuple values (any int dtype; reduced mod num_freq).
    Returns (log_abs, angle), each (num_freq,) float, matching
    :func:`repro.kernels.ref.logcf_ref`.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n = num_freq
    dtype = probs.dtype
    a, a2, shift = split_modmult_operands(values, n)

    nt = probs.shape[0]
    ntp = pl.cdiv(nt, tb) * tb
    # p = 0 padding contributes log(1) = 0 to both outputs.
    p = jnp.pad(probs, (0, ntp - nt))
    a = jnp.pad(a, (0, ntp - nt))
    a2 = jnp.pad(a2, (0, ntp - nt))

    nfp = pl.cdiv(n, fb) * fb
    grid = (nfp // fb, ntp // tb)

    la, an = pl.pallas_call(
        functools.partial(_logcf_kernel, num_freq=n, shift=shift, fb=fb, tb=tb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tb), lambda f, t: (0, t)),
            pl.BlockSpec((1, tb), lambda f, t: (0, t)),
            pl.BlockSpec((1, tb), lambda f, t: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((1, fb), lambda f, t: (0, f)),
            pl.BlockSpec((1, fb), lambda f, t: (0, f)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, nfp), dtype),
            jax.ShapeDtypeStruct((1, nfp), dtype),
        ],
        interpret=interpret,
    )(p.reshape(1, -1), a.reshape(1, -1), a2.reshape(1, -1))
    return la[0, :n], an[0, :n]
