"""Pallas TPU kernel: (G, O)-tiled grouped cumulant accumulation — the
grouped moment-approximation hot path.

The scalar kernel (:mod:`repro.kernels.cumulants`) accumulates ONE set of
partial cumulant sums over all tuples; grouped approximate SUM/COUNT (paper
§V-C.3 under a GROUP BY) needs G of them, one per group:

    s[g, j] = sum_{i : gid_i = g} v_i^j * kappa_j(p_i),   j = 1..orders

A per-group loop over the scalar kernel would re-stream the tuple column G
times; this kernel streams it once per group *block* and scatters each
tuple's contribution to its group column in-register, reusing the two
proven designs verbatim:

  * the per-tuple order rows come from cumulants.py — one shared Horner
    sweep over the (orders, degree+1) Bernoulli-cumulant coefficient table
    (kappa_{j+1} = p(1-p) dkappa_j/dp) and v^j by iterated multiply;
  * the grouped scatter comes from group_cf.py — a 0/1 segment mask
    contracted on the MXU, with gid-presorted tuples and per-block
    [min gid, max gid] ranges in SMEM skipping non-intersecting steps.

Canonical per-group order
-------------------------
A group's sum must not depend on where its tuples sit in the tuple
stream: the sharded plans (``db/distributed.py``) feed the kernel the same
per-group tuple sequences as ``mesh=None`` but at other offsets, and the
engine promises bit-identical results.  An MXU contraction over a tile
adds its terms in an order fixed by lane position, and a group cut by a
tile boundary is summed as two partials — so on the chip the plain
segment-mask scatter rounded differently under each layout.  The kernel
therefore fixes the order by the group alone:

  * tuples with p = 0 contribute nothing and are dropped (their presence
    differs between layouts);
  * each group's tuple run is cut into pieces of at most TB tuples,
    counted back from its last tuple;
  * :func:`presort_operands` lays the stream out in super tiles of 2*TB
    lanes: super tile k holds the pieces whose last tuple falls in stream
    tile k, so it holds each piece whole and at most one piece per group;
  * in the kernel a segmented Hillis-Steele scan along the lanes sums each
    piece in a tree fixed by positions relative to the piece's start, and
    only the piece's last lane (its total) enters the segment-mask matmul
    — one nonzero term per group column, which the MXU passes exactly;
  * the accumulator adds a group's pieces in stream order.

TPU mapping
-----------
grid = (G_blocks, T_blocks); the tuple axis is the innermost reduction axis
so each (OB, GB) accumulator tile stays VMEM-resident while super tiles
stream through.  Orders live on the SUBLANE axis (OB pads orders <= 12 up
to the f32 sublane multiple of 8) and groups on the LANE axis (GB a
multiple of 128) — the opposite orientation from group_cf's (GB, FB) tiles,
because padding a dozen orders to a 128-lane quantum would waste 10x more
accumulator (and matmul) than padding the group axis does.  Per grid step
(W = 2*TB lanes):

    contrib[j, t] = kappa_j(p_t) * v_t^j                  (OB, W)
    total[j, t]   = piece sum at its last lane t, else 0   (OB, W)
    M[r, t]       = 1 if gid_t == gi*GB + r else 0        (GB, W)
    acc[j, r]    += sum_t total[j, t] * M[r, t]           one MXU matmul

Rows j > orders of ``contrib`` are explicit zero padding — v^j is never
materialised past ``orders``, so large |v| cannot overflow into the
padding rows.

VMEM budget (defaults gb=128, ob=8..16, tb=512 so W=1024, f32):
    contrib / scan tiles          3 x (OB, W) x 4B   = 192 KB (ob=16)
    segment mask                  (GB, W) x 4B       = 512 KB
    accumulator                   (OB, GB) x 4B      = 8 KB
well inside the ~16 MB v5e VMEM with double-buffering headroom.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The segment-mask scatter must not round its f32 operand: Mosaic's
# default matmul precision feeds the MXU bf16 (2^-9 relative per term).
_EXACT = jax.lax.Precision.HIGHEST
# Lanes with no tuple carry this group id, which matches no column.
_NO_GROUP = -1


def _group_cumulants_kernel(gmin_ref, gmax_ref, p_ref, v_ref, g_ref,
                            coef_ref, out_ref, *, orders: int, degree: int,
                            ob: int, gb: int, width: int):
    gi = pl.program_id(0)
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Skip the whole step when this super tile cannot touch any of this
    # block's group columns [gi*gb, gi*gb + gb).
    col_lo = gi * gb
    hit = (gmin_ref[ti] < col_lo + gb) & (gmax_ref[ti] >= col_lo)

    @pl.when(hit)
    def _acc():
        p = p_ref[...]            # (1, W)
        v = v_ref[...]            # (1, W)
        g = g_ref[...]            # (1, W)
        coef = coef_ref[...]      # (orders, degree+1), row j-1 = kappa_j

        # kappa_j(p) for all orders at once: Horner over descending powers
        # (shared with the scalar cumulants kernel).
        kap = jnp.broadcast_to(coef[:, degree:degree + 1],
                               (orders, width)) * 0.0 \
            + coef[:, degree:degree + 1]
        for d in range(degree - 1, -1, -1):
            kap = kap * p + coef[:, d:d + 1]      # (orders, W)

        # v^j rows by iterated multiply: vpow[j-1] = v^j.
        rows = [v]
        for _ in range(orders - 1):
            rows.append(rows[-1] * v)
        vpow = jnp.concatenate(rows, axis=0)      # (orders, W)

        y = kap * vpow                            # (orders, W)
        if ob > orders:
            # Zero sublane padding — never computed as v^j, so no overflow.
            y = jnp.concatenate(
                [y, jnp.zeros((ob - orders, width), y.dtype)],
                axis=0)                           # (OB, W)

        # Segmented inclusive scan along the lanes (Hillis-Steele): after
        # the step with shift d, lane i holds the sum of its piece's lanes
        # (i - 2d, i], added in a tree fixed by positions relative to the
        # piece's first lane.  A super tile holds at most one piece per
        # group, so a piece is a run of equal group ids.
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        d = 1
        while d < width:
            same = (pltpu.roll(g, d, 1) == g) & (lane >= d)
            y = y + jnp.where(same, pltpu.roll(y, d, 1), 0.0)
            d *= 2
        # Keep each piece's total at its last lane only.
        last = (pltpu.roll(g, width - 1, 1) != g) | (lane == width - 1)
        y = jnp.where(last, y, 0.0)

        # Segment-mask scatter: cols (GB, 1) vs gids (1, W) -> (GB, W) 0/1
        # mask; one MXU matmul contracts the lanes into (OB, GB), with one
        # nonzero term per group column.
        cols = col_lo + jax.lax.broadcasted_iota(jnp.int32, (gb, 1), 0)
        m = (g == cols).astype(p.dtype)
        dims = (((1,), (1,)), ((), ()))
        out_ref[...] += jax.lax.dot_general(y, m, dims,
                                            preferred_element_type=p.dtype,
                                            precision=_EXACT)


def _suffix_min(x: jnp.ndarray) -> jnp.ndarray:
    """min(x[i:]) for every i, by doubling shifts: log2(n) elementwise
    passes that compile in well under a second, where
    ``lax.cummin(reverse=True)`` on a 750k column takes ~90 s to compile
    for a v5e."""
    n, s = x.shape[0], 1
    while s < n:
        pad = jnp.full((s,), jnp.iinfo(x.dtype).max, x.dtype)
        x = jnp.minimum(x, jnp.concatenate([x[s:], pad]))
        s *= 2
    return x


def presort_operands(probs: jnp.ndarray, values: jnp.ndarray,
                     gids: jnp.ndarray, tb: int = 512):
    """The super-tile layout of :func:`group_cumulant_sums` (see the module
    docstring), hoisted so callers can build it once and reuse it across
    repeated dispatches with the same ``tb``.  Returns
    ``(p, v, g)``, each of ``2 * tb * ceil(n / tb)`` lanes; lanes with no
    tuple hold p = v = 0 and group :data:`_NO_GROUP`.  Pass as
    ``operands=``."""
    probs = jnp.asarray(probs)
    values = jnp.asarray(values)
    n = probs.shape[0]
    ntiles = pl.cdiv(n, tb)
    width = 2 * tb
    dropped = jnp.iinfo(jnp.int32).max
    # p = 0 tuples add exact zeros; dropping them makes every group's
    # sequence the same whichever other rows share the column.
    key = jnp.where(probs != 0, jnp.asarray(gids).astype(jnp.int32),
                    dropped)
    order = jnp.argsort(key)                      # stable
    k = key[order]
    i = jnp.arange(n, dtype=jnp.int32)
    run_end = jnp.concatenate([k[1:] != k[:-1], jnp.ones((1,), bool)])
    end = _suffix_min(jnp.where(run_end, i, n))   # last tuple of the group
    # Pieces of at most tb tuples, counted back from the group's last
    # tuple; a piece goes to the super tile of the stream tile where it
    # ends (nondecreasing along the stream).
    tile = (i + (end - i) % tb) // tb
    first = jnp.searchsorted(tile, jnp.arange(ntiles, dtype=jnp.int32))
    slot = jnp.arange(ntiles * width, dtype=jnp.int32)
    src = first[slot // width] + slot % width
    at = jnp.minimum(src, n - 1)
    ok = (src < n) & (tile[at] == slot // width) & (k[at] != dropped)
    take = order[at]
    return (jnp.where(ok, probs[take], 0).astype(probs.dtype),
            jnp.where(ok, values[take], 0).astype(values.dtype),
            jnp.where(ok, k[at], _NO_GROUP))


@functools.partial(jax.jit, static_argnames=(
    "num_groups", "orders", "ob", "gb", "tb", "interpret"))
def group_cumulant_sums(probs: jnp.ndarray, values: jnp.ndarray,
                        gids: jnp.ndarray, *, num_groups: int,
                        orders: int = 8, ob: int | None = None,
                        gb: int = 128, tb: int = 512,
                        interpret: bool | None = None, operands=None):
    """(G, O)-tiled Pallas grouped cumulant accumulation.

    probs:  (n,) float tuple probabilities (p = 0 rows contribute nothing).
    values: (n,) tuple values (cast to the probs dtype).
    gids:   (n,) int group ids in [0, num_groups).
    operands: optional super-tile layout from :func:`presort_operands`
    with the same ``tb``; when given, probs/values/gids are ignored and
    the per-call sort is skipped.
    Returns (num_groups, orders) partial cumulant sums, row g matching
    :func:`repro.kernels.ref.cumulants_ref` over group g's tuples
    (= :func:`repro.kernels.ref.group_cumulants_ref`); row g depends only
    on group g's tuples with p != 0, in stream order — not on where they
    sit in the stream.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    from repro.core.approx import _bernoulli_cumulant_polys
    dtype = probs.dtype
    # deg(kappa_j) = j <= orders, so the table truncates exactly at
    # degree = orders (the dropped high columns are identically zero).
    degree = orders
    table = np.asarray(_bernoulli_cumulant_polys()[1:orders + 1],
                       dtype=np.float64)[:, :degree + 1]
    coef = jnp.asarray(table, dtype)
    if ob is None:
        ob = pl.cdiv(orders, 8) * 8               # f32 sublane multiple
    assert ob >= orders and ob % 8 == 0

    if operands is None:
        operands = presort_operands(probs, values, gids, tb)
    p, v, g = operands
    width = 2 * tb
    assert p.shape[0] % width == 0, "operands laid out for another tb"
    v = v.astype(dtype)

    gblocks = g.reshape(-1, width)
    gmin = jnp.min(jnp.where(gblocks == _NO_GROUP,
                             jnp.iinfo(jnp.int32).max, gblocks), axis=1)
    gmax = gblocks.max(axis=1)                     # (T_blocks,) in SMEM

    ngp = pl.cdiv(num_groups, gb) * gb
    grid = (ngp // gb, gblocks.shape[0])

    # The block ranges sit whole in SMEM (a (1, 1) block per step breaks
    # the TPU's (8, 128)-or-full-dim block rule): 4 bytes per super tile.
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    lanes = pl.BlockSpec((1, width), lambda g, t: (0, t))
    out = pl.pallas_call(
        functools.partial(_group_cumulants_kernel, orders=orders,
                          degree=degree, ob=ob, gb=gb, width=width),
        grid=grid,
        in_specs=[
            smem, smem, lanes, lanes, lanes,
            pl.BlockSpec((orders, degree + 1), lambda g, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((ob, gb), lambda g, t: (0, g)),
        out_shape=jax.ShapeDtypeStruct((ob, ngp), dtype),
        interpret=interpret,
    )(gmin, gmax, p.reshape(1, -1), v.reshape(1, -1), g.reshape(1, -1), coef)
    return out[:orders, :num_groups].T            # (G, orders)
