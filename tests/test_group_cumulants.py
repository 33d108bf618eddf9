"""The (G, O)-tiled grouped-cumulants Pallas kernel and its dispatch.

Kernel tests run in interpret mode (same BlockSpec tiling as the TPU
target) and carry the ``kernels`` marker.  f32 parity is checked against
the f32 segment-sum oracle: for high orders the kappa polynomial
evaluation is conditioning-limited in f32 (coefficients reach ~1e7), so
kernel-vs-f64 error equals oracle-vs-f64 error and the meaningful
contract is "exactly as accurate as the existing f32 path" — the f64
interpret run is the tight absolute check."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import uda
from repro.kernels import cumulants, group_cumulants, ref
from repro.kernels import ops as kops


def _inputs(rng, n, num_groups, dtype=jnp.float32, vmax=5.0):
    p = jnp.asarray(rng.uniform(0.01, 0.99, n), dtype)
    v = jnp.asarray(rng.uniform(0.0, vmax, n), dtype)
    g = jnp.asarray(rng.integers(0, num_groups, n), jnp.int32)
    return p, v, g


def _assert_scale_aware_close(got, want, p, v, g, num_groups, orders,
                              rtol=2e-6):
    """Grouped cumulant sums are conditioning-limited, not
    arithmetic-limited: kappa_12's coefficients reach ~1e7 and v^j ~1e8
    while the evaluated sums sit orders of magnitude lower, so BOTH the
    kernel and the f32 oracle carry error ~eps times the
    absolute-coefficient scale sum_d |c_jd| p^d * v^j (summed per group).
    Their difference is bounded the same way — measured at a few f32 ULPs
    of that scale across every sweep config; rtol gives ~10x headroom."""
    from repro.core.approx import _bernoulli_cumulant_polys
    tabs = jnp.abs(jnp.asarray(_bernoulli_cumulant_polys()[1:orders + 1],
                               jnp.float32))
    pf = jnp.asarray(p, jnp.float32)
    vf = jnp.asarray(v, jnp.float32)
    powers = pf[None, :] ** jnp.arange(tabs.shape[1],
                                       dtype=jnp.float32)[:, None]
    vpow = vf[None, :] ** jnp.arange(1, orders + 1,
                                     dtype=jnp.float32)[:, None]
    scale = jax.ops.segment_sum(((tabs @ powers) * vpow).T, g,
                                num_segments=num_groups)
    err = np.abs(np.asarray(got) - np.asarray(want))
    bound = rtol * np.maximum(np.asarray(scale), 1.0)
    np.testing.assert_array_less(err, bound)


# ------------------------------------------------------------- kernel
@pytest.mark.kernels
@pytest.mark.parametrize("n,num_groups", [
    (100, 3), (300, 5), (1000, 12), (513, 9), (2048, 64), (1500, 200),
])
@pytest.mark.parametrize("orders", [4, 8])
def test_group_cumulants_kernel_sweep(rng, n, num_groups, orders):
    p, v, g = _inputs(rng, n, num_groups)
    sk = group_cumulants.group_cumulant_sums(
        p, v, g, num_groups=num_groups, orders=orders, interpret=True)
    sr = ref.group_cumulants_ref(p, v, g, num_groups, orders)
    assert sk.shape == (num_groups, orders)
    _assert_scale_aware_close(sk, sr, p, v, g, num_groups, orders)


@pytest.mark.kernels
def test_group_cumulants_orders12_scale_aware(rng):
    """orders = 12 sums cancel: bound the error against the per-(g, j)
    magnitude scale sum |kappa_j * v^j| instead of the (near-zero) sums
    themselves — the same conditioning budget the f32 oracle spends."""
    n, num_groups, orders = 1024, 16, 12
    p, v, g = _inputs(rng, n, num_groups)
    sk = group_cumulants.group_cumulant_sums(
        p, v, g, num_groups=num_groups, orders=orders, interpret=True)
    sr = ref.group_cumulants_ref(p, v, g, num_groups, orders)
    _assert_scale_aware_close(sk, sr, p, v, g, num_groups, orders)


@pytest.mark.kernels
def test_group_cumulants_f64_tight(rng):
    """f64 interpret run vs the f64 oracle: no conditioning excuse — the
    kernel's Horner/iterated-power/mask-matmul arithmetic must agree to
    full double precision."""
    n, num_groups, orders = 700, 11, 8
    p, v, g = _inputs(rng, n, num_groups, dtype=jnp.float64)
    sk = group_cumulants.group_cumulant_sums(
        p, v, g, num_groups=num_groups, orders=orders, interpret=True)
    sr = ref.group_cumulants_ref(p, v, g, num_groups, orders)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr),
                               rtol=1e-9, atol=1e-12)


@pytest.mark.kernels
def test_group_cumulants_vs_scalar_per_group(rng):
    """Tiled grouped kernel == the scalar cumulants kernel run per group
    (the per-group loop the (G, O) tiling replaces)."""
    n, num_groups, orders = 900, 6, 8
    p, v, g = _inputs(rng, n, num_groups)
    sk = group_cumulants.group_cumulant_sums(
        p, v, g, num_groups=num_groups, orders=orders, interpret=True)
    for gi in range(num_groups):
        pg = jnp.where(g == gi, p, 0.0)
        ss = cumulants.cumulant_sums(pg, v, orders=orders, interpret=True)
        np.testing.assert_allclose(np.asarray(sk[gi]), np.asarray(ss),
                                   rtol=5e-3, atol=1e-3)


@pytest.mark.kernels
def test_group_cumulants_block_sizes(rng):
    """Every (gb, ob, tb) tiling computes the same (G, orders) state."""
    n, num_groups, orders = 1100, 150, 8
    p, v, g = _inputs(rng, n, num_groups)
    want = group_cumulants.group_cumulant_sums(
        p, v, g, num_groups=num_groups, orders=orders, interpret=True)
    for gb, ob, tb in ((128, 8, 256), (256, 8, 512), (128, 16, 512),
                       (128, 8, 1024)):
        got = group_cumulants.group_cumulant_sums(
            p, v, g, num_groups=num_groups, orders=orders,
            gb=gb, ob=ob, tb=tb, interpret=True)
        np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=str((gb, ob, tb)))


@pytest.mark.kernels
def test_group_cumulants_large_values_no_padding_overflow(rng):
    """ob > orders padding rows are explicit zeros, never computed as
    v^j: values whose v^(ob) would overflow f32 must still come out
    finite and correct through the padded (OB, TB) contrib tile."""
    n, num_groups, orders = 600, 5, 4            # ob pads 4 -> 8
    p = jnp.asarray(rng.uniform(0.01, 0.99, n), jnp.float32)
    v = jnp.asarray(rng.uniform(1e8, 5e8, n), jnp.float32)  # v^8 ~ 1e67
    g = jnp.asarray(rng.integers(0, num_groups, n), jnp.int32)
    sk = group_cumulants.group_cumulant_sums(
        p, v, g, num_groups=num_groups, orders=orders, interpret=True)
    assert np.isfinite(np.asarray(sk)).all()
    sr = ref.group_cumulants_ref(p, v, g, num_groups, orders)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=5e-3)


@pytest.mark.kernels
@pytest.mark.parametrize("tb", [128, 512])
def test_group_cumulants_layout_invariant(rng, tb):
    """A group's sums depend only on its own p != 0 tuples, in stream
    order: a stream holding a third of the groups, with p = 0 rows of
    other groups interleaved (the owner-local stream of a sharded plan),
    gives those groups bit-identical sums.  Group 0 spans several
    tb-tuple pieces."""
    n, G = 3000, 300
    p, v, g = _inputs(rng, n, G)
    g = np.array(g)
    g[rng.choice(n, 700, replace=False)] = 0
    kw = dict(num_groups=G, orders=8, tb=tb, interpret=True)
    want = np.asarray(group_cumulants.group_cumulant_sums(
        p, v, jnp.asarray(g), **kw))
    keep = np.flatnonzero(g % 3 == 0)
    m = 500
    at = np.sort(rng.integers(0, keep.size + 1, m))
    pz = np.insert(np.asarray(p)[keep], at, 0.0)
    vz = np.insert(np.asarray(v)[keep], at, rng.uniform(0, 5, m))
    gz = np.insert(g[keep], at, rng.integers(0, G, m))
    got = np.asarray(group_cumulants.group_cumulant_sums(
        jnp.asarray(pz, jnp.float32), jnp.asarray(vz, jnp.float32),
        jnp.asarray(gz, jnp.int32), **kw))
    kept = np.arange(0, G, 3)
    np.testing.assert_array_equal(got[kept], want[kept])


def test_presorted_operands_bit_equal(rng):
    """group_cumulant_sums with hoisted presort_operands == the
    self-sorting call, bit for bit, including through the ops.py
    dispatch wrapper."""
    n, num_groups = 640, 24
    p, v, g = _inputs(rng, n, num_groups)
    operands = group_cumulants.presort_operands(p, v, g)
    want = group_cumulants.group_cumulant_sums(
        p, v, g, num_groups=num_groups, orders=8)
    got = group_cumulants.group_cumulant_sums(
        p, v, g, num_groups=num_groups, orders=8, operands=operands)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    a = kops.group_cumulant_sums(p, v, g, num_groups, use_kernel=True)
    b = kops.group_cumulant_sums(
        p, v, g, num_groups, use_kernel=True,
        operands=kops.presort_group_cumulant_operands(p, v, g))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------- dispatch
def test_ops_dispatch_small_uses_ref(rng):
    """Tiny inputs route to the oracle (padding would dominate) — the
    auto path must be bit-equal to the oracle there."""
    p = jnp.asarray(rng.uniform(0.1, 0.9, 8), jnp.float32)
    v = jnp.asarray(rng.uniform(0, 3, 8), jnp.float32)
    g = jnp.asarray([0, 1, 0, 1, 2, 2, 0, 1], jnp.int32)
    got = kops.group_cumulant_sums(p, v, g, 3)
    want = ref.group_cumulants_ref(p, v, g, 3, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_dispatch_f64_uses_ref(rng):
    """f64 columns decline the (f32-tiled) kernel even above the size
    threshold."""
    p = jnp.asarray(rng.uniform(0.1, 0.9, 1024), jnp.float64)
    v = jnp.asarray(rng.uniform(0, 3, 1024), jnp.float64)
    g = jnp.asarray(rng.integers(0, 4, 1024), jnp.int32)
    got = kops.group_cumulant_sums(p, v, g, 4)
    want = ref.group_cumulants_ref(p, v, g, 4, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.kernels
def test_uda_accumulate_grouped_kernel_parity(rng):
    """uda.accumulate with kernel='pallas' (the grouped-kernel UDA
    dispatch) vs kernel='xla' (the blocked scatter-add scan) — same
    grouped cumulant state within f32 accumulation-order tolerance, and
    the scalar max_groups=1 path still dispatches too."""
    n, G = 2048, 64
    p, v, g = _inputs(rng, n, G)
    u = {"c": uda.SumCumulants(8)}
    a = uda.accumulate(u, p, v, g, max_groups=G, kernel="pallas")["c"]
    b = uda.accumulate(u, p, v, g, max_groups=G, kernel="xla")["c"]
    scale = float(jnp.max(jnp.abs(b.terms)))
    np.testing.assert_allclose(np.asarray(a.terms), np.asarray(b.terms),
                               atol=5e-3 * max(scale, 1.0))
    a1 = uda.accumulate(u, p, v, None, max_groups=1, kernel="pallas")["c"]
    b1 = uda.accumulate(u, p, v, None, max_groups=1, kernel="xla")["c"]
    s1 = float(jnp.max(jnp.abs(b1.terms)))
    np.testing.assert_allclose(np.asarray(a1.terms), np.asarray(b1.terms),
                               atol=5e-3 * max(s1, 1.0))


def test_update_tables_sized_to_orders(rng):
    """SumCumulants.update / cumulants_ref truncate the coefficient table
    and power basis at degree = orders (deg kappa_j = j): the streaming
    update must agree with the full MAX_ORDER-degree evaluation."""
    from repro.core.approx import _bernoulli_cumulant_polys
    n, G, orders = 64, 4, 6
    p, v, g = _inputs(rng, n, G, dtype=jnp.float64)
    u = uda.SumCumulants(orders)
    st = u.update(u.init(G, jnp.float64), p, v, g)
    table = jnp.asarray(_bernoulli_cumulant_polys()[1:orders + 1],
                        jnp.float64)            # full (orders, MAX+1) table
    powers = p[None, :] ** jnp.arange(table.shape[1],
                                      dtype=jnp.float64)[:, None]
    vpow = v[None, :] ** jnp.arange(1, orders + 1,
                                    dtype=jnp.float64)[:, None]
    want = jax.ops.segment_sum(((table @ powers) * vpow).T, g,
                               num_segments=G)
    np.testing.assert_allclose(np.asarray(st.terms), np.asarray(want),
                               rtol=1e-12, atol=1e-12)
    sr = ref.cumulants_ref(p, v, orders)
    np.testing.assert_allclose(np.asarray(sr),
                               np.asarray(want.sum(axis=0)), rtol=1e-12)
