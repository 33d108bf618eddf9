"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs ref.py oracles
(interpret=True on CPU; BlockSpec tiling identical to the TPU target).
The grouped (G, F)-tiled CF kernel is covered in test_group_cf.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import pb_cf, polymul, cumulants

pytestmark = pytest.mark.kernels


@pytest.mark.parametrize("n,num_freq", [
    (1, 8), (100, 129), (256, 256), (300, 257), (1000, 1001),
    (2048, 4096), (5000, 2047),
])
def test_logcf_kernel_sweep(rng, n, num_freq):
    p = jnp.asarray(rng.uniform(0.01, 0.99, n), jnp.float32)
    v = jnp.asarray(rng.integers(0, 50, n), jnp.int32)
    la_k, an_k = pb_cf.logcf(p, v, num_freq=num_freq, interpret=True)
    la_r, an_r = ref.logcf_ref(p, v.astype(jnp.float32), num_freq)
    np.testing.assert_allclose(np.asarray(la_k), np.asarray(la_r),
                               atol=5e-4 * max(1, n / 500))
    np.testing.assert_allclose(np.asarray(an_k), np.asarray(an_r),
                               atol=5e-4 * max(1, n / 500))


def test_logcf_kernel_large_values(rng):
    """k*a far beyond int32/f32 exactness: the split-modmult must hold."""
    n, num_freq = 500, 1 << 14
    p = jnp.asarray(rng.uniform(0.01, 0.99, n), jnp.float32)
    v = jnp.asarray(rng.integers(0, num_freq, n), jnp.int32)
    la_k, an_k = pb_cf.logcf(p, v, num_freq=num_freq, interpret=True)
    la_r, an_r = ref.logcf_ref(jnp.asarray(p, jnp.float64),
                               jnp.asarray(v, jnp.float64), num_freq)
    np.testing.assert_allclose(np.asarray(la_k),
                               np.asarray(la_r, dtype=np.float32), atol=2e-3)


@pytest.mark.parametrize("na,nb", [
    (1, 1), (5, 130), (129, 129), (130, 200), (512, 512), (1000, 300),
    (2000, 2000),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_polymul_kernel_sweep(rng, na, nb, dtype):
    a = jnp.asarray(rng.uniform(0, 1, na), dtype)
    b = jnp.asarray(rng.uniform(0, 1, nb), dtype)
    ck = polymul.polymul(a, b, interpret=True)
    cr = ref.polymul_ref(a, b)
    np.testing.assert_allclose(np.asarray(ck), np.asarray(cr),
                               rtol=1e-5, atol=1e-4)


def test_polymul_block_sizes(rng):
    a = jnp.asarray(rng.uniform(0, 1, 700), jnp.float32)
    b = jnp.asarray(rng.uniform(0, 1, 500), jnp.float32)
    want = np.asarray(ref.polymul_ref(a, b))
    for bsize in (128, 256, 512):
        got = np.asarray(polymul.polymul(a, b, bsize=bsize, interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n", [1, 100, 1024, 3000])
@pytest.mark.parametrize("orders", [4, 8])
def test_cumulants_kernel_sweep(rng, n, orders):
    p = jnp.asarray(rng.uniform(0.01, 0.99, n), jnp.float32)
    v = jnp.asarray(rng.uniform(0, 5, n), jnp.float32)
    sk = cumulants.cumulant_sums(p, v, orders=orders, interpret=True)
    sr = ref.cumulants_ref(p, v, orders)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr),
                               rtol=5e-3, atol=1e-4)


def test_ops_dispatch_small_uses_ref(rng):
    """Tiny inputs route to the oracle (padding would dominate)."""
    p = jnp.asarray(rng.uniform(0.1, 0.9, 8), jnp.float32)
    v = jnp.ones((8,), jnp.float32)
    la, an = ops.logcf(p, v, 9)
    la_r, an_r = ref.logcf_ref(p, v, 9)
    np.testing.assert_allclose(np.asarray(la), np.asarray(la_r), atol=1e-6)


def test_kernel_end_to_end_distribution(rng):
    """Kernel log-CF -> FFT == possible-worlds, closing the loop."""
    from repro.core import pgf as P, poisson_binomial as pb
    n = 300
    probs = rng.uniform(0.05, 0.95, n)
    p = jnp.asarray(probs, jnp.float32)
    la, an = pb_cf.logcf(p, jnp.ones((n,), jnp.int32), num_freq=n + 1,
                         interpret=True)
    coeffs = pb.logcf_finalize(jnp.asarray(la, jnp.float64),
                               jnp.asarray(an, jnp.float64))
    mean = float(jnp.sum(coeffs * jnp.arange(n + 1)))
    assert mean == pytest.approx(float(probs.sum()), rel=1e-3)
    assert float(coeffs.sum()) == pytest.approx(1.0, abs=1e-3)


def test_kernel_atan2_every_quadrant():
    """The CF kernels' polynomial atan2 (Mosaic lowers no atan2/atan)
    against numpy's, over all four quadrants, the axes and the origin:
    within 2 f32 ulps of pi, the largest angle it returns."""
    g = np.concatenate([-np.logspace(-6, 3, 60), [0.0],
                        np.logspace(-6, 3, 60)]).astype(np.float32)
    y, x = np.meshgrid(g, g)
    got = np.asarray(pb_cf.atan2(jnp.asarray(y), jnp.asarray(x)))
    want = np.arctan2(y.astype(np.float64), x.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2 * np.spacing(np.float32(np.pi)))
