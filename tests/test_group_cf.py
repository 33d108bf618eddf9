"""The (G, F)-tiled grouped log-CF Pallas kernel and the planner's
``GroupAgg(method="exact")`` path built on it.

Kernel tests run in interpret mode (same BlockSpec tiling as the TPU
target) and carry the ``kernels`` marker so the Pallas path is exercised in
tier-1 on CPU-only machines; planner tests check the possible-worlds
oracle, frequency-slab chunking, and (in a subprocess) 2-device mesh ==
single-device equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import MeshCheck
from repro.core import uda
from repro.core.config import default_float
from repro.core.pgf import possible_worlds_pgf
from repro.db.plans import GroupAgg, Scan, compile_plan
from repro.db.table import Table
from repro.kernels import group_cf, pb_cf, ref
from repro.kernels import ops as kops


def _inputs(rng, n, num_groups, vmax=50):
    p = jnp.asarray(rng.uniform(0.01, 0.99, n), jnp.float32)
    v = jnp.asarray(rng.integers(0, vmax, n), jnp.int32)
    g = jnp.asarray(rng.integers(0, num_groups, n), jnp.int32)
    return p, v, g


def _assert_angles_close(got, want, atol):
    """Angle sums are per-factor principal values whose 2*pi branch offsets
    cancel at exp() time (the ref.py contract); f32-vs-f64 sin at theta ==
    pi flips individual branches, so compare modulo 2*pi."""
    d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    wrapped = np.abs(np.mod(d + np.pi, 2 * np.pi) - np.pi)
    np.testing.assert_array_less(wrapped, atol)


# ------------------------------------------------------------- kernel
@pytest.mark.kernels
@pytest.mark.parametrize("n,num_groups,num_freq", [
    (100, 3, 64), (300, 5, 129), (1000, 12, 300), (513, 9, 64),
    (2048, 64, 512), (1500, 200, 257),
])
def test_group_logcf_kernel_sweep(rng, n, num_groups, num_freq):
    p, v, g = _inputs(rng, n, num_groups)
    la_k, an_k = group_cf.group_logcf(p, v, g, num_groups=num_groups,
                                      num_freq=num_freq, interpret=True)
    la_r, an_r = ref.group_logcf_ref(jnp.asarray(p, jnp.float64), v, g,
                                     num_groups, num_freq)
    np.testing.assert_allclose(np.asarray(la_k), np.asarray(la_r),
                               atol=5e-4 * max(1, n / 500))
    # f32 sin/atan2 near the theta == pi branch cut loses a few more bits
    # against the f64 reference than the log-abs path does.
    _assert_angles_close(an_k, an_r, 2e-3 * max(1, n / 500))


@pytest.mark.kernels
def test_group_logcf_tiled_vs_scalar_per_group(rng):
    """Tiled grouped kernel == the scalar pb_cf kernel run per group (the
    per-group loop the (G, F) tiling replaces)."""
    n, num_groups, num_freq = 700, 6, 200
    p, v, g = _inputs(rng, n, num_groups)
    la_g, an_g = group_cf.group_logcf(p, v, g, num_groups=num_groups,
                                      num_freq=num_freq, interpret=True)
    for gi in range(num_groups):
        pg = jnp.where(g == gi, p, 0.0)
        la_s, an_s = pb_cf.logcf(pg, v, num_freq=num_freq, interpret=True)
        np.testing.assert_allclose(np.asarray(la_g[gi]), np.asarray(la_s),
                                   atol=2e-3)
        np.testing.assert_allclose(np.asarray(an_g[gi]), np.asarray(an_s),
                                   atol=2e-3)


@pytest.mark.kernels
def test_group_logcf_block_sizes(rng):
    """Every (gb, fb, tb) tiling computes the same (G, F) state."""
    n, num_groups, num_freq = 900, 20, 192
    p, v, g = _inputs(rng, n, num_groups)
    want = group_cf.group_logcf(p, v, g, num_groups=num_groups,
                                num_freq=num_freq, interpret=True)
    for gb, fb, tb in ((8, 128, 256), (16, 256, 512), (8, 256, 1024),
                      (24, 128, 128)):
        got = group_cf.group_logcf(p, v, g, num_groups=num_groups,
                                   num_freq=num_freq, gb=gb, fb=fb, tb=tb,
                                   interpret=True)
        for a, b in zip(want, got):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, err_msg=str((gb, fb, tb)))


@pytest.mark.kernels
def test_group_logcf_freq_slabs(rng):
    """Slab runs [lo, lo+cnt) concatenate to the full-range run — the
    planner's memory-budget chunking contract."""
    n, num_groups, num_freq = 600, 10, 320
    p, v, g = _inputs(rng, n, num_groups)
    full = group_cf.group_logcf(p, v, g, num_groups=num_groups,
                                num_freq=num_freq, interpret=True)
    slabs = [group_cf.group_logcf(p, v, g, num_groups=num_groups,
                                  num_freq=num_freq, freq_lo=lo,
                                  freq_cnt=cnt, interpret=True)
             for lo, cnt in ((0, 128), (128, 128), (256, 64))]
    cat = tuple(jnp.concatenate([s[i] for s in slabs], axis=-1)
                for i in range(2))
    for a, b in zip(full, cat):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.kernels
def test_group_logcf_large_values_exact_phase(rng):
    """k*a far beyond int32/f32 exactness: the split-modmult must hold for
    the grouped kernel exactly as for the scalar one."""
    n, num_groups, num_freq = 500, 4, 1 << 14
    p = jnp.asarray(rng.uniform(0.01, 0.99, n), jnp.float32)
    v = jnp.asarray(rng.integers(0, num_freq, n), jnp.int32)
    g = jnp.asarray(rng.integers(0, num_groups, n), jnp.int32)
    la_k, _ = group_cf.group_logcf(p, v, g, num_groups=num_groups,
                                   num_freq=num_freq, freq_cnt=256,
                                   interpret=True)
    la_r, _ = ref.group_logcf_ref(jnp.asarray(p, jnp.float64),
                                  jnp.asarray(v, jnp.float64), g,
                                  num_groups, num_freq, freq_cnt=256)
    np.testing.assert_allclose(np.asarray(la_k), np.asarray(la_r), atol=2e-3)


@pytest.mark.kernels
def test_oracle_phase_exact_with_f32_probs(rng):
    """The small-n oracle route must stay phase-exact with f32 probs and
    large k*v (the phase grid runs at f64 under x64, not the probs dtype)."""
    n, num_groups, num_freq = 100, 3, 1 << 14
    p = jnp.asarray(rng.uniform(0.1, 0.9, n), jnp.float32)
    v = jnp.asarray(rng.integers(0, num_freq, n), jnp.int32)
    g = jnp.asarray(rng.integers(0, num_groups, n), jnp.int32)
    la, _ = kops.group_logcf(p, v, g, num_groups, num_freq)  # auto: oracle
    la_r, _ = ref.group_logcf_ref(jnp.asarray(p, jnp.float64),
                                  jnp.asarray(v, jnp.int64), g,
                                  num_groups, num_freq)
    np.testing.assert_allclose(np.asarray(la), np.asarray(la_r), atol=1e-3)


@pytest.mark.kernels
def test_kernel_int64_values_nonpow2_freq(rng):
    """64-bit values >= 2^31 with a non-power-of-two grid: the mod-N
    reduction must run in the source dtype before the int32 narrowing."""
    n, num_groups, num_freq = 300, 4, 1001
    p = jnp.asarray(rng.uniform(0.1, 0.9, n), jnp.float32)
    v = jnp.asarray(rng.integers(1 << 31, 1 << 40, n), jnp.int64)
    g = jnp.asarray(rng.integers(0, num_groups, n), jnp.int32)
    la_k, _ = group_cf.group_logcf(p, v, g, num_groups=num_groups,
                                   num_freq=num_freq, interpret=True)
    la_r, _ = ref.group_logcf_ref(jnp.asarray(p, jnp.float64), v, g,
                                  num_groups, num_freq)
    np.testing.assert_allclose(np.asarray(la_k), np.asarray(la_r), atol=1e-3)


@pytest.mark.kernels
def test_ops_dispatch_small_uses_ref(rng):
    """Tiny inputs route to the oracle (padding would dominate)."""
    p = jnp.asarray(rng.uniform(0.1, 0.9, 8), jnp.float32)
    v = jnp.ones((8,), jnp.int32)
    g = jnp.asarray([0, 1, 0, 1, 2, 2, 0, 1], jnp.int32)
    la, an = kops.group_logcf(p, v, g, 3, 9)
    la_r, an_r = ref.group_logcf_ref(p, v, g, 3, 9)
    np.testing.assert_allclose(np.asarray(la), np.asarray(la_r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(an), np.asarray(an_r), atol=1e-6)


# ------------------------------------------------- UDA / oracle parity
G = 4


def _data(seed, n=14):
    r = np.random.default_rng(seed)
    p = r.uniform(0.05, 0.95, n)
    v = r.integers(1, 8, n)
    g = r.integers(0, G, n)
    mask = r.uniform(0, 1, n) > 0.25
    return np.where(mask, p, 0.0), v, g


@pytest.mark.parametrize("seed", range(3))
def test_sumcf_accumulate_full_oracle_parity(seed):
    """SumCF.accumulate_full (the grouped kernel dispatch entry, pure-JAX
    fallback at this size) vs the 2^n possible-worlds oracle — masked, and
    with the state merged in two halves."""
    p, v, g = _data(seed)
    dt = default_float()
    num_freq = int(v.sum()) + 1
    u = uda.SumCF(num_freq)
    pj, vj, gj = jnp.asarray(p, dt), jnp.asarray(v), jnp.asarray(g)
    one = u.accumulate_full(u.init(G, dt), pj, vj, gj, G)
    h = len(p) // 2
    a = u.accumulate_full(u.init(G, dt), pj[:h], vj[:h], gj[:h], G)
    b = u.accumulate_full(u.init(G, dt), pj[h:], vj[h:], gj[h:], G)
    for st in (one, u.merge(a, b)):
        coeffs = np.asarray(u.finalize(st))
        for gi in range(G):
            oracle = possible_worlds_pgf(p[g == gi],
                                         v[g == gi].astype(float), "SUM")
            for outcome, pr in oracle.items():
                assert coeffs[gi, int(outcome)] == pytest.approx(
                    pr, abs=1e-9), (seed, gi, outcome)


@pytest.mark.parametrize("seed", range(2))
def test_groupagg_exact_planner_oracle(seed):
    """compile_plan GroupAgg(method='exact') == possible worlds, and the
    frequency-slab chunked compile is bit-identical to the unchunked one."""
    p, v, g = _data(seed)
    num_freq = int(v.sum()) + 1
    t = Table.from_columns({"g": jnp.asarray(g), "v": jnp.asarray(v)},
                           prob=jnp.asarray(p))
    plan = GroupAgg(Scan("t"), ("g",), "v", "SUM", G, "exact",
                    num_freq=num_freq,
                    extra=(("cnt", "", "COUNT", "exact"),))
    out = compile_plan(plan)({"t": t})
    chunked = compile_plan(plan, cf_budget_elems=2 * G)({"t": t})
    coeffs, cnt = np.asarray(out["exact"]), np.asarray(out["cnt"])
    for gi in range(G):
        sel = g == gi
        for outcome, pr in possible_worlds_pgf(
                p[sel], v[sel].astype(float), "SUM").items():
            assert coeffs[gi, int(outcome)] == pytest.approx(pr, abs=1e-9)
        for outcome, pr in possible_worlds_pgf(
                p[sel], np.ones(sel.sum()), "COUNT").items():
            assert cnt[gi, int(outcome)] == pytest.approx(pr, abs=1e-9)
    for k in ("exact", "cnt", "confidence"):
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(chunked[k]), err_msg=k)


@pytest.mark.kernels
def test_kernel_dispatch_preserves_big_integer_values(rng):
    """Values above 2^24 must reach the kernel uncast: an f32 round-trip
    would corrupt them before the exact mod-num_freq phase."""
    n, num_groups, num_freq = 512, 4, 1 << 16
    p = jnp.asarray(rng.uniform(0.1, 0.9, n), jnp.float32)
    v = jnp.asarray(rng.integers(0, 1 << 28, n), jnp.int32)
    g = jnp.asarray(rng.integers(0, num_groups, n), jnp.int32)
    st = uda.accumulate({"cf": uda.SumCF(num_freq, freq_cnt=128)}, p, v, g,
                        max_groups=num_groups, kernel="pallas")["cf"]
    la_r, _ = ref.group_logcf_ref(jnp.asarray(p, jnp.float64),
                                  jnp.asarray(v, jnp.int64), g,
                                  num_groups, num_freq, freq_cnt=128)
    np.testing.assert_allclose(np.asarray(st.log_abs), np.asarray(la_r),
                               atol=2e-3)


def test_groupagg_exact_rejects_minmax():
    t = Table.from_columns({"g": jnp.zeros((4,), jnp.int32),
                            "v": jnp.ones((4,), jnp.int32)},
                           prob=jnp.full((4,), 0.5))
    plan = GroupAgg(Scan("t"), ("g",), "v", "MIN", 2, "exact", num_freq=8)
    with pytest.raises(ValueError, match="SUM/COUNT"):
        compile_plan(plan)({"t": t})


def test_groupagg_exact_requires_num_freq():
    t = Table.from_columns({"g": jnp.zeros((4,), jnp.int32),
                            "v": jnp.ones((4,), jnp.int32)},
                           prob=jnp.full((4,), 0.5))
    plan = GroupAgg(Scan("t"), ("g",), "v", "SUM", 2, "exact")
    with pytest.raises(ValueError, match="num_freq"):
        compile_plan(plan)({"t": t})


def test_groupagg_unknown_method_error_names_exact():
    plan = GroupAgg(Scan("t"), ("g",), "v", "SUM", 2, "bogus")
    t = Table.from_columns({"g": jnp.zeros((4,), jnp.int32),
                            "v": jnp.ones((4,), jnp.int32)},
                           prob=jnp.full((4,), 0.5))
    with pytest.raises(ValueError, match="'normal', 'cumulants' or 'exact'"):
        compile_plan(plan)({"t": t})


@pytest.mark.kernels
def test_group_logcf_traced_freq_lo_bit_equal(rng):
    """A traced freq_lo (the model-axis rank window) must be BIT-equal to
    the static-slab call AND to the matching columns of the full run —
    and different windows must share ONE compiled kernel (the traced
    scalar rides in SMEM, so the executable is window-independent)."""
    from repro.kernels.group_cf import _group_logcf
    n, num_groups, num_freq = 600, 10, 320
    p, v, g = _inputs(rng, n, num_groups)
    full = group_cf.group_logcf(p, v, g, num_groups=num_groups,
                                num_freq=num_freq)
    for lo, cnt in ((0, 128), (128, 128), (256, 64)):
        static = group_cf.group_logcf(p, v, g, num_groups=num_groups,
                                      num_freq=num_freq, freq_lo=lo,
                                      freq_cnt=cnt)
        traced = group_cf.group_logcf(p, v, g, num_groups=num_groups,
                                      num_freq=num_freq,
                                      freq_lo=jnp.int32(lo), freq_cnt=cnt)
        for a, b, c in zip(traced, static, full):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(c[:, lo:lo + cnt]))
    before = _group_logcf._cache_size()
    group_cf.group_logcf(p, v, g, num_groups=num_groups, num_freq=num_freq,
                         freq_lo=jnp.int32(64), freq_cnt=128)
    assert _group_logcf._cache_size() == before, \
        "a new traced window re-compiled the kernel"


def test_traced_freq_lo_requires_freq_cnt(rng):
    p, v, g = _inputs(rng, 32, 2)
    with pytest.raises(ValueError, match="freq_cnt"):
        group_cf.group_logcf(p, v, g, num_groups=2, num_freq=64,
                             freq_lo=jnp.int32(8))


# --------------------------------------------------- mesh equivalence
# (conftest `mesh_check`: one subprocess per device count)
_EXACT_MESH = """
import jax, jax.numpy as jnp
from repro.core import enable_x64
enable_x64()
from repro.db import tpch
from repro.db.plans import GroupAgg, Scan, compile_plan
mesh = jax.make_mesh((2,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
db = tpch.generate(n_orders=64, seed=5)
tables = db.tables()
plan = GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity", "SUM",
                128, "exact", num_freq=256,
                extra=(("cnt", "", "COUNT", "exact"),))
ref = compile_plan(plan, None)(tables)
for got in (compile_plan(plan, mesh)(tables),
            compile_plan(plan, mesh, cf_budget_elems=1 << 12)(tables)):
    for k in ("exact", "cnt", "confidence"):
        d = float(jnp.max(jnp.abs(jnp.asarray(ref[k]) -
                                  jnp.asarray(got[k]))))
        assert d < 1e-9, (k, d)
print("OK")
"""

_EXACT_MODEL_SHARDED = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import enable_x64
enable_x64()
from repro.db import tpch
from repro.db.plans import GroupAgg, Scan, compile_plan
db = tpch.generate(n_orders=64, seed=5)
tables = db.tables()
plan = GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity", "SUM",
                128, "exact", num_freq=256,
                extra=(("cnt", "", "COUNT", "exact"),))
ref = compile_plan(plan, None)(tables)
auto = (jax.sharding.AxisType.Auto,) * 2
m12 = jax.make_mesh((1, 2), ("data", "model"), axis_types=auto)
m22 = jax.make_mesh((2, 2), ("data", "model"), axis_types=auto)
runs = [
    ("1x2", compile_plan(plan, m12)),
    ("2x2", compile_plan(plan, m22)),
    ("1x2-slab", compile_plan(plan, m12,
                              cf_budget_elems=1 << 12)),
]
for name, fn in runs:
    got = fn(tables)
    for k in ("exact", "cnt", "confidence"):
        assert_biteq(name + "/" + k, ref[k], got[k], nan_equal=False)
print("BITEQ OK")
"""

MESH_CHECKS = {
    "test_groupagg_exact_mesh_equivalence": MeshCheck(2, _EXACT_MESH,
                                                      seconds=35),
    "test_groupagg_exact_model_sharded_bit_equal": MeshCheck(
        4, _EXACT_MODEL_SHARDED, seconds=105),
}


@pytest.mark.multidevice
def test_groupagg_exact_mesh_equivalence(mesh_check):
    """Exact GroupAgg on a 2-device mesh == single device, both unchunked
    and with a slab budget small enough to force multi-pass psum merges."""
    assert "OK" in mesh_check()


@pytest.mark.multidevice
def test_groupagg_exact_model_sharded_bit_equal(mesh_check):
    """Exact GroupAgg with a >1-sized MODEL axis — each frequency slab
    split into per-rank windows via the traced-freq_lo kernel path and
    reassembled by one tiled all-gather — must be BIT-identical to the
    unsharded compile, on a pure-model 1x2 mesh, a 2x2 data x model mesh,
    and under a slab budget that forces multi-pass accumulation."""
    assert "BITEQ OK" in mesh_check()


def test_presorted_operands_bit_equal():
    """group_logcf with hoisted presort_operands == the self-sorting call,
    bit for bit, across frequency slabs (the exact-CF slab loop reuses ONE
    prep for every slab)."""
    import numpy as np
    from repro.kernels import group_cf, ops as kops
    r = np.random.default_rng(0)
    n, G, F = 640, 24, 96
    p = jnp.asarray(r.uniform(0.01, 0.99, n), jnp.float32)
    v = jnp.asarray(r.integers(0, 4, n), jnp.int32)
    g = jnp.asarray(r.integers(0, G, n), jnp.int32)
    operands = group_cf.presort_operands(p, v, g, F)
    for lo, cnt in ((0, F), (0, 32), (32, 32), (64, F - 64)):
        la_ref, an_ref = group_cf.group_logcf(
            p, v, g, num_groups=G, num_freq=F, freq_lo=lo, freq_cnt=cnt)
        la, an = group_cf.group_logcf(
            p, v, g, num_groups=G, num_freq=F, freq_lo=lo, freq_cnt=cnt,
            operands=operands)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(la_ref))
        np.testing.assert_array_equal(np.asarray(an), np.asarray(an_ref))
    # the dispatch wrapper threads operands through to the kernel too
    la, an = kops.group_logcf(p, v, g, G, F, use_kernel=True,
                              operands=kops.presort_group_operands(p, v, g,
                                                                   F))
    la_ref, an_ref = kops.group_logcf(p, v, g, G, F, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(la_ref))


def test_cf_chunk_operands_planner_hoist():
    """uda.cf_chunk_operands mirrors the kernel dispatch guards (None when
    the kernel would not run) and its operands reproduce the accumulate
    result bit for bit when forced through the kernel path."""
    import numpy as np
    from repro.core import uda
    r = np.random.default_rng(1)
    n, G, F = 1024, 8, 64
    p = jnp.asarray(r.uniform(0.01, 0.99, n), jnp.float32)
    v = jnp.asarray(r.integers(0, 3, n), jnp.int32)
    g = jnp.asarray(r.integers(0, G, n), jnp.int32)
    # CPU backend ('auto' => no Pallas dispatch): must decline the hoist
    assert uda.cf_chunk_operands(F, p, v, g, max_groups=G,
                                 num_chunks=4) is None \
        or jax.default_backend() == "tpu"
    ops4 = uda.cf_chunk_operands(F, p, v, g, max_groups=G, num_chunks=4,
                                 kernel="pallas")
    assert ops4 is not None and len(ops4) == 4
    udas = {"cf": uda.SumCF(F)}
    a = uda.accumulate_chunked(udas, p, v, g, max_groups=G, num_chunks=4,
                               kernel="pallas")["cf"]
    b = uda.accumulate_chunked(udas, p, v, g, max_groups=G, num_chunks=4,
                               kernel="pallas",
                               cf_operands={"cf": ops4})["cf"]
    np.testing.assert_array_equal(np.asarray(a.log_abs),
                                  np.asarray(b.log_abs))
    np.testing.assert_array_equal(np.asarray(a.angle), np.asarray(b.angle))
    # ragged columns (chunks don't divide) decline rather than misalign
    assert uda.cf_chunk_operands(F, p, v, g, max_groups=G,
                                 num_chunks=3, kernel="pallas") is None
