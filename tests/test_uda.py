"""The grouped segment-UDA subsystem vs the 2^n possible-worlds oracle.

Every registered UDA is checked grouped, masked, and with its state merged
in two halves (any partition + any merge tree must give the same final
distribution — that's what makes the sharded execution valid), plus
BIT-EQUAL compile_plan(mesh) == compile_plan(None) checks on 2- and
4-device CPU meshes through the conftest mesh-equivalence harness."""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import MeshCheck, equiv_check
from repro.core import uda
from repro.core.config import default_float
from repro.core.pgf import possible_worlds_pgf

G = 4


def _data(seed, n=14):
    r = np.random.default_rng(seed)
    p = r.uniform(0.05, 0.95, n)
    v = r.integers(1, 8, n).astype(float)
    g = r.integers(0, G, n)
    mask = r.uniform(0, 1, n) > 0.25
    return p, v, g, mask


def _states(u, p, v, g):
    """(one-shot state, merged-in-two-halves state) through the canonical
    accumulation loop."""
    dt = default_float()
    pj, vj, gj = (jnp.asarray(p, dt), jnp.asarray(v, dt), jnp.asarray(g))
    one = uda.accumulate({"u": u}, pj, vj, gj, max_groups=G)["u"]
    h = p.shape[0] // 2
    a = uda.accumulate({"u": u}, pj[:h], vj[:h], gj[:h], max_groups=G)["u"]
    b = uda.accumulate({"u": u}, pj[h:], vj[h:], gj[h:], max_groups=G)["u"]
    return one, u.merge(a, b)


def _oracles(p, v, g, mask, monoid):
    p = np.where(mask, p, 0.0)
    return {gi: possible_worlds_pgf(p[g == gi], v[g == gi], monoid)
            for gi in range(G)}


def _moment(oracle, k, mu=0.0):
    return sum(pr * (x - mu) ** k for x, pr in oracle.items()
               if np.isfinite(x))


@pytest.mark.parametrize("seed", range(3))
def test_atleastone_parity(seed):
    p, v, g, mask = _data(seed)
    pm = np.where(mask, p, 0.0)
    for st in _states(uda.AtLeastOne(), pm, v, g):
        conf = np.asarray(uda.AtLeastOne().finalize(st))
        for gi, oracle in _oracles(p, v, g, mask, "COUNT").items():
            want = 1.0 - oracle.get(0.0, 0.0)
            assert conf[gi] == pytest.approx(want, abs=1e-12), (seed, gi)


@pytest.mark.parametrize("seed", range(3))
def test_normal_parity(seed):
    p, v, g, mask = _data(seed)
    pm = np.where(mask, p, 0.0)
    u = uda.SumNormal()
    for st in _states(u, pm, v, g):
        mu, var = map(np.asarray, u.finalize(st))
        for gi, oracle in _oracles(p, v, g, mask, "SUM").items():
            m1 = _moment(oracle, 1)
            assert mu[gi] == pytest.approx(m1, abs=1e-10)
            assert var[gi] == pytest.approx(_moment(oracle, 2, m1), abs=1e-9)


@pytest.mark.parametrize("seed", range(3))
def test_cumulants_parity(seed):
    p, v, g, mask = _data(seed)
    pm = np.where(mask, p, 0.0)
    u = uda.SumCumulants(6)
    for st in _states(u, pm, v, g):
        terms = np.asarray(u.finalize(st))
        for gi, oracle in _oracles(p, v, g, mask, "SUM").items():
            m1 = _moment(oracle, 1)
            m2 = _moment(oracle, 2, m1)
            m3 = _moment(oracle, 3, m1)      # 3rd central == 3rd cumulant
            assert terms[gi, 0] == pytest.approx(m1, abs=1e-10)
            assert terms[gi, 1] == pytest.approx(m2, abs=1e-9)
            assert terms[gi, 2] == pytest.approx(m3, abs=1e-9)


@pytest.mark.parametrize("seed", range(3))
def test_cf_parity(seed):
    p, v, g, mask = _data(seed)
    pm = np.where(mask, p, 0.0)
    num_freq = int(v.sum()) + 1
    u = uda.SumCF(num_freq)
    for st in _states(u, pm, v, g):
        coeffs = np.asarray(u.finalize(st))
        for gi, oracle in _oracles(p, v, g, mask, "SUM").items():
            for outcome, pr in oracle.items():
                assert coeffs[gi, int(outcome)] == pytest.approx(
                    pr, abs=1e-10), (seed, gi, outcome)


@pytest.mark.parametrize("name,monoid", [("min", "MIN"), ("max", "MAX")])
@pytest.mark.parametrize("seed", range(3))
def test_minmax_parity(name, monoid, seed):
    p, v, g, mask = _data(seed)
    pm = np.where(mask, p, 0.0)
    u = uda.make(name, kappa=16)
    for st in _states(u, pm, v, g):
        vals, mass, p_tail = map(np.asarray, u.finalize(st))
        pe = np.asarray(u.p_empty(st))
        for gi, oracle in _oracles(p, v, g, mask, monoid).items():
            for outcome, pr in oracle.items():
                if np.isinf(outcome):
                    assert p_tail[gi] == pytest.approx(pr, abs=1e-12)
                    assert pe[gi] == pytest.approx(pr, abs=1e-12)
                else:
                    got = mass[gi][vals[gi] == outcome].sum()
                    assert got == pytest.approx(pr, abs=1e-12), \
                        (name, seed, gi, outcome)


def test_minmax_merge_run_fold_keeps_tail_tight():
    """§V-B.2 under heavy duplication: the SAME kappa values in both
    merge inputs used to occupy 2x the buffer slots (split slots competed
    for capacity and inflated the truncation tail); the in-network run
    fold collapses them, so the merged state keeps the full support and
    the tail stays exactly the beyond-support mass."""
    k = 4
    u = uda.MinMax(kappa=k)
    p = jnp.full((6,), 0.5, default_float())
    v = jnp.asarray([0, 1, 2, 3, 4, 5], default_float())
    a = uda.accumulate({"u": u}, p, v, None, max_groups=1)["u"]
    b = uda.accumulate({"u": u}, p, v, None, max_groups=1)["u"]
    st = u.merge(a, b)
    vals = np.asarray(st.values[0])
    fin = vals[np.isfinite(vals)]
    assert fin.size == np.unique(fin).size == k     # runs folded, full k
    np.testing.assert_allclose(np.asarray(st.log_none[0]),
                               2 * np.asarray(a.log_none[0]), rtol=1e-12)
    _, mass, p_tail = u.finalize(st)
    # tail = P(min >= 4) over BOTH copies = (1-p)^(2 tuples per value * 4)
    assert float(p_tail[0]) == pytest.approx(0.25 ** k, abs=1e-12)
    assert float(mass.sum() + p_tail[0]) == pytest.approx(1.0, abs=1e-12)


def test_minmax_merge_with_init_is_identity():
    """merge(init, x) == x bitwise — the invariant the partitioned
    (HashPartitioned) merge leans on: non-owner shards contribute exact
    init states, so the cross-owner fold must preserve the owner's state
    bit for bit (db.distributed.partitioned_merge)."""
    import jax
    r = np.random.default_rng(2)
    u = uda.MinMax(kappa=8)
    p = jnp.asarray(r.uniform(0.05, 0.95, 40), default_float())
    v = jnp.asarray(r.integers(1, 12, 40), default_float())
    g = jnp.asarray(r.integers(0, G, 40))
    x = uda.accumulate({"u": u}, p, v, g, max_groups=G)["u"]
    init = u.init(G, default_float())
    for m in (u.merge(init, x), u.merge(x, init)):
        for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(m)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_minmax_truncation_tail():
    """kappa smaller than the support: dropped mass lands in the tail and
    the kept+tail masses stay a distribution (§V-B.2)."""
    n = 10
    p = np.full(n, 0.5)
    v = np.arange(n, dtype=float)
    u = uda.MinMax(kappa=4)
    st = uda.accumulate({"u": u}, jnp.asarray(p, default_float()),
                        jnp.asarray(v, default_float()), None,
                        max_groups=1)["u"]
    _, mass, p_tail = u.finalize(st)
    assert float(np.asarray(mass).sum() + p_tail[0]) == pytest.approx(1.0)
    assert float(p_tail[0]) == pytest.approx(0.5 ** 4)
    assert float(u.p_empty(st)[0]) == pytest.approx(0.5 ** n)


def test_scalar_is_one_group(rng):
    """gids=None (the scalar facade's path) == explicit single group."""
    p = jnp.asarray(rng.uniform(0.1, 0.9, 20), default_float())
    v = jnp.asarray(rng.integers(1, 5, 20), default_float())
    u = uda.SumCF(int(np.asarray(v).sum()) + 1)
    a = uda.accumulate({"u": u}, p, v, None, max_groups=1)["u"]
    b = uda.accumulate({"u": u}, p, v, jnp.zeros((20,), jnp.int32),
                       max_groups=1)["u"]
    np.testing.assert_allclose(np.asarray(a.log_abs), np.asarray(b.log_abs),
                               atol=1e-12)


def test_every_registered_uda_constructs():
    import jax
    args = {"cf": dict(num_freq=8), "count_cf": dict(capacity=7)}
    for name in uda.REGISTRY:
        u = uda.make(name, **args.get(name, {}))
        st = u.init(3)
        for leaf in jax.tree.leaves(st):
            assert leaf.shape[0] == 3, name     # vectorised over groups
        m = u.merge(st, st)                     # merge preserves shapes
        assert jax.tree.map(jnp.shape, m) == jax.tree.map(jnp.shape, st)


# --------------------------------------------------- mesh-aware compilation
_MESH_EQUIVALENCE = """
db = tpch.generate(n_orders=64, seed=5)
tables = db.tables()
plans = {
    "normal": GroupAgg(Scan("lineitem"), ("l_returnflag", "l_linestatus"),
                       "l_quantity", "SUM", 8, "normal",
                       extra=(("c", "l_quantity", "SUM", "cumulants"),
                              ("n", "", "COUNT", "normal"))),
    "exact": GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity",
                      "SUM", 128, "exact", num_freq=256),
    "min": GroupAgg(Scan("lineitem"), ("l_returnflag",), "l_quantity",
                    "MIN", 8, kappa=64),
    "max": GroupAgg(Scan("lineitem"), ("l_returnflag",), "l_quantity",
                    "MAX", 8, kappa=64),
    "reweight": ReweightGreater(Scan("lineitem"), ("l_orderkey",),
                                "l_quantity", "", 128, threshold=80.0),
}
pairs = [(name, compile_plan(p, None)(tables), compile_plan(p, mesh)(tables))
         for name, p in plans.items()]
"""


@pytest.mark.multidevice
def test_compile_plan_mesh_equivalence(mesh_check):
    """compile_plan(root, mesh) is BIT-EQUAL to compile_plan(root) on a
    2-device CPU mesh, across GroupAgg methods, MIN/MAX, and
    ReweightGreater (the sharded frontend's canonical-chunk fold tree)."""
    assert "BITEQ OK" in mesh_check()


_SECOND_RUN_COMPILES_NOTHING = """
enable_x64()
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: compiles.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)
tables = tpch.generate(n_orders=64, seed=5).tables()
plan = lambda: GroupAgg(Scan("lineitem"), ("l_returnflag",), "l_quantity",
                        "SUM", 24, "normal",
                        extra=(("c", "l_quantity", "SUM", "cumulants"),))
for m in (mesh, None):
    fn = compile_plan(plan(), m)
    first = fn(tables)
    n = len(compiles)
    assert n > 0, m
    assert_biteq("again", first, fn(tables), nan_equal=False)
    assert_biteq("recompiled", first, compile_plan(plan(), m)(tables),
                 nan_equal=False)
    assert len(compiles) == n, (m, len(compiles) - n)
print("NO RECOMPILES OK")
"""


@pytest.mark.multidevice
def test_second_eager_run_compiles_nothing(mesh_check):
    """An eager run on a 2-device mesh (and on one device) compiles each
    of its programs once: running the plan again — by the same compiled
    function or by a fresh compile of an equal plan — compiles nothing,
    and answers bit for bit the same."""
    assert "NO RECOMPILES OK" in mesh_check()


_4DEV_AND_JIT = """
db = tpch.generate(n_orders=64, seed=5)
tables = db.tables()
plan = GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity", "SUM", 128,
                "normal", extra=(("c", "l_quantity", "SUM", "cumulants"),))
pairs = [
    ("eager", compile_plan(plan, None)(tables),
     compile_plan(plan, mesh)(tables)),
    ("jit", jax.jit(compile_plan(plan, None))(tables),
     jax.jit(compile_plan(plan, mesh))(tables)),
]
"""


@pytest.mark.multidevice
def test_compile_plan_4dev_and_jit_bit_equal(mesh_check):
    """The determinism contract holds for any power-of-two shard count
    dividing the canonical chunk grid (here 4), and under jit (comparing
    jitted against jitted — XLA fusion differs between jit and eager, but
    sharding never does)."""
    assert "BITEQ OK" in mesh_check()


# ------------------------------------------- generalized canonical fold
def test_tree_fold_pow2_base_plus_sequential_tail():
    """The fixed tree shape: balanced pairwise over the largest pow2
    prefix, then a sequential left fold of the tail — checked structurally
    with a symbolic merge."""
    class Sym(uda.UDA):
        def merge(self, a, b):
            return f"({a}+{b})"

    u = Sym()
    assert uda.tree_fold(u, ["a"]) == "a"
    assert uda.tree_fold(u, list("abcd")) == "((a+b)+(c+d))"
    assert uda.tree_fold(u, list("abcde")) == "(((a+b)+(c+d))+e)"
    assert uda.tree_fold(u, list("abcdef")) == "((((a+b)+(c+d))+e)+f)"
    assert uda.tree_fold(u, list("abc")) == "((a+b)+c)"
    with pytest.raises(ValueError):
        uda.tree_fold(u, [])


@pytest.mark.parametrize("num_chunks", [2, 3, 5, 6, 8])
def test_accumulate_chunk_states_fold_matches_chunked(num_chunks):
    """accumulate_chunked == tree_fold over accumulate_chunk_states, bit
    for bit, for any chunk count (the sharded frontend composes the two
    across shards) — and stays allclose to the unchunked accumulate."""
    import jax
    r = np.random.default_rng(3)
    n = 30
    p = jnp.asarray(r.uniform(0.05, 0.95, n), default_float())
    v = jnp.asarray(r.integers(1, 6, n), default_float())
    g = jnp.asarray(r.integers(0, G, n))
    udas = {"n": uda.SumNormal(), "c": uda.AtLeastOne()}
    folded = uda.accumulate_chunked(udas, p, v, g, max_groups=G,
                                    num_chunks=num_chunks)
    parts = uda.accumulate_chunk_states(udas, p, v, g, max_groups=G,
                                        num_chunks=num_chunks)
    assert len(parts) == num_chunks
    for name, u in udas.items():
        refold = uda.tree_fold(u, [q[name] for q in parts])
        for a, b in zip(jax.tree.leaves(folded[name]),
                        jax.tree.leaves(refold)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    flat = uda.accumulate(udas, p, v, g, max_groups=G)
    np.testing.assert_allclose(np.asarray(folded["n"].terms),
                               np.asarray(flat["n"].terms), rtol=1e-12)


_SUMCF_MODEL_WINDOW = """
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import enable_x64, uda
enable_x64()
mesh = jax.make_mesh((2,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
r = np.random.default_rng(0)
n, G, F = 96, 4, 64
p = jnp.asarray(r.uniform(0.05, 0.95, n))
v = jnp.asarray(r.integers(1, 8, n))
g = jnp.asarray(r.integers(0, G, n), jnp.int32)
full = uda.SumCF(F)
want = full.accumulate_full(full.init(G), p, v, g, G)

f_loc = F // 2
@partial(shard_map, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
         check_vma=False)
def windowed(p, v, g):
    rank = jax.lax.axis_index("model")
    u = uda.SumCF(F, freq_lo=rank * f_loc, freq_cnt=f_loc)
    st = u.accumulate_full(u.init(G), p, v, g, G)
    return u.reduce_model(st, "model")
got = windowed(p, v, g)
for a, b in zip(want, got):
    assert a.shape == b.shape, (a.shape, b.shape)
    assert jnp.array_equal(a, b), float(jnp.max(jnp.abs(a - b)))
print("BITEQ OK")
"""


@pytest.mark.multidevice
def test_sumcf_model_window_reduce_model_bit_equal(mesh_check):
    """The model-axis frequency-split primitive, isolated: each model rank
    accumulates its SumCF window (traced freq_lo = rank * f_loc) over the
    SAME replicated tuples, ``reduce_model`` reassembles with one tiled
    all-gather — BIT-equal to the full-width accumulate (per-frequency CF
    columns are computed independently, so the split changes nothing)."""
    assert "BITEQ OK" in mesh_check()


_3DEV_NON_POW2 = """
db = tpch.generate(n_orders=64, seed=5)
tables = db.tables()
plan = GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity", "SUM", 128,
                "normal", extra=(("c", "l_quantity", "SUM", "cumulants"),))
mk = lambda mesh=None, **kw: compile_plan(plan, mesh, **kw)(tables)
pairs = [
    ("eager", mk(), mk(mesh)),
    ("jit", jax.jit(compile_plan(plan, None))(tables),
     jax.jit(compile_plan(plan, mesh))(tables)),
    ("chunks6", mk(canonical_chunks=6), mk(mesh, canonical_chunks=6)),
]
"""


@pytest.mark.multidevice
def test_compile_plan_3dev_non_pow2_bit_equal(mesh_check):
    """The determinism contract now covers shard counts that do NOT
    divide the canonical chunk grid: every chunk state is computed on one
    shard, gathered, and folded in the one fixed tree — 3 devices against
    the 8-chunk grid, eager and jit, plus a non-pow2 grid."""
    assert "BITEQ OK" in mesh_check()


# ------------------------------------------------ the multi-device checks
# (conftest `mesh_check`: one subprocess per device count)
MESH_CHECKS = {
    "test_compile_plan_mesh_equivalence": equiv_check(_MESH_EQUIVALENCE,
                                                      seconds=60),
    "test_second_eager_run_compiles_nothing": MeshCheck(
        2, _SECOND_RUN_COMPILES_NOTHING, seconds=5),
    "test_sumcf_model_window_reduce_model_bit_equal": MeshCheck(
        2, _SUMCF_MODEL_WINDOW, seconds=10),
    "test_compile_plan_4dev_and_jit_bit_equal": equiv_check(
        _4DEV_AND_JIT, devices=4, seconds=15),
    "test_compile_plan_3dev_non_pow2_bit_equal": equiv_check(
        _3DEV_NON_POW2, devices=3, seconds=35),
}
