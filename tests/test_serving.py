"""The query-serving layer: structural plan keys, the bounded LRU plan
cache, and batched parameterized sweeps (db/serving.py, db/plans.py).

The contracts under test:

* ``plan_key`` is STRUCTURAL — two independently constructed, identical
  plans share a key (through lambdas: bytecode + captured constants),
  explicit-default arguments don't change it, and different captured
  constants do;
* a plan-cache hit returns results BIT-IDENTICAL to the cold compile on
  every execution path (resident, streamed, mesh) — every comparison is
  exact equality, never allclose;
* compiling more distinct plans than the cache capacity EVICTS — the
  live-executable population stays flat (the accretion-segfault guard),
  for both the serving cache and the streamed executor's wave cache;
* a batched N-point sweep (default scan mode) is bit-equal per point to
  N sequential runs of the family's jitted executable, regardless of
  chunking.
"""
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import MeshCheck, assert_biteq
from repro.db import serving, tpch
from repro.db.plans import (GroupAgg, LRUCache, Scan, Select, compile_plan,
                            plan_key, plan_params, set_wave_cache_capacity,
                            wave_cache_info)
from repro.db.serving import PlanCache, QueryService

pytestmark = pytest.mark.serving


@pytest.fixture(autouse=True)
def _bounded_compile_cache():
    # Serving tests compile many distinct plans on purpose; keep the
    # single-process suite's compiler footprint flat afterwards.
    yield
    jax.clear_caches()


def _db():
    return tpch.generate(n_orders=48, lines_per_order=4, n_parts=24,
                         n_suppliers=8, n_customers=24, seed=0)


# ------------------------------------------------------ structural plan keys
class TestPlanKey:
    def test_fresh_identical_plans_share_key(self):
        # Every serving plan, constructed twice from scratch: the keys
        # must match even though the lambdas are distinct objects.
        a = tpch.serving_plans()
        b = tpch.serving_plans()
        for name in a:
            assert plan_key(a[name]) == plan_key(b[name]), name

    def test_explicit_defaults_share_key(self):
        # Golden against default-argument drift: passing the defaults
        # explicitly is the same plan.
        assert plan_key(tpch.q3_plan()) == plan_key(
            tpch.q3_plan(segment=1, max_groups=512, order_join_budget=None))
        assert plan_key(tpch.q18_plan()) == plan_key(
            tpch.q18_plan(qty_threshold=150.0, max_groups=2048))

    def test_keyword_order_shares_key(self):
        # Golden against field reordering at the construction site.
        a = GroupAgg(child=Scan("lineitem"), keys=("l_returnflag",),
                     value="l_quantity", agg="SUM", max_groups=8)
        b = GroupAgg(max_groups=8, agg="SUM", value="l_quantity",
                     keys=("l_returnflag",), child=Scan("lineitem"))
        assert plan_key(a) == plan_key(b)

    def test_captured_constants_differ(self):
        def sel(lim):
            return Select(Scan("lineitem"), lambda t: t["l_quantity"] < lim)

        assert plan_key(sel(10.0)) == plan_key(sel(10.0))
        assert plan_key(sel(10.0)) != plan_key(sel(11.0))

    def test_predicate_logic_differs(self):
        a = Select(Scan("lineitem"), lambda t: t["l_quantity"] < 10.0)
        b = Select(Scan("lineitem"), lambda t: t["l_quantity"] > 10.0)
        assert plan_key(a) != plan_key(b)

    def test_family_params_discovered(self):
        assert plan_params(tpch.q6_family()) == {"disc_lo", "disc_hi",
                                                 "qty_lim"}
        assert plan_params(tpch.q18_family()) == {"qty_threshold"}
        assert plan_params(tpch.q6_plan()) == set()


# ----------------------------------------------------------- LRU primitives
class TestLRUCache:
    def test_eviction_order_and_counters(self):
        dropped = []
        c = LRUCache(2, on_evict=dropped.append)
        c.put("a", 1)
        c.put("b", 2)
        assert c.get("a") == 1          # refresh a: b is now LRU
        c.put("c", 3)
        assert dropped == [2]
        assert c.get("b") is None
        assert c.get("a") == 1 and c.get("c") == 3
        info = c.info()
        assert info["size"] == 2 and info["evictions"] == 1
        assert info["hits"] == 3 and info["misses"] == 1

    def test_set_capacity_trims(self):
        dropped = []
        c = LRUCache(4, on_evict=dropped.append)
        for i in range(4):
            c.put(i, i)
        c.set_capacity(1)
        assert len(c) == 1 and dropped == [0, 1, 2]
        with pytest.raises(ValueError):
            c.set_capacity(0)

    def test_clear_runs_evict_hook(self):
        dropped = []
        c = LRUCache(4, on_evict=dropped.append)
        c.put("a", 1)
        c.put("b", 2)
        c.clear()
        assert sorted(dropped) == [1, 2] and len(c) == 0


# ------------------------------------------------- bounded wave-cache guard
@pytest.mark.outofcore
def test_wave_cache_bounded():
    """Compiling more distinct STREAMED plans than the wave-cache
    capacity keeps the cache flat and counts evictions (the unbounded
    `_wave_cache` accretion this PR removes)."""
    tables = _db().tables()
    old = set_wave_cache_capacity(3)
    try:
        base = wave_cache_info()["evictions"]
        for c in range(7):
            lim = float(10 + c)
            root = GroupAgg(
                Select(Scan("lineitem"),
                       (lambda t, lim=lim: t["l_quantity"] < lim)),
                ("l_returnflag",), "l_quantity", "SUM", 8)
            compile_plan(root, device_row_budget=64)(tables)
        info = wave_cache_info()
        assert info["size"] <= 3
        assert info["evictions"] - base >= 4
    finally:
        set_wave_cache_capacity(old)


def test_plan_cache_bounded_and_entries_die():
    """2x-capacity distinct plans through the serving cache: size stays
    at capacity and the evicted entries (holding the compiled
    executables) become garbage."""
    tables = _db().tables()
    svc = QueryService(tables, capacity=2)
    refs = []
    for c in range(4):
        lim = float(10 + c)
        root = GroupAgg(
            Select(Scan("lineitem"),
                   (lambda t, lim=lim: t["l_quantity"] < lim)),
            ("l_returnflag",), "l_quantity", "SUM", 8)
        svc.submit(root)
        entry, hit = svc.cache.entry(root, None, jit=True)
        assert hit
        refs.append(weakref.ref(entry))
    info = svc.cache.info()
    assert info["size"] == 2 and info["evictions"] >= 2
    del entry
    gc.collect()
    dead = sum(r() is None for r in refs)
    assert dead >= 2, f"evicted cache entries still alive ({dead}/4 dead)"


# ------------------------------------------------ cache-hit bit-equality
class TestCacheHitBiteq:
    def test_resident_all_queries(self):
        tables = _db().tables()
        svc = QueryService(tables, capacity=16)
        plans_a = tpch.serving_plans()
        cold = {}
        for name, p in plans_a.items():
            out, info = svc.submit(p)
            assert not info["hit"], name
            cold[name] = out
        # Fresh plan OBJECTS on the warm pass: hits must be structural.
        for name, p in tpch.serving_plans().items():
            out, info = svc.submit(p)
            assert info["hit"], name
            assert_biteq(name, cold[name], out)

    @pytest.mark.outofcore
    def test_streamed(self):
        tables = _db().tables()
        svc = QueryService(tables, capacity=16, device_row_budget=64)
        cold, i0 = svc.submit(tpch.q1_plan())
        warm, i1 = svc.submit(tpch.q1_plan())
        assert not i0["hit"] and i1["hit"]
        assert_biteq("q1-streamed", cold, warm)

    @pytest.mark.multidevice
    def test_mesh(self, mesh_check):
        assert "BITEQ OK" in mesh_check()


# ----------------------------------------------------- parameterized sweeps
class TestSweep:
    def _batches(self, n):
        return [
            ("q6", tpch.q6_family(),
             dict(disc_lo=jnp.full((n,), 5.0), disc_hi=jnp.full((n,), 7.0),
                  qty_lim=jnp.arange(1.0, n + 1.0))),
            ("q18", tpch.q18_family(),
             dict(qty_threshold=jnp.linspace(100.0, 240.0, n))),
        ]

    def test_sweep_biteq_sequential(self):
        tables = _db().tables()
        svc = QueryService(tables, capacity=16)
        n = 6
        for name, fam, batch in self._batches(n):
            out, info = svc.sweep(fam, batch)
            assert info["points"] == n and info["launches"] == 1
            seq = jax.jit(svc.cache.entry(fam, None, jit=False)[0].fn)
            for i in range(n):
                point = {k: v[i] for k, v in batch.items()}
                assert_biteq(f"{name}[{i}]",
                              seq(tables, point),
                              jax.tree.map(lambda l: l[i], out))

    def test_sweep_chunked_biteq(self):
        tables = _db().tables()
        whole = QueryService(tables, capacity=16)
        chunked = QueryService(tables, capacity=16, batch_row_budget=2000)
        for name, fam, batch in self._batches(6):
            a, ia = whole.sweep(fam, batch)
            b, ib = chunked.sweep(fam, batch)
            assert ia["launches"] == 1 and ib["launches"] > 1
            assert_biteq(name, a, b)

    def test_resweep_hits_cache(self):
        tables = _db().tables()
        svc = QueryService(tables, capacity=16)
        _, fam, batch = self._batches(4)[0]
        _, i0 = svc.sweep(fam, batch)
        assert not i0["hit"]
        # different N, fresh plan object: still one executable
        _, fam2, batch2 = self._batches(8)[0]
        _, i1 = svc.sweep(fam2, batch2)
        assert i1["hit"]

    def test_vmap_mode_close(self):
        # vmap trades bit-equality for lane parallelism: allclose only.
        tables = _db().tables()
        scan = QueryService(tables, capacity=16)
        vmap = QueryService(tables, capacity=16, sweep_mode="vmap")
        _, fam, batch = self._batches(4)[0]
        a, _ = scan.sweep(fam, batch)
        b, _ = vmap.sweep(fam, batch)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-5, atol=1e-7)

    def test_sweep_validation(self):
        tables = _db().tables()
        svc = QueryService(tables, capacity=16)
        fam = tpch.q6_family()
        good = dict(disc_lo=jnp.zeros((4,)), disc_hi=jnp.ones((4,)),
                    qty_lim=jnp.ones((4,)))
        with pytest.raises(ValueError, match="param_batch"):
            svc.sweep(fam, {k: good[k] for k in ("disc_lo", "disc_hi")})
        with pytest.raises(ValueError, match="param_batch"):
            svc.sweep(fam, {**good, "qty_lim": jnp.ones((3,))})
        with pytest.raises(ValueError, match="parameterized"):
            svc.sweep(tpch.q6_plan(), good)
        with pytest.raises(NotImplementedError):
            svc.sweep(fam, good, device_row_budget=64)
        with pytest.raises(ValueError, match="sweep_mode"):
            QueryService(tables, sweep_mode="loop")

    def test_submit_param_validation(self):
        tables = _db().tables()
        fn = compile_plan(tpch.q6_family())
        with pytest.raises(ValueError, match="parameters mismatch"):
            fn(tables)                                # all params missing
        with pytest.raises(ValueError, match="parameters mismatch"):
            fn(tables, dict(disc_lo=5.0, disc_hi=7.0, qty_lim=24.0,
                            extra=1.0))


# ------------------------------------------------------------ service stats
def test_serving_stats_counters():
    tables = _db().tables()
    svc = QueryService(tables, capacity=16)
    p = tpch.q6_plan()
    svc.submit(p)
    svc.submit(p)
    svc.sweep(tpch.q6_family(),
              dict(disc_lo=jnp.full((4,), 5.0), disc_hi=jnp.full((4,), 7.0),
                   qty_lim=jnp.arange(1.0, 5.0)))
    s = svc.stats.as_dict()
    # requests counts submits AND sweeps; the sweep's first compile is a
    # miss, the second submit a hit.
    assert s["requests"] == 3 and s["cache_hits"] == 1
    assert s["batched_requests"] == 1 and s["batched_points"] == 4
    assert s["hit_rate"] == pytest.approx(1 / 3, abs=1e-3)


# ------------------------------------------------ the multi-device check
# (conftest `mesh_check`: one subprocess per device count)
MESH_CHECKS = {"test_mesh": MeshCheck(2, """
import jax, numpy as np
from repro.core import enable_x64
enable_x64()
from repro.db import tpch
from repro.db.serving import QueryService
mesh = jax.make_mesh((2,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
db = tpch.generate(n_orders=48, lines_per_order=4, n_parts=24,
                   n_suppliers=8, n_customers=24, seed=0)
svc = QueryService(db.tables(), mesh, capacity=16)
for name, plan in tpch.serving_plans().items():
    cold, i0 = svc.submit(plan)
    assert not i0["hit"], name
    warm, i1 = svc.submit(plan)
    assert i1["hit"], name
    for a, b in zip(jax.tree.leaves(cold), jax.tree.leaves(warm)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), name
print("BITEQ OK")
""", seconds=10)}
