"""TPC-H workload (paper §VIII): all query/mode cells + semantic checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import MeshCheck, equiv_check
from repro.db import operators as ops
from repro.db import tpch


@pytest.fixture(scope="module")
def db():
    return tpch.generate(n_orders=200, seed=7)


@pytest.mark.parametrize("qname", list(tpch.QUERIES))
@pytest.mark.parametrize("mode", tpch.MODES)
def test_all_query_mode_cells_run(db, qname, mode):
    out = tpch.QUERIES[qname](db, mode)
    for leaf in jax.tree.leaves(out):
        arr = np.asarray(leaf)
        assert not np.isnan(arr.astype(float)).any(), (qname, mode)


def test_q1_deterministic_matches_numpy(db):
    out = tpch.q1(db, "deterministic")
    li = db.lineitem
    mask = np.asarray(li.valid) & (np.asarray(li["l_shipdate"])
                                   <= tpch.DAY0_1995 + 500)
    rf = np.asarray(li["l_returnflag"])
    ls = np.asarray(li["l_linestatus"])
    qty = np.asarray(li["l_quantity"])
    # group codes sorted ascending; recompute the same grouping
    codes = rf * (1 << 20) + ls
    got_total = np.asarray(out["sum_qty"])[np.asarray(out["valid"])].sum()
    assert got_total == qty[mask].sum()


def test_q1_aggregate_mean_matches_deterministic_expectation(db):
    """E[SUM] over worlds == sum of p_i * v_i (per group)."""
    agg = tpch.q1(db, "aggregate")
    li = db.lineitem
    sel = ops.select(li, lambda t: t["l_shipdate"] <= tpch.DAY0_1995 + 500)
    ids, _, _ = ops.group_ids(sel, ["l_returnflag", "l_linestatus"], 8)
    p = np.asarray(sel.masked_prob())
    v = np.asarray(sel["l_quantity"])
    mu_want = np.bincount(np.asarray(ids), p * v, minlength=8)
    np.testing.assert_allclose(np.asarray(agg["qty"][0]), mu_want,
                               rtol=1e-10)


def test_q6_exact_vs_moment_vs_normal(db):
    out = tpch.q6(db, "aggregate", num_freq=1 << 12)
    mu, var = out["normal"]
    coeffs = np.asarray(out["exact_coeffs"])
    grid = np.arange(len(coeffs))
    mean_exact = float((coeffs * grid).sum())
    var_exact = float((coeffs * (grid - mean_exact) ** 2).sum())
    assert float(mu) == pytest.approx(mean_exact, rel=1e-6)
    assert float(var) == pytest.approx(var_exact, rel=1e-4)
    # moment path agrees on first two cumulants
    cum = np.asarray(out["cumulants"])
    assert cum[0] == pytest.approx(mean_exact, rel=1e-6)
    assert cum[1] == pytest.approx(var_exact, rel=1e-4)


def test_q18_reweight_is_probability(db):
    out = tpch.q18(db, "aggregate")
    p = np.asarray(out["p_qualifies"])[np.asarray(out["valid"])]
    assert ((p >= 0) & (p <= 1)).all()
    gc = tpch.q18(db, "group_confidence")
    c = np.asarray(gc["confidence"])[np.asarray(gc["valid"])]
    assert ((c >= 0) & (c <= 1 + 1e-9)).all()


def test_q20_full_plan_probabilities_valid(db):
    out = tpch.q20(db, "aggregate")
    p = np.asarray(out["prob"])[np.asarray(out["valid"])]
    assert ((p >= -1e-12) & (p <= 1 + 1e-9)).all()
    conf = tpch.q20(db, "confidence")["confidence"]
    assert 0.0 <= float(conf) <= 1.0


def test_queries_scale_invariant_shapes():
    """Static capacities: output shapes don't depend on the data."""
    small = tpch.generate(n_orders=50, seed=1)
    big = tpch.generate(n_orders=400, seed=2)
    a = tpch.q1(small, "aggregate")
    b = tpch.q1(big, "aggregate")
    assert jax.tree.map(jnp.shape, a) == jax.tree.map(jnp.shape, b)


# --------------------------------------------------- sharded frontend
# The multi-device checks, one subprocess per device count (conftest
# `mesh_check`).  Every script draws the same database from `memo`, and
# the aggregate-mode reference of each query is computed once per process.
_DB = 'db = memo("db", lambda: tpch.generate(n_orders=48, seed=3))\n'


def _query_equiv(qname, seconds):
    return equiv_check(_DB + f"""
fn = tpch.QUERIES[{qname!r}]
pairs = [(mode, memo((mode, {qname!r}), lambda: fn(db, mode)),
          fn(db, mode, mesh=mesh))
         for mode in ("confidence", "group_confidence", "aggregate")]
""", seconds=seconds)


_ALL_QUERIES = _DB + """
shuffle = dict(join_gather_budget=4, copartition=False)
pairs = []
for qname, fn in sorted(tpch.QUERIES.items()):
    ref = memo(("aggregate", qname), lambda: fn(db, "aggregate"))
    pairs.append((qname, ref, fn(db, "aggregate", mesh=mesh)))
    pairs.append((qname + "/shuffle", ref,
                  fn(db, "aggregate", mesh=mesh, plan_opts=shuffle)))
"""

_COPARTITIONED = "enable_x64()\n" + _DB + """
from repro.db import distributed as dist
for qname, kwargs, opts in (("q3", dict(order_join_budget=4), {}),
                            ("q18", {}, dict(agg_shuffle_budget=4))):
    fn = tpch.QUERIES[qname]
    for mode in ("group_confidence", "aggregate"):
        ref = fn(db, mode, **kwargs)
        dist.reset_collective_counts()
        got = fn(db, mode, mesh=mesh, plan_opts=opts, **kwargs)
        c = dict(dist.COLLECTIVE_COUNTS)
        assert c.get("shuffle_back", 0) == 0, (qname, mode, c)
        assert c.get("merge_psum", 0) >= 1, (qname, mode, c)
        assert_biteq(qname + "/" + mode, ref, got, nan_equal=False)
print("FUSED OK")
"""

# `seconds`: measured on the CPU with four batches running at once.  With
# two devices the all-queries check finds its references in `memo`.
MESH_CHECKS = {
    **{f"test_query_mesh_bit_equal[{q}]": _query_equiv(q, seconds)
       for q, seconds in (("q1", 20), ("q18", 15), ("q20", 40), ("q3", 20),
                          ("q6", 5))},
    **{f"test_all_queries_mesh_bit_equal_any_shard_count[{d}]":
       equiv_check(_ALL_QUERIES, devices=d, seconds=seconds)
       for d, seconds in ((2, 60), (3, 145), (4, 130))},
    **{f"test_q3_q18_copartitioned_bit_equal_zero_roundtrips[{d}]":
       MeshCheck(d, _COPARTITIONED, seconds=15) for d in (2, 3, 4)},
}


@pytest.mark.multidevice
@pytest.mark.parametrize("qname", sorted(tpch.QUERIES))
def test_query_mesh_bit_equal(mesh_check, qname):
    """Each TPC-H plan through the sharded frontend on a 2-device mesh is
    BIT-IDENTICAL to the single-device compile, in every probabilistic
    mode (scan/join/group-id inputs sharded end-to-end)."""
    assert "BITEQ OK" in mesh_check()


@pytest.mark.multidevice
@pytest.mark.parametrize("devices", [2, 3, 4])
def test_all_queries_mesh_bit_equal_any_shard_count(mesh_check, devices):
    """The determinism contract on 2-, 3- and 4-shard meshes for ALL five
    TPC-H queries (aggregate mode), both with the default gather-join
    lowering and with a tiny join_gather_budget that lowers every
    over-budget FK join to a hash-exchange strategy — pinned to the
    unfused ShuffleJoin + shuffle-home + PartialAgg path with
    copartition=False (the cost model would otherwise fuse q3's GROUP
    BY; the fused pipeline has its own dedicated parity test)."""
    assert "BITEQ OK" in mesh_check()


@pytest.mark.multidevice
@pytest.mark.parametrize("devices", [2, 3, 4])
def test_q3_q18_copartitioned_bit_equal_zero_roundtrips(mesh_check, devices):
    """The fused shuffle -> aggregate pipeline on real queries: Q3 with a
    per-join budget that hash-exchanges the orders join (the GROUP BY
    keys on l_orderkey, so the cost model fuses it) and Q18 with
    ``agg_shuffle_budget`` repartitioning the lineitem aggregation — both
    BIT-IDENTICAL to the single-device compile on 2-, 3- and 4-shard
    meshes, with ZERO shuffle_back round-trips (asserted via the
    collective counter) and the one-psum partitioned merge."""
    assert "FUSED OK" in mesh_check()


def test_deterministic_db_gives_deterministic_answers():
    """p = 1 everywhere: aggregate mode's mean == deterministic answer,
    variance == 0 (the gamma-embedding sanity check, §IV-E)."""
    db1 = tpch.generate(n_orders=100, seed=3, prob_mode="ones")
    det = tpch.q1(db1, "deterministic")
    agg = tpch.q1(db1, "aggregate")
    np.testing.assert_allclose(np.asarray(agg["qty"][0]),
                               np.asarray(det["sum_qty"]).astype(float),
                               rtol=1e-12)
    np.testing.assert_allclose(np.asarray(agg["qty"][1]), 0.0, atol=1e-9)
