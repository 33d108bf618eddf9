"""Self-healing execution: ExecutionReport diagnostics, the RetryPolicy
escalation ladder, and fault-tolerant wave resume (db/plans.py,
db/report.py, testing/faults.py).

The contracts under test:

* a clean run's report is CLEAN (``issues() == {}``) and collecting it
  changes no result bit;
* every failure mode — exchange overflow, group-code-table overflow,
  MIN/MAX truncation tail mass, injected transfer faults — is DETECTED
  in the report (including through boolean outputs that swallow the NaN
  poison) and HEALED by ``run_plan``'s escalation within
  ``RetryPolicy.max_attempts``;
* the healed answer is BIT-IDENTICAL to a run launched with the final
  escalated parameters from the start (every comparison here is exact
  equality, never allclose);
* a fault-injected streamed run resumes from the last completed wave —
  completed waves are never re-streamed.
"""
import jax
import numpy as np
import pytest

from conftest import MeshCheck, assert_biteq
from repro.db import tpch
from repro.db.plans import (GroupAgg, RetryExhausted, RetryPolicy, Scan,
                            compile_plan, run_plan)
from repro.testing import faults

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _bounded_compile_cache():
    """Every retry attempt is a fresh compile at escalated parameters, so
    this module accretes far more live executables than any other test
    file; dropping them after each test keeps the single-process suite's
    compiler footprint flat for the files that run after."""
    yield
    jax.clear_caches()


def _db():
    # lineitem 192 rows (csz 24 on the 8-chunk grid): device_row_budget=64
    # streams only lineitem, same scale as tests/test_streamed.py.
    return tpch.generate(n_orders=48, lines_per_order=4, n_parts=24,
                         n_suppliers=8, n_customers=24, seed=0)


# ===================================================== clean-path report
def test_clean_run_report_is_clean_and_free():
    """Happy path: report collection flags nothing and changes no bit."""
    db = _db()
    root = GroupAgg(Scan("lineitem"), ("l_returnflag", "l_linestatus"),
                    "l_quantity", "SUM", 8, "normal")
    ref = compile_plan(root)(db.tables())
    out, rep = compile_plan(root, with_report=True)(db.tables())
    assert_biteq("clean", ref, out)
    assert rep.issues() == {} and rep.ok()
    assert rep.describe() == "clean"
    assert rep.overflow_total() == 0
    # one aggregation pass was diagnosed: confidence + sum states counted
    assert any(k.endswith(".sum") for k in rep.state_nan)
    assert all(int(v) == 0 for v in rep.state_nan.values())


def test_minmax_tail_mass_surfaced():
    """Satellite: the §V-B.2 truncation mass is a public per-group result
    (q18_topk) AND a report signal — exactly 0 when kappa covers every
    distinct value, positive when it truncates."""
    db = _db()
    wide = tpch.q18_topk(db, max_groups=64, kappa=50)   # 50 >= distinct qtys
    assert wide["tail_mass"].shape == (64,)
    np.testing.assert_array_equal(np.asarray(wide["tail_mass"]),
                                  np.zeros(64))
    narrow = tpch.q18_topk(db, max_groups=64, kappa=1)
    tails = np.asarray(narrow["tail_mass"])
    valid = np.asarray(narrow["valid"])
    assert (tails[valid] > 0).any()
    assert (tails >= 0).all() and (tails <= 1).all()
    root = GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity",
                    "MAX", 64, kappa=1)
    _, rep = compile_plan(root, with_report=True)(db.tables())
    assert rep.max_tail_mass() > 0 and "tail" in rep.issues()
    assert rep.issues(tail_tol=1.0) == {}        # tolerance gates it


def test_kappa_escalation_converges_bit_equal():
    """Tail mass above tolerance -> kappa doubles until exact; the healed
    answer equals an oversized-from-the-start run bit for bit."""
    db = _db()
    root = GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity",
                    "MAX", 64, kappa=1)
    out, rep = run_plan(root, db.tables(),
                        policy=RetryPolicy(max_attempts=6, tail_tol=0.0))
    scale = rep.final_params["kappa_scale"]
    assert rep.waves["attempts"] > 1 and scale > 1
    assert rep.max_tail_mass() == 0.0
    big = GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity",
                   "MAX", 64, kappa=scale)
    ref = compile_plan(big)(db.tables())
    assert_biteq("kappa", ref, out)


def test_group_overflow_escalation():
    """48 live orders into a 16-entry group-code table: the lost rows are
    counted (NaN never fires — the kept groups stay exact) and max_groups
    doubles until nothing is lost."""
    db = _db()
    root = GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity",
                    "SUM", 16, "normal")
    _, rep = compile_plan(root, with_report=True)(db.tables())
    assert "group_overflow" in rep.issues()
    out, rep2 = run_plan(root, db.tables(),
                         policy=RetryPolicy(max_attempts=4))
    assert rep2.issues() == {}
    scale = rep2.final_params["groups_scale"]
    assert scale >= 4                            # 16 -> 64 holds 48 groups
    ref = compile_plan(GroupAgg(Scan("lineitem"), ("l_orderkey",),
                                "l_quantity", "SUM", 16 * scale,
                                "normal"))(db.tables())
    assert_biteq("groups", ref, out)


def test_retry_exhausted_carries_report():
    db = _db()
    root = GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity",
                    "MAX", 64, kappa=1)
    with pytest.raises(RetryExhausted) as ei:
        run_plan(root, db.tables(), policy=RetryPolicy(max_attempts=1))
    assert ei.value.report is not None
    assert "tail" in ei.value.report.issues()


# ================================================= streamed wave resume
def test_streamed_transient_fault_resumes_bit_equal():
    """A transfer fault mid-wave re-ships ONLY the faulted wave: the log
    shows the same wave re-shipped, no completed wave re-streamed, and
    the result is bit-identical to the fault-free run — for the plain-agg
    (Q1) and exact-CF (Q6) streamed shapes."""
    db = _db()
    for qname, call in (("q1", lambda **kw: tpch.q1(db, "aggregate", **kw)),
                        ("q6", lambda **kw: tpch.q6(db, "aggregate",
                                                    num_freq=256, **kw))):
        ref = call()
        opts = dict(device_row_budget=64, stream_wave_chunks=1)
        # 8 waves per phase: occurrence 10 is phase B, wave 2
        with faults.inject(faults.FaultPlan(transfer_calls={10})) as fp:
            got = call(plan_opts=opts)
        assert fp.consumed(), qname
        assert_biteq(qname, ref, got)
        (fi, fw), = [(i, w) for i, w, _r, f in fp.log if f]
        after = [w for i, w, _r, f in fp.log if i > fi]
        # ship order is monotone within one wave loop; a later loop
        # (next phase / next slab pass) restarts at wave 0 — only judge
        # the loop the fault happened in.
        seg = []
        for w in after:
            if seg and w < seg[-1]:
                break
            seg.append(w)
        assert seg[0] == fw, (qname, "retry must re-ship the SAME wave")
        # monotone + starts at fw => no completed wave re-streamed


def test_streamed_fault_during_prefetch_no_double_file():
    """A fault on the DOUBLE-BUFFERED prefetch (wave w+1 ships while wave
    w computes): the wave loop retires w first, so the retry cannot file
    any chunk twice (ChunkStateAccumulator asserts exactly-once)."""
    db = _db()
    ref = tpch.q1(db, "aggregate")
    for occ in (1, 9, 12, 15):
        with faults.inject(faults.FaultPlan(transfer_calls={occ})) as fp:
            got = tpch.q1(db, "aggregate",
                          plan_opts=dict(device_row_budget=64,
                                         stream_wave_chunks=1))
        assert fp.consumed(), occ
        assert_biteq(f"q1/occ{occ}", ref, got)


def test_streamed_fault_exhausts_inloop_retries_annotated():
    """A persistent fault escapes after ``stream_wave_retries`` re-ships,
    annotated with the halved wave size for the controller."""
    db = _db()
    with faults.inject(faults.FaultPlan(transfer_rows_over=50)):
        with pytest.raises(faults.TransferFault) as ei:
            tpch.q1(db, "aggregate",
                    plan_opts=dict(device_row_budget=64,
                                   stream_wave_chunks=4))
    assert ei.value.wave_chunks == 2 and not ei.value.at_minimum


def test_wave_halving_retry():
    """Persistent too-big-transfer fault (96-row waves fail, 48-row waves
    pass): run_plan re-lowers with the halved wave and the result is
    bit-identical to the resident answer."""
    db = _db()
    root = GroupAgg(Scan("lineitem"), ("l_returnflag", "l_linestatus"),
                    "l_quantity", "SUM", 8, "normal")
    with faults.inject(faults.FaultPlan(transfer_rows_over=50)):
        out, rep = run_plan(root, db.tables(),
                            policy=RetryPolicy(max_attempts=4),
                            device_row_budget=64, stream_wave_chunks=4)
    assert rep.waves["attempts"] == 2
    assert rep.final_params["stream_wave_chunks"] == 2
    ref = compile_plan(root, device_row_budget=64)(db.tables())
    assert_biteq("halved", ref, out)


# ==================================== multi-device overflow + silent NaN
_OVERFLOW_SCRIPT = r"""
import jax, numpy as np, jax.numpy as jnp
from repro.core import enable_x64
enable_x64()
from repro.db import plans as L
from repro.db.table import Table

mesh = jax.make_mesh((3,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
n = 48
# probe keys == 1 (mod 3): every row routes to one owner, so slack 0.25
# buckets overflow under jit (traced keys keep the slack sizing).
left = Table({"k": jnp.asarray((np.arange(n) %% 30) * 3 + 1)},
             jnp.full((n,), 0.5), jnp.ones((n,), bool))
rk = np.arange(0, 200) * 3 + 1
right = Table({"rk": jnp.asarray(rk), "w": jnp.asarray(rk %% 7)},
              jnp.full((rk.size,), 0.9), jnp.ones((rk.size,), bool))
tables = {"left": left, "right": right}
join = L.FKJoin(L.Scan("left"), L.Scan("right"), "k", "rk", ("w",))
opts = dict(join_gather_budget=1, copartition=%(copart)s)
root = L.GroupAgg(join, ("k",), "w", "SUM", 64)

# 1. plain jit run: overflow fires and the report sees it
fn = jax.jit(L.compile_plan(root, mesh, with_report=True,
                            shuffle_slack=0.25, **opts))
out, rep = fn(tables)
assert rep.overflow_total() > 0, "expected an overflowing exchange"
assert "overflow" in rep.issues()
mu = np.asarray(out["sum"][0])
assert np.isnan(mu).any(), "NaN poison backstop must fire"

# 2. boolean-output regression: the NaN poison collapses to False in a
# boolean column, but the report still detects the overflow.
flag = L.Map(join, "flag", lambda t: t.prob > 0.5)
bfn = jax.jit(L.compile_plan(flag, mesh, with_report=True,
                             shuffle_slack=0.25, **opts))
bt, brep = bfn(tables)
fl = np.asarray(bt["flag"])
assert fl.dtype == np.bool_ and not fl.any(), "NaN collapsed silently"
assert brep.overflow_total() > 0, "report must catch the silent overflow"

# 3. an injected exchange fault surfaces from the shuffle trace
if not %(copart)s:
    from repro.testing import faults
    with faults.inject(faults.FaultPlan(exchange_calls={0})) as fpx:
        try:
            jax.jit(L.compile_plan(root, mesh, shuffle_slack=3.0,
                                   **opts))(tables)
            raise AssertionError("expected TransferFault")
        except faults.TransferFault:
            pass
    assert fpx.consumed()

# 4. RetryPolicy heals it within <=3 attempts, bit-equal to a run
# launched at the final escalated parameters.
out2, rep2 = L.run_plan(root, tables, mesh,
                        policy=L.RetryPolicy(max_attempts=3), jit=True,
                        shuffle_slack=0.25, **opts)
assert rep2.issues() == {}
assert rep2.waves["attempts"] <= 3
fp = rep2.final_params
fn3 = jax.jit(L.compile_plan(root, mesh, with_report=True,
                             shuffle_slack=fp["shuffle_slack"],
                             shuffle_bucket_floor=fp["shuffle_bucket_floor"],
                             **opts))
out3, rep3 = fn3(tables)
assert rep3.issues() == {}
assert_biteq("healed", out3, out2, nan_equal=False)
assert np.isfinite(np.asarray(out2["sum"][0])[np.asarray(out2["valid"])]).all()
print("OVERFLOW RETRY OK")
"""


_FUZZ_SCRIPT = r"""
import jax, numpy as np, jax.numpy as jnp
from repro.core import enable_x64
enable_x64()
from repro.db import plans as L
from repro.db.table import Table

mesh = jax.make_mesh((3,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

def trial(seed, copart):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(24, 72))
    # skewed keys: most rows hash to one owner mod 3
    owner = int(rng.integers(0, 3))
    base = rng.integers(0, 40, n) * 3 + owner
    mix = rng.integers(0, 120, n)
    keys = np.where(rng.random(n) < 0.85, base, mix).astype(np.int64)
    left = Table({"k": jnp.asarray(keys)},
                 jnp.asarray(rng.uniform(0.1, 0.9, n)),
                 jnp.asarray(rng.random(n) < 0.9))
    rk = np.arange(0, 120)
    right = Table({"rk": jnp.asarray(rk), "w": jnp.asarray(rk %% 5)},
                  jnp.full((rk.size,), 0.8), jnp.ones((rk.size,), bool))
    tables = {"left": left, "right": right}
    root = L.GroupAgg(L.FKJoin(L.Scan("left"), L.Scan("right"),
                               "k", "rk", ("w",)),
                      ("k",), "w", "SUM", 128)
    opts = dict(join_gather_budget=1, copartition=copart)
    out, rep = L.run_plan(root, tables, mesh,
                          policy=L.RetryPolicy(max_attempts=3), jit=True,
                          shuffle_slack=0.25, **opts)
    assert rep.issues() == {}, (seed, copart, rep.describe())
    assert rep.waves["attempts"] <= 3
    # oversized from the start: slack = n_shards pins buckets at the
    # sender's local rows, overflow impossible
    big = jax.jit(L.compile_plan(root, mesh, shuffle_slack=3.0, **opts))
    ref = big(tables)
    assert_biteq(f"seed={seed} copart={copart}", ref, out, nan_equal=False)

for seed in %(seeds)s:
    for copart in (False, True):
        trial(seed, copart)
print("CONVERGENCE FUZZ OK")
"""


MESH_CHECKS = {
    **{f"test_overflow_retry_3shard[{copart}]": MeshCheck(
        3, _OVERFLOW_SCRIPT % dict(copart=copart), seconds=10)
       for copart in (False, True)},
    "test_retry_convergence_fuzz_seeded": MeshCheck(
        3, _FUZZ_SCRIPT % dict(seeds=[0, 1, 2]), seconds=30),
}


@pytest.mark.multidevice
@pytest.mark.parametrize("copart", [False, True])
def test_overflow_retry_3shard(mesh_check, copart):
    """An overflowing 3-shard exchange under jit: detected in the report
    (through a boolean output too), healed by RetryPolicy in <=3
    attempts, bit-equal to a run at the final escalated parameters —
    for both the ShuffleJoin and CoPartitionedJoin lowerings."""
    assert "OVERFLOW RETRY OK" in mesh_check()


@pytest.mark.multidevice
def test_retry_convergence_fuzz_seeded(mesh_check):
    """Seeded-fallback fuzz (always runs): skewed key distributions on a
    3-shard mesh converge under RetryPolicy within max_attempts and
    match the oversized-from-the-start run bit for bit, for both
    ShuffleJoin and CoPartitionedJoin."""
    assert "CONVERGENCE FUZZ OK" in mesh_check()


@pytest.mark.multidevice
@pytest.mark.slow
def test_retry_convergence_fuzz_hypothesis():
    """The hypothesis-driven sweep (skipped without hypothesis, matching
    the repo's seeded-fallback pattern): random seeds drive the same
    trial harness."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import strategies as st
    from conftest import Batch

    @hyp.given(st.lists(st.integers(0, 10_000), min_size=2, max_size=4,
                        unique=True))
    @hyp.settings(max_examples=3, deadline=None)
    def check(seeds):
        out = Batch({"fuzz": _FUZZ_SCRIPT % dict(seeds=seeds)},
                    devices=3, timeout=120).outcomes()["fuzz"]
        assert out.error is None, out.error
        assert "CONVERGENCE FUZZ OK" in out.stdout

    check()


# ================================================== fault-plan mechanics
def test_fault_plan_mechanics():
    fp = faults.FaultPlan(transfer_calls={1}, exchange_calls={0},
                          transfer_rows_over=100)
    with faults.inject(fp):
        faults.on_transfer(0, 10)
        with pytest.raises(faults.TransferFault):
            faults.on_transfer(0, 10)            # one-shot occurrence 1
        faults.on_transfer(1, 10)                # consumed: passes now
        with pytest.raises(faults.TransferFault):
            faults.on_transfer(2, 101)           # persistent rows_over
        with pytest.raises(faults.TransferFault):
            faults.on_exchange()
        faults.on_exchange()
        with pytest.raises(RuntimeError):
            with faults.inject(faults.FaultPlan()):   # no nesting
                pass
    assert fp.consumed()
    assert [f for *_x, f in fp.log] == [False, True, False, True]
    faults.on_transfer(0, 10**9)                 # hooks are no-ops outside
    faults.on_exchange()


def test_queryservice_healed_replay():
    """Serving-layer retries neither poison nor duplicate cache entries:
    each escalation attempt keys its own entry, the service remembers the
    converged final_params, and a RESUBMIT of the healed plan runs one
    clean attempt, hits the cache, and answers bit-identically."""
    from repro.db.serving import QueryService

    db = _db()
    root = GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity",
                    "SUM", 16, "normal")            # overflows: 48 groups
    svc = QueryService(db.tables(), capacity=16,
                       policy=RetryPolicy(max_attempts=4))
    out1, info1 = svc.submit(root)
    assert info1["attempts"] > 1
    assert info1["report"].issues() == {}
    misses_after_heal = svc.cache.misses
    out2, info2 = svc.submit(root)
    assert info2["attempts"] == 1                   # replays final_params
    assert info2["hit"] and svc.cache.misses == misses_after_heal
    assert_biteq("healed-replay", out1, out2)
    # the healed hit also equals a from-scratch escalated run
    out3, _ = run_plan(root, db.tables(),
                       policy=RetryPolicy(max_attempts=4))
    assert_biteq("healed-vs-fresh", out1, out3)
