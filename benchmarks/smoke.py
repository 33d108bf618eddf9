"""Per-PR perf smoke: one tiny planner-compiled TPC-H query per UDA method,
gated against a checked-in baseline.

Runs Q3-shaped GroupAgg plans through ``compile_plan`` (the unified
segment-UDA path) for every aggregation method — normal, cumulants, exact
(grouped log-CF), min/max — plus the ReweightGreater plan shape, and prints
wall times, so refactors of the UDA subsystem show perf regressions per-PR.
It also measures the grouped-exact planner path against a per-group scalar
``logcf`` loop (the pre-kernel execution strategy) at G >= 64, the
sharded relational frontend (the full shard_map pipeline on a 1-device
('data',) mesh) so the distributed scan/join/group-id path is gated too,
the gather- vs shuffle-lowered FK join (a per-join gather_budget forces
the hash-exchange strategies), and the fused CoPartitionedJoin +
PartitionedAgg pipeline vs shuffle + gather-home on the Q3-shaped
workload (with the shuffle_back round-trips saved, gated structurally).
The baseline JSON additionally records the static replicated-vs-sharded
peak rows/device accounting of the frontend AND the gather-vs-shuffle
build-side rows/device of a join whose build side exceeds the gather
budget (the ShuffleJoin memory contract).  The out-of-core streamed path
is gated three ways: the double-buffered vs synchronous wave-transfer
wall times (the overlap win, floored on multi-core hosts), the static
streamed-vs-resident peak rows/device at 1x and 8x data — the streamed
peak must stay FLAT as the table grows 8x past the device row budget —
and the column-pruned slab bytes of the streamed Q6 pass, which must
stay strictly below the unpruned bytes (Q6 reads 3 of lineitem's 10
columns) alongside a per-wave host-slice time row.
The self-healing happy path is gated too: the with-ExecutionReport run
of the Q1-shaped plan must stay within ``TOLERANCE`` of the plain run
and ``run_plan`` must resolve it in one attempt (diagnostics are free
when nothing is wrong).  The query-serving layer is gated three ways:
the cached-submit latency row (baseline), the plan-cache hit-vs-cold
ratio (floored at ``MIN_CACHE_HIT_SPEEDUP`` — a 'hit' that re-traces
collapses it), and the 64-point parameterized Q6 sweep vs 64 sequential
per-point compiles (floored at ``MIN_BATCH_SPEEDUP`` — amortising the
compile is the feature).  The grouped-kernel raw-speed PR adds two more:
the (G, O)-tiled grouped-cumulants kernel dispatch vs the blocked
scatter-add scan it bypasses (floored at ``MIN_GROUP_CUM_SPEEDUP`` on
TPU, a scaled-down interpret-mode floor on CPU), and the static
per-device frequency elements of a model-axis-sharded exact-CF slab,
which must cover the grid at 1/model_shards width.

    PYTHONPATH=src python benchmarks/smoke.py [--mesh] [--check] [--update]

--check  compares against benchmarks/BENCH_smoke_baseline.json and exits
         nonzero on a > ``TOLERANCE``x per-method regression (or on a
         grouped-exact speedup below ``MIN_EXACT_SPEEDUP``x).
--update rewrites the baseline from this run.
--mesh   additionally compiles the same plans against a host-device mesh and
         reports the distributed timings (requires >1 device or XLA_FLAGS
         host device count).

Timings are best-of-``repeat`` (not mean): the gate needs the low-noise
floor of each method, not its scheduler-jitter average.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "src"))

from repro.db import tpch
from repro.db.plans import (GroupAgg, Map, ReweightGreater, Scan, Select,
                            compile_plan, shard_capacity)

BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_smoke_baseline.json")
TOLERANCE = 1.3             # per-method regression gate (cur <= tol * base)
STREAM_TOLERANCE = 2.0      # streamed host-loop rows: the eager wave loop
                            # (host slicing + per-wave dispatch) has far
                            # higher run-to-run variance than the pure
                            # device rows, especially on 1-core hosts
MIN_EXACT_SPEEDUP = 5.0     # grouped exact vs per-group scalar loop floor
MIN_STREAM_OVERLAP = 1.2    # sync / double-buffered streamed-pass floor
MIN_CACHE_HIT_SPEEDUP = 50.0  # plan-cache hit vs cold compile floor
MIN_BATCH_SPEEDUP = 10.0    # batched-64 sweep vs 64 sequential compiles
MIN_GROUP_CUM_SPEEDUP = 10.0  # grouped-cumulants kernel vs scatter-add scan


def _group_cum_floor() -> float:
    """On TPU the (G, O)-tiled kernel must beat the blocked scatter-add
    scan by ``MIN_GROUP_CUM_SPEEDUP``; on CPU the kernel runs in interpret
    mode, where the one fused dispatch still beats the per-block scan
    (measured ~35x on 1-core hosts) but with enough allocator variance
    that the gate scales down to a 4x floor."""
    return (MIN_GROUP_CUM_SPEEDUP
            if jax.default_backend() == "tpu" else 4.0)


def _stream_overlap_floor() -> float:
    """The overlap gate needs a second core: host slab assembly and the
    XLA compute pool can only run concurrently on multi-core hosts.  On a
    single core the double-buffered pipeline cannot beat the serialised
    loop — and its wall time swings with allocator state — so the gate
    degrades to a catastrophe check (>= 0.3x, 'double buffering is not
    pathologically broken') while the overlap_win row is still recorded
    for machines where the win is physical.  The double_buffer timing row
    is gated ONLY through this ratio (relative to the same-run sync row),
    never against the baseline."""
    return MIN_STREAM_OVERLAP if (os.cpu_count() or 1) > 1 else 0.3


def _plans(max_groups: int = 256):
    li = Select(Scan("lineitem"), lambda t: t["l_shipdate"] > tpch.DAY0_1995)
    keys = ("l_orderkey",)
    return {
        "normal": GroupAgg(li, keys, "l_quantity", "SUM", max_groups,
                           "normal"),
        "cumulants": GroupAgg(li, keys, "l_quantity", "SUM", max_groups,
                              "cumulants"),
        # exact grouped SUM + COUNT distributions sharing one pass; per-order
        # quantity sums fit the 256-frequency grid of the synthetic data.
        "exact": GroupAgg(li, keys, "l_quantity", "SUM", max_groups,
                          "exact", num_freq=256,
                          extra=(("count", "", "COUNT", "exact"),)),
        "min": GroupAgg(li, keys, "l_quantity", "MIN", max_groups, kappa=32),
        "max": GroupAgg(li, keys, "l_quantity", "MAX", max_groups, kappa=32),
        "reweight": ReweightGreater(li, keys, "l_quantity", "", max_groups,
                                    threshold=60.0),
    }


def _time(fn, args, repeat):
    out = fn(*args)                                  # compile + warm
    jax.block_until_ready(jax.tree.leaves(out))
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(jax.tree.leaves(out))
        best = min(best, time.perf_counter() - t0)
    return best


def bench(n_orders: int = 1000, repeat: int = 5, mesh=None):
    db = tpch.generate(n_orders=n_orders, seed=0)
    tables = db.tables()
    rows = []
    for method, plan in _plans().items():
        fn = jax.jit(compile_plan(plan, mesh))
        dt = _time(fn, (tables,), repeat)
        tag = "mesh" if mesh is not None else "1dev"
        rows.append((f"smoke/{method}/{tag}", dt * 1e6,
                     f"n_orders={n_orders}"))
    return rows


def bench_exact_speedup(G: int = 64, tuples_per_group: int = 64,
                        num_freq: int = 256, repeat: int = 3):
    """Grouped-exact planner path vs the per-group scalar logcf loop it
    replaces: G separate single-group CF accumulations over the full
    (masked) tuple column, i.e. the only way to run grouped exact before
    the (G, F)-tiled path existed."""
    from repro.core import uda
    from repro.db.table import Table

    rng = np.random.default_rng(0)
    n = G * tuples_per_group
    gids = jnp.asarray(rng.integers(0, G, n), jnp.int32)
    probs = jnp.asarray(rng.uniform(0.0, 1.0, n), jnp.float32)
    vals = jnp.asarray(rng.integers(1, 4, n), jnp.int32)
    t = Table.from_columns({"g": gids, "v": vals}, prob=probs)
    plan = GroupAgg(Scan("t"), ("g",), "v", "SUM", G, "exact",
                    num_freq=num_freq)
    grouped = jax.jit(compile_plan(plan))
    t_grouped = _time(grouped, ({"t": t},), repeat)

    @jax.jit
    def loop(p, v):
        rows = []
        for g in range(G):
            pg = jnp.where(gids == g, p, 0.0)
            st = uda.accumulate({"cf": uda.SumCF(num_freq)}, pg, v, None,
                                max_groups=1)["cf"]
            rows.append(uda.SumCF(num_freq).finalize(st)[0])
        return jnp.stack(rows)
    t_loop = _time(loop, (probs, vals), repeat)
    return [(f"smoke/exact_speedup/G{G}", t_loop / max(t_grouped, 1e-12),
             f"grouped={t_grouped * 1e6:.1f}us,loop={t_loop * 1e6:.1f}us")]


def bench_grouped_cumulants(G: int = 64, tuples_per_group: int = 64,
                            orders: int = 8, repeat: int = 5):
    """The (G, O)-tiled grouped-cumulants kernel dispatch vs the blocked
    scatter-add scan it bypasses: the same ``uda.accumulate`` call with
    ``kernel='pallas'`` (the fused Pallas path, interpret mode on CPU) and
    ``kernel='xla'`` (the streaming ``lax.scan`` + ``_scatter_add``
    update).  ``--check`` floors the ratio at ``_group_cum_floor()``."""
    from repro.core import uda

    rng = np.random.default_rng(0)
    n = G * tuples_per_group
    p = jnp.asarray(rng.uniform(0.01, 0.99, n), jnp.float32)
    v = jnp.asarray(rng.uniform(0.0, 5.0, n), jnp.float32)
    g = jnp.asarray(rng.integers(0, G, n), jnp.int32)
    times = {}
    for tag in ("pallas", "xla"):
        def run(tag=tag):
            return uda.accumulate({"c": uda.SumCumulants(orders)}, p, v, g,
                                  max_groups=G, kernel=tag)
        times[tag] = _time(run, (), repeat)
    return [(f"smoke/grouped_cumulants/pallas/G{G}", times["pallas"] * 1e6,
             f"n={n},orders={orders}"),
            (f"smoke/grouped_cumulants/xla/G{G}", times["xla"] * 1e6,
             f"n={n},orders={orders}"),
            (f"smoke/grouped_cumulants_speedup/G{G}",
             times["xla"] / max(times["pallas"], 1e-12),
             f"pallas={times['pallas'] * 1e6:.1f}us,"
             f"xla={times['xla'] * 1e6:.1f}us")]


def bench_sharded_frontend(n_orders: int = 1000, repeat: int = 5):
    """The full sharded frontend (scan/select/join/group-ids inside one
    shard_map) on a 1-device ('data',) mesh: same Q3-shaped plan as
    smoke/normal plus an FKJoin, timed against the baseline so shard_map
    pipeline overhead regressions are caught per-PR even though the
    parent process only sees one device."""
    from repro.db.plans import FKJoin

    db = tpch.generate(n_orders=n_orders, seed=0)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    li = Select(Scan("lineitem"), lambda t: t["l_shipdate"] > tpch.DAY0_1995)
    j = FKJoin(li, Scan("orders"), "l_orderkey", "o_orderkey",
               ("o_totalprice",))
    plan = GroupAgg(j, ("l_orderkey",), "l_quantity", "SUM", 256, "normal")
    fn = jax.jit(compile_plan(plan, mesh))
    dt = _time(fn, (db.tables(),), repeat)
    return [("smoke/sharded_frontend/mesh1", dt * 1e6,
             f"n_orders={n_orders}")]


def frontend_layout(n_orders: int = 1000, shards: int = 8,
                    chunks: int = 8) -> dict:
    """Static peak rows/device of the biggest relation (lineitem): the
    replicated frontend keeps every (chunk-padded) row on every device;
    the sharded frontend keeps the contiguous 1/shards block.  Uses the
    same ``plans.shard_capacity`` padding formula as ``compile_plan``, and
    is gated against the checked-in baseline by ``--check`` so a layout
    regression (e.g. the frontend quietly re-replicating scans, or chunk
    padding blowing up) fails the smoke gate."""
    db = tpch.generate(n_orders=n_orders, seed=0)
    npad = shard_capacity(db.lineitem.capacity, chunks, shards)
    return {"replicated": npad, "sharded": npad // shards, "shards": shards}


def shuffle_layout(n_orders: int = 1000, shards: int = 8,
                   chunks: int = 8, slack: float = 4.0) -> dict:
    """Static peak BUILD-side rows/device of an FK join whose build side
    (orders) exceeds the gather budget: the gather strategy replicates the
    whole build table on every device; the shuffle strategy keeps the hash
    bucket plus the static exchange buffers.  Computed from the lowered
    physical plan (the same ``physical.lower_plan`` the compiler runs), so
    the O(build/shards) memory contract of the ShuffleJoin is gated by
    ``--check`` against the baseline."""
    from repro.db import physical as phys
    from repro.db.plans import FKJoin

    db = tpch.generate(n_orders=n_orders, seed=0)
    caps = {k: shard_capacity(t.capacity, chunks, shards)
            for k, t in db.tables().items()}
    join = FKJoin(Select(Scan("lineitem"),
                         lambda t: t["l_shipdate"] > tpch.DAY0_1995),
                  Scan("orders"), "l_orderkey", "o_orderkey",
                  ("o_totalprice",))
    lowered = phys.lower_plan(join, caps, n_shards=shards, sharded=True,
                              join_gather_budget=caps["orders"] - 1,
                              shuffle_slack=slack)
    assert isinstance(lowered, phys.ShuffleJoin), phys.explain(lowered)
    # gather: the whole build table lands on every device; shuffle: the
    # received hash bucket (n_shards send buckets of build_bucket rows)
    # plus the probe request/response buffers.
    return {"gather_build_rows": caps["orders"],
            "shuffle_build_rows": shards * lowered.build_bucket,
            "shuffle_probe_rows": shards * lowered.probe_bucket,
            "shards": shards}


def bench_shuffle_join(n_orders: int = 1000, repeat: int = 5):
    """Gather- vs shuffle-lowered FK join wall time on the 1-device
    ('data',) mesh: the same Q3-shaped join as smoke/sharded_frontend,
    compiled once per strategy (a tiny per-join gather_budget forces the
    shuffle lowering), so the shuffle path's exchange overhead is gated
    per-PR alongside its memory accounting."""
    from repro.db.plans import FKJoin

    db = tpch.generate(n_orders=n_orders, seed=0)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    li = Select(Scan("lineitem"), lambda t: t["l_shipdate"] > tpch.DAY0_1995)
    rows = []
    for tag, budget in (("gather", None), ("shuffle", 1)):
        j = FKJoin(li, Scan("orders"), "l_orderkey", "o_orderkey",
                   ("o_totalprice",), gather_budget=budget)
        plan = GroupAgg(j, ("l_orderkey",), "l_quantity", "SUM", 256,
                        "normal")
        # copartition=False pins the ShuffleJoin + shuffle-home strategy
        # (the GROUP BY keys on the join key, so the cost model would
        # otherwise fuse it — bench_copartitioned_agg measures that).
        fn = jax.jit(compile_plan(plan, mesh, copartition=False))
        dt = _time(fn, (db.tables(),), repeat)
        rows.append((f"smoke/shuffle_join/{tag}/mesh1", dt * 1e6,
                     f"n_orders={n_orders}"))
    return rows


def bench_copartitioned_agg(n_orders: int = 1000, repeat: int = 5):
    """The fused shuffle -> aggregate pipeline vs shuffle + gather-home on
    the Q3-shaped workload (GROUP BY on the FK-join key, build side over
    the gather budget): same logical plan, compiled once with the fused
    CoPartitionedJoin + PartitionedAgg lowering and once with
    ``copartition=False`` (ShuffleJoin + shuffle_back + PartialAgg).
    Alongside the wall times, counts the shuffle_back round-trips each
    strategy traces — the fused pipeline must save at least one, and
    ``--check`` gates both the saving and fused-beats-shuffle."""
    from repro.db import distributed as dist
    from repro.db.plans import FKJoin

    db = tpch.generate(n_orders=n_orders, seed=0)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    li = Select(Scan("lineitem"), lambda t: t["l_shipdate"] > tpch.DAY0_1995)
    j = FKJoin(li, Scan("orders"), "l_orderkey", "o_orderkey",
               ("o_totalprice",), gather_budget=1)
    plan = GroupAgg(j, ("l_orderkey",), "l_quantity", "SUM", 256, "normal")
    rows, back = [], {}
    for tag, copart in (("fused", True), ("shuffle_home", False)):
        fn = jax.jit(compile_plan(plan, mesh, copartition=copart))
        dist.reset_collective_counts()
        dt = _time(fn, (db.tables(),), repeat)   # warm call traces once
        back[tag] = dist.COLLECTIVE_COUNTS.get("shuffle_back", 0)
        rows.append((f"smoke/copartitioned_agg/{tag}/mesh1", dt * 1e6,
                     f"n_orders={n_orders}"))
    rows.append(("smoke/copartitioned_agg/roundtrips_saved",
                 back["shuffle_home"] - back["fused"],
                 f"shuffle_back {back['shuffle_home']}->{back['fused']}"))
    return rows


def bench_streamed(n_orders: int = 8000, repeat: int = 5):
    """Out-of-core streamed aggregation: the Q1-shaped pass over a host
    lineitem 16x the per-device row budget, double-buffered vs synchronous
    transfer.  ``compile_plan`` is called ONCE per variant and the
    compiled fn reused (the streamed path is an eager host wave loop
    whose per-wave jit cache lives in the compile closure), and the
    canonical chunk grid is scaled with the table (csz ~= 500 rows) so the
    wave size tracks the budget, not the table.  Alongside the wall
    times, reports the sync/double-buffer ratio — the overlap win the
    transfer pipeline exists for — which ``--check`` gates against
    ``MIN_STREAM_OVERLAP``."""
    from repro.db.table import HostTable

    db = tpch.generate(n_orders=n_orders, seed=0)
    n_li = db.lineitem.capacity                       # n_orders * 4 rows
    chunks = max(8, n_li // 500)
    budget = 2000                                     # waves of 2k rows
    li = Select(Scan("lineitem"), lambda t: t["l_shipdate"] > tpch.DAY0_1995)
    plan = GroupAgg(li, ("l_returnflag", "l_linestatus"), "l_quantity",
                    "SUM", 8, "normal")
    tables = dict(db.tables())
    tables["lineitem"] = HostTable.from_table(db.lineitem)
    rows, times = [], {}
    for tag, db_buf in (("double_buffer", True), ("sync", False)):
        fn = compile_plan(plan, None, device_row_budget=budget,
                          canonical_chunks=chunks, stream_double_buffer=db_buf)
        times[tag] = _time(fn, (tables,), repeat)
        rows.append((f"smoke/streamed/{tag}/1dev", times[tag] * 1e6,
                     f"n_li={n_li},budget={budget}"))
    rows.append(("smoke/streamed/overlap_win",
                 times["sync"] / max(times["double_buffer"], 1e-12),
                 f"sync={times['sync'] * 1e6:.1f}us,"
                 f"db={times['double_buffer'] * 1e6:.1f}us"))
    return rows


def bench_stream_pruning(n_orders: int = 2000, repeat: int = 3):
    """Column pruning on the streamed Q6 pass, measured in slab bytes:
    the Q6 predicate + value expression read 3 of lineitem's 10 columns,
    so the pruned wave slabs must ship strictly fewer host->device bytes
    than the unpruned slabs over the same table — ``--check`` gates the
    strict inequality, and both byte counters are baseline-gated (they
    are static properties of the lowering, so any growth is a pruning
    regression).  Also records the per-wave host-slice time of the
    pruned pass (the zero-alloc ping-pong slab assembly path), averaged
    over ``repeat`` full passes to damp scheduler jitter."""
    from repro.db import plans as P
    from repro.db.table import HostTable

    db = tpch.generate(n_orders=n_orders, seed=0)
    n_li = db.lineitem.capacity
    chunks = max(8, n_li // 500)
    budget = 2000
    plan = tpch.q6_plan()
    tables = dict(db.tables())
    tables["lineitem"] = HostTable.from_table(db.lineitem)
    rows, stats = [], {}
    for tag, prune in (("pruned", True), ("unpruned", False)):
        fn = compile_plan(plan, None, device_row_budget=budget,
                          canonical_chunks=chunks,
                          stream_prune_columns=prune)
        out = fn(tables)                              # warm per-wave jits
        jax.block_until_ready(jax.tree.leaves(out))
        P.reset_stream_stats()
        for _ in range(repeat):
            out = fn(tables)
            jax.block_until_ready(jax.tree.leaves(out))
        s = P.stream_stats()
        stats[tag] = s
        rows.append((f"smoke/streamed/slab_bytes/{tag}",
                     s["slab_bytes"] / repeat,
                     f"waves={s['waves'] // repeat},n_li={n_li}"))
    s = stats["pruned"]
    rows.append(("smoke/streamed/slice_us_per_wave",
                 s["slice_s"] / max(s["waves"], 1) * 1e6,
                 f"waves={s['waves'] // repeat},repeat={repeat}"))
    return rows


def bench_retry_overhead(n_orders: int = 1000, repeat: int = 5):
    """The happy path of the self-healing controller must be (nearly)
    free: the Q1-shaped resident plan jitted once plain and once with
    ``with_report=True`` (the ExecutionReport threaded through the run),
    reported as the with-report / plain wall-time ratio.  ``--check``
    gates the ratio at ``TOLERANCE`` — diagnostics may not tax clean
    runs — and the bench asserts ``run_plan`` resolves the clean plan in
    ONE attempt (zero retries burned when nothing is wrong)."""
    from repro.db.plans import RetryPolicy, run_plan

    db = tpch.generate(n_orders=n_orders, seed=0)
    tables = db.tables()
    li = Select(Scan("lineitem"), lambda t: t["l_shipdate"] > tpch.DAY0_1995)
    plan = GroupAgg(li, ("l_returnflag", "l_linestatus"), "l_quantity",
                    "SUM", 8, "normal")
    t_base = _time(jax.jit(compile_plan(plan)), (tables,), repeat)
    t_rep = _time(jax.jit(compile_plan(plan, with_report=True)),
                  (tables,), repeat)
    _, rep = run_plan(plan, tables, policy=RetryPolicy(max_attempts=2))
    assert rep.waves["attempts"] == 1, rep.describe()
    assert rep.issues() == {}, rep.describe()
    return [("smoke/retry_overhead", t_rep / max(t_base, 1e-12),
             f"base={t_base * 1e6:.1f}us,report={t_rep * 1e6:.1f}us")]


def bench_serving(n_orders: int = 1000, repeat: int = 5):
    """The query-serving layer's reason to exist, measured: round 0
    submits every TPC-H serving plan cold (full trace + compile), later
    rounds resubmit FRESH plan objects — the structural plan cache must
    serve them from the same executables.  Gated two ways: the cached
    submit latency is baseline-gated like any timing row, and the
    cold/hit ratio is floored at ``MIN_CACHE_HIT_SPEEDUP`` (if a cache
    'hit' ever re-traces, the ratio collapses to ~1 and the gate
    fires)."""
    from repro.db.serving import QueryService

    db = tpch.generate(n_orders=n_orders, seed=0)
    svc = QueryService(db.tables(), capacity=16)
    plans = tpch.serving_plans()
    t0 = time.perf_counter()
    for name, plan in plans.items():
        out, info = svc.submit(plan)
        jax.block_until_ready(jax.tree.leaves(out))
        assert not info["hit"], name
    t_cold = (time.perf_counter() - t0) / len(plans)
    best = float("inf")
    for _ in range(repeat):
        fresh = tpch.serving_plans()        # new objects: hits must be
        t0 = time.perf_counter()            # structural, not identity
        for name, plan in fresh.items():
            out, info = svc.submit(plan)
            jax.block_until_ready(jax.tree.leaves(out))
            assert info["hit"], name
        best = min(best, (time.perf_counter() - t0) / len(fresh))
    return [("smoke/serving/hit/1dev", best * 1e6,
             f"qps={1.0 / best:.0f},n_orders={n_orders}"),
            ("smoke/serving/cache_hit_speedup", t_cold / best,
             f"cold={t_cold * 1e6:.0f}us,hit={best * 1e6:.0f}us")]


def bench_batched_sweep(n_orders: int = 200, n_points: int = 64):
    """A 64-point Q6 what-if sweep, both ways: 64 per-point plans with
    baked constants (64 traces + 64 compiles — what the engine did
    before parameter lifting) vs ONE compiled q6_family executable
    running all 64 points as one batched device program.  ``--check``
    floors the ratio at ``MIN_BATCH_SPEEDUP``; wall times include each
    arm's compiles because amortising the compile IS the feature."""
    from repro.db.serving import QueryService

    db = tpch.generate(n_orders=n_orders, seed=0)
    tables = db.tables()
    lims = [float(i + 1) for i in range(n_points)]

    def baked(lim):
        sel = Select(Scan("lineitem"),
                     lambda t: (t["l_shipdate"] >= tpch.DAY0_1995 - 400)
                     & (t["l_shipdate"] < tpch.DAY0_1995)
                     & (t["l_discount"] >= 5.0) & (t["l_discount"] <= 7.0)
                     & (t["l_quantity"] < lim))
        val = Map(sel, "q6_value",
                  lambda t: t["l_quantity"] * t["l_discount"])
        return GroupAgg(val, (), "q6_value", "SUM", 1, "normal",
                        extra=(("cumulants", "q6_value", "SUM",
                                "cumulants"),))

    t0 = time.perf_counter()
    for lim in lims:
        out = jax.jit(compile_plan(baked(lim)))(tables)
        jax.block_until_ready(jax.tree.leaves(out))
    t_seq = time.perf_counter() - t0
    jax.clear_caches()      # drop the 64 accreted executables (the
    #                         failure mode the serving layer bounds)
    svc = QueryService(tables, capacity=4)
    batch = dict(disc_lo=jnp.full((n_points,), 5.0),
                 disc_hi=jnp.full((n_points,), 7.0),
                 qty_lim=jnp.asarray(lims))
    t0 = time.perf_counter()
    out, info = svc.sweep(tpch.q6_family(), batch)
    jax.block_until_ready(jax.tree.leaves(out))
    t_batch = time.perf_counter() - t0
    return [(f"smoke/serving/batched{n_points}_speedup", t_seq / t_batch,
             f"seq={t_seq:.2f}s,batched={t_batch:.2f}s,"
             f"launches={info['launches']}")]


def streamed_layout(n_orders: int = 1000, budget: int = 2000,
                    csz: int = 500) -> dict:
    """Static peak rows/device of the streamed scan at 1x and 8x data:
    the resident compile keeps the whole padded table on the device; the
    streamed compile keeps two double-buffered wave slabs sized by the
    budget.  The canonical chunk grid scales with the table (fixed
    ~``csz``-row chunks) so the wave slab — and the streamed peak — is
    FLAT under 8x table growth, while the resident footprint grows 8x.
    Computed from the lowered physical plan's modeled cost and gated
    structurally and against the baseline by ``--check``."""
    from repro.db import physical as phys

    peaks = {}
    for scale in (1, 8):
        n_li = n_orders * 4 * scale
        chunks = max(8, n_li // csz)
        cap = shard_capacity(n_li, chunks, 1)
        plan = GroupAgg(Select(Scan("lineitem"),
                               lambda t: t["l_shipdate"] > tpch.DAY0_1995),
                        ("l_returnflag", "l_linestatus"), "l_quantity",
                        "SUM", 8, "normal")
        lowered = phys.lower_plan(plan, {"lineitem": cap}, n_shards=1,
                                  sharded=False, canonical_chunks=chunks,
                                  device_row_budget=budget)
        sc = lowered.child.child.child
        assert isinstance(sc, phys.StreamedScan), phys.explain(lowered)
        peaks[scale] = {"resident_rows": cap,
                        "streamed_peak_rows": int(sc.cost.peak_rows)}
    return {"x1": peaks[1], "x8": peaks[8], "budget": budget}


def model_cf_layout(num_freq: int = 256, model_shards: int = 4) -> dict:
    """Static per-device frequency elements of one exact-CF slab under
    model-axis sharding: ``compile_plan`` splits every slab into
    per-model-rank windows of ``ceil(cnt / model_shards)`` frequencies
    (``plans._model_freq_split`` — the accumulation each device actually
    runs before the one reassembling all-gather), so the per-device phase
    work must drop by the axis size.  Gated structurally (sharded *
    shards covers the grid, sharded < unsharded) and against the baseline
    by ``--check`` so the planner quietly falling back to full-width
    accumulation fails the smoke gate."""
    from repro.db import plans as P

    f_loc = P._model_freq_split(num_freq, model_shards)
    return {"unsharded": num_freq,
            "sharded": f_loc if f_loc is not None else num_freq,
            "model_shards": model_shards}


def _check(rows) -> int:
    if not os.path.exists(BASELINE_PATH):
        print(f"FAIL: no baseline at {BASELINE_PATH}; run --update first")
        return 1
    with open(BASELINE_PATH) as f:
        base_all = json.load(f)
    base = base_all["rows"]
    failures = 0
    missing = set(base) - {name for name, _, _ in rows}
    for name in sorted(missing):   # a dropped/renamed method is a failure,
        print(f"FAIL {name}: in baseline but not measured "
              "(renamed or broken method? run --update to drop it)")
        failures += 1              # not a silently disarmed gate
    values = {name: value for name, value, _ in rows}
    saved = values.get("smoke/copartitioned_agg/roundtrips_saved")
    if saved is not None:
        base_saved = base_all.get("copartitioned_roundtrips_saved", 1)
        if saved < base_saved:
            print(f"FAIL copartitioned_agg: {saved} shuffle_back "
                  f"round-trips saved < baseline {base_saved} (the fused "
                  "pipeline is paying the trip home again)")
            failures += 1
        fused = values.get("smoke/copartitioned_agg/fused/mesh1")
        home = values.get("smoke/copartitioned_agg/shuffle_home/mesh1")
        if fused is not None and home is not None and fused > home * TOLERANCE:
            print(f"FAIL copartitioned_agg: fused {fused:.1f}us > "
                  f"{TOLERANCE} x shuffle_home {home:.1f}us (the fused "
                  "pipeline stopped beating shuffle + gather-home)")
            failures += 1
    retry = values.get("smoke/retry_overhead")
    if retry is not None and retry > TOLERANCE:
        print(f"FAIL retry_overhead: with-report run {retry:.2f}x plain "
              f"> {TOLERANCE}x (diagnostics are taxing the happy path)")
        failures += 1
    hit = values.get("smoke/serving/cache_hit_speedup")
    if hit is not None and hit < MIN_CACHE_HIT_SPEEDUP:
        print(f"FAIL serving: cache-hit speedup {hit:.1f}x < "
              f"{MIN_CACHE_HIT_SPEEDUP}x floor (structural hits are "
              "re-tracing)")
        failures += 1
    batched = values.get("smoke/serving/batched64_speedup")
    if batched is not None and batched < MIN_BATCH_SPEEDUP:
        print(f"FAIL serving: batched-64 sweep {batched:.1f}x < "
              f"{MIN_BATCH_SPEEDUP}x over 64 sequential compiles")
        failures += 1
    pruned = values.get("smoke/streamed/slab_bytes/pruned")
    unpruned = values.get("smoke/streamed/slab_bytes/unpruned")
    if pruned is not None and unpruned is not None and pruned >= unpruned:
        print(f"FAIL streamed: pruned slab bytes {pruned:.0f} >= unpruned "
              f"{unpruned:.0f} (column pruning stopped shrinking the Q6 "
              "wave slabs — Q6 reads 3 of lineitem's 10 columns)")
        failures += 1
    overlap = values.get("smoke/streamed/overlap_win")
    if overlap is not None and overlap < _stream_overlap_floor():
        print(f"FAIL streamed: overlap win {overlap:.2f}x < "
              f"{_stream_overlap_floor()}x floor (double-buffered transfer "
              "stopped hiding the host->device copy)")
        failures += 1
    for name, value, _ in rows:
        if name in ("smoke/copartitioned_agg/roundtrips_saved",
                    "smoke/streamed/overlap_win",
                    "smoke/streamed/double_buffer/1dev",
                    "smoke/retry_overhead",
                    "smoke/serving/cache_hit_speedup",
                    "smoke/serving/batched64_speedup"):
            continue                     # ratio/structural rows, gated above
        if name.startswith("smoke/exact_speedup"):
            if value < MIN_EXACT_SPEEDUP:
                print(f"FAIL {name}: speedup {value:.2f}x < "
                      f"{MIN_EXACT_SPEEDUP}x floor")
                failures += 1
            continue
        if name.startswith("smoke/grouped_cumulants_speedup"):
            if value < _group_cum_floor():
                print(f"FAIL {name}: speedup {value:.2f}x < "
                      f"{_group_cum_floor()}x floor (the grouped-cumulants "
                      "kernel stopped beating the scatter-add scan)")
                failures += 1
            continue
        if name not in base:
            print(f"WARN {name}: not in baseline (run --update to record)")
            continue
        tol = STREAM_TOLERANCE if name.startswith("smoke/streamed/") \
            else TOLERANCE
        if value > tol * base[name]:
            print(f"FAIL {name}: {value:.1f}us > {tol} x "
                  f"{base[name]:.1f}us baseline")
            failures += 1
    base_layout = base_all.get("peak_rows_per_device")
    layout = frontend_layout()
    if base_layout is None:
        print("WARN layout: no peak_rows_per_device in baseline "
              "(run --update to record)")
    elif (layout["replicated"] != base_layout["replicated"]
          or layout["sharded"] > base_layout["sharded"]):
        print(f"FAIL layout: peak rows/device {layout} regressed vs "
              f"baseline {base_layout} (the sharded frontend's "
              "O(rows/shards) accounting changed)")
        failures += 1
    base_shuffle = base_all.get("shuffle_join_rows_per_device")
    shuffle = shuffle_layout()
    if shuffle["shuffle_build_rows"] >= shuffle["gather_build_rows"]:
        print(f"FAIL shuffle layout: {shuffle} — the shuffle join no "
              "longer beats replicating the build side")
        failures += 1
    if base_shuffle is None:
        print("WARN shuffle layout: no shuffle_join_rows_per_device in "
              "baseline (run --update to record)")
    elif (shuffle["shuffle_build_rows"] > base_shuffle["shuffle_build_rows"]
          or shuffle["shuffle_probe_rows"]
          > base_shuffle["shuffle_probe_rows"]):
        print(f"FAIL shuffle layout: {shuffle} regressed vs baseline "
              f"{base_shuffle} (the ShuffleJoin's O(build/shards) "
              "accounting changed)")
        failures += 1
    base_model = base_all.get("model_cf_freq_elems_per_device")
    mcf = model_cf_layout()
    if (mcf["sharded"] >= mcf["unsharded"]
            or mcf["sharded"] * mcf["model_shards"] < mcf["unsharded"]):
        print(f"FAIL model-CF layout: {mcf} — per-device frequency "
              "elements are not a 1/model_shards cover of the grid (the "
              "model-axis exact-CF split is off or overlapping)")
        failures += 1
    if base_model is None:
        print("WARN model-CF layout: no model_cf_freq_elems_per_device in "
              "baseline (run --update to record)")
    elif mcf["sharded"] > base_model["sharded"]:
        print(f"FAIL model-CF layout: {mcf} regressed vs baseline "
              f"{base_model} (per-device exact-CF phase work grew)")
        failures += 1
    base_stream = base_all.get("streamed_rows_per_device")
    stream = streamed_layout()
    if stream["x8"]["streamed_peak_rows"] != stream["x1"]["streamed_peak_rows"]:
        print(f"FAIL streamed layout: {stream} — streamed peak rows are "
              "not flat under 8x table growth (the wave slab is tracking "
              "the table, not the budget)")
        failures += 1
    if stream["x8"]["streamed_peak_rows"] >= stream["x8"]["resident_rows"]:
        print(f"FAIL streamed layout: {stream} — streaming no longer "
              "beats keeping the table resident")
        failures += 1
    if base_stream is None:
        print("WARN streamed layout: no streamed_rows_per_device in "
              "baseline (run --update to record)")
    elif (stream["x8"]["streamed_peak_rows"]
          > base_stream["x8"]["streamed_peak_rows"]):
        print(f"FAIL streamed layout: {stream} regressed vs baseline "
              f"{base_stream} (the double-buffered O(wave) residency "
              "accounting changed)")
        failures += 1
    print("CHECK " + ("FAILED" if failures else "PASSED")
          + f" ({len(rows)} rows, tol {TOLERANCE}x)")
    return 1 if failures else 0


def _update(rows):
    skip = ("smoke/exact_speedup", "smoke/copartitioned_agg/roundtrips",
            "smoke/streamed/overlap_win", "smoke/streamed/double_buffer",
            "smoke/retry_overhead", "smoke/serving/cache_hit_speedup",
            "smoke/serving/batched64_speedup",
            "smoke/grouped_cumulants_speedup")
    recorded = {name: us for name, us, _ in rows
                if not name.startswith(skip)}
    saved = {name: v for name, v, _ in rows
             if name == "smoke/copartitioned_agg/roundtrips_saved"}
    with open(BASELINE_PATH, "w") as f:
        json.dump({"tolerance": TOLERANCE, "repeat": "best-of",
                   "peak_rows_per_device": frontend_layout(),
                   "shuffle_join_rows_per_device": shuffle_layout(),
                   "streamed_rows_per_device": streamed_layout(),
                   "model_cf_freq_elems_per_device": model_cf_layout(),
                   "copartitioned_roundtrips_saved":
                       int(min(saved.values())) if saved else 1,
                   "rows": recorded}, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {BASELINE_PATH} ({len(recorded)} rows)")


def main() -> int:
    rows = bench()
    rows += bench_sharded_frontend()
    rows += bench_shuffle_join()
    rows += bench_copartitioned_agg()
    rows += bench_streamed()
    rows += bench_stream_pruning()
    rows += bench_retry_overhead()
    rows += bench_serving()
    rows += bench_batched_sweep()
    rows += bench_exact_speedup()
    rows += bench_grouped_cumulants()
    if "--mesh" in sys.argv and len(jax.devices()) > 1:
        from repro.launch.mesh import make_host_mesh
        rows += bench(mesh=make_host_mesh())
    for name, v, extra in rows:
        print(f"{name},{v:.1f},{extra}")
    if "--update" in sys.argv:
        _update(rows)
    if "--check" in sys.argv:
        return _check(rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
