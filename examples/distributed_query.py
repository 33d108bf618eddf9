"""Distributed PGF query on a host-device mesh: the paper's aggregate
query as one shard_map program — per-shard UDA accumulate, one psum merge,
replicated FFT finalize (DESIGN.md §2).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_query.py
"""
import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

sys.path.insert(0, "src")

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.db import distributed as dist


def main():
    n_dev = len(jax.devices())
    data = max(1, n_dev // 2)
    mesh = jax.make_mesh((data, n_dev // data), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    print(f"mesh: {dict(mesh.shape)} over {n_dev} host devices")

    n, G, F = 1 << 18, 256, 1024
    rng = np.random.default_rng(0)
    probs = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
    # selective query: most tuples fail the predicate (value 0); the exact
    # global SUM distribution lives on the F-grid of the survivors
    v_np = np.zeros(n, np.float32)
    hot = rng.choice(n, 400, replace=False)
    v_np[hot] = rng.integers(1, 4, 400)
    values = jnp.asarray(v_np)
    gids = jnp.asarray(rng.integers(0, G, n), jnp.int32)

    step = dist.make_query_step(mesh, max_groups=G, num_freq=F)
    pd, vd, gd = dist.shard_columns(mesh, (probs, values, gids))
    out = step(pd, vd, gd)                       # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    conf, normal, cum, coeffs = jax.block_until_ready(step(pd, vd, gd))
    dt = time.perf_counter() - t0

    print(f"{n:,} probabilistic tuples -> {G} groups + exact global "
          f"distribution ({F} support) in {dt*1e3:.1f} ms "
          f"({n/dt/1e6:.2f} Mtuples/s on host-CPU stand-in devices)")
    print(f"  global COUNT-ish distribution mass: {float(coeffs.sum()):.6f}")
    print(f"  group 0: confidence={float(conf[0]):.4f} "
          f"E[SUM]={float(normal[0,0]):.1f} "
          f"sigma={float(jnp.sqrt(normal[0,1])):.2f}")
    mean_exact = float((coeffs * jnp.arange(F)).sum())
    print(f"  E[global SUM] from exact PGF = {mean_exact:.1f} "
          f"(closed form {float((probs*values).sum()):.1f})")

    # ---- the sharded relational frontend: a full TPC-H plan on the mesh.
    # Scans, the FK join, group-id assignment and the aggregation all run
    # on shard-local row blocks inside one shard_map (db/plans.py), and
    # the result is BIT-IDENTICAL to the single-device compile.
    from repro.db import tpch
    db = tpch.generate(n_orders=2000, seed=0)
    ref = tpch.q3(db, "aggregate")
    t0 = time.perf_counter()
    got = jax.block_until_ready(tpch.q3(db, "aggregate", mesh=mesh))
    dt = time.perf_counter() - t0
    bit_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)))
    shards = mesh.shape["data"]
    print(f"TPC-H Q3 via the sharded frontend on {shards} data shards in "
          f"{dt*1e3:.1f} ms: bit-equal to single-device = {bit_equal} "
          f"(rows/device {db.lineitem.capacity // shards:,} vs "
          f"{db.lineitem.capacity:,} replicated)")

    # ---- the shuffle-partitioned FK join: force every over-budget build
    # side onto the hash-exchange strategy (db/physical.py ShuffleJoin —
    # build rows and probe keys alltoall'd to key % n_shards owners,
    # matched shard-locally, responses shuffled home).  Same bits, but
    # peak build rows/device drop from O(build) to O(build/shards).
    # copartition=False pins the shuffle-home strategy: Q3's GROUP BY
    # keys on the join key, so the cost model would otherwise fuse it —
    # that pipeline is the next section.
    t0 = time.perf_counter()
    shuf = jax.block_until_ready(
        tpch.q3(db, "aggregate", mesh=mesh,
                plan_opts=dict(join_gather_budget=64, copartition=False)))
    dt = time.perf_counter() - t0
    bit_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(shuf)))
    print(f"TPC-H Q3 with shuffle-partitioned joins (gather budget 64) in "
          f"{dt*1e3:.1f} ms: bit-equal to single-device = {bit_equal} "
          f"(build rows/device {db.orders.capacity // shards:,} vs "
          f"{db.orders.capacity:,} gathered)")

    # ---- the co-partitioned shuffle -> aggregate pipeline: Q3's GROUP BY
    # keys on the join key, so the cost model (db/cost.py) fuses the
    # orders join with the aggregation — matched rows STAY at their
    # l_orderkey % n_shards owner (CoPartitionedJoin), the whole GROUP BY
    # runs owner-locally (PartitionedAgg), and the merge is ONE psum of
    # the folded group states.  Zero shuffle-home round-trips, same bits.
    dist.reset_collective_counts()
    t0 = time.perf_counter()
    fused = jax.block_until_ready(
        tpch.q3(db, "aggregate", mesh=mesh, order_join_budget=64))
    dt = time.perf_counter() - t0
    bit_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(fused)))
    counts = dict(dist.COLLECTIVE_COUNTS)
    print(f"TPC-H Q3 with the co-partitioned join->agg pipeline in "
          f"{dt*1e3:.1f} ms: bit-equal to single-device = {bit_equal}, "
          f"shuffle_back round-trips = {counts.get('shuffle_back', 0)} "
          f"(collectives: {counts})")


if __name__ == "__main__":
    main()
