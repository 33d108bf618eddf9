"""The sharded relational frontend's protocol pieces vs brute force.

Four layers:
  * the distributed group-id protocol (local unique -> merge of per-shard
    code tables -> searchsorted) is pure integer math, so it is fuzzed
    in-process against the single-pass `jnp.unique` oracle — under
    `hypothesis` when installed, and always via seeded fallbacks (the
    test_pgf.py pattern);
  * fk_join contract enforcement (duplicate build keys, nonnegative group
    keys) and possible-worlds parity, single-device;
  * the shuffle-partitioned join protocol (operators.bucket_slots /
    scatter_to_buckets / take_from_buckets + the per-owner fk_join), also
    pure math once the all_to_all is emulated host-side: fuzzed against
    the global fk_join oracle and the possible-worlds enumeration,
    duplicate-key rejection and bucket-overflow accounting included;
  * subprocess tests on real 2- and 3-device meshes: sharded fk_join
    possible-worlds parity, gather- and shuffle-strategy bit-equality,
    and the overflow NaN poisoning.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import MeshCheck, equiv_check
from repro.db import operators as ops
from repro.db.plans import FKJoin, GroupAgg, Scan, compile_plan
from repro.db.table import Table


# ------------------------------------------------ group-id protocol fuzz
def _check_group_ids_protocol(keys, valid, max_groups, n_shards):
    """Sharded two-phase group ids == single-pass oracle, bit for bit."""
    keys = np.asarray(keys, np.int64)
    valid = np.asarray(valid, bool)
    t = Table.from_columns({"k": jnp.asarray(keys)}, valid=jnp.asarray(valid))
    ids_ref, codes_ref, gv_ref = ops.group_ids(t, ["k"], max_groups)

    code_live, big = ops.live_key_codes(t, ["k"])
    n = keys.shape[0]
    per = -(-n // n_shards)
    cl = jnp.pad(code_live, (0, per * n_shards - n), constant_values=big)
    local = [ops.merge_group_codes(cl[s * per:(s + 1) * per], max_groups)
             for s in range(n_shards)]
    merged = ops.merge_group_codes(jnp.concatenate(local), max_groups)
    ids = ops.codes_to_ids(code_live, merged)

    np.testing.assert_array_equal(np.asarray(merged), np.asarray(codes_ref))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_ref))
    np.testing.assert_array_equal(np.asarray(merged != big),
                                  np.asarray(gv_ref))


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("n_shards", [2, 3, 4, 8])
def test_group_ids_protocol_seeded(seed, n_shards):
    """Duplicates, invalid rows, and near/over-capacity cardinality: the
    merge of per-shard code tables is exact even when shards drop codes
    (operators.merge_group_codes), so overflow clipping matches too."""
    r = np.random.default_rng(seed)
    n = int(r.integers(4, 65))
    max_groups = int(r.integers(2, 17))
    # key range around max_groups drives near- and over-capacity cases
    keys = r.integers(0, max(1, int(max_groups * r.uniform(0.5, 2.0))), n)
    valid = r.uniform(0, 1, n) > 0.3
    _check_group_ids_protocol(keys, valid, max_groups, n_shards)


def test_group_ids_protocol_edge_cases():
    # all rows invalid; single live key; exactly max_groups distinct keys
    _check_group_ids_protocol([3, 1, 4], [False, False, False], 4, 2)
    _check_group_ids_protocol([7] * 6, [True] * 6, 4, 3)
    _check_group_ids_protocol(np.arange(8), [True] * 8, 8, 4)


def test_group_ids_protocol_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=100, deadline=None)
    @given(st.lists(st.integers(0, 24), min_size=1, max_size=48),
           st.data(), st.integers(2, 16), st.sampled_from([2, 3, 4, 8]))
    def run(keys, data, max_groups, n_shards):
        valid = data.draw(st.lists(st.booleans(), min_size=len(keys),
                                   max_size=len(keys)))
        _check_group_ids_protocol(keys, valid, max_groups, n_shards)

    run()


# ------------------------------------------- nonnegative-key enforcement
def test_group_ids_rejects_negative_keys():
    t = Table.from_columns({"k": jnp.asarray([1, -2, 3])})
    with pytest.raises(ValueError, match="negative"):
        ops.group_ids(t, ["k"], 4)


def test_group_key_columns_rejects_negative_keys():
    t = Table.from_columns({"k": jnp.asarray([0, 1, 2]),
                            "c": jnp.asarray([5, -1, 7])})
    ids, _, _ = ops.group_ids(t, ["k"], 4)
    with pytest.raises(ValueError, match="negative"):
        ops.group_key_columns(t, ["c"], ids, 4)


def test_negative_key_on_invalid_row_is_fine():
    """Dead rows never write representatives — only valid rows are
    checked (the identity-0 write is exactly what the mask is for)."""
    t = Table.from_columns({"k": jnp.asarray([1, -2, 3])},
                           valid=jnp.asarray([True, False, True]))
    ids, codes, gvalid = ops.group_ids(t, ["k"], 4)
    assert int(np.asarray(gvalid).sum()) == 2


def test_compile_plan_surfaces_negative_key_error():
    t = Table.from_columns({"g": jnp.asarray([0, -1, 2]),
                            "v": jnp.asarray([1, 1, 1])})
    plan = GroupAgg(Scan("t"), ("g",), "v", "SUM", 4)
    with pytest.raises(ValueError, match="negative"):
        compile_plan(plan)({"t": t})


def test_compile_plan_accepts_any_chunk_grid():
    """Non-power-of-two canonical chunk grids are legal now (the pow2-base
    + sequential-tail tree of uda.tree_fold covers any chunk count); only
    non-positive grids are rejected."""
    t = Table.from_columns({"g": jnp.asarray([0, 1, 0, 1, 0]),
                            "v": jnp.asarray([1, 2, 3, 4, 5])},
                           prob=jnp.asarray([.5, .4, .3, .2, .1]))
    plan = GroupAgg(Scan("t"), ("g",), "v", "SUM", 4)
    mu8, _ = compile_plan(plan, canonical_chunks=8)({"t": t})["sum"]
    mu6, _ = compile_plan(plan, canonical_chunks=6)({"t": t})["sum"]
    np.testing.assert_allclose(np.asarray(mu8), np.asarray(mu6), rtol=1e-12)
    with pytest.raises(ValueError, match="positive"):
        compile_plan(plan, canonical_chunks=0)


# ---------------------------------------------------- fk_join semantics
def test_fk_join_rejects_duplicate_valid_build_keys():
    left = Table.from_columns({"k": jnp.asarray([0, 1])})
    right = Table.from_columns({"k": jnp.asarray([1, 1, 2]),
                                "pay": jnp.asarray([10, 11, 12])})
    with pytest.raises(ValueError, match="duplicate valid keys"):
        ops.fk_join(left, right, "k", "k", ["pay"])
    # the same key duplicated on an INVALID row is fine
    right2 = right.with_valid(jnp.asarray([True, False, True]))
    out = ops.fk_join(left, right2, "k", "k", ["pay"])
    assert int(out["pay"][1]) == 10


def _worlds_fk_join_marginals(left, right, lk, rk):
    """Brute-force P(output row present) per left row: enumerate presence
    worlds of both relations; a row survives iff its tuple and its unique
    valid key match are both present."""
    lp = np.asarray(left.prob)
    rp = np.asarray(right.prob)
    lv = np.asarray(left.valid)
    rv = np.asarray(right.valid)
    lkv = np.asarray(left[lk])
    rkv = np.asarray(right[rk])
    nl, nr = lp.size, rp.size
    marg = np.zeros(nl)
    for wl in range(1 << nl):
        pl_w = np.prod([lp[i] if wl >> i & 1 else 1 - lp[i]
                        for i in range(nl)])
        for wr in range(1 << nr):
            pw = pl_w * np.prod([rp[j] if wr >> j & 1 else 1 - rp[j]
                                 for j in range(nr)])
            for i in range(nl):
                if not (lv[i] and wl >> i & 1):
                    continue
                match = [j for j in range(nr)
                         if rv[j] and (wr >> j & 1) and rkv[j] == lkv[i]]
                if match:
                    marg[i] += pw
    return marg


def _tiny_join_tables(rng):
    # left keys include 3 (missing from the valid build side) and an
    # invalid left row; right carries a probability column via `pay`.
    left = Table.from_columns(
        {"k": jnp.asarray([0, 1, 2, 3, 1, 0]),
         "lv": jnp.asarray([5, 6, 7, 8, 9, 4])},
        prob=jnp.asarray(rng.uniform(0.1, 0.9, 6)),
        valid=jnp.asarray([True, True, True, True, False, True]))
    right = Table.from_columns(
        {"k": jnp.asarray([0, 1, 2, 3]),
         "pay": jnp.asarray([10, 11, 12, 13])},
        prob=jnp.asarray(rng.uniform(0.1, 0.9, 4)),
        valid=jnp.asarray([True, True, True, False]))  # key 3 dead
    return left, right


def test_fk_join_possible_worlds_parity(rng):
    left, right = _tiny_join_tables(rng)
    out = ops.fk_join(left, right, "k", "k", ["pay"])
    marg = _worlds_fk_join_marginals(left, right, "k", "k")
    got = np.where(np.asarray(out.valid), np.asarray(out.prob), 0.0)
    np.testing.assert_allclose(got, marg, atol=1e-12)
    # carried columns come from the unique match
    for i in np.flatnonzero(np.asarray(out.valid)):
        assert int(out["pay"][i]) == 10 + int(out["k"][i])


# ------------------------------------------------- sharded-path parity
_FK_JOIN_PARITY = """
import numpy as np
from repro.db import physical as phys
rng = np.random.default_rng(7)
left = Table.from_columns(
    {"k": jnp.asarray([0, 1, 2, 3, 1, 0, 2, 1]),
     "lv": jnp.asarray([5, 6, 7, 8, 9, 4, 3, 2])},
    prob=jnp.asarray(rng.uniform(0.1, 0.9, 8)),
    valid=jnp.asarray([True, True, True, True, False, True, True, True]))
right = Table.from_columns(
    {"k": jnp.asarray([0, 1, 2, 3]),
     "pay": jnp.asarray([10, 11, 12, 13])},
    prob=jnp.asarray(rng.uniform(0.1, 0.9, 4)),
    valid=jnp.asarray([True, True, True, False]))
tables = {"L": left, "R": right}
plan = FKJoin(Scan("L"), Scan("R"), "k", "k", ("pay",))
proot = phys.lower_plan(plan, {"L": 8, "R": 8}, n_shards=__DEVICES__,
                        sharded=True, join_gather_budget=1)
assert isinstance(proot, phys.ShuffleJoin), phys.explain(proot)
ref = compile_plan(plan, None)(tables)
got = compile_plan(plan, mesh)(tables)
shuf = compile_plan(plan, mesh, join_gather_budget=1)(tables)
pairs = [("gathered", ref, got), ("shuffled", ref, shuf)]

# possible-worlds parity of the sharded output (padded rows are invalid)
lp, rp = np.asarray(left.prob), np.asarray(right.prob)
lv, rv = np.asarray(left.valid), np.asarray(right.valid)
lk, rk = np.asarray(left["k"]), np.asarray(right["k"])
marg = np.zeros(lp.size)
for wl in range(1 << lp.size):
    plw = np.prod([lp[i] if wl >> i & 1 else 1 - lp[i]
                   for i in range(lp.size)])
    for wr in range(1 << rp.size):
        pw = plw * np.prod([rp[j] if wr >> j & 1 else 1 - rp[j]
                            for j in range(rp.size)])
        for i in range(lp.size):
            if lv[i] and wl >> i & 1 and any(
                    rv[j] and wr >> j & 1 and rk[j] == lk[i]
                    for j in range(rp.size)):
                marg[i] += pw
p_out = np.where(np.asarray(got.valid), np.asarray(got.prob), 0.0)
assert p_out.shape[0] >= lp.size and not p_out[lp.size:].any()
np.testing.assert_allclose(p_out[:lp.size], marg, atol=1e-12)
for i in np.flatnonzero(np.asarray(got.valid)):
    assert int(got["pay"][i]) == 10 + int(got["k"][i])
"""


@pytest.mark.multidevice
def test_fk_join_sharded_worlds_parity(mesh_check):
    """FKJoin through the sharded frontend: bit-equal to the single-device
    compile, possible-worlds parity for the carried probabilities, and the
    same answers when a tiny join_gather_budget lowers the join to the
    shuffle-partitioned strategy (NO replicated fallback exists anymore —
    asserted against the physical plan)."""
    assert "BITEQ OK" in mesh_check()


_GROUP_IDS_ON_MESH = """
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.db import distributed as dist
from repro.db import operators as ops
rng = np.random.default_rng(11)
n, MG = 64, 16
t = Table.from_columns(
    {"k": jnp.asarray(rng.integers(0, 24, n))},
    valid=jnp.asarray(rng.uniform(0, 1, n) > 0.3))
ids_ref, codes_ref, gv_ref = ops.group_ids(t, ["k"], MG)

def f(tt):
    ids, codes, gv = dist.group_ids_sharded(tt, ["k"], MG, ("data",))
    return jax.lax.all_gather(ids, "data", axis=0, tiled=True), codes, gv

ids, codes, gv = shard_map(f, mesh=mesh, in_specs=(P("data"),),
                           out_specs=P(), check_vma=False)(t)
pairs = [("group_ids", (ids_ref, codes_ref, gv_ref), (ids, codes, gv))]
"""


@pytest.mark.multidevice
def test_group_ids_sharded_on_mesh(mesh_check):
    """The real shard_map path of db.distributed.group_ids_sharded against
    the single-device oracle, including near-capacity cardinality."""
    assert "BITEQ OK" in mesh_check()


# ------------------------------------------- shuffle-exchange protocol
def _emulated_shuffle_fk_join(left, right, lk, rk, right_cols, n_shards,
                              probe_cap, build_cap):
    """Host-side emulation of dist.shuffle_fk_join: same per-shard bucket
    math and per-owner fk_join, with the two all_to_alls replaced by a
    numpy transpose of the (sender, owner) bucket grid.  Returns the
    reassembled global output Table pieces + the total overflow count."""
    nl, nr = left.capacity, right.capacity
    assert nl % n_shards == 0 and nr % n_shards == 0
    bl, br = nl // n_shards, nr // n_shards

    def shard(t, s, n):
        sl = slice(s * n, (s + 1) * n)
        return Table({k: v[sl] for k, v in t.columns.items()},
                     t.prob[sl], t.valid[sl])

    # per-shard send buckets (build side and probe requests)
    bsend, bmask, psend, pmask, slots, sents = [], [], [], [], [], []
    overflow = 0
    for s in range(n_shards):
        rt = shard(right, s, br)
        key = rt[rk].astype(jnp.int32)
        slot, sent, over = ops.bucket_slots(key % n_shards, rt.valid,
                                            n_shards, build_cap)
        overflow += int(over)
        cols = {"_key": key, "_prob": rt.prob,
                **{c: rt[c] for c in right_cols}}
        bsend.append(ops.scatter_to_buckets(cols, slot,
                                            n_shards * build_cap))
        bmask.append(np.asarray(jnp.zeros((n_shards * build_cap,), bool)
                                .at[slot].set(sent, mode="drop")))
        lt = shard(left, s, bl)
        lkey = lt[lk].astype(jnp.int32)
        slot, sent, over = ops.bucket_slots(lkey % n_shards, lt.valid,
                                            n_shards, probe_cap)
        overflow += int(over)
        psend.append(ops.scatter_to_buckets({"_key": lkey}, slot,
                                            n_shards * probe_cap))
        pmask.append(np.asarray(jnp.zeros((n_shards * probe_cap,), bool)
                                .at[slot].set(sent, mode="drop")))
        slots.append(slot)
        sents.append(sent)

    def transpose(bufs, cap):   # the all_to_all: out_d[s] = in_s[d]
        return [{k: np.concatenate([np.asarray(b[k]).reshape(
            n_shards, cap, -1)[d, :, 0] if np.asarray(b[k]).ndim == 1
            else np.asarray(b[k])[d * cap:(d + 1) * cap]
            for b in bufs]) for k in bufs[0]} for d in range(n_shards)]

    brecv = transpose(bsend, build_cap)
    bmrecv = [np.concatenate([m.reshape(n_shards, build_cap)[d]
                              for m in bmask]) for d in range(n_shards)]
    precv = transpose(psend, probe_cap)
    pmrecv = [np.concatenate([m.reshape(n_shards, probe_cap)[d]
                              for m in pmask]) for d in range(n_shards)]

    # per-owner local match, responses transposed home
    resp = []
    for d in range(n_shards):
        build = Table({rk: jnp.asarray(brecv[d]["_key"]),
                       **{c: jnp.asarray(brecv[d][c]) for c in right_cols}},
                      jnp.asarray(brecv[d]["_prob"]),
                      jnp.asarray(bmrecv[d]))
        req = Table({lk: jnp.asarray(precv[d]["_key"])},
                    jnp.ones((n_shards * probe_cap,), left.prob.dtype),
                    jnp.asarray(pmrecv[d]))
        m = ops.fk_join(req, build, lk, rk, right_cols)
        resp.append({"_p": m.prob, "_hit": m.valid,
                     **{c: m[c] for c in right_cols}})
    back = transpose(resp, probe_cap)

    # per-origin reassembly into the original row positions
    probs, valids, cols_out = [], [], {c: [] for c in right_cols}
    for s in range(n_shards):
        got = ops.take_from_buckets(
            {k: jnp.asarray(v) for k, v in back[s].items()},
            slots[s], sents[s])
        lt = shard(left, s, bl)
        probs.append(np.asarray(lt.prob * got["_p"]))
        valids.append(np.asarray(lt.valid & got["_hit"]))
        for c in right_cols:
            cols_out[c].append(np.asarray(got[c]))
    return (np.concatenate(probs), np.concatenate(valids),
            {c: np.concatenate(v) for c, v in cols_out.items()}, overflow)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("n_shards", [2, 3, 4])
def test_shuffle_join_protocol_matches_fk_join_oracle(seed, n_shards):
    """The emulated shuffle protocol == the global fk_join, bit for bit
    (probabilities, validity, carried columns including the deterministic
    zero-fill of miss rows), at full bucket capacity (no overflow)."""
    r = np.random.default_rng(seed)
    nl, nr = 6 * n_shards, 2 * n_shards
    left = Table.from_columns(
        {"k": jnp.asarray(r.integers(0, nr + 2, nl)),
         "lv": jnp.asarray(r.integers(0, 50, nl))},
        prob=jnp.asarray(r.uniform(0.05, 0.95, nl)),
        valid=jnp.asarray(r.uniform(0, 1, nl) > 0.2))
    right = Table.from_columns(
        {"k": jnp.asarray(np.arange(nr)),
         "pay": jnp.asarray(r.integers(10, 99, nr))},
        prob=jnp.asarray(r.uniform(0.05, 0.95, nr)),
        valid=jnp.asarray(r.uniform(0, 1, nr) > 0.2))
    ref = ops.fk_join(left, right, "k", "k", ["pay"])
    prob, valid, cols, overflow = _emulated_shuffle_fk_join(
        left, right, "k", "k", ["pay"], n_shards,
        probe_cap=nl // n_shards, build_cap=nr // n_shards)
    assert overflow == 0
    np.testing.assert_array_equal(prob, np.asarray(ref.prob))
    np.testing.assert_array_equal(valid, np.asarray(ref.valid))
    np.testing.assert_array_equal(cols["pay"], np.asarray(ref["pay"]))


def test_shuffle_join_protocol_possible_worlds_parity(rng):
    """End-to-end semantics of the shuffled join against the 2^n worlds
    enumeration (not just against fk_join)."""
    left, right = _tiny_join_tables(rng)
    left = left.pad_to(6)
    right = right.pad_to(6)
    marg = _worlds_fk_join_marginals(left, right, "k", "k")
    prob, valid, _, overflow = _emulated_shuffle_fk_join(
        left, right, "k", "k", ["pay"], 3, probe_cap=2, build_cap=2)
    assert overflow == 0
    np.testing.assert_allclose(np.where(valid, prob, 0.0), marg, atol=1e-12)


def test_shuffle_join_protocol_rejects_duplicate_build_keys():
    """Duplicate valid build keys land on the same hash owner, where the
    local fk_join's many-to-one contract check rejects them (concrete
    data, as in eager execution)."""
    left = Table.from_columns({"k": jnp.asarray([0, 1, 2, 3])})
    right = Table.from_columns({"k": jnp.asarray([1, 3, 3, 2]),
                                "pay": jnp.asarray([10, 11, 12, 13])})
    with pytest.raises(ValueError, match="duplicate valid keys"):
        _emulated_shuffle_fk_join(left, right, "k", "k", ["pay"], 2,
                                  probe_cap=2, build_cap=2)


def test_bucket_slots_overflow_accounting():
    """Rows beyond a bucket's capacity are dropped but counted; in-range
    ranks are dense per destination."""
    dest = jnp.asarray([0, 0, 0, 1, 0, 1])
    ok = jnp.asarray([True, True, True, True, True, False])
    slot, sent, over = ops.bucket_slots(dest, ok, 2, 2)
    assert int(over) == 2                      # 4 ok-rows to bucket 0, cap 2
    np.testing.assert_array_equal(np.asarray(sent),
                                  [True, True, False, True, False, False])
    np.testing.assert_array_equal(np.asarray(slot)[np.asarray(sent)],
                                  [0, 1, 2])   # dest*cap + rank
    # dropped and not-ok rows park out of range (scatter mode="drop")
    assert (np.asarray(slot)[~np.asarray(sent)] == 4).all()


@pytest.mark.parametrize("seed", range(6))
def test_concrete_bucket_capacity_covers_every_demand(seed):
    """Fuzz the skew-adaptive sizing: the histogram capacity equals the
    worst (sender, owner) demand, so bucket_slots never overflows at that
    capacity — for any key distribution."""
    from repro.db import physical as phys

    r = np.random.default_rng(seed)
    shards = int(r.integers(2, 5))
    local = int(r.integers(1, 9))
    n = shards * local
    skew = int(r.integers(1, 4 * shards))
    keys = r.integers(0, skew, n)
    valid = r.uniform(0, 1, n) > 0.25
    t = Table.from_columns({"k": jnp.asarray(keys)},
                           valid=jnp.asarray(valid))
    cap = phys.concrete_bucket_capacity(t, "k", shards)
    want = 1
    for s in range(shards):
        d = (keys[s * local:(s + 1) * local]
             [valid[s * local:(s + 1) * local]]) % shards
        if d.size:
            want = max(want, int(np.bincount(d, minlength=shards).max()))
        _, _, over = ops.bucket_slots(
            jnp.asarray(keys[s * local:(s + 1) * local] % shards),
            jnp.asarray(valid[s * local:(s + 1) * local]), shards, cap)
        assert int(over) == 0
    assert cap == want


@pytest.mark.parametrize("seed", range(6))
def test_bucket_slots_roundtrip_fuzz(seed):
    """scatter_to_buckets o take_from_buckets is the identity on sent rows
    (the response-routing invariant of the shuffle join)."""
    r = np.random.default_rng(seed)
    n, shards = int(r.integers(4, 40)), int(r.integers(2, 5))
    cap = int(r.integers(1, 6))
    dest = jnp.asarray(r.integers(0, shards, n))
    ok = jnp.asarray(r.uniform(0, 1, n) > 0.25)
    payload = jnp.asarray(r.integers(0, 1000, n))
    slot, sent, over = ops.bucket_slots(dest, ok, shards, cap)
    assert int(jnp.sum(sent)) + int(over) == int(jnp.sum(ok))
    bufs = ops.scatter_to_buckets({"x": payload}, slot, shards * cap)
    got = ops.take_from_buckets(bufs, slot, sent)["x"]
    np.testing.assert_array_equal(np.asarray(got)[np.asarray(sent)],
                                  np.asarray(payload)[np.asarray(sent)])
    assert (np.asarray(got)[~np.asarray(sent)] == 0).all()


_SHUFFLE_3SHARD = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import enable_x64
enable_x64()
from repro.db.plans import FKJoin, Scan, compile_plan
from repro.db.table import Table
mesh = jax.make_mesh((3,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(5)
# skewed: every left key hits owner 0 (key % 3 == 0)
left = Table.from_columns(
    {"k": jnp.asarray([0, 3, 6, 9, 0, 3, 6, 9, 0, 3, 6, 9])},
    prob=jnp.asarray(rng.uniform(0.1, 0.9, 12)))
right = Table.from_columns(
    {"k": jnp.asarray([0, 3, 6, 9, 12, 15]),
     "pay": jnp.asarray([10, 11, 12, 13, 14, 15])},
    prob=jnp.asarray(rng.uniform(0.1, 0.9, 6)))
tables = {"L": left, "R": right}
plan = FKJoin(Scan("L"), Scan("R"), "k", "k", ("pay",))
ref = compile_plan(plan, None)(tables)
ok = compile_plan(plan, mesh, join_gather_budget=1)(tables)
assert_biteq("shuffled", ref, ok, nan_equal=False)
# eager + concrete keys: histogram-sized buckets absorb the skew even at
# slack 1.0 (no overflow, bit-equal)
adaptive = compile_plan(plan, mesh, join_gather_budget=1,
                        shuffle_slack=1.0)(tables)
assert_biteq("adaptive", ref, adaptive, nan_equal=False)
# jit: traced keys -> slack 1.0 buckets ceil(local/3) < the skewed
# demand -> overflow NaN-poisons
bad = jax.jit(compile_plan(plan, mesh, join_gather_budget=1,
                           shuffle_slack=1.0))(tables)
assert np.isnan(np.asarray(bad.prob)).all(), np.asarray(bad.prob)
print("OK")
"""


@pytest.mark.multidevice
def test_shuffle_join_3shard_mesh_skew_and_overflow_poisoning(mesh_check):
    """On a real 3-device mesh with every key hashing to owner 0: the
    shuffle-lowered plan is bit-equal to mesh=None.  Eager compiles see
    the concrete keys and size buckets from the real histogram, so even
    slack 1.0 cannot overflow (the skew-adaptive capacities); under jit
    the keys are traced, the slack sizing comes back, and overflowing
    buckets poison the join probabilities with NaN (accounted, never
    silently wrong)."""
    assert "OK" in mesh_check()


_STATS_TABLES = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import enable_x64
enable_x64()
from repro.db.plans import FKJoin, Scan, compile_plan
from repro.db.table import Table
mesh = jax.make_mesh((3,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(5)
left = Table.from_columns(
    {"k": jnp.asarray([0, 3, 6, 9, 0, 3, 6, 9, 0, 3, 6, 9])},
    prob=jnp.asarray(rng.uniform(0.1, 0.9, 12)))
right = Table.from_columns(
    {"k": jnp.asarray([0, 3, 6, 9, 12, 15]),
     "pay": jnp.asarray([10, 11, 12, 13, 14, 15])},
    prob=jnp.asarray(rng.uniform(0.1, 0.9, 6)))
tables = {"L": left, "R": right}
plan = FKJoin(Scan("L"), Scan("R"), "k", "k", ("pay",))
ref = compile_plan(plan, None)(tables)
# jit + stats tables: the traced compile sizes buckets from the concrete
# stand-ins' histograms -> the skew fits even at slack 1.0, bit-equal
good = jax.jit(compile_plan(plan, mesh, join_gather_budget=1,
                            shuffle_slack=1.0,
                            stats_tables=tables))(tables)
assert_biteq("stats", ref, good, nan_equal=False)
# unrepresentative stats (uniform keys) undersize owner 0's bucket: the
# overflow guard still NaN-poisons instead of dropping rows silently
fake = {"L": Table.from_columns(
            {"k": jnp.asarray([0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11])},
            prob=left.prob),
        "R": right}
bad = jax.jit(compile_plan(plan, mesh, join_gather_budget=1,
                           shuffle_slack=1.0,
                           stats_tables=fake))(tables)
assert np.isnan(np.asarray(bad.prob)).any(), np.asarray(bad.prob)
print("STATS OK")
"""


@pytest.mark.multidevice
def test_stats_tables_make_jit_buckets_skew_adaptive(mesh_check):
    """The carried traced-key item: ``compile_plan(stats_tables=...)``
    hands the lowering concrete stand-in tables, so the key % n_shards
    histograms size the jit path's buckets OUTSIDE the trace.  The same
    skewed join that NaN-poisons under jit with flat slack 1.0 buckets
    (previous test) is bit-equal to mesh=None when the stats tables
    carry the real key population — and a WRONG histogram still has the
    NaN overflow guard as the backstop."""
    assert "STATS OK" in mesh_check()


# ------------------------------------------------ the multi-device checks
# (conftest `mesh_check`: one subprocess per device count)
MESH_CHECKS = {
    "test_fk_join_sharded_worlds_parity": equiv_check(_FK_JOIN_PARITY,
                                                      seconds=10),
    "test_group_ids_sharded_on_mesh": equiv_check(_GROUP_IDS_ON_MESH,
                                                  seconds=10),
    "test_shuffle_join_3shard_mesh_skew_and_overflow_poisoning": MeshCheck(
        3, _SHUFFLE_3SHARD, seconds=20),
    "test_stats_tables_make_jit_buckets_skew_adaptive": MeshCheck(
        3, _STATS_TABLES, seconds=5),
}
