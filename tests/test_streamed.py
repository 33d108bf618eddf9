"""Out-of-core streamed execution (db/plans.py StreamedScan path).

The contract under test: with ``device_row_budget`` set, a base table
whose per-shard rows exceed the budget stays HOST-side and the
aggregation pass above it runs as waves — and the result is
BIT-IDENTICAL to the fully-resident compile for ANY wave size and ANY
shard count (the canonical-chunk fold contract of db/plans.py extended
across host→device waves).  Every comparison here is exact equality,
never allclose.
"""
import dataclasses

import jax
import numpy as np
import pytest

from conftest import MeshCheck, assert_biteq
from repro.db import plans as P
from repro.db import tpch
from repro.db.plans import (GroupAgg, Scan, Select, compile_plan,
                            shard_capacity)
from repro.db.table import HostTable, Table

pytestmark = pytest.mark.outofcore


def _db():
    # lineitem = 192 rows (csz 24 on the default 8-chunk grid); orders 48,
    # partsupp 96, everything else smaller — so device_row_budget=64
    # streams ONLY lineitem (and 128 for q20, whose partsupp build side
    # must stay resident).
    return tpch.generate(n_orders=48, lines_per_order=4, n_parts=24,
                         n_suppliers=8, n_customers=24, seed=0)


# ------------------------------------------------ single-device streaming
_QUERY_BUDGET = {"q1": 64, "q3": 64, "q6": 64, "q18": 64, "q20": 128}


def _run_query(db, qname, plan_opts=None):
    kw = dict(plan_opts=plan_opts) if plan_opts else {}
    if qname == "q1":
        return tpch.q1(db, "aggregate", **kw)
    if qname == "q3":
        return tpch.q3(db, "aggregate", max_groups=64, **kw)
    if qname == "q6":
        return tpch.q6(db, "aggregate", num_freq=256, **kw)
    if qname == "q18":
        return tpch.q18(db, "aggregate", max_groups=64, **kw)
    return tpch.q20(db, "aggregate", max_groups=64, **kw)


@pytest.mark.parametrize("prune", [True, False])
@pytest.mark.parametrize("qname", sorted(tpch.QUERIES))
def test_streamed_bit_equal_resident(qname, prune):
    """Every TPC-H query: streamed lineitem == resident, bit for bit —
    with required-column pruning on (the default) and off."""
    db = _db()
    ref = _run_query(db, qname)
    got = _run_query(db, qname,
                     dict(device_row_budget=_QUERY_BUDGET[qname],
                          stream_prune_columns=prune))
    assert_biteq(f"{qname}/prune={prune}", ref, got)


def _plan_for(qname):
    return {"q1": lambda: tpch.q1_plan(),
            "q3": lambda: tpch.q3_plan(),
            "q6": lambda: tpch.q6_plan(num_freq=256),
            "q18": lambda: tpch.q18_plan(),
            "q20": lambda: tpch.q20_plan()}[qname]()


@pytest.mark.parametrize("qname", sorted(tpch.QUERIES))
def test_streamed_bit_equal_disk_backed(qname, tmp_path):
    """Every TPC-H query streaming from a DISK-BACKED (save -> open,
    np.memmap columns) lineitem is bit-identical to the resident compile
    — pruned and unpruned, across wave sizes."""
    db = _db()
    plan = _plan_for(qname)
    tabs = db.tables()
    ref = compile_plan(plan)(tabs)
    HostTable.from_table(tabs["lineitem"]).save(str(tmp_path / "li"))
    disk = dict(tabs)
    disk["lineitem"] = HostTable.open(str(tmp_path / "li"))
    for prune in (True, False):
        got = compile_plan(plan,
                           device_row_budget=_QUERY_BUDGET[qname],
                           stream_prune_columns=prune)(disk)
        assert_biteq(f"{qname}/disk/prune={prune}", ref, got)
    for wc in (1, 3, 8):
        got = compile_plan(plan,
                           device_row_budget=_QUERY_BUDGET[qname],
                           stream_wave_chunks=wc)(disk)
        assert_biteq(f"{qname}/disk/wc={wc}", ref, got)


def test_save_open_roundtrip(tmp_path):
    """save -> open restores every array (values, dtypes) and the
    VIRTUAL padding (only stored rows hit the disk); mmap_mode=None
    loads into RAM instead."""
    ht = HostTable({"a": np.arange(10), "b": np.linspace(0, 1, 10)},
                   prob=np.full(10, 0.5),
                   valid=np.arange(10) % 3 != 0).pad_to(16)
    ht.save(str(tmp_path))
    assert ht.stored_rows == 10 and ht.capacity == 16
    back = HostTable.open(str(tmp_path))
    assert back.capacity == 16 and back.stored_rows == 10
    for k in ("a", "b"):
        assert isinstance(back[k], np.memmap)
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(ht[k]))
        assert back[k].dtype == ht[k].dtype
    np.testing.assert_array_equal(back.prob, ht.prob)
    np.testing.assert_array_equal(back.valid, ht.valid)
    assert_biteq("roundtrip/to_table", ht.to_table(), back.to_table())
    ram = HostTable.open(str(tmp_path), mmap_mode=None)
    assert not isinstance(ram["a"], np.memmap)
    np.testing.assert_array_equal(ram["a"], ht["a"])


@pytest.mark.parametrize("wave_chunks", [1, 3, 8])
def test_wave_size_invariance(wave_chunks):
    """The wave schedule is invisible in the results: one chunk per wave,
    a ragged tail (3 of 8 chunk slots per wave => a padding wave), and the
    whole table in one wave all reproduce the resident bits."""
    db = _db()
    for qname in ("q1", "q6", "q18"):
        ref = _run_query(db, qname)
        got = _run_query(db, qname,
                         dict(device_row_budget=64,
                              stream_wave_chunks=wave_chunks))
        assert_biteq(f"{qname}/wc{wave_chunks}", ref, got)


def test_sync_transfer_matches_double_buffered():
    """stream_double_buffer only changes the transfer schedule, never the
    numbers."""
    db = _db()
    ref = _run_query(db, "q1")
    got = _run_query(db, "q1", dict(device_row_budget=64,
                                    stream_double_buffer=False))
    assert_biteq("q1/sync", ref, got)


def test_streamed_exact_cf_frequency_slabs():
    """Exact-CF aggregation with a cf budget forcing multiple frequency
    slabs, streamed: the per-wave slab passes and the cross-wave chunk
    fold compose with the frequency-slab loop bit-exactly."""
    db = _db()
    ref = tpch.q6(db, "aggregate", num_freq=256,
                  plan_opts=dict(cf_budget_elems=256))
    got = tpch.q6(db, "aggregate", num_freq=256,
                  plan_opts=dict(cf_budget_elems=256, device_row_budget=64))
    assert_biteq("q6/cf_slabs", ref, got)


# ----------------------------------------------------- host-table surface
def test_host_table_streams_and_materialises():
    """A HostTable input streams under a budget, materialises without one,
    and both reproduce the device-resident bits."""
    db = _db()
    plan = GroupAgg(Scan("lineitem"), ("l_returnflag",), "l_quantity",
                    "SUM", 4, "normal")
    dev = db.tables()
    host = dict(dev)
    host["lineitem"] = HostTable.from_table(db.lineitem)
    ref = compile_plan(plan, None)(dev)
    assert_biteq("host/resident", ref, compile_plan(plan, None)(host))
    assert_biteq("host/streamed", ref,
                  compile_plan(plan, None, device_row_budget=64)(host))


def test_host_table_slabs():
    ht = HostTable({"a": np.arange(10)}, prob=np.full(10, 0.5))
    s = ht.slab(8, 4)
    assert isinstance(s, Table) and s.capacity == 4
    np.testing.assert_array_equal(np.asarray(s["a"]), [8, 9, 0, 0])
    np.testing.assert_array_equal(np.asarray(s.valid),
                                  [True, True, False, False])
    np.testing.assert_array_equal(np.asarray(s.prob), [0.5, 0.5, 0.0, 0.0])
    ws = ht.pad_to(12).wave_slab((0, 6), 3)
    np.testing.assert_array_equal(np.asarray(ws["a"]), [0, 1, 2, 6, 7, 8])
    starts = [s0 for s0, _ in ht.slabs(4)]
    assert starts == [0, 4, 8]


def test_wave_slab_strided_non_contiguous_starts():
    """Per-shard runs with gaps between them (the mesh wave layout):
    each shard contributes its own run, concatenated in shard order —
    and a run reaching past the stored rows zero-fills (virtual pad)."""
    ht = HostTable({"a": np.arange(20)}, prob=np.full(20, 0.25)).pad_to(24)
    ws = ht.wave_slab((2, 11, 21), 3)
    np.testing.assert_array_equal(np.asarray(ws["a"]),
                                  [2, 3, 4, 11, 12, 13, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(ws.valid)[-4:],
                                  [True, False, False, False])
    np.testing.assert_array_equal(np.asarray(ws.prob)[-3:], [0, 0, 0])


def test_wave_slab_zero_alloc_out_buffers():
    """wave_slab(out=) fills the caller's preallocated buffers in place
    (the streamed executor's ping-pong pair) and returns the same
    arrays; a second fill overwrites, including zeroed tails."""
    ht = HostTable({"a": np.arange(10, dtype=np.int64)},
                   prob=np.full(10, 0.5)).pad_to(12)
    buf = ht.alloc_slab(6)
    out = ht.wave_slab((0, 6), 3, out=buf)
    assert out.columns["a"] is buf.columns["a"]
    assert out.prob is buf.prob and out.valid is buf.valid
    np.testing.assert_array_equal(buf.columns["a"], [0, 1, 2, 6, 7, 8])
    out2 = ht.wave_slab((3, 9), 3, out=buf)
    np.testing.assert_array_equal(buf.columns["a"], [3, 4, 5, 9, 0, 0])
    np.testing.assert_array_equal(buf.valid, [1, 1, 1, 1, 0, 0])
    np.testing.assert_array_equal(buf.prob[-2:], [0.0, 0.0])


def test_single_row_and_one_chunk_tables():
    """Degenerate sizes: a single-row table padded to one chunk slot
    slabs/streams correctly, and a one-chunk table streams in one wave."""
    ht = HostTable({"a": np.asarray([7])}, prob=np.asarray([0.5]))
    p = ht.pad_to_multiple(8)
    assert p.capacity == 8 and p.stored_rows == 1
    s = p.slab(0, 8)
    np.testing.assert_array_equal(np.asarray(s["a"]),
                                  [7, 0, 0, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(s.valid)[:2], [True, False])
    db = _db()
    one = Table.from_columns(
        {"k": db.lineitem["l_returnflag"][:8],
         "v": db.lineitem["l_quantity"][:8]},
        prob=db.lineitem.prob[:8])
    plan = GroupAgg(Scan("t"), ("k",), "v", "SUM", 4, "normal")
    ref = compile_plan(plan, None, canonical_chunks=1)({"t": one})
    got = compile_plan(plan, None, canonical_chunks=1,
                       device_row_budget=4)(
        {"t": HostTable.from_table(one)})
    assert_biteq("one-chunk", ref, got)


def test_select_columns_shares_arrays_and_pad():
    ht = HostTable({"a": np.arange(10), "b": np.arange(10) * 2},
                   prob=np.full(10, 0.5)).pad_to(16)
    pruned = ht.select_columns(["a"])
    assert set(pruned.columns) == {"a"}
    assert pruned["a"] is ht["a"] and pruned.prob is ht.prob
    assert pruned.capacity == 16
    np.testing.assert_array_equal(np.asarray(pruned.slab(8, 4)["a"]),
                                  [8, 9, 0, 0])


def test_pruned_stream_ships_fewer_bytes():
    """The runtime byte counters: Q6 (3 of 10 lineitem columns) pruned
    ships strictly fewer slab bytes than unpruned, and the host-slice
    timer advances."""
    db = _db()
    host = dict(db.tables())
    host["lineitem"] = HostTable.from_table(db.lineitem)
    plan = tpch.q6_plan()
    seen = {}
    for prune in (True, False):
        P.reset_stream_stats()
        compile_plan(plan, None, device_row_budget=64,
                     stream_wave_chunks=1,    # pin: isolate the payload
                     stream_prune_columns=prune)(host)
        seen[prune] = P.stream_stats()
    assert seen[True]["slab_bytes"] < seen[False]["slab_bytes"]
    assert seen[True]["waves"] == seen[False]["waves"]
    assert seen[True]["slice_s"] >= 0.0


def test_stats_tables_accepts_host_table():
    """compile_plan(stats_tables=...) histograms a HostTable's numpy
    columns directly: under jit (traced runtime tables) the concrete
    stats size the exchange buckets, same answer as eager."""
    db = _db()
    plan = tpch.q3_plan()
    tabs = db.tables()
    ref = compile_plan(plan)(tabs)
    stats = {k: HostTable.from_table(t) for k, t in tabs.items()}
    fn = compile_plan(plan, stats_tables=stats,
                      join_gather_budget=1)   # force exchanges
    got = jax.jit(fn)(tabs)
    assert_biteq("stats/host", ref, got)


def test_pad_to_multiple_cached():
    """The chunk-grid pad memo: re-padding to the same grid is free (the
    streamed executor re-pads every compiled() call)."""
    t = Table.from_columns({"a": np.arange(10)})
    p = t.pad_to_multiple(8)
    assert p.capacity == 16
    assert p.pad_to_multiple(8) is p
    ht = HostTable({"a": np.arange(10)})
    hp = ht.pad_to_multiple(8)
    assert hp.capacity == 16 and hp.pad_to_multiple(8) is hp


# -------------------------------------------------------- error surfaces
def test_streamed_build_side_rejected():
    """Only the probe side of a join may stream: a budget that would
    stream a build-side table is a loud NotImplementedError, not a wrong
    answer."""
    db = _db()
    with pytest.raises(NotImplementedError, match="build side"):
        tpch.q20(db, "aggregate", max_groups=64,
                 plan_opts=dict(device_row_budget=64))


def test_streamed_requires_aggregation():
    """A StreamedScan with no aggregation above it cannot execute (the
    wave loop folds per-chunk UDA states, not raw relational output)."""
    db = _db()
    fn = compile_plan(Select(Scan("lineitem"),
                             lambda t: t["l_quantity"] > 0),
                      None, device_row_budget=64)
    with pytest.raises(NotImplementedError,
                       match="grouped aggregation") as ei:
        fn(db.tables())
    # the error names the workaround knobs
    assert "device_row_budget" in str(ei.value)
    assert "to_table" in str(ei.value)


# ------------------------------------------------------------ mesh waves
_STREAMED_MESH = """
enable_x64()
db = tpch.generate(n_orders=48, lines_per_order=4, n_parts=24,
                   n_suppliers=8, n_customers=24, seed=0)

opts = dict(device_row_budget=128)
assert_biteq("q1", tpch.q1(db, "aggregate"),
             tpch.q1(db, "aggregate", mesh=mesh, plan_opts=opts))
assert_biteq("q3", tpch.q3(db, "aggregate", max_groups=64),
             tpch.q3(db, "aggregate", max_groups=64, mesh=mesh,
                     plan_opts=opts))
assert_biteq("q6", tpch.q6(db, "aggregate", num_freq=256),
             tpch.q6(db, "aggregate", num_freq=256, mesh=mesh,
                     plan_opts=opts))
assert_biteq("q18", tpch.q18(db, "aggregate", max_groups=64),
             tpch.q18(db, "aggregate", max_groups=64, mesh=mesh,
                      plan_opts=opts))
assert_biteq("q20", tpch.q20(db, "aggregate", max_groups=64),
             tpch.q20(db, "aggregate", max_groups=64, mesh=mesh,
                      plan_opts=opts))
assert_biteq("q1_wc1", tpch.q1(db, "aggregate"),
             tpch.q1(db, "aggregate", mesh=mesh,
                     plan_opts=dict(device_row_budget=128,
                                    stream_wave_chunks=1)))

# disk-backed (save -> open, mmap columns) lineitem on the mesh, with
# and without column pruning
import tempfile
from repro.db.table import HostTable
tabs = db.tables()
ref = compile_plan(tpch.q1_plan(), mesh)(tabs)
with tempfile.TemporaryDirectory() as d:
    HostTable.from_table(tabs["lineitem"]).save(d)
    disk = dict(tabs)
    disk["lineitem"] = HostTable.open(d)
    for prune in (True, False):
        got = compile_plan(tpch.q1_plan(), mesh, device_row_budget=128,
                           stream_prune_columns=prune)(disk)
        assert_biteq("q1_disk_prune=%s" % prune, ref, got)
print("STREAM BITEQ OK")
"""

MESH_CHECKS = {f"test_streamed_mesh_bit_equal[{d}]":
               MeshCheck(d, _STREAMED_MESH, seconds=seconds)
               for d, seconds in ((2, 70), (3, 80))}


@pytest.mark.multidevice
@pytest.mark.parametrize("devices", [2, 3])
def test_streamed_mesh_bit_equal(mesh_check, devices):
    """Streamed execution on a real multi-device mesh — including the
    3-shard count that does not divide the 8-chunk grid — is bit-equal to
    the single-device RESIDENT compile, across query shapes (plain agg,
    join spine, scalar agg, reweight, plan suffix above the streamed
    pass) and a 1-chunk wave schedule."""
    assert "STREAM BITEQ OK" in mesh_check()
