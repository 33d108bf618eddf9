"""The reduction from a trace to busy time, layer time and named gaps."""
import json
import os

import pytest

from bench.harness import trace
from conftest import ROOT

MS = 1_000_000
SORT = ("%sort.3 = (s32[64]{0:T(1024)}, s32[64]{0:T(1024)}) "
        "sort(s32[64]{0:T(1024)} %a, s32[64]{0:T(1024)} %b)")
FUSION1 = "%fusion.1 = f32[64]{0:T(1024)} fusion(f32[64]{0} %c), kind=kLoop"
WHILE = ("%while.7 = (s32[]{:T(128)}, f32[8]{0}) while((s32[]{:T(128)}, "
         "f32[8]{0}) %t), condition=%c, body=%b")
KERNEL = ("%group_cumulant_sums.2 = f32[8,128]{1,0:T(8,128)} custom-call("
          "s32[4]{0} %g), custom_call_target=\"tpu_custom_call\"")
FUSION2 = "%fusion.2 = f32[64]{0:T(1024)} fusion(f32[64]{0} %d), kind=kLoop"


def _records():
    """Window [0, 100) ms.  Device 0: ops [10, 30) sort, [20, 40)
    fusion (overlapping), [58, 72) a while loop around [60, 70) kernel,
    [95, 120) fusion (clipped at 100).  Host: request.q3 [5, 50),
    collect [50, 55), request.q6 [55, 100)."""
    host = dict(plane="/host:CPU", line="python", events=[
        ["window", 0, 100 * MS], ["request.q3", 5 * MS, 45 * MS],
        ["collect", 50 * MS, 5 * MS], ["request.q6", 55 * MS, 45 * MS]])
    dev = dict(plane="/device:TPU:0", line="XLA Ops", events=[
        [SORT, 10 * MS, 20 * MS], [FUSION1, 20 * MS, 20 * MS],
        [WHILE, 58 * MS, 14 * MS], [KERNEL, 60 * MS, 10 * MS],
        [FUSION2, 95 * MS, 25 * MS]])
    other = dict(plane="/device:TPU:0", line="XLA Modules", events=[
        ["jit_x", 0, 100 * MS]])
    return [host, dev, other]


MATCH = dict(sort=["^sort:"], uda=["^custom-call:group_cumulant_sums"],
             collective=["^all-reduce"])


def test_busy_union_and_window():
    r = trace.Reduced(_records(), [0], MATCH)
    assert r.window_s == pytest.approx(0.100)
    # union: [10, 40) + [58, 72) + [95, 100) = 49 ms
    assert r.busy_s == pytest.approx(0.049)
    assert r.idle_share() == pytest.approx(0.51)


def test_matcher_attribution():
    r = trace.Reduced(_records(), [0], MATCH)
    assert r.layer_s == pytest.approx(dict(sort=0.020, uda=0.010))
    assert "collective" not in r.layer_s
    assert r.op_s["fusion:fusion.2 f32[64]"] == pytest.approx(0.005)
    # the loop's self time leaves out the kernel inside it
    assert r.op_s["while:while.7 (s32[], f32[8])"] == pytest.approx(0.004)


def test_short_names():
    assert trace.short_name(KERNEL) == (
        "custom-call:group_cumulant_sums.2 f32[8,128]")
    assert trace.short_name(SORT) == "sort:sort.3 (s32[64], s32[64])"
    assert trace.short_name("request.q3") == "request.q3"


def test_gaps_named_by_host_span():
    r = trace.Reduced(_records(), [0], MATCH)
    # gaps [0, 10) mid 5 -> request.q3 starts at 5: covers; [40, 58) mid
    # 49 -> request.q3; [72, 95) mid 83.5 -> request.q6.
    got = sorted(r.gaps)
    assert [n for n, _ in got] == ["request.q3", "request.q3", "request.q6"]
    assert [s for _, s in got] == pytest.approx([0.010, 0.018, 0.023])
    b = r.breakdown()
    assert b["idle_gaps"][0] == ["request.q3", pytest.approx(0.028)]
    assert {n for n, _ in b["device_ops"][:2]} == {
        "fusion:fusion.1 f32[64]", "sort:sort.3 (s32[64], s32[64])"}


def test_devices_averaged():
    recs = _records()
    recs.append(dict(plane="/device:TPU:1", line="XLA Ops",
                     events=[["%all-reduce.1 = f32[8]{0} all-reduce("
                              "f32[8]{0} %x), to_apply=%add", 0, 100 * MS]]))
    r = trace.Reduced(recs, [0, 1], MATCH)
    assert r.devices_found == 2
    assert r.busy_s == pytest.approx((0.049 + 0.100) / 2)
    assert r.layer_s["collective"] == pytest.approx(0.100)


def test_no_window_span_is_an_error():
    recs = [r for r in _records() if r["plane"] != "/host:CPU"]
    with pytest.raises(ValueError):
        trace.Reduced(recs, [0], MATCH)


def test_recorded_chip_trace():
    """One Q6 request recorded on a v5e: the reduction against a
    brute-force union on a microsecond grid and hand-matched kernels."""
    import gzip

    import numpy as np

    with gzip.open(os.path.join(os.path.dirname(__file__), "data",
                                "q6_trace.json.gz"), "rt") as f:
        recs = json.load(f)["records"]
    matchers = {}
    for name in ("uda_kernels", "sort"):
        with open(os.path.join(ROOT, "bench", "matchers",
                               f"{name}.json")) as f:
            m = json.load(f)
        matchers[m["layer"]] = m["ops"]
    r = trace.Reduced(recs, [0], matchers)
    (win,) = [e for e in recs[0]["events"] if e[0] == "window"]
    grid = np.zeros(win[2] // 1000 + 1, bool)
    ops = recs[1]["events"]
    for _, s, d in ops:
        a, b = max(s, 0) // 1000, min(s + d, win[2]) // 1000
        grid[a:b] = True
    assert r.window_s == pytest.approx(win[2] * 1e-9)
    assert r.busy_s == pytest.approx(grid.sum() * 1e-6, abs=2e-5)
    kernels = [d for n, s, d in ops if n.startswith("%cumulant_sums")
               and "custom-call(" in n]
    sorts = [d for n, s, d in ops if " sort(" in n]
    assert kernels and sorts
    assert r.layer_s["uda"] == pytest.approx(sum(kernels) * 1e-9)
    assert r.layer_s["sort"] == pytest.approx(sum(sorts) * 1e-9)
    assert 0.0 < r.idle_share() < 0.1
