"""Cells resolve by name, and a new cell needs only new files."""
import json
import os
import shutil

import numpy as np
import pytest

from bench.harness import runner
from bench.harness.spec import Cell
from conftest import ROOT


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload",
                         [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves(workload):
    cell = Cell(ROOT, workload)
    assert cell.queries and cell.end_to_end and cell.per_layer
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    for q, mod in cell.queries.items():
        assert set(cell.limits[q]) >= {"keys", "err"}
        dom = mod.domain(cell.options[q])
        point = {p: list(v)[0] for p, v in dom.items()}
        assert mod.params(point)
    for name in cell.layers:
        assert callable(cell.layers[name].read)
    assert cell.peaks("TPU v5 lite")["hbm_bytes_per_s"] > 0
    with pytest.raises(KeyError):
        cell.peaks("some other device")


def test_cell_added_from_files_only(tmp_path):
    """A new configuration, traffic mix, query, matcher and per-layer
    metric, each a new file, plus new BENCHMARK.json entries."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = _bench()
    cfg = json.load(open(os.path.join(ROOT, b["configs"][0]["file"])))
    cfg["n_orders"] = 512
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "bench", "traffic", "q6_only.json"),
              "w") as f:
        json.dump(dict(queries=["q6_twin"], options={}), f)
    shutil.copy(os.path.join(ROOT, "bench", "queries", "q6.py"),
                os.path.join(root, "bench", "queries", "q6_twin.py"))
    shutil.copy(os.path.join(ROOT, "bench", "queries", "q6.limits.json"),
                os.path.join(root, "bench", "queries",
                             "q6_twin.limits.json"))
    with open(os.path.join(root, "bench", "matchers", "fusions.json"),
              "w") as f:
        json.dump(dict(layer="fusion", ops=["fusion"]), f)
    with open(os.path.join(root, "bench", "layers", "fusion_ms.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx.layer_ms_per_answer('fusion')\n")
    b["configs"].append(dict(name="tiny", source="test", reduced=[],
                             file="bench/configs/tiny.json", why="test"))
    b["workloads"].append(dict(name="tiny.q6", config="tiny",
                               traffic="q6_only", chips=1, why="test"))
    b["per_layer"].append(dict(name="fusion_ms", unit="ms", better="lower",
                               source="device_trace", layer="fusion",
                               moves="answers_per_s",
                               workloads=["tiny.q6"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    cell = Cell(root, "tiny.q6")
    assert cell.scale["n_orders"] == 512
    assert list(cell.queries) == ["q6_twin"]
    assert "fusion" in cell.matchers and "fusion_ms" in cell.layers
    stream = runner.requests(cell, seed=3)
    first = [next(stream) for _ in range(4)]
    assert [(s, q) for s, q, _ in first] == [(i, "q6_twin") for i in range(4)]
    assert first[0] == next(runner.requests(cell, seed=3))
    assert "fusion_ms" not in Cell(root, "sf1.scan_agg").layers


def test_requests_are_streams_of_permutations():
    cell = Cell(ROOT, "sf1.grouped_join")
    stream = runner.requests(cell, seed=2 ** 40 + 1)
    reqs = [next(stream) for _ in range(30)]
    for i in range(0, 30, 3):
        assert sorted(q for _, q, _ in reqs[i:i + 3]) == ["q18", "q20", "q3"]
        assert {s for s, _, _ in reqs[i:i + 3]} == {i // 3}
    again = runner.requests(cell, seed=2 ** 40 + 1)
    assert [next(again) for _ in range(30)] == reqs
    # every parameter runs through its whole domain before a value repeats
    segments = [p["segment"] for _, q, p in reqs if q == "q3"]
    assert sorted(segments[:5]) == [0, 1, 2, 3, 4]
    assert sorted(segments[5:10]) == [0, 1, 2, 3, 4]
