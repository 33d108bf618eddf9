"""The benchmark's own tests: CPU only, in this process, a few seconds
each.  Four virtual CPU devices stand in for a 2x2 host; every Pallas
kernel runs in interpret mode, as on the CPU the program otherwise takes
its jnp oracles.

    python -m pytest bench/tests
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


import json  # noqa: E402

import pytest  # noqa: E402


# Cells whose files are kept under bench/ but that BENCHMARK.json does not
# list yet (not proven on the chip); the tests still drive them.
PREPARED_CONFIGS = [dict(name="tpch_sf1_2x2", source="test", reduced=[],
                         file="bench/configs/tpch_sf1_2x2.json", why="test")]
PREPARED_CELLS = [dict(name="sf1_2x2.q3_copart", config="tpch_sf1_2x2",
                       traffic="q3_copart", chips=4, why="test")]
# A TPC-H-shaped database of 2,560 orders.
TINY = dict(n_orders=2560, n_parts=2000, n_suppliers=100, n_customers=256)


def all_cells() -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)["workloads"] + PREPARED_CELLS


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout whose configurations hold 2,560 orders: ``BENCHMARK.json``
    with each configuration's file rewritten and the prepared cells added,
    ``bench/`` linked."""
    root = tmp_path_factory.mktemp("tiny")
    os.symlink(os.path.join(ROOT, "bench"), root / "bench")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] += PREPARED_CONFIGS
    bench["workloads"] += PREPARED_CELLS
    (root / "cfg").mkdir()
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        cfg.update(TINY)
        c["file"] = f"cfg/{c['name']}.json"
        with open(root / c["file"], "w") as f:
            json.dump(cfg, f)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(root)


@pytest.fixture
def kernels_interpreted(monkeypatch):
    """The program's Pallas kernels in interpret mode (the CPU otherwise
    takes its jnp oracles, which the chip never runs)."""
    from repro.core import uda
    monkeypatch.setattr(uda, "_use_pallas", lambda k: k != "xla")
