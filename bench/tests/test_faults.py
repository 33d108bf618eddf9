"""A run with its timed path broken underneath reads ``correct`` false:
an answer returned unchanged from an earlier request (a state left
unchanged), half of the rows left out, one answer altered where it is
produced, and (on a 4-chip cell) the exchange between chips left out.
The harness's look for a chip is skipped: these run on CPU devices, and
each fault is planted in the system under test from the outside."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import system
from bench.harness.runner import run_cell
from bench.harness.spec import Cell
from conftest import all_cells


def _cases():
    out = []
    for w in all_cells():
        faults = [None, "stale", "half_rows", "altered"]
        if w["chips"] > 1:
            faults.append("no_exchange")
        out += [(w["name"], f) for f in faults]
    return out


def _alter(out):
    """One valid row of every float leaf moved by a part in a thousand."""
    valid = out["valid"] if isinstance(out, dict) else out.valid
    i = int(np.argmax(np.asarray(valid).reshape(-1)))
    n = np.asarray(valid).size

    def bump(x):
        if (jnp.issubdtype(x.dtype, jnp.floating) and x.ndim
                and x.shape[0] == n):
            return x.at[i].set(x[i] * 1.001 + 1e-3)
        return x

    return jax.tree.map(bump, out)


def _half_rows(h, shards):
    h["lineitem"]["valid"][h["lineitem"]["valid"].size // 2:] = False


def _no_exchange(h, shards):
    """Each shard joins only the dimension rows it holds itself, as a plan
    whose exchange between chips were left out would."""
    o, c = h["orders"], h["customer"]
    o_shard = o["o_orderkey"] * shards // o["o_orderkey"].size
    c_shard = o["o_custkey"] * shards // c["c_custkey"].size
    o["valid"] &= o_shard == c_shard


def _plant(monkeypatch, fault):
    if fault in ("half_rows", "no_exchange"):
        cut = _half_rows if fault == "half_rows" else _no_exchange
        init = system.System.__init__

        def broken_init(self, h, config, devices):
            h = {k: dict(t, valid=t["valid"].copy()) for k, t in h.items()}
            cut(h, len(devices))
            init(self, h, config, devices)

        monkeypatch.setattr(system.System, "__init__", broken_init)
    elif fault in ("stale", "altered"):
        submit = system.System.submit
        last = {}

        def broken_submit(self, plan, params):
            if fault == "stale" and id(plan) in last:
                return last[id(plan)], dict(attempts=1)
            out, info = submit(self, plan, params)
            last[id(plan)] = out
            return (_alter(out) if fault == "altered" else out), info

        monkeypatch.setattr(system.System, "submit", broken_submit)


@pytest.mark.parametrize("workload,fault", _cases())
def test_fault_reads_incorrect(workload, fault, tiny_root,
                               kernels_interpreted, monkeypatch):
    chips = Cell(tiny_root, workload).chips
    _plant(monkeypatch, fault)
    result, log = run_cell(tiny_root, workload, seed=2 ** 31 + 11,
                           seconds=1.0 if fault == "stale" else 0.2,
                           trace=False,
                           devices=jax.devices()[:chips],
                           t_process=time.perf_counter())
    assert result["attempted"] > (len(Cell(tiny_root, workload).queries)
                                  if fault == "stale" else 0)
    assert result["correct"] is (fault is None), "\n".join(log)
    if fault is not None:
        assert result["failed"] > 0
