"""Run a batch of multi-device check scripts in ONE process.

    python tests/mesh_runner.py BATCH.json RESULTS.jsonl

BATCH.json holds ``{"devices": N, "checks": [[name, script], ...]}``.
The parent (``tests/conftest.py``) sets XLA_FLAGS so this process sees N
host devices.  Each script runs in a fresh copy of the prelude
namespace, and its outcome — captured stdout, and the
traceback if it raised — is appended to RESULTS.jsonl as soon as it
ends, so a batch cut by its time limit keeps the outcomes it reached.

What scripts share is only what they compute the same way: the imported
modules, compiled programs, and values put in ``memo``.  State that a
result depends on is reset around every script: the x64 flag, the
collective counters and the active fault plan.

``assert_biteq`` is the one bit-equality check of the test suite, used
here and, through ``conftest``, in-process.
"""
import contextlib
import io
import json
import linecache
import sys
import time
import traceback

import jax
import numpy as np


def assert_biteq(name, ref, got, nan_equal=True):
    """``got`` is ``ref`` bit for bit: the same tree, and per leaf the
    same shape, dtype and elements.  ``nan_equal`` lets a NaN match a NaN
    in the same place (the NaN-poison outputs); without it any NaN fails."""
    la, ta = jax.tree.flatten(ref)
    lb, tb = jax.tree.flatten(got)
    assert str(ta) == str(tb), (name, str(ta), str(tb))
    for i, (a, b) in enumerate(zip(la, lb)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, \
            (name, i, a.shape, b.shape, a.dtype, b.dtype)
        eq = a == b
        f = a.astype(np.float64, copy=False)
        g = b.astype(np.float64, copy=False)
        if nan_equal:
            eq |= np.isnan(f) & np.isnan(g)
        if not eq.all():
            raise AssertionError(
                f"{name} leaf {i}: {np.flatnonzero(~eq).size} of {a.size} "
                f"elements differ, max |d| = {np.nanmax(np.abs(f - g))}")


_MEMO: dict = {}


def memo(key, make):
    """``make()``, computed once per process under ``key`` — for a
    reference that several scripts compute the same way."""
    if key not in _MEMO:
        _MEMO[key] = make()
    return _MEMO[key]


def _prelude(devices: int) -> dict:
    """The names every mesh script may use without importing them."""
    import jax.numpy as jnp

    from repro.core import enable_x64
    from repro.db import distributed, tpch
    from repro.db.plans import (FKJoin, GroupAgg, Map, Project,
                                ReweightGreater, Scan, Select, compile_plan)
    from repro.db.table import Table
    distributed.reuse_eager_shard_map_programs()
    mesh = jax.make_mesh((devices,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    names = dict(jax=jax, jnp=jnp, np=np, enable_x64=enable_x64, tpch=tpch,
                 Table=Table, compile_plan=compile_plan, mesh=mesh)
    names.update(FKJoin=FKJoin, GroupAgg=GroupAgg, Map=Map, Project=Project,
                 ReweightGreater=ReweightGreater, Scan=Scan, Select=Select)
    return names


def _run(name: str, script: str, shared: dict) -> str | None:
    """Run one script in a fresh copy of ``shared``; its traceback, or
    None.  The state a result could depend on is reset around it: the
    x64 flag, the collective counters and the active fault plan."""
    from repro.db import distributed
    from repro.testing import faults
    x64 = jax.config.jax_enable_x64
    distributed.reset_collective_counts()
    linecache.cache[name] = (len(script), None, script.splitlines(True),
                             name)
    try:
        exec(compile(script, name, "exec"), dict(shared))
        if faults._ACTIVE is not None:
            raise AssertionError("the script left a fault plan active")
    except Exception:
        return traceback.format_exc()
    finally:
        faults._ACTIVE = None
        jax.config.update("jax_enable_x64", x64)
    return None


def main(batch_path: str, results_path: str) -> None:
    with open(batch_path) as f:
        batch = json.load(f)
    shared = dict(_prelude(batch["devices"]), assert_biteq=assert_biteq,
                  memo=memo)
    with open(results_path, "a") as out:
        for name, script in batch["checks"]:
            buf = io.StringIO()
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(buf):
                error = _run(name, script, shared)
            out.write(json.dumps(dict(
                name=name, stdout=buf.getvalue(), error=error,
                seconds=round(time.perf_counter() - t0, 3))) + "\n")
            out.flush()


if __name__ == "__main__":
    main(*sys.argv[1:])
