# NOTE: no --xla_force_host_platform_device_count here (smoke tests and
# benches must see 1 device; only launch/dryrun pins 512).  Multi-device
# tests spawn subprocesses with their own XLA_FLAGS.
import os
import subprocess
import sys

import jax
import pytest

from repro.core import enable_x64

enable_x64()  # the PGF engine's exactness tests need f64 on CPU

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(script: str, devices: int = 2) -> str:
    """Run a test script in a subprocess with its own multi-device CPU
    XLA_FLAGS (the conftest pins the parent process to 1 device) — the ONE
    copy of the boilerplate shared by every `multidevice` test module."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.fixture(autouse=True, scope="module")
def _flat_compiler_footprint():
    """The CPU backend segfaulted inside backend_compile once a single
    process accreted a few hundred live compiled executables (seen with
    jaxlib 0.4.36: the unmodified full suite died with a fatal SIGSEGV in
    whichever test file crossed the threshold, always under
    compile_or_get_cached).
    Dropping the jit caches at module boundaries keeps the compiler
    footprint flat; cross-module cache reuse is negligible since each
    file compiles its own shapes."""
    yield
    jax.clear_caches()


@pytest.fixture
def rng():
    import numpy as np
    return np.random.default_rng(0)


# --------------------------------------------------- mesh-equivalence harness
# `compile_plan(root, mesh)` promises BIT-IDENTICAL results to the
# single-device compile (the canonical-chunk fold tree, db/plans.py).  The
# harness runs a setup script under a multi-device CPU subprocess and
# asserts exact equality — shapes, dtypes and every bit of every leaf.
_MESH_EQUIV_TEMPLATE = '''
import jax, jax.numpy as jnp, numpy as np
from repro.core import enable_x64
enable_x64()
from repro.db import tpch
from repro.db.plans import (FKJoin, GroupAgg, Map, Project, ReweightGreater,
                            Scan, Select, compile_plan)
from repro.db.table import Table
mesh = jax.make_mesh((__DEVICES__,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))

__SETUP__

if "pairs" not in dir():
    # default harness shape: setup defined `plan` and `tables`
    pairs = [("plan", compile_plan(plan, None)(tables),
              compile_plan(plan, mesh)(tables))]

for name, ref, got in pairs:
    la, ta = jax.tree.flatten(ref)
    lb, tb = jax.tree.flatten(got)
    assert str(ta) == str(tb), (name, str(ta), str(tb))
    for i, (a, b) in enumerate(zip(la, lb)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, \\
            (name, i, a.shape, b.shape, a.dtype, b.dtype)
        if not np.array_equal(a, b):
            eq = a == b
            f = a.astype(np.float64, copy=False)
            g = b.astype(np.float64, copy=False)
            eq |= np.isnan(f) & np.isnan(g)      # NaN == NaN for the diff
            bad = np.flatnonzero(~eq)
            raise AssertionError(
                name + " leaf " + str(i) + ": " + str(bad.size)
                + " of " + str(a.size) + " elements differ, max |d| = "
                + str(np.nanmax(np.abs(f - g))))
print("BITEQ OK")
'''


@pytest.fixture
def mesh_equiv():
    """Run `setup` under a multi-device CPU subprocess and assert that
    compile_plan on the 1-D data mesh is bit-equal to the single-device
    compile.  `setup` either defines `plan` and `tables`, or a `pairs`
    list of (name, ref_pytree, got_pytree) for query-level checks; the
    subprocess exposes `mesh`, `tpch`, every plan Node and `compile_plan`.
    """
    def check(setup: str, devices: int = 2) -> str:
        # setup first so its own __DEVICES__ occurrences resolve too
        script = (_MESH_EQUIV_TEMPLATE
                  .replace("__SETUP__", setup)
                  .replace("__DEVICES__", str(devices)))
        out = run_sub(script, devices=devices)
        assert "BITEQ OK" in out
        return out
    return check
