# NOTE: no --xla_force_host_platform_device_count here (smoke tests and
# benches must see 1 device; only launch/dryrun pins 512).  Multi-device
# checks run in subprocesses with their own XLA_FLAGS (`mesh_check`).
import dataclasses
import faulthandler
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import jax
import pytest

from mesh_runner import assert_biteq  # noqa: F401  (re-exported)
from repro.core import enable_x64

enable_x64()  # the PGF engine's exactness tests need f64 on CPU

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(ROOT, "tests")

#: Seconds each phase (setup, call, teardown) of a test may take before it
#: fails with its stack: about 5x the slowest in-process test (~37 s under
#: the suite's 6 workers).  A test that waits for a subprocess gets that
#: subprocess's own limit on top.
TIME_LIMIT = 180


class TimeLimitExceeded(Exception):
    """A test phase outran its time limit."""


def _on_alarm(signum, frame):
    raise TimeLimitExceeded("the test outran its time limit (stacks of "
                            "all threads are on stderr)")


def _arm(seconds: float) -> None:
    if seconds > 0:
        signal.setitimer(signal.ITIMER_REAL, seconds)
        faulthandler.dump_traceback_later(seconds, exit=False)
    else:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()


def extend_time_limit(seconds: float) -> None:
    """Add ``seconds`` to the running test phase's limit (for a
    subprocess that has its own, shorter limit)."""
    remaining, _ = signal.getitimer(signal.ITIMER_REAL)
    if remaining > 0:
        _arm(remaining + seconds)


def _limited_phase(item):
    previous = signal.signal(signal.SIGALRM, _on_alarm)
    _arm(TIME_LIMIT)
    try:
        return (yield)
    finally:
        _arm(0)
        signal.signal(signal.SIGALRM, previous or signal.SIG_DFL)


pytest_runtest_setup = pytest.hookimpl(wrapper=True)(_limited_phase)
pytest_runtest_call = pytest.hookimpl(wrapper=True)(_limited_phase)
pytest_runtest_teardown = pytest.hookimpl(wrapper=True)(_limited_phase)


def _subprocess_env(devices: int) -> dict:
    return dict(os.environ,
                XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
                PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), TESTS]))


# ------------------------------------------------- batched multi-device checks
@dataclasses.dataclass(frozen=True)
class MeshCheck:
    """One multi-device check script of a test module.  ``seconds`` is its
    measured cost inside its batch on the CPU; a batch may take 3x the sum
    of its checks' costs, plus START_SECONDS."""
    devices: int
    script: str
    seconds: float


#: A batch subprocess's allowance for starting Python and importing JAX.
START_SECONDS = 30


@dataclasses.dataclass(frozen=True)
class Outcome:
    stdout: str
    error: str | None       # traceback, or why the script did not finish


class Batch:
    """ONE subprocess running ``checks`` (name -> script) on ``devices``
    host devices (see ``mesh_runner.py``), started at once; ``outcomes``
    waits for it.  A batch cut by ``timeout`` (counted from its start)
    fails the script it was running and the ones after it; the outcomes
    before it stand."""

    def __init__(self, checks: dict, devices: int, timeout: float):
        self.names, self.timeout = list(checks), timeout
        self._dir = tempfile.TemporaryDirectory()
        self._results = os.path.join(self._dir.name, "results.jsonl")
        batch = os.path.join(self._dir.name, "batch.json")
        with open(batch, "w") as f:
            json.dump(dict(devices=devices, checks=list(checks.items())), f)
        self._stderr = open(os.path.join(self._dir.name, "stderr"), "w+")
        self._deadline = time.monotonic() + timeout
        self._proc = subprocess.Popen(
            [sys.executable, os.path.join(TESTS, "mesh_runner.py"), batch,
             self._results], env=_subprocess_env(devices),
            stdout=subprocess.DEVNULL, stderr=self._stderr)
        self._outcomes = None

    def outcomes(self) -> dict:
        if self._outcomes is None:
            left = max(self._deadline - time.monotonic(), 0)
            extend_time_limit(left)
            try:
                code = self._proc.wait(timeout=left)
                self._stderr.seek(0)
                cut = (f"the batch subprocess exited with code {code}:\n"
                       f"{self._stderr.read()[-3000:]}")
            except subprocess.TimeoutExpired:
                cut = (f"the batch subprocess outran its limit of "
                       f"{self.timeout} s")
            outcomes = {}
            if os.path.exists(self._results):
                with open(self._results) as f:
                    for line in f:
                        r = json.loads(line)
                        outcomes[r["name"]] = Outcome(r["stdout"], r["error"])
            pending = [n for n in self.names if n not in outcomes]
            for i, name in enumerate(pending):
                outcomes[name] = Outcome("", (
                    f"{cut}\n(while {name} ran)" if i == 0 else
                    f"not run: {cut}\n(while {pending[0]} ran)"))
            self._outcomes = outcomes
            self.close()
        return self._outcomes

    def close(self) -> None:
        if self._proc.poll() is None:
            self._proc.kill()
            self._proc.wait()
        self._stderr.close()
        self._dir.cleanup()


@pytest.fixture(autouse=True, scope="module")
def _mesh_batches(request):
    """Start the module's multi-device batches — one subprocess per device
    count, holding the module's selected ``MESH_CHECKS`` of that count —
    as the module starts, so they run beside its in-process tests."""
    table = getattr(request.module, "MESH_CHECKS", {})
    selected = {item.name for item in request.session.items
                if item.module is request.module}
    by_devices: dict = {}
    for name, c in table.items():
        if name in selected:
            by_devices.setdefault(c.devices, {})[name] = c
    batches = {d: Batch({n: c.script for n, c in checks.items()}, d,
                        timeout=3 * sum(c.seconds for c in checks.values())
                        + START_SECONDS)
               for d, checks in by_devices.items()}
    yield batches
    for b in batches.values():
        b.close()


@pytest.fixture
def mesh_check(request, _mesh_batches):
    """Wait for this test's entry of its module's ``MESH_CHECKS`` (keyed by
    the test's node name, e.g. ``test_x[3]``) and return its stdout; fail
    with its traceback."""
    def check() -> str:
        name = request.node.name
        devices = request.module.MESH_CHECKS[name].devices
        outcome = _mesh_batches[devices].outcomes()[name]
        if outcome.error is not None:
            pytest.fail(f"{name} on {devices} devices:\n{outcome.error}",
                        pytrace=False)
        return outcome.stdout
    return check


# mesh-equivalence checks: `compile_plan(root, mesh)` promises BIT-IDENTICAL
# results to the single-device compile (the canonical-chunk fold tree,
# db/plans.py), asserted exactly — shapes, dtypes and every bit of every leaf.
_EQUIV_EPILOGUE = '''
if "pairs" not in dir():
    # default shape: the setup defined `plan` and `tables`
    pairs = [("plan", compile_plan(plan, None)(tables),
              compile_plan(plan, mesh)(tables))]
for name, ref, got in pairs:
    assert_biteq(name, ref, got, nan_equal=False)
print("BITEQ OK")
'''


def equiv_check(setup: str, *, seconds: float, devices: int = 2
                ) -> MeshCheck:
    """A MeshCheck that compile_plan on the 1-D data mesh (``mesh``) is
    bit-equal to the single-device compile.  ``setup`` (x64 on) defines
    either ``plan`` and ``tables``, or a ``pairs`` list of (name,
    ref_pytree, got_pytree); it sees ``mesh``, ``tpch``, every plan Node,
    ``compile_plan``, ``Table`` and ``memo``.  ``__DEVICES__`` in it is
    replaced by the device count."""
    script = "enable_x64()\n" + setup + _EQUIV_EPILOGUE
    return MeshCheck(devices, script.replace("__DEVICES__", str(devices)),
                     seconds)


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
