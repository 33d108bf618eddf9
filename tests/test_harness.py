"""The test harness itself (conftest.py): batched multi-device scripts keep
their outcomes apart, a hung script fails on its batch's own limit, and a
hung test fails on its per-test limit."""
import signal
import time

import pytest

import conftest
from conftest import Batch, TimeLimitExceeded


def test_batch_reports_each_script_apart():
    """One subprocess, three scripts: the failure names its script and
    keeps its traceback, and the scripts around it pass."""
    out = Batch({"passes": "print('fine')",
                 "fails": "x = 1\nassert x == 2, 'x is not 2'",
                 "after": "print('still runs')"},
                devices=1, timeout=120).outcomes()
    assert out["passes"].error is None
    assert out["passes"].stdout == "fine\n"
    err = out["fails"].error
    assert 'File "fails", line 2' in err
    assert "assert x == 2" in err                  # the source line
    assert "AssertionError: x is not 2" in err
    assert out["after"].error is None
    assert out["after"].stdout == "still runs\n"


def test_batch_hang_fails_on_its_own_limit():
    """A script that hangs is cut by its batch's limit, well inside the
    test's: the scripts before it keep their outcomes, the ones after it
    report that they did not run."""
    t0 = time.monotonic()
    out = Batch({"before": "print('ok')",
                 "hangs": "import time\ntime.sleep(600)",
                 "after": "print('never')"},
                devices=1, timeout=30).outcomes()
    assert time.monotonic() - t0 < 60
    assert out["before"].error is None and out["before"].stdout == "ok\n"
    assert "outran its limit of 30 s" in out["hangs"].error
    assert "while hangs ran" in out["hangs"].error
    assert out["after"].error.startswith("not run")


def test_time_limit_fails_a_hung_test():
    """Every test phase runs under an alarm; when it fires, the test fails
    with TimeLimitExceeded instead of holding the worker."""
    remaining, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < remaining <= conftest.TIME_LIMIT
    conftest.extend_time_limit(5)
    extended, _ = signal.getitimer(signal.ITIMER_REAL)
    assert extended > remaining
    with pytest.raises(TimeLimitExceeded):
        conftest._arm(0.5)                         # shrink this phase's limit
        time.sleep(30)
