"""The per-test time limit (``tests/_time_limit.py``): a test that hangs
fails alone, by name and within its limit, and the tests after it run."""
import os
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_INNER = """
import signal
import time

import pytest


@pytest.mark.time_limit(1)
def test_sleeps_past_its_limit():
    time.sleep(30)


@pytest.mark.time_limit(1)
def test_hangs_where_no_signal_reaches():
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
    time.sleep(30)


def test_after_the_hangs():
    assert True
"""


def _run(pytester, monkeypatch, *args):
    pytester.makepyfile(test_inner=_INNER)
    monkeypatch.setenv(
        "PYTHONPATH", _REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    start = time.monotonic()
    result = pytester.runpytest_subprocess(
        "-p", "tests._time_limit", "-p", "no:cacheprovider", *args)
    return result, time.monotonic() - start


def test_a_test_past_its_limit_fails_by_name_and_the_next_runs(
        pytester, monkeypatch):
    result, elapsed = _run(pytester, monkeypatch, "-k", "sleeps or after")
    result.assert_outcomes(passed=1, failed=1)
    result.stdout.fnmatch_lines([
        "*TimeLimitExceeded: test_inner.py::test_sleeps_past_its_limit "
        "ran past its time limit of 1 s*"])
    # the stack where the test stood when the limit came
    result.stdout.fnmatch_lines(["*time.sleep(30)*"])
    assert elapsed < 20, elapsed


@pytest.mark.time_limit(120)
def test_a_hang_out_of_the_signals_reach_ends_its_worker_alone(
        pytester, monkeypatch):
    """With the timer's signal blocked, the backstop writes the stacks
    and ends the worker; pytest-xdist reports that test as failed and
    runs the rest on a new worker."""
    result, elapsed = _run(pytester, monkeypatch, "-p", "xdist", "-n", "1")
    result.assert_outcomes(passed=1, failed=2)
    result.stdout.fnmatch_lines([
        "*crashed while running*test_hangs_where_no_signal_reaches*"])
    # every thread's stack, the hung test's among them
    result.stderr.fnmatch_lines([
        "Timeout*", "*test_inner.py*in test_hangs_where_no_signal_reaches"])
    assert elapsed < 60, elapsed
