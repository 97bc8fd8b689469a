"""A time limit for every test, so that a hang fails the test that hangs
and the run goes on.

Each test -- setup, call and teardown -- runs under ``signal.setitimer``
on the main thread, where pytest (and each pytest-xdist 3.x worker) runs
its tests: at the limit the test fails with its name and the stack it was
standing in. A hang inside C code never gives the signal handler control
back, so ``faulthandler`` backs the timer up: a while after the limit it
writes every thread's stack to stderr and ends the process; pytest-xdist
then reports the test as failed with its worker and starts a new worker
for the rest of the run.

The limit is ``DEFAULT_SECONDS``; ``@pytest.mark.time_limit(seconds)``
sets another for one test.
"""
import faulthandler
import os
import signal
import sys
import threading

import pytest

#: more than twice the slowest test of the tier-1 run
DEFAULT_SECONDS = 300.0


#: a copy of the process's stderr, taken before any test's capture: the
#: backstop's stacks go there (under pytest-xdist, the terminal's)
_STDERR = pytest.StashKey[int]()


class TimeLimitExceeded(Exception):
    """A test ran past its time limit."""


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "time_limit(seconds): fail the test if its setup, call "
        f"and teardown take longer than this (default {DEFAULT_SECONDS:g})")
    config.stash[_STDERR] = os.dup(sys.__stderr__.fileno())


def pytest_unconfigure(config):
    os.close(config.stash[_STDERR])


def _seconds(item):
    marker = item.get_closest_marker("time_limit")
    return float(marker.args[0]) if marker else DEFAULT_SECONDS


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    seconds = _seconds(item)

    def expire(signum, frame):
        raise TimeLimitExceeded(
            f"{item.nodeid} ran past its time limit of {seconds:g} s")

    previous = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    # the backstop waits as long again (at most 30 s more) for the
    # timer's exception to unwind the test
    faulthandler.dump_traceback_later(seconds + min(seconds, 30.0),
                                      exit=True,
                                      file=item.config.stash[_STDERR])
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)
