"""A time limit for every test in this directory.

A test that hangs (a forked worker waiting on a lock, a subprocess that
never exits) would otherwise hold its xdist worker until the whole run is
cut, and no test after it reports.  Past `LIMIT_S` the test fails with
its name, the limit and every thread's stack instead.  pytest-xdist runs
tests in each worker's main thread, so SIGALRM reaches them.  The limit
cannot interrupt a hang inside a C call that never returns to Python."""
import faulthandler
import signal
import tempfile

import pytest

LIMIT_S = 300


@pytest.fixture(autouse=True)
def _time_limit(request):
    def on_alarm(signum, frame):
        with tempfile.TemporaryFile() as f:
            faulthandler.dump_traceback(f, all_threads=True)
            f.seek(0)
            stacks = f.read().decode(errors="replace")
        pytest.fail(f"{request.node.nodeid} exceeded the {LIMIT_S} s "
                    f"per-test limit; thread stacks:\n{stacks}")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
