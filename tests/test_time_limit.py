"""The harness's per-test time limit (``conftest.time_limit``): a body
that waits where Python can see it fails by name, and the next one runs."""

import queue
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

from conftest import TEST_TIME_LIMIT_S, time_limit


def _accept():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        s.listen(1)
        s.accept()


def _join():
    t = threading.Thread(target=time.sleep, args=(5,), daemon=True)
    t.start()
    t.join()


def _child():
    child = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(5)"])
    try:
        child.wait()
    finally:
        child.kill()
        child.wait()


@pytest.mark.parametrize("wait", [
    lambda: time.sleep(5), lambda: queue.Queue().get(), _accept, _join, _child,
], ids=["sleep", "queue", "socket", "join", "child"])
def test_a_body_that_outlives_its_limit_fails_by_name(wait):
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception,
                       match=r"the sleeper outlived its time limit of 0\.2 s"):
        with time_limit(0.2, "the sleeper"):
            wait()
    assert time.monotonic() - t0 < 4
    # the limit of the test this runs in is armed again, the handler its own
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= TEST_TIME_LIMIT_S
    with pytest.raises(pytest.fail.Exception, match="test_time_limit.py::"):
        signal.raise_signal(signal.SIGALRM)
