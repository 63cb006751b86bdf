"""The limit every test runs under (``tests/conftest.py``)."""
import hashlib
import os
import subprocess
import sys
import textwrap
import time

import pytest

from conftest import GRACE_S


@pytest.fixture
def dumped(limit_dump_fd, tmp_path):
    """Turns the dump of a test past its limit from the run's stderr into a
    file (the dump is armed with the descriptor's number, before this), and
    reads it."""
    stderr = os.dup(limit_dump_fd)
    with open(tmp_path / "dump", "w+") as dump:
        os.dup2(dump.fileno(), limit_dump_fd)

        def read():
            dump.seek(0)  # the dump moved the offset the descriptors share
            return dump.read()

        yield read
        os.dup2(stderr, limit_dump_fd)
        os.close(stderr)


@pytest.mark.limit(1)
def test_a_test_that_sleeps_past_its_limit_fails_by_name(request):
    """The alarm armed for THIS test by the autouse fixture, at the second
    its mark asks for, raises in its body and names it."""
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as caught:
        time.sleep(30)
    assert time.monotonic() - t0 < 5
    assert caught.value.msg == f"{request.node.nodeid} exceeded 1 s"


@pytest.mark.limit(1)
def test_a_test_stuck_in_native_code_is_named_by_the_dump(request, dumped):
    """One native call that outlasts the limit by seconds (as a compile
    does): the alarm waits for it to return, and the dump, a second after
    the limit, shows the test's frame."""
    t0 = time.monotonic()
    hashlib.pbkdf2_hmac("sha256", b"", b"", 200_000)
    rounds = int(200_000 * 5 / (time.monotonic() - t0))  # about 5 s of them
    with pytest.raises(pytest.fail.Exception, match="exceeded 1 s"):
        hashlib.pbkdf2_hmac("sha256", b"", b"", rounds)
    stacks = dumped()
    assert "Past its limit (1 s)!" in stacks and f"in {request.node.name}" in stacks


STUCK_FOR_GOOD = """
    import threading

    import pytest


    @pytest.mark.limit(2)
    def test_stuck_where_the_alarm_is_lost():
        lock = threading.Lock()
        lock.acquire()

        class Held:
            def __del__(self):
                lock.acquire()

        Held()  # the alarm's exception is raised in this __del__: discarded
        Held()  # and the alarm was armed once: nothing interrupts this one


    def test_after_it():
        pass
"""


@pytest.mark.limit(2 + GRACE_S + 90)
def test_a_test_that_loses_its_alarm_ends_its_worker_not_the_run(tmp_path):
    """PR 42's hang, as the driver runs the suite (xdist, ``loadfile``): the
    stuck test's worker is ended ``GRACE_S`` after its limit, xdist fails
    that test by name, once, and a new worker runs the rest of the file. The
    seconds over ``limit + GRACE_S`` are three workers' start-up (22 s inside
    a whole run on eight cores); a second hang would show as a second failure."""
    (tmp_path / "test_stuck.py").write_text(textwrap.dedent(STUCK_FOR_GOOD))
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [tests, os.path.dirname(tests), os.environ.get("PYTHONPATH", "")]
    ))
    t0 = time.monotonic()
    child = subprocess.run(
        [sys.executable, "-m", "pytest", "test_stuck.py", "-q", "-p", "conftest",
         "-p", "xdist", "-n", "2", "--dist", "loadfile", "-p", "no:cacheprovider",
         "-p", "no:randomly"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=2 + GRACE_S + 80,
    )
    took = time.monotonic() - t0
    said = child.stdout + child.stderr
    assert took < 2 + GRACE_S + 70, said
    assert "crashed while running 'test_stuck.py::test_stuck_where" in said, said
    assert "1 failed, 1 passed" in said and "node down" in said, said
    assert f"Timeout (0:00:{2 + GRACE_S})!" in said, said
