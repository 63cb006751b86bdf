"""The limit every test runs under (``tests/conftest.py``)."""
import hashlib
import os
import time

import pytest


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
    assert "Timeout (0:00:02)!" in stacks and f"in {request.node.name}" in stacks
