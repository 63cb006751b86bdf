"""Test harness: virtual 8-device CPU mesh.

Reference analog: CTest runs every suite under ``mpirun -np {1,2,4}``
(cpp/test/CMakeLists.txt:44-117). Here a single process gets 8 virtual XLA CPU
devices (SURVEY.md §4.3) and the same tests run on 1-, 2-, 4- and 8-device
meshes via the ``ctx`` fixtures.

Every test runs under a limit of its own (``LIMIT_S``, or what its
``@pytest.mark.limit(seconds)`` says): past it an alarm fails the test by
name. The alarm's exception waits while the main thread is inside native
code (an XLA compile is not interrupted), so a second later every thread's
stack is dumped to the run's own stderr, which names the test that is stuck.
The exception can also be lost: raised inside a ``__del__`` it is discarded
there, and the alarm is armed once. So where the test still runs ``GRACE_S``
after its limit, the stacks are dumped again and the process is ended. Under
xdist that fails the test by name ("worker ... crashed while running ..."),
and a new worker takes the rest of the file: a hang costs one test and
``limit + GRACE_S`` seconds, never the run.
"""
import faulthandler
import os
import signal
import sys
import threading

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

import cylon_tpu as ct

#: seconds a test may take: three times the longest tier-1 test (ROADMAP D7)
LIMIT_S = 180
#: seconds past its limit after which a test that still runs ends its process
GRACE_S = 30

_DUMP_FD = pytest.StashKey[int]()


def pytest_configure(config):
    # here the capture is suspended, so fd 2 is the run's own stderr: a dump
    # written there is read while the stuck test is still running
    config.stash[_DUMP_FD] = os.dup(sys.__stderr__.fileno())


def pytest_unconfigure(config):
    os.close(config.stash[_DUMP_FD])


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """``--dist loadfile`` with its crash handling repaired. xdist 3.8 puts
    everything a crashed worker was ever given back on the queue, the test
    that crashed it still pending and the finished files as empty units: the
    next worker runs that test again (one that ends its worker would end its
    replacements too, 24 of them under ``-n 6``), and a worker handed an
    empty unit is never given another."""
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class CrashedOnce(LoadFileScheduling):
        def remove_node(self, node):
            crashed = None
            for scope, unit in self.assigned_work.pop(node).items():
                left = {nodeid: False for nodeid, done in unit.items() if not done}
                if left and crashed is None:
                    crashed = next(iter(left))
                    del left[crashed]
                if left:
                    self.workqueue[scope] = left
            for other in self.assigned_work:
                self._reschedule(other)
            return crashed

    return CrashedOnce(config, log)


@pytest.fixture(scope="session")
def limit_dump_fd(request):
    """The descriptor a test past its limit dumps the stacks to."""
    return request.config.stash[_DUMP_FD]


@pytest.fixture(autouse=True)
def _limit(request, limit_dump_fd):
    """Fails the test once its seconds have passed and the main thread is in
    Python (``pytest.fail``'s exception is no ``Exception``, so the code
    under test does not swallow it); where the test still runs a second
    later, dumps every thread's stack; where it still runs ``GRACE_S``
    later, dumps them again and ends the process. The end is the watchdog's
    (a C thread that needs no interpreter lock, and there is one of it); the
    first dump only has to find the lock free, as it is during a compile."""
    marker = request.node.get_closest_marker("limit")
    seconds = marker.args[0] if marker else LIMIT_S

    def on_alarm(signum, frame):
        pytest.fail(f"{request.node.nodeid} exceeded {seconds} s", pytrace=False)

    def name_it():
        os.write(limit_dump_fd, f"Past its limit ({seconds} s)!\n".encode())
        faulthandler.dump_traceback(file=limit_dump_fd)

    was = signal.signal(signal.SIGALRM, on_alarm)
    namer = threading.Timer(seconds + 1, name_it)
    namer.daemon = True
    namer.start()
    faulthandler.dump_traceback_later(
        seconds + GRACE_S, exit=True, file=limit_dump_fd
    )
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        namer.cancel()
        signal.signal(signal.SIGALRM, was)


@pytest.fixture(scope="session")
def devices():
    d = jax.devices()
    assert len(d) >= 8, f"need 8 virtual CPU devices, got {len(d)}"
    return d


@pytest.fixture(scope="session")
def local_ctx(devices):
    return ct.CylonContext.init()


@pytest.fixture(scope="session", params=[1, 2, 4, 8])
def world_ctx(request, devices):
    """Mesh sizes mirroring the reference's mpirun -np sweep (+8)."""
    n = request.param
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:n]))


@pytest.fixture(scope="session")
def ctx8(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:8]))


@pytest.fixture
def rng():
    return np.random.default_rng(42)
