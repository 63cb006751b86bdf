"""The finalizer rule (``cylon_tpu/obs/__init__.py``): what the collector
runs appends to a deque and frees what it alone owns; it takes no lock,
emits no metric, opens no span.

The collector runs a finalizer wherever an allocation lands, so also on a
thread inside ``obs/metrics.py``'s ``with _lock:``. Every case here holds
one of the package's locks on the calling thread and drops the object: a
finalizer that reaches for that lock never returns (PR 42's tier-1 runs
were cut by ``HostArena.__del__`` doing so), and the case is failed by its
limit or by the seconds it counts.
"""
import gc
import os
import time

import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import col, native
from cylon_tpu.obs import metrics, resource, trace
from cylon_tpu.parallel import spill
from cylon_tpu.serve.scheduler import ServeScheduler

#: the module locks a finalizer may find held by its own thread; "own" is
#: the lock of the accounts that the finalizer's object gives back to
MODULE_LOCKS = {
    "metrics": metrics._lock,
    "resource": resource._lock,
    "spill": spill._arena_lock,
    "trace": trace._finish_lock,
}


def _drop(holder, lock, how):
    """Drops ``holder[0]`` while this thread holds ``lock``: by the last
    reference going (``del``), or as part of a reference cycle that only
    a collection finds. An alarm raised inside a ``__del__`` is discarded
    there, so the seconds are counted too."""
    if how == "cycle":
        ring = [holder[0]]
        ring.append(ring)
        del ring
    t0 = time.monotonic()
    with lock:
        del holder[0]
        gc.collect()
    assert time.monotonic() - t0 < 5, "the finalizer waited for a held lock"


def _staged_arena(backing=spill.TIER_HOST):
    arena = spill.HostArena([("k", np.int64, False)], backing)
    arena.append_batch([(np.arange(100, dtype=np.int64), None)])
    return arena


def _settled():
    """Live and disk bytes of the arenas once nothing waits to be
    collected, with both gauges made to say them."""
    gc.collect()
    spill._arena_adjust(0, 0)
    return _accounts()


def _accounts():
    """Live and disk bytes of the arenas, and what the two gauges say."""
    live, _peak, disk, _disk_peak = spill.arena_bytes()
    said = metrics.snapshot()
    return (
        live, disk,
        said["shuffle.spill.host_bytes"]["last"],
        said["shuffle.spill.disk_bytes"]["last"],
    )


@pytest.mark.limit(10)
@pytest.mark.parametrize("how", ["del", "cycle"])
@pytest.mark.parametrize("held", ["metrics", "spill"])
def test_an_unclosed_arena_dropped_under_a_lock_returns(held, how):
    """PR 42's hang in 12 lines: ``rollup_span`` holds ``metrics._lock``,
    its allocation starts a collection, and the collection finalises an
    arena that still counts bytes."""
    before = _settled()
    holder = [_staged_arena()]
    assert spill.arena_bytes()[0] == before[0] + 800
    _drop(holder, MODULE_LOCKS[held], how)
    assert _accounts() == before


@pytest.mark.limit(10)
def test_a_dropped_arena_gives_its_bytes_and_files_back_once(
    tmp_path, monkeypatch
):
    """The next touch of the accounts after a drop reads the bytes of
    before, in ``arena_bytes()`` and in both gauges; the arena's files and
    directory are gone; and nothing is given back a second time."""
    monkeypatch.setenv("CYLON_TPU_SPILL_DIR", str(tmp_path))
    before = _settled()
    arena = _staged_arena(spill.TIER_DISK)
    made = arena._dir
    assert os.listdir(made) and os.path.dirname(made) == str(tmp_path)
    assert _accounts() == (before[0] + 800, before[1] + 800) * 2
    stale = metrics.snapshot()["shuffle.spill.host_bytes"]["count"]
    del arena
    # the finalizer reported nothing; the files went with it
    assert metrics.snapshot()["shuffle.spill.host_bytes"]["count"] == stale
    assert not os.path.exists(made)
    assert _accounts() == before

    # the collector's call made by hand, to have the arena afterwards
    arena = _staged_arena(spill.TIER_DISK)
    made = arena._dir
    arena.__del__()
    assert _accounts() == before and not os.path.exists(made)
    arena.close()
    arena.close()
    assert _accounts() == before
    del arena
    assert not spill._ARENA_DEAD and _accounts() == before


def _dropped_arena(ctx, monkeypatch):
    read = lambda: spill.arena_bytes()[0]  # noqa: E731
    before = read()
    return before, [_staged_arena()], spill._arena_lock, read


def _dropped_table(ctx, monkeypatch):
    """``ResourceLedger._unregister``, the table's ``weakref.finalize``."""
    monkeypatch.setenv("CYLON_TPU_TRACE", "tree")  # the ledger is on
    ledger = resource.ledger(ctx)
    read = lambda: ledger.snapshot()["device_bytes"]  # noqa: E731
    before = read()
    table = ct.Table.from_pydict(ctx, {"k": np.arange(64, dtype=np.int32)})
    assert read() > before
    return before, [table], ledger._lock, read


def _dropped_future(ctx, monkeypatch):
    """The lease of a ``QueryFuture`` dropped unconsumed
    (``ServeScheduler._dropped``)."""
    sched = ServeScheduler(ctx, auto_start=False)
    table = ct.Table.from_pydict(ctx, {"k": np.arange(64, dtype=np.int32)})
    fut = sched.submit(table.lazy().filter(col("k") > 3))
    sched.run_pending()
    assert sched.stats()["leases"] == 1
    return 0, [fut], sched._lock, lambda: sched.stats()["inflight_bytes"]


def _dropped_pool(ctx, monkeypatch):
    """``native.MemoryPool.__del__``: one native call, no accounts."""
    if not native.available():
        pytest.skip("native runtime unavailable")
    pool = native.MemoryPool(1 << 12)
    pool.alloc_array((16,), np.int64)[:] = 1
    return 0, [pool], None, lambda: 0


@pytest.mark.limit(10)
@pytest.mark.parametrize(
    "dropped,held",
    [
        (dropped, held)
        for dropped in (
            _dropped_arena, _dropped_table, _dropped_future, _dropped_pool
        )
        for held in (*MODULE_LOCKS, "own")
        if (dropped, held) != (_dropped_pool, "own")
    ],
)
def test_every_finalizer_returns_under_every_lock(
    local_ctx, monkeypatch, dropped, held
):
    """One case for each thing the collector can run in ``cylon_tpu/``,
    under each lock: the drop returns, and the accounts it gives back to
    read, at their next touch, what they read before the object was."""
    gc.collect()
    before, holder, own, read = dropped(local_ctx, monkeypatch)
    _drop(holder, own if held == "own" else MODULE_LOCKS[held], "cycle")
    assert read() == before
