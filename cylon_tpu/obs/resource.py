"""The resource ledger: live memory accounting on every tier the engine
touches — device HBM, host arenas, spill disk, serving leases.

PR 8 gave the engine a latency axis (per-fingerprint histograms) and
PR 11 fed decisions from it; nothing watched the RESOURCE axis: admission
control leases a static input-bytes estimate, the spill gauges are
process-global peaks nobody attributes, and a table leaked by a caller
is invisible until the OOM. This module is the memory half of the
observability stack (ROADMAP item 4's "feed admission from observed
per-query footprints"; Exoshuffle's application-level memory-accounting
thesis):

DEVICE HBM
    Every :class:`~cylon_tpu.table.Table` registers its device buffers
    here at construction (``table.py`` calls :func:`note_table`), and a
    ``weakref.finalize`` on the table unregisters them — frees are
    observed when the GC drops the table, with NO sync anywhere (byte
    counts come from ``jax.Array.nbytes``, a shape property). Buffers
    shared between tables (project/rename reuse Column objects) are
    refcounted by buffer identity, so a projection costs zero ledger
    bytes and nothing double-counts.

HOST + DISK
    Wrapped from the spill engine's own accounting
    (``parallel/spill.arena_bytes`` — the numbers behind the
    ``shuffle.spill.host_bytes`` / ``disk_bytes`` gauges).

SERVING LEASES
    Read from the context's serving scheduler (admitted-but-unconsumed
    bytes — the admission-control axis).

ATTRIBUTION
    A table created while a query's exec-observation record is open
    (``obs/store.exec_obs`` — the same chain PR 8/11 attribute gate
    observations through) adds its bytes to that record's ``dev`` field,
    so the observation store journals a per-fingerprint FOOTPRINT
    distribution and ``plan/feedback.py`` can replace the static
    admission estimate with the observed p95. A table created while a
    query TRACE is active additionally remembers the trace's qid, which
    powers the leak detector: :meth:`ResourceLedger.leaks` flags tables
    still live ``CYLON_TPU_LEAK_GRACE_S`` seconds after their owning
    query finished, each with the creation site (first stack frame
    outside ``cylon_tpu/``) that allocated it.

COST DISCIPLINE: the ledger is DISABLED unless an ops surface is on
(``CYLON_TPU_METRICS_PORT`` / ``CYLON_TPU_OBS_DIR`` set, or tracing
active) — the disabled path is one :func:`enabled` check per table
construction, covered by the <2% trace-smoke overhead pin. Enabled or
not, nothing here ever touches the device or fetches: graft-lint pins
:func:`note_table` / :func:`query_finished` at 0 sync sites and every
public :class:`ResourceLedger` method DISPATCH_SAFE.
"""
from __future__ import annotations

import os
import sys
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional

from ..utils import envgate as _eg

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_lock = threading.Lock()
#: every live ledger, for the /metrics exporter (per-context accounting,
#: process-wide exposition)
_LEDGERS: "weakref.WeakSet" = weakref.WeakSet()
#: qid -> finish time of recently finished query traces (the leak
#: detector's "query closed" clock); FIFO-bounded
_FINISHED: Dict[int, float] = {}
_FINISHED_CAP = 4096


def enabled() -> bool:
    """Is the ledger on? True when any ops surface wants it: the metrics
    endpoint, the observation store, or active tracing. Read per call —
    this is the ONE check the disabled path pays per Table construction."""
    if _eg.METRICS_PORT.get():
        return True
    if _eg.OBS_DIR.get():
        return True
    return _eg.TRACE.truthy()


def ledger(ctx) -> "ResourceLedger":
    """The context's ledger, created on first use (per-context accounting:
    tables register with their own context's ledger)."""
    led = ctx.__dict__.get("_res_ledger")
    if led is None:
        with ctx._cache_lock:
            led = ctx.__dict__.get("_res_ledger")
            if led is None:
                led = ResourceLedger(ctx)
                ctx.__dict__["_res_ledger"] = led
                with _lock:
                    _LEDGERS.add(led)
    return led


def ledgers() -> List["ResourceLedger"]:
    """Every live context's ledger (the exporter's enumeration)."""
    with _lock:
        return list(_LEDGERS)


def _creation_site() -> str:
    """First stack frame OUTSIDE cylon_tpu/: the user call that caused
    this allocation — what a leak report must point at."""
    f = sys._getframe(2)
    while f is not None:
        fn = f.f_code.co_filename
        if not fn.startswith(_PKG_DIR):
            return f"{fn}:{f.f_lineno}"
        f = f.f_back
    return "<internal>"


def note_table(table) -> None:
    """Register one freshly constructed Table's device buffers with its
    context's ledger (called from ``Table.__init__``). No-op — and the
    only cost — when the ledger is disabled. Never syncs: byte counts
    are ``nbytes`` shape properties of buffers already referenced."""
    if not enabled():
        return
    ledger(table.ctx)._register(table)


def note_rebuffer(table) -> None:
    """Re-register a table whose column buffers were swapped in place
    (``Table._materialize_counts``' overshoot compaction): without this
    the ledger would keep counting the freed pre-compaction buffers for
    the table's whole lifetime while the compaction wrapper's finalizer
    stole the live ones. No-op when disabled or never registered."""
    if not enabled():
        return
    led = table.ctx.__dict__.get("_res_ledger")
    if led is not None:
        led._rebuffer(table)


def query_finished(q) -> None:
    """Stamp a query trace's finish time (called from
    ``obs.trace._maybe_finish``) so the leak detector can age tables
    against their owning query's close."""
    with _lock:
        _FINISHED[q.qid] = time.monotonic()
        while len(_FINISHED) > _FINISHED_CAP:
            _FINISHED.pop(next(iter(_FINISHED)))


def leak_grace_s() -> float:
    try:
        return max(float(_eg.LEAK_GRACE_S.get()), 0.0)
    except ValueError:
        return 30.0


class ResourceLedger:
    """One context's live resource accounting. All state is host dicts
    under one lock; reads (:meth:`snapshot`, :meth:`leaks`) are
    DISPATCH_SAFE — they can run from a metrics scrape thread while the
    engine dispatches."""

    def __init__(self, ctx):
        self._ctx_ref = weakref.ref(ctx)
        self._lock = threading.Lock()
        # buffer identity -> [nbytes, refcount] (id() keys are safe:
        # entries are removed when the refcount hits 0, before the id
        # can be reused)
        self._bufs: Dict[int, List[int]] = {}
        # table identity -> {bytes, site, t, qid, obs_key, ref}
        self._tables: Dict[int, Dict[str, Any]] = {}
        # finalizer hand-off (the finalizer rule, obs/__init__.py): the
        # table finalizer appends here and the next ledger operation
        # drains it under the lock
        self._dead: "deque" = deque()
        self.device_bytes = 0
        self.device_peak = 0

    # -- registration (engine side) ------------------------------------
    def _register(self, table, attrib: Optional[Dict[str, Any]] = None) -> None:
        from . import store as _store
        from . import trace as _trace

        keys: List[int] = []
        tbytes = 0
        new_bytes = 0
        with self._lock:
            self._drain_dead_locked()
            for col in table._columns.values():
                for arr in (col.data, col.valid):
                    if arr is None:
                        continue
                    k = id(arr)
                    keys.append(k)
                    nb = int(arr.nbytes)
                    tbytes += nb
                    b = self._bufs.get(k)
                    if b is None:
                        self._bufs[k] = [nb, 1]
                        new_bytes += nb
                    else:
                        b[1] += 1
            self.device_bytes += new_bytes
            self.device_peak = max(self.device_peak, self.device_bytes)
            live = self.device_bytes
            ntab = len(self._tables) + 1
            q = _trace.current()
            ent: Dict[str, Any] = {
                "bytes": tbytes,
                "site": (
                    attrib["site"] if attrib else _creation_site()
                ),
                "t": attrib["t"] if attrib else time.monotonic(),
                "qid": (
                    attrib["qid"] if attrib
                    else (q.qid if q is not None else None)
                ),
                "label": (
                    attrib["label"] if attrib
                    else (q.label if q is not None else "")
                ),
                "ref": weakref.ref(table),
                "keys": tuple(keys),
            }
            # finalize() never holds the table; its handle lives on the
            # entry so a buffer swap (_rebuffer) can detach the stale one
            ent["fin"] = weakref.finalize(
                table, self._unregister, id(table), tuple(keys)
            )
            self._tables[id(table)] = ent
        # gauges refresh on every registration (a projection changes
        # live_tables with zero new bytes) and on snapshot() — so frees,
        # observed at the deferred drain, reach the rollup at the next
        # ledger touch instead of leaving a stale-high current value
        from ..utils.tracing import gauge

        gauge("ledger.device_bytes", live)
        gauge("ledger.live_tables", ntab)
        # footprint attribution: bytes allocated under an open
        # exec-observation record feed the per-fingerprint footprint
        # distribution the admission re-coster reads (plan/feedback.py)
        _store.note_dev_bytes(new_bytes)

    def _rebuffer(self, table) -> None:
        """Re-register a table whose column buffers were swapped in
        place (the materialize-time overshoot compaction): release the
        stale buffers NOW, detach the stale finalizer (its keys would
        otherwise double-release when the table dies), and register the
        new buffers under the original creation attribution."""
        attrib = None
        with self._lock:
            self._drain_dead_locked()
            ent = self._tables.pop(id(table), None)
            if ent is not None:
                fin = ent.get("fin")
                if fin is not None:
                    fin.detach()
                self._release_keys_locked(ent["keys"])
                attrib = ent
        self._register(table, attrib=attrib)

    def _unregister(self, tid: int, keys) -> None:
        """The table finalizer, under the finalizer rule
        (``obs/__init__.py``): one append, no lock, no metric."""
        self._dead.append((tid, keys))

    def _release_keys_locked(self, keys) -> None:
        freed = 0
        for k in keys:
            b = self._bufs.get(k)
            if b is None:
                continue
            b[1] -= 1
            if b[1] <= 0:
                del self._bufs[k]
                freed += b[0]
        self.device_bytes -= freed

    def _drain_dead_locked(self) -> None:
        """Apply deferred finalizer frees (caller holds ``self._lock``)."""
        while True:
            try:
                tid, keys = self._dead.popleft()
            except IndexError:
                break
            self._tables.pop(tid, None)
            self._release_keys_locked(keys)

    # -- read side (ops surface) ---------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Point-in-time ledger state: per-context device bytes + peak
        and live-table count, the process-wide host/disk arena bytes
        (wrapping the ``shuffle.spill.*`` accounting), and the context
        scheduler's admitted-lease bytes. Host dict reads only."""
        from ..parallel import spill as _spill
        from ..utils.tracing import gauge

        with self._lock:
            self._drain_dead_locked()
            dev = self.device_bytes
            peak = self.device_peak
            ntab = len(self._tables)
        # scrape-driven gauge refresh: frees applied by the drain above
        # reach the rollup's current value here
        gauge("ledger.device_bytes", dev)
        gauge("ledger.live_tables", ntab)
        host, host_peak, disk, disk_peak = _spill.arena_bytes()
        lease = 0
        lease_count = 0
        ctx = self._ctx_ref()
        if ctx is not None:
            sched = ctx.__dict__.get("_serve_sched")
            if sched is not None:
                st = sched.stats()
                lease = st["inflight_bytes"]
                lease_count = st.get("leases", 0)
        return {
            "device_bytes": dev,
            "device_peak": peak,
            "live_tables": ntab,
            "host_bytes": host,
            "host_peak": host_peak,
            "disk_bytes": disk,
            "disk_peak": disk_peak,
            "serve_lease_bytes": lease,
            # lease-LEAK accounting (ISSUE 14): the number of admitted-
            # but-unreleased leases. The chaos harness asserts this
            # returns to 0 after every fault campaign — a failure path
            # that forgets to release shows up here, not as a slow
            # admission-budget starvation in production
            "serve_lease_count": lease_count,
        }

    def leaks(self, grace_s: Optional[float] = None) -> List[Dict[str, Any]]:
        """Tables still device-resident ``grace_s`` (default
        ``CYLON_TPU_LEAK_GRACE_S``) seconds after their owning query
        trace finished, each with creation-site attribution. A table
        with no owning trace (created outside any query) is never
        flagged — the detector ages tables against query lifecycle, not
        wall clock."""
        if grace_s is None:
            grace_s = leak_grace_s()
        now = time.monotonic()
        out: List[Dict[str, Any]] = []
        with self._lock:
            self._drain_dead_locked()
            entries = list(self._tables.values())
        with _lock:
            finished = dict(_FINISHED)
        for ent in entries:
            qid = ent.get("qid")
            if qid is None:
                continue
            done = finished.get(qid)
            if done is None or now - done < grace_s:
                continue
            if ent["ref"]() is None:
                continue  # raced the GC: not a leak
            out.append({
                "bytes": ent["bytes"],
                "site": ent["site"],
                "age_s": round(now - done, 3),
                "qid": qid,
                "label": ent["label"],
            })
        return out
