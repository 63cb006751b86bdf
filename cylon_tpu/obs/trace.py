"""Query-scoped trace contexts: structured span trees per query.

The flat tracer interleaved every concurrent query's spans into one
module-global dict — under the 8-thread dispatch hammer
(tests/test_concurrent_dispatch.py) nothing was attributable to the
query that produced it. Here a ``contextvars.ContextVar`` carries the
ACTIVE :class:`QueryTrace`: every ``span``/``bump``/``gauge`` lands in
(a) the process-global rollup (:mod:`.metrics` — the compat surface the
graft-lint plan registry asserts on) and (b) the active query's own span
tree and counters. Contextvars are per-thread by construction, so two
threads dispatching concurrently build two disjoint trees with zero
coordination — the rollup stays the cross-query sum.

Trace contexts open at:

- ``LazyFrame.dispatch()`` / ``collect()`` — one trace per plan
  execution, labeled with the plan-fingerprint key;
- any OUTERMOST eager-op span when tracing is enabled — one trace per
  eager op chain's top-level op;
- explicitly, via :func:`query_trace` (``force=True`` ignores the env
  gate — ``explain(analyze=True)`` uses it).

Sync-free device timing: a dispatched query's buffers are still in
flight when ``dispatch()`` returns, so its real end time is unknowable
without a host sync — which the dispatch-async engine forbids
(graft-lint L3 pins ``q3_dispatch`` at EXACTLY one sync). Instead the
result Table carries a pending record; ``Table._materialize_counts`` —
the ONE existing deferred count fetch — calls :func:`resolve_table`
AFTER its fetch returns, which stamps the device-resolved end time and
feeds the plan-fingerprint latency histogram. The trace layer therefore
never fetches: ``analysis/contracts.py`` pins 0 sync sites on this
module's hot entry points, and the runtime census under an enabled
tracer is asserted by ``tools/trace_smoke.py`` in CI.

Always on, beside all of that: every public call leaves one
:class:`OpRecord` of where the HOST's time went (every program call and
every fetch timed, the fetches named by site), kept in the flight ring
whether tracing is enabled or not — :func:`op`, :func:`note_dispatch`,
:class:`fetch_wait`; ``obs.last_ops`` / ``obs.slowest_ops`` read them.

Disabled cost: with tracing off and no active trace, ``span()`` takes
the legacy fast path — one contextvar read, one perf_counter pair, one
locked rollup update; NO Span/QueryTrace allocation
(tests/test_obs.py pins zero allocation; tools/trace_smoke.py gates the
per-query overhead under 2%).
"""
from __future__ import annotations

import contextlib
import functools
import itertools
import sys
import threading
import time
from contextvars import ContextVar
from typing import Any, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

from ..utils import envgate as _eg
from . import export as _export
from . import metrics as _metrics
from . import store as _obsstore
from .stages import FETCH_SITES

_ACTIVE: "ContextVar[Optional[QueryTrace]]" = ContextVar(
    "cylon_tpu_query_trace", default=None
)
_ANALYZE: "ContextVar[bool]" = ContextVar("cylon_tpu_analyze", default=False)
_QIDS = itertools.count(1)


def trace_enabled() -> bool:
    """Per-span stderr LOGGING gate (the original CYLON_TPU_TRACE=1
    contract — unchanged)."""
    return _eg.TRACE.get() == "1"


def tracing_active() -> bool:
    """Structured query-trace gate: any truthy CYLON_TPU_TRACE value.
    ``=1`` traces AND logs each span; ``=tree`` (or any other truthy
    value) builds span trees + the flight ring without the stderr
    firehose."""
    return _eg.TRACE.truthy()


# ----------------------------------------------------------------------
# the host's part of a public call: one always-on record
# ----------------------------------------------------------------------
_ns = time.perf_counter_ns
#: the exposed time before a public call's first dispatch has no fetch in
#: front of it; a record files it under this name, beside the fetch sites
OP_START = "op.start"
_FETCH_EVENT = {site: "host_sync." + site for site in FETCH_SITES}
_GAP_EVENT = {
    site: "host.gap." + site for site in (*FETCH_SITES, OP_START)
}


class OpRecord:
    """Where the host's time went in one public call (``Table.join``,
    ``distributed_sort``, ``LazyFrame.collect``, ...: :func:`op`), built
    from the two places where the host meets the device: every program call
    (``engine.get_kernel``'s timed callable, :func:`note_dispatch`) and
    every fetch (``table._fetch``, :class:`fetch_wait`). Times are
    ``perf_counter_ns``. Always on; written by the thread that opened it
    alone, read by anyone through :meth:`as_dict`.

    ``exposed_ns`` estimates the time the call left the device with nothing
    to do: from the record's start to its first dispatch's return, and from
    every fetch's return (the device has drained, or is about to) to the
    next dispatch's return, the host's wait in a further fetch left out
    (the device is busy with what that fetch waits for). It is an upper
    estimate: after the first of two fetches in a row the device may still
    hold the second's program. A dispatch or fetch between this call's
    return and the thread's next public call (the deferred count fetch of
    ``Table.row_count``) is this record's TAIL: it is counted here, its
    duration added to ``tail_ns``, and an interval still open is cut where
    the next record opens, so no nanosecond is counted twice. What is left
    of the call is plain host time: :meth:`plain_ns`."""

    __slots__ = (
        "rid", "name", "thread", "t0", "t1", "tail_ns",
        "n_dispatch", "dispatch_ns", "n_fetch", "wait_ns", "exposed_ns",
        "sites", "max_wait_ns", "max_wait_site", "max_wait_after",
        "_gap_t0", "_gap_site", "_gap_ann",
    )

    def __init__(self, name: str, t0: int):
        self.rid = next(_QIDS)
        self.name = name
        self.thread = threading.get_ident()
        self.t0 = t0
        self.t1 = 0
        self.tail_ns = 0
        self.n_dispatch = self.dispatch_ns = 0
        self.n_fetch = self.wait_ns = self.exposed_ns = 0
        #: site -> [fetches, wait_ns, exposed_ns]
        self.sites: Dict[str, List[int]] = {OP_START: [0, 0, 0]}
        self.max_wait_ns = 0
        self.max_wait_site = self.max_wait_after = ""
        self._gap_ann = None
        self._open_gap(OP_START, t0)

    # -- the exposed interval: open at a fetch's return, closed at the
    # next dispatch's return; also a host event on the profiler's clock
    def _open_gap(self, site: str, now: int) -> None:
        self._gap_t0, self._gap_site = now, site
        self._gap_ann = TraceAnnotation(_GAP_EVENT[site])
        self._gap_ann.__enter__()

    def _mark_gap(self, now: int) -> None:
        """Count the open interval up to ``now``; it stays open."""
        d = now - self._gap_t0
        self.exposed_ns += d
        self.sites[self._gap_site][2] += d
        self._gap_t0 = now

    def _close_gap(self, now: int) -> None:
        self._mark_gap(now)
        self._gap_t0 = 0
        self._gap_ann.__exit__(None, None, None)
        self._gap_ann = None

    def wall_ns(self) -> int:
        """The call and its tail."""
        return max(self.t1 - self.t0, 0) + self.tail_ns

    def plain_ns(self) -> int:
        """The Python between the programs: the call and its tail less
        the waits and the program calls."""
        return self.wall_ns() - self.wait_ns - self.dispatch_ns

    def as_dict(self) -> Dict[str, Any]:
        return {
            "rid": self.rid, "name": self.name, "thread": self.thread,
            "start_ns": self.t0, "end_ns": self.t1, "tail_ns": self.tail_ns,
            "n_dispatch": self.n_dispatch, "dispatch_ns": self.dispatch_ns,
            "n_fetch": self.n_fetch, "wait_ns": self.wait_ns,
            "exposed_ns": self.exposed_ns, "plain_ns": self.plain_ns(),
            "sites": {
                site: {"n_fetch": n, "wait_ns": w, "exposed_ns": e}
                for site, (n, w, e) in self.sites.items()
            },
            "max_wait": {
                "ns": self.max_wait_ns, "site": self.max_wait_site,
                "after": self.max_wait_after,
            },
        }


class _Host(threading.local):
    """A thread's clock readings at the device's edge."""

    rec: Optional[OpRecord] = None  # the open record, else the last one
    open = False                    # a public call is running
    program = ""                    # the program called last
    dispatch_ns = 0                 # how long that call took
    t_dispatch = 0                  # when it returned
    t_fetch = 0                     # when the last fetch returned


_HOST = _Host()


def op(name: str):
    """Decorator of a public call: the outermost one on a thread opens an
    :class:`OpRecord` (and, inside a profiler session, the host event
    ``op.<name>``); inner ones fold into it."""
    event = "op." + name

    def deco(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            h = _HOST
            if h.open:
                return fn(*args, **kwargs)
            now = _ns()
            prev = h.rec
            if prev is not None and prev._gap_t0:
                prev._close_gap(now)  # the tail ends where the next opens
            rec = h.rec = OpRecord(name, now)
            h.open = True
            try:
                with TraceAnnotation(event):
                    return fn(*args, **kwargs)
            finally:
                now = rec.t1 = _ns()
                if rec._gap_t0:
                    rec._mark_gap(now)
                h.open = False
                _export.record_op(rec)

        return call

    return deco


def note_dispatch(program, t0: int, t1: int) -> None:
    """One call of ``program`` (an ``engine.TimedProgram``: its ``name``,
    and ``span`` = ``dispatch.<name>``) took the host from ``t0`` to
    ``t1``: the rollup span and the thread's record."""
    dt = t1 - t0
    _metrics.rollup_span(program.span, dt * 1e-9)
    h = _HOST
    h.program, h.dispatch_ns, h.t_dispatch = program.name, dt, t1
    rec = h.rec
    if rec is not None:
        rec.n_dispatch += 1
        rec.dispatch_ns += dt
        if rec._gap_t0:
            rec._close_gap(t1)  # the device has work again
        if not h.open:
            rec.tail_ns += dt
            _export.consider_slow(rec)


class fetch_wait:
    """The one place a device-to-host fetch is timed and named (entered by
    ``table._fetch`` alone): the rollup span ``host_sync.<site>`` and the
    site's wait histogram, the thread's record, and inside a profiler
    session the host event ``host_sync.<site>``."""

    __slots__ = ("site", "t0", "ann")

    def __init__(self, site: str):
        self.site = site

    def __enter__(self):
        self.ann = TraceAnnotation(_FETCH_EVENT[self.site])
        rec = _HOST.rec
        now = self.t0 = _ns()
        if rec is not None and rec._gap_t0:
            rec._close_gap(now)  # a wait is no exposed time
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        site = self.site
        h = _HOST
        t1 = h.t_fetch = _ns()
        dt = t1 - self.t0
        _metrics.rollup_wait(site, dt * 1e-9)
        rec = h.rec
        if rec is None:
            return
        rec.n_fetch += 1
        rec.wait_ns += dt
        at = rec.sites.get(site)
        if at is None:
            at = rec.sites[site] = [0, 0, 0]
        at[0] += 1
        at[1] += dt
        if dt > rec.max_wait_ns:
            rec.max_wait_ns, rec.max_wait_site = dt, site
            rec.max_wait_after = h.program
        rec._open_gap(site, t1)
        if not h.open:
            rec.tail_ns += dt
            _export.consider_slow(rec)


def last_dispatch_s() -> float:
    """Seconds the host spent in this thread's last program call."""
    return _HOST.dispatch_ns * 1e-9


def last_dispatch_return_s() -> float:
    """When this thread's last program call returned (``perf_counter``)."""
    return _HOST.t_dispatch * 1e-9


def last_fetch_return_s() -> float:
    """When this thread's last fetch returned (``perf_counter``): the
    device-resolved end of whatever the fetch waited for."""
    return _HOST.t_fetch * 1e-9


class Span:
    """One timed phase inside a query trace. ``attrs`` carries structured
    annotations (rows, collective bytes, node ids, gate decisions);
    ``counters`` holds the bumps that fired while this span was the
    innermost open one — {name: [count, rows]}."""

    __slots__ = ("name", "t0", "t1", "rows", "attrs", "counters", "children")

    def __init__(self, name: str, t0: float, rows: Optional[int],
                 attrs: Optional[Dict[str, Any]]):
        self.name = name
        self.t0 = t0
        self.t1: Optional[float] = None
        self.rows = rows
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.counters: Dict[str, List[int]] = {}
        self.children: List["Span"] = []

    def dur_s(self) -> float:
        return max((self.t1 if self.t1 is not None else self.t0) - self.t0, 0.0)

    def walk(self) -> Iterator["Span"]:
        yield self
        for c in self.children:
            yield from c.walk()


class QueryTrace:
    """One query's structured trace: a span tree plus per-query counters
    and gauges. Single-threaded by construction (the contextvar confines
    a trace to the thread that opened it); lifecycle::

        open --(spans/bumps)--> closed --(resolve_table at the deferred
        count fetch, when a dispatched result is pending)--> finished

    ``finished`` traces go to the flight-recorder ring (:mod:`.export`).
    A dispatched-but-never-materialized query stays unfinished and is
    simply never recorded — recording it would require the host sync the
    engine refuses to make."""

    __slots__ = (
        "qid", "name", "kind", "hist_key", "obs_key", "label", "thread",
        "t0", "t1", "resolved", "closed", "finished", "pending",
        "spans", "_stack", "counters", "values", "attrs", "op",
    )

    def __init__(self, name: str, kind: str = "query"):
        self.qid = next(_QIDS)
        self.name = name
        self.kind = kind
        self.hist_key: Optional[str] = None
        self.obs_key: Optional[str] = None
        self.label = name
        self.thread = threading.get_ident()
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None
        self.resolved: Optional[float] = None
        self.closed = False
        self.finished = False
        self.pending = False
        self.spans: List[Span] = []
        self._stack: List[Span] = []
        self.counters: Dict[str, List[int]] = {}
        self.values: Dict[str, float] = {}
        self.attrs: Dict[str, Any] = {}
        #: the public call's always-on record (OpRecord) this trace was
        #: opened inside, so that trace and record are one set of numbers
        self.op: Optional["OpRecord"] = _HOST.rec if _HOST.open else None

    # -- span plumbing (called only from this thread's span()) ---------
    def _open(self, name, rows, attrs) -> Span:
        sp = Span(name, time.perf_counter(), rows, attrs)
        (self._stack[-1].children if self._stack else self.spans).append(sp)
        self._stack.append(sp)
        return sp

    def _close(self, sp: Span) -> None:
        sp.t1 = time.perf_counter()
        if self._stack and self._stack[-1] is sp:
            self._stack.pop()
        elif sp in self._stack:  # pragma: no cover - unbalanced exit
            self._stack.remove(sp)

    def _count(self, name: str, rows: Optional[int]) -> None:
        for store in (
            (self.counters, self._stack[-1].counters)
            if self._stack else (self.counters,)
        ):
            c = store.get(name)
            if c is None:
                c = store[name] = [0, 0]
            c[0] += 1
            if rows is not None:
                c[1] += int(rows)

    def _value(self, name: str, value: float) -> None:
        self.values[name] = float(value)
        if self._stack:
            self._stack[-1].attrs[name] = float(value)

    # -- read-side helpers ---------------------------------------------
    def all_spans(self) -> Iterator[Span]:
        for sp in self.spans:
            yield from sp.walk()

    def wall_s(self) -> float:
        end = self.resolved if self.resolved is not None else self.t1
        return max((end if end is not None else self.t0) - self.t0, 0.0)

    def device_resolved_s(self) -> Optional[float]:
        """Dispatch-open to deferred-count-fetch-return wall: the
        sync-free 'device' latency (None until resolved)."""
        if self.resolved is None:
            return None
        return max(self.resolved - self.t0, 0.0)


def current() -> Optional[QueryTrace]:
    return _ACTIVE.get()


_finish_lock = threading.Lock()


def _maybe_finish(q: QueryTrace) -> None:
    # the closing (dispatching) thread and the resolving (materializing)
    # thread can race here; the lock makes finish exactly-once so the
    # ring never holds a duplicate and query.traces never over-counts
    with _finish_lock:
        if q.finished or not q.closed:
            return
        if q.pending and q.resolved is None:
            return  # a dispatched result will resolve us at its count fetch
        q.finished = True
    # resolve any window-pending stage-clock profiles (fused-pipeline
    # dispatches) BEFORE the ring/export see the trace: the device-
    # resolved end is stamped, so this is host arithmetic only — prof
    # owns a 0-site sync budget exactly like resolve_table. Lazy import:
    # prof imports this module for the active-trace contextvar.
    from . import prof as _prof

    _prof.finalize(q)
    _metrics.rollup_count("query.traces")
    _export.record(q)
    # persist the trace's per-node wall/rows/coll bytes when the
    # observation store is on (host dict+file work only — never a sync)
    _obsstore.record_trace(q)
    # stamp the finish time for the resource ledger's leak detector
    # (tables attributed to this query age against THIS clock); lazy
    # import — resource imports this module for the contextvar
    from . import resource as _resource

    _resource.query_finished(q)


# ----------------------------------------------------------------------
# the instrumentation surface (span / bump / gauge / annotate)
# ----------------------------------------------------------------------
@contextlib.contextmanager
def span(name: str, rows: Optional[int] = None, **attrs) -> Iterator[Optional[Span]]:
    """Time one phase. Always feeds the process-global rollup; when a
    query trace is active (or tracing is enabled, opening an implicit
    per-op-chain trace at the outermost span) also records a tree node
    and yields it so the caller can attach attrs."""
    q = _ACTIVE.get()
    token = sp = None
    if q is None and tracing_active():
        # outermost span of an eager op chain: implicit per-chain trace
        q = QueryTrace(name, kind="op")
        token = _ACTIVE.set(q)
    if q is not None:
        sp = q._open(name, rows, attrs)
    t0 = time.perf_counter()
    try:
        # inside a jax.profiler session the span is a host event on the
        # device trace's clock; outside one a TraceMe is a flag test
        with TraceAnnotation(name):
            yield sp
    finally:
        if sp is None:
            # disabled fast path: rollup only, nothing allocated
            dt = time.perf_counter() - t0
        else:
            q._close(sp)
            dt = sp.dur_s()
        _metrics.rollup_span(name, dt, rows)
        if trace_enabled():
            extra = f" rows={rows}" if rows is not None else ""
            print(
                f"[cylon_tpu] {name}: {dt * 1e3:.2f} ms{extra}",
                file=sys.stderr,
            )
        if token is not None:
            _ACTIVE.reset(token)
            q.t1 = sp.t1
            q.closed = True
            _maybe_finish(q)


def bump(name: str, rows: Optional[int] = None) -> None:
    """Count an event in the rollup AND the active query trace (if any),
    attributed to the innermost open span."""
    _metrics.rollup_count(name, rows)
    q = _ACTIVE.get()
    if q is not None:
        q._count(name, rows)


def gauge(name: str, value: float) -> None:
    """Record a measured value (not a duration); the active trace keeps
    the latest per-query value on the innermost span."""
    _metrics.rollup_value(name, value)
    q = _ACTIVE.get()
    if q is not None:
        q._value(name, value)
    if trace_enabled():
        print(f"[cylon_tpu] {name} = {value:.4f}", file=sys.stderr)


def annotate_add(**attrs) -> None:
    """Accumulate numeric annotations on the innermost open span of the
    active trace (no-op when tracing is off). The shuffle engine uses
    this to attach per-exchange collective bytes/rounds to whichever
    span — typically the owning ``plan.node.*`` — is executing."""
    q = _ACTIVE.get()
    if q is None:
        return
    target = q._stack[-1].attrs if q._stack else q.attrs
    for k, v in attrs.items():
        prev = target.get(k)
        target[k] = (prev + v) if isinstance(prev, (int, float)) else v


# ----------------------------------------------------------------------
# explicit query traces + the deferred (sync-free) resolution hook
# ----------------------------------------------------------------------
@contextlib.contextmanager
def query_trace(
    name: str, kind: str = "query", force: bool = False
) -> Iterator[Optional[QueryTrace]]:
    """Open a query trace for the block. Without ``force``: no-op when
    one is already active (spans then nest into the outer trace — yields
    None) or tracing is disabled. ``force=True`` ALWAYS opens a trace,
    shadowing any active one for the block (``explain(analyze=True)``
    must get its own span tree even inside a user's query_trace)."""
    if not force and (_ACTIVE.get() is not None or not tracing_active()):
        yield None
        return
    q = QueryTrace(name, kind=kind)
    token = _ACTIVE.set(q)
    try:
        yield q
    finally:
        _ACTIVE.reset(token)
        if q.t1 is None:
            q.t1 = time.perf_counter()
        q.closed = True
        _maybe_finish(q)


def attach_result(
    table,
    fingerprint=None,
    label: str = "",
    t0: Optional[float] = None,
    hist_key: Optional[str] = None,
    obs_key: Optional[str] = None,
    batch_b: Optional[int] = None,
) -> None:
    """Bind a dispatched result Table to the active trace / the latency
    histogram. The table's deferred count fetch (``_materialize_counts``)
    will call :func:`resolve_table`, stamping the device-resolved end
    time and observing ``fetch-time - t0`` into the fingerprint-keyed
    histogram — with NO additional host sync (the fetch already
    happened). Counts already host-known resolve immediately.

    Hot callers (``LazyFrame.dispatch``, the serving scheduler) pass the
    PRECOMPUTED ``hist_key`` hoisted onto the cached executor entry
    (``engine.PlanEntry``); ``fingerprint=`` hashes per call and remains
    for one-shot diagnostic callers only. ``obs_key`` (+ optional
    ``batch_b``, the serving batch size) additionally lands the resolved
    latency in the persistent observation store (obs/store.py)."""
    q = _ACTIVE.get()
    key = hist_key
    if key is None and fingerprint is not None:
        key = _metrics.fingerprint_key(fingerprint)
    if q is not None:
        q.pending = True
        if key is not None:
            q.hist_key = key
        if obs_key is not None:
            q.obs_key = obs_key
        if label:
            q.label = label
        if t0 is None:
            t0 = q.t0
    if q is None and key is None and obs_key is None:
        return
    rec = (q, key, label, t0 if t0 is not None else time.perf_counter(),
           obs_key, batch_b)
    if table._counts_host is not None:
        _resolve_record(rec, time.perf_counter())
        return
    # a plan whose output is a passthrough of a still-deferred table
    # (e.g. a bare Scan) can attach a second record before the first
    # resolves — chain them; one fetch resolves every pending query.
    # Serialized under the table's _mat_lock (non-None whenever counts
    # are deferred): resolve_table drains the list while the
    # materializing thread holds the same lock, so a record can never
    # land on an already-drained table and stay pending forever.
    with table._mat_lock:
        if table._counts_host is None:
            pending = getattr(table, "_obs_pending", None)
            if pending is None:
                table._obs_pending = [rec]
            else:
                pending.append(rec)
            return
    # lost the race: another thread materialized while we acquired
    _resolve_record(rec, time.perf_counter())


def resolve_table(table) -> None:
    """The deferred-timing hook: called by ``Table._materialize_counts``
    right after its (pre-existing) count fetch returns. Never fetches
    itself — graft-lint budgets pin this function at 0 sync sites."""
    recs = getattr(table, "_obs_pending", None)
    if not recs:
        return
    table._obs_pending = None
    now = time.perf_counter()
    for rec in recs:
        _resolve_record(rec, now)


def _resolve_record(rec, now: float) -> None:
    q, key, label, t0, obs_key, batch_b = rec
    if key is not None:
        _metrics.observe_latency(key, max(now - t0, 0.0), label=label)
    if obs_key is not None:
        # the persistent store's latency journal — the fetch already
        # happened, this is host file I/O only
        _obsstore.observe_latency(obs_key, max(now - t0, 0.0), batch_b)
    if q is not None:
        q.resolved = now
        _maybe_finish(q)


# ----------------------------------------------------------------------
# explain(analyze=True) support
# ----------------------------------------------------------------------
@contextlib.contextmanager
def analyze_mode() -> Iterator[None]:
    """While active, the plan executor materializes EVERY node's result
    (diagnostic per-node syncs — rows in/out become exact). Only
    ``LazyFrame.explain(analyze=True)`` sets this; the production
    dispatch path never does, keeping its 1-sync contract."""
    token = _ANALYZE.set(True)
    try:
        yield
    finally:
        _ANALYZE.reset(token)


def analyze_active() -> bool:
    return _ANALYZE.get()


# ----------------------------------------------------------------------
# device profiler passthrough (the jax.profiler wrapper)
# ----------------------------------------------------------------------
@contextlib.contextmanager
def profile(log_dir: str) -> Iterator[None]:
    """Capture a device-level profiler trace (Perfetto/XPlane via
    jax.profiler) around a block, alongside the host-side spans."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
