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

Disabled cost: with tracing off and no active trace, ``span()`` takes
the legacy fast path — one contextvar read, one perf_counter pair, one
locked rollup update; NO Span/QueryTrace allocation
(tests/test_obs.py pins zero allocation; tools/trace_smoke.py gates the
per-query overhead under 2%).
"""
from __future__ import annotations

import contextlib
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
        "spans", "_stack", "counters", "values", "attrs",
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
