"""Query-scoped telemetry (ISSUE 8): span trees, sync-free device timing,
a metrics registry with plan-fingerprint latency histograms, and exporters.

Four modules, layered bottom-up:

- :mod:`.metrics` — the process-global ROLLUP (the old ``utils/tracing``
  aggregate: {name: count/total/max/rows}, always on, lock-serialized)
  plus the latency-histogram registry keyed by plan fingerprint — the
  substrate of the ROADMAP-1 serving benchmark's p50/p95/p99 columns.
- :mod:`.trace` — the contextvar-based query trace: a structured span
  TREE per query (eager op chain or ``LazyFrame.dispatch()``), with
  per-query counters/gauges so concurrent queries never interleave, and
  the deferred device-timing hook that rides the existing
  ``_materialize_counts`` fetch (it never adds a host sync — graft-lint
  L3 budgets pin that mechanically).
- :mod:`.export` — the bounded flight-recorder ring of the last N query
  traces and the Chrome trace-event (Perfetto-loadable) exporter, one
  track per query (plus per-shard stage tracks for profiled queries);
  and, always on, the last 256 public calls' records of where the host's
  time went (:class:`.trace.OpRecord`: every dispatch and every fetch
  timed, the fetches by site) with the eight slowest the process has
  seen: :func:`last_ops`, :func:`slowest_ops`.
- :mod:`.prof` — the critical-path profiler (ISSUE 15,
  ``CYLON_TPU_PROF``): per-stage per-shard device stage clocks derived
  sync-free from already-fetched counts + the deferred-fetch window,
  the straggler ledger (``prof.straggler_ratio*``), the measured
  overlap ledger, and longest-path attribution over span trees
  (EXPLAIN ANALYZE "crit %", ``tools/traceview --critical``).
- :mod:`.store` — the PERSISTENT observation journal (ISSUE 11):
  per-fingerprint profiles surviving across runs under
  ``CYLON_TPU_OBS_DIR`` (one journal per writer process — opsd, workers
  and benchmarks share a directory), the evidence the feedback re-coster
  (``plan/feedback.py``) tunes the engine's adaptive gates from.
- :mod:`.resource` — the resource LEDGER (ISSUE 12): live device-HBM
  accounting via per-Table weakref finalizers, host/disk arena and
  serving-lease watermarks, per-fingerprint footprint attribution (the
  admission re-coster's evidence), and the query-scoped leak detector.
  The collector runs a finalizer at any allocation, on a thread that may
  hold any lock (the metrics registry's, the finalizer's own), so THE
  FINALIZER RULE holds for every one in the package: *a finalizer
  appends to a deque and frees what it alone owns; it takes no lock,
  emits no metric, opens no span.* The next explicit touch of the
  accounts drains the deque under their lock and reports the gauges
  outside it. The three that give bytes back all do so: a table
  (``ResourceLedger._unregister``), a spill arena
  (``parallel/spill.HostArena.__del__``, which also unlinks its own
  files) and a dropped serving future's lease
  (``serve/scheduler.ServeScheduler._dropped``);
  ``tests/test_finalizers.py`` holds each lock in turn and drops one.
- :mod:`.slo` — rolling-window SLO rules (p99 burn vs target, shed
  rate, leak, resource headroom) with OK/WARN/BREACH transitions into
  the flight ring; the ``/healthz`` substrate.

The live ops endpoint (``OpsServer`` in :mod:`.export`, started by
``CYLON_TPU_METRICS_PORT``) serves all of it: ``/metrics`` (Prometheus
text exposition), ``/healthz``, ``/queries``.

``utils/tracing.py`` is the thin compat shim over this package: every
pre-existing call site (``span``/``bump``/``gauge``/``report``/...)
keeps working, and the process-global rollup keeps feeding the
graft-lint plan registry (``analysis/plans.py``) unchanged.
"""
from . import export, metrics, prof, resource, slo, stages, store, trace  # noqa: F401
from .metrics import (  # noqa: F401
    fingerprint_key,
    latency_quantiles,
    latency_report,
    observe_latency,
)
from .trace import (  # noqa: F401
    QueryTrace,
    Span,
    annotate_add,
    query_trace,
    tracing_active,
)
from .export import (  # noqa: F401
    OpsServer,
    ensure_ops_server,
    last_ops,
    prometheus_text,
    slowest_ops,
    traces,
    validate_prometheus,
    write_chrome,
)
from .resource import ResourceLedger, ledger  # noqa: F401
from .slo import SLOMonitor, monitor  # noqa: F401

__all__ = [
    "OpsServer",
    "QueryTrace",
    "ResourceLedger",
    "SLOMonitor",
    "Span",
    "annotate_add",
    "ensure_ops_server",
    "export",
    "fingerprint_key",
    "latency_quantiles",
    "last_ops",
    "latency_report",
    "ledger",
    "metrics",
    "monitor",
    "observe_latency",
    "prof",
    "prometheus_text",
    "query_trace",
    "resource",
    "slo",
    "slowest_ops",
    "store",
    "trace",
    "traces",
    "tracing_active",
    "validate_prometheus",
    "write_chrome",
]
