"""The metrics registry: process-global rollup + latency histograms.

Two stores, both lock-serialized and host-only (graft-lint pins that this
module owns ZERO host-sync sites — telemetry must never touch the
device):

ROLLUP
    The aggregate the old flat tracer kept: ``{name: {count, total_s,
    max_s, rows}}``. Always on — the graft-lint plan registry
    (``analysis/plans.py``), the benchmark gates and dozens of tests
    assert on these counters, so the rollup survives the query-scoped
    refactor unchanged. ``utils/tracing.report()`` / ``get_count()`` /
    ``reset_trace()`` are shims over it.

HISTOGRAMS
    Latency distributions keyed by an arbitrary string — in production
    the PLAN FINGERPRINT (:func:`fingerprint_key`), so every repeated
    collect of one plan shape lands in one distribution and a serving
    benchmark reads p50/p95/p99 per query shape straight from here
    (ROADMAP item 1's "queries/sec at a fixed p99"). Buckets are
    geometric (24/decade, ~10% relative resolution) so the registry is
    O(buckets), never O(samples), no matter how many queries a serving
    process answers. ``LazyFrame.dispatch()`` observes into this
    registry unconditionally (tracing enabled or not): the histogram
    update is one lock + one dict bump, and serving metrics must not
    require the trace ring.

Stable metric names: every engine counter/gauge/span family is declared
in :data:`STABLE_METRICS` with its kind; docs/ARCHITECTURE.md renders
the same table. New instrumentation starts there — an undeclared name is
a review finding (``tests/test_obs.py`` enforces coverage for everything
a q3 run emits).
"""
from __future__ import annotations

import hashlib
import math
import threading
from collections import OrderedDict, defaultdict
from typing import Dict, Optional, Tuple

from ..utils import envgate as _eg

_lock = threading.Lock()

_ROLLUP: Dict[str, Dict[str, float]] = defaultdict(
    lambda: {"count": 0, "total_s": 0.0, "max_s": 0.0, "rows": 0,
             "last": None}
)


# ----------------------------------------------------------------------
# the process-global rollup (compat surface of utils/tracing.py)
# ----------------------------------------------------------------------
def _span_locked(name: str, dt: float) -> Dict[str, float]:
    s = _ROLLUP[name]
    s["count"] += 1
    s["total_s"] += dt
    s["max_s"] = max(s["max_s"], dt)
    return s


def rollup_span(name: str, dt: float, rows: Optional[int] = None) -> None:
    with _lock:
        s = _span_locked(name, dt)
        if rows is not None:
            s["rows"] += int(rows)


def rollup_count(name: str, rows: Optional[int] = None) -> None:
    with _lock:
        s = _ROLLUP[name]
        s["count"] += 1
        if rows is not None:
            s["rows"] += int(rows)


def rollup_value(name: str, value: float) -> None:
    with _lock:
        s = _ROLLUP[name]
        s["count"] += 1
        s["total_s"] += float(value)
        s["max_s"] = max(s["max_s"], float(value))
        # the CURRENT gauge value (max_s is the process peak): the
        # Prometheus exposition needs both, and "last is not None" is
        # how the exporter tells a gauge family from a counter
        s["last"] = float(value)


def get_count(name: str) -> int:
    with _lock:
        return int(_ROLLUP[name]["count"]) if name in _ROLLUP else 0


def snapshot() -> Dict[str, Dict[str, float]]:
    """Deep-copied rollup: {name: {count, total_s, max_s, rows}}."""
    with _lock:
        return {k: dict(v) for k, v in _ROLLUP.items()}


def report(prefix: Optional[str] = None) -> Dict[str, Dict[str, float]]:
    stats = snapshot()
    if prefix is None:
        return stats
    return {k: v for k, v in stats.items() if k.startswith(prefix)}


def reset_rollup() -> None:
    with _lock:
        _ROLLUP.clear()


# ----------------------------------------------------------------------
# latency histograms keyed by plan fingerprint
# ----------------------------------------------------------------------
#: geometric bucket resolution: 24 buckets per decade ~= 10% per step —
#: coarse enough to stay O(1) memory per key, fine enough that a p99
#: read-off is within one resolution step of the true sample quantile
BUCKETS_PER_DECADE = 24


def bucket_of(seconds: float) -> int:
    """The geometric bucket a duration falls in (the one copy of the
    rule: :meth:`Histogram.record` and the tests that look a stall up)."""
    return int(math.floor(
        math.log10(max(float(seconds), 1e-9)) * BUCKETS_PER_DECADE
    ))


class Histogram:
    """Geometric-bucket latency histogram (seconds). NOT thread-safe on
    its own — every registry access serializes under the module lock."""

    __slots__ = ("buckets", "n", "total_s", "min_s", "max_s")

    def __init__(self) -> None:
        self.buckets: Dict[int, int] = {}
        self.n = 0
        self.total_s = 0.0
        self.min_s = math.inf
        self.max_s = 0.0

    def record(self, seconds: float) -> None:
        s = max(float(seconds), 1e-9)
        b = bucket_of(s)
        self.buckets[b] = self.buckets.get(b, 0) + 1
        self.n += 1
        self.total_s += s
        self.min_s = min(self.min_s, s)
        self.max_s = max(self.max_s, s)

    def quantile(self, q: float) -> float:
        """Upper edge of the bucket holding the q-quantile sample,
        clamped to the observed [min, max] (exact at the extremes)."""
        if not self.n:
            return 0.0
        edge = bucket_quantile(self.buckets, q)
        return min(max(edge, self.min_s), self.max_s)


#: the in-process histogram registry is BOUNDED: a serving process
#: answering a million distinct fingerprints must not grow host memory
#: without limit. LRU order = last observation; capacity scales with the
#: flight-ring knob (the one "how much observability state" dial) at
#: HIST_CAP_PER_RING entries per ring slot, floored at HIST_CAP_MIN.
#: Evicted histograms flush to the persistent observation store when one
#: is configured (obs/store.py) — bounding memory never loses a sample.
_HISTS: "OrderedDict[str, Histogram]" = OrderedDict()
_HIST_LABELS: Dict[str, str] = {}
HIST_CAP_PER_RING = 16
HIST_CAP_MIN = 256


def hist_capacity() -> int:
    """Max in-process latency-histogram keys, derived from
    CYLON_TPU_TRACE_RING (read per miss — resizable without restart)."""
    try:
        ring = int(_eg.TRACE_RING.get())
    except ValueError:
        ring = 64
    return max(HIST_CAP_PER_RING * max(ring, 1), HIST_CAP_MIN)


def fingerprint_key(fingerprint) -> str:
    """Stable short key for a plan fingerprint (any reprable value):
    12 hex chars of blake2s over the repr — the histogram / trace-track
    identity of one plan shape within a process.

    The repr walk over a deep plan tuple is NOT free, so the cached
    executor entry hoists its key (``engine.PlanEntry.hist_key``) and the
    serving hot loop never re-hashes; ``plan.fingerprint.hash`` counts
    every hash performed so tests can pin the hot loop at zero."""
    rollup_count("plan.fingerprint.hash")
    return hashlib.blake2s(
        repr(fingerprint).encode(), digest_size=6
    ).hexdigest()


def observe_latency(key: str, seconds: float, label: str = "") -> None:
    """Record one query latency under ``key`` (a fingerprint_key, or any
    caller-chosen stable name, e.g. a benchmark row). A NEW key past
    :func:`hist_capacity` LRU-evicts the coldest entries; evicted
    histograms flush to the observation store (outside the lock) so no
    observation is lost when one is configured."""
    evicted = []
    with _lock:
        h = _HISTS.get(key)
        if h is None:
            cap = hist_capacity()
            while len(_HISTS) >= cap:
                k2, h2 = _HISTS.popitem(last=False)
                evicted.append((k2, h2, _HIST_LABELS.pop(k2, "")))
            h = _HISTS[key] = Histogram()
        else:
            _HISTS.move_to_end(key)
        if label and key not in _HIST_LABELS:
            _HIST_LABELS[key] = label
        h.record(seconds)
    if evicted:
        rollup_count("obs.hist.evicted", rows=len(evicted))
        from . import store as _obstore

        if _obstore.store() is not None:
            for k2, h2, lb in evicted:
                _obstore.absorb_histogram(k2, h2, lb)


def latency_quantiles(key: str) -> Optional[Dict[str, float]]:
    """{count, mean_s, p50_s, p95_s, p99_s, max_s} or None (no samples)."""
    with _lock:
        h = _HISTS.get(key)
        if h is None or not h.n:
            return None
        return _summary(h)


def _summary(h: Histogram) -> Dict[str, float]:
    return {
        "count": h.n,
        "mean_s": h.total_s / h.n,
        "p50_s": h.quantile(0.50),
        "p95_s": h.quantile(0.95),
        "p99_s": h.quantile(0.99),
        "max_s": h.max_s,
    }


def latency_report() -> Dict[str, Dict[str, float]]:
    """All keys: {key: {label, count, p50_s, p95_s, p99_s, ...}}."""
    with _lock:
        keys = list(_HISTS)
        labels = dict(_HIST_LABELS)
    out = {}
    for k in keys:
        q = latency_quantiles(k)
        if q is not None:
            q["label"] = labels.get(k, "")
            out[k] = q
    return out


def bucket_snapshot() -> Dict[str, Dict]:
    """Raw per-key histogram buckets: ``{key: {label, n, b: {bucket:
    count}}}``. Bucket counts are monotone, so two snapshots DIFF into
    the window's distribution — the SLO monitor's rolling-p99 substrate
    (obs/slo.py); the cumulative registry itself stays windowless."""
    with _lock:
        return {
            k: {
                "label": _HIST_LABELS.get(k, ""),
                "n": h.n,
                "b": dict(h.buckets),
            }
            for k, h in _HISTS.items()
        }


def bucket_quantile(buckets: Dict[int, int], q: float) -> float:
    """THE geometric-bucket quantile read-off (seconds, upper edge of
    the bucket holding the q-quantile sample), unclamped. The one copy:
    :meth:`Histogram.quantile` wraps it with the observed min/max clamp,
    ``obs.store.lat_quantile`` with the profile's, and the SLO monitor's
    windowed bucket DIFFS use it bare (a diff has no extremes) — a
    bucket-scheme change can never skew one consumer silently."""
    n = sum(buckets.values())
    if not n:
        return 0.0
    target = q * n
    acc = 0
    for b in sorted(buckets):
        acc += buckets[b]
        if acc >= target:
            return 10.0 ** ((b + 1) / BUCKETS_PER_DECADE)
    return 0.0


def reset_latency() -> None:
    with _lock:
        _HISTS.clear()
        _HIST_LABELS.clear()


# ----------------------------------------------------------------------
# the host's waits and program calls (obs.trace notes them, always on)
# ----------------------------------------------------------------------
#: one wait histogram a fetch site (``obs.stages.FETCH_SITES``, so the
#: registry is bounded by the vocabulary): a stalled fetch is a bucket with
#: a count, where the rollup's ``max_s`` keeps only the worst
_WAITS: Dict[str, Histogram] = {}


def rollup_wait(site: str, dt: float) -> None:
    """One device-to-host fetch at ``site`` that the host waited ``dt``
    seconds in: the rollup span ``host_sync.<site>`` and the site's
    histogram, under one lock."""
    with _lock:
        _span_locked("host_sync." + site, dt)
        h = _WAITS.get(site)
        if h is None:
            h = _WAITS[site] = Histogram()
        h.record(dt)


def wait_report() -> Dict[str, Dict]:
    """``{site: {count, mean_s, p50_s, p95_s, p99_s, max_s, buckets}}`` of
    the waits since the process started (or :func:`reset_waits`)."""
    with _lock:
        return {
            site: {**_summary(h), "buckets": dict(h.buckets)}
            for site, h in _WAITS.items() if h.n
        }


def reset_waits() -> None:
    with _lock:
        _WAITS.clear()


# ----------------------------------------------------------------------
# the documented stable names (docs/ARCHITECTURE.md "Observability")
# ----------------------------------------------------------------------
#: name-or-prefix -> (kind, meaning). Prefixes end with "."; a metric is
#: DECLARED when it matches an exact name or starts with a prefix. The
#: names are a compatibility surface: benchmarks, CI gates and the
#: graft-lint plan registry assert on them, so renames are breaking
#: changes made only with their consumers.
STABLE_METRICS: Dict[str, Tuple[str, str]] = {
    "host_sync": ("counter", "device->host count fetches (the sync census)"),
    "host_sync.": (
        "span", "the host's wait in each device->host fetch, by site "
        "(obs.stages.FETCH_SITES; table._fetch is the one place that "
        "counts, times and names a fetch): count, total_s, max_s, and a "
        "wait histogram a site (/metrics: quantiles)"),
    "dispatch.": (
        "span", "host time inside each program call, by program name "
        "(engine.get_kernel's cached callable reads the clock around the "
        "jitted call; a compile shows here as the first call's seconds)"),
    "sort": ("span", "local sort dispatch"),
    "sort.ride_lanes": (
        "counter", "payload rides of Table.sort and the speculative join's "
        "right sort, counted at dispatch (rows= 32-bit lanes that rode)"),
    "sort.ride_batches": (
        "counter", "the same rides (rows= sorts that carried the lanes: 1 "
        "when they fit one, more past ops/sort.RIDE_LANES)"),
    "sort.topk": ("span", "Table.topk's one program, at dispatch"),
    "plan.topk": ("counter", "TopK plan nodes lowered onto Table.topk"),
    "unique": ("span", "local unique dispatch"),
    "stats.measure": ("span", "on-demand column range-stats kernel"),
    "join.": (
        "mixed", "join phases as spans (speculative/fused/pallas_pk/"
        "sum_pushdown/semi: the semi-reduction's dispatch and counts "
        "fetch) + the emit census as counters, from numbers the host "
        "holds: emit_slots (rows= slots an emit wrote) and emit_rows "
        "(rows= rows live in them)"),
    "join.emit.": (
        "counter", "which form of its left half a speculative INNER/LEFT "
        "emit took, a shard, read from the flag that rides the totals' "
        "fetch (ops.join._emit_inner_left): .handthrough (every live left "
        "row emitted exactly once, the left columns passed as they lie) "
        "and .gathered (the run expansion and the packed left gather); "
        "rows= the live output rows of the shards that took it. A counted "
        "join fetches nothing and bumps neither"),
    "join.route.replicate": (
        "counter", "distributed joins on a mesh that took the replicate "
        "route: one side gathered whole to every chip, the other not moved "
        "(Table.distributed_join and the planner's Join alike; rows= both "
        "sides' host-known rows)"),
    "join.route.shuffle": (
        "counter", "distributed joins on a mesh that hash-shuffled both "
        "sides (rows= both sides' rows as far as the host holds them; "
        "join.replicate.rows and shuffle.coll_rows over the two routes' "
        "rows is the share of a join's input that crossed the mesh)"),
    "join.replicate": (
        "span", "the replicate route's gather of the small side, at "
        "dispatch (program join_replicate, device stage join.replicate; "
        "rows= the side's rows; a host event inside a profiler session)"),
    "join.replicate.rows": (
        "counter", "rows those gathers brought to chips that did not hold "
        "them (rows= the side's rows times the other chips)"),
    "join.semi_join": (
        "span", "a semi or anti join as an operator (Table.join how='semi' "
        "/ 'anti'): the keys-only program's dispatch and, where the rows "
        "are compacted, the kept count's fetch (rows= both sides' "
        "host-known rows)"),
    "join.semi.left_rows": (
        "counter", "left rows semi and anti joins were asked about (rows= "
        "the left's host-known row count, 0 while it is deferred)"),
    "join.semi.kept_rows": (
        "counter", "left rows they kept (rows= the fetched count; nothing "
        "where the hit mask was handed to an aggregate, which fetches no "
        "count)"),
    "join.semi.payload_rows": (
        "counter", "rows a semi or anti join gathered or compacted (rows= "
        "the kept rows on the eager path, 0 where the planner's "
        "semi_as_mask handed the hit mask to the aggregate above)"),
    "setop.": ("span", "union/subtract/intersect dispatch"),
    "groupby.": (
        "mixed", "groupby phases as spans (emit) + the path a call took as "
        "counters: dense_path, factorize_path, partial_path, "
        "raw_shuffle_path"),
    "groupby.partial_path": (
        "counter", "group-bys on a mesh that reduced every shard's own rows "
        "to a partial state first: combined in place on the dense plan (no "
        "exchange of rows; bumped beside groupby.dense_path and "
        "groupby.partial.rows), or shipped as one partial row a group a "
        "shard (beside groupby.precombine.*)"),
    "groupby.raw_shuffle_path": (
        "counter", "group-bys on a mesh that shuffled their input rows: an "
        "op without a partial state (var / std / nunique / quantile)"),
    "groupby.precombine.rows_in": (
        "counter", "input rows of the group-bys that shipped partial rows "
        "(rows= the table's host-known row count, 0 while it is deferred); "
        "shuffle.coll_rows over it is the share that crossed the mesh"),
    "groupby.precombine.rows_out": (
        "counter", "partial rows their pre-combines left, over all shards "
        "(rows= the fetched counts' sum)"),
    "groupby.precombine.fullest": (
        "counter", "the same of the fullest shard (rows= the fetched "
        "counts' largest: what the capacity is counted from)"),
    "groupby.precombine.slots": (
        "counter", "slots a shard's partial table crossed the mesh in "
        "(rows= round_cap of the fullest shard's count)"),
    "groupby.compact.steps": (
        "counter", "sort-and-segment group-bys (one a shard program: a "
        "Table.groupby, a pre-combine, a combine) whose run heads reached "
        "their slots by the log-step compress, ops.sort.step_compact "
        "(rows= the slots of a shard the moves pass over)"),
    "groupby.compact.passes": (
        "counter", "passes those compresses make over their slots (rows= "
        "len(ops.sort.step_passes(slots)): one a bit of the slot count, "
        "one a two bits from ops.sort.STEP_TWO_BITS_MIN_SLOTS slots)"),
    "groupby.partial.rows": (
        "counter", "input rows those calls aggregated without an exchange "
        "(rows= the table's host-known row count, 0 while it is deferred); "
        "beside shuffle.coll_rows it is the share of rows that never "
        "crossed the mesh"),
    "groupby.combine.slots": (
        "counter", "slots of the partial table a combine moved (rows= the "
        "dense id space: the product of the key spans, 1 without keys)"),
    "shuffle.count": ("span", "shuffle count-phase kernel + fetch"),
    "shuffle.exchange": ("span", "whole K-round exchange wall"),
    "shuffle.round.": ("span", "per-round pack/collective/compact dispatch"),
    "shuffle.rounds": ("counter", "round count K per shuffle (rows=K)"),
    "shuffle.pack.ride_lanes": (
        "counter", "payload rides of the pack's sort by destination, "
        "counted at each pack dispatch (rows= 32-bit lanes that rode)"),
    "shuffle.pack.ride_batches": (
        "counter", "the same rides (rows= sorts that carried the lanes: 1 "
        "when they fit one, more past ops/sort.RIDE_LANES)"),
    "shuffle.range.": (
        "counter", "how evenly a range shuffle's sampled splitters cut the "
        "rows, from the counts it fetches anyway: shard_rows_max (rows= "
        "the fullest shard's) and shard_rows_mean (rows= the mean)"),
    "shuffle.hash.": (
        "counter", "how unevenly a hash shuffle spread the rows, from the "
        "count matrix chosen after the semi filter's decision: "
        "shard_rows_max (rows= the fullest shard's) and shard_rows_mean "
        "(rows= the mean)"),
    "shuffle.coll_slots": (
        "counter", "row slots a shuffle's collective rounds ship (rows= "
        "K x world^2 x cap), beside shuffle.exchanged_bytes"),
    "shuffle.coll_rows": (
        "counter", "rows those rounds carry (rows= the chosen count "
        "matrix's sum less a skew-split schedule's relayed tail): over "
        "shuffle.coll_slots it is how full the exchange's buffers are"),
    "shuffle.reassemble.": (
        "counter", "a shuffle of more than one round, or with a relay or "
        "ring tail, reassembling its parts in one program (.parts rows= "
        "the blocks written, .rows rows= the live rows placed); a "
        "one-round shuffle bumps neither"),
    "shuffle.compact.": (
        "counter", "which front-pack a compact dispatch of a shuffle round "
        "ran, bumped by the host at the dispatch: .blocks a one-hop "
        "receive's block writes at the running offsets of the received "
        "counts (parallel.shuffle.front_pack_chunks; rows= the chunks "
        "placed, the mesh's size), .by_order the liveness argsort and a "
        "gather an array that the two-hop receive and the ring relay keep"),
    "shuffle.bincount.": (
        "counter", "which form a count of rows into bins took "
        "(ops.partition.bin_counts: the shuffle's bucket counts, the range "
        "partitioner's histogram), bumped where a kernel is TRACED, not "
        "where it runs: .dense a compare and a sum (rows= the bins, up to "
        "DENSE_BINS_MAX), .scatter an int32 scatter-add past it"),
    "gather.f64.": (
        "counter", "how the float64 columns of a packed row gather "
        "(ops.gather.pack_gather: the join emits, take, filter, the row "
        "subsets) were carried, bumped where a kernel is TRACED, not where "
        "it runs: .packed as two 32-bit lanes of the gather's matrix (rows= "
        "the float64 columns; the source has at most F64_PACK_RATIO rows an "
        "index row), .alone each by a gather of its own where the gather is "
        "more selective than that"),
    "shuffle.overlap_efficiency": (
        "gauge", "fraction of the measured exchange device window "
        "(dispatch-open to the deferred round-count fetch return) spent "
        "issuing overlapped work — host assembly after the fetch is "
        "excluded (ISSUE 15's measured overlap ledger)"),
    "prof.": (
        "mixed", "critical-path profiler (obs/prof.py, CYLON_TPU_PROF): "
        "stage_ms.<stage> gauges (per-stage device stage clocks: the "
        "measured window apportioned over per-shard work units fetched "
        "by the existing count phase — zero added syncs) + "
        "straggler_ratio[.<stage>] gauges (max/mean per-shard stage "
        "time; the skew_trigger re-coster's evidence) + the degraded "
        "counter (a profiler failure flips profiling off, never a "
        "query)"),
    "shuffle.exchanged_bytes": (
        "counter", "global collective payload bytes per shuffle (rows="
        "K x world^2 x cap x effective row bytes)"),
    "shuffle.skew_split": (
        "counter", "skew-adaptive schedules applied (rows=heavy-bucket "
        "tail rows relayed through the host instead of padded rounds)"),
    "shuffle.spill.": (
        "mixed", "spill tiers (parallel/spill.py): tier/peak_device_bytes/"
        "host_bytes/disk_bytes gauges; shuffles/staged_rounds/"
        "staged_bytes/relay_bytes/tier2_promotions/ooc_joins counters; "
        "stage/ooc_* spans; I/O degradation ladder (ISSUE 14): "
        "io_retries / tier_degraded (disk arenas re-planned onto host "
        "RAM) / io_failures (ladder exhausted -> typed SpillIOError) / "
        "reaped_dirs (dead-pid spill dirs reclaimed at context init)"),
    "shuffle.semi_filter.": (
        "mixed", "semi-join gate: selectivity gauge, applied/gate_skipped/"
        "pruned_rows counters, sketch span"),
    "shuffle.quant.": (
        "mixed", "lossy wire tier (ops/quant.py): applied/gate_skipped/"
        "cols/bytes_saved counters + row_bytes_ratio gauge on the "
        "shuffle wire; spill_bytes_saved/spill_reencoded/"
        "relay_bytes_saved counters on the host crossings"),
    "semi_filter.sketch_bytes": ("counter", "sketch collective wire bytes"),
    "lane_pack.": (
        "mixed", "bit-width packing: stats_kernel/sort_fused/join_fused/"
        "groupby_fused counters, wire.* gate counters + ratio gauge"),
    "ordering.": (
        "counter", "order-property consumers: sort_elided/dist_sort_elided/"
        "sort_suffix/join_presorted_probe/join_key_order_emit/"
        "setop_sorted_probe/unique_run_detect/groupby_run_detect"),
    "plan.optimize": ("span", "rule rewriting"),
    "plan.lower": ("span", "detach + executor build"),
    "plan.execute": ("span", "lowered plan execution"),
    "plan.node.": ("span", "per-plan-node execution (node_id attr)"),
    "plan.rule.": ("counter", "one bump per optimizer rule firing"),
    "plan.rule.semi_as_mask": (
        "counter", "a semi or anti join directly under a dense group-by or "
        "a keyless aggregate handed over its hit mask uncompacted"),
    "plan.cache.": ("counter", "plan-fingerprint executable cache hit/miss"),
    "plan.fingerprint.hash": (
        "counter", "fingerprint_key hashes performed (hoisted onto the "
        "cached executor entry: flat across cached collects)"),
    "serve.": (
        "mixed", "query serving (cylon_tpu/serve): queue_depth / "
        "inflight_bytes / leases / batch_occupancy gauges; submitted / "
        "completed / backpressure.wait / budget_overflow / batches / "
        "singles counters; batch_cache.hit/miss; serve.stack span; "
        "degradation counters (ISSUE 14): batch_fallback (stacked-batch "
        "failure fell back to per-binding singles), batch_quarantined "
        "(group formed as a single under the poisoned-shape cooldown), "
        "worker_died / worker_respawn (supervision), close_orphans "
        "(queries failed typed by close())"),
    "serve.errors": (
        "counter", "typed query failures (one per future failed with a "
        "CylonError; split by scope under serve.errors.<scope>) — the "
        "error-rate SLO rule's substrate"),
    "serve.errors.": ("counter", "serve.errors split by failure scope"),
    "serve.shed.": (
        "counter", "admission sheds split by reason: admission_budget "
        "(a single estimate exceeds the in-flight budget — load), "
        "queue_depth (full queue / worker-less nowait — load), "
        "unconsumed_cap (held results past the 2x hard cap — a consumer "
        "leak); the SLO rules and an autoscaler read the split to tell "
        "load from leak"),
    "query.": ("mixed", "query-level rollup: query.traces recorded"),
    "autotune.": (
        "counter", "feedback re-coster applications (plan/feedback.py): "
        "semi_forced / semi_skipped / tier_promoted / footprint_admit "
        "(admission leased the tuned observed footprint instead of the "
        "static input-bytes estimate)"),
    "ledger.": (
        "gauge", "resource ledger (obs/resource.py): device_bytes / "
        "live_tables gauges (max_s = process peak watermark); the full "
        "watermark set — host/disk/lease/leaks — is exposed by the "
        "/metrics ledger section, which reads snapshot() directly"),
    "slo.": (
        "mixed", "SLO monitor (obs/slo.py): state.<rule> gauges "
        "(0=OK 1=WARN 2=BREACH) + transitions counter (each transition "
        "also lands a kind='slo' record in the flight ring)"),
    "obs.": (
        "counter", "obs-layer internals: hist.evicted (bounded histogram "
        "registry LRU evictions, rows=entries flushed); "
        "journal_degraded (a journal write failed — the store flipped "
        "to in-memory-only telemetry; queries unaffected)"),
    "fault.injected.": (
        "counter", "fault injections delivered per seam "
        "(cylon_tpu/fault/inject.py; armed via CYLON_TPU_FAULTS — zero "
        "in production)"),
    "stream.": (
        "mixed", "streaming ingest + incremental views (cylon_tpu/"
        "stream): append counter (rows=batch rows) with append.chunks / "
        "append.rejected / append.rollback; state_bytes gauge (per-"
        "append high-water of the host state arenas); refresh counter + "
        "refresh.{noop,full,fallback,inc} mode split and refresh."
        "delta_rows (rows=delta size) — the refresh-vs-recompute "
        "crossover evidence beside the journaled latencies; subs / "
        "subs.stale / subs.refresh.* subscription counters; "
        "stream.refresh latency histogram via observe_latency"),
    "overhead.": ("span", "trace_smoke calibration probes (tools only)"),
}


def is_declared(name: str) -> bool:
    """Is a metric name covered by the stable-name table?"""
    if name in STABLE_METRICS:
        return True
    return any(
        name.startswith(p) for p in STABLE_METRICS if p.endswith(".")
    )
