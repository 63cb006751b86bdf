"""Query-scoped telemetry (ISSUE 8): cylon_tpu/obs/.

Covers the tentpole surface end to end:

- span-TREE shape of a traced q3 collect (plan.node spans nested under
  plan.execute, node ids, per-query counters, device-resolved end);
- ``explain(analyze=True)`` golden assertions (per-node ms / rows /
  coll MB / gate decisions on the fused q3 shape);
- Chrome trace-event export: schema-validates and round-trips;
- DISABLED tracer allocates nothing (no Span / QueryTrace objects) and
  leaves the flight ring untouched;
- flight-recorder ring eviction under CYLON_TPU_TRACE_RING;
- fingerprint latency histograms (quantile math + the always-on
  dispatch observation path);
- every metric a q3 run emits is covered by the documented stable-name
  table (obs.metrics.STABLE_METRICS);
- two concurrent traced queries build DISJOINT trees while the
  process-global rollup keeps the cross-query sum;
- ``utils/tracing.profile()`` smoke (the jax.profiler passthrough).
"""
import gc
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import col
from cylon_tpu.obs import export as obs_export
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.obs import trace as obs_trace
from cylon_tpu.utils import tracing


def _q3(ctx, rng, n=3000, salt=0.0):
    ta = ct.Table.from_pydict(
        ctx,
        {"k": rng.integers(0, 40, n).astype(np.int32),
         "v": rng.normal(size=n).astype(np.float32)},
    )
    tb = ct.Table.from_pydict(
        ctx,
        {"rk": rng.integers(0, 40, n).astype(np.int32),
         "w": rng.normal(size=n).astype(np.float32)},
    )
    return (
        ta.lazy()
        .join(tb.lazy(), left_on="k", right_on="rk")
        .filter(col("w") > salt)
        .groupby("k", {"v": "sum"})
    )


@pytest.fixture
def traced(monkeypatch):
    """Structured tracing on (no stderr log), fresh ring."""
    monkeypatch.setenv("CYLON_TPU_TRACE", "tree")
    obs_export.reset_ring()
    yield
    obs_export.reset_ring()


# ----------------------------------------------------------------------
# span trees
# ----------------------------------------------------------------------
def test_q3_span_tree_shape(ctx8, rng, traced):
    lf = _q3(ctx8, rng)
    lf.collect()  # compile outside the assertion run
    obs_export.reset_ring()
    lf.collect()
    qs = [q for q in obs_export.traces() if q.kind == "plan"]
    assert len(qs) == 1
    q = qs[0]
    roots = [sp.name for sp in q.spans]
    assert roots == ["plan.optimize", "plan.lower", "plan.execute"]
    execute = q.spans[-1]
    # per-node spans nest under plan.execute, parent/child links intact:
    # the fused q3 node is the root, its Filter input nested below it
    names = [sp.name for sp in execute.walk()]
    assert "plan.node.FusedJoinGroupBySum" in names
    assert "plan.node.Filter" in names
    fused = next(
        sp for sp in execute.walk()
        if sp.name == "plan.node.FusedJoinGroupBySum"
    )
    assert any(
        c.name == "plan.node.Filter" for c in fused.walk()
    ), "input node must be a descendant of its consumer's span"
    assert isinstance(fused.attrs.get("node_id"), int)
    # per-query counters: the cache hit of this collect is attributed to
    # THIS query, not just the global blob
    assert q.counters["plan.cache.hit"][0] == 1
    # the span carries collective accounting from the pair shuffle
    assert fused.attrs.get("coll_bytes", 0) > 0
    assert q.hist_key, "dispatch must label the trace with the fingerprint"
    # device-resolved end time rode the deferred count fetch
    assert q.device_resolved_s() is not None
    assert q.resolved >= q.t0


def test_eager_chain_implicit_trace(ctx8, rng, traced):
    t = ct.Table.from_pydict(
        ctx8, {"k": rng.integers(0, 10, 512).astype(np.int32)}
    )
    obs_export.reset_ring()
    t.shuffle(["k"])
    ops = [q for q in obs_export.traces() if q.kind == "op"]
    assert ops, "an outermost eager span must open an implicit trace"
    names = {sp.name for q in ops for sp in q.all_spans()}
    assert "shuffle.exchange" in names


# ----------------------------------------------------------------------
# explain(analyze=True)
# ----------------------------------------------------------------------
def test_explain_analyze_golden_q3(ctx8, rng):
    lf = _q3(ctx8, rng, salt=0.111)
    text = lf.explain(analyze=True)
    assert "== Analyzed plan (executed) ==" in text
    # the fused node line carries measured time, rows in->out and coll MB
    fused_line = next(
        ln for ln in text.splitlines() if "FusedJoinGroupBySum" in ln
    )
    assert " ms (self " in fused_line
    assert "rows=" in fused_line and "->" in fused_line
    assert "coll=" in fused_line and "MB" in fused_line
    # gate decisions are printed per node; the plan-cache decision rides
    # the summary line (it fires before the trace opens)
    assert "gates[" in text
    assert "plan-cache hit" in text or "plan-cache miss" in text
    # scan rows are exact (analyze materializes every node)
    scan_line = next(
        ln for ln in text.splitlines()
        if "Scan [k, v]" in ln and "**" in ln
    )
    assert "rows=3000" in scan_line
    assert "Plan fingerprint: " in text
    assert "Rewrites fired: " in text
    # the default path is unchanged (no measurements, both plans shown)
    plain = lf.explain()
    assert "== Optimized plan ==" in plain and "**" not in plain
    # the analyzed run is diagnostic: its (per-node-synced, possibly
    # compile-laden) wall must NOT land in the fingerprint histogram
    # that serving p50/p99 reads
    obs_metrics.reset_latency()
    lf.explain(analyze=True)
    assert obs_metrics.latency_report() == {}


def test_explain_analyze_keeps_dispatch_sync_contract(devices, rng):
    """The analyzed run is diagnostic; the PRODUCTION dispatch path must
    still perform zero syncs at dispatch + one at materialization — the
    q3_dispatch contract shape: a 1-device mesh (serving: many
    concurrent single-replica queries), where the fused plan has no
    shuffle and the whole chain defers its count fetch."""
    from cylon_tpu.analysis.hostsync import sync_monitor

    ctx1 = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:1])
    )
    lf = _q3(ctx1, rng, salt=0.222)
    lf.explain(analyze=True)  # warm + analyzed (per-node syncs allowed)
    lf.collect()
    with sync_monitor() as events:
        t = lf.dispatch()
    assert events == [], [e.site for e in events]
    with sync_monitor() as events:
        t._materialize()
    assert [e.site for e in events] == ["_materialize_counts"]


# ----------------------------------------------------------------------
# Chrome export
# ----------------------------------------------------------------------
def test_chrome_export_schema_and_roundtrip(ctx8, rng, traced, tmp_path):
    lf = _q3(ctx8, rng)
    lf.collect()
    obs_export.reset_ring()
    lf.collect()
    lf.collect()
    qs = obs_export.traces()
    n_spans = sum(len(list(q.all_spans())) for q in qs)
    path = tmp_path / "trace.json"
    n_events = obs_export.write_chrome(str(path))
    doc = obs_export.load_chrome(str(path))
    assert obs_export.validate_chrome(doc) == []
    # per query: one thread_name metadata + one query event + its spans
    assert n_events == len(doc["traceEvents"]) == n_spans + 2 * len(qs)
    tracks = obs_export.summarize(doc)
    plan_tracks = [t for t in tracks.values() if t["name"].startswith("plan:")]
    assert len(plan_tracks) == 2
    for t in plan_tracks:
        assert t["spans"] > 0 and t["query_ms"] > 0
        assert t["args"].get("fingerprint")
    # raw-JSON round trip: what we wrote is what a Perfetto load parses
    assert json.loads(path.read_text())["displayTimeUnit"] == "ms"


# ----------------------------------------------------------------------
# disabled-tracer pins
# ----------------------------------------------------------------------
def test_disabled_tracer_allocates_nothing(ctx8, rng, monkeypatch):
    monkeypatch.delenv("CYLON_TPU_TRACE", raising=False)
    lf = _q3(ctx8, rng, salt=0.333)
    lf.collect()  # warm
    obs_export.reset_ring()
    gc.collect()
    before = sum(
        isinstance(o, (obs_trace.Span, obs_trace.QueryTrace))
        for o in gc.get_objects()
    )
    lf.collect()
    gc.collect()
    after = sum(
        isinstance(o, (obs_trace.Span, obs_trace.QueryTrace))
        for o in gc.get_objects()
    )
    assert after == before, "disabled tracer must allocate no trace objects"
    assert obs_export.traces() == []
    assert obs_trace.current() is None


def test_disabled_span_still_feeds_rollup(local_ctx):
    tracing.reset_trace()
    with tracing.span("unit.disabled", rows=7):
        pass
    rep = tracing.get_trace_report()
    assert rep["unit.disabled"]["count"] == 1
    assert rep["unit.disabled"]["rows"] == 7


# ----------------------------------------------------------------------
# flight ring
# ----------------------------------------------------------------------
def test_ring_eviction(ctx8, rng, traced, monkeypatch):
    monkeypatch.setenv("CYLON_TPU_TRACE_RING", "4")
    lf = _q3(ctx8, rng)
    lf.collect()
    obs_export.reset_ring()
    for _ in range(6):
        lf.collect()
    qs = obs_export.traces()
    assert len(qs) == 4, "ring must hold exactly CYLON_TPU_TRACE_RING traces"
    qids = [q.qid for q in qs]
    assert qids == sorted(qids), "oldest-first order"
    # the evicted traces are the two oldest (strictly increasing qids)
    assert qids[0] > 0 and len(set(qids)) == 4


# ----------------------------------------------------------------------
# latency histograms (the serving substrate)
# ----------------------------------------------------------------------
def test_histogram_quantiles_unit():
    h = obs_metrics.Histogram()
    for ms in range(1, 101):  # 1..100 ms uniform
        h.record(ms / 1e3)
    assert h.n == 100
    # geometric buckets: ~10% relative resolution at any quantile
    assert h.quantile(0.50) == pytest.approx(0.050, rel=0.15)
    assert h.quantile(0.99) == pytest.approx(0.099, rel=0.15)
    assert h.quantile(1.0) == pytest.approx(h.max_s)
    assert obs_metrics.Histogram().quantile(0.5) == 0.0


def test_dispatch_observes_fingerprint_histogram(ctx8, rng, monkeypatch):
    """Latency histograms fill WITHOUT tracing enabled: the serving
    metrics path is always on, and the end time rides the deferred
    materialization (no extra sync)."""
    monkeypatch.delenv("CYLON_TPU_TRACE", raising=False)
    obs_metrics.reset_latency()
    lf = _q3(ctx8, rng, salt=0.444)
    for _ in range(3):
        lf.collect()
    rep = obs_metrics.latency_report()
    [(key, ent)] = [
        (k, v) for k, v in rep.items() if "FusedJoinGroupBySum" in v["label"]
    ]
    assert ent["count"] == 3
    assert 0 < ent["p50_s"] <= ent["p95_s"] <= ent["p99_s"]
    assert obs_metrics.latency_quantiles(key)["count"] == 3
    assert obs_metrics.latency_quantiles("no-such-key") is None


# ----------------------------------------------------------------------
# stable metric names
# ----------------------------------------------------------------------
def test_replicate_route_metrics_all_declared(ctx8, rng):
    """What a distributed join on the replicate route emits, eager and
    through the planner, is declared by its own name (not by the
    ``join.`` family alone) and reads as the route's docstring says."""
    import cylon_tpu as ct

    n = 4096
    big = ct.Table.from_pydict(ctx8, {
        "k": rng.integers(0, 6, n).astype(np.int32), "v": rng.random(n),
    })
    small = ct.Table.from_pydict(ctx8, {
        "k": np.arange(4, dtype=np.int32), "w": rng.random(4),
    })
    tracing.reset_trace()
    big.distributed_join(small, on="k", how="left")
    big.lazy().join(small.lazy(), on="k", how="left").collect()
    rep = tracing.get_trace_report()
    for name in (
        "join.route.replicate", "join.replicate", "join.replicate.rows",
    ):
        assert name in obs_metrics.STABLE_METRICS, name
        assert rep[name]["count"] == 2, name
    assert "join.route.shuffle" in obs_metrics.STABLE_METRICS
    assert "join.route.shuffle" not in rep
    assert rep["join.replicate.rows"]["rows"] == 2 * 4 * 7
    assert rep["join.route.replicate"]["rows"] == 2 * (n + 4)
    assert not [k for k in rep if k.startswith("shuffle.")]
    undeclared = [k for k in rep if not obs_metrics.is_declared(k)]
    assert undeclared == [], undeclared


def test_q3_metrics_all_declared(ctx8, rng):
    """Everything a q3 run (and a shuffle) emits into the rollup is
    covered by the documented stable-name table."""
    tracing.reset_trace()
    lf = _q3(ctx8, rng, salt=0.555)
    lf.collect()
    lf.collect()
    undeclared = [
        name for name in tracing.get_trace_report()
        if not obs_metrics.is_declared(name)
    ]
    assert undeclared == [], undeclared


# ----------------------------------------------------------------------
# concurrent isolation (the 8-thread acceptance twin lives in
# tests/test_concurrent_dispatch.py)
# ----------------------------------------------------------------------
@pytest.mark.skipif(
    (os.cpu_count() or 1) < 2,
    reason="two in-flight 8-device collective programs deadlock XLA:CPU's "
           "device-count-sized dispatch pool on a single-core host (the "
           "cross-run rendezvous strand documented in "
           "tests/test_concurrent_dispatch.py) — the hammer twin there "
           "carries the same guard",
)
def test_two_threads_two_disjoint_trees(ctx8, rng, traced):
    lf = _q3(ctx8, rng)
    lf.collect()  # warm: the hammer exercises the lock-free hit path
    obs_export.reset_ring()
    tracing.reset_trace()
    barrier = threading.Barrier(2)

    def worker(_):
        barrier.wait()
        return lf.collect().to_pydict()

    with ThreadPoolExecutor(max_workers=2) as ex:
        a, b = list(ex.map(worker, range(2)))
    assert list(a) == list(b)
    qs = [q for q in obs_export.traces() if q.kind == "plan"]
    assert len(qs) == 2, "two threads must record two disjoint traces"
    assert qs[0].thread != qs[1].thread
    s0 = set(map(id, qs[0].all_spans()))
    s1 = set(map(id, qs[1].all_spans()))
    assert not (s0 & s1), "span trees must not share nodes"
    for q in qs:
        assert any(
            sp.name == "plan.execute" for sp in q.all_spans()
        )
        assert q.counters["plan.cache.hit"][0] == 1
    # the process-global rollup is preserved as the cross-query sum
    assert tracing.get_count("plan.cache.hit") == sum(
        q.counters["plan.cache.hit"][0] for q in qs
    )


# ----------------------------------------------------------------------
# review-hardening regressions
# ----------------------------------------------------------------------
def test_plan_order_unique_ids_on_shared_subplan(ctx8, rng):
    """A reused LazyFrame shares Node objects between branches (a DAG);
    plan_order must keep the first-visit id, never collapse a revisited
    subtree onto a colliding id (which mapped one node's measured span
    onto another node's rendered line)."""
    from cylon_tpu.plan import lower as _lower

    t = ct.Table.from_pydict(
        ctx8,
        {"k": rng.integers(0, 9, 128).astype(np.int32),
         "v": rng.normal(size=128).astype(np.float32)},
    )
    base = t.lazy().filter(col("v") > 0)
    lf = base.union(base)
    ids = list(_lower.plan_order(lf._plan).values())
    assert len(ids) == len(set(ids)), f"colliding node ids: {ids}"
    text = lf.explain(analyze=True)
    assert "== Analyzed plan (executed) ==" in text


def test_pending_records_chain_on_passthrough(ctx8, rng, traced):
    """A plan whose output is a passthrough of a still-deferred table
    (bare Scan root) attaches a second pending record to the SAME table;
    the one count fetch must resolve BOTH queries' traces, not clobber
    the first."""
    t = ct.Table.from_pydict(
        ctx8, {"k": rng.integers(0, 9, 64).astype(np.int32)}
    )
    obs_export.reset_ring()
    d1 = t.lazy().filter(col("k") > 2).dispatch()  # counts deferred
    d2 = d1.lazy().dispatch()  # Scan root: passthrough of d1
    assert d2 is d1
    d1._materialize()
    qs = [q for q in obs_export.traces() if q.kind == "plan"]
    assert len(qs) == 2, [q.name for q in obs_export.traces()]
    assert all(q.device_resolved_s() is not None for q in qs)


# ----------------------------------------------------------------------
# device profiler passthrough
# ----------------------------------------------------------------------
def test_profile_smoke(local_ctx, tmp_path):
    import os

    import jax.numpy as jnp

    d = str(tmp_path / "prof")
    with tracing.profile(d):
        (jnp.arange(128) * 3).block_until_ready()
    produced = [
        os.path.join(r, f) for r, _dirs, fs in os.walk(d) for f in fs
    ]
    assert produced, "jax.profiler must have written a trace"

# ----------------------------------------------------------------------
# critical-path profiler (ISSUE 15): stage clocks, straggler ledger,
# critical-path reports, the measured overlap ledger, fault degradation
# ----------------------------------------------------------------------
@pytest.fixture
def profiled(monkeypatch, traced):
    """Profiler + structured tracing on, re-armed, fresh rollup."""
    from cylon_tpu.obs import prof as obs_prof

    monkeypatch.setenv("CYLON_TPU_PROF", "1")
    obs_prof.reset()
    tracing.reset_trace()
    yield
    obs_prof.reset()


def test_stage_clocks_uniform_vs_one_hot(ctx8, rng, profiled):
    """The straggler ledger separates a one-hot 8-way shuffle (compact /
    relay ratio = world) from a uniform one (ratio ~1); stage-clock
    annotations land on the exchange span."""
    n = 8000
    t = ct.Table.from_pydict(
        ctx8, {"k": rng.integers(0, 2000, n).astype(np.int32)}
    )
    t.shuffle(["k"])
    rep = tracing.report("prof.")
    assert rep["prof.straggler_ratio"]["last"] < 1.5
    assert "prof.stage_ms.pack" in rep
    tracing.reset_trace()
    obs_export.reset_ring()
    hot = ct.Table.from_pydict(ctx8, {"k": np.zeros(n, np.int32)})
    hot.shuffle(["k"])
    rep = tracing.report("prof.")
    assert rep["prof.straggler_ratio"]["last"] > 3.0
    # the measured clocks annotate the owning exchange span
    q = [q for q in obs_export.traces() if q.kind == "op"][-1]
    ex = next(sp for sp in q.all_spans() if sp.name == "shuffle.exchange")
    assert any(k.startswith("prof_") and k.endswith("_ms") for k in ex.attrs)
    assert ex.attrs["prof_straggler"] > 3.0


def test_disabled_profiler_records_nothing(ctx8, rng, traced, monkeypatch):
    monkeypatch.delenv("CYLON_TPU_PROF", raising=False)
    tracing.reset_trace()
    t = ct.Table.from_pydict(
        ctx8, {"k": rng.integers(0, 50, 512).astype(np.int32)}
    )
    t.shuffle(["k"])
    assert not tracing.report("prof.")
    q = [q for q in obs_export.traces() if q.kind == "op"][-1]
    from cylon_tpu.obs import prof as obs_prof

    assert obs_prof.PROF_ATTR not in q.attrs


def test_overlap_gauge_excludes_host_assembly(ctx8, rng, monkeypatch):
    """The measured overlap ledger: the gauge's denominator ends at the
    deferred round-count fetch return, so host-side assembly AFTER the
    fetch (here: an injected delay in the post-fetch ordering stamp)
    cannot drag the efficiency toward zero — the exact bug of the old
    host-wall proxy, which divided by the full assembly wall."""
    import time as _t

    from cylon_tpu.parallel import shuffle as psh

    t = ct.Table.from_pydict(
        ctx8, {"k": rng.integers(0, 40, 1024).astype(np.int32)}
    )
    t.shuffle(["k"])  # warm the kernels: dispatch wall ~ device wall
    real = psh.ordering_after_shuffle
    delay = 0.6

    def slow(kind):
        _t.sleep(delay)  # post-fetch host assembly work
        return real(kind)

    monkeypatch.setattr(psh, "ordering_after_shuffle", slow)
    import cylon_tpu.table as table_mod

    monkeypatch.setattr(table_mod._sh, "ordering_after_shuffle", slow)
    tracing.reset_trace()
    t0 = time.perf_counter()
    t.shuffle(["k"])
    wall = time.perf_counter() - t0
    assert wall >= delay  # the delay really ran inside the shuffle
    eff = tracing.report("shuffle.")["shuffle.overlap_efficiency"]["last"]
    # warm tiny shuffle: issuing overlaps nearly the whole device window.
    # Under the old proxy the injected second lands in the denominator
    # and eff collapses under wall_disp / (wall_disp + 1 s) ~= 0.05.
    assert eff > 0.25, eff
    assert 0.0 <= eff <= 1.0


def test_fused_stage_clocks_resolve_deferred(ctx8, rng, profiled):
    """A fused q3 dispatch attaches window-PENDING stage clocks that
    resolve when the deferred count fetch stamps the query end — and the
    Chrome export then carries per-shard prof.* stage tracks."""
    lf = _q3(ctx8, rng)
    lf.collect()  # compile
    obs_export.reset_ring()
    lf.collect()
    qs = [q for q in obs_export.traces() if q.kind == "plan"]
    assert len(qs) == 1
    from cylon_tpu.obs import prof as obs_prof

    profs = qs[0].attrs.get(obs_prof.PROF_ATTR)
    assert profs, "fused dispatch must attach a stage profile"
    assert all(p.window_s is not None for p in profs), "finalize must run"
    doc = obs_export.chrome_doc()
    assert not obs_export.validate_chrome(doc)
    stage_events = [
        e for e in doc["traceEvents"]
        if e.get("ph") == "X" and str(e["name"]).startswith("prof.")
    ]
    assert len(stage_events) >= ctx8.world_size
    # prof shard tracks must NOT leak into the per-query summary
    tracks = obs_export.summarize(doc)
    assert all(isinstance(tid, int) for tid in tracks)
    # rollup gauges landed at finalize time
    assert "prof.stage_ms.pack" in tracing.report("prof.")


def test_prof_fault_seam_degrades_not_fails(ctx8, rng, monkeypatch):
    """An armed obs.prof seam degrades the profiler to OFF (counted
    prof.degraded) and the query is unaffected."""
    from cylon_tpu import fault
    from cylon_tpu.obs import prof as obs_prof

    monkeypatch.setenv("CYLON_TPU_PROF", "1")
    monkeypatch.setenv("CYLON_TPU_FAULTS", "obs.prof:p=1")
    fault.reset()
    obs_prof.reset()
    c0 = obs_metrics.get_count("prof.degraded")
    try:
        t = ct.Table.from_pydict(
            ctx8, {"k": rng.integers(0, 30, 1024).astype(np.int32)}
        )
        res = t.shuffle(["k"])
        assert res.row_count == 1024  # the query survived
        assert fault.inject.fired("obs.prof") >= 1
        assert obs_metrics.get_count("prof.degraded") == c0 + 1
        assert obs_prof.degraded()
        assert not obs_prof.profiling_active()
    finally:
        monkeypatch.delenv("CYLON_TPU_FAULTS")
        fault.reset()
        obs_prof.reset()


def test_explain_analyze_crit_column(ctx8, rng):
    """explain(analyze=True) prints a critical-path share per node, and
    the shares on the critical path sum to ~100%."""
    import re

    text = _q3(ctx8, rng, salt=0.222).explain(analyze=True)
    shares = [int(m) for m in re.findall(r"crit (\d+)%", text)]
    assert shares, text
    assert 90 <= sum(shares) <= 110  # off-path nodes print crit 0%


def test_traceview_critical_report(ctx8, rng, profiled, tmp_path, capsys):
    """traceview --critical names the bottleneck stage: a skew-side
    stage (relay/collective) on the one-hot shape, a local stage
    (pack/compact) on the uniform shape."""
    import tools.traceview as tv

    n = 8000
    out = {}
    for name, keys in (
        ("uniform", rng.integers(0, 2000, n).astype(np.int32)),
        ("one-hot", np.zeros(n, np.int32)),
    ):
        obs_export.reset_ring()
        ct.Table.from_pydict(ctx8, {"k": keys}).shuffle(["k"])
        path = str(tmp_path / f"{name}.json")
        obs_export.write_chrome(path)
        assert tv.main([path, "--critical"]) == 0
        out[name] = capsys.readouterr().out
        assert "bottleneck stage:" in out[name]
        assert "measured stage clocks" in out[name]
    assert re_bottleneck(out["one-hot"]) in ("relay", "collective")
    assert re_bottleneck(out["uniform"]) in ("pack", "compact")


def re_bottleneck(text):
    """The bottleneck stage of the MEASURED (stage-clock) track — an
    eager shuffle also records a count-phase op trace whose span-wall
    fold reports 'count'."""
    import re

    m = re.search(r"bottleneck stage: (\w+) \([^)]*measured", text)
    return m.group(1) if m else None


def test_traceview_critical_unprofiled_fallback(ctx8, rng, traced,
                                                tmp_path, capsys):
    """--critical on an UNPROFILED trace falls back to the span-wall
    fold and still reports a path + stage ranking."""
    import tools.traceview as tv

    obs_export.reset_ring()
    ct.Table.from_pydict(
        ctx8, {"k": rng.integers(0, 40, 2048).astype(np.int32)}
    ).shuffle(["k"])
    path = str(tmp_path / "plain.json")
    obs_export.write_chrome(path)
    assert tv.main([path, "--critical"]) == 0
    text = capsys.readouterr().out
    assert "bottleneck stage:" in text
    assert "span-wall fold" in text


def test_traceview_live_renders_ops_endpoint(ctx8, rng, capsys):
    """--live renders a running ops endpoint (healthz + /metrics +
    flight ring) and exits 0; an unreachable endpoint exits 1."""
    import tools.traceview as tv

    srv = obs_export.OpsServer(0)
    port = srv.start()
    try:
        assert tv.main(["--live", f"http://127.0.0.1:{port}"]) == 0
        text = capsys.readouterr().out
        assert "healthz:" in text
    finally:
        srv.stop()
    assert tv.main(["--live", "http://127.0.0.1:9"]) == 1
    assert "unreachable" in capsys.readouterr().err


def test_prof_metrics_all_declared(ctx8, rng, profiled):
    """Everything a profiled one-hot shuffle emits stays covered by the
    stable-name table."""
    tracing.reset_trace()
    ct.Table.from_pydict(ctx8, {"k": np.zeros(4096, np.int32)}).shuffle(["k"])
    undeclared = [
        name for name in tracing.get_trace_report()
        if not obs_metrics.is_declared(name)
    ]
    assert not undeclared, undeclared
