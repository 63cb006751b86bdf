"""The host's part of a query, as the program records it (``obs.trace``):
one always-on record a public call, built from every program call
(``engine.TimedProgram``) and every fetch (``table._fetch(arr, site)``).

The eight cells' own queries (``chipbench``'s generators and query modules
at 4,096 rows on the CPU mesh) each leave one record a call whose fetches
are the ``host_sync`` census of that call, by sites of the vocabulary; the
deferred count fetch lands in the preceding record's tail; a nested public
call opens no second record; a stalled fetch is the slowest record, named
by its site, and a bucket of that site's histogram; every ``_fetch(`` of
the package passes a literal site; and the rollup's census equals the
contracts' (``analysis/contracts.py``)."""
import ast
import os
import sys
import time

import jax
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import engine, obs
from cylon_tpu import table as table_mod
from cylon_tpu.analysis import contracts
from cylon_tpu.analysis.hostsync import sync_monitor
from cylon_tpu.obs import export as obs_export
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.obs import stages
from cylon_tpu.obs import trace as obs_trace
from cylon_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

ROWS = 4096
#: ``host_syncs`` a query, as the benchmark has read them since PR 33
CELLS = {
    "join-w1": 1, "sort-w1": 0, "join-w4": 5, "tpch-q1-w1": 1,
    "groupby-w1": 1, "tpch-q3-w1": 4, "join-skew-w4": 5, "sort-w4": 2,
}
RECORD_KEYS = {
    "rid", "name", "thread", "start_ns", "end_ns", "tail_ns", "n_dispatch",
    "dispatch_ns", "n_fetch", "wait_ns", "exposed_ns", "plain_ns", "sites",
    "max_wait",
}


@pytest.fixture(scope="module")
def contexts(devices):
    return {
        chips: ct.CylonContext.init_distributed(
            ct.TPUConfig(devices=devices[:chips])
        )
        for chips in (1, 4)
    }


def _query(contexts, name, seed=2**31 + 35):
    cell = harness.Cell(name)
    data = cell.generator.make(cell.config, seed, ROWS)
    tables = harness.load_tables(contexts[cell.chips], data)
    return cell.query.build(tables, cell.traffic["params"])


def _new_records(since_rid):
    return [r for r in obs.last_ops() if r["rid"] > since_rid]


def _last_rid():
    recs = obs.last_ops(1)
    return recs[-1]["rid"] if recs else 0


def _table(ctx, rng, n=2000):
    return ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, 64, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
    })


# -- (a) one record a call, its fetches the census, its sites named -------
@pytest.mark.parametrize("name", list(CELLS))
def test_a_cells_query_leaves_one_record(contexts, name):
    call = _query(contexts, name)
    for _ in range(2):
        harness.ready(call())
    rid, syncs = _last_rid(), tracing.get_count("host_sync")
    waits = {
        k: v["count"] for k, v in tracing.report("host_sync.").items()
    }
    harness.ready(call())
    syncs = tracing.get_count("host_sync") - syncs
    (rec,) = _new_records(rid)
    assert set(rec) == RECORD_KEYS
    assert rec["n_fetch"] == syncs == CELLS[name]
    assert set(rec["sites"]) <= set(stages.FETCH_SITES) | {obs_trace.OP_START}
    assert sum(s["n_fetch"] for s in rec["sites"].values()) == rec["n_fetch"]
    assert sum(s["wait_ns"] for s in rec["sites"].values()) == rec["wait_ns"]
    assert (
        sum(s["exposed_ns"] for s in rec["sites"].values())
        == rec["exposed_ns"]
    )
    # the rollup's spans are the same fetches, by the same names
    after = tracing.report("host_sync.")
    for site, s in rec["sites"].items():
        if s["n_fetch"]:
            assert stages.FETCH_SITES[site]  # a count sync, every one
            assert (
                after["host_sync." + site]["count"]
                - waits.get("host_sync." + site, 0)
            ) == s["n_fetch"]
    # the call and its tail hold its waits and its program calls
    wall = rec["end_ns"] - rec["start_ns"] + rec["tail_ns"]
    assert rec["n_dispatch"] >= 1 and rec["dispatch_ns"] > 0
    assert wall >= rec["wait_ns"] + rec["dispatch_ns"]
    assert rec["plain_ns"] == wall - rec["wait_ns"] - rec["dispatch_ns"]
    # exposed: at least the start of the call to its first program's
    # return, and no more than the call, its tail and the caller's loop
    assert rec["sites"][obs_trace.OP_START]["exposed_ns"] > 0
    assert rec["exposed_ns"] <= wall - rec["wait_ns"] + 50_000_000
    if rec["n_fetch"]:
        assert rec["max_wait"]["site"] in rec["sites"]
        assert 0 < rec["max_wait"]["ns"] <= rec["wait_ns"]
        assert "dispatch." + rec["max_wait"]["after"] in tracing.report(
            "dispatch."
        )


# -- (b) the deferred count fetch is the preceding record's tail ----------
def test_deferred_count_fetch_lands_in_the_tail(contexts):
    call = _query(contexts, "groupby-w1")
    harness.ready(call())
    rid = _last_rid()
    out = call()
    (rec,) = _new_records(rid)
    assert rec["n_fetch"] == 0 and rec["tail_ns"] == 0 and rec["end_ns"]
    out.row_count  # the caller asks: Table._materialize_counts fetches
    (again,) = _new_records(rid)
    assert again["rid"] == rec["rid"] and again["end_ns"] == rec["end_ns"]
    assert again["sites"]["table.counts"]["n_fetch"] == again["n_fetch"] == 1
    assert again["tail_ns"] == again["wait_ns"] > 0
    assert again["max_wait"]["after"] == "groupby"
    # the tail's exposed interval is cut where the next record opens
    time.sleep(0.02)
    open_gap = _new_records(rid)[0]["sites"]["table.counts"]["exposed_ns"]
    harness.ready(call())
    first, second = _new_records(rid)
    cut = first["sites"]["table.counts"]["exposed_ns"]
    assert cut - open_gap >= 15_000_000
    harness.ready(call())
    assert _new_records(rid)[0]["exposed_ns"] == first["exposed_ns"]
    assert second["start_ns"] >= first["end_ns"]


# -- (c) nesting ----------------------------------------------------------
def test_a_nested_public_call_opens_no_second_record(contexts, rng):
    ctx = contexts[4]
    a, b = _table(ctx, rng), _table(ctx, rng)
    for _ in range(2):
        a.distributed_join(b, on="k").row_count
    rid = _last_rid()
    # distributed_join calls Table.join, itself a public call
    a.distributed_join(b, on="k").row_count
    (rec,) = _new_records(rid)
    assert rec["name"] == "distributed_join"
    assert "join.speculative" in rec["sites"]
    # a lazy plan: collect -> dispatch -> Table.filter / groupby / sort
    plan = a.lazy().filter(ct.col("v") > 0.0).groupby("k", {"v": "sum"})
    plan.collect()
    rid = _last_rid()
    plan.collect()
    (rec,) = _new_records(rid)
    assert rec["name"] == "collect" and rec["n_dispatch"] >= 1
    # and each direct call is a record of its own
    rid = _last_rid()
    a.sort("v")
    a.filter(a.column("v").data > 0)
    a.topk("v", 3)
    assert [r["name"] for r in _new_records(rid)] == ["sort", "filter", "topk"]


def test_records_of_two_threads_do_not_mix(contexts, rng):
    import threading

    ctx = contexts[1]
    t = _table(ctx, rng)
    t.sort("v")
    rid = _last_rid()
    seen = {}

    def work(tag):
        t.sort("v")
        seen[tag] = threading.get_ident()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    recs = _new_records(rid)
    assert len(recs) == 3 and all(r["n_dispatch"] == 1 for r in recs)
    assert {r["thread"] for r in recs} == set(seen.values())


# -- (d) a stall is the slowest record, by site, and a bucket -------------
def test_a_stalled_fetch_is_the_slowest_record(contexts, rng, monkeypatch):
    ctx = contexts[1]
    a, b = _table(ctx, rng), _table(ctx, rng)
    for _ in range(2):
        a.join(b, on="k").row_count
    obs_export.reset_ops()
    obs_metrics.reset_waits()
    stalls = []

    class Stalled(obs_trace.fetch_wait):
        """The fourth join's fetch of its totals waits 50 ms longer."""

        def __enter__(self):
            entered = super().__enter__()
            if self.site == "join.speculative":
                stalls.append(self)
                if len(stalls) == 4:
                    time.sleep(0.05)
            return entered

    monkeypatch.setattr(obs_trace, "fetch_wait", Stalled)
    for _ in range(6):
        a.join(b, on="k").row_count
    assert len(stalls) == 6
    slowest = obs.slowest_ops()
    assert 1 <= len(slowest) <= obs_export.SLOWEST_KEPT
    walls = [r["end_ns"] - r["start_ns"] + r["tail_ns"] for r in slowest]
    assert walls == sorted(walls, reverse=True)
    top = slowest[0]
    assert top["name"] == "join"
    assert top["max_wait"]["site"] == "join.speculative"
    assert top["max_wait"]["after"] == "join_spec"
    assert 50e6 <= top["max_wait"]["ns"] < 500e6
    assert top["rid"] == obs.last_ops()[3]["rid"]
    # the histogram of that site holds it as a bucket of its own
    waits = obs_metrics.wait_report()["join.speculative"]
    assert waits["count"] == 6
    bucket = obs_metrics.bucket_of(top["max_wait"]["ns"] * 1e-9)
    assert waits["buckets"][bucket] == 1
    assert max(waits["buckets"]) == bucket
    assert waits["max_s"] == pytest.approx(top["max_wait"]["ns"] * 1e-9)
    assert tracing.report("host_sync.")["host_sync.join.speculative"][
        "max_s"
    ] >= 0.05
    # and the ops endpoint's documents show both
    text = obs.prometheus_text()
    assert obs.validate_prometheus(text) == []
    assert 'cylon_tpu_host_sync_wait_seconds{site="join.speculative",' in text
    assert "cylon_tpu_dispatch_join_spec_seconds_total" in text
    assert obs_export.slowest_json()[0]["host"]["rid"] == top["rid"]
    listed = obs_export.queries_json()
    assert [q["host"]["rid"] for q in listed if q["kind"] == "op"][-6:] == [
        r["rid"] for r in obs.last_ops(6)
    ]


def test_the_ring_keeps_the_last_records_and_the_slowest(contexts, rng):
    t = _table(contexts[1], rng)
    t.sort("v")
    obs_export.reset_ops()
    for _ in range(obs_export.OPS_KEPT + 5):
        t.sort("v")
    recs = obs.last_ops()
    assert len(recs) == obs_export.OPS_KEPT
    assert [r["rid"] for r in recs] == sorted(r["rid"] for r in recs)
    assert obs.last_ops(3) == recs[-3:] and obs.last_ops(0) == []
    assert len(obs.slowest_ops()) == obs_export.SLOWEST_KEPT


def test_a_traced_query_carries_the_same_record(contexts, rng, monkeypatch):
    monkeypatch.setenv("CYLON_TPU_TRACE", "tree")
    t = _table(contexts[1], rng)
    obs_export.reset_ring()
    t.sort("v")
    (q,) = [q for q in obs.traces() if q.name == "sort"]
    assert q.op is not None and q.op.name == "sort"
    assert q.op.as_dict() == obs.last_ops(1)[0]
    (entry,) = [e for e in obs_export.queries_json() if e["qid"] == q.qid]
    assert entry["host"]["rid"] == q.op.rid
    # listed once: under its trace, not again as a bare record
    assert q.op.rid not in [
        e["qid"] for e in obs_export.queries_json() if e["kind"] == "op"
    ]


# -- (e) the timed callable ------------------------------------------------
def test_every_cached_program_is_timed(contexts):
    ctx = contexts[1]
    key = ("unit_host_timeline",)

    def build():
        def kern(dp, rep):
            return dp * 2

        return kern

    x = jax.numpy.arange(8, dtype=jax.numpy.int32)
    first = engine.get_kernel(ctx, key, build)
    np.testing.assert_array_equal(np.asarray(first(x, ())), np.arange(8) * 2)
    cached = engine.get_kernel(ctx, key, build)
    assert isinstance(cached, engine.TimedProgram)
    assert cached is engine.get_kernel(ctx, key, build)  # no closure a call
    assert cached.__name__ == "unit_host_timeline"
    before = tracing.report("dispatch.")["dispatch.unit_host_timeline"]
    assert before["count"] == 1
    t0 = time.perf_counter()
    cached(x, ())
    held = time.perf_counter() - t0
    after = tracing.report("dispatch.")["dispatch.unit_host_timeline"]
    assert after["count"] == 2
    assert 0 < after["total_s"] - before["total_s"] <= held
    assert obs_trace.last_dispatch_s() == pytest.approx(
        after["total_s"] - before["total_s"]
    )
    assert obs_metrics.is_declared("dispatch.unit_host_timeline")
    assert obs_metrics.is_declared("host_sync.table.counts")
    # the compiled program is still to be had from the cache entry
    assert "unit_host_timeline" in cached.lower(x, ()).as_text()


# -- (f) every fetch of the package names its site ------------------------
def _package_files():
    pkg = os.path.join(ROOT, "cylon_tpu")
    for folder, _dirs, files in os.walk(pkg):
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(folder, name)


def test_every_fetch_passes_a_literal_site_of_the_vocabulary():
    sites, bumps = [], []
    for path in _package_files():
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            called = getattr(fn, "id", None) or getattr(fn, "attr", None)
            where = f"{os.path.relpath(path, ROOT)}:{node.lineno}"
            if called == "_fetch":
                assert len(node.args) == 2 and not node.keywords, where
                site = node.args[1]
                assert isinstance(site, ast.Constant), where
                assert site.value in stages.FETCH_SITES, (where, site.value)
                sites.append(site.value)
            elif called == "bump" and node.args and isinstance(
                node.args[0], ast.Constant
            ) and node.args[0].value == "host_sync":
                bumps.append(where)
    # one name a call site, every name of the vocabulary in use
    assert sorted(sites) == sorted(stages.FETCH_SITES)
    # and the census is counted in one place
    assert len(bumps) == 1 and bumps[0].startswith("cylon_tpu/table.py:")


def test_a_site_outside_the_vocabulary_is_refused():
    with pytest.raises(KeyError):
        table_mod._fetch(jax.numpy.arange(2), "nowhere")


# -- (g) the rollup's census is the contracts' ----------------------------
@pytest.mark.parametrize("contract,world", [
    ("dist_join", 4), ("q3_dispatch", 1), ("shuffle_single", 4),
])
def test_rollup_census_equals_the_contracts(contexts, rng, contract, world):
    ctx = contexts[world]
    a, b = _table(ctx, rng), _table(ctx, rng)
    if contract == "dist_join":
        def run():
            return a.distributed_join(b, on="k")
    elif contract == "shuffle_single":
        def run():
            return a.shuffle(["k"])
    else:
        plan = (
            a.lazy().join(b.lazy(), on="k").groupby("k_x", {"v_x": "sum"})
        )

        def run():
            return plan.dispatch()._materialize()

    for _ in range(2):
        run()
    before = tracing.get_count("host_sync")
    with sync_monitor() as events:
        run()
    counted = tracing.get_count("host_sync") - before
    want = contracts.CONTRACTS[contract].host_syncs
    assert counted == len(events) == (want(1) if callable(want) else want)
