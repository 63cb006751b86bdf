"""The concurrent query-serving engine (ISSUE 9).

Four pinned properties:

- DIFFERENTIAL: a batch of B same-fingerprint bindings executed as ONE
  stacked device program must produce, per binding, exactly the rows the
  serial ``collect()`` of that binding produces — across worlds {1,4,8},
  int and dictionary-encoded string keys, nulls, and every batchable
  tail (fused q3 groupby-sum, multi-agg, sort, project, left/right
  joins). Values are integer-valued f32 so sums are order-exact and the
  comparison is EQUALITY, not tolerance.
- ADMISSION: under a tight in-flight byte budget, N threads hammering
  ``collect_async`` must backpressure (submitters wait) and still lose
  or duplicate NOTHING; the shed path raises ServeOverloadError without
  touching admitted work.
- CACHE: B bindings compile exactly one batched executor per
  (fingerprint, pow2-B-bucket) — the serve tier's compile-once pin.
- HOT-LOOP HASHING (ISSUE 9 small fix): repeated cached collects perform
  ZERO fingerprint_key hashes — the key is hoisted onto the cached
  executor entry (``engine.PlanEntry.hist_key``).
"""
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import col
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.serve import (
    QueryFuture,
    ServeOverloadError,
    ServeScheduler,
    estimate_query_bytes,
    is_batchable,
)
from cylon_tpu.utils import tracing


@pytest.fixture(scope="module", params=[1, 4, 8])
def serve_ctx(request, devices):
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[: request.param])
    )


@pytest.fixture(scope="module")
def sctx4(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:4]))


def _mk_binding(ctx, rng, n, str_keys=False, nulls=False):
    """One (left, right) parameter binding. Values are integer-valued
    float32 so reduction order cannot perturb sums (exact equality)."""
    if str_keys:
        k = rng.choice([f"s{i}" for i in range(12)], n).astype(object)
        rk = rng.choice([f"s{i}" for i in range(15)], n).astype(object)
        if nulls:
            k[rng.random(n) < 0.1] = None
    else:
        k = rng.integers(0, 20, n).astype(np.int32)
        rk = rng.integers(0, 20, n).astype(np.int32)
    ta = ct.Table.from_pydict(
        ctx, {"k": k, "v": rng.integers(-50, 50, n).astype(np.float32)}
    )
    tb = ct.Table.from_pydict(
        ctx, {"rk": rk, "w": rng.integers(-50, 50, n).astype(np.float32)}
    )
    return ta, tb


def _q3(ta, tb):
    return (
        ta.lazy()
        .join(tb.lazy(), left_on="k", right_on="rk")
        .filter(col("w") > 0.0)
        .groupby("k", {"v": "sum"})
    )


def _canon(pydict):
    """Canonical row order + null normalization: batched execution
    guarantees the exact row SET (and per-query sort-key order), not the
    serial shard-concatenation order — equal key tuples may hash to
    different shards once the binding id joins the key."""
    df = pd.DataFrame(pydict)
    for c in df.columns:
        if df[c].dtype == object:
            df[c] = df[c].map(lambda v: "\0null" if v is None else str(v))
    df = df.fillna("\0null").astype(str)
    return df.sort_values(list(df.columns)).reset_index(drop=True)


def _assert_same(got, want, label=""):
    assert list(got) == list(want), (label, list(got), list(want))
    pd.testing.assert_frame_equal(
        _canon(got), _canon(want), check_dtype=False, obj=label or "result"
    )


def _run_batched(ctx, plans):
    s = ServeScheduler(ctx, auto_start=False)
    futs = [s.submit(p) for p in plans]
    s.run_pending()
    return [f.result(timeout=120) for f in futs]


# ----------------------------------------------------------------------
# batched-vs-serial exact differential, worlds {1, 4, 8}
# ----------------------------------------------------------------------
def test_batched_equals_serial_q3(serve_ctx, rng):
    plans = [
        _q3(*_mk_binding(serve_ctx, rng, 150 + 37 * i)) for i in range(5)
    ]
    oracle = [p.collect().to_pydict() for p in plans]
    before = tracing.get_count("serve.batch_cache.miss")
    got = _run_batched(serve_ctx, plans)
    assert tracing.get_count("serve.batches") >= 1
    assert tracing.get_count("serve.batch_cache.miss") == before + 1
    for i, t in enumerate(got):
        _assert_same(t.to_pydict(), oracle[i], f"q3 binding {i}")


def test_batched_equals_serial_string_nulls(serve_ctx, rng):
    """Dictionary-encoded keys with per-binding dictionaries: stacking
    must unify them (codes remapped against the union dictionary)."""
    plans = [
        _q3(*_mk_binding(serve_ctx, rng, 120 + 29 * i, str_keys=True,
                         nulls=True))
        for i in range(4)
    ]
    oracle = [p.collect().to_pydict() for p in plans]
    for i, t in enumerate(_run_batched(serve_ctx, plans)):
        _assert_same(t.to_pydict(), oracle[i], f"string binding {i}")


_TAILS = {
    "sort": lambda ta, tb: ta.lazy()
    .join(tb.lazy(), left_on="k", right_on="rk")
    .sort(["k", "v"]),
    "left-project": lambda ta, tb: ta.lazy()
    .join(tb.lazy(), left_on="k", right_on="rk", how="left")
    .select(["k", "w"]),
    "right": lambda ta, tb: ta.lazy().join(
        tb.lazy(), left_on="k", right_on="rk", how="right"
    ),
    "multi-agg": lambda ta, tb: ta.lazy()
    .filter(col("v") > 0.0)
    .groupby("k", {"v": ["min", "count", "mean"]}),
}


@pytest.mark.parametrize("name", list(_TAILS))
def test_batched_equals_serial_tails(serve_ctx, rng, name):
    """Non-q3 batchable shapes: sort tail, left-join + project,
    right join, multi-aggregate groupby."""
    build = _TAILS[name]
    plans = [
        build(*_mk_binding(serve_ctx, rng, 100 + 13 * i)) for i in range(3)
    ]
    oracle = [p.collect().to_pydict() for p in plans]
    for i, t in enumerate(_run_batched(serve_ctx, plans)):
        got = t.to_pydict()
        _assert_same(got, oracle[i], f"{name} binding {i}")
        if name == "sort":
            # RAW order, not just the canonicalized set: each
            # binding's slice must come out in its requested sort
            # order (qid-leading batched sort + stable split)
            order = np.lexsort(
                (np.asarray(got["v"]), np.asarray(got["k"]))
            )
            assert np.array_equal(
                order, np.arange(len(got["k"]))
            ), f"sort binding {i} rows not in (k, v) order"


def test_unbatchable_limit_falls_back_to_singles(sctx4, rng):
    ta, _ = _mk_binding(sctx4, rng, 80)
    lf = ta.lazy().sort("k").limit(7)
    assert not is_batchable(lf.plan)
    before = tracing.get_count("serve.singles")
    s = ServeScheduler(sctx4, auto_start=False)
    futs = [s.submit(lf), s.submit(ta.lazy().sort("k").limit(7))]
    s.run_pending()
    want = lf.collect().to_pydict()
    for f in futs:
        _assert_same(f.result(timeout=60).to_pydict(), want, "limit")
    assert tracing.get_count("serve.singles") == before + 2


def test_dataframe_collect_async_roundtrip(sctx4, rng):
    df = ct.DataFrame(
        {"a": np.arange(40, dtype=np.int64),
         "b": rng.integers(0, 9, 40).astype(np.int32)},
        ctx=sctx4,
    )
    fut = df.collect_async()
    assert isinstance(fut, QueryFuture)
    out = fut.result(timeout=60)
    assert isinstance(out, ct.DataFrame)
    _assert_same(out.to_table().to_pydict(), df.to_table().to_pydict())


# ----------------------------------------------------------------------
# admission control: backpressure + shed
# ----------------------------------------------------------------------
def test_hammer_backpressure_zero_lost(sctx4, rng, monkeypatch):
    """16 threads, each submitting AND consuming its own distinct
    binding (the concurrent-serving pattern) through a worker scheduler
    whose in-flight budget admits ~3 unconsumed queries: submitters must
    WAIT (the backpressure queue engages while the drain is frozen), a
    shed — possible if consumption momentarily lags past the 2x hard
    cap — is retried like a real client, and every query resolves
    exactly once to its own binding's serial result."""
    bindings = [_mk_binding(sctx4, rng, 120 + 7 * i) for i in range(16)]
    plans = [_q3(ta, tb) for ta, tb in bindings]
    oracle = [p.collect().to_pydict() for p in plans]
    est = estimate_query_bytes(
        [bindings[0][0], bindings[0][1]]
    )
    monkeypatch.setenv("CYLON_TPU_SERVE_INFLIGHT_BYTES", str(3 * est))
    wait_before = tracing.get_count("serve.backpressure.wait")
    s = ServeScheduler(sctx4, auto_start=True)
    s.pause()  # freeze the drain: the first wave MUST backpressure
    barrier = threading.Barrier(16)

    def worker(i):
        barrier.wait()
        while True:
            try:
                fut = s.submit(plans[i])
                break
            except ServeOverloadError:
                time.sleep(0.005)  # shed: back off and retry
        return i, fut.result(timeout=120).to_pydict()

    with ThreadPoolExecutor(max_workers=16) as ex:
        pending = [ex.submit(worker, i) for i in range(16)]
        # with the drain frozen the budget admits ~3 queries, so the
        # other submitters are provably waiting: poll the counter, THEN
        # release the drain
        deadline = time.monotonic() + 30
        while (
            tracing.get_count("serve.backpressure.wait") == wait_before
            and time.monotonic() < deadline
        ):
            time.sleep(0.01)
        assert tracing.get_count("serve.backpressure.wait") > wait_before
        s.resume()
        results = dict(p.result(timeout=180) for p in pending)
    assert len(results) == 16
    for i in range(16):
        _assert_same(results[i], oracle[i], f"hammer binding {i}")
    assert s.drain(timeout=30)
    assert s.stats()["inflight_bytes"] == 0  # everything consumed
    s.close()


def test_shed_error_contract(sctx4, rng, monkeypatch):
    ta, tb = _mk_binding(sctx4, rng, 100)
    lf = _q3(ta, tb)
    # sheds count by REASON (serve.shed.*), so the SLO rules and an
    # autoscaler can tell offered load from a consumer leak
    budget_before = tracing.get_count("serve.shed.admission_budget")
    queue_before = tracing.get_count("serve.shed.queue_depth")

    # (a) a query whose estimate alone exceeds the hard cap sheds at
    # submit, blocking or not
    monkeypatch.setenv("CYLON_TPU_SERVE_INFLIGHT_BYTES", "1")
    s = ServeScheduler(sctx4, auto_start=False)
    with pytest.raises(ServeOverloadError):
        s.submit(lf)
    assert (
        tracing.get_count("serve.shed.admission_budget") == budget_before + 1
    )
    monkeypatch.delenv("CYLON_TPU_SERVE_INFLIGHT_BYTES")

    # (b) a full queue sheds nowait submitters and loses nothing admitted
    monkeypatch.setenv("CYLON_TPU_SERVE_QUEUE_DEPTH", "2")
    f1 = s.submit(lf)
    f2 = s.submit(_q3(*_mk_binding(sctx4, rng, 90)))
    with pytest.raises(ServeOverloadError):
        s.submit(_q3(*_mk_binding(sctx4, rng, 80)), block=False)
    assert tracing.get_count("serve.shed.queue_depth") == queue_before + 1
    s.run_pending()
    assert f1.result(timeout=60).row_count == lf.collect().row_count
    assert f2.exception(timeout=60) is None


def test_inflight_lease_released_on_consumption(sctx4, rng):
    """The byte budget covers fulfilled-but-unread results: leases stay
    held after dispatch, release on result() consumption, and release
    via the GC finalizer when an unconsumed future is dropped."""
    s = ServeScheduler(sctx4, auto_start=False)
    futs = [s.submit(_q3(*_mk_binding(sctx4, rng, 70))) for _ in range(3)]
    held = s.stats()["inflight_bytes"]
    assert held > 0
    s.run_pending()
    assert all(f.done() for f in futs)
    # fulfilled != consumed: leases stay held, and batched dispatch adds
    # the split-burst surcharge so admission sees the slices' footprint
    assert s.stats()["inflight_bytes"] >= held
    for f in futs:
        f.result(timeout=60)
    assert s.stats()["inflight_bytes"] == 0
    fut = s.submit(_q3(*_mk_binding(sctx4, rng, 60)))
    s.run_pending()
    assert s.stats()["inflight_bytes"] > 0
    del fut  # dropped unconsumed: the finalizer returns the lease
    import gc

    gc.collect()
    assert s.stats()["inflight_bytes"] == 0


# ----------------------------------------------------------------------
# compile-once pins
# ----------------------------------------------------------------------
def test_batch_cache_one_compile_per_bucket(sctx4, rng, monkeypatch):
    """B bindings -> exactly 1 batched-executor compile per (fingerprint,
    pow2 B bucket); re-serving the same shape at the same bucket is a
    pure cache hit."""
    monkeypatch.setenv("CYLON_TPU_SERVE_BATCH_MAX", "8")
    # a literal no other test uses: a fresh fingerprint
    build = lambda ta, tb: (  # noqa: E731
        ta.lazy()
        .join(tb.lazy(), left_on="k", right_on="rk")
        .filter(col("w") > 0.3216549)
        .groupby("k", {"v": "sum"})
    )
    bindings = [_mk_binding(sctx4, rng, 90 + 5 * i) for i in range(8)]
    miss0 = tracing.get_count("serve.batch_cache.miss")
    hit0 = tracing.get_count("serve.batch_cache.hit")
    s = ServeScheduler(sctx4, auto_start=False)

    def serve_all(n):
        futs = [s.submit(build(ta, tb)) for ta, tb in bindings[:n]]
        s.run_pending()
        return [f.result(timeout=120) for f in futs]

    serve_all(8)  # bucket 8: compile
    assert tracing.get_count("serve.batch_cache.miss") == miss0 + 1
    serve_all(8)  # bucket 8 again: hit
    assert tracing.get_count("serve.batch_cache.miss") == miss0 + 1
    assert tracing.get_count("serve.batch_cache.hit") == hit0 + 1
    serve_all(3)  # bucket 4 (pow2-padded): one new compile
    assert tracing.get_count("serve.batch_cache.miss") == miss0 + 2


def test_cached_collect_zero_fingerprint_hashes(sctx4, rng):
    """The ISSUE-9 small fix: the histogram key is hoisted onto the
    cached executor entry, so the serving hot loop re-derives NOTHING —
    plan.fingerprint.hash stays flat across cached collects (it used to
    grow by one per collect), while the latency histogram keeps filling
    under the hoisted key."""
    lf = _q3(*_mk_binding(sctx4, rng, 130))
    lf.collect()  # compile: hashes once, onto the entry
    hist_key = lf._executable()[2].hist_key
    q_before = obs_metrics.latency_quantiles(hist_key)["count"]
    before = tracing.get_count("plan.fingerprint.hash")
    for _ in range(5):
        lf.collect()
    assert tracing.get_count("plan.fingerprint.hash") == before
    assert obs_metrics.latency_quantiles(hist_key)["count"] == q_before + 5
