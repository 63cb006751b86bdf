"""Streaming ingestion + incremental view maintenance (ISSUE 16).

The invariant under test everywhere: an incremental refresh is
indistinguishable from the ``CYLON_TPU_NO_IVM=1`` full-recompute oracle
(exact canonicalized equality — test data uses integer-valued floats so
float32 sums associate exactly), generations never alias in any
fingerprint-keyed cache, and every failure ends typed with the prior
generation still queryable and the state arena rolled back.
"""
import os

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import fault, stream
from cylon_tpu.fault import StreamIngestError
from cylon_tpu.fault import inject as finject
from cylon_tpu.fault.errors import CylonError
from cylon_tpu.plan import lazy as lazy_mod


@pytest.fixture(scope="module", params=[1, 4, 8])
def sctx(request, devices):
    """Worlds {1, 4, 8}: the ISSUE-mandated differential sweep."""
    n = request.param
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:n]))


@pytest.fixture(scope="module")
def ctx4(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:4]))


@pytest.fixture(autouse=True)
def _disarm(monkeypatch):
    monkeypatch.delenv("CYLON_TPU_FAULTS", raising=False)
    monkeypatch.delenv("CYLON_TPU_NO_IVM", raising=False)
    fault.reset()
    yield
    fault.reset()


def _str_keys(rng, n, keyspace=16, null_p=0.1):
    k = rng.choice([f"s{i:02d}" for i in range(keyspace)], n).astype(object)
    if null_p:
        k[rng.random(n) < null_p] = None
    return k


def _batch(rng, n, null_p=0.1):
    """Dict batch: string keys (with nulls), integer-valued float32
    payload — float sums associate exactly, so oracle equality is ==."""
    return {
        "k": _str_keys(rng, n, null_p=null_p),
        "v": rng.integers(-40, 40, n).astype(np.float32),
    }


def _is_str(col):
    # pandas 3 gives string columns the ``str`` dtype, older ones ``object``
    return col.dtype == object or pd.api.types.is_string_dtype(col.dtype)


def _canon(t):
    df = t.to_pandas()
    for c in df.columns:
        if _is_str(df[c]):
            df[c] = df[c].fillna("\x00<null>")
    return df.sort_values(list(df.columns)).reset_index(drop=True)


def _assert_equal(got, want):
    a, b = _canon(got), _canon(want)
    assert list(a.columns) == list(b.columns)
    # The incremental path must reproduce the full-recompute SCHEMA too,
    # not just the values (host-merged partials rebuild via object arrays).
    assert list(a.dtypes) == list(b.dtypes), f"{list(a.dtypes)} != {list(b.dtypes)}"
    assert len(a) == len(b), f"{len(a)} rows != oracle {len(b)}"
    for c in a.columns:
        av, bv = a[c].to_numpy(), b[c].to_numpy()
        if _is_str(a[c]):
            assert (av == bv).all(), f"column {c} mismatch"
        else:
            np.testing.assert_array_equal(av, bv, err_msg=f"column {c}")


def _oracle(build, *sources):
    with stream.ivm_disabled():
        return stream.view(build, *sources).refresh()


# ---------------------------------------------------------------------------
# differentials vs the CYLON_TPU_NO_IVM=1 oracle, worlds {1, 4, 8}
# ---------------------------------------------------------------------------

def test_groupby_differential(sctx, rng):
    """Streaming scan -> filter -> groupby, multi-append, nulls."""
    tab = stream.AppendableTable(sctx, _batch(rng, 400))
    build = lambda t: (
        t.lazy().filter(ct.col("v") > -10).groupby("k", {"v": ["sum", "min"]})
    )
    v = stream.view(build, tab)
    _assert_equal(v.refresh(), _oracle(build, tab))
    for n in (150, 1, 90):  # multi-append including a 1-row delta
        tab.append(_batch(rng, n))
        _assert_equal(v.refresh(), _oracle(build, tab))
    assert v.stats["inc"] == 3 and v.stats["full"] == 1


def test_join_differential_both_sides(sctx, rng):
    """Inner join with BOTH sides streaming, groupby root, interleaved
    appends folded into single refreshes."""
    left = stream.AppendableTable(sctx, _batch(rng, 300))
    right = stream.AppendableTable(sctx, {
        "rk": _str_keys(rng, 80),
        "w": rng.integers(0, 30, 80).astype(np.float32),
    })
    build = lambda lt, rt: (
        lt.lazy().join(rt.lazy(), left_on="k", right_on="rk")
        .groupby("k", {"v": "sum", "w": "max"})
    )
    v = stream.view(build, left, right)
    v.refresh()
    # two left appends + one right append before ONE refresh
    left.append(_batch(rng, 120))
    right.append({"rk": _str_keys(rng, 40),
                  "w": rng.integers(0, 30, 40).astype(np.float32)})
    left.append(_batch(rng, 60))
    _assert_equal(v.refresh(), _oracle(build, left, right))
    assert v.stats["inc"] == 1


def test_filter_only_differential(sctx, rng):
    """No aggregate root: the delta just rides the Filter chain and the
    result is prev ++ chain(delta) (bag concat, no dedup)."""
    tab = stream.AppendableTable(sctx, _batch(rng, 200))
    build = lambda t: t.lazy().filter(ct.col("v") >= 0)
    v = stream.view(build, tab)
    v.refresh()
    tab.append(_batch(rng, 80))
    tab.append(_batch(rng, 80))  # duplicates across appends must survive
    _assert_equal(v.refresh(), _oracle(build, tab))
    assert v.stats["inc"] >= 1


def test_mean_falls_back_full(ctx4, rng):
    """mean is not mergeable from its own output: classified fallback,
    still oracle-equal."""
    tab = stream.AppendableTable(ctx4, _batch(rng, 150))
    build = lambda t: t.lazy().groupby("k", {"v": "mean"})
    v = stream.view(build, tab)
    v.refresh()
    tab.append(_batch(rng, 60))
    _assert_equal(v.refresh(), _oracle(build, tab))
    assert v.stats["fallback"] == 1 and v.stats["inc"] == 0


def test_empty_delta_and_noop(ctx4, rng):
    tab = stream.AppendableTable(ctx4, _batch(rng, 100))
    v = stream.view(lambda t: t.lazy().groupby("k", {"v": "sum"}), tab)
    r1 = v.refresh()
    g = tab.generation
    assert tab.append({"k": np.array([], object),
                       "v": np.array([], np.float32)}) == g  # no gen bump
    assert v.refresh() is r1 and v.stats["noop"] == 1  # nothing moved


def test_append_during_inflight_refresh(ctx4, rng):
    """An append landing between plan and commit must not be silently
    folded in: the commit publishes the PLANNED generation and the view
    stays stale, so the next refresh picks the new rows up."""
    tab = stream.AppendableTable(ctx4, _batch(rng, 200))
    build = lambda t: t.lazy().groupby("k", {"v": "sum"})
    v = stream.view(build, tab)
    v.refresh()
    tab.append(_batch(rng, 50))
    mode, lf, commit = v._plan_refresh()     # refresh in flight
    assert mode == "inc"
    tab.append(_batch(rng, 70))              # lands mid-flight
    commit(lf.collect())
    assert v.generations == [1] and v.stale()
    _assert_equal(v.refresh(), _oracle(build, tab))


# ---------------------------------------------------------------------------
# generation identity: plans can never alias across refreshes
# ---------------------------------------------------------------------------

def test_generation_keyed_fingerprint_no_aliasing(ctx4, rng):
    tab = stream.AppendableTable(ctx4, _batch(rng, 100))
    build = lambda t: t.lazy().groupby("k", {"v": "sum"})
    fps = []
    for _ in range(3):
        snap = tab.table()
        fps.append(lazy_mod.gated_fingerprint(build(snap).plan))
        tab.append(_batch(rng, 30))
    assert len(set(fps)) == 3, "same plan shape aliased across generations"
    # and the delta stamp is distinct from every snapshot stamp
    d = tab.delta_table(0)
    fp_d = lazy_mod.gated_fingerprint(build(d).plan)
    assert fp_d not in fps


def test_snapshot_descriptors_invalidated(ctx4, rng):
    """Appends invalidate Ordering/ColStat: snapshots are re-encoded
    fresh and never inherit a stale descriptor from an older
    generation's snapshot."""
    tab = stream.AppendableTable(ctx4, {
        "k": np.arange(64, dtype=np.int64),
        "v": np.ones(64, np.float32),
    })
    s0 = tab.table()
    s0.sort("k")  # stamp an ordering + stats onto the gen-0 snapshot
    tab.append({"k": np.array([3, 1], np.int64),
                "v": np.array([1.0, 1.0], np.float32)})
    s1 = tab.table()
    assert s1 is not s0
    assert s1._ordering is None and len(s1._stats) == 0
    d = tab.delta_table(0)
    assert d._ordering is None and len(d._stats) == 0


# ---------------------------------------------------------------------------
# ingest contract: schema validation, rollback, budget, watermarks
# ---------------------------------------------------------------------------

def test_append_schema_rejected_and_rolled_back(ctx4, rng):
    tab = stream.AppendableTable(ctx4, _batch(rng, 50))
    g, rows = tab.generation, tab.row_count
    snap_before = tab.table()
    for bad in (
        {"k": _str_keys(rng, 4), "WRONG": np.ones(4, np.float32)},
        {"k": _str_keys(rng, 4)},                                  # missing col
        {"k": np.arange(4), "v": np.ones(4, np.float32)},          # int keys
        {"k": _str_keys(rng, 4), "v": np.ones(3, np.float32)},     # ragged
        {"k": _str_keys(rng, 4), "v": np.array(["x"] * 4, object)},
    ):
        with pytest.raises(StreamIngestError) as ei:
            tab.append(bad)
        assert ei.value.retryable and ei.value.scope == "table"
    assert tab.generation == g and tab.row_count == rows
    _assert_equal(tab.table(), snap_before)  # prior gen still queryable


def test_watermarks_and_state_budget(ctx4, rng, monkeypatch):
    tab = stream.AppendableTable(ctx4, _batch(rng, 100))
    tab.append(_batch(rng, 40))
    tab.append(_batch(rng, 7))
    assert [tab.watermark(g) for g in range(3)] == [100, 140, 147]
    assert tab.rows_since(1) == 7 and tab.rows_since(0) == 47
    assert tab.delta_table(1).row_count == 7
    monkeypatch.setenv("CYLON_TPU_STREAM_STATE_BUDGET", "1")
    with pytest.raises(StreamIngestError):
        tab.append(_batch(rng, 10))
    assert tab.generation == 2 and tab.row_count == 147


def test_chunked_staging(ctx4, rng, monkeypatch):
    monkeypatch.setenv("CYLON_TPU_STREAM_CHUNK_ROWS", "16")
    tab = stream.AppendableTable(ctx4, _batch(rng, 10))
    tab.append(_batch(rng, 50))  # 4 chunks
    assert tab.row_count == 60
    _assert_equal(
        stream.view(lambda t: t.lazy().groupby("k", {"v": "sum"}), tab)
        .refresh(),
        _oracle(lambda t: t.lazy().groupby("k", {"v": "sum"}), tab),
    )


# ---------------------------------------------------------------------------
# fault seams: typed failures, state retention
# ---------------------------------------------------------------------------

def test_fault_append_rolls_back(ctx4, rng, monkeypatch):
    tab = stream.AppendableTable(ctx4, _batch(rng, 80))
    snap = tab.table()
    monkeypatch.setenv("CYLON_TPU_FAULTS", "stream.append:n=1")
    fault.reset()
    with pytest.raises(StreamIngestError):
        tab.append(_batch(rng, 20))
    assert finject.fired("stream.append") == 1
    assert tab.generation == 0 and tab.row_count == 80
    _assert_equal(tab.table(), snap)
    assert tab.append(_batch(rng, 20)) == 1  # injector exhausted: recovers


def test_fault_refresh_retains_state(ctx4, rng, monkeypatch):
    tab = stream.AppendableTable(ctx4, _batch(rng, 80))
    build = lambda t: t.lazy().groupby("k", {"v": "sum"})
    v = stream.view(build, tab)
    r0 = v.refresh()
    tab.append(_batch(rng, 30))
    monkeypatch.setenv("CYLON_TPU_FAULTS", "stream.refresh:n=1")
    fault.reset()
    with pytest.raises(CylonError):
        v.refresh()
    assert finject.fired("stream.refresh") == 1
    assert v._result is r0 and v.generations == [0]  # untouched
    _assert_equal(v.refresh(), _oracle(build, tab))  # same delta retries


def test_stream_fault_spec_validation():
    with pytest.raises(finject.FaultSpecError):
        finject.parse_spec("stream.append:kind=exec")  # errno-only seam
    finject.parse_spec("stream.append:n=1:kind=ENOSPC")
    finject.parse_spec("stream.refresh:kind=timeout")  # typed-kind seam
    with pytest.raises(finject.FaultSpecError):
        finject.parse_spec("stream.refresh:match=abc")  # unkeyed seam


# ---------------------------------------------------------------------------
# subscriptions
# ---------------------------------------------------------------------------

def test_subscription_re_resolution(ctx4, rng):
    tab = stream.AppendableTable(ctx4, _batch(rng, 200))
    build = lambda t: t.lazy().groupby("k", {"v": "sum"})
    sub = stream.subscribe(stream.view(build, tab))
    r1 = sub.result()
    assert sub.done() and not sub.stale()
    assert sub.result() is r1               # fresh: retained, no dispatch
    tab.append(_batch(rng, 60))
    assert sub.stale() and not sub.done()   # append marked it stale
    _assert_equal(sub.result(), _oracle(build, tab))
    assert sub.done()


def test_subscription_refresh_async_future(ctx4, rng):
    tab = stream.AppendableTable(ctx4, _batch(rng, 150))
    build = lambda t: t.lazy().groupby("k", {"v": "sum"})
    sub = stream.subscribe(stream.view(build, tab))
    fut = sub.refresh_async()
    got = fut.result(timeout=120)
    _assert_equal(got, _oracle(build, tab))
    tab.append(_batch(rng, 40))
    fut2 = sub.refresh_async()              # rides the serve scheduler
    _assert_equal(fut2.result(timeout=120), _oracle(build, tab))
    sub.close()


def test_subscription_failed_refresh_stays_stale(ctx4, rng, monkeypatch):
    tab = stream.AppendableTable(ctx4, _batch(rng, 100))
    sub = stream.subscribe(
        stream.view(lambda t: t.lazy().groupby("k", {"v": "sum"}), tab)
    )
    sub.result()
    tab.append(_batch(rng, 30))
    monkeypatch.setenv("CYLON_TPU_FAULTS", "stream.refresh:n=1")
    fault.reset()
    with pytest.raises(CylonError):
        sub.result()
    assert sub.stale()                      # not wedged fresh
    monkeypatch.delenv("CYLON_TPU_FAULTS")
    fault.reset()
    _assert_equal(
        sub.result(),
        _oracle(lambda t: t.lazy().groupby("k", {"v": "sum"}), tab),
    )
