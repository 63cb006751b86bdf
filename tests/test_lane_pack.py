"""Bit-width-adaptive lane packing tests (ISSUE 5).

Three layers, mirroring test_ordering.py:
  1. stats lifecycle — ColStat measurement (ensure_stats vs numpy),
     carriage through row-subset/rename ops, establishment by the shuffle
     count pass, and invalidation on in-place mutation;
  2. differential — every packed path (fused multi-key sort, fused
     groupby factorize, fused join probe, wire-narrowed shuffle) against
     the CYLON_TPU_NO_LANE_PACK=1 oracle at worlds {1, 2, 4, 8},
     including null masks, dictionary string keys, negative ints,
     descending keys, and f64 (which must decline);
  3. the pinned acceptance — the multi-key q3 pipeline (join ->
     groupby-SUM over two narrow int keys) runs >= 25% fewer traced
     sort-pass bytes at world 1, strictly fewer sort ops at world 4, with
     identical output.
"""
import os
import sys

import numpy as np
import pandas as pd
import pandas.testing as pdt
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import cylon_tpu as ct
from cylon_tpu.ops import stats as stmod
from cylon_tpu.ops.sort import plan_lane_fusion
from cylon_tpu.utils.tracing import get_count, reset_trace


@pytest.fixture(scope="module")
def ctx1(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:1]))


@pytest.fixture(scope="module")
def ctx4(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:4]))


def _norm(df):
    out = df.copy()
    for c in out.columns:
        # pandas 3 gives string columns the ``str`` dtype, older ones ``object``
        if out[c].dtype == object or pd.api.types.is_string_dtype(out[c].dtype):
            out[c] = out[c].astype(object).map(
                lambda v: "\x00null" if pd.isna(v) else str(v)
            )
        else:
            out[c] = out[c].astype(np.float64)
    out = out.fillna(-1e30)
    return out.sort_values(list(out.columns), kind="mergesort").reset_index(
        drop=True
    )


def _assert_same(a, b):
    ap, bp = _norm(a.to_pandas()), _norm(b.to_pandas())
    pdt.assert_frame_equal(ap, bp)


# ----------------------------------------------------------------------
# 1. stats lifecycle
# ----------------------------------------------------------------------

def test_ensure_stats_bounds_match_numpy(ctx1, rng):
    n = 3000
    a = rng.integers(-500, 4000, n).astype(np.int32)
    t = ct.Table.from_pydict(ctx1, {
        "a": a,
        "f": rng.normal(size=n).astype(np.float64),
    })
    st = t.ensure_stats(["a", "f"])
    assert st["f"] is None  # f64 has no packable lane
    got = st["a"]
    assert got.cls == "i32"
    # orderable i32 encoding = value ^ 0x80000000 (sign flip)
    enc = (a.astype(np.int64) + 2**31).astype(np.uint64)
    assert got.lo == int(enc.min()) and got.hi == int(enc.max())
    # cached: second call returns the same object, no recompute
    assert t.ensure_stats(["a"])["a"] is got


def test_stats_measure_masked_values_too(ctx1, rng):
    """Null rows' PAYLOAD values ride sort lanes and wire fields, so the
    bounds must cover them — the stats ignore the validity mask."""
    n = 1000
    a = np.zeros(n, object)
    a[:] = 5
    a[0] = 999  # this row will be null, but its payload is still 999...
    df = pd.DataFrame({"a": a})
    df.loc[0, "a"] = None
    t = ct.Table.from_pandas(ctx1, df)
    # encode_host turns None into a masked fill value; whatever it is,
    # the measured span must cover every LIVE physical value
    st = t.ensure_stats(["a"])["a"]
    phys, _valid = t._host_physical("a")
    shift = 2**31 if st.cls == "i32" else 2**63
    enc = phys.astype(object) + shift  # object: no int64 overflow
    assert st.lo <= int(min(enc)) and st.hi >= int(max(enc))


def test_stats_carry_through_row_subsets(ctx1, rng):
    n = 2000
    t = ct.Table.from_pydict(ctx1, {
        "a": rng.integers(0, 100, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
    })
    st = t.ensure_stats(["a"])["a"]
    f = t.filter(t.column("a").data < 50)
    assert f._stats["a"] == st  # conservative bounds survive the subset
    s = t.sort("a")
    assert s._stats["a"] == st  # permutation
    r = t.rename({"a": "b"})
    assert r._stats["b"] == st  # descriptor follows its column
    p = t.project(["a"])
    assert p._stats["a"] == st


def test_shuffle_count_pass_establishes_stats(ctx4, rng):
    n = 4000
    t = ct.Table.from_pydict(ctx4, {
        "k": rng.integers(0, 300, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
    })
    assert not t._stats
    reset_trace()
    s = t.shuffle(["k"])
    # global bounds measured by the count kernel, attached to BOTH the
    # input (cache) and the output (values survive the reroute) with NO
    # dedicated stats kernel
    assert get_count("lane_pack.stats_kernel") == 0
    assert "k" in t._stats and "k" in s._stats
    assert t._stats["k"] == s._stats["k"]
    # ...so a downstream groupby pays no stats sync either
    reset_trace()
    s.groupby("k", {"v": "sum"})
    assert get_count("lane_pack.stats_kernel") == 0


def test_stats_invalidated_on_mutation(ctx1, rng):
    n = 1000
    t = ct.Table.from_pydict(ctx1, {
        "a": rng.integers(0, 50, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
    })
    t.ensure_stats(["a"])
    assert t._stats
    t["a"] = np.arange(n).astype(np.int32) * 100000  # in-place mutation
    assert not t._stats  # stale bounds must not drive a packing plan
    # re-measured stats reflect the NEW values, and the packed sort of the
    # mutated table matches the oracle (the regression this guards: a
    # stale 6-bit plan over the new 27-bit values would corrupt the order)
    st = t.ensure_stats(["a"])["a"]
    assert st.hi - st.lo >= 100000 * (n - 1)
    with stmod.disabled():
        want = t.sort(["a", "v"])
    _assert_same(t.sort(["a", "v"]), want)

    t2 = ct.Table.from_pydict(ctx1, {"a": np.arange(10, dtype=np.int32)})
    t2.ensure_stats(["a"])
    t2.dropna(inplace=True)
    t2["b"] = np.ones(10, np.float32)
    assert not t2._stats


# ----------------------------------------------------------------------
# 2. planner unit
# ----------------------------------------------------------------------

def test_plan_fuses_narrow_keys_into_one_word():
    # the ISSUE's headline shape: 12 + 16 + 20 bits -> ONE uint64 word
    specs = [("i32", 12, False, True), ("i32", 16, False, True),
             ("u32", 20, False, True)]
    plan = plan_lane_fusion(specs, pad_bits=2, prefix_bits=0, allow64=True)
    assert plan is not None and plan.n_words == 1 and plan.allow64
    assert plan.n_plain == 4  # 3 value lanes + pad
    # without x64 the same shape needs two uint32 words — still a win
    plan32 = plan_lane_fusion(specs, pad_bits=2, prefix_bits=0, allow64=False)
    assert plan32 is not None and plan32.n_words == 2 and not plan32.allow64


def test_plan_declines():
    # unknown stats on any key
    assert plan_lane_fusion(
        [("i32", 8, False, True), None], 2, 0, True
    ) is None
    # descending float (NaN-last pinning has no rebased-field encoding)
    assert plan_lane_fusion([("f32", 16, False, False)], 2, 0, True) is None
    # no strict gain: one full-width key is already one lane
    assert plan_lane_fusion([("i32", 32, False, True)], 2, 0, False) is None
    # a >32-bit field needs the single-uint64-word layout
    assert plan_lane_fusion([("i64", 40, False, True)], 2, 0, False) is None
    # null flags pack too: masked 32-bit key fuses 3 lanes -> 2 words
    p = plan_lane_fusion([("i32", 32, True, True)], 2, 0, False)
    assert p is not None and p.n_words == 2 and p.n_plain == 3


def test_bit_layout_round_trip(rng):
    """assemble_words/extract_fields invert each other for widths that
    straddle word boundaries and exceed 32 bits, and word-lex order
    equals field-lex order."""
    import jax.numpy as jnp

    bits = [2, 1, 12, 40, 17, 0, 30]  # pad, null, narrow, wide, straddlers
    n = 512
    fields = []
    for b in bits:
        hi = (1 << b) - 1
        v = rng.integers(0, hi + 1, n)
        fields.append(jnp.asarray(
            v.astype(np.uint64) if b > 32 else v.astype(np.uint32)
        ))
    for allow64 in (False, True):
        layout = stmod.layout_words(bits, allow64)
        words = stmod.assemble_words(fields, layout)
        got = stmod.extract_fields(words, layout, bits)
        for b, f, g in zip(bits, fields, got):
            assert np.array_equal(np.asarray(f), np.asarray(g)), (b, allow64)
        # order equivalence: tuple-compare the words (msb-first) vs fields
        wt = list(zip(*[np.asarray(w) for w in words]))
        ft = list(zip(*[np.asarray(f) for f in fields]))
        order_w = sorted(range(n), key=lambda i: (wt[i], i))
        order_f = sorted(range(n), key=lambda i: (ft[i], i))
        assert order_w == order_f, allow64


# ----------------------------------------------------------------------
# 3. differentials vs the CYLON_TPU_NO_LANE_PACK oracle
# ----------------------------------------------------------------------

def _mixed_frame(rng, n, null_p=0.15):
    k1 = rng.integers(-200, 1500, n).astype(np.int32).astype(object)
    if null_p:
        k1[rng.random(n) < null_p] = None
    return pd.DataFrame({
        "k1": k1,
        "k2": rng.choice([f"s{i}" for i in range(40)], n),
        "k3": (rng.integers(-50, 50, n) * 3).astype(np.int64),
        "v": rng.normal(size=n).astype(np.float32),
    })


@pytest.mark.parametrize("world", [1, 2, 4, 8])
def test_sort_packed_vs_oracle(world, devices, rng):
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )
    df = _mixed_frame(rng, 3000)
    t = ct.Table.from_pandas(ctx, df)
    reset_trace()
    got = t.sort(["k1", "k2", "k3"], ascending=[True, False, True])
    assert get_count("lane_pack.sort_fused") >= 1
    with stmod.disabled():
        t2 = ct.Table.from_pandas(ctx, df)
        want = t2.sort(["k1", "k2", "k3"], ascending=[True, False, True])
    pdt.assert_frame_equal(got.to_pandas(), want.to_pandas())


@pytest.mark.parametrize("world", [1, 2, 4, 8])
def test_join_groupby_packed_vs_oracle(world, devices, rng):
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )
    ldf = _mixed_frame(rng, 1500)
    rdf = _mixed_frame(rng, 1500).rename(columns={"v": "w"})
    lt, rt = ct.Table.from_pandas(ctx, ldf), ct.Table.from_pandas(ctx, rdf)
    j = lt.distributed_join(rt, on=["k1", "k2"], how="inner")
    g = j.distributed_groupby("k1_x", {"v": "sum"})
    with stmod.disabled():
        lt2 = ct.Table.from_pandas(ctx, ldf)
        rt2 = ct.Table.from_pandas(ctx, rdf)
        jw = lt2.distributed_join(rt2, on=["k1", "k2"], how="inner")
        gw = jw.distributed_groupby("k1_x", {"v": "sum"})
    _assert_same(j, jw)
    _assert_same(g, gw)


def test_f64_key_declines_but_matches(ctx1, rng):
    n = 1200
    df = pd.DataFrame({
        "a": rng.integers(0, 40, n).astype(np.int32),
        "f": rng.normal(size=n).astype(np.float64),
    })
    t = ct.Table.from_pandas(ctx1, df)
    reset_trace()
    got = t.sort(["a", "f"])
    assert get_count("lane_pack.sort_fused") == 0  # f64 must decline
    with stmod.disabled():
        want = ct.Table.from_pandas(ctx1, df).sort(["a", "f"])
    pdt.assert_frame_equal(got.to_pandas(), want.to_pandas())


@pytest.mark.parametrize("world", [2, 4, 8])
def test_wire_narrowed_shuffle_vs_oracle(world, devices, rng):
    """The stats-driven wire codec ships narrow ints + 1-bit masks and the
    received table is identical to the plain int32-lane exchange."""
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )
    df = _mixed_frame(rng, 4000)
    t = ct.Table.from_pandas(ctx, df)
    reset_trace()
    got = t.shuffle(["k1"])
    assert get_count("lane_pack.wire.applied") >= 1
    with stmod.disabled():
        t2 = ct.Table.from_pandas(ctx, df)
        want = t2.shuffle(["k1"])
    assert (got.row_counts == want.row_counts).all()
    _assert_same(got, want)


def test_wire_gate_declines_without_gain(ctx4, rng):
    """Full-width mask-free floats leave nothing to narrow: the wire plan
    is absent (not merely unprofitable) and the plain codec runs."""
    n = 3000
    t = ct.Table.from_pydict(ctx4, {
        "k": (rng.normal(size=n) * 1e6).astype(np.float32),
        "v": rng.normal(size=n).astype(np.float32),
    })
    reset_trace()
    t.shuffle(["k"])
    assert get_count("lane_pack.wire.applied") == 0


def test_setops_and_unique_packed_vs_oracle(ctx4, rng):
    df1 = _mixed_frame(rng, 1200)[["k1", "k3"]]
    df2 = _mixed_frame(rng, 1200)[["k1", "k3"]]
    a, b = ct.Table.from_pandas(ctx4, df1), ct.Table.from_pandas(ctx4, df2)
    got_i = a.distributed_intersect(b)
    got_u = a.distributed_unique(["k1"])
    with stmod.disabled():
        a2 = ct.Table.from_pandas(ctx4, df1)
        b2 = ct.Table.from_pandas(ctx4, df2)
        want_i = a2.distributed_intersect(b2)
        want_u = a2.distributed_unique(["k1"])
    _assert_same(got_i, want_i)
    _assert_same(got_u, want_u)


def test_kill_switch_silences_everything(ctx4, rng):
    df = _mixed_frame(rng, 1500)
    with stmod.disabled():
        t = ct.Table.from_pandas(ctx4, df)
        reset_trace()
        t.sort(["k1", "k3"])
        t.shuffle(["k1"])
        t.groupby("k1", {"v": "sum"})
        assert t.ensure_stats(["k1"]) == {}
        for c in ("lane_pack.sort_fused", "lane_pack.groupby_fused",
                  "lane_pack.join_fused", "lane_pack.wire.applied",
                  "lane_pack.stats_kernel"):
            assert get_count(c) == 0, c


# ----------------------------------------------------------------------
# 4. plan layer
# ----------------------------------------------------------------------

def test_explain_annotates_stats_and_fingerprint_tracks_gate(ctx1, rng):
    n = 1000
    t = ct.Table.from_pydict(ctx1, {
        "k": rng.integers(0, 100, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
    })
    t.ensure_stats(["k"])
    lf = t.lazy().groupby("k", {"v": "sum"})
    txt = lf.explain()
    assert "-- stats:" in txt and "k:" in txt
    # the kill switch is part of the plan-executable identity: flipping it
    # must re-optimize (a cache miss), never reuse the packed executor
    from cylon_tpu.utils.tracing import get_count as gc

    lf.collect()
    before = gc("plan.cache.miss")
    with stmod.disabled():
        lf.collect()
    assert gc("plan.cache.miss") == before + 1


# ----------------------------------------------------------------------
# 5. the pinned q3 acceptance gate
# ----------------------------------------------------------------------

def _sort_totals(op):
    from benchmarks.roofline import Report, analyze
    from cylon_tpu import engine

    op()  # warm
    engine.record_kernels(True)
    try:
        op()
    finally:
        kernels = engine.recorded_kernels()
        engine.record_kernels(False)
    total = Report()
    for fn, args in kernels:
        rep = analyze(fn, *args)
        total.sort_count += rep.sort_count
        total.sort_pass_bytes += rep.sort_pass_bytes
        total.collective_bytes += rep.collective_bytes
    return total


@pytest.mark.parametrize("world", [1, 4])
def test_q3_sort_gb_reduction(world, devices):
    """Acceptance: the multi-key narrow-lane q3 pipeline (inner join on
    two int keys spanning ~12 and ~16 bits -> groupby-SUM) through lane
    packing runs with >= 25% fewer traced sort-pass bytes at world 1
    (where the relational sorts are the whole cost) and strictly fewer
    sort ops + no sort-byte regression at world 4 (where the shuffle
    engine's compaction argsorts dilute the ratio), with identical
    output and no collective-byte regression."""
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )
    rng = np.random.default_rng(16)
    n = 20000
    lt = ct.Table.from_pydict(ctx, {
        "k1": rng.integers(0, 4000, n).astype(np.int32),
        "k2": rng.integers(0, 60000, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
    })
    rt = ct.Table.from_pydict(ctx, {
        "k1": rng.integers(0, 4000, n).astype(np.int32),
        "k2": rng.integers(0, 60000, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32),
    })
    res = {}

    def q3(tag):
        def run():
            res[tag] = lt.distributed_join(
                rt, on=["k1", "k2"], how="inner"
            ).distributed_groupby(["k1_x", "k2_x"], {"v": "sum"})

        return run

    tp = _sort_totals(q3("packed"))
    with stmod.disabled():
        tu = _sort_totals(q3("oracle"))
    assert tp.sort_count < tu.sort_count
    assert tp.collective_bytes <= tu.collective_bytes
    reduction = 1.0 - tp.sort_pass_bytes / tu.sort_pass_bytes
    floor = 0.25 if world == 1 else 0.0
    assert reduction >= floor, (
        f"sort-pass bytes only reduced {reduction:.1%} at world={world} "
        f"({tu.sort_pass_bytes / 1e9:.3f} -> {tp.sort_pass_bytes / 1e9:.3f} GB)"
    )
    _assert_same(res["packed"], res["oracle"])
