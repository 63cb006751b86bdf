"""Order-property propagation tests (ISSUE 3).

Three layers:
  1. descriptor correctness — which ops establish, carry, and destroy the
     ordering descriptor (incl. survival/invalidation across the K-round
     chunked shuffle);
  2. differential — every sorted-input fast path (groupby run-detect, sort
     no-op/suffix, unique run-detect, single-column set-op probe, key-order
     join emit, presorted-right probe) against the generic path with the
     consumer gates disabled (CYLON_TPU_NO_ORDERING=1), on randomized
     tables (the fuzz oracle pattern);
  3. the pinned q3 acceptance — join->groupby-SUM through the key-order
     emit must run >= 30% fewer traced sort-pass bytes than the eager
     unordered path, with identical output, and ``.explain()`` must show
     the elided groupby lexsort.
"""
import os
import sys

import numpy as np
import pandas as pd
import pandas.testing as pdt
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import cylon_tpu as ct
from cylon_tpu import Ordering
from cylon_tpu import ordering as ordmod
from cylon_tpu.plan import rules as plan_rules
from cylon_tpu.utils.tracing import get_count, reset_trace


@pytest.fixture(scope="module")
def ctx1(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:1]))


@pytest.fixture(scope="module")
def ctx4(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:4]))


def _tables(ctx, rng, n=2000, keyspace=None, fanout_safe=True):
    keyspace = keyspace or (n if fanout_safe else 50)
    lt = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, keyspace, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
    })
    rt = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, keyspace, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32),
    })
    return lt, rt


def _gates_off():
    return ordmod.disabled()  # the ONE env toggle (cylon_tpu/ordering.py)


# ----------------------------------------------------------------------
# 1. descriptor lifecycle
# ----------------------------------------------------------------------
def test_sort_establishes_descriptor(ctx1):
    rng = np.random.default_rng(0)
    lt, _ = _tables(ctx1, rng, n=500)
    assert lt.ordering is None
    s = lt.sort(["k", "v"], ascending=[True, False])
    o = s.ordering
    assert o is not None
    assert o.keys == ("k", "v") and o.ascending == (True, False)
    assert o.scope == "shard" and o.lexsort_exact
    # descending second key: not canonical
    assert not o.canonical
    s2 = lt.sort("k")
    assert s2.ordering.canonical  # mask-free ascending


def test_descriptor_validation():
    with pytest.raises(ValueError):
        ordmod.validate(Ordering(keys=(), ascending=()), ["a"])
    with pytest.raises(ValueError):
        ordmod.validate(
            Ordering(keys=("nope",), ascending=(True,)), ["a"]
        )
    with pytest.raises(ValueError):
        ordmod.validate(
            Ordering(keys=("a",), ascending=(True, False)), ["a"]
        )
    with pytest.raises(ValueError):  # canonical demands ascending
        ordmod.validate(
            Ordering(keys=("a",), ascending=(False,), canonical=True), ["a"]
        )


def test_with_ordering_rejects_unknown_key(ctx1):
    rng = np.random.default_rng(1)
    lt, _ = _tables(ctx1, rng, n=100)
    with pytest.raises(ValueError):
        lt.with_ordering(Ordering(keys=("zz",), ascending=(True,)))


def test_carry_and_truncate(ctx1):
    rng = np.random.default_rng(2)
    lt, _ = _tables(ctx1, rng, n=500)
    s = lt.sort(["k", "v"])
    # filter / project / rename / drop / set_index carry or truncate
    assert s.filter(s.column("v").data > 0).ordering.keys == ("k", "v")
    assert s.project(["k"]).ordering.keys == ("k",)
    assert s.project(["v"]).ordering is None  # 'v' is not a key PREFIX
    assert s.rename({"k": "key"}).ordering.keys == ("key", "v")
    assert s.drop(["v"]).ordering.keys == ("k",)
    assert s.set_index("k").ordering is not None
    # unique keeps a subset of rows in order
    assert s.unique(["k"]).ordering.keys == ("k", "v")


def test_groupby_output_is_key_ordered(ctx1):
    rng = np.random.default_rng(3)
    lt, _ = _tables(ctx1, rng, n=800, keyspace=60)
    g = lt.groupby("k", {"v": "sum"})
    o = g.ordering
    assert o is not None and o.keys == ("k",) and o.canonical
    kv = g.to_pandas()["k"].to_numpy()
    assert (np.diff(kv) >= 0).all()


def test_shuffle_invalidates_across_chunked_rounds(ctx4):
    """Survival check at K>1: a multi-round chunked shuffle must DROP the
    descriptor (rounds land source-major and interleave key ranges)."""
    from cylon_tpu.parallel import shuffle as sh
    from cylon_tpu.utils.tracing import report

    rng = np.random.default_rng(4)
    lt, _ = _tables(ctx4, rng, n=4000)
    s = lt.sort("k")
    assert s.ordering is not None
    reset_trace()
    # tiny budget forces K > 1 rounds
    shuffled = s.shuffle(["k"], byte_budget=2048)
    rounds = int(report("shuffle.")["shuffle.rounds"]["rows"])
    assert rounds > 1, "budget did not force a multi-round shuffle"
    assert shuffled.ordering is None
    # and at K == 1 too
    assert s.shuffle(["k"], byte_budget=1 << 40).ordering is None
    assert sh.ordering_after_shuffle("hash") is None
    assert sh.ordering_after_shuffle("range") is None
    with pytest.raises(ValueError):
        sh.ordering_after_shuffle("bogus")


def test_distributed_sort_sets_global_scope_and_elides(ctx4):
    rng = np.random.default_rng(5)
    lt, _ = _tables(ctx4, rng, n=3000)
    s = lt.distributed_sort("k")
    assert s.ordering is not None and s.ordering.scope == "global"
    reset_trace()
    s2 = s.distributed_sort("k")
    assert get_count("ordering.dist_sort_elided") == 1
    assert s2.ordering == s.ordering
    pdt.assert_frame_equal(s2.to_pandas(), s.to_pandas())


def test_inplace_mutation_drops_descriptor(ctx1):
    rng = np.random.default_rng(6)
    lt, _ = _tables(ctx1, rng, n=200)
    s = lt.sort("k")
    assert s.ordering is not None
    s["v2"] = np.arange(s.row_count, dtype=np.float32)
    assert s.ordering is None


def test_plan_sees_mutation_not_stale_scan_capture(ctx1):
    """A plan built over a sorted table, collected AFTER an in-place
    mutation cleared the descriptor, must NOT elide its Sort off the stale
    plan-build-time claim."""
    rng = np.random.default_rng(60)
    lt, _ = _tables(ctx1, rng, n=400)
    s = lt.sort("k")
    lf = s.lazy().sort("k")
    assert plan_rules.ORDER_REUSE in lf.explain()  # elidable right now
    # in-place mutation scrambles k and clears the descriptor
    s["k"] = rng.permutation(s.to_pandas()["k"].to_numpy())
    assert plan_rules.ORDER_REUSE not in lf.explain()
    out = lf.collect().to_pandas()["k"].to_numpy()
    assert (np.diff(out) >= 0).all(), "stale Scan ordering elided a needed sort"


# ----------------------------------------------------------------------
# 2. differential fast paths (gates on vs off)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_groupby_run_detect_differential(ctx1, seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(100, 2000))
    lt, _ = _tables(ctx1, rng, n=n, keyspace=int(rng.integers(2, 80)))
    s = lt.sort("k")
    reset_trace()
    got = s.groupby("k", {"v": ["sum", "count", "mean"]})
    assert get_count("ordering.groupby_run_detect") == 1
    with _gates_off():
        want = s.groupby("k", {"v": ["sum", "count", "mean"]})
    pdt.assert_frame_equal(got.to_pandas(), want.to_pandas())


def test_sort_noop_and_suffix_differential(ctx1):
    rng = np.random.default_rng(7)
    lt, _ = _tables(ctx1, rng, n=1500, keyspace=40)
    s = lt.sort("k")
    reset_trace()
    e = s.sort("k")
    assert get_count("ordering.sort_elided") == 1
    pdt.assert_frame_equal(e.to_pandas(), s.to_pandas())
    # the elided result is a fresh handle: mutating it must not write
    # through to the source table
    e["z"] = np.zeros(e.row_count, np.float32)
    assert "z" not in s.column_names and s.ordering is not None
    got = s.sort(["k", "v"])
    assert get_count("ordering.sort_suffix") == 1
    with _gates_off():
        want = s.sort(["k", "v"])
    pdt.assert_frame_equal(got.to_pandas(), want.to_pandas())
    # and against a from-scratch full sort of the source table
    pdt.assert_frame_equal(got.to_pandas(), lt.sort(["k", "v"]).to_pandas())
    # direction mismatch on the prefix must NOT elide
    reset_trace()
    d = s.sort("k", ascending=False)
    assert get_count("ordering.sort_elided") == 0
    assert (np.diff(d.to_pandas()["k"].to_numpy()) <= 0).all()


@pytest.mark.parametrize("keep", ["first", "last"])
def test_unique_run_detect_differential(ctx1, keep):
    rng = np.random.default_rng(8)
    lt, _ = _tables(ctx1, rng, n=1200, keyspace=30)
    s = lt.sort("k")
    reset_trace()
    got = s.unique(["k"], keep=keep)
    assert get_count("ordering.unique_run_detect") == 1
    with _gates_off():
        want = s.unique(["k"], keep=keep)
    pdt.assert_frame_equal(got.to_pandas(), want.to_pandas())


@pytest.mark.parametrize("op", ["union", "subtract", "intersect"])
def test_setop_sorted_probe_differential(ctx1, op):
    rng = np.random.default_rng(9)
    lt, rt = _tables(ctx1, rng, n=900, keyspace=70)
    lk, rk = lt.project(["k"]).sort("k"), rt.project(["k"]).sort("k")
    reset_trace()
    got = getattr(lk, op)(rk)
    assert get_count("ordering.setop_sorted_probe") == 1
    with _gates_off():
        want = getattr(lk, op)(rk)
    pdt.assert_frame_equal(got.to_pandas(), want.to_pandas())


def test_join_presorted_right_differential(ctx1):
    rng = np.random.default_rng(10)
    lt, rt = _tables(ctx1, rng, n=1500)
    rs = rt.sort("k")
    reset_trace()
    got = lt.join(rs, on="k", how="inner")
    assert get_count("ordering.join_presorted_probe") == 1
    with _gates_off():
        want = lt.join(rs, on="k", how="inner")
    pdt.assert_frame_equal(got.to_pandas(), want.to_pandas())


@pytest.mark.parametrize("how", ["inner", "left"])
def test_join_key_order_emit_differential(ctx1, how):
    rng = np.random.default_rng(11)
    lt, rt = _tables(ctx1, rng, n=1500)
    got = lt.join(rt, on="k", how=how, emit_order="key")
    assert got.ordering is not None and got.ordering.keys == ("k_x",)
    kv = got.to_pandas()["k_x"].to_numpy()
    assert (np.diff(kv) >= 0).all(), "key-order emit not key-sorted"
    plain = lt.join(rt, on="k", how=how)
    cols = ["k_x", "v", "w"]
    pdt.assert_frame_equal(
        got.to_pandas().sort_values(cols).reset_index(drop=True),
        plain.to_pandas().sort_values(cols).reset_index(drop=True),
    )


def test_join_key_order_overflow_falls_back(ctx1):
    """Fanout past the speculative cap: the key-order request must degrade
    to a correct left-order join with NO descriptor, never a wrong claim."""
    rng = np.random.default_rng(12)
    n = 3000
    lt, rt = _tables(ctx1, rng, n=n, keyspace=None, fanout_safe=False)
    got = lt.join(rt, on="k", how="inner", emit_order="key")
    assert got.ordering is None  # overflow -> two-phase left-order path
    plain = lt.join(rt, on="k", how="inner")
    cols = ["k_x", "v", "w"]
    pdt.assert_frame_equal(
        got.to_pandas().sort_values(cols).reset_index(drop=True),
        plain.to_pandas().sort_values(cols).reset_index(drop=True),
    )


def test_join_key_order_rejects_right_outer(ctx1):
    rng = np.random.default_rng(13)
    lt, rt = _tables(ctx1, rng, n=100)
    for how in ("right", "outer"):
        with pytest.raises(ValueError):
            lt.join(rt, on="k", how=how, emit_order="key")
    with pytest.raises(ValueError):
        lt.distributed_join(rt, on="k", mode="fused", emit_order="key")


def test_null_keys_key_order_join_groupby(ctx1):
    """Null join keys through the key-order emit + groupby run-detect: the
    canonical descriptor must keep null==null adjacency intact."""
    rng = np.random.default_rng(14)
    n = 600
    k = rng.integers(0, 40, n).astype(np.float64)
    k[rng.random(n) < 0.2] = np.nan
    ldf = pd.DataFrame({"k": k, "v": rng.normal(size=n).astype(np.float32)})
    rdf = pd.DataFrame({
        "k": rng.permutation(np.arange(40).astype(np.float64)),
        "w": rng.normal(size=40).astype(np.float32),
    })
    lt = ct.Table.from_pandas(ctx1, ldf)
    rt = ct.Table.from_pandas(ctx1, rdf)
    j = lt.join(rt, on="k", how="left", emit_order="key")
    g = j.groupby("k_x", {"v": "sum"})
    with _gates_off():
        want = lt.join(rt, on="k", how="left").groupby("k_x", {"v": "sum"})
    sort_cols = ["k_x", "v_sum"]
    pdt.assert_frame_equal(
        g.to_pandas().sort_values(sort_cols).reset_index(drop=True),
        want.to_pandas().sort_values(sort_cols).reset_index(drop=True),
    )


# ----------------------------------------------------------------------
# satellite: take() uniform-shard short-circuit
# ----------------------------------------------------------------------
def test_take_uniform_short_circuit_matches_general(ctx4):
    rng = np.random.default_rng(15)
    # 4 shards x 250 rows: perfectly uniform -> divmod path
    lt, _ = _tables(ctx4, rng, n=1000)
    assert lt.row_counts.max() == lt.row_counts.min()
    idx = rng.integers(0, 1000, 300)
    got = lt.take(idx).to_pandas()
    host = lt.to_pandas()
    pdt.assert_frame_equal(got, host.iloc[idx].reset_index(drop=True))
    # negative indices still work through the short circuit
    got2 = lt.take(np.array([-1, 0, -1000])).to_pandas()
    pdt.assert_frame_equal(
        got2, host.iloc[[999, 0, 0]].reset_index(drop=True)
    )
    # non-uniform shards (filter skews counts) take the searchsorted path
    flt = lt.filter(lt.column("v").data > 0.3)
    if flt.row_counts.max() != flt.row_counts.min():
        m = flt.row_count
        idx2 = rng.integers(0, m, min(m, 100))
        pdt.assert_frame_equal(
            flt.take(idx2).to_pandas(),
            flt.to_pandas().iloc[idx2].reset_index(drop=True),
        )


# ----------------------------------------------------------------------
# 3. the pinned q3 acceptance + explain
# ----------------------------------------------------------------------
def _traced_sorts(op):
    """``op``'s programs in dispatch order, each as (name, roofline report)."""
    from benchmarks.roofline import analyze
    from cylon_tpu import engine

    op()  # warm
    engine.record_kernels(True)
    try:
        op()
    finally:
        kernels = engine.recorded_kernels()
        engine.record_kernels(False)
    return [(fn.__name__, analyze(fn, *args)) for fn, args in kernels]


def _stage_sorts(rep, stage):
    """Pass bytes of the sorts ``rep`` traced under the named stage."""
    return [b for scope, b in rep.sorts if stage in scope.split("/")]


@pytest.mark.parametrize("world", [1, 4])
def test_q3_sort_pass_bytes_reduction(world, devices):
    """Acceptance: q3 (join -> groupby-SUM) through order propagation runs
    with >= 30% fewer traced sort-pass bytes than the eager unordered path,
    identical output.

    The bytes are those of the sorts in front of the aggregation: the
    join's, and the factorize sort of the group-by that takes the join's
    rows, which is what a key-ordered join output elides. The aggregation
    itself (``groupby.segment_sum``) sorts nothing on either path (a scan,
    and the run heads moved to their slots by selects since PR 46; one
    compaction sort from PR 28 until then), and behind the first aggregation
    (the partials' shuffle and the final group-by of a mesh) both paths
    run the same programs."""
    from cylon_tpu.obs import stages

    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )
    rng = np.random.default_rng(16)
    n = 20000
    lt = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
    })
    rt = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32),
    })
    res = {}

    def q3_eager():
        res["e"] = lt.distributed_join(
            rt, on="k", how="inner"
        ).distributed_groupby("k_x", {"v": "sum"})

    def q3_ordered():
        res["o"] = lt.distributed_join(
            rt, on="k", how="inner", emit_order="key"
        ).distributed_groupby("k_x", {"v": "sum"})

    def split(progs):
        """(bytes of the sorts in front of the first aggregation, that
        group-by's report, the programs behind it)."""
        at = [name for name, _rep in progs].index("groupby")
        agg = progs[at][1]
        front = sum(rep.sort_pass_bytes for _name, rep in progs[:at]) + sum(
            _stage_sorts(agg, stages.GROUPBY_KEY_IDS)
        )
        return front, agg, progs[at + 1:]

    eager, ordered = _traced_sorts(q3_eager), _traced_sorts(q3_ordered)
    fe, agg_e, rest_e = split(eager)
    fo, agg_o, rest_o = split(ordered)
    # the group-by of the join's rows: one factorize sort, none when the
    # rows come key-ordered; no sort behind it either way
    assert len(_stage_sorts(agg_e, stages.GROUPBY_KEY_IDS)) == 1
    assert len(_stage_sorts(agg_o, stages.GROUPBY_KEY_IDS)) == 0
    for _name, rep in [p for p in eager + ordered if p[0] == "groupby"]:
        assert len(_stage_sorts(rep, stages.GROUPBY_SEGMENT_SUM)) == 0
    assert agg_e.sort_count == 1 and agg_o.sort_count == 0
    # behind it: the same programs, sort for sort
    assert [(name, rep.sort_count) for name, rep in rest_e] == [
        (name, rep.sort_count) for name, rep in rest_o
    ]
    reduction = 1.0 - fo / fe
    assert reduction >= 0.30, (
        f"sort-pass bytes only reduced {reduction:.1%} "
        f"({fe / 1e9:.3f} -> {fo / 1e9:.3f} GB)"
    )
    te = sum(rep.sort_pass_bytes for _name, rep in eager)
    to = sum(rep.sort_pass_bytes for _name, rep in ordered)
    assert to < te
    assert sum(r.sort_count for _n, r in ordered) < sum(
        r.sort_count for _n, r in eager
    )
    pdt.assert_frame_equal(
        res["e"].to_pandas().sort_values("k_x").reset_index(drop=True),
        res["o"].to_pandas().sort_values("k_x").reset_index(drop=True),
    )


def test_explain_q3_shows_elided_lexsort(ctx4):
    """Acceptance: .explain() surfaces the order property per node and the
    elided groupby lexsort on the q3 plan (count agg — a shape the fused
    join+groupby rule does not take, so order_reuse carries it)."""
    rng = np.random.default_rng(17)
    lt, rt = _tables(ctx4, rng, n=2000)
    rt = rt.rename({"k": "rk"})
    lf = lt.lazy().join(
        rt.lazy(), left_on="k", right_on="rk", how="inner"
    ).groupby("k", {"v": "count"})
    text = lf.explain()
    assert plan_rules.ORDER_REUSE in text
    assert "emit=key-order" in text
    assert "lexsort elided" in text
    assert "-- order:" in text  # per-node order property
    # the rewritten plan computes the same thing
    got = lf.collect().to_pandas().sort_values("k").reset_index(drop=True)
    want = (
        lt.distributed_join(rt, left_on=["k"], right_on=["rk"], how="inner")
        .distributed_groupby("k", {"v": "count"})
        .to_pandas().sort_values("k").reset_index(drop=True)
    )
    pdt.assert_frame_equal(got, want)


def test_explain_global_sort_elision_over_range_shuffle(ctx4):
    """At world > 1 the planner's Sort physicalizes a range Shuffle under
    itself; when the shuffle's input already holds the requested order at
    GLOBAL scope, order_reuse drops BOTH (the eager distributed_sort no-op
    lifted into the plan)."""
    rng = np.random.default_rng(20)
    lt, _ = _tables(ctx4, rng, n=2000)
    s = lt.distributed_sort("v")
    assert s.ordering is not None and s.ordering.scope == "global"
    text = s.lazy().sort("v").explain()
    assert plan_rules.ORDER_REUSE in text
    opt = text.split("== Optimized plan ==")[1]
    assert "Sort" not in opt and "Shuffle" not in opt
    pdt.assert_frame_equal(
        s.lazy().sort("v").collect().to_pandas(), s.to_pandas()
    )
    # an unsorted input keeps both nodes
    text2 = lt.lazy().sort("v").explain()
    opt2 = text2.split("== Optimized plan ==")[1]
    assert "Sort" in opt2 and "Shuffle range" in opt2


def test_explain_sort_elision_rewrite(ctx1):
    rng = np.random.default_rng(18)
    lt, _ = _tables(ctx1, rng, n=300)
    s = lt.sort("k")
    text = s.lazy().sort("k").explain()
    assert plan_rules.ORDER_REUSE in text
    # the optimized plan has no Sort node left
    opt = text.split("== Optimized plan ==")[1]
    assert "Sort" not in opt
    pdt.assert_frame_equal(
        s.lazy().sort("k").collect().to_pandas(), s.to_pandas()
    )


def test_escape_hatch_gates_plan_rewrites(ctx4):
    """CYLON_TPU_NO_ORDERING=1 must disable the order_reuse rewrites too
    (not just the eager kernel gates), and the plan cache must not alias
    executors across gate states."""
    rng = np.random.default_rng(21)
    lt, rt = _tables(ctx4, rng, n=1000)
    rt = rt.rename({"k": "rk"})
    lf = lt.lazy().join(
        rt.lazy(), left_on="k", right_on="rk", how="inner"
    ).groupby("k", {"v": "count"})
    assert plan_rules.ORDER_REUSE in lf.explain()
    with _gates_off():
        assert plan_rules.ORDER_REUSE not in lf.explain()
        off = lf.collect().to_pandas().sort_values("k").reset_index(drop=True)
    on = lf.collect().to_pandas().sort_values("k").reset_index(drop=True)
    pdt.assert_frame_equal(on, off)


def test_plan_cache_keyed_by_input_ordering(ctx1):
    """Two same-shape plans over inputs that differ ONLY in their ordering
    descriptor must not alias in the plan-fingerprint cache (the rewrites
    consumed the descriptor)."""
    rng = np.random.default_rng(19)
    lt, _ = _tables(ctx1, rng, n=300)
    s = lt.sort("k")
    f1 = lt.lazy().sort("k").plan.fingerprint()
    f2 = s.lazy().sort("k").plan.fingerprint()
    assert f1 != f2
