"""Planner tests: rewrite-rule firing via .explain(), lazy-vs-eager
differential parity (fixed + randomized), and the plan-fingerprint cache.

The eager ops are the oracle everywhere: the planner must never change a
result, only how it is computed.
"""
import numpy as np
import numpy.testing as npt
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import col, lit
from cylon_tpu.plan import rules as plan_rules
from cylon_tpu.utils import tracing


def _tables(ctx, rng, n=1200, keyspace=40, val_dtype=np.float32, nulls=False):
    a = pd.DataFrame({
        "k": rng.integers(0, keyspace, n).astype(np.int32),
        "v": rng.normal(size=n).astype(val_dtype),
        "extra": rng.normal(size=n),
    })
    b = pd.DataFrame({
        "rk": rng.integers(0, keyspace, n // 2).astype(np.int32),
        "w": rng.normal(size=n // 2).astype(np.float32),
    })
    if nulls:
        a.loc[a.sample(frac=0.1, random_state=1).index, "v"] = np.nan
    return ct.Table.from_pandas(ctx, a), ct.Table.from_pandas(ctx, b)


def _sorted_pdf(t, by):
    return t.to_pandas().sort_values(by).reset_index(drop=True)


def _assert_frames_close(lp, ep, rtol=1e-4):
    assert list(lp.columns) == list(ep.columns)
    assert lp.shape == ep.shape
    for c in lp.columns:
        l, e = lp[c].to_numpy(), ep[c].to_numpy()
        if l.dtype.kind == "f" or e.dtype.kind == "f":
            npt.assert_allclose(
                l.astype(np.float64), e.astype(np.float64), rtol=rtol,
                atol=1e-5, equal_nan=True,
            )
        else:
            npt.assert_array_equal(l, e)


# ----------------------------------------------------------------------
# acceptance: filter -> join -> groupby(sum)
# ----------------------------------------------------------------------
def test_acceptance_filter_join_groupby_sum(ctx8, rng):
    ta, tb = _tables(ctx8, rng)
    lf = (
        ta.lazy()
        .join(tb.lazy(), left_on="k", right_on="rk")
        .filter(col("w") > 0.0)
        .groupby("k", {"v": "sum"})
    )
    text = lf.explain()
    # >= 3 distinct rules, including shuffle elimination and the fused
    # join+groupby pushdown selecting join_sum_by_key_pushdown
    for rule in (
        plan_rules.FILTER_PUSHDOWN,
        plan_rules.PROJECTION_PUSHDOWN,
        plan_rules.SHUFFLE_ELIM,
        plan_rules.FUSED_JOIN_GROUPBY,
    ):
        assert rule in text, f"{rule} missing from explain:\n{text}"
    assert "join_sum_by_key_pushdown" in text

    res = lf.collect()
    joined = ta.distributed_join(tb, left_on=["k"], right_on=["rk"])
    eager = joined.filter(joined.column("w").data > 0.0).groupby(
        "k", {"v": "sum"}
    )
    _assert_frames_close(_sorted_pdf(res, "k"), _sorted_pdf(eager, "k"))


def test_plan_cache_hit_no_recompile(ctx8, rng):
    ta, tb = _tables(ctx8, rng)
    lf = (
        ta.lazy()
        .join(tb.lazy(), left_on="k", right_on="rk")
        .filter(col("w") > 0.0)
        .groupby("k", {"v": "sum"})
    )
    first = lf.collect()
    hits0 = tracing.get_count("plan.cache.hit")
    kernels0 = len(ctx8._jit_cache)
    # identical plan shape + data: pure cache hit, zero new kernel programs
    second = lf.collect()
    assert tracing.get_count("plan.cache.hit") == hits0 + 1
    assert len(ctx8._jit_cache) == kernels0, "cache hit must not recompile"
    assert second.column_names == first.column_names
    # fresh LazyFrame objects over fresh (equal-schema) data: same
    # fingerprint, still a hit (sizes are jit's business, not the plan's)
    ta2, tb2 = _tables(ctx8, np.random.default_rng(7))
    lf2 = (
        ta2.lazy()
        .join(tb2.lazy(), left_on="k", right_on="rk")
        .filter(col("w") > 0.0)
        .groupby("k", {"v": "sum"})
    )
    third = lf2.collect()
    assert tracing.get_count("plan.cache.hit") == hits0 + 2
    assert third.column_names == first.column_names


# ----------------------------------------------------------------------
# individual rules
# ----------------------------------------------------------------------
def test_explain_each_rule_fires_on_trigger_plan(ctx8, rng):
    ta, tb = _tables(ctx8, rng)
    # filter pushdown: filter sits above a join whose right side covers it
    t1 = ta.lazy().join(tb.lazy(), left_on="k", right_on="rk").filter(
        col("w") > 0.0
    )
    assert plan_rules.FILTER_PUSHDOWN in t1.explain()
    # projection pushdown: select a subset after a join
    t2 = ta.lazy().join(tb.lazy(), left_on="k", right_on="rk").select(
        ["k", "w"]
    )
    assert plan_rules.PROJECTION_PUSHDOWN in t2.explain()
    # shuffle elimination: groupby on the join key of a just-shuffled join
    t3 = ta.lazy().join(tb.lazy(), left_on="k", right_on="rk").groupby(
        "k", {"w": "min"}
    )
    ex3 = t3.explain()
    assert plan_rules.SHUFFLE_ELIM in ex3
    assert plan_rules.FUSED_JOIN_GROUPBY not in ex3  # min() is not sum()
    # fused join+groupby: sum of a float32 LEFT column by the join key
    t4 = ta.lazy().join(tb.lazy(), left_on="k", right_on="rk").groupby(
        "k", {"v": "sum"}
    )
    assert plan_rules.FUSED_JOIN_GROUPBY in t4.explain()


def test_fused_rule_gates(ctx8, rng):
    ta, tb = _tables(ctx8, rng, val_dtype=np.int32)
    # int value column: generic path (wide accumulator), still correct
    lf = ta.lazy().join(tb.lazy(), left_on="k", right_on="rk").groupby(
        "k", {"v": "sum"}
    )
    assert plan_rules.FUSED_JOIN_GROUPBY not in lf.explain()
    res = lf.collect()
    joined = ta.distributed_join(tb, left_on=["k"], right_on=["rk"])
    eager = joined.groupby("k", {"v": "sum"})
    _assert_frames_close(_sorted_pdf(res, "k"), _sorted_pdf(eager, "k"))


def test_fused_path_with_null_values(ctx8, rng):
    ta, tb = _tables(ctx8, rng, nulls=True)
    lf = ta.lazy().join(tb.lazy(), left_on="k", right_on="rk").groupby(
        "k", {"v": "sum"}
    )
    assert plan_rules.FUSED_JOIN_GROUPBY in lf.explain()
    res = lf.collect()
    joined = ta.distributed_join(tb, left_on=["k"], right_on=["rk"])
    eager = joined.groupby("k", {"v": "sum"})
    _assert_frames_close(_sorted_pdf(res, "k"), _sorted_pdf(eager, "k"))


def test_shuffle_elimination_correctness(world_ctx, rng):
    """join -> groupby on the join key must equal the eager two-shuffle
    path on every mesh size (the eliminated shuffle is the one the eager
    distributed_groupby would run)."""
    ta, tb = _tables(world_ctx, rng, n=800)
    lf = ta.lazy().join(tb.lazy(), left_on="k", right_on="rk").groupby(
        "k", {"w": "max"}
    )
    res = lf.collect()
    joined = ta.distributed_join(tb, left_on=["k"], right_on=["rk"])
    eager = joined.distributed_groupby("k", {"w": "max"})
    _assert_frames_close(_sorted_pdf(res, "k"), _sorted_pdf(eager, "k"))


def test_filter_pushdown_not_through_outer_join(ctx8, rng):
    """A right-column predicate must NOT move below a LEFT join (it would
    turn matched rows into unmatched instead of dropping them)."""
    ta, tb = _tables(ctx8, rng, n=600)
    lf = (
        ta.lazy()
        .join(tb.lazy(), left_on="k", right_on="rk", how="left")
        .filter(col("w") > 0.5)
    )
    # the rule may still fire for OTHER filters; assert correctness
    res = lf.collect()
    joined = ta.distributed_join(tb, left_on=["k"], right_on=["rk"], how="left")
    from cylon_tpu.plan.expr import filter_mask

    eager = joined.filter(
        filter_mask(col("w") > 0.5, {n: joined.column(n) for n in joined.column_names})
    )
    _assert_frames_close(
        _sorted_pdf(res, ["k", "v", "w"]), _sorted_pdf(eager, ["k", "v", "w"])
    )


def test_chained_join_no_subset_elision(ctx8, rng):
    """A table partitioned on hash('a') is co-located for ('a','b') but
    PLACED differently than a fresh hash of both columns — a second join on
    ('a','b') must keep its shuffles or matches silently vanish."""
    n = 2000
    a = pd.DataFrame({"a": rng.integers(0, 20, n).astype(np.int32),
                      "b": rng.integers(0, 20, n).astype(np.int32)})
    b = pd.DataFrame({"a": rng.integers(0, 20, n).astype(np.int32),
                      "w": rng.normal(size=n).astype(np.float32)})
    c = pd.DataFrame({"a2": rng.integers(0, 20, 300).astype(np.int32),
                      "b2": rng.integers(0, 20, 300).astype(np.int32),
                      "z": rng.normal(size=300).astype(np.float32)})
    ta, tb, tc = (ct.Table.from_pandas(ctx8, x) for x in (a, b, c))
    lf = (ta.lazy().join(tb.lazy(), on="a")
          .join(tc.lazy(), left_on=["a_x", "b"], right_on=["a2", "b2"]))
    got = lf.collect().row_count
    want = len(a.merge(b, on="a").rename(columns={"a": "a_x"})
               .merge(c, left_on=["a_x", "b"], right_on=["a2", "b2"]))
    assert got == want
    # exact same-key chained join: elision IS sound and must still fire
    lf2 = (ta.lazy().join(tb.lazy(), on="a")
           .join(tc.lazy(), left_on=["a_x"], right_on=["a2"]))
    assert plan_rules.SHUFFLE_ELIM in lf2.explain()
    got2 = lf2.collect().row_count
    want2 = len(a.merge(b, on="a").rename(columns={"a": "a_x"})
                .merge(c, left_on=["a_x"], right_on=["a2"]))
    assert got2 == want2


def test_cache_isolated_from_shared_scan_mutation(ctx8, rng):
    """A cached executor must keep its compile-time scan ordinals even when
    a different plan sharing a Scan node reassigns them (ordinals are
    frozen into detached stubs at compile time)."""
    ta, tb = _tables(ctx8, rng, n=300)
    base = ta.lazy()
    p1 = base.join(tb.lazy(), left_on="k", right_on="rk")
    first = p1.collect()
    # base's Scan is shared; this plan walks it at a different DFS position
    p2 = tb.lazy().join(base, left_on="rk", right_on="k")
    p2.collect()
    again = p1.collect()  # cache hit: must still read the RIGHT tables
    assert again.row_count == first.row_count
    assert again.column_names == first.column_names


# ----------------------------------------------------------------------
# surface ops
# ----------------------------------------------------------------------
def test_lazy_local_ops(local_ctx, rng):
    df = pd.DataFrame({
        "a": rng.integers(0, 20, 300).astype(np.int64),
        "b": rng.normal(size=300),
    })
    t = ct.Table.from_pandas(local_ctx, df)
    res = (
        t.lazy().filter((col("a") >= 5) & (col("a") < 15)).select(["a", "b"])
        .sort("a").collect()
    )
    exp = df[(df.a >= 5) & (df.a < 15)].sort_values("a").reset_index(drop=True)
    got = res.to_pandas().reset_index(drop=True)
    npt.assert_array_equal(got["a"].to_numpy(), exp["a"].to_numpy())
    npt.assert_allclose(
        np.sort(got["b"].to_numpy()), np.sort(exp["b"].to_numpy())
    )


def test_lazy_sort_global(ctx8, rng):
    df = pd.DataFrame({"a": rng.permutation(1000).astype(np.int32),
                       "b": rng.normal(size=1000)})
    t = ct.Table.from_pandas(ctx8, df)
    res = t.lazy().sort("a").collect()
    eager = t.distributed_sort("a")
    npt.assert_array_equal(
        res.to_pandas()["a"].to_numpy(), eager.to_pandas()["a"].to_numpy()
    )


def test_lazy_limit_and_head(ctx8, rng):
    df = pd.DataFrame({"a": np.arange(500, dtype=np.int64)})
    t = ct.Table.from_pandas(ctx8, df)
    assert t.lazy().limit(7).collect().row_count == 7
    assert t.lazy().head().collect().row_count == 5
    assert t.lazy().limit(10_000).collect().row_count == 500


def test_lazy_union(ctx8, rng):
    a = pd.DataFrame({"a": rng.integers(0, 30, 200).astype(np.int64)})
    b = pd.DataFrame({"a": rng.integers(15, 45, 200).astype(np.int64)})
    ta, tb = ct.Table.from_pandas(ctx8, a), ct.Table.from_pandas(ctx8, b)
    res = ta.lazy().union(tb.lazy()).collect()
    eager = ta.distributed_union(tb)
    npt.assert_array_equal(
        np.sort(res.to_pandas()["a"].to_numpy()),
        np.sort(eager.to_pandas()["a"].to_numpy()),
    )


def test_lazy_string_key_join(ctx8, rng):
    a = pd.DataFrame({
        "k": rng.choice([f"s{i}" for i in range(12)], 300).astype(object),
        "v": rng.normal(size=300).astype(np.float32),
    })
    b = pd.DataFrame({
        "k": rng.choice([f"s{i}" for i in range(12)], 150).astype(object),
        "w": rng.normal(size=150).astype(np.float32),
    })
    ta, tb = ct.Table.from_pandas(ctx8, a), ct.Table.from_pandas(ctx8, b)
    lf = ta.lazy().join(tb.lazy(), on="k").groupby("k_x", {"v": "sum"})
    assert plan_rules.FUSED_JOIN_GROUPBY in lf.explain()
    res = lf.collect()
    eager = ta.distributed_join(tb, on="k").groupby("k_x", {"v": "sum"})
    _assert_frames_close(_sorted_pdf(res, "k_x"), _sorted_pdf(eager, "k_x"))


def test_lazy_string_literal_filter(ctx8, rng):
    a = pd.DataFrame({
        "k": rng.choice(["ant", "bee", "cow", "dog"], 200).astype(object),
        "v": rng.normal(size=200),
    })
    t = ct.Table.from_pandas(ctx8, a)
    res = t.lazy().filter(col("k") >= "bee").collect().to_pandas()
    exp = a[a.k >= "bee"]
    assert sorted(res["k"]) == sorted(exp["k"])
    res2 = t.lazy().filter(col("k") == "cow").collect().to_pandas()
    assert sorted(res2["k"]) == sorted(a[a.k == "cow"]["k"])


def test_lazy_dataframe_entrypoint(local_ctx, rng):
    df = ct.DataFrame({"a": [3, 1, 2], "b": [1.0, 2.0, 3.0]})
    out = df.lazy().sort("a").collect()
    npt.assert_array_equal(out.to_pandas()["a"].to_numpy(), [1, 2, 3])


def test_lazy_validates_eagerly(local_ctx):
    t = ct.Table.from_pydict(ct.CylonContext.init(), {"a": [1, 2, 3]})
    lf = t.lazy()
    with pytest.raises(KeyError):
        lf.select(["nope"])
    with pytest.raises(KeyError):
        lf.filter(col("nope") > 0)
    with pytest.raises(TypeError):
        lf.filter(lambda env: env)


def test_explain_pre_and_post_sections(ctx8, rng):
    ta, tb = _tables(ctx8, rng)
    text = (
        ta.lazy().join(tb.lazy(), left_on="k", right_on="rk")
        .groupby("k", {"v": "sum"}).explain()
    )
    assert "== Logical plan ==" in text
    assert "== Optimized plan ==" in text
    assert text.index("Logical") < text.index("Optimized")


# ----------------------------------------------------------------------
# randomized differential suite: optimized plan vs eager oracle
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_differential_random_plans(ctx8, seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(200, 1500))
    keyspace = int(rng.integers(4, 60))
    ta, tb = _tables(ctx8, rng, n=n, keyspace=keyspace,
                     nulls=bool(rng.integers(0, 2)))
    filt = bool(rng.integers(0, 2))
    agg_op = rng.choice(["sum", "min", "max", "count", "mean"])

    lf = ta.lazy().join(tb.lazy(), left_on="k", right_on="rk")
    joined = ta.distributed_join(tb, left_on=["k"], right_on=["rk"])
    if filt:
        lf = lf.filter(col("v") > 0.0)
        from cylon_tpu.plan.expr import filter_mask

        joined = joined.filter(filter_mask(
            col("v") > 0.0, {c: joined.column(c) for c in joined.column_names}
        ))
    lf = lf.groupby("k", {"v": str(agg_op)})
    eager = joined.distributed_groupby("k", {"v": str(agg_op)})
    _assert_frames_close(_sorted_pdf(lf.collect(), "k"), _sorted_pdf(eager, "k"))


# ----------------------------------------------------------------------
# observability
# ----------------------------------------------------------------------
def test_collect_emits_plan_spans_and_report(ctx8, rng):
    tracing.reset_trace()
    ta, tb = _tables(ctx8, rng, n=400)
    lf = ta.lazy().join(tb.lazy(), left_on="k", right_on="rk").groupby(
        "k", {"v": "sum"}
    )
    lf.collect()
    rep = tracing.report()
    for name in ("plan.optimize", "plan.lower", "plan.execute"):
        assert rep[name]["count"] == 1, rep
    lf.collect()
    rep = tracing.report()
    for name in ("plan.optimize", "plan.lower", "plan.execute"):
        assert rep[name]["count"] == 2, "spans must be emitted on cache hits too"
    rules_rep = tracing.report("plan.rule.")
    assert rules_rep[f"plan.rule.{plan_rules.FUSED_JOIN_GROUPBY}"]["count"] == 2
    assert rules_rep[f"plan.rule.{plan_rules.SHUFFLE_ELIM}"]["count"] == 2
    # a never-seen plan shape must register a miss in the engine stats
    misses0 = __import__("cylon_tpu").engine.plan_cache_stats()["misses"]
    ta.lazy().select(["extra", "k"]).filter(col("extra") < 0.0).collect()
    stats = __import__("cylon_tpu").engine.plan_cache_stats()
    assert stats["hits"] >= 1 and stats["misses"] == misses0 + 1


# ----------------------------------------------------------------------
# partial_aggregate: an aggregate the dense plan takes is combined in place
# ----------------------------------------------------------------------
def _flag_table(ctx, rng, n=1500):
    return ct.Table.from_pydict(ctx, {
        "flag": rng.choice(np.array(["A", "N", "R"]), n),
        "status": rng.choice(np.array(["F", "O"]), n),
        "v": rng.random(n), "d": rng.integers(0, 100, n),
    })


def _q1_shape(t):
    return (
        t.lazy().filter(col("d") < 90)
        .groupby(["flag", "status"], {"v": ["sum", "mean", "count"]})
        .sort(["flag", "status"])
    )


def test_partial_aggregate_fires_on_a_mesh_and_not_on_one_shard(ctx8, local_ctx, rng):
    text = _q1_shape(_flag_table(ctx8, rng)).explain()
    assert f"{plan_rules.PARTIAL_AGGREGATE} x1" in text.split("Rewrites fired:")[1]
    local = _q1_shape(_flag_table(local_ctx, rng)).explain()
    assert plan_rules.PARTIAL_AGGREGATE not in local
    assert "Shuffle" not in local.split("== Optimized plan ==")[1]


def test_explain_names_the_partial_aggregate(ctx8, rng):
    optimized = _q1_shape(_flag_table(ctx8, rng)).explain().split(
        "== Optimized plan =="
    )[1]
    node = optimized.strip().splitlines()[0]
    assert node.startswith("GroupBy [flag, status]")
    assert "[partial aggregate: combined in place, no shuffle" in node
    assert "mask (col('d') < 90)" in node  # the filter rides it
    assert "@global" in node  # one copy, in key order, over the mesh
    keyless = _flag_table(ctx8, rng).lazy().agg({"v": "sum"}).explain()
    assert "Aggregate agg [sum(v)] [partial aggregate" in keyless


def test_the_sort_over_a_partial_aggregate_is_elided_on_a_mesh(ctx8, rng):
    t = _flag_table(ctx8, rng)
    lf = _q1_shape(t)
    text = lf.explain()
    optimized = text.split("== Optimized plan ==")[1]
    assert "Sort" not in optimized and "Shuffle" not in optimized
    assert f"{plan_rules.ORDER_REUSE} x1" in text
    tracing.reset_trace()
    got = lf.collect().to_pandas()
    rep = tracing.report()
    assert not any(k.startswith("shuffle.") for k in rep), sorted(rep)
    assert rep["groupby.partial_path"]["count"] == 1
    assert rep[f"plan.rule.{plan_rules.PARTIAL_AGGREGATE}"]["count"] == 1
    df = t.to_pandas()
    want = df[df["d"] < 90].groupby(["flag", "status"]).agg(
        v_sum=("v", "sum"), v_mean=("v", "mean"), v_count=("v", "count"),
    ).reset_index()
    _assert_frames_close(got, want, rtol=1e-12)
