"""Semi and anti joins as operators, and TPC-H Q4 through ``Table.lazy()``,
against plain numpy (``q4_reference.py``) and pandas on seeded data at a
small size, worlds 1 and 4, eager and lazy. With it: the null rule,
duplicate right keys, both masks, empty sides, key kinds, payload columns
bit for bit, the ``semi_capable`` fallback, what ``explain()`` says of
``semi_as_mask``, the observability names, and the INNER join's
semi-reduction programs, which are what they were.
"""
import os
import sys

import numpy as np
import pandas as pd
import pytest

import jax
import jax.monitoring

import cylon_tpu as ct
from cylon_tpu.column import Column
from cylon_tpu.obs import export as obs_export
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.obs import stages
from cylon_tpu.ops import join as _j
from cylon_tpu.ops import sketch as _sketch
from cylon_tpu.plan import col, lit
from cylon_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import q4_reference  # noqa: E402
from chipbench.generators import tpch_q4_tables  # noqa: E402

CONFIG = {"rows": {"orders": 15_000_000}, "orderdate": ["1992-01-01", "1998-08-02"]}
HOWS = ["semi", "anti"]
KEEP = {"semi": lambda hit: hit, "anti": lambda hit: ~hit}


@pytest.fixture(scope="module")
def ctxs(devices):
    """One context a world for the whole file: its compiled programs are
    shared by the cases."""
    return {
        w: ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:w]))
        for w in (1, 4)
    }


def _tables(ctx, data):
    return {
        name: ct.Table.from_numpy(ctx, list(cols), list(cols.values()))
        for name, cols in data.items()
    }


def _q4(tables, how="semi", date="1993-07-01", months=3):
    """(the two filtered sides, Q4 over them), as the query module says it."""
    d0, d1 = q4_reference.quarter(date, months)
    orders = tables["orders"].lazy().filter(
        (col("o_orderdate") >= lit(d0)) & (col("o_orderdate") < lit(d1))
    )
    lineitem = tables["lineitem"].lazy().filter(
        col("l_commitdate") < col("l_receiptdate")
    )
    return orders, lineitem, (
        orders.join(lineitem, left_on="o_orderkey", right_on="l_orderkey", how=how)
        .groupby("o_orderpriority", {"o_orderkey": "count"})
        .sort("o_orderpriority")
    )


# a year and not a quarter, so that a few thousand rows leave every
# priority orders with and without a late line
DATE = "1993-01-01"


@pytest.fixture(scope="module")
def q4_data():
    return tpch_q4_tables.make(CONFIG, 2**31 + 41, 12_000)


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("mode", ["lazy", "eager"])
def test_q4_equals_the_reference(ctxs, q4_data, world, how, mode):
    want = q4_reference.q4(q4_data, DATE, 12)
    assert want["semi"]["order_count"].min() > 0
    assert want["anti"]["order_count"].min() > 0
    tables = _tables(ctxs[world], q4_data)
    orders, lineitem, query = _q4(tables, how, DATE, 12)
    if mode == "lazy":
        got = query.collect().to_pydict()
        assert list(got["o_orderpriority"]) == list(want[how]["o_orderpriority"])
        assert np.array_equal(got["o_orderkey_count"], want[how]["order_count"])
        return
    kept = orders.collect().distributed_join(
        lineitem.collect(), left_on=["o_orderkey"], right_on=["l_orderkey"],
        how=how,
    )
    assert kept.column_names == ["o_orderkey", "o_orderdate", "o_orderpriority"]
    keys = kept.to_pydict()["o_orderkey"]
    if world == 1:
        assert np.array_equal(keys, want[how + "_keys"])  # the left's order
    else:
        assert np.array_equal(np.sort(keys), np.sort(want[how + "_keys"]))


# ----------------------------------------------------------------------
# the operator, case by case, against pandas
# ----------------------------------------------------------------------
def _frames(case, rng):
    n, m = 300, 220
    left = pd.DataFrame({
        "k": rng.integers(0, 120, n).astype(np.int32),
        "x": rng.normal(size=n),
        "row": np.arange(n, dtype=np.int64),
    })
    right = pd.DataFrame({
        "rk": rng.integers(60, 200, m).astype(np.int32),
        "y": rng.normal(size=m),
    })
    on = (["k"], ["rk"])
    if case == "duplicate_right":
        right = pd.concat([right] * 3, ignore_index=True)
    elif case == "null_left":
        left["k"] = left["k"].astype("float64")
        left.loc[::7, "k"] = np.nan
        right["rk"] = right["rk"].astype("float64")
    elif case == "null_right":
        # a null on BOTH sides: EXISTS pairs no null with a null
        left["k"] = left["k"].astype("float64")
        left.loc[::11, "k"] = np.nan
        right["rk"] = right["rk"].astype("float64")
        right.loc[::5, "rk"] = np.nan
    elif case == "empty_right":
        right = right.iloc[:0]
    elif case == "empty_left":
        left = left.iloc[:0]
    elif case == "two_column_key":
        left["k2"] = rng.integers(0, 3, n).astype(np.int32)
        right["rk2"] = rng.integers(0, 3, m).astype(np.int32)
        on = (["k", "k2"], ["rk", "rk2"])
    elif case == "string_key":
        left["k"] = np.array([f"id{v:03d}" for v in left["k"]], object)
        right["rk"] = np.array([f"id{v:03d}" for v in right["rk"]], object)
    elif case == "int64_key":
        left["k"] = left["k"].astype(np.int64) * (1 << 33)
        right["rk"] = right["rk"].astype(np.int64) * (1 << 33)
    return left, right, on


def _hits(left, right, on):
    """Which left rows have a partner: a null key has none."""
    lk = [left[c] for c in on[0]]
    rk = [right[c] for c in on[1]]
    r_ok = ~np.logical_or.reduce([c.isna() for c in rk]) if len(right) else []
    partners = set(zip(*[c[r_ok] for c in rk])) if len(right) else set()
    l_ok = ~np.logical_or.reduce([c.isna() for c in lk])
    return np.array(
        [ok and key in partners for ok, key in zip(l_ok, zip(*lk))], bool
    ).reshape(len(left))


CASES = [
    "plain", "duplicate_right", "null_left", "null_right", "empty_right",
    "empty_left", "two_column_key", "string_key", "int64_key",
]


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("case,world", [
    # an empty pandas frame has no shard to lay out on a mesh
    (c, w) for c in CASES for w in (1, 4) if (c, w) != ("empty_left", 4)
])
def test_operator_case_by_case(ctxs, case, how, world):
    left, right, on = _frames(case, np.random.default_rng(len(case)))
    keep = KEEP[how](_hits(left, right, on))
    ctx = ctxs[world]
    tl, tr = ct.Table.from_pandas(ctx, left), ct.Table.from_pandas(ctx, right)
    got = tl.distributed_join(tr, left_on=on[0], right_on=on[1], how=how)
    assert got.column_names == list(left.columns)  # left's names, no suffix
    rows = got.to_pydict()["row"]
    want = left["row"].to_numpy()[keep]
    assert len(set(rows.tolist())) == len(rows)  # each left row at most once
    if world == 1:
        assert np.array_equal(rows, want)  # and in the left's order
    else:
        assert np.array_equal(np.sort(rows), want)
    # the same through the planner
    lazy = tl.lazy().join(
        tr.lazy(), left_on=on[0], right_on=on[1], how=how
    ).collect()
    assert lazy.column_names == list(left.columns)
    assert np.array_equal(np.sort(lazy.to_pydict()["row"]), want)


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("how", HOWS)
def test_both_masks_ride_the_join(ctxs, how, world):
    """Filters on both sides become the join's masks where their rows do
    not move (one shard), and stay filters under the exchange on a mesh:
    the rows are the same."""
    rng = np.random.default_rng(5)
    left, right, on = _frames("plain", rng)
    ctx = ctxs[world]
    tl, tr = ct.Table.from_pandas(ctx, left), ct.Table.from_pandas(ctx, right)
    query = tl.lazy().filter(col("x") > lit(-0.3)).join(
        tr.lazy().filter(col("y") < lit(0.4)), left_on="k", right_on="rk",
        how=how,
    )
    plan = query.explain().split("== Optimized plan ==")[1]
    if world == 1:
        assert "left-mask" in plan and "right-mask" in plan
        assert "Filter" not in plan
    else:
        assert plan.count("Filter") == 2 and "mask" not in plan
    l2, r2 = left[left.x > -0.3], right[right.y < 0.4]
    keep = KEEP[how](_hits(l2, r2, on))
    got = np.sort(query.collect().to_pydict()["row"])
    assert np.array_equal(got, l2["row"].to_numpy()[keep])


@pytest.mark.parametrize("how", HOWS)
def test_payload_columns_come_back_bit_for_bit(ctxs, how):
    """Every dtype of the left side as payload, in row order, the bits the
    input had; ordering and stats kept as a filter keeps them."""
    rng = np.random.default_rng(8)
    n = 400
    values = rng.normal(size=n) * 10.0 ** rng.integers(-30, 30, n)
    values[::9] = -0.0
    left = pd.DataFrame({
        "k": np.sort(rng.integers(0, 150, n)).astype(np.int32),
        "f64": values,
        "f32": rng.normal(size=n).astype(np.float32),
        "i64": rng.integers(-2**62, 2**62, n),
        "when": np.datetime64("1992-01-01") + rng.integers(0, 2000, n).astype("timedelta64[D]"),
        "word": rng.choice(["ab", "cd", "ef", "gh"], n),
        "flag": rng.integers(0, 2, n).astype(bool),
    })
    right = pd.DataFrame({"rk": rng.integers(50, 250, 200).astype(np.int32)})
    tl = ct.Table.from_pandas(ctxs[1], left).sort("k")
    tr = ct.Table.from_pandas(ctxs[1], right)
    assert tl.ordering is not None
    tl.ensure_stats(["k"])
    got = tl.join(tr, left_on="k", right_on="rk", how=how)
    keep = KEEP[how](left["k"].isin(right["rk"]).to_numpy())
    before = tl.to_pydict()
    after = got.to_pydict()
    for name, column in before.items():
        a, b = np.asarray(column)[keep], np.asarray(after[name])
        if a.dtype.kind == "f":
            bits = {4: np.uint32, 8: np.uint64}[a.dtype.itemsize]
            a, b = a.view(bits), b.view(bits)
        assert np.array_equal(a, b), name
    assert got.ordering == tl.ordering
    assert got.column_stats.get("k") == tl.column_stats.get("k")
    # a column without a validity lane keeps none
    assert all(got.column(c).valid is None for c in got.column_names)


def test_semi_plus_anti_is_the_left_side_and_semi_is_the_unique_inner_join(ctxs):
    rng = np.random.default_rng(13)
    left, right, on = _frames("duplicate_right", rng)
    tl = ct.Table.from_pandas(ctxs[1], left)
    tr = ct.Table.from_pandas(ctxs[1], right)
    semi = tl.join(tr, left_on="k", right_on="rk", how="semi")
    anti = tl.join(tr, left_on="k", right_on="rk", how="anti")
    rows = np.concatenate([semi.to_pydict()["row"], anti.to_pydict()["row"]])
    assert np.array_equal(np.sort(rows), left["row"].to_numpy())
    inner = tl.join(
        tr.project(["rk"]).unique(), left_on="k", right_on="rk", how="inner"
    ).project(list(left.columns))
    assert inner.column_names == semi.column_names
    for name, column in semi.to_pydict().items():
        assert np.array_equal(column, inner.to_pydict()[name]), name


@pytest.mark.parametrize("how", HOWS)
def test_positions_that_do_not_fit_the_word_take_the_wide_sort(
    ctxs, how, monkeypatch
):
    """``semi_capable`` says no (forced here at a small word): the same
    program carries the dead flag through its sort as an operand of its
    own, and the rows are the same."""
    rng = np.random.default_rng(21)
    left, right, on = _frames("plain", rng)
    keep = KEEP[how](_hits(left, right, on))
    monkeypatch.setattr(_j, "_SEMI_DEAD", 1 << 6)
    ctx = ctxs[1]
    tl, tr = ct.Table.from_pandas(ctx, left), ct.Table.from_pandas(ctx, right)
    assert not _j.semi_capable(tl.shard_cap, tr.shard_cap)
    m = tl.column("x").data > -0.5
    got = tl.join(tr, left_on="k", right_on="rk", how=how, _left_mask=m)
    want = left["row"].to_numpy()[keep & (left.x > -0.5).to_numpy()]
    assert np.array_equal(got.to_pydict()["row"], want)
    wide = [
        key for key, _f, _s in stages.dispatched_programs(ctx)
        if key[0] == "join_semi_rows" and key[7]
    ]
    assert wide
    # and as the aggregate's mask
    count = tl.lazy().join(
        tr.lazy(), left_on="k", right_on="rk", how=how
    ).agg({"row": "count"}).collect().to_pydict()["row_count"]
    assert int(count[0]) == int(keep.sum())


# ----------------------------------------------------------------------
# the planner
# ----------------------------------------------------------------------
def _explain_pair(ctx):
    rng = np.random.default_rng(34)
    left, right, _on = _frames("plain", rng)
    left["word"] = rng.choice(["ab", "cd", "ef"], len(left))
    return ct.Table.from_pandas(ctx, left), ct.Table.from_pandas(ctx, right)


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("shape,fires", [
    ("dense_groupby", True), ("keyless_agg", True), ("sorted_groupby", False),
])
def test_explain_names_semi_as_mask_where_it_fired(ctxs, how, shape, fires):
    tl, tr = _explain_pair(ctxs[1])
    joined = tl.lazy().join(tr.lazy(), left_on="k", right_on="rk", how=how)
    query = {
        "dense_groupby": lambda: joined.groupby("word", {"row": "count"}),
        "keyless_agg": lambda: joined.agg({"x": "sum", "row": "count"}),
        # a float key has no dense plan: the group-by sorts its rows
        "sorted_groupby": lambda: joined.groupby("x", {"row": "count"}),
    }[shape]()
    text = query.explain()
    assert ("semi_as_mask x1" in text) is fires
    assert ("[semi_as_mask: no row compacted" in text) is fires
    before = tracing.get_count("plan.rule.semi_as_mask")
    got = query.collect()
    assert tracing.get_count("plan.rule.semi_as_mask") - before == int(fires)
    left, right = tl.to_pandas(), tr.to_pandas()
    keep = KEEP[how](left["k"].isin(right["rk"]))
    if shape == "keyless_agg":
        out = got.to_pydict()
        assert int(out["row_count"][0]) == int(keep.sum())
        np.testing.assert_allclose(out["x_sum"][0], left["x"][keep].sum(), rtol=1e-12)
    else:
        by = "word" if shape == "dense_groupby" else "x"
        want = left[keep].groupby(by)["row"].count()
        out = got.to_pandas().sort_values(by)
        assert np.array_equal(out["row_count"].to_numpy(), want.to_numpy())


def test_q4_plan_masks_the_join_fetches_once_and_gathers_nothing(ctxs, q4_data):
    tables = _tables(ctxs[1], q4_data)
    _orders, _lineitem, query = _q4(tables)
    text = query.explain()
    plan = text.split("== Optimized plan ==")[1]
    assert "Filter" not in plan
    assert "left-mask" in plan and "right-mask" in plan
    assert "join_mask x2" in text and "semi_as_mask x1" in text
    # what only the masks read goes no further than the masks
    assert "Join how=semi on [o_orderkey=l_orderkey]" in plan
    query.collect().row_count  # warm
    tracing_before = tracing.snapshot()
    syncs = tracing.get_count("host_sync")
    query.collect().row_count
    assert tracing.get_count("host_sync") - syncs == 1
    after = tracing.snapshot()

    def moved(name, field="count"):
        return after.get(name, {}).get(field, 0) - tracing_before.get(
            name, {}
        ).get(field, 0)

    assert moved("join.semi.payload_rows") == 1
    assert moved("join.semi.payload_rows", "rows") == 0
    assert moved("join.semi.kept_rows") == 0  # no count was fetched
    assert moved("dispatch.join_semi_rows") == 1
    assert moved("dispatch.groupby_dense") == 1
    for name in ("dispatch.join_semi_take", "dispatch.filter",
                 "dispatch.join_spec", "dispatch.join_semi"):
        assert moved(name) == 0, name


def test_a_filter_over_a_semi_join_goes_under_it(ctxs):
    tl, tr = _explain_pair(ctxs[1])
    query = tl.lazy().join(
        tr.lazy(), left_on="k", right_on="rk", how="anti"
    ).filter(col("x") > lit(0.0))
    text = query.explain()
    plan = text.split("== Optimized plan ==")[1]
    assert "filter_pushdown x1" in text and "join_mask x1" in text
    assert "left-mask (col('x') > 0.0)" in plan
    # the right side keeps its key alone
    assert "Project [rk]" in plan
    left, right = tl.to_pandas(), tr.to_pandas()
    keep = ~left["k"].isin(right["rk"]) & (left["x"] > 0.0)
    assert np.array_equal(
        query.collect().to_pydict()["row"], left["row"].to_numpy()[keep]
    )


def test_the_two_types_and_their_spellings():
    assert _j.join_type_id("semi") == _j.join_type_id("left_semi") == _j.SEMI
    assert _j.join_type_id("anti") == _j.join_type_id("LEFT-ANTI") == _j.ANTI
    assert _j.SEMI_TYPES == (_j.SEMI, _j.ANTI)
    with pytest.raises(ValueError):
        _j.join_type_id("right_semi")
    # the pair shuffle may prune a semi join's left side, an anti join's
    # neither (a left row without a partner is what it emits)
    assert _sketch.join_filter_sides("semi") == "a"
    assert _sketch.join_filter_sides("left_semi") == "a"
    assert _sketch.join_filter_sides("anti") is None
    assert _sketch.join_filter_sides("inner") == "both"


def test_what_a_semi_join_refuses(ctxs):
    tl, tr = _explain_pair(ctxs[4])
    with pytest.raises(ValueError, match="mode='eager'"):
        tl.distributed_join(tr, left_on=["k"], right_on=["rk"], how="semi",
                            mode="fused")
    with pytest.raises(ValueError, match="emit_order"):
        tl.join(tr, left_on="k", right_on="rk", how="anti", emit_order="key")
    spelled = tl.lazy().join(
        tr.lazy(), left_on="k", right_on="rk", how="left_semi"
    )
    assert "Join how=semi" in spelled.explain()


# ----------------------------------------------------------------------
# observability
# ----------------------------------------------------------------------
def test_spans_counters_stage_and_fetch_site_are_declared(ctxs):
    tl, tr = _explain_pair(ctxs[1])
    tl.join(tr, left_on="k", right_on="rk", how="semi").row_count
    names = tracing.snapshot()
    for name in (
        "join.semi_join", "join.semi.left_rows", "join.semi.kept_rows",
        "join.semi.payload_rows", "host_sync.join.semi_join",
        "dispatch.join_semi_rows", "dispatch.join_semi_take",
    ):
        assert name in names, name
        assert obs_metrics.is_declared(name), name
    for name in ("join.semi_join", "join.semi.left_rows",
                 "join.semi.kept_rows", "join.semi.payload_rows",
                 "plan.rule.semi_as_mask"):
        assert name in obs_metrics.STABLE_METRICS, name
    assert stages.FETCH_SITES["join.semi_join"] is True
    assert stages.JOIN_SEMI_MASK == "join.semi_mask" in stages.VOCABULARY
    text = obs_export.prometheus_text()
    assert obs_export.validate_prometheus(text) == []
    for name in ("join_semi_payload_rows", "join_semi_kept_rows",
                 "join_semi_join"):
        assert name in text, name
    # the record of the call files its one fetch under the site
    rec = ct.obs.last_ops(1)[0]
    assert rec["name"] == "join" and "join.semi_join" in rec["sites"]


def _stage_names(fn, spec):
    text = fn.lower(*spec).as_text(debug_info=True)
    return {
        part for path in stages._PATH.findall(text)
        for part in path.split("/") if part in stages.VOCABULARY
    }


def test_the_operator_runs_under_its_two_stages_and_the_inner_join_as_before(ctxs):
    """The semi and anti joins' keys-only program holds ``join.semi`` and
    ``join.semi_mask``; an INNER join that a filter rides keeps its two
    programs, their keys and their stage names, and no ``join.semi_mask``."""
    ctx = ctxs[1]
    tl, tr = _explain_pair(ctx)
    tl.lazy().filter(col("x") > lit(-0.5)).join(
        tr.lazy().filter(col("y") < lit(0.5)), left_on="k", right_on="rk"
    ).collect().row_count
    tl.lazy().join(
        tr.lazy(), left_on="k", right_on="rk", how="semi"
    ).agg({"row": "count"}).collect().row_count
    by_name = {}
    for key, fn, spec in stages.dispatched_programs(ctx):
        by_name.setdefault(fn.__name__, []).append((key, fn, spec))
    (key, fn, spec), = [
        p for p in by_name["join_semi"] if p[0][2:4] == (True, True)
    ]
    assert key[:5] == ("join_semi", ((0,), (0,)), True, True, None)
    assert _stage_names(fn, spec) == {
        "join.key_ids", "join.semi", "sort_engine"
    }
    # one merged sort and one one-operand sort, two run scans between
    text = fn.lower(*spec).as_text()
    assert text.count("stablehlo.sort") == 2
    key, fn, spec = by_name["join_reduce"][0]
    assert key[:3] == ("join_reduce", 4, 2)
    assert _stage_names(fn, spec) == {"join.semi"}
    masks = [p for p in by_name["join_semi_rows"] if p[0][6]]  # as_mask
    assert masks
    key, fn, spec = masks[0]
    assert _stage_names(fn, spec) == {
        "join.key_ids", "join.semi", "join.semi_mask", "sort_engine"
    }
    assert fn.lower(*spec).as_text().count("stablehlo.sort") == 2


# ----------------------------------------------------------------------
# what the two cells found on the way: a plan built anew traces nothing,
# and a long string column is dictionary-coded without python objects
# ----------------------------------------------------------------------
def test_a_plan_built_anew_for_every_literal_traces_and_compiles_nothing(ctxs):
    """Q1's shape with its DELTA drawn a query: the schema's dtype
    promotion is asked of JAX once a combination (``plan/expr
    ._binop_dtype``), and the literal is an argument of the program."""
    rng = np.random.default_rng(55)
    n = 500
    t = ct.Table.from_pydict(ctxs[1], {
        "flag": rng.choice(["A", "N", "R"], n),
        "price": rng.uniform(1, 100, n), "disc": rng.integers(0, 11, n) / 100.0,
        "day": np.datetime64("1998-01-01") + rng.integers(0, 400, n).astype("timedelta64[D]"),
    })

    def query(delta):
        cutoff = np.datetime64("1998-12-01") - np.timedelta64(delta, "D")
        return (
            t.lazy().filter(col("day") <= lit(cutoff))
            .with_columns({"net": col("price") * (1 - col("disc"))})
            .groupby("flag", {"net": "sum", "price": "mean"}).sort("flag")
        )

    for delta in (60, 61):  # warm: every program, both sides of the cache
        query(delta).collect().row_count
    events = []

    def on(event, duration, **_kw):
        if "compile" in event:
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    got = {d: query(d).collect().to_pydict() for d in (62, 90, 120, 62)}
    del on  # the listener stays registered; it only appends
    assert events == [], sorted(set(events))
    day = np.asarray(t.to_pydict()["day"]).astype("datetime64[D]")
    keep = day <= np.datetime64("1998-12-01") - np.timedelta64(120, "D")
    flags = np.asarray(t.to_pydict()["flag"], object)[keep]
    assert list(got[120]["flag"]) == sorted(set(flags))


@pytest.mark.parametrize("values", [
    np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"])[
        np.random.default_rng(1).integers(0, 5, 5000)],
    np.array(["b", "", "a", "\u00fc", "zz", "a"], dtype="<U8"),
    np.array(["x"] * 3, dtype="<U4"),
    np.array([f"id{v:05d}" for v in np.random.default_rng(2).integers(0, 3000, 4000)]),
], ids=["priorities", "empty-and-wide", "one-value", "many-values"])
def test_a_numpy_string_column_is_coded_as_np_unique_codes_it(values):
    codes, valid, dtype, dictionary = Column.encode_host(values)
    want, inverse = np.unique(
        np.asarray(np.asarray(values, object), str), return_inverse=True
    )
    assert valid is None and dtype.is_dictionary
    assert dictionary.dtype == want.dtype and np.array_equal(dictionary, want)
    assert codes.dtype == np.int32 and np.array_equal(codes, inverse)
