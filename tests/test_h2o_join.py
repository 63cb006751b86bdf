"""The h2oai/db-benchmark join questions on meshes of 1, 4 and 8, and the
replicate route of ``Table.distributed_join`` they brought (PR 48).

- q1, q2, q3 and q5 of ``h2o_join_reference.py`` by the integer ids and q4
  by the string id (N = 40,000; small, medium and big of 10, 40 and 40,000
  rows: the source's 1,000:1 for medium) against the plain reference:
  rows, columns, which rows are null, the per-key sums, the source's chk;
- the route is the tables' sizes' alone: q1-q4 replicate the right side,
  q5 shuffles, and one question through both routes gives one result;
- the legal side by join type (``left`` never replicates its left side,
  ``outer`` never replicates), by the route counters;
- a count the host does not hold takes the shuffle route and nothing is
  fetched to decide;
- the replicate route's result lies where the big side lay, in its order;
- the shapes of ``join-w4`` and ``join-skew-w4`` stay on the shuffle route;
- ``Table.lazy().join(...)`` takes the same route and ``explain()`` names
  it; a group-by above it keeps its own Shuffle.
"""
import functools

import jax
import numpy as np
import numpy.testing as npt
import pytest

import cylon_tpu as ct
from cylon_tpu.config import REPLICATE_JOIN_MIN_RATIO
from cylon_tpu.obs import stages
from cylon_tpu.ops import join as _j
from cylon_tpu.utils import tracing

import h2o_join_reference as h2o

N = 40_000
LEVELS = (10, 40, N)
WORLDS = [1, 4, 8]
MESHES = [4, 8]
REPLICATE, SHUFFLE = "join.route.replicate", "join.route.shuffle"
SHUFFLE_STAGES = ("shuffle.count", "shuffle.exchange", "shuffle.rounds")


@functools.lru_cache(maxsize=None)
def _ctx(world):
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:world])
    )


@functools.lru_cache(maxsize=None)
def _data(seed=48):
    return h2o.make(N, seed, LEVELS)


@functools.lru_cache(maxsize=None)
def _tables(world, seed=48):
    return {
        name: ct.Table.from_numpy(_ctx(world), list(cols), list(cols.values()))
        for name, cols in _data(seed).items()
    }


@functools.lru_cache(maxsize=None)
def _answer(question, seed=48):
    return h2o.answer(_data(seed), question)


def _routes():
    return {k: tracing.get_count(k) for k in (REPLICATE, SHUFFLE)}


def _took(before) -> dict:
    return {k: v - before[k] for k, v in _routes().items() if v != before[k]}


def _ask(world, question, right=None):
    right_name, key, how = h2o.QUESTIONS[question]
    t = _tables(world)
    return t["x"].distributed_join(
        t[right_name] if right is None else right, on=key, how=how
    )


def _by_x_row(table) -> tuple:
    """The result's columns and null masks with its rows in the order of
    ``x``'s unique ``id3`` (a row of ``x`` comes out at most once)."""
    names = table.column_names
    id3 = "id3" if "id3" in names else "id3_x"
    got = table.to_pandas()
    order = np.argsort(got[id3].to_numpy(), kind="stable")
    cols = {c: got[c].to_numpy()[order] for c in names}
    return cols, {c: got[c].isna().to_numpy()[order] for c in names}


def _assert_answer(table, want: dict):
    assert table.row_count == want["rows"]
    assert table.column_names == list(want["columns"])
    order = np.argsort(want["columns"].get(
        "id3", want["columns"].get("id3_x")), kind="stable")
    cols, nulls = _by_x_row(table)
    for name, values in want["columns"].items():
        null = want["nulls"].get(name)
        null = np.zeros(len(values), bool) if null is None else null
        npt.assert_array_equal(nulls[name], null[order], err_msg=name)
        live = ~null[order]
        npt.assert_array_equal(
            cols[name][live], values[order][live], err_msg=name
        )


# ----------------------------------------------------------------------
# the five questions against the reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("world", WORLDS)
@pytest.mark.parametrize("question", sorted(h2o.QUESTIONS))
def test_question_against_the_reference(world, question):
    out = _ask(world, question)
    want = _answer(question)
    _assert_answer(out, want)
    # exactly one copy of the result over the mesh
    assert int(out.row_counts.sum()) == want["rows"]


@pytest.mark.parametrize("world", MESHES)
@pytest.mark.parametrize("question", ["q1", "q2", "q3", "q5"])
def test_chk_and_per_key_sums(world, question):
    """The source's own check and the sums a key, from the result."""
    want = _answer(question)
    cols, nulls = _by_x_row(_ask(world, question))
    matched = ~nulls["v2"]
    npt.assert_array_equal(
        matched, want["matched"][np.argsort(
            want["columns"].get("id3", want["columns"].get("id3_x")))]
    )
    got = {
        "rows": len(matched), "matched": matched, "key": want["key"],
        "columns": {c: np.where(nulls[c], 0, cols[c]) for c in cols},
    }
    npt.assert_allclose(h2o.chk(got), h2o.chk(want), rtol=1e-12)
    sums, ref = h2o.per_key_sums(got), h2o.per_key_sums(want)
    npt.assert_array_equal(sums["keys"], ref["keys"])
    npt.assert_array_equal(sums["rows"], ref["rows"])
    for v in ("v1", "v2"):
        npt.assert_allclose(sums[v], ref[v], rtol=1e-12, atol=0)


def test_the_law_of_the_generator():
    """What the issue states of the data: a right table holds each key
    once, about 90% of x's rows find a partner in medium, a tenth of
    medium's rows match nothing, and q3 has exactly N rows."""
    data = _data()
    for name, key in (("small", "id1"), ("medium", "id2"), ("big", "id3")):
        keys = data[name][key]
        assert len(np.unique(keys)) == len(keys) == LEVELS[
            ("small", "medium", "big").index(name)
        ]
    q3 = _answer("q3")
    assert q3["rows"] == N
    assert 0.85 < q3["matched"].mean() < 0.95
    used = np.isin(data["medium"]["id2"], data["x"]["id2"])
    assert (~used).sum() == h2o.one_side_only(LEVELS[1])
    # every key of its side at least once
    assert len(np.unique(data["x"]["id2"])) == LEVELS[1]
    assert (data["x"]["id5"] == np.char.add(
        "id", data["x"]["id2"].astype(str))).all()


# ----------------------------------------------------------------------
# the route: by the sizes alone
# ----------------------------------------------------------------------
@pytest.mark.parametrize("world", MESHES)
@pytest.mark.parametrize("question,route", [
    ("q1", REPLICATE), ("q2", REPLICATE), ("q3", REPLICATE),
    ("q4", REPLICATE), ("q5", SHUFFLE),
])
def test_route_by_table_sizes(world, question, route):
    before = _routes()
    snap = tracing.snapshot()
    out = _ask(world, question)
    assert _took(before) == {route: 1}
    after = tracing.snapshot()
    moved = [
        s for s in SHUFFLE_STAGES
        if after.get(s, {}).get("count", 0) != snap.get(s, {}).get("count", 0)
    ]
    if route == REPLICATE:
        assert moved == [], moved
        # sharded as x was: the big side crossed nothing
        npt.assert_array_equal(
            out.row_counts if question == "q3" else out.row_counts > 0,
            _tables(world)["x"].row_counts if question == "q3"
            else np.ones(world, bool),
        )
    else:
        assert moved, "the shuffle route ran no shuffle"


def test_world_one_takes_no_route():
    before = _routes()
    _ask(1, "q3")
    assert _took(before) == {}


@pytest.mark.parametrize("world", MESHES)
@pytest.mark.parametrize("question", ["q2", "q3"])
def test_both_routes_give_one_result(world, question):
    """The same question with medium's count still on the device takes
    the shuffle route (below), and the two results are one."""
    t = _tables(world)
    medium = t["medium"]
    deferred = medium.filter(medium.column("v2").data >= 0.0)
    assert deferred._counts_host is None
    before = _routes()
    shuffled = _ask(world, question, right=deferred)
    assert _took(before) == {SHUFFLE: 1}
    before = _routes()
    replicated = _ask(world, question)
    assert _took(before) == {REPLICATE: 1}
    a, a_null = _by_x_row(shuffled)
    b, b_null = _by_x_row(replicated)
    assert list(a) == list(b)
    for c in a:
        npt.assert_array_equal(a_null[c], b_null[c], err_msg=c)
        npt.assert_array_equal(a[c][~a_null[c]], b[c][~b_null[c]], err_msg=c)


def test_a_count_not_on_the_host_fetches_nothing_to_decide():
    t = _tables(4)
    medium = t["medium"]
    deferred = medium.filter(medium.column("v2").data >= 0.0)
    syncs = tracing.get_count("host_sync")
    assert deferred._host_size() is None
    assert _j.replicate_side(
        "left", t["x"]._host_size(), deferred._host_size(), 4
    ) is None
    assert tracing.get_count("host_sync") == syncs
    assert deferred._counts_host is None
    # and either way round
    assert _j.replicate_side(
        "inner", deferred._host_size(), t["x"]._host_size(), 4
    ) is None


@pytest.mark.parametrize("how,small_right,small_left", [
    ("inner", REPLICATE, REPLICATE),
    ("left", REPLICATE, SHUFFLE),
    ("right", SHUFFLE, REPLICATE),
    ("outer", SHUFFLE, SHUFFLE),
    ("semi", REPLICATE, SHUFFLE),
    ("anti", REPLICATE, SHUFFLE),
])
def test_legal_side_by_join_type(how, small_right, small_left):
    """Which side may be replicated follows from the join type: asked
    with the small table on either side, by the route counters; and the
    result is pandas' either way."""
    t = _tables(4)
    data = _data()
    import pandas as pd

    x = pd.DataFrame({c: data["x"][c] for c in ("id2", "v1")})
    m = pd.DataFrame({c: data["medium"][c] for c in ("id2", "v2")})
    tx, tm = t["x"].project(["id2", "v1"]), t["medium"].project(["id2", "v2"])
    for (lt, ldf), (rt, rdf), route in (
        ((tx, x), (tm, m), small_right), ((tm, m), (tx, x), small_left),
    ):
        before = _routes()
        out = lt.distributed_join(rt, on="id2", how=how)
        assert _took(before) == {route: 1}, (how, route)
        if how in ("semi", "anti"):
            hit = ldf.id2.isin(rdf.id2)
            want = ldf[hit if how == "semi" else ~hit]
            assert out.column_names == list(ldf.columns)
        else:
            want = ldf.merge(rdf, on="id2", how=how)
            lv, rv = ldf.columns[1], rdf.columns[1]
            assert out.column_names == ["id2_x", lv, "id2_y", rv]
        assert out.row_count == len(want)
        got = out.to_pandas()
        for v in [c for c in got.columns if c.startswith("v")]:
            npt.assert_allclose(
                np.sort(got[v].dropna().to_numpy()),
                np.sort(want[v].dropna().to_numpy()), rtol=0, atol=0,
            )
            assert got[v].isna().sum() == want[v].isna().sum()


def test_the_rule_is_the_constant():
    """One part in REPLICATE_JOIN_MIN_RATIO of a chip's share, in bytes."""
    k = REPLICATE_JOIN_MIN_RATIO
    big = (k * 4 * 100, 8)
    assert _j.replicate_side("inner", big, (100, 8), 4) == "right"
    assert _j.replicate_side("inner", big, (101, 8), 4) is None
    assert _j.replicate_side("inner", big, (50, 16), 4) == "right"
    assert _j.replicate_side("inner", big, (51, 16), 4) is None
    assert _j.replicate_side("inner", (100, 8), big, 4) == "left"
    assert _j.replicate_side("inner", big, (100, 8), 1) is None
    # the source's ratios replicate, the accepted cells' do not
    assert _j.replicate_side("left", (10**8, 20), (10**5, 16), 4) == "right"
    assert _j.replicate_side("inner", (10**8, 20), (10**2, 12), 4) == "right"
    assert _j.replicate_side("inner", (10**8, 20), (10**8, 36), 4) is None
    assert _j.replicate_side("inner", (4 * 10**6, 16), (4 * 10**6, 16), 4) is None
    assert _j.replicate_side("inner", (16 * 10**6, 16), (10**6, 16), 4) is None


@pytest.mark.parametrize("rows_l,rows_r,cell", [
    (4_000, 4_000, "join-w4"), (16_000, 1_000, "join-skew-w4"),
])
def test_the_accepted_join_cells_shapes_shuffle(rows_l, rows_r, cell):
    """join-w4 (1:1) and join-skew-w4 (16:1) at a small size: the shuffle
    route, as before PR 48."""
    rng = np.random.default_rng(7)
    ctx = _ctx(4)
    left = ct.Table.from_numpy(ctx, ["k", "v"], [
        rng.integers(0, rows_r, rows_l).astype(np.int64),
        rng.random(rows_l),
    ])
    right = ct.Table.from_numpy(ctx, ["k", "v"], [
        rng.permutation(rows_r).astype(np.int64), rng.random(rows_r),
    ])
    before = _routes()
    out = left.distributed_join(right, on="k", how="inner")
    assert _took(before) == {SHUFFLE: 1}, cell
    assert out.row_count == rows_l


# ----------------------------------------------------------------------
# where the result lies
# ----------------------------------------------------------------------
@pytest.mark.parametrize("world", MESHES)
def test_replicate_keeps_the_big_sides_shards_and_order(world):
    t = _tables(world)
    x = t["x"]
    out = _ask(world, "q3")
    npt.assert_array_equal(out.row_counts, x.row_counts)
    got, want = out.to_pydict(), x.to_pydict()
    # shard by shard, row by row: x's rows where they lay, in their order
    npt.assert_array_equal(got["id3"], want["id3"])
    npt.assert_array_equal(got["v1"], want["v1"])
    # the inner join keeps the rows that match, still in x's order a shard
    inner = _ask(world, "q2")
    keep = np.isin(want["id2"], _data()["medium"]["id2"])
    npt.assert_array_equal(inner.to_pydict()["id3"], want["id3"][keep])
    cuts = np.cumsum(x.row_counts)[:-1]
    npt.assert_array_equal(
        inner.row_counts, [k.sum() for k in np.split(keep, cuts)]
    )


@pytest.mark.parametrize("world", MESHES)
def test_left_side_replicated_runs_from_the_big_side(world):
    """medium RIGHT JOIN x: the left side is the small one; the result is
    sharded as x was, in x's order, columns left first."""
    t = _tables(world)
    x, medium = t["x"].project(["id2", "id3", "v1"]), t["medium"].project(
        ["id2", "v2"])
    before = _routes()
    out = medium.distributed_join(x, on="id2", how="right")
    assert _took(before) == {REPLICATE: 1}
    assert out.column_names == ["id2_x", "v2", "id2_y", "id3", "v1"]
    npt.assert_array_equal(out.row_counts, x.row_counts)
    npt.assert_array_equal(out.to_pydict()["id3"], x.to_pydict()["id3"])
    want = _answer("q3")
    got = out.to_pandas().sort_values("id3")
    order = np.argsort(want["columns"]["id3"])
    npt.assert_array_equal(
        got["v2"].isna().to_numpy(), want["nulls"]["v2"][order]
    )


def test_the_ordering_descriptor_is_the_big_sides():
    t = _tables(4)
    x = t["x"].project(["id2", "id3", "v1"]).sort("id3")
    assert x.ordering is not None
    out = x.distributed_join(t["medium"].project(["id2", "v2"]), on="id2",
                             how="left")
    assert out.ordering is not None and out.ordering.keys == ("id3",)


def test_no_replicated_table_escapes():
    """The gathered side lives inside the route: the result's shards hold
    one copy, and the small table handed in is as it was."""
    t = _tables(4)
    medium = t["medium"]
    counts = medium.row_counts.copy()
    out = _ask(4, "q2")
    npt.assert_array_equal(medium.row_counts, counts)
    assert out.row_count == _answer("q2")["rows"]
    rep = medium._replicated()  # what the route holds, for the record
    assert rep.row_counts.tolist() == [LEVELS[1]] * 4


def test_counters_of_the_route():
    t = _tables(4)
    snap = tracing.snapshot()
    _ask(4, "q3")
    after = tracing.snapshot()

    def moved(name, field="rows"):
        return after[name][field] - snap.get(name, {}).get(field, 0)

    assert moved(REPLICATE, "count") == 1
    assert moved(REPLICATE) == N + LEVELS[1]
    assert moved("join.replicate.rows") == LEVELS[1] * 3
    assert moved("join.replicate", "count") == 1
    assert t["x"].row_count == N


# ----------------------------------------------------------------------
# the planner takes the same route
# ----------------------------------------------------------------------
@pytest.mark.parametrize("world", MESHES)
@pytest.mark.parametrize("question", ["q2", "q3", "q5"])
def test_lazy_join_takes_the_same_route(world, question):
    right_name, key, how = h2o.QUESTIONS[question]
    t = _tables(world)
    plan = t["x"].lazy().join(t[right_name].lazy(), on=key, how=how)
    text = plan.explain()
    before = _routes()
    out = plan.collect()
    if question == "q5":
        assert _took(before) == {SHUFFLE: 1}
        assert "route=replicate" not in text and "Shuffle hash" in text
    else:
        assert _took(before) == {REPLICATE: 1}
        assert "route=replicate-right" in text
        assert "join_replicate" in text and "Shuffle" not in text
        assert "semi-filter" not in text
        npt.assert_array_equal(out.row_counts > 0, np.ones(world, bool))
    _assert_answer(out, _answer(question))


def test_lazy_groupby_above_a_replicated_join_keeps_its_shuffle():
    """The replicated join claims no placement: a group-by that ships its
    rows (a std has no partial state) keeps its own Shuffle above it, and
    the fused join->groupby, which needs both sides placed, declines."""
    import pandas as pd

    t = _tables(4)
    plan = (
        t["x"].lazy().join(t["medium"].lazy(), on="id2", how="inner")
        .groupby("id2_x", {"v1": ["sum", "std"]})
    )
    text = plan.explain().split("== Optimized plan ==")[1]
    assert "route=replicate-right" in text and "Shuffle hash [id2_x]" in text
    assert "fused_join_groupby" not in text
    got = plan.collect().to_pandas().sort_values("id2_x")
    want = _answer("q2")["columns"]
    ref = pd.DataFrame({"k": want["id2_x"], "v1": want["v1"]}).groupby(
        "k")["v1"].agg(["sum", "std"]).reset_index()
    npt.assert_array_equal(got["id2_x"].to_numpy(), ref["k"].to_numpy())
    npt.assert_allclose(got["v1_sum"].to_numpy(), ref["sum"], rtol=1e-12)
    npt.assert_allclose(got["v1_std"].to_numpy(), ref["std"], rtol=1e-9)


def test_lazy_sum_above_a_replicated_join():
    t = _tables(4)
    plan = (
        t["x"].lazy().join(t["medium"].lazy(), on="id2", how="inner")
        .groupby("id2_x", {"v1": "sum", "v2": "sum"})
    )
    assert "route=replicate-right" in plan.explain()
    got = plan.collect().to_pandas().sort_values("id2_x")
    ref = h2o.per_key_sums(_answer("q2"))
    npt.assert_array_equal(got["id2_x"].to_numpy(), ref["keys"])
    npt.assert_allclose(got["v1_sum"].to_numpy(), ref["v1"], rtol=1e-12)
    npt.assert_allclose(got["v2_sum"].to_numpy(), ref["v2"], rtol=1e-12)


@pytest.mark.parametrize("how", ["semi", "anti"])
@pytest.mark.parametrize("shape", ["dense_groupby", "keyless_agg"])
def test_lazy_semi_join_as_a_mask_takes_the_replicate_route(how, shape):
    """``semi_as_mask`` over a replicated semi or anti join of x with
    medium on four shards: the route is counted once like any other, only
    medium's key column is gathered, and the aggregate reads the verdicts
    over x's rows where they lie."""
    t = _tables(4)
    data = _data()
    joined = t["x"].lazy().join(t["medium"].lazy(), on="id2", how=how)
    # id4, the string twin of id1, has a dense plan (dictionary codes)
    query = (
        joined.groupby("id4", {"v1": "sum", "id3": "count"})
        if shape == "dense_groupby"
        else joined.agg({"v1": "sum", "id3": "count"})
    )
    text = query.explain()
    assert "semi_as_mask x1" in text and "join_replicate x1" in text
    assert "route=replicate-right" in text and "Shuffle" not in text
    before, snap = _routes(), tracing.snapshot()
    got = query.collect().to_pandas()
    assert _took(before) == {REPLICATE: 1}
    after = tracing.snapshot()
    rows = "join.replicate.rows"
    assert after[rows]["rows"] - snap.get(rows, {}).get("rows", 0) == (
        LEVELS[1] * 3)
    lanes = [
        key[1] for key, _f, _s in stages.dispatched_programs(_ctx(4))
        if key[0] == "join_replicate"
    ]
    assert 1 in lanes  # the keys alone, not medium's five columns
    x = data["x"]
    keep = np.isin(x["id2"], data["medium"]["id2"]) == (how == "semi")
    if shape == "keyless_agg":
        assert int(got["id3_count"][0]) == int(keep.sum())
        npt.assert_allclose(got["v1_sum"][0], x["v1"][keep].sum(), rtol=1e-12)
    else:
        got = got.sort_values("id4")
        words, inverse = np.unique(x["id4"][keep], return_inverse=True)
        npt.assert_array_equal(got["id4"].to_numpy().astype(str), words)
        npt.assert_array_equal(
            got["id3_count"].to_numpy(), np.bincount(inverse))
        npt.assert_allclose(
            got["v1_sum"].to_numpy(),
            np.bincount(inverse, weights=x["v1"][keep]), rtol=1e-12)


def test_lazy_filter_under_a_side_takes_the_shuffle_route():
    """A filter's output has no host-known count: both Shuffles stand."""
    from cylon_tpu.plan.expr import col

    t = _tables(4)
    plan = t["x"].lazy().join(
        t["medium"].lazy().filter(col("v2") >= 0.0), on="id2", how="inner"
    )
    text = plan.explain().split("== Optimized plan ==")[1]
    assert "route=replicate" not in text and text.count("Shuffle hash") == 2
    before = _routes()
    out = plan.collect()
    assert _took(before) == {SHUFFLE: 1}
    assert out.row_count == _answer("q2")["rows"]


def test_the_route_is_part_of_the_plans_identity():
    """One plan shape over a small and over an equal right table: two
    fingerprints, so a cached executor never serves the other route."""
    t = _tables(4)
    a = t["x"].lazy().join(t["medium"].lazy(), on="id2", how="inner")
    b = t["x"].lazy().join(t["x"].lazy(), on="id2", how="inner")
    c = t["x"].lazy().join(t["medium"].lazy(), on="id2", how="inner")
    assert a.plan.fingerprint() == c.plan.fingerprint()
    assert a.plan.pick_route() == "right" and b.plan.pick_route() is None
    small = ct.Table.from_numpy(
        _ctx(4), ["id2", "v2"],
        [np.arange(4_000, dtype=np.int32), np.ones(4_000)],
    )
    m2 = t["medium"].project(["id2", "v2"])
    d = t["x"].lazy().join(m2.lazy(), on="id2", how="inner")
    e = t["x"].lazy().join(small.lazy(), on="id2", how="inner")
    assert d.plan.pick_route() == "right" and e.plan.pick_route() is None
    assert d.plan.fingerprint() != e.plan.fingerprint()
