"""The dense low-cardinality group-by, the filter that rides it as a mask,
and expression columns on the planned path (PR 27).

- TPC-H Q1 through ``Table.lazy()`` against the plain numpy reference of
  ``q1_reference.py``, world 1 and world 4, to a tolerance float32 values
  fail;
- the dense path against the factorize path (forced through the internal
  ``_dense`` argument) on the same inputs, column for column, over the
  edge cases; the path a call took read from its rollup counter;
- ``GroupBy(Filter)`` with the mask against filter-then-groupby;
- the ``WithColumns`` node: schema, explain, fingerprint, pruning.
"""
import jax
import numpy as np
import numpy.testing as npt
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import col, lit
from cylon_tpu.column import Column
from cylon_tpu.ops import groupby as _g
from cylon_tpu.plan import nodes, rules
from cylon_tpu.utils import tracing

import q1_reference as ref

#: worst relative gap allowed between the planned Q1 and the reference. A
#: group adds a few thousand float64 values of one sign, so two orders of
#: summation differ by a few units of 2**-53 times the square root of
#: that count, about 1e-14; a float32 value is off by up to 2**-24 = 6e-8,
#: six orders above (``test_float32_values_fail_the_tolerance``).
Q1_RTOL = 1e-12
Q1_ROWS = 6000
KEYS = ["l_returnflag", "l_linestatus"]
RESULT = {
    "l_quantity_sum": "sum_qty", "l_extendedprice_sum": "sum_base_price",
    "disc_price_sum": "sum_disc_price", "charge_sum": "sum_charge",
    "l_quantity_mean": "avg_qty", "l_extendedprice_mean": "avg_price",
    "l_discount_mean": "avg_disc", "l_quantity_count": "count_order",
}
DENSE, FACTORIZE = "groupby.dense_path", "groupby.factorize_path"


def _ctx(world):
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:world])
    )


@pytest.fixture(scope="module", params=[1, 4], ids=lambda w: f"w{w}")
def ctx(request):
    return _ctx(request.param)


@pytest.fixture(scope="module")
def ctx1():
    return _ctx(1)


def _q1(table):
    disc_price = col("l_extendedprice") * (1 - col("l_discount"))
    return (
        table.lazy()
        .filter(col("l_shipdate") <= lit(ref.CUTOFF))
        .with_columns({
            "disc_price": disc_price,
            "charge": disc_price * (1 + col("l_tax")),
        })
        .groupby(KEYS, {
            "l_quantity": ["sum", "mean", "count"],
            "l_extendedprice": ["sum", "mean"],
            "disc_price": "sum", "charge": "sum", "l_discount": "mean",
        })
        .sort(KEYS)
    )


def _load(ctx, li):
    return ct.Table.from_numpy(ctx, list(li), list(li.values()))


def _worst_gap(got, want):
    return max(
        float(np.max(np.abs(got[c] - want[r]) / np.abs(want[r])))
        for c, r in RESULT.items() if r != "count_order"
    )


def _took(before):
    """(dense calls, factorize calls) since ``before``."""
    return (
        tracing.get_count(DENSE) - before[0],
        tracing.get_count(FACTORIZE) - before[1],
    )


def _counts():
    return tracing.get_count(DENSE), tracing.get_count(FACTORIZE)


# -- Q1 against the plain reference ------------------------------------
@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_planned_q1_matches_the_numpy_reference(ctx, seed):
    li = ref.lineitem(seed, Q1_ROWS)
    want = ref.q1(li)
    got = _q1(_load(ctx, li)).collect().to_pydict()
    assert list(got)[:2] == KEYS and sorted(got) == sorted(KEYS + list(RESULT))
    for k in KEYS:  # the groups position for position: also the key order
        assert list(got[k]) == list(want[k])
    assert len(want["count_order"]) == 4
    npt.assert_array_equal(got["l_quantity_count"], want["count_order"])
    assert got["l_quantity_sum"].dtype == np.float64
    assert _worst_gap(got, want) < Q1_RTOL


def test_float32_values_fail_the_tolerance(ctx1):
    li = ref.lineitem(5, Q1_ROWS)
    want = ref.q1(li)
    low = {
        c: a.astype(np.float32).astype(np.float64) if a.dtype == np.float64 else a
        for c, a in li.items()
    }
    got = _q1(_load(ctx1, low)).collect().to_pydict()
    npt.assert_array_equal(got["l_quantity_count"], want["count_order"])
    assert _worst_gap(got, want) > 1e3 * Q1_RTOL


def test_q1_plan_at_world_1_rides_the_mask_and_the_dense_path(ctx1):
    lf = _q1(_load(ctx1, ref.lineitem(7, Q1_ROWS)))
    text = lf.explain()
    assert "Rewrites fired: filter_as_mask x1" in text
    optimized = text.split("== Optimized plan ==")[1]
    assert "Filter" not in optimized and "mask (col('l_shipdate')" in optimized
    assert "WithColumns [disc_price=" in optimized
    before, syncs = _counts(), tracing.get_count("host_sync")
    out = lf.collect()
    assert _took(before) == (1, 0)
    assert out.row_count == 4
    # the dictionary keys need no measurement: the result's fetch alone
    assert tracing.get_count("host_sync") - syncs == 1
    # the group-by's order descriptor is the sort's: key order, exact
    assert out.ordering.keys == tuple(KEYS) and out.ordering.lexsort_exact


def test_programs_are_named_and_carry_their_stages():
    """``jit_groupby_dense`` / ``jit_groupby`` / ``jit_expr_eval`` on the
    trace's module line, and the stage names in their compiled text."""
    from cylon_tpu.obs import stages

    ctx = _ctx(1)
    t = _load(ctx, ref.lineitem(9, 512))
    _q1(t).collect()
    t.groupby(KEYS, {"l_quantity": "sum"}, _dense=False).row_count
    named = {}
    for _key, fn, spec in stages.dispatched_programs(ctx):
        text = stages._compiled_text(fn.lower(*spec))
        named.setdefault(fn.__name__, set()).update(
            part for _text, op in stages.parse_compiled(text)[1]
            for part in op.split("/") if part in stages.VOCABULARY
        )
    assert stages.GROUPBY_DENSE_AGG in named["groupby_dense"]
    assert stages.SORT_ENGINE not in named["groupby_dense"]
    assert stages.GROUPBY_SEGMENT_SUM not in named["groupby_dense"]
    assert {stages.GROUPBY_KEY_IDS, stages.GROUPBY_SEGMENT_SUM,
            stages.SORT_ENGINE} <= named["groupby"]
    assert named["expr_eval"] == {stages.EXPR_EVAL}
    assert "filter" not in named  # the predicate rode the group-by


# -- dense against factorize, column for column -------------------------
def _frames_equal(a, b, filtered=False):
    """Equal outputs, column for column. ``filtered``: ``b`` grouped the
    output of a filter, whose columns all carry a validity lane and whose
    ranges were measured over the rows left, so those two (both sound
    either way) are not compared."""
    pa, pb = a.to_pandas(), b.to_pandas()
    assert list(pa.columns) == list(pb.columns)
    assert [str(t) for t in pa.dtypes] == [str(t) for t in pb.dtypes]
    assert a.row_count == b.row_count
    for c in a.column_names:
        assert a.column(c).dtype.type == b.column(c).dtype.type, c
        assert a.column(c).data.dtype == b.column(c).data.dtype, c
        if not filtered:
            assert (a.column(c).valid is None) == (b.column(c).valid is None), c
    if filtered:  # a validity lane on the key withdraws ``lexsort_exact``
        assert a.ordering._replace(lexsort_exact=False) == \
            b.ordering._replace(lexsort_exact=False)
    else:
        assert a.ordering == b.ordering
        assert a.column_stats == b.column_stats
    # float sums add in another order on the two paths: 1e-12 (see Q1_RTOL)
    pd.testing.assert_frame_equal(pa, pb, rtol=1e-12, atol=0)


def _case(name, rng, n=3000):
    """``(columns, keys, agg, mask | None)`` of one edge case."""
    v = rng.random(n)
    i = rng.integers(-50, 50, n)
    agg = {"v": ["sum", "mean", "min", "max", "count"], "i": ["sum", "min"]}
    if name == "int_keys":
        return {"a": rng.integers(-3, 4, n), "b": rng.integers(0, 5, n).astype(np.int16),
                "v": v, "i": i}, ["a", "b"], agg, None
    if name == "dictionary_keys":
        return {"a": rng.choice(np.array(["x", "yy", "zzz"]), n),
                "b": rng.choice(np.array(["p", "q"]), n), "v": v, "i": i}, ["a", "b"], agg, None
    if name == "mixed_keys":
        return {"a": rng.choice(np.array(["x", "yy", "zzz"]), n),
                "b": rng.integers(10, 14, n), "c": rng.integers(0, 2, n).astype(bool),
                "v": v, "i": i}, ["a", "b", "c"], agg, None
    if name == "combination_with_no_row":
        a = rng.integers(0, 4, n)
        b = np.where(a == 2, 0, rng.integers(0, 3, n))  # (2,1), (2,2) never occur
        return {"a": a, "b": b, "v": v, "i": i}, ["a", "b"], agg, None
    if name == "nulls_in_a_value_column":
        v = v.copy()
        v[rng.random(n) < 0.2] = np.nan
        a = rng.integers(0, 6, n)
        v[a == 3] = np.nan  # a group whose every value is null
        return {"a": a, "v": v, "i": i}, ["a"], agg, None
    if name == "null_keys":
        a = rng.integers(0, 4, n).astype(object)
        a[rng.random(n) < 0.1] = None
        return {"a": a, "b": rng.integers(0, 3, n), "v": v, "i": i}, ["a", "b"], agg, None
    if name == "every_row_masked_out":
        return {"a": rng.integers(0, 4, n), "v": v, "i": i}, ["a"], agg, np.zeros(n, bool)
    if name == "mask_empties_a_group":
        a = rng.integers(0, 5, n)
        return {"a": a, "v": v, "i": i}, ["a"], agg, (a != 1) & (v < 0.7)
    if name == "one_group":
        return {"a": np.full(n, 7), "v": v, "i": i}, ["a"], agg, None
    raise AssertionError(name)


CASES = [
    "int_keys", "dictionary_keys", "mixed_keys", "combination_with_no_row",
    "nulls_in_a_value_column", "null_keys", "every_row_masked_out",
    "mask_empties_a_group", "one_group",
]


@pytest.mark.parametrize("name", CASES)
def test_dense_path_equals_factorize_path(ctx, name):
    cols, keys, agg, mask = _case(name, np.random.default_rng(len(name)))
    t = ct.Table.from_pydict(ctx, cols)
    before = _counts()
    dense = t.groupby(keys, agg, _mask=mask)
    assert _took(before) == (1, 0), "the input qualifies: the dense path"
    before = _counts()
    plain = (t if mask is None else t.filter(mask)).groupby(keys, agg, _dense=False)
    assert _took(before) == (0, 1)
    _frames_equal(dense, plain, filtered=mask is not None)
    if name == "every_row_masked_out":
        assert dense.row_count == 0
    if name == "combination_with_no_row" and ctx.world_size == 1:
        assert dense.row_count == 4 * 3 - 2


@pytest.mark.parametrize("values,dense", [
    (_g.DENSE_MAX_SLOTS - 1, True), (_g.DENSE_MAX_SLOTS, True),
    (_g.DENSE_MAX_SLOTS + 1, False), (4096, False),
])
def test_the_span_decides_the_path(ctx1, values, dense):
    """Up to ``DENSE_MAX_SLOTS`` key values the dense path, one more the
    factorize path (4,096 uniform int64 keys: the upstream suite's shape);
    the same answer on either side of the constant."""
    rng = np.random.default_rng(values)
    k = rng.integers(100, 100 + values, 4096).astype(np.int64)
    k[:2] = 100, 100 + values - 1  # the range is exactly ``values`` wide
    t = ct.Table.from_pydict(ctx1, {"k": k, "v": rng.random(4096)})
    before = _counts()
    got = t.groupby("k", {"v": ["sum", "count"]})
    assert _took(before) == ((1, 0) if dense else (0, 1))
    want = pd.DataFrame({"k": k, "v": t.to_pydict()["v"]}).groupby("k").agg(
        v_sum=("v", "sum"), v_count=("v", "count")).reset_index()
    pd.testing.assert_frame_equal(got.to_pandas(), want, rtol=1e-12)
    before = _counts()
    _frames_equal(got, t.groupby("k", {"v": ["sum", "count"]}, _dense=False))
    assert _took(before) == (0, 1)


@pytest.mark.parametrize("agg", [
    {"v": "var"}, {"v": "std"}, {"v": "nunique"}, {"v": "median"},
    {"v": ["sum", "quantile"]},
])
def test_an_op_with_no_dense_form_takes_the_factorize_path(ctx1, agg):
    rng = np.random.default_rng(1)
    t = ct.Table.from_pydict(ctx1, {"a": rng.integers(0, 4, 500), "v": rng.random(500)})
    before = _counts()
    t.groupby("a", agg)
    assert _took(before) == (0, 1)


def test_float_keys_and_sorted_input_stay_on_the_existing_path(ctx1):
    rng = np.random.default_rng(2)
    t = ct.Table.from_pydict(ctx1, {
        "f": rng.integers(0, 4, 500).astype(np.float64),
        "a": rng.integers(0, 4, 500), "v": rng.random(500),
    })
    before = _counts()
    t.groupby("f", {"v": "sum"})  # a float64 key has no measured range
    run_detect = tracing.get_count("ordering.groupby_run_detect")
    t.sort("a").groupby("a", {"v": "sum"})  # provably sorted: run-detect
    assert _took(before) == (0, 2)
    assert tracing.get_count("ordering.groupby_run_detect") == run_detect + 1


def test_a_drifting_range_compiles_nothing(ctx1):
    """The lower bounds are arguments of ``jit_groupby_dense`` and the spans
    are rounded to powers of two: another range, the same program."""
    rng = np.random.default_rng(3)

    def run(lo, width):
        t = ct.Table.from_pydict(ctx1, {
            "k": rng.integers(lo, lo + width, 512), "v": rng.random(512)})
        return t.groupby("k", {"v": "sum"}).row_count

    run(0, 7)
    programs = len(ctx1.__dict__["_jit_cache"])
    assert run(1000, 8) <= 8 and run(-5, 6) <= 6
    assert len(ctx1.__dict__["_jit_cache"]) == programs


def test_a_collect_frees_its_intermediate_tables_without_the_collector(ctx1):
    """The executor's memo is cleared when a plan has run: a computed
    projection of a large table must not wait for the cycle collector."""
    import gc

    rng = np.random.default_rng(12)
    n = 1 << 12
    t = ct.Table.from_pydict(ctx1, {"a": rng.integers(0, 4, n), "p": rng.random(n)})
    lf = t.lazy().with_columns({"x": col("p") * 2}).groupby("a", {"x": "sum"})

    def live():
        return sum(a.size >= n for a in jax.live_arrays())

    lf.collect()
    gc.collect()
    gc.disable()
    try:
        before = live()
        for _ in range(3):
            assert lf.collect().row_count == 4
        assert live() == before
    finally:
        gc.enable()


# -- the filter as the aggregate's row mask ------------------------------
@pytest.mark.parametrize("keys,dense", [(["a"], True), (["wide"], False)])
def test_groupby_of_filter_equals_filter_then_groupby(ctx1, keys, dense):
    rng = np.random.default_rng(4)
    n = 2000
    t = ct.Table.from_pydict(ctx1, {
        "a": rng.integers(0, 5, n), "wide": rng.integers(0, 5000, n),
        "x": rng.integers(0, 100, n), "v": rng.random(n),
    })
    lf = t.lazy().filter(col("x") < 30).groupby(keys, {"v": ["sum", "count"]})
    assert "filter_as_mask" in lf.explain()
    before = _counts()
    got = lf.collect()
    assert _took(before) == ((1, 0) if dense else (0, 1))
    mask = t.to_pydict()["x"] < 30
    want = t.filter(mask).groupby(keys, {"v": ["sum", "count"]}, _dense=False)
    _frames_equal(got, want, filtered=True)
    # a null predicate row counts in no aggregate, as a filter drops it
    xn = t.to_pydict()["x"].astype(np.float64)
    xn[::3] = np.nan
    tn = ct.Table.from_pydict(ctx1, {**t.to_pydict(), "x": xn})
    got = tn.lazy().filter(col("x") < 30).groupby(keys, {"v": "count"}).collect()
    want = tn.filter((xn < 30)).groupby(keys, {"v": "count"}, _dense=False)
    _frames_equal(got, want, filtered=True)


def test_on_a_mesh_the_filter_stays_under_the_shuffle():
    ctx = _ctx(4)
    rng = np.random.default_rng(6)
    t = ct.Table.from_pydict(ctx, {
        "a": rng.integers(0, 5, 800), "x": rng.integers(0, 100, 800),
        "v": rng.random(800)})
    lf = t.lazy().filter(col("x") < 30).groupby("a", {"v": "sum"})
    text = lf.explain()
    assert "filter_as_mask" not in text
    assert text.split("== Optimized plan ==")[1].index("Shuffle") < \
        text.split("== Optimized plan ==")[1].index("Filter")
    want = pd.DataFrame(t.to_pydict()).query("x < 30").groupby("a").agg(
        v_sum=("v", "sum")).reset_index()
    got = lf.collect().to_pandas().sort_values("a").reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want, rtol=1e-12)


# -- the computed projection ---------------------------------------------
def test_with_columns_node_schema_explain_and_fingerprint(ctx1):
    rng = np.random.default_rng(8)
    t = ct.Table.from_pydict(ctx1, {
        "p": rng.random(100), "d": rng.integers(0, 10, 100),
        "s": rng.choice(np.array(["a", "b"]), 100)})
    lf = t.lazy().with_columns({"net": col("p") * (1 - col("d")), "d": col("d") + 1})
    assert lf.columns == ["p", "d", "s", "net"]  # replaced in place, new last
    node = lf.plan
    assert isinstance(node, nodes.WithColumns)
    assert node.dtype_of("net")[1] == "float64" and node.dtype_of("d")[1] == "int64"
    assert "WithColumns [net=(col('p') * (1 - col('d'))), d=(col('d') + 1)]" in lf.explain()
    other = t.lazy().with_columns({"net": col("p") * (2 - col("d")), "d": col("d") + 1})
    assert node.fingerprint() != other.plan.fingerprint()
    got = lf.collect().to_pydict()
    host = t.to_pydict()
    npt.assert_allclose(got["net"], host["p"] * (1 - host["d"]), rtol=1e-15)
    npt.assert_array_equal(got["d"], host["d"] + 1)
    assert list(got["s"]) == list(host["s"])
    with pytest.raises(KeyError):
        t.lazy().with_columns({"x": col("nope") + 1})
    with pytest.raises(TypeError):
        t.lazy().with_columns({"x": 3})


def test_with_columns_keeps_stats_and_ordering_of_untouched_columns(ctx1):
    t = ct.Table.from_pydict(ctx1, {"k": np.arange(64) % 7, "v": np.arange(64.0)})
    s = t.sort("k")
    s.ensure_stats(["k"])
    out = s.lazy().with_columns({"w": col("v") * 2}).collect()
    assert out.ordering == s.ordering and out.column_stats == s.column_stats
    assert s.lazy().with_columns({"k": col("k") + 1}).collect().ordering is None
    node = s.lazy().with_columns({"w": col("v") * 2}).plan
    assert set(node.col_stats()) == {"k"} and node.ordering() is not None


def test_an_unused_computed_column_is_pruned_and_a_filter_passes_below(ctx1):
    rng = np.random.default_rng(9)
    t = ct.Table.from_pydict(ctx1, {
        "a": rng.integers(0, 3, 200), "p": rng.random(200), "q": rng.random(200)})
    lf = (
        t.lazy()
        .with_columns({"used": col("p") * 2, "unused": col("q") * 3})
        .filter(col("p") > 0.5)
        .groupby("a", {"used": "sum"})
    )
    opt, fired = rules.optimize(lf.plan, 1)
    assert rules.FILTER_PUSHDOWN in fired and rules.FILTER_AS_MASK in fired
    text = opt.render()
    assert "unused" not in text and "q" not in opt.children[0].children[0].names
    host = pd.DataFrame(t.to_pydict()).query("p > 0.5")
    want = (host.assign(used=host.p * 2).groupby("a").agg(used_sum=("used", "sum"))
            .reset_index())
    pd.testing.assert_frame_equal(lf.collect().to_pandas(), want, rtol=1e-12)
    # a predicate over a computed column stays above it (and still rides)
    lf2 = (t.lazy().with_columns({"u": col("p") * 2}).filter(col("u") > 1.0)
           .groupby("a", {"q": "count"}))
    want2 = host.groupby("a").agg(q_count=("q", "count")).reset_index()
    pd.testing.assert_frame_equal(lf2.collect().to_pandas(), want2)


def test_a_literal_sweep_compiles_one_expression_program(ctx1):
    rng = np.random.default_rng(10)
    t = ct.Table.from_pydict(ctx1, {"a": rng.integers(0, 3, 300), "x": rng.random(300)})
    counts = []
    for bound in (0.25, 0.5, 0.75):
        lf = t.lazy().filter(col("x") < bound).with_columns({"y": col("x") * bound})
        got = lf.collect().to_pydict()
        host = t.to_pydict()
        npt.assert_allclose(np.sort(got["y"]), np.sort(host["x"][host["x"] < bound] * bound))
        counts.append(len(ctx1.__dict__["_jit_cache"]))
    assert counts[0] == counts[1] == counts[2]


def _narrow_table(ctx):
    """32-bit columns, whose comparison with a python literal shows
    whether the literal was weakly typed (0.1 as float32 equals the
    column's 0.1; as float64 it does not), and a dictionary column."""
    return ct.Table.from_pydict(ctx, {
        "x": np.array([0.1, 0.2, 0.3, 0.5, 0.1, 0.7], np.float32),
        "i": np.array([1, 2, 3, 2**31 - 2, -5, 2], np.int32),
        "s": np.array(["a", "b", "a", "a", "b", "a"]),
    })


@pytest.mark.parametrize("pred, rows", [
    (lambda: col("x") == 0.1, 2), (lambda: col("x") <= 0.3, 4),
    (lambda: 0.3 >= col("x"), 4), (lambda: col("x") != 0.5, 5),
    (lambda: col("x") * 2 > 0.6, 2), (lambda: col("x") + 1 < 1.25, 3),
    (lambda: col("x") == np.float32(0.1), 2),
    (lambda: col("x") == np.float64(0.1), 0),  # a typed scalar stays typed
    (lambda: col("i") == 2, 2), (lambda: col("i") > 1.5, 4),
    (lambda: col("i") + 1 > 2**31 - 2, 1), (lambda: -col("i") >= 5, 1),
    (lambda: (col("i") % 2 == 0) & (col("x") < 0.6), 2),
], ids=lambda v: None if callable(v) else str(v))
def test_a_planned_filter_types_its_literals_as_the_eager_one(ctx, pred, rows):
    """One predicate three ways gives the same rows: planned (the
    ``jit_expr_eval`` program, its literals arguments), eager
    (``filter_mask``: ``jnp`` calls on the columns), and planned beside a
    term on a dictionary column, which sends the whole predicate down
    the eager path."""
    from cylon_tpu.plan.expr import filter_mask

    t = _narrow_table(ctx)
    planned = t.lazy().filter(pred()).collect().to_pandas()
    assert len(planned) == rows
    env = {n: t.column(n) for n in t.column_names}
    eager = t.filter(filter_mask(pred(), env)).to_pandas()
    mixed = t.lazy().filter(pred() & (col("s") != "zz")).collect().to_pandas()
    key = ["i", "x"]  # world 4 emits the shards' rows in shard order
    for other in (eager, mixed):
        pd.testing.assert_frame_equal(
            planned.sort_values(key, ignore_index=True),
            other.sort_values(key, ignore_index=True),
        )


@pytest.mark.parametrize("expr, dtype", [
    (lambda: col("x") * 2.0, "float32"), (lambda: 1 - col("x"), "float32"),
    (lambda: col("i") + 1, "int32"), (lambda: col("i") * 1.5, "float64"),
    (lambda: col("i") / 2, "float32"), (lambda: col("x") * col("i"), "float32"),
    (lambda: col("x") + np.float64(1), "float64"),
    (lambda: col("i") + np.int64(1), "int64"),
], ids=lambda v: None if callable(v) else v)
def test_a_computed_column_promotes_as_the_eager_expression(ctx1, expr, dtype):
    """``with_columns`` keeps a 32-bit column 32 bits wide under a python
    literal: the program's result, the eager evaluation's and the plan's
    schema name one dtype, value for value."""
    t = _narrow_table(ctx1)
    lf = t.lazy().with_columns({"y": expr()})
    got = lf.collect()
    eager, _valid = expr().evaluate({n: t.column(n) for n in t.column_names})
    assert got.column("y").data.dtype == eager.dtype == np.dtype(dtype)
    assert dict((n, p) for n, _t, p in lf.plan.schema)["y"] == dtype
    npt.assert_array_equal(
        got.to_pydict()["y"], np.asarray(eager)[: t.row_count]
    )


def test_a_date_literal_compares_in_the_columns_unit(ctx1):
    days = np.datetime64("1998-08-30") + np.arange(6).astype("timedelta64[D]")
    t = ct.Table.from_pydict(ctx1, {"d": days, "v": np.arange(6.0)})
    got = t.lazy().filter(col("d") <= lit(np.datetime64("1998-09-02"))).collect()
    assert got.row_count == 4
    assert lit(np.datetime64("1998-09-02")).physical() == 904694400 * 10**9


@pytest.mark.parametrize("values", [
    np.array(["R", "A", "N", "A", ""]), np.array(["yy", "x", "zzz", "x"]),
    np.array([], dtype="<U1"), np.array(["é", "a", "Z"]),
])
def test_fixed_width_strings_encode_as_the_object_path_does(values):
    fast = Column.encode_host(values)
    slow = Column.encode_host(values.astype(object))
    npt.assert_array_equal(fast[0], slow[0])
    assert fast[0].dtype == np.int32 and fast[1] is None
    assert fast[2].type == slow[2].type
    assert list(fast[3]) == list(slow[3])
