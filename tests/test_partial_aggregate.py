"""An aggregate's partial state is combined where the rows lie (PR 39).

- TPC-H Q1 and Q6 through ``Table.lazy()`` on meshes of 1, 4 and 8 against
  the plain numpy references ``q1_reference.py`` / ``q6_reference.py``;
  on a mesh no row is exchanged and the groups lie on the first shard;
- ``Table.distributed_groupby`` on the combined path against the one-shard
  dense group-by (``tests/test_groupby_dense.py`` holds that one to the
  factorize path) over that file's edge cases, every op of ``DENSE_OPS``
  against pandas, uneven shards and a shard with no live row;
- the shares add up: the shards' partial slot tables, summed by numpy,
  are the one-shard table, and the mesh's combine adds in shard order;
- the aggregate without keys: one row, also where no row passes;
- a group-by the dense plan declines keeps its Shuffle and its result.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import numpy.testing as npt
import pandas as pd
import pytest
from jax.sharding import PartitionSpec as P

import cylon_tpu as ct
from cylon_tpu import col, lit
from cylon_tpu.ops import groupby as _g
from cylon_tpu.utils import tracing

import q1_reference as q1ref
import q6_reference as q6ref
from test_groupby_dense import CASES, Q1_RTOL, RESULT, KEYS, _case, _q1

ROWS = 3000
WORLDS = [1, 4, 8]
OPS = {_g.SUM: "sum", _g.COUNT: "count", _g.MIN: "min", _g.MAX: "max",
       _g.MEAN: "mean"}
PARTIAL, DENSE = "groupby.partial_path", "groupby.dense_path"
RAW = "groupby.raw_shuffle_path"


@functools.lru_cache(maxsize=None)
def _ctx(world):
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:world])
    )


@pytest.fixture(scope="module", params=WORLDS, ids=lambda w: f"w{w}")
def ctx(request):
    return _ctx(request.param)


@pytest.fixture(scope="module", params=WORLDS[1:], ids=lambda w: f"w{w}")
def mesh(request):
    return _ctx(request.param)


def _load(ctx, li):
    return ct.Table.from_numpy(ctx, list(li), list(li.values()))


def _rows(name):
    return tracing.snapshot().get(name, {}).get("rows", 0)


def _q6(table):
    return (
        table.lazy()
        .with_columns({"revenue": col("l_extendedprice") * col("l_discount")})
        .filter(
            (col("l_shipdate") >= lit(q6ref.DATE_LO))
            & (col("l_shipdate") < lit(q6ref.DATE_HI))
            & (col("l_discount") >= lit(q6ref.DISCOUNT_LO))
            & (col("l_discount") <= lit(q6ref.DISCOUNT_HI))
            & (col("l_quantity") < lit(q6ref.QUANTITY))
        )
        .agg({"revenue": ["sum", "count"]})
    )


def _on_first_shard(table, rows):
    counts = list(table.row_counts)
    assert counts == [rows] + [0] * (len(counts) - 1)


# -- Q1 and Q6 against the plain references ------------------------------
def test_planned_q1_is_combined_in_place(ctx):
    li = q1ref.lineitem(39, ROWS)
    want = q1ref.q1(li)
    lf = _q1(_load(ctx, li))
    partial, moved = tracing.get_count(PARTIAL), _rows("shuffle.coll_rows")
    syncs = tracing.get_count("host_sync")
    out = lf.collect()
    got = out.to_pydict()
    for k in KEYS:  # the groups position for position: also the key order
        assert list(got[k]) == list(want[k])
    npt.assert_array_equal(got["l_quantity_count"], want["count_order"])
    for c, r in RESULT.items():
        npt.assert_allclose(got[c], want[r], rtol=Q1_RTOL)
    assert tracing.get_count("host_sync") - syncs == 1  # the result's count
    assert _rows("shuffle.coll_rows") == moved, "no row is exchanged"
    mesh = ctx.world_size > 1
    assert tracing.get_count(PARTIAL) - partial == (1 if mesh else 0)
    _on_first_shard(out, 4)
    assert out.ordering.keys == tuple(KEYS)
    assert out.ordering.scope == ("global" if mesh else "shard")


@pytest.mark.parametrize("seed", [6, 2**31 + 39])
def test_planned_q6_matches_the_numpy_reference(ctx, seed):
    li = q1ref.lineitem(seed, ROWS)
    want = q6ref.q6(li)
    assert want["count"] > 20, "the rehearsal's rows must pass some"
    moved = _rows("shuffle.coll_rows")
    out = _q6(_load(ctx, li)).collect()
    _on_first_shard(out, 1)
    got = out.to_pydict()
    assert list(got) == ["revenue_sum", "revenue_count"]
    assert got["revenue_count"][0] == want["count"]
    npt.assert_allclose(got["revenue_sum"][0], want["revenue"], rtol=Q1_RTOL)
    assert _rows("shuffle.coll_rows") == moved


def test_q6_counts_the_rows_the_decimal_query_means():
    """``0.06 + 0.01`` in float64 is 0.06999999999999999: a bound computed
    so drops every row whose discount is 0.07. The bounds the references
    and ``chipbench/queries/tpch_q6.py`` use are the doubles nearest 0.05
    and 0.07; counted in integer hundredths the same rows pass."""
    li = q1ref.lineitem(7, 20000)
    exact = q6ref.passing_cents(li)
    assert q6ref.q6(li)["count"] == exact
    out = _q6(_load(_ctx(1), li)).collect().to_pydict()
    assert out["revenue_count"][0] == exact
    naive = (
        (li["l_shipdate"] >= q6ref.DATE_LO) & (li["l_shipdate"] < q6ref.DATE_HI)
        & (li["l_discount"] >= 0.06 - 0.01) & (li["l_discount"] <= 0.06 + 0.01)
        & (li["l_quantity"] < q6ref.QUANTITY)
    )
    assert 0.5 * exact < naive.sum() < 0.8 * exact, "a third of the rows lost"


def test_float32_values_fail_q6s_tolerance():
    li = q1ref.lineitem(5, ROWS)
    want = q6ref.q6(li)
    low = {
        c: a.astype(np.float32).astype(np.float64) if a.dtype == np.float64 else a
        for c, a in li.items()
    }
    got = _q6(_load(_ctx(4), low)).collect().to_pydict()["revenue_sum"][0]
    assert abs(got - want["revenue"]) / want["revenue"] > 1e3 * Q1_RTOL


# -- the aggregate without keys ------------------------------------------
AGG_ALL = {"v": ["sum", "mean", "min", "max", "count"], "i": ["sum", "max"]}


def test_a_keyless_aggregate_is_one_row_when_no_row_passes(ctx):
    rng = np.random.default_rng(1)
    t = ct.Table.from_pydict(ctx, {
        "v": rng.random(500), "i": rng.integers(-9, 9, 500)})
    lf = t.lazy().filter(col("v") > 2.0).agg(AGG_ALL)
    out = lf.collect()
    _on_first_shard(out, 1)
    got = out.to_pandas()
    assert list(got.columns) == [
        "v_sum", "v_mean", "v_min", "v_max", "v_count", "i_sum", "i_max"]
    assert got["v_count"][0] == 0
    for c in got.columns.drop("v_count"):
        assert pd.isna(got[c][0]), c  # null, as SQL has it
    assert out.ordering is None
    # the eager door: the same through Table.groupby([]) a shard
    eager = t.distributed_groupby([], AGG_ALL, _mask=t.to_pydict()["v"] > 2.0)
    assert eager.row_count == 1 and eager.to_pandas()["v_count"][0] == 0


def test_a_keyless_aggregate_over_every_row(ctx):
    rng = np.random.default_rng(2)
    v, i = rng.random(777), rng.integers(-9, 9, 777)
    v[rng.random(777) < 0.2] = np.nan  # nulls are skipped
    t = ct.Table.from_pydict(ctx, {"v": v, "i": i})
    got = t.lazy().agg(AGG_ALL).collect().to_pydict()
    live = v[~np.isnan(v)]
    npt.assert_allclose(got["v_sum"][0], live.sum(), rtol=1e-12)
    npt.assert_allclose(got["v_mean"][0], live.mean(), rtol=1e-12)
    assert got["v_min"][0] == live.min() and got["v_max"][0] == live.max()
    assert got["v_count"][0] == len(live)
    assert got["i_sum"][0] == i.sum() and got["i_max"][0] == i.max()
    assert got["i_sum"].dtype == np.int64


def test_a_keyless_aggregate_takes_the_dense_ops_alone():
    t = ct.Table.from_pydict(_ctx(4), {"v": np.arange(8.0)})
    with pytest.raises(ValueError, match="sum, count, min, max and mean"):
        t.lazy().agg({"v": "std"})
    for call in (t.groupby, t.distributed_groupby):
        with pytest.raises(ValueError, match="without keys"):
            call([], {"v": "nunique"})


# -- distributed_groupby on the combined path -----------------------------
@pytest.mark.parametrize("name", CASES)
def test_combined_equals_the_one_shard_group_by(mesh, name):
    cols, keys, agg, mask = _case(name, np.random.default_rng(len(name)))
    one = ct.Table.from_pydict(_ctx(1), cols).groupby(keys, agg, _mask=mask)
    partial, dense = tracing.get_count(PARTIAL), tracing.get_count(DENSE)
    many = ct.Table.from_pydict(mesh, cols).distributed_groupby(
        keys, agg, _mask=mask)
    assert tracing.get_count(PARTIAL) - partial == 1
    assert tracing.get_count(DENSE) - dense == 1
    _on_first_shard(many, one.row_count)
    assert many.ordering == one.ordering._replace(scope="global")
    for c in one.column_names:
        assert many.column(c).data.dtype == one.column(c).data.dtype, c
    # float sums add in another order over the shards: 1e-12 (Q1_RTOL)
    pd.testing.assert_frame_equal(
        many.to_pandas(), one.to_pandas(), rtol=1e-12, atol=0)


def _nullable(rng, n=2000):
    a = rng.integers(0, 4, n).astype(object)
    a[rng.random(n) < 0.1] = None
    v = rng.normal(size=n)
    v[rng.random(n) < 0.2] = np.nan
    return {"a": a, "s": rng.choice(np.array(["x", "yy", "zzz"]), n),
            "v": v, "i": rng.integers(-50, 50, n)}


@pytest.mark.parametrize("op", sorted(OPS.values()))
def test_every_dense_op_against_pandas(ctx, op):
    """Nullable keys and values, three ops' worth of columns a call."""
    cols = _nullable(np.random.default_rng(11))
    got = ct.Table.from_pydict(ctx, cols).distributed_groupby(
        ["s", "a"], {"v": op, "i": op}).to_pandas()
    df = pd.DataFrame(cols)
    df["a"] = df["a"].astype("float64")  # None -> NaN, one null group a key
    how = (lambda s: s.sum(min_count=1)) if op == "sum" else op
    want = df.groupby(["s", "a"], dropna=False).agg(
        **{f"v_{op}": ("v", how), f"i_{op}": ("i", op)}).reset_index()
    assert len(got) == len(want)
    assert list(got["s"]) == list(want["s"])
    npt.assert_array_equal(got["a"].astype("float64"), want["a"])
    for c in (f"v_{op}", f"i_{op}"):
        npt.assert_allclose(
            got[c].astype("float64"), want[c].astype("float64"), rtol=1e-12)


def test_uneven_shards_and_a_shard_with_no_live_row(mesh):
    """A filter leaves the first shard without a row, the rest uneven and
    their counts on the device; five rows leave shards of a mesh of eight
    with none at all."""
    rng = np.random.default_rng(5)
    n = 1600
    k, v = rng.integers(0, 5, n), rng.random(n)
    t = ct.Table.from_pydict(mesh, {"k": k, "v": v})
    keep = (np.arange(n) >= n // mesh.world_size) & (v < 0.8)
    got = t.filter(keep).distributed_groupby("k", {"v": ["sum", "count"]})
    want = pd.DataFrame({"k": k, "v": v})[keep].groupby("k").agg(
        v_sum=("v", "sum"), v_count=("v", "count")).reset_index()
    pd.testing.assert_frame_equal(got.to_pandas(), want, rtol=1e-12)
    few = ct.Table.from_pydict(mesh, {"k": k[:5], "v": v[:5]})
    got = few.distributed_groupby("k", {"v": "sum"}).to_pandas()
    want = pd.DataFrame({"k": k[:5], "v": v[:5]}).groupby("k").agg(
        v_sum=("v", "sum")).reset_index()
    pd.testing.assert_frame_equal(got, want, rtol=1e-12)


# -- the shares add up -----------------------------------------------------
PIECES = [(0, 700), (700, 800), (800, 1900), (1900, 3000)]  # uneven


@pytest.mark.parametrize("op", sorted(OPS))
@pytest.mark.parametrize("kind", ["float", "int"])
def test_the_shards_partial_tables_add_up(op, kind):
    rng = np.random.default_rng(op)
    n, slots = 3000, 6
    gid = rng.integers(0, slots + 1, n).astype(np.int32)
    gid[700:800] = slots  # a shard with no live row
    gid = jnp.asarray(gid)
    # values of one sign, as the cells' decimals are: with cancellation a
    # sum's last bit says nothing about the order it was added in
    data = jnp.asarray(
        rng.random(n) if kind == "float" else rng.integers(-99, 99, n))
    valid = jnp.asarray(rng.random(n) < 0.9)
    partial = jax.jit(_g.dense_partial, static_argnums=(0, 4))
    cnt, acc = partial(op, data, valid, gid, slots)
    parts = [
        partial(op, data[a:b], valid[a:b], gid[a:b], slots) for a, b in PIECES
    ]
    # counts: bit for bit
    npt.assert_array_equal(sum(np.asarray(c) for c, _a in parts), cnt)
    if op == _g.COUNT:
        assert acc is None
        return
    accs = np.stack([np.asarray(a) for _c, a in parts])
    if op in (_g.MIN, _g.MAX):
        fold = np.min if op == _g.MIN else np.max
        npt.assert_array_equal(fold(accs, axis=0), acc)
    elif accs.dtype.kind == "i":
        npt.assert_array_equal(accs.sum(axis=0), acc)
    else:  # a float sum: within one ulp a shard
        whole = np.asarray(acc)
        assert np.all(
            np.abs(accs.sum(axis=0) - whole) <= len(PIECES) * np.spacing(np.abs(whole))
        )


def test_the_combine_adds_in_shard_order(mesh):
    """``dense_combine`` on the mesh gives, on every shard, the shards'
    float partials added one after the other from shard 0 on (so the same
    bits in every run), counts and integer sums exactly, minima and maxima
    of 64-bit columns without a 64-bit all-reduce."""
    from cylon_tpu.compat import shard_map

    world, slots = mesh.world_size, 5
    rng = np.random.default_rng(world)
    rows = rng.integers(0, 1000, (world, slots)).astype(np.int32)
    fsum = rng.normal(size=(world, slots)) * 10.0 ** rng.integers(-8, 8, (world, slots))
    isum = rng.integers(-2**40, 2**40, (world, slots))
    fmin = rng.normal(size=(world, slots))

    def kern(rows, fsum, isum, fmin):
        out_rows, parts = _g.dense_combine(rows[0], [
            (_g.SUM, rows[0], fsum[0]), (_g.SUM, rows[0], isum[0]),
            (_g.MIN, rows[0], fmin[0]), (_g.MAX, rows[0], isum[0]),
            (_g.COUNT, rows[0], None),
        ], mesh.axis_name)
        return [out_rows[None]] + [
            x[None] for _op, c, a in parts for x in (c, a) if x is not None
        ]

    axis = P(mesh.axis_name)
    out = jax.jit(shard_map(
        kern, mesh=mesh.mesh, in_specs=(axis,) * 4, out_specs=axis,
    ))(rows, fsum, isum, fmin)
    out = [np.asarray(x) for x in out]
    ordered = fsum[0]
    for p in range(1, world):
        ordered = ordered + fsum[p]
    for shard in range(world):  # every shard holds the whole table's
        npt.assert_array_equal(out[0][shard], rows.sum(axis=0))
        assert out[2][shard].tobytes() == ordered.tobytes()  # bit for bit
        npt.assert_array_equal(out[4][shard], isum.sum(axis=0))
        npt.assert_array_equal(out[6][shard], fmin.min(axis=0))
        npt.assert_array_equal(out[8][shard], isum.max(axis=0))
        npt.assert_array_equal(out[9][shard], rows.sum(axis=0))


def test_a_query_gives_the_same_bits_run_to_run(mesh):
    t = _load(mesh, q1ref.lineitem(8, ROWS))
    first, again = (_q1(t).collect().to_pydict() for _ in range(2))
    for c in RESULT:
        assert first[c].tobytes() == again[c].tobytes(), c


# -- what the dense plan declines keeps its plan and its result -----------
def _plans(lf):
    text = lf.explain()
    return text.split("== Optimized plan ==")[1], text.split("Rewrites fired:")[1]


@pytest.mark.parametrize("shape", [
    "float_key", "groupby_w1_cardinality", "std", "no_measured_range",
])
def test_a_declined_group_by_keeps_its_shuffle_and_its_result(mesh, shape):
    rng = np.random.default_rng(4)
    n = 2048
    k = rng.integers(0, 6, n)
    agg = {"v": "std"} if shape == "std" else {"v": "sum"}
    if shape == "float_key":
        k = k.astype(np.float64)
    if shape == "groupby_w1_cardinality":  # keys uniform over the row count
        k = rng.integers(0, n, n)
    t = ct.Table.from_pydict(mesh, {"k": k, "v": rng.random(n)})
    if shape != "no_measured_range":
        t.ensure_stats(["k"])
    lf = t.lazy().groupby("k", agg)
    optimized, fired = _plans(lf)
    assert "Shuffle hash [k]" in optimized and "partial" not in optimized + fired
    dense, moved = tracing.get_count(DENSE), _rows("shuffle.coll_rows")
    partial, raw = tracing.get_count(PARTIAL), tracing.get_count(RAW)
    got = lf.collect().to_pandas().sort_values("k").reset_index(drop=True)
    assert tracing.get_count(DENSE) == dense, "not combined in place"
    # a sum crosses the mesh as a partial row a group a shard (PR 45), a
    # deviation as its rows
    took = (tracing.get_count(PARTIAL) - partial, tracing.get_count(RAW) - raw)
    assert took == ((0, 1) if shape == "std" else (1, 0))
    assert _rows("shuffle.coll_rows") > moved, "rows were exchanged"
    op = "std" if shape == "std" else "sum"
    want = pd.DataFrame({"k": k, "v": t.to_pydict()["v"]}).groupby("k").agg(
        **{f"v_{op}": ("v", op)}).reset_index()
    pd.testing.assert_frame_equal(got, want, rtol=1e-9)


def test_a_measured_integer_key_is_combined_in_place(mesh):
    rng = np.random.default_rng(6)
    k = rng.integers(-3, 9, 1500)
    t = ct.Table.from_pydict(mesh, {"k": k, "v": rng.random(1500)})
    t.ensure_stats(["k"])
    lf = t.lazy().groupby("k", {"v": ["mean", "max"]}).sort("k")
    optimized, fired = _plans(lf)
    assert "Shuffle" not in optimized and "Sort" not in optimized
    assert "partial_aggregate x1" in fired and "order_reuse x1" in fired
    want = pd.DataFrame({"k": k, "v": t.to_pydict()["v"]}).groupby("k").agg(
        v_mean=("v", "mean"), v_max=("v", "max")).reset_index()
    pd.testing.assert_frame_equal(lf.collect().to_pandas(), want, rtol=1e-12)


def test_a_cached_partial_plan_is_held_to_its_order_when_the_table_declines(mesh):
    """The plan cache keys on the plan's shape, not on a dictionary's size:
    a plan compiled for three key values meets a table of 2,000. The table
    then shuffles, and lowering sorts, so the node's claim (global key
    order, on which the Sort was dropped) still holds."""
    rng = np.random.default_rng(9)

    def frame(values, n=4000):
        names = np.array([f"k{i:05d}" for i in range(values)])
        return {"s": rng.choice(names, n), "v": rng.random(n)}

    def query(cols):
        t = ct.Table.from_pydict(mesh, cols)
        return t.lazy().groupby("s", {"v": "sum"}).sort("s")

    few, many = frame(3), frame(2000)
    assert "partial_aggregate x1" in _plans(query(few))[1]
    assert query(few).collect().row_count == 3
    dense, moved = tracing.get_count(DENSE), _rows("shuffle.coll_rows")
    got = query(many).collect().to_pandas()
    assert tracing.get_count(DENSE) == dense, "2,000 slots: not dense"
    assert _rows("shuffle.coll_rows") > moved
    want = pd.DataFrame(many).groupby("s").agg(v_sum=("v", "sum")).reset_index()
    pd.testing.assert_frame_equal(got, want, rtol=1e-12)
