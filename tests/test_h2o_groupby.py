"""The h2oai/db-benchmark group-by questions on meshes of 1, 4 and 8, and
the exchange of partials they brought (PR 45).

- q1-q7 and q10 of ``h2o_groupby_reference.py`` (N = 40,000, K = 20: 2,000
  ids) through ``Table.distributed_groupby`` against the plain reference,
  over the integer ids and the string ids (the dictionary path);
- which route a question takes on a mesh: q3's and q10's shapes ship a
  partial row a group (``groupby.partial_path``, ``groupby.precombine.*``),
  q6's (a median, a deviation) ships its rows (``groupby.raw_shuffle_path``);
- the partial rows travel at ``round_cap`` of the fullest shard's count:
  the shuffle's programs are lowered at it and not at the input's capacity;
- the state (M13): a count beside a sum, a mean of an int32 column, null
  keys and values, a masked input, a shard with no row, shards that hold
  every key and shards with disjoint keys;
- two runs on one seed give equal bits, and the shares add up to the
  whole: the shards' partial tables, combined on the host by the
  reference's arithmetic, are the reference of the uncut table.
"""
import functools

import jax
import numpy as np
import numpy.testing as npt
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu.engine import round_cap
from cylon_tpu.obs import stages
from cylon_tpu.table import _PartialAgg
from cylon_tpu.utils import tracing

import compact_cases
import h2o_groupby_reference as h2o

N, K = 40_000, 20
WORLDS = [1, 4, 8]
PARTIAL, RAW = "groupby.partial_path", "groupby.raw_shuffle_path"
PRECOMBINE = tuple(
    f"groupby.precombine.{c}"
    for c in ("rows_in", "rows_out", "fullest", "slots")
)


@functools.lru_cache(maxsize=None)
def _ctx(world):
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:world])
    )


@functools.lru_cache(maxsize=None)
def _data(seed=45):
    return h2o.make(N, K, seed)


@functools.lru_cache(maxsize=None)
def _table(world, seed=45):
    data = _data(seed)
    return ct.Table.from_numpy(_ctx(world), list(data), list(data.values()))


def _in_key_order(table, by) -> dict:
    """The result's columns with its groups (each shard emits its own, in
    its own key order) put in the reference's order."""
    got = table.to_pydict()
    order = np.lexsort([got[k] for k in reversed(by)])
    return {c: np.asarray(a)[order] for c, a in got.items()}


def _assert_answer(got: dict, want: dict, rtol=1e-12):
    assert set(got) == set(want)
    for c, w in want.items():
        g = got[c]
        assert len(g) == len(w), c
        if w.dtype.kind == "f":
            assert g.dtype == np.float64, c
            npt.assert_allclose(g, w, rtol=rtol, atol=0, err_msg=c)
        else:  # keys and integer aggregates: exact, sums and counts wide
            if c.endswith(("_sum", "_count")):
                assert g.dtype == np.int64, c
            npt.assert_array_equal(g, w, err_msg=c)


def _rows(name) -> int:
    return tracing.snapshot().get(name, {}).get("rows", 0)


# -- the questions -------------------------------------------------------
@pytest.mark.parametrize("world", WORLDS, ids=lambda w: f"w{w}")
@pytest.mark.parametrize("name", sorted(h2o.QUESTIONS, key=lambda q: int(q[1:])))
def test_question_matches_the_reference(world, name):
    by, agg = h2o.QUESTIONS[name]
    result = _table(world).distributed_groupby(by, agg)
    want = h2o.answer(_data(), by, agg)
    assert result.row_count == len(want[by[0]])
    # the median interpolates and the deviation squares: 1e-9
    _assert_answer(
        _in_key_order(result, by), want, rtol=1e-9 if name == "q6" else 1e-12
    )
    # every group exactly once over the mesh
    keys = np.rec.fromarrays([result.to_pydict()[k] for k in by])
    assert len(np.unique(keys)) == len(keys)


def test_q7_is_the_two_aggregates_and_a_difference():
    by, agg = h2o.QUESTIONS["q7"]
    got = _in_key_order(_table(4).distributed_groupby(by, agg), by)
    want = h2o.question(_data(), "q7")
    npt.assert_array_equal(got["v1_max"] - got["v2_min"], want["range_v1_v2"])
    assert set(h2o.UNSUPPORTED) == {"q8", "q9"}  # listed, not faked


@pytest.mark.parametrize("name,route", [
    ("q3", PARTIAL), ("q5", PARTIAL), ("q10", PARTIAL), ("q6", RAW),
])
def test_the_route_a_question_takes_on_a_mesh(name, route):
    by, agg = h2o.QUESTIONS[name]
    before = {c: tracing.get_count(c) for c in (PARTIAL, RAW)}
    rows_in, rows_out, _fullest, _slots = (_rows(c) for c in PRECOMBINE)
    moved = _rows("shuffle.coll_rows")
    result = _table(4).distributed_groupby(by, agg)
    took = {c: tracing.get_count(c) - before[c] for c in before}
    assert took == {PARTIAL: int(route == PARTIAL), RAW: int(route == RAW)}
    moved = _rows("shuffle.coll_rows") - moved
    if route == RAW:
        assert _rows(PRECOMBINE[0]) == rows_in and moved == N
        return
    assert _rows(PRECOMBINE[0]) - rows_in == N
    # what crossed the mesh is the partial rows, one a group a shard
    partials = _rows(PRECOMBINE[1]) - rows_out
    assert moved == partials
    assert result.row_count <= partials <= min(N, 4 * result.row_count)


def test_the_lazy_group_by_takes_the_same_route():
    by, agg = h2o.QUESTIONS["q3"]
    partial, moved = tracing.get_count(PARTIAL), _rows("shuffle.coll_rows")
    lazy = _table(4).lazy().groupby(by, agg).collect()
    assert tracing.get_count(PARTIAL) - partial == 1
    assert _rows("shuffle.coll_rows") - moved < N // 4
    _assert_answer(_in_key_order(lazy, by), h2o.answer(_data(), by, agg))
    # every shard owns the groups whose keys hash to it, as the plan's
    # Shuffle placed them
    eager = _table(4).distributed_groupby(by, agg)
    npt.assert_array_equal(lazy.row_counts, eager.row_counts)


# -- however the run heads reach their slots, one table (PR 46) -----------
@pytest.mark.parametrize("way", ["sort", "two-bits-a-pass"])
@pytest.mark.parametrize("world", [1, 4], ids=lambda w: f"w{w}")
def test_q5_is_one_table_however_the_heads_move(monkeypatch, world, way):
    """Question 5 (the benchmark's ``h2o-q5-w4``) through the pre-combine
    and the combine with the compaction sort that moved the run heads
    until PR 46, and with the wider pass of the compress: the table of
    the default pass, bit for bit, shard for shard."""
    by, agg = h2o.QUESTIONS["q5"]
    data = _data()

    def q5(patch, how):
        ctx = compact_cases.fresh_ctx(patch, how, world)
        table = ct.Table.from_numpy(ctx, list(data), list(data.values()))
        result = table.distributed_groupby(by, agg)
        return compact_cases.live_bits(result), list(result.row_counts)

    with pytest.MonkeyPatch.context() as patch:
        want = q5(patch, "bit-a-pass")
    assert q5(monkeypatch, way) == want
    _assert_answer(
        _in_key_order(_table(world).distributed_groupby(by, agg), by),
        h2o.answer(data, by, agg),
    )


# -- the capacity the partials travel at ---------------------------------
def test_the_partials_travel_at_their_counted_capacity():
    """100 ids over 40,000 rows: a shard's 100 partial rows cross the mesh
    in 128 slots, not in the input's 16,384."""
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:4])
    )
    rng = np.random.default_rng(7)
    ids = rng.integers(1, 101, N).astype(np.int32)
    v3 = np.round(rng.random(N) * 100.0, 6)
    table = ct.Table.from_numpy(ctx, ["id", "v3"], [ids, v3])
    before = [_rows(c) for c in PRECOMBINE]
    result = table.distributed_groupby("id", {"v3": "sum"}, _dense=False)
    rows_in, rows_out, fullest, slots = (
        _rows(c) - b for c, b in zip(PRECOMBINE, before)
    )
    assert (rows_in, rows_out, fullest) == (N, 400, 100)
    assert slots == round_cap(100) == 128 and table.shard_cap == 16_384
    assert result.row_count == 100
    # the shuffle's programs, by the row-sized arrays they were lowered
    # with (global: four shards)
    lowered_at = {}
    for key, _fn, spec in stages.dispatched_programs(ctx):
        if not str(key[0]).startswith("shuffle"):
            continue
        phase = next((p for p in ("count", "pack") if p in key), key[0])
        lowered_at[phase] = {
            leaf.shape[0] for leaf in jax.tree.leaves(spec)
            if getattr(leaf, "ndim", 0) == 1 and leaf.shape[0] >= 4 * 128
        }
    assert set(lowered_at) == {
        "count", "pack", "shuffle_coll", "shuffle_compact"}
    for phase, rows in lowered_at.items():
        assert 4 * table.shard_cap not in rows, phase
    assert lowered_at["count"] == lowered_at["pack"] == {4 * 128}


# -- the state -----------------------------------------------------------
def _frame(rng, n=6000, ids=700):
    return pd.DataFrame({
        "k": rng.integers(0, ids, n).astype(np.int32),
        "i": rng.integers(-40, 40, n).astype(np.int32),
        "x": np.round(rng.random(n) * 100.0, 6),
        "f": rng.random(n).astype(np.float32),
    })


def _null_keys(rng):
    df = _frame(rng)
    k = df["k"].astype("float64")
    k[rng.random(len(df)) < 0.1] = np.nan
    x = df["x"].copy()
    x[rng.random(len(df)) < 0.2] = np.nan
    x[df["k"] == 3] = np.nan  # a group whose every value is null
    return df.assign(k=k, x=x)


def _every_key_on_every_shard(rng):
    df = _frame(rng)
    return df.assign(k=np.tile(np.arange(1500, dtype=np.int32), 4))


def _disjoint_keys(rng):
    df = _frame(rng)
    return df.assign(k=np.sort(df["k"].to_numpy()))


#: name -> (frame, agg, mask of the rows that count | None)
STATE_CASES = {
    "count_beside_sum": (_frame, {"i": ["sum", "count"], "x": "mean"}, None),
    "mean_of_int32": (_frame, {"i": "mean"}, None),
    "every_op_of_one_column": (
        _frame, {"x": ["sum", "count", "min", "max", "mean"],
                 "f": ["mean", "sum"]}, None),
    "null_keys_and_values": (
        _null_keys, {"x": ["sum", "mean", "count", "min"], "i": "max"}, None),
    "masked_input": (
        _frame, {"i": ["sum", "count"], "x": "mean"},
        lambda df: (df["x"] < 70.0) & (df["k"] != 5)),
    "a_shard_with_no_row": (
        _frame, {"i": "sum", "x": ["mean", "max"]},
        lambda df: pd.Series(np.arange(len(df)) >= len(df) // 4)),
    "every_key_on_every_shard": (
        _every_key_on_every_shard, {"i": ["sum", "count"], "x": "sum"}, None),
    "disjoint_keys": (_disjoint_keys, {"i": "min", "x": ["sum", "mean"]}, None),
}


@pytest.mark.parametrize("name", STATE_CASES)
def test_state_against_pandas(name):
    make, agg, keep = STATE_CASES[name]
    df = make(np.random.default_rng(len(name)))
    table = ct.Table.from_pandas(_ctx(4), df)
    mask = None if keep is None else keep(df).to_numpy()
    partial = tracing.get_count(PARTIAL)
    got = table.distributed_groupby("k", agg, _mask=mask, _dense=False)
    assert tracing.get_count(PARTIAL) - partial == 1
    named = {
        f"{c}_{op}": (c, (lambda s: s.sum(min_count=1)) if op == "sum" else op)
        for c, ops in agg.items()
        for op in ([ops] if isinstance(ops, str) else ops)
    }
    kept = df if mask is None else df[mask]
    # a mean adds a float32 column in float64 (pandas' answers in float32)
    kept = kept.assign(f=kept["f"].astype("float64"))
    want = kept.groupby("k", dropna=False).agg(**named).reset_index()
    got = got.to_pandas().sort_values("k", na_position="last")
    assert len(got) == len(want) and list(got.columns) == list(want.columns)
    for c in want.columns:
        # float32 sums add in float32, as the one-shard group-by's do
        rtol = 1e-4 if c == "f_sum" else 1e-12
        npt.assert_allclose(
            got[c].astype("float64"), want[c].astype("float64"), rtol=rtol,
            err_msg=c,
        )
    if name == "every_key_on_every_shard":
        assert _rows(PRECOMBINE[2]) >= 1500  # a partial row a key a shard
    if name == "a_shard_with_no_row":
        assert table.filter(mask).row_counts[0] == 0


def test_mesh_result_has_the_one_shard_group_bys_types():
    agg = {"i": ["sum", "count", "mean", "min"], "f": ["sum", "mean", "max"]}
    df = _frame(np.random.default_rng(3))
    one = ct.Table.from_pandas(_ctx(1), df).groupby("k", agg)
    many = ct.Table.from_pandas(_ctx(4), df).distributed_groupby(
        "k", agg, _dense=False)
    assert many.column_names == one.column_names
    for c in one.column_names:
        assert many.column(c).data.dtype == one.column(c).data.dtype, c
        assert (many.column(c).valid is None) == (one.column(c).valid is None), c


# -- equal bits, and the shares add up -----------------------------------
@pytest.mark.parametrize("world", WORLDS[1:], ids=lambda w: f"w{w}")
def test_two_runs_on_one_seed_give_equal_bits(world):
    agg = {"v1": ["sum", "count"], "v3": ["sum", "mean"]}
    runs = [
        _table(world).distributed_groupby("id6", agg).to_pydict()
        for _ in range(2)
    ]
    for c in runs[0]:
        assert runs[0][c].tobytes() == runs[1][c].tobytes(), c
    # and a second table of the same seed, loaded anew
    data = _data()
    again = ct.Table.from_numpy(
        _ctx(world), list(data), list(data.values())
    ).distributed_groupby("id6", agg).to_pydict()
    for c in again:
        assert again[c].tobytes() == runs[0][c].tobytes(), c


def test_the_shards_partial_tables_add_up_to_the_whole():
    """The pre-combine's output (a partial row a group a shard), combined
    on the host by the reference's own arithmetic, is the reference of the
    uncut table: a sum of sums, a sum of counts, a mean as their ratio."""
    by, agg = ["id6"], {"v1": ["sum", "count"], "v3": "mean", "v2": "max"}
    table = _table(4)
    plan = _PartialAgg.of(
        table, [(c, ct.ops.groupby.agg_op_id(o), o)
                for c, ops in agg.items()
                for o in ([ops] if isinstance(ops, str) else ops)],
    )
    names = [f"{c}_{n}" for c, _o, n in plan.state_specs()]
    # every state once: v1's sum and count, v3's sum and count, v2's max
    assert names == ["v1_sum", "v1_count", "v3_sum", "v3_count", "v2_max"]
    partials = plan.pre_combine(table, by)
    whole = h2o.answer(_data(), by, agg)
    groups = len(whole["id6"])
    assert groups < partials.row_count <= 4 * groups
    assert len(partials.row_counts) == 4 and partials.row_counts.min() > 0
    parts = partials.to_pydict()
    combined = h2o.answer(parts, by, {
        "v1_sum": "sum", "v1_count": "sum", "v3_sum": "sum",
        "v3_count": "sum", "v2_max": "max",
    })
    npt.assert_array_equal(combined["id6"], whole["id6"])
    npt.assert_array_equal(combined["v1_sum_sum"], whole["v1_sum"])
    npt.assert_array_equal(combined["v1_count_sum"], whole["v1_count"])
    npt.assert_array_equal(combined["v2_max_max"], whole["v2_max"])
    npt.assert_allclose(
        combined["v3_sum_sum"] / combined["v3_count_sum"], whole["v3_mean"],
        rtol=1e-13,
    )
    # and the mesh's own combine gives the same
    merged = _in_key_order(table.distributed_groupby(by, agg), by)
    _assert_answer(merged, whole)


# -- a mask rides the sort-and-segment path ------------------------------
@pytest.mark.parametrize("world", [1, 4], ids=lambda w: f"w{w}")
def test_a_mask_rides_the_factorize_sort_as_padding(world):
    """``groupby(_mask=)`` off the dense path equals filter-then-group-by
    for every kind of op, and compacts nothing: no filter program runs."""
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:world])
    )
    df = _null_keys(np.random.default_rng(12))
    table = ct.Table.from_pandas(ctx, df)
    mask = ((df["i"] > -20) & (df["k"] != 7)).to_numpy()
    agg = {"x": ["sum", "mean", "std", "median", "nunique"],
           "i": ["count", "min", "max"]}
    filtered = table.filter(mask).groupby("k", agg, _dense=False).to_pandas()
    ran = {key[0] for key, _fn, _spec in stages.dispatched_programs(ctx)}
    assert "filter" in ran
    fresh = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:world])
    )
    table = ct.Table.from_pandas(fresh, df)
    masked = table.groupby("k", agg, _mask=mask, _dense=False)
    ran = {key[0] for key, _fn, _spec in stages.dispatched_programs(fresh)}
    assert "groupby" in ran and "filter" not in ran
    pd.testing.assert_frame_equal(masked.to_pandas(), filtered, rtol=1e-12)
    if world > 1:
        return
    # rows already in key order have no sort to ride: filtered first
    ordered = table.sort("k").groupby("k", {"x": "sum"}, _mask=mask[
        np.argsort(df["k"].to_numpy(), kind="stable")])
    npt.assert_allclose(
        ordered.to_pandas()["x_sum"].to_numpy(dtype="float64"),
        filtered["x_sum"].to_numpy(dtype="float64"), rtol=1e-12)
