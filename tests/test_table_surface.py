"""pycylon Table surface breadth: where/mask, __getitem__/__setitem__,
iterrows, string astype, row-UDF select.

Reference analog: python/pycylon/data/table.pyx:1066-2411 (getitem/setitem
filters, where, iterrows, astype) and cpp table.cpp:504-529 (UDF Select with
a Row cursor, row.hpp:24-52). Oracle: pandas.
"""
import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct


@pytest.fixture
def tbl(world_ctx, rng):
    df = pd.DataFrame(
        {
            "a": rng.integers(0, 10, 60).astype(np.int64),
            "b": rng.normal(size=60),
            "s": rng.choice(["x", "y", "z"], 60),
        }
    )
    df.loc[5, "b"] = np.nan
    return ct.Table.from_pandas(world_ctx, df), df


def _sorted_eq(t, df):
    a = t.to_pandas().sort_values(list(df.columns)).reset_index(drop=True)
    b = df.sort_values(list(df.columns)).reset_index(drop=True)
    pd.testing.assert_frame_equal(a, b, check_dtype=False)


def test_where_null(tbl):
    t, df = tbl
    cond = t["a"] > 4
    out = t.project(["a", "b"]).where(cond).to_pandas()
    exp = df[["a", "b"]].where(df["a"] > 4)
    assert np.allclose(out["b"].to_numpy(), exp["b"].to_numpy(), equal_nan=True)
    assert np.allclose(out["a"].to_numpy(), exp["a"].to_numpy(), equal_nan=True)


def test_where_other_scalar(tbl):
    t, df = tbl
    cond = t["a"] > 4
    out = t.project(["a"]).where(cond, -1).to_pandas()
    exp = df[["a"]].where(df["a"] > 4, -1)
    assert (out["a"].to_numpy() == exp["a"].to_numpy()).all()


def test_mask_scalar(tbl):
    t, df = tbl
    cond = t["a"] > 4
    out = t.project(["a"]).mask(cond, 0).to_pandas()
    exp = df[["a"]].mask(df["a"] > 4, 0)
    assert (out["a"].to_numpy() == exp["a"].to_numpy()).all()


def test_where_string_col(tbl):
    t, df = tbl
    cond = t["a"] > 4
    out = t.project(["s"]).where(cond, "none").to_pandas()
    exp = df[["s"]].where(df["a"] > 4, "none")
    assert (out["s"].to_numpy() == exp["s"].to_numpy()).all()


def test_getitem_forms(tbl):
    t, df = tbl
    assert t["a"].column_names == ["a"]
    assert t[["a", "s"]].column_names == ["a", "s"]
    filt = t[t["a"] > 4]
    assert filt.row_count == int((df["a"] > 4).sum())
    sl = t[10:20]
    assert sl.row_count == 10
    assert (sl.to_pandas()["a"].to_numpy() == df["a"].to_numpy()[10:20]).all()


def test_setitem_column_and_scalar(tbl):
    t, df = tbl
    t["c"] = np.arange(60)
    assert "c" in t.column_names
    assert (t.to_pandas()["c"].to_numpy() == np.arange(60)).all()
    t["d"] = 7
    assert (t.to_pandas()["d"].to_numpy() == 7).all()


def test_setitem_mask(tbl):
    t, df = tbl
    num = t.project(["a"])
    num[num["a"] > 4] = 0
    exp = df[["a"]].mask(df["a"] > 4, 0)
    assert (num.to_pandas()["a"].to_numpy() == exp["a"].to_numpy()).all()


def test_iterrows(tbl):
    t, df = tbl
    rows = list(t.iterrows())
    assert len(rows) == len(df)
    # spot check a handful of rows (order preserved)
    for i in (0, 7, 59):
        idx, row = rows[i]
        assert row["a"] == df["a"].iloc[i]
        assert row["s"] == df["s"].iloc[i]


def test_astype_numeric_to_string(tbl):
    t, df = tbl
    out = t.project(["a"]).astype(str).to_pandas()
    assert (out["a"].to_numpy() == df["a"].astype(str).to_numpy()).all()


def test_astype_string_to_numeric(world_ctx):
    df = pd.DataFrame({"v": ["1", "2", "30", "2"]})
    t = ct.Table.from_pandas(world_ctx, df)
    out = t.astype({"v": np.int64}).to_pandas()
    assert (out["v"].to_numpy() == np.array([1, 2, 30, 2])).all()
    outf = t.astype({"v": np.float32}).to_pandas()
    assert np.allclose(outf["v"].to_numpy(), [1.0, 2.0, 30.0, 2.0])


def test_select_rows_udf(tbl):
    t, df = tbl
    out = t.select_rows(lambda r: r["a"] > 4 and r["s"] != "x")
    exp = df[(df["a"] > 4) & (df["s"] != "x")]
    assert out.row_count == len(exp)
    _sorted_eq(out, exp)


def test_row_cursor(tbl):
    t, _ = tbl
    from cylon_tpu.table import Row

    host = t.to_pydict()
    r = Row(host, 3)
    assert set(r.keys()) == {"a", "b", "s"}
    assert r.row_index == 3
    assert r["a"] == host["a"][3]


def test_join_config_object(local_ctx, rng):
    """JoinConfig object form (reference join_config.hpp:26-189 with static
    builders)."""
    import pandas as pd

    a = pd.DataFrame({"k": rng.integers(0, 10, 50), "x": rng.normal(size=50)})
    b = pd.DataFrame({"k": rng.integers(0, 10, 40), "y": rng.normal(size=40)})
    ta, tb = ct.Table.from_pandas(local_ctx, a), ct.Table.from_pandas(local_ctx, b)
    cfg = ct.JoinConfig.inner_join(on="k", suffixes=("_l", "_r"))
    out = ta.join(tb, config=cfg)
    exp = a.merge(b, on="k", suffixes=("_l", "_r"))
    assert out.row_count == len(exp)
    assert "k_l" in out.column_names and "k_r" in out.column_names
    with pytest.raises(ValueError):
        ct.JoinConfig("inner", algorithm="quantum")
    with pytest.raises(ValueError):
        ct.JoinConfig("sideways")


# ----------------------------------------------------------------------
# a column that arrives dictionary-coded (PR 48)
# ----------------------------------------------------------------------
def _coded(codes, words):
    kind = np.dtype(np.int32, metadata={"dictionary": np.asarray(words)})
    return np.asarray(codes, np.int32).astype(kind)


def test_codes_whose_dtype_carries_their_dictionary_load_as_strings(world_ctx, rng):
    """``np.dtype(int32, metadata={"dictionary": ...})``: the codes are
    loaded as they are, over the dictionary as it is (values that occur or
    not), and the column is a string column like any other: it decodes,
    sorts by value and joins a column coded by the loader itself."""
    words = np.array(["id1", "id10", "id2", "id3", "id7"])
    codes = rng.integers(0, 4, 60)  # "id7" never occurs
    t = ct.Table.from_numpy(
        world_ctx, ["s", "v"], [_coded(codes, words), np.arange(60.0)]
    )
    col = t.column("s")
    assert col.dtype.is_dictionary and col.dictionary is words
    assert col.data.dtype == np.int32 and col.valid is None
    got = t.to_pandas()
    assert (got["s"].to_numpy() == words[codes]).all()
    by_value = t.distributed_sort("s").to_pandas()["s"].to_numpy().astype(str)
    assert (by_value == np.sort(words[codes])).all()
    other = ct.Table.from_pandas(world_ctx, pd.DataFrame(
        {"s": ["id2", "id7", "id9"], "w": [1.0, 2.0, 3.0]}
    ))
    join = t.distributed_join(other, on="s", how="inner")
    want = got.merge(other.to_pandas(), on="s")
    assert join.row_count == len(want) == int((codes == 2).sum())


@pytest.mark.parametrize("codes,words,why", [
    ([0, 1], ["b", "a"], "sorted"),
    ([0, 1], ["a", "a"], "sorted"),
    ([0, 2], ["a", "b"], "outside"),
    ([-1, 0], ["a", "b"], "outside"),
    ([0, 1], [1, 2], "unicode"),
])
def test_a_coded_column_is_checked_for_what_it_takes(local_ctx, codes, words, why):
    with pytest.raises(ValueError, match=why):
        ct.Table.from_numpy(local_ctx, ["s"], [_coded(codes, words)])
