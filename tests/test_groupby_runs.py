"""The sort-and-segment group-by's run reduction (ops/sort.run_reduce and
ops/groupby.groupby_aggregate) against numpy and pandas.

Every aggregate of that path is a segmented scan over the runs of the
factorize sort, and a run's first row reaches its group's slot by the
log-step compress (ops/sort.step_compact); these cases hold its edges: one
run, a run a row, runs of 1 to 100,000 rows, nulls, several keys, no live
row under a padded capacity, and float64 values whose neighbours differ by
sixteen orders of magnitude (where a difference of prefix sums is off by
far more than the 1e-11 the benchmark allows a group's sum).
"""
import functools

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import cylon_tpu as ct
from cylon_tpu.ops import sort as _sort
from cylon_tpu.utils import tracing

OPS = ["sum", "count", "min", "max", "mean", "var", "std"]


# ----------------------------------------------------------------------
# the scan itself
# ----------------------------------------------------------------------

def _numpy_run_reduce(run_end, vals, kind):
    fn = {"sum": np.add, "min": np.minimum, "max": np.maximum}[kind]
    out = vals.copy()
    for i in range(len(vals) - 2, -1, -1):
        if not run_end[i]:
            out[i] = fn(vals[i], out[i + 1])
    return out


@pytest.mark.parametrize("kind", ["sum", "min", "max"])
@pytest.mark.parametrize("n", [1, 7, 128, 129, 1000, 16384 + 5])
@pytest.mark.parametrize("dtype", [np.float64, np.int64])
def test_run_reduce_matches_a_plain_loop(rng, kind, n, dtype):
    run_end = rng.random(n) < 0.05
    run_end[-1] = True
    vals = (rng.normal(size=n) * 1000).astype(dtype)
    (got,) = jax.jit(
        lambda e, v: _sort.run_reduce(e, [v], [kind])
    )(run_end, vals)
    exp = _numpy_run_reduce(run_end, vals, kind)
    if dtype is np.int64 or kind != "sum":
        assert (np.asarray(got) == exp).all()
    else:
        np.testing.assert_allclose(np.asarray(got), exp, rtol=1e-12, atol=1e-9)


def test_run_reduce_scans_several_lanes_at_once(rng):
    n = 5000
    run_end = rng.random(n) < 0.01
    run_end[-1] = True
    a, b = rng.normal(size=n), rng.integers(-50, 50, n)
    got = jax.jit(
        lambda e, x, y: _sort.run_reduce(e, [x, y, y], ["sum", "min", "max"])
    )(run_end, a, b)
    np.testing.assert_allclose(
        np.asarray(got[0]), _numpy_run_reduce(run_end, a, "sum"), rtol=1e-12,
        atol=1e-12,
    )
    assert (np.asarray(got[1]) == _numpy_run_reduce(run_end, b, "min")).all()
    assert (np.asarray(got[2]) == _numpy_run_reduce(run_end, b, "max")).all()


# ----------------------------------------------------------------------
# the compress: a run's first row to its group's slot, without a sort
# ----------------------------------------------------------------------

def _kept(rng, cap, share, where):
    """``keep`` [cap]: ``share`` of the rows of the half (or the whole)
    that ``where`` names; "one" keeps a single row there."""
    lo, hi = {
        "front": (0, max(1, cap // 2)), "back": (cap // 2, cap),
        "spread": (0, cap),
    }[where]
    keep = np.zeros(cap, bool)
    if share == "one":
        keep[rng.integers(lo, hi)] = True
    else:
        keep[lo:hi] = rng.random(hi - lo) < share
    return keep


def _compress_payloads(rng, cap):
    """One payload of every kind the group-by carries, then more of them
    than one sort would take (``RIDE_LANES``)."""
    pays = [
        _ride_lane(rng, d, cap)
        for d in (np.bool_, np.int32, np.uint32, np.int64, np.float32,
                  np.float64)
    ]
    f64 = pays[-1]
    f64[: min(cap, 2)] = [np.nan, -0.0][: min(cap, 2)]
    pays += [_ride_lane(rng, np.float64, cap) for _ in range(_sort.RIDE_LANES)]
    return pays


@functools.lru_cache(maxsize=None)
def _step_compact(bits):
    """One jitted compress a width of a pass: the constant is read while
    tracing, so the two widths must not share a cache of programs."""
    return jax.jit(lambda k, p: _sort.step_compact(k, p))


@pytest.fixture(params=[1, 2], ids=["bit-a-pass", "two-bits-a-pass"])
def step_bits(request, monkeypatch):
    """Both widths of a pass of ``step_compact``, whatever the slots."""
    monkeypatch.setattr(
        _sort, "STEP_TWO_BITS_MIN_SLOTS", 0 if request.param == 2 else 1 << 62
    )
    return request.param


@pytest.mark.parametrize("where", ["front", "back", "spread"])
@pytest.mark.parametrize("share", [0.0, "one", 0.037, 0.63, 1.0])
@pytest.mark.parametrize("cap", [1, 127, 128, 129, 300, 4096, 65_537])
def test_step_compact_moves_kept_rows_to_the_front(
    rng, step_bits, cap, share, where
):
    """The kept rows' payloads lie first, in their order and bit for bit
    (``np.flatnonzero`` is the oracle), whatever their dtype and however
    many; the positions are the kept rows', then the sentinel ``cap``."""
    keep = _kept(rng, cap, share, where)
    pays = _compress_payloads(rng, cap)
    pos, got = _step_compact(step_bits)(keep, pays)
    at = np.flatnonzero(keep)
    assert len(_sort.step_passes(cap)) == -(-(cap - 1).bit_length() // step_bits)
    pos = np.asarray(pos)
    assert pos.dtype == np.int32
    assert (pos[: len(at)] == at).all() and (pos[len(at):] == cap).all()
    assert len(got) == len(pays) > _sort.RIDE_LANES
    for g, p in zip(got, pays):
        g = np.asarray(g)
        assert g.dtype == p.dtype and g.shape == p.shape
        assert g[: len(at)].tobytes() == p[at].tobytes()


def test_step_compact_holds_no_sort_gather_or_scatter():
    keep = jax.numpy.zeros(5000, bool)
    pays = [jax.numpy.zeros(5000, d) for d in (np.uint32, np.int64, np.float64)]
    text = str(jax.make_jaxpr(lambda k, p: _sort.step_compact(k, p))(keep, pays))
    for prim in (" sort[", "gather[", "scatter", "cumsum[", "while[", "scan["):
        assert prim not in text, prim


# ----------------------------------------------------------------------
# the group-by over its edge cases
# ----------------------------------------------------------------------

def _one_group(rng):
    return pd.DataFrame({"k": np.full(3000, 7), "v": rng.normal(size=3000)})


def _row_a_group(rng):
    return pd.DataFrame({"k": rng.permutation(3000), "v": rng.normal(size=3000)})


def _long_and_short_runs(rng):
    # 2,000 groups: runs of 1 to 100,000 rows
    sizes = np.concatenate([
        rng.integers(1, 40, 1996), [1, 1000, 10_000, 100_000]
    ])
    keys = np.repeat(rng.permutation(len(sizes)), sizes)
    rng.shuffle(keys)
    return pd.DataFrame({"k": keys, "v": rng.normal(size=len(keys))})


def _null_values(rng):
    k = rng.integers(0, 40, 2000)
    v = rng.normal(size=2000)
    v[rng.random(2000) < 0.3] = np.nan
    v[k == 5] = np.nan  # a group with no value at all
    v[k == 39] = np.nan
    return pd.DataFrame({"k": k, "v": v})


def _null_and_multi_keys(rng):
    a = rng.integers(0, 6, 2500).astype(np.float64)
    a[rng.random(2500) < 0.1] = np.nan
    b = rng.integers(-3, 3, 2500)
    v = rng.normal(size=2500)
    v[rng.random(2500) < 0.2] = np.nan
    return pd.DataFrame({"a": a, "b": b, "v": v})


def _mixed_magnitudes(rng):
    # neighbouring groups of 1e-8 and 1e+8: a prefix sum over the sorted
    # rows carries 1e+11 by the time it reaches a group that sums to 1e-6
    k = rng.integers(0, 400, 40_000)
    v = rng.random(40_000) * np.where(k % 2 == 0, 1e-8, 1e8)
    return pd.DataFrame({"k": k, "v": v})


CASES = {
    "one-group": _one_group,
    "row-a-group": _row_a_group,
    "runs-1-to-100000": _long_and_short_runs,
    "null-values": _null_values,
    "null-and-multi-keys": _null_and_multi_keys,
    "mixed-magnitudes": _mixed_magnitudes,
}


def _expected(df, keys, op):
    g = df.groupby(keys, dropna=False, sort=True)["v"]
    exp = (g.agg(op) if op != "sum" else g.sum(min_count=1)).reset_index()
    return exp.sort_values(keys, na_position="last").reset_index(drop=True)


def _check(got, exp, keys, op, rtol):
    assert len(got) == len(exp)
    for k in keys:
        np.testing.assert_array_equal(
            got[k].to_numpy(np.float64), exp[k].to_numpy(np.float64)
        )
    g = got[f"v_{op}"].to_numpy(np.float64)
    e = exp["v"].to_numpy(np.float64)
    if op == "count":
        assert (g == e).all()
        return
    assert (np.isnan(g) == np.isnan(e)).all()
    ok = ~np.isnan(e)
    np.testing.assert_allclose(g[ok], e[ok], rtol=rtol, atol=0)


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("case", list(CASES))
def test_groupby_runs_match_pandas(local_ctx, rng, case, op):
    df = CASES[case](rng)
    keys = [c for c in df.columns if c != "v"]
    before = tracing.get_count("groupby.factorize_path")
    got = ct.Table.from_pandas(local_ctx, df).groupby(
        keys, {"v": op}, _dense=False
    ).to_pandas()
    assert tracing.get_count("groupby.factorize_path") == before + 1
    # sums, means and extrema hold to the benchmark's limit; the one-pass
    # variance (sum of squares less the squared sum) cancels, as it did
    rtol = 1e-6 if op in ("var", "std") else 1e-11
    if case == "mixed-magnitudes" and op in ("var", "std"):
        rtol = 1e-3
    _check(got, _expected(df, keys, op), keys, op, rtol)


def test_prefix_differences_would_fail_the_mixed_magnitudes(rng):
    """The control of the case above: the same sums as differences of one
    float64 prefix sum over the sorted rows miss 1e-11 by orders."""
    df = _mixed_magnitudes(rng).sort_values("k", kind="stable")
    prefix = np.cumsum(df["v"].to_numpy())
    last = np.flatnonzero(np.diff(df["k"].to_numpy(), append=-1))
    sums = np.diff(prefix[last], prepend=0.0)
    exp = df.groupby("k")["v"].sum().to_numpy()
    assert np.max(np.abs(sums - exp) / exp) > 1e-9


@pytest.mark.parametrize("op", OPS)
def test_groupby_of_no_live_row_under_a_padded_capacity(local_ctx, rng, op):
    df = _null_values(rng)
    t = ct.Table.from_pandas(local_ctx, df)
    none = t.filter(np.zeros(len(df), bool))
    assert none.shard_cap > 0
    got = none.groupby("k", {"v": op}, _dense=False)
    assert got.row_count == 0
    # and with a few live rows under the same padding
    few = t.filter(df["k"].to_numpy() < 3)
    got = few.groupby("k", {"v": op}, _dense=False).to_pandas()
    _check(got, _expected(df[df["k"] < 3], ["k"], op), ["k"], op, 1e-6)


@pytest.mark.parametrize("case", list(CASES))
def test_pipeline_groupby_on_sorted_input_gives_the_groupby_table(
    local_ctx, rng, case
):
    df = CASES[case](rng)
    keys = [c for c in df.columns if c != "v"]
    agg = {"v": ["sum", "count", "min", "max", "mean", "var", "std"]}
    t = ct.Table.from_pandas(local_ctx, df)
    a = t.sort(keys).pipeline_groupby(keys, agg).to_pandas()
    b = t.groupby(keys, agg, _dense=False).to_pandas()
    assert list(a.columns) == list(b.columns) and len(a) == len(b)
    for c in a.columns:
        x, y = a[c].to_numpy(np.float64), b[c].to_numpy(np.float64)
        assert (np.isnan(x) == np.isnan(y)).all()
        if c in keys or c in ("v_count", "v_min", "v_max"):
            np.testing.assert_array_equal(x, y)
        else:
            # the same values added in another order within a run
            np.testing.assert_allclose(x, y, rtol=1e-6, atol=0)


@pytest.mark.parametrize("op", ["nunique", "median"])
def test_pair_sorted_ops_use_the_same_runs(local_ctx, rng, op):
    df = _null_values(rng)
    df["v"] = np.round(df["v"], 1)
    got = ct.Table.from_pandas(local_ctx, df).groupby(
        "k", {"v": op}, _dense=False
    ).to_pandas()
    exp = _expected(df, ["k"], op)
    g, e = got[f"v_{op}"].to_numpy(np.float64), exp["v"].to_numpy(np.float64)
    if op == "nunique":
        assert (g == e).all()
    else:
        assert (np.isnan(g) == np.isnan(e)).all()
        np.testing.assert_allclose(g[~np.isnan(e)], e[~np.isnan(e)], rtol=1e-12)


# ----------------------------------------------------------------------
# keys read back out of the fused sort words
# ----------------------------------------------------------------------

def _word_key_frames(rng):
    n = 3000
    v = rng.normal(size=n)
    return {
        "int64-far-from-zero": pd.DataFrame({
            "k": rng.integers(0, 500, n) + (1 << 40), "v": v}),
        "int8-negative": pd.DataFrame({
            "k": rng.integers(-100, 100, n).astype(np.int8), "v": v}),
        "nullable-int": pd.DataFrame({
            "k": pd.array(
                np.where(rng.random(n) < 0.1, None, rng.integers(-5, 60, n)),
                dtype="Int64",
            ), "v": v}),
        "bool-and-string": pd.DataFrame({
            "a": rng.random(n) < 0.5,
            "b": rng.choice(["x", "yy", "zzz", "w"], n), "v": v}),
        "uint32-and-int16": pd.DataFrame({
            "a": rng.integers(0, 9, n).astype(np.uint32) + np.uint32(4e9),
            "b": rng.integers(-300, 300, n).astype(np.int16), "v": v}),
    }


@pytest.mark.parametrize(
    "case", ["int64-far-from-zero", "int8-negative", "nullable-int",
             "bool-and-string", "uint32-and-int16"],
)
def test_keys_decoded_from_the_sort_words_match_pandas(local_ctx, rng, case):
    df = _word_key_frames(rng)[case]
    keys = [c for c in df.columns if c != "v"]
    before = tracing.get_count("lane_pack.groupby_fused")
    # (from_pandas makes a nullable integer a float64: arrow keeps it one)
    got = ct.Table.from_arrow(
        local_ctx, pa.Table.from_pandas(df, preserve_index=False)
    ).groupby(keys, {"v": ["sum", "count"]}, _dense=False).to_pandas()
    # the fused plan engaged, so the keys did not ride: they were decoded
    assert tracing.get_count("lane_pack.groupby_fused") == before + 1
    exp = df.groupby(keys, dropna=False, sort=True)["v"].agg(
        ["sum", "count"]).reset_index()
    exp = exp.sort_values(keys, na_position="last").reset_index(drop=True)
    assert len(got) == len(exp)
    for k in keys:
        g, e = got[k], exp[k]
        assert (g.isna().to_numpy() == e.isna().to_numpy()).all()
        ok = ~e.isna().to_numpy()
        assert (g[ok].to_numpy() == e[ok].to_numpy()).all(), k
    np.testing.assert_allclose(
        got["v_sum"].to_numpy(np.float64), exp["sum"].to_numpy(), rtol=1e-11
    )
    assert (got["v_count"].to_numpy() == exp["count"].to_numpy()).all()


# ----------------------------------------------------------------------
# many columns: the payloads ride the sort in batches
# ----------------------------------------------------------------------

_RIDE_DTYPES = [
    np.float64, np.int64, np.uint64, np.float32, np.int32, np.uint32,
    np.int16, np.uint16, np.int8, np.uint8, np.bool_,
]


def _ride_lane(rng, dtype, n):
    if dtype is np.bool_:
        return rng.random(n) < 0.5
    if np.issubdtype(dtype, np.floating):
        x = rng.normal(size=n).astype(dtype)
        x[:4] = [np.nan, -0.0, np.inf, -np.inf][: len(x)]
        return x
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, n, dtype=dtype, endpoint=True)


@pytest.mark.parametrize("lanes", [8, 9, 11, 23, 40])
def test_ride_sort_in_batches_is_the_one_sort(rng, lanes):
    """Any number of payloads of any dtypes come out of ``ride_sort`` as
    they come out of one sort, bit for bit."""
    n = 777
    key = rng.integers(0, 50, n).astype(np.int32)
    pays = [
        _ride_lane(rng, _RIDE_DTYPES[i % len(_RIDE_DTYPES)], n)
        for i in range(lanes)
    ]

    def one_sort(ps):
        out = jax.lax.sort(tuple([key] + list(ps)), num_keys=1, is_stable=True)
        return out[0], list(out[1:])

    skey, got = jax.jit(lambda ps: _sort.ride_sort(one_sort, ps))(pays)
    order = np.argsort(key, kind="stable")
    assert (np.asarray(skey) == key[order]).all()
    for g, p in zip(got, pays):
        g = np.asarray(g)
        assert g.dtype == p.dtype
        assert g.tobytes() == p[order].tobytes()


def test_ride_sort_batches_only_past_its_lanes(rng):
    key = jax.numpy.arange(64, dtype=np.int32)

    def one_sort(ps):
        out = jax.lax.sort(tuple([key] + list(ps)), num_keys=1)
        return out[0], list(out[1:])

    def sorts(k):
        pays = [jax.numpy.zeros(64, np.float64)] * k
        text = str(jax.make_jaxpr(lambda ps: _sort.ride_sort(one_sort, ps))(pays))
        return text.count(" sort["), text.count("scan[") + text.count("while[")

    assert sorts(_sort.RIDE_LANES // 2) == (1, 0)
    # the keys' own sort, and one sort in the loop over the batches
    assert sorts(_sort.RIDE_LANES // 2 + 1) == (2, 1)
    assert sorts(4 * _sort.RIDE_LANES) == (2, 1)


@pytest.mark.parametrize("dtypes,want", [
    ([np.int64, np.float64], (4, 1)),  # the suite's table fits one sort
    ([np.float64] * 4, (8, 1)),
    ([np.float64] * 5, (10, 2)),
    ([np.int64] + [np.float64] * 5, (12, 3)),
    ([np.int32] * 8 + [np.bool_], (9, 2)),
    ([np.float64, np.int64, np.float32, np.int8, np.bool_] * 4, (28, 4)),
], ids=lambda v: str(len(v)) if isinstance(v, list) else None)
def test_ride_census_counts_the_sorts_ride_sort_runs(dtypes, want):
    """``ride_census`` (what the host counts at dispatch) says how many
    lanes ride and how many sorts carry them: the program ``ride_sort``
    traces holds that many, the loops' trips summed."""
    import re

    assert _sort.ride_census(dtypes) == want
    key = jax.numpy.arange(64, dtype=np.int32)

    def one_sort(ps):
        out = jax.lax.sort(tuple([key] + list(ps)), num_keys=1)
        return out[0], list(out[1:])

    pays = [jax.numpy.zeros(64, d) for d in dtypes]
    text = str(jax.make_jaxpr(lambda ps: _sort.ride_sort(one_sort, ps))(pays))
    trips = sum(int(n) for n in re.findall(r"length=(\d+)", text))
    if want[0] <= _sort.RIDE_LANES:
        assert trips == 0 and text.count(" sort[") == 1
    else:
        assert trips == want[1]


@pytest.mark.parametrize("nullable", [False, True])
@pytest.mark.parametrize("presorted", [False, True])
def test_groupby_of_many_columns_matches_pandas(
    local_ctx, rng, nullable, presorted
):
    """Twelve float64 columns and four narrower ones, more lanes than one
    sort carries: every aggregate against pandas."""
    n = 6000
    df = pd.DataFrame({"k": rng.integers(0, 300, n).astype(np.int64)})
    agg = {}
    for j in range(12):
        v = rng.normal(size=n)
        if nullable and j % 3 == 0:
            v[rng.random(n) < 0.2] = np.nan
        df[f"f{j}"] = v
        agg[f"f{j}"] = ["sum", "mean"] if j % 2 else ["min", "max"]
    df["i32"] = rng.integers(-1000, 1000, n).astype(np.int32)
    df["i16"] = rng.integers(-1000, 1000, n).astype(np.int16)
    df["u8"] = rng.integers(0, 255, n).astype(np.uint8)
    df["f32"] = rng.normal(size=n).astype(np.float32)
    agg.update({"i32": ["sum", "min"], "i16": ["max", "count"],
                "u8": ["sum", "max"], "f32": ["min", "max"]})
    if presorted:
        df = df.sort_values("k", kind="stable").reset_index(drop=True)
    t = ct.Table.from_pandas(local_ctx, df)
    got = (
        t.pipeline_groupby("k", agg) if presorted
        else t.groupby("k", agg, _dense=False)
    ).to_pandas()
    exp = df.groupby("k", sort=True).agg(agg)
    exp.columns = [f"{c}_{op}" for c, op in exp.columns]
    exp = exp.reset_index()
    assert (got["k"].to_numpy() == exp["k"].to_numpy()).all()
    for name in exp.columns[1:]:
        g = got[name].to_numpy(np.float64)
        e = exp[name].to_numpy(np.float64)
        if name.endswith("_sum"):  # pandas sums an all-null group to 0
            e = np.where(np.isnan(g), np.nan, e)
        assert (np.isnan(g) == np.isnan(e)).all(), name
        ok = ~np.isnan(e)
        np.testing.assert_allclose(g[ok], e[ok], rtol=1e-11, err_msg=name)
