"""The whole sort-and-segment group-by, however its run heads reach their
slots (PR 46): the compaction sort that moved them until then (kept as an
oracle in ``compact_cases.py``) and either width of a pass of the log-step
compress (``ops/sort.step_compact``) give ONE table, bit for bit, and that
table is pandas'. Then what the host counts at dispatch, and the scopes the
moves carry in the lowered program.

A file of its own beside ``test_groupby_runs.py`` (whose cases, ops and
checks it borrows) so that the two halves run on two workers: every case
here builds its kernels in fresh contexts.
"""
import functools
import re

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import cylon_tpu as ct
from cylon_tpu.ops import sort as _sort
from cylon_tpu.utils import tracing

import compact_cases
import test_groupby_runs as runs


@functools.lru_cache(maxsize=None)
def _case_tables(case):
    """``{way: (live bits, frame)}`` of ``case``'s group-by with every op
    of ``runs.OPS`` at once, a fresh context a way."""
    df = runs.CASES[case](np.random.default_rng(42))
    keys = [c for c in df.columns if c != "v"]
    out = {}
    for way in compact_cases.WAYS:
        with pytest.MonkeyPatch.context() as patch:
            ctx = compact_cases.fresh_ctx(patch, way)
            got = ct.Table.from_pandas(ctx, df).groupby(
                keys, {"v": runs.OPS}, _dense=False
            )
            out[way] = (compact_cases.live_bits(got), got.to_pandas())
    return df, keys, out


@pytest.mark.parametrize("op", runs.OPS)
@pytest.mark.parametrize("case", list(runs.CASES))
def test_groupby_is_one_table_however_the_heads_move(case, op):
    df, keys, tables = _case_tables(case)
    bits, frame = tables["bit-a-pass"]
    for way in ("sort", "two-bits-a-pass"):
        other, _frame = tables[way]
        for name in keys + [f"v_{op}"]:
            assert other[name] == bits[name], (way, name)
    rtol = 1e-6 if op in ("var", "std") else 1e-11
    if case == "mixed-magnitudes" and op in ("var", "std"):
        rtol = 1e-3
    runs._check(frame, runs._expected(df, keys, op), keys, op, rtol)


def _masked_groupby(ctx, rng):
    df = runs._null_and_multi_keys(rng)
    mask = (df["b"] != 0).to_numpy() & (rng.random(len(df)) < 0.7)
    got = ct.Table.from_pandas(ctx, df).groupby(
        ["a", "b"], {"v": ["sum", "count", "max"]}, _dense=False, _mask=mask
    )
    exp = df[mask].groupby(["a", "b"], dropna=False)["v"].agg(
        ["count", "max"]).reset_index()
    return got, exp.rename(columns={"count": "v_count", "max": "v_max"})


def _presorted_groupby(ctx, rng):
    df = runs._null_values(rng).sort_values("k", kind="stable")
    df = df.reset_index(drop=True)
    got = ct.Table.from_pandas(ctx, df).pipeline_groupby(
        "k", {"v": ["sum", "count", "min", "mean"]}
    )
    exp = df.groupby("k")["v"].agg(["count", "min", "mean"]).reset_index()
    return got, exp.rename(columns=lambda c: c if c == "k" else f"v_{c}")


def _pair_sorted_groupby(ctx, rng):
    # NUNIQUE and QUANTILE read ``first``, the head's own position
    df = runs._null_values(rng)
    df["v"] = np.round(df["v"], 1)
    got = ct.Table.from_pandas(ctx, df).groupby(
        "k", {"v": ["nunique", "median", "count"]}, _dense=False
    )
    exp = df.groupby("k")["v"].agg(["nunique", "median", "count"]).reset_index()
    return got, exp.rename(columns=lambda c: c if c == "k" else f"v_{c}")


def _many_nullable_columns(ctx, rng):
    n = 2000
    df = pd.DataFrame({"k": rng.integers(0, 200, n).astype(np.int64)})
    agg = {}
    for j in range(6):
        v = rng.normal(size=n)
        v[rng.random(n) < 0.2] = np.nan
        df[f"f{j}"] = v
        agg[f"f{j}"] = ["min", "max"] if j % 2 else ["mean", "count"]
    df["i32"] = rng.integers(-1000, 1000, n).astype(np.int32)
    agg["i32"] = ["sum", "min"]
    got = ct.Table.from_pandas(ctx, df).groupby("k", agg, _dense=False)
    exp = df.groupby("k").agg(agg)
    exp.columns = [f"{c}_{op}" for c, op in exp.columns]
    return got, exp.reset_index()


def _word_keys(ctx, rng):
    df = runs._word_key_frames(rng)["bool-and-string"]
    got = ct.Table.from_arrow(
        ctx, pa.Table.from_pandas(df, preserve_index=False)
    ).groupby(["a", "b"], {"v": ["sum", "count"]}, _dense=False)
    exp = df.groupby(["a", "b"])["v"].agg(["sum", "count"]).reset_index()
    return got, exp.rename(columns={"sum": "v_sum", "count": "v_count"})


SHAPES = {
    "mask": _masked_groupby,
    "presorted": _presorted_groupby,
    "nunique-median": _pair_sorted_groupby,
    "many-nullable-columns": _many_nullable_columns,
    "keys-from-the-sort-words": _word_keys,
}


@pytest.mark.parametrize("way", ["sort", "two-bits-a-pass"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_groupby_shapes_are_one_table_however_the_heads_move(
    monkeypatch, shape, way
):
    """A masked input, rows already in key order, the ops that read a
    head's own position, nullable values in more lanes than one sort
    carried, keys decoded from the sort words: each against pandas and
    equal, bit for bit, to the table of the default pass."""
    with pytest.MonkeyPatch.context() as patch:
        ctx = compact_cases.fresh_ctx(patch, "bit-a-pass")
        table, exp = SHAPES[shape](ctx, np.random.default_rng(7))
        want = compact_cases.live_bits(table)
        frame = table.to_pandas()
    ctx = compact_cases.fresh_ctx(monkeypatch, way)
    got, _exp = SHAPES[shape](ctx, np.random.default_rng(7))
    assert compact_cases.live_bits(got) == want
    assert len(frame) == len(exp)
    for name in exp.columns:
        g, e = frame[name], exp[name]
        if e.dtype == bool or not pd.api.types.is_numeric_dtype(e):
            assert (g.to_numpy() == e.to_numpy()).all(), name
            continue
        g, e = g.to_numpy(np.float64), e.to_numpy(np.float64)
        assert (np.isnan(g) == np.isnan(e)).all(), name
        np.testing.assert_allclose(
            g[~np.isnan(e)], e[~np.isnan(e)], rtol=1e-11, err_msg=name
        )


COMPACT_COUNTERS = ("groupby.compact.steps", "groupby.compact.passes")


@pytest.mark.parametrize("rows,cap,passes", [
    (500, 512, 9),     # under the rule: a pass a bit of the slot count
    (3000, 4096, 6),   # from it on: a pass a two bits
])
def test_groupby_dispatch_counts_the_compress(monkeypatch, rng, rows, cap, passes):
    """A sort-and-segment group-by bumps ``groupby.compact.steps`` with
    its slots and ``groupby.compact.passes`` with the passes over them,
    counted from the rule the kernel follows (``step_passes``)."""
    from cylon_tpu.obs import metrics as obs_metrics

    monkeypatch.setattr(_sort, "STEP_TWO_BITS_MIN_SLOTS", 1024)
    ctx = ct.CylonContext.init()
    table = ct.Table.from_pandas(ctx, pd.DataFrame({
        "k": rng.integers(0, 90, rows), "v": rng.normal(size=rows)}))
    assert table.shard_cap == cap
    before = {c: tracing.snapshot().get(c, {}) for c in COMPACT_COUNTERS}
    assert table.groupby("k", {"v": "sum"}, _dense=False).row_count == 90
    for name, moved in zip(COMPACT_COUNTERS, (cap, passes)):
        now = tracing.snapshot()[name]
        assert now["count"] - before[name].get("count", 0) == 1, name
        assert now["rows"] - before[name].get("rows", 0) == moved, name
        assert obs_metrics.is_declared(name) and name in obs_metrics.STABLE_METRICS
    assert len(_sort.step_passes(cap)) == passes
    # a dense group-by compacts nothing
    before = {c: tracing.get_count(c) for c in COMPACT_COUNTERS}
    table.groupby("k", {"v": "sum"})
    assert {c: tracing.get_count(c) for c in COMPACT_COUNTERS} == before


def test_the_compress_runs_under_its_callers_stage_and_no_sort_engine(rng):
    """``jit_groupby`` holds ONE sort, the factorize sort under
    ``groupby.key_ids`` and ``sort_engine``; the moves of the run heads
    (pads and selects) carry ``groupby.segment_sum`` and never
    ``sort_engine`` on their ``op_name`` path: the compress is no sort,
    and ``sort_engine_ms`` does not count it."""
    from cylon_tpu.obs import stages

    ctx = ct.CylonContext.init()
    table = ct.Table.from_pandas(ctx, pd.DataFrame({
        "k": rng.integers(0, 900, 3000) + (1 << 33), "v": rng.normal(size=3000)}))
    assert table.groupby("k", {"v": "sum"}, _dense=False).row_count > 0
    texts = [
        fn.lower(*spec).compile().as_text()
        for _key, fn, spec in stages.dispatched_programs(ctx)
        if fn.__name__ == "groupby"
    ]
    assert len(texts) == 1
    # every instruction (fused ones too) with its ``op_name`` path
    rows = [
        (stages._METADATA.sub("", line), found[1])
        for line in texts[0].splitlines()
        if (found := stages._OP_NAME.search(line))
    ]
    sorts = [path for text, path in rows if re.search(r"\ssort\(", text)]
    assert sorts and all(
        stages.in_sort_engine(p) and stages.stage_of(p) == stages.GROUPBY_KEY_IDS
        for p in sorts
    ), sorts
    under = [
        (text, path) for text, path in rows
        if stages.stage_of(path) == stages.GROUPBY_SEGMENT_SUM
    ]
    moves = [path for text, path in under if re.search(r"\s(pad|select)\(", text)]
    assert len(moves) >= 2 * len(_sort.step_passes(table.shard_cap))
    assert not [path for _text, path in under if stages.in_sort_engine(path)]
