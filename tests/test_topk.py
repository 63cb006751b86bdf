"""``Table.topk`` and the planner's ``TopK``: the first ``n`` rows of the
global order, held to ``sort`` then ``head`` (the path it replaces) and to
pandas' stable ``sort_values``; the rewrite that makes it from
``Limit(Sort(x))``; and the programs it runs (one, ``jit_topk``, under the
``sort.topk`` scope, which sorts no payload column)."""
import numpy as np
import pandas as pd
import pandas.testing as pdt
import pytest

import cylon_tpu as ct
from cylon_tpu.obs import stages
from cylon_tpu.plan import col
from cylon_tpu.utils import tracing


def _ctx(devices, world):
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )


def _frame(rng, rows, ties=False, nulls=False):
    keyspace = 7 if ties else 10 * rows + 10
    df = pd.DataFrame({
        "a": rng.permutation(keyspace)[:rows] if not ties
        else rng.integers(0, keyspace, rows),
        "b": rng.normal(size=rows),
        "s": rng.choice(["ash", "birch", "cedar", "elm"], rows),
        "row": np.arange(rows),
    })
    if nulls:
        df.loc[rng.random(rows) < 0.2, "b"] = np.nan
    return df


#: (id, sort keys, directions, n, rows, ties in the keys, nulls)
CASES = [
    ("descending", ["a"], [False], 10, 300, False, False),
    ("ascending", ["a"], [True], 5, 300, False, False),
    ("two-keys-mixed", ["a", "b"], [True, False], 25, 300, True, False),
    ("float64-desc", ["b"], [False], 10, 300, False, False),
    ("nulls-last", ["b"], [True], 290, 300, False, True),
    ("nulls-last-desc", ["b", "row"], [False, True], 290, 300, False, True),
    ("ties-row-order", ["a"], [False], 40, 300, True, False),
    ("dictionary-key", ["s", "a"], [True, False], 12, 300, False, False),
    ("n-over-rows", ["a"], [True], 1000, 37, False, False),
    ("n-zero", ["a"], [True], 0, 37, False, False),
    ("one-row", ["a"], [False], 3, 1, False, False),
]


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize(
    "case", CASES, ids=[c[0] for c in CASES]
)
def test_topk_is_sort_then_head(devices, rng, case, world):
    _id, by, asc, n, rows, ties, nulls = case
    ctx = _ctx(devices, world)
    df = _frame(rng, rows, ties, nulls)
    t = ct.Table.from_pandas(ctx, df)
    got = t.topk(by, n, asc).to_pandas().reset_index(drop=True)
    old = t.distributed_sort(by, asc).to_pandas().head(n).reset_index(drop=True)
    want = df.sort_values(
        by, ascending=asc, kind="stable", na_position="last"
    ).head(n).reset_index(drop=True)
    assert len(got) == min(n, rows)
    # the keys position for position, against both oracles
    pdt.assert_frame_equal(got[by], want[by], check_dtype=False)
    pdt.assert_frame_equal(got[by], old[by], check_dtype=False)
    if world == 1 or not ties:
        # every column: ties break in row order, as a stable sort breaks
        # them (across a mesh the sample sort's exchange decides among
        # rows equal in every key, for the old path as for this one)
        tied = got[by].duplicated(keep=False).any()
        if world == 1 or not tied:
            pdt.assert_frame_equal(got, want, check_dtype=False)
    else:
        # the rows are rows of the table, each at most once
        assert got["row"].is_unique
        merged = got.merge(df, on=["row"], suffixes=("", "_src"))
        assert len(merged) == len(got)


def test_topk_fetches_nothing_on_one_device(devices, rng):
    ctx = _ctx(devices, 1)
    t = ct.Table.from_pandas(ctx, _frame(rng, 500))
    t.topk("a", 10, False)  # compile
    before = tracing.get_count("host_sync")
    out = t.topk("a", 10, False)
    assert tracing.get_count("host_sync") == before
    assert out.shard_cap == 16  # round_cap(n), not the table's capacity
    assert out.row_count == 10  # the count's own fetch
    assert tracing.get_count("host_sync") == before + 1


def test_the_rule_makes_a_topk_of_limit_over_sort(devices, rng):
    ctx = _ctx(devices, 1)
    df = _frame(rng, 400, ties=True)
    t = ct.Table.from_pandas(ctx, df)
    lf = t.lazy().filter(col("a") > 1).sort(
        ["a", "b"], ascending=[False, True]
    ).limit(15)
    text = lf.explain()
    optimized = text.split("== Optimized plan ==")[1]
    assert "TopK 15 by [a, b] asc=[False, True]" in optimized
    assert "Sort" not in optimized and "Limit" not in optimized
    assert "topk x1" in text
    before = tracing.get_count("plan.topk")
    got = lf.collect().to_pandas().reset_index(drop=True)
    assert tracing.get_count("plan.topk") == before + 1
    want = df[df["a"] > 1].sort_values(
        ["a", "b"], ascending=[False, True], kind="stable"
    ).head(15).reset_index(drop=True)
    pdt.assert_frame_equal(got, want, check_dtype=False)


@pytest.mark.parametrize("world", [1, 4])
def test_lazy_limit_over_sort_equals_the_eager_pair(devices, rng, world):
    ctx = _ctx(devices, world)
    df = _frame(rng, 600)
    t = ct.Table.from_pandas(ctx, df)
    got = t.lazy().sort("b", ascending=False).limit(20).collect().to_pandas()
    want = df.sort_values("b", ascending=False, kind="stable").head(20)
    pdt.assert_frame_equal(
        got.reset_index(drop=True), want.reset_index(drop=True),
        check_dtype=False,
    )


def test_a_limit_without_a_sort_stays_a_limit(devices, rng):
    ctx = _ctx(devices, 1)
    t = ct.Table.from_pandas(ctx, _frame(rng, 50))
    text = t.lazy().limit(5).explain()
    assert "TopK" not in text and "Limit 5" in text
    assert t.lazy().limit(5).collect().row_count == 5


def test_topk_sorts_keys_and_a_position_and_nothing_else(devices, rng):
    """The program: one, named for what it does, every instruction of the
    sort under ``sort.topk`` and the engine's scope, its operands the key
    lanes, the padding class and one row position: no payload rides."""
    ctx = _ctx(devices, 1)
    df = _frame(rng, 256)
    t = ct.Table.from_pandas(ctx, df)
    t.topk("a", 8, False).row_count
    programs = {
        fn.__name__: (fn, spec)
        for _k, fn, spec in stages.dispatched_programs(ctx)
    }
    fn, spec = programs["topk"]
    _module, rows = stages.parse_compiled(fn.lower(*spec).compile().as_text())
    sorts = [(text, op) for text, op in rows if " sort(" in text]
    assert sorts
    for text, op in sorts:
        assert stages.stage_of(op) == stages.SORT_TOPK
        assert stages.in_sort_engine(op)
        # a key lane, the padding class, the position: not the table's
        # four columns (a float64 alone would be two operands more)
        assert text.count("%") <= 1 + 4, text
