"""Join edge cases: fast-path sentinels, NaN semantics, x64-off mode, the
emit's two forms."""
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
import ride_cases
from cylon_tpu.utils import tracing


def test_int32_max_keys(ctx8):
    """Live keys equal to INT32_MAX canonicalize to the padding sentinel —
    the probe's count correction must keep them exact."""
    lmax = np.int32(2**31 - 1)
    l = pd.DataFrame({"k": np.array([lmax, 0, 5, lmax, 7], np.int32),
                      "x": np.arange(5.0)})
    r = pd.DataFrame({"k": np.array([lmax, 5, lmax, lmax, 2], np.int32),
                      "y": np.arange(5.0) * 10})
    tl = ct.Table.from_pandas(ctx8, l)
    tr = ct.Table.from_pandas(ctx8, r)
    for how in ["inner", "left", "right", "outer"]:
        got = tl.distributed_join(tr, on="k", how=how)
        exp = l.merge(r, on="k", how=how)
        assert got.row_count == len(exp), (how, got.row_count, len(exp))
    # value check for inner
    got = tl.distributed_join(tr, on="k", how="inner").to_pandas()
    exp = l.merge(r, on="k", how="inner")
    assert sorted(got["x"].tolist()) == sorted(exp["x"].tolist())
    assert sorted(got["y"].tolist()) == sorted(exp["y"].tolist())


def test_nan_keys_match_like_pandas(ctx8):
    """pandas.merge matches NaN keys to NaN (and never to 0.0)."""
    l = pd.DataFrame({"k": np.array([np.nan, 0.0, 1.5], np.float64),
                      "x": [1.0, 2.0, 3.0]})
    r = pd.DataFrame({"k": np.array([np.nan, 0.0, 2.5], np.float64),
                      "y": [10.0, 20.0, 30.0]})
    tl = ct.Table.from_pandas(ctx8, l)
    tr = ct.Table.from_pandas(ctx8, r)
    got = tl.distributed_join(tr, on="k", how="inner").to_pandas()
    exp = l.merge(r, on="k", how="inner")
    assert got.shape[0] == exp.shape[0]
    assert sorted(got["x"].tolist()) == sorted(exp["x"].tolist())


def test_multi_key_join(ctx8, rng):
    l = pd.DataFrame({
        "a": rng.integers(0, 5, 40),
        "b": rng.integers(0, 4, 40),
        "x": rng.normal(size=40),
    })
    r = pd.DataFrame({
        "a": rng.integers(0, 5, 35),
        "b": rng.integers(0, 4, 35),
        "y": rng.normal(size=35),
    })
    tl = ct.Table.from_pandas(ctx8, l)
    tr = ct.Table.from_pandas(ctx8, r)
    for how in ["inner", "left", "outer"]:
        got = tl.distributed_join(tr, on=["a", "b"], how=how)
        exp = l.merge(r, on=["a", "b"], how=how)
        assert got.row_count == len(exp), how


def test_left_on_right_on(ctx8, rng):
    l = pd.DataFrame({"ka": rng.integers(0, 10, 30), "x": rng.normal(size=30)})
    r = pd.DataFrame({"kb": rng.integers(0, 10, 25), "y": rng.normal(size=25)})
    tl = ct.Table.from_pandas(ctx8, l)
    tr = ct.Table.from_pandas(ctx8, r)
    got = tl.distributed_join(tr, left_on=["ka"], right_on=["kb"], how="inner")
    exp = l.merge(r, left_on="ka", right_on="kb", how="inner")
    assert got.row_count == len(exp)
    assert got.column_names == ["ka", "x", "kb", "y"]


NO_X64_SCRIPT = r"""
import os
os.environ["CYLON_TPU_NO_X64"] = "1"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np, pandas as pd
import cylon_tpu as ct
rng = np.random.default_rng(0)
l = pd.DataFrame({"k": rng.integers(0, 50, 300).astype(np.int32),
                  "x": rng.normal(size=300).astype(np.float32)})
r = pd.DataFrame({"k": rng.integers(0, 50, 200).astype(np.int32),
                  "y": rng.normal(size=200).astype(np.float32)})
ctx = ct.CylonContext.init_distributed(ct.TPUConfig())
tl = ct.Table.from_pandas(ctx, l); tr = ct.Table.from_pandas(ctx, r)
got = tl.distributed_join(tr, on="k", how="inner")
exp = l.merge(r, on="k", how="inner")
assert got.row_count == len(exp), (got.row_count, len(exp))
gs = np.sort(got.to_pandas()["x"].to_numpy()); es = np.sort(exp["x"].to_numpy())
assert np.allclose(gs, es)
print("NO_X64_JOIN_OK", got.row_count)
"""


def test_join_without_x64():
    """The benchmark config: x64 disabled, int32 keys — the fast path must
    not rely on int64 existing (regression for the live-bit packing bug)."""
    out = subprocess.run(
        [sys.executable, "-c", NO_X64_SCRIPT],
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "NO_X64_JOIN_OK" in out.stdout


def test_mixed_dtype_keys(ctx8):
    """int32 vs uint32 keys must promote before canonicalization."""
    l = pd.DataFrame({"k": np.array([1, 2, 3, 5], np.int32), "x": [1.0, 2.0, 3.0, 4.0]})
    r = pd.DataFrame({"k": np.array([1, 2, 3, 4], np.uint32), "y": [1.0, 2.0, 3.0, 4.0]})
    tl = ct.Table.from_pandas(ctx8, l)
    tr = ct.Table.from_pandas(ctx8, r)
    got = tl.distributed_join(tr, on="k", how="inner")
    assert got.row_count == 3
    # int32 min vs uint32 0 must NOT match
    l2 = pd.DataFrame({"k": np.array([-(2**31)], np.int32), "x": [1.0]})
    r2 = pd.DataFrame({"k": np.array([0], np.uint32), "y": [1.0]})
    got2 = ct.Table.from_pandas(ctx8, l2).distributed_join(
        ct.Table.from_pandas(ctx8, r2), on="k", how="inner"
    )
    assert got2.row_count == 0


def test_f32_zero_sign_distributed(ctx8):
    """-0.0 and +0.0 float32 keys must match across the shuffle (hash lane
    canonicalization, ops/hash.py f32 branch)."""
    import pandas as pd

    l = {"k": np.array([-0.0, 1.0], np.float32), "v": np.array([1, 2], np.int32)}
    r = {"k": np.array([0.0, 2.0], np.float32), "w": np.array([3, 4], np.int32)}
    lt = ct.Table.from_pydict(ctx8, l)
    rt = ct.Table.from_pydict(ctx8, r)
    out = lt.distributed_join(rt, on="k", how="inner")
    expect = pd.DataFrame(l).merge(pd.DataFrame(r), on="k")
    assert out.row_count == len(expect) == 1


def test_mixed_width_int_keys_distributed(ctx8, rng):
    """int32 vs int64 keys promote BEFORE the shuffle so equal values hash to
    the same shard (table.py _promote_key_pair)."""
    import pandas as pd

    kl = rng.integers(0, 100, 300).astype(np.int32)
    kr = rng.integers(0, 100, 200).astype(np.int64)
    lt = ct.Table.from_pydict(ctx8, {"k": kl, "v": rng.normal(size=300)})
    rt = ct.Table.from_pydict(ctx8, {"k": kr, "w": rng.normal(size=200)})
    out = lt.distributed_join(rt, on="k", how="inner")
    expect = pd.DataFrame({"k": kl.astype(np.int64)}).merge(
        pd.DataFrame({"k": kr}), on="k"
    )
    assert out.row_count == len(expect)


def test_mixed_sign_promotion_requires_x64(ctx8):
    """int32 x uint32 promotes to int64; with x64 disabled that must raise
    (silent wrap would fabricate matches, e.g. 2**31 == -2**31)."""
    from cylon_tpu.compat import enable_x64

    lt = ct.Table.from_pydict(ctx8, {"k": np.array([-(2**31)], np.int32)})
    rt = ct.Table.from_pydict(ctx8, {"k": np.array([2**31], np.uint32)})
    with enable_x64(False):
        with pytest.raises(ValueError, match="64-bit"):
            lt.join(rt, on="k", how="inner")


def test_speculative_overflow_falls_back(world_ctx, rng):
    """Join output larger than the speculative cap (cap_l+cap_r): the
    single-dispatch path must detect overflow and rerun the exact two-phase
    count->emit (table.py Table.join speculative block)."""
    import pandas as pd

    # 64 rows per side, all the same key -> 4096 output rows >> 64+64
    k = np.zeros(64, np.int32)
    lt = ct.Table.from_pydict(world_ctx, {"k": k, "v": np.arange(64, dtype=np.int32)})
    rt = ct.Table.from_pydict(world_ctx, {"k": k, "w": np.arange(64, dtype=np.int32)})
    out = lt.join(rt, on="k", how="inner")
    assert out.row_counts.sum() == sum(
        int(n) * int(m) for n, m in zip(lt.row_counts, rt.row_counts)
    )
    dout = lt.distributed_join(rt, on="k", how="inner")
    assert dout.row_counts.sum() == 64 * 64
    expect = pd.DataFrame({"k": k, "v": np.arange(64)}).merge(
        pd.DataFrame({"k": k, "w": np.arange(64)}), on="k"
    )
    got = (
        dout.to_pandas()[["k_x", "v", "w"]]
        .rename(columns={"k_x": "k"})
        .sort_values(["v", "w"])
        .reset_index(drop=True)
    )
    pd.testing.assert_frame_equal(
        got, expect.sort_values(["v", "w"]).reset_index(drop=True), check_dtype=False
    )


def test_fused_overflow_retry_on_mesh(world_ctx, rng):
    """Fused mode with undersized capacities on a mesh: the overflow lane
    must trigger the capacity-doubling retry (table.py _fused_join loop) and
    the retried result must match pandas. Extreme skew (every row the same
    key) lands the whole join on ONE shard, so the initial join_cap of
    2*(1+respill)*world*bucket_cap is guaranteed too small."""
    n = 64
    k = np.zeros(n, np.int32)
    lt = ct.Table.from_pydict(
        world_ctx, {"k": k, "v": np.arange(n, dtype=np.int32)}
    )
    rt = ct.Table.from_pydict(
        world_ctx, {"k": k, "w": np.arange(n, dtype=np.int32)}
    )
    out = lt.distributed_join(rt, on="k", how="inner", mode="fused")
    assert out.row_counts.sum() == n * n
    expect = (
        pd.DataFrame({"k": k, "v": np.arange(n)})
        .merge(pd.DataFrame({"k": k, "w": np.arange(n)}), on="k")
        .sort_values(["v", "w"])
        .reset_index(drop=True)
    )
    got = (
        out.to_pandas()[["k_x", "v", "w"]]
        .rename(columns={"k_x": "k"})
        .sort_values(["v", "w"])
        .reset_index(drop=True)
    )
    pd.testing.assert_frame_equal(got, expect, check_dtype=False)


def test_join_compacts_tiny_output(ctx8, rng):
    """A selective join output is compacted below the speculative cap."""
    n = 3000
    lt = ct.Table.from_pydict(
        ctx8, {"k": np.arange(n, dtype=np.int32), "v": rng.normal(size=n)}
    )
    rt = ct.Table.from_pydict(
        ctx8, {"k": np.array([7], np.int32), "w": np.array([1.0], np.float32)}
    )
    out = lt.distributed_join(rt, on="k", how="inner")
    assert out.row_count == 1
    assert out.shard_cap <= 64  # not the speculative cap_l+cap_r


def test_local_string_vs_numeric_key_raises(local_ctx):
    """Mixed string/numeric key pairs are rejected in the LOCAL join too —
    otherwise dictionary codes would compare against numeric values
    (table.py _unify_dict_pair guard)."""
    lt = ct.Table.from_pydict(local_ctx, {"k": ["a", "b", "c"]})
    rt = ct.Table.from_pydict(local_ctx, {"k": np.array([0, 1, 9], np.int32)})
    with pytest.raises(ValueError, match="string key"):
        lt.join(rt, on="k", how="inner")


def test_join_count_int32_wrap_raises(local_ctx):
    """65536 x 65536 rows on one key = 2^32 matches: the int32 count wraps to
    0, the float32 shadow catches it (ops/join.py count_overflow_check)."""
    n = 65536
    k = np.zeros(n, np.int32)
    lt = ct.Table.from_pydict(local_ctx, {"k": k})
    rt = ct.Table.from_pydict(local_ctx, {"k": k})
    with pytest.raises(ValueError, match="2\\^31"):
        lt.join(rt, on="k", how="inner")


# ----------------------------------------------------------------------
# the right side's columns ride the key sort (PR 30): 64-bit ones as their
# two halves, bit for bit; more than eight lanes in batches
# ----------------------------------------------------------------------

def _reference_join(lcols, rcols, how):
    """INNER / LEFT join on ``k`` in plain numpy: ``[(data, valid)]`` of the
    left columns then the right ones, a right column null where a LEFT
    join's row found no match."""
    lk, rk = lcols["k"][0], rcols["k"][0]
    r_order = np.argsort(rk, kind="stable")
    lo = np.searchsorted(rk[r_order], lk, "left")
    cnt = np.searchsorted(rk[r_order], lk, "right") - lo
    rows = np.maximum(cnt, 1) if how == "left" else cnt
    li = np.repeat(np.arange(len(lk)), rows)
    within = np.arange(rows.sum()) - np.repeat(np.cumsum(rows) - rows, rows)
    matched = np.repeat(cnt, rows) > 0
    ri = r_order[np.where(matched, np.repeat(lo, rows) + within, 0)]
    out = [(d[li], None if v is None else v[li]) for d, v in lcols.values()]
    for d, v in rcols.values():
        out.append((d[ri], matched if v is None else matched & v[ri]))
    return out


def _row_set(cols):
    """Rows as bits (zero under a null) with their validity, in one
    canonical order: what two joins of the same rows agree on."""
    lanes = []
    for data, valid in cols:
        valid = np.ones(len(data), bool) if valid is None else valid
        lanes += [ride_cases.bits(data, valid).astype(np.uint64), valid.astype(np.uint64)]
    mat = np.stack(lanes, axis=1)
    return mat[np.lexsort(mat.T[::-1])]


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("schema", ride_cases.SCHEMAS + ("wide5",))
def test_joined_columns_come_out_bit_for_bit(devices, rng, schema, how, world):
    """An INNER and a LEFT join whose right side holds int64, float64,
    nullable float64, mixed 32/64-bit and five float64 columns: every
    emitted row equals the numpy join's as bits (NaN payloads, -0.0,
    infinities, a subnormal, int64's extremes), and the right side rode
    one sort, or batches of it past eight lanes."""
    ctx = ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:world]))
    lcols = ride_cases.columns(rng, 1200, schema, "l")
    rcols = ride_cases.columns(rng, 900, schema, "r")
    lt, rt = ride_cases.table(ctx, lcols), ride_cases.table(ctx, rcols)
    before = ride_cases.ride_counts(tracing)
    out = lt.distributed_join(rt, on="k", how=how)
    lanes, batches = np.subtract(ride_cases.ride_counts(tracing), before)
    got = list(ride_cases.physical(out).values())
    want = _reference_join(lcols, rcols, how)
    assert len(got) == len(want) and out.row_count == len(want[0][0])
    assert (_row_set(got) == _row_set(want)).all()
    assert (lanes, batches) == {
        "int64": (4, 1), "float64": (4, 1), "nullable-float64": (5, 1),
        "mixed": (7, 1), "wide5": (12, 3),
    }[schema]


# ----------------------------------------------------------------------
# the emit's two forms (PR 49): where every live left row is emitted
# exactly once the left columns are handed through, else the run expansion
# and the packed left gather run; one ``cond``, decided from the counts
# ----------------------------------------------------------------------

_ONCE_NL, _ONCE_CAP_L, _ONCE_NR, _ONCE_CAP_R = 40, 64, 24, 32
#: (build side, left rows): (a) every left row has its one partner; (b)
#: some left rows have none; (c) one build key twice, and left rows on it;
#: (d) one left row has none
_ONCE_CASES = {
    # case: (how, the flag)
    "a-inner": ("inner", 1), "a-left": ("left", 1), "b-left": ("left", 1),
    "c-inner": ("inner", 0), "c-left": ("left", 0), "d-inner": ("inner", 0),
}


def _once_columns(rng, case):
    """Left: ``k`` int32, ``a`` int64 (its extremes), ``f`` float64 (values
    whose halves matter: 1e-30, -0.0, two NaN payloads, a subnormal) and
    ``n`` int32 under a validity lane. Right: ``k`` and a float64."""
    rk = rng.permutation(_ONCE_NR).astype(np.int32)
    lk = rng.integers(0, _ONCE_NR, _ONCE_NL).astype(np.int32)
    if case[0] == "b":
        lk[rng.choice(_ONCE_NL, 9, replace=False)] = _ONCE_NR + 5
    elif case[0] == "c":
        rk[3] = rk[7]          # one build key twice
        lk[[2, 11]] = rk[7]    # and two left rows on it
        lk[lk == rk[3]] = rk[7]
    elif case[0] == "d":
        lk[17] = _ONCE_NR + 5
    f = rng.normal(size=_ONCE_NL)
    f[:8] = np.concatenate([ride_cases._F64, [1e-30, -1e-30]])
    a = rng.integers(-(1 << 62), 1 << 62, _ONCE_NL, dtype=np.int64)
    a[:4] = ride_cases._I64
    lcols = {
        "k": (lk, None), "a": (a, None), "f": (f, None),
        "n": (rng.integers(-99, 99, _ONCE_NL).astype(np.int32),
              rng.random(_ONCE_NL) < 0.7),
    }
    rcols = {"k": (rk, None), "w": (rng.normal(size=_ONCE_NR), None)}
    return lcols, rcols


def _padded(cols, cap):
    """``[(data, valid)]`` on the device at ``cap`` slots, the padding's
    values not the live rows' (a slice of it would show)."""
    import jax.numpy as jnp

    def pad(x, fill):
        return jnp.asarray(np.concatenate(
            [x, np.full(cap - len(x), fill, x.dtype)]
        ))

    return [
        (pad(d, 77), None if v is None else pad(v, True))
        for d, v in cols.values()
    ]


def _live(cols, n):
    return [
        (np.asarray(d)[:n], None if v is None else np.asarray(v)[:n])
        for d, v in cols
    ]


def _assert_rows_bit_for_bit(got, want):
    assert len(got) == len(want)
    for (gd, gv), (wd, wv) in zip(got, want):
        assert gd.dtype == wd.dtype and len(gd) == len(wd)
        gv = np.ones(len(gd), bool) if gv is None else gv
        wv = np.ones(len(wd), bool) if wv is None else wv
        assert (gv == wv).all()
        assert (ride_cases.bits(gd, gv) == ride_cases.bits(wd, wv)).all()


@pytest.mark.parametrize("cap_out", [48, 64, 96])  # below, at, above cap_l
@pytest.mark.parametrize("case", sorted(_ONCE_CASES))
def test_emit_hands_the_left_side_through_where_each_row_emits_once(
    rng, case, cap_out
):
    """The speculative join's program and the exact path's emit, on a
    build side unique on its key (and not): the live rows are the numpy
    join's bit for bit, row for row; the flag says which form ran; and each
    form called on its own gives the same live rows wherever it may run."""
    import jax
    import jax.numpy as jnp
    from cylon_tpu.ops import join as _j

    how, flag = _ONCE_CASES[case]
    howi = _j.join_type_id(how)
    lcols, rcols = _once_columns(rng, case)
    want = _reference_join(lcols, rcols, how)
    total = len(want[0][0])
    assert total <= cap_out
    l, r = _padded(lcols, _ONCE_CAP_L), _padded(rcols, _ONCE_CAP_R)
    nl, nr = jnp.int32(_ONCE_NL), jnp.int32(_ONCE_NR)

    out, n_out, _shadow, handed = jax.jit(
        lambda l, r, nl, nr: _j.spec_join(
            l[:1], r[:1], l, r, nl, nr, howi, cap_out
        )
    )(l, r, nl, nr)
    assert (int(n_out), int(handed)) == (total, flag)
    _assert_rows_bit_for_bit(_live(out, total), want)
    # a LEFT join's left columns carry no validity lane of the emit's own
    if how == "left":
        assert [v is None for _d, v in out[:4]] == [True, True, True, False]

    # the exact two-phase path's emit, and the two forms on their own
    lo, cnt, r_order, r_cnt = jax.jit(
        lambda lk, rk: _j.probe_arrays(
            lk, rk, nl, nr, _ONCE_CAP_L, _ONCE_CAP_R, howi
        )
    )(l[:1], r[:1])
    out2, n_out2 = jax.jit(
        lambda *a: _j.emit_gather(*a, nl, nr, howi, cap_out)
    )(lo, cnt, r_order, r_cnt, l, r)
    assert int(n_out2) == total
    _assert_rows_bit_for_bit(_live(out2, total), want)

    cnt_adj, all_valid = cnt, how == "left"
    if how == "left":
        live_l = np.arange(_ONCE_CAP_L) < _ONCE_NL
        cnt_adj = jnp.where(live_l & (np.asarray(cnt) == 0), 1, cnt)
    forms = [_j._left_gathered(
        lo, cnt, cnt_adj, l, cap_out, _ONCE_CAP_R, all_valid)]
    if flag:
        forms.append(_j._left_handed_through(
            lo, cnt, l, nl, cap_out, _ONCE_CAP_R, all_valid))
    for out_l, rpos, total_l in forms:
        assert int(total_l) == total
        _assert_rows_bit_for_bit(_live(out_l, total), want[:4])
        rpos = np.asarray(rpos)
        assert (rpos[:total] == np.asarray(forms[0][1])[:total]).all()
        assert (rpos[total:] == -1).all()


def test_the_programs_left_gather_stands_inside_a_branch_alone():
    """The lowered ``join_spec`` of ``join-w1``'s schema: the run
    expansion's scatter and the packed left gather (``[rows, 6]``: key 2,
    base, cnt, value 2) stand in the first branch of ONE ``case``, the
    other branch gathers, scatters and sorts nothing, and the right gather
    (``[rows, 4]``) follows the ``case`` behind one barrier."""
    import re

    import jax
    import jax.numpy as jnp
    from cylon_tpu.ops import join as _j

    rows = 4096

    def join(lk, lv, rk, rv, nl, nr):
        left, right = [(lk, None), (lv, None)], [(rk, None), (rv, None)]
        return _j.spec_join(
            left[:1], right[:1], left, right, nl, nr, _j.INNER, rows
        )

    lines = jax.jit(join).lower(
        *[jax.ShapeDtypeStruct((rows,), d)
          for d in (jnp.int64, jnp.float64, jnp.int64, jnp.float64)],
        *[jax.ShapeDtypeStruct((), jnp.int32)] * 2,
    ).as_text().split("\n")
    cases = [i for i, line in enumerate(lines) if '"stablehlo.case"' in line]
    assert len(cases) == 1
    start = cases[0]
    indent = re.match(r" *", lines[start]).group()
    middle = next(
        i for i in range(start, len(lines)) if lines[i] == indent + "}, {"
    )
    end = next(
        i for i in range(middle, len(lines))
        if lines[i].startswith(indent + "}) :")
    )

    def at(pattern):
        return [i for i, line in enumerate(lines) if re.search(pattern, line)]

    (left_gather,) = at(rf'"stablehlo\.gather"\(.*:\s*\(tensor<{rows}x6xi32>')
    (right_gather,) = at(rf'"stablehlo\.gather"\(.*:\s*\(tensor<{rows}x4xi32>')
    assert start < left_gather < middle and right_gather > end
    # the barrier that keeps the compiler from mixing the right gather with
    # the branches (PERF.md section 6, PR 49: two sorts of join-w1)
    (barrier,) = at(r"stablehlo\.optimization_barrier")
    assert end < barrier < right_gather
    expansion = [
        i for i in at(r'"stablehlo\.scatter"')
        if f"tensor<{rows + 1}xi32>" in lines[i + 3]
    ]
    assert len(expansion) == 1 and start < expansion[0] < middle
    handed = "\n".join(lines[middle:end])
    assert not re.search(r"stablehlo\.(gather|scatter|sort)|call @cum", handed)


@pytest.mark.parametrize("how", ["inner", "left"])
def test_the_emits_form_is_counted_a_shard_on_the_mesh(devices, how):
    """Four shards: a foreign-key join (every probe row has one partner on
    its shard) bumps ``join.emit.handthrough`` by the output's rows, a
    join of uniform keys ``join.emit.gathered``, and the fetches a join
    makes are what they were (the flag rides the totals')."""
    ctx = ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:4]))
    gen = np.random.default_rng(2147549002)
    # 256 build keys, each once; 4,096 probe keys drawn from them, skewed
    build = gen.permutation(1 << 20)[:256].astype(np.int64)
    fk = {
        "left": {"k": build[np.minimum(gen.zipf(1.25, 4096), 256) - 1],
                 "v": gen.random(4096)},
        "right": {"k": build, "w": gen.random(256)},
    }
    uniform = {
        # half as many partners as rows, so the speculative capacity holds
        "left": {"k": gen.integers(0, 4096, 2048), "v": gen.random(2048)},
        "right": {"k": gen.integers(0, 4096, 2048), "w": gen.random(2048)},
    }

    def counted(data):
        left, right = (
            ct.Table.from_numpy(ctx, list(cols), list(cols.values()))
            for cols in (data["left"], data["right"])
        )
        names = ("join.emit.handthrough", "join.emit.gathered")
        before = [tracing.snapshot().get(n, {}).get("rows", 0) for n in names]
        syncs = tracing.get_count("host_sync")
        out = left.distributed_join(right, on="k", how=how)
        rows = out.row_count
        syncs = tracing.get_count("host_sync") - syncs
        after = [tracing.snapshot().get(n, {}).get("rows", 0) for n in names]
        want = pd.DataFrame(data["left"]).merge(
            pd.DataFrame(data["right"]), on="k", how=how)
        assert rows == len(want)
        return list(np.subtract(after, before)), rows, syncs

    moved, rows, fk_syncs = counted(fk)
    assert moved == [rows, 0] and rows == 4096
    moved, rows, uniform_syncs = counted(uniform)
    assert moved == [0, rows] and rows > 0
    # both sides shuffled, one speculative join: the same fetches either way
    assert fk_syncs == uniform_syncs
