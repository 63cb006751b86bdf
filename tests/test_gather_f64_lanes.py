"""A float64 column rides the packed row gather as two 32-bit lanes.

``ops.gather.pack_gather`` carries a float64 in its ``[cap, L]`` matrix by
one of two codecs, chosen by the platform of the mesh a kernel is traced
for: on a TPU the two float32 the chip holds the value as, had by
arithmetic (no bitcast of a float64 lowers there); elsewhere the two words
of its bits. Both give back the value they were given, so every gather is
a per-column ``take``'s; which columns ride the matrix follows the shapes
(``F64_PACK_RATIO``), and the exchange's format (``lane_plan`` /
``pack_cols``) keeps a float64 as a passthrough.

The TPU rule runs here on the CPU over values that ARE two float32 (a CPU
holds an IEEE double, which the rule cannot carry in general); that it is
exact on the chip's own float64 is the chip's to say (``PERF.md`` section
6, PR 40).
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import cylon_tpu as ct
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.ops import gather as _g
from cylon_tpu.ops import join as _j
from cylon_tpu.utils.tracing import report, reset_trace

R = _g.F64_PACK_RATIO
F32 = np.finfo(np.float32)


def _two_floats(rng, n):
    """Float64 values that are a sum of two float32, ``|l| <= ulp(h) / 2``
    and l on a 2^-23 grid of that bound, so the sum is exact in an IEEE
    double (48 bits) as it is in the chip's pair."""
    h = ((1 + rng.random(n)) * 10.0 ** rng.integers(-15, 20, n)).astype(
        np.float32
    ) * rng.choice(np.float32([-1, 1]), n)
    half_ulp = np.spacing(np.abs(h)).astype(np.float64) / 2
    l = (half_ulp * rng.integers(-(1 << 23), (1 << 23) + 1, n) / (1 << 23))
    l = l.astype(np.float32)
    assert np.all(np.abs(l.astype(np.float64)) <= half_ulp)
    x = h.astype(np.float64) + l.astype(np.float64)
    assert np.all(x - h.astype(np.float64) == l.astype(np.float64))  # exact
    return x


SPECIALS = {
    "zeros": np.array([0.0, -0.0]),
    "infinities": np.array([np.inf, -np.inf]),
    "nan": np.array([np.nan]),
    "float32_extremes": np.array(
        [F32.max, -F32.max, F32.tiny, -F32.tiny], np.float64
    ),
}
#: what an IEEE double holds and two float32 cannot: the CPU rule's alone
IEEE_ONLY = {
    "subnormals": np.array([5e-324, -5e-324, 2.2e-308 / 4]),
    "past_float32_range": np.array([1e300, -1e300, 1e-300]),
    "all_53_bits": np.array([1 / 3, np.pi, 1 + 2.0 ** -52, -(2.0 ** 53 - 1)]),
}


def _bits(x):
    return np.asarray(x, np.float64).view(np.uint64)


def _round_trip(x, platform):
    fn = jax.jit(
        lambda a: _g._f64_from_lanes(_g._f64_to_lanes(a, platform), platform)
    )
    lanes = jax.jit(lambda a: _g._f64_to_lanes(a, platform))(jnp.asarray(x))
    assert [(l.dtype, l.shape) for l in lanes] == [(jnp.int32, x.shape)] * 2
    return np.asarray(fn(jnp.asarray(x)))


def _assert_same_values(got, want):
    """Equal element for element, NaN where NaN, and the sign bit kept
    (``-0.0 == 0.0``, so the sign is compared on its own)."""
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan], want[~nan])
    np.testing.assert_array_equal(np.signbit(got[~nan]), np.signbit(want[~nan]))


# ----------------------------------------------------------------------
# (a) the CPU rule: the two words of the bits, every bit pattern
# ----------------------------------------------------------------------

@pytest.mark.parametrize(
    "kind", ["random_bits", "uniform01", *SPECIALS, *IEEE_ONLY]
)
def test_bit_words_rule_keeps_every_bit(kind, rng):
    if kind == "random_bits":
        x = rng.integers(0, 2**64, 4096, dtype=np.uint64).view(np.float64)
    elif kind == "uniform01":
        x = rng.random(4096)
    else:
        x = {**SPECIALS, **IEEE_ONLY}[kind]
    got = _round_trip(x, "cpu")
    np.testing.assert_array_equal(_bits(got), _bits(x))


# ----------------------------------------------------------------------
# (b) the TPU rule's arithmetic, run on the CPU over two-float values
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["two_floats", "float32_values", *SPECIALS])
def test_two_float_rule_keeps_every_value_and_sign(kind, rng):
    if kind == "two_floats":
        x = _two_floats(rng, 4096)
    elif kind == "float32_values":  # no low half at all
        x = rng.standard_normal(4096).astype(np.float32).astype(np.float64)
    else:
        x = SPECIALS[kind]
    _assert_same_values(_round_trip(x, "tpu"), x)


def test_two_float_rule_splits_into_the_nearest_float32_and_the_rest(rng):
    x = _two_floats(rng, 4096)
    hi, lo = (
        np.asarray(l).view(np.float32)
        for l in jax.jit(lambda a: _g._f64_to_lanes(a, "tpu"))(jnp.asarray(x))
    )
    np.testing.assert_array_equal(hi, x.astype(np.float32))
    np.testing.assert_array_equal(
        lo.astype(np.float64), x - hi.astype(np.float64)
    )
    # an infinity's low half is 0, not inf - inf
    _hi, lo = jax.jit(lambda a: _g._f64_to_lanes(a, "tpu"))(
        jnp.asarray([np.inf, -np.inf, np.nan])
    )
    np.testing.assert_array_equal(np.asarray(lo), 0)


#: a double a host can hold whose low half is a float32 subnormal: the
#: high half 2^-80, the rest 2^-130
TINY = 2.0 ** -80 * (1 + 2.0 ** -50)


@pytest.mark.parametrize(
    "value,flagged",
    [
        (TINY, True), (-TINY, True), (1e-30, True), (2.0 ** -75, True),
        (2.0 ** -126, True),
        # from 2^-74 on what 53 bits leave of the high half is normal
        (2.0 ** -74, False), (1.0, False), (-3e38, False),
        # nothing is left of a zero or of a subnormal high half
        (0.0, False), (-0.0, False), (1e-40, False),
        (np.inf, False), (np.nan, False),
    ],
)
def test_rows_whose_low_half_may_flush_are_read_from_the_high_half(
    value, flagged
):
    hi, _lo = _g._f64_to_lanes(jnp.asarray([value], jnp.float64), "tpu")
    assert bool(_g._f64_low_half_may_flush(hi)[0]) is flagged
    # the flag covers every double whose low half IS a float32 subnormal
    if np.isfinite(value):
        low = np.float32(value - np.float64(np.float32(value)))
        assert flagged or low == 0 or abs(low) >= F32.tiny


def test_the_rule_follows_the_mesh_platform():
    assert _g._f64_two_float("tpu")
    assert not _g._f64_two_float("cpu") and not _g._f64_two_float("gpu")


# ----------------------------------------------------------------------
# (c) pack_gather against a per-column take
# ----------------------------------------------------------------------

def _column(kind, cap, rng, tiny=False):
    if kind == "float64":
        x = _two_floats(rng, cap)
        x[:5] = [0.0, -0.0, np.inf, -np.inf, np.nan]
        if tiny:
            x[5:7] = [TINY, -TINY]
        return x
    if kind == "int64":
        return rng.integers(-(1 << 62), 1 << 62, cap)
    if kind == "float32":
        return rng.standard_normal(cap).astype(np.float32)
    if kind == "bool":
        return rng.random(cap) < 0.5
    if kind == "int8":
        return rng.integers(-128, 128, cap).astype(np.int8)
    raise AssertionError(kind)


SCHEMAS = {
    # (dtype kind, has a validity lane)
    "float64_alone": [("float64", False)],
    "suite_side": [("int64", False), ("float64", False)],
    "two_float64_one_masked": [("float64", True), ("int64", False),
                               ("float64", False)],
    "every_kind_masked": [("bool", True), ("float64", True), ("float32", True),
                          ("int8", False), ("int64", True), ("float64", True)],
    "no_float64": [("int64", True), ("float32", False)],
}
SHAPES = {
    # (source rows, index rows)
    "same_length": (1000, 1000),
    "index_longer": (300, 2000),
    "index_shorter": (2000, 300),
    "at_the_ratio": (R * 96, 96),
    "past_the_ratio": (R * 96 + 1, 96),
    "selective": (128 * 64, 64),
}


def _branches_traced(fn, *args):
    """How many two-branch ``cond``s the jaxpr of ``fn(*args)`` holds."""
    return str(jax.make_jaxpr(fn)(*args)).count("cond[")


@pytest.mark.parametrize("rule", ["cpu", "tpu", "tpu_tiny"])
@pytest.mark.parametrize("all_valid", [False, True], ids=["nulling", "all_valid"])
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("schema", SCHEMAS)
def test_pack_gather_is_a_take_of_every_column(
    schema, shape, all_valid, rule, rng, monkeypatch
):
    """``tpu``: the two-float codec under its guard, the packed branch
    taken; ``tpu_tiny``: a value whose low half the chip would flush, so
    the guard takes the lone gathers (here, where nothing is flushed, both
    branches are exact: which one ran is the chip question's to show)."""
    cap, n = SHAPES[shape]
    kinds = SCHEMAS[schema]
    platform = rule[:3]
    datas = [_column(k, cap, rng, tiny=rule == "tpu_tiny") for k, _ in kinds]
    valids = [rng.random(cap) < 0.8 if m else None for _, m in kinds]
    idx = rng.integers(0, cap, n).astype(np.int32)
    idx[::7] = -1  # no source row
    extra = rng.integers(0, 1 << 30, cap).astype(np.int32)
    # the codec a kernel of a TPU mesh would trace, run here
    monkeypatch.setattr(_g, "mesh_platform", lambda: platform)
    reset_trace()

    def kern(cols, i, e):
        return _g.pack_gather(cols, i, extra_lanes=[e], all_valid=all_valid)

    args = (
        [
            (jnp.asarray(d), None if v is None else jnp.asarray(v))
            for d, v in zip(datas, valids)
        ],
        jnp.asarray(idx), jnp.asarray(extra),
    )
    out, (extra_g,) = jax.jit(kern)(*args)
    safe = np.clip(idx, 0, cap - 1)
    ok = idx >= 0
    np.testing.assert_array_equal(np.asarray(extra_g), extra[safe])
    for (data, valid), d, v in zip(out, datas, valids):
        got = np.asarray(data)
        assert got.dtype == d.dtype
        if d.dtype == np.float64:
            _assert_same_values(got, d[safe])
        else:
            np.testing.assert_array_equal(got, d[safe])
        if all_valid:
            want_v = None if v is None else v[safe]
        else:
            want_v = ok if v is None else ok & v[safe]
        if want_v is None:
            assert valid is None
        else:
            np.testing.assert_array_equal(np.asarray(valid), want_v)
    # which side of the rule the float64 columns fell on
    n_f64 = sum(k == "float64" for k, _ in kinds)
    rep = report("gather.f64")
    want = "gather.f64.packed" if cap <= R * n else "gather.f64.alone"
    if n_f64:
        assert list(rep) == [want] and int(rep[want]["rows"]) == n_f64
    else:
        assert not rep
    # the guard is there where the two-float split is, and nowhere else
    guarded = platform == "tpu" and n_f64 and cap <= R * n
    assert _branches_traced(kern, *args) == (1 if guarded else 0)


def test_the_exchange_format_keeps_a_float64_a_passthrough():
    """``lane_plan`` / ``pack_cols`` are the shuffle's, the wire codec's and
    the spill's contract: a float64 has no lanes there."""
    key = jnp.arange(8, dtype=jnp.int64)
    val = jnp.arange(8, dtype=jnp.float64)
    mask = jnp.ones((8,), bool)
    cols = [(key, None), (val, mask)]
    assert _g.lane_plan(cols) == [("int64", 2, False), (None, 0, True)]
    plan, lanes, passthrough = _g.pack_cols(cols)
    assert plan == _g.lane_plan(cols)
    assert len(lanes) == 3 and list(passthrough) == [1]  # key hi/lo + mask
    assert passthrough[1] is val


# ----------------------------------------------------------------------
# (d) the lowered join: the emit's float64 halves are lanes of its packed
# gathers; a selective gather keeps its lone ones
# ----------------------------------------------------------------------

def _gather_operands(lowered_text):
    """The operand type of every gather in a lowered (StableHLO) text."""
    return re.findall(
        r'"stablehlo\.gather"\(.*?:\s*\(tensor<([^>]+)>', lowered_text
    )


def test_join_w1s_emit_holds_exactly_its_packed_gathers():
    """``spec_join`` over ``join-w1``'s schema (an int64 key and a float64
    value a side): the left rows with ``base`` / ``cnt`` are ONE
    ``[rows, 6]`` gather (key 2, base, cnt, value 2), the key-sorted right
    rows ONE ``[rows, 4]`` (key 2, value 2), and no float column is
    gathered on its own."""
    rows = 4096

    def join(lk, lv, rk, rv, nl, nr):
        left, right = [(lk, None), (lv, None)], [(rk, None), (rv, None)]
        return _j.spec_join(
            left[:1], right[:1], left, right, nl, nr, _j.INNER, rows
        )

    reset_trace()
    text = jax.jit(join).lower(
        *[jax.ShapeDtypeStruct((rows,), d)
          for d in (jnp.int64, jnp.float64, jnp.int64, jnp.float64)],
        *[jax.ShapeDtypeStruct((), jnp.int32)] * 2,
    ).as_text()
    operands = _gather_operands(text)
    packed = sorted(o for o in operands if o.endswith("xi32") and "x" in o[:-5])
    assert packed == [f"{rows}x4xi32", f"{rows}x6xi32"], operands
    assert not [o for o in operands if o.endswith(("xf64", "xf32"))], operands
    rep = report("gather.f64")
    assert int(rep["gather.f64.packed"]["rows"]) == 2 and len(rep) == 1


def test_a_selective_reduction_keeps_its_lone_float64_gather():
    """``reduce_by_hits`` as ``tpch-q3-w1`` runs it: few rows drawn from a
    long fact table. Stacking the source would copy all of it, so the
    float64 column is gathered alone, as it always was."""
    cap, cap_lo, cap_ro = 1 << 16, 64, 256

    def reduce(hits, stats, lkey, rkey, rval):
        return _j.reduce_by_hits(
            hits, stats, [(lkey, None)], [(rkey, None), (rval, None)],
            cap_lo, cap_ro,
        )

    reset_trace()
    text = jax.jit(reduce).lower(
        jax.ShapeDtypeStruct((cap + cap // 8,), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32),
        jax.ShapeDtypeStruct((cap // 8,), jnp.int32),
        jax.ShapeDtypeStruct((cap,), jnp.int32),
        jax.ShapeDtypeStruct((cap,), jnp.float64),
    ).as_text()
    operands = _gather_operands(text)
    assert f"{cap}xf64" in operands, operands
    assert not [o for o in operands if o.count("x") == 2], operands
    rep = report("gather.f64")
    assert int(rep["gather.f64.alone"]["rows"]) == 1 and len(rep) == 1


# ----------------------------------------------------------------------
# (e) the counters
# ----------------------------------------------------------------------

@pytest.mark.parametrize("name", ["gather.f64.packed", "gather.f64.alone"])
def test_counters_are_declared(name):
    assert obs_metrics.is_declared(name)


def test_a_join_of_float64_payloads_counts_its_packed_columns(local_ctx, rng):
    """Through the public call, on the CPU mesh's own rule: the result is
    pandas', and the rollup says the float64 columns rode the matrix."""
    import pandas as pd

    a = pd.DataFrame({"k": rng.integers(0, 300, 1000), "v": rng.random(1000)})
    b = pd.DataFrame({"k": rng.integers(0, 300, 900), "w": rng.random(900)})
    # a schema no other test of this process has joined: the counter is
    # bumped where a kernel is traced, and a cached kernel is not traced
    a["v2"] = -a["v"]
    reset_trace()
    got = (
        ct.Table.from_pandas(local_ctx, a)
        .join(ct.Table.from_pandas(local_ctx, b), on="k", how="inner")
        .to_pandas()
    )
    want = a.merge(b, on="k")
    cols = ["k_x", "v", "v2", "w"]
    got = got[cols].sort_values(cols).reset_index(drop=True)
    want = want.rename(columns={"k": "k_x"})[cols]
    want = want.sort_values(cols).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    # three float64 columns an emit (the speculative one, and the exact one
    # after it where the fan-out overflowed the speculation)
    rep = report("gather.f64")
    assert list(rep) == ["gather.f64.packed"]
    assert int(rep["gather.f64.packed"]["rows"]) >= 3
