"""Width-adaptive radix sort engine tests.

Layers, mirroring test_lane_pack.py:
  1. engine unit — digit lane planning (span/bias hints, float decline),
     pass census arithmetic, and the stable single-pass kernel against
     numpy on raw lanes;
  2. differential — every consumer shape (multi-key sort incl. NaN-last
     and descending floats, null sentinels, dictionary string codes,
     straddled >32-bit fused sort words, unique, groupby, join,
     shuffle) in EXACT emitted order against the CYLON_TPU_NO_RADIX=1
     bitonic oracle at worlds {1, 4, 8} — the stable lexsort
     permutation is unique, so order equality is the contract, not
     row-set equality;
  3. selection — the impl tag recompiles (never aliases) across
     CYLON_TPU_SORT_IMPL flips, and the forced Pallas tier (interpret
     mode on CPU) emits the same permutation.

The default engine is the native sort, which is also the oracle's, so
the subject side of every test here runs under an explicit
``CYLON_TPU_SORT_IMPL=radix`` (the ``radix_forced`` fixture); the kill
switch inside ``_oracle`` wins over that force. The default's own
tests are in test_sort_default.py.
"""
import os
import sys

import numpy as np
import pandas as pd
import pandas.testing as pdt
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp

import cylon_tpu as ct
from cylon_tpu.ops import radix as rx


@pytest.fixture(autouse=True)
def radix_forced(monkeypatch):
    """Every test's subject side is the radix engine, by force."""
    monkeypatch.setenv("CYLON_TPU_SORT_IMPL", "radix")


@pytest.fixture(scope="module")
def ctx1(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:1]))


def _ctx(devices, world):
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )


def _emitted_equal(got, want):
    """Exact emitted-order equality (no re-sort: a stability or
    permutation bug must not be masked by canonicalization)."""
    g = got.to_pandas().reset_index(drop=True)
    w = want.to_pandas().reset_index(drop=True)
    pdt.assert_frame_equal(g, w)


def _oracle(fn):
    with rx.disabled():
        return fn()


# ---------------------------------------------------------------------------
# 1. engine unit
# ---------------------------------------------------------------------------

def test_pass_census_arithmetic():
    assert rx.passes_for_spans([(0, 20)]) == 5
    assert rx.passes_for_spans([(19, 64)]) == 12  # the 3-key packed word
    assert rx.passes_for_spans([(0, 1)]) == 1
    assert rx.passes_for_spans([(0, 8)], impl="radix_pallas") == 1
    assert rx.bitonic_passes(1024, 1) == 55
    assert rx.bitonic_passes(1024, 3) == 165


def test_plan_declines_float_lanes():
    lanes = [jnp.zeros(8, jnp.float32), jnp.zeros(8, jnp.uint32)]
    assert rx.plan_lanes(lanes, None) is None


def test_single_pass_stable_vs_numpy(rng):
    n = 513
    lane = jnp.asarray(rng.integers(0, 16, n), jnp.uint32)
    perm = jnp.arange(n, dtype=jnp.int32)
    got = np.asarray(rx.radix_pass(lane, perm, 0, 4))
    want = np.argsort(np.asarray(lane), kind="stable")
    np.testing.assert_array_equal(got, want)


def test_lexsort_perm_matches_numpy_lexsort(rng):
    n = 700
    a = rng.integers(0, 50, n).astype(np.uint32)
    b = rng.integers(0, 1000, n).astype(np.uint32)
    # lanes least-significant first (the ops/sort.py convention)
    perm = rx.lexsort_perm(
        [jnp.asarray(b), jnp.asarray(a)], n,
        [rx.span_hint(0, 10), rx.span_hint(0, 6)],
    )
    assert perm is not None
    np.testing.assert_array_equal(np.asarray(perm), np.lexsort((b, a)))


# ---------------------------------------------------------------------------
# 2. differential vs the bitonic oracle, exact emitted order
# ---------------------------------------------------------------------------

def _sort_pair(ctx, df, keys, **kw):
    got = ct.Table.from_pandas(ctx, df).sort(keys, **kw)
    want = _oracle(lambda: ct.Table.from_pandas(ctx, df).sort(keys, **kw))
    _emitted_equal(got, want)


@pytest.mark.parametrize("world", [1, 4, 8])
def test_nan_last_floats(world, devices, rng):
    n = 900
    vals = rng.normal(size=n).astype(np.float64)
    vals[rng.random(n) < 0.15] = np.nan
    df = pd.DataFrame({
        "g": rng.integers(0, 12, n).astype(np.int32),
        "f": vals,
        "v": np.arange(n, dtype=np.int64),
    })
    # float key lanes make the digit planner decline; the int prefix
    # still radix-sorts when fused plans split — either way the emitted
    # order (NaN last within each group) must equal the oracle's
    _sort_pair(_ctx(devices, world), df, ["g", "f"])


@pytest.mark.parametrize("world", [1, 4, 8])
def test_descending_floats(world, devices, rng):
    n = 800
    vals = rng.normal(size=n).astype(np.float32)
    vals[rng.random(n) < 0.1] = np.nan
    df = pd.DataFrame({
        "f": vals,
        "k": rng.integers(-40, 40, n).astype(np.int32),
        "v": np.arange(n, dtype=np.int64),
    })
    _sort_pair(_ctx(devices, world), df, ["f", "k"],
               ascending=[False, False])


@pytest.mark.parametrize("world", [1, 4, 8])
def test_null_sentinels(world, devices, rng):
    n = 1000
    k1 = rng.integers(0, 30, n).astype(object)
    k1[rng.random(n) < 0.2] = None
    k2 = rng.integers(-500, 500, n).astype(object)
    k2[rng.random(n) < 0.2] = None
    df = pd.DataFrame({"k1": k1, "k2": k2,
                       "v": np.arange(n, dtype=np.int64)})
    _sort_pair(_ctx(devices, world), df, ["k1", "k2"])


@pytest.mark.parametrize("world", [1, 4, 8])
def test_dict_codes(world, devices, rng):
    n = 900
    words = np.array([f"w{i:03d}" for i in range(40)], dtype=object)
    k = rng.choice(words, n)
    k[rng.random(n) < 0.1] = None
    df = pd.DataFrame({
        "s": k,
        "k": rng.integers(0, 9, n).astype(np.int8),
        "v": np.arange(n, dtype=np.int64),
    })
    _sort_pair(_ctx(devices, world), df, ["s", "k"],
               ascending=[True, False])


@pytest.mark.parametrize("world", [1, 4, 8])
def test_straddled_64bit_fused_word(world, devices, rng):
    # ~20+16+7 key bits + null/pad lanes fuse into ONE uint64 sort word
    # whose lanes straddle the 32-bit boundary: the pass loop must walk
    # digit windows across the full 64-bit width
    n = 1100
    df = pd.DataFrame({
        "a": rng.integers(0, 1_000_000, n).astype(np.int32),
        "b": rng.integers(0, 60_000, n).astype(np.int32),
        "c": rng.integers(0, 120, n).astype(np.int32),
        "v": np.arange(n, dtype=np.int64),
    })
    ctx = _ctx(devices, world)
    _sort_pair(ctx, df, ["a", "b", "c"])
    _sort_pair(ctx, df, ["a", "b", "c"], ascending=[True, False, True])


@pytest.mark.parametrize("world", [1, 4, 8])
def test_unique_groupby_join_shuffle(world, devices, rng):
    n = 800
    df = pd.DataFrame({
        "k": rng.integers(0, 60, n).astype(np.int32),
        "j": rng.integers(-9, 9, n).astype(np.int64),
        "v": rng.normal(size=n).astype(np.float32),
    })
    rdf = pd.DataFrame({
        "k": rng.integers(0, 60, n // 2).astype(np.int32),
        "w": rng.normal(size=n // 2).astype(np.float32),
    })
    ctx = _ctx(devices, world)

    def build():
        t = ct.Table.from_pandas(ctx, df)
        r = ct.Table.from_pandas(ctx, rdf)
        u = t.unique(["k", "j"])
        g = t.distributed_groupby(["k", "j"], {"v": "sum"})
        j = t.distributed_join(r, on="k", how="inner")
        out = [u, g, j]
        if world > 1:
            out.append(t.shuffle(["k"]))
        return out

    got = build()
    want = _oracle(build)
    for g, w in zip(got, want):
        _emitted_equal(g, w)


# ---------------------------------------------------------------------------
# 3. impl selection
# ---------------------------------------------------------------------------

def test_impl_tag_recompiles_never_aliases(ctx1, rng, monkeypatch):
    n = 600
    df = pd.DataFrame({
        "a": rng.integers(0, 4000, n).astype(np.int32),
        "v": np.arange(n, dtype=np.int64),
    })
    t = ct.Table.from_pandas(ctx1, df)
    cache = ctx1.__dict__.setdefault("_jit_cache", {})
    monkeypatch.setenv("CYLON_TPU_SORT_IMPL", "radix")
    want = t.sort(["a"]).to_pandas()
    n0 = len(cache)
    monkeypatch.setenv("CYLON_TPU_SORT_IMPL", "bitonic")
    got = t.sort(["a"]).to_pandas()
    assert len(cache) == n0 + 1  # the flip compiled its OWN program
    pdt.assert_frame_equal(got, want)
    monkeypatch.setenv("CYLON_TPU_SORT_IMPL", "radix")
    t.sort(["a"]).to_pandas()
    assert len(cache) == n0 + 1  # flip-back reused the cached program


def test_forced_pallas_tier_matches(ctx1, rng, monkeypatch):
    n = 1024  # TILE-aligned: the Pallas pass engages (interpret on CPU)
    df = pd.DataFrame({
        "a": rng.integers(0, 1 << 16, n).astype(np.int32),
        "b": rng.integers(0, 1 << 12, n).astype(np.int32),
        "v": np.arange(n, dtype=np.int64),
    })
    t = ct.Table.from_pandas(ctx1, df)
    monkeypatch.setenv("CYLON_TPU_SORT_IMPL", "radix_pallas")
    got = t.sort(["a", "b"])
    monkeypatch.delenv("CYLON_TPU_SORT_IMPL")
    want = _oracle(lambda: ct.Table.from_pandas(ctx1, df).sort(["a", "b"]))
    _emitted_equal(got, want)


def test_kill_switch_forces_bitonic(ctx1, rng, monkeypatch):
    monkeypatch.setenv("CYLON_TPU_NO_RADIX", "1")
    assert rx.resolved_impl() == "bitonic"
    monkeypatch.setenv("CYLON_TPU_SORT_IMPL", "radix")
    assert rx.resolved_impl() == "bitonic"  # kill-switch wins over force
