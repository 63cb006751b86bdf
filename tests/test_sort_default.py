"""The sort engine's default: the chip's native sort.

With nothing forced, ``ops/radix.resolved_impl()`` ends in ``"bitonic"``
(the value the code gives ``jax.lax.sort`` / ``jnp.argsort``), so
``lexsort_perm`` / ``argsort_perm`` return None and every call site
takes its native branch. The radix engine stays reachable by
``CYLON_TPU_SORT_IMPL=radix`` (its own tests: test_radix_sort.py).

  1. the resolver: the default, and the precedence of kill switch,
     force and tuned decision above it;
  2. the programs the default dispatches hold no ``radix_pass`` and
     trace no radix pass (``radix.trace_passes``), and a forced radix
     run of the same programs does both, so the check can tell;
  3. default and forced-radix results agree in exact emitted order
     (the stable lexsort permutation is unique).
"""
import numpy as np
import pandas as pd
import pandas.testing as pdt
import pytest

import jax.numpy as jnp

import cylon_tpu as ct
from cylon_tpu.obs import stages
from cylon_tpu.ops import radix as rx
from cylon_tpu.plan import feedback as fb
from cylon_tpu.utils import tracing

FORCE, KILL = "CYLON_TPU_SORT_IMPL", "CYLON_TPU_NO_RADIX"


@pytest.fixture(autouse=True)
def clean_env(monkeypatch):
    monkeypatch.delenv(FORCE, raising=False)
    monkeypatch.delenv(KILL, raising=False)


def _ctx(devices, world):
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )


# ---------------------------------------------------------------------------
# 1. the resolver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env,tuned,want", [
    ({}, None, "bitonic"),
    ({FORCE: "auto"}, None, "bitonic"),
    ({FORCE: "radix"}, None, "radix"),
    ({FORCE: "radix_pallas"}, None, "radix_pallas"),
    ({FORCE: "quicksort"}, None, "bitonic"),  # an unknown name forces the native sort
    ({}, "radix", "radix"),  # a tuned decision stands above the default
    ({FORCE: "bitonic"}, "radix", "bitonic"),  # the force above the decision
    ({FORCE: "auto"}, "radix", "radix"),  # 'auto' forces nothing
    ({KILL: "1", FORCE: "radix"}, "radix", "bitonic"),  # the kill switch first
], ids=[
    "clean", "auto", "force-radix", "force-pallas", "force-unknown",
    "tuned", "force-over-tuned", "auto-leaves-tuned", "kill-switch-first",
])
def test_resolver_precedence(monkeypatch, env, tuned, want):
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    with fb.applying((True, fb.Decisions(sort_impl=tuned))):
        assert rx.resolved_impl() == want
        assert rx.impl_tag() == (
            "sort_impl", want, rx.RADIX_BITS, rx.PALLAS_RADIX_BITS
        )


def test_default_perm_helpers_decline(rng):
    lane = jnp.asarray(rng.integers(0, 16, 257), jnp.uint32)
    assert rx.lexsort_perm([lane], 257, [rx.span_hint(0, 4)]) is None
    assert rx.argsort_perm(lane, rx.bound_hint(15)) is None
    assert rx.kernel_kwargs() == {}


@pytest.mark.parametrize("impl", ["default", "radix"])
def test_kv_sort_is_the_stable_sort(monkeypatch, rng, impl):
    if impl != "default":
        monkeypatch.setenv(FORCE, impl)
    keys = rng.integers(0, 40, 700).astype(np.int32)
    skey, spay = rx.kv_sort(
        jnp.asarray(keys), jnp.arange(700, dtype=jnp.int32), rx.bound_hint(40)
    )
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(np.asarray(skey), keys[order])
    np.testing.assert_array_equal(np.asarray(spay), order)


# ---------------------------------------------------------------------------
# 2. no radix pass in the default's programs
# ---------------------------------------------------------------------------

def _suite_tables(ctx, rows=2048, seed=5):
    """The benchmark's widths: int64 key over the row count, float64 value."""
    rng = np.random.default_rng(seed)
    return tuple(
        ct.Table.from_numpy(
            ctx, ["k", name],
            [rng.integers(0, rows, rows).astype(np.int64), rng.random(rows)],
        )
        for name in ("v", "w")
    )


def _dispatch(devices, op, world):
    """Run ``op`` on a context of its own; (lowered text a program, passes
    traced)."""
    ctx = _ctx(devices, world)
    ta, tb = _suite_tables(ctx)
    before = tracing.get_count("radix.trace_passes")
    if op == "sort":
        assert ta.distributed_sort("k").row_count == 2048
    else:
        assert ta.distributed_join(tb, on="k", how="inner").row_count > 0
    texts = {}
    for _key, fn, spec in stages.dispatched_programs(ctx):
        texts.setdefault(fn.__name__, []).append(fn.lower(*spec).as_text())
    return texts, tracing.get_count("radix.trace_passes") - before


@pytest.mark.parametrize("op,world,must", [
    ("sort", 1, ["sort"]),
    ("join", 1, ["join_spec"]),
    ("sort", 4, ["sort", "shuffle_pack"]),
    ("join", 4, ["join_spec", "shuffle_pack"]),
], ids=["sort-w1", "join-w1", "sort-w4", "join-w4"])
def test_default_programs_hold_no_radix_pass(devices, monkeypatch, op, world, must):
    texts, traced = _dispatch(devices, op, world)
    assert traced == 0
    for name in must:
        assert name in texts, (name, sorted(texts))
    for name, programs in texts.items():
        for text in programs:
            assert "radix_pass" not in text, name
    # the same programs under the force: the check can tell
    monkeypatch.setenv(FORCE, "radix")
    texts, traced = _dispatch(devices, op, world)
    assert traced > 0
    for name in must:
        assert any("radix_pass" in text for text in texts[name]), name


# ---------------------------------------------------------------------------
# 3. default against forced radix, exact emitted order
# ---------------------------------------------------------------------------

def _frames(rng):
    n = 900
    df = pd.DataFrame({
        "k": rng.integers(0, 70, n).astype(np.int64),
        "j": rng.integers(-9, 9, n).astype(np.int32),
        "v": rng.random(n),
        "f": rng.normal(size=n).astype(np.float32),
    })
    rdf = pd.DataFrame({
        "k": rng.integers(0, 70, n // 2).astype(np.int64),
        "w": rng.random(n // 2),
    })
    return df, rdf


_OPS = {
    "sort": lambda t, r: t.distributed_sort(["k", "j"]),
    "sort_desc": lambda t, r: t.sort(["j", "k"], ascending=[False, True]),
    "join": lambda t, r: t.distributed_join(r, on="k", how="inner"),
    "join_left": lambda t, r: t.distributed_join(r, on="k", how="left"),
    "groupby": lambda t, r: t.distributed_groupby(["k", "j"], {"v": "sum"}),
    "unique": lambda t, r: t.unique(["k", "j"]),
    "shuffle": lambda t, r: t.shuffle(["k"]),
}


@pytest.mark.parametrize("op,world", [
    (op, world) for world in (1, 4) for op in _OPS
    if (op, world) != ("shuffle", 1)  # one shard: nothing is shuffled
], ids=lambda p: str(p))
def test_default_agrees_with_forced_radix(devices, monkeypatch, rng, op, world):
    df, rdf = _frames(rng)
    ctx = _ctx(devices, world)

    def run():
        t, r = ct.Table.from_pandas(ctx, df), ct.Table.from_pandas(ctx, rdf)
        return _OPS[op](t, r).to_pandas().reset_index(drop=True)

    got = run()
    monkeypatch.setenv(FORCE, "radix")
    before = tracing.get_count("radix.trace_passes")
    want = run()
    assert tracing.get_count("radix.trace_passes") > before  # radix did run
    pdt.assert_frame_equal(got, want)
