"""Windowed Pallas expand (ops/pallas_gather) semantics on the CPU mesh
(interpret mode), plus end-to-end join equivalence of the windowed emit
path vs the XLA-gather emit path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cylon_tpu.ops import join as J
from cylon_tpu.ops.pallas_gather import expand_available, expand_rows

pytestmark = pytest.mark.skipif(
    not expand_available(), reason="pallas unavailable"
)


@pytest.mark.parametrize("impl", ["take", "onehot", "take_db", "onehot_db"])
@pytest.mark.parametrize(
    "m,hot,T",
    [(700, 0, 512), (700, 3, 512), (3, 0, 512), (9000, 2, 2048)],
)
def test_expand_rows_oracle(rng, impl, m, hot, T):
    # expand contract: every count >= 1 (zero-count rows are compacted away
    # by the caller — a zero would create a step > 1 and a window miss)
    cnt = rng.integers(1, 4, m)
    if hot:
        cnt[rng.integers(0, m, hot)] = 700  # skewed runs (step 0: safe)
    li = np.repeat(np.arange(m), cnt).astype(np.int32)
    if len(li) == 0:
        li = np.zeros(1, np.int32)
    L = 5
    src = rng.integers(-(2**31), 2**31, (L, m), dtype=np.int64).astype(np.int32)
    got = np.asarray(
        expand_rows(jnp.asarray(src), jnp.asarray(li), T=T, impl=impl,
                    interpret=True)
    )
    want = src[:, np.clip(li, 0, m - 1)]
    assert (got == want).all()


def _emit_pair(rng, how, n_l, n_r, keyspace, with_valid=False, with_f64=False):
    """Run both emit impls on one random probe state; return their outputs."""
    cap_l = max(1 << (n_l - 1).bit_length(), 8)
    cap_r = max(1 << (n_r - 1).bit_length(), 8)
    lk = np.zeros(cap_l, np.int32)
    rk = np.zeros(cap_r, np.int32)
    lk[:n_l] = rng.integers(0, keyspace, n_l)
    rk[:n_r] = rng.integers(0, keyspace, n_r)
    lv = np.zeros(cap_l, np.float32)
    lv[:n_l] = rng.normal(size=n_l)
    rv = np.zeros(cap_r, np.float32)
    rv[:n_r] = rng.normal(size=n_r)
    nl = jnp.int32(n_l)
    nr = jnp.int32(n_r)
    l_key_cols = [(jnp.asarray(lk), None)]
    r_key_cols = [(jnp.asarray(rk), None)]
    l_cols = [(jnp.asarray(lk), None), (jnp.asarray(lv), None)]
    if with_valid:
        lval = np.ones(cap_l, bool)
        lval[: n_l // 2] = rng.random(n_l // 2) > 0.3
        l_cols[1] = (l_cols[1][0], jnp.asarray(lval))
    if with_f64:
        l_cols.append((jnp.asarray(lv.astype(np.float64) * 3), None))
    r_cols = [(jnp.asarray(rk), None), (jnp.asarray(rv), None)]

    howi = J.join_type_id(how)
    lo, cnt, r_order, r_cnt = J.probe_arrays(
        l_key_cols, r_key_cols, nl, nr, cap_l, cap_r, howi
    )
    total = int(J.count_from_probe(cnt, r_cnt, nl, nr, howi))
    cap_out = max(1 << (max(total, 1) - 1).bit_length(), 8)
    from cylon_tpu.ops.gather import pack_gather

    r_sorted, _ = pack_gather(r_cols, r_order)
    r_sorted = [
        (d, None) for (d, v) in r_sorted
    ]  # r_cols mask-free: keep mask-free
    outs = {}
    for impl in ("gather", "windowed_interp"):
        cols, n_out, _handed = J._emit_inner_left(
            lo, cnt, l_cols, r_sorted, nl, howi, cap_out, cap_r, impl
        )
        outs[impl] = (
            [(np.asarray(d), None if v is None else np.asarray(v)) for d, v in cols],
            int(n_out),
        )
    return outs, total


def _rows(cols, n):
    """Set-comparable row tuples (validity-aware)."""
    out = []
    for i in range(n):
        row = []
        for d, v in cols:
            ok = True if v is None else bool(v[i])
            row.append(None if not ok else d[i].item())
        out.append(tuple(row))
    return sorted(out, key=repr)


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("n_l,n_r,keyspace", [(300, 200, 40), (64, 64, 5), (5, 300, 3)])
def test_windowed_emit_matches_gather_emit(rng, how, n_l, n_r, keyspace):
    outs, total = _emit_pair(rng, how, n_l, n_r, keyspace)
    (a_cols, a_n), (b_cols, b_n) = outs.values()
    assert a_n == b_n == total
    assert _rows(a_cols, a_n) == _rows(b_cols, b_n)


def test_windowed_emit_validity_and_f64(rng):
    outs, total = _emit_pair(
        rng, "left", 200, 150, 30, with_valid=True, with_f64=True
    )
    (a_cols, a_n), (b_cols, b_n) = outs.values()
    assert a_n == b_n == total
    assert _rows(a_cols, a_n) == _rows(b_cols, b_n)


def test_windowed_emit_wide_table_gate(rng, monkeypatch):
    """Tables wide enough to overflow the expand's VMEM must silently take
    the XLA gather path (the windowed kernel must not even be invoked)."""
    import cylon_tpu.ops.pallas_gather as pg

    def boom(*a, **k):  # pragma: no cover - must not be reached
        raise AssertionError("expand_rows called despite the VMEM gate")

    monkeypatch.setattr(pg, "expand_rows_raw", boom)
    n, cap = 40, 64
    lk = np.zeros(cap, np.int32)
    lk[:n] = rng.integers(0, 10, n)
    rk = lk.copy()
    # 110 int64 columns -> 220 data lanes + bookkeeping > the 200-lane gate
    l_cols = [(jnp.asarray(lk), None)] + [
        (jnp.asarray(np.arange(cap, dtype=np.int64)), None) for _ in range(110)
    ]
    lo, cnt, r_order, r_cnt = J.probe_arrays(
        [(jnp.asarray(lk), None)], [(jnp.asarray(rk), None)],
        jnp.int32(n), jnp.int32(n), cap, cap, J.INNER,
    )
    from cylon_tpu.ops.gather import pack_gather

    r_sorted, _ = pack_gather([(jnp.asarray(rk), None)], r_order)
    cols, n_out, _handed = J._emit_inner_left(
        lo, cnt, l_cols, [(r_sorted[0][0], None)],
        jnp.int32(n), J.INNER, 256, cap, "windowed_interp",
    )
    assert int(n_out) > 0  # produced via the gather path, kernel untouched


def test_windowed_emit_empty_left(rng):
    outs, total = _emit_pair(rng, "inner", 0, 50, 5)
    (a_cols, a_n), (b_cols, b_n) = outs.values()
    assert a_n == b_n == total == 0


@pytest.mark.parametrize("force_sm", [False, True])
def test_windowed_emit_multidevice_shard_map(ctx8, rng, monkeypatch, force_sm):
    """The windowed emit per-shard inside jit(shard_map) on a multi-device
    mesh (VERDICT r4 item 3's correctness gate), plus the forced-shard_map
    knob the hardware probe uses. Compares the full distributed join
    against pandas."""
    import pandas as pd

    import cylon_tpu as ct

    monkeypatch.setenv("CYLON_TPU_EMIT_IMPL", "windowed")
    if force_sm:
        monkeypatch.setenv("CYLON_TPU_FORCE_SHARD_MAP", "1")
    n = 300
    ldf = pd.DataFrame({
        "k": rng.integers(0, 40, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
    })
    rdf = pd.DataFrame({
        "k": rng.integers(0, 40, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32),
    })
    left = ct.Table.from_pydict(ctx8, {c: ldf[c].values for c in ldf})
    right = ct.Table.from_pydict(ctx8, {c: rdf[c].values for c in rdf})
    got = left.distributed_join(right, on="k", how="left").to_pandas()
    want = ldf.merge(rdf, on="k", how="left")
    want = want.assign(k_x=want["k"], k_y=want["k"]).drop(columns=["k"])
    # left-join null k_y: table semantics keep k_y null only for unmatched
    want.loc[want["w"].isna(), "k_y"] = np.nan
    cols = sorted(got.columns)
    g = got[cols].sort_values(cols, kind="mergesort").reset_index(drop=True)
    w = want[cols].sort_values(cols, kind="mergesort").reset_index(drop=True)
    pd.testing.assert_frame_equal(g, w, check_dtype=False, atol=1e-6)
