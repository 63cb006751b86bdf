"""Fused Pallas shuffle codec tests (ISSUE 20, ops/pallas_codec.py).

Four layers, mirroring the quant tier's differential layout
(test_quant_wire.py):

  1. kernel unit differentials — fused_pack_dest (hash mode AND
     pid-input mode) against the exact XLA chain it replaces
     (hash_partition_ids -> bucket_counts -> build_send_slots_round),
     and fused_compact_move against the mask -> stable argsort ->
     gather it replaces, bit-for-bit including the dead tail;
  2. edge cases — zero-row chunks through pack_lane_buffer /
     split_header and through the fused move, garbage pids behind the
     live count, multi-round respill windows;
  3. end-to-end differentials vs the CYLON_TPU_NO_PALLAS_CODEC=1
     oracle at worlds {1, 4, 8}: bit-exact table outputs (the codec is
     lossless by contract — quantized lanes too, because both impls
     ship the SAME q8 codes and scales);
  4. gate pins — resolver ladder, structural decliners (multi-header
     quant wire, non-pow2 world), and the impl tag that keys the
     kernel caches.
"""
import os

import numpy as np
import pandas as pd
import pytest

import jax.numpy as jnp

import cylon_tpu as ct
from cylon_tpu.ops import pallas_codec as pc
from cylon_tpu.ops import partition as part
from cylon_tpu.parallel import shuffle as _sh

pytestmark = pytest.mark.skipif(
    not pc.codec_available(), reason="pallas unavailable"
)


@pytest.fixture(scope="module")
def ctx1(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:1]))


@pytest.fixture(scope="module")
def ctx4(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:4]))


@pytest.fixture(scope="module")
def ctx8(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:8]))


@pytest.fixture(autouse=True)
def _clean_env():
    saved = {
        k: os.environ.get(k)
        for k in ("CYLON_TPU_CODEC_IMPL", "CYLON_TPU_NO_PALLAS_CODEC",
                  "CYLON_TPU_QUANT_TOL")
    }
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _xla_pack(pid, world, bc, r):
    cnt = _sh.bucket_counts(pid, world)
    dest, _ = _sh.build_send_slots_round(pid, cnt, world, bc, r)
    return np.asarray(dest), np.asarray(cnt)


# ----------------------------------------------------------------------
# 1. kernel unit differentials
# ----------------------------------------------------------------------

@pytest.mark.parametrize("world", [2, 4, 8])
def test_fused_pack_hash_mode_matches_xla_chain(world, rng):
    cap, n = 1024, 900
    kcols = [
        (jnp.asarray(rng.integers(-5000, 5000, cap).astype(np.int32)),
         jnp.asarray(rng.integers(0, 2, cap).astype(bool))),
        (jnp.asarray((rng.normal(size=cap) * 40).astype(np.float32)), None),
    ]
    pid = part.hash_partition_ids(kcols, jnp.int32(n), world)
    words, valids, hv = pc.hash_operands(kcols)
    # bc small enough that hot buckets respill: rounds 0 and 1 both
    # carry rows and round 2 is all-dropped — every window is exercised
    bc = (n // world) // 2
    for r in range(3):
        dest, cnt = _xla_pack(pid, world, bc, r)
        dest_f, cnt_f = pc.fused_pack_dest(
            words, valids, hv, jnp.int32(n), r, world, bc, interpret=True
        )
        assert np.array_equal(np.asarray(cnt_f), cnt)
        assert np.array_equal(np.asarray(dest_f), dest), f"round {r}"


@pytest.mark.parametrize("world", [4, 8])
def test_fused_pack_pid_mode_matches_xla_chain(world, rng):
    """pid-input mode (range/task/semi packs): the kernel consumes an
    XLA pid lane carrying the shared pid == P dead sentinel — for
    filtered live rows AND for garbage behind the live count, which the
    kernel's own rowid < n fold must drop."""
    cap, n = 1024, 800
    pid_np = rng.integers(0, world + 1, cap).astype(np.int32)  # incl. P
    garbage = pid_np.copy()
    garbage[n:] = rng.integers(0, world, cap - n)  # junk past n
    ref_pid = pid_np.copy()
    ref_pid[n:] = world  # the sentinel compute_pid guarantees
    bc = (n // world) // 2
    for r in range(2):
        dest, cnt = _xla_pack(jnp.asarray(ref_pid), world, bc, r)
        dest_f, cnt_f = pc.fused_pack_dest(
            [], [], (), jnp.int32(n), r, world, bc,
            pid=jnp.asarray(garbage), interpret=True,
        )
        assert np.array_equal(np.asarray(cnt_f), cnt)
        assert np.array_equal(np.asarray(dest_f), dest), f"round {r}"


def test_fused_compact_matches_argsort_gather(rng):
    world, bc, lm = 8, 16, 3
    move = jnp.asarray(
        rng.integers(-(2 ** 31), 2 ** 31 - 1, (world * bc, lm)).astype(
            np.int32
        )
    )
    for counts in (
        rng.integers(0, bc + 1, world).astype(np.int32),
        np.zeros(world, np.int32),                      # nothing received
        np.full(world, bc, np.int32),                   # every slot live
        np.array([bc, 0, 3, 0, bc, 1, 0, 7], np.int32),  # zero-row chunks
    ):
        rc = jnp.asarray(counts)
        mask, total = _sh.received_row_mask(rc, world, bc)
        order = jnp.argsort(~mask, stable=True)
        ref = np.asarray(move[order])
        moved, tot = pc.fused_compact_move(move, rc, world, bc,
                                           interpret=True)
        assert int(tot) == int(total) == int(counts.sum())
        assert np.array_equal(np.asarray(moved), ref), counts


# ----------------------------------------------------------------------
# 2. edge cases through the shared XLA scatter/header helpers
# ----------------------------------------------------------------------

def test_zero_row_chunks_through_pack_and_split(rng):
    """Buckets with zero rows: the fused dest/cnt drive the SAME
    pack_lane_buffer scatter and split_header strip as the XLA chain —
    empty chunks keep a zero header count and all-dead data rows."""
    world, cap, n, bc = 8, 512, 400, 64
    # rows only for even-numbered buckets; odd buckets are empty
    pid_np = (rng.integers(0, world // 2, cap) * 2).astype(np.int32)
    pid_np[n:] = world
    pid = jnp.asarray(pid_np)
    dest_f, cnt_f = pc.fused_pack_dest(
        [], [], (), jnp.int32(n), 0, world, bc, pid=pid, interpret=True
    )
    dest_x, cnt_x = _xla_pack(pid, world, bc, 0)
    assert np.array_equal(np.asarray(cnt_f), cnt_x)
    lanes = [jnp.asarray(rng.integers(0, 1000, cap).astype(np.int32))]
    rcnt = _sh.round_counts(cnt_f, bc, 0)
    buf_f = _sh.pack_lane_buffer(lanes, dest_f, rcnt, world, bc)
    buf_x = _sh.pack_lane_buffer(lanes, jnp.asarray(dest_x), rcnt, world, bc)
    assert np.array_equal(np.asarray(buf_f), np.asarray(buf_x))
    data, recv = _sh.split_header(buf_f, world)
    assert np.array_equal(np.asarray(recv), np.asarray(rcnt))
    assert np.asarray(recv)[1::2].sum() == 0  # odd chunks: zero rows
    # and the fused move handles those zero-row chunks exactly
    mask, _tot = _sh.received_row_mask(recv, world, bc)
    order = jnp.argsort(~mask, stable=True)
    moved, tot = pc.fused_compact_move(data, recv, world, bc,
                                       interpret=True)
    assert np.array_equal(np.asarray(moved), np.asarray(data[order]))
    assert int(tot) == int(np.asarray(rcnt).sum())


def test_pack_single_partition_world():
    """world=1 (pow2): everything lands in bucket 0; sentinel rows drop."""
    cap, n, bc = 256, 200, 256
    pid = jnp.asarray(
        np.r_[np.zeros(n, np.int32), np.ones(cap - n, np.int32)]
    )
    dest_f, cnt_f = pc.fused_pack_dest(
        [], [], (), jnp.int32(n), 0, 1, bc, pid=pid, interpret=True
    )
    dest_x, cnt_x = _xla_pack(pid, 1, bc, 0)
    assert np.array_equal(np.asarray(cnt_f), cnt_x)
    assert np.array_equal(np.asarray(dest_f), dest_x)


# ----------------------------------------------------------------------
# 3. end-to-end differentials vs the kill-switch oracle
# ----------------------------------------------------------------------

def _diff_tables(out, ref):
    cols = list(out.columns)
    o = out.sort_values(cols).reset_index(drop=True)
    r = ref.sort_values(cols).reset_index(drop=True)
    pd.testing.assert_frame_equal(o, r)


def _join_frames(rng, n=700):
    la = pd.DataFrame({
        "k": rng.integers(0, 150, n).astype(np.int64),
        "v": rng.normal(size=n),                      # f64 lane
        "s": rng.normal(size=n).astype(np.float32),
    })
    lb = pd.DataFrame({
        "k": rng.integers(0, 150, n).astype(np.int64),
        "w": (rng.normal(size=n) * 10).astype(np.float32),
    })
    return la, lb


@pytest.mark.parametrize("ctxname", ["ctx1", "ctx4", "ctx8"])
def test_join_bit_exact_vs_oracle(ctxname, request, rng):
    ctx = request.getfixturevalue(ctxname)
    la, lb = _join_frames(rng)
    ta = ct.Table.from_pandas(ctx, la)
    tb = ct.Table.from_pandas(ctx, lb)
    os.environ["CYLON_TPU_CODEC_IMPL"] = "pallas"
    out = ta.distributed_join(tb, on=["k"]).to_pandas()
    with pc.disabled():
        ref = ta.distributed_join(tb, on=["k"]).to_pandas()
    assert len(out) > 0
    _diff_tables(out, ref)


@pytest.mark.parametrize("ctxname", ["ctx4", "ctx8"])
def test_groupby_bit_exact_vs_oracle(ctxname, request, rng):
    """Non-semi hash shuffle: the pack kernel's hash-fused mode."""
    ctx = request.getfixturevalue(ctxname)
    df = pd.DataFrame({
        "g": rng.integers(0, 60, 900).astype(np.int64),
        "x": rng.normal(size=900),
    })
    t = ct.Table.from_pandas(ctx, df)
    os.environ["CYLON_TPU_CODEC_IMPL"] = "pallas"
    out = t.distributed_groupby(["g"], {"x": "sum"}).to_pandas()
    with pc.disabled():
        ref = t.distributed_groupby(["g"], {"x": "sum"}).to_pandas()
    _diff_tables(out, ref)


def test_quantized_wire_bit_exact_vs_oracle(ctx4, rng):
    """All-quantized packs (pack_cols_quant): the multi-header q8 wire
    declines the pack kernel but keeps the fused compact — and both
    codec impls ship identical q8 codes + scales, so even the lossy
    lanes diff EXACTLY between impls."""
    df_a = pd.DataFrame({
        "k": rng.integers(0, 100, 600).astype(np.int32),
        "a": (rng.normal(size=600) * 30).astype(np.float32),
        "b": (rng.normal(size=600) * 5).astype(np.float32),
    })
    df_b = pd.DataFrame({
        "k": rng.integers(0, 100, 500).astype(np.int32),
        "c": (rng.normal(size=500) * 2).astype(np.float32),
    })
    ta = ct.Table.from_pandas(ctx4, df_a)
    tb = ct.Table.from_pandas(ctx4, df_b)
    os.environ["CYLON_TPU_QUANT_TOL"] = "1e-2"
    os.environ["CYLON_TPU_CODEC_IMPL"] = "pallas"
    out = ta.distributed_join(tb, on=["k"]).to_pandas()
    with pc.disabled():
        ref = ta.distributed_join(tb, on=["k"]).to_pandas()
    _diff_tables(out, ref)


def test_f64_passthrough_lane_vs_oracle(ctx4, rng):
    """f64 payload columns ride the passthrough gather keyed by the
    fused move's carried order lane — bit-exact against the oracle's
    argsort-gather order."""
    df = pd.DataFrame({
        "k": rng.integers(0, 80, 640).astype(np.int64),
        "p": rng.normal(size=640),  # float64 passthrough
    })
    t = ct.Table.from_pandas(ctx4, df)
    os.environ["CYLON_TPU_CODEC_IMPL"] = "pallas"
    out = t.distributed_sort(["k"]).to_pandas()
    with pc.disabled():
        ref = t.distributed_sort(["k"]).to_pandas()
    pd.testing.assert_frame_equal(
        out.reset_index(drop=True), ref.reset_index(drop=True)
    )


# ----------------------------------------------------------------------
# 4. gate pins
# ----------------------------------------------------------------------

def test_resolver_ladder_and_tag():
    os.environ.pop("CYLON_TPU_CODEC_IMPL", None)
    os.environ.pop("CYLON_TPU_NO_PALLAS_CODEC", None)
    # the auto default selects no kernel the TPU compiler refuses: XLA on
    # every platform until a pack kernel lowers (ops/pallas_codec.py)
    assert pc.resolved_impl() == "xla"
    assert pc.kernel_kwargs() == {}
    os.environ["CYLON_TPU_CODEC_IMPL"] = "xla"
    assert pc.resolved_impl() == "xla"
    tag_x = pc.impl_tag()
    os.environ["CYLON_TPU_CODEC_IMPL"] = "pallas"
    assert pc.resolved_impl() == "pallas"
    tag_p = pc.impl_tag()
    assert tag_x != tag_p and tag_x[0] == "codec_impl"
    os.environ.pop("CYLON_TPU_CODEC_IMPL", None)
    with pc.disabled():
        assert pc.resolved_impl() == "xla"
        assert not pc.gate_state()[0]
    assert pc.gate_state()[0]


def test_structural_decliners():
    # multi-header quant wire declines the pack kernel
    assert pc.pack_supported("hash", False, True, 1, 8)
    assert not pc.pack_supported("hash", False, True, 2, 8)
    # non-pow2 / oversized worlds decline
    assert not pc.pack_supported("hash", False, True, 1, 6)
    assert not pc.pack_supported("hash", False, True, 1, 2048)
    # kind/semi select the MODE, not engagement
    assert pc.pack_supported("range", False, True, 1, 8)
    assert pc.pack_supported("hash", True, True, 1, 8)
    assert pc.pack_fuses_hash("hash", False)
    assert not pc.pack_fuses_hash("hash", True)
    assert not pc.pack_fuses_hash("range", False)
    # compact: topo branch and VMEM-overflow move matrices decline
    assert pc.compact_supported(True, False, 8, 64, 4)
    assert not pc.compact_supported(True, True, 8, 64, 4)
    assert not pc.compact_supported(False, False, 8, 64, 4)
    big = pc.COMPACT_VMEM_BUDGET
    assert not pc.compact_supported(True, False, 8, big, 4)


def test_row_pass_tables_agree_with_census():
    from cylon_tpu.analysis import contracts as _c
    from cylon_tpu.obs import prof as _p

    assert pc.PACK_ROW_PASSES == _c.CODEC_PACK_ROW_PASSES
    assert pc.COMPACT_ROW_PASSES == _c.CODEC_COMPACT_ROW_PASSES
    for impl, passes in pc.PACK_ROW_PASSES.items():
        assert _p.PACK_WEIGHT_BY_IMPL[impl] == float(passes)
    for impl, passes in pc.COMPACT_ROW_PASSES.items():
        assert _p.COMPACT_WEIGHT_BY_IMPL[impl] == float(passes)
    assert pc.pack_row_passes("pallas", fuse_hash=False) == 2
    assert pc.pack_row_passes("pallas") == 1
    assert pc.pack_row_passes("xla", fuse_hash=False) == 3
