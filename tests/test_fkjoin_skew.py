"""The skewed fact-to-dimension join (``fkjoin-zipf125-w4``): the
generator's properties, ``distributed_join`` against a row-level reference
over worlds, exponents and join types, the skew split against its oracle
at world 8, the hash shuffle's new counters against numpy, and the two
properties of the round planner that the cell's shape rests on (no relay
on four evenly loaded shards; 2^19 slots and twice a uniform key's
rounds for this configuration). All on the CPU mesh at a few thousand rows."""
import json
import os
import sys

import jax
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu.config import DEFAULT_SHUFFLE_BYTE_BUDGET
from cylon_tpu.engine import shard_caps
from cylon_tpu.ops import partition as _p
from cylon_tpu.parallel import shuffle as _sh
from cylon_tpu.parallel import spill as _spill
from cylon_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import fkjoin_reference  # noqa: E402
from chipbench.generators import zipf_fk  # noqa: E402

with open(os.path.join(ROOT, "chipbench/configs/fkjoin-zipf125-w4.json")) as f:
    CONFIG = json.load(f)
ROWS = 4096
_CTX = {}


def _ctx(devices, world):
    if world not in _CTX:
        _CTX[world] = ct.CylonContext.init_distributed(
            ct.TPUConfig(devices=devices[:world])
        )
    return _CTX[world]


def _config(s):
    return dict(CONFIG, zipf_exponent=s)


def _tables(ctx, data):
    return [
        ct.Table.from_numpy(ctx, list(cols), list(cols.values()))
        for cols in (data["left"], data["right"])
    ]


def _rollup(*names):
    snap = tracing.snapshot()
    return [snap.get(n, {}).get("rows", 0) for n in names]


def _destinations(keys, world):
    """The shard of every key under the program's own hash, as numpy."""
    k = jax.numpy.asarray(keys)
    return np.asarray(_p.hash_partition_ids([(k, None)], len(keys), world))


# ----------------------------------------------------------------------
# the generator
# ----------------------------------------------------------------------

def test_generator_build_keys_are_a_permutation_and_every_probe_has_one():
    data = zipf_fk.make(CONFIG, 2147530001, 1 << 16)
    build, probe = data["right"]["k"], data["left"]["k"]
    assert len(probe) == 1 << 16 and len(build) == (1 << 16) // zipf_fk.RATIO
    assert (np.sort(build) == np.arange(len(build))).all()
    assert not (build == np.arange(len(build))).all()  # in a drawn order
    assert probe.min() >= 0 and probe.max() < len(build)
    assert probe.dtype == build.dtype == np.int64
    assert data["left"]["v"].dtype == data["right"]["w"].dtype == np.float64
    # the build payload lies on the grid that makes a key's sum exact
    grid = data["right"]["w"] * (1 << zipf_fk.GRID_BITS)
    assert (grid == np.round(grid)).all() and len(np.unique(grid)) > 4000
    assert (data["right"]["w"].astype(np.float32) != data["right"]["w"]).any()


@pytest.mark.parametrize("s", [1.05, 1.25])
def test_generator_top_key_share_is_the_finite_zipf_laws(s):
    n = (1 << 18) // zipf_fk.RATIO
    data = zipf_fk.make(_config(s), 31, 1 << 18)
    want = 1.0 / (np.arange(1, n + 1, dtype=np.float64) ** -s).sum()
    share = (data["left"]["k"] == 0).mean()
    assert abs(share / want - 1) < 0.02, (share, want)


def test_generator_repeats_by_seed_and_keeps_the_hot_keys_across_seeds():
    a = zipf_fk.make(CONFIG, 7, ROWS)
    b = zipf_fk.make(CONFIG, 7, ROWS)
    c = zipf_fk.make(CONFIG, 2147530001, ROWS)
    for t in a:
        for col in a[t]:
            assert (a[t][col] == b[t][col]).all()
            assert not (a[t][col] == c[t][col]).all()
    hot = [np.argsort(-np.bincount(d["left"]["k"]))[:3] for d in (a, c)]
    assert (hot[0] == hot[1]).all() and (hot[0] == [0, 1, 2]).all()


def test_generator_exponent_zero_is_the_uniform_foreign_key():
    data = zipf_fk.make(_config(0), 5, 1 << 16)
    counts = np.bincount(data["left"]["k"], minlength=(1 << 16) // 16)
    assert counts.max() < 3 * counts.mean()


# ----------------------------------------------------------------------
# distributed_join against the row-level reference
# ----------------------------------------------------------------------

@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("s", [0, 1.05, 1.25])
@pytest.mark.parametrize("world", [1, 4, 8])
def test_distributed_join_holds_the_reference_rows(devices, world, s, how):
    data = zipf_fk.make(_config(s), 2147530001 + world, ROWS)
    if how == "left":
        # thin the dimension, the hottest key with it, so that a left join
        # is not an inner one
        keep = data["right"]["k"] % 5 != 0
        data["right"] = {c: a[keep] for c, a in data["right"].items()}
    left, right = _tables(_ctx(devices, world), data)
    got = left.distributed_join(right, on="k", how=how)
    want = fkjoin_reference.join_rows(data["left"], data["right"], "k", how)
    if how == "inner":
        assert len(want["k"]) == ROWS  # every probe row has its partner
    else:
        assert not want["matched"].all() and len(want["k"]) == ROWS
    assert got.row_count == len(want["k"])
    assert fkjoin_reference.first_wrong_row(got.to_pydict(), want, "k") is None


def test_reference_names_a_re_paired_row():
    data = zipf_fk.make(CONFIG, 3, ROWS)
    want = fkjoin_reference.join_rows(data["left"], data["right"], "k")
    got = {"k_x": want["k"], "k_y": want["k"], "v": want["v"],
           "w": want["w"].copy()}
    assert fkjoin_reference.first_wrong_row(got, want, "k") is None
    # two rows of different keys swap their build values: per-row, not
    # per-key, is where it shows
    i = int(np.argmax(want["k"] != want["k"][0]))
    got["w"][[0, i]] = got["w"][[i, 0]]
    said = fkjoin_reference.first_wrong_row(got, want, "k")
    assert said is not None and said.startswith("row 0 ") and ": w is" in said


def test_skew_split_and_its_oracle_give_the_same_rows_at_world_8(
    devices, monkeypatch
):
    """On eight shards a bucket can pass four times the mean (on four
    evenly loaded ones it cannot: below), so the skew split engages; with
    it and without it the join holds the same rows."""
    data = zipf_fk.make(_config(2.5), 11, ROWS)  # the top key holds 3 in 4
    want = fkjoin_reference.join_rows(data["left"], data["right"], "k")
    left, right = _tables(_ctx(devices, 8), data)
    (before,) = _rollup("shuffle.skew_split")
    split = left.distributed_join(right, on="k", how="inner").to_pydict()
    (after,) = _rollup("shuffle.skew_split")
    assert after > before  # rows went through the relay
    monkeypatch.setenv("CYLON_TPU_NO_SKEW_SPLIT", "1")
    padded = left.distributed_join(right, on="k", how="inner").to_pydict()
    assert _rollup("shuffle.skew_split") == [after]
    for got in (split, padded):
        assert fkjoin_reference.first_wrong_row(got, want, "k") is None


# ----------------------------------------------------------------------
# the counters
# ----------------------------------------------------------------------

NEW_COUNTERS = (
    "shuffle.hash.shard_rows_max", "shuffle.hash.shard_rows_mean",
    "shuffle.coll_slots", "shuffle.coll_rows", "shuffle.rounds",
)


def test_hash_shuffle_counters_read_what_numpy_counts(devices):
    world = 4
    data = zipf_fk.make(CONFIG, 19, ROWS)["left"]
    table = ct.Table.from_numpy(
        _ctx(devices, world), list(data), list(data.values())
    )
    before = _rollup(*NEW_COUNTERS)
    assert table.shuffle(["k"]).row_count == ROWS
    fullest, mean, slots, rows, rounds = (
        a - b for a, b in zip(_rollup(*NEW_COUNTERS), before)
    )
    dst = _destinations(data["k"], world)
    received = np.bincount(dst, minlength=world)
    assert fullest == received.max() and mean == ROWS // world
    assert fullest > 1.5 * mean  # the Zipf key shows
    assert rows == ROWS
    # the matrix by source: the table's even row split
    bounds = np.concatenate([[0], np.cumsum(shard_caps(ROWS, world)[0])])
    matrix = np.stack([
        np.bincount(dst[a:b], minlength=world)
        for a, b in zip(bounds[:-1], bounds[1:])
    ])
    cap = slots // (rounds * world * world)
    assert slots == rounds * world * world * cap
    assert cap * (rounds - 1) < matrix.max() <= cap * rounds


def test_range_shuffle_bumps_no_hash_counter(devices):
    data = zipf_fk.make(CONFIG, 23, ROWS)["left"]
    table = ct.Table.from_numpy(
        _ctx(devices, 4), list(data), list(data.values())
    )
    names = NEW_COUNTERS[:2]
    before = _rollup(*names)
    assert table.distributed_sort("v").row_count == ROWS
    assert _rollup(*names) == before


def test_new_counters_are_in_the_catalog():
    from cylon_tpu.obs import metrics

    for name in NEW_COUNTERS:
        assert metrics.is_declared(name), name


# ----------------------------------------------------------------------
# the planner's properties the cell's shape rests on
# ----------------------------------------------------------------------

def _even_matrices(rng, world, rows, n):
    """Count matrices whose sources hold ``rows`` each, from near uniform
    to one-hot, plus the extremes."""
    for _ in range(n):
        alpha = 10.0 ** rng.uniform(-2, 1)
        shares = rng.dirichlet(np.full(world, alpha), size=world)
        m = np.floor(shares * rows).astype(np.int64)
        m[:, 0] += rows - m.sum(axis=1)
        yield m
    one_hot = np.zeros((world, world), np.int64)
    one_hot[:, 2] = rows
    yield one_hot
    same_skew = np.tile(
        np.array([rows - 3, 1, 1, 1], np.int64)[:world], (world, 1)
    )
    yield same_skew


@pytest.mark.parametrize("row_bytes", [12, 16])
def test_no_relay_on_four_shards_whose_sources_hold_equal_rows(row_bytes):
    """A bucket is heavy when it is OVER four times the mean bucket. With
    four sources of R rows each the mean is R / 4 and no bucket can pass
    R, so on the benchmark's only world size the skew split never engages
    for an eager shuffle: skew is paid for by rounds and padding alone. A
    change of the trigger that breaks this is a decision, not an
    accident."""
    rng = np.random.default_rng(33)
    for rows in (8_000_000, 500_000, 4096):
        for m in _even_matrices(rng, 4, rows, 60):
            assert (m.sum(axis=1) == rows).all()
            sched = _spill.plan_schedule(
                m, row_bytes, 4, DEFAULT_SHUFFLE_BYTE_BUDGET
            )
            assert not sched.adaptive, m
            assert (sched.bucket_cap, sched.n_rounds) == _sh.plan_rounds(
                m, row_bytes, 4, DEFAULT_SHUFFLE_BYTE_BUDGET
            )


def test_relay_can_engage_from_eight_shards_on():
    m = np.zeros((8, 8), np.int64) + 10
    m[:, 5] = 100_000  # 8 sources of equal rows, one hot destination
    assert (m.sum(axis=1) == m.sum(axis=1)[0]).all()
    sched = _spill.plan_schedule(m, 16, 8, DEFAULT_SHUFFLE_BYTE_BUDGET)
    assert sched.adaptive and sched.relay_rows() > 0


@pytest.mark.parametrize("row_bytes", [12, 16])
@pytest.mark.parametrize("probe,rounds,uniform_rounds", [
    (16_000_000, 4, 2),  # the configuration as it is run
    (32_000_000, 8, 4),  # ISSUE 33's rows, at which a query took 14 s
])
def test_plan_rounds_for_the_cells_matrix(
    row_bytes, probe, rounds, uniform_rounds
):
    """``join-skew-w4``'s probe table under the default budget: 2^19 slots
    a bucket and twice the rounds a uniform key of the same rows takes.
    The matrix is the law's, not a draw's: each key's share of the rows
    goes to the shard the program's hash gives the key, and every source
    holds a quarter of each."""
    build = probe // zipf_fk.RATIO
    if probe == 16_000_000:
        assert zipf_fk.sizes(CONFIG, None) == (build, probe)
    cdf = zipf_fk.zipf_cdf(build, CONFIG["zipf_exponent"])
    share = np.bincount(
        _destinations(np.arange(build, dtype=np.int64), 4),
        weights=np.diff(cdf, prepend=0.0), minlength=4,
    )
    assert 0.48 < share.max() < 0.51  # one shard receives half the table
    matrix = np.tile(np.round(share * probe / 4).astype(np.int64), (4, 1))
    fullest = int(matrix.sum(axis=0).max())
    assert 0.9 < fullest / (1 << (fullest - 1).bit_length()) < 0.96
    assert _sh.plan_rounds(
        matrix, row_bytes, 4, DEFAULT_SHUFFLE_BYTE_BUDGET
    ) == (1 << 19, rounds)
    uniform = np.full((4, 4), probe // 16, np.int64)
    assert _sh.plan_rounds(
        uniform, row_bytes, 4, DEFAULT_SHUFFLE_BYTE_BUDGET
    ) == (1 << 19, uniform_rounds)


# ----------------------------------------------------------------------
# the packed gather in blocks (what the 2^24-slot join needed to fit)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("n_idx", [8, 9, 64, 77])
def test_packed_gather_in_blocks_is_the_one_gather(monkeypatch, n_idx):
    """Past ``PACK_GATHER_BLOCK`` index rows ``pack_gather`` gathers block
    by block (a gathered [rows, L] matrix costs 512 bytes a row on a TPU,
    8 GiB at the 2^24 slots of the skewed join's shards); the rows are the
    one gather's, whether or not the length is a multiple of the block."""
    from cylon_tpu.ops import gather as _g

    rng = np.random.default_rng(n_idx)
    cap = 40
    cols = [
        (jax.numpy.asarray(rng.integers(-2**62, 2**62, cap)), None),
        (jax.numpy.asarray(rng.random(cap)), None),
        (jax.numpy.asarray(rng.integers(0, 9, cap).astype(np.int32)),
         jax.numpy.asarray(rng.random(cap) < 0.7)),
    ]
    extra = [jax.numpy.asarray(rng.integers(0, 99, cap).astype(np.int32))]
    idx = jax.numpy.asarray(rng.integers(-1, cap, n_idx).astype(np.int32))
    whole = _g.pack_gather(cols, idx, extra_lanes=extra)
    monkeypatch.setattr(_g, "PACK_GATHER_BLOCK", 8)
    blocked = _g.pack_gather(cols, idx, extra_lanes=extra)
    for (a, av), (b, bv) in zip(whole[0], blocked[0]):
        assert a.dtype == b.dtype and (np.asarray(a) == np.asarray(b)).all()
        assert (av is None) == (bv is None)
        assert av is None or (np.asarray(av) == np.asarray(bv)).all()
    assert (np.asarray(whole[1][0]) == np.asarray(blocked[1][0])).all()
    text = jax.jit(
        lambda i: _g.pack_gather(cols, i, extra_lanes=extra)
    ).lower(idx).as_text()
    assert ("while" in text) == (n_idx > 8)
