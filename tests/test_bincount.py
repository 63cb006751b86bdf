"""A count of rows into a static handful of bins: a compare and a sum.

``ops.partition.bin_counts`` serves the shuffle's bucket counts
(``parallel.shuffle.bucket_counts``) and the range partitioner's histogram
(``ops.partition.range_partition_ids``). Up to ``DENSE_BINS_MAX`` bins it
is a compare against the bin ids and a sum over the rows, past it an int32
scatter-add; either way the counts are ``np.bincount``'s, so every
partition id, send window and capacity downstream is what the scatter-add
gave. The range partitioner as it stood before (an int64 scatter-add
histogram) is kept here as the oracle.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

import cylon_tpu as ct
from cylon_tpu.compat import enable_x64
from cylon_tpu.ops import partition as _p
from cylon_tpu.ops.sort import wide_float, wide_int
from cylon_tpu.parallel import shuffle as _sh
from cylon_tpu.utils.tracing import report, reset_trace

B_MAX = _p.DENSE_BINS_MAX
#: not a multiple of 128: the reduction's last tile is ragged
CAP = 1000
BINS = sorted({1, 3, 4, 64, B_MAX, B_MAX + 1, 4096})


def _ids(kind, num_bins, rng):
    if kind == "sentinel":  # the shuffle's pid lane: live ids, then padding
        ids = rng.integers(0, num_bins, CAP)
        ids[rng.random(CAP) < 0.2] = num_bins  # the semi filter's dropped rows
        ids[CAP - 77:] = num_bins
    elif kind == "one_bin":
        ids = np.full(CAP, num_bins - 1)
    elif kind == "no_live_rows":
        ids = np.full(CAP, num_bins)
    elif kind == "out_of_range":  # either side, far and near
        ids = rng.integers(-3, num_bins + 3, CAP)
        ids[::7] = np.iinfo(np.int32).max
        ids[1::7] = np.iinfo(np.int32).min
    else:
        raise AssertionError(kind)
    return ids.astype(np.int32)


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize(
    "kind", ["sentinel", "one_bin", "no_live_rows", "out_of_range"]
)
@pytest.mark.parametrize("num_bins", BINS)
def test_bin_counts_are_numpys(num_bins, kind, x64, rng):
    ids = _ids(kind, num_bins, rng)
    inside = ids[(ids >= 0) & (ids < num_bins)]
    want = np.bincount(inside, minlength=num_bins)
    with enable_x64(x64):
        got = jax.jit(lambda i: _p.bin_counts(i, num_bins))(jnp.asarray(ids))
        via_shuffle = _sh.bucket_counts(jnp.asarray(ids), num_bins)
    assert got.dtype == jnp.int32 and got.shape == (num_bins,)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(via_shuffle), want)


def test_the_form_follows_the_static_bin_count():
    """One function, one threshold: the rollup says which form a traced
    kernel holds, and the lowered text agrees."""
    ids = jnp.zeros((CAP,), jnp.int32)
    for num_bins, name, other in (
        (B_MAX, "shuffle.bincount.dense", "shuffle.bincount.scatter"),
        (B_MAX + 1, "shuffle.bincount.scatter", "shuffle.bincount.dense"),
    ):
        reset_trace()
        text = jax.jit(
            lambda i, b=num_bins: _p.bin_counts(i, b)
        ).lower(ids).as_text()
        rep = report("shuffle.bincount")
        assert int(rep[name]["rows"]) == num_bins and other not in rep
        assert ("scatter" in text) == name.endswith("scatter")


def _range_partition_ids_before(key, n, num_partitions, num_bins=None,
                                axis_name=None, ascending=True):
    """``range_partition_ids`` as the parent commit had it: the histogram
    an int64 (``wide_int``) scatter-add of every row. The oracle."""
    data, valid = key
    cap = data.shape[0]
    if num_bins is None:
        num_bins = 16 * num_partitions
    x = _p._as_float(data)
    live = jnp.arange(cap, dtype=jnp.int32) < n
    ok = live if valid is None else (live & valid)
    big = jnp.asarray(np.finfo(np.dtype(wide_float())).max, wide_float())
    lo = jnp.min(jnp.where(ok, x, big))
    hi = jnp.max(jnp.where(ok, x, -big))
    if axis_name is not None:
        ends = jax.lax.all_gather(jnp.stack([lo, hi]), axis_name)
        lo, hi = jnp.min(ends[:, 0]), jnp.max(ends[:, 1])
    span = jnp.maximum(hi - lo, 1e-300)
    b = jnp.clip(((x - lo) / span * num_bins).astype(jnp.int32), 0, num_bins - 1)
    b = jnp.where(ok, b, num_bins)
    hist = jnp.zeros((num_bins,), wide_int()).at[b].add(1, mode="drop")
    if axis_name is not None:
        hist = jax.lax.psum(hist, axis_name)
    total = jnp.sum(hist)
    cum = jnp.cumsum(hist) - hist
    mid = cum.astype(wide_float()) + hist.astype(wide_float()) / 2
    per_part = jnp.maximum(total.astype(wide_float()) / num_partitions, 1.0)
    bin_to_part = jnp.clip(
        (mid / per_part).astype(jnp.int32), 0, num_partitions - 1
    )
    pid = bin_to_part[jnp.clip(b, 0, num_bins - 1)]
    if not ascending:
        pid = num_partitions - 1 - pid
    pid = jnp.where(ok, pid, num_partitions - 1)
    return jnp.where(live, pid, num_partitions).astype(jnp.int32)


def _key(kind, rows, rng):
    if kind == "int64":
        return rng.integers(-2**40, 2**40, rows).astype(np.int64), None
    if kind == "float64":  # a heavy tail, so the equal-width bins fill unevenly
        return rng.lognormal(sigma=2.0, size=rows), None
    if kind == "nullable":
        return rng.integers(0, 500, rows).astype(np.int64), rng.random(rows) < 0.8
    raise AssertionError(kind)


@pytest.mark.parametrize("num_bins", [None, B_MAX + 1], ids=["bins16P", "binsPast"])
@pytest.mark.parametrize("ascending", [True, False], ids=["asc", "desc"])
@pytest.mark.parametrize("kind", ["int64", "float64", "nullable"])
@pytest.mark.parametrize("where", ["local", "mesh4"])
def test_range_partition_ids_are_the_scatter_histograms(
    where, kind, ascending, num_bins, devices, rng
):
    world, cap = 4, 1000
    shards = 1 if where == "local" else world
    data, valid = _key(kind, shards * cap, rng)
    n = np.asarray([cap - 13 * (s + 1) for s in range(shards)], np.int32)
    axis = None if where == "local" else "dp"

    def both(data, valid, n):
        key = (data, valid if kind == "nullable" else None)
        kw = dict(num_bins=num_bins, axis_name=axis, ascending=ascending)
        return (
            _p.range_partition_ids(key, n[0], world, **kw),
            _range_partition_ids_before(key, n[0], world, **kw),
        )

    if where == "mesh4":
        spec = PartitionSpec("dp")
        both = jax.shard_map(
            both, mesh=Mesh(np.array(devices[:world]), ("dp",)),
            in_specs=spec, out_specs=spec,
        )
    if valid is None:
        valid = np.ones(shards * cap, bool)
    got, want = jax.jit(both)(
        jnp.asarray(data), jnp.asarray(valid), jnp.asarray(n)
    )
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and it partitions: every live row has a shard, padding the sentinel
    got = np.asarray(got).reshape(shards, cap)
    for s in range(shards):
        assert (got[s, : n[s]] < world).all() and (got[s, n[s]:] == world).all()


@pytest.fixture
def fresh_ctx4(devices):
    """A context of its own: the counters are bumped where a kernel is
    traced, and a context another test has used holds its kernels built."""
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:4]))


@pytest.mark.parametrize("op", ["sort", "join", "sort_bins_past"])
def test_a_four_shard_shuffle_counts_by_compare_and_sum(fresh_ctx4, op, rng):
    rows = 4000
    a = ct.Table.from_pydict(
        fresh_ctx4,
        {"k": rng.integers(0, 1000, rows).astype(np.int64),
         "v": rng.normal(size=rows)},
    )
    reset_trace()
    if op == "join":
        b = ct.Table.from_pydict(
            fresh_ctx4,
            {"k": rng.integers(0, 1000, rows).astype(np.int64),
             "w": rng.normal(size=rows)},
        )
        out = a.distributed_join(b, on="k", how="inner")
        want = a.to_pandas().merge(b.to_pandas(), on="k")
        assert out.row_count == len(want)
    else:
        bins = B_MAX + 1 if op == "sort_bins_past" else 0
        out = a.distributed_sort("k", num_bins=bins)
        np.testing.assert_array_equal(
            out.to_pandas()["k"].to_numpy(),
            np.sort(a.to_pandas()["k"].to_numpy()),
        )
    rep = report("shuffle.bincount")
    assert rep["shuffle.bincount.dense"]["count"] >= 1
    # the caller's own num_bins is the one way onto the scatter side
    assert ("shuffle.bincount.scatter" in rep) == (op == "sort_bins_past")
