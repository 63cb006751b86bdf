"""Partition-id assignment: hash partitioning and sample-sort range partitioning.

Reference analogs:
- hash partition kernels (cpp/src/cylon/arrow/arrow_partition_kernels.cpp:
  67-330): per-row murmur3 / pseudo-hash -> ``hash % num_partitions`` with a
  power-of-2 fast path (:51-61). Here the hash is the vectorized murmur3 of
  ops/hash.py and the modulo is one XLA op over the whole column.
- range partition kernel (:332-455): sample ``num_samples`` values, global
  min/max, build a ``num_bins`` histogram, **AllReduce the bin counts**
  (:406-416 — MPI_Allreduce there, ``lax.psum`` here), then split bins into
  equal-weight partitions (:418-440). Here every row is binned (no sample)
  and the histogram is :func:`bin_counts`: a compare against the bin ids
  and a sum over the rows, not a scatter-add of every row.

``axis_name=None`` runs the same code single-shard (local mode) — the psum
becomes a no-op, mirroring the reference's LOCAL short-circuit
(compute/aggregate_utils.hpp:48-51).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import bump
from .hash import hash_columns
from .sort import KeyCol, wide_float, wide_int

#: widest histogram :func:`bin_counts` takes as a compare and a sum; past it
#: the rows are scatter-added. One v5e chip, 2^20 int32 ids, device time a
#: call (PR 38): the dense pass 0.013 / 0.037 / 0.59 / 0.69 / 2.79 / 12.81 ms
#: at 4 / 64 / 256 / 1,024 / 4,096 / 16,384 bins; the int32 scatter-add 9.16
#: ms up to 1,024 bins and 6.96 from 4,096, whatever the ids (all in one bin
#: the same); the int64 one 62.5. The two meet near 10,000 bins: the bound is
#: the widest measured count at which the dense pass still wins.
DENSE_BINS_MAX = 4096


def bin_counts(idx: jax.Array, num_bins: int) -> jax.Array:
    """Rows per bin -> ``[num_bins]`` int32: ``counts[j] = sum_i (idx[i] ==
    j)``. Ids outside ``[0, num_bins)`` are dropped (the shuffle's padding
    sentinel ``P``, the range partitioner's out-of-range bin ``num_bins``).

    Up to :data:`DENSE_BINS_MAX` bins (a static Python int, so the choice is
    made where the kernel is traced) the count is a compare against the bin
    ids and a sum over the rows: one elementwise pass that makes no
    ``[rows, bins]`` array, where a scatter-add serialises on the row. Past
    it the compare costs more than the scatter and the rows are
    scatter-added. Either way the sum is taken in int32 (a shard's capacity
    is under 2^31): under x64 an int64 histogram compiles for a TPU to a
    variadic scatter-add over its two 32-bit halves. The rollup counters
    ``shuffle.bincount.dense`` / ``.scatter`` (``rows=`` the bins) say
    which form a run's programs hold.
    """
    if num_bins <= DENSE_BINS_MAX:
        bump("shuffle.bincount.dense", rows=num_bins)
        bins = jnp.arange(num_bins, dtype=idx.dtype)
        return jnp.sum(idx[:, None] == bins[None, :], axis=0, dtype=jnp.int32)
    bump("shuffle.bincount.scatter", rows=num_bins)
    # a negative index would wrap to the far end before "drop" is applied
    idx = jnp.where(idx < 0, num_bins, idx)
    return jnp.zeros((num_bins,), jnp.int32).at[idx].add(1, mode="drop")


def hash_partition_ids(
    key_cols: Sequence[KeyCol], n: jax.Array, num_partitions: int,
    hash_shift: int = 0,
) -> jax.Array:
    """Target partition per row (uint32 hash mod P); padding rows -> P.

    ``hash_shift`` consumes DIFFERENT hash bits (h >> shift) so that two
    nested partitionings of the same keys stay independent: the out-of-core
    join buckets on the high bits (shift=16) precisely because each
    bucket-pair join re-partitions on the low bits for its mesh shuffle —
    with the same bits, every row of bucket b would land on shard
    b mod world and the "distributed" bucket join would degenerate to one
    device (observed: 16384-cap output shards from 512-cap inputs)."""
    h = hash_columns(key_cols)
    if hash_shift:
        h = h >> np.uint32(hash_shift)
    cap = h.shape[0]
    if num_partitions & (num_partitions - 1) == 0:
        pid = (h & np.uint32(num_partitions - 1)).astype(jnp.int32)
    else:
        pid = (h % np.uint32(num_partitions)).astype(jnp.int32)
    live = jnp.arange(cap, dtype=jnp.int32) < n
    return jnp.where(live, pid, num_partitions)


def _as_float(data: jax.Array) -> jax.Array:
    if jnp.issubdtype(data.dtype, jnp.floating):
        return jnp.where(jnp.isnan(data), jnp.zeros_like(data), data).astype(wide_float())
    return data.astype(wide_float())


def range_partition_ids(
    key: KeyCol,
    n: jax.Array,
    num_partitions: int,
    num_bins: Optional[int] = None,
    axis_name: Optional[str] = None,
    ascending: bool = True,
) -> jax.Array:
    """Sample-sort range partitioning on a single key column.

    Partition boundaries are chosen so partitions receive ~equal global row
    counts and partition i holds keys <= partition i+1's keys (ascending), so
    a post-shuffle local sort yields a globally sorted table.

    Every row is binned into ``num_bins`` equal-width bins between the
    global extrema; the local histogram is :func:`bin_counts` (int32: a
    shard holds under 2^31 rows), widened only in front of the all-reduce
    (the global total may pass 2^31), and whole bins go to partitions by
    equal cumulative weight.

    Default num_bins mirrors the reference: 16 * num_partitions
    (partition/partition.cpp:182). Nulls and padding go to the last partition
    (nulls-last sort order).
    """
    data, valid = key
    cap = data.shape[0]
    if num_bins is None:
        num_bins = 16 * num_partitions
    x = _as_float(data)
    live = jnp.arange(cap, dtype=jnp.int32) < n
    ok = live if valid is None else (live & valid)
    # sentinel must dominate the key dtype's full range: finfo of the WIDE
    # float (f64-max under x64), not f32-max, or f64 keys above 3.4e38 would
    # break the min/max and collapse every row into one partition
    big = jnp.asarray(np.finfo(np.dtype(wide_float())).max, wide_float())
    lo = jnp.min(jnp.where(ok, x, big))
    hi = jnp.max(jnp.where(ok, x, -big))
    if axis_name is not None:
        # one all_gather of the per-shard extrema, reduced locally, instead
        # of pmin + pmax: lo/hi are float64 under x64, and the TPU compiler
        # lowers a 64-bit all-reduce only for sums ("UNIMPLEMENTED:
        # Supported lowering only of Sum all reduce"). Same values, one
        # collective fewer.
        ends = jax.lax.all_gather(jnp.stack([lo, hi]), axis_name)  # [P, 2]
        lo, hi = jnp.min(ends[:, 0]), jnp.max(ends[:, 1])
    span = jnp.maximum(hi - lo, 1e-300)
    # local histogram over num_bins equal-width bins
    b = jnp.clip(((x - lo) / span * num_bins).astype(jnp.int32), 0, num_bins - 1)
    b = jnp.where(ok, b, num_bins)  # nulls+padding counted out of range
    hist = bin_counts(b, num_bins).astype(wide_int())
    if axis_name is not None:
        hist = jax.lax.psum(hist, axis_name)  # reference MPI_Allreduce :410
    total = jnp.sum(hist)
    # bin -> partition: equal cumulative weight split (reference
    # build_bin_to_partition :418-440), by the rows in front of the bin's
    # MIDDLE, so a bin goes where most of it lies. By the rows in front of
    # the bin (the reference's rule) evenly filled bins put the bin at a
    # boundary a few rows short of it or past it by chance, and a whole bin
    # (1/16 of a partition) goes to one side: enough to double the
    # power-of-two capacities downstream (PERF.md section 6, PR 31). Still
    # monotone in the bin, so partitions stay ordered.
    cum = jnp.cumsum(hist) - hist  # exclusive
    mid = cum.astype(wide_float()) + hist.astype(wide_float()) / 2
    per_part = jnp.maximum(total.astype(wide_float()) / num_partitions, 1.0)
    bin_to_part = jnp.clip(
        (mid / per_part).astype(jnp.int32), 0, num_partitions - 1
    )
    pid = bin_to_part[jnp.clip(b, 0, num_bins - 1)]
    if not ascending:
        pid = num_partitions - 1 - pid
    # nulls -> last partition; padding -> P sentinel
    pid = jnp.where(ok, pid, num_partitions - 1)
    return jnp.where(live, pid, num_partitions).astype(jnp.int32)
