"""Sort/segment-based groupby-aggregate kernels.

Reference analog: cpp/src/cylon/groupby/hash_groupby.cpp — ``make_groups``
builds dense group ids via a row-hash map (:92-126) then typed aggregate
kernels run per column (aggregate<op> templates, resolver ~:143-230); the
aggregate op set {SUM, COUNT, MIN, MAX, MEAN, VAR, STDDEV, NUNIQUE, QUANTILE}
comes from compute/aggregate_kernels.hpp:40-50.

TPU-native design: group ids come from :func:`factorize` (lexsort +
run-detect — ids are dense AND in sorted key order, so the output doubles as
the sorted-key pipeline groupby, groupby/pipeline_groupby.cpp); aggregates are
XLA ``segment_sum/min/max`` ops, which lower to efficient sorted-segment
reductions. Single dispatch: num_groups <= live rows bounds the output
statically, so one kernel + one host sync covers count AND emit.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import stages as _stages
from .factorize import factorize
from .sort import (
    KeyCol, lexsort_indices, orderable_key, rows_differ, wide_float, wide_int,
)
from .stats import decode_enc

# aggregation op ids, mirroring reference AggregationOpId
# (compute/aggregate_kernels.hpp:40-50)
SUM, COUNT, MIN, MAX, MEAN, VAR, STDDEV, NUNIQUE, QUANTILE, COUNT_DISTINCT = range(10)

_AGG_NAMES = {
    "sum": SUM, "count": COUNT, "min": MIN, "max": MAX, "mean": MEAN,
    "avg": MEAN, "var": VAR, "std": STDDEV, "stddev": STDDEV,
    "nunique": NUNIQUE, "quantile": QUANTILE, "median": QUANTILE,
    "count_distinct": NUNIQUE, "size": COUNT,
}


def agg_op_id(name) -> int:
    if isinstance(name, int):
        return name
    try:
        return _AGG_NAMES[name.lower()]
    except KeyError:
        raise ValueError(f"unknown aggregation {name!r}") from None


def group_ids(
    key_cols: Sequence[KeyCol], n: jax.Array, cap: int, fuse=None
) -> Tuple[jax.Array, jax.Array]:
    """(ids [cap] int32 with padding -> cap, num_groups scalar).

    ``fuse``: stats-driven sort-word fusion plan for the factorize lanes
    (ops/sort.FusePlan; Table.groupby derives it from the key columns'
    range stats) — identical ids in fewer chained sort passes."""
    return factorize(key_cols, n, cap, fuse=fuse)


def sorted_group_ids(
    key_cols: Sequence[KeyCol], n: jax.Array, cap: int
) -> Tuple[jax.Array, jax.Array]:
    """Group ids for input ALREADY sorted by the key columns: a single
    run-detection pass, no lexsort (reference PipelineGroupBy,
    groupby/pipeline_groupby.cpp:30-90 — run detection + per-run aggregates
    over sorted input). Same contract as :func:`group_ids`, and the ids come
    out in key order by construction.

    Callers either guarantee sortedness themselves (``pipeline_groupby``,
    the reference contract) or let ``Table.groupby`` prove it from the
    table's ordering descriptor (cylon_tpu/ordering.py): input canonically
    ordered by a key prefix run-detects with null==null adjacency intact,
    so the ids — and therefore the emitted group order — match the
    factorize path exactly."""
    idx = jnp.arange(cap, dtype=jnp.int32)
    live = idx < n
    boundary = rows_differ(key_cols, cap) & live
    ids = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    ids = jnp.where(live, ids, jnp.int32(cap))
    return ids.astype(jnp.int32), jnp.sum(boundary).astype(jnp.int32)


def group_representatives(ids: jax.Array, cap_out: int) -> jax.Array:
    """First-occurrence row index of each group id -> [cap_out] int32.

    Entries for ids >= cap_out are dropped; absent groups get cap (clamp on
    gather + group count masking makes that safe).
    """
    cap = ids.shape[0]
    rows = jnp.arange(cap, dtype=jnp.int32)
    rep = jnp.full((cap_out,), cap, jnp.int32)
    # min row index per id == first occurrence
    return rep.at[ids].min(rows, mode="drop")


def _masked(values: jax.Array, valid: Optional[jax.Array], fill) -> jax.Array:
    if valid is None:
        return values
    return jnp.where(valid, values, jnp.asarray(fill, values.dtype))


def _seg_sum(vals, ids, cap_out):
    with jax.named_scope(_stages.GROUPBY_SEGMENT_SUM):
        return jnp.zeros((cap_out,), vals.dtype).at[ids].add(vals, mode="drop")


def _seg_min(vals, ids, cap_out, init):
    with jax.named_scope(_stages.GROUPBY_SEGMENT_SUM):
        return jnp.full((cap_out,), init, vals.dtype).at[ids].min(
            vals, mode="drop"
        )


def _seg_max(vals, ids, cap_out, init):
    with jax.named_scope(_stages.GROUPBY_SEGMENT_SUM):
        return jnp.full((cap_out,), init, vals.dtype).at[ids].max(
            vals, mode="drop"
        )


def _type_extrema(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype), jnp.array(-jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max, dtype), jnp.asarray(info.min, dtype)


def aggregate_column(
    op: int,
    data: jax.Array,
    valid: Optional[jax.Array],
    ids: jax.Array,
    num_groups: jax.Array,
    cap_out: int,
    ddof: int = 1,
    quantile: float = 0.5,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Aggregate one value column over group ids. Null entries are skipped
    (pandas semantics; count counts non-null). Returns (out [cap_out], valid).
    """
    vmask = valid if valid is not None else jnp.ones(data.shape, bool)
    # padding rows already have ids == cap (dropped by mode="drop" scatters
    # when cap >= cap_out; make sure by re-masking)
    live_ids = jnp.where(vmask, ids, jnp.int32(data.shape[0]))
    cnt = _seg_sum(vmask.astype(wide_int()), live_ids, cap_out)
    gmask = jnp.arange(cap_out) < num_groups
    if op == COUNT:
        return jnp.where(gmask, cnt, 0), None
    if op == SUM:
        acc = data.astype(wide_int()) if jnp.issubdtype(data.dtype, jnp.integer) else data
        s = _seg_sum(_masked(acc, vmask, 0), live_ids, cap_out)
        return jnp.where(gmask, s, jnp.zeros_like(s)), gmask & (cnt > 0) if valid is not None else None
    if op in (MIN, MAX):
        hi, lo = _type_extrema(data.dtype)
        if op == MIN:
            out = _seg_min(_masked(data, vmask, hi), live_ids, cap_out, hi)
        else:
            out = _seg_max(_masked(data, vmask, lo), live_ids, cap_out, lo)
        has = gmask & (cnt > 0)
        return out, (has if valid is not None else None)
    if op == MEAN:
        s = _seg_sum(_masked(data.astype(wide_float()), vmask, 0.0), live_ids, cap_out)
        out = s / jnp.maximum(cnt, 1)
        return jnp.where(gmask, out, 0.0), gmask & (cnt > 0)
    if op in (VAR, STDDEV):
        x = _masked(data.astype(wide_float()), vmask, 0.0)
        s = _seg_sum(x, live_ids, cap_out)
        ss = _seg_sum(x * x, live_ids, cap_out)
        denom = jnp.maximum(cnt - ddof, 1)
        mean = s / jnp.maximum(cnt, 1)
        var = (ss - s * mean) / denom
        var = jnp.maximum(var, 0.0)
        out = jnp.sqrt(var) if op == STDDEV else var
        return jnp.where(gmask, out, 0.0), gmask & (cnt > ddof)
    if op == NUNIQUE:
        # distinct (id, value) pairs: lexsort by (id, value), run-detect
        cap = data.shape[0]
        d = data
        if jnp.issubdtype(d.dtype, jnp.floating):
            d = jnp.where(jnp.isnan(d), jnp.zeros_like(d), d)
        order = lexsort_indices([d, live_ids], cap)
        sid = live_ids[order]
        sval = d[order]
        newpair = (
            (sid != jnp.roll(sid, 1)) | (sval != jnp.roll(sval, 1))
        ).at[0].set(True)
        uniq = _seg_sum(newpair.astype(wide_int()), sid, cap_out)
        return jnp.where(gmask, uniq, 0), None
    if op == QUANTILE:
        cap = data.shape[0]
        d = _masked(data.astype(wide_float()), vmask, jnp.inf)
        order = lexsort_indices([d, live_ids], cap)
        sid = live_ids[order]
        sval = d[order]
        # method='sort': the default 'scan' binary search is ~8x slower on TPU
        starts = jnp.searchsorted(
            sid, jnp.arange(cap_out), side="left", method="sort"
        ).astype(jnp.int32)
        q = quantile
        pos = starts.astype(wide_float()) + q * jnp.maximum(cnt - 1, 0)
        lo_i = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, cap - 1)
        hi_i = jnp.clip(jnp.ceil(pos).astype(jnp.int32), 0, cap - 1)
        frac = pos - jnp.floor(pos)
        out = sval[lo_i] * (1 - frac) + sval[hi_i] * frac
        has = gmask & (cnt > 0)
        return jnp.where(has, out, 0.0), has
    raise ValueError(f"unsupported aggregation op {op}")


# ops that can be pre-combined locally before the shuffle (reference
# ASSOCIATIVE_OPS = {SUM, MIN, MAX}, groupby/groupby.cpp:24-31; COUNT combines
# as SUM of partial counts)
ASSOCIATIVE = frozenset({SUM, MIN, MAX})


# ----------------------------------------------------------------------
# dense low-cardinality aggregation (no sort, no scatter, no gather)
# ----------------------------------------------------------------------
#: ops the dense path computes; var/std/nunique/quantile stay on the
#: factorize path (they sort or square the values)
DENSE_OPS = frozenset({SUM, COUNT, MIN, MAX, MEAN})

#: most slots (the product of the key columns' spans, a nullable key
#: taking one more) for which ``Table.groupby`` takes the dense path by
#: itself. Each aggregate is one masked reduction a slot, so the work a
#: row grows with the slots, while the factorize path's (a sort, and a
#: per-element scatter an aggregate) does not. Measured on a v5e chip at
#: 16,777,216 rows (PERF.md section 6, PR 27): Q1's eight aggregates take
#: 16.9 / 39.7 / 129.8 / 497.6 ms at 4 / 64 / 256 / 1,024 slots against
#: 14,539 ms on the factorize path at 4 and at 1,024 groups; one float64
#: sum 5.1 / 9.8 / 110.4 ms at 4 / 64 / 1,024 against 2,657 ms. Both are
#: linear in the slots from 64 on, so the paths would cross near 25,000.
#: 1,024 is the largest count that was measured: 24 to 29 times ahead.
DENSE_MAX_SLOTS = 1024


def dense_slots(spans: Sequence[int], nullable: Sequence[bool]) -> int:
    """Slots of the dense id space: the product of the spans, a nullable
    key taking one more slot (its null, last)."""
    total = 1
    for span, null in zip(spans, nullable):
        total *= span + (1 if null else 0)
    return total


def dense_group_ids(
    key_cols: Sequence[KeyCol], los, spans: Sequence[int], n: jax.Array,
    mask: Optional[jax.Array],
) -> jax.Array:
    """Slot of every row, [cap] int32: arithmetic on the rebased keys,
    first key most significant, a null key in its column's last slot (the
    canonical order of :func:`factorize`). Padding rows and rows whose
    ``mask`` is false get the slot count, which no reduction matches.

    ``los`` are the lower bounds of the keys' orderable encodings (traced
    scalars: a drifting range compiles nothing), ``spans`` the static
    widths that hold every live value."""
    with jax.named_scope(_stages.GROUPBY_KEY_IDS):
        cap = key_cols[0][0].shape[0]
        keep = jnp.arange(cap, dtype=jnp.int32) < n
        if mask is not None:
            keep = keep & mask
        gid = jnp.zeros((cap,), jnp.int32)
        for (data, valid), lo, span in zip(key_cols, los, spans):
            code = (orderable_key(data) - lo).astype(jnp.int32)
            slots = span
            if valid is not None:
                code = jnp.where(valid, code, jnp.int32(span))
                slots = span + 1
            gid = gid * jnp.int32(slots) + code
        total = dense_slots(spans, [v is not None for _d, v in key_cols])
        return jnp.where(keep, gid, jnp.int32(total))


def dense_rows(gid: jax.Array, slots: int) -> jax.Array:
    """Rows of each slot, [slots] int32 (a slot with none is no group)."""
    with jax.named_scope(_stages.GROUPBY_DENSE_AGG):
        onehot = gid[None, :] == jnp.arange(slots, dtype=jnp.int32)[:, None]
        return jnp.sum(onehot, axis=1, dtype=jnp.int32)


def dense_aggregate(
    op: int, data: jax.Array, valid: Optional[jax.Array], gid: jax.Array,
    slots: int,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """One aggregate of :data:`DENSE_OPS` over the slots: a masked
    reduction a slot, in the dtypes and with the null rules of
    :func:`aggregate_column` (nulls skipped, count counts non-null, a
    float sum stays in the column's dtype, an integer sum widens).
    Returns (out [slots], valid [slots] | None); a slot with no row is
    dropped by the caller."""
    with jax.named_scope(_stages.GROUPBY_DENSE_AGG):
        onehot = gid[None, :] == jnp.arange(slots, dtype=jnp.int32)[:, None]
        if valid is not None:
            onehot = onehot & valid[None, :]
        # at most cap < 2**31 rows a shard: an int32 count is exact
        cnt = jnp.sum(onehot, axis=1, dtype=jnp.int32)
        has = (cnt > 0) if valid is not None else None
        if op == COUNT:
            return cnt.astype(wide_int()), None

        def reduce(x, fill, fn):
            return fn(jnp.where(onehot, x[None, :], fill), axis=1)

        if op == SUM:
            acc = (
                data.astype(wide_int())
                if jnp.issubdtype(data.dtype, jnp.integer) else data
            )
            return reduce(acc, jnp.zeros((), acc.dtype), jnp.sum), has
        if op in (MIN, MAX):
            hi, lo = _type_extrema(data.dtype)
            if op == MIN:
                return reduce(data, hi, jnp.min), has
            return reduce(data, lo, jnp.max), has
        if op == MEAN:
            x = data.astype(wide_float())
            s = reduce(x, jnp.zeros((), x.dtype), jnp.sum)
            return s / jnp.maximum(cnt, 1), cnt > 0
    raise ValueError(f"aggregation op {op} has no dense form")


def dense_emit(
    rows: jax.Array, aggs, key_meta, los, spans: Sequence[int],
    nullable: Sequence[bool], cap_out: int,
):
    """Drop the slots with no row and decode the keys of the rest from
    their slot: ``(key columns + aggregate columns, each [cap_out], number
    of groups)``, groups in canonical key order. ``key_meta`` holds each
    key's ``(enc class, dtype)``; the handful of slots is reordered by a
    stable argsort of their emptiness."""
    with jax.named_scope(_stages.GROUPBY_DENSE_AGG):
        slots = rows.shape[0]
        present = rows > 0
        ng = jnp.sum(present, dtype=jnp.int32)
        order = jnp.argsort(~present, stable=True).astype(jnp.int32)
        order = jnp.pad(order, (0, cap_out - slots))
        gmask = jnp.arange(cap_out, dtype=jnp.int32) < ng
        rest = order
        keys = []
        for (cls, dtype), lo, span, null in reversed(
            list(zip(key_meta, los, spans, nullable))
        ):
            width = span + (1 if null else 0)
            code = rest % jnp.int32(width)
            rest = rest // jnp.int32(width)
            data = decode_enc(lo + code.astype(lo.dtype), cls, dtype)
            keys.append((data, gmask & (code < span)))
        out = keys[::-1]
        for a, av in aggs:
            out.append((a[order], None if av is None else gmask & av[order]))
        return out, ng
