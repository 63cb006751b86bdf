"""Sort/segment-based groupby-aggregate kernels.

Reference analog: cpp/src/cylon/groupby/hash_groupby.cpp — ``make_groups``
builds dense group ids via a row-hash map (:92-126) then typed aggregate
kernels run per column (aggregate<op> templates, resolver ~:143-230); the
aggregate op set {SUM, COUNT, MIN, MAX, MEAN, VAR, STDDEV, NUNIQUE, QUANTILE}
comes from compute/aggregate_kernels.hpp:40-50.

TPU-native design, the sort-and-segment path (:func:`groupby_aggregate`):
one stable sort brings the rows into canonical key order
(:func:`ops.factorize.factorize_runs`: lexsort + run-detect, so the output
doubles as the sorted-key pipeline groupby, groupby/pipeline_groupby.cpp)
with the value columns riding it as payload operands (and any key column
that cannot be read back out of the fused sort words), and the group-by
never leaves that order: every aggregate is a segmented scan over
the runs of equal keys (:func:`ops.sort.run_reduce`; a run adds only its
own values, so a float sum is as exact as its group is small), and each
run's first row, which then holds the group's keys and totals, reaches
the group's slot by log-step moves (:func:`ops.sort.step_compact`: the
order is known and so are the targets, so no second sort finds them out).
There is no per-element scatter and no row-sized gather: on a v5e a
scatter-add costs 114 ns a row and a sort 1.3 ns (PERF.md section 6,
PR 28), and the moves a third to a half of the sort they replaced (PR 46).
Many columns ride the one sort in batches of eight 32-bit lanes
(:func:`ops.sort.ride_sort`), so the program holds the same few sorts,
and compiles in the same time, at 32 aggregates as at 16; the moves take
every lane as it is. Single dispatch: num_groups <= live
rows bounds the output statically, so one kernel + one host sync covers
count AND emit. Keys of few distinct values take the dense path below
instead (no sort at all).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import stages as _stages
from .factorize import factorize_runs
from .sort import (
    KeyCol, fit_slots, flatten_cols, fused_decodable, fused_key_decode,
    lexsort_indices, orderable_key, run_reduce, scan_identity, step_compact,
    unflatten_cols, wide_float, wide_int,
)
from .stats import decode_enc, wire_narrowable

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


def _masked(values: jax.Array, valid: Optional[jax.Array], fill) -> jax.Array:
    if valid is None:
        return values
    return jnp.where(valid, values, jnp.asarray(fill, values.dtype))


def groupby_aggregate(
    key_cols: Sequence[KeyCol],
    val_cols: Sequence[KeyCol],
    ops: Sequence[Tuple[int, int]],
    n: jax.Array,
    cap_out: int,
    fuse=None,
    presorted: bool = False,
    ddof: int = 1,
    quantile: float = 0.5,
    mask: Optional[jax.Array] = None,
):
    """The sort-and-segment group-by of one shard: ``ops`` are
    ``(aggregation op, position in val_cols)`` pairs.

    Returns (the groups' key columns, one ``(out, valid)`` an op, all
    [cap_out] with the groups in slots ``[0, num_groups)`` in canonical
    key order; num_groups scalar int32). Groups past ``cap_out`` are
    dropped. Null values are skipped (pandas semantics; count counts
    non-null).

    Everything happens in the SORTED order of the factorize sort
    (stage ``groupby.key_ids``): the key and value columns ride that sort
    as payload operands, each aggregate is a reduction over the runs of
    equal keys (:func:`ops.sort.run_reduce`), and a run's first row, which
    then holds the group's keys and totals, moves to the group's slot by
    the log-step compress (:func:`ops.sort.step_compact`; stage
    ``groupby.segment_sum``). One sort, no row-sized scatter or gather;
    ``presorted`` input runs no sort at all. A key the ``fuse`` plan's
    sort words hold bit for bit does not ride: the words go through the
    compress and the key is decoded at the slots.

    ``fuse``: stats-driven sort-word fusion plan for the factorize lanes
    (ops/sort.FusePlan; Table.groupby derives it from the key columns'
    range stats): the same runs in fewer chained sort passes.

    ``mask`` ([cap] bool; not with ``presorted``): a row whose mask is
    false counts in no aggregate and founds no group, as if filtered
    first. It rides the factorize sort as padding: no compaction in front."""
    key_cols, val_cols = list(key_cols), list(val_cols)
    cap = key_cols[0][0].shape[0]
    keep = None  # the rows that count, where they are not the first n
    if mask is not None:
        keep = (jnp.arange(cap, dtype=jnp.int32) < n) & mask
        n = jnp.sum(keep, dtype=jnp.int32)
    # a key the fused sort words hold bit for bit is read back out of them
    # at the groups' slots; any other rides the sort, and moves to the
    # slots, beside the values
    in_words = (
        fused_decodable(fuse, key_cols)
        if fuse is not None and not presorted else [False] * len(key_cols)
    )
    riders = [c for c, w in zip(key_cols, in_words) if not w]
    with jax.named_scope(_stages.GROUPBY_KEY_IDS):
        start, run_end, flat, words = factorize_runs(
            key_cols, n, cap, flatten_cols(riders + val_cols),
            fuse=fuse, presorted=presorted, keep=keep,
        )
    words = list(words) if any(in_words) else []
    cols = unflatten_cols(riders + val_cols, flat)
    with jax.named_scope(_stages.GROUPBY_SEGMENT_SUM):
        carried, aggs, num_groups = _aggregate_runs(
            start, run_end, n, words + flatten_cols(cols[: len(riders)]),
            cols[len(riders):], ops, cap_out, ddof, quantile,
        )
        gmask = jnp.arange(cap_out, dtype=jnp.int32) < num_groups
        # the decode wants the rows the words were made of, unsorted
        decoded = fused_key_decode(
            fuse, carried[: len(words)], key_cols,
            jnp.arange(cap, dtype=jnp.int32) < n if keep is None else keep,
        ) if words else []
        rode = iter(unflatten_cols(riders, carried[len(words):]))
        keys = [decoded[i] if w else next(rode) for i, w in enumerate(in_words)]
        keys = [(d, gmask if v is None else gmask & v) for d, v in keys]
    return keys, aggs, num_groups


def _pair_sort(gid, data, valid, cap):
    """The rows of NUNIQUE / QUANTILE's own order: by group, then nulls
    last, then value. A group keeps the rows ``[first, first + size)`` it
    has in the factorize order, so its runs and slots are the same.
    Returns (sorted group ids, sorted values, sorted validity | None)."""
    lanes = [data, gid] if valid is None else [data, ~valid, gid]
    order = lexsort_indices(lanes, cap)
    return gid[order], data[order], None if valid is None else valid[order]


def _aggregate_runs(
    start, run_end, n, carry, svals, ops, cap_out, ddof, quantile
):
    """The aggregates of :func:`groupby_aggregate` over rows in sorted
    order: ``start`` marks the live rows that open a run, whose ``carry``
    arrays go to the group's slot with its totals, by the moves of
    :func:`ops.sort.step_compact` and by no sort. Returns (carried
    arrays, ``(out, valid)`` an op, each [cap_out]; num_groups)."""
    cap = start.shape[0]
    # the per-row lanes the one scan reduces, by what they hold: ``sum``
    # and ``mean`` of one column share a lane
    lanes: dict = {}

    def lane(key, kind, vals):
        lanes.setdefault(key, (kind, vals))
        return key

    def fsum(j, tag, fn):
        data, valid = svals[j]
        x = _masked(data.astype(wide_float()), valid, 0.0)
        return lane((j, tag), "sum", fn(x))

    gid = None
    if any(op in (NUNIQUE, QUANTILE) for op, _j in ops):
        gid = jnp.where(
            jnp.arange(cap, dtype=jnp.int32) < n,
            jnp.cumsum(start.astype(jnp.int32)) - 1, jnp.int32(cap),
        )

    # what each op reads at a run's first row, then how a slot finishes it
    plans = []
    for op, j in ops:
        data, valid = svals[j]
        cnt = None
        if valid is not None:
            cnt = lane((j, "cnt"), "sum", valid.astype(jnp.int32))
        if op == COUNT:
            hs = ()
        elif op == SUM and data.dtype == wide_float():
            hs = (fsum(j, "fsum", lambda x: x),)  # the lane of its mean
        elif op == SUM:
            acc = (
                data.astype(wide_int())
                if jnp.issubdtype(data.dtype, jnp.integer) else data
            )
            hs = (lane((j, "sum"), "sum", _masked(acc, valid, 0)),)
        elif op in (MIN, MAX):
            kind = "min" if op == MIN else "max"
            fill = scan_identity(kind, data.dtype)
            hs = (lane((j, kind), kind, _masked(data, valid, fill)),)
        elif op == MEAN:
            hs = (fsum(j, "fsum", lambda x: x),)
        elif op in (VAR, STDDEV):
            hs = (fsum(j, "fsum", lambda x: x), fsum(j, "fsumsq", lambda x: x * x))
        elif op == NUNIQUE:
            # distinct (group, value) pairs: run-detect in the pair order
            d = data
            if jnp.issubdtype(d.dtype, jnp.floating):
                d = jnp.where(jnp.isnan(d), jnp.zeros_like(d), d)
            sid, sval, svalid = _pair_sort(gid, d, valid, cap)
            newpair = (
                (sid != jnp.roll(sid, 1)) | (sval != jnp.roll(sval, 1))
            ).at[0].set(True)
            if svalid is not None:
                newpair = newpair & svalid
            hs = (lane((j, "nunique"), "sum", newpair.astype(jnp.int32)),)
        elif op == QUANTILE:
            d = _masked(data.astype(wide_float()), valid, jnp.inf)
            hs = (_pair_sort(gid, d, None, cap)[1],)
        else:
            raise ValueError(f"unsupported aggregation op {op}")
        plans.append((op, valid, cnt, hs))

    reduced = run_reduce(
        run_end, [v for _k, v in lanes.values()], [k for k, _v in lanes.values()]
    )
    first, packed = step_compact(start, list(carry) + reduced)
    num_groups = jnp.sum(start, dtype=jnp.int32)
    # a group's rows lie between its first row and the next group's
    last = jnp.arange(cap, dtype=jnp.int32) == num_groups - 1
    size = fit_slots(jnp.where(last, n, jnp.roll(first, -1)) - first, cap_out)
    first = fit_slots(first, cap_out)
    packed = [fit_slots(x, cap_out) for x in packed]
    slot = dict(zip(lanes, packed[len(carry):]))
    gmask = jnp.arange(cap_out, dtype=jnp.int32) < num_groups

    out = []
    for op, valid, cnt_h, hs in plans:
        cnt = jnp.where(gmask, size if cnt_h is None else slot[cnt_h], 0)
        has = gmask & (cnt > 0)
        if op == COUNT:
            out.append((cnt.astype(wide_int()), None))
        elif op == SUM:
            s = slot[hs[0]]
            out.append((
                jnp.where(gmask, s, jnp.zeros_like(s)),
                has if valid is not None else None,
            ))
        elif op in (MIN, MAX):
            out.append((slot[hs[0]], has if valid is not None else None))
        elif op == MEAN:
            mean = slot[hs[0]] / jnp.maximum(cnt, 1)
            out.append((jnp.where(gmask, mean, 0.0), has))
        elif op in (VAR, STDDEV):
            s, ss = slot[hs[0]], slot[hs[1]]
            var = (ss - s * (s / jnp.maximum(cnt, 1))) / jnp.maximum(cnt - ddof, 1)
            var = jnp.maximum(var, 0.0)
            res = jnp.sqrt(var) if op == STDDEV else var
            out.append((jnp.where(gmask, res, 0.0), gmask & (cnt > ddof)))
        elif op == NUNIQUE:
            out.append((
                jnp.where(gmask, slot[hs[0]], 0).astype(wide_int()), None
            ))
        else:  # QUANTILE: interpolate between the group's neighbours
            sval = hs[0]
            pos = first.astype(wide_float()) + quantile * jnp.maximum(cnt - 1, 0)
            lo_i = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, cap - 1)
            hi_i = jnp.clip(jnp.ceil(pos).astype(jnp.int32), 0, cap - 1)
            frac = pos - jnp.floor(pos)
            q = sval[lo_i] * (1 - frac) + sval[hi_i] * frac
            out.append((jnp.where(has, q, 0.0), has))
    return packed[: len(carry)], out, num_groups


# ----------------------------------------------------------------------
# the partial state of the sort-and-segment path: what a shard reduces its
# own rows to before the rows of a group meet on one shard
# ----------------------------------------------------------------------
#: ops whose state over disjoint sets of rows combines group by group: a
#: sum as its sum, a count as its count, a minimum and a maximum as
#: themselves, a mean as its sum and its count (the reference pre-combines
#: {SUM, MIN, MAX} alone, groupby/groupby.cpp:24-31). var / std / nunique /
#: quantile have no such state here and see every row.
PARTIAL_OPS = frozenset({SUM, COUNT, MIN, MAX, MEAN})

#: how a state combines: the op that reduces the shards' partial rows
_STATE_COMBINE = {SUM: SUM, COUNT: SUM, MIN: MIN, MAX: MAX}


def partial_states(ops: Sequence[Tuple[int, int]], wide: Sequence[bool]):
    """The partial state of ``ops`` (``(op, value column)`` pairs, every op
    of :data:`PARTIAL_OPS`): ``(states, reads)``. ``states`` are the
    distinct ``(op, value column, widen)`` a shard reduces its rows to, op
    one of SUM, COUNT, MIN, MAX, each computed once however many ops read
    it; ``widen`` marks a mean's sum over a column narrower than
    ``wide_float`` (``wide[column]`` false), which adds in ``wide_float``
    as the local mean does. ``reads[i]`` are the positions in ``states``
    that ``ops[i]`` is finished from: (sum, count) for a mean, else one."""
    states: list = []

    def state(op, j, widen=False):
        if (op, j, widen) not in states:
            states.append((op, j, widen))
        return states.index((op, j, widen))

    reads = []
    for op, j in ops:
        if op == MEAN:
            reads.append((state(SUM, j, not wide[j]), state(COUNT, j)))
        elif op in _STATE_COMBINE:
            reads.append((state(op, j),))
        else:
            raise ValueError(f"aggregation op {op} has no partial state")
    return tuple(states), tuple(reads)


def widened(col: KeyCol) -> KeyCol:
    """A value column as the ``wide_float`` lane a mean adds it in."""
    data, valid = col
    return data.astype(wide_float()), valid


def combine_ops(states) -> list:
    """``(op, position)`` pairs that reduce the state columns of
    :func:`partial_states`, in their order, over a group's partial rows."""
    return [(_STATE_COMBINE[op], i) for i, (op, _j, _w) in enumerate(states)]


def finish_states(ops: Sequence[Tuple[int, int]], reads, combined):
    """The aggregates of ``ops`` from a group's ``combined`` state columns
    (``(out, valid)`` a state, as :func:`groupby_aggregate` reduced them),
    typed and null as :func:`_aggregate_runs` gives them: a mean is its
    sum over its count, null where the count is 0; any other op is its
    state."""
    out = []
    for (op, _j), read in zip(ops, reads):
        if op == MEAN:
            (total, _tv), (cnt, _cv) = combined[read[0]], combined[read[1]]
            mean = total.astype(wide_float()) / jnp.maximum(cnt, 1)
            out.append((mean, cnt > 0))
        else:
            out.append(combined[read[0]])
    return out


# ----------------------------------------------------------------------
# dense low-cardinality aggregation (no sort, no scatter, no gather)
# ----------------------------------------------------------------------
#: ops the dense path computes; var/std/nunique/quantile stay on the
#: sort-and-segment path (they sort or square the values)
DENSE_OPS = frozenset({SUM, COUNT, MIN, MAX, MEAN})

#: most slots (the product of the key columns' spans, a nullable key
#: taking one more) for which ``Table.groupby`` takes the dense path by
#: itself. Each aggregate is one masked reduction a slot, so the work a
#: row grows with the slots, while the sort-and-segment path's (a sort, a
#: scan and the moves to the slots) does not. Measured on a v5e chip at
#: 16,777,216 rows (PERF.md section 6, PR 27): Q1's eight aggregates take 16.9 / 39.7 /
#: 129.8 / 497.6 ms at 4 / 64 / 256 / 1,024 slots, one float64 sum 5.1 /
#: 9.8 / 110.4 ms at 4 / 64 / 1,024, linear in the slots from 64 on. The
#: constant was set against the other path's scatter form (14.5 s and
#: 2.7 s there, whatever the groups); that path is some thirty times
#: faster since PR 28, and PERF.md section 6 (PR 28) has what is known of
#: where the two cross now.
DENSE_MAX_SLOTS = 1024


def dense_span(stat) -> Optional[int]:
    """Slots a key column with range ``stat`` (``ops.stats.ColStat``)
    takes on the dense path: its span rounded up to a power of two, so
    that a drifting range compiles nothing; None where there is no stat
    or the key's encoding is not an integer's (a float key)."""
    if stat is None or not wire_narrowable(stat.cls):
        return None
    return 1 << max(0, stat.hi - stat.lo).bit_length()


def dense_slots(spans: Sequence[int], nullable: Sequence[bool]) -> int:
    """Slots of the dense id space: the product of the spans, a nullable
    key taking one more slot (its null, last)."""
    total = 1
    for span, null in zip(spans, nullable):
        total *= span + (1 if null else 0)
    return total


def dense_group_ids(
    key_cols: Sequence[KeyCol], los, spans: Sequence[int], n: jax.Array,
    mask: Optional[jax.Array], cap: Optional[int] = None,
) -> jax.Array:
    """Slot of every row, [cap] int32: arithmetic on the rebased keys,
    first key most significant, a null key in its column's last slot (the
    canonical order of :func:`ops.factorize.factorize_runs`). Padding rows and rows whose
    ``mask`` is false get the slot count, which no reduction matches.

    ``los`` are the lower bounds of the keys' orderable encodings (traced
    scalars: a drifting range compiles nothing), ``spans`` the static
    widths that hold every live value. With no key at all every kept row
    has slot 0 of one (an aggregate over the whole table), and ``cap``
    says how many rows a shard holds."""
    with jax.named_scope(_stages.GROUPBY_KEY_IDS):
        if key_cols:
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


def dense_partial(
    op: int, data: jax.Array, valid: Optional[jax.Array], gid: jax.Array,
    slots: int,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """One shard's partial state of an aggregate of :data:`DENSE_OPS`:
    ``(cnt, acc)``, the values that count a slot ([slots] int32; nulls are
    skipped) and their masked sum, minimum or maximum a slot (None for
    COUNT; MEAN keeps its sum and divides in :func:`dense_finalize`), in
    the dtypes of :func:`groupby_aggregate` (a float sum stays in the
    column's dtype, an integer sum widens). The partials of disjoint sets
    of rows combine slot by slot (:func:`dense_combine`)."""
    with jax.named_scope(_stages.GROUPBY_DENSE_AGG):
        onehot = gid[None, :] == jnp.arange(slots, dtype=jnp.int32)[:, None]
        if valid is not None:
            onehot = onehot & valid[None, :]
        # at most cap < 2**31 rows a shard: an int32 count is exact
        cnt = jnp.sum(onehot, axis=1, dtype=jnp.int32)
        if op == COUNT:
            return cnt, None

        def reduce(x, fill, fn):
            return fn(jnp.where(onehot, x[None, :], fill), axis=1)

        if op == SUM:
            acc = (
                data.astype(wide_int())
                if jnp.issubdtype(data.dtype, jnp.integer) else data
            )
            return cnt, reduce(acc, jnp.zeros((), acc.dtype), jnp.sum)
        if op in (MIN, MAX):
            kind, fn = ("min", jnp.min) if op == MIN else ("max", jnp.max)
            return cnt, reduce(data, scan_identity(kind, data.dtype), fn)
        if op == MEAN:
            x = data.astype(wide_float())
            return cnt, reduce(x, jnp.zeros((), x.dtype), jnp.sum)
    raise ValueError(f"aggregation op {op} has no dense form")


def dense_finalize(
    op: int, cnt: jax.Array, acc: Optional[jax.Array], may_be_empty: bool,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """An aggregate's ``(out [slots], valid [slots] | None)`` from its
    (combined) partial state. ``may_be_empty``: a slot that has rows may
    still hold no value (the column is nullable, or there are no keys and
    so the one slot is emitted whatever it holds); the result is then null
    there, as SQL has it, and a count 0. A slot with no row at all is
    dropped by the caller."""
    with jax.named_scope(_stages.GROUPBY_DENSE_AGG):
        if op == COUNT:
            return cnt.astype(wide_int()), None
        if op == MEAN:
            return acc / jnp.maximum(cnt, 1), cnt > 0
        return acc, (cnt > 0) if may_be_empty else None


def dense_aggregate(
    op: int, data: jax.Array, valid: Optional[jax.Array], gid: jax.Array,
    slots: int, may_be_empty: Optional[bool] = None,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """One aggregate of :data:`DENSE_OPS` over the slots of one shard that
    holds every row: a masked reduction a slot (:func:`dense_partial`)
    and its closing arithmetic (:func:`dense_finalize`), with the null
    rules of :func:`groupby_aggregate` (nulls skipped, count counts
    non-null). Returns (out [slots], valid [slots] | None)."""
    if may_be_empty is None:
        may_be_empty = valid is not None
    return dense_finalize(
        op, *dense_partial(op, data, valid, gid, slots), may_be_empty
    )


#: how an op's accumulator combines (every other: a sum)
_COMBINE = {MIN: jnp.minimum, MAX: jnp.maximum}


def _fold(parts: jax.Array, fn) -> jax.Array:
    """``parts`` [world, ...] folded along the first axis in shard order:
    ((p0 . p1) . p2) ..., the same on every shard and in every run."""
    acc = parts[0]
    for p in range(1, parts.shape[0]):
        acc = fn(acc, parts[p])
    return acc


def dense_combine(rows: jax.Array, parts, axis_name: str):
    """The shards' partial slot tables combined over the mesh axis: ``rows``
    ([slots] int32) and ``parts`` (``(op, cnt, acc)`` an aggregate, as
    :func:`dense_partial` gives them) in, the same structure out, every
    shard holding the whole table's. Stage ``groupby.combine``.

    Counts and integer sums are exact in any order and take one ``psum``
    each dtype. A float sum is NOT: the shards' partials are gathered
    (``all_gather`` moves a float64 as the 32-bit halves the chip holds it
    in, and adds nothing) and added in shard order on every shard, in the
    arithmetic the reductions themselves use, so a query gives the same
    bits run to run and the mesh's reduction order is nobody's choice.
    Minima and maxima ride the same gather: the TPU compiler lowers a
    64-bit all-reduce for sums only (``ops/partition.py``). The lanes of
    one dtype share a collective: Q1's nine lanes of six slots are one
    psum and one all_gather."""
    with jax.named_scope(_stages.GROUPBY_COMBINE):
        lanes = [(rows, jnp.add)]
        for op, cnt, acc in parts:
            lanes.append((cnt, jnp.add))
            if acc is not None:
                lanes.append((acc, _COMBINE.get(op, jnp.add)))
        groups = {}  # (dtype, summed exactly) -> the lanes' positions
        for i, (x, fn) in enumerate(lanes):
            exact = fn is jnp.add and jnp.issubdtype(x.dtype, jnp.integer)
            groups.setdefault((x.dtype, exact), []).append(i)
        out = [None] * len(lanes)
        for (_dtype, exact), idx in groups.items():
            stacked = jnp.stack([lanes[i][0] for i in idx])
            if exact:
                total = jax.lax.psum(stacked, axis_name)
                for k, i in enumerate(idx):
                    out[i] = total[k]
            else:
                every = jax.lax.all_gather(stacked, axis_name)
                for k, i in enumerate(idx):  # [world, lanes, slots]
                    out[i] = _fold(every[:, k], lanes[i][1])
        it = iter(out)
        rows = next(it)
        return rows, [
            (op, next(it), None if acc is None else next(it))
            for op, _cnt, acc in parts
        ]


def dense_emit(
    rows: jax.Array, aggs, key_meta, los, spans: Sequence[int],
    nullable: Sequence[bool], cap_out: int,
):
    """Drop the slots with no row and decode the keys of the rest from
    their slot: ``(key columns + aggregate columns, each [cap_out], number
    of groups)``, groups in canonical key order. ``key_meta`` holds each
    key's ``(enc class, dtype)``; the handful of slots is reordered by a
    stable argsort of their emptiness. With no key the one slot is a row
    whatever it holds: an aggregate over no rows is still one row."""
    with jax.named_scope(_stages.GROUPBY_DENSE_AGG):
        slots = rows.shape[0]
        present = (rows > 0) if key_meta else jnp.ones_like(rows, jnp.bool_)
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
