"""Fingerprint-batched execution: B parameter bindings, ONE device program.

The compile-once/serve-many substrate (plan-fingerprint executable cache,
Scan-stub detachment) makes same-shape plans over different tables share
one executor — but a serial loop still pays the per-dispatch Python cost
of the whole lowered op chain once PER QUERY, and at serving sizes that
overhead dominates. This module removes it with the classic
key-augmentation trick, done at the PLAN level so the whole optimizer
(fused q3 pushdown, shuffle elimination, semi filters, pruning) applies
to the batch exactly as it applies to one query:

1. ``stack_tables``: one sync-free kernel concatenates the B bindings of
   each Scan ordinal into a single front-packed table and stamps a
   binding-id column (``__cylon_qid``) per row. Deferred input counts
   ride in as device operands — stacking performs ZERO host syncs.
2. ``build_batched_template``: rewrite the logical plan so the qid rides
   every data-dependent boundary — prepended to join keys on both sides,
   to groupby keys, and to sort keys — which makes the batch semantically
   B disjoint queries inside one program (rows of different bindings can
   never join, group, or dedup together).
3. ``split_batch``: every binding's slice (a compact-mask over its qid
   plus a packed gather, projected back to the original output schema)
   from ONE fused kernel dispatch.

Batchability is a conservative whitelist (Scan / Filter / Project / Join
except full-outer / GroupBy / Sort / Union); anything else — and any
schema already using the reserved qid name — falls back to per-query
async execution in the scheduler. Full-outer joins are excluded because
neither side's qid survives non-null on every row; Limit because "first
n rows" is a per-query global the stacked program cannot express.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..column import Column
from ..dtypes import DataType, Type
from ..engine import get_kernel, round_cap
from ..fault.errors import CylonError
from ..plan.nodes import (
    Filter,
    GroupBy,
    Join,
    Node,
    Project,
    Scan,
    Sort,
    Union,
)
from ..table import Table
from ..utils.tracing import span

#: the reserved binding-id column name (schemas using it are unbatchable)
QID = "__cylon_qid"


class Unbatchable(CylonError):
    """This plan shape cannot ride the stacked batch program.

    Re-parented onto the typed taxonomy (cylon_tpu/fault): scope =
    "query" — the shape simply executes per-binding instead; nothing is
    poisoned. Internal control flow (``is_batchable`` catches it), never
    surfaced to a future."""


# ----------------------------------------------------------------------
# plan rewrite: thread the binding id through every relational boundary
# ----------------------------------------------------------------------
def _qid_scan_stub(scan: Scan) -> Scan:
    """A detached Scan stub over the STACKED table: original schema plus
    the qid column, frozen ordinal, no ordering/stats claims (the stacked
    table makes none)."""
    stub = Scan.__new__(Scan)
    stub.table = None
    stub.ordinal = scan.ordinal
    stub.table_ordering = None
    stub.table_stats = {}
    # the stacked table's counts are still on the device: a join over it
    # takes the shuffle route (Join.pick_route)
    stub.table_host_rows = None
    stub.schema = tuple(scan.schema) + ((QID, int(Type.INT32), "int32"),)
    return stub


def _rewrite(node: Node, memo: Dict[int, Tuple[Node, str]]) -> Tuple[Node, str]:
    """Recursively build the batched twin of ``node``. Returns the new
    node plus the OUTPUT NAME its binding-id column rides under (joins
    may suffix it)."""
    got = memo.get(id(node))
    if got is not None:
        return got
    if isinstance(node, Scan):
        out: Tuple[Node, str] = (_qid_scan_stub(node), QID)
    elif isinstance(node, Filter):
        child, q = _rewrite(node.children[0], memo)
        out = (Filter(child, node.expr), q)
    elif isinstance(node, Project):
        child, q = _rewrite(node.children[0], memo)
        cols = list(node.cols)
        if q not in cols:
            cols.append(q)
        out = (Project(child, cols), q)
    elif isinstance(node, Sort):
        child, q = _rewrite(node.children[0], memo)
        # qid leads: a range shuffle partitions bindings apart and the
        # per-binding suffix order matches the serial sort's key order
        out = (Sort(child, (q,) + node.by, (True,) + node.ascending), q)
    elif isinstance(node, GroupBy):
        if not node.keys:
            # one row a binding also where none of its rows pass: a
            # group-by on the qid would drop that binding's row
            raise Unbatchable("aggregate without keys")
        child, q = _rewrite(node.children[0], memo)
        out = (GroupBy(child, (q,) + node.keys, node.aggs, mask=node.mask), q)
    elif isinstance(node, Join):
        if node.how == "outer":
            # neither side's qid is non-null on every output row
            raise Unbatchable("full-outer join")
        left, ql = _rewrite(node.children[0], memo)
        right, qr = _rewrite(node.children[1], memo)
        j = Join(
            left, right, (ql,) + node.l_on, (qr,) + node.r_on,
            node.how, node.suffixes,
        )
        # the surviving (never-null) side's qid identifies the binding:
        # left for inner/left joins, right for right joins
        q = j.l_rename[ql] if node.how in ("inner", "left") else j.r_rename[qr]
        out = (j, q)
    elif isinstance(node, Union):
        left, ql = _rewrite(node.children[0], memo)
        right, qr = _rewrite(node.children[1], memo)
        if ql != qr or left.names != right.names:
            raise Unbatchable("union with mismatched batched schemas")
        # distinct-union stays per-binding: rows of different bindings
        # differ in qid, so cross-binding dedup cannot happen
        out = (Union(left, right), ql)
    else:
        raise Unbatchable(type(node).__name__)
    memo[id(node)] = out
    return out


class BatchTemplate:
    """The batched twin of one logical plan: a detached plan whose Scans
    expect stacked tables (original columns + qid), plus the names the
    split step needs."""

    __slots__ = ("root", "qid_out", "out_names", "n_scans")

    def __init__(self, root: Node, qid_out: str, out_names: List[str],
                 n_scans: int):
        self.root = root
        self.qid_out = qid_out
        self.out_names = out_names
        self.n_scans = n_scans


def build_batched_template(plan: Node, n_scans: int) -> BatchTemplate:
    """Rewrite ``plan`` (ordinals already assigned by ``scan_tables``)
    into its batched twin. Raises :class:`Unbatchable` for unsupported
    shapes or schemas that collide with the reserved qid name."""

    def check(n: Node) -> None:
        if isinstance(n, Scan):
            if any(e[0].startswith(QID) for e in n.schema):
                raise Unbatchable(f"schema uses reserved column {QID}")
            return
        for c in n.children:
            check(c)

    check(plan)
    root, qid_out = _rewrite(plan, {})
    if qid_out not in root.names:  # pragma: no cover - defensive
        raise Unbatchable("binding id pruned from the batched output")
    return BatchTemplate(root, qid_out, list(plan.names), n_scans)


def is_batchable(plan: Node) -> bool:
    try:
        build_batched_template(plan, 0)
        return True
    except Unbatchable:
        return False


# ----------------------------------------------------------------------
# table stacking: B bindings -> one table + qid column, zero host syncs
# ----------------------------------------------------------------------
def _union_dictionaries(tables: List[Table], name: str):
    """(union dictionary, per-table remap arrays or None) for one
    dictionary-encoded column across the B bindings — host-side merge of
    the (sorted, unique) dictionaries; identical dictionaries skip the
    in-kernel remap gather entirely."""
    dicts = [t._columns[name].dictionary for t in tables]
    if all(
        d is dicts[0] or np.array_equal(d, dicts[0]) for d in dicts[1:]
    ):
        return dicts[0], [None] * len(tables)
    union = dicts[0]
    for d in dicts[1:]:
        union = np.union1d(union, d)
    remaps = [np.searchsorted(union, d).astype(np.int32) for d in dicts]
    return union, remaps


def stack_tables(ctx, tables: List[Table], pad_to: int) -> Table:
    """Concatenate B same-schema bindings into ONE table whose per-shard
    rows are the front-packed union of the bindings' live rows, plus an
    int32 ``__cylon_qid`` column holding each row's binding index.

    Sync-free by construction: each binding's (possibly still deferred)
    count lane rides in as a device operand and the output count lane is
    their in-kernel sum, so the stacked table is itself a deferred-count
    handle. ``pad_to`` pow2-pads the batch with zero-row slots (reusing
    binding 0's buffers under a zero count) so the batched program cache
    stays one entry per (fingerprint, B-bucket)."""
    t0 = tables[0]
    names = t0.column_names
    with span("serve.stack", rows=len(tables)):
        dicts: Dict[str, np.ndarray] = {}
        remaps_by_col: Dict[str, List[Optional[np.ndarray]]] = {}
        for n in names:
            if t0._columns[n].dictionary is not None:
                dicts[n], remaps_by_col[n] = _union_dictionaries(tables, n)
        zero_counts = jax.device_put(
            np.zeros(t0.world_size, np.int32), ctx.sharding
        )
        dp = []
        remaps = []
        for i in range(pad_to):
            t = tables[i] if i < len(tables) else t0
            cnt = t.counts_dev if i < len(tables) else zero_counts
            dp.append((cnt, t._flat_cols()))
            # padding slots reuse binding 0's buffers (under a zero
            # count), so they take binding 0's remap too
            ri = i if i < len(tables) else 0
            remaps.append(tuple(
                None if n not in remaps_by_col else remaps_by_col[n][ri]
                for n in names
            ))
        out_cap = round_cap(sum(t._shard_cap for t in tables))
        key = ("serve_stack", pad_to, len(names))
        fn = get_kernel(ctx, key, _stack_builder)
        out_cols, counts = fn(
            (tuple(dp),),
            (jnp.zeros((out_cap,), jnp.int8), tuple(remaps)),
        )
        cols: "OrderedDict[str, Column]" = OrderedDict()
        for n, (data, valid) in zip(names, out_cols[:-1]):
            src = t0._columns[n]
            cols[n] = Column(data, src.dtype, valid, dicts.get(n, src.dictionary))
        qid_data, _ = out_cols[-1]
        cols[QID] = Column(
            qid_data, DataType.from_numpy_dtype(np.dtype(np.int32))
        )
        return Table(ctx, cols, counts, out_cap)


def _stack_builder():
    """Per-shard stacking kernel: scatter each slot's live rows to its
    cumulative offset (out-of-range indices drop, so dead rows and
    zero-count padding slots write nothing); derive everything from
    operand shapes/structure so nothing is baked into the trace."""

    def kern(dp, rep):
        (slots,) = dp
        dummy, remaps = rep
        out_cap = dummy.shape[0]
        ncols = len(slots[0][1])
        any_valid = [
            any(cols[j][1] is not None for _, cols in slots)
            for j in range(ncols)
        ]
        outs = [
            jnp.zeros((out_cap,), slots[0][1][j][0].dtype)
            for j in range(ncols)
        ]
        valids = [
            jnp.zeros((out_cap,), jnp.bool_) if any_valid[j] else None
            for j in range(ncols)
        ]
        qid = jnp.zeros((out_cap,), jnp.int32)
        offset = jnp.int32(0)
        for i, (cnt, cols) in enumerate(slots):
            n = cnt[0].astype(jnp.int32)
            cap_i = cols[0][0].shape[0]
            ar = jnp.arange(cap_i, dtype=jnp.int32)
            idx = jnp.where(ar < n, offset + ar, out_cap)
            for j, (d, v) in enumerate(cols):
                rm = remaps[i][j]
                if rm is not None:
                    d = jnp.asarray(rm)[d]
                outs[j] = outs[j].at[idx].set(d, mode="drop")
                if any_valid[j]:
                    vv = (
                        v if v is not None
                        else jnp.ones((cap_i,), jnp.bool_)
                    )
                    valids[j] = valids[j].at[idx].set(vv, mode="drop")
            qid = qid.at[idx].set(
                jnp.full((cap_i,), i, jnp.int32), mode="drop"
            )
            offset = offset + n
        out_cols = [(outs[j], valids[j]) for j in range(ncols)]
        out_cols.append((qid, None))
        return out_cols, offset.reshape(1)

    return kern


# ----------------------------------------------------------------------
# result split: ALL B bindings' slices in one kernel dispatch
# ----------------------------------------------------------------------
def split_batch(
    result: Table, template: BatchTemplate, b: int, bucket: int
) -> List[Table]:
    """Every binding's slice of the batched result from ONE kernel
    dispatch: per binding a compact-mask over ``qid == i`` and one packed
    gather, all fused into a single XLA program — the per-query dispatch
    cost the batch exists to amortize must not sneak back in through the
    split. Each slice is a deferred-count handle projected to the
    original output schema; compaction of the (sound but loose)
    full-result capacity happens at each slice's materialize, exactly
    like ``filter``.

    The kernel is built for the pow2 ``bucket`` (padding slices come out
    empty and are dropped), so the split compiles once per (bucket,
    schema) like the stack kernel and the batched executor — never once
    per arrival-process group size. Until materialize compacts them, the
    ``bucket`` slices transiently hold bucket x the stacked capacity;
    the scheduler charges that burst to the queries' admission leases
    (:func:`split_bytes_estimate`)."""
    from ..ops import gather as _g_pack
    from ..ops import setops as _s

    names = template.out_names
    src = [result._columns[n] for n in names]
    qid = result._columns[template.qid_out].data
    flat = [(c.data, c.valid) for c in src]
    cap_out = result._shard_cap
    key = ("serve_split", bucket, len(names))

    def build():
        def kern(dp, rep):
            (q, cols, counts) = dp
            cap = q.shape[0]
            live = jnp.arange(cap, dtype=jnp.int32) < counts[0]
            outs = []
            for i in range(bucket):
                idx, total = _s.compact_mask(live & (q == i), cap)
                packed, _ = _g_pack.pack_gather(list(cols), idx)
                outs.append((packed, total.reshape(1)))
            return outs

        return kern

    out = get_kernel(result.ctx, key, build)(
        (qid, flat, result.counts_dev), ()
    )
    slices = []
    for packed, counts_i in out[:b]:
        cols: "OrderedDict[str, Column]" = OrderedDict()
        for n, c, (data, valid) in zip(names, src, packed):
            cols[n] = Column(data, c.dtype, valid, c.dictionary)
        slices.append(Table(result.ctx, cols, counts_i, cap_out))
    return slices


def split_bytes_estimate(result: Table, template: BatchTemplate) -> int:
    """Device bytes ONE slice of ``result`` occupies before its
    materialize-time compaction (full stacked capacity per column) — the
    admission-lease surcharge for the batched split's transient burst."""
    total = 0
    for n in template.out_names:
        c = result._columns[n]
        total += int(c.data.nbytes)
        if c.valid is not None:
            total += int(c.valid.nbytes)
    return total
