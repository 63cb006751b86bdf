"""The rule-based plan rewriter.

``optimize(root, world_size)`` runs its passes in this order and returns
the rewritten plan plus the ordered list of rule firings (surfaced by
``.explain()`` and counted into the tracing registry by ``collect()``):

0. ``topk`` — ``Limit(Sort(x))`` becomes ``TopK`` (lowered to
   ``Table.topk``: the key lanes sorted, ``n`` rows gathered, no count
   fetched), before the physicalizer can put a range shuffle under the
   sort;
1. ``filter_pushdown`` — move Filters below Projects/Sorts/Unions, below
   the covering side of a Join, and below a GroupBy when the predicate
   only reads group keys (the later physicalize pass then inserts shuffles
   ABOVE the pushed filters, so filters also shrink every exchange);
2. physicalize — insert the Shuffle nodes distribution requires (hash
   shuffles under joins/groupbys/unions, a range shuffle under a global
   sort); mesh of 1 inserts nothing. ``join_replicate``: a Join whose
   inputs' row counts the host holds and one of whose sides is small
   enough to hold whole on every chip (``Join.pick_route``, the eager
   ``Table.distributed_join``'s own rule, ``ops.join.replicate_side``)
   gets NO Shuffle and is marked ``route``: lowering gathers that side
   and joins where the other side's rows lie. The join then claims no
   placement (a GroupBy above it keeps its Shuffle), and the rules below
   that read two Shuffles under a distributed join (``shuffle_elimination``,
   ``semi_filter``) and the fused join->groupby decline it;
3. ``shuffle_elimination`` — drop a Shuffle whose input is already placed
   right: a groupby only needs its keys CO-LOCATED (a subset placement
   suffices), while a join/union input must be placed by EXACTLY the same
   ordered key tuple the other side will hash (plus dtype-identical key
   pairs) — a subset placement co-locates rows but routes them to
   different shards than the fresh hash of the full tuple;
3b. ``partial_aggregate`` — on a mesh, a GroupBy whose Shuffle still
   stands and which the dense plan takes (every op one of sum, count,
   min, max, mean; every key with a known integer range, from
   ``Node.col_stats`` and the dictionary sizes a Scan reports; few enough
   slots) loses the Shuffle and is marked ``partial``: every shard
   reduces its own rows, the partial states are combined in place
   (``Table.distributed_groupby``) and the groups lie on the first shard
   in key order, so the node's order is global and ``order_reuse`` drops
   a following Sort with its range shuffle. An aggregate without keys
   (``LazyFrame.agg``) is always one. Any other GroupBy keeps its plan;
4. ``fused_join_groupby`` — collapse GroupBy(sum)-over-inner-Join on the
   join key into :class:`~cylon_tpu.plan.nodes.FusedJoinGroupBySum`
   (lowers to ``ops.join.join_sum_by_key_pushdown``);
5. ``order_reuse`` — propagate order properties (``Node.ordering()``, the
   sortedness analog of partitioning): drop a Sort whose input already has
   the exact requested order identity-exactly, and rewrite a
   GroupBy-over-Join on the join keys (the q3 shape the fused rule does
   not take — non-sum/non-f32 aggregates, multi-agg, left joins) so the
   join emits GROUPED-KEY order (``Join(emit_key_order=True)`` lowers to
   ``emit_order='key'``, same kernel cost) and the groupby's factorize
   lexsort elides into a run-detect;
6. ``semi_filter`` — annotate Join / FusedJoinGroupBySum nodes whose input
   Shuffles both still stand with their semi-join filter eligibility by
   join type (inner: both sides; left: right side only; right: left side
   only; semi: left side only; outer and anti: never — false-positive-only
   pruning must not touch rows that emit unconditionally). Lowering
   threads the annotation into the pair shuffle (``table._shuffle_pair(semi=...)``), where each eligible
   side's rows are probed against the OTHER side's broadcast key sketch
   (ops/sketch.py) before they are packed; printed by ``.explain()`` and
   part of the plan fingerprint. CYLON_TPU_NO_SEMI_FILTER=1 disables;
7. ``projection_pushdown`` — prune unused columns down to the scans (and
   below the shuffles, where narrower rows mean fewer exchanged lanes).

Between 4 and 5, ``filter_as_mask`` turns a Filter directly under a
GroupBy into the aggregate's row mask (``GroupBy.mask``): the table skips
the rows in its reductions instead of compacting every column first; and
``join_mask`` does the same for a Filter directly under a side of an INNER
Join (``Join.masks``): the join's keys-only semi-reduction treats a masked
row as dead, and nothing is gathered at the table's capacity. A semi or an
anti Join takes the filters of both sides the same way (a right-side
filter narrows the set that is searched), and ``semi_as_mask`` then spares
such a Join directly under an aggregate its one compaction: the aggregate
takes the join's hit mask as its row mask (``Join.hit_mask``).
"""
from __future__ import annotations

from typing import List, Sequence, Set, Tuple

import numpy as np

from .nodes import (
    Filter,
    FusedJoinGroupBySum,
    GroupBy,
    Join,
    SEMI_HOWS,
    Limit,
    Node,
    Project,
    Scan,
    Shuffle,
    Sort,
    TopK,
    Union,
    WithColumns,
    _covers,
    _placed_by,
)

FILTER_PUSHDOWN = "filter_pushdown"
FILTER_AS_MASK = "filter_as_mask"
SHUFFLE_ELIM = "shuffle_elimination"
FUSED_JOIN_GROUPBY = "fused_join_groupby"
ORDER_REUSE = "order_reuse"
SEMI_FILTER = "semi_filter"
PROJECTION_PUSHDOWN = "projection_pushdown"
TOPK = "topk"
JOIN_MASK = "join_mask"
PARTIAL_AGGREGATE = "partial_aggregate"
SEMI_AS_MASK = "semi_as_mask"
JOIN_REPLICATE = "join_replicate"


def optimize(root: Node, world_size: int) -> Tuple[Node, List[str]]:
    fired: List[str] = []
    root = _make_topk(root, fired)
    root = _push_filters(root, fired)
    if world_size > 1:
        root = _physicalize(root, fired)
    root = _eliminate_shuffles(root, fired)
    if world_size > 1:
        root = _partial_aggregates(root, fired)
    root = _fuse_join_groupby(root, fired)
    root = _filter_as_mask(root, fired)
    root = _filter_as_join_mask(root, fired)
    root = _semi_as_mask(root, fired)
    root = _reuse_order(root, fired)
    if world_size > 1:
        root = _annotate_semi_filter(root, fired)
    root = _prune_columns(root, fired)
    return root, fired


# ----------------------------------------------------------------------
# 0. a limit over a sort is a top-k
# ----------------------------------------------------------------------
def _make_topk(node: Node, fired: List[str]) -> Node:
    """``Limit(Sort(x, by), n)`` -> ``TopK(x, by, n)``. Runs first: the
    physicalizer then puts no range shuffle under a sort whose output is
    cut to ``n`` rows at once (``Table.topk`` takes each shard's first
    ``n`` and orders only those across the mesh)."""
    kids = [_make_topk(c, fired) for c in node.children]
    node = node.with_children(kids) if node.children else node
    if isinstance(node, Limit) and isinstance(node.children[0], Sort):
        sort = node.children[0]
        fired.append(TOPK)
        return TopK(sort.children[0], sort.by, sort.ascending, node.n)
    return node


# ----------------------------------------------------------------------
# 1. filter pushdown
# ----------------------------------------------------------------------
def _push_filters(node: Node, fired: List[str]) -> Node:
    node = node.with_children([_push_filters(c, fired) for c in node.children])
    if not isinstance(node, Filter):
        return node
    child = node.children[0]
    expr = node.expr
    cols = expr.columns()
    if isinstance(child, (Project, Sort)):
        # row filters commute with column subsets and per-shard sorts
        # (Project never renames, so the expr passes unchanged). No Shuffle
        # case: this pass runs BEFORE physicalize, so shuffles don't exist
        # yet — filters end up below them because physicalize inserts each
        # shuffle directly under its consumer, above the pushed filter.
        fired.append(FILTER_PUSHDOWN)
        inner = _push_filters(Filter(child.children[0], expr), fired)
        return child.with_children([inner])
    if isinstance(child, WithColumns) and not cols & child.computed:
        # the predicate reads none of the computed columns: filter first,
        # compute on the rows that are left
        fired.append(FILTER_PUSHDOWN)
        inner = _push_filters(Filter(child.children[0], expr), fired)
        return child.with_children([inner])
    if isinstance(child, Union):
        # distinct(l ∪ r) filtered == distinct(filter(l) ∪ filter(r))
        fired.append(FILTER_PUSHDOWN)
        kids = [_push_filters(Filter(c, expr), fired) for c in child.children]
        return child.with_children(kids)
    if isinstance(child, GroupBy) and cols <= set(child.keys):
        # a predicate over group keys holds uniformly within each group
        fired.append(FILTER_PUSHDOWN)
        inner = _push_filters(Filter(child.children[0], expr), fired)
        return child.with_children([inner])
    if isinstance(child, Join):
        l_out = set(child.l_rename.values())
        r_out = set(child.r_rename.values())
        inv_l = {v: k for k, v in child.l_rename.items()}
        inv_r = {v: k for k, v in child.r_rename.items()}
        # pushing below a side is only sound when that side's rows survive
        # the join unconditionally filtered (not resurrected as outer
        # nulls); a semi or anti join's output is left columns alone
        if cols <= l_out and child.how in ("inner", "left") + SEMI_HOWS:
            fired.append(FILTER_PUSHDOWN)
            left = _push_filters(
                Filter(child.children[0], expr.rename(inv_l)), fired
            )
            return child.with_children([left, child.children[1]])
        if cols <= r_out and child.how in ("inner", "right"):
            fired.append(FILTER_PUSHDOWN)
            right = _push_filters(
                Filter(child.children[1], expr.rename(inv_r)), fired
            )
            return child.with_children([child.children[0], right])
    return node


# ----------------------------------------------------------------------
# 2. physicalize: insert the shuffles distribution requires
# ----------------------------------------------------------------------
def _physicalize(node: Node, fired: List[str]) -> Node:
    kids = [_physicalize(c, fired) for c in node.children]
    if isinstance(node, Join):
        # the inputs' host-known sizes are the same above and below this
        # pass: it only puts Shuffles under nodes whose counts are unknown
        side = node.pick_route()
        if side is not None:
            # the replicate route: no Shuffle under either side, and every
            # rule that reads two Shuffles under a distributed join finds
            # none here (shuffle_elimination, semi_filter, the fused
            # join->groupby, which stands over a placed join alone)
            fired.append(JOIN_REPLICATE)
            return node.replaced(kids, route=side)
        kids = [
            Shuffle(kids[0], node.l_on, "hash"),
            Shuffle(kids[1], node.r_on, "hash"),
        ]
    elif isinstance(node, GroupBy) and node.keys:
        kids = [Shuffle(kids[0], node.keys, "hash")]
    elif isinstance(node, Union):
        kids = [Shuffle(k, k.names, "hash") for k in kids]
    elif isinstance(node, Sort):
        # sample-sort recipe: range-partition on the primary key, then the
        # local sort makes the global order (Table.distributed_sort)
        kids = [Shuffle(kids[0], (node.by[0],), "range", node.ascending[0])]
    return node.with_children(kids) if node.children else node


# ----------------------------------------------------------------------
# 3. redundant-shuffle elimination
# ----------------------------------------------------------------------
def _dtypes_match(a: Node, a_cols: Sequence[str], b: Node, b_cols: Sequence[str]) -> bool:
    """Both sides of a two-table op will hash each key pair over the same
    physical dtype (no runtime promotion), so an existing partitioning on
    one side stays aligned with a fresh shuffle on the other."""
    try:
        return all(
            a.dtype_of(x) == b.dtype_of(y) for x, y in zip(a_cols, b_cols)
        )
    except KeyError:
        return False


def _elide(child: Node, fired: List[str], exact: bool) -> Node:
    """Drop ``child`` if it is a hash Shuffle whose input is already placed
    correctly. ``exact`` demands the SAME ordered placement tuple (two-table
    consumers: both sides must agree on the placement function); single-table
    consumers only need co-location, so a subset placement suffices."""
    if not (isinstance(child, Shuffle) and child.kind == "hash"):
        return child
    part = child.children[0].partitioning()
    ok = (
        _placed_by(part, child.keys) if exact
        else _covers(part, set(child.keys))
    )
    if ok:
        fired.append(SHUFFLE_ELIM)
        return child.children[0]
    return child


def _eliminate_shuffles(node: Node, fired: List[str]) -> Node:
    kids = [_eliminate_shuffles(c, fired) for c in node.children]
    node = node.with_children(kids) if node.children else node
    if isinstance(node, (GroupBy,)):
        return node.with_children([_elide(node.children[0], fired, False)])
    if isinstance(node, Join):
        left, right = node.children
        if _dtypes_match(left, node.l_on, right, node.r_on):
            return node.with_children(
                [_elide(left, fired, True), _elide(right, fired, True)]
            )
        return node
    if isinstance(node, Union):
        left, right = node.children
        if _dtypes_match(left, left.names, right, right.names):
            return node.with_children(
                [_elide(left, fired, True), _elide(right, fired, True)]
            )
        return node
    return node


# ----------------------------------------------------------------------
# 3b. an aggregate the dense plan takes is combined where its rows lie
# ----------------------------------------------------------------------
def _dense_plan_takes(node: GroupBy, child: Node) -> bool:
    """Will ``Table._dense_groupby_plan`` take this aggregate over
    ``child``'s rows? Decided from what the plan carries: the ops, and
    the keys' ranges in ``child.col_stats()`` (a dictionary column's from
    its size). Every key is counted as nullable (the plan holds no
    validity), so a yes here is a yes there; a no keeps the Shuffle, under
    which the table still picks its kernel a shard."""
    from ..ops import groupby as _g

    if not all(_g.agg_op_id(op) in _g.DENSE_OPS for _c, op in node.aggs):
        return False
    stats = child.col_stats()
    spans = [_g.dense_span(stats.get(k)) for k in node.keys]
    if None in spans:
        return False
    return _g.dense_slots(spans, [True] * len(spans)) <= _g.DENSE_MAX_SLOTS


def _partial_aggregates(node: Node, fired: List[str]) -> Node:
    """``GroupBy(Shuffle hash(x))`` -> ``GroupBy(x, partial=True)`` where
    the dense plan takes it; a GroupBy without keys (never given a
    Shuffle) likewise. Runs after shuffle elimination: a GroupBy whose
    input is already co-located has no Shuffle left and stays local."""
    kids = [_partial_aggregates(c, fired) for c in node.children]
    node = node.with_children(kids) if node.children else node
    if not isinstance(node, GroupBy) or node.partial:
        return node
    child = node.children[0]
    if node.keys:
        if not (
            isinstance(child, Shuffle) and child.kind == "hash"
            and child.keys == node.keys
        ):
            return node
        child = child.children[0]
        if not _dense_plan_takes(node, child):
            return node
    fired.append(PARTIAL_AGGREGATE)
    return node.replaced(child, partial=True)


# ----------------------------------------------------------------------
# 4. fused join -> groupby-SUM pushdown
# ----------------------------------------------------------------------
def _fuse_join_groupby(node: Node, fired: List[str]) -> Node:
    kids = [_fuse_join_groupby(c, fired) for c in node.children]
    node = node.with_children(kids) if node.children else node
    if not isinstance(node, GroupBy) or node.partial:
        return node
    join = node.children[0]
    if not isinstance(join, Join) or join.how != "inner":
        return node
    if join.route is not None:
        return node  # the fused kernel needs both sides placed by the key
    if len(node.aggs) != 1 or node.aggs[0][1] != "sum":
        return node
    val_out, _ = node.aggs[0]
    inv_l = {v: k for k, v in join.l_rename.items()}
    if val_out not in inv_l:
        return node  # the kernel sums a LEFT column (c_r * sum(v_l))
    val_src = inv_l[val_out]
    if val_src in join.l_on:
        return node  # summing the key itself: keep the generic path
    dt = np.dtype(join.children[0].dtype_of(val_src)[1])
    if dt.kind != "f" or dt.itemsize > 4:
        # the pushdown accumulates in the value dtype; ints need the wide
        # accumulator of the generic groupby. The 32-bit gate rests on a
        # rule PR 30 retired (a 64-bit lane does ride a TPU sort, as its
        # two halves): lifting it changes plans, ROADMAP D15
        return node
    # group keys must be exactly the join keys, each pair once (either
    # side's name: inner-join key values agree rowwise)
    l_pos = {n: i for i, n in enumerate(join.l_key_out)}
    r_pos = {n: i for i, n in enumerate(join.r_key_out)}
    key_order = []
    for k in node.keys:
        if k in l_pos:
            key_order.append(l_pos[k])
        elif k in r_pos:
            key_order.append(r_pos[k])
        else:
            return node
    if sorted(key_order) != list(range(len(join.l_on))):
        return node
    fired.append(FUSED_JOIN_GROUPBY)
    val_dtype = join.children[0].dtype_of(val_src)
    return FusedJoinGroupBySum(
        join.children[0], join.children[1], join.l_on, join.r_on, val_src,
        node.keys, key_order, f"{val_out}_sum", val_dtype,
    )


# ----------------------------------------------------------------------
# 4b. a filter directly under an aggregate rides it as a row mask
# ----------------------------------------------------------------------
def _filter_as_mask(node: Node, fired: List[str]) -> Node:
    """``GroupBy(Filter(x, p))`` -> ``GroupBy(x, mask=p)``: the aggregate
    skips the rows instead of a compaction gathering every column of the
    rest first. Runs after physicalize and shuffle elimination, so on a
    mesh a filter that stands under the group-by's Shuffle stays there and
    shrinks the exchange. ``WithColumns`` between the two is stepped over
    when the predicate reads none of its columns (filter pushdown put the
    filter below it; the mask is taken over the computed table, whose rows
    are the same)."""
    kids = [_filter_as_mask(c, fired) for c in node.children]
    node = node.with_children(kids) if node.children else node
    if not isinstance(node, GroupBy) or node.mask is not None:
        return node
    child, above = node.children[0], None
    if isinstance(child, WithColumns):
        child, above = child.children[0], child
    if not isinstance(child, Filter):
        return node
    if above is not None and child.expr.columns() & above.computed:
        return node
    fired.append(FILTER_AS_MASK)
    below = child.children[0]
    if above is not None:
        below = above.with_children([below])
    return node.replaced(below, mask=child.expr)


# ----------------------------------------------------------------------
# 4c. a filter directly under an inner join rides it as a row mask
# ----------------------------------------------------------------------
def _filter_as_join_mask(node: Node, fired: List[str]) -> Node:
    """``Join(Filter(x, p), y)`` -> ``Join(x, y, masks=(p, None))``, either
    side, INNER, semi and anti joins (an outer join would bring a dropped
    row back as an unmatched one; a semi or anti join keeps no left row
    its filter dropped, and a right row its filter dropped is no partner:
    the filter narrows the set that is searched, which is what it did
    under the join). The filter no longer compacts every column of
    its table at the table's capacity: the join's keys-only semi-reduction
    treats a masked row as dead, and only the rows that have a partner are
    ever gathered (``Table.join``'s docstring has the capacity rule). Runs
    after physicalize and shuffle elimination, so on a mesh a filter that
    stands under the join's Shuffle stays there and shrinks the exchange;
    a side is masked only where its rows do not move."""
    kids = [_filter_as_join_mask(c, fired) for c in node.children]
    node = node.with_children(kids) if node.children else node
    if not isinstance(node, Join) or node.how not in ("inner",) + SEMI_HOWS:
        return node
    kids, masks = list(node.children), list(node.masks)
    before = len(fired)
    for side in (0, 1):
        while isinstance(kids[side], Filter):
            f = kids[side]
            masks[side] = (
                f.expr if masks[side] is None else masks[side] & f.expr
            )
            kids[side] = f.children[0]
            fired.append(JOIN_MASK)
    if len(fired) == before:
        return node
    return node.replaced(kids, masks=tuple(masks))


# ----------------------------------------------------------------------
# 4d. a semi or anti join directly under an aggregate hands over its mask
# ----------------------------------------------------------------------
def _semi_as_mask(node: Node, fired: List[str]) -> Node:
    """``GroupBy(Join how=semi|anti)`` -> the same with ``Join.hit_mask``,
    where the dense plan takes the group-by (:func:`_dense_plan_takes`) or
    it has no keys: the join compacts nothing, and its verdicts over the
    left side's rows, where they lie, are the aggregate's row mask, as
    ``filter_as_mask`` does for a filter. Any other group-by sorts its
    rows, which is cheaper on the few a compaction leaves: declined."""
    kids = [_semi_as_mask(c, fired) for c in node.children]
    node = node.with_children(kids) if node.children else node
    if not isinstance(node, GroupBy):
        return node
    join = node.children[0]
    if (
        not isinstance(join, Join) or join.how not in SEMI_HOWS
        or join.hit_mask
    ):
        return node
    if node.keys and not _dense_plan_takes(node, join):
        return node
    fired.append(SEMI_AS_MASK)
    return node.replaced(join.replaced(join.children, hit_mask=True))


# ----------------------------------------------------------------------
# 5. order-property propagation / reuse
# ----------------------------------------------------------------------
def _reuse_order(node: Node, fired: List[str]) -> Node:
    """Consume ``Node.ordering()`` claims (runs AFTER shuffle elimination
    and the fused pushdown, so a planner Shuffle still standing between a
    producer and consumer correctly blocks reuse — shuffles claim no
    order)."""
    kids = [_reuse_order(c, fired) for c in node.children]
    node = node.with_children(kids) if node.children else node
    if isinstance(node, Sort):
        from ..ordering import matches_sort_spec

        child = node.children[0]
        o = child.ordering()
        if matches_sort_spec(o, node.by, node.ascending) == len(node.by):
            # identity-exact: the input IS the sort's output
            fired.append(ORDER_REUSE)
            return child
        if (
            isinstance(child, Shuffle) and child.kind == "range"
            and child.keys == (node.by[0],)
            and child.asc0 == node.ascending[0]
        ):
            # the physicalized sample-sort pair: when the shuffle's input
            # already holds the requested order at GLOBAL scope, both the
            # range re-partition and the local sort are redundant (the
            # eager distributed_sort no-op, lifted into the plan)
            o2 = child.children[0].ordering()
            if (
                o2 is not None and o2.scope == "global"
                and matches_sort_spec(o2, node.by, node.ascending)
                == len(node.by)
            ):
                fired.append(ORDER_REUSE)
                return child.children[0]
        return node
    if (
        isinstance(node, GroupBy) and not node.sorted_input
        and not node.partial  # reduced where its rows lie: no order to use
    ):
        from ..ordering import enabled

        join = node.children[0]
        if (
            enabled()  # the escape hatch gates this rewrite too
            and isinstance(join, Join)
            and join.how in ("inner", "left")
            and not join.emit_key_order
            and 0 < len(node.keys) <= len(join.l_on)
            and tuple(node.keys) == join.l_key_out[: len(node.keys)]
        ):
            # grouping by (a prefix of) the join's left-side key outputs:
            # flip the join to the key-order emit (same kernel cost — only
            # the probe's compaction key changes) and annotate the groupby;
            # the eager gate run-detects off the emitted descriptor
            fired.append(ORDER_REUSE)
            j2 = join.replaced(join.children, emit_key_order=True)
            return node.replaced(j2, sorted_input=True)
    return node


# ----------------------------------------------------------------------
# 6. semi-join sketch filter annotation
# ----------------------------------------------------------------------
def _both_shuffled(node: Node, l_on, r_on) -> bool:
    """The pair-exchange precondition: BOTH inputs are (still) hash
    Shuffles on their side's join keys — lowering then routes the pair
    through ``_shuffle_pair``, the only place the sketch exchange can
    overlap the pack dispatch. An elided shuffle means that side's rows
    never repack, so there is no exchange for the filter to shrink."""
    left, right = node.children
    return (
        isinstance(left, Shuffle) and left.kind == "hash"
        and set(left.keys) == set(l_on)
        and isinstance(right, Shuffle) and right.kind == "hash"
        and set(right.keys) == set(r_on)
    )


def _annotate_semi_filter(node: Node, fired: List[str]) -> Node:
    """Mark Join / FusedJoinGroupBySum nodes whose pair shuffle may prune
    rows against the other side's key sketch (ops/sketch.py). Annotation
    only — the eager engine re-checks soundness (hash-class pairing, size
    payoff) and measures selectivity at run time; the plan records the
    join-type eligibility so ``.explain()`` shows it and the fingerprint
    distinguishes filtered from unfiltered executors."""
    from ..ops.sketch import enabled, join_filter_sides

    kids = [_annotate_semi_filter(c, fired) for c in node.children]
    node = node.with_children(kids) if node.children else node
    if not enabled():
        return node
    # Join/Fused nodes always have children, so `node` is already the
    # fresh with_children copy above — safe to stamp the attribute
    if isinstance(node, Join) and node.semi_filter is None:
        sides = join_filter_sides(node.how)
        if sides is not None and _both_shuffled(node, node.l_on, node.r_on):
            fired.append(SEMI_FILTER)
            # table-side names: 'both' | the single filtered input side
            node.semi_filter = {"both": "both", "a": "left", "b": "right"}[
                sides
            ]
    elif isinstance(node, FusedJoinGroupBySum) and node.semi_filter is None:
        if _both_shuffled(node, node.l_on, node.r_on):
            fired.append(SEMI_FILTER)
            node.semi_filter = "both"  # the fused node is an inner join
    return node


# ----------------------------------------------------------------------
# 7. projection pushdown (column pruning)
# ----------------------------------------------------------------------
def _narrowed(node: Node, req: Set[str], fired: List[str]) -> Node:
    """Recursively prune, then guarantee the output schema is exactly the
    requested columns (node-schema order)."""
    out = _prune(node, req, fired)
    keep = [n for n in out.names if n in req]
    if keep != out.names:
        fired.append(PROJECTION_PUSHDOWN)
        out = Project(out, keep)
    return out


def _prune(node: Node, req: Set[str], fired: List[str]) -> Node:
    """Prune columns not needed upstream. The result's schema may still be
    wider than ``req`` (a GroupBy always emits keys + aggregates); the root
    caller re-narrows where exactness matters."""
    if isinstance(node, Scan):
        keep = [n for n in node.names if n in req]
        if keep != node.names:
            fired.append(PROJECTION_PUSHDOWN)
            return Project(node, keep)
        return node
    if isinstance(node, Project):
        keep = [c for c in node.cols if c in req]
        child = _prune(node.children[0], set(keep), fired)
        if keep != list(node.cols):
            fired.append(PROJECTION_PUSHDOWN)
        if child.names == keep:
            return child
        return Project(child, keep)
    if isinstance(node, Filter):
        child = _prune(node.children[0], req | node.expr.columns(), fired)
        return node.with_children([child])
    if isinstance(node, (Shuffle,)):
        child = _prune(node.children[0], req | set(node.keys), fired)
        return node.with_children([child])
    if isinstance(node, Sort):
        child = _prune(node.children[0], req | set(node.by), fired)
        return node.with_children([child])
    if isinstance(node, Limit):
        child = _prune(node.children[0], req, fired)
        return node.with_children([child])
    if isinstance(node, TopK):
        child = _prune(node.children[0], req | set(node.by), fired)
        return node.with_children([child])
    if isinstance(node, GroupBy):
        need = set(node.keys) | {c for c, _ in node.aggs}
        if node.mask is not None:
            need |= node.mask.columns()
        child = _prune(node.children[0], need, fired)
        return node.with_children([child])
    if isinstance(node, WithColumns):
        # a computed column nobody asks for is not computed
        kept = [(n, e) for n, e in node.exprs if n in req]
        below = set(node.children[0].names)
        need = {n for n in req if n in below}.union(
            *(e.columns() for _n, e in kept)
        )
        child = _prune(node.children[0], need, fired)
        if len(kept) != len(node.exprs):
            fired.append(PROJECTION_PUSHDOWN)
        return WithColumns(child, kept) if kept else child
    if isinstance(node, Join):
        l_req = {s for s, o in node.l_rename.items() if o in req} | set(node.l_on)
        r_req = {s for s, o in node.r_rename.items() if o in req} | set(node.r_on)
        if node.how in SEMI_HOWS:
            # nothing of the right side comes out: its keys, and below
            # what its mask reads
            r_req = set(node.r_on)
        kids, keep = [], list(node.keep)
        for side, need in enumerate((l_req, r_req)):
            mask = node.masks[side]
            reads = set() if mask is None else mask.columns()
            child = _prune(node.children[side], need | reads, fired)
            if reads - need:
                # what only the mask reads goes no further than the mask
                keep[side] = tuple(n for n in child.names if n in need)
                fired.append(PROJECTION_PUSHDOWN)
            kids.append(child)
        return node.replaced(kids, keep=tuple(keep))
    if isinstance(node, FusedJoinGroupBySum):
        left = _prune(node.children[0], set(node.l_on) | {node.val_col}, fired)
        right = _prune(node.children[1], set(node.r_on), fired)
        return node.with_children([left, right])
    if isinstance(node, Union):
        # distinct-union semantics depend on EVERY column: no pruning below
        return node
    return node.with_children([_prune(c, req, fired) for c in node.children]) \
        if node.children else node


def _prune_columns(root: Node, fired: List[str]) -> Node:
    return _narrowed(root, set(root.names), fired)
