"""Lowering: optimized plan -> calls into the existing eager Table ops.

``build_executor`` compiles a plan into a closure ``fn(tables) -> Table``
(``tables`` = the Scan inputs in ordinal order). The closure is what the
plan-fingerprint cache in ``engine.py`` stores: re-collecting an
equal-shape plan skips optimize+lower entirely, and the eager ops it calls
hit the per-context jit cache, so nothing recompiles.

Join-family nodes own their input Shuffles: the eager layer promotes key
dtypes and unifies dictionaries BEFORE hashing (``table.distributed_join``),
so a planner-inserted Shuffle under a Join must run after that pairing —
lowering peels it off the child and replays it inside the join recipe.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import ordering as _ord
from ..column import Column
from ..engine import get_kernel
from ..obs import stages as _stages
from ..obs import trace as _obstrace
from ..ops.join import join_type_id
from ..utils.tracing import span
from .expr import keep_mask, numeric_literals
from .nodes import (
    Filter,
    FusedJoinGroupBySum,
    GroupBy,
    Join,
    Limit,
    Node,
    Project,
    Scan,
    Shuffle,
    Sort,
    TopK,
    Union,
    WithColumns,
)


def scan_tables(root: Node) -> list:
    """Assign Scan ordinals in DFS order (shared scans keep one ordinal) and
    return their bound tables in that order. Called before fingerprinting."""
    tables: list = []
    seen: Dict[int, int] = {}
    # a loop and not a function that calls itself: this runs once a
    # query, and a self-naming closure is a cycle for the collector
    stack = [root]
    while stack:
        n = stack.pop()
        if isinstance(n, Scan):
            if id(n) not in seen:
                seen[id(n)] = len(tables)
                tables.append(n.table)
            n.ordinal = seen[id(n)]
        else:
            stack.extend(reversed(n.children))
    return tables


def detach_scans(root: Node) -> Node:
    """Copy the plan with table-less Scan stubs (frozen ordinals, same
    schema). The plan cache stores executors built over the DETACHED plan:
    live Scan nodes are shared with the user's LazyFrame and mutable (a
    later collect of a plan sharing a Scan re-assigns its ordinal), and
    their ``.table`` refs would otherwise pin the first collect's device
    buffers for the context's lifetime."""
    memo: Dict[int, Node] = {}

    def walk(n: Node) -> Node:
        got = memo.get(id(n))
        if got is not None:
            return got
        if isinstance(n, Scan):
            stub = Scan.__new__(Scan)
            stub.table = None
            stub.ordinal = n.ordinal
            stub.schema = n.schema
            stub.table_ordering = n.ordering()  # frozen compile-time claim
            stub.table_stats = dict(n.col_stats())  # frozen likewise
            stub.table_stream_gen = n.stream_gen()  # frozen likewise
            stub.table_host_rows = n.host_rows()  # frozen likewise
            out: Node = stub
        elif n.children:
            out = n.with_children([walk(c) for c in n.children])
        else:
            out = n
        memo[id(n)] = out
        return out

    return walk(root)


def _peel_shuffle(child: Node, keys: Sequence[str]):
    """(grandchild, needs_shuffle) for a join-family input: a planner
    Shuffle on exactly the side's keys is replayed inside the join recipe
    (after dict unification + key promotion)."""
    if (
        isinstance(child, Shuffle)
        and child.kind == "hash"
        and set(child.keys) == set(keys)
    ):
        return child.children[0], True
    return child, False


# plan-side semi_filter annotation -> table._shuffle_pair sides
_SEMI_SIDES = {"both": "both", "left": "a", "right": "b"}


def _prepare_join_inputs(
    lt, rt, l_keys, r_keys, l_shuf: bool, r_shuf: bool, semi=None
):
    """The join-input invariant in ONE place (used by Join and the fused
    node): unify dictionaries and promote key dtypes BEFORE hashing, then
    replay the peeled planner Shuffles on the prepared pair. When BOTH
    sides re-partition, one chunked-engine call shuffles the pair with
    interleaved round dispatch (table._shuffle_pair) — the lazy path picks
    up the same overlap, byte-budget, and semi-join sketch-filter plumbing
    as the eager join (``semi`` = the node's semi_filter annotation)."""
    from ..table import (
        _bump_join_route,
        _promote_key_pair,
        _shuffle_pair,
        _unify_dict_pair,
    )

    lt, rt = _unify_dict_pair(lt, rt, l_keys, r_keys)
    lt, rt = _promote_key_pair(lt, rt, l_keys, r_keys)
    if lt.world_size > 1:
        if l_shuf and r_shuf:
            _bump_join_route("shuffle", lt, rt)
            lt, rt = _shuffle_pair(
                lt, l_keys, rt, r_keys, semi=_SEMI_SIDES.get(semi)
            )
        elif l_shuf:
            lt = lt._shuffle_impl(kind="hash", key_names=l_keys)
        elif r_shuf:
            rt = rt._shuffle_impl(kind="hash", key_names=r_keys)
    return lt, rt


def plan_order(root: Node) -> Dict[int, int]:
    """Stable pre-order numbering of a plan's nodes: the ``node_id`` a
    per-node span carries, and the id ``explain(analyze=True)`` joins
    spans back to rendered tree lines with. Computed identically here
    and in the renderer because both walk the SAME detached plan object
    the cached executor closed over."""
    order: Dict[int, int] = {}

    def number(n: Node) -> None:
        if id(n) in order:
            return  # shared subplan (DAG): keep the first-visit id
        order[id(n)] = len(order)
        for c in n.children:
            number(c)

    number(root)
    return order


def build_executor(root: Node) -> Callable[[List], "object"]:
    """Compile the plan into ``fn(tables) -> Table``.

    Every node executes under a ``plan.node.<Type>`` span carrying its
    pre-order ``node_id`` — with tracing off that is one disabled-path
    span call per node (rollup bump only); with a query trace active the
    spans nest into the query's tree and ``explain(analyze=True)`` joins
    them back to plan lines. Under ``obs.trace.analyze_mode()`` (set
    ONLY by explain(analyze=True) — never the production dispatch path)
    each node's result is materialized so rows in/out are exact; that is
    a diagnostic per-node sync by design."""
    order = plan_order(root)

    def run(tables: List):
        return _execute(root, order, tables, {})

    return run


def _execute(node: Node, order: Dict[int, int], tables: List, memo: dict):
    """One node of a run, its children first; ``memo`` holds the run's
    results so far (a shared subplan runs once). A module-level function
    and not a closure of ``run`` that calls itself: a function that names
    itself is a reference cycle, and a memo hanging from it (a computed
    projection of a 60M-row table is a gigabyte of HBM) would live until
    the cycle collector happens by, some queries later."""
    got = memo.get(id(node))
    if got is not None:
        return got
    with span(
        "plan.node." + type(node).__name__, node_id=order[id(node)],
    ) as sp:
        out = _lower_one(
            node, lambda child: _execute(child, order, tables, memo), tables
        )
        if _obstrace.analyze_active():
            out._materialize()
        if sp is not None:
            rows = out._rows_hint()
            if rows is not None:
                sp.attrs["rows_out"] = rows
    memo[id(node)] = out
    return out


def eval_exprs(t, exprs) -> list:
    """Plan expressions over table ``t``'s columns, as ``(data, valid |
    None)`` pairs in the columns' padded layout: one program
    (``jit_expr_eval``, stage ``expr.eval``) whose numeric literals are
    arguments, so a sweep of a literal compiles nothing. The literals go
    in as the scalars they are: a python ``0.1`` is as weakly typed in
    the program as in an eager evaluation. An expression that reads a
    dictionary column is compared through the dictionary on the host and
    evaluates eagerly, as before."""
    used = sorted(set().union(*(e.columns() for e in exprs)))
    if not used:
        raise ValueError("a plan expression must read a column")
    if any(t._columns[n].dtype.is_dictionary for n in used):
        env = {n: t._columns[n] for n in used}
        return [e.evaluate(env) for e in exprs]
    lits = [l for e in exprs for l in numeric_literals(e)]
    dtypes = tuple(t._columns[n].dtype for n in used)
    key = (
        "expr_eval", tuple(e.shape_key() for e in exprs), tuple(used),
        tuple(int(d.type) for d in dtypes),
    )

    def build():
        def kern(dp, rep):
            cols = dp
            cap = cols[0][0].shape[0]
            env = {
                n: Column(d, dt, v, None)
                for n, dt, (d, v) in zip(used, dtypes, cols)
            }
            vals = {id(l): x for l, x in zip(lits, rep)}
            with jax.named_scope(_stages.EXPR_EVAL):
                out = []
                for e in exprs:
                    d, v = e.evaluate(env, vals)
                    out.append((jnp.broadcast_to(d, (cap,)), v))
                return out

        return kern

    return get_kernel(t.ctx, key, build)(
        t._flat_cols(used), tuple(l.physical() for l in lits)
    )


def _expr_mask(t, expr) -> jax.Array:
    """A plan predicate as the boolean keep mask ``Table.filter`` and
    ``Table.groupby(_mask=)`` take (a null predicate row is dropped)."""
    return keep_mask(*eval_exprs(t, [expr])[0])


def _globally_ordered(t, keys) -> bool:
    """Does ``t``'s descriptor prove its rows in ``keys`` order over the
    whole mesh (one shard is the whole mesh)?"""
    o = t._ordering
    return _ord.covers_prefix(o, keys) and (
        t.world_size == 1 or o.scope == "global"
    )


class _MaskedRows:
    """What a Join that ``semi_as_mask`` marked hands the aggregate
    directly above it: the left side's table, no row moved, and the
    join's verdicts as a row mask over its padded layout."""

    __slots__ = ("table", "mask")

    def __init__(self, table, mask):
        self.table, self.mask = table, mask

    def _materialize(self):
        return self.table._materialize()

    def _rows_hint(self):
        return None  # the kept rows are never counted


def _lower_one(node: Node, ex, tables):
    if isinstance(node, Scan):
        return tables[node.ordinal]
    if isinstance(node, Project):
        return ex(node.children[0]).project(list(node.cols))
    if isinstance(node, Filter):
        t = ex(node.children[0])
        return t.filter(_expr_mask(t, node.expr))
    if isinstance(node, WithColumns):
        t = ex(node.children[0])
        out = eval_exprs(t, [e for _n, e in node.exprs])
        return t._with_arrays(
            {n: kc for (n, _e), kc in zip(node.exprs, out)}
        )
    if isinstance(node, Sort):
        return ex(node.children[0]).sort(list(node.by), list(node.ascending))
    if isinstance(node, Shuffle):
        t = ex(node.children[0])
        if t.world_size == 1:
            return t
        if node.kind == "hash":
            return t._shuffle_impl(kind="hash", key_names=list(node.keys))
        return t._shuffle_impl(
            kind="range", key_names=[node.keys[0]], asc0=node.asc0
        )
    if isinstance(node, GroupBy):
        # a hash Shuffle on exactly the node's keys belongs to the
        # group-by's own recipe (as a join owns its inputs'): the table
        # ships a partial row a group where the ops have a partial state
        # and the rows themselves where they have none, and either way
        # every shard ends up owning the groups whose keys hash to it
        child = node.children[0]
        exchange = (
            isinstance(child, Shuffle) and child.kind == "hash"
            and child.keys == node.keys and not node.sorted_input
        )
        t = ex(child.children[0] if exchange else child)
        spec: Dict[str, list] = {}
        for c, op in node.aggs:
            spec.setdefault(c, []).append(op)
        # semi_as_mask rewrite: the join below kept its rows where they
        # lie and hands over which of them it keeps
        hits = None
        if isinstance(t, _MaskedRows):
            t, hits = t.table, t.mask
        # filter_as_mask rewrite: the table takes the predicate as the
        # aggregate's row mask (and still picks its own kernel)
        mask = None if node.mask is None else _expr_mask(t, node.mask)
        if hits is not None:
            mask = hits if mask is None else hits & mask
        keys = list(node.keys)
        if node.partial or not keys:
            # partial_aggregate rewrite: no Shuffle stands under the node;
            # the table combines the shards' partial states in place where
            # its dense plan applies and shuffles where it does not
            res = t.distributed_groupby(keys, spec, _mask=mask)
            if keys and not _globally_ordered(res, keys):
                # the node claimed global key order (a Sort above it may be
                # gone): hold the table to it if it took the other path
                res = res.distributed_sort(keys)
        elif exchange and t.world_size > 1:
            res = t._groupby_exchange(keys, spec, mask)
        else:
            res = t.groupby(keys, spec, _mask=mask)
        # multiple ops per column group in dict order; restore plan order
        if res.column_names != node.names:
            res = res.project(node.names)
        return res
    if isinstance(node, Join):
        lchild, l_shuf = _peel_shuffle(node.children[0], node.l_on)
        rchild, r_shuf = _peel_shuffle(node.children[1], node.r_on)
        lt, rt = ex(lchild), ex(rchild)
        # join_mask rewrite: each side's predicate as its row mask, read
        # before the columns only the mask needs are left behind
        sides, masks = [], []
        for t, mask, keep in zip((lt, rt), node.masks, node.keep):
            masks.append(None if mask is None else _expr_mask(t, mask))
            if keep is not None:
                t = t.project([n for n in t.column_names if n in keep])
            sides.append(t)
        (lt, rt), (l_mask, r_mask) = sides, masks
        # pre-rename both sides to the build-time output names so pruning
        # can never change the suffixing (nodes.Join docstring)
        lt = lt.rename({n: node.l_rename[n] for n in lt.column_names})
        rt = rt.rename({n: node.r_rename[n] for n in rt.column_names})
        l_keys, r_keys = list(node.l_key_out), list(node.r_key_out)
        if node.route is not None and lt.world_size > 1:
            # the replicate route (rules._physicalize put no Shuffle under
            # this join): the same table call as the eager join's. A side
            # under a Filter has no host-known count and takes the shuffle
            # route, so no mask ever rides this one
            assert l_mask is None and r_mask is None
            if node.hit_mask:  # semi_as_mask: the verdicts, nothing moved
                return _MaskedRows(*lt._join_replicated(
                    rt, node.route, l_keys, r_keys, node.how, as_mask=True,
                ))
            return lt._join_replicated(
                rt, node.route, l_keys, r_keys, node.how,
                suffixes=node.suffixes,
                emit_order="key" if node.emit_key_order else "left",
            )
        lt, rt = _prepare_join_inputs(
            lt, rt, l_keys, r_keys, l_shuf, r_shuf, semi=node.semi_filter
        )
        if node.hit_mask:
            return _MaskedRows(*lt._semi_join(
                rt, l_keys, r_keys, join_type_id(node.how), l_mask, r_mask,
                as_mask=True,
            ))
        return lt.join(
            rt, left_on=l_keys, right_on=r_keys, how=node.how,
            suffixes=node.suffixes,
            # order_reuse rewrite: emit grouped-key order so the consumer's
            # lexsort elides (the eager join stamps the ordering descriptor
            # and e.g. Table.groupby auto-run-detects off it)
            emit_order="key" if node.emit_key_order else "left",
            _left_mask=l_mask, _right_mask=r_mask,
        )
    if isinstance(node, FusedJoinGroupBySum):
        lchild, l_shuf = _peel_shuffle(node.children[0], node.l_on)
        rchild, r_shuf = _peel_shuffle(node.children[1], node.r_on)
        l_on, r_on = list(node.l_on), list(node.r_on)
        lt, rt = _prepare_join_inputs(
            ex(lchild), ex(rchild), l_on, r_on, l_shuf, r_shuf,
            semi=node.semi_filter,
        )
        # kernel emits key columns in join-pair order; name them so that
        # projecting to node.names restores the groupby key order
        pair_names = [None] * len(l_on)
        for name, ki in zip(node.out_keys, node.key_order):
            pair_names[ki] = name
        res = lt._join_sum_pushdown(
            rt, l_on, r_on, node.val_col, pair_names, node.out_val
        )
        if res.column_names != node.names:
            res = res.project(node.names)
        return res
    if isinstance(node, Union):
        return ex(node.children[0]).union(ex(node.children[1]))
    if isinstance(node, TopK):
        _obstrace.bump("plan.topk")
        return ex(node.children[0]).topk(
            list(node.by), node.n, list(node.ascending)
        )
    if isinstance(node, Limit):
        t = ex(node.children[0])
        return t.take(np.arange(min(node.n, t.row_count), dtype=np.int64))
    raise TypeError(f"no lowering for plan node {type(node).__name__}")
