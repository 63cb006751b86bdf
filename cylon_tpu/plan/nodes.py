"""Logical-plan IR: immutable nodes with schema + partitioning propagation.

Schema entries are ``(name, type_id, physical_dtype_str)`` — enough for the
rewrite rules to gate on (is the aggregate column float32? is a key
dictionary-encoded?) and for the plan fingerprint, without holding any data.

Partitioning is DERIVED, not stored: :meth:`Node.partitioning` returns the
list of column-name sets whose equal tuples are guaranteed co-located on one
shard. The eager layer tracks this only implicitly (a ``_shuffle_impl`` has
just happened); making it a plan property is what lets the rewriter prove a
re-partition redundant (Exoshuffle-style shuffle elimination, PAPERS.md
arxiv 2203.05072).
"""
from __future__ import annotations

from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

from .. import ordering as _ord
from ..ordering import Ordering

SchemaEntry = Tuple[str, int, str]  # (name, Type enum value, physical dtype)
# ORDERED key tuples: order is part of the placement function's identity
# (hashing ['a','b'] and ['b','a'] routes differently), so two-table
# consumers demand exact tuple equality while single-table co-location
# checks may relax to subsets (_covers).
Partitioning = List[Tuple[str, ...]]


def _suffix_names(lnames, rnames, suffixes):
    overlap = set(lnames) & set(rnames)
    out = [n + suffixes[0] if n in overlap else n for n in lnames]
    out += [n + suffixes[1] if n in overlap else n for n in rnames]
    return out


class Node:
    """Base plan node. ``children`` is a tuple; nodes are treated as
    immutable — rewrites build new nodes via :meth:`with_children`."""

    children: Tuple["Node", ...] = ()
    schema: Tuple[SchemaEntry, ...] = ()

    @property
    def names(self) -> List[str]:
        return [e[0] for e in self.schema]

    def dtype_of(self, name: str) -> Tuple[int, str]:
        for n, t, p in self.schema:
            if n == name:
                return t, p
        raise KeyError(name)

    def with_children(self, kids: Sequence["Node"]) -> "Node":
        raise NotImplementedError

    def partitioning(self) -> Partitioning:
        """Column sets whose equal tuples are co-located (see module doc)."""
        return []

    def ordering(self) -> Optional[Ordering]:
        """The node's output order property, derived like partitioning:
        what the eager op provably establishes/preserves (see
        cylon_tpu/ordering.py). None = no claim. The ``order_reuse`` rule
        consumes it, and ``.explain()`` prints it per node."""
        return None

    def col_stats(self) -> Dict[str, object]:
        """Known column range stats (ops/stats.ColStat) of this node's
        output, derived like partitioning/ordering: Scans read their
        table's measured bounds, row-subset/rename nodes carry them
        (bounds are conservative over any subset), value-rewriting nodes
        drop them. Advisory only — the eager kernels re-derive their own
        packing gates from live tables; ``.explain()`` prints the
        quantized widths per node."""
        return {}

    def host_rows(self) -> Optional[Tuple[int, int]]:
        """``(rows, world size)`` where the node's row count is known on
        the host before anything runs: a Scan of a table whose counts the
        host holds, and the nodes that keep every row of one (Project,
        WithColumns). ``None`` anywhere else, a Filter's output included:
        what ``Join.pick_route`` weighs, and it fetches nothing."""
        return None

    def row_bytes(self) -> int:
        """Bytes a row of the node's output takes on the device, by its
        schema's physical dtypes (validity lanes left out)."""
        return sum(np.dtype(p).itemsize for _n, _t, p in self.schema)

    def _params(self) -> tuple:
        """Node-local fingerprint parameters (no children, no schema —
        schema is derived and scans carry theirs explicitly)."""
        return ()

    def fingerprint(self) -> tuple:
        return (
            type(self).__name__,
            self._params(),
            tuple(c.fingerprint() for c in self.children),
        )

    def label(self) -> str:
        """One-line description for ``.explain()``."""
        return type(self).__name__

    def line(self) -> str:
        """The node's single rendered line (label + derived properties)
        — shared by :meth:`render` and the ``explain(analyze=True)``
        annotated renderer (plan/lazy.py)."""
        line = self.label()
        o = self.ordering()
        if o is not None:
            line += f"  -- order: {o.describe()}"
        stats = self.col_stats()
        if stats:
            from ..ops.stats import field_bits

            widths = ", ".join(
                f"{n}:{field_bits(v)}b" for n, v in sorted(stats.items())
            )
            line += f"  -- stats: {widths}"
        return line

    def render(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.line()]
        for c in self.children:
            lines.append(c.render(indent + 1))
        return "\n".join(lines)


class Scan(Node):
    """A concrete bound Table. ``ordinal`` is assigned in original-plan DFS
    order at collect time and is how the cached executor finds its input."""

    def __init__(self, table):
        self.table = table
        self.ordinal: Optional[int] = None
        # detached stubs freeze the descriptor they were compiled under
        # (lower.detach_scans); live Scans read it from the table at USE
        # time below — an in-place mutation (__setitem__) clears the
        # table's descriptor, and a capture here would let the order_reuse
        # rewrite act on the stale claim. Range stats follow the same
        # live-read / frozen-stub discipline.
        self.table_ordering: Optional[Ordering] = None
        self.table_stats: Dict[str, object] = {}
        self.table_stream_gen = None
        self.table_host_rows: Optional[Tuple[int, int]] = None
        self.schema = tuple(
            (n, int(table._columns[n].dtype.type), str(table._columns[n].data.dtype))
            for n in table.column_names
        )

    def with_children(self, kids):
        assert not kids
        return self

    def ordering(self) -> Optional[Ordering]:
        if self.table is None:  # detached stub
            return self.table_ordering
        return self.table._ordering

    def col_stats(self) -> Dict[str, object]:
        if self.table is None:  # detached stub
            return dict(self.table_stats)
        stats = dict(self.table._stats)
        # a dictionary column's codes have a range without a measurement
        # (what Table._dense_groupby_plan reads): the partial_aggregate
        # rule must see it on a table no group-by has touched yet
        from ..ops.stats import dictionary_stat

        for n, c in self.table._columns.items():
            if c.dtype.is_dictionary and n not in stats:
                stats[n] = dictionary_stat(len(c.dictionary))
        return stats

    def host_rows(self) -> Optional[Tuple[int, int]]:
        if self.table is None:  # detached stub
            return self.table_host_rows
        rows = self.table._rows_hint()  # never a fetch
        return None if rows is None else (rows, self.table.world_size)

    def stream_gen(self):
        """The bound table's streaming identity ``(source_token,
        generation)``, or None for ordinary (non-appendable) tables.
        Stamped by ``stream/ingest.py`` on every snapshot it hands out;
        live-read here (frozen on detached stubs) so the generation
        rides :func:`~cylon_tpu.plan.lazy.gated_fingerprint` — a cached
        executable (and its observation identity) can never alias across
        refreshes of a growing table."""
        if self.table is None:  # detached stub
            return self.table_stream_gen
        return getattr(self.table, "_stream_gen", None)

    def _params(self) -> tuple:
        # the ordering descriptor is part of the plan identity: a cached
        # executor whose rewrites consumed (or ignored) input sortedness
        # must not be reused for an input with a different order property.
        # Read LIVE at fingerprint time (collect), same snapshot optimize
        # sees in the same collect call. The stream generation follows
        # the same discipline: same snapshot, same live read.
        return (
            self.ordinal, self.schema, self.table.world_size,
            self.ordering(), self.stream_gen(),
        )

    def label(self) -> str:
        return f"Scan [{', '.join(self.names)}]"


class Project(Node):
    def __init__(self, child: Node, cols: Sequence[str]):
        missing = [c for c in cols if c not in child.names]
        if missing:
            raise KeyError(f"project columns not in input: {missing}")
        self.children = (child,)
        self.cols = tuple(cols)
        by_name = {e[0]: e for e in child.schema}
        self.schema = tuple(by_name[c] for c in cols)

    def with_children(self, kids):
        return Project(kids[0], self.cols)

    def host_rows(self) -> Optional[Tuple[int, int]]:
        return self.children[0].host_rows()

    def partitioning(self) -> Partitioning:
        kept = set(self.cols)
        return [s for s in self.children[0].partitioning() if set(s) <= kept]

    def ordering(self) -> Optional[Ordering]:
        return _ord.truncate_to(self.children[0].ordering(), self.cols)

    def col_stats(self) -> Dict[str, object]:
        kept = set(self.cols)
        return {
            n: v for n, v in self.children[0].col_stats().items()
            if n in kept
        }

    def _params(self) -> tuple:
        return (self.cols,)

    def label(self) -> str:
        return f"Project [{', '.join(self.cols)}]"


class Filter(Node):
    def __init__(self, child: Node, expr):
        missing = [c for c in sorted(expr.columns()) if c not in child.names]
        if missing:
            raise KeyError(f"filter references unknown columns: {missing}")
        self.children = (child,)
        self.expr = expr
        self.schema = child.schema

    def with_children(self, kids):
        return Filter(kids[0], self.expr)

    def partitioning(self) -> Partitioning:
        return self.children[0].partitioning()

    def ordering(self) -> Optional[Ordering]:
        return self.children[0].ordering()  # row subset keeps row order

    def col_stats(self) -> Dict[str, object]:
        # a row subset only shrinks ranges: the bounds stay conservative
        return self.children[0].col_stats()

    def _params(self) -> tuple:
        return (self.expr.key(),)

    def label(self) -> str:
        return f"Filter {self.expr!r}"


class WithColumns(Node):
    """Computed projection: the child's columns plus one column a
    ``(name, expression)`` pair, a name that exists replaced in place
    (lowered by ``plan.lower.eval_exprs``, one program over the rows)."""

    def __init__(self, child: Node, exprs: Sequence[Tuple[str, object]]):
        from ..dtypes import DataType
        from .expr import result_dtype

        exprs = tuple((str(n), e) for n, e in exprs)
        if len({n for n, _e in exprs}) != len(exprs):
            raise ValueError("with_columns names a column twice")
        for name, e in exprs:
            missing = [c for c in sorted(e.columns()) if c not in child.names]
            if missing:
                raise KeyError(
                    f"with_columns {name!r} reads unknown columns: {missing}"
                )
        self.children = (child,)
        self.exprs = exprs
        phys = {n: p for n, _t, p in child.schema}
        new = {}
        for name, e in exprs:
            p = result_dtype(e, phys.__getitem__)
            new[name] = (name, int(DataType.from_numpy_dtype(p).type), p)
        self.schema = tuple(
            [new.get(e[0], e) for e in child.schema]
            + [new[n] for n, _e in exprs if n not in phys]
        )

    @property
    def computed(self) -> FrozenSet[str]:
        return frozenset(n for n, _e in self.exprs)

    def with_children(self, kids):
        return WithColumns(kids[0], self.exprs)

    def host_rows(self) -> Optional[Tuple[int, int]]:
        return self.children[0].host_rows()

    def partitioning(self) -> Partitioning:
        return [
            s for s in self.children[0].partitioning()
            if not set(s) & self.computed
        ]

    def ordering(self) -> Optional[Ordering]:
        kept = [n for n in self.children[0].names if n not in self.computed]
        return _ord.truncate_to(self.children[0].ordering(), kept)

    def col_stats(self) -> Dict[str, object]:
        # rows untouched: the bounds of the columns that were not replaced
        return {
            n: v for n, v in self.children[0].col_stats().items()
            if n not in self.computed
        }

    def _params(self) -> tuple:
        return tuple((n, e.key()) for n, e in self.exprs)

    def label(self) -> str:
        spec = ", ".join(f"{n}={e!r}" for n, e in self.exprs)
        return f"WithColumns [{spec}]"


#: other spellings of the two join types that emit left rows only
_SEMI_SPELLINGS = {"left_semi": "semi", "left_anti": "anti"}
SEMI_HOWS = ("semi", "anti")


class Join(Node):
    """Equi-join. Output names are fixed at BUILD time from the full child
    schemas (``_suffix_names``, the eager Table.join convention) and kept
    through rewrites: lowering renames each side to its out-names before
    joining, so later column pruning cannot change the naming.

    ``how`` "semi" / "anti" (EXISTS / NOT EXISTS): the output is the LEFT
    child's schema under the left's own names (no suffix: nothing of the
    right side comes out), its rows a subset of the left's in their
    order."""

    def __init__(
        self,
        left: Node,
        right: Node,
        l_on: Sequence[str],
        r_on: Sequence[str],
        how: str = "inner",
        suffixes: Tuple[str, str] = ("_x", "_y"),
        _renames: Optional[Tuple[Dict[str, str], Dict[str, str]]] = None,
        emit_key_order: bool = False,
        semi_filter: Optional[str] = None,
        masks: Tuple[object, object] = (None, None),
        keep: Tuple[Optional[Tuple[str, ...]], Optional[Tuple[str, ...]]] = (
            None, None,
        ),
        hit_mask: bool = False,
        route: Optional[str] = None,
    ):
        self.children = (left, right)
        self.l_on = tuple(l_on)
        self.r_on = tuple(r_on)
        self.how = _SEMI_SPELLINGS.get(how.replace("-", "_").lower(), how)
        # set by the physicalize pass on a mesh: the side ("right" /
        # "left") this join gathers whole to every chip while the other
        # stays where it lies (``Table.distributed_join``'s replicate
        # route, by the same rule: :meth:`pick_route`). Such a join gets
        # no Shuffle under it and claims no placement; None: both sides
        # are hash-shuffled, or already lie right
        self.route = route
        # set by the semi_as_mask rewrite on a semi or anti join directly
        # under an aggregate: the join compacts nothing and hands its hit
        # mask, over the left side's rows where they lie, to the aggregate
        self.hit_mask = bool(hit_mask)
        self.suffixes = tuple(suffixes)
        # set by the join_mask rewrite: the predicate of a Filter that stood
        # directly under a side of this INNER join, over that side's column
        # names, now the side's row mask (``Table.join(_left_mask=...)``:
        # the same rows as filter-then-join, but nothing is compacted
        # before the join's keys-only semi-reduction has said which rows
        # have a partner). ``keep`` (set by projection pushdown) names the
        # columns of a side that go into the join, where its mask reads
        # columns nothing above the join does
        self.masks = tuple(masks)
        self.keep = tuple(None if k is None else tuple(k) for k in keep)
        # set by the order_reuse rewrite: lower with emit_order='key' so the
        # join's probe kv-sort doubles as the downstream op's key sort
        self.emit_key_order = bool(emit_key_order)
        # set by the semi_filter rewrite: which input sides' shuffles may
        # prune against the other side's key sketch ('both'/'left'/'right';
        # None = ineligible or disabled) — see ops/sketch.join_filter_sides
        self.semi_filter = semi_filter
        if _renames is not None:
            self.l_rename, self.r_rename = _renames
        elif self.how in SEMI_HOWS:
            self.l_rename = {n: n for n in left.names}
            self.r_rename = {n: n for n in right.names}
        else:
            lnames, rnames = left.names, right.names
            out = _suffix_names(lnames, rnames, suffixes)
            self.l_rename = dict(zip(lnames, out[: len(lnames)]))
            self.r_rename = dict(zip(rnames, out[len(lnames):]))
        schema = [(self.l_rename[n], t, p) for n, t, p in left.schema]
        if self.how not in SEMI_HOWS:
            schema += [(self.r_rename[n], t, p) for n, t, p in right.schema]
        self.schema = tuple(schema)

    def with_children(self, kids):
        return self.replaced(kids)

    def replaced(self, kids, **changes) -> "Join":
        """A copy over ``kids`` that keeps what the rewrites have written
        on this node (renames, emit order, semi filter, masks, kept
        columns), ``changes`` overriding."""
        notes = {
            "_renames": (self.l_rename, self.r_rename),
            "emit_key_order": self.emit_key_order,
            "semi_filter": self.semi_filter,
            "masks": self.masks, "keep": self.keep,
            "hit_mask": self.hit_mask, "route": self.route,
        }
        notes.update(changes)
        return Join(
            kids[0], kids[1], self.l_on, self.r_on, self.how, self.suffixes,
            **notes,
        )

    @property
    def l_key_out(self) -> Tuple[str, ...]:
        return tuple(self.l_rename[n] for n in self.l_on)

    @property
    def r_key_out(self) -> Tuple[str, ...]:
        return tuple(self.r_rename[n] for n in self.r_on)

    def pick_route(self) -> Optional[str]:
        """The side the join would replicate over its mesh, by
        ``ops.join.replicate_side`` over what the host holds of both
        inputs (:meth:`Node.host_rows`; a semi or anti join ships the
        right side's keys alone, as the eager join projects them first).
        Part of the plan's identity (:meth:`_params`): an executor built
        for one route never serves a pair of tables that takes the other."""
        from ..ops.join import replicate_side

        sizes = []
        for side, child in enumerate(self.children):
            got = child.host_rows()
            if got is None:
                return None
            if side == 1 and self.how in SEMI_HOWS:
                phys = {n: p for n, _t, p in child.schema}
                width = sum(np.dtype(phys[n]).itemsize for n in self.r_on)
            else:
                width = child.row_bytes()
            sizes.append((got[0], width))
            world = got[1]
        return replicate_side(self.how, sizes[0], sizes[1], world)

    def partitioning(self) -> Partitioning:
        left, right = self.children
        if self.route is not None:
            return []  # the big side's rows stay wherever they lay
        if self.how in SEMI_HOWS:
            return left.partitioning()  # a subset of its rows, where they lie
        l_ok = _placed_by(left.partitioning(), self.l_on)
        r_ok = _placed_by(right.partitioning(), self.r_on)
        if not (l_ok and r_ok):
            return []
        out: Partitioning = []
        # matched rows carry equal key values on both sides; unmatched
        # OUTER rows have nulls on the other side, so only the side whose
        # keys are never null co-locates the output (full outer: neither).
        # The tuples keep the SHUFFLE order (l_on/r_on order): that order
        # is the placement function both inputs were routed by.
        if self.how in ("inner", "left"):
            out.append(self.l_key_out)
        if self.how in ("inner", "right"):
            out.append(self.r_key_out)
        return out

    def ordering(self) -> Optional[Ordering]:
        if self.emit_key_order and self.how in ("inner", "left"):
            return Ordering(
                keys=self.l_key_out,
                ascending=(True,) * len(self.l_on),
                nulls_last=True, scope="shard", canonical=True,
                lexsort_exact=False,
            )
        if self.route == "left":
            # the join runs from the big side, the right one: ITS rows
            # repeat in their order (Table._join_replicated)
            return _ord.rename(self.children[1].ordering(), self.r_rename)
        if self.how in ("inner", "left") + SEMI_HOWS:
            # the emit repeats left rows in left order (semi, anti: keeps
            # some of them): the left input's descriptor survives, under
            # the join's output names
            return _ord.rename(self.children[0].ordering(), self.l_rename)
        return None

    def col_stats(self) -> Dict[str, object]:
        # every output VALUE comes from an input row (outer rows add
        # nulls, not values), so each side's bounds survive under the
        # join's output names
        out: Dict[str, object] = {}
        for n, v in self.children[0].col_stats().items():
            out[self.l_rename.get(n, n)] = v
        if self.how in SEMI_HOWS:
            return out
        for n, v in self.children[1].col_stats().items():
            out[self.r_rename.get(n, n)] = v
        return out

    def _params(self) -> tuple:
        # semi_filter is part of the plan identity: a cached executor that
        # lowers the filtered pair exchange must not serve an annotation-
        # free (or differently-sided) plan
        return (
            self.l_on, self.r_on, self.how, self.suffixes,
            tuple(sorted(self.l_rename.items())),
            tuple(sorted(self.r_rename.items())),
            self.emit_key_order, self.semi_filter,
            tuple(None if m is None else m.key() for m in self.masks),
            self.keep, self.hit_mask, self.route or self.pick_route(),
        )

    def label(self) -> str:
        keys = ", ".join(f"{a}={b}" for a, b in zip(self.l_on, self.r_on))
        tail = " emit=key-order" if self.emit_key_order else ""
        if self.route:
            tail += (
                f" route=replicate-{self.route} [the {self.route} side"
                " gathered to every chip, the other not moved]"
            )
        if self.semi_filter:
            tail += f" semi-filter={self.semi_filter}"
        for side, m in zip(("left", "right"), self.masks):
            if m is not None:
                tail += f" {side}-mask {m!r}"
        if self.hit_mask:
            tail += (
                " [semi_as_mask: no row compacted, the hit mask goes to the"
                " aggregate]"
            )
        elif self.how in SEMI_HOWS:
            tail += " [capacity: keys only, then round_cap of the kept rows]"
        elif any(m is not None for m in self.masks):
            tail += (
                " [capacity: semi-reduce, then round_cap of the counted rows]"
            )
        return f"Join how={self.how} on [{keys}]{tail}"


class GroupBy(Node):
    """Aggregates a group of equal ``keys``; with no key (``LazyFrame.agg``)
    one row over the whole table, also where no row passes."""

    def __init__(
        self,
        child: Node,
        keys: Sequence[str],
        aggs: Sequence[Tuple[str, str]],
        sorted_input: bool = False,
        mask=None,
        partial: bool = False,
    ):
        self.children = (child,)
        self.keys = tuple(keys)
        self.aggs = tuple(aggs)  # [(value column, op name)]
        # set by the filter_as_mask rewrite: the predicate of a Filter that
        # stood directly under this node, now the aggregate's row mask
        # (``Table.groupby(_mask=...)``: the same groups and aggregates as
        # filter-then-groupby, without compacting the rows first)
        self.mask = mask
        # annotation set by the order_reuse rewrite: the child provably
        # emits key order, so lowering's eager groupby will run-detect
        # instead of lexsorting (the eager gate re-verifies — the plan
        # claim is advisory, the kernel choice is the table's)
        self.sorted_input = bool(sorted_input)
        # set by the partial_aggregate rewrite on a mesh: the dense plan
        # takes this aggregate, so no Shuffle stands under it; each shard
        # reduces its own rows and the partial states are combined in
        # place (``Table.distributed_groupby``), the groups emitted once,
        # on the first shard, in key order. Lowering holds the result to
        # that, whatever path the table then takes
        self.partial = bool(partial)
        by_name = {e[0]: e for e in child.schema}
        out = [by_name[k] for k in keys]
        for c, op in self.aggs:
            _, t, p = by_name[c]
            out.append((f"{c}_{op}",) + _agg_out_dtype(op, t, p))
        self.schema = tuple(out)

    def replaced(self, child: Node, **changes) -> "GroupBy":
        """A copy over ``child`` with ``changes`` to the annotations."""
        kw = dict(
            sorted_input=self.sorted_input, mask=self.mask,
            partial=self.partial,
        )
        kw.update(changes)
        return GroupBy(child, self.keys, self.aggs, **kw)

    def with_children(self, kids):
        return self.replaced(kids[0])

    def partitioning(self) -> Partitioning:
        if self.partial:
            return []  # every group on the first shard: no claim needed
        kept = set(self.keys)
        return [s for s in self.children[0].partitioning() if set(s) <= kept]

    def ordering(self) -> Optional[Ordering]:
        if not self.keys:
            return None  # one row: nothing to order by
        # groups emit in canonical key order (factorize id order). A
        # partial aggregate's lie on ONE shard, so the order is global,
        # and a slot table's null keys all carry one payload (the slot
        # past the span), so a lexsort of it is the identity: the claim
        # that lets order_reuse drop a Sort and its range shuffle
        return Ordering(
            keys=self.keys, ascending=(True,) * len(self.keys),
            nulls_last=True, scope="global" if self.partial else "shard",
            canonical=True, lexsort_exact=self.partial,
        )

    def col_stats(self) -> Dict[str, object]:
        kept = set(self.keys)
        return {
            n: v for n, v in self.children[0].col_stats().items()
            if n in kept
        }

    def _params(self) -> tuple:
        return (
            self.keys, self.aggs, self.sorted_input,
            None if self.mask is None else self.mask.key(), self.partial,
        )

    def label(self) -> str:
        spec = ", ".join(f"{op}({c})" for c, op in self.aggs)
        tail = (
            " [input key-ordered: groupby lexsort elided]"
            if self.sorted_input else ""
        )
        if self.partial:
            tail += (
                " [partial aggregate: combined in place, no shuffle,"
                " groups on the first shard]"
            )
        if self.mask is not None:
            tail += f" mask {self.mask!r}"
        head = f"GroupBy [{', '.join(self.keys)}]" if self.keys else "Aggregate"
        return f"{head} agg [{spec}]{tail}"


class Sort(Node):
    """Local (per-shard) sort; a preceding range Shuffle makes it global."""

    def __init__(self, child: Node, by: Sequence[str], ascending: Sequence[bool]):
        self.children = (child,)
        self.by = tuple(by)
        self.ascending = tuple(bool(a) for a in ascending)
        self.schema = child.schema

    def with_children(self, kids):
        return Sort(kids[0], self.by, self.ascending)

    def partitioning(self) -> Partitioning:
        return self.children[0].partitioning()

    def ordering(self) -> Optional[Ordering]:
        # canonical is a mask-dependent property the plan can't see; the
        # identity claim (lexsort_exact) is what the sort-elision rule needs
        child = self.children[0]
        scope = "shard"
        if (
            isinstance(child, Shuffle) and child.kind == "range"
            and child.keys == (self.by[0],)
            and child.asc0 == self.ascending[0]
        ):
            scope = "global"  # the sample-sort recipe
        return Ordering(
            keys=self.by, ascending=self.ascending, nulls_last=True,
            scope=scope, canonical=False, lexsort_exact=True,
        )

    def col_stats(self) -> Dict[str, object]:
        return self.children[0].col_stats()  # a permutation of the rows

    def _params(self) -> tuple:
        return (self.by, self.ascending)

    def label(self) -> str:
        return f"Sort by [{', '.join(self.by)}] asc={list(self.ascending)}"


class Shuffle(Node):
    """Physical re-partition over the mesh: hash (relational ops) or range
    (global sort). Inserted by the physicalizer; the redundant-shuffle rule
    removes it when the child already co-locates the keys."""

    def __init__(self, child: Node, keys: Sequence[str], kind: str = "hash",
                 asc0: bool = True):
        self.children = (child,)
        self.keys = tuple(keys)
        self.kind = kind
        self.asc0 = bool(asc0)
        self.schema = child.schema

    def with_children(self, kids):
        return Shuffle(kids[0], self.keys, self.kind, self.asc0)

    def partitioning(self) -> Partitioning:
        if self.kind == "hash":
            return [self.keys]
        return []  # range partitions co-locate ranges, not equal tuples

    def col_stats(self) -> Dict[str, object]:
        return self.children[0].col_stats()  # rows reroute, values don't

    def _params(self) -> tuple:
        return (self.keys, self.kind, self.asc0)

    def label(self) -> str:
        return f"Shuffle {self.kind} [{', '.join(self.keys)}]"


class Union(Node):
    """Distinct set-union (Table.union semantics)."""

    def __init__(self, left: Node, right: Node):
        if left.names != right.names:
            raise ValueError(
                f"union requires identical schemas: {left.names} vs {right.names}"
            )
        self.children = (left, right)
        self.schema = left.schema

    def with_children(self, kids):
        return Union(kids[0], kids[1])

    def partitioning(self) -> Partitioning:
        # local distinct-union keeps rows on their shard: co-location sets
        # holding on BOTH inputs survive
        l = self.children[0].partitioning()
        r = self.children[1].partitioning()
        return [s for s in l if s in r]

    def label(self) -> str:
        return "Union"


class Limit(Node):
    """First ``n`` rows in global row order (lowers to Table.take, which
    re-splits evenly across shards — so partitioning is lost)."""

    def __init__(self, child: Node, n: int):
        self.children = (child,)
        self.n = int(n)
        self.schema = child.schema

    def with_children(self, kids):
        return Limit(kids[0], self.n)

    def _params(self) -> tuple:
        return (self.n,)

    def label(self) -> str:
        return f"Limit {self.n}"


class TopK(Node):
    """The first ``n`` rows of the global order by ``by``: what
    ``Limit(Sort(x))`` means, made by the ``topk`` rewrite and lowered to
    ``Table.topk``, which orders the key lanes alone, gathers ``n`` rows
    and fetches no row count. Rows re-split like Limit's, so partitioning
    is lost; the rows come out in the requested order."""

    def __init__(self, child: Node, by: Sequence[str],
                 ascending: Sequence[bool], n: int):
        self.children = (child,)
        self.by = tuple(by)
        self.ascending = tuple(bool(a) for a in ascending)
        self.n = int(n)
        self.schema = child.schema

    def with_children(self, kids):
        return TopK(kids[0], self.by, self.ascending, self.n)

    def _params(self) -> tuple:
        return (self.by, self.ascending, self.n)

    def label(self) -> str:
        return (
            f"TopK {self.n} by [{', '.join(self.by)}] "
            f"asc={list(self.ascending)}"
        )


class FusedJoinGroupBySum(Node):
    """INNER join + groupby-SUM(left value) BY the join key, collapsed into
    ``ops.join.join_sum_by_key_pushdown`` (one merged kv-sort instead of the
    join emit + groupby sort chain; >3x by the roofline model). Produced by
    the ``fused_join_groupby`` rewrite; children are the join's children
    (including any planner-inserted Shuffles)."""

    def __init__(
        self,
        left: Node,
        right: Node,
        l_on: Sequence[str],
        r_on: Sequence[str],
        val_col: str,               # LEFT-side source column being summed
        out_keys: Sequence[str],    # output key names, groupby key order
        key_order: Sequence[int],   # join-key-pair index for each out key
        out_val: str,
        val_dtype: Tuple[int, str],
        semi_filter: Optional[str] = None,
    ):
        self.children = (left, right)
        self.l_on = tuple(l_on)
        self.r_on = tuple(r_on)
        self.val_col = val_col
        self.out_keys = tuple(out_keys)
        self.key_order = tuple(key_order)
        self.out_val = out_val
        # the fused node IS an inner join: the semi_filter rewrite may mark
        # both input shuffles prunable, exactly like Join
        self.semi_filter = semi_filter
        lby = {e[0]: e for e in left.schema}
        entries = []
        for name, ki in zip(self.out_keys, self.key_order):
            _, t, p = lby[self.l_on[ki]]
            entries.append((name, t, p))
        entries.append((out_val,) + tuple(val_dtype))
        self.schema = tuple(entries)

    def with_children(self, kids):
        return FusedJoinGroupBySum(
            kids[0], kids[1], self.l_on, self.r_on, self.val_col,
            self.out_keys, self.key_order, self.out_val,
            self.schema[-1][1:], semi_filter=self.semi_filter,
        )

    def partitioning(self) -> Partitioning:
        left, right = self.children
        if _placed_by(left.partitioning(), self.l_on) and _placed_by(
            right.partitioning(), self.r_on
        ):
            # placement order is the join-pair order, not groupby order
            pair_names = [None] * len(self.l_on)
            for name, ki in zip(self.out_keys, self.key_order):
                pair_names[ki] = name
            return [tuple(pair_names)]
        return []

    def ordering(self) -> Optional[Ordering]:
        # join_sum_by_key_pushdown numbers groups over the merged kv-sort:
        # canonical key order, keys in join-pair order
        pair_names = [None] * len(self.l_on)
        for name, ki in zip(self.out_keys, self.key_order):
            pair_names[ki] = name
        return Ordering(
            keys=tuple(pair_names), ascending=(True,) * len(pair_names),
            nulls_last=True, scope="shard", canonical=True,
            lexsort_exact=False,
        )

    def _params(self) -> tuple:
        return (
            self.l_on, self.r_on, self.val_col, self.out_keys,
            self.key_order, self.out_val, self.semi_filter,
        )

    def label(self) -> str:
        keys = ", ".join(f"{a}={b}" for a, b in zip(self.l_on, self.r_on))
        tail = f" semi-filter={self.semi_filter}" if self.semi_filter else ""
        return (
            f"FusedJoinGroupBySum on [{keys}] sum({self.val_col}) "
            f"-> join_sum_by_key_pushdown{tail}"
        )


def _covers(partitioning: Partitioning, keys: set) -> bool:
    """Single-table co-location: some guaranteed co-location column set is
    a subset of ``keys`` — equal key tuples agree on that subset, hence
    share a shard. NOT sufficient for two-table consumers (see _placed_by:
    both sides must agree on the exact placement function)."""
    return any(set(s) <= keys for s in partitioning)


def _placed_by(partitioning: Partitioning, keys: Tuple[str, ...]) -> bool:
    """Two-table-consumer check: the input is already placed by EXACTLY the
    shuffle's ordered key tuple, i.e. the same hash placement the other
    side will be routed by. A subset placement (hash of fewer columns)
    co-locates rows but routes them to DIFFERENT shards than a fresh hash
    of the full tuple — eliding on a subset would silently drop matches."""
    return any(tuple(s) == tuple(keys) for s in partitioning)


def _agg_out_dtype(op: str, t: int, p: str) -> Tuple[int, str]:
    """Approximate output dtype of one aggregate (display + fingerprint
    only; lowering defers to the eager kernels' real promotion)."""
    from ..dtypes import Type

    if op in ("count", "nunique"):
        return int(Type.INT64), "int64"
    if op in ("mean", "var", "std", "quantile", "median"):
        return int(Type.DOUBLE), "float64"
    if op == "sum" and not p.startswith("float"):
        return int(Type.INT64), "int64"
    return t, p
