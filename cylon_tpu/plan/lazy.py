"""LazyFrame: the user-facing lazy query surface.

``Table.lazy()`` / ``DataFrame.lazy()`` return a :class:`LazyFrame`; each
method appends a logical node; nothing executes until ``.collect()``, which
optimizes (rules.py), lowers (lower.py) and runs — with the whole
optimize+lower product cached in ``engine.py`` under the plan's structural
fingerprint, so repeated collects of the same plan shape skip straight to
execution (and the eager kernels underneath hit the jit cache: no
recompile). ``.explain()`` shows the pre- and post-rewrite plans and which
rules fired.
"""
from __future__ import annotations

import time as _time
from typing import Dict, List, Optional, Sequence, Tuple, Union as TUnion

from ..engine import PlanEntry, plan_executable
from ..obs import metrics as _obsmetrics
from ..obs import store as _obsstore
from ..obs import trace as _obstrace
from ..utils.tracing import bump, span
from . import feedback as _feedback
from . import lower as _lower
from . import rules as _rules
from .expr import Col, Expr, col
from .nodes import (
    Filter,
    GroupBy,
    Join,
    Limit,
    Node,
    Project,
    Scan,
    Sort,
    Union,
    WithColumns,
)


def _as_list(x) -> List[str]:
    if isinstance(x, str):
        return [x]
    return list(x)


def gated_fingerprint(plan: Node) -> tuple:
    """The executable identity of a plan: its structural fingerprint plus
    the ordering / semi-filter / lane-packing escape-hatch gate states.
    The gates change which rewrites fire and which kernels the lowered
    ops pick, so they are part of the identity — a mid-process env flip
    must re-optimize, never reuse a cached executor built under the
    other gate state. The ONE copy of this recipe: ``_executable`` keys
    the plan cache with it and the serving scheduler groups/keys batches
    with it (graft-lint L1 sees the gate reads threaded into both cache
    keys through this carrier)."""
    from ..ops.quant import gate_state as _quant_gate
    from ..ops.sketch import enabled as _semi_enabled
    from ..ops.stats import enabled as _pack_enabled
    from ..ordering import enabled as _ord_enabled
    from ..parallel.spill import gate_state as _spill_gate
    from ..parallel.topo import gate_state as _topo_gate

    # the spill component carries the forced-tier knob and the skew-split
    # gate: both are host dispatch policy, but a cached executor's lowered
    # shuffles re-read them per run THROUGH this identity — a flip must
    # re-enter the cache, never serve a result staged under the other
    # tier/schedule regime. The quant component carries the lossy-wire
    # kill switch + tolerance: the tolerance decides every lowered
    # shuffle's codec picks, so a flip (including turning the tier on)
    # re-optimizes and re-keys the serving batch cache instead of
    # aliasing an exact-wire executor
    # the topo component carries the 2-D topology kill switch + the
    # CYLON_TPU_MESH declaration: together with the per-context
    # mesh_shape (which rides the shuffle kernel cache keys), they
    # decide whether every lowered exchange is flat or two-hop — a
    # mid-process flip re-optimizes instead of aliasing a two-hop
    # executor onto a flat run (parallel/topo.py)
    base = (
        plan.fingerprint(), _ord_enabled(), _semi_enabled(), _pack_enabled(),
        _spill_gate(), _quant_gate(), _topo_gate(),
    )
    # the feedback component: (autotune active, tuned Decisions) — every
    # telemetry-driven override (shuffle budget, semi mode, serve bucket,
    # spill tier) is part of the executable identity, so a decision flip
    # recompiles exactly once and never aliases; the observation store is
    # keyed by `base` (WITHOUT this component) so flips keep feeding one
    # profile (plan/feedback.py)
    return base + (_feedback.fingerprint_component(base),)


def _normalize_aggs(agg: Dict[str, TUnion[str, Sequence[str]]]) -> List[Tuple[str, str]]:
    out: List[Tuple[str, str]] = []
    for c, ops in agg.items():
        ops_list = ops if isinstance(ops, (list, tuple)) else [ops]
        for o in ops_list:
            if not isinstance(o, str):
                raise TypeError(f"agg op must be a string name, got {o!r}")
            out.append((c, o))
    return out


class LazyFrame:
    """A deferred query plan over :class:`~cylon_tpu.table.Table` inputs."""

    def __init__(self, plan: Node, ctx):
        self._plan = plan
        self._ctx = ctx

    # -- construction ------------------------------------------------------
    @classmethod
    def from_table(cls, table) -> "LazyFrame":
        return cls(Scan(table), table.ctx)

    def _wrap(self, node: Node) -> "LazyFrame":
        return LazyFrame(node, self._ctx)

    # -- introspection -----------------------------------------------------
    @property
    def columns(self) -> List[str]:
        return self._plan.names

    @property
    def plan(self) -> Node:
        return self._plan

    def __repr__(self):
        return f"LazyFrame[{', '.join(self.columns)}]\n{self._plan.render()}"

    # -- plan builders -----------------------------------------------------
    def filter(self, predicate: Expr) -> "LazyFrame":
        """Keep rows where the :mod:`~cylon_tpu.plan.expr` predicate is true
        (null predicate rows drop, pandas-style)."""
        if not isinstance(predicate, Expr):
            raise TypeError(
                "LazyFrame.filter takes a plan expression, e.g. "
                "filter(col('a') > 3) — opaque callables would be invisible "
                "to the optimizer"
            )
        return self._wrap(Filter(self._plan, predicate))

    def select(self, columns: TUnion[str, Sequence[str]], *more: str) -> "LazyFrame":
        items = (
            [columns] if isinstance(columns, (str, Col)) else list(columns)
        ) + list(more)
        cols = [c.name if isinstance(c, Col) else c for c in items]
        return self._wrap(Project(self._plan, cols))

    def with_columns(self, exprs: Dict[str, Expr]) -> "LazyFrame":
        """Add computed columns, one a ``name -> expression`` entry:
        ``with_columns({"net": col("price") * (1 - col("disc"))})``. A
        name that exists is replaced in place; an aggregate over an
        expression is a ``groupby`` over the computed column."""
        for name, e in exprs.items():
            if not isinstance(e, Expr):
                raise TypeError(
                    f"with_columns[{name!r}] takes a plan expression, "
                    f"got {type(e).__name__}"
                )
        return self._wrap(WithColumns(self._plan, list(exprs.items())))

    def join(
        self,
        other: "LazyFrame",
        on: Optional[TUnion[str, Sequence[str]]] = None,
        how: str = "inner",
        left_on: Optional[TUnion[str, Sequence[str]]] = None,
        right_on: Optional[TUnion[str, Sequence[str]]] = None,
        suffixes: Tuple[str, str] = ("_x", "_y"),
    ) -> "LazyFrame":
        """An equi-join plan node; ``how`` as :meth:`Table.join` takes it:
        inner, left, right, outer, and **semi** / **anti** (also
        ``left_semi`` / ``left_anti``): the rows of this frame that have
        (have no) partner in ``other`` on the keys, EXISTS / NOT EXISTS.
        A semi or anti join's output is this frame's columns under their
        own names (no suffix), each row at most once however many partners
        it has, in this frame's order; a null key has no partner (semi
        drops the row, anti keeps it: NOT EXISTS, not NOT IN). Filters on
        either side ride such a join as row masks (``join_mask``), and
        directly under a ``groupby`` the dense plan takes, or under
        :meth:`agg`, the join compacts nothing: its hit mask is the
        aggregate's row mask (``semi_as_mask`` in ``explain()``)."""
        if not isinstance(other, LazyFrame):
            raise TypeError("join expects another LazyFrame (use .lazy())")
        if other._ctx is not self._ctx:
            raise ValueError("cannot join LazyFrames from different contexts")
        if on is not None:
            if left_on is not None or right_on is not None:
                raise ValueError("pass either on= or left_on/right_on, not both")
            l_on = r_on = _as_list(on)
        else:
            if left_on is None or right_on is None:
                raise ValueError("join needs on= or both left_on/right_on")
            l_on, r_on = _as_list(left_on), _as_list(right_on)
            if len(l_on) != len(r_on):
                raise ValueError("left_on/right_on length mismatch")
        return self._wrap(
            Join(self._plan, other._plan, l_on, r_on, how, suffixes)
        )

    def groupby(
        self,
        by: TUnion[str, Sequence[str]],
        agg: Optional[Dict[str, TUnion[str, Sequence[str]]]] = None,
    ):
        """With ``agg``: a GroupBy plan node (column naming matches eager
        ``Table.groupby``: ``col_op``). Without: a :class:`LazyGroupBy`
        builder (``.agg()/.sum()/...``)."""
        keys = _as_list(by)
        if agg is None:
            return LazyGroupBy(self, keys)
        return self._wrap(GroupBy(self._plan, keys, _normalize_aggs(agg)))

    def agg(
        self, spec: Dict[str, TUnion[str, Sequence[str]]]
    ) -> "LazyFrame":
        """Aggregates over the whole frame, no keys: ``filter(p).agg({"x":
        "sum"})`` is ``select sum(x) where p``. Ops: sum, count, min, max,
        mean; columns are named ``col_op`` as in :meth:`groupby`. Exactly
        one row comes back, also where no row passes: ``count`` 0 and
        every other aggregate null, as SQL has it. A filter below rides
        the reductions as their row mask, and on a mesh each shard's
        partial state is combined in place (``partial_aggregate``)."""
        from ..ops import groupby as _g

        aggs = _normalize_aggs(spec)
        bad = sorted({
            op for _c, op in aggs if _g.agg_op_id(op) not in _g.DENSE_OPS
        })
        if bad:
            raise ValueError(
                "LazyFrame.agg takes sum, count, min, max and mean; group "
                f"by a key for {bad}"
            )
        return self._wrap(GroupBy(self._plan, (), aggs))

    def sort(
        self,
        by: TUnion[str, Sequence[str]],
        ascending: TUnion[bool, Sequence[bool]] = True,
    ) -> "LazyFrame":
        keys = _as_list(by)
        asc = [ascending] * len(keys) if isinstance(ascending, bool) else list(ascending)
        if len(asc) != len(keys):
            raise ValueError("ascending length must match sort keys")
        return self._wrap(Sort(self._plan, keys, asc))

    def union(self, other: "LazyFrame") -> "LazyFrame":
        if other._ctx is not self._ctx:
            raise ValueError("cannot union LazyFrames from different contexts")
        return self._wrap(Union(self._plan, other._plan))

    def limit(self, n: int) -> "LazyFrame":
        return self._wrap(Limit(self._plan, n))

    def head(self, n: int = 5) -> "LazyFrame":
        return self.limit(n)

    # -- execution ---------------------------------------------------------
    def explain(self, analyze: bool = False) -> str:
        """Pre-rewrite plan, post-rewrite plan, and the rules that fired.

        Each node line carries its derived order property (``-- order:
        [k asc] @shard`` — ``Node.ordering()``, the sortedness analog of
        partitioning); an ``order_reuse`` firing shows up as a dropped Sort
        or a ``Join ... emit=key-order`` + ``GroupBy ... [input
        key-ordered: groupby lexsort elided]`` pair.

        ``analyze=True`` RUNS the plan (through the same cached executor
        the production path uses) under a forced query trace and prints
        the optimized tree annotated per node with measured wall time
        (total and self), rows in/out, collective MB shipped, and which
        adaptive gates engaged (semi-filter, wire narrowing, ordering
        elisions, plan-cache hit/miss) — the EXPLAIN ANALYZE of this
        engine. Diagnostic by design: every node's result is
        materialized for exact row counts, so an analyzed run performs
        per-node host syncs the production ``dispatch()`` path never
        does (that path stays pinned at exactly 1 — graft-lint's
        ``q3_dispatch`` contract)."""
        if analyze:
            return self._explain_analyze()
        opt, fired = _rules.optimize(self._plan, self._ctx.world_size)
        lines = ["== Logical plan ==", self._plan.render(), "",
                 "== Optimized plan ==", opt.render(), ""]
        lines.append(_fired_line(fired))
        return "\n".join(lines)

    @_obstrace.op("collect")
    def collect(self):
        """Optimize, lower and execute the plan; returns an eager Table
        with host-known row counts (the result's deferred count lane is
        materialized — ONE host sync — before returning)."""
        t = self.dispatch()
        t._materialize()
        return t

    def collect_async(self, block: bool = True):
        """Submit this plan to the context's serving scheduler; returns a
        :class:`~cylon_tpu.serve.QueryFuture` immediately.

        The submit path only enqueues — it performs ZERO host syncs and
        ZERO execution (graft-lint pins ``LazyFrame.collect_async`` =
        DISPATCH_SAFE); the scheduler's worker runs the sync-free
        ``dispatch()`` machinery, batching same-fingerprint plans over
        different parameter bindings into one stacked device program, and
        ``QueryFuture.result()`` is the single deferred materialize. So a
        caller overlaps N in-flight queries on one device stream::

            futs = [q.collect_async() for q in queries]   # admission-gated
            tables = [f.result() for f in futs]           # one sync each

        ``block=False`` sheds with :class:`~cylon_tpu.serve
        .ServeOverloadError` instead of waiting when admission control
        (``CYLON_TPU_SERVE_INFLIGHT_BYTES`` / ``_QUEUE_DEPTH``) is at
        capacity."""
        from ..serve.scheduler import submit as _serve_submit

        return _serve_submit(self, block=block)

    def _executable(self):
        """Optimize+lower through the plan-fingerprint cache: returns
        ``(tables, fingerprint, PlanEntry, hit)`` — the ONE copy of the
        compile/cache recipe shared by ``dispatch()`` and
        ``explain(analyze=True)``. The entry carries the precomputed
        histogram key (``PlanEntry.hist_key``), so a cache hit performs
        zero fingerprint hashing."""
        ctx = self._ctx
        tables = _lower.scan_tables(self._plan)
        fingerprint = gated_fingerprint(self._plan)

        def compile_plan():
            with span("plan.optimize"):
                opt, fired = _rules.optimize(self._plan, ctx.world_size)
            with span("plan.lower"):
                # detach first: the cached executor must hold frozen scan
                # ordinals and no table references (lower.detach_scans)
                opt = _lower.detach_scans(opt)
                fn = _lower.build_executor(opt)
            return PlanEntry(
                opt, tuple(fired), fn,
                _obsmetrics.fingerprint_key(fingerprint),
                _feedback.base_key(fingerprint[:-1]),
            )

        entry, hit = plan_executable(ctx, fingerprint, compile_plan)
        return tables, fingerprint, entry, hit

    @_obstrace.op("dispatch")
    def dispatch(self):
        """Execute the plan WITHOUT the result-count host sync — the
        ``collect_async`` precursor for concurrent query serving.

        Every lowered single-dispatch eager op defers its count fetch, so
        the whole chain is queued on the device with ZERO host syncs (for
        sync-free plan shapes, e.g. the fused q3 join->groupby-SUM) and
        the returned Table's buffers may still be in flight. Its row
        counts materialize — the ONE host sync, attributed to
        ``_materialize_counts`` — on first access (``row_counts`` /
        ``to_pydict`` / ...). graft-lint pins this: the ``q3_dispatch``
        contract (analysis/contracts.py) requires exactly one sync, at
        result fetch, both statically (L3 sync budgets) and at runtime
        (the monitored fetch census).

        Telemetry: each dispatch opens a query trace (when tracing is
        enabled — two concurrent dispatches build two DISJOINT span
        trees via the contextvar context) and ALWAYS observes its
        dispatch-to-count-fetch latency into the plan-fingerprint
        histogram (``obs.metrics``) — the end time rides the deferred
        materialization, never an extra sync."""
        t_q = _time.perf_counter()
        with _obstrace.query_trace(
            type(self._plan).__name__, kind="plan"
        ):
            tables, fingerprint, entry, hit = self._executable()
            opt, fired, fn = entry.opt, entry.fired, entry.fn
            if hit:
                # cached optimize+lower: emit the spans anyway so every
                # collect is visible in tracing.report() (at ~zero cost)
                with span("plan.optimize"):
                    pass
                with span("plan.lower"):
                    pass
            for f in fired:
                bump(f"plan.rule.{f}")
            # apply the tuned decisions the executor was keyed under and
            # collect this execution's gate observations for the store
            # (both no-ops when autotune/the store are off)
            with _feedback.applying(fingerprint[-1]), \
                    _obsstore.exec_obs(entry.obs_key):
                with span("plan.execute"):
                    out = fn(tables)
            _obstrace.attach_result(
                out, hist_key=entry.hist_key, obs_key=entry.obs_key,
                label=opt.label(), t0=t_q,
            )
            return out

    def _explain_analyze(self) -> str:
        """Run the plan through the cached executor under a forced query
        trace with per-node materialization, then render the optimized
        tree annotated from the measured span tree."""
        t_q = _time.perf_counter()
        tables, fingerprint, entry, hit = self._executable()
        opt, fired, fn = entry.opt, entry.fired, entry.fn
        with _obstrace.analyze_mode():
            with _obstrace.query_trace(
                type(self._plan).__name__, kind="explain", force=True,
            ) as q:
                # same tuned decisions the executor was keyed under —
                # an analyzed run must execute the regime it annotates
                with _feedback.applying(fingerprint[-1]), \
                        _obsstore.exec_obs(entry.obs_key):
                    with span("plan.execute"):
                        out = fn(tables)
                # fingerprint deliberately NOT passed: an analyzed run's
                # per-node diagnostic syncs (+ compile on a cache miss)
                # must never land a sample in the fingerprint histogram
                # that serving p50/p99 reads — only the trace end time
                # rides the deferred resolution here
                _obstrace.attach_result(out, label=opt.label(), t0=t_q)
                out._materialize()
        lines = [
            "== Logical plan ==", self._plan.render(), "",
            "== Analyzed plan (executed) ==",
            _render_analyzed(opt, q), "",
            _fired_line(fired),
        ]
        tuned = _feedback.describe(fingerprint[:-1])
        lines.append(
            "Tuned gates:" + ("" if tuned else " (none)")
        )
        lines.extend(f"  {t}" for t in tuned)
        lines.append(
            f"Plan fingerprint: {entry.hist_key}"
            f"  plan-cache {'hit' if hit else 'miss'}"
            f"  total {q.wall_s() * 1e3:.1f} ms"
            f"  rows out {out.row_count}"
        )
        return "\n".join(lines)


# ----------------------------------------------------------------------
# explain(analyze=True) rendering helpers
# ----------------------------------------------------------------------
#: counter families rendered as per-node "gates": the engine's adaptive
#: decisions, attributable to the node whose execution made them
_GATE_PREFIXES = (
    "ordering.", "shuffle.semi_filter.", "lane_pack.", "plan.cache.",
    # the spill planner's per-node decisions: tier engagement and
    # skew-split relays render beside coll MB on the owning node's line
    "shuffle.skew_split", "shuffle.spill.shuffles",
    "shuffle.spill.staged_rounds",
)


def _fired_line(fired) -> str:
    if not fired:
        return "Rewrites fired: (none)"
    counts: Dict[str, int] = {}
    for f in fired:
        counts[f] = counts.get(f, 0) + 1
    return "Rewrites fired: " + ", ".join(
        f"{k} x{v}" for k, v in sorted(counts.items())
    )


def _node_exclusive(sp) -> Dict:
    """Per-node EXCLUSIVE aggregation over one ``plan.node.*`` span's
    subtree, stopping at nested ``plan.node.*`` spans (their bytes and
    gate decisions belong to the child's rendered line): collective
    bytes shipped, gate-decision counters, and the summed wall of the
    direct child-node spans (for self-time)."""
    agg = {"coll": 0, "gates": {}, "child_wall": 0.0}

    def fold(s, top: bool) -> None:
        if not top and s.name.startswith("plan.node."):
            agg["child_wall"] += s.dur_s()
            return
        v = s.attrs.get("coll_bytes")
        if isinstance(v, (int, float)):
            agg["coll"] += int(v)
        for name, cr in s.counters.items():
            if name.startswith(_GATE_PREFIXES):
                agg["gates"][name] = agg["gates"].get(name, 0) + cr[0]
        for c in s.children:
            fold(c, False)

    fold(sp, True)
    return agg


def _render_analyzed(root, q) -> str:
    """The optimized tree, each line annotated from its measured
    ``plan.node`` span: wall/self ms, rows in->out, coll MB, the
    critical-path share (obs/prof.py longest self-time root-to-leaf
    attribution — "crit 0%" marks a node OFF the critical path), and
    gates."""
    from ..obs import prof as _prof

    order = _lower.plan_order(root)
    by_id: Dict[int, object] = {}
    for sp in q.all_spans():
        nid = sp.attrs.get("node_id")
        if nid is not None and sp.name.startswith("plan.node."):
            by_id[nid] = sp
    crit = _prof.node_crit_shares(q)
    lines: List[str] = []

    def walk(n, indent: int) -> None:
        prefix = "  " * indent + n.line()
        sp = by_id.get(order[id(n)])
        if sp is None:
            lines.append(prefix)
        else:
            agg = _node_exclusive(sp)
            wall = sp.dur_s() * 1e3
            self_ms = max(wall - agg["child_wall"] * 1e3, 0.0)
            parts = [f"{wall:.1f} ms (self {self_ms:.1f})"]
            rows_out = sp.attrs.get("rows_out")
            if rows_out is not None:
                if n.children:
                    # a span-less child (e.g. a Shuffle peeled into the
                    # join recipe) contributes its own spanned inputs
                    def rows_of(c) -> int:
                        csp = by_id.get(order[id(c)])
                        if csp is not None:
                            return int(csp.attrs.get("rows_out") or 0)
                        return sum(rows_of(g) for g in c.children)

                    rows_in = sum(rows_of(c) for c in n.children)
                    parts.append(f"rows={rows_in}->{rows_out}")
                else:
                    parts.append(f"rows={rows_out}")
            if agg["coll"]:
                parts.append(f"coll={agg['coll'] / 1e6:.2f} MB")
            if id(sp) in crit:
                parts.append(f"crit {crit[id(sp)] * 100:.0f}%")
            if agg["gates"]:
                parts.append(
                    "gates["
                    + ", ".join(
                        f"{k} x{v}" if v > 1 else k
                        for k, v in sorted(agg["gates"].items())
                    )
                    + "]"
                )
            lines.append(prefix + "  ** " + "  ".join(parts))
        for c in n.children:
            walk(c, indent + 1)

    walk(root, 0)
    return "\n".join(lines)


class LazyGroupBy:
    """``lf.groupby('k')`` builder: ``.agg({...})`` or a shortcut reducer."""

    def __init__(self, frame: LazyFrame, keys: List[str]):
        self._frame = frame
        self._keys = keys

    def agg(self, spec: Dict[str, TUnion[str, Sequence[str]]]) -> LazyFrame:
        return self._frame.groupby(self._keys, spec)

    def _all_values(self, op: str) -> LazyFrame:
        vals = [c for c in self._frame.columns if c not in self._keys]
        return self.agg({c: op for c in vals})

    def sum(self) -> LazyFrame:
        return self._all_values("sum")

    def min(self) -> LazyFrame:
        return self._all_values("min")

    def max(self) -> LazyFrame:
        return self._all_values("max")

    def mean(self) -> LazyFrame:
        return self._all_values("mean")

    def count(self) -> LazyFrame:
        return self._all_values("count")


_ = col  # re-exported via plan/__init__
