"""The machine-readable collective/host-sync contract table.

This is the single source of truth for "how many collectives may this
path issue, and where may it touch the host". The hand-written pins in
``tests/test_shuffle_chunked.py`` / ``tests/test_semi_filter.py``
re-export these constants instead of carrying their own literals, the
jaxpr layer of ``python -m tools.graft_lint`` checks every contract
against a registry of representative plans traced on a dryrun mesh
(:mod:`.plans`), and CI runs both.

Contract semantics
------------------
- ``collectives``: exact TOTAL traced collective-primitive count for one
  warm execution of the op, as a function of the round count K (the
  census walker scales ``scan`` bodies by trip count, so fused K-round
  programs count correctly).
- per-primitive bounds (``all_to_all`` etc.): exact counts by primitive
  name.
- ``host_syncs``: exact device->host fetch count for one warm execution
  — crucially K-INDEPENDENT for the chunked engine (a sync inside the
  round dispatch loop would scale with K; that regression is the whole
  point of the zero-host-sync round loop).
- ``sync_sites``: the WHITELIST of function names allowed to fetch. For
  the chunked shuffle that is exactly ``_shuffle_many`` — the count-phase
  fetch and the ONE deferred round-count fetch after the last dispatch.
  Any other site observed during the monitored run is a violation.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple, Union

Count = Union[int, Callable[[int], int]]


def _eval(c: Optional[Count], k: int) -> Optional[int]:
    if c is None:
        return None
    return c(k) if callable(c) else int(c)


@dataclass(frozen=True)
class CollectiveContract:
    name: str
    description: str
    # exact totals (None = unconstrained), each an int or fn of round
    # count K
    collectives: Optional[Count] = None
    all_to_all: Optional[Count] = None
    all_gather: Optional[Count] = None
    psum: Optional[Count] = None
    # exact host fetches per warm execution; must be K-independent
    host_syncs: Optional[Count] = None
    # function names allowed to perform device->host fetches
    sync_sites: Tuple[str, ...] = ()
    # host-callback primitives allowed inside traced programs (none, for
    # every shipped path)
    allow_callbacks: bool = False

    def check(
        self,
        census: "object",
        k: int = 1,
        sync_events: Optional[list] = None,
    ) -> list:
        """Violation strings for a measured (census, sync_events) pair.

        ``census`` is a :class:`cylon_tpu.analysis.jaxpr_pass.Census`.
        """
        out = []
        pairs = [
            ("collectives", self.collectives, census.total),
            ("all_to_all", self.all_to_all, census.counts.get("all_to_all", 0)),
            # jax files a collective whose operand is replicated under the
            # ``*_invariant`` name; the contract counts the operation
            ("all_gather", self.all_gather,
             census.counts.get("all_gather", 0)
             + census.counts.get("all_gather_invariant", 0)),
            ("psum", self.psum,
             census.counts.get("psum", 0)
             + census.counts.get("psum_invariant", 0)),
        ]
        for label, want, got in pairs:
            w = _eval(want, k)
            if w is not None and got != w:
                out.append(
                    f"{self.name}: {label} = {got}, contract says {w} (K={k})"
                )
        if not self.allow_callbacks and census.host_callbacks:
            out.append(
                f"{self.name}: host-callback primitives inside traced "
                f"programs: {census.host_callbacks}"
            )
        if sync_events is not None:
            w = _eval(self.host_syncs, k)
            if w is not None and len(sync_events) != w:
                out.append(
                    f"{self.name}: {len(sync_events)} host syncs, contract "
                    f"says {w} (K={k}): "
                    + ", ".join(e.site for e in sync_events)
                )
            bad = [e for e in sync_events if e.site not in self.sync_sites]
            if bad:
                out.append(
                    f"{self.name}: host sync outside the whitelisted sites "
                    f"{self.sync_sites}: "
                    + ", ".join(f"{e.site} ({e.file}:{e.line})" for e in bad)
                )
        return out


# ----------------------------------------------------------------------
# the pinned numbers (tests re-export these — change them ONLY with the
# engine change that moves them, never to green a failing pin)
# ----------------------------------------------------------------------

#: an eager distributed join issues exactly 2 payload collectives (one
#: header-fused all_to_all per side) — down from 4 pre-fusion (PR 2)
DIST_JOIN_PAYLOAD_COLLECTIVES = 2

#: the semi-join sketch filter adds exactly ONE all_gather on top (PR 4)
DIST_JOIN_SKETCH_COLLECTIVES = 1


def replicate_join_collectives(lanes: int) -> int:
    """The replicate route of a distributed join (PR 48) gathers the small
    side by one ``all_gather`` a lane (a column's data, a validity lane
    where it has one) and one of the shards' counts, in ONE program; the
    big side crosses nothing, so no all_to_all is issued at all."""
    return lanes + 1


def shuffle_collectives(k: int) -> int:
    """A K-round chunked shuffle issues exactly K collectives: the count
    exchange rides the payload collective's header rows (PR 2)."""
    return k


def fused_join_collectives(respill: int) -> int:
    """The fused join step: each side's shuffle is (1 + respill)
    header-fused all_to_alls, plus the 2 overflow psums."""
    return 2 * (1 + respill) + 2


def fused_q3_collectives(respill: int, num_slices: int = 1) -> int:
    """The fused join->groupby-SUM (q3) step: the pair's sliced shuffle
    rounds (2 sides x num_slices x (1 + respill) fused all_to_alls) plus
    3 psums — the 2 shuffle-overflow reductions and the global
    grand-total psum the q3 shape adds."""
    return 2 * num_slices * (1 + respill) + 3


#: a two-hop exchange under a declared 2-D topology (PR 17) issues
#: exactly TWO grouped all_to_alls where the flat exchange issues one:
#: the inner-axis combining hop plus the outer-axis shipping hop. The
#: count still rides the header rows of hop 1 and the re-fused combined
#: headers of hop 2 — the sync discipline is unchanged.
TWO_HOP_COLLECTIVES_PER_EXCHANGE = 2


def shuffle_two_hop_collectives(k: int) -> int:
    """A K-round chunked shuffle under a 2-D topology: 2K grouped
    all_to_alls (inner + outer hop per round), still zero extra host
    syncs — the per-axis byte accounting is host arithmetic."""
    return TWO_HOP_COLLECTIVES_PER_EXCHANGE * k


def fused_join_two_hop_collectives(respill: int) -> int:
    """The fused join step with a 2-D topology threaded through the
    pipeline: each side's (1 + respill) exchanges decompose into 2
    grouped all_to_alls, plus the same 2 overflow psums."""
    return 2 * TWO_HOP_COLLECTIVES_PER_EXCHANGE * (1 + respill) + 2


def fused_q3_two_hop_collectives(respill: int, num_slices: int = 1) -> int:
    """The fused q3 step under a 2-D topology: the pair's sliced
    two-hop shuffle rounds plus the same 3 psums."""
    return (
        2 * TWO_HOP_COLLECTIVES_PER_EXCHANGE * num_slices * (1 + respill)
        + 3
    )


#: per-table host syncs of one chunked shuffle: the count-phase fetch and
#: the ONE deferred round-count fetch after the last dispatch — both in
#: ``_shuffle_many``, and K-independent by construction
SHUFFLE_HOST_SYNCS_PER_TABLE = 2

#: a SPILLED shuffle (tier >= 1, parallel/spill.py) adds exactly one
#: staging fetch per round on top of SHUFFLE_HOST_SYNCS_PER_TABLE — the
#: round's compacted output crossing into the host arena. This is the
#: ONE sanctioned K-DEPENDENT sync family: spilling trades syncs for
#: device memory by design, and the budget below pins the trade to the
#: spill module's owned sites so the in-HBM round loop stays sync-free.
SPILL_STAGE_HOST_SYNCS_PER_ROUND = 1

#: a skew-split schedule (spill.plan_schedule with a relay) adds exactly
#: ONE relay fetch per shuffle, K-independent — the heavy-bucket tails
#: ride a single extraction program and one host crossing
SKEW_RELAY_HOST_SYNCS = 1

#: the functions allowed to fetch during a shuffle: the whitelisted
#: deferred count fetch, plus the up-front materialization of a deferred-
#: count INPUT (applies the pending overshoot compaction before the pack
#: kernels specialize on the capacity; see docs/ARCHITECTURE.md "Static
#: invariants")
SHUFFLE_SYNC_SITES = (
    "_shuffle_many",
    "_shuffle_many_rounds",  # phase 2 (the round loop + deferred fetch),
    # split out so the failure-domain wrapper in _shuffle_many can close
    # spill sinks and type errors without a 300-line try block
    "_materialize_counts",
)


# ----------------------------------------------------------------------
# Layer 3: host-sync budgets + effect signatures (ISSUE 7)
# ----------------------------------------------------------------------

#: a dispatch-async eager op performs ZERO host syncs at dispatch time —
#: its count fetch is deferred to result materialization
EAGER_OP_HOST_SYNCS = 0

#: the q3 dispatch() contract: exactly ONE host sync, at result fetch
Q3_DISPATCH_HOST_SYNCS = 1

#: ...attributed to the deferred-count materialization, nowhere else
Q3_DISPATCH_SYNC_SITES = ("_materialize_counts",)

#: the ops the optimized q3 plan lowers to (plan/rules.fused_join_groupby
#: + pushdowns); each must hold a 0-site static sync budget so the ONE
#: materialization sync is provably the only fetch of a q3 dispatch
Q3_DISPATCH_OPS = (
    "Table.filter",
    "Table.project",
    "Table._join_sum_pushdown",
)


@dataclass(frozen=True)
class SyncBudget:
    """Exact number of distinct device->host sync SITES a budget-owning
    function may reach (reachability stops at other owners — each polices
    its own sites, the L1 key-builder scoping rule applied to effects).

    ``amortized``: the sync is paid at most once per table/result and
    cached (a deferred-count materialization, an ensure_stats
    measurement) — delegation to an amortized owner classifies a caller
    as MATERIALIZE, not SYNC, on the L3 effect lattice."""

    sites: int
    amortized: bool = False
    note: str = ""


#: the static sync-site pin table (:mod:`.syncfree` enforces EXACT
#: equality: a new fetch on a 0-budget op is a CI failure with a
#: file:line call path; a removed fetch is a pin update HERE, made with
#: the engine change that moves it)
SYNC_SITE_BUDGETS: Dict[str, SyncBudget] = {
    # dispatch-async eager ops: the count fetch is deferred (EAGER_OP_HOST_SYNCS)
    "Table.filter": SyncBudget(0, note="single-dispatch, deferred counts"),
    "Table.project": SyncBudget(0, note="metadata only"),
    "Table.sort": SyncBudget(0, note="permutation: counts pass through"),
    "Table.groupby": SyncBudget(0, note="static group bound, deferred counts"),
    "Table.unique": SyncBudget(0, note="subset bound, deferred counts"),
    "Table._two_table_setop": SyncBudget(
        0, note="union/subtract/intersect: subset bound, deferred counts"
    ),
    "Table._join_sum_pushdown": SyncBudget(
        0, note="fused q3 kernel: static group bound, deferred counts"
    ),
    # ops that own genuine host decisions
    "Table.join": SyncBudget(
        5,
        note="speculative stats fetch (overflow check) + exact-path probe "
        "stats fetch + the pallas_pk stats fetch + the semi-reduction's "
        "counts fetch (a selective join then skips the speculative one: "
        "its row count is known) + a semi or anti join's kept count (the "
        "only fetch of such a join; none where its hit mask goes to an "
        "aggregate) — each a packed single fetch; the emit phases reuse "
        "the probe counts",
    ),
    "Table._fused_join": SyncBudget(1, note="fused-step stats fetch"),
    "table._shuffle_many": SyncBudget(
        2,
        note="count-phase fetch + ONE deferred round-count fetch; "
        "K-independent (SHUFFLE_HOST_SYNCS_PER_TABLE)",
    ),
    "task.task_partition": SyncBudget(
        1, note="ONE sort+count fetch covers all T task splits"
    ),
    # the spill tiers (parallel/spill.py): staging and relay fetches are
    # owned HERE, not by _shuffle_many — the in-HBM round loop keeps its
    # 2-site budget and the spill module polices the sanctioned
    # K-dependent staging syncs (SPILL_STAGE_HOST_SYNCS_PER_ROUND)
    "spill.stage_table": SyncBudget(
        2,
        note="one packed lane-matrix fetch + one f64-passthrough fetch "
        "per staged round (the spill-aware lane codec: 2 transfers for "
        "ALL columns, not one per column)",
    ),
    "spill.fetch_relay": SyncBudget(
        2,
        note="the ONE skew-relay crossing per shuffle: packed lane "
        "matrix + f64 passthroughs of every over-quota row",
    ),
    "spill.shards_to_table": SyncBudget(
        2,
        note="restaging host rows onto the mesh: from_encoded_shards' "
        "per-shard device_put barriers (data + validity)",
    ),
    # the telemetry layer (ISSUE 8): observability must NEVER sync. The
    # span/bump/gauge surface, the deferred-timing resolution hook that
    # rides _materialize_counts' existing fetch, and the histogram
    # update all own 0 sync sites — so the instrumented q3 dispatch path
    # provably keeps its exactly-1-host-sync budget (the runtime census
    # twin under an ENABLED tracer runs in tools/trace_smoke.py).
    "obs.trace.span": SyncBudget(
        0, note="span timing is host perf_counter only"
    ),
    "obs.trace.resolve_table": SyncBudget(
        0, note="stamps the deferred end time AFTER the count fetch the "
        "engine already made; adds none",
    ),
    "obs.metrics.observe_latency": SyncBudget(
        0, note="lock + dict bump, pure host"
    ),
    # the critical-path profiler (ISSUE 15): stage clocks are derived
    # from counts the engine ALREADY fetched plus perf_counter stamps —
    # a profiled dispatch keeps the exact same sync census as an
    # unprofiled one (runtime twin: tools/trace_smoke.py re-runs the q3
    # census under CYLON_TPU_PROF=1)
    "obs.prof.record_stages": SyncBudget(
        0, note="window + counts already host-known; numpy arithmetic "
        "and rollup gauges only",
    ),
    "obs.prof.record_fused": SyncBudget(
        0, note="dispatch-time shape-derived work units; the window "
        "resolves later at the existing deferred count fetch",
    ),
    "obs.prof.finalize": SyncBudget(
        0, note="derives pending stage seconds AFTER resolve_table "
        "stamped the device-resolved end; adds none",
    ),
    "obs.prof.critical_path": SyncBudget(
        0, note="host tree walk over an already-built span forest"
    ),
    # the ops surface (ISSUE 12): the ledger hook every Table
    # construction pays, the query-finish stamp, the SLO evaluation and
    # the Prometheus render are all pure host dict math — a metrics
    # scrape (or a leak report) can NEVER sync the device
    "obs.resource.note_table": SyncBudget(
        0, note="ledger registration: nbytes shape reads + weakref "
        "finalize, pure host",
    ),
    "obs.resource.query_finished": SyncBudget(
        0, note="leak-detector clock stamp, dict write under lock"
    ),
    "SLOMonitor.evaluate": SyncBudget(
        0, note="rule math over already-collected counter snapshots"
    ),
    "obs.export.prometheus_text": SyncBudget(
        0, note="text render over rollup/ledger/SLO snapshots"
    ),
    # the serving layer (ISSUE 9): the scheduler worker and the whole
    # submit path own ZERO sync sites — a served query's single sync is
    # QueryFuture.result, whose one budgeted site is the audited blocking
    # wait on the worker's fulfillment (the count fetch itself is the
    # table's amortized materialization, reached through it)
    "QueryFuture.result": SyncBudget(
        1, note="THE per-query sync point: blocks on fulfillment, then "
        "forces the deferred count fetch in the caller's thread",
    ),
    # the fault-injection seams (ISSUE 14): a seam hook can raise, count
    # and read env — it can NEVER touch the device. `check` itself is a
    # REBOUND module attribute (no-op <-> armed), so the budgets pin the
    # two concrete hook functions it can resolve to; this is what
    # "graft-lint keeps every seam DISPATCH_SAFE" means mechanically: a
    # future edit that fetches inside either hook (or anything it
    # calls) fails CI with the call path.
    "inject._check_armed": SyncBudget(
        0, note="armed seam hook: seeded RNG draw + counter + typed "
        "raise, pure host",
    ),
    "inject._check_noop": SyncBudget(
        0, note="disabled seam hook: a bare return",
    ),
    # amortized machinery: paid once, cached
    "Table._materialize_counts": SyncBudget(
        1, amortized=True,
        note="THE deferred result fetch (+ in-place overshoot compaction)",
    ),
    "Table.ensure_stats": SyncBudget(
        1, amortized=True,
        note="on-demand column range stats; cached on the table, free for "
        "shuffle outputs (the count pass measured them)",
    ),
}


#: the pinned effect signature of every public entry point on the
#: certified dispatch surface (:func:`cylon_tpu.analysis.syncfree
#: .public_entries`): DISPATCH_SAFE < MATERIALIZE < SYNC — see
#: docs/ARCHITECTURE.md "Static invariants" for the lattice semantics.
#: Filled per-entry; syncfree flags any public entry missing here
#: (effect-unpinned) or drifting from its pin (effect-drift).
EFFECT_SIGNATURES: Dict[str, str] = {
    "DataFrame.add_prefix": "DISPATCH_SAFE",
    "DataFrame.add_suffix": "DISPATCH_SAFE",
    "DataFrame.applymap": "SYNC",
    "DataFrame.astype": "SYNC",
    # serving submit (ISSUE 9): enqueue-only, provably sync-free — the
    # acceptance pin "submit path = exactly 0 host syncs"
    "DataFrame.collect_async": "DISPATCH_SAFE",
    "DataFrame.columns": "DISPATCH_SAFE",
    "DataFrame.concat": "SYNC",
    "DataFrame.context": "DISPATCH_SAFE",
    "DataFrame.count": "SYNC",
    "DataFrame.drop": "DISPATCH_SAFE",
    "DataFrame.drop_duplicates": "SYNC",
    "DataFrame.fillna": "DISPATCH_SAFE",
    "DataFrame.groupby": "SYNC",
    "DataFrame.iloc": "DISPATCH_SAFE",
    "DataFrame.index": "DISPATCH_SAFE",
    "DataFrame.is_cpu": "DISPATCH_SAFE",
    "DataFrame.is_device": "DISPATCH_SAFE",
    "DataFrame.isin": "DISPATCH_SAFE",
    "DataFrame.isna": "DISPATCH_SAFE",
    "DataFrame.isnull": "DISPATCH_SAFE",
    "DataFrame.iterrows": "SYNC",
    "DataFrame.join": "SYNC",
    "DataFrame.lazy": "DISPATCH_SAFE",
    "DataFrame.loc": "DISPATCH_SAFE",
    "DataFrame.mask": "MATERIALIZE",
    "DataFrame.max": "SYNC",
    "DataFrame.mean": "SYNC",
    "DataFrame.merge": "SYNC",
    "DataFrame.min": "SYNC",
    "DataFrame.notna": "DISPATCH_SAFE",
    "DataFrame.notnull": "DISPATCH_SAFE",
    "DataFrame.rename": "DISPATCH_SAFE",
    "DataFrame.reset_index": "DISPATCH_SAFE",
    "DataFrame.set_index": "DISPATCH_SAFE",
    "DataFrame.shape": "MATERIALIZE",
    "DataFrame.sort_values": "SYNC",
    "DataFrame.sum": "SYNC",
    "DataFrame.table": "DISPATCH_SAFE",
    "DataFrame.to_arrow": "SYNC",
    "DataFrame.to_cpu": "DISPATCH_SAFE",
    "DataFrame.to_csv": "SYNC",
    "DataFrame.to_device": "DISPATCH_SAFE",
    "DataFrame.to_dict": "SYNC",
    "DataFrame.to_numpy": "SYNC",
    "DataFrame.to_pandas": "SYNC",
    "DataFrame.to_table": "DISPATCH_SAFE",
    "DataFrame.where": "MATERIALIZE",
    "LazyFrame.agg": "DISPATCH_SAFE",
    "LazyFrame.collect": "SYNC",
    # the serving submit path (ISSUE 9): enqueue-only — zero host syncs
    "LazyFrame.collect_async": "DISPATCH_SAFE",
    "LazyFrame.columns": "DISPATCH_SAFE",
    "LazyFrame.dispatch": "SYNC",
    # re-pinned with ISSUE 8: explain(analyze=True) EXECUTES the plan
    # (per-node materialization is the point of EXPLAIN ANALYZE), so the
    # static worst case over both paths is SYNC; the analyze-free path
    # still performs no execution
    "LazyFrame.explain": "SYNC",
    "LazyFrame.filter": "DISPATCH_SAFE",
    "LazyFrame.from_table": "DISPATCH_SAFE",
    "LazyFrame.groupby": "DISPATCH_SAFE",
    "LazyFrame.head": "DISPATCH_SAFE",
    "LazyFrame.join": "DISPATCH_SAFE",
    "LazyFrame.limit": "DISPATCH_SAFE",
    "LazyFrame.plan": "DISPATCH_SAFE",
    "LazyFrame.select": "DISPATCH_SAFE",
    "LazyFrame.sort": "DISPATCH_SAFE",
    "LazyFrame.union": "DISPATCH_SAFE",
    "LazyFrame.with_columns": "DISPATCH_SAFE",
    # the serving layer (ISSUE 9): submit/admission is DISPATCH_SAFE;
    # QueryFuture.result is the single per-query SYNC point; the drain
    # entry points that EXECUTE plans classify like dispatch (SYNC —
    # distributed lowering delegates to the shuffle's budgeted fetches)
    # the ops surface (ISSUE 12): ledger reads, SLO evaluation and the
    # endpoint lifecycle are all DISPATCH_SAFE — observability can never
    # sync the device (acceptance pin: every new obs entry point)
    "OpsServer.start": "DISPATCH_SAFE",
    "OpsServer.stop": "DISPATCH_SAFE",
    "OpsServer.port": "DISPATCH_SAFE",
    "QueryFuture.done": "DISPATCH_SAFE",
    "QueryFuture.exception": "DISPATCH_SAFE",
    "QueryFuture.result": "SYNC",
    "ResourceLedger.snapshot": "DISPATCH_SAFE",
    "ResourceLedger.leaks": "DISPATCH_SAFE",
    "SLOMonitor.evaluate": "DISPATCH_SAFE",
    "SLOMonitor.states": "DISPATCH_SAFE",
    "SLOMonitor.healthy": "DISPATCH_SAFE",
    "ServeScheduler.close": "DISPATCH_SAFE",
    "ServeScheduler.drain": "DISPATCH_SAFE",
    "ServeScheduler.pause": "DISPATCH_SAFE",
    "ServeScheduler.resume": "DISPATCH_SAFE",
    "ServeScheduler.run_pending": "SYNC",
    "ServeScheduler.stats": "DISPATCH_SAFE",
    "ServeScheduler.submit": "DISPATCH_SAFE",
    "Table.add_column": "DISPATCH_SAFE",
    "Table.add_prefix": "DISPATCH_SAFE",
    "Table.add_suffix": "DISPATCH_SAFE",
    "Table.applymap": "SYNC",
    "Table.astype": "SYNC",
    "Table.build_index": "DISPATCH_SAFE",
    "Table.clear": "MATERIALIZE",
    "Table.column": "DISPATCH_SAFE",
    "Table.column_count": "DISPATCH_SAFE",
    "Table.column_names": "DISPATCH_SAFE",
    "Table.column_stats": "DISPATCH_SAFE",
    "Table.concat": "MATERIALIZE",
    "Table.context": "DISPATCH_SAFE",
    "Table.count": "SYNC",
    "Table.counts_dev": "MATERIALIZE",
    "Table.distributed_groupby": "SYNC",
    "Table.distributed_intersect": "SYNC",
    "Table.distributed_join": "SYNC",
    "Table.distributed_pipeline_groupby": "SYNC",
    "Table.distributed_sort": "SYNC",
    "Table.distributed_subtract": "SYNC",
    "Table.distributed_union": "SYNC",
    "Table.distributed_unique": "SYNC",
    "Table.drop": "DISPATCH_SAFE",
    "Table.dropna": "MATERIALIZE",
    "Table.dtype_of": "DISPATCH_SAFE",
    "Table.ensure_stats": "SYNC",
    "Table.equals": "SYNC",
    "Table.fillna": "DISPATCH_SAFE",
    "Table.filter": "MATERIALIZE",
    "Table.from_arrow": "SYNC",
    "Table.from_encoded": "SYNC",
    "Table.from_encoded_shards": "SYNC",
    "Table.from_list": "SYNC",
    "Table.from_numpy": "SYNC",
    "Table.from_pandas": "SYNC",
    "Table.from_pydict": "SYNC",
    "Table.from_shards": "SYNC",
    "Table.get_index": "DISPATCH_SAFE",
    "Table.groupby": "MATERIALIZE",
    "Table.hash_partition": "DISPATCH_SAFE",
    "Table.iloc": "DISPATCH_SAFE",
    "Table.index": "MATERIALIZE",
    "Table.intersect": "DISPATCH_SAFE",
    "Table.isin": "DISPATCH_SAFE",
    "Table.isna": "DISPATCH_SAFE",
    "Table.isnull": "DISPATCH_SAFE",
    "Table.iterrows": "SYNC",
    "Table.join": "SYNC",
    "Table.lazy": "DISPATCH_SAFE",
    "Table.live_mask": "DISPATCH_SAFE",
    "Table.loc": "DISPATCH_SAFE",
    "Table.mask": "MATERIALIZE",
    "Table.max": "SYNC",
    "Table.mean": "SYNC",
    "Table.merge": "MATERIALIZE",
    "Table.min": "SYNC",
    "Table.minmax": "SYNC",
    "Table.notna": "DISPATCH_SAFE",
    "Table.notnull": "DISPATCH_SAFE",
    "Table.ordering": "DISPATCH_SAFE",
    "Table.pipeline_groupby": "DISPATCH_SAFE",
    "Table.project": "DISPATCH_SAFE",
    "Table.rename": "DISPATCH_SAFE",
    "Table.reset_index": "DISPATCH_SAFE",
    "Table.row_count": "MATERIALIZE",
    "Table.row_counts": "MATERIALIZE",
    "Table.select": "DISPATCH_SAFE",
    "Table.select_rows": "SYNC",
    "Table.set_index": "DISPATCH_SAFE",
    "Table.shape": "MATERIALIZE",
    "Table.shard_cap": "DISPATCH_SAFE",
    "Table.show": "SYNC",
    "Table.shuffle": "SYNC",
    "Table.sort": "MATERIALIZE",
    "Table.subtract": "DISPATCH_SAFE",
    "Table.sum": "SYNC",
    "Table.take": "MATERIALIZE",
    "Table.task_partition": "SYNC",
    "Table.to_arrow": "SYNC",
    "Table.to_csv": "SYNC",
    "Table.to_numpy": "SYNC",
    "Table.to_pandas": "SYNC",
    "Table.to_pydict": "SYNC",
    "Table.to_string": "SYNC",
    "Table.topk": "SYNC",
    "Table.union": "DISPATCH_SAFE",
    "Table.unique": "DISPATCH_SAFE",
    "Table.where": "MATERIALIZE",
    "Table.with_ordering": "DISPATCH_SAFE",
    "Table.world_size": "DISPATCH_SAFE",
}

CONTRACTS: Dict[str, CollectiveContract] = {
    "shuffle_single": CollectiveContract(
        name="shuffle_single",
        description=(
            "single-table K-round hash shuffle (eager engine): K fused "
            "all_to_alls, 2 K-independent host syncs, both in "
            "_shuffle_many"
        ),
        collectives=shuffle_collectives,
        all_to_all=shuffle_collectives,
        host_syncs=SHUFFLE_HOST_SYNCS_PER_TABLE,
        sync_sites=SHUFFLE_SYNC_SITES,
    ),
    "shuffle_wire_packed": CollectiveContract(
        name="shuffle_wire_packed",
        description=(
            "bit-width-narrowed shuffle (PR 5): the wire plan changes lane "
            "layout, never the collective count or the sync discipline"
        ),
        collectives=shuffle_collectives,
        all_to_all=shuffle_collectives,
        host_syncs=SHUFFLE_HOST_SYNCS_PER_TABLE,
        sync_sites=SHUFFLE_SYNC_SITES,
    ),
    "shuffle_quant": CollectiveContract(
        name="shuffle_quant",
        description=(
            "quantized-wire shuffle (ISSUE 13): the lossy q8 tier "
            "changes lane layout and widens the header rows (block "
            "scales ride the count collective), never the collective "
            "count or the sync discipline"
        ),
        collectives=shuffle_collectives,
        all_to_all=shuffle_collectives,
        host_syncs=SHUFFLE_HOST_SYNCS_PER_TABLE,
        sync_sites=SHUFFLE_SYNC_SITES,
    ),
    "dist_join": CollectiveContract(
        name="dist_join",
        description=(
            "eager distributed inner join, semi filter off: one "
            "header-fused all_to_all per side, zero extra collectives; "
            "pair count fetches + deferred round fetches in _shuffle_many "
            "plus the ONE speculative-join stats fetch in Table.join"
        ),
        collectives=DIST_JOIN_PAYLOAD_COLLECTIVES,
        all_to_all=DIST_JOIN_PAYLOAD_COLLECTIVES,
        all_gather=0,
        host_syncs=2 * SHUFFLE_HOST_SYNCS_PER_TABLE + 1,
        sync_sites=SHUFFLE_SYNC_SITES + ("join",),
    ),
    "dist_join_semi": CollectiveContract(
        name="dist_join_semi",
        description=(
            "semi-filtered distributed inner join: 2 payload all_to_alls "
            "+ exactly 1 sketch all_gather; the filter adds NO host sync "
            "(the filtered counts ride the existing count fetch)"
        ),
        collectives=DIST_JOIN_PAYLOAD_COLLECTIVES
        + DIST_JOIN_SKETCH_COLLECTIVES,
        all_to_all=DIST_JOIN_PAYLOAD_COLLECTIVES,
        all_gather=DIST_JOIN_SKETCH_COLLECTIVES,
        host_syncs=2 * SHUFFLE_HOST_SYNCS_PER_TABLE + 1,
        sync_sites=SHUFFLE_SYNC_SITES + ("join",),
    ),
    "dist_join_replicate": CollectiveContract(
        name="dist_join_replicate",
        description=(
            "eager distributed join on the replicate route (one side at "
            "most 1/REPLICATE_JOIN_MIN_RATIO of a chip's share of the "
            "other): the small side's lanes and counts all_gathered in one "
            "program (K passed as its lanes), NO all_to_all, no count "
            "fetched to decide or to size (the host holds the counts), and "
            "the ONE speculative-join stats fetch in Table.join"
        ),
        collectives=replicate_join_collectives,
        all_to_all=0,
        all_gather=replicate_join_collectives,
        host_syncs=1,
        sync_sites=("join",),
    ),
    "fused_join_step": CollectiveContract(
        name="fused_join_step",
        description=(
            "fully fused distributed join program (pipeline.py): "
            "2 x (1 + respill) header-fused all_to_alls + 2 overflow "
            "psums, all inside ONE XLA program (K passed as 1 + respill)"
        ),
        # checked via jaxpr census with k = respill
        collectives=lambda respill: fused_join_collectives(respill),
        all_to_all=lambda respill: 2 * (1 + respill),
        psum=2,
    ),
    "q3_fused_step": CollectiveContract(
        name="q3_fused_step",
        description=(
            "fused join->groupby-SUM (TPC-H q3 shape) program: "
            "2 x (1 + respill) fused all_to_alls + 3 psums (2 overflow "
            "reductions + the global grand-total)"
        ),
        collectives=lambda respill: fused_q3_collectives(respill),
        all_to_all=lambda respill: 2 * (1 + respill),
        psum=3,
    ),
    "shuffle_two_hop": CollectiveContract(
        name="shuffle_two_hop",
        description=(
            "K-round hash shuffle under a declared 2-D topology (PR 17): "
            "2K grouped all_to_alls — the inner-axis combining hop plus "
            "the outer-axis shipping hop per round — with the SAME 2-site "
            "sync discipline as the flat shuffle (counts ride headers on "
            "both hops). The CYLON_TPU_NO_TOPO kill switch restores "
            "shuffle_single's census exactly"
        ),
        collectives=shuffle_two_hop_collectives,
        all_to_all=shuffle_two_hop_collectives,
        host_syncs=SHUFFLE_HOST_SYNCS_PER_TABLE,
        sync_sites=SHUFFLE_SYNC_SITES,
    ),
    "fused_join_step_topo": CollectiveContract(
        name="fused_join_step_topo",
        description=(
            "fully fused distributed join program with a 2-D topology "
            "threaded through the pipeline: 2 x 2 x (1 + respill) grouped "
            "all_to_alls (each side's exchange = inner hop + outer hop) "
            "+ the same 2 overflow psums, all inside ONE XLA program"
        ),
        collectives=lambda respill: fused_join_two_hop_collectives(respill),
        all_to_all=lambda respill: 2
        * TWO_HOP_COLLECTIVES_PER_EXCHANGE
        * (1 + respill),
        psum=2,
    ),
    "q3_fused_step_topo": CollectiveContract(
        name="q3_fused_step_topo",
        description=(
            "fused join->groupby-SUM (q3) program with a 2-D topology: "
            "2 x 2 x (1 + respill) grouped all_to_alls + 3 psums (2 "
            "overflow reductions + the global grand-total)"
        ),
        collectives=lambda respill: fused_q3_two_hop_collectives(respill),
        all_to_all=lambda respill: 2
        * TWO_HOP_COLLECTIVES_PER_EXCHANGE
        * (1 + respill),
        psum=3,
    ),
    "eager_sync_free": CollectiveContract(
        name="eager_sync_free",
        description=(
            "dispatch-async eager ops (filter / project / groupby / "
            "unique / set-op / sort): zero collectives-unconstrained, "
            "ZERO host syncs at dispatch — the count fetch is deferred "
            "to result materialization (L3 budget: 0 sites)"
        ),
        host_syncs=EAGER_OP_HOST_SYNCS,
        sync_sites=(),
    ),
    "q3_dispatch": CollectiveContract(
        name="q3_dispatch",
        description=(
            "LazyFrame.dispatch() of the fused q3 join->groupby-SUM plan "
            "on a 1-device mesh: ZERO host syncs at dispatch, exactly ONE "
            "at result fetch, attributed to _materialize_counts (the "
            "collect_async precursor contract; runtime twin of the "
            "static q3-dispatch-budget check)"
        ),
        host_syncs=Q3_DISPATCH_HOST_SYNCS,
        sync_sites=Q3_DISPATCH_SYNC_SITES,
    ),
}
