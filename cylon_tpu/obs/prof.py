"""Critical-path profiler (ISSUE 15): per-stage, per-shard device stage
clocks, the straggler ledger, and longest-path attribution over span
trees — all sync-free.

WHY: the obs stack could say how long a query took (fingerprint
histograms, span trees, EXPLAIN ANALYZE) but not WHERE the time went —
stage times were host-dispatch-wall proxies, no per-shard timing
existed, and a straggler shard stayed invisible until it broke an SLO.
Exoshuffle (PAPERS.md 2203.05072) and the Cylon scaling follow-up
(2212.13732) both argue that a shuffle decomposed into ATTRIBUTABLE
stages is what makes policy tuning possible; this module is that lens
for the TPU engine, and ``plan/feedback.py``'s ``skew_trigger`` decision
is its first tuning consumer (the ROADMAP-4 "tune the 4x-mean skew
trigger from profiles" item).

HOW THE CLOCKS WORK (and why they add no sync): a dispatched stage's
real end time is unknowable without a host sync, which the
dispatch-async engine forbids. But the engine ALREADY holds, on the
host, everything a stage clock needs:

- the per-shard, per-stage WORK each stage performed — the measured
  ``[src, dst]`` count matrix of the shuffle's count phase (pack scans
  ``local_rows`` per round, the collective ships ``K x world x cap``
  padded slots per shard, compact front-packs ``received_rows``, the
  skew relay double-crosses its over-quota tail through host PCIe) —
  fetched ONCE in phase 0, before any round dispatched;
- the DEVICE WINDOW the stages ran in — dispatch-open to the return of
  the ONE deferred round-count fetch the engine already makes
  (``table._shuffle_many_rounds``), or, for the fully fused pipeline,
  to the query's device-resolved end stamped by
  :func:`obs.trace.resolve_table` when ``_materialize_counts``' existing
  fetch returns.

A stage clock is the window apportioned over the weighted work units:
``t[stage][shard] = window * W[stage] * units[stage][shard] / total``.
The per-stage weights are calibration constants (relative per-row cost,
documented at :data:`STAGE_WEIGHTS`); the RATIOS the ledger publishes —
straggler ``max/mean`` within a stage, stage shares along the critical
path — are exact functions of the measured counts and do not depend on
the absolute calibration. Everything is host float math over
already-fetched numbers: graft-lint pins every entry point here at a
0-site sync budget, and ``tools/trace_smoke.py`` asserts the q3 dispatch
census is unchanged under an ENABLED profiler.

SURFACE:

- gauges ``prof.stage_ms.<stage>`` / ``prof.straggler_ratio[.<stage>]``
  in the rollup (Prometheus-exported via ``/metrics``);
- ``prof_<stage>_ms`` / ``prof_straggler`` annotations on the owning
  exchange span (rendered by EXPLAIN ANALYZE and Perfetto);
- per-shard stage tracks in the Chrome export (``obs/export.py``);
- straggler evidence journaled into the observation store
  (``obs.store.note_stages``) — the ``skew_trigger`` re-coster's
  substrate;
- :func:`critical_path` / :func:`critical_report` — longest self-time
  root-to-leaf attribution over ``plan.node.*`` span trees, feeding
  ``explain(analyze=True)``'s "crit %" column and
  ``tools/traceview --critical``.

FAILURE DOMAIN: profiling must never fail a query. Every record path
runs under the ``obs.prof`` fault seam (``cylon_tpu/fault/inject.py``)
and a broad except: any failure counts ``prof.degraded`` and flips
profiling OFF for the process (:func:`reset` re-arms) — the chaos gate
(``tools/chaos_smoke.py``) drives this mechanically.

DISABLED COST: one env read per shuffle/fused dispatch
(``profiling_active()``); ``tools/trace_smoke.py`` folds it into the
same <2% calibration budget as the disabled tracer.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..utils import envgate as _eg
from . import metrics as _metrics

#: relative per-work-unit cost of each stage (calibration constants —
#: the straggler ratios and critical-path SHARES are weight-independent
#: within a stage; the weights only arbitrate BETWEEN stages):
#:
#: - ``pack``:       3.0 per locally scanned row per round (the
#:                   partition-id hash, the bucket counts and the sort
#:                   by destination every lane rides; a modelled weight,
#:                   not a measured one). The 3x also keeps the pack-vs-
#:                   collective verdict stable on uniform shapes: the
#:                   collective's pow2 bucket rounding can inflate its
#:                   slots up to 2x the live rows, and a weight of 2
#:                   would leave the two stages within rounding noise;
#: - ``collective``: 1.0 per padded collective row slot (the all_to_all
#:                   moves every slot whether live or padding — which is
#:                   exactly why a hot bucket inflates this stage);
#: - ``compact``:    1.0 per received row (header split + lane-level
#:                   front-pack move);
#: - ``relay``:      4.0 per relayed row — the skew tail crosses host
#:                   PCIe twice (device->host fetch, host->device
#:                   restage), each crossing ~2x a collective slot
#:                   (parallel/spill.RELAY_COST_FACTOR's calibration).
STAGE_WEIGHTS: Dict[str, float] = {
    "pack": 3.0,
    "collective": 1.0,
    "coll_inner": 1.0,
    "coll_outer": 1.0,
    "compact": 1.0,
    "relay": 4.0,
}

#: render/lay-out order of the stage tracks (pipeline order). A two-hop
#: topology shuffle (parallel/topo.py) splits the single ``collective``
#: track into per-axis ``coll_inner`` (grouped inner all_to_all) and
#: ``coll_outer`` (combined-chunk outer all_to_all) clocks — flat
#: shuffles keep the merged track, so the ledger is comparable across
#: the CYLON_TPU_NO_TOPO differential.
STAGE_ORDER: Tuple[str, ...] = (
    "pack", "collective", "coll_inner", "coll_outer", "compact", "relay"
)

#: the key under which a QueryTrace carries its attached StageProfiles
#: (``__``-prefixed: the exporters exclude it from plain attr rendering
#: and expand it into per-shard stage tracks instead)
PROF_ATTR = "__prof__"

_DEGRADED = [False]  # flipped by _degrade(); reset() re-arms


def profiling_active() -> bool:
    """Profiler gate: ``CYLON_TPU_PROF`` truthy and not degraded. One
    env read — the whole disabled cost per shuffle/fused dispatch."""
    return not _DEGRADED[0] and _eg.PROF.truthy()


def _degrade(exc: BaseException) -> None:
    """A profiler failure degrades to profiling-off for the process —
    counted, never propagated: a query must be unaffected."""
    _DEGRADED[0] = True
    _metrics.rollup_count("prof.degraded")


def degraded() -> bool:
    """Has a profiler failure flipped profiling off for the process?"""
    return _DEGRADED[0]


def reset() -> None:
    """Re-arm a degraded profiler (tests / chaos rounds)."""
    _DEGRADED[0] = False


# ----------------------------------------------------------------------
# the stage-clock record
# ----------------------------------------------------------------------
class StageProfile:
    """One profiled execution's stage clocks: per-stage per-shard
    weighted work units plus the measured device window. ``window_s`` is
    ``None`` for a fused-pipeline profile until the query's deferred
    count fetch resolves it (:func:`finalize`)."""

    __slots__ = ("kind", "world", "t0", "window_s", "units")

    def __init__(
        self,
        kind: str,
        world: int,
        t0: float,
        window_s: Optional[float],
        units: Dict[str, np.ndarray],
    ):
        self.kind = kind
        self.world = int(world)
        self.t0 = float(t0)
        self.window_s = window_s
        self.units = units

    # -- derived clocks -------------------------------------------------
    def _total_units(self) -> float:
        return float(sum(u.sum() for u in self.units.values())) or 1.0

    def seconds(self) -> Dict[str, float]:
        """Global per-stage seconds: the window apportioned over the
        weighted units ({} until the window resolves)."""
        if self.window_s is None:
            return {}
        tot = self._total_units()
        return {
            s: self.window_s * float(u.sum()) / tot
            for s, u in self.units.items()
        }

    def shard_seconds(self) -> Dict[str, np.ndarray]:
        """Per-stage per-shard seconds ({} until the window resolves)."""
        if self.window_s is None:
            return {}
        tot = self._total_units()
        return {
            s: self.window_s * u / tot for s, u in self.units.items()
        }

    def stragglers(self) -> Dict[str, float]:
        """Per-stage ``max/mean`` shard-time ratio (weight-independent:
        the per-unit cost cancels within a stage). A perfectly balanced
        stage reads 1.0; a one-hot 8-way compact reads ~8."""
        out: Dict[str, float] = {}
        for s, u in self.units.items():
            mean = float(u.mean())
            if mean > 0:
                out[s] = float(u.max()) / mean
        return out

    def straggler_ratio(self) -> float:
        return max(self.stragglers().values(), default=1.0)


def shuffle_units(
    parts: Iterable[
        Tuple[Any, int, int, Optional[np.ndarray], Optional[tuple]]
    ],
    world: int,
) -> Dict[str, np.ndarray]:
    """Per-shard weighted work units of one ``_shuffle_many`` call from
    its host-known plan: ``parts`` is one ``(send_counts [src, dst],
    n_rounds, bucket_cap, relay-or-None, topo_plan-or-None)`` tuple per
    shuffled table (``topo_plan`` = the two-hop ``(outer, inner, cap_o,
    n_header)`` when the 2-D topology decomposed the exchange). Pure
    numpy over counts the phase-0 fetch already returned."""
    units = {s: np.zeros(world, np.float64) for s in STAGE_ORDER}
    for send_counts, n_rounds, bucket_cap, relay, topo_plan in parts:
        m = np.asarray(send_counts, np.float64).reshape(-1, world)
        k = max(int(n_rounds), 1)
        # pack scans the local table once per round
        units["pack"] += STAGE_WEIGHTS["pack"] * k * m.sum(axis=1)
        # the collective ships K x world x cap padded slots per shard —
        # uniform by construction (the padding IS the skew cost). A
        # two-hop plan splits the clock per axis: the inner grouped
        # all_to_all still moves world x cap slots, the outer hop moves
        # outer x cap_o COMBINED slots (the decomposition's saving
        # reads directly off this track vs the flat world x cap).
        if topo_plan is not None:
            outer, inner, cap_o = (
                int(topo_plan[0]), int(topo_plan[1]), int(topo_plan[2])
            )
            units["coll_inner"] += (
                STAGE_WEIGHTS["coll_inner"] * k * world * int(bucket_cap)
            )
            units["coll_outer"] += (
                STAGE_WEIGHTS["coll_outer"] * k * outer * cap_o
            )
        else:
            units["collective"] += (
                STAGE_WEIGHTS["collective"] * k * world * int(bucket_cap)
            )
        # compact front-packs what each shard received
        units["compact"] += STAGE_WEIGHTS["compact"] * m.sum(axis=0)
        if relay is not None:
            r = np.asarray(relay, np.float64).reshape(-1, world)
            units["relay"] += STAGE_WEIGHTS["relay"] * r.sum(axis=0)
    return {s: u for s, u in units.items() if u.sum() > 0}


def fused_units(
    world: int,
    bucket_cap: int,
    rounds: int,
    rows_l: int,
    rows_r: int,
    join_cap: int,
) -> Dict[str, np.ndarray]:
    """Per-shard units of one fused-pipeline step (join / q3 pushdown).
    The fused program fetches nothing before dispatch, so only
    SHAPE-derived work is host-known: per-shard attribution is uniform
    (honest — per-shard counts would cost the sync the pipeline exists
    to avoid), but the stage SPLIT still feeds the critical path."""
    ones = np.ones(max(world, 1), np.float64)
    rows_local = float(rows_l + rows_r) / max(world, 1)
    k = max(int(rounds), 1)
    return {
        "pack": STAGE_WEIGHTS["pack"] * k * rows_local * ones,
        "collective": (
            STAGE_WEIGHTS["collective"] * k * world * int(bucket_cap) * ones
        ),
        # the fused compact + probe/emit work over the joined capacity
        "compact": STAGE_WEIGHTS["compact"] * float(join_cap) * ones,
    }


# ----------------------------------------------------------------------
# recording (the engine-facing surface; 0-site sync budgets)
# ----------------------------------------------------------------------
def _attach(profile: StageProfile) -> None:
    from . import trace as _trace

    q = _trace.current()
    if q is None:
        return
    profs = q.attrs.get(PROF_ATTR)
    if profs is None:
        profs = q.attrs[PROF_ATTR] = []
    profs.append(profile)


def _emit(profile: StageProfile, q, journal: bool) -> None:
    """Publish a window-resolved profile: rollup gauges, annotations on
    the OWNING trace ``q`` (passed explicitly — a deferred fused profile
    resolves after the ambient contextvars moved on, possibly inside a
    DIFFERENT query's execution, so reading ``trace.current()`` here
    would mis-attribute the clocks), and — on the inline path only
    (``journal``, where the owning exec-observation record is still the
    active one) — the observation-store straggler evidence. Host
    dict/file work only."""
    from . import store as _obsstore

    secs = profile.seconds()
    ratios = profile.stragglers()
    attrs: Dict[str, float] = {}
    for s, v in secs.items():
        _metrics.rollup_value(f"prof.stage_ms.{s}", v * 1e3)
        attrs[f"prof_{s}_ms"] = round(v * 1e3, 3)
    for s, v in ratios.items():
        _metrics.rollup_value(f"prof.straggler_ratio.{s}", v)
    overall = profile.straggler_ratio()
    _metrics.rollup_value("prof.straggler_ratio", overall)
    attrs["prof_straggler"] = round(overall, 3)
    if q is not None:
        target = q._stack[-1].attrs if q._stack else q.attrs
        target.update(attrs)
    if journal:
        _obsstore.note_stages(
            {
                s: (secs.get(s, 0.0), ratios.get(s, 1.0))
                for s in profile.units
            },
        )


def record_stages(kind, units, world, t0, t_dev) -> None:
    """Stage clocks for one execution whose device window ``[t0,
    t_dev]`` is ALREADY host-known (its owning fetch returned before
    this call): pure arithmetic — no fetch, no dispatch (graft-lint
    budget: 0 sites)."""
    if not profiling_active():
        return
    try:
        from .. import fault as _fault
        from . import trace as _trace

        _fault.inject.check("obs.prof")
        units = {
            s: np.asarray(u, np.float64)
            for s, u in units.items()
            if float(np.asarray(u).sum()) > 0
        }
        if not units:
            return
        profile = StageProfile(
            kind, world, t0, max(t_dev - t0, 1e-9), units,
        )
        # inline: the current trace IS the owning query and the active
        # exec-observation record is its own — annotate AND journal
        _emit(profile, _trace.current(), journal=True)
        _attach(profile)
    except Exception as e:  # profiling must never fail a query
        _degrade(e)


def record_shuffle(parts, world, t0, t_dev) -> None:
    """Stage clocks for one eager K-round shuffle, called by
    ``table._shuffle_many_rounds`` AFTER its one deferred round-count
    fetch returned: the device window ``[t0, t_dev]`` and the count
    matrices are both already host-known."""
    if not profiling_active():
        return
    try:
        units = shuffle_units(parts, world)
    except Exception as e:
        _degrade(e)
        return
    record_stages("shuffle", units, world, t0, t_dev)


def record_fused(units: Dict[str, np.ndarray], world: int, t0: float) -> None:
    """Stage clocks for one fused-pipeline dispatch. The window is NOT
    known here (the fused program is still in flight); the profile
    attaches to the active query trace PENDING and :func:`finalize`
    resolves it when the deferred count fetch stamps the query's
    device-resolved end — the same ride-along discipline as
    ``obs.trace.resolve_table``. No active trace = no resolution point,
    so the record is skipped (not buffered forever)."""
    if not profiling_active():
        return
    try:
        from .. import fault as _fault
        from . import trace as _trace

        _fault.inject.check("obs.prof")
        if _trace.current() is None:
            return
        units = {
            s: np.asarray(u, np.float64)
            for s, u in units.items()
            if float(np.asarray(u).sum()) > 0
        }
        if not units:
            return
        _attach(StageProfile("fused", world, t0, None, units))
    except Exception as e:
        _degrade(e)


def finalize(q) -> None:
    """Resolve any window-pending profiles on a finishing query trace
    (called from ``obs.trace._maybe_finish`` before the trace is
    exported): the window is dispatch-open to the query's
    device-resolved end — both already stamped, nothing fetched. The
    clocks annotate ``q`` itself (the ambient contextvars may already
    belong to a DIFFERENT query — e.g. the deferred table materializes
    inside a later execution); no store journaling here, for the same
    reason (the owning exec record closed at plan-execution exit, and a
    fused profile's per-shard units are uniform anyway — no straggler
    evidence to lose)."""
    profs = q.attrs.get(PROF_ATTR)
    if not profs:
        return
    try:
        end = q.resolved if q.resolved is not None else q.t1
        for p in profs:
            if p.window_s is not None or end is None:
                continue
            p.window_s = max(end - p.t0, 1e-9)
            _emit(p, q, journal=False)
    except Exception as e:
        _degrade(e)


# ----------------------------------------------------------------------
# critical-path analysis over span trees
# ----------------------------------------------------------------------
class _ESpan:
    """Exported-event twin of ``obs.trace.Span`` (name/children/attrs +
    duration), so one critical-path core serves live traces and Chrome
    trace files alike."""

    __slots__ = ("name", "t0", "dur", "attrs", "children")

    def __init__(self, name: str, t0: float, dur: float, attrs: Dict):
        self.name = name
        self.t0 = t0
        self.dur = dur
        self.attrs = attrs or {}
        self.children: List["_ESpan"] = []

    def dur_s(self) -> float:
        return self.dur


def _events_to_tree(events: List[Dict], tid) -> List[_ESpan]:
    """Rebuild one track's span forest from its "X" events via ts/dur
    containment (events are exported in tree pre-order)."""
    spans = [
        e for e in events
        if e.get("tid") == tid and e.get("ph") == "X"
        and not str(e.get("name", "")).startswith(("query:", "prof."))
    ]
    roots: List[_ESpan] = []
    stack: List[_ESpan] = []
    for e in spans:
        sp = _ESpan(
            str(e.get("name", "")), float(e["ts"]) / 1e6,
            float(e["dur"]) / 1e6, e.get("args") or {},
        )
        while stack and sp.t0 >= stack[-1].t0 + stack[-1].dur - 1e-9:
            stack.pop()
        (stack[-1].children if stack else roots).append(sp)
        stack.append(sp)
    return roots


def _node_children(sp) -> List:
    """Direct ``plan.node.*`` descendants of a span, stopping at the
    first nested node level (each node owns its own subtree)."""
    out: List = []
    stack = list(sp.children)
    while stack:
        c = stack.pop()
        if c.name.startswith("plan.node."):
            out.append(c)
        else:
            stack.extend(c.children)
    return out


def critical_path(roots) -> Dict[str, Any]:
    """Longest-path attribution over a span forest's ``plan.node.*``
    tree: the root-to-leaf chain maximizing summed SELF time (node wall
    minus its direct child nodes' wall — concurrent-dispatch overlap is
    already collapsed into the parent's wall by the nesting).

    Returns ``{"total_s", "path": [(span, self_s)], "shares":
    {id(span): self_s / total_s for EVERY node span}}`` — off-path nodes
    carry share 0.0. Empty dict when no node spans exist."""
    top: List = []
    stack = list(roots)
    while stack:
        sp = stack.pop()
        if sp.name.startswith("plan.node."):
            top.append(sp)
        else:
            stack.extend(sp.children)
    if not top:
        return {}

    def chain(sp) -> Tuple[float, List[Tuple[Any, float]]]:
        kids = _node_children(sp)
        self_s = max(sp.dur_s() - sum(k.dur_s() for k in kids), 0.0)
        best_t, best_p = 0.0, []
        for k in kids:
            t, pth = chain(k)
            if t > best_t:
                best_t, best_p = t, pth
        return self_s + best_t, [(sp, self_s)] + best_p

    total, path = max((chain(sp) for sp in top), key=lambda tp: tp[0])
    total = max(total, 1e-12)
    shares = {id(sp): self_s / total for sp, self_s in path}
    # every node OFF the path gets an explicit 0 share
    stack = list(top)
    while stack:
        sp = stack.pop()
        shares.setdefault(id(sp), 0.0)
        stack.extend(_node_children(sp))
    return {"total_s": total, "path": path, "shares": shares}


def node_crit_shares(q) -> Dict[int, float]:
    """{id(span): critical-path share} over a live QueryTrace's node
    spans — the ``explain(analyze=True)`` "crit %" substrate."""
    cp = critical_path(q.spans)
    return cp.get("shares", {}) if cp else {}


#: span-name families folded into stage buckets when no measured
#: prof_*_ms annotations exist on a trace (an unprofiled run still gets
#: a coarse host-wall stage attribution)
_STAGE_SPAN_FAMILIES: Tuple[Tuple[str, str], ...] = (
    ("shuffle.round.pack", "pack"),
    ("shuffle.round.collective", "collective"),
    ("shuffle.round.compact", "compact"),
    ("shuffle.round.relay", "relay"),
    ("shuffle.spill.stage", "relay"),
    ("shuffle.count", "count"),
)


def critical_report(events: List[Dict], tid) -> Optional[Dict[str, Any]]:
    """The ``traceview --critical`` substrate for ONE exported track:
    critical-path node attribution plus the bottleneck STAGE — from the
    measured ``prof_<stage>_ms`` stage clocks when the run was profiled,
    else folded from the stage span families' host walls."""
    roots = _events_to_tree(events, tid)
    if not roots:
        return None
    cp = critical_path(roots)
    stages: Dict[str, float] = {}
    measured = False
    stack = list(roots)
    while stack:
        sp = stack.pop()
        stack.extend(sp.children)
        for k, v in sp.attrs.items():
            if (
                k.startswith("prof_") and k.endswith("_ms")
                and isinstance(v, (int, float))
            ):
                measured = True
                stages[k[5:-3]] = stages.get(k[5:-3], 0.0) + float(v)
    if not measured:
        stack = list(roots)
        while stack:
            sp = stack.pop()
            stack.extend(sp.children)
            for prefix, stage in _STAGE_SPAN_FAMILIES:
                if sp.name.startswith(prefix):
                    stages[stage] = stages.get(stage, 0.0) + sp.dur_s() * 1e3
                    break
    bottleneck = max(stages, key=stages.get) if stages else None
    out: Dict[str, Any] = {
        "stages_ms": {s: round(v, 3) for s, v in stages.items()},
        "measured": measured,
        "bottleneck": bottleneck,
    }
    if cp:
        out["total_ms"] = cp["total_s"] * 1e3
        out["path"] = [
            (sp.name[len("plan.node."):], self_s * 1e3,
             cp["shares"][id(sp)])
            for sp, self_s in cp["path"]
        ]
    return out
