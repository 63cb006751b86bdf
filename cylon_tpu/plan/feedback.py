"""Telemetry-driven gate re-costing: the feedback autopilot.

Every adaptive gate in the engine runs on a static guess — the 32 MiB
shuffle budget (``config.DEFAULT_SHUFFLE_BYTE_BUDGET``), the semi-filter
size gate (``SEMI_FILTER_MIN_PAYOFF``), the pow2 serve-batch bucket, the
spill-tier budget line — while the observation store (``obs/store.py``)
holds, per gated plan fingerprint, exactly what those heuristics
approximate: measured hottest-bucket rows, bytes/row, semi-filter
selectivity, per-shard staged bytes, and the serving latency histogram.
This module closes the loop at OPTIMIZE time: :func:`decisions_for`
consults the fingerprint's profile and returns a :class:`Decisions`
record overriding the statics, with HYSTERESIS (a decision flips only
after ``CYLON_TPU_AUTOTUNE_MIN_OBS`` consistent observations, and — for
cost-modeled decisions — only when the incumbent's modeled cost exceeds
the candidate's by ``CYLON_TPU_AUTOTUNE_MARGIN``), so noisy workloads
never oscillate recompiles.

FINGERPRINT DISCIPLINE — the non-negotiable part: every tuned decision
rides the plan fingerprint. :func:`fingerprint_component` returns the
``(active, Decisions)`` tuple that ``plan/lazy.gated_fingerprint``
appends beside the ordering/semi/lane-pack/spill gates, so graft-lint's
``gate-not-in-key`` rule polices the autotune state like every other
gate and a decision flip re-enters the plan cache (exactly one
recompile), never aliases a cached executor built under the other
regime. Profiles are keyed by the BASE fingerprint (:func:`base_key` —
everything EXCEPT this component), so a flip keeps feeding the same
evidence instead of fragmenting it.

APPLICATION: the decisions chosen at optimize time reach the execution
sites through the :func:`applying` context (a contextvar the dispatch /
serving paths open around plan execution): ``table._shuffle_many`` reads
:func:`tuned_shuffle_budget` / :func:`tuned_spill_tier`,
``table._shuffle_pair`` reads :func:`tuned_semi_mode`, and the serving
scheduler caps its batch group size with ``Decisions.serve_bucket``.
Every decision is POLICY, never semantics — results are bit-identical to
the static-heuristic run (``CYLON_TPU_NO_AUTOTUNE=1``, the differential
oracle; ``tools/fuzz_campaign.py --profile autotune`` pins it).

The semi decision has a measure-then-decide lifecycle: a shape with no
selectivity evidence runs in ``"explore"`` mode (the sketch builds past
the static size gate so the count pass MEASURES selectivity — bounded
cost: after ``MIN_OBS`` observations the decision settles to ``"on"``
(low observed selectivity: force the sketch), ``"off"`` (high: skip
even building it, saving the sketch collective), or static (mid-band —
fall back to the payoff gate).
"""
from __future__ import annotations

import contextlib
import hashlib
import threading
from contextvars import ContextVar
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

from ..obs import store as _store
from ..utils import envgate as _eg

# the autotune kill switch: the static-heuristic oracle for
# differentials, declared beside the other consumer-module gates.
# Threaded into the executable identity via fingerprint_component below.
autotune_enabled, autotune_disabled = _eg.env_gate(
    "CYLON_TPU_NO_AUTOTUNE",
    keyed_via="plan/lazy.gated_fingerprint appends this module's "
    "(active, Decisions) component to every plan fingerprint — the "
    "plan-executable cache, the serving batch cache and the latency "
    "histograms all key through it, so a gate flip (or any tuned "
    "decision flip) recompiles instead of aliasing",
    note="=1 disables telemetry-driven gate re-costing (the "
    "static-heuristic differential oracle)",
)

#: selectivity bands for the semi decision (hysteresis lives in the gap)
SEL_FORCE_ON = 0.6
SEL_FORCE_OFF = 0.9
#: tuned-budget clamp (bytes)
BUDGET_FLOOR = 1 << 20
BUDGET_CEIL = 1 << 28
#: promote the spill tier when observed staged bytes reach this fraction
#: of the device budget; release the promotion under the low-water mark
SPILL_HIGH_WATER = 0.8
SPILL_LOW_WATER = 0.6
#: tuned admission footprints never lease below this (mirrors the
#: scheduler's _EST_FLOOR: zero-size queries stay countable)
FOOTPRINT_FLOOR = 1024
#: skew-trigger tuning (ROADMAP-4, ISSUE 15): the engagement ratio the
#: relay drops to when the stage clocks show a straggler the static
#: 4x-mean trigger ignores, the observed hot/mean band that counts as
#: "mild skew the static trigger misses", and the straggler-ratio
#: evidence floor (max/mean per-stage shard time, obs/prof.py) below
#: which the padded plan is fine and nothing flips
SKEW_TRIGGER_TUNED = 2
SKEW_MILD_MIN = 1.5
STRAGGLER_ENGAGE = 2.0


class Decisions(NamedTuple):
    """The tuned overrides for one plan shape. ``None`` = keep the
    static heuristic. Hashable + repr-stable: this tuple IS the
    fingerprint component (and the explain annotation source)."""

    shuffle_budget: Optional[int] = None
    semi_mode: Optional[str] = None   # "explore" | "on" | "off" | None
    serve_bucket: Optional[int] = None
    spill_tier: Optional[int] = None
    #: observed per-query device footprint (pow2-rounded p95 bytes from
    #: the resource ledger's evidence): the serving scheduler leases
    #: THIS instead of the static input-bytes estimate — small-footprint
    #: shapes admit more concurrency, over-estimated shapes stop
    #: thrashing backpressure (ROADMAP item 4's admission follow-up)
    footprint: Optional[int] = None
    #: skew-split engagement ratio (x mean bucket) replacing the static
    #: SKEW_MIN_RATIO=4 when the straggler ledger (obs/prof.py stage
    #: clocks) shows a shard-time straggler on a mildly-skewed shape the
    #: static trigger ignores; ``table._shuffle_many`` threads it into
    #: ``spill.plan_schedule(trigger=)``
    skew_trigger: Optional[int] = None
    #: topology hop mode (parallel/topo.py): ``"1hop"`` forces the flat
    #: single all_to_all on a declared 2-D mesh when the observed
    #: per-axis bytes show the two-hop decomposition saves nothing
    #: cross-outer (dense cross-group traffic drives cap_o to its
    #: I*cap ceiling — the extra inner hop is then pure cost);
    #: ``"2hop"`` pins the decomposition; None = the default (two-hop
    #: whenever a topology is declared). Policy only: both modes are
    #: row-exact, the CYLON_TPU_NO_TOPO oracle pins it.
    hop_mode: Optional[str] = None


DECISIONS_OFF = Decisions()
#: dec-dict sentinel: the decision was MADE and it is "keep the static"
#: (distinct from not-yet-decided, which keeps the semi explore mode on)
STATIC = "static"


def min_observations() -> int:
    try:
        return max(int(_eg.AUTOTUNE_MIN_OBS.get()), 1)
    except ValueError:
        return 8


def margin() -> float:
    try:
        return max(float(_eg.AUTOTUNE_MARGIN.get()), 0.0)
    except ValueError:
        return 0.2


def p99_target_s() -> Optional[float]:
    raw = _eg.SERVE_P99_TARGET_MS.get()
    if not raw:
        return None
    try:
        return float(raw) / 1e3
    except ValueError:
        return None


# ----------------------------------------------------------------------
# fingerprint plumbing
# ----------------------------------------------------------------------
_key_lock = threading.Lock()
_KEY_MEMO: Dict[tuple, str] = {}
_KEY_MEMO_CAP = 1024


def base_key(base: tuple) -> str:
    """Stable short key of a BASE gated fingerprint (the full tuple minus
    the feedback component): the store's profile identity. Memoized so
    the serving hot path never re-walks the deep tuple; hashed with its
    own blake2s (NOT obs.metrics.fingerprint_key) so the
    ``plan.fingerprint.hash`` counter pins stay flat."""
    k = _KEY_MEMO.get(base)
    if k is None:
        k = hashlib.blake2s(repr(base).encode(), digest_size=6).hexdigest()
        with _key_lock:
            if len(_KEY_MEMO) >= _KEY_MEMO_CAP:
                _KEY_MEMO.pop(next(iter(_KEY_MEMO)))
            _KEY_MEMO[base] = k
    return k


def fingerprint_component(base: tuple) -> tuple:
    """The ``(active, Decisions)`` element ``gated_fingerprint`` appends.
    ``active`` is True only when the kill switch is off AND a store is
    configured — flipping either re-keys every plan, exactly like the
    ordering/semi/lane-pack gates beside it."""
    active = autotune_enabled() and _store.store() is not None
    if not active:
        return (False, DECISIONS_OFF)
    return (True, decisions_for(base))


def decisions_for(base: tuple) -> Decisions:
    """The current tuned decisions for a base fingerprint: a lock-free
    read of the profile's cached decision tuple (updated under the store
    lock as observations arrive). A shape with no profile yet starts in
    semi explore mode (measure-then-decide)."""
    s = _store.store()
    if s is None:
        return DECISIONS_OFF
    tup = s.dec_tuple(base_key(base))
    if tup is None:
        return Decisions(semi_mode="explore")
    return Decisions(*tup)


def decisions_of(fingerprint: tuple) -> Decisions:
    """The Decisions embedded in a FULL gated fingerprint (its trailing
    feedback component), for consumers holding the fingerprint itself —
    the serving scheduler's group-size cap."""
    comp = fingerprint[-1]
    if (
        isinstance(comp, tuple) and len(comp) == 2
        and isinstance(comp[1], Decisions) and comp[0]
    ):
        return comp[1]
    return DECISIONS_OFF


# ----------------------------------------------------------------------
# application context: optimize-time decisions -> execution sites
# ----------------------------------------------------------------------
_APPLIED: "ContextVar[Optional[Decisions]]" = ContextVar(
    "cylon_tpu_autotune_applied", default=None
)


@contextlib.contextmanager
def applying(component: tuple):
    """Make a fingerprint's decisions visible to the execution sites for
    the block (dispatch / serving wrap plan execution in this). The
    component is what :func:`fingerprint_component` returned FOR THE KEY
    the executor was cached under — application and identity can never
    disagree."""
    if not (isinstance(component, tuple) and len(component) == 2 and component[0]):
        yield
        return
    token = _APPLIED.set(component[1])
    try:
        yield
    finally:
        _APPLIED.reset(token)


def tuned_shuffle_budget() -> Optional[int]:
    d = _APPLIED.get()
    return d.shuffle_budget if d is not None else None


def tuned_semi_mode() -> Optional[str]:
    d = _APPLIED.get()
    return d.semi_mode if d is not None else None


def tuned_spill_tier() -> Optional[int]:
    d = _APPLIED.get()
    return d.spill_tier if d is not None else None


def tuned_skew_trigger() -> Optional[int]:
    d = _APPLIED.get()
    return d.skew_trigger if d is not None else None


def tuned_hop_mode() -> Optional[str]:
    d = _APPLIED.get()
    return d.hop_mode if d is not None else None


# ----------------------------------------------------------------------
# proposers + hysteresis (called by the store as observations absorb)
# ----------------------------------------------------------------------
def effective_decisions(p: Dict[str, Any]) -> tuple:
    """Profile -> the Decisions field tuple the fingerprint carries.
    Pure function of the profile (no mutation): the store caches its
    result per profile for the lock-free hot-path read."""
    dec = p.get("dec", {})
    sm = dec.get("semi_mode")
    if sm is None:
        # undecided: stay in explore mode until the DECISION lands (the
        # proposer settles every measured shape to on/off/static once the
        # evidence clears the hysteresis depth) — switching on raw
        # observation counts here would recompile twice per flip
        sm = "explore"
    elif sm == STATIC:
        sm = None
    return (
        dec.get("shuffle_budget"),
        sm,
        dec.get("serve_bucket"),
        dec.get("spill_tier"),
        dec.get("footprint"),
        dec.get("skew_trigger"),
        dec.get("hop_mode"),
    )


def update_profile_decisions(p: Dict[str, Any], kind: str = "exec") -> None:
    """Re-cost the tuned decisions the arriving record kind carries
    evidence for (``exec`` -> shuffle budget / semi / spill tier;
    ``lat`` -> serve bucket), flipping under hysteresis: a candidate
    differing from the incumbent must win ``min_observations()``
    CONSECUTIVE gate-relevant observations (alternating evidence resets
    the streak — the no-flap pin) and, where a cost model exists, beat
    the incumbent by ``margin()``. Runs under the store lock."""
    m = min_observations()
    dec = p.setdefault("dec", {})
    pend = p.setdefault("pend", {})
    flipped = False
    for field, (cand, margin_ok) in _proposals(p, kind).items():
        cur = dec.get(field)
        if cand == cur:
            pend.pop(field, None)
            continue
        enc = repr(cand)
        pe = pend.get(field)
        if pe is not None and pe[0] == enc:
            pe[1] += 1
        else:
            pe = pend[field] = [enc, 1]
        if pe[1] >= m and margin_ok and not flipped:
            # at most ONE field flips per observation: every flip
            # re-keys the plan, and the recompile pin (exactly one
            # plan-cache miss per flip) must hold even when two gates'
            # hysteresis streaks mature on the same record — the
            # runner-up keeps its matured streak and flips on the next
            # gate-relevant observation
            dec[field] = cand
            pend.pop(field, None)
            flipped = True
            p["flips"] = p.get("flips", 0) + 1
            if field == "serve_bucket":
                # the latency evidence was gathered under the OLD bucket;
                # a fresh window judges the new one (else the stale p99
                # keeps proposing further halvings)
                from ..obs.store import _new_lat

                p["serve_lat"] = _new_lat()
    p["_dec"] = effective_decisions(p)


def _proposals(
    p: Dict[str, Any], kind: str = "exec"
) -> Dict[str, Tuple[Any, bool]]:
    out: Dict[str, Tuple[Any, bool]] = {}
    mg = margin()
    m = min_observations()

    if kind == "exec":
        # -- semi filter: engage/skip from observed selectivity ---------
        if p.get("sel_n", 0) >= m:
            mean_sel = p["sel_sum"] / p["sel_n"]
            if mean_sel <= SEL_FORCE_ON:
                out["semi_mode"] = ("on", True)
            elif mean_sel >= SEL_FORCE_OFF:
                out["semi_mode"] = ("off", True)
            else:
                out["semi_mode"] = (STATIC, True)

        # -- shuffle byte budget: size to the measured hottest bucket ---
        if (
            p.get("n", 0) >= m and p.get("hot", 0) > 0
            and p.get("world", 0) > 1
        ):
            cand, ok = _budget_proposal(p, mg)
            out["shuffle_budget"] = (cand, ok)

        # -- spill tier: promote before the budget line -----------------
        from ..parallel import spill as _spill

        budget = _spill.device_spill_budget()
        if budget is not None and p.get("n", 0) >= m:
            if p.get("staged_max", 0) >= SPILL_HIGH_WATER * budget:
                out["spill_tier"] = (_spill.TIER_HOST, True)
            elif p.get("staged_max", 0) < SPILL_LOW_WATER * budget:
                out["spill_tier"] = (None, True)

        # -- skew trigger: engage the relay on mild skew the static
        # 4x-mean ratio ignores, from the stage clocks' straggler
        # evidence (ROADMAP-4's open skew-trigger item) ------------------
        if (
            p.get("strag_n", 0) >= m and p.get("hot", 0) > 0
            and p.get("mean_bucket", 0) > 0 and p.get("world", 0) > 1
        ):
            cand, ok = _skew_trigger_proposal(p, mg)
            out["skew_trigger"] = (cand, ok)

        # -- topology hop mode: 1-hop vs 2-hop from the observed
        # per-axis bytes (parallel/topo.py). Every observation on a
        # 2-D-declared shape carries BOTH modes' cross-outer bytes
        # (note_shuffle's inter/inter_alt — exact host formulas), so
        # the comparison never needs an exploratory flip ---------------
        if p.get("hop_n", 0) >= m and p.get("topo"):
            cand, ok = _hop_mode_proposal(p, mg)
            out["hop_mode"] = (cand, ok)

        # -- admission footprint: lease observed bytes, not the static
        # input-size estimate. The p95 of the ledger-attributed per-query
        # device bytes, pow2-rounded so the candidate is STABLE under
        # run-to-run noise (hysteresis needs consecutive identical
        # proposals; raw p95 would never repeat) -------------------------
        foot = p.get("foot") or {}
        if foot.get("n", 0) >= m:
            from ..obs.store import lat_quantile

            p95 = lat_quantile(foot, 0.95)
            cand = 1 << max(int(p95) - 1, 1).bit_length()
            out["footprint"] = (max(cand, FOOTPRINT_FLOOR), True)

    elif kind == "lat":
        # -- serve batch bucket vs the p99 target, judged ONLY on the
        # serving latency window (samples that carried a batch size) ----
        target = p99_target_s()
        if (
            target is not None
            and p.get("serve_lat", {}).get("n", 0) >= m
        ):
            cand, ok = _serve_bucket_proposal(p, target, mg)
            out["serve_bucket"] = (cand, ok)

    return out


def _round_cost(p: Dict[str, Any], budget: int) -> int:
    """Modeled collective row slots (cap x K) for this shape under a
    byte budget, using the SAME planner the engine runs
    (shuffle.plan_rounds) over a synthetic histogram with the observed
    hottest and mean buckets."""
    from ..parallel import shuffle as _sh

    world = max(int(p.get("world", 1)), 1)
    counts = np.full(
        (world, world), max(int(p.get("mean_bucket", 0)), 0), np.int64
    )
    counts[0, 0] = int(p["hot"])
    cap, k = _sh.plan_rounds(
        counts, max(int(p["row_bytes"]), 1), world, int(budget)
    )
    return cap * k


def _budget_proposal(p: Dict[str, Any], mg: float) -> Tuple[Any, bool]:
    """Candidate byte budget sized so the hottest observed bucket clears
    in one round (``2 * world * cap_full * row_bytes`` — the inverse of
    shuffle.budget_bucket_cap's bound), clamped to [BUDGET_FLOOR,
    BUDGET_CEIL]. Margin rule: GROW only when the modeled collective
    slots shrink by >= margin (fewer rounds / less pow2 rounding waste);
    SHRINK whenever slots stay equal (pure peak-memory win)."""
    from ..config import shuffle_byte_budget
    from ..engine import round_cap

    # the baseline a candidate is judged against is the budget this
    # shape actually runs with UNtuned — the context's configured budget
    # as journaled by the execution site — not the process-wide default
    # (a context with a custom budget must tune against its own)
    static = p.get("static_budget") or shuffle_byte_budget()
    incumbent = p.get("dec", {}).get("shuffle_budget") or static
    cap_full = round_cap(int(p["hot"]))
    needed = 2 * int(p["world"]) * cap_full * int(p["row_bytes"])
    cand = int(min(max(needed, BUDGET_FLOOR), BUDGET_CEIL))
    if cand == static:
        return (None, True)
    cost_inc = _round_cost(p, incumbent)
    cost_cand = _round_cost(p, cand)
    if cand > incumbent:
        return (cand, cost_cand <= cost_inc * (1.0 - mg))
    return (cand, cost_cand <= cost_inc)


def _skew_trigger_proposal(p: Dict[str, Any], mg: float) -> Tuple[Any, bool]:
    """Candidate skew-split engagement ratio from the straggler ledger.

    The static trigger relays only buckets past 4x the mean — a 2-3x
    "mild" hot bucket still pads every collective round to its pow2 cap.
    When the profiles show (a) the shape sits in that mild band, (b) the
    stage clocks measured a real shard-time straggler
    (``STRAGGLER_ENGAGE``), and (c) re-planning the observed histogram
    under the tuned trigger actually cuts the modeled shipped cost
    (collective slots + relay-factor x relayed rows) past the margin,
    propose ``SKEW_TRIGGER_TUNED``. Anything else settles back to the
    static trigger — results are identical either way (the relay is
    routing policy), only bytes and stragglers move."""
    from ..parallel import spill as _spill

    ratio = p["hot"] / max(p["mean_bucket"], 1)
    strag = p.get("strag_sum", 0.0) / max(p.get("strag_n", 1), 1)
    if (
        ratio >= _spill.SKEW_MIN_RATIO or ratio < SKEW_MILD_MIN
        or strag < STRAGGLER_ENGAGE
    ):
        return (None, True)
    from ..config import shuffle_byte_budget

    world = max(int(p["world"]), 1)
    counts = np.full(
        (world, world), max(int(p["mean_bucket"]), 0), np.int64
    )
    counts[0, 0] = int(p["hot"])
    budget = int(
        p.get("dec", {}).get("shuffle_budget")
        or p.get("static_budget") or shuffle_byte_budget()
    )
    rb = max(int(p["row_bytes"]), 1)
    s_static = _spill.plan_schedule(counts, rb, world, budget)
    s_tuned = _spill.plan_schedule(
        counts, rb, world, budget, trigger=SKEW_TRIGGER_TUNED
    )
    if not s_tuned.adaptive:
        return (None, True)  # the tuned trigger would not engage either

    def cost(s):
        return (
            s.coll_row_slots(world)
            + _spill.RELAY_COST_FACTOR * s.relay_rows()
        )

    return (
        SKEW_TRIGGER_TUNED,
        cost(s_tuned) <= cost(s_static) * (1.0 - mg),
    )


def _hop_mode_proposal(p: Dict[str, Any], mg: float) -> Tuple[Any, bool]:
    """Candidate hop mode from the per-axis byte evidence.

    Two-hop exists to shrink the cross-outer (slow-axis) traffic: the
    padded-chunk overhead drops from O(world * cap) to O(outer * cap_o),
    but only when traffic is clustered enough that cap_o stays under its
    I*cap ceiling — a dense cross-group workload gets NO outer saving
    and pays the inner hop on top. The profile holds both modes' mean
    cross-outer bytes for the same observed plans, so: propose "1hop"
    when two-hop's cross-outer bytes fail to undercut flat's by the
    margin (the decomposition is pure cost here), settle back to None
    (the two-hop default) once the saving clears it. Results are
    identical either way — only bytes and recompiles move."""
    n = max(int(p.get("hop_n", 1)), 1)
    i2 = p.get("hop_i2_sum", 0) / n
    i1 = p.get("hop_i1_sum", 0) / n
    if i1 <= 0:
        return (None, True)
    if i2 > i1 * (1.0 - mg):
        return ("1hop", True)
    return (None, True)


def _serve_bucket_proposal(
    p: Dict[str, Any], target: float, mg: float
) -> Tuple[Any, bool]:
    from ..obs.store import lat_quantile

    try:
        batch_max = max(int(_eg.SERVE_BATCH_MAX.get()), 1)
    except ValueError:
        batch_max = 16
    cur = p.get("dec", {}).get("serve_bucket") or batch_max
    p99 = lat_quantile(p.get("serve_lat") or p["lat"], 0.99)
    if p99 > target:
        cand = max(cur // 2, 1)
        return (cand if cand < batch_max else None,
                p99 > target * (1.0 + mg))
    if p99 <= target * 0.5 and cur < batch_max:
        cand = min(cur * 2, batch_max)
        return (cand if cand < batch_max else None, True)
    return (cur if cur < batch_max else None, True)


# ----------------------------------------------------------------------
# explain(analyze=True) annotations
# ----------------------------------------------------------------------
def describe(base: tuple) -> list:
    """Human-readable ``<gate> tuned: <value> (was <static>, n=<obs>)``
    lines for every tuned decision of this shape (empty when autotune is
    inactive or nothing is tuned)."""
    s = _store.store()
    if s is None or not autotune_enabled():
        return []
    key = base_key(base)
    d = decisions_for(base)
    p = s.profile_snapshot(key) or {}
    from ..config import SEMI_FILTER_MIN_PAYOFF, shuffle_byte_budget

    lines = []
    if d.shuffle_budget is not None:
        lines.append(
            f"shuffle_budget tuned: {d.shuffle_budget} "
            f"(was {shuffle_byte_budget()}, n={p.get('n', 0)})"
        )
    if d.semi_mode is not None:
        lines.append(
            f"semi_filter tuned: {d.semi_mode} "
            f"(was payoff>={SEMI_FILTER_MIN_PAYOFF}x, n={p.get('sel_n', 0)})"
        )
    if d.serve_bucket is not None:
        try:
            bm = int(_eg.SERVE_BATCH_MAX.get())
        except ValueError:
            bm = 16
        lines.append(
            f"serve_bucket tuned: {d.serve_bucket} "
            f"(was {bm}, n={p.get('serve_lat', {}).get('n', 0)})"
        )
    if d.spill_tier is not None:
        lines.append(
            f"spill_tier tuned: {d.spill_tier} "
            f"(was budget-line, n={p.get('n', 0)})"
        )
    if d.footprint is not None:
        lines.append(
            f"admission footprint tuned: {d.footprint} B "
            f"(was input-bytes estimate, "
            f"n={p.get('foot', {}).get('n', 0)})"
        )
    if d.skew_trigger is not None:
        from ..parallel.spill import SKEW_MIN_RATIO

        lines.append(
            f"skew_trigger tuned: {d.skew_trigger}x-mean "
            f"(was {SKEW_MIN_RATIO}x-mean, "
            f"n={p.get('strag_n', 0)})"
        )
    if d.hop_mode is not None:
        lines.append(
            f"hop_mode tuned: {d.hop_mode} "
            f"(was 2hop-on-topology, n={p.get('hop_n', 0)})"
        )
    return lines
