"""Declared environment knobs: the ONE registry of every ``CYLON_TPU_*``
variable the framework reads, plus the shared kill-switch machinery.

Why a registry instead of scattered ``os.environ.get`` calls: PRs 1-5 each
shipped "review hardening" fixes from the same bug family — a gate that
changes kernel behavior but is missing from a kernel cache key, so a
mid-process env flip silently reuses the program compiled under the other
gate state. The static analyzer (``cylon_tpu/analysis``; ``python -m
tools.graft_lint``) enforces that invariant mechanically, and it needs a
machine-readable answer to "what kind of knob is this and how does it
reach compiled programs?". Every knob therefore declares:

- ``kind`` — the policy class the analyzer applies (see ``KINDS`` below);
- ``keyed_via`` — for knobs that alter traced programs, the audited
  description of the mechanism that threads them into the kernel cache
  key / plan fingerprint (the analyzer verifies the mechanism exists for
  ``impl``/``kill-switch`` kinds; for the others the declaration IS the
  audit and the analyzer instead enforces the kind's read-site policy).

Reading a ``CYLON_TPU_*`` variable through raw ``os.environ`` anywhere in
``cylon_tpu/`` is itself a lint finding (rule ``unregistered-env-read``):
new knobs start here.
"""
from __future__ import annotations

import contextlib
import os
from typing import Dict, Optional

# ----------------------------------------------------------------------
# knob kinds and the analyzer policy attached to each
# ----------------------------------------------------------------------
KINDS = {
    # Read at TRACE time (inside a kernel body) or while choosing what a
    # kernel body will contain: MUST be threaded into every consumer
    # kernel's cache key (the analyzer verifies a keyed carrier exists).
    "impl": "trace-time kernel-impl choice; must land in the cache key",
    # VAR=1 disables an optimization; the gate decision changes traced
    # programs, so consumers must key it exactly like an impl knob.
    "kill-switch": "optimization escape hatch; gate decision must be keyed",
    # Selects WHICH distinctly-keyed dispatch path runs; never read inside
    # a kernel body (the analyzer enforces host-only reads).
    "dispatch": "host-side path selection between distinctly-keyed programs",
    # Host-resolved numeric tuning; reaches programs only through operand
    # shapes / replicated operands, which jit keys intrinsically. Host-only
    # reads enforced.
    "tuning": "host-resolved sizing knob; reaches kernels via shapes only",
    # Read once at import / context init, before any kernel exists.
    "startup": "import/init-time configuration",
    # Alters logging only, never a compiled program.
    "observability": "logging/trace output only",
    # Native-extension build configuration (no XLA program involvement).
    "native": "native extension build/runtime config",
}

REGISTRY: Dict[str, "EnvKnob"] = {}


class EnvKnob:
    """One declared environment variable. Instantiating registers it."""

    __slots__ = ("var", "default", "kind", "keyed_via", "note")

    def __init__(
        self,
        var: str,
        default: str = "",
        kind: str = "impl",
        keyed_via: Optional[str] = None,
        note: str = "",
    ) -> None:
        if kind not in KINDS:
            raise ValueError(f"unknown knob kind {kind!r} for {var}")
        if kind in ("impl", "kill-switch") and not keyed_via:
            raise ValueError(
                f"{var}: kind={kind!r} requires keyed_via= (the audited "
                "cache-key threading mechanism)"
            )
        self.var = var
        self.default = default
        self.kind = kind
        self.keyed_via = keyed_via
        self.note = note
        REGISTRY[var] = self

    def get(self) -> str:
        """Current value (per-call read — flips take effect immediately)."""
        return os.environ.get(self.var, self.default)

    def raw(self) -> Optional[str]:
        """Raw environment value, ``None`` when unset (no default)."""
        return os.environ.get(self.var)

    def truthy(self) -> bool:
        """Set to anything non-empty and non-'0'."""
        return self.get() not in ("", "0")

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"EnvKnob({self.var!r}, kind={self.kind!r})"


def env_gate(var: str, keyed_via: str = "", note: str = ""):
    """(enabled, disabled) pair for a ``VAR=1``-disables kill switch.

    ``enabled()`` reads the env per call — gate flips between calls take
    effect immediately (consumers key compiled kernels on the chosen
    path, so flips recompile, never alias). ``disabled()`` is a
    reentrant save/set/restore context manager: the differential-oracle
    toggle for tests and fuzz profiles.

    Declares the variable in the registry as a kill-switch; ``keyed_via``
    documents (for the analyzer and for reviewers) the mechanism that
    threads the gate decision into kernel cache keys / plan fingerprints.
    """
    EnvKnob(
        var,
        "0",
        kind="kill-switch",
        keyed_via=keyed_via
        or "consumers thread each gate decision into their kernel cache "
        "key; the plan fingerprint includes the gate (plan/lazy.py)",
        note=note,
    )

    def enabled() -> bool:
        return os.environ.get(var, "0") != "1"

    @contextlib.contextmanager
    def disabled():
        prev = os.environ.get(var)
        os.environ[var] = "1"
        try:
            yield
        finally:
            if prev is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = prev

    return enabled, disabled


# ----------------------------------------------------------------------
# knob declarations (kill-switch gates are declared at their consumer
# modules via env_gate: CYLON_TPU_NO_ORDERING in ordering.py,
# CYLON_TPU_NO_SEMI_FILTER in ops/sketch.py, CYLON_TPU_NO_LANE_PACK in
# ops/stats.py)
# ----------------------------------------------------------------------

# -- trace-time kernel-impl choices (ops/join.py) -----------------------
# All four are read while building join-family kernel bodies; impl_tag()
# packages their values as the cache-key component every join-family key
# appends, so a mid-process A/B flip recompiles instead of reusing the
# stale program.
REPEAT_IMPL = EnvKnob(
    "CYLON_TPU_REPEAT_IMPL", "scatter", kind="impl",
    keyed_via="ops.join.impl_tag appended to every join-family cache key",
    note="repeat-expand lowering: 'scatter' (default, measured faster on "
    "v5e) or 'sort' (argsort trick)",
)
SEGSUM_IMPL = EnvKnob(
    "CYLON_TPU_SEGSUM_IMPL", "scatter", kind="impl",
    keyed_via="ops.join.impl_tag appended to every join-family cache key",
    note="segment-sum lowering in the fused join->groupby pushdown",
)
EMIT_IMPL = EnvKnob(
    "CYLON_TPU_EMIT_IMPL", "gather", kind="impl",
    keyed_via="ops.join.impl_tag appended to every join-family cache key",
    note="join emit: 'gather' (default) or 'windowed' (Pallas expand)",
)
EXPAND_GATHER = EnvKnob(
    "CYLON_TPU_EXPAND_GATHER", "take", kind="impl",
    keyed_via="ops.join.impl_tag appended to every join-family cache key",
    note="in-kernel gather flavor of the Pallas windowed expand",
)
FORCE_SHARD_MAP = EnvKnob(
    "CYLON_TPU_FORCE_SHARD_MAP", "0", kind="impl",
    keyed_via="engine.get_kernel appends its wrapping flags "
    "(use_shard_map, check_vma) to every cache key",
    note="keep shard_map on a 1-device mesh (hardware probe only)",
)

# -- host-side dispatch selection --------------------------------------
EXACT_JOIN = EnvKnob(
    "CYLON_TPU_EXACT_JOIN", "0", kind="dispatch",
    keyed_via="speculative and exact paths dispatch under distinct key "
    "suffixes ('spec' vs 'probe'/'emit'); no program aliasing",
    note="=1 forces the exact two-phase count->emit join path",
)

# -- host-resolved tuning ----------------------------------------------
SHUFFLE_BUDGET = EnvKnob(
    "CYLON_TPU_SHUFFLE_BUDGET", "", kind="tuning",
    keyed_via="budget -> bucket_cap -> static shapes of the round "
    "kernels' rep operands (jit shape specialization)",
    note="per-round shuffle exchange byte budget (config.py)",
)
SKETCH_BITS = EnvKnob(
    "CYLON_TPU_SKETCH_BITS", "", kind="tuning",
    keyed_via="bits -> sketch operand shapes + the 'semi_sketch' cache "
    "key's bits component",
    note="semi-join sketch bit cap (config.py)",
)

# -- streaming ingest + incremental views (cylon_tpu/stream/; the
# CYLON_TPU_NO_IVM kill switch — the full-recompute differential oracle
# — is declared at its consumer module, stream/delta.py, via env_gate) --
STREAM_CHUNK_ROWS = EnvKnob(
    "CYLON_TPU_STREAM_CHUNK_ROWS", "", kind="tuning",
    keyed_via="host-side staging only: chunking bounds the per-append "
    "copy into the state arena and never reaches a kernel shape (the "
    "snapshot's shard caps are derived from TOTAL arena rows)",
    note="max rows copied into the stream state arena per staging chunk "
    "(stream/ingest.py); unset/empty = 65536",
)
STREAM_STATE_BUDGET = EnvKnob(
    "CYLON_TPU_STREAM_STATE_BUDGET", "", kind="tuning",
    keyed_via="host-side admission only (append-time byte check against "
    "the table's state arena); rejected appends roll back before any "
    "buffer is touched, so no compiled program ever sees the decision",
    note="max state-arena bytes per appendable table (stream/ingest.py); "
    "an append that would exceed it fails typed (StreamIngestError, "
    "prior generation untouched); unset/empty = unlimited",
)

# -- quantized float wire tier (ops/quant.py; the CYLON_TPU_NO_QUANT
# kill switch is declared at its consumer module via env_gate) ----------
QUANT_TOL = EnvKnob(
    "CYLON_TPU_QUANT_TOL", "", kind="dispatch",
    keyed_via="host-side codec selection: the tolerance picks each float "
    "payload column's lossy codec (ops.quant.codec_for), and the decided "
    "codecs ride the WirePlan 'q' fields already appended to every "
    "pack/compact kernel cache key (plus the relay/spill quant "
    "signatures); the plan fingerprint carries ops.quant.gate_state — "
    "no program aliasing across a tolerance flip",
    note="per-column relative error tolerance of the lossy float wire "
    "tier (shuffle wire, spill staging, skew relay, fused psum): "
    ">= 1e-2 engages block-scaled int8, >= 2^-8 bf16, >= 2^-23 "
    "f64->f32 demotion; unset/empty = exact wire (today's behavior)",
)

# -- spill tiers (parallel/spill.py; the CYLON_TPU_NO_SKEW_SPLIT kill
# switch is declared at its consumer module via env_gate) ---------------
SPILL_TIER = EnvKnob(
    "CYLON_TPU_SPILL_TIER", "", kind="dispatch",
    keyed_via="host-side tier selection between the in-HBM round path "
    "and the arena staging path; staged and in-HBM rounds dispatch the "
    "same compiled kernels plus the separately-keyed ('spill_pack',) "
    "fetch program — no program aliasing. The forced tier also rides "
    "the plan fingerprint (spill.gate_state in plan/lazy.py)",
    note="force the spill tier: 0=HBM rounds, 1=host-RAM arenas, "
    "2=disk-backed arenas; empty = decide from the measured counts",
)
SPILL_DEVICE_BUDGET = EnvKnob(
    "CYLON_TPU_SPILL_DEVICE_BUDGET", "", kind="tuning",
    keyed_via="per-shard staged-output byte threshold for the host-side "
    "tier decision; reaches no compiled program (staging fetches the "
    "same round outputs the in-HBM path keeps resident)",
    note="per-shard staged-output bytes above which shuffle rounds "
    "spill off-device (unset = never, tier 0 unless forced)",
)
SPILL_HOST_BUDGET = EnvKnob(
    "CYLON_TPU_SPILL_HOST_BUDGET", "", kind="tuning",
    keyed_via="host arena allocation policy only (RAM vs memmap "
    "backing); never reaches a compiled program",
    note="total live host-arena bytes above which arena growth promotes "
    "to disk-backed buffers (tier 1 -> tier 2)",
)
SPILL_DIR = EnvKnob(
    "CYLON_TPU_SPILL_DIR", "", kind="tuning",
    keyed_via="filesystem location of tier-2 memmap files only; never "
    "reaches a compiled program",
    note="directory for tier-2 disk-spill arenas (default: a tempdir)",
)

# -- query serving (cylon_tpu/serve) -----------------------------------
# All three are host-resolved admission/batching knobs read per call in
# the scheduler (flips take effect on the next submit/drain cycle); none
# is ever read at trace time. BATCH_MAX is the only one that reaches
# compiled programs at all — through the batch size, which lands in both
# the stacked operand shapes (jit shape specialization) and the
# (fingerprint, B-bucket) batched-executor cache key.
SERVE_INFLIGHT_BYTES = EnvKnob(
    "CYLON_TPU_SERVE_INFLIGHT_BYTES", "", kind="tuning",
    keyed_via="admission control only: bounds the estimated bytes of "
    "admitted-but-unCONSUMED queries (leases released at result "
    "materialization / failure / future GC); never reaches a compiled "
    "program",
    note="serving in-flight byte budget (default 1 GiB); a single query "
    "estimated above it is shed with ServeOverloadError",
)
SERVE_BATCH_MAX = EnvKnob(
    "CYLON_TPU_SERVE_BATCH_MAX", "16", kind="tuning",
    keyed_via="batch size -> pow2 B bucket -> the (fingerprint, B) "
    "serve_batch_executable cache key + stacked operand shapes (jit "
    "shape specialization)",
    note="max same-fingerprint bindings fused into one stacked device "
    "program (pow2-bucketed; 1 disables batching, keeping async submit)",
)
SERVE_QUEUE_DEPTH = EnvKnob(
    "CYLON_TPU_SERVE_QUEUE_DEPTH", "256", kind="tuning",
    keyed_via="host-side admission only: bounds the pending-query queue; "
    "never reaches a compiled program",
    note="pending-query cap per scheduler: a full queue backpressures "
    "blocking submitters and sheds nowait submitters",
)

# -- import/init-time configuration ------------------------------------
NO_X64 = EnvKnob(
    "CYLON_TPU_NO_X64", "", kind="startup",
    note="=1 skips jax_enable_x64 at import (pure-32-bit pipelines)",
)
PLATFORM = EnvKnob(
    "CYLON_TPU_PLATFORM", "", kind="startup",
    note="pin the jax platform before first backend touch",
)
COMPILE_EFFORT = EnvKnob(
    "CYLON_TPU_COMPILE_EFFORT", "", kind="startup",
    note="XLA scheduling-effort tradeoff, read once at import",
)
COMPILE_CACHE = EnvKnob(
    "CYLON_TPU_COMPILE_CACHE", "", kind="startup",
    note="=0 opts accelerator contexts out of the default persistent "
    "compile cache (<checkout>/.jax_cache); JAX_COMPILATION_CACHE_DIR "
    "places the cache, this knob never does",
)

# -- self-tuning execution (obs/store.py + plan/feedback.py; the
# CYLON_TPU_NO_AUTOTUNE kill switch is declared at its consumer module
# plan/feedback.py via env_gate) ----------------------------------------
OBS_DIR = EnvKnob(
    "CYLON_TPU_OBS_DIR", "", kind="tuning",
    keyed_via="presence/location of the persistent observation store; "
    "the autotune state it enables rides the plan fingerprint as the "
    "(active, Decisions) component plan/feedback.fingerprint_component "
    "appends in plan/lazy.gated_fingerprint — every tuned decision is "
    "part of the executable identity, so a store flip re-enters the "
    "plan cache instead of aliasing",
    note="directory of the persistent per-fingerprint observation "
    "journal (obs/store.py); unset disables the store AND every "
    "telemetry-driven gate re-costing decision",
)
AUTOTUNE_MIN_OBS = EnvKnob(
    "CYLON_TPU_AUTOTUNE_MIN_OBS", "8", kind="tuning",
    keyed_via="hysteresis depth of the feedback re-coster only: a tuned "
    "decision flips after this many CONSISTENT observations; the flipped "
    "decision (not this knob) rides the plan fingerprint",
    note="observations a candidate decision must win consecutively "
    "before the feedback optimizer flips a gate (plan/feedback.py)",
)
AUTOTUNE_MARGIN = EnvKnob(
    "CYLON_TPU_AUTOTUNE_MARGIN", "0.2", kind="tuning",
    keyed_via="hysteresis margin of the feedback re-coster only: the "
    "incumbent decision's modeled cost must exceed the candidate's by "
    "this fraction before a flip; the flipped decision rides the plan "
    "fingerprint",
    note="relative cost margin a candidate decision must beat the "
    "incumbent by before the feedback optimizer flips (plan/feedback.py)",
)
SERVE_P99_TARGET_MS = EnvKnob(
    "CYLON_TPU_SERVE_P99_TARGET_MS", "", kind="tuning",
    keyed_via="feeds the serve-batch-bucket proposal only; the chosen "
    "bucket rides the plan fingerprint (Decisions.serve_bucket) and the "
    "(fingerprint, B-bucket) serve_batch_executable key",
    note="per-fingerprint serving p99 target in milliseconds: observed "
    "p99 above it halves the tuned serve batch bucket, p99 under half "
    "of it doubles the bucket back toward CYLON_TPU_SERVE_BATCH_MAX "
    "(unset = no batch-size tuning)",
)

# -- chaos / robustness (cylon_tpu/fault + the degradation machinery) ---
# FAULTS alters which HOST code paths raise (never a compiled program, a
# cache key, or a result when it doesn't fire): observability kind,
# host-only reads enforced. SPILL_RETRIES and SERVE_DEADLINE_MS are
# host-resolved policy numbers read per call; neither reaches a kernel.
FAULTS = EnvKnob(
    "CYLON_TPU_FAULTS", "", kind="observability",
    note="deterministic fault-injection spec (cylon_tpu/fault/inject.py): "
    "comma-separated 'seam[:p=0.05][:kind=ENOSPC][:n=3][:seed=7]"
    "[:match=substr]' clauses arming the named seams (spill.write/"
    "spill.read/arena.alloc/serve.batch_exec/serve.single_exec/"
    "serve.worker/obs.journal/obs.prof). Seeded per-seam RNG: a "
    "campaign replays "
    "from its spec. Unset = every seam is a module-level no-op; read at "
    "import and at explicit fault.inject.refresh()/reset() — the hook "
    "is REBOUND, not re-gated per call, to keep the disabled cost at a "
    "bare function call",
)
SPILL_RETRIES = EnvKnob(
    "CYLON_TPU_SPILL_RETRIES", "2", kind="tuning",
    keyed_via="host-side spill I/O retry depth only (bounded backoff in "
    "parallel/spill._retry_io); never reaches a compiled program",
    note="bounded-backoff retries for a failed spill arena write/read "
    "before the degradation ladder re-plans onto the host-RAM tier (or "
    "fails the one query with SpillIOError)",
)
SERVE_DEADLINE_MS = EnvKnob(
    "CYLON_TPU_SERVE_DEADLINE_MS", "", kind="tuning",
    keyed_via="host-side serving policy only: bounds a query's "
    "submit-to-fulfillment wall; expired queries FAIL typed "
    "(QueryTimeoutError) with their admission lease released instead of "
    "hanging; never reaches a compiled program",
    note="per-query serving deadline in milliseconds, measured from "
    "submit: enforced at batch formation (expired queued queries fail "
    "without executing) and in QueryFuture.result()/exception() waits "
    "(unset = no deadline — waits are caller-bounded only)",
)

# -- observability ------------------------------------------------------
# All three trace knobs are host-only by declared contract (the L1
# trace-time-read rule): they gate span logging/recording/export and can
# never reach a kernel body or a cache key — an instrumented q3 dispatch
# keeps its EXACT 1-host-sync budget (analysis/contracts.py
# Q3_DISPATCH_HOST_SYNCS; runtime census in tools/trace_smoke.py).
TRACE = EnvKnob(
    "CYLON_TPU_TRACE", "0", kind="observability",
    note="=1 logs each span as it closes AND records query span trees; "
    "any other truthy value (e.g. 'tree') records the structured traces "
    "without the per-span stderr log; alters no program",
)
PROF = EnvKnob(
    "CYLON_TPU_PROF", "0", kind="observability",
    note="truthy enables the critical-path profiler (obs/prof.py): "
    "per-stage per-shard stage clocks for the shuffle round pipeline "
    "and the fused pipeline, derived on the host from the counts the "
    "engine already fetched plus the existing deferred-fetch window — "
    "zero added host syncs (graft-lint pins prof.* at 0-site budgets); "
    "alters no compiled program",
)
TRACE_RING = EnvKnob(
    "CYLON_TPU_TRACE_RING", "64", kind="observability",
    note="flight-recorder capacity: the last N finished query traces "
    "kept in memory (obs/export.py); read per record, host-only",
)
TRACE_EXPORT = EnvKnob(
    "CYLON_TPU_TRACE_EXPORT", "", kind="observability",
    note="when set, the flight ring is written to this path as Chrome "
    "trace-event JSON (Perfetto-loadable) at interpreter exit",
)
METRICS_PORT = EnvKnob(
    "CYLON_TPU_METRICS_PORT", "", kind="observability",
    note="when set, context init starts the in-process ops endpoint "
    "(obs/export.OpsServer): /metrics (Prometheus text exposition), "
    "/healthz (SLO state), /queries (flight ring as JSON). Also "
    "enables the resource ledger. '9100' binds loopback (the endpoint "
    "is unauthenticated); 'host:9100' (e.g. 0.0.0.0:9100) opts into a "
    "wider bind for off-host scrapes; 0 picks an ephemeral port "
    "(tests)",
)
SLO_WINDOW_S = EnvKnob(
    "CYLON_TPU_SLO_WINDOW_S", "60", kind="observability",
    note="rolling evaluation window (seconds) of the SLO monitor "
    "(obs/slo.py): p99 burn-rate, shed-rate and headroom rules judge "
    "only the samples inside it, so /healthz recovers once a breach "
    "ages out of the window",
)
LEAK_GRACE_S = EnvKnob(
    "CYLON_TPU_LEAK_GRACE_S", "30", kind="observability",
    note="resource-ledger leak grace (seconds): a device-resident table "
    "still live this long after its owning query trace finished is "
    "flagged (with its creation site) by ResourceLedger.leaks()",
)
NO_EFFECT_LINT = EnvKnob(
    "CYLON_TPU_NO_EFFECT_LINT", "0", kind="observability",
    keyed_via="never reaches a compiled program: read only by "
    "tools/graft_lint to skip the Layer-3 effect pass",
    note="=1 skips graft-lint Layer 3 (effect/sync-freedom analysis) — "
    "an escape hatch for a mid-incident CI unblock, never for merging "
    "a signature drift (re-pin EFFECT_SIGNATURES instead)",
)

# -- native extension ---------------------------------------------------
NATIVE_ASAN = EnvKnob(
    "CYLON_TPU_NATIVE_ASAN", "0", kind="native",
    note="build the native codecs under AddressSanitizer",
)
NO_NATIVE = EnvKnob(
    "CYLON_TPU_NO_NATIVE", "", kind="native",
    note="disable the native C++ codecs (pure-python fallbacks)",
)
