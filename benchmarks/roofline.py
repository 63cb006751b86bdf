"""Roofline accounting for the sort-based kernel designs (VERDICT round-2
item 2): how close does each op run to the HBM-bandwidth bound implied by
its algorithm?

Model
-----
TPU XLA ``sort`` is a bitonic sorting network: ``P(n) = k*(k+1)/2`` passes
for ``k = ceil(log2 n)``, each pass streaming every operand lane once.
Gathers/scatters pay PER ELEMENT (~4-9 ns each on v5e at the narrow row
widths the packed codec uses — measured round 3 via the join stage
profile), modeled as ``GATHER_PASS_EQ`` sequential-pass equivalents per
operand byte. Everything elementwise fuses into one read + one write pass
(XLA fusion).

The op's **model time** is total modeled traffic / peak HBM bandwidth; the
**%membw** column of the bench output is ``model_time / measured_time`` — the
fraction of the algorithm's own bandwidth bound the implementation
achieves. A low %membw means dispatch overhead or unfused overhead; a high
%membw with a slow op means the *algorithm* is the cost (too many passes)
— that is the signal a Pallas kernel with fewer passes can cash in.

The traffic count is not hand-maintained: ``analyze(fn, *args)`` traces the
jitted function and walks the ClosedJaxpr, summing operand bytes per sort
(weighted by its pass count), per gather/scatter (weighted by
GATHER_PASS_EQ), and one pass over everything else that touches data.

Usage:
    from benchmarks.roofline import analyze, model_seconds
    rep = analyze(fn, *example_args)
    t_model = model_seconds(rep, hbm_gbps=819)
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

# v5e (tpu v5 litepod) peak HBM bandwidth, GB/s. Override per device.
HBM_GBPS_DEFAULT = 819.0
# Re-calibrated round 3 on the live chip (benchmarks/profile_join_pieces.py
# stage deltas at 16M rows): the join's packed left gather measured 291 ms
# for ~600 MB of in+out operand bytes -> 291ms * 819GB/s / 600MB ~= 400
# pass-equivalents; the repeat scatter gives ~500 by the same arithmetic.
# (Round 2's "~10x a sequential pass" compared against an eager-fence
# "sequential pass" that was mostly dispatch latency — off by ~40x.)
# Per-element engines on this chip cost ~4-9 ns/element regardless of row
# width at narrow rows, so this UNDERSTATES wide-row gathers' efficiency;
# treat gather/scatter-heavy model times as a calibrated cost model, not a
# bandwidth bound — the byte-vs-element gap IS the Pallas-gather prize.
GATHER_PASS_EQ = 400.0

_SORT_PRIMS = {"sort"}
_GATHER_PRIMS = {"gather", "dynamic_slice", "take"}
_SCATTER_PRIMS = {
    "scatter", "scatter-add", "scatter_add", "scatter_max", "scatter_min",
    "scatter_mul",
}


def _nbytes(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize
    except Exception:
        return 0


def _bitonic_passes(n: int) -> float:
    if n <= 1:
        return 1.0
    k = math.ceil(math.log2(n))
    return k * (k + 1) / 2.0


@dataclass
class Report:
    sort_bytes_per_pass: int = 0
    sort_pass_bytes: float = 0.0  # sum over sorts: operand bytes * passes
    sort_count: int = 0
    sort_passes: float = 0.0  # total modeled passes across all sorts
    gather_bytes: float = 0.0  # pass-equivalent weighted
    scatter_bytes: float = 0.0
    elementwise_bytes: float = 0.0
    collective_bytes: int = 0
    collective_count: int = 0
    by_prim: Dict[str, float] = field(default_factory=dict)
    # every sort with the ``jax.named_scope`` path it was traced under and
    # its pass bytes: which stage of a program a sort belongs to
    sorts: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def total_model_bytes(self) -> float:
        return (
            self.sort_pass_bytes
            + self.gather_bytes
            + self.scatter_bytes
            + self.elementwise_bytes
        )


def _merge_scaled(rep: Report, sub: Report, scale: float) -> None:
    rep.sort_bytes_per_pass += int(sub.sort_bytes_per_pass * scale)
    rep.sort_pass_bytes += sub.sort_pass_bytes * scale
    rep.sort_count += int(sub.sort_count * scale)
    rep.sort_passes += sub.sort_passes * scale
    rep.gather_bytes += sub.gather_bytes * scale
    rep.scatter_bytes += sub.scatter_bytes * scale
    rep.elementwise_bytes += sub.elementwise_bytes * scale
    rep.collective_bytes += int(sub.collective_bytes * scale)
    rep.collective_count += int(sub.collective_count * scale)
    for k, v in sub.by_prim.items():
        rep.by_prim[k] = rep.by_prim.get(k, 0.0) + v * scale
    rep.sorts += [(name, b * scale) for name, b in sub.sorts]


def _walk(jaxpr, rep: Report, scope: str = "") -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        # a nested jaxpr's name stacks are relative to its equation's
        here = "/".join(
            p for p in (scope, str(eqn.source_info.name_stack)) if p
        )
        if prim == "scan":
            # a scan body executes `length` times: walk it once and scale
            # (the K-sliced fused join runs its K rounds in ONE scan — an
            # unscaled walk under-reports its collectives/sorts by K).
            # `while` has no static trip count and stays counted once.
            sub = eqn.params.get("jaxpr")
            inner = getattr(sub, "jaxpr", sub)
            if inner is not None and hasattr(inner, "eqns"):
                trips = int(eqn.params.get("length", 1))
                sub_rep = Report()
                _walk(inner, sub_rep, here)
                _merge_scaled(rep, sub_rep, trips)
            continue
        if prim == "pallas_call":
            # a hand-scheduled kernel: price it as STREAMED bytes (one read
            # of inputs + one write of outputs) and do NOT recurse into the
            # kernel body: its jnp.take runs on VMEM-resident vregs, and
            # pricing it at the HBM per-element gather rate
            # (GATHER_PASS_EQ) would overstate traffic ~400x — beating that
            # rate is the kernel's entire purpose. Known bias: the windowed
            # expand actually DMAs ~1.03 * L * n_out bytes of window READS
            # (output-proportional), while this prices reads at L * cap —
            # in heavy-repeat regimes (n_out >> cap) actual read traffic
            # exceeds the model by up to ~2x, so a low measured %membw on
            # expand-heavy ops partly reflects window re-reads, not only
            # dispatch overhead.
            w = sum(
                _nbytes(x.aval) for x in eqn.invars if hasattr(x, "aval")
            ) + sum(
                _nbytes(x.aval) for x in eqn.outvars if hasattr(x, "aval")
            )
            rep.elementwise_bytes += w
            rep.by_prim[prim] = rep.by_prim.get(prim, 0.0) + w
            continue
        # recurse into nested jaxprs (pjit/closed_call/scan/while/cond/
        # shard_map). A param may hold a raw Jaxpr (has .eqns) or a
        # ClosedJaxpr (has .jaxpr) — shard_map uses the former.
        def _sub(v):
            if hasattr(v, "eqns"):
                return v
            inner = getattr(v, "jaxpr", None)
            return inner if inner is not None and hasattr(inner, "eqns") else None

        for v in eqn.params.values():
            sub = _sub(v)
            if sub is not None:
                _walk(sub, rep, here)
            elif isinstance(v, (list, tuple)):
                for vi in v:
                    sub = _sub(vi)
                    if sub is not None:
                        _walk(sub, rep, here)
        if prim in (
            "pjit", "closed_call", "custom_jvp_call", "custom_vjp_call",
            "shard_map", "cond", "scan", "while", "remat", "checkpoint",
        ):
            # container primitives: their bodies were just recursed into;
            # adding the container's own in/out bytes would double-count
            # every jit/shard_map boundary. (scan bodies are scaled by trip
            # count above; `while` bodies are still counted once.)
            continue
        in_bytes = sum(_nbytes(x.aval) for x in eqn.invars if hasattr(x, "aval"))
        out_bytes = sum(_nbytes(x.aval) for x in eqn.outvars if hasattr(x, "aval"))
        if prim in _SORT_PRIMS:
            n = 0
            for x in eqn.invars:
                if hasattr(x, "aval") and x.aval.shape:
                    n = max(n, int(x.aval.shape[eqn.params.get("dimension", -1)]))
            passes = _bitonic_passes(n)
            rep.sort_count += 1
            rep.sort_bytes_per_pass += in_bytes
            rep.sort_pass_bytes += in_bytes * passes
            rep.sort_passes += passes
            rep.by_prim["sort"] = rep.by_prim.get("sort", 0.0) + in_bytes * passes
            rep.sorts.append((here, in_bytes * passes))
        elif prim in _GATHER_PRIMS:
            w = (in_bytes + out_bytes) * GATHER_PASS_EQ
            rep.gather_bytes += w
            rep.by_prim[prim] = rep.by_prim.get(prim, 0.0) + w
        elif prim in _SCATTER_PRIMS:
            w = (in_bytes + out_bytes) * GATHER_PASS_EQ
            rep.scatter_bytes += w
            rep.by_prim[prim] = rep.by_prim.get(prim, 0.0) + w
        elif prim in ("all_to_all", "all_gather", "all_gather_invariant",
                      "psum", "psum_invariant", "ppermute",
                      "reduce_scatter"):
            rep.collective_bytes += in_bytes
            rep.collective_count += 1
            rep.by_prim[prim] = rep.by_prim.get(prim, 0.0) + in_bytes
        else:
            # elementwise/reduction: fused — count one read + one write
            w = in_bytes + out_bytes
            rep.elementwise_bytes += w


def analyze(fn, *args, **kwargs) -> Report:
    """Trace ``fn(*args)`` and return its modeled HBM traffic."""
    closed = jax.make_jaxpr(fn, **kwargs)(*args)
    rep = Report()
    _walk(closed.jaxpr, rep)
    return rep


def traced_collectives(op, warm: bool = True):
    """Run ``op`` with engine kernel recording on and return
    (total traced collective count, per-program collective bytes for the
    programs that issue any). The shared accounting of the shuffle bench's
    CI gate and tests/test_shuffle_chunked.py. ``warm=True`` runs ``op``
    once first so compilation happens outside the recorded call."""
    from cylon_tpu import engine

    if warm:
        op()
    engine.record_kernels(True)
    try:
        op()
    finally:
        kernels = engine.recorded_kernels()
        engine.record_kernels(False)
    count, per_bytes = 0, []
    for fn, args in kernels:
        rep = analyze(fn, *args)
        count += rep.collective_count
        if rep.collective_count:
            per_bytes.append(rep.collective_bytes)
    return count, per_bytes


def model_seconds(rep: Report, hbm_gbps: float = HBM_GBPS_DEFAULT) -> float:
    """Bandwidth-bound lower time for the modeled traffic."""
    return rep.total_model_bytes / (hbm_gbps * 1e9)


def pct_membw(rep: Report, measured_s: float,
              hbm_gbps: float = HBM_GBPS_DEFAULT) -> float:
    """Fraction (0-1) of the algorithm's bandwidth bound achieved."""
    if measured_s <= 0:
        return 0.0
    return model_seconds(rep, hbm_gbps) / measured_s
