"""Sort-engine smoke — the width-adaptive radix CI gate.

Gates (exit 1 on any failure):

1. **Pass/byte cut** — the 3-key packed sort shape (12+16+20-bit keys
   fused into one 64-bit word) and the q3_ordered chain (key-order join
   emit -> groupby run-detect, the shape whose REMAINING sorts are the
   probe argsort + shuffle gather order) must both run >= the gate
   (default 30%) fewer traced sort-pass bytes under the radix engine
   than the CYLON_TPU_NO_RADIX=1 bitonic oracle, with strictly fewer
   traced sort passes (roofline census: a radix histogram pass counts 1,
   a bitonic network L(L+1)/2).
2. **Oracle-exact output** — the radix run's emitted row order is
   bit-identical to the oracle's on the sort shape (the stable lexsort
   permutation is unique, so this is equality, not tolerance), and the
   q3 aggregate matches row-for-row.
3. **Exactly-one-recompile impl flip** — flipping CYLON_TPU_SORT_IMPL
   on a warmed sort costs exactly ONE new kernel-cache program, and
   flipping back costs ZERO (the first program must still be cached:
   the impl tag keys, never aliases).
4. **Census cross-check** — ops/radix.py's digit width and pass census
   agree with the analysis/contracts.py pins the docs quote.

Usage:
  JAX_PLATFORMS=cpu python tools/sort_smoke.py --rows 50000
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _fail(msg: str) -> None:
    print(f"SORT SMOKE GATE FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def measure(op):
    """(Report totals, warm seconds) over every recorded kernel dispatch
    of one warm call (the lane_pack_bench discipline)."""
    from benchmarks.roofline import Report, analyze
    from cylon_tpu import engine

    op()  # warm (compile outside the recorded call)
    engine.record_kernels(True)
    t0 = time.perf_counter()
    try:
        op()
    finally:
        dt = time.perf_counter() - t0
        kernels = engine.recorded_kernels()
        engine.record_kernels(False)
    total = Report()
    for fn, args in kernels:
        rep = analyze(fn, *args)
        total.sort_count += rep.sort_count
        total.sort_pass_bytes += rep.sort_pass_bytes
        total.sort_passes += rep.sort_passes
        total.radix_passes += rep.radix_passes
        total.radix_pass_bytes += rep.radix_pass_bytes
    return total, dt


def run(rows: int, world: int, gate: float) -> int:
    import __graft_entry__ as ge

    devices = ge._force_cpu_mesh(max(world, 1))

    import cylon_tpu as ct
    from benchmarks.lane_pack_bench import make_join_pair, make_sort_table
    from cylon_tpu.analysis import contracts
    from cylon_tpu.ops import radix as rx

    # -- gate 4 first: the static census pins (no compile needed) -------
    if rx.RADIX_BITS != contracts.RADIX_SORT_DIGIT_BITS:
        _fail(
            f"digit width drift: ops.radix.RADIX_BITS={rx.RADIX_BITS} vs "
            f"contracts.RADIX_SORT_DIGIT_BITS={contracts.RADIX_SORT_DIGIT_BITS}"
        )
    if rx.PALLAS_RADIX_BITS != contracts.PALLAS_RADIX_SORT_DIGIT_BITS:
        _fail("pallas digit width drift between ops.radix and contracts")
    for bits in (1, 4, 20, 42, 64):
        if rx.passes_for_spans([(0, bits)]) != contracts.radix_sort_passes(bits):
            _fail(f"pass census drift at {bits} bits")
    if rx.bitonic_passes(1 << 10, 1) != contracts.bitonic_sort_sweeps(1 << 10, 1):
        _fail("bitonic sweep census drift at cap 1024")

    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )
    rng = np.random.default_rng(0)
    n = rows

    # -- shape 1: the 3-key packed sort --------------------------------
    t = make_sort_table(ct, ctx, rng, n)
    res = {}

    def msort_radix():
        res["r"] = t.sort(["a", "b", "c"])

    def msort_oracle():
        res["o"] = t.sort(["a", "b", "c"])

    sr, tsr = measure(msort_radix)
    with rx.disabled():
        so, tso = measure(msort_oracle)

    # -- shape 2: q3_ordered (key-order join emit -> groupby run-detect;
    # the probe argsort + shuffle gather order are the surviving sorts) -
    lt, rt = make_join_pair(ct, ctx, rng, n)
    res2 = {}

    def q3_radix():
        res2["r"] = lt.distributed_join(
            rt, on=["k1", "k2"], how="inner", emit_order="key"
        ).distributed_groupby(["k1_x", "k2_x"], {"v": "sum"})

    def q3_oracle():
        res2["o"] = lt.distributed_join(
            rt, on=["k1", "k2"], how="inner", emit_order="key"
        ).distributed_groupby(["k1_x", "k2_x"], {"v": "sum"})

    jr, tjr = measure(q3_radix)
    with rx.disabled():
        jo, tjo = measure(q3_oracle)

    def cut(r, o):
        return 1.0 - r / o if o else 0.0

    sort_cut = cut(sr.sort_pass_bytes, so.sort_pass_bytes)
    q3_cut = cut(jr.sort_pass_bytes, jo.sort_pass_bytes)
    rec = {
        "benchmark": "sort_smoke",
        "rows": n,
        "world": world,
        "sort_oracle_passes": round(so.sort_passes, 1),
        "sort_radix_passes": round(sr.sort_passes, 1),
        "sort_oracle_gb": round(so.sort_pass_bytes / 1e9, 4),
        "sort_radix_gb": round(sr.sort_pass_bytes / 1e9, 4),
        "sort_gb_cut_pct": round(100 * sort_cut, 1),
        "q3_oracle_passes": round(jo.sort_passes, 1),
        "q3_radix_passes": round(jr.sort_passes, 1),
        "q3_oracle_gb": round(jo.sort_pass_bytes / 1e9, 4),
        "q3_radix_gb": round(jr.sort_pass_bytes / 1e9, 4),
        "q3_gb_cut_pct": round(100 * q3_cut, 1),
        "radix_warm_s": round(tsr + tjr, 4),
        "oracle_warm_s": round(tso + tjo, 4),
    }
    print(json.dumps(rec), flush=True)

    # -- gate 2: oracle-exact output -----------------------------------
    g = res["r"].to_pandas().reset_index(drop=True)
    w = res["o"].to_pandas().reset_index(drop=True)
    if len(g) != len(w) or not g.equals(w):
        _fail("radix sort emitted order differs from the bitonic oracle")
    keys = ["k1_x", "k2_x"]
    gq = res2["r"].to_pandas().sort_values(keys).reset_index(drop=True)
    wq = res2["o"].to_pandas().sort_values(keys).reset_index(drop=True)
    if len(gq) != len(wq) or not gq.equals(wq):
        _fail("radix q3_ordered aggregate differs from the oracle")

    # -- gate 1: pass/byte cuts ----------------------------------------
    if sr.radix_passes < 1:
        _fail("no radix_pass traced on the 3-key packed sort")
    if sr.sort_passes >= so.sort_passes:
        _fail(
            f"sort passes did not drop: radix {sr.sort_passes} vs "
            f"oracle {so.sort_passes}"
        )
    if sort_cut < gate:
        _fail(
            f"3-key packed sort-pass bytes cut {100 * sort_cut:.1f}% "
            f"(< gate {100 * gate:.0f}%)"
        )
    if jr.sort_passes >= jo.sort_passes:
        _fail(
            f"q3_ordered sort passes did not drop: radix {jr.sort_passes} "
            f"vs oracle {jo.sort_passes}"
        )
    if q3_cut < gate:
        _fail(
            f"q3_ordered sort-pass bytes cut {100 * q3_cut:.1f}% "
            f"(< gate {100 * gate:.0f}%)"
        )

    # -- gate 3: impl flip costs exactly one program, flip-back zero ---
    # a key combination nothing above compiled, so both impls start cold
    cache = ctx.__dict__.setdefault("_jit_cache", {})
    flip_keys = ["c", "a"]
    flip_want = None
    prev = os.environ.get("CYLON_TPU_SORT_IMPL")
    try:
        os.environ["CYLON_TPU_SORT_IMPL"] = "radix"
        flip_want = t.sort(flip_keys).to_pandas()  # warm this impl's program
        n0 = len(cache)
        os.environ["CYLON_TPU_SORT_IMPL"] = "bitonic"
        flip = t.sort(flip_keys).to_pandas()
        n1 = len(cache)
        if n1 - n0 != 1:
            _fail(
                f"impl flip compiled {n1 - n0} new programs (expected "
                "exactly 1: the sort kernel under the new impl tag)"
            )
        if not flip.equals(flip_want):
            _fail("bitonic flip output differs from the radix emit")
        os.environ["CYLON_TPU_SORT_IMPL"] = "radix"
        t.sort(flip_keys).to_pandas()
        if len(cache) != n1:
            _fail(
                "flip-back recompiled: the radix program was not retained "
                "under its own key"
            )
    finally:
        if prev is None:
            os.environ.pop("CYLON_TPU_SORT_IMPL", None)
        else:
            os.environ["CYLON_TPU_SORT_IMPL"] = prev

    print(
        f"# sort smoke ok: packed sort -{100 * sort_cut:.1f}% "
        f"({so.sort_passes:.0f}->{sr.sort_passes:.0f} passes), q3_ordered "
        f"-{100 * q3_cut:.1f}% ({jo.sort_passes:.0f}->{jr.sort_passes:.0f} "
        "passes), impl flip = 1 recompile, flip-back = 0",
        file=sys.stderr,
    )
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=50_000)
    ap.add_argument("--world", type=int, default=4)
    ap.add_argument("--gate", type=float, default=0.30,
                    help="minimum fractional sort-pass-byte reduction")
    args = ap.parse_args()
    # the subject side is the radix engine, by force: the default is the
    # native sort, which is also the oracle's (rx.disabled() wins over it)
    os.environ.setdefault("CYLON_TPU_SORT_IMPL", "radix")
    sys.exit(run(args.rows, args.world, args.gate))


if __name__ == "__main__":
    main()
