"""High-cardinality string-key join, end to end (VERDICT r3 item 6).

Measures the three phases the 10B-row north star cares about separately —
ingest (host string encode: np.unique per table), dictionary unification
(union of two sorted dictionaries + device code remap), and the join kernel
itself — so the host-vs-device cost split is explicit. The dictionary union
runs through the native two-pointer merge (native/runtime.cpp
ct_dict_union_u32) when available; CYLON_TPU_NO_NATIVE=1 re-runs it through
np.union1d for the A/B.

Reference analog: BinaryHashPartitionKernel hashes raw strings per row
(arrow/arrow_partition_kernels.cpp:243-305) — here strings become
order-preserving int32 codes once at ingest and every kernel is integer.

Usage: python benchmarks/string_join_bench.py [--rows N] [--card C] [--cpu]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("CYLON_TPU_NO_X64", "1")

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=16_000_000)
    ap.add_argument("--card", type=int, default=0,
                    help="key cardinality per side (default rows//2)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import __graft_entry__ as ge

    if not args.cpu:
        import bench as _b

        _b.require_tpu()  # --cpu is the only way onto the CPU
    else:
        ge._force_cpu_mesh(1)
        args.rows = min(args.rows, 1_000_000)

    import jax

    import cylon_tpu as ct
    from bench import fence
    from cylon_tpu import native
    from cylon_tpu.table import _unify_dict_pair

    platform = jax.devices()[0].platform
    n = args.rows
    card = args.card or n // 2
    rng = np.random.default_rng(0)

    # distinct-per-side key universes with ~50% overlap: the union is real
    # work (neither side's dictionary contains the other)
    def keys(offset):
        ints = rng.integers(0, 2 * card, n) + offset
        return np.char.add("k", ints.astype("U16"))

    lk_host = keys(0)
    rk_host = keys(card)

    ctx = ct.CylonContext.init()

    # --- phase 1: ingest (host encode: np.unique -> sorted dict + codes) ---
    t0 = time.perf_counter()
    left = ct.Table.from_pydict(
        ctx, {"k": lk_host, "v": rng.normal(size=n).astype(np.float32)}
    )
    right = ct.Table.from_pydict(
        ctx, {"k": rk_host, "w": rng.normal(size=n).astype(np.float32)}
    )
    fence(left)
    fence(right)
    ingest_s = time.perf_counter() - t0
    da = len(left.column("k").dictionary)
    db = len(right.column("k").dictionary)

    # --- phase 2: dictionary unification (host union + device remap) ---
    t0 = time.perf_counter()
    lu, ru = _unify_dict_pair(left, right, ["k"], ["k"])
    fence(lu)
    fence(ru)
    unify_s = time.perf_counter() - t0

    # --- phase 3: the join itself on pre-unified tables ---
    def join():
        out = lu.join(ru, on="k", how="inner")
        fence(out)
        return out

    t0 = time.perf_counter()
    out = join()
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(args.reps):
        t0 = time.perf_counter()
        out = join()
        best = min(best, time.perf_counter() - t0)

    print(json.dumps({
        "benchmark": "string_key_join",
        "rows": 2 * n, "dict_a": int(da), "dict_b": int(db),
        "platform": platform,
        "native_union": bool(native.available()),
        "ingest_s": round(ingest_s, 3),
        "unify_s": round(unify_s, 3),
        "join_warm_s": round(best, 4),
        "join_compile_s": round(compile_s, 2),
        "join_rows": int(out.row_count),
        "end_to_end_rows_per_sec": round(
            2 * n / (ingest_s + unify_s + best)
        ),
        "join_rows_per_sec": round(2 * n / best),
    }), flush=True)


if __name__ == "__main__":
    main()
