"""Head-to-head: Pallas PK-FK probe vs the sort-based spec_join
(VERDICT round-2 item 6).

Same inputs (unique right keys — the PK-FK shape the reference's own
benchmark generator produces with keyspace = n), same semantics (inner
join emit of matched row-index pairs). Prints one JSON line per
implementation; on TPU the pallas kernel compiles to Mosaic, on CPU it
runs in interpret mode (correctness smoke only — interpret is not a
performance mode, the line is marked).

Usage: python benchmarks/pallas_bench.py [--rows N] [--cpu] [--bucket B]
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
    ap.add_argument("--rows", type=int, default=4_000_000)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--bucket", type=int, default=256)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    import __graft_entry__ as ge

    if not args.cpu:
        import bench as _b

        _b.require_tpu()  # --cpu is the only way onto the CPU
    else:
        ge._force_cpu_mesh(1)
        args.rows = min(args.rows, 100_000)  # interpret mode is slow

    import jax
    import jax.numpy as jnp

    from bench import fence  # noqa: F401 (import sets the compile cache env)
    from cylon_tpu.ops import join as _j
    from cylon_tpu.ops.pallas_join import pk_inner_join

    platform = jax.devices()[0].platform
    interpret = platform == "cpu"
    n = args.rows
    rng = np.random.default_rng(0)
    r_key = rng.permutation(np.arange(2 * n, dtype=np.int32))[:n]  # unique PK
    l_key = rng.choice(r_key, size=n, replace=True)  # FK, all hit

    lk = jnp.asarray(l_key)
    rk = jnp.asarray(r_key)
    nl = jnp.int32(n)
    nr = jnp.int32(n)

    def timed(fn, label, extra=None):
        t0 = time.perf_counter()
        out = fn()
        # dependent-scalar fetch: the fence (one fetch, DCE-proof)
        total = int(np.asarray(out[2]))
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            out = fn()
            total = int(np.asarray(out[2]))
            best = min(best, time.perf_counter() - t0)
        print(json.dumps({
            "benchmark": label,
            "rows": 2 * n,
            "platform": platform,
            "warm_s": round(best, 4),
            "compile_s": round(compile_s, 2),
            "rows_per_sec": round(2 * n / best),
            "join_rows": total,
            **(extra or {}),
        }), flush=True)
        return total

    # -- sort-based spec_join (the production path) --
    cap_out = 1 << (2 * n - 1).bit_length()

    @jax.jit
    def sort_join():
        out, total, _shadow, _handed = _j.spec_join(
            [(lk, None)], [(rk, None)],
            [(lk, None)], [(rk, None)],
            nl, nr, _j.INNER, cap_out,
        )
        return out, None, total

    t_sort = timed(sort_join, "pk_join_sort_based")

    # -- pallas bucketed probe --
    def pallas_join():
        l_idx, r_idx, total, bad = pk_inner_join(
            lk, rk, nl, nr, B=args.bucket, interpret=interpret,
        )
        return (l_idx, r_idx), total, bad

    def pallas_wrapped():
        (li, ri), total, bad = pallas_join()
        assert int(np.asarray(bad)) == 0, "speculation miss (fallback case)"
        return (li, ri), None, total

    t_pal = timed(
        pallas_wrapped, "pk_join_pallas_bucketed",
        {"bucket": args.bucket, "interpret": interpret},
    )
    assert t_sort == t_pal, (t_sort, t_pal)


if __name__ == "__main__":
    main()
