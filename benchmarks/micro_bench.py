"""Micro-benchmarks for kernel-implementation decisions on real TPU.

Currently: the `_repeat_ss` implementation choice (ops/join.py). The
roofline model prices the sort variant's two (n+cap_out)-element argsorts
at ~35% of the whole 16M-row join, and the scatter+cummax variant at a
tenth of that — but round-2 measurements showed XLA TPU scatters sometimes
lose to sorts, so the decision needs hardware numbers: this prints one
JSON line per (impl, size) plus a verdict line, and the flagship join
timed under each impl.

Usage: python benchmarks/micro_bench.py [--rows N] [--cpu]
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
    import jax.numpy as jnp

    from cylon_tpu.ops import join as _j

    platform = jax.devices()[0].platform
    n = args.rows
    cap_out = 1 << (2 * n - 1).bit_length()
    rng = np.random.default_rng(0)
    cnt_host = rng.integers(0, 3, n).astype(np.int32)
    ends = jnp.asarray(np.cumsum(cnt_host).astype(np.int32))

    def run_repeat(impl):
        os.environ["CYLON_TPU_REPEAT_IMPL"] = impl

        total = int(cnt_host.sum())

        @jax.jit
        def f(e):
            li = _j._repeat_ss(e, cap_out)
            # both impls are only defined on the live prefix; mask the rest
            live = jnp.arange(cap_out, dtype=jnp.int32) < total
            return jnp.sum(jnp.where(live, li, 0).astype(jnp.int64) & 0xFFFF)

        t0 = time.perf_counter()
        v = int(np.asarray(f(ends)))
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            v = int(np.asarray(f(ends)))
            best = min(best, time.perf_counter() - t0)
        print(json.dumps({
            "benchmark": f"repeat_ss_{impl}", "rows": n, "platform": platform,
            "warm_s": round(best, 4), "compile_s": round(compile_s, 2),
            "check": v,
        }), flush=True)
        return best, v

    r_sort = run_repeat("sort")
    r_scatter = run_repeat("scatter")
    t_sort, t_scatter = r_sort[0], r_scatter[0]
    assert r_sort[1] == r_scatter[1], (r_sort, r_scatter)

    # the flagship local join under each impl
    keyspace = n
    lk = jnp.asarray(rng.integers(0, keyspace, n).astype(np.int32))
    rk = jnp.asarray(rng.integers(0, keyspace, n).astype(np.int32))
    lv = jnp.asarray(rng.normal(size=n).astype(np.float32))

    def run_join(impl):
        os.environ["CYLON_TPU_REPEAT_IMPL"] = impl
        cap_j = 1 << (2 * n - 1).bit_length()

        @jax.jit
        def f(a, b, v):
            out, total, _, _ = _j.spec_join(
                [(a, None)], [(b, None)],
                [(a, None), (v, None)], [(b, None)],
                jnp.int32(n), jnp.int32(n), _j.INNER, cap_j,
            )
            # checksum every output lane: returning only `total` lets XLA
            # dead-code-eliminate the emit (gather + repeat), which is
            # exactly the part the impl choice changes — the r03 capture
            # showed a 0.86x "slowdown" that was a DCE artifact
            s = jnp.float32(0)
            for d, _v in out:
                s = s + jnp.sum(d.astype(jnp.float32))
            return total, s

        t0 = time.perf_counter()
        tot, chk = f(lk, rk, lv)
        tot = int(np.asarray(tot)); float(chk)
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            # ONE host fetch for both scalars (two sequential fetches would
            # add a second device->host round-trip per rep to warm_s)
            tot, _chk = jax.device_get(f(lk, rk, lv))
            tot = int(tot)
            best = min(best, time.perf_counter() - t0)
        print(json.dumps({
            "benchmark": f"spec_join_repeat_{impl}", "rows": 2 * n,
            "platform": platform, "warm_s": round(best, 4),
            "compile_s": round(compile_s, 2),
            "rows_per_sec": round(2 * n / best), "join_rows": tot,
        }), flush=True)
        return best, tot

    js, cs = run_join("sort")
    jsc, csc = run_join("scatter")
    assert cs == csc, (cs, csc)
    os.environ.pop("CYLON_TPU_REPEAT_IMPL", None)
    print(json.dumps({
        "verdict": "scatter" if jsc < js else "sort",
        "repeat_speedup_scatter": round(t_sort / t_scatter, 2),
        "join_speedup_scatter": round(js / jsc, 2),
    }), flush=True)

    # -- segment-sum impl for join_sum_by_key_pushdown (the q3-fused core;
    # its three scatter-adds are the suspected cause of the measured-vs-
    # model gap: warm 0.51 s vs model 0.05 s at 8M input rows) --
    def run_pushdown(impl):
        os.environ["CYLON_TPU_SEGSUM_IMPL"] = impl
        group_cap = 1 << (n - 1).bit_length()

        # fresh jit per impl: the env is read at trace time
        @jax.jit
        def f(a, b, v):
            s, ng, nj, ovg = _j.join_sum_by_key_pushdown(
                [(a, None)], [(b, None)], (v, None),
                jnp.int32(n), jnp.int32(n), group_cap,
            )
            return jnp.sum(s), ng, nj

        t0 = time.perf_counter()
        tot, ng, nj = jax.device_get(f(lk, rk, lv))
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            tot, ng, nj = jax.device_get(f(lk, rk, lv))
            best = min(best, time.perf_counter() - t0)
        print(json.dumps({
            "benchmark": f"pushdown_segsum_{impl}", "rows": 2 * n,
            "platform": platform, "warm_s": round(best, 4),
            "compile_s": round(compile_s, 2), "groups": int(ng),
            "join_rows": int(nj), "sum": float(tot),
        }), flush=True)
        return best, (int(ng), int(nj))

    ps, pcs = run_pushdown("scatter")
    pss, pcss = run_pushdown("sorted")
    assert pcs == pcss, (pcs, pcss)
    os.environ.pop("CYLON_TPU_SEGSUM_IMPL", None)
    print(json.dumps({
        "verdict_segsum": "sorted" if pss < ps else "scatter",
        "pushdown_speedup_sorted": round(ps / pss, 2),
    }), flush=True)


if __name__ == "__main__":
    main()
