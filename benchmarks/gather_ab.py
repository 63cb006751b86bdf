"""A/B the join emit implementations on real TPU (VERDICT r3 item 1).

Round-3 stage profile: the two emit gathers are ~0.6 s of the 1.07 s
16M-row join kernel, vs a ~2 ms byte-roofline. This bench measures, with
DCE-proofed checksums (memory: returning only the count let XLA eliminate
the emit and inverted a round-3 verdict):

1. isolated left-expand: XLA packed gather vs Pallas windowed expand
   (ops/pallas_gather, impl=take and impl=onehot);
2. the full spec_join under emit_impl='gather' vs 'windowed';
3. the packed gather with/without indices_are_sorted (cheap XLA-only probe
   of whether sortedness alone buys anything).

Usage: python benchmarks/gather_ab.py [--rows N] [--cpu]
One JSON line per measurement; a final verdict line.
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
    ap.add_argument("--lanes", type=int, default=6)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import __graft_entry__ as ge

    if not args.cpu:
        import bench as _b

        _b.require_tpu()  # --cpu is the only way onto the CPU
    else:
        ge._force_cpu_mesh(1)
        args.rows = min(args.rows, 500_000)

    import jax
    import jax.numpy as jnp

    from cylon_tpu.ops import join as _j
    from cylon_tpu.ops.pallas_gather import expand_rows

    platform = jax.devices()[0].platform
    interpret = platform != "tpu"
    n = args.rows
    L = args.lanes
    rng = np.random.default_rng(0)

    def timed(fn, *xs):
        t0 = time.perf_counter()
        out = jax.device_get(fn(*xs))
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            out = jax.device_get(fn(*xs))
            best = min(best, time.perf_counter() - t0)
        return best, compile_s, out

    # ---- 1. isolated expand: same inputs, three impls ----
    cnt_host = rng.integers(0, 3, n).astype(np.int32)
    total = int(cnt_host.sum())
    cap_out = 1 << (total - 1).bit_length()
    ends = jnp.asarray(np.cumsum(cnt_host).astype(np.int32))
    src_host = rng.integers(-(2**31), 2**31, (L, n), dtype=np.int64).astype(
        np.int32
    )
    srcT = jnp.asarray(src_host)  # lane-major for the expand
    src_rows = jnp.asarray(src_host.T.copy())  # row-major for pack_gather

    def checksum(m):  # [L, cap_out] or [cap_out, L]
        # uint32 wrap is deterministic and identical across impls (int64 is
        # unavailable under CYLON_TPU_NO_X64)
        return jnp.sum(m.astype(jnp.uint32) & np.uint32(0xFFFF))

    @jax.jit
    def xla_gather(e, s):
        li = _j._repeat_ss(e, cap_out)
        live = jnp.arange(cap_out, dtype=jnp.int32) < total
        safe = jnp.clip(li, 0, n - 1)
        g = s[safe]  # ONE packed gather, the production shape
        return checksum(jnp.where(live[:, None], g, 0))

    @jax.jit
    def xla_gather_sorted(e, s):
        li = _j._repeat_ss(e, cap_out)  # raw cummax: non-decreasing incl tail
        live = jnp.arange(cap_out, dtype=jnp.int32) < total
        safe = jnp.clip(li, 0, n - 1)
        g = jnp.take(s, safe, axis=0, indices_are_sorted=True)
        return checksum(jnp.where(live[:, None], g, 0))

    cnt_dev = jnp.asarray(cnt_host)

    def expand_impl(impl):
        # mirrors _emit_inner_left_windowed: compact emitting rows first
        # (the expand contract is step <= 1, which zero-count rows break),
        # so this measures the REAL replacement cost: scatter + expand
        @jax.jit
        def f(cnt, s_rows):
            em = (cnt > 0).astype(jnp.int32)
            slot = jnp.cumsum(em) - em
            dest = jnp.where(cnt > 0, slot, n)
            packed_c = jnp.zeros((n, L), jnp.int32).at[dest].set(
                s_rows, mode="drop"
            )
            cnt_c = jnp.zeros((n,), jnp.int32).at[dest].set(cnt, mode="drop")
            ends_c = jnp.cumsum(cnt_c)
            li_c = _j._repeat_ss(ends_c, cap_out)
            out = expand_rows(
                packed_c.T, li_c, impl=impl, interpret=interpret
            )
            live = jnp.arange(cap_out, dtype=jnp.int32) < total
            return checksum(jnp.where(live[None, :], out, 0))

        return f

    results = {}
    for name, fn, args2 in [
        ("emit_xla_gather", xla_gather, (ends, src_rows)),
        ("emit_xla_gather_sorted", xla_gather_sorted, (ends, src_rows)),
        ("emit_windowed_take", expand_impl("take"), (cnt_dev, src_rows)),
        ("emit_windowed_onehot", expand_impl("onehot"), (cnt_dev, src_rows)),
        ("emit_windowed_take_db", expand_impl("take_db"), (cnt_dev, src_rows)),
        (
            "emit_windowed_onehot_db",
            expand_impl("onehot_db"),
            (cnt_dev, src_rows),
        ),
    ]:
        try:
            best, compile_s, chk = timed(fn, *args2)
        except Exception as e:  # Mosaic ceiling: record, keep going
            print(json.dumps({
                "benchmark": name, "rows": n, "platform": platform,
                "error": f"{type(e).__name__}: {str(e)[:300]}",
            }), flush=True)
            continue
        results[name] = (best, int(chk))
        print(json.dumps({
            "benchmark": name, "rows": n, "lanes": L, "platform": platform,
            "warm_s": round(best, 4), "compile_s": round(compile_s, 2),
            "check": int(chk),
        }), flush=True)
    checks = {v[1] for v in results.values()}
    assert len(checks) <= 1, f"checksum divergence: {results}"

    # ---- 2. full spec_join, gather vs windowed emit ----
    keyspace = n
    lk = jnp.asarray(rng.integers(0, keyspace, n).astype(np.int32))
    rk = jnp.asarray(rng.integers(0, keyspace, n).astype(np.int32))
    lv = jnp.asarray(rng.normal(size=n).astype(np.float32))
    rv = jnp.asarray(rng.normal(size=n).astype(np.float32))
    cap_j = 1 << (2 * n - 1).bit_length()

    def run_join(emit_impl, tag):
        @jax.jit
        def f(a, b, v, w):
            out, tot, _, _ = _j.spec_join(
                [(a, None)], [(b, None)],
                [(a, None), (v, None)], [(b, None), (w, None)],
                jnp.int32(n), jnp.int32(n), _j.INNER, cap_j, emit_impl,
            )
            s = jnp.float32(0)
            for d, _v in out:
                s = s + jnp.sum(d.astype(jnp.float32))
            return tot, s

        try:
            best, compile_s, (tot, chk) = timed(f, lk, rk, lv, rv)
        except Exception as e:
            print(json.dumps({
                "benchmark": f"spec_join_{tag}", "rows": 2 * n,
                "platform": platform,
                "error": f"{type(e).__name__}: {str(e)[:300]}",
            }), flush=True)
            return None
        print(json.dumps({
            "benchmark": f"spec_join_{tag}", "rows": 2 * n,
            "platform": platform, "warm_s": round(best, 4),
            "compile_s": round(compile_s, 2),
            "rows_per_sec": round(2 * n / best), "join_rows": int(tot),
        }), flush=True)
        return best, int(tot)

    jg = run_join("gather", "gather")
    variants = []
    for gi in ("take", "onehot", "take_db", "onehot_db"):
        os.environ["CYLON_TPU_EXPAND_GATHER"] = gi
        variants.append(run_join("windowed", f"windowed_{gi}"))
    os.environ.pop("CYLON_TPU_EXPAND_GATHER", None)
    for other in variants:
        if jg and other:
            assert jg[1] == other[1], (jg, other)

    best_w = min(
        [x for x in variants if x], default=None, key=lambda t: t[0]
    )
    if jg and best_w:
        print(json.dumps({
            "verdict": "windowed" if best_w[0] < jg[0] else "gather",
            "join_speedup_windowed": round(jg[0] / best_w[0], 3),
        }), flush=True)


if __name__ == "__main__":
    main()
