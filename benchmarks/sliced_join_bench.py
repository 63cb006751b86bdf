"""Sliced fused join + probe-depth benchmark (VERDICT r4 item 4).

PARITY.md's north-star lever 1 is hash-sliced shuffle rounds
(``distributed_join(mode='fused', num_slices=K)``): K rounds of 1/K volume
cut the probe sort to log2(2n/K)^2 passes with unchanged total shuffle
bytes. This bench turns the lever's arithmetic into measurements:

A. probe-sort depth sweep — the merged kv-sort (the exact
   ``lax.sort((keys, pay), num_keys=1, is_stable=True)`` construction of
   ops/join._merged_counts) timed at 2n/K merged elements for each K.
   Runs on ANY device count, including the single real TPU chip — this is
   the measured constant the 10B-row projection extrapolates from, and
   ``K * t(2n/K) / t(2n)`` is the realized probe-cost ratio of a K-sliced
   run (vs the analytic (log2(2n/K)/log2(2n))^2).

B. full sliced fused join sweep (world > 1 meshes; the virtual CPU mesh
   here — num_slices is a no-op without a shuffle to ride): warm wall +
   traced collective count/volume per K, proving K rounds x 1/K volume =
   constant total bytes while the probe depth drops.

C. radix pre-bucket vs flat probe sort (PARITY.md's "one unmeasured
   piece"): a b-bit LSD binary-split partition (cumsum + scatter per bit)
   against the flat kv-sort and against pre-bucket + batched short sorts.
   PARITY predicts the scatter passes LOSE on TPU (per-element cost ~400
   sequential-pass-equivalents); this measures it either way.

One JSON line per row. Usage:
  python benchmarks/sliced_join_bench.py [--rows N] [--cpu] [--slices 1,4,32,256]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("CYLON_TPU_NO_X64", "1")

import numpy as np


def emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=16_000_000,
                    help="rows PER SIDE for the probe-depth sweep")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--mesh", type=int, default=8, help="CPU mesh width")
    ap.add_argument("--slices", type=str, default="1,4,32,256")
    ap.add_argument("--radix-bits", type=int, default=8)
    args = ap.parse_args()
    slices = [int(s) for s in args.slices.split(",")]

    import __graft_entry__ as ge

    if not args.cpu:
        import bench as _b

        _b.require_tpu()  # --cpu is the only way onto the CPU
    else:
        ge._force_cpu_mesh(args.mesh)
        args.rows = min(args.rows, 1_000_000)

    import jax
    import jax.numpy as jnp

    from run_bench import _bench, _roofline_recorded, _sync

    platform = jax.devices()[0].platform
    n = args.rows
    rng = np.random.default_rng(11)

    # ---- A. probe-sort depth sweep ------------------------------------
    # one jitted program per size; checksum BOTH outputs (DCE-proof: an
    # unused payload operand would let XLA drop it and change the bytes)
    def make_sort(m):
        @jax.jit
        def f(keys, pay):
            sk, sp = jax.lax.sort((keys, pay), num_keys=1, is_stable=True)
            return jnp.sum(sk[:8].astype(jnp.uint32)) + jnp.sum(
                sp[-8:].astype(jnp.uint32)
            )

        return f

    def bench_sort_at(K):
        m = max((2 * n) // K, 1024)
        m = 1 << (m - 1).bit_length()  # pow2 cap, like the engine's buckets
        keys = jnp.asarray(
            rng.integers(-(2**31), 2**31, m, dtype=np.int64).astype(np.int32)
        )
        pay = jnp.arange(m, dtype=jnp.int32)
        f = make_sort(m)
        s, c = _bench(lambda: float(f(keys, pay)), args.reps)
        return m, s, c

    # the flat (K=1) baseline is ALWAYS measured, whatever --slices says:
    # probe_ratio_vs_flat must mean "vs one full-size sort" for every row
    _, s_flat, _ = bench_sort_at(1)
    for K in slices:
        m, s, c = bench_sort_at(K)
        lg = math.log2(m)
        emit({
            "benchmark": f"probe_sort_depth_K{K}",
            "platform": platform,
            "merged_rows": m,
            "warm_s": round(s, 4),
            "compile_s": round(c, 2),
            "ns_per_row": round(1e9 * s / m, 3),
            "bitonic_passes": round(lg * lg / 2, 1),
            # realized total probe cost of K rounds at 2n/K rows each,
            # vs ONE round at the full 2n
            "k_rounds_total_s": round(K * s, 4),
            "probe_ratio_vs_flat": round((K * s) / s_flat, 3),
        })

    # ---- B. full sliced fused join sweep (needs a real shuffle) --------
    import cylon_tpu as ct

    world = len(jax.devices()) if use_cpu else 1
    if world > 1:
        ctx = ct.CylonContext.init_distributed(
            ct.TPUConfig(devices=jax.devices()[:world])
        )
        left = ct.Table.from_pydict(
            ctx,
            {
                "k": rng.integers(0, n, n).astype(np.int32),
                "v": rng.normal(size=n).astype(np.float32),
            },
        )
        right = ct.Table.from_pydict(
            ctx,
            {
                "k": rng.integers(0, n, n).astype(np.int32),
                "w": rng.normal(size=n).astype(np.float32),
            },
        )
        base_rows = None
        for K in slices:
            def run(K=K):
                out = left.distributed_join(
                    right, on="k", how="inner", mode="fused", num_slices=K
                )
                _sync(out)
                return out

            try:
                s, c = _bench(lambda: run(), args.reps)
            except RuntimeError as e:
                emit({
                    "benchmark": f"sliced_fused_join_K{K}",
                    "platform": platform, "world": world, "rows": 2 * n,
                    "error": str(e)[:200],
                })
                continue
            out = run()
            if base_rows is None:
                base_rows = out.row_count
            extra = {}
            _roofline_recorded(extra, 0.0, s, lambda: run())
            emit({
                "benchmark": f"sliced_fused_join_K{K}",
                "platform": platform,
                "world": world,
                "rows": 2 * n,
                "rows_out": int(out.row_count),
                "match_K1": bool(out.row_count == base_rows),
                "warm_s": round(s, 4),
                "compile_s": round(c, 2),
                "rows_per_sec": round(2 * n / s),
                **extra,
            })
    else:
        emit({
            "benchmark": "sliced_fused_join_sweep",
            "platform": platform,
            "skipped": "1-device mesh: num_slices has no shuffle to ride "
                       "(probe-depth sweep above is the 1-chip evidence)",
        })

    # ---- C. radix pre-bucket vs flat probe sort ------------------------
    b = args.radix_bits
    m = 1 << (max(2 * n, 1024) - 1).bit_length()
    m = min(m, 1 << 25) if platform == "cpu" else m  # 1-core host guard
    keys = jnp.asarray(
        rng.integers(0, 2**31, m, dtype=np.int64).astype(np.int32)
    )
    pay = jnp.arange(m, dtype=jnp.int32)

    @jax.jit
    def flat_sort(keys, pay):
        sk, sp = jax.lax.sort((keys, pay), num_keys=1, is_stable=True)
        return jnp.sum(sk[:8].astype(jnp.uint32)) + jnp.sum(
            sp[-8:].astype(jnp.uint32)
        )

    @jax.jit
    def radix_partition(keys, pay):
        # b-bit LSD binary split on the TOP b bits (bucket id = high bits,
        # as the hash-slice rounds use): per bit, a stable two-way
        # partition = cumsum + full-width scatter of (key, pay)
        k, p = keys, pay
        for bit in range(31 - b, 31):
            bv = (k >> np.int32(bit)) & np.int32(1)
            nz = jnp.sum(np.int32(1) - bv)
            pos0 = jnp.cumsum(np.int32(1) - bv) - (np.int32(1) - bv)
            pos1 = nz + jnp.cumsum(bv) - bv
            dest = jnp.where(bv == 0, pos0, pos1)
            k = jnp.zeros_like(k).at[dest].set(k)
            p = jnp.zeros_like(p).at[dest].set(p)
        return jnp.sum(k[:8].astype(jnp.uint32)) + jnp.sum(
            p[-8:].astype(jnp.uint32)
        )

    B = 1 << b

    @jax.jit
    def bucketed_sort(keys, pay):
        # pre-bucket by top-b bits via one short-key sort, then batched
        # independent short sorts ([B, m/B] — lax.sort sorts the last axis)
        bid = jax.lax.shift_right_logical(keys, np.int32(31 - b))
        sb, sk, sp = jax.lax.sort((bid, keys, pay), num_keys=1, is_stable=True)
        k2 = sk.reshape(B, m // B)
        p2 = sp.reshape(B, m // B)
        # buckets are uniform here so the reshape rows are ~aligned to
        # bucket boundaries; boundary straddle rows would need a merge fix
        # in production — the micro bench measures the PASS cost shape
        k3, p3 = jax.lax.sort((k2, p2), num_keys=1, is_stable=True)
        return jnp.sum(k3[0, :8].astype(jnp.uint32)) + jnp.sum(
            p3[-1, -8:].astype(jnp.uint32)
        )

    rows = {}
    for name, fn in (
        ("flat_sort", flat_sort),
        ("radix_prebucket_scatter", radix_partition),
        ("bucket_then_batched_sort", bucketed_sort),
    ):
        s, c = _bench(lambda fn=fn: float(fn(keys, pay)), args.reps)
        rows[name] = s
        emit({
            "benchmark": f"radix_ab_{name}",
            "platform": platform,
            "rows": m,
            "radix_bits": b,
            "warm_s": round(s, 4),
            "compile_s": round(c, 2),
            "ns_per_row": round(1e9 * s / m, 3),
        })
    emit({
        "benchmark": "radix_ab_verdict",
        "platform": platform,
        "rows": m,
        "winner": min(rows, key=rows.get),
        "radix_vs_flat": round(rows["radix_prebucket_scatter"]
                               / rows["flat_sort"], 3),
        "bucketed_vs_flat": round(rows["bucket_then_batched_sort"]
                                  / rows["flat_sort"], 3),
    })


if __name__ == "__main__":
    main()
