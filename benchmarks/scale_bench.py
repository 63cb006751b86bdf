"""Scale proof above 16M rows (VERDICT r4 item 7 / BASELINE config 3).

The reference's published numbers are 200M-row joins over 160 workers and
a 1B-row distributed sort (BASELINE.md); the largest cylon_tpu measurement
anywhere was 16M rows/side. This bench runs, on whatever backend is
reachable (host RAM bounds it, not HBM — the out-of-core join exists for
exactly this):

1. distributed sort at --sort-rows (default 250M; 1B with --sort-rows
   1000000000) over the widest mesh, sample-sort shuffle, fenced;
2. out-of-core join at --join-rows per side (default 100M) streamed
   through bounded device memory in --buckets Grace buckets, with the
   per-phase cost split (spill fetch / stage upload / join / drain fetch)
   and peak-RSS residency evidence.

One JSON line per row, like run_bench. Peak RSS comes from
resource.getrusage(RUSAGE_SELF).ru_maxrss (KiB on Linux).

Usage: python benchmarks/scale_bench.py [--sort-rows N] [--join-rows N]
       [--cpu] [--mesh 8] [--reps 1] [--skip-sort] [--skip-join]
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("CYLON_TPU_NO_X64", "1")

import numpy as np


def emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def rss_gb() -> float:
    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sort-rows", type=int, default=250_000_000)
    ap.add_argument("--join-rows", type=int, default=100_000_000,
                    help="rows PER SIDE for the out-of-core join")
    ap.add_argument("--buckets", type=int, default=32)
    ap.add_argument("--chunks", type=int, default=32)
    ap.add_argument("--reps", type=int, default=1)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--mesh", type=int, default=8)
    ap.add_argument("--skip-sort", action="store_true")
    ap.add_argument("--skip-join", action="store_true")
    args = ap.parse_args()

    import __graft_entry__ as ge

    if not args.cpu:
        import bench as _b

        _b.require_tpu()  # --cpu is the only way onto the CPU
    else:
        ge._force_cpu_mesh(args.mesh)

    import jax

    import cylon_tpu as ct
    from bench import fence as _sync

    devices = jax.devices()
    platform = devices[0].platform
    world = len(devices) if use_cpu else 1
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )

    # ---- 1. big distributed sort (BASELINE config 3) -------------------
    if not args.skip_sort:
        n = args.sort_rows
        rng = np.random.default_rng(0)
        t0 = time.perf_counter()
        # generate in slabs to keep the host copy transient
        key = rng.integers(-(2**31), 2**31, n, dtype=np.int64).astype(
            np.int32
        )
        tbl = ct.Table.from_pydict(ctx, {"k": key})
        gen_s = time.perf_counter() - t0
        del key
        t0 = time.perf_counter()
        out = tbl.distributed_sort("k")
        _sync(out)
        first_s = time.perf_counter() - t0
        best = first_s
        for _ in range(max(0, args.reps - 1)):
            t0 = time.perf_counter()
            out = tbl.distributed_sort("k")
            _sync(out)
            best = min(best, time.perf_counter() - t0)
        # verify global order on the REAL layout: the sorted table is
        # range-partitioned across shards, each shard front-packed into a
        # cap-sized segment — check per-shard live-prefix monotonicity plus
        # shard-boundary order (one host fetch of the column)
        d = np.asarray(out._columns["k"].data)
        counts = np.asarray(out.counts_dev)
        cap = d.shape[0] // world
        segs = [d[i * cap : i * cap + counts[i]] for i in range(world)]
        mono = all((np.diff(s) >= 0).all() for s in segs)
        nonempty = [s for s in segs if len(s)]
        mono = mono and all(
            nonempty[i][-1] <= nonempty[i + 1][0]
            for i in range(len(nonempty) - 1)
        )
        emit({
            "benchmark": "scale_distributed_sort",
            "platform": platform,
            "world": world,
            "rows": n,
            "warm_s": round(best, 2),
            "first_s": round(first_s, 2),
            "gen_s": round(gen_s, 2),
            "rows_per_sec": round(n / best),
            "sorted_ok": mono,
            "peak_rss_gb": rss_gb(),
        })
        del tbl, out

    # ---- 2. out-of-core join at >=100M rows/side -----------------------
    if not args.skip_join:
        from cylon_tpu.parallel.ooc import OutOfCoreJoin

        n = args.join_rows
        chunk = max(n // args.chunks, 1)
        rng = np.random.default_rng(1)
        # chunk GENERATORS: the whole point is bounded residency — no
        # materialized 100M-row host array outside the streamed chunks
        def chunks(seed, vname):
            r = np.random.default_rng(seed)
            for _ in range(args.chunks):
                m = chunk
                yield {
                    "k": r.integers(0, n, m).astype(np.int32),
                    vname: r.normal(size=m).astype(np.float32),
                }

        t0 = time.perf_counter()
        job = OutOfCoreJoin(
            ctx, on="k", how="inner", num_buckets=args.buckets
        )
        sink = job.execute(chunks(2, "v"), chunks(3, "w"))
        wall = time.perf_counter() - t0
        emit({
            "benchmark": "scale_ooc_join",
            "platform": platform,
            "world": world,
            "rows": 2 * n,
            "rows_out": int(sink.rows),
            "chunks": args.chunks,
            "buckets": args.buckets,
            "wall_s": round(wall, 2),
            "rows_per_sec": round(2 * n / wall),
            "peak_rss_gb": rss_gb(),
            **{k: round(v, 2) for k, v in job.cost_split.items()},
        })


if __name__ == "__main__":
    main()
