"""Probe: compiled Pallas windowed emit under ``jit(shard_map)`` (VERDICT
r4 item 3).

Round 3 found compiled ``pallas_call`` recursing at trace time when the
emit kernel ran under ``jit(shard_map(...))`` on TPU, and gated the
windowed emit off for multi-chip meshes — exactly where the north star
lives. The suspected trigger was the NESTED jit (`expand_rows` carried its
own @jax.jit inside the shard_map-wrapped kernel); the emit path now calls
the unjitted ``expand_rows_raw``.

Only one real chip is reachable, so this probe runs the production join
kernel on a 1-device mesh with ``CYLON_TPU_FORCE_SHARD_MAP=1`` — the same
``jit(shard_map(kernel-embedding-pallas_call))`` program structure a
multi-chip mesh builds, minus the collectives (which contain no pallas and
are exercised by ``dryrun_multichip``'s 8/16/32-device CPU runs). PASS
here plus the multi-device interpret dryrun is the strongest multi-chip
evidence this environment can produce.

For each expand variant: correctness vs the XLA-gather emit (row-set
equality on a seeded join) and warm timing. One JSON line per variant plus
a summary line; RecursionError is caught and reported as the historical
failure mode.

Usage: python benchmarks/shardmap_pallas_probe.py [--rows N] [--cpu]
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


def emit_line(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=2_000_000)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import __graft_entry__ as ge

    if not args.cpu:
        import bench as _b

        _b.require_tpu()  # --cpu is the only way onto the CPU
    else:
        ge._force_cpu_mesh(1)
        args.rows = min(args.rows, 200_000)

    import jax

    import cylon_tpu as ct

    platform = jax.devices()[0].platform
    n = args.rows
    rng = np.random.default_rng(3)
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:1])
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

    import bench as _b
    import pandas as pd

    def run_join():
        out = left.distributed_join(right, on="k", how="inner")
        # fence: one dispatch + one fetch (timing only — its sum covers
        # dead PADDING rows too, whose garbage legitimately differs
        # between emit impls)
        return out, _b.fence(out)

    def canon(tbl):
        df = tbl.to_pandas()
        cols = sorted(df.columns)
        return df[cols].sort_values(cols, kind="mergesort").reset_index(
            drop=True
        )

    # reference result: default gather emit, no shard_map forcing
    base_out, _ = run_join()
    base_rows = base_out.row_count
    base_df = canon(base_out)

    results = []
    for impl in ("take", "take_db", "onehot", "onehot_db"):
        env = {
            "CYLON_TPU_EMIT_IMPL": "windowed",
            "CYLON_TPU_EXPAND_GATHER": impl,
            "CYLON_TPU_FORCE_SHARD_MAP": "1",
        }
        os.environ.update(env)
        row = {
            "benchmark": f"shardmap_pallas_probe_{impl}",
            "platform": platform,
            "rows": n,
            "forced_shard_map": True,
        }
        try:
            t0 = time.perf_counter()
            out1, _ = run_join()
            row["compile_s"] = round(time.perf_counter() - t0, 2)
            # correctness: live-row set equality vs the gather emit (host
            # compare once, outside the timed reps)
            row["ok"] = bool(
                out1.row_count == base_rows and canon(out1).equals(base_df)
            )
            row["rows_out"] = int(out1.row_count)
            best = float("inf")
            for _ in range(args.reps):
                t0 = time.perf_counter()
                _, _sum = run_join()
                best = min(best, time.perf_counter() - t0)
            row["warm_s"] = round(best, 4)
        except RecursionError as e:
            row["ok"] = False
            row["error"] = f"RecursionError: {e}"[:200]
            row["recursion"] = True  # the historical r3 failure mode
        except Exception as e:
            row["ok"] = False
            row["error"] = f"{type(e).__name__}: {e}"[:300]
        finally:
            for k in env:
                os.environ.pop(k, None)
        results.append(row)
        emit_line(row)

    n_ok = sum(r.get("ok") for r in results)
    emit_line(
        {
            "benchmark": "shardmap_pallas_probe_summary",
            "platform": platform,
            "rows": n,
            "variants_ok": n_ok,
            "variants_total": len(results),
            "verdict": "shard_map_pallas_ok" if n_ok == len(results)
            else "shard_map_pallas_blocked",
        }
    )


if __name__ == "__main__":
    main()
