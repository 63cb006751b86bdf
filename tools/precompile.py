"""Pre-warm the persistent XLA compilation cache — the build-step analog.

The C++ reference pays its optimization once at `cmake --build` time; an
XLA program pays it on first trace per (program, shapes) per machine. This
script is the equivalent of the reference's build step: run it once on a
fresh machine (or bake it into an image) and the hot op set — the
speculative join, the two-phase probe/emit, fused join, sort, set ops,
groupby — is already in the persistent cache
(`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`; context.py)
for every pow2 capacity
bucket requested, so first user calls compile-warm.

Capacities are pow2-rounded by the engine (shape bucketing), so warming
bucket caps {2^lo .. 2^hi} covers EVERY row count in that range.

Usage:
  python tools/precompile.py                 # caps 1M..16M, world=1
  python tools/precompile.py --lo 20 --hi 24 --ops join,sort
  python tools/precompile.py --cpu           # warm the CPU-backend cache
  python tools/precompile.py --cpu --topo 4x2 --lo 12 --hi 16
                                             # warm the two-hop shuffle
                                             # kernels on an OxI mesh
One JSON line per (op, cap): compile wall + cache status.

``--topo OxI`` declares a 2-D mesh of O*I devices (CYLON_TPU_MESH
equivalent), so the warmed set additionally covers the hierarchical
shuffle: hop-1 pack + inner all_to_all, the count-informed cross-outer
repack, hop-2 outer all_to_all, and the structured fused-join exchange
— each per capacity bucket, exactly the kernels a topology-declared
production context will request first.
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

ALL_OPS = ("join", "sort", "setops", "groupby")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lo", type=int, default=20, help="min cap = 2^lo")
    ap.add_argument("--hi", type=int, default=24, help="max cap = 2^hi")
    ap.add_argument("--ops", type=str, default=",".join(ALL_OPS))
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--topo", type=str, default="",
                    help="OxI 2-D mesh (e.g. 4x2): warm the two-hop "
                         "shuffle kernels on a world of O*I devices")
    args = ap.parse_args()

    world = 1
    if args.topo:
        o, i = (int(x) for x in args.topo.lower().split("x"))
        world = o * i

    if args.cpu:
        import __graft_entry__ as ge

        ge._force_cpu_mesh(world)

    import jax

    import cylon_tpu as ct

    platform = jax.devices()[0].platform
    if len(jax.devices()) < world:
        raise SystemExit(
            f"--topo {args.topo} needs {world} devices, have "
            f"{len(jax.devices())} (add --cpu for a virtual mesh)"
        )
    ops = [o.strip() for o in args.ops.split(",") if o.strip()]
    cfg = ct.TPUConfig(devices=jax.devices()[:world])
    if args.topo:
        cfg = ct.TPUConfig(devices=jax.devices()[:world],
                           mesh_shape=args.topo)
    ctx = ct.CylonContext.init_distributed(cfg)
    rng = np.random.default_rng(0)

    def make(n, vname):
        df = {
            "k": rng.integers(0, max(n, 2), n).astype(np.int32),
            vname: rng.normal(size=n).astype(np.float32),
        }
        if world == 1:
            return ct.Table.from_pydict(ctx, df)
        per = max(n // world, 1)
        return ct.Table.from_shards(ctx, [
            {"k": df["k"][s * per:(s + 1) * per],
             vname: df[vname][s * per:(s + 1) * per]}
            for s in range(world)
        ])

    for p in range(args.lo, args.hi + 1):
        cap = 1 << p
        # n just under the cap keeps the pow2 rounding AT this bucket
        n = cap - 1
        left = make(n, "v")
        right = make(n, "w")

        def t(name, fn):
            t0 = time.perf_counter()
            try:
                fn()
                err = None
            except Exception as e:  # keep warming the rest
                err = f"{type(e).__name__}: {str(e)[:200]}"
            wall = time.perf_counter() - t0
            line = {"op": name, "cap": cap, "platform": platform,
                    "wall_s": round(wall, 2)}
            if err:
                line["error"] = err
            print(json.dumps(line), flush=True)

        if "join" in ops:
            t("join_inner", lambda: left.join(right, on="k"))
            t("join_left", lambda: left.join(right, on="k", how="left"))
            t(
                "dist_join",
                lambda: left.distributed_join(right, on="k"),
            )
            t(
                "dist_join_fused",
                lambda: left.distributed_join(right, on="k", mode="fused"),
            )
        if "sort" in ops:
            t("sort", lambda: left.sort("v"))
            t("dist_sort", lambda: left.distributed_sort("v"))
        if "setops" in ops:
            lk = left.project(["k"])
            rk = right.project(["k"])
            t("union", lambda: lk.union(rk))
            t("subtract", lambda: lk.subtract(rk))
        if "groupby" in ops:
            t(
                "groupby_sum",
                lambda: left.distributed_groupby("k", {"v": "sum"}),
            )
        # drop per-bucket jit caches so memory stays bounded across buckets
        ctx.__dict__.get("_jit_cache", {}).clear()
        jax.clear_caches()


if __name__ == "__main__":
    main()
