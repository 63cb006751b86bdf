"""Headline measurement: distributed inner join throughput on the chip.

Mirrors the reference's flagship benchmark (distributed inner join, strong
scaling — docs/docs/arch.md:148-160; driver
cpp/src/examples/bench/table_join_dist_test.cpp). Baseline normalization:
Cylon joins 2x200M-row tables in 141.5 s on 1 CPU worker (BASELINE.md)
-> 400e6/141.5 = 2.827e6 input rows/sec/worker. ``vs_baseline`` is our
per-chip input-row rate over that.

One process, and it needs a TPU: without one it exits non-zero and prints
no result — a CPU run is never reported under a device metric's name. Run
it on the chip as ``python bench.py``; prints ONE JSON line
{"metric", "value", "unit", "vs_baseline", ...} naming the device it ran on.

Env knobs: BENCH_ROWS (rows a side, default 8,000,000), BENCH_REPS.
"""
import json
import os
import sys
import time

import numpy as np

# keep the benchmark in 32-bit: TPU int64 is emulated and the baseline join
# is on int keys that fit int32
os.environ.setdefault("CYLON_TPU_NO_X64", "1")

BASELINE_ROWS_PER_SEC = 400e6 / 141.5  # cylon 1-worker input rows/sec

_FENCE_CACHE: dict = {}


def fence(tbl) -> float:
    """Completion fence: fetch a scalar that depends on every output column,
    so the timed region ends when the work has, and nothing is dead code.

    ONE jitted program (cached per shape signature), not an eager op chain:
    each eager op is its own dispatch, and the fence must cost one dispatch
    + one fetch, or it IS the benchmark."""
    import jax
    import jax.numpy as jnp

    datas = [c.data for c in tbl._columns.values()]
    key = tuple((d.shape, str(d.dtype)) for d in datas)
    fn = _FENCE_CACHE.get(key)
    if fn is None:

        @jax.jit
        def fn(ds):
            s = jnp.float32(0)
            for d in ds:
                s = s + jnp.sum(d.astype(jnp.float32))
            return s

        _FENCE_CACHE[key] = fn
    return float(fn(datas))


def require_tpu():
    """The first device, or exit non-zero: a measurement that asked for the
    chip never carries on on the CPU (the ``benchmarks/`` scripts take
    ``--cpu`` for a CPU run, and label it so)."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench: needs a TPU, found {dev.platform}; nothing was measured")
    return dev


def main() -> int:
    import jax

    import cylon_tpu as ct
    from cylon_tpu.ops.join import emit_impl_for

    dev = require_tpu()

    # rows PER SIDE (run_bench.py's lines record TOTAL input rows)
    n = int(os.environ.get("BENCH_ROWS", 8_000_000))
    reps = int(os.environ.get("BENCH_REPS", 5))
    rng = np.random.default_rng(0)
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:1])
    )
    keyspace = n  # ~1 match per key on average, like the reference generator
    left = ct.Table.from_pydict(
        ctx,
        {
            "k": rng.integers(0, keyspace, n).astype(np.int32),
            "v": rng.normal(size=n).astype(np.float32),
        },
    )
    right = ct.Table.from_pydict(
        ctx,
        {
            "k": rng.integers(0, keyspace, n).astype(np.int32),
            "w": rng.normal(size=n).astype(np.float32),
        },
    )

    # warmup (compile) — measured separately so the JSON records both
    t0 = time.perf_counter()
    fence(left.distributed_join(right, on="k", how="inner"))
    compile_s = time.perf_counter() - t0

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fence(left.distributed_join(right, on="k", how="inner"))
        best = min(best, time.perf_counter() - t0)

    rate = 2 * n / best / ctx.world_size  # per-chip
    print(json.dumps({
        "metric": "dist_inner_join_input_rows_per_sec_per_chip",
        "value": round(rate),
        "unit": "rows/s",
        "vs_baseline": round(rate / BASELINE_ROWS_PER_SEC, 3),
        "warm_s": round(best, 4),
        "compile_s": round(compile_s, 2),
        # the RESOLVED emit impl (the env request resolved against the
        # mesh), plus the expand variant when windowed actually ran
        "emit_impl": emit_impl_for(ctx.world_size, dev.platform),
        "expand_gather": os.environ.get("CYLON_TPU_EXPAND_GATHER", "take"),
        "platform": dev.platform,
        "device": dev.device_kind,
        "rows": n,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
