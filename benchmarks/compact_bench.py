"""How a group-by's run heads reach their slots, timed on the chip (PR 46).

Times the compaction sort the group-by ran until PR 46 (one unstable
``jax.lax.sort`` of every slot keyed on the row's own position, the
payloads riding; kept here as the reference and nowhere in the library)
against ``ops.sort.step_compact``, the log-step compress, at one bit and at
two bits a pass, over

- 2^16, 2^18, 2^20, 2^22, 2^24 and 2^26 slots,
- 3.7% of the slots kept (``h2o-q5-w4``'s 2,500,000 run heads of 2^26) and
  63% (``groupby-w1``'s 2.53M of 2^22),
- ``groupby-w1``'s lanes (a uint32 key word, one float64 sum) and
  ``h2o-q5-w4``'s (a uint32 word, two int64 sums, one float64).

A time is the host's clock around one call that ends in
``block_until_ready``, the least of ``--reps``; a call costs the host some
0.1 ms whatever it holds, so the smallest sizes read the dispatch, not the
device. The inputs are made on the device from ``--seed``; every variant's
kept rows are summed and compared with the sort's. One JSON line a
measurement, then the table as markdown (also written under
``chiprun_out/``): what ``ops.sort.STEP_TWO_BITS_MIN_SLOTS`` is set from.

Usage: python benchmarks/compact_bench.py [--logs 16,18,...]
           [--variants sort,steps-1-bit,steps-2-bits] [--cpu]
No cell runs it. It needs a TPU and exits non-zero without one (``--cpu``
rehearses the control flow at small sizes and prints no device number).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LANES = {
    "groupby-w1": ("uint32", "float64"),
    "h2o-q5-w4": ("uint32", "int64", "int64", "float64"),
}
KEPT = (0.037, 0.63)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--logs", default="16,18,20,22,24,26")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=46)
    ap.add_argument("--variants", default="sort,steps-1-bit,steps-2-bits")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)
    from cylon_tpu.ops import sort as _sort

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu:
        sys.exit(f"compact_bench needs a TPU, found {device.platform}")

    def sort_compact(keep, pays):
        cap = keep.shape[0]
        key = jnp.where(keep, jnp.arange(cap, dtype=jnp.int32), jnp.int32(cap))
        out = jax.lax.sort(tuple([key] + list(pays)), num_keys=1, is_stable=False)
        return list(out[1:])

    def steps(two_bits_from):
        def run(keep, pays):
            _sort.STEP_TWO_BITS_MIN_SLOTS = two_bits_from  # read while tracing
            return _sort.step_compact(keep, pays)[1]

        return run

    variants = {
        "sort": sort_compact,
        "steps-1-bit": steps(1 << 62),
        "steps-2-bits": steps(0),
    }
    variants = {name: variants[name] for name in args.variants.split(",")}

    def inputs(n, share, dtypes):
        def make(seed):
            i = jnp.arange(n, dtype=jnp.uint32)
            h = i * jnp.uint32(2654435761) + seed
            h = (h ^ (h >> 15)) * jnp.uint32(2246822519)
            h = h ^ (h >> 13)
            keep = h < jnp.uint32(int(share * 2**32))
            pays = []
            for j, dtype in enumerate(dtypes):
                x = (h * jnp.uint32(2 * j + 3)) ^ i
                pays.append(
                    x.astype(jnp.float64) * 1.25e-3 if dtype == "float64"
                    else x.astype(dtype)
                )
            return keep, pays

        return jax.jit(make)(jnp.uint32(args.seed % (1 << 32)))

    def kept_sums(out, keep):
        live = jnp.arange(keep.shape[0]) < jnp.sum(keep)
        return [
            float(jnp.sum(jnp.where(live, x.astype(jnp.float64), 0.0)))
            for x in out
        ]

    standing = _sort.STEP_TWO_BITS_MIN_SLOTS
    rows = []
    for log2 in (int(x) for x in args.logs.split(",")):
        n = 1 << log2
        for cell, dtypes in LANES.items():
            want = {}
            for name, fn in variants.items():
                jitted = jax.jit(fn)
                for share in KEPT:
                    keep, pays = inputs(n, share, dtypes)
                    t0 = time.perf_counter()
                    jax.block_until_ready(jitted(keep, pays))
                    first = time.perf_counter() - t0
                    times = []
                    for _ in range(args.reps):
                        t0 = time.perf_counter()
                        out = jax.block_until_ready(jitted(keep, pays))
                        times.append(time.perf_counter() - t0)
                    sums = kept_sums(out, keep)
                    # (the first variant named is what the others are held to)
                    same = want.setdefault(share, sums) == sums
                    row = {
                        "slots_log2": log2, "lanes": cell, "variant": name,
                        "kept": share, "ms": round(min(times) * 1e3, 4),
                        "first_call_s": round(first, 2), "same_rows": same,
                        "device": device.device_kind,
                    }
                    print(json.dumps(row), flush=True)
                    rows.append(row)
                    del out, keep, pays
    _sort.STEP_TWO_BITS_MIN_SLOTS = standing

    head = "| slots | lanes | kept | " + " | ".join(variants) + " |"
    lines = [head, "|" + " --- |" * (3 + len(variants))]
    for log2 in sorted({r["slots_log2"] for r in rows}):
        for cell in LANES:
            for share in KEPT:
                ms = {
                    r["variant"]: r["ms"] for r in rows
                    if (r["slots_log2"], r["lanes"], r["kept"]) == (log2, cell, share)
                }
                lines.append(
                    f"| 2^{log2} | {cell} | {share:.1%} | "
                    + " | ".join(f"{ms[v]:.3f}" for v in variants) + " |"
                )
    table = "\n".join(lines)
    print(table)
    if not args.cpu:
        os.makedirs("chiprun_out", exist_ok=True)
        stem = "chiprun_out/compact_bench." + "+".join(variants)
        with open(stem + ".md", "w") as f:
            f.write(table + "\n")
        with open(stem + ".jsonl", "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    if not all(r["same_rows"] for r in rows):
        sys.exit("a variant's kept rows differ from the sort's")


if __name__ == "__main__":
    main()
