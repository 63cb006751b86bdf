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

``--receive`` (PR 47) asks the other compaction's question: how the rows a
shuffle round received reach the front of their buffer. The buffer is four
chunks, each a live prefix (95% full, ``sort-w4``'s, and 48%,
``join-skew-w4``'s slot fill), as a ``[rows, 1]`` or ``[rows, 2]`` int32 lane
matrix beside one float64 passthrough column; timed are ``order`` (the
argsort of the liveness mask and a gather an array, what
``parallel.shuffle.compact_received_lanes`` keeps for general masks),
``blocks`` (``parallel.shuffle.front_pack_chunks``: a block write a chunk),
``blocks-1d`` (the same over the matrix's columns, one by one) and ``steps``
(``ops.sort.step_compact`` over the mask). Under 1 ms the host's clock around
one call reads the dispatch, so a ``--receive`` time is that of ``--chain``
calls enqueued back to back and waited for once, over their count.

Usage: python benchmarks/compact_bench.py [--logs 16,18,...]
           [--variants sort,steps-1-bit,steps-2-bits] [--cpu]
       python benchmarks/compact_bench.py --receive [--logs 20,21]
           [--variants order,blocks,blocks-1d,steps] [--cpu]
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

#: the received buffer of ``--receive``: chunks, their fill, lane widths
WORLD = 4
FILL = (0.95, 0.48)
WIDTHS = (1, 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--logs")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=46)
    ap.add_argument("--variants")
    ap.add_argument("--receive", action="store_true")
    ap.add_argument("--chain", type=int, default=20)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.logs is None:
        args.logs = "20,21" if args.receive else "16,18,20,22,24,26"
    if args.variants is None:
        args.variants = (
            "order,blocks,blocks-1d,steps" if args.receive
            else "sort,steps-1-bit,steps-2-bits"
        )

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)
    from cylon_tpu.ops import sort as _sort

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu:
        sys.exit(f"compact_bench needs a TPU, found {device.platform}")
    if args.receive:
        return receive(args, device)

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


def receive(args, device):
    """The ``--receive`` question (module docstring): one JSON line a
    measurement, then the table; exits non-zero where a variant's live rows
    are not ``order``'s bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cylon_tpu.ops import sort as _sort
    from cylon_tpu.parallel import shuffle as _sh

    def by_order(mat, pt, counts):
        mask, _total = _sh.received_row_mask(counts, WORLD, mat.shape[0] // WORLD)
        front = _sh.order_front(mask)
        g = front(mat)
        return [g[:, j] for j in range(g.shape[1])] + [front(pt)]

    def by_blocks(mat, pt, counts):
        g = _sh.front_pack_chunks(mat, counts)
        return [g[:, j] for j in range(g.shape[1])] + [
            _sh.front_pack_chunks(pt, counts)
        ]

    def by_blocks_1d(mat, pt, counts):
        return [
            _sh.front_pack_chunks(x, counts)
            for x in [mat[:, j] for j in range(mat.shape[1])] + [pt]
        ]

    def by_steps(mat, pt, counts):
        mask, _total = _sh.received_row_mask(counts, WORLD, mat.shape[0] // WORLD)
        lanes = [mat[:, j] for j in range(mat.shape[1])] + [pt]
        return _sort.step_compact(mask, lanes)[1]

    variants = {
        "order": by_order, "blocks": by_blocks, "blocks-1d": by_blocks_1d,
        "steps": by_steps,
    }
    variants = {name: variants[name] for name in args.variants.split(",")}

    @jax.jit
    def make(seed, mat_like):
        n, width = mat_like.shape
        i = jnp.arange(n, dtype=jnp.uint32)
        h = i * jnp.uint32(2654435761) + seed
        h = (h ^ (h >> 15)) * jnp.uint32(2246822519)
        h = h ^ (h >> 13)
        mat = jnp.stack(
            [(h * jnp.uint32(2 * j + 3)).astype(jnp.int32) for j in range(width)],
            axis=1,
        )
        return mat, (h ^ i).astype(jnp.float64) * 1.25e-3

    rows = []
    for log2 in (int(x) for x in args.logs.split(",")):
        n = 1 << log2
        bc = n // WORLD
        for width in WIDTHS:
            mat, pt = make(
                jnp.uint32(args.seed % (1 << 32)), jnp.zeros((n, width), jnp.int8)
            )
            for fill in FILL:
                # ragged counts about the fill; the last chunk the fullest
                counts = np.minimum(
                    (bc * fill * np.linspace(0.9, 1.05, WORLD)).astype(np.int32), bc
                )
                total = int(counts.sum())
                counts_dev = jnp.asarray(counts)
                want = None
                for name, fn in variants.items():
                    jitted = jax.jit(fn)
                    t0 = time.perf_counter()
                    out = jax.block_until_ready(jitted(mat, pt, counts_dev))
                    first = time.perf_counter() - t0
                    times = []
                    for _ in range(args.reps):
                        t0 = time.perf_counter()
                        for _k in range(args.chain):
                            out = jitted(mat, pt, counts_dev)
                        jax.block_until_ready(out)
                        times.append((time.perf_counter() - t0) / args.chain)
                    live = [np.asarray(x)[:total] for x in out]
                    live[-1] = live[-1].view(np.uint64)
                    # (the first variant named is what the others are held to)
                    if want is None:
                        want = live
                    same = all(np.array_equal(a, b) for a, b in zip(live, want))
                    row = {
                        "slots_log2": log2, "width": width, "fill": fill,
                        "variant": name, "ms": round(min(times) * 1e3, 4),
                        "first_call_s": round(first, 2), "same_rows": same,
                        "device": device.device_kind,
                    }
                    print(json.dumps(row), flush=True)
                    rows.append(row)
                    del out, live

    head = "| slots | lanes | fill | " + " | ".join(variants) + " |"
    lines = [head, "|" + " --- |" * (3 + len(variants))]
    for log2 in sorted({r["slots_log2"] for r in rows}):
        for width in WIDTHS:
            for fill in FILL:
                ms = {
                    r["variant"]: r["ms"] for r in rows
                    if (r["slots_log2"], r["width"], r["fill"]) == (log2, width, fill)
                }
                lines.append(
                    f"| 2^{log2} | s32[rows,{width}] + f64 | {fill:.0%} | "
                    + " | ".join(f"{ms[v]:.3f}" for v in variants) + " |"
                )
    table = "\n".join(lines)
    print(table)
    if not args.cpu:
        os.makedirs("chiprun_out", exist_ok=True)
        stem = "chiprun_out/compact_bench.receive"
        with open(stem + ".md", "w") as f:
            f.write(table + "\n")
        with open(stem + ".jsonl", "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    if not all(r["same_rows"] for r in rows):
        sys.exit("a variant's live rows differ from the first's")


if __name__ == "__main__":
    main()
