"""chip_smoke.py: the quickest proof that the dataframe path still starts
on the chip.

Drives the normal path once through the public API (``CylonContext`` ->
``Table`` -> ``distributed_join`` -> ``distributed_groupby`` ->
``distributed_sort``) at a size its users would call real, and checks every
result against a plain numpy/pandas reference computed here from the same
seeded arrays. One process, default settings, no fallback: without a TPU it
exits non-zero before any phase runs.

    python chip_smoke.py             one chip: the wide phase (int32/float32,
                                     16,777,216 rows a side) and the
                                     default-dtype phase (int64/float64
                                     pandas frames, 1,048,576 rows a side)
    python chip_smoke.py --chips 4   four chips: the cross-chip phase only
                                     (the chunked shuffle; 4,194,304 rows a
                                     side per chip) and its reference

The seconds it prints are smoke observations of one cold and one warm call,
not benchmark numbers. The last line of its output is the device line, e.g.
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np
import pandas as pd

import cylon_tpu as ct
from cylon_tpu import native

#: rows a side of the wide phase, and of the cross-chip phase over all chips
ROWS = 1 << 24
#: rows a side of the default-dtype phase (the README quick start's dtypes)
DEFAULT_DTYPE_ROWS = 1 << 20
#: value tolerance by dtype: float32 sums of a few rows each against a
#: float64 reference; float64 as a TPU emulates it (about 48 bits)
RTOL = {np.dtype(np.float32): 1e-5, np.dtype(np.float64): 1e-12}


def check(ok: bool, what: str) -> None:
    """A failed check ends the run: nothing here catches it."""
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


def make_arrays(rows: int, seed: int, key_dtype, val_dtype):
    """Two tables' columns: uniform keys over keyspace = rows (the upstream
    suite's shape), values in [0, 1)."""
    rng = np.random.default_rng(seed)
    lk = rng.integers(0, rows, rows).astype(key_dtype)
    rk = rng.integers(0, rows, rows).astype(key_dtype)
    lv = rng.random(rows).astype(val_dtype)
    rv = rng.random(rows).astype(val_dtype)
    return lk, lv, rk, rv


def _ready(table) -> int:
    """Wait for the device: the row count is the result's one host sync,
    then every column buffer is awaited. Returns the row count."""
    n = table.row_count
    jax.block_until_ready(
        [table.column(c).data for c in table.column_names]
    )
    return n


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    n = _ready(out)
    return out, n, time.perf_counter() - t0


def _pair_hash(k: np.ndarray, v: np.ndarray) -> int:
    """Order-independent checksum of (key, value) rows: a permutation of
    the rows keeps it, a lost, duplicated or re-paired row changes it."""
    bits = v.view(np.uint32 if v.dtype.itemsize == 4 else np.uint64)
    h = k.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    h ^= bits.astype(np.uint64) * np.uint64(0xC2B2AE3D27D4EB4F)
    h ^= h >> np.uint64(29)
    h *= np.uint64(0xBF58476D1CE4E5B9)
    return int(h.sum(dtype=np.uint64))


def _peak_bytes(ctx):
    """Largest ``peak_bytes_in_use`` over the context's devices; None where
    the backend does not report memory (the CPU rehearsal)."""
    stats = [d.memory_stats() for d in ctx.mesh.devices.flat]
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def _check_spread(ctx, table, what: str) -> None:
    """Every column of ``table`` is sharded over all of the context's
    devices with one padded length."""
    world = ctx.world_size
    for name in table.column_names:
        shards = table.column(name).data.addressable_shards
        check(
            len({s.device for s in shards}) == world,
            f"{what}.{name}: shards on {len({s.device for s in shards})} "
            f"devices, not {world}",
        )
        check(
            len({s.data.shape for s in shards}) == 1,
            f"{what}.{name}: shards of unequal padded length",
        )


def run_ops(ctx, left, right, arrays, held, observations: dict) -> None:
    """join -> group-by -> sort on loaded tables, each run cold then warm
    and each result compared with its reference. ``held`` is the left
    table read back from the device."""
    lk, lv, rk, rv = arrays
    rows = len(lk)
    rtol = RTOL[lv.dtype]
    # the plain reference: per-key counts and sums by bincount
    cnt_l = np.bincount(lk, minlength=rows)
    cnt_r = np.bincount(rk, minlength=rows)
    sum_l = np.bincount(lk, weights=lv.astype(np.float64), minlength=rows)
    sum_r = np.bincount(rk, weights=rv.astype(np.float64), minlength=rows)
    want_pairs = cnt_l * cnt_r
    want_rows = int(want_pairs.sum())
    want_keys = np.flatnonzero(want_pairs)
    want_sums = sum_l[want_keys] * cnt_r[want_keys]

    cold, warm = {}, {}

    # -- join ---------------------------------------------------------
    def join():
        return left.distributed_join(right, on="k", how="inner")

    joined, n_join, cold["join"] = _timed(join)
    again, n_again, warm["join"] = _timed(join)
    check(n_again == n_join, "warm join row count differs from cold")
    del again
    check(n_join == want_rows, f"join rows {n_join} != reference {want_rows}")
    if ctx.world_size > 1:
        _check_spread(ctx, joined, "join")
        per_shard = np.asarray(joined.row_counts)
        check(
            (per_shard > 0).all() and int(per_shard.sum()) == want_rows,
            f"join rows per shard {per_shard.tolist()}: every shard must "
            f"hold rows and they must sum to {want_rows}",
        )
        observations["join_rows_per_shard"] = per_shard.tolist()
    got = joined.to_pydict()
    check(
        sorted(got) == ["k_x", "k_y", "v", "w"],
        f"join columns {sorted(got)}",
    )
    check(np.array_equal(got["k_x"], got["k_y"]), "join keys differ by side")
    check(
        np.array_equal(np.bincount(got["k_x"], minlength=rows), want_pairs),
        "join rows per key differ from reference",
    )
    for col, ref in (("v", sum_l * cnt_r), ("w", sum_r * cnt_l)):
        sums = np.bincount(
            got["k_x"], weights=got[col].astype(np.float64), minlength=rows
        )
        check(
            np.allclose(sums, ref, rtol=1e-9, atol=0.0),
            f"join column {col}: per-key sums differ from reference",
        )
    del got

    # -- group-by over the join result ---------------------------------
    def groupby():
        return joined.distributed_groupby("k_x", {"v": "sum"})

    grouped, n_groups, cold["groupby"] = _timed(groupby)
    again, n_again, warm["groupby"] = _timed(groupby)
    check(n_again == n_groups, "warm group-by row count differs from cold")
    del again
    check(
        n_groups == len(want_keys),
        f"group-by groups {n_groups} != reference {len(want_keys)}",
    )
    got = grouped.to_pydict()
    order = np.argsort(got["k_x"], kind="stable")
    check(
        np.array_equal(got["k_x"][order], want_keys),
        "group-by keys differ from reference",
    )
    check(
        np.allclose(got["v_sum"][order], want_sums, rtol=rtol, atol=0.0),
        f"group-by sums differ from reference beyond rtol {rtol}",
    )
    del got, grouped, joined

    # -- sort one table by value ----------------------------------------
    def sort():
        return left.distributed_sort("v")

    ordered, n_sorted, cold["sort"] = _timed(sort)
    again, n_again, warm["sort"] = _timed(sort)
    check(n_again == n_sorted, "warm sort row count differs from cold")
    del again
    check(n_sorted == rows, f"sort rows {n_sorted} != input {rows}")
    got = ordered.to_pydict()
    # a sort only moves rows, so against the values as the device holds
    # them (``held``; _phase checked those against the seeded arrays) the
    # output is exactly sorted(v): monotone, and a permutation
    check(
        np.array_equal(got["v"], np.sort(held["v"])),
        "sort output is not sorted(v)",
    )
    check(
        _pair_hash(got["k"], got["v"]) == _pair_hash(held["k"], held["v"]),
        "sort re-paired, lost or duplicated rows",
    )

    observations.update(
        join_rows=n_join,
        groups=n_groups,
        sorted_rows=n_sorted,
        seconds_cold_incl_compile=cold,
        seconds_warm=warm,
    )


def _phase(ctx, name, rows, seed, key_dtype, val_dtype, load) -> dict:
    arrays = make_arrays(rows, seed, key_dtype, val_dtype)
    lk, lv, rk, rv = arrays
    t0 = time.perf_counter()
    left = load(ctx, {"k": lk, "v": lv})
    right = load(ctx, {"k": rk, "w": rv})
    _ready(left)
    _ready(right)
    obs = {
        "phase": name,
        "note": "smoke observations, not benchmark numbers",
        "world": ctx.world_size,
        "rows_a_side": rows,
        "key_dtype": np.dtype(key_dtype).name,
        "value_dtype": np.dtype(val_dtype).name,
        "seconds_load": time.perf_counter() - t0,
    }
    if ctx.world_size > 1:
        _check_spread(ctx, left, "left")
        _check_spread(ctx, right, "right")
        if ctx.platform == "tpu":
            in_use = [
                int(d.memory_stats()["bytes_in_use"])
                for d in ctx.mesh.devices.flat
            ]
            check(
                all(b > 0 for b in in_use),
                f"bytes in use per device after load {in_use}",
            )
            obs["bytes_in_use_after_load"] = in_use
    # what the device holds is what was loaded: keys exactly, values to the
    # dtype's tolerance (a TPU has no native float64; a float64 column
    # comes back within RTOL of what went in, not bit for bit)
    held = left.to_pydict()
    check(np.array_equal(held["k"], lk), "loaded keys differ from input")
    check(
        np.allclose(held["v"], lv, rtol=RTOL[lv.dtype], atol=0.0),
        "loaded values differ from input beyond tolerance",
    )
    obs["values_held_bit_exact"] = bool(np.array_equal(held["v"], lv))
    run_ops(ctx, left, right, arrays, held, obs)
    obs.update(
        peak_bytes_in_use=_peak_bytes(ctx),
        native_reader_loaded=native.available(),
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
    )
    return obs


def _from_numpy(ctx, cols):
    return ct.Table.from_numpy(ctx, list(cols), list(cols.values()))


def _from_pandas(ctx, cols):
    return ct.Table.from_pandas(ctx, pd.DataFrame(cols))


def wide_phase(ctx, rows: int, seed: int) -> dict:
    """int32 key + float32 value, the widths the hot path is built for."""
    return _phase(ctx, "wide", rows, seed, np.int32, np.float32, _from_numpy)


def default_dtype_phase(ctx, rows: int, seed: int) -> dict:
    """int64/float64 pandas frames, as the README's quick start loads."""
    return _phase(
        ctx, "default_dtype", rows, seed, np.int64, np.float64, _from_pandas
    )


def cross_chip_phase(ctx, rows: int, seed: int) -> dict:
    """The path across chips: the chunked shuffle under join, group-by and
    sort. ``rows`` is a side's total over all chips."""
    check(ctx.world_size > 1, "the cross-chip phase needs more than one chip")
    return _phase(
        ctx, "cross_chip", rows, seed, np.int32, np.float32, _from_numpy
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rows", type=int, default=ROWS,
        help="rows a side of the wide / cross-chip phase (over all chips)",
    )
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4 runs the cross-chip phase and its reference, nothing else",
    )
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(
            f"chip_smoke: needs {args.chips} TPU device(s), found "
            f"{len(devices)} x {devices[0].platform}; nothing was run",
            file=sys.stderr,
        )
        return 2

    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[: args.chips])
    )
    if args.chips == 1:
        phases = [
            (wide_phase, args.rows),
            (default_dtype_phase, min(args.rows, DEFAULT_DTYPE_ROWS)),
        ]
    else:
        phases = [(cross_chip_phase, args.rows)]
    for phase, rows in phases:
        print(json.dumps(phase(ctx, rows, args.seed)), flush=True)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
