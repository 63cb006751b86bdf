"""The readings that the limits of ``correct`` are set from.

    python3 chipbench/control.py --workload join-w1 --seeds 101,102,103

For each seed, in one process (set-up is long, so the seeds share the
compiled programs): the cell's own query on the cell's own data through the
harness's own window and comparison (a short window, two queries), then the
control, the same with every float64 value rounded through float32
(``checks.lower_precision``), which has to come out as not correct. Prints
one JSON line a seed with every number compared, and a last line with the
sound runs' largest and the control's smallest of each number. Needs the
cell's chips like the harness; the benchmark's own runs never run it.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench.checks import lower_precision  # noqa: E402


def readings(cell, devices, seeds, rows=None, queries=2) -> dict:
    """``{"sound": {number: [values]}, "control": {...}, "verdicts": [...]}``"""
    import cylon_tpu as ct

    log = harness.CompileLog()
    ctx = ct.CylonContext.init_distributed(ct.TPUConfig(devices=list(devices)))
    out = {"sound": {}, "control": {}, "verdicts": []}
    for seed in seeds:
        for side, data_filter in (
            ("sound", None),
            ("control", lambda d: lower_precision(d, cell.config)),
        ):
            result = harness.run_cell(
                cell, devices, seed, 1e9, False, time.perf_counter(),
                rows=rows, log=log, data_filter=data_filter,
                max_queries=queries, ctx=ctx,
            )
            for name, value, _limit in result["numbers"]:
                out[side].setdefault(name, []).append(value)
            out["verdicts"].append((seed, side, result["correct"]))
            print(json.dumps({
                "seed": seed, "side": side, "correct": result["correct"],
                "numbers": result["numbers"],
                "query_ms": result["metrics"]["query_p50_ms"]["value"],
            }), flush=True)
    return out


def summary(out: dict) -> dict:
    return {
        name: {
            "sound_largest": max(values),
            "control_smallest": min(out["control"].get(name, [float("nan")])),
        }
        for name, values in out["sound"].items()
    }


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench.control: needs {cell.chips} TPU device(s)",
              file=sys.stderr)
        return 2
    out = readings(
        cell, devices[: cell.chips], [int(s) for s in args.seeds.split(",")]
    )
    print(json.dumps({"workload": cell.name, "summary": summary(out)}))
    sound_ok = all(ok for _, side, ok in out["verdicts"] if side == "sound")
    control_fails = not any(
        ok for _, side, ok in out["verdicts"] if side == "control"
    )
    return 0 if sound_ok and control_fails else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
