"""The harness: one process, one cell, one run.

Set-up (data from the seed, load to HBM, warm-up until a whole call passes
with no compile event), then the window (a closed loop of the cell's one
query), then, outside every timing, the comparison with the plain reference
that decides ``correct``. Everything that belongs to one configuration, one
traffic mix, one query or one per-layer metric is a file of its own that is
found by the name ``BENCHMARK.json`` gives; see README.md.
"""
import argparse
import contextlib
import glob
import importlib
import json
import math
import os
import shutil
import sys
import time

import numpy as np

from chipbench import trace_reduce
from chipbench.checks import Number

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: where a traced run keeps its trace while it reduces it (inside the
#: checkout, listed in .gitignore, removed when the run ends)
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")
MAX_WARMUPS = 4
COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/core/compile/jaxpr_trace_duration",
)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def process_age() -> float:
    """Seconds since this process was started, from /proc (0 where there is
    none): the interpreter's own start-up belongs to the set-up."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


class CompileLog:
    """Every trace and backend-compile event of the process, with its time."""

    def __init__(self):
        import jax.monitoring

        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event in COMPILE_EVENTS:
            self.events.append((time.perf_counter(), event, duration))

    def since(self, t):
        return [e for e in self.events if e[0] >= t]


class Cell:
    """One entry of ``workloads`` with its configuration and traffic files,
    its query module and the per-layer metrics that apply to it."""

    def __init__(self, workload: str, manifest: dict | None = None):
        self.manifest = manifest or load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"chipbench: no cell {workload!r} in BENCHMARK.json")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config = load_json(ROOT, configs[self.entry["config"]]["file"])
        self.traffic = load_json(HERE, "traffic", self.entry["traffic"] + ".json")
        self.query = importlib.import_module(
            "chipbench.queries." + self.traffic["query"]
        )
        self.generator = importlib.import_module(
            "chipbench.generators." + self.config["generator"]
        )

    def metrics(self, group: str):
        """The manifest's metrics of ``group`` that this cell reports."""
        return [
            m for m in self.manifest[group]
            if self.name in m.get("workloads", [self.name])
        ]


def ready(table) -> int:
    """Wait for the device: the row count is the result's one host sync,
    then every column buffer is awaited (chip_smoke's ``_ready``)."""
    import jax

    n = table.row_count
    jax.block_until_ready([table.column(c).data for c in table.column_names])
    return n


def load_tables(ctx, data: dict) -> dict:
    import cylon_tpu as ct

    tables = {
        name: ct.Table.from_numpy(ctx, list(cols), list(cols.values()))
        for name, cols in data.items()
    }
    for t in tables.values():
        ready(t)
    return tables


def warm_up(call, log: CompileLog) -> int:
    """Call the query until a whole call passes with no compile event."""
    for i in range(1, MAX_WARMUPS + 1):
        t = time.perf_counter()
        ready(call())
        if not log.since(t):
            return i
    raise SystemExit(
        f"chipbench: still compiling after {MAX_WARMUPS} warm-up calls: "
        f"{sorted({e[1] for e in log.events[-8:]})}"
    )


def run_window(call, seconds: float, seed: int, max_queries=None) -> dict:
    """The closed loop. A query starts only while the window is open and the
    last one is let finish. Keeps the last result and one more, drawn from
    the seed over all the window's queries (a reservoir of one), for the
    comparison after the window."""
    rng = np.random.default_rng(seed)
    latencies, counts = [], []
    sample = last = None
    start = time.perf_counter()
    end = start
    while (end - start) < seconds or not latencies:
        if max_queries is not None and len(latencies) >= max_queries:
            break
        last = None  # the previous result is dropped before the next call
        t = time.perf_counter()
        last = call()
        counts.append(ready(last))
        end = time.perf_counter()
        latencies.append(end - t)
        if rng.integers(0, len(latencies)) == 0:
            sample = last
    kept = [last] if sample is last else [sample, last]
    return {
        "start": start, "end": end,
        "latencies": latencies, "counts": counts, "kept": kept,
    }


def percentile_nearest_rank(values, q: float) -> float:
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def peaks_for(kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    peaks = load_json(HERE, "peaks.json")
    if kind not in peaks or kind.startswith("_"):
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r}")
    return peaks[kind]


def compare(cell: Cell, window: dict, data: dict) -> list:
    """Every number compared, beside its limit: each query's row count and,
    for the results kept, the query's own comparison with its reference."""
    ref = cell.query.reference(data, cell.traffic["params"])
    numbers = [Number(
        "window.row_counts_wrong",
        sum(int(n) != ref["rows"] for n in window["counts"]), 0,
    )]
    for table in window["kept"]:
        numbers.extend(cell.query.compare(table, ref, cell.config))
    return numbers


def read_layer_metrics(cell: Cell, obs: dict) -> dict:
    out = {}
    for m in cell.metrics("per_layer"):
        reader = importlib.import_module("chipbench.layer_metrics." + m["name"])
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def reduce_trace(name: str, seed: int) -> dict:
    """Read the trace the window left in ``TRACE_DIR``, reduce it, remove it."""
    paths = glob.glob(
        os.path.join(TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        raise SystemExit("chipbench: the profiler wrote no trace")
    raw = trace_reduce.read_xplane(paths[0])
    reduced = trace_reduce.reduce_events(raw["devices"], raw["host"])
    dump = os.environ.get("CHIPBENCH_DUMP")
    if dump:  # for a look at a trace by hand; changes no number
        os.makedirs(dump, exist_ok=True)
        with open(os.path.join(dump, f"{name}-{seed}.json"), "w") as f:
            json.dump({"lines": raw["lines"], "samples": raw["samples"],
                       "reduced": reduced}, f)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return reduced


def run_cell(cell: Cell, devices, seed: int, seconds: float, trace: bool,
             started: float, rows=None, log: CompileLog | None = None,
             data_filter=None, max_queries=None, ctx=None) -> dict:
    """Set-up, window and comparison on ``devices``; the result line as a
    dict. ``rows`` (a tiny size) and ``data_filter`` (the control's lower
    precision) are for the rehearsal, the tests and ``control.py``, which
    also shares one ``ctx`` (and so its compiled programs) between seeds."""
    import jax

    import cylon_tpu as ct
    from cylon_tpu.utils import tracing

    log = log or CompileLog()
    phases = {"imports_s": time.perf_counter() - started}
    ctx = ctx or ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=list(devices))
    )
    data = cell.generator.make(cell.config, seed, rows)
    loaded = data_filter(data) if data_filter else data
    phases["data_s"] = time.perf_counter() - started
    tables = load_tables(ctx, loaded)
    phases["load_s"] = time.perf_counter() - started
    call = cell.query.build(tables, cell.traffic["params"])
    warmups = warm_up(call, log)
    phases["warm_up_s"] = time.perf_counter() - started

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
        max_queries = max_queries or int(cell.traffic["trace_queries"])
    syncs = tracing.get_count("host_sync")
    window_start = time.perf_counter()
    annotation = (
        jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT)
        if trace else contextlib.nullcontext()
    )
    try:
        with annotation:
            window = run_window(call, seconds, seed, max_queries)
    finally:
        if trace:
            jax.profiler.stop_trace()
    syncs = tracing.get_count("host_sync") - syncs
    window_compiles = [
        e for e in log.since(window_start) if e[0] <= window["end"]
    ]
    memory_peak = peak_bytes(devices)
    setup_s = window_start - started

    lat = window["latencies"]
    queries = len(lat)
    in_rows = cell.query.input_rows(data, cell.traffic["params"])
    end_to_end = {
        "rows_per_s": queries * in_rows / (window["end"] - window["start"]),
        "query_p50_ms": 1e3 * percentile_nearest_rank(lat, 0.5),
        "query_p95_ms": 1e3 * percentile_nearest_rank(lat, 0.95),
        "setup_s": setup_s,
    }
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": memory_peak,
    }
    result = {"attempted": queries, "failed": 0, "device": device}

    if trace:
        reduced = reduce_trace(cell.name, seed)
        busy = [d["busy_s"] for d in reduced["devices"].values()]
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_reduce.breakdown(reduced)
        obs = {
            "queries": queries,
            "counters": {"host_sync": syncs},
            "window_compile_events": window_compiles,
            "trace": reduced,
            "least_bytes": cell.query.least_bytes(
                data, cell.traffic["params"], int(window["counts"][-1])
            ),
            "peaks": peaks_for(device["kind"]),
        }
        result["metrics"] = read_layer_metrics(cell, obs)
    else:
        units = {m["name"]: m["unit"] for m in cell.metrics("end_to_end")}
        result["metrics"] = {
            name: {"value": float(end_to_end[name]), "unit": unit}
            for name, unit in units.items()
        }

    # outside every timing: the comparison with the plain reference
    t = time.perf_counter()
    numbers = compare(cell, window, data)
    for n in numbers:
        print(f"compared {n.name} = {n.value!r} limit {n.limit!r} "
              f"{'ok' if n.ok else 'FAILED'}")
    result["correct"] = all(n.ok for n in numbers)
    print(json.dumps({
        "info": cell.name, "seed": seed, "warmup_calls": warmups,
        "setup_reached_s": phases,
        "end_to_end": end_to_end, "host_syncs_window": syncs,
        "window_compile_events": len(window_compiles),
        "latencies_ms": [1e3 * x for x in lat],
        "check_s": time.perf_counter() - t,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }))
    result["numbers"] = [list(n) for n in numbers]
    return result


def main(argv, started: float) -> int:
    ap = argparse.ArgumentParser(prog="chipbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    # set-up counts from the process's start, not from this module's import
    age = process_age()
    if age > 0:
        started = time.perf_counter() - age

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(
            f"chipbench: {cell.name} needs {cell.chips} TPU device(s), found "
            f"{len(devices)} x {devices[0].platform}; nothing was run",
            file=sys.stderr,
        )
        return 2
    result = run_cell(
        cell, devices[: cell.chips], args.seed, args.seconds,
        bool(args.trace), started,
    )
    order = ("correct", "attempted", "failed", "metrics", "device", "breakdown")
    print(json.dumps({k: result[k] for k in order if k in result}), flush=True)
    return 0
