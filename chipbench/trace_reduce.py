"""From a profiler trace to the few numbers the layer metrics read.

``read_xplane`` turns an ``.xplane.pb`` (through ``jax.profiler.ProfileData``,
nothing but JAX) into plain ``(name, start_ns, end_ns)`` events per device
and for the host. ``reduce_events`` works on those lists alone, so the tests
drive it with hand-made events: busy intervals (the union of a device's
operation intervals inside the window), per-operation self time, collective
time, and the longest idle gaps named by what the host was doing in them.
"""
import re

import numpy as np

#: the line of a device plane that holds one event per executed operation
OP_LINE = "XLA Ops"
#: the host annotation the harness puts around the traced window
WINDOW_EVENT = "chipbench.window"
COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|alltoall|allgather|allreduce",
    re.IGNORECASE,
)
TOP = 10
#: how many of the longest gaps are looked up in the host's events
GAPS_NAMED = 40


def read_xplane(path: str) -> dict:
    """``{"devices": {plane: [event]}, "host": [event], "lines": {...}}``
    with events as ``(name, start_ns, end_ns)``; ``samples`` holds the
    first events of each device line."""
    from jax.profiler import ProfileData

    devices, host, lines, samples = {}, [], {}, {}
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            events = [
                (
                    short_name(e.name) if is_device else e.name,
                    float(e.start_ns), float(e.start_ns + e.duration_ns),
                )
                for e in line.events
            ]
            lines[f"{plane.name}|{line.name}"] = len(events)
            if is_device:  # for a look by hand (CHIPBENCH_DUMP)
                first = next(iter(line.events), None)
                samples[f"{plane.name}|{line.name}"] = {
                    "events": events[:12],
                    "stats": [] if first is None else [
                        (str(k), str(v)[:200]) for k, v in first.stats
                    ],
                }
            if is_device and line.name == OP_LINE:
                devices.setdefault(plane.name, []).extend(events)
            elif plane.name.startswith("/host:"):
                host.extend(events)
    return {
        "devices": devices, "host": host, "lines": lines, "samples": samples
    }


#: ``%fusion.122 = s32[8388608]{0:T(1024)} fusion(...), kind=kCustom, ...``
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_KIND = re.compile(r"kind=(\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO text; keep the
    name, the output shape, the opcode and the fusion kind or call target:
    ``fusion.122 s32[8388608] fusion:kCustom``."""
    head, eq, rest = name.partition(" = ")
    op = _OPCODE.search(rest)
    if not eq or not op:
        return name[:120]
    shape = "tuple" if rest.startswith("(") else re.split(r"[{ ]", rest, 1)[0]
    detail = _KIND.search(rest) or _TARGET.search(rest)
    return (
        f"{head.lstrip('%')} {shape} {op[1]}"
        + (":" + detail[1] if detail else "")
    )


def merge(intervals):
    """Union of ``(start, end)`` intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events) -> dict:
    """Seconds by name, each event counted without the events nested in it
    (a ``while`` holds its body's operations: the body gets the time)."""
    totals = {}
    stack = []  # [name, start, end, seconds covered by direct children]

    def close(item):
        name, s, e, covered = item
        totals[name] = totals.get(name, 0.0) + max(0.0, (e - s) - covered) / 1e9

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += max(0.0, min(e, stack[-1][2]) - s)
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return totals


def _clip(events, window):
    lo, hi = window
    return [
        (n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi
    ]


def _name_gap(gap, host):
    """What the host was doing in ``gap``: the shortest host event that
    covers at least half of it (the innermost frame that explains it),
    else the one that overlaps it most."""
    if not len(host["names"]):
        return "no host event"
    lo, hi = gap
    overlap = np.minimum(host["ends"], hi) - np.maximum(host["starts"], lo)
    covers = overlap >= 0.5 * (hi - lo)
    if covers.any():
        length = np.where(covers, host["ends"] - host["starts"], np.inf)
        return host["names"][int(np.argmin(length))]
    if (overlap > 0).any():
        return host["names"][int(np.argmax(overlap))]
    return "no host event"


def reduce_events(devices: dict, host: list, window=None) -> dict:
    """The reduction. ``window`` is ``(start_ns, end_ns)``; where it is not
    given it is the host's ``chipbench.window`` event, else the span of the
    device events."""
    if window is None:
        marks = [(s, e) for n, s, e in host if n == WINDOW_EVENT]
        if marks:
            window = marks[0]
        else:
            every = [ev for evs in devices.values() for ev in evs]
            if not every:
                return {"window_s": 0.0, "devices": {}}
            window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    spans = [ev for ev in host if ev[0] != WINDOW_EVENT]
    host_index = {
        "names": [n for n, _, _ in spans],
        "starts": np.array([s for _, s, _ in spans], np.float64),
        "ends": np.array([e for _, _, e in spans], np.float64),
    }
    out = {"window_s": (window[1] - window[0]) / 1e9, "devices": {}}
    for plane in sorted(devices):
        events = _clip(devices[plane], window)
        busy = merge((s, e) for _, s, e in events)
        ops = self_times(events)
        edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
        gaps = sorted(
            (
                (edges[i], edges[i + 1])
                for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]
            ),
            key=lambda g: g[0] - g[1],
        )
        named = {}
        for gap in gaps[:GAPS_NAMED]:
            name = _name_gap(gap, host_index)
            named[name] = named.get(name, 0.0) + (gap[1] - gap[0]) / 1e9
        out["devices"][plane] = {
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "n_ops": len(events),
            "ops": sorted(ops.items(), key=lambda kv: -kv[1]),
            "collective_s": sum(
                t for n, t in ops.items() if COLLECTIVE.search(n)
            ),
            "idle_gaps": sorted(named.items(), key=lambda kv: -kv[1])[:TOP],
        }
    return out


def breakdown(reduced: dict) -> dict:
    """The result line's ``breakdown``, from the first device."""
    if not reduced["devices"]:
        return {"device_ops": [], "idle_gaps": []}
    first = reduced["devices"][sorted(reduced["devices"])[0]]
    return {
        "device_ops": [[n, t] for n, t in first["ops"][:TOP]],
        "idle_gaps": [[n, t] for n, t in first["idle_gaps"]],
    }
