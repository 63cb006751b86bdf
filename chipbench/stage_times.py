"""Per-stage device time: the reduced trace joined with the program's own
stage table.

The trace names a device operation by its HLO instruction text, which
carries no ``op_name``; the program knows which programs it dispatched and
what their compiled text calls each instruction
(``cylon_tpu.obs.stages.device_stage_table``). Both sides are shortened by
``trace_reduce.short_name``, so table and trace are keyed by one function.
An operation's stage is the outermost name of the program's vocabulary on
its ``op_name`` path; ``sort_engine`` is also summed wherever it occurs on
the path. An operation the table gives no stage, gives two different
answers under one short name, or lists under a stale module (the compile
cache handed back an executable from before a scope moved) is
unattributed.

The five readers in ``layer_metrics/`` share one split a run: it is kept
in ``obs`` itself, so the table is built once a process.
"""
import json

from chipbench.trace_reduce import short_name

_KEPT = "_stage_split"
UNATTRIBUTED = "unattributed"


def stage_index(table: dict, stages) -> dict:
    """``{short name: (stage, in sort_engine)}``; ``None`` in place of the
    pair where the table's rows disagree or the module is stale."""
    stale = set(table["stale"])
    index = {}
    for module, text, op_name in table["rows"]:
        name = short_name(text.removeprefix("ROOT "))
        answer = (
            None if module in stale
            else (stages.stage_of(op_name), stages.in_sort_engine(op_name))
        )
        if index.setdefault(name, answer) != answer:
            index[name] = None
    return index


def split_ops(ops, index: dict, queries: int) -> dict:
    """Milliseconds a query by stage from ``[(short name, seconds)]``."""
    by_stage, engine_in = {}, {}
    for name, seconds in ops:
        stage, engine = index.get(name) or (None, False)
        stage = stage or UNATTRIBUTED
        by_stage[stage] = by_stage.get(stage, 0.0) + seconds
        if engine:
            engine_in[stage] = engine_in.get(stage, 0.0) + seconds
    per_query = 1e3 / queries
    busy = sum(by_stage.values())
    return {
        "stages_ms": {s: t * per_query for s, t in by_stage.items()},
        "sort_engine_in_ms": {s: t * per_query for s, t in engine_in.items()},
        "sort_engine_ms": sum(engine_in.values()) * per_query,
        "busy_ms": busy * per_query,
        "unattributed_share": (
            100.0 * by_stage.get(UNATTRIBUTED, 0.0) / busy if busy else 0.0
        ),
    }


def split(obs: dict):
    """The split of the first device's busy time, or ``None`` where there
    is no trace, the trace has no device plane, or the program has no
    stage table (a commit from before the stage names). Nothing is asked
    of the program before the trace is known to hold a device."""
    if _KEPT not in obs:
        obs[_KEPT] = _split(obs)
    return obs[_KEPT]


def _split(obs: dict):
    trace = obs.get("trace")
    if not trace or not trace.get("devices") or not obs.get("queries"):
        return None
    try:
        from cylon_tpu.obs import stages
    except ImportError:
        return None
    table = stages.device_stage_table()
    first = trace["devices"][sorted(trace["devices"])[0]]
    out = split_ops(
        first["ops"], stage_index(table, stages), obs["queries"]
    )
    print(json.dumps({
        "info": "stage_split", **out,
        "table": {
            "programs": table["programs"], "rows": len(table["rows"]),
            "stale": table["stale"], "seconds": table["seconds"],
        },
    }))
    return out


def stage_ms(obs: dict, stage: str):
    """One stage's milliseconds a query; ``None`` where it did not run."""
    found = split(obs)
    return None if found is None else found["stages_ms"].get(stage)
