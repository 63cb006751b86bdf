"""``join_emit_fill``: the share of the slots that the joins' emits wrote
that held a row: the program's rollup counters ``join.emit_rows`` over
``join.emit_slots`` (``obs/trace.bump`` in ``Table.join``, from numbers
the host holds anyway). Read over the process: every call from the first
warm-up on is the cell's one query, so the ratio is the window's. ``None``
where the program has no such counters (a commit from before them)."""
from cylon_tpu.utils import tracing


def read(obs: dict):
    rollup = tracing.snapshot()
    slots = rollup.get("join.emit_slots", {}).get("rows", 0)
    if not slots:
        return None
    return 100.0 * rollup.get("join.emit_rows", {}).get("rows", 0) / slots
