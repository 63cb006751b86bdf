"""``shuffle_slot_fill``: the share of the row slots that the shuffles'
collective rounds shipped that held a row: the program's rollup counters
``shuffle.coll_rows`` over ``shuffle.coll_slots`` (``obs/trace.bump`` in
``_shuffle_many``: K x world^2 x ``bucket_cap`` slots, and the rows of the
chosen count matrix that ride them), in percent. An equal-chunk all_to_all
ships every bucket at the hottest one's rounds, so skew reads as empty
slots. Read over the process: every call from the first warm-up on is the
cell's one query. ``None`` where the program has no such counters (a
commit from before them)."""
from cylon_tpu.utils import tracing


def read(obs: dict):
    rollup = tracing.snapshot()
    slots = rollup.get("shuffle.coll_slots", {}).get("rows", 0)
    if not slots:
        return None
    return 100.0 * rollup.get("shuffle.coll_rows", {}).get("rows", 0) / slots
