"""``groupby_partial_slot_fill``: how full the partial tables travel: the
fullest shard's partial rows over the slots a shard's partial table is
shipped in (``round_cap`` of that count), from the program's rollup
counters ``groupby.precombine.fullest`` over ``groupby.precombine.slots``
(``obs/trace.bump`` in ``Table._groupby_exchange``, both a query), in
percent. A capacity that follows the count reads over 50 by construction;
partials shipped at the input's capacity would read the groups over the
rows. ``None`` where the program has no such counters (a commit from
before them)."""
from cylon_tpu.utils import tracing


def read(obs: dict):
    rollup = tracing.snapshot()
    slots = rollup.get("groupby.precombine.slots", {}).get("rows", 0)
    if not slots:
        return None
    fullest = rollup.get("groupby.precombine.fullest", {}).get("rows", 0)
    return 100.0 * fullest / slots
