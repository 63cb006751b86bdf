"""``join_emit_handthrough_share``: of the rows the process's speculative
INNER/LEFT join emits wrote, the share whose shard handed its left columns
through (every live left row emitted exactly once: no run expansion, no
left gather), in percent. The program's rollup counters
``join.emit.handthrough`` over it plus ``join.emit.gathered`` (``rows=``
the live output rows of the shards that took each form, bumped in
``Table.join`` from the flag that rides the totals' fetch). Both sum over
the process (every call from the first warm-up on is the cell's one
query). 100 where a table is joined to another by that one's unique key
and every row finds at most (LEFT) or exactly (INNER) one partner, 0 where
a left row repeats. ``None`` where the program has neither counter (a
commit from before the emit's two forms) or no such emit ran."""
from cylon_tpu.utils import tracing


def read(obs: dict):
    rollup = tracing.snapshot()
    handed, gathered = (
        rollup.get("join.emit." + form, {}).get("rows", 0)
        for form in ("handthrough", "gathered")
    )
    if not handed + gathered:
        return None
    return 100.0 * handed / (handed + gathered)
