"""``semi_payload_rows``: rows a semi or anti join gathered or compacted, a
join: the program's rollup counter ``join.semi.payload_rows`` (``rows=``
the left side's kept rows where the join compacted them, 0 where the
planner's ``semi_as_mask`` handed the hit mask to the aggregate above),
over its bumps, one a join. Read over the process: every call from the
first warm-up on is the cell's one query, so the mean is the window's.
``None`` where the program has no such counter (a commit from before the
operator, a query without one)."""
from cylon_tpu.utils import tracing


def read(obs: dict):
    counter = tracing.snapshot().get("join.semi.payload_rows")
    if not counter or not counter.get("count"):
        return None
    return counter.get("rows", 0) / counter["count"]
