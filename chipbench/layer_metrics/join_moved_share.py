"""``join_moved_share``: of the rows the process's distributed joins took
in, the share that crossed the mesh, in percent. The program's rollup
counters: ``join.replicate.rows`` (the rows a replicate route's gather
brought to chips that did not hold them) plus ``shuffle.coll_rows`` (the
rows the collective rounds of every shuffle carried, of which one part in
``chips`` stays on the chip it was on: a hash spreads them evenly) over
the ``rows`` of ``join.route.replicate`` and ``join.route.shuffle`` (both
sides' rows of every distributed join, by the route it took). All sum
over the process (every call from the first warm-up on is the cell's one
query). About 0.3 where a side a thousandth the size is replicated over
four chips, about 75 where both sides are shuffled. ``None`` where the
program has no such counter (a commit from before the routes)."""
from cylon_tpu.utils import tracing


def read(obs: dict):
    rollup = tracing.snapshot()
    rows_in = sum(
        rollup.get("join.route." + route, {}).get("rows", 0)
        for route in ("replicate", "shuffle")
    )
    if not rows_in:
        return None
    chips = max(1, len(obs["trace"]["devices"])) if obs.get("trace") else 1
    moved = (
        rollup.get("join.replicate.rows", {}).get("rows", 0)
        + rollup.get("shuffle.coll_rows", {}).get("rows", 0)
        * (chips - 1) / chips
    )
    return 100.0 * moved / rows_in
