"""``groupby_partial_share``: of the rows the process's group-bys took in,
the share that was aggregated where it lay: the program's rollup counter
``groupby.partial.rows`` (``obs/trace.bump`` in ``Table._groupby_dense``,
``rows=`` the input rows of a group-by whose shards' partial states were
combined in place) over those plus ``shuffle.coll_rows`` (the rows the
collective rounds of every shuffle carried), in percent. Both sum over the
process (every call from the first warm-up on is the cell's one query);
100 when no row crossed the mesh. ``None`` where the program has no such
counter (a commit from before it, which shuffles every row)."""
from cylon_tpu.utils import tracing


def read(obs: dict):
    rollup = tracing.snapshot()
    if "groupby.partial.rows" not in rollup:
        return None
    partial = rollup["groupby.partial.rows"].get("rows", 0)
    moved = rollup.get("shuffle.coll_rows", {}).get("rows", 0)
    if partial + moved <= 0:
        return None
    return 100.0 * partial / (partial + moved)
