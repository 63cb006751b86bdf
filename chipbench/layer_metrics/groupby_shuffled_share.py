"""``groupby_shuffled_share``: of the rows the process's group-bys took in
on their way to an exchange of partials, the share that crossed the mesh:
the program's rollup counters ``shuffle.coll_rows`` (the rows the
collective rounds of every shuffle carried) over
``groupby.precombine.rows_in`` (``obs/trace.bump`` in
``Table._groupby_exchange``, ``rows=`` the input rows of a group-by that
shipped a partial row a group a shard), in percent. Both sum over the
process (every call from the first warm-up on is the cell's one query).
About 100 times the chips over the rows a group where every chip holds
every group; 100 where the partial aggregate cuts nothing. ``None`` where
the program has no such counter (a commit from before it)."""
from cylon_tpu.utils import tracing


def read(obs: dict):
    rollup = tracing.snapshot()
    rows_in = rollup.get("groupby.precombine.rows_in", {}).get("rows", 0)
    if not rows_in:
        return None
    return 100.0 * rollup.get("shuffle.coll_rows", {}).get("rows", 0) / rows_in
