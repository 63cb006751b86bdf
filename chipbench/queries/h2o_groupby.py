"""Query ``h2o_groupby``: a question of the h2oai/db-benchmark group-by
task that sums columns by one integer id,
``table.distributed_groupby(by, {column: "sum", ...})``; the cell's is
question 5, ``sum v1:v3 by id6``.

The call, its plain reference, the comparison that decides ``correct`` and
the least bytes the query must move. The reference is this module's own
copy of ``tests/h2o_groupby_reference.py``'s arithmetic, reads only the
seeded arrays and takes nothing the program made (the module's one look
into ``ops/`` asks whether the checkout has the partial state that the
once-a-run question below is about). The ids are the integers
1..N/K, so the groups, their counts and their sums all come from
``np.bincount`` over the id itself and no ``np.unique`` of half a billion
rows is taken. A group adds about K = 100 values under 100, so a running
float64 sum is within a few 1e-15 of itself and no blocked sum is needed
(``tpch_q1``'s groups add 29 million values and do need one); an integer
sum rides float64 weights and is exact far below 2**53.

Question 5 works three sums, which says nothing of a state of more than
one number. So once a run and outside every timing the comparison also
asks the same table ``{first column: [sum, count], last column: mean}`` by
the same id (a count beside a sum, a mean as its sum and its count: the
partial state of ROADMAP M13) and holds it to the reference the same way.
"""
import numpy as np

from cylon_tpu.ops import groupby as _program_groupby

from chipbench.checks import Number, rel_gap
from chipbench.queries.inner_join import shards_wrong

#: limit on a group sum's (and the once-a-run mean's) worst relative gap
#: over the groups, by the precision the configuration states: about a
#: hundred float64 addends under 100 a group read a few 1e-15 on the chip,
#: float32 values in their place from about 1e-8. PERF.md section 2 has the
#: readings the limit was set from.
VALUE_LIMIT = {"float64": 1e-11, "float32": 1e-5}

#: the once-a-run question needs the partial state it asks about: a commit
#: from before it (the parent of PR 45) ships every input row for a count
#: beside a sum, half a billion of them here, which is another query and
#: minutes of one; there the check is left out and says so
HAS_PARTIAL_STATE = hasattr(_program_groupby, "PARTIAL_OPS")

#: what ``build`` was last given, for the once-a-run check and the
#: readers: the harness hands ``compare`` a result and the reference, not
#: the tables
_RUN = {}


def build(tables: dict, params: dict):
    _RUN.clear()
    _RUN.update(tables=tables, params=params)
    table, by, agg = tables[params["table"]], params["by"], params["agg"]

    def call():
        return table.distributed_groupby(by, dict(agg))

    return call


def input_rows(data: dict, params: dict) -> int:
    return len(data[params["table"]][params["by"]])


def device_bytes(array: np.ndarray) -> int:
    """Bytes of a column as the device holds it: these are integers and
    floats, held at the width the host array has."""
    return array.nbytes


def least_bytes(data: dict, params: dict, out_rows: int) -> int:
    """The WHOLE query over the mesh: the key and the summed columns read
    once, the result's rows (the key and a 64-bit sum a column) written
    once. A traced run asks for this before its per-layer readers run:
    what the pre-combine alone must move is kept here for
    ``groupby_partial_hbm_share``."""
    cols = data[params["table"]]
    read = sum(device_bytes(cols[c]) for c in (params["by"], *params["agg"]))
    row = cols[params["by"]].dtype.itemsize + 8 * len(params["agg"])
    _RUN["read_bytes"], _RUN["row_bytes"] = read, row
    return read + out_rows * row


def partial_least_bytes(partial_rows: int):
    """What the pre-combine must move on all shards together: the columns
    read once, the shards' partial rows (the program's own count of them)
    written once. ``None`` before :func:`least_bytes` was asked."""
    if "read_bytes" not in _RUN:
        return None
    return _RUN["read_bytes"] + int(partial_rows) * _RUN["row_bytes"]


def reference(data: dict, params: dict) -> dict:
    """The groups in id order with their counts and sums (computed once a
    run's data: the bincounts over every row take the longest of a run's
    check)."""
    if _RUN.get("ref_of") is not data:
        _RUN["ref"], _RUN["ref_of"] = _reference(data, params), data
    return _RUN["ref"]


def _reference(data: dict, params: dict) -> dict:
    cols = data[params["table"]]
    by = cols[params["by"]]
    space = int(by.max()) + 1 if len(by) else 1
    count = np.bincount(by, minlength=space)
    groups = np.flatnonzero(count)  # ascending: id order
    sums = {}
    for c in params["agg"]:
        total = np.bincount(by, weights=cols[c], minlength=space)[groups]
        integer = np.issubdtype(cols[c].dtype, np.integer)
        sums[c] = total.astype(np.int64) if integer else total
    return {
        "by": params["by"], "rows": len(groups),
        "keys": groups.astype(by.dtype), "count": count[groups],
        "sums": sums,
        "columns": sorted([params["by"], *(f"{c}_sum" for c in sums)]),
    }


def _in_key_order(table) -> dict:
    """The result gathered from its shards (each holds its own groups, in
    its own id order) and put in id order."""
    got = table.to_pydict()
    order = np.argsort(got[_RUN["params"]["by"]], kind="stable")
    return {c: np.asarray(a)[order] for c, a in got.items()}


def _sum_numbers(prefix: str, got: dict, ref: dict, limit: float) -> list:
    """Every aggregate of ``got`` (in id order, as many rows as the
    reference has) beside the reference's: integers exact, floats by
    their worst relative gap over the groups."""
    numbers = []
    for name, want in ref.items():
        have = got[name]
        if np.issubdtype(want.dtype, np.integer):
            wrong = have.dtype != np.int64 or (have != want).any()
            gap = int(np.abs(have.astype(np.int64) - want).sum()) or int(wrong)
            numbers.append(Number(f"{prefix}{name}_gap", gap, 0))
        else:
            numbers.append(
                Number(f"{prefix}{name}_relgap", rel_gap(have, want), limit)
            )
    return numbers


def state_agg(params: dict) -> dict:
    """The once-a-run question: a count beside a sum on the first summed
    column, a mean of the last."""
    columns = list(params["agg"])
    return {columns[0]: ["sum", "count"], columns[-1]: "mean"}


def _once_a_run(ref: dict, limit: float) -> list:
    """M13's state through the same table and the same call, outside
    every timing, on the tables ``build`` was given."""
    params = _RUN["params"]
    agg = state_agg(params)
    (first, _ops), (last, _op) = agg.items()
    table = _RUN["tables"][params["table"]]
    out = table.distributed_groupby(params["by"], agg)
    numbers = [
        Number("h2o.m13_rows_gap", abs(int(out.row_count) - ref["rows"]), 0)
    ]
    if numbers[0].value:
        return numbers
    got = _in_key_order(out)
    numbers.append(Number(
        "h2o.m13_keys_wrong", int((got[ref["by"]] != ref["keys"]).sum()), 0
    ))
    want = {
        f"{first}_sum": ref["sums"][first], f"{first}_count": ref["count"],
        f"{last}_mean": ref["sums"][last] / ref["count"],
    }
    if sorted(got) != sorted([ref["by"], *want]):
        return numbers + [Number("h2o.m13_columns_wrong", 1, 0)]
    return numbers + _sum_numbers("h2o.m13_", got, want, limit)


def compare(table, ref: dict, config: dict) -> list:
    limit = VALUE_LIMIT[config["guarantees"]["value_precision"]]
    n = int(table.row_count)
    got = _in_key_order(table)
    numbers = [
        Number("h2o.rows_gap", abs(n - ref["rows"]), 0),
        Number("h2o.columns_wrong", int(sorted(got) != ref["columns"]), 0),
    ]
    if numbers[1].value:
        return numbers
    keys = got[ref["by"]]
    # a key on two shards, or twice on one: a group left uncombined
    numbers.append(Number(
        "h2o.groups_split", len(keys) - len(np.unique(keys)), 0
    ))
    if numbers[0].value:
        return numbers  # position for position means nothing below
    # every distinct id once, position for position in id order
    numbers.append(Number(
        "h2o.keys_wrong", int((keys != ref["keys"]).sum()), 0
    ))
    numbers.extend(_sum_numbers(
        "h2o.", got, {f"{c}_sum": s for c, s in ref["sums"].items()}, limit
    ))
    world = table.ctx.world_size
    if world > 1:
        numbers.append(Number(
            "h2o.shards_wrong", shards_wrong(table, world, ref["rows"]), 0
        ))
    if "tables" in _RUN and not _RUN.get("checked_once"):
        _RUN["checked_once"] = True
        if HAS_PARTIAL_STATE:
            numbers.extend(_once_a_run(ref, limit))
        else:
            print("h2o_groupby: this checkout has no partial state for a "
                  "count beside a sum or a mean; the once-a-run check of it "
                  "was left out")
    return numbers
