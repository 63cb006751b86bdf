"""Query ``sort``: ``table.distributed_sort(by)``.

The call, its plain reference, the comparison that decides ``correct`` and
the least bytes the query must move. ``chip_smoke.py`` checked its sort
against the values the device held; here the reference reads only the
seeded arrays, so values are compared to the precision's limit in one
canonical order (by key, then value) and not bit for bit.
"""
import numpy as np

from chipbench.checks import Number, rel_gap

#: limit on a value's worst relative gap, by the precision the
#: configuration states: a sort only moves values, so the gap is what
#: loading and reading back a float64 costs on a TPU (~1e-15); float32 in
#: its place is off by up to 2**-24 = 6e-8. PERF.md section 2 has the
#: readings the limit was set from.
VALUE_LIMIT = {"float64": 1e-11, "float32": 1e-5}


def build(tables: dict, params: dict):
    table, by = tables[params["table"]], params["by"]

    def call():
        return table.distributed_sort(by)

    return call


def input_rows(data: dict, params: dict) -> int:
    return len(data[params["table"]][params["by"]])


def least_bytes(data: dict, params: dict, out_rows: int) -> int:
    """Every column read once and written once."""
    cols = data[params["table"]].values()
    return sum(a.nbytes for a in cols) + out_rows * sum(
        a.dtype.itemsize for a in cols
    )


def reference(data: dict, params: dict) -> dict:
    cols, by = data[params["table"]], params["by"]
    others = [c for c in cols if c != by]
    order = np.lexsort([cols[c] for c in reversed(others)] + [cols[by]])
    return {
        "by": by, "others": others, "columns": sorted(cols),
        "rows": len(order),
        "sorted": {c: a[order] for c, a in cols.items()},
    }


def compare(table, ref: dict, config: dict) -> list:
    limit = VALUE_LIMIT[config["guarantees"]["value_precision"]]
    n = int(table.row_count)
    got = table.to_pydict()
    numbers = [
        Number("sort.rows_gap", abs(n - ref["rows"]), 0),
        Number("sort.columns_wrong", int(sorted(got) != ref["columns"]), 0),
    ]
    if numbers[0].value or numbers[1].value:
        return numbers
    by = ref["by"]
    # the output as emitted must be ordered by the key and hold exactly the
    # reference's keys: one comparison covers both
    numbers.append(Number(
        "sort.keys_wrong", int((got[by] != ref["sorted"][by]).sum()), 0
    ))
    # rows with equal keys may come in any order: compare in the canonical
    # order (key, then the other columns), which keeps every pairing
    order = np.lexsort([got[c] for c in reversed(ref["others"])] + [got[by]])
    for c in ref["others"]:
        numbers.append(Number(
            f"sort.{c}_relgap", rel_gap(got[c][order], ref["sorted"][c]), limit
        ))
    return numbers
