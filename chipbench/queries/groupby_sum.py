"""Query ``groupby_sum``: ``table.distributed_groupby(by, {value: "sum"})``.

The call, its plain reference, the comparison that decides ``correct`` and
the least bytes the query must move. On the upstream suite's keys (uniform
over as many values as rows) nearly every key is its own group, so the
engine's sort-and-segment path does all the work and its dense path must
not be taken. The reference reads only the seeded arrays.
"""
import numpy as np

from chipbench.checks import Number, rel_gap

#: limit on a group sum's worst relative gap, by the precision the
#: configuration states: a group adds a handful of float64 values (~1e-15
#: on a TPU); float32 in their place are off by up to 2**-24 = 6e-8.
#: PERF.md section 2 has the readings the limit was set from.
VALUE_LIMIT = {"float64": 1e-11, "float32": 1e-5}


def build(tables: dict, params: dict):
    table, by, value = tables[params["table"]], params["by"], params["value"]

    def call():
        return table.distributed_groupby(by, {value: "sum"})

    return call


def input_rows(data: dict, params: dict) -> int:
    return len(data[params["table"]][params["by"]])


def least_bytes(data: dict, params: dict, out_rows: int) -> int:
    """The key and the value read once, a key and a sum written a group."""
    cols = data[params["table"]]
    by, value = cols[params["by"]], cols[params["value"]]
    return by.nbytes + value.nbytes + out_rows * (
        by.dtype.itemsize + value.dtype.itemsize
    )


def reference(data: dict, params: dict) -> dict:
    """The distinct keys in order and each one's float64 sum (bincount)."""
    cols = data[params["table"]]
    by, value = params["by"], params["value"]
    keys, codes = np.unique(cols[by], return_inverse=True)
    sums = np.bincount(
        codes.reshape(-1), weights=cols[value].astype(np.float64),
        minlength=len(keys),
    )
    return {
        "by": by, "sum": f"{value}_sum", "columns": sorted([by, f"{value}_sum"]),
        "rows": len(keys), "keys": keys, "sums": sums,
    }


def compare(table, ref: dict, config: dict) -> list:
    limit = VALUE_LIMIT[config["guarantees"]["value_precision"]]
    n = int(table.row_count)
    got = table.to_pydict()
    numbers = [
        Number("groupby.rows_gap", abs(n - ref["rows"]), 0),
        Number("groupby.columns_wrong", int(sorted(got) != ref["columns"]), 0),
    ]
    if numbers[0].value or numbers[1].value:
        return numbers
    # the groups as emitted: every distinct key once, in canonical order
    numbers.append(Number(
        "groupby.keys_wrong", int((got[ref["by"]] != ref["keys"]).sum()), 0
    ))
    numbers.append(Number(
        f"groupby.{ref['sum']}_relgap", rel_gap(got[ref["sum"]], ref["sums"]),
        limit,
    ))
    return numbers
