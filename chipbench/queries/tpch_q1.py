"""Query ``tpch_q1``: TPC-H Q1, the pricing summary report, through the
planner (``Table.lazy()``: filter, with_columns, groupby, sort).

    select l_returnflag, l_linestatus, sum(l_quantity),
           sum(l_extendedprice), sum(l_extendedprice*(1-l_discount)),
           sum(l_extendedprice*(1-l_discount)*(1+l_tax)), avg(l_quantity),
           avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day
    group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus

The call, its plain reference, the comparison that decides ``correct`` and
the least bytes the query must move. The reference reads only the seeded
arrays (a boolean mask, a group code, ``np.bincount`` with float64
weights) and imports nothing of the program.
"""
import numpy as np

from chipbench.checks import Number, rel_gap

from cylon_tpu.plan import col, lit
from cylon_tpu.plan.lazy import LazyFrame

if not hasattr(LazyFrame, "with_columns"):
    # a commit from before the computed projection cannot say the query:
    # stop before any set-up
    raise SystemExit(
        "chipbench: tpch_q1 needs LazyFrame.with_columns, which this "
        "checkout lacks; nothing was run"
    )

KEYS = ("l_returnflag", "l_linestatus")
#: result column -> what the reference calls it
SUMS = {
    "l_quantity_sum": "sum_qty", "l_extendedprice_sum": "sum_base_price",
    "disc_price_sum": "sum_disc_price", "charge_sum": "sum_charge",
}
AVGS = {
    "l_quantity_mean": "avg_qty", "l_extendedprice_mean": "avg_price",
    "l_discount_mean": "avg_disc",
}
COUNT = "l_quantity_count"
#: limit on the worst relative gap over groups of the four sums, by the
#: precision the configuration states. A group adds up to 29 million
#: float64 products in the chip's two-float arithmetic: sound runs read at
#: most 3.8e-14 over eleven seeds, the float32 control at least 5.7e-10,
#: so 5e-12 stands two orders from either (PERF.md section 2).
VALUE_LIMIT = {"float64": 5e-12, "float32": 1e-4}
#: limit on the worst relative gap over groups of the three averages.
#: ``avg_disc`` is the one aggregate that reads ``l_discount`` alone, so
#: without it a mean of the wrong column or a float32 accumulation of the
#: discount would pass. Sound runs read at most 4.0e-13 (the discount's
#: mean over the 0.39M-row N/F group), the float32 control at least
#: 1.36e-9, three and a half orders apart: 2e-11 stands 50 times above the
#: one and 68 times below the other, the most either way that gap allows
#: (PERF.md section 2)
AVGS_LIMIT = {"float64": 2e-11, "float32": 1e-4}
#: rows the reference adds in one running sum (2**16)
BLOCK_BITS = 16


def build(tables: dict, params: dict):
    table = tables[params["table"]]
    cutoff = np.datetime64(params["shipdate_max"])
    disc_price = col("l_extendedprice") * (1 - col("l_discount"))
    query = (
        table.lazy()
        .filter(col("l_shipdate") <= lit(cutoff))
        .with_columns({
            "disc_price": disc_price,
            "charge": disc_price * (1 + col("l_tax")),
        })
        .groupby(list(KEYS), {
            "l_quantity": ["sum", "mean", "count"],
            "l_extendedprice": ["sum", "mean"],
            "disc_price": "sum", "charge": "sum", "l_discount": "mean",
        })
        .sort(list(KEYS))
    )
    return query.collect


def input_rows(data: dict, params: dict) -> int:
    return len(data[params["table"]]["l_shipdate"])


def least_bytes(data: dict, params: dict, out_rows: int) -> int:
    """The seven columns read once (at the widths the device holds them:
    8 bytes a decimal and a date, 4 a flag's code, which is what the host
    arrays take too) and the ten result columns written once."""
    cols = data[params["table"]].values()
    return sum(a.nbytes for a in cols) + out_rows * 8 * (
        len(KEYS) + len(SUMS) + len(AVGS) + 1
    )


def reference(data: dict, params: dict) -> dict:
    """Q1 in plain numpy over the seven arrays."""
    li = data[params["table"]]
    keep = li["l_shipdate"] <= np.datetime64(params["shipdate_max"])
    # a one-character string is its code point: the pair as one group code
    flag = li["l_returnflag"].view(np.uint32)[keep].astype(np.int64)
    status = li["l_linestatus"].view(np.uint32)[keep].astype(np.int64)
    width = int(status.max()) + 1 if len(status) else 1
    code = flag * width + status
    count = np.bincount(code)
    groups = np.flatnonzero(count)  # ascending: (flag, status) order
    # a running float64 sum of 29 million values drifts by some 1e-13 of
    # itself, as much as the result it judges: add the rows a block at a
    # time (one bincount over block and group), then the blocks' sums
    slots = len(count)
    blocked = (np.arange(len(code)) >> BLOCK_BITS) * slots + code
    blocks = (len(code) >> BLOCK_BITS) + 1

    def total(values):
        partial = np.bincount(blocked, weights=values, minlength=blocks * slots)
        return partial.reshape(blocks, slots).sum(axis=0)[groups]

    qty = li["l_quantity"][keep]
    price = li["l_extendedprice"][keep]
    disc = li["l_discount"][keep]
    disc_price = price * (1 - disc)
    charge = disc_price * (1 + li["l_tax"][keep])
    n = count[groups]
    return {
        "rows": len(groups),
        "columns": sorted([*KEYS, *SUMS, *AVGS, COUNT]),
        "l_returnflag": np.array([chr(c) for c in groups // width], object),
        "l_linestatus": np.array([chr(c) for c in groups % width], object),
        "count_order": n,
        "sum_qty": total(qty), "sum_base_price": total(price),
        "sum_disc_price": total(disc_price), "sum_charge": total(charge),
        "avg_qty": total(qty) / n, "avg_price": total(price) / n,
        "avg_disc": total(disc) / n,
    }


def compare(table, ref: dict, config: dict) -> list:
    precision = config["guarantees"]["value_precision"]
    n = int(table.row_count)
    got = table.to_pydict()
    numbers = [
        Number("q1.rows_gap", abs(n - ref["rows"]), 0),
        Number("q1.columns_wrong", int(sorted(got) != ref["columns"]), 0),
    ]
    if numbers[0].value or numbers[1].value:
        return numbers
    # the groups as emitted: position for position, so also in key order
    numbers.append(Number(
        "q1.groups_wrong",
        int(sum((got[k] != ref[k]).sum() for k in KEYS)), 0,
    ))
    numbers.append(Number(
        "q1.count_order_wrong",
        int((got[COUNT] != ref["count_order"]).sum()), 0,
    ))
    for name, columns, limit in (("sums", SUMS, VALUE_LIMIT), ("avgs", AVGS, AVGS_LIMIT)):
        numbers.append(Number(
            f"q1.{name}_relgap",
            max(rel_gap(got[c], ref[r]) for c, r in columns.items()),
            limit[precision],
        ))
    return numbers
