"""Query ``tpch_q6``: TPC-H Q6, the forecasting revenue change query,
through the planner (``Table.lazy()``: with_columns, filter, agg).

    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= date ':1' and l_shipdate < date ':1' + interval '1' year
      and l_discount between :2 - 0.01 and :2 + 0.01 and l_quantity < :3

An aggregate without keys: one row, one column. The call, its plain
reference, the comparison that decides ``correct`` and the least bytes the
query must move. The reference reads only the seeded arrays (a boolean
mask, ``np.sum`` of the float64 products that pass) and imports nothing
of the program.

**The discount's bounds.** The specification's ``DISCOUNT - 0.01`` and
``+ 0.01`` are decimal arithmetic. In float64 ``0.06 + 0.01`` is
0.06999999999999999 and drops every row whose discount is 0.07, a third
of the rows that should pass. The bounds are taken in hundredths, as the
generator writes a discount (``k / 100.0``): the doubles nearest 0.05 and
0.07.
"""
import numpy as np

from chipbench.checks import Number, rel_gap

from cylon_tpu.plan import col, lit
from cylon_tpu.plan.lazy import LazyFrame

if not hasattr(LazyFrame, "agg"):
    # a commit from before the aggregate without keys cannot say the
    # query: stop before any set-up
    raise SystemExit(
        "chipbench: tpch_q6 needs LazyFrame.agg, which this checkout "
        "lacks; nothing was run"
    )

#: the four columns the query reads
READS = ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")
RESULT = "revenue_sum"
#: limit on the relative gap of the revenue, by the precision the
#: configuration states. The revenue adds about 1.14 million float64
#: products (1.9% of the rows pass) in the chip's two-float arithmetic
#: and the reference adds the same products pairwise (``np.sum``): sound
#: runs on the chip read at most 2.42e-14 over eleven seeds, a few steps
#: of the 2**-47 a two-float addition is good to. The float32 control
#: reads at least 0.3877 over four seeds: a float32 0.07 lies above the
#: double 0.07 and fails the upper bound, so every row with that discount
#: drops (PERF.md section 2, PR 39). Between the two readings lie
#: thirteen orders; the limit is Q1's for its sums (``tpch_q1.VALUE_LIMIT``,
#: the same arithmetic over the same column): 5e-12 stands 200 times
#: above the sound runs' largest, and also under what rounding the
#: product alone through float32 would give (2**-24 a value, 1e-9 and
#: more in a sum of a million products of one sign; arithmetic, not a
#: reading), which a control that loses rows cannot show.
VALUE_LIMIT = {"float64": 5e-12, "float32": 1e-4}


def bounds(params: dict) -> dict:
    """The predicate's five literals: the year of ship dates, the
    discount's bounds as the doubles nearest the decimal values, the
    quantity."""
    first = np.datetime64(params["date"], "D")
    month = first.astype("datetime64[M]")
    hundredths = round(params["discount"] * 100)
    return {
        "date_lo": first,
        # the same day of the month, twelve months on
        "date_hi": (month + 12).astype("datetime64[D]") + (first - month),
        "discount_lo": (hundredths - 1) / 100.0,
        "discount_hi": (hundredths + 1) / 100.0,
        "quantity": params["quantity"],
    }


def build(tables: dict, params: dict):
    table = tables[params["table"]]
    b = bounds(params)
    query = (
        table.lazy()
        .with_columns({"revenue": col("l_extendedprice") * col("l_discount")})
        .filter(
            (col("l_shipdate") >= lit(b["date_lo"]))
            & (col("l_shipdate") < lit(b["date_hi"]))
            & (col("l_discount") >= lit(b["discount_lo"]))
            & (col("l_discount") <= lit(b["discount_hi"]))
            & (col("l_quantity") < lit(b["quantity"]))
        )
        .agg({"revenue": "sum"})
    )
    return query.collect


def input_rows(data: dict, params: dict) -> int:
    return len(data[params["table"]]["l_shipdate"])


def least_bytes(data: dict, params: dict, out_rows: int) -> int:
    """The four columns the query reads, once, at the widths the device
    holds them (8 bytes a decimal and a date), and the one float64 out."""
    return 8 * len(READS) * input_rows(data, params) + 8 * out_rows


def passes(li: dict, params: dict) -> np.ndarray:
    """The predicate over the seeded arrays, a boolean a row."""
    b = bounds(params)
    return (
        (li["l_shipdate"] >= b["date_lo"]) & (li["l_shipdate"] < b["date_hi"])
        & (li["l_discount"] >= b["discount_lo"])
        & (li["l_discount"] <= b["discount_hi"])
        & (li["l_quantity"] < b["quantity"])
    )


def reference(data: dict, params: dict) -> dict:
    """Q6 in plain numpy over the four arrays."""
    li = data[params["table"]]
    keep = passes(li, params)
    # np.sum adds pairwise: a million addends of one sign drift by 1e-16
    # times the logarithm of their count, not by its square root
    revenue = np.sum(li["l_extendedprice"][keep] * li["l_discount"][keep])
    return {
        "rows": 1, "columns": [RESULT], "passing": int(keep.sum()),
        "revenue": np.array([revenue], np.float64),
    }


def compare(table, ref: dict, config: dict) -> list:
    precision = config["guarantees"]["value_precision"]
    n = int(table.row_count)
    got = table.to_pydict()
    numbers = [
        Number("q6.rows_gap", abs(n - ref["rows"]), 0),
        Number("q6.columns_wrong", int(sorted(got) != ref["columns"]), 0),
    ]
    if numbers[0].value or numbers[1].value:
        return numbers
    revenue = np.asarray(got[RESULT], np.float64)
    numbers.append(Number(
        "q6.revenue_null", int(np.isnan(revenue).sum()), 0,
    ))
    numbers.append(Number(
        "q6.revenue_relgap", rel_gap(revenue, ref["revenue"]),
        VALUE_LIMIT[precision],
    ))
    return numbers
