"""TPC-H Q4, the order priority checking query, in plain numpy: the
reference that ``tests/test_tpch_q4.py`` and (copied)
``chipbench/queries/tpch_q4.py`` hold the program to. It imports nothing
of the program.

    select o_orderpriority, count(*) as order_count from orders
    where o_orderdate >= date 'DATE'
      and o_orderdate < date 'DATE' + interval '3' month
      and exists (select * from lineitem
                  where l_orderkey = o_orderkey
                    and l_commitdate < l_receiptdate)
    group by o_orderpriority order by o_orderpriority

Two masks, ``np.isin`` of the quarter's order keys in the late lines' keys,
``np.bincount`` of the priority codes. Beside the query's answer it gives
the anti twin (NOT EXISTS: the quarter's orders with NO late line, by
priority), the quarter's orders by priority (the two add up to it) and the
order keys the semi join keeps, in row order.
"""
import numpy as np


def quarter(date, months: int = 3):
    """``[date, date + months)`` as two days: the interval is in calendar
    months, so the end keeps the day of the month."""
    d0 = np.datetime64(date, "D")
    month = d0.astype("datetime64[M]")
    day = d0 - month.astype("datetime64[D]")
    return d0, (month + int(months)).astype("datetime64[D]") + day


def by_priority(priority: np.ndarray, keep: np.ndarray):
    """(the priorities that have a kept order, ascending; their counts)."""
    names, codes = np.unique(priority, return_inverse=True)
    count = np.bincount(codes[keep], minlength=len(names))
    live = np.flatnonzero(count)
    return names[live].astype(object), count[live].astype(np.int64)


def q4(data: dict, date="1993-07-01", months: int = 3) -> dict:
    od, li = data["orders"], data["lineitem"]
    d0, d1 = quarter(date, months)
    # the quarter's orders (a few of every hundred), in row order
    rows = np.flatnonzero((od["o_orderdate"] >= d0) & (od["o_orderdate"] < d1))
    keys, priority = od["o_orderkey"][rows], od["o_orderpriority"][rows]
    late = li["l_commitdate"] < li["l_receiptdate"]
    has_late = np.isin(keys, li["l_orderkey"][late])
    out = {}
    for name, keep in (
        ("semi", has_late), ("anti", ~has_late),
        ("quarter", np.ones(len(rows), bool)),
    ):
        out[name] = dict(zip(
            ("o_orderpriority", "order_count"), by_priority(priority, keep)
        ))
    out["semi_keys"], out["anti_keys"] = keys[has_late], keys[~has_late]
    out["late_lines"] = int(late.sum())
    return out
