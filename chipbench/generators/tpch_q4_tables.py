"""Generator ``tpch_q4_tables``: the six columns of ORDERS and LINEITEM
that TPC-H's Q4 reads.

Populated by the rules of the specification's clause 4.2.3 (dbgen), from
the seed alone and in bulk; what ``tpch_q3_tables`` already says of the two
tables (the sparse order key, the 1 to 7 lines an order, the block a draw)
is imported from it. ORDERS: the key sparse as dbgen's (the first 8 of
every 32 integers), the order date uniform over [STARTDATE, ENDDATE - 151
days], the priority one of five words with equal chance. LINEITEM: 1 to 7
lines an order with equal chance, each with its order's key, the commit
date 30 to 90 days after the ORDER's date, the receipt date 1 to 30 days
after a ship date that is 1 to 121 days after the order's (so a line is
late, ``l_commitdate < l_receiptdate``, with a chance of 63.2%, and an
order of 1-7 lines has a late one with 91.7%). Keys are int32, dates
``datetime64[D]``, the priority a numpy string. The configuration's
``assumed`` lists what the specification leaves open.
"""
import numpy as np

from chipbench.generators.tpch_q3_tables import BLOCK, LINES_MEAN

PRIORITIES = np.array(
    ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
)


def make(config: dict, seed: int, rows: int | None = None) -> dict:
    """``{"orders": {...}, "lineitem": {...}}``; ``rows`` (the rehearsal's
    tiny size) is about that many lineitem rows."""
    orders = (
        int(config["rows"]["orders"]) if rows is None
        else max(8, int(rows) // LINES_MEAN)
    )
    first, last = (np.datetime64(d) for d in config["orderdate"])
    days = int((last - first).astype(int)) + 1
    rng = np.random.default_rng(seed)
    # the draws, in this order (part of the configuration's ``assumed``)
    orderday = rng.integers(0, days, orders, dtype=np.int32)
    priority = rng.integers(0, len(PRIORITIES), orders, dtype=np.int8)
    lines = rng.integers(1, 8, orders, dtype=np.int8)

    i = np.arange(orders, dtype=np.int32)
    orderkey = (i >> 3) * 32 + (i & 7) + 1
    n = int(lines.sum(dtype=np.int64))
    l_orderday = np.repeat(orderday, lines)
    commitdate = np.empty(n, "datetime64[D]")
    receiptdate = np.empty(n, "datetime64[D]")
    for lo in range(0, n, BLOCK):
        m = min(BLOCK, n - lo)
        at = slice(lo, lo + m)
        commit_after = rng.integers(30, 91, m, dtype=np.int32)
        ship_after = rng.integers(1, 122, m, dtype=np.int32)
        receipt_after = rng.integers(1, 31, m, dtype=np.int32)
        commitdate[at] = first + (l_orderday[at] + commit_after).astype(
            "timedelta64[D]"
        )
        receiptdate[at] = first + (
            l_orderday[at] + ship_after + receipt_after
        ).astype("timedelta64[D]")
    return {
        "orders": {
            "o_orderkey": orderkey,
            "o_orderdate": first + orderday.astype("timedelta64[D]"),
            "o_orderpriority": PRIORITIES[priority],
        },
        "lineitem": {
            "l_orderkey": np.repeat(orderkey, lines),
            "l_commitdate": commitdate,
            "l_receiptdate": receiptdate,
        },
    }
