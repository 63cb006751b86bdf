"""Generator ``tpch_lineitem``: the Q1/Q6 columns of TPC-H's LINEITEM.

Populated by the rules of the specification's clause 4.2.3 (dbgen), from
the seed alone and in bulk: quantity a uniform integer 1-50, discount 0.00
to 0.10 and tax 0.00 to 0.08 in steps of 0.01, the extended price the
quantity times the part's retail price (a function of a uniform part key),
the ship date 1-121 days after a uniform order date, the receipt date 1-30
days after that; the return flag "R" or "A" with equal chance where the
row was received by 1995-06-17 and "N" otherwise, the line status "O"
where it shipped after that day and "F" otherwise. The decimal columns are
float64, as a dataframe loader reads decimal(15,2); the two flags are
one-character strings; the date is ``datetime64[D]``. The configuration's
``assumed`` lists what the specification leaves open: each row draws its
own order date, the generator and the order of the draws.

The rows are made a block at a time into columns allocated once: a fresh
machine pays for every page it touches for the first time, and the draws
and intermediates of one block are reused by the next.
"""
import numpy as np

CURRENT_DATE = np.datetime64("1995-06-17")
#: rows drawn at a time (the block's eight draws follow each other in the
#: generator's stream, then the next block's)
BLOCK = 1 << 22
DTYPES = {"float64": np.float64, "str": "<U1", "datetime64[D]": "datetime64[D]"}


def make(config: dict, seed: int, rows: int | None = None) -> dict:
    """``{"lineitem": {column: array}}`` for ``config``; ``rows`` overrides
    the configuration's (the CPU rehearsal's tiny size)."""
    rows = int(config["rows"] if rows is None else rows)
    first, last = (np.datetime64(d) for d in config["orderdate"])
    days = int((last - first).astype(int)) + 1
    parts = int(config["partkeys"])
    flags, statuses = np.array(["A", "R", "N"]), np.array(["F", "O"])
    rng = np.random.default_rng(seed)
    out = {
        name: np.empty(rows, DTYPES[kind])
        for name, kind in config["tables"]["lineitem"].items()
    }
    for lo in range(0, rows, BLOCK):
        n = min(BLOCK, rows - lo)
        at = slice(lo, lo + n)
        # the draws, in this order (part of the configuration's ``assumed``)
        quantity = rng.integers(1, 51, n, dtype=np.int32)
        discount = rng.integers(0, 11, n, dtype=np.int32)
        tax = rng.integers(0, 9, n, dtype=np.int32)
        partkey = rng.integers(1, parts + 1, n, dtype=np.int32)
        orderday = rng.integers(0, days, n, dtype=np.int32)
        ship_after = rng.integers(1, 122, n, dtype=np.int32)
        receipt_after = rng.integers(1, 31, n, dtype=np.int32)
        returned = rng.integers(0, 2, n, dtype=np.int8)

        # clause 4.2.3: p_retailprice in cents, so the product is exact
        retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
        shipdate = first + (orderday + ship_after).astype("timedelta64[D]")
        receiptdate = shipdate + receipt_after.astype("timedelta64[D]")
        out["l_quantity"][at] = quantity
        out["l_extendedprice"][at] = (quantity * retail) / 100.0
        out["l_discount"][at] = discount / 100.0
        out["l_tax"][at] = tax / 100.0
        out["l_returnflag"][at] = flags[
            np.where(receiptdate <= CURRENT_DATE, returned, 2)
        ]
        out["l_linestatus"][at] = statuses[
            (shipdate > CURRENT_DATE).astype(np.int8)
        ]
        out["l_shipdate"][at] = shipdate
    return {"lineitem": out}
