"""Generator ``tpch_q3_tables``: the ten columns of CUSTOMER, ORDERS and
LINEITEM that TPC-H's Q3 reads.

Populated by the rules of the specification's clause 4.2.3 (dbgen), from
the seed alone and in bulk. CUSTOMER: the key 1..customers, the market
segment one of five words with equal chance. ORDERS: the key sparse as
dbgen's (the first 8 of every 32 integers), the customer a uniform key
that is never a multiple of 3 (a third of the customers have no order),
the order date uniform over [STARTDATE, ENDDATE - 151 days], the ship
priority 0. LINEITEM: 1 to 7 lines an order with equal chance, each with
its order's key, the extended price the quantity (1-50) times the part's
retail price (a function of a uniform part key), the discount 0.00 to 0.10
in steps of 0.01, the ship date 1-121 days after the ORDER's date (which is
what makes Q3's last join selective: only the orders of the 121 days
before the query's date have a line that ships after it). Keys are int32,
decimals float64 (as ``tpch_lineitem`` makes them), dates
``datetime64[D]``, the segment a numpy string. The configuration's
``assumed`` lists what the specification leaves open.
"""
import numpy as np

SEGMENTS = np.array(
    ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
)
#: lineitem rows drawn at a time
BLOCK = 1 << 22
#: orders a lineitem row at scale (1-7 lines with equal chance: 4 a order)
LINES_MEAN = 4
#: orders a customer (clause 4.2.3: 1,500,000 orders and 150,000 customers
#: a unit of scale)
ORDERS_A_CUSTOMER = 10


def sizes(config: dict, rows: int | None) -> tuple:
    """(customers, orders): the configuration's, or for the rehearsal's
    ``rows`` (about that many lineitem rows) in the schema's proportions."""
    if rows is None:
        return int(config["rows"]["customer"]), int(config["rows"]["orders"])
    orders = max(8, int(rows) // LINES_MEAN)
    return max(3, orders // ORDERS_A_CUSTOMER), orders


def make(config: dict, seed: int, rows: int | None = None) -> dict:
    """``{"customer": {...}, "orders": {...}, "lineitem": {...}}``."""
    customers, orders = sizes(config, rows)
    first, last = (np.datetime64(d) for d in config["orderdate"])
    days = int((last - first).astype(int)) + 1
    parts = int(config["partkeys"])
    rng = np.random.default_rng(seed)
    # the draws, in this order (part of the configuration's ``assumed``)
    segment = rng.integers(0, len(SEGMENTS), customers, dtype=np.int8)
    # a customer key that is no multiple of 3: the j-th such key
    j = rng.integers(0, customers - customers // 3, orders, dtype=np.int32)
    orderday = rng.integers(0, days, orders, dtype=np.int32)
    lines = rng.integers(1, 8, orders, dtype=np.int8)

    i = np.arange(orders, dtype=np.int32)
    orderkey = (i >> 3) * 32 + (i & 7) + 1
    orderdate = first + orderday.astype("timedelta64[D]")
    out = {
        "customer": {
            "c_custkey": np.arange(1, customers + 1, dtype=np.int32),
            "c_mktsegment": SEGMENTS[segment],
        },
        "orders": {
            "o_orderkey": orderkey,
            "o_custkey": 3 * (j >> 1) + (j & 1) + 1,
            "o_orderdate": orderdate,
            "o_shippriority": np.zeros(orders, np.int32),
        },
    }
    n = int(lines.sum(dtype=np.int64))
    l_orderkey = np.repeat(orderkey, lines)
    l_orderday = np.repeat(orderday, lines)
    price = np.empty(n, np.float64)
    discount = np.empty(n, np.float64)
    shipdate = np.empty(n, "datetime64[D]")
    for lo in range(0, n, BLOCK):
        m = min(BLOCK, n - lo)
        at = slice(lo, lo + m)
        quantity = rng.integers(1, 51, m, dtype=np.int32)
        disc = rng.integers(0, 11, m, dtype=np.int32)
        partkey = rng.integers(1, parts + 1, m, dtype=np.int32)
        ship_after = rng.integers(1, 122, m, dtype=np.int32)
        # clause 4.2.3: p_retailprice in cents, so the product is exact
        retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
        price[at] = (quantity * retail) / 100.0
        discount[at] = disc / 100.0
        shipdate[at] = first + (l_orderday[at] + ship_after).astype(
            "timedelta64[D]"
        )
    out["lineitem"] = {
        "l_orderkey": l_orderkey,
        "l_extendedprice": price,
        "l_discount": discount,
        "l_shipdate": shipdate,
    }
    return out
