"""TPC-H Q3 in plain numpy: the reference the planned path is held to.

Imports nothing of the program. Ten arrays of three tables in; boolean
masks, ``np.isin`` and ``np.searchsorted`` for the two joins,
``np.bincount`` with float64 weights for the revenue of an order (at most
seven addends a group, so nothing drifts and no blocks are needed),
``np.lexsort`` for the order. Out: every group, and the ten rows.
"""
import numpy as np

SEGMENT = "BUILDING"
DATE = np.datetime64("1995-03-15")


def q3(data: dict, segment=SEGMENT, date=DATE, limit=10, tie_gap=0.0) -> dict:
    """``{"groups": {column: array}, "top": {column: array}}``: every
    (l_orderkey, o_orderdate, o_shippriority) group in key order, and the
    first ``limit`` of them by revenue descending, then order date. Asserts
    that neighbours among the first ``limit + 1`` revenues differ by more
    than ``tie_gap`` of them, so a tie cannot flip the answer."""
    date = np.datetime64(date)
    cu, od, li = data["customer"], data["orders"], data["lineitem"]
    building = cu["c_custkey"][cu["c_mktsegment"] == segment]
    early = (od["o_orderdate"] < date) & np.isin(od["o_custkey"], building)
    # the orders that pass, by key (dbgen's keys are distinct)
    by_key = np.argsort(od["o_orderkey"][early], kind="stable")
    okeys = od["o_orderkey"][early][by_key]
    odate = od["o_orderdate"][early][by_key]
    oprio = od["o_shippriority"][early][by_key]
    late = li["l_shipdate"] > date
    lkey = li["l_orderkey"][late]
    at = np.searchsorted(okeys, lkey)
    at[at == len(okeys)] = 0
    hit = okeys[at] == lkey if len(okeys) else np.zeros(len(lkey), bool)
    slot = at[hit]  # the order's position: one group an order
    revenue_row = (
        li["l_extendedprice"][late][hit] * (1 - li["l_discount"][late][hit])
    )
    count = np.bincount(slot, minlength=len(okeys))
    revenue = np.bincount(slot, weights=revenue_row, minlength=len(okeys))
    live = np.flatnonzero(count)
    groups = {
        "l_orderkey": okeys[live], "revenue": revenue[live],
        "o_orderdate": odate[live], "o_shippriority": oprio[live],
    }
    # order by revenue desc, o_orderdate
    order = np.lexsort((groups["o_orderdate"], -groups["revenue"]))
    first = groups["revenue"][order[: limit + 1]]
    assert (first[:-1] - first[1:] > tie_gap * np.abs(first[:-1])).all(), first
    top = {k: v[order[:limit]] for k, v in groups.items()}
    return {"groups": groups, "top": top, "joined_rows": int(hit.sum())}
