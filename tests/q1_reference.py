"""TPC-H Q1 in plain numpy: the reference the planned path is held to.

Imports nothing of the program. Seven arrays in (four float64 decimals,
two one-character string flags, a ``datetime64[D]`` ship date); a boolean
mask, one group code a row, ``np.bincount`` with float64 weights; one row
a (returnflag, linestatus) pair that has a passing row, in key order.
"""
import numpy as np

CURRENT_DATE = np.datetime64("1995-06-17")
CUTOFF = np.datetime64("1998-12-01") - np.timedelta64(90, "D")  # DELTA = 90


def lineitem(seed: int, rows: int) -> dict:
    """Seeded rows by TPC-H's population rules (clause 4.2.3), cut to the
    columns Q1 reads; a few thousand rows fall into all four groups."""
    rng = np.random.default_rng(seed)
    quantity = rng.integers(1, 51, rows)
    partkey = rng.integers(1, 200_001, rows)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    ship = np.datetime64("1992-01-01") + (
        rng.integers(0, 2406, rows) + rng.integers(1, 122, rows)
    ).astype("timedelta64[D]")
    receipt = ship + rng.integers(1, 31, rows).astype("timedelta64[D]")
    flag = np.where(
        receipt <= CURRENT_DATE,
        np.where(rng.integers(0, 2, rows) == 1, "R", "A"), "N",
    )
    return {
        "l_quantity": quantity.astype(np.float64),
        "l_extendedprice": quantity * retail / 100.0,
        "l_discount": rng.integers(0, 11, rows) / 100.0,
        "l_tax": rng.integers(0, 9, rows) / 100.0,
        "l_returnflag": flag,
        "l_linestatus": np.where(ship > CURRENT_DATE, "O", "F"),
        "l_shipdate": ship,
    }


def q1(li: dict, cutoff=CUTOFF) -> dict:
    """``{column: array}`` of Q1's result, one entry a group."""
    keep = li["l_shipdate"] <= cutoff
    flags, flag_code = np.unique(li["l_returnflag"][keep], return_inverse=True)
    stats, stat_code = np.unique(li["l_linestatus"][keep], return_inverse=True)
    code = flag_code * len(stats) + stat_code
    slots = len(flags) * len(stats)
    count = np.bincount(code, minlength=slots)
    groups = np.flatnonzero(count)
    # rows are added a block at a time and the blocks' sums after that: a
    # single running sum of millions of values drifts by 1e-13 of itself
    blocked = (np.arange(len(code)) >> 12) * slots + code
    blocks = (len(code) >> 12) + 1

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
        "l_returnflag": flags[groups // len(stats)],
        "l_linestatus": stats[groups % len(stats)],
        "sum_qty": total(qty), "sum_base_price": total(price),
        "sum_disc_price": total(disc_price), "sum_charge": total(charge),
        "avg_qty": total(qty) / n, "avg_price": total(price) / n,
        "avg_disc": total(disc) / n, "count_order": n,
    }
