"""TPC-H Q6 in plain numpy: the reference the planned path is held to.

Imports nothing of the program. Four arrays in (ship date, discount,
quantity, extended price, as ``q1_reference.lineitem`` makes them); a
boolean mask and one float64 sum out.

The specification's ``DISCOUNT - 0.01`` and ``+ 0.01`` are decimal
arithmetic: in float64 ``0.06 + 0.01`` is 0.06999999999999999 and drops
every row whose discount is 0.07. The bounds here are the doubles nearest
0.05 and 0.07, which are the values a generator that writes ``k / 100.0``
holds; :func:`passing_cents` counts the same rows in integer hundredths,
with no float comparison at all.
"""
import numpy as np

DATE_LO = np.datetime64("1994-01-01")   # validation DATE
DATE_HI = np.datetime64("1995-01-01")   # + interval '1' year
DISCOUNT_LO, DISCOUNT_HI = 0.05, 0.07   # validation DISCOUNT 0.06 -/+ 0.01
QUANTITY = 24                           # validation QUANTITY


def passes(li: dict) -> np.ndarray:
    """Q6's predicate, a boolean a row."""
    return (
        (li["l_shipdate"] >= DATE_LO) & (li["l_shipdate"] < DATE_HI)
        & (li["l_discount"] >= DISCOUNT_LO) & (li["l_discount"] <= DISCOUNT_HI)
        & (li["l_quantity"] < QUANTITY)
    )


def passing_cents(li: dict) -> int:
    """Rows that pass, counted with the discount in integer hundredths
    (5, 6 or 7) and the quantity as an integer: what the decimal query
    means, whatever a float comparison makes of 0.07."""
    hundredths = np.rint(li["l_discount"] * 100).astype(np.int64)
    quantity = np.rint(li["l_quantity"]).astype(np.int64)
    keep = (
        (li["l_shipdate"] >= DATE_LO) & (li["l_shipdate"] < DATE_HI)
        & (hundredths >= 5) & (hundredths <= 7) & (quantity < QUANTITY)
    )
    return int(keep.sum())


def q6(li: dict) -> dict:
    """``{"revenue": sum(l_extendedprice * l_discount), "count": rows}``
    over the rows that pass; the revenue of no row is None (SQL's null)."""
    keep = passes(li)
    n = int(keep.sum())
    revenue = np.sum(li["l_extendedprice"][keep] * li["l_discount"][keep])
    return {"revenue": float(revenue) if n else None, "count": n}
