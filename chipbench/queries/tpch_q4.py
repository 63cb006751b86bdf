"""Query ``tpch_q4``: TPC-H Q4, the order priority checking query, through
the planner (``Table.lazy()``: two filters, a semi join, groupby, sort).

    select o_orderpriority, count(*) as order_count from orders
    where o_orderdate >= date '1993-07-01'
      and o_orderdate < date '1993-07-01' + interval '3' month
      and exists (select * from lineitem
                  where l_orderkey = o_orderkey
                    and l_commitdate < l_receiptdate)
    group by o_orderpriority order by o_orderpriority

The call, its plain reference, the comparison that decides ``correct`` and
the least bytes the query must move. The query is said as written; the
planner's rules, not this module, turn the filters into the join's masks
and the join's hit mask into the aggregate's (``semi_as_mask``), so the
timed query compacts and gathers nothing. The reference reads only the
seeded arrays (two masks, ``np.isin``, ``np.bincount``) and imports
nothing of the program; it is ``tests/q4_reference.py``'s, copied.

Five counts say little about HOW the orders were found, so once a run and
outside every timing the comparison also runs the same plan with
``how="anti"`` (NOT EXISTS), holds it to the reference's anti counts and
semi plus anti to the quarter's orders, and runs the eager
``Table.join(how="semi")`` on the filtered tables (the compacted path,
which the timed plan never takes), its rows and order keys held to the
reference.
"""
import numpy as np

from chipbench.checks import Number

from cylon_tpu.ops.join import join_type_id
from cylon_tpu.plan import col, lit

try:
    # the one look into ops/: can this checkout say a semi join at all?
    join_type_id("semi")
except ValueError:
    # a commit from before the operator would fail at the first warm-up
    # call, after a minute of set-up: stop before any
    raise SystemExit(
        "chipbench: tpch_q4 needs Table.join(how='semi'), which this "
        "checkout lacks; nothing was run"
    ) from None

KEY = "o_orderpriority"
COUNT = "o_orderkey_count"

#: what ``build`` was last given, for the once-a-run checks and the
#: readers: the harness hands ``compare`` a result and the reference, not
#: the tables
_RUN = {}


def quarter(date, months: int = 3):
    """``[date, date + months)`` as two days: the interval is in calendar
    months, so the end keeps the day of the month."""
    d0 = np.datetime64(date, "D")
    month = d0.astype("datetime64[M]")
    day = d0 - month.astype("datetime64[D]")
    return d0, (month + int(months)).astype("datetime64[D]") + day


def _sides(tables: dict, params: dict):
    d0, d1 = quarter(params["date"], params["months"])
    orders = tables["orders"].lazy().filter(
        (col("o_orderdate") >= lit(d0)) & (col("o_orderdate") < lit(d1))
    )
    lineitem = tables["lineitem"].lazy().filter(
        col("l_commitdate") < col("l_receiptdate")
    )
    return orders, lineitem


def _plan(tables: dict, params: dict, how: str = "semi"):
    orders, lineitem = _sides(tables, params)
    return (
        orders.join(
            lineitem, left_on="o_orderkey", right_on="l_orderkey", how=how
        )
        .groupby(KEY, {"o_orderkey": "count"})
        .sort(KEY)
    )


def build(tables: dict, params: dict):
    _RUN.clear()
    _RUN.update(tables=tables, params=params)
    return _plan(tables, params).collect


def input_rows(data: dict, params: dict) -> int:
    return sum(len(next(iter(cols.values()))) for cols in data.values())


def device_bytes(array: np.ndarray) -> int:
    """Bytes of a column as the device holds it: a string is its int32
    dictionary code, a date int64 nanoseconds, the rest as on the host."""
    return len(array) * (4 if array.dtype.kind == "U" else array.dtype.itemsize)


def least_bytes(data: dict, params: dict, out_rows: int) -> int:
    """The six columns of the two tables read once and the result's two
    columns (4 + 8 bytes a row) written once. A traced run asks for this
    before its per-layer readers run: the semi join's own least bytes are
    kept here for ``semi_join_hbm_share``."""
    _RUN["semi_least_bytes"] = semi_least_bytes(data)
    read = sum(device_bytes(a) for cols in data.values() for a in cols.values())
    return read + out_rows * 12


def semi_least_bytes(data: dict) -> int:
    """What a semi join must move whatever implements it: both sides' key
    ids (int32) and live masks (a byte a row) read once, the left side's
    hit mask (a byte a row) written once. No payload column, no position,
    no count."""
    left = len(data["orders"]["o_orderkey"])
    right = len(data["lineitem"]["l_orderkey"])
    return (left + right) * (4 + 1) + left


def reference(data: dict, params: dict) -> dict:
    """Q4 and its anti twin in plain numpy over the six arrays (computed
    once a run's data)."""
    if _RUN.get("ref_of") is not data:
        _RUN["ref"], _RUN["ref_of"] = _reference(data, params), data
    return _RUN["ref"]


def _by_priority(priority: np.ndarray, keep: np.ndarray):
    """(the priorities that have a kept order, ascending; their counts)."""
    names, codes = np.unique(priority, return_inverse=True)
    count = np.bincount(codes[keep], minlength=len(names))
    live = np.flatnonzero(count)
    return names[live].astype(object), count[live].astype(np.int64)


def _reference(data: dict, params: dict) -> dict:
    od, li = data["orders"], data["lineitem"]
    d0, d1 = quarter(params["date"], params["months"])
    # the quarter's orders (a few of every hundred), in row order
    rows = np.flatnonzero((od["o_orderdate"] >= d0) & (od["o_orderdate"] < d1))
    keys, priority = od["o_orderkey"][rows], od["o_orderpriority"][rows]
    late = li["l_commitdate"] < li["l_receiptdate"]
    has_late = np.isin(keys, li["l_orderkey"][late])
    out = {"columns": sorted([KEY, COUNT])}
    for name, keep in (
        ("semi", has_late), ("anti", ~has_late),
        ("quarter", np.ones(len(rows), bool)),
    ):
        out[name] = dict(zip((KEY, COUNT), _by_priority(priority, keep)))
    out["rows"] = len(out["semi"][COUNT])
    out["semi_keys"] = keys[has_late]
    return out


def _counts_gap(got: dict, want: dict) -> int:
    """Groups that differ in number or name, else the counts' total gap."""
    if len(got[COUNT]) != len(want[COUNT]):
        return abs(len(got[COUNT]) - len(want[COUNT]))
    if (np.asarray(got[KEY], object) != want[KEY]).any():
        return int((np.asarray(got[KEY], object) != want[KEY]).sum())
    return int(np.abs(got[COUNT].astype(np.int64) - want[COUNT]).sum())


def _once_a_run(ref: dict, semi: dict) -> list:
    """The anti twin through the same plan, and the eager semi join on the
    filtered tables: outside every timing, on the tables ``build`` was
    given."""
    tables, params = _RUN["tables"], _RUN["params"]
    anti = _plan(tables, params, "anti").collect().to_pydict()
    numbers = [Number("q4.anti_counts_gap", _counts_gap(anti, ref["anti"]), 0)]
    # semi plus anti is the quarter's orders, priority by priority
    both = {}
    for part in (semi, anti):
        for name, n in zip(part[KEY], part[COUNT]):
            both[name] = both.get(name, 0) + int(n)
    whole = dict(zip(ref["quarter"][KEY], ref["quarter"][COUNT]))
    numbers.append(Number(
        "q4.semi_plus_anti_gap",
        sum(abs(both.get(k, 0) - int(whole.get(k, 0)))
            for k in set(both) | set(whole)), 0,
    ))
    orders, lineitem = (side.collect() for side in _sides(tables, params))
    kept = orders.join(
        lineitem, left_on="o_orderkey", right_on="l_orderkey", how="semi"
    )
    want = ref["semi_keys"]
    numbers.append(Number(
        "q4.eager_rows_gap", abs(int(kept.row_count) - len(want)), 0
    ))
    got = kept.to_pydict()
    numbers.append(Number(
        "q4.eager_columns_wrong",
        int(list(got) != ["o_orderkey", "o_orderdate", KEY]), 0,
    ))
    keys = got["o_orderkey"]
    numbers.append(Number(
        "q4.eager_keys_wrong",
        int((keys != want).sum()) if len(keys) == len(want)
        else abs(len(keys) - len(want)), 0,
    ))
    return numbers


def compare(table, ref: dict, config: dict) -> list:
    n = int(table.row_count)
    got = table.to_pydict()
    numbers = [
        Number("q4.rows_gap", abs(n - ref["rows"]), 0),
        Number("q4.columns_wrong", int(sorted(got) != ref["columns"]), 0),
    ]
    if numbers[0].value or numbers[1].value:
        return numbers
    # the rows as emitted: position for position, so also in priority order
    want = ref["semi"]
    numbers.append(Number(
        "q4.priorities_wrong",
        int((np.asarray(got[KEY], object) != want[KEY]).sum()), 0,
    ))
    numbers.append(Number(
        "q4.counts_gap",
        int(np.abs(got[COUNT].astype(np.int64) - want[COUNT]).sum()), 0,
    ))
    if "tables" in _RUN and not _RUN.get("checked_once"):
        _RUN["checked_once"] = True
        numbers.extend(_once_a_run(ref, got))
    return numbers
