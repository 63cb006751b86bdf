"""Query ``tpch_q3``: TPC-H Q3, the shipping priority query, through the
planner (``Table.lazy()``: filter, join, with_columns, groupby, sort,
limit).

    select l_orderkey, sum(l_extendedprice*(1-l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
      and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
      and l_shipdate > date '1995-03-15'
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate limit 10

The call, its plain reference, the comparison that decides ``correct`` and
the least bytes the query must move. The joins are said in the order
written (customer with orders, then lineitem). The reference reads only
the seeded arrays (masks, ``np.isin``, ``np.searchsorted``,
``np.bincount`` with float64 weights, ``np.lexsort``) and imports nothing
of the program; it is ``tests/q3_reference.py``'s, copied.

Ten rows cannot show a row lost in the 114,000 groups under them, so the
comparison also runs the same plan WITHOUT its limit, once a run and
outside every timing, on the tables ``build`` was given, and holds every
group to the reference.
"""
import numpy as np

import cylon_tpu as ct
from chipbench.checks import Number, rel_gap

from cylon_tpu.plan import col, lit

if not hasattr(ct.Table, "topk"):
    # a commit from before the top-k would order all the groups of a join
    # that emits at its probe side's capacity: stop before any set-up
    raise SystemExit(
        "chipbench: tpch_q3 needs Table.topk (the planner's TopK), which "
        "this checkout lacks; nothing was run"
    )

KEYS = ("l_orderkey", "o_orderdate", "o_shippriority")
REVENUE = "revenue_sum"
#: limit on the worst relative gap of a revenue, over the ten rows and over
#: every group. A revenue adds at most seven float64 products in the
#: chip's two-float arithmetic; the limit stands between the sound runs'
#: largest reading and the float32 control's smallest (PERF.md section 2)
VALUE_LIMIT = {"float64": 1e-11, "float32": 1e-4}

#: what ``build`` was last given, for the un-limited check and the readers:
#: the harness hands ``compare`` a result and the reference, not the tables
_RUN = {}


def _plan(tables: dict, params: dict, limited: bool):
    date = np.datetime64(params["date"])
    customer = tables["customer"].lazy().filter(
        col("c_mktsegment") == lit(params["segment"])
    )
    orders = tables["orders"].lazy().filter(col("o_orderdate") < lit(date))
    lineitem = tables["lineitem"].lazy().filter(col("l_shipdate") > lit(date))
    query = (
        customer.join(orders, left_on="c_custkey", right_on="o_custkey")
        .join(lineitem, left_on="o_orderkey", right_on="l_orderkey")
        .with_columns({
            "revenue": col("l_extendedprice") * (1 - col("l_discount")),
        })
        .groupby(list(KEYS), {"revenue": "sum"})
    )
    if limited:
        query = query.sort(
            [REVENUE, "o_orderdate"], ascending=[False, True]
        ).limit(int(params["limit"]))
    return query


def build(tables: dict, params: dict):
    _RUN.clear()
    _RUN.update(tables=tables, params=params)
    return _plan(tables, params, True).collect


def input_rows(data: dict, params: dict) -> int:
    return sum(len(next(iter(cols.values()))) for cols in data.values())


def device_bytes(array: np.ndarray) -> int:
    """Bytes of a column as the device holds it: a string is its int32
    dictionary code, a date int64 nanoseconds, the rest as on the host."""
    return len(array) * (4 if array.dtype.kind == "U" else array.dtype.itemsize)


def least_bytes(data: dict, params: dict, out_rows: int) -> int:
    """The ten columns of the three tables read once and the result's four
    columns (4 + 8 + 8 + 4 bytes a row) written once. A traced run asks
    for this before its per-layer readers run, and ``topk_hbm_share``
    needs the number of groups: the reference is computed here and kept
    for the comparison."""
    reference(data, params)
    read = sum(device_bytes(a) for cols in data.values() for a in cols.values())
    return read + out_rows * 24


def topk_least_bytes(groups: int, out_rows: int) -> int:
    """What the top-k must move: the two key lanes of every group
    (``revenue_sum`` and ``o_orderdate``, 8 bytes each) read once and the
    kept rows' four columns written once."""
    return groups * 16 + out_rows * 24


def reference(data: dict, params: dict) -> dict:
    """Q3 in plain numpy over the ten arrays: every group and the ten rows
    (computed once a run's data)."""
    if _RUN.get("ref_of") is not data:
        _RUN["ref"], _RUN["ref_of"] = _reference(data, params), data
    return _RUN["ref"]


def _reference(data: dict, params: dict) -> dict:
    date = np.datetime64(params["date"])
    limit = int(params["limit"])
    cu, od, li = data["customer"], data["orders"], data["lineitem"]
    building = cu["c_custkey"][cu["c_mktsegment"] == params["segment"]]
    early = (od["o_orderdate"] < date) & np.isin(od["o_custkey"], building)
    by_key = np.argsort(od["o_orderkey"][early], kind="stable")
    okeys = od["o_orderkey"][early][by_key]
    odate = od["o_orderdate"][early][by_key]
    oprio = od["o_shippriority"][early][by_key]
    late = li["l_shipdate"] > date
    lkey = li["l_orderkey"][late]
    at = np.searchsorted(okeys, lkey)
    at[at == len(okeys)] = 0
    hit = okeys[at] == lkey if len(okeys) else np.zeros(len(lkey), bool)
    slot = at[hit]
    # at most seven addends a group: a plain bincount does not drift
    revenue_row = (
        li["l_extendedprice"][late][hit] * (1 - li["l_discount"][late][hit])
    )
    count = np.bincount(slot, minlength=len(okeys))
    revenue = np.bincount(slot, weights=revenue_row, minlength=len(okeys))
    live = np.flatnonzero(count)
    groups = {
        "l_orderkey": okeys[live], REVENUE: revenue[live],
        "o_orderdate": odate[live], "o_shippriority": oprio[live],
    }
    order = np.lexsort((groups["o_orderdate"], -groups[REVENUE]))
    # two of the first eleven revenues inside the value limit of each other
    # could change places without a fault: the answer must not hang on it
    first = groups[REVENUE][order[: limit + 1]]
    assert (
        first[:-1] - first[1:] > VALUE_LIMIT["float64"] * np.abs(first[:-1])
    ).all(), first
    top = {k: v[order[:limit]] for k, v in groups.items()}
    return {
        "rows": len(top[REVENUE]), "columns": sorted([*KEYS, REVENUE]),
        "top": top, "groups": groups, "joined_rows": int(hit.sum()),
    }


def _keys_wrong(got: dict, want: dict) -> int:
    """Key columns that differ, position for position (dates by the day)."""
    wrong = 0
    for k in KEYS:
        g, w = got[k], want[k]
        if w.dtype.kind == "M":
            g, w = g.astype("datetime64[D]"), w.astype("datetime64[D]")
        wrong += int((g != w).sum())
    return wrong


def _all_groups(ref: dict, limit: float) -> list:
    """The same plan without its limit, once a run: every group held to the
    reference, in key order (the group-by's, the reference's by order key,
    which decides the other two)."""
    table = _plan(_RUN["tables"], _RUN["params"], False).collect()
    got = table.to_pydict()
    want = ref["groups"]
    if len(got[REVENUE]) != len(want[REVENUE]):
        return [Number(
            "q3.groups_wrong", abs(len(got[REVENUE]) - len(want[REVENUE])), 0
        )]
    by_key = np.argsort(got["l_orderkey"], kind="stable")
    got = {k: v[by_key] for k, v in got.items()}
    return [
        Number("q3.groups_wrong", _keys_wrong(got, want), 0),
        Number(
            "q3.all_revenue_relgap", rel_gap(got[REVENUE], want[REVENUE]),
            limit,
        ),
    ]


def compare(table, ref: dict, config: dict) -> list:
    precision = config["guarantees"]["value_precision"]
    n = int(table.row_count)
    got = table.to_pydict()
    numbers = [
        Number("q3.rows_gap", abs(n - ref["rows"]), 0),
        Number("q3.columns_wrong", int(sorted(got) != ref["columns"]), 0),
    ]
    if numbers[0].value or numbers[1].value:
        return numbers
    # the ten rows as emitted: position for position, so also the order
    numbers.append(Number("q3.keys_wrong", _keys_wrong(got, ref["top"]), 0))
    numbers.append(Number(
        "q3.revenue_relgap", rel_gap(got[REVENUE], ref["top"][REVENUE]),
        VALUE_LIMIT[precision],
    ))
    if "tables" in _RUN and not _RUN.get("checked_all"):
        _RUN["checked_all"] = True
        numbers.extend(_all_groups(ref, VALUE_LIMIT[precision]))
    return numbers
