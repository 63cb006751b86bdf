"""Query ``tpch_q1_delta``: TPC-H Q1 with its substitution parameter.

Clause 2.4.1.3 draws DELTA, a whole number of days in [60, 120], anew for
every execution; ``tpch_q1`` runs the validation value, 90, every time.
Here every call draws its own DELTA from ``numpy.random.default_rng(
delta_seed)`` (the same sequence in every run and on both sides of a
pair), builds Q1 with ``l_shipdate <= shipdate_base - DELTA`` through
``Table.lazy()`` (``tpch_q1.build``: the plan, unedited) and collects it.
What it guards: a literal is an argument of the program that evaluates it
(``jit_expr_eval``), so 61 values compile nothing and transfer nothing a
fixed literal does not.

The reference, the comparison and the limits are ``tpch_q1``'s: each kept
result is held to the reference OF ITS OWN DELTA. Every DELTA in the range
leaves the same four groups, so the harness's row-count check stands.
"""
import numpy as np

from chipbench.checks import Number
from chipbench.queries import tpch_q1

#: distinct DELTAs a window of the cell must have run
DISTINCT_MIN = 30
#: queries from which a window is held to it: draws from 61 values number
#: 30 distinct ones long before (the traced run's three queries and the
#: rehearsal's few cannot, and print the number beside no limit)
HELD_FROM = 120

#: the DELTA of every result by the result's ``id`` (a kept result is
#: alive, so no later one has its id), every DELTA drawn, and the
#: references computed, by DELTA
_RUN = {}


def delta_range(params: dict) -> tuple:
    lo, hi = (int(x) for x in str(params["delta_days"]).split("-"))
    return lo, hi


def cutoff(params: dict, delta: int) -> np.datetime64:
    return np.datetime64(params["shipdate_base"], "D") - np.timedelta64(
        int(delta), "D"
    )


def q1_params(params: dict, delta: int) -> dict:
    return {
        "table": params["table"], "delta_days": int(delta),
        "shipdate_max": str(cutoff(params, delta)),
    }


def build(tables: dict, params: dict):
    lo, hi = delta_range(params)
    rng = np.random.default_rng(int(params["delta_seed"]))
    _RUN.clear()
    _RUN.update(params=params, drawn=[], delta_of={}, refs={})

    def call():
        delta = int(rng.integers(lo, hi + 1))
        out = tpch_q1.build(tables, q1_params(params, delta))()
        _RUN["drawn"].append(delta)
        _RUN["delta_of"][id(out)] = delta
        return out

    return call


def input_rows(data: dict, params: dict) -> int:
    return tpch_q1.input_rows(data, params)


def least_bytes(data: dict, params: dict, out_rows: int) -> int:
    return tpch_q1.least_bytes(data, params, out_rows)


def reference(data: dict, params: dict) -> dict:
    """The row count every DELTA of the range gives (the four groups), and
    the data, for the reference of each kept result's own DELTA."""
    lo, _hi = delta_range(params)
    _RUN["data"] = data
    return {"rows": reference_of(data, params, lo)["rows"], "params": params}


def reference_of(data: dict, params: dict, delta: int) -> dict:
    refs = _RUN.setdefault("refs", {})
    if refs.get("of") is not data:
        refs.clear()
        refs["of"] = data
    if delta not in refs:
        refs[delta] = tpch_q1.reference(data, q1_params(params, delta))
    return refs[delta]


def compare(table, ref: dict, config: dict) -> list:
    delta = _RUN.get("delta_of", {}).get(id(table))
    if delta is None:
        # a result this module did not hand out has no DELTA to be held to
        return [Number("q1_delta.unknown_result", 1, 0)]
    numbers = tpch_q1.compare(
        table, reference_of(_RUN["data"], ref["params"], delta), config
    )
    if not _RUN.get("counted"):
        _RUN["counted"] = True
        drawn = _RUN["drawn"]
        numbers.append(Number(
            "q1_delta.distinct_short",
            max(0, DISTINCT_MIN - len(set(drawn))),
            0 if len(drawn) >= HELD_FROM else float("inf"),
        ))
    return numbers
