"""Query ``h2o_join``: a question of the h2oai/db-benchmark join task that
joins ``x`` to a right table unique on the key,
``x.distributed_join(right, on=key, how=how)``; the cell's is question 3,
``x LEFT JOIN medium ON id2``.

The call, its plain reference, the comparison that decides ``correct`` and
the least bytes the query must move. The reference is this module's own
copy of ``tests/h2o_join_reference.py``'s arithmetic, reads only the
seeded arrays and takes nothing the program made (the module's one look
into ``cylon_tpu`` reads the route counters of the once-a-run questions).

A right table holds every key once, so a row of ``x`` has at most one
partner and comes out at most once: the comparison is row for row and
needs no sort of 1e8 rows. ``x.id3`` is unique in ``x`` (the source's
level of N values), so a result row names the row of ``x`` it repeats by
one table lookup, whatever order and on whatever chip it came out: the
shuffle route (the parent's) and the replicate route are held alike.
The partner of a row is one more lookup of its key in a table over the key
space. The result is read as the device holds it (a string column as its int32
codes, a null as its validity lane says). Exact numbers (limit 0): the
rows and columns, every string column's dictionary the one it was loaded
with, every row of ``x``
the right number of times with its own left columns, the right side's
columns null if and only if the key has no partner and all of them
together, the partner's own keys beside a matched row. Value numbers:
the worst relative gap over the keys of the sums of ``v1``, ``v2`` and
``v1 * v2`` over the matched rows, the worst gap of a row's ``v1`` and
``v2`` from the row's own, and the source's ``chk`` (``sum(v1)``,
``sum(v2)`` of the whole result, added in blocks of 65,536 as
``tpch_q1``'s reference adds).

Question 3 says nothing of the inner join or of a build side of a hundred
rows. So once a run and outside every timing the comparison also asks the
same resident tables question 2 (``inner`` on ``id2``) and question 1
(``x`` inner ``small`` on ``id1``), holds them to the reference the same
way, and reads the route counter: all three took the replicate route,
where the checkout has one.
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.checks import Number, rel_gap
from chipbench.queries.inner_join import shards_wrong

#: limit on a key's sums' and on a row's values' worst relative gap, by the
#: precision the configuration states: a key adds about 1,000 float64
#: values under 100, which two orders of adding leave within about 1e-13 of
#: each other, and a row's value is a copy (gap 0); float32 values in
#: their place are off by up to 2**-24 = 6e-8 a row. PERF.md section 2 has
#: the readings the limit was set from.
VALUE_LIMIT = {"float64": 1e-11, "float32": 1e-5}
#: limit on the gap of the whole result's ``sum(v1)`` / ``sum(v2)``: 1e8
#: addends' rounding errors cancel, so float32 values move the total by a
#: few 1e-12 of itself only (``sum(v2)``, whose errors repeat a key's rows,
#: by 1e-10) and the limit of a key's sum would pass them. Two orders of
#: adding the same float64 values in blocks read under 1e-14.
CHK_LIMIT = {"float64": 2e-13, "float32": 1e-7}
CHK_BLOCK = 65_536
#: rows the reference and the comparison work on at a time, on threads
BLOCK = 1 << 22

#: what ``build`` was last given and what the reference keeps, for the
#: once-a-run questions and the readers: the harness hands ``compare`` a
#: result and the reference, not the tables
_RUN = {}


def _ask(tables: dict, q: dict):
    left = tables[q.get("left", "x")]
    return left.distributed_join(tables[q["right"]], on=q["on"], how=q["how"])


def build(tables: dict, params: dict):
    routes = _route_counts()
    if routes is None and tables[params["left"]].ctx.world_size > 1:
        # found out, not predicted (my chip run, PR 48): the parent of
        # PR 48 hash-shuffles all of x and had not finished its set-up
        # after 480 s at N = 1e8. The cell IS the replicate route; a
        # checkout without it is refused here, at once and cleanly
        raise SystemExit(
            "h2o_join: this checkout's distributed_join has no replicate "
            "route (no join.route.replicate counter): it would hash-shuffle "
            "the big side whole, which is not the deployment this cell "
            "measures; nothing was run"
        )
    _RUN.clear()
    _RUN.update(tables=tables, params=params, routes=routes)

    def call():
        return _ask(tables, params)

    return call


def input_rows(data: dict, params: dict) -> int:
    """Rows a query takes in: both sides of the join."""
    on = params["on"]
    return len(data[params["left"]][on]) + len(data[params["right"]][on])


def _row_bytes(cols: dict) -> int:
    return sum(a.dtype.itemsize for a in cols.values())


def least_bytes(data: dict, params: dict, out_rows: int) -> int:
    """The WHOLE query over the mesh: both inputs read once, the result
    (both sides' columns, and one byte of validity a right-side column of
    a left join) written once. What the emit alone must move is counted
    in ``layer_metrics/join_outer_emit_hbm_share.py`` from the shapes
    kept here."""
    left, right = data[params["left"]], data[params["right"]]
    lanes = len(right) if params["how"] == "left" else 0
    shapes = {
        "left_rows": len(left[params["on"]]), "left_row": _row_bytes(left),
        "right_rows": len(right[params["on"]]),
        "right_row": _row_bytes(right),
        "out_rows": int(out_rows),
        "out_row": _row_bytes(left) + _row_bytes(right) + lanes,
    }
    _RUN["shapes"] = shapes
    return (
        shapes["left_rows"] * shapes["left_row"]
        + shapes["right_rows"] * shapes["right_row"]
        + shapes["out_rows"] * shapes["out_row"]
    )


def emit_shapes():
    """The sizes :func:`least_bytes` last counted, ``None`` before."""
    return _RUN.get("shapes")


def output_names(left: dict, right: dict) -> tuple:
    both = set(left) & set(right)
    return (
        [c + "_x" if c in both else c for c in left],
        [c + "_y" if c in both else c for c in right],
    )


def blocked_sum(values: np.ndarray) -> float:
    """``values`` added in blocks of ``CHK_BLOCK``, then the blocks."""
    pad = (-len(values)) % CHK_BLOCK
    if pad:
        values = np.concatenate([values, np.zeros(pad, values.dtype)])
    return float(values.reshape(-1, CHK_BLOCK).sum(axis=1).sum())


def _over_blocks(fn, rows: int) -> list:
    """``fn(slice)`` over ``rows`` in blocks of ``BLOCK`` (a multiple of
    ``CHK_BLOCK``), on threads (numpy indexes, compares and adds without
    the interpreter's lock), the answers in block order: 1e8 rows take one
    thread minutes."""
    blocks = [slice(i, min(i + BLOCK, rows)) for i in range(0, rows, BLOCK)]
    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        return list(pool.map(fn, blocks))


def _lookup(keys: np.ndarray, space: int) -> np.ndarray:
    """key -> the row that holds it, -1 where none does (keys unique, so
    the blocks write apart)."""
    table = np.full(space, -1, np.int32)

    def fill(at):
        table[keys[at]] = np.arange(at.start, at.stop, dtype=np.int32)

    _over_blocks(fill, len(keys))
    return table


def _key_sums(keys, matched, v1, v2, space: int) -> np.ndarray:
    """``[rows, sum v1, sum v2, sum v1 * v2]`` a key over the matched rows
    of one block."""
    k = keys[matched]
    a, b = v1[matched], v2[matched]
    return np.stack([
        np.bincount(k, minlength=space).astype(np.float64),
        np.bincount(k, weights=a, minlength=space),
        np.bincount(k, weights=b, minlength=space),
        np.bincount(k, weights=a * b, minlength=space),
    ])


def reference(data: dict, params: dict) -> dict:
    """Computed once a run's data (the lookups over 1e8 rows take the
    longest of a run's check)."""
    if _RUN.get("ref_of") is not data:
        _RUN["data"] = data
        _RUN["ref"], _RUN["ref_of"] = _reference(data, params), data
    return _RUN["ref"]


def _reference(data: dict, q: dict) -> dict:
    x, right = data[q.get("left", "x")], data[q["right"]]
    on, how = q["on"], q["how"]
    xk, rk = x[on], right[on]
    n = len(xk)
    space = int(max(xk.max(), rk.max())) + 1
    by_key = _lookup(rk, space)
    part = np.empty(n, np.int32)  # the partner of every row of x
    v1, rv2 = x["v1"], right["v2"]

    def block(at):
        p = part[at] = by_key[xk[at]]
        matched = p >= 0
        v2 = np.where(matched, rv2[np.maximum(p, 0)], 0.0)
        kept = slice(None) if how == "left" else matched
        return (
            _key_sums(xk[at], matched, v1[at], v2, space),
            int((~matched).sum()),
            blocked_sum(v1[at][kept]), blocked_sum(v2[kept]),
        )

    found = _over_blocks(block, n)
    per_key = sum(f[0] for f in found)
    unmatched = sum(f[1] for f in found)
    l_names, r_names = output_names(x, right)
    row_id = q.get("row_id", "id3")
    return {
        "q": q, "rows": n if how == "left" else n - unmatched,
        "columns": l_names + r_names,
        "l_names": l_names, "r_names": r_names, "space": space,
        "part": part, "unmatched": unmatched,
        "row_of": _lookup(x[row_id], int(x[row_id].max()) + 1),
        "row_id": l_names[list(x).index(row_id)],
        "per_key": per_key,
        "chk": (sum(f[2] for f in found), sum(f[3] for f in found)),
    }


def _live(array, counts: np.ndarray) -> np.ndarray:
    """The live rows of a device column, shard after shard."""
    shards = sorted(array.addressable_shards, key=lambda s: s.index[0].start or 0)
    return np.concatenate([
        np.asarray(s.data)[: int(c)] for s, c in zip(shards, counts)
    ])


def _host_columns(table) -> tuple:
    """``(values, nulls, dictionaries)`` a column of the result as the
    device holds it: a string column as its int32 codes (1e8 strings are
    never made; its dictionary is compared once, whole), a null as its
    validity lane says and not as a NaN a conversion put there (``None``:
    the column has no such lane). Every shard's copy to the host is asked
    for before the first is waited on, so the chips' transfers overlap
    (6 GB a result at the cell's size)."""
    counts = np.asarray(table.row_counts)
    columns = {name: table.column(name) for name in table.column_names}
    for col in columns.values():
        for array in (col.data, col.valid):
            if array is not None:
                for shard in array.addressable_shards:
                    shard.data.copy_to_host_async()
    values, nulls, dictionaries = {}, {}, {}
    for name, col in columns.items():
        values[name] = _live(col.data, counts)
        nulls[name] = None if col.valid is None else ~_live(col.valid, counts)
        dictionaries[name] = col.dictionary
    return values, nulls, dictionaries


def _compare(table, ref: dict, data: dict, limit: float, chk_limit: float,
             prefix: str) -> list:
    q = ref["q"]
    x, right = data[q.get("left", "x")], data[q["right"]]
    n = int(table.row_count)
    numbers = [
        Number(prefix + "rows_gap", abs(n - ref["rows"]), 0),
        Number(prefix + "columns_wrong",
               int(table.column_names != ref["columns"]), 0),
    ]
    if numbers[1].value:
        return numbers
    got, null_of, dictionaries = _host_columns(table)
    # a string column comes out over the dictionary it was loaded with
    # (a program that loaded a factor's codes as integers has none on
    # either side)
    loaded = _RUN.get("tables", {})
    dictionaries_wrong = 0
    for side, names in ((q.get("left", "x"), ref["l_names"]),
                        (q["right"], ref["r_names"])):
        for name, c in zip(names, data[side]):
            if side not in loaded:
                continue
            want, have = loaded[side].column(c).dictionary, dictionaries[name]
            dictionaries_wrong += (want is None) != (have is None) or not (
                have is want or np.array_equal(have, want)
            )
    numbers.append(Number(
        prefix + "dictionaries_wrong", int(dictionaries_wrong), 0))

    def nulls(name, at):
        lane = null_of[name]
        return np.zeros(at.stop - at.start, bool) if lane is None else lane[at]

    left_join = q["how"] == "left"
    seen = np.zeros(len(ref["part"]), np.uint8)
    l_ints = [(name, x[c]) for name, c in zip(ref["l_names"], x)
              if x[c].dtype.kind != "f"]
    r_ints = [(name, right[c]) for name, c in zip(ref["r_names"], right)
              if right[c].dtype.kind != "f"]
    xk, xv1, rv2 = x[q["on"]], x["v1"], right["v2"]

    def block(at):
        """One block of the result's rows: the row of x each repeats (by
        the id that names it), its partner, and every count and gap."""
        rid = got[ref["row_id"]][at]
        known = ~nulls(ref["row_id"], at) & (rid >= 0) & (
            rid < len(ref["row_of"]))
        row = ref["row_of"][np.where(known, rid, 0).astype(np.int64)]
        known &= row >= 0
        row = np.where(known, row, 0)
        seen[row[known]] = 1
        part = ref["part"][row]
        matched = known & (part >= 0)
        at_right = np.maximum(part, 0)
        # nulls: the right side's columns null exactly where the key has
        # no partner, all of them together; the left side's never
        r_null = np.stack([nulls(c, at) for c in ref["r_names"]])
        nulls_wrong = int((r_null != ~matched).any(axis=0).sum()) + sum(
            int(nulls(c, at).sum()) for c in ref["l_names"]
        )
        # keys: the row's own left columns; beside a matched row its
        # partner's own integer columns
        wrong = int((~known).sum())
        for name, want in l_ints:
            wrong += int((got[name][at][known] != want[row[known]]).sum())
        for name, want in r_ints:
            ok = matched & ~r_null[ref["r_names"].index(name)]
            wrong += int((got[name][at][ok] != want[at_right[ok]]).sum())
        v1 = got["v1"][at]
        v2 = np.where(matched, got["v2"][at], 0.0)
        return (
            int(r_null.all(axis=0).sum()), nulls_wrong, wrong,
            rel_gap(v1[known], xv1[row[known]]),
            rel_gap(v2[matched], rv2[at_right[matched]]),
            _key_sums(xk[row], matched, v1, v2, ref["space"]),
            blocked_sum(v1), blocked_sum(v2),
        )

    found = _over_blocks(block, n)
    numbers.append(Number(
        prefix + "unmatched_gap",
        abs(sum(f[0] for f in found)
            - (ref["unmatched"] if left_join else 0)), 0,
    ))
    numbers.append(Number(
        prefix + "nulls_wrong", sum(f[1] for f in found), 0))
    # every row of x that must come out did (with the rows' count right
    # and no other row named, each came out once)
    expected = np.ones(len(seen), bool) if left_join else ref["part"] >= 0
    numbers.append(Number(
        prefix + "keys_wrong",
        sum(f[2] for f in found) + int((seen.view(bool) != expected).sum()),
        0,
    ))
    world = table.ctx.world_size
    if world > 1:
        # the cell's own query under the name inner_join gives it
        name = "join." if prefix == "h2o_join." else prefix
        numbers.append(Number(
            name + "shards_wrong", shards_wrong(table, world, ref["rows"]), 0
        ))
    if any(num.value for num in numbers):
        return numbers  # the values below need the right rows to mean anything
    numbers.append(Number(
        prefix + "v1_row_relgap", max([f[3] for f in found] or [0.0]), limit))
    numbers.append(Number(
        prefix + "v2_row_relgap", max([f[4] for f in found] or [0.0]), limit))
    sums = sum(f[5] for f in found)
    wrong_rows = int((sums[0] != ref["per_key"][0]).sum())
    numbers.append(Number(prefix + "key_rows_wrong", wrong_rows, 0))
    for i, name in ((1, "v1"), (2, "v2"), (3, "cross")):
        numbers.append(Number(
            f"{prefix}{name}_sum_relgap",
            rel_gap(sums[i], ref["per_key"][i]), limit,
        ))
    for i, name in ((6, "v1"), (7, "v2")):
        total = sum(f[i] for f in found)
        numbers.append(Number(
            f"{prefix}chk_{name}_relgap",
            rel_gap(np.array([total]), np.array([ref["chk"][i - 6]])),
            chk_limit,
        ))
    return numbers


def _route_counts() -> tuple:
    """``(replicate, shuffle)`` bumps of the process so far, ``None`` where
    the checkout has no such counter (a commit from before the routes)."""
    try:
        from cylon_tpu.obs.metrics import STABLE_METRICS
        from cylon_tpu.utils import tracing
    except ImportError:
        return None
    if "join.route.replicate" not in STABLE_METRICS:
        return None
    return (
        tracing.get_count("join.route.replicate"),
        tracing.get_count("join.route.shuffle"),
    )


def _once_a_run(config: dict, limit: float, chk_limit: float) -> list:
    """Questions 2 and 1 through the same tables and the same call,
    outside every timing, and the route every query of the run took."""
    data, tables = _RUN["data"], _RUN["tables"]
    numbers = []
    for q in _RUN["params"].get("once_a_run", ()):
        ref = _reference(data, {**q, "row_id": _RUN["params"]["row_id"]})
        out = _ask(tables, q)
        numbers.extend(_compare(
            out, ref, data, limit, chk_limit, f"h2o_join.{q['name']}_"
        ))
    if tables["x"].ctx.world_size > 1:
        # since ``build``: question 3 (warm-ups and window) and the two
        # above, every one of them on the replicate route
        took = [now - then
                for now, then in zip(_route_counts(), _RUN["routes"])]
        numbers.append(Number("h2o_join.shuffle_routes", took[1], 0))
        numbers.append(Number(
            "h2o_join.replicate_routes_missing", int(took[0] < 3), 0))
    return numbers


def compare(table, ref: dict, config: dict) -> list:
    precision = config["guarantees"]["value_precision"]
    limit, chk_limit = VALUE_LIMIT[precision], CHK_LIMIT[precision]
    numbers = _compare(table, ref, _RUN["data"], limit, chk_limit, "h2o_join.")
    if "tables" in _RUN and not _RUN.get("checked_once"):
        _RUN["checked_once"] = True
        numbers.extend(_once_a_run(config, limit, chk_limit))
    return numbers
