"""Query ``inner_join``: ``left.distributed_join(right, on=k, how="inner")``.

The call, its plain reference, the comparison that decides ``correct`` and
the least bytes the query must move. The reference and the checks are
copied from ``chip_smoke.py`` (``run_ops``), which PR 22 proved on the
chip; they read only the seeded arrays, never anything the program made.
"""
import numpy as np

from chipbench.checks import Number, rel_gap

#: limit on the per-key sums' worst relative gap, by the precision the
#: configuration states. float64: a TPU holds a float64 within ~1e-15 of
#: what was loaded and a key's sum adds a few such values; float32 values
#: in its place are off by up to 2**-24 = 6e-8. PERF.md section 2 has the
#: readings the limit was set from.
VALUE_LIMIT = {"float64": 1e-11, "float32": 1e-5}


def build(tables: dict, params: dict):
    left, right = tables[params["left"]], tables[params["right"]]
    on, how = params["on"], params["how"]

    def call():
        return left.distributed_join(right, on=on, how=how)

    return call


def input_rows(data: dict, params: dict) -> int:
    """Rows a query takes in: both sides of the join."""
    on = params["on"]
    return len(data[params["left"]][on]) + len(data[params["right"]][on])


def least_bytes(data: dict, params: dict, out_rows: int) -> int:
    """Bytes the join cannot avoid moving through HBM: every input column
    read once, every output column (both keys, both values) written once."""
    sides = [data[params["left"]], data[params["right"]]]
    read = sum(a.nbytes for side in sides for a in side.values())
    out_row = sum(a.dtype.itemsize for side in sides for a in side.values())
    return read + out_rows * out_row


def reference(data: dict, params: dict) -> dict:
    """Per-key counts and sums by bincount: what an inner join on uniform
    integer keys must hold, whatever order it emits rows in."""
    on = params["on"]
    left, right = data[params["left"]], data[params["right"]]
    (lval,) = [c for c in left if c != on]
    (rval,) = [c for c in right if c != on]
    lk, rk = left[on], right[on]
    space = int(max(lk.max(), rk.max())) + 1
    cnt_l = np.bincount(lk, minlength=space)
    cnt_r = np.bincount(rk, minlength=space)
    sum_l = np.bincount(lk, weights=left[lval].astype(np.float64), minlength=space)
    sum_r = np.bincount(rk, weights=right[rval].astype(np.float64), minlength=space)
    pairs = cnt_l * cnt_r
    return {
        "space": space,
        "columns": sorted([f"{on}_x", f"{on}_y", lval, rval]),
        "key": f"{on}_x", "key_other": f"{on}_y", "lval": lval, "rval": rval,
        "pairs": pairs,
        "rows": int(pairs.sum()),
        "sums": {lval: sum_l * cnt_r, rval: sum_r * cnt_l},
        "cross": sum_l * sum_r,
    }


def compare(table, ref: dict, config: dict) -> list:
    """Numbers compared, each beside its limit. Exact ones have limit 0."""
    limit = VALUE_LIMIT[config["guarantees"]["value_precision"]]
    n = int(table.row_count)
    got = table.to_pydict()
    columns_wrong = sorted(got) != ref["columns"]
    numbers = [
        Number("join.rows_gap", abs(n - ref["rows"]), 0),
        Number("join.columns_wrong", int(columns_wrong), 0),
    ]
    if columns_wrong:
        return numbers
    k = got[ref["key"]]
    in_space = (k >= 0) & (k < ref["space"])
    per_key = np.bincount(k[in_space], minlength=ref["space"])
    numbers.append(Number(
        "join.keys_wrong",
        int((~in_space).sum())
        + int((k != got[ref["key_other"]]).sum())
        + int((per_key != ref["pairs"]).sum()),
        0,
    ))
    if numbers[-1].value or numbers[0].value:
        return numbers  # the sums below need the right rows to mean anything
    lv = got[ref["lval"]].astype(np.float64)
    rv = got[ref["rval"]].astype(np.float64)
    for name, weights, want in (
        (ref["lval"], lv, ref["sums"][ref["lval"]]),
        (ref["rval"], rv, ref["sums"][ref["rval"]]),
        ("cross", lv * rv, ref["cross"]),
    ):
        sums = np.bincount(k, weights=weights, minlength=ref["space"])
        numbers.append(
            Number(f"join.{name}_sum_relgap", rel_gap(sums, want), limit)
        )
    world = table.ctx.world_size
    if world > 1:
        numbers.append(Number(
            "join.shards_wrong", shards_wrong(table, world, ref["rows"]), 0
        ))
    return numbers


def shards_wrong(table, world: int, rows: int) -> int:
    """Faults in how the result is spread: every column sharded over all
    the context's devices with one padded length, every shard holding rows,
    the shards' rows summing to the result's (chip_smoke's _check_spread)."""
    wrong = 0
    for name in table.column_names:
        shards = table.column(name).data.addressable_shards
        wrong += len({s.device for s in shards}) != world
        wrong += len({s.data.shape for s in shards}) != 1
    per_shard = np.asarray(table.row_counts)
    wrong += int((per_shard <= 0).sum()) + (int(per_shard.sum()) != rows)
    return int(wrong)
