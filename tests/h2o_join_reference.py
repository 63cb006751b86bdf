"""Plain reference of the h2oai/db-benchmark join task.

The public benchmark by which pandas, dask, data.table, polars, cuDF, Spark
and DuckDB dataframes are compared (github.com/h2oai/db-benchmark). What is
known of the source and kept here (the repository has no copy of it; the
chip configuration lists the same under ``assumed``):

* **The data** (``_data/join-datagen.R``; a dataset is named
  ``J1_<N>_<rows of the right table>_<NAs>_<sorted>``). Three key levels
  of n = N/1e6, N/1e3 and N values. For each the generator shuffles the
  integers 1..1.1 n and gives the first 0.9 n to both sides, the next
  0.1 n to the left table ``x`` alone and the last 0.1 n to the right
  tables alone (:func:`split_keys`). A column over a level holds each of
  its side's n keys at least once and draws the rest uniformly with
  replacement, the whole shuffled (:func:`sample_all`); where the table has
  exactly n rows that is each key ONCE, in shuffled order. ``x`` (N rows)
  has ``id1``, ``id2``, ``id3`` over the three levels, ``id4``-``id6``
  their string twins (``"id%d"``) and ``v1 = round(runif(N, max = 100),
  6)``; ``small`` (N/1e6 rows) has ``id1, id4, v2``; ``medium`` (N/1e3
  rows) ``id1, id2, id4, id5, v2``; ``big`` (N rows) all six ids and
  ``v2``. So every right table is unique on the id of its own level, a
  row of ``x`` has at most one partner there, about 90% of ``x``'s rows
  find one, and a tenth of the right table's rows match nothing.
* **The questions** (each solution's ``join-*`` script), q1 to q5 of
  :data:`QUESTIONS`; every solution then takes ``sum(v1)``, ``sum(v2)`` of
  the result (:func:`chk`).

:func:`answer` is plain numpy over the arrays alone (the right table's
keys sorted, ``np.searchsorted``, a matched mask; a null is a mask beside
the value) and takes nothing the program made. The key levels are
arguments so that a small N keeps the source's ratios between the tables
(N/1e6 is no row at N = 40,000).
"""
import numpy as np

#: question -> (right table, the key column of both sides, join type)
QUESTIONS = {
    "q1": ("small", "id1", "inner"),
    "q2": ("medium", "id2", "inner"),
    "q3": ("medium", "id2", "left"),
    "q4": ("medium", "id5", "inner"),
    "q5": ("big", "id3", "inner"),
}
#: the columns of each table, in the source's order
COLUMNS = {
    "x": ("id1", "id2", "id3", "id4", "id5", "id6", "v1"),
    "small": ("id1", "id4", "v2"),
    "medium": ("id1", "id2", "id4", "id5", "v2"),
    "big": ("id1", "id2", "id3", "id4", "id5", "id6", "v2"),
}
V_MAX, V_DECIMALS = 100.0, 6


def levels_of(n: int) -> tuple:
    """The source's three key levels at N = ``n``."""
    return (max(1, n // 1_000_000), max(1, n // 1_000), n)


def one_side_only(n: int) -> int:
    """Keys of a level of ``n`` that one side alone holds: a tenth (one
    where a tenth rounds to none and the level has a key to spare)."""
    return int(round(0.1 * n)) or int(n > 1)


def split_keys(rng, n: int) -> tuple:
    """``(x's keys, the right tables' keys)`` of a level of ``n`` values:
    n each, drawn from a shuffle of 1..n + one_side_only(n), the first
    ``n - one_side_only(n)`` of it common to both."""
    extra = one_side_only(n)
    key = rng.permutation(n + extra) + 1
    common = key[: n - extra]
    return (
        np.concatenate([common, key[n - extra: n]]),
        np.concatenate([common, key[n: n + extra]]),
    )


def sample_all(rng, keys: np.ndarray, size: int) -> np.ndarray:
    """``size`` values of ``keys``, each at least once, the rest uniform
    with replacement, the whole shuffled."""
    assert len(keys) <= size
    rest = rng.choice(keys, size - len(keys), replace=True)
    return rng.permutation(np.concatenate([keys, rest])).astype(np.int32)


def _twin(ids: np.ndarray) -> np.ndarray:
    return np.char.add("id", ids.astype(str))


def make(n: int, seed: int, levels: tuple = None) -> dict:
    """``{"x", "small", "medium", "big"}``, each a dict of columns in the
    source's order. ``levels`` are the three key levels' sizes (the rows
    of small, medium and big); the source's at ``None``."""
    rng = np.random.default_rng(seed)
    levels = levels_of(n) if levels is None else tuple(levels)
    sides = [split_keys(rng, m) for m in levels]

    def ids(side: int, rows: int, upto: int) -> dict:
        out = {}
        for i in range(upto):
            out[f"id{i + 1}"] = sample_all(rng, sides[i][side], rows)
        for i in range(upto):
            out[f"id{i + 4}"] = _twin(out[f"id{i + 1}"])
        return out

    def values(rows: int) -> np.ndarray:
        return np.round(rng.random(rows) * V_MAX, V_DECIMALS)

    tables = {"x": dict(ids(0, n, 3), v1=values(n))}
    for upto, (name, rows) in enumerate(
        zip(("small", "medium", "big"), levels), start=1
    ):
        tables[name] = dict(ids(1, rows, upto), v2=values(rows))
    return {
        t: {c: cols[c] for c in COLUMNS[t] if c in cols}
        for t, cols in tables.items()
    }


def output_names(left: tuple, right: tuple, suffixes=("_x", "_y")) -> tuple:
    """The result's column names: the left table's, then the right's, a
    name both have suffixed by side (the dataframe convention)."""
    both = set(left) & set(right)
    return (
        [c + suffixes[0] if c in both else c for c in left],
        [c + suffixes[1] if c in both else c for c in right],
    )


def partners(x_key: np.ndarray, r_key: np.ndarray) -> np.ndarray:
    """For every row of ``x`` the row of the right table with its key, -1
    where there is none. The right table is unique on the key."""
    order = np.argsort(r_key, kind="stable")
    sorted_keys = r_key[order]
    assert (sorted_keys[1:] != sorted_keys[:-1]).all(), "right key repeats"
    at = np.searchsorted(sorted_keys, x_key)
    at = np.minimum(at, len(sorted_keys) - 1)
    hit = sorted_keys[at] == x_key
    return np.where(hit, order[at], -1)


def answer(data: dict, question: str) -> dict:
    """The question's result with its rows in ``x``'s row order:
    ``columns`` (name -> values, a null row holding the right column's
    zero), ``nulls`` (name -> mask, for the right side's columns of a left
    join), ``x_rows`` (the row of ``x`` every result row repeats) and
    ``matched`` (whether it has a partner)."""
    right_name, key, how = QUESTIONS[question]
    x, right = data["x"], data[right_name]
    part = partners(x[key], right[key])
    keep = np.arange(len(part)) if how == "left" else np.flatnonzero(part >= 0)
    matched = part[keep] >= 0
    l_names, r_names = output_names(tuple(x), tuple(right))
    columns, nulls = {}, {}
    for name, c in zip(l_names, x):
        columns[name] = x[c][keep]
    for name, c in zip(r_names, right):
        got = right[c][np.maximum(part[keep], 0)]
        if how == "left":
            got = np.where(matched, got, np.zeros((), got.dtype))
            nulls[name] = ~matched
        columns[name] = got
    return {
        "rows": len(keep), "columns": columns, "nulls": nulls,
        "x_rows": keep, "matched": matched,
        "key": l_names[list(x).index(key)],
    }


def chk(result: dict) -> tuple:
    """The source's check of a result: ``sum(v1)``, ``sum(v2)`` (a null
    adds nothing)."""
    v2 = result["columns"]["v2"]
    return (
        float(result["columns"]["v1"].sum()),
        float(v2[result["matched"]].sum()),
    )


def per_key_sums(result: dict) -> dict:
    """Over the matched rows, by the join key: the rows and the sums of
    ``v1`` and ``v2`` a key, in key order."""
    m = result["matched"]
    keys, inv = np.unique(result["columns"][result["key"]][m], return_inverse=True)
    out = {"keys": keys, "rows": np.bincount(inv, minlength=len(keys))}
    for v in ("v1", "v2"):
        out[v] = np.bincount(
            inv, weights=result["columns"][v][m], minlength=len(keys)
        )
    return out
