"""Plain reference of the h2oai/db-benchmark group-by task.

The public benchmark by which pandas, dask, data.table, polars, cuDF, Spark
and DuckDB dataframes are compared (github.com/h2oai/db-benchmark). What is
known of the source and kept here:

* **The data** (``_data/groupby-datagen.R``; a dataset is named
  ``G1_<N>_<K>_<NAs>_<sorted>``): N rows of nine columns, every draw with
  replacement and independent of the others. ``id1`` and ``id2`` are the
  strings ``"id%03d"`` of 1..K, ``id3`` the strings ``"id%010d"`` of
  1..N/K; ``id4`` and ``id5`` integers uniform over 1..K, ``id6`` over
  1..N/K; ``v1`` an integer uniform over 1..5, ``v2`` over 1..15, ``v3 =
  round(runif(N, max = 100), 6)``. :func:`make` is that rule at any N and K
  from one ``numpy`` generator (R's integers are int32 here, the order of
  the draws is the columns').
* **The questions** (each solution's ``groupby-*`` script), q1 to q10 of
  :data:`QUESTIONS`. q8 (the two largest ``v3`` a group) needs a rank
  within a group (ROADMAP M8) and q9 (the squared correlation of ``v1`` and
  ``v2`` a group) an aggregate over a product of two columns: the engine
  has neither, they are listed in :data:`UNSUPPORTED` and are not faked.

:func:`answer` is plain numpy over the arrays alone (``np.unique``,
``np.bincount`` with float64 weights, ``np.minimum.at`` /
``np.maximum.at``, a lexsort and a loop over the groups for the median and
the deviation) and takes nothing the program made. A group adds about K
values (N rows over N/K ids), or N/K of them over K ids, which at the sizes
the tests use is a few thousand at most: a running float64 sum of so few
values under 100 is within a few 1e-15 of itself, so no blocked sum is
needed here (``tpch_q1``'s reference adds 29 million values a group and
does need one). An integer sum is taken through float64 weights and is
exact while it stays under 2**53.
"""
import numpy as np

#: question -> (group keys, the engine's ``agg`` mapping). q7 is asked as
#: ``max(v1) - min(v2)``: the two aggregates are the group-by, the
#: difference is arithmetic on its result (``range_v1_v2`` in the answer)
QUESTIONS = {
    "q1": (["id1"], {"v1": "sum"}),
    "q2": (["id1", "id2"], {"v1": "sum"}),
    "q3": (["id3"], {"v1": "sum", "v3": "mean"}),
    "q4": (["id4"], {"v1": "mean", "v2": "mean", "v3": "mean"}),
    "q5": (["id6"], {"v1": "sum", "v2": "sum", "v3": "sum"}),
    "q6": (["id4", "id5"], {"v3": ["median", "std"]}),
    "q7": (["id3"], {"v1": "max", "v2": "min"}),
    "q10": (
        ["id1", "id2", "id3", "id4", "id5", "id6"],
        {"v3": "sum", "v1": "count"},
    ),
}
UNSUPPORTED = {
    "q8": "largest two v3 by id6: a rank within a group (ROADMAP M8)",
    "q9": "cor(v1, v2)^2 by id2, id4: an aggregate over a product of two "
          "columns",
}


def make(n: int, k: int, seed: int) -> dict:
    """The nine columns of ``G1_<n>_<k>_0_0``, drawn in the columns' order."""
    rng = np.random.default_rng(seed)
    ids = max(1, n // k)

    def draw(hi):
        return rng.integers(1, hi + 1, n, dtype=np.int32)

    def named(width, codes):
        return np.char.add("id", np.char.zfill(codes.astype(str), width))

    return {
        "id1": named(3, draw(k)), "id2": named(3, draw(k)),
        "id3": named(10, draw(ids)),
        "id4": draw(k), "id5": draw(k), "id6": draw(ids),
        "v1": draw(5), "v2": draw(15),
        "v3": np.round(rng.random(n) * 100.0, 6),
    }


def groups(columns) -> tuple:
    """``(the distinct key tuples in lexicographic order, one array a key;
    every row's group number)``."""
    codes = np.zeros(len(columns[0]), np.int64)
    for col in columns:
        values, inverse = np.unique(col, return_inverse=True)
        codes = codes * len(values) + inverse.reshape(-1)
    distinct, first, inverse = np.unique(
        codes, return_index=True, return_inverse=True
    )
    return [col[first] for col in columns], inverse.reshape(-1)


def aggregate(op: str, values: np.ndarray, group: np.ndarray, n: int):
    """One aggregate over ``n`` groups, named as the engine names its ops."""
    count = np.bincount(group, minlength=n)
    if op == "count":
        return count
    if op in ("sum", "mean"):
        total = np.bincount(
            group, weights=values.astype(np.float64), minlength=n
        )
        if op == "mean":
            return total / count
        integer = np.issubdtype(values.dtype, np.integer)
        return total.astype(np.int64) if integer else total
    if op in ("min", "max"):
        out = np.full(n, values.max() if op == "min" else values.min())
        (np.minimum if op == "min" else np.maximum).at(out, group, values)
        return out
    if op in ("median", "std"):
        order = np.lexsort((values, group))
        parts = np.split(values[order].astype(np.float64), np.cumsum(count)[:-1])
        if op == "median":
            return np.array([np.median(p) for p in parts])
        return np.array([np.std(p, ddof=1) for p in parts])
    raise ValueError(f"no reference for {op!r}")


def answer(data: dict, by, agg: dict) -> dict:
    """``{column: array}`` of the group-by's result, groups in key order,
    aggregate columns named ``<column>_<op>`` as ``Table.groupby`` does."""
    keys, group = groups([data[k] for k in by])
    out = dict(zip(by, keys))
    for col, ops in agg.items():
        for op in [ops] if isinstance(ops, str) else ops:
            out[f"{col}_{op}"] = aggregate(op, data[col], group, len(keys[0]))
    return out


def question(data: dict, name: str) -> dict:
    """The answer to one of :data:`QUESTIONS` (q7 with its difference)."""
    by, agg = QUESTIONS[name]
    out = answer(data, by, agg)
    if name == "q7":
        out["range_v1_v2"] = out["v1_max"] - out["v2_min"]
    return out
