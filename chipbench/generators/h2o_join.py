"""Generator ``h2o_join``: the tables of the h2oai/db-benchmark join task
that questions 1 to 3 read, dataset ``J1_<N>_NA_0_0`` with its right-hand
tables ``J1_<N>_<N/1e6>_0_0`` (small) and ``J1_<N>_<N/1e3>_0_0`` (medium)
(``_data/join-datagen.R``: no NAs, unsorted).

The law (``tests/h2o_join_reference.py`` is the same law from one plain
stream; this module is its own copy, made to build 1e8 rows in seconds):

* a key level of n values shuffles the integers 1..n + n/10 and gives the
  first 0.9 n to both sides, the next n/10 to ``x`` alone, the last n/10
  to the right tables alone (:func:`split_keys`);
* a column over a level holds each of its side's n keys at least once and
  draws the rest uniformly with replacement, the whole shuffled: here the
  n sure occurrences are written at n distinct positions drawn without
  replacement over uniform draws, which is the same distribution as the
  source's ``sample(c(keys, sample(keys, size - n, TRUE)))``
  (:func:`sample_all`);
* ``x`` has ``id1`` (level N/1e6), ``id2`` (N/1e3), ``id3`` (N) and
  ``v1 = round(runif(N, max = 100), 6)``; ``medium`` has ``id1``, ``id2``
  (each of its level's keys ONCE) and ``v2``; ``small`` has ``id1`` (each
  once) and ``v2``.

``x.id3`` holds N distinct ids out of 1..1.1 N, which the source shuffles;
here it is the affine bijection ``(a i + b) mod 1.1 N`` of the row number
(``a`` coprime, from the seed): no question the cell asks reads ``id3``
(``big`` is not made, the configuration's ``reduced``), the comparison
uses it as what it is at the source, a column that names the row, and a
true shuffle of 1.1e8 integers is seconds of one thread in every run's
set-up.

The string twins (the source's factors: ``x.id4``-``id6`` =
``sprintf("id%d", id1..id3)``, ``medium.id4``/``id5``, ``small.id4``) are
made as what a dataframe engine holds of a factor: int32 codes over the
sorted dictionary of the level's ``"id%d"`` strings
(:func:`level_dictionary`; the code of ``"id<k>"`` is the place of ``k``
among 1..n + n/10 in the order of their decimal strings, by arithmetic),
the array's dtype carrying the dictionary
(``np.dtype(int32, metadata={"dictionary": ...})``). A program that
reads that (``Column.encode_host`` from PR 48 on) loads a string column
without making 1e8 strings; one that does not loads the same codes as an
int32 column: the same bytes a row on the device either way.

The rows of ``x`` are made in blocks of ``BLOCK``, each block from a
generator of its own seeded by ``(seed, block number)``, on several threads
(numpy draws without the interpreter's lock), as ``h2o_groupby.py``. The
key levels, the right tables and the sure positions come from
``default_rng((seed, -1))``-like streams of their own (``_stream``). The
configuration's ``assumed`` says so.
"""
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: rows a block
BLOCK = 1 << 22
V_MAX, V_DECIMALS = 100.0, 6
#: a factor column and the integer id it is the ``"id%d"`` twin of
TWIN_OF = {"id4": "id1", "id5": "id2", "id6": "id3"}
#: streams beside the blocks', by name (a block's stream is (seed, b))
_STREAMS = {"levels": 1, "right": 2, "sure": 3}


def _stream(seed: int, name: str):
    return np.random.default_rng((int(seed), 1 << 40, _STREAMS[name]))


def levels_of(config: dict, rows: int) -> tuple:
    """Keys of the small and the medium level at N = ``rows``: N over the
    source's divisors, one at least."""
    small, medium = (int(d) for d in config["level_divisors"])
    return max(1, rows // small), max(1, rows // medium)


def one_side_only(n: int) -> int:
    """Keys of a level of ``n`` that one side alone holds: a tenth (one
    where a tenth rounds to none and the level has a key to spare, so that
    a rehearsal of four keys still has a row without a partner)."""
    return int(round(0.1 * n)) or int(n > 1)


def split_keys(rng, n: int) -> tuple:
    """``(x's keys, the right tables' keys)`` of a level: n each, int32."""
    extra = one_side_only(n)
    key = (rng.permutation(n + extra) + 1).astype(np.int32)
    common = key[: n - extra]
    return (
        np.concatenate([common, key[n - extra: n]]),
        np.concatenate([common, key[n: n + extra]]),
    )


def sample_all(rng, keys: np.ndarray, size: int) -> np.ndarray:
    """``size`` values of ``keys``, each at least once (one stream)."""
    out = keys[rng.integers(0, len(keys), size)]
    out[rng.choice(size, len(keys), replace=False)] = rng.permutation(keys)
    return out


def level_dictionary(top: int) -> tuple:
    """``(dictionary, code of k at k - 1)`` of a level whose keys are
    1..``top``: the strings ``"id<k>"`` sorted, and each integer's place
    among them, which is its place among 1..``top`` in the order of their
    decimal strings (``"10" < "100" < "2"``). No string is made or sorted:
    the place is counted (for every length of string, the integers of that
    length whose string sorts before k's) and the code points are written
    at it, a run of integers of one digit count at a time (so that every
    divisor is a constant), on threads."""
    lengths = len(str(top))
    width = 2 + lengths
    code = np.empty(top, np.int32)
    points = np.zeros((top, width), np.uint32)
    runs = []
    for d in range(1, lengths + 1):
        lo, hi = 10 ** (d - 1), min(10 ** d, top + 1)
        runs += [(d, i, min(i + BLOCK, hi)) for i in range(lo, hi, BLOCK)]

    def run(of):
        d, lo, hi = of
        k = np.arange(lo, hi, dtype=np.int64)
        place = np.zeros(len(k), np.int64)
        for length in range(1, lengths + 1):
            first = 10 ** (length - 1)
            if length <= d:
                # the integers of this length below k's first ``length``
                # digits, and that prefix itself where it is a proper one
                place += k // 10 ** (d - length) - first + (length < d)
            else:
                # the longer integers below k followed by zeros
                below = np.minimum(k * 10 ** (length - d), top + 1) - first
                place += np.maximum(below, 0)
        code[lo - 1: hi - 1] = place
        row = np.zeros((len(k), width), np.uint32)
        row[:, 0], row[:, 1] = ord("i"), ord("d")
        for j in range(d):
            row[:, 2 + j] = k // 10 ** (d - 1 - j) % 10 + ord("0")
        points[place] = row

    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        list(pool.map(run, runs))
    return points.view(f"<U{width}").reshape(top), code


def twin(ids: np.ndarray, level: tuple) -> np.ndarray:
    """The factor twin of an integer id column: its codes over the level's
    dictionary, which the dtype carries."""
    dictionary, code = level
    kind = np.dtype(np.int32, metadata={"dictionary": dictionary})
    return code[ids - 1].astype(kind)


def values(rng, rows: int) -> np.ndarray:
    return np.round(rng.random(rows) * V_MAX, V_DECIMALS)


def make(config: dict, seed: int, rows: int | None = None) -> dict:
    """``{"x", "medium", "small"}`` for ``config``; ``rows`` overrides the
    configuration's N (the CPU rehearsal's tiny size), the levels
    following it."""
    rows = int(config["rows"] if rows is None else rows)
    n1, n2 = levels_of(config, rows)
    rng = _stream(seed, "levels")
    (x1, r1), (x2, r2) = split_keys(rng, n1), split_keys(rng, n2)
    # id3: N distinct ids of 1..N + N/10, by an affine bijection of the row
    space3 = rows + one_side_only(rows)
    mult = int(rng.integers(1, space3))
    while math.gcd(mult, space3) != 1:
        mult += 1
    shift = int(rng.integers(0, space3))

    x = {name: np.empty(rows, kind)
         for name, kind in config["tables"]["x"].items() if kind != "string"}

    def block(b: int):
        at = slice(b * BLOCK, min((b + 1) * BLOCK, rows))
        n = at.stop - at.start
        brng = np.random.default_rng((int(seed), b))
        # the draws, in this order (part of the configuration's ``assumed``)
        x["id1"][at] = x1[brng.integers(0, n1, n)]
        x["id2"][at] = x2[brng.integers(0, n2, n)]
        row = np.arange(at.start, at.stop, dtype=np.int64)
        x["id3"][at] = (row * mult + shift) % space3 + 1
        x["v1"][at] = values(brng, n)

    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        list(pool.map(block, range(-(-rows // BLOCK))))
    # every key of x's side at least once: the sure occurrences
    rng = _stream(seed, "sure")
    for name, keys in (("id1", x1), ("id2", x2)):
        x[name][rng.choice(rows, len(keys), replace=False)] = (
            rng.permutation(keys)
        )

    rng = _stream(seed, "right")
    medium = {
        "id1": sample_all(rng, r1, n2), "id2": rng.permutation(r2),
        "v2": values(rng, n2),
    }
    small = {"id1": rng.permutation(r1), "v2": values(rng, n1)}
    # the factor twins, in the source's column order
    levels = [level_dictionary(n + one_side_only(n)) for n in (n1, n2)]
    levels.append(level_dictionary(space3))
    tables = {"x": x, "medium": medium, "small": small}
    for name, cols in tables.items():
        for c, of in TWIN_OF.items():
            if c in config["tables"][name]:
                cols[c] = twin(cols[of], levels[int(of[2]) - 1])
        tables[name] = {c: cols[c] for c in config["tables"][name]}
    return tables
