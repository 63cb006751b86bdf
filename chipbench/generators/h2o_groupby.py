"""Generator ``h2o_groupby``: the columns question 5 reads of the
h2oai/db-benchmark group-by dataset ``G1_<N>_<K>_0_0``
(``_data/groupby-datagen.R``: no NAs, unsorted).

``id6`` an integer uniform over 1..N/K, ``v1`` over 1..5, ``v2`` over
1..15 (R's ``sample(n, N, TRUE)``: with replacement, independent of every
other column), ``v3 = round(runif(N, max = 100), 6)``. R's integers are
int32, ``v3`` is the float64 nearest the six-decimal value. The other five
id columns are not made (the configuration's ``reduced``: a deployment that
asks question 5 loads the four columns it reads).

The rows are made in blocks of ``BLOCK``, each block from a generator of
its own seeded by ``(seed, block number)`` and drawing its four columns in
the table's order, so the blocks are independent of each other and are made
on several threads (numpy draws without the interpreter's lock): half a
billion rows would take a single stream a minute of every run's set-up.
The configuration's ``assumed`` says so.
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: rows a block
BLOCK = 1 << 22
#: (low, high) of the integer value columns, both ends drawn
RANGES = {"v1": (1, 5), "v2": (1, 15)}
#: ``v3`` is uniform under this, rounded to this many decimals
V3_MAX, V3_DECIMALS = 100.0, 6


def ids_of(config: dict, rows: int) -> int:
    """Distinct values ``id6`` draws from: N/K, as the source's N/K."""
    return max(1, int(rows) // int(config["K"]))


def make(config: dict, seed: int, rows: int | None = None) -> dict:
    """``{"x": {id6, v1, v2, v3}}`` for ``config``; ``rows`` overrides the
    configuration's N (the CPU rehearsal's tiny size), N/K following it."""
    rows = int(config["rows"] if rows is None else rows)
    ids = ids_of(config, rows)
    schema = config["tables"]["x"]
    out = {name: np.empty(rows, kind) for name, kind in schema.items()}

    def block(b: int):
        at = slice(b * BLOCK, min((b + 1) * BLOCK, rows))
        n = at.stop - at.start
        rng = np.random.default_rng((int(seed), b))
        # the draws, in this order (part of the configuration's ``assumed``)
        out["id6"][at] = rng.integers(1, ids + 1, n, dtype=np.int32)
        for name, (lo, hi) in RANGES.items():
            out[name][at] = rng.integers(lo, hi + 1, n, dtype=np.int32)
        out["v3"][at] = np.round(rng.random(n) * V3_MAX, V3_DECIMALS)

    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        list(pool.map(block, range(-(-rows // BLOCK))))
    return {"x": out}
