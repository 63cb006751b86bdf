"""Generator ``zipf_fk``: a fact table whose foreign key is Zipf against a
dimension of unique keys (Blanas, Li and Patel, SIGMOD 2011: the build
relation R and the probe relation S of the evaluation's dataset).

``right`` (R, build): the keys 0 .. N-1 once each, in an order drawn from
the seed, and a payload. ``left`` (S, probe): keys drawn by inverse CDF
from the exact finite Zipf law over the N ranks with the configuration's
exponent (0 is the uniform foreign key), rank r being the key r - 1, rows
in the order drawn; and a payload. Every probe row has exactly one
partner. The rank-to-key map is fixed and NOT drawn from the seed, so the
hot keys, and with them every capacity of the program's plan, are the same
on every seed. The configuration's ``assumed`` lists what the source
leaves open.
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: draws a block of the inverse-CDF search
SEARCH_BLOCK = 1 << 20
#: probe rows a build row (the source's 256M : 16M)
RATIO = 16
#: the build payload lies on a grid of 2**-GRID_BITS, so that the sum of
#: any number of copies of it up to 2**(53 - GRID_BITS) = 33,554,432 is
#: exact in float64 in whatever order they are added (the configuration's
#: ``assumed`` says why); float32 keeps 24 bits, so the control still
#: changes it
GRID_BITS = 28


def sizes(config: dict, rows: int | None) -> tuple:
    """(build rows, probe rows): the configuration's, or for the
    rehearsal's ``rows`` (the probe rows) in the source's ratio."""
    if rows is None:
        return int(config["rows"]["right"]), int(config["rows"]["left"])
    return max(1, int(rows) // RATIO), int(rows)


def zipf_cdf(n: int, s: float) -> np.ndarray:
    """The exact finite Zipf law over ranks 1..n: P(r) = r**-s / H(n, s)."""
    weights = np.arange(1, n + 1, dtype=np.float64) ** -float(s)
    cdf = np.cumsum(weights)
    return cdf / cdf[-1]


def inverse_cdf(cdf: np.ndarray, u: np.ndarray) -> np.ndarray:
    """The 0-based rank of every draw: the binary search in blocks over a
    few threads (numpy drops the lock inside it), which changes no value;
    the millions of draws are most of a run's data set-up."""
    out = np.empty(len(u), np.int64)
    blocks = [(a, min(a + SEARCH_BLOCK, len(u)))
              for a in range(0, len(u), SEARCH_BLOCK)]

    def search(block):
        a, b = block
        out[a:b] = np.searchsorted(cdf, u[a:b], side="right")

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(search, blocks))
    return out


def make(config: dict, seed: int, rows: int | None = None) -> dict:
    """``{"left": {k, v}, "right": {k, w}}`` for ``config``."""
    build, probe = sizes(config, rows)
    (lk, lk_t), (lv, lv_t) = config["tables"]["left"].items()
    (rk, rk_t), (rv, rv_t) = config["tables"]["right"].items()
    rng = np.random.default_rng(seed)
    # the draws, in this order (part of the configuration's ``assumed``)
    right_keys = rng.permutation(build)
    right_vals = rng.integers(0, 1 << GRID_BITS, build) / float(1 << GRID_BITS)
    u = rng.random(probe)
    left_vals = rng.random(probe)
    keys = inverse_cdf(zipf_cdf(build, config["zipf_exponent"]), u)
    # u < 1 and the CDF ends at 1, so a rank is at most build - 1; the
    # minimum guards the last float of the normalised sum
    np.minimum(keys, build - 1, out=keys)

    def typed(a, dtype):
        return a.astype(dtype, copy=False)

    return {
        "left": {lk: typed(keys, lk_t), lv: typed(left_vals, lv_t)},
        "right": {rk: typed(right_keys, rk_t), rv: typed(right_vals, rv_t)},
    }
