"""Generator ``uniform_keys``: the upstream join benchmark's two tables.

Copied from ``chip_smoke.py`` (``make_arrays``), which PR 22 proved on the
chip: integer keys drawn uniformly from [0, key space), one value column a
table in [0, 1). The dtypes, the rows and the key space come from the
configuration's file; the seed is the only other input.
"""
import numpy as np


def make(config: dict, seed: int, rows: int | None = None) -> dict:
    """``{table: {column: array}}`` for ``config``; ``rows`` overrides the
    configuration's (the CPU rehearsal's tiny size)."""
    rows = int(config["rows"] if rows is None else rows)
    space = rows if config["key_space"] == "rows" else int(config["key_space"])
    rng = np.random.default_rng(seed)
    # keys of every table first, then values, in the tables' order (the
    # order chip_smoke.py draws them in)
    keys = {
        t: rng.integers(0, space, rows) for t in config["tables"]
    }
    out = {}
    for t, schema in config["tables"].items():
        (kname, kdtype), (vname, vdtype) = schema.items()
        out[t] = {
            kname: keys[t].astype(kdtype),
            vname: rng.random(rows).astype(vdtype),
        }
    return out
