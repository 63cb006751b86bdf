"""The comparison that decides ``correct``: numbers, each beside a limit."""
from typing import NamedTuple

import numpy as np


class Number(NamedTuple):
    """One number compared: a run is correct while every value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        # a NaN value fails: NaN <= limit is False
        return bool(self.value <= self.limit)


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Worst |got - want| over |want| (over 1 where want is 0)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if got.size == 0:
        return 0.0
    scale = np.where(want == 0, 1.0, np.abs(want))
    gap = np.abs(got - want) / scale
    return float(np.max(np.where(np.isnan(gap), np.inf, gap)))


def lower_precision(data: dict, config: dict) -> dict:
    """The control's data: every column of the precision the configuration
    states, rounded through the nearest precision below it (float32 for
    float64) and handed back in the stated dtype, so that the same programs
    run on it. What a later PR that held values in float32 would produce."""
    stated = np.dtype(config["guarantees"]["value_precision"])
    lower = np.dtype(config["lower_precision"])
    return {
        t: {
            c: a.astype(lower).astype(stated) if a.dtype == stated else a
            for c, a in cols.items()
        }
        for t, cols in data.items()
    }
