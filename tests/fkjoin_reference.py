"""Row-level reference for a join of a fact table to a dimension of
unique keys, in plain numpy: every probe row finds its one build row by
``searchsorted`` into the sorted build keys. Where ``chipbench``'s
per-key sums say THAT a key's rows are wrong, this names the row."""
import numpy as np


def join_rows(probe: dict, build: dict, on: str, how: str = "inner") -> dict:
    """The joined rows of ``probe`` (``{on, value}``) and ``build``
    (``{on, value}``, its keys unique) in the canonical order (key, then the
    probe's value): ``{on: keys, <probe value>: ..., <build value>: ...,
    "matched": bool}``. ``how`` is ``inner`` (probe rows without a build
    row are dropped) or ``left`` (they stay, their build value NaN)."""
    (pval,) = [c for c in probe if c != on]
    (bval,) = [c for c in build if c != on]
    bk = np.asarray(build[on])
    order = np.argsort(bk, kind="stable")
    sorted_keys = bk[order]
    assert (np.diff(sorted_keys) > 0).all(), "build keys must be unique"
    pk = np.asarray(probe[on])
    at = np.minimum(np.searchsorted(sorted_keys, pk), len(sorted_keys) - 1)
    matched = sorted_keys[at] == pk
    joined = np.where(
        matched, np.asarray(build[bval], np.float64)[order][at], np.nan
    )
    keep = matched if how == "inner" else np.ones(len(pk), bool)
    keys = pk[keep]
    vals = np.asarray(probe[pval], np.float64)[keep]
    canon = np.lexsort([vals, keys])
    return {
        on: keys[canon], pval: vals[canon], bval: joined[keep][canon],
        "matched": matched[keep][canon],
    }


def first_wrong_row(got: dict, want: dict, on: str):
    """``None`` where the program's joined columns (``{on}_x``, ``{on}_y``
    and the two values, nulls as NaN) hold exactly ``want``'s rows, else a
    sentence naming the first row, in the canonical order, that differs."""
    (pval, bval) = [c for c in want if c not in (on, "matched")]
    kx = np.asarray(got[f"{on}_x"])
    if len(kx) != len(want[on]):
        return f"{len(kx)} rows, the reference has {len(want[on])}"
    pv = np.asarray(got[pval], np.float64)
    canon = np.lexsort([pv, kx])
    ky = np.asarray(got[f"{on}_y"], np.float64)[canon]
    columns = (
        (f"{on}_x", kx[canon].astype(np.float64), want[on].astype(np.float64)),
        (pval, pv[canon], want[pval]),
        (bval, np.asarray(got[bval], np.float64)[canon], want[bval]),
        (f"{on}_y", ky, np.where(want["matched"], want[on], np.nan)),
    )
    for name, have, ref in columns:
        wrong = ~((have == ref) | (np.isnan(have) & np.isnan(ref)))
        if wrong.any():
            i = int(np.argmax(wrong))
            return (
                f"row {i} of the canonical order (key {want[on][i]}, "
                f"{pval} {want[pval][i]!r}): {name} is {have[i]!r}, "
                f"the reference has {ref[i]!r}"
            )
    return None
