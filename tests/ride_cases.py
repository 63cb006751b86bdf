"""Tables whose payload columns ride a sort (``Table.sort``, the
speculative join's right sort), with the values a float32 detour or a lane
codec would lose: NaNs of two payloads, -0.0, both infinities, a subnormal,
int64's extremes. Built from physical arrays (``Table.from_encoded``), so a
NaN stays a value and a validity lane is what the schema says it is. The
comparisons are of bits, never of floats.
"""
from collections import OrderedDict

import numpy as np

import cylon_tpu as ct
from cylon_tpu.dtypes import DataType

SCHEMAS = ("int64", "float64", "nullable-float64", "mixed")

_F64 = np.array(
    [0x7FF8000000000001, 0xFFF80000DEADBEEF, 0x8000000000000000,
     0x7FF0000000000000, 0xFFF0000000000000, 0x0000000000000001],
    np.uint64,
).view(np.float64)
_I64 = np.array([np.iinfo(np.int64).min, np.iinfo(np.int64).max, -1, 0], np.int64)


def _f64(rng, rows):
    x = rng.normal(size=rows)
    at = rng.choice(rows, 4 * len(_F64), replace=False)
    x[at] = np.tile(_F64, 4)
    return x


def _i64(rng, rows):
    x = rng.integers(-(1 << 62), 1 << 62, rows, dtype=np.int64)
    x[rng.choice(rows, 4 * len(_I64), replace=False)] = np.tile(_I64, 4)
    return x


def columns(rng, rows, schema, tag):
    """``{name: (data, valid | None)}``: the key ``k`` (int64, each value
    about four times, so the order within a key shows stability) and the
    payload columns of ``schema``, their names ending in ``tag``."""
    cols = OrderedDict(k=(rng.integers(0, rows // 4, rows).astype(np.int64), None))
    if schema == "int64":
        cols["a" + tag] = (_i64(rng, rows), None)
    elif schema == "float64":
        cols["f" + tag] = (_f64(rng, rows), None)
    elif schema == "nullable-float64":
        cols["f" + tag] = (_f64(rng, rows), rng.random(rows) < 0.7)
    elif schema == "mixed":
        cols["i" + tag] = (rng.integers(-99, 99, rows).astype(np.int32), None)
        cols["f" + tag] = (_f64(rng, rows), rng.random(rows) < 0.7)
        cols["s" + tag] = (rng.normal(size=rows).astype(np.float32), None)
    elif schema.startswith("wide"):  # "wide5": five float64 columns
        for i in range(int(schema[4:])):
            cols[f"f{i}{tag}"] = (_f64(rng, rows), None)
    else:
        raise ValueError(schema)
    return cols


def table(ctx, cols):
    return ct.Table.from_encoded(ctx, OrderedDict(
        (name, (data, valid, DataType.from_numpy_dtype(data.dtype), None))
        for name, (data, valid) in cols.items()
    ))


def bits(data, valid=None):
    """A column as unsigned integers of its own width, zero under a null
    (what lies under a null is no value)."""
    data = np.ascontiguousarray(data)
    out = data.view(np.dtype(f"uint{8 * data.dtype.itemsize}"))
    return out if valid is None else np.where(valid, out, 0).astype(out.dtype)


def physical(t):
    """``{name: (data, valid | None)}`` of a table's live rows, shard after
    shard, in the physical encoding."""
    return OrderedDict((n, t._host_physical(n)) for n in t.column_names)


def assert_same_bits(got, want):
    """Two ``{name: (data, valid)}`` hold the same columns, validity lanes
    and bits, row for row."""
    assert list(got) == list(want)
    for name in want:
        (gd, gv), (wd, wv) = got[name], want[name]
        assert gd.dtype == wd.dtype and len(gd) == len(wd), name
        assert (gv is None) == (wv is None), name
        if wv is not None:
            assert (gv == wv).all(), name
        assert (bits(gd, gv) == bits(wd, wv)).all(), name


def ride_counts(tracing):
    """``(lanes, batches)`` summed over the rides counted so far."""
    got = tracing.report("sort.ride")
    return tuple(
        int(got[name]["rows"]) if name in got else 0
        for name in ("sort.ride_lanes", "sort.ride_batches")
    )
