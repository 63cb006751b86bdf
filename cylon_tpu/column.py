"""Column: a typed, nullable device-resident column.

Reference analog: ``cylon::Column`` wrapping ``arrow::ChunkedArray``
(cpp/src/cylon/column.hpp:31-104). Here the physical storage is a single
fixed-capacity ``jax.Array`` (rows beyond the table's valid count are padding),
plus an optional bool validity mask (Arrow validity-bitmap analog) and, for
dictionary-encoded types, a host-side **sorted** numpy dictionary so that code
order == value order (sorts/range-partitions work on codes directly).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .dtypes import DataType, Type


class Column:
    __slots__ = ("data", "valid", "dtype", "dictionary")

    def __init__(
        self,
        data: jax.Array,
        dtype: DataType,
        valid: Optional[jax.Array] = None,
        dictionary: Optional[np.ndarray] = None,
    ):
        self.data = data
        self.dtype = dtype
        self.valid = valid  # None == all rows valid
        self.dictionary = dictionary
        if dtype.is_dictionary and dictionary is None:
            raise ValueError("dictionary-encoded column requires a dictionary")

    # -- construction -------------------------------------------------------
    @staticmethod
    def encode_host(values: np.ndarray) -> tuple[np.ndarray, Optional[np.ndarray], DataType, Optional[np.ndarray]]:
        """Host-side: raw numpy values -> (physical data, valid, dtype, dict).

        Strings/objects are dictionary-encoded with a *sorted* dictionary
        (np.unique) so code comparisons are order-equivalent to value
        comparisons. NaN / None / NaT become nulls.

        A column that is dictionary-coded already comes in as its int32
        codes, the array's dtype carrying the dictionary:
        ``np.dtype(np.int32, metadata={"dictionary": <sorted unicode
        array>})``, the numpy spelling of a pandas Categorical or an Arrow
        DictionaryArray. The codes are loaded as they are and no string is
        made, sorted or hashed (1e8 rows of a key of 1e8 values load as an
        integer column does); :func:`_encode_coded` checks what it takes.
        """
        values = np.asarray(values)
        coded = (values.dtype.metadata or {}).get("dictionary")
        if coded is not None:
            return _encode_coded(values, coded)
        if values.dtype == np.dtype("U1"):
            # one-character strings (a flag column) hold no None, NaN or
            # bool, and a value sorts as its code point: no object pass and
            # no sort, mark the code points that occur and look every
            # value's rank up in that table
            points = values.reshape(-1).view(np.uint32)
            seen = np.zeros(0x110000, bool)
            seen[points] = True
            codes = (np.cumsum(seen, dtype=np.int32) - 1)[points]
            dictionary = np.flatnonzero(seen).astype(np.uint32).view("U1")
            return codes, None, DataType(Type.STRING), dictionary
        if values.dtype.kind == "U" and values.ndim == 1 and len(values):
            coded = _encode_fixed_width(values)
            if coded is not None:
                return coded[0], None, DataType(Type.STRING), coded[1]
        if values.dtype.kind in ("U", "S", "O"):
            vals = np.asarray(values, dtype=object)
            is_null = np.array([v is None or (isinstance(v, float) and np.isnan(v)) for v in vals])
            if values.dtype.kind == "O":
                # Arrow-style inference for object columns: if every non-null
                # value is numeric/bool, the column is numeric — NOT strings
                # (pyarrow infers double/int64 here; stringifying would make
                # -0.0 != 0.0 and "10" < "9").
                live = [v for v, nul in zip(vals, is_null) if not nul]
                if live and all(
                    isinstance(v, (int, float, np.integer, np.floating, bool, np.bool_))
                    for v in live
                ):
                    if all(isinstance(v, (bool, np.bool_)) for v in live):
                        num = np.where(is_null, False, vals).astype(bool)
                        return Column.encode_host(num) if not is_null.any() else (
                            num, ~is_null, DataType.from_numpy_dtype(np.dtype(bool)), None
                        )
                    if all(
                        isinstance(v, (int, np.integer)) and not isinstance(v, (bool, np.bool_))
                        for v in live
                    ):
                        # exact int64 with a validity mask — a float64 fall-
                        # back would corrupt keys above 2^53 (pyarrow infers
                        # int64 + validity bitmap here too)
                        try:
                            num = np.where(is_null, 0, vals).astype(np.int64)
                        except OverflowError:
                            # Python int outside int64 range: keep the column
                            # exact via the dictionary/string encoding below
                            num = None
                        if num is not None:
                            if not is_null.any():
                                return Column.encode_host(num)
                            return (
                                num, ~is_null,
                                DataType.from_numpy_dtype(np.dtype(np.int64)), None,
                            )
                    else:
                        num = np.full(len(vals), np.nan, np.float64)
                        num[~is_null] = [float(v) for v in live]
                        return Column.encode_host(num)
            filler = ""
            # stray bools inside a string column stringify as 'true'/'false',
            # matching promote_encoded_shards' BOOL->STRING promotion so the
            # same logical value encodes identically on every shard
            vals = np.asarray(
                [
                    ("true" if v is True else "false" if v is False else v)
                    if isinstance(v, (bool, np.bool_))
                    else v
                    for v in vals
                ],
                dtype=object,
            )
            safe = np.where(is_null, filler, vals)
            dictionary, codes = np.unique(np.asarray(safe, dtype=str), return_inverse=True)
            codes = codes.astype(np.int32)
            valid = None if not is_null.any() else ~is_null
            return codes, valid, DataType(Type.STRING), dictionary
        if values.dtype.kind == "M":  # datetime64 -> int64 ns
            data = values.astype("datetime64[ns]").astype(np.int64)
            is_null = np.isnat(values)
            valid = None if not is_null.any() else ~is_null
            return data, valid, DataType(Type.TIMESTAMP), None
        if values.dtype.kind == "m":  # timedelta64 -> int64 ns DURATION
            data = values.astype("timedelta64[ns]").astype(np.int64)
            is_null = np.isnat(values)
            valid = None if not is_null.any() else ~is_null
            return data, valid, DataType(Type.DURATION), None
        if values.dtype.kind == "f":
            is_null = np.isnan(values)
            valid = None if not is_null.any() else ~is_null
            return values, valid, DataType.from_numpy_dtype(values.dtype), None
        return values, None, DataType.from_numpy_dtype(values.dtype), None

    # -- properties ---------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def with_data(self, data, valid="__same__") -> "Column":
        v = self.valid if valid == "__same__" else valid
        return Column(data, self.dtype, v, self.dictionary)

    def valid_mask(self) -> jax.Array:
        """Materialized validity mask (all-true if None)."""
        if self.valid is None:
            return jnp.ones(self.data.shape, dtype=bool)
        return self.valid

    # -- host conversion ----------------------------------------------------
    def decode_host(self, data_np: np.ndarray, valid_np: Optional[np.ndarray]):
        """Physical host values -> logical numpy values (strings decoded,
        nulls as NaN/None)."""
        if self.dtype.is_dictionary:
            out = self.dictionary[np.clip(data_np, 0, len(self.dictionary) - 1)]
            out = out.astype(object)
            if valid_np is not None:
                out[~valid_np] = None
            return out
        if self.dtype.type == Type.TIMESTAMP:
            out = data_np.astype("datetime64[ns]")
            if valid_np is not None:
                out[~valid_np] = np.datetime64("NaT")
            return out
        if self.dtype.type == Type.DURATION:
            out = data_np.astype("timedelta64[ns]")
            if valid_np is not None:
                out[~valid_np] = np.timedelta64("NaT")
            return out
        if valid_np is not None and not valid_np.all():
            if self.dtype.type == Type.BOOL:
                # keep booleans boolean (pandas object column with None),
                # not 1.0/0.0 floats
                out = data_np.astype(bool).astype(object)
                out[~valid_np] = None
                return out
            out = data_np.astype(np.float64, copy=True)
            out[~valid_np] = np.nan
            return out
        return data_np

    def __repr__(self):
        return f"Column({self.dtype}, cap={self.capacity}, nullable={self.valid is not None})"


def unify_dictionaries(a: Column, b: Column) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Build the union dictionary of two dictionary columns and the
    old-code -> new-code remapping vectors (host side).

    Needed before any cross-table comparison/hash of string columns: each
    table encodes its strings against its own dictionary; the union keeps the
    sorted invariant so code order remains value order.

    Both dictionaries are sorted and unique (the Column invariant), so the
    native two-pointer merge (native/runtime.cpp ct_dict_union_u32) computes
    union + both remaps in O(Da+Db) — at high-cardinality string-join scale
    np.union1d's concat + full host sort is the measured bottleneck this
    avoids. Falls back to numpy when the native lib is unavailable or the
    dictionaries aren't plain 'U' arrays.
    """
    from . import native

    got = native.dict_union(np.asarray(a.dictionary), np.asarray(b.dictionary))
    if got is not None:
        return got
    union = np.union1d(a.dictionary, b.dictionary)
    map_a = np.searchsorted(union, a.dictionary).astype(np.int32)
    map_b = np.searchsorted(union, b.dictionary).astype(np.int32)
    return union, map_a, map_b


def _encode_coded(values: np.ndarray, dictionary):
    """``encode_host`` of int32 codes whose dtype carries their dictionary:
    a unicode array, sorted and without repeats (code order is value order
    everywhere a dictionary column is sorted, compared or range-partitioned),
    every code inside it. One pass over the codes and one over the
    dictionary; neither is copied."""
    dictionary = np.asarray(dictionary)
    if values.dtype != np.int32 or values.ndim != 1:
        raise ValueError("dictionary codes are a one-dimensional int32 array")
    if dictionary.dtype.kind != "U" or dictionary.ndim != 1:
        raise ValueError("a dictionary is a one-dimensional unicode array")
    if len(dictionary) > 1 and not (dictionary[:-1] < dictionary[1:]).all():
        raise ValueError("a dictionary is sorted and holds a value once")
    if len(values) and not (
        0 <= int(values.min()) and int(values.max()) < len(dictionary)
    ):
        raise ValueError("a dictionary code lies outside its dictionary")
    return values.view(np.int32), None, DataType(Type.STRING), dictionary


def _encode_fixed_width(values: np.ndarray, block: int = 1 << 18):
    """``(codes int32, sorted dictionary)`` of a numpy string array, as
    ``np.unique(values, return_inverse=True)`` gives them, without sorting
    the rows as strings (15 million 15-character strings: a minute through
    python objects, 18 s through ``np.unique``, a few seconds this way).
    A numpy string array holds no None, NaN or bool. Every row's code
    points are folded into one 64-bit word (a block of rows at a time, so
    that the block stays in cache), the words are de-duplicated, and the
    few distinct strings are sorted; a fold that maps two different
    strings to one word (seen by comparing every row's code points with
    its representative's) gives ``None`` and the caller takes the general
    path."""
    n, width = len(values), values.dtype.itemsize // 4
    if width == 0:
        return None
    points = np.ascontiguousarray(values).view(np.uint32).reshape(n, width)
    weights = (
        np.uint64(0x9E3779B97F4A7C15) ** np.arange(1, width + 1, dtype=np.uint64)
    )
    word = np.empty(n, np.uint64)
    with np.errstate(over="ignore"):
        for lo in range(0, n, block):
            word[lo:lo + block] = points[lo:lo + block] @ weights
    distinct = np.unique(word)
    inverse = np.searchsorted(distinct, word)
    first = np.empty(len(distinct), np.int64)
    first[inverse] = np.arange(n)  # any row of a word stands for it
    rep = points[first]
    for lo in range(0, n, block):
        if not np.array_equal(rep[inverse[lo:lo + block]], points[lo:lo + block]):
            return None
    found = values[first]
    order = np.argsort(found, kind="stable")
    rank = np.empty(len(order), np.int32)
    rank[order] = np.arange(len(order), dtype=np.int32)
    dictionary = found[order]
    # the width np.unique of the values as python strings would give
    tight = max(1, int(np.char.str_len(dictionary).max()))
    return rank[inverse], dictionary.astype(f"<U{tight}")
