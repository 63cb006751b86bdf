"""Dense-id factorization of rows by key columns.

This is the TPU-native replacement for the reference's hash-map machinery
(ska::bytell_hash_map row maps, cpp/src/cylon/arrow/arrow_comparator.hpp:28-121
``TableRowIndexHash/EqualTo`` and the two-table variants): instead of building
a scatter-heavy hash table, rows are lexsorted and run-detected, assigning each
distinct key tuple a dense id in **sorted key order**. Every downstream
relational op (join, groupby, set ops, unique) consumes these ids.

All functions are static-shaped and jit-safe.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .sort import (
    KeyCol,
    canonical_row_lanes,
    rows_differ,
    sentinel_compact,
    sorted_runs,
    sorted_runs_payload,
)


def factorize_runs(
    key_cols: Sequence[KeyCol],
    n: jax.Array,
    cap: int,
    payloads: Sequence[jax.Array],
    fuse=None,
    presorted: bool = False,
    keep: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, list, Optional[list]]:
    """Factorization for a consumer that stays in SORTED space (the
    group-by): the rows are brought into canonical key order with
    ``payloads`` riding the sort; no ids are made and nothing is sorted
    back to the original row order.

    Returns (run_start [cap] bool: a live row that opens a run of equal
    keys; run_end [cap] bool: a row that closes one, the last live row
    always among them; the payloads in sorted order; the sorted canonical
    lanes msb first, the ``fuse`` plan's words where one is given, None
    for ``presorted`` input). Live rows sort first, so the runs of the
    live rows are the groups, in key order, and a stable sort keeps each
    run's rows in their original order.

    ``presorted``: the rows already are in that order (the caller's
    contract, or the table's ordering descriptor): no sort at all, the
    runs are read off the key columns (reference PipelineGroupBy,
    groupby/pipeline_groupby.cpp:30-90).

    ``keep`` ([cap] bool; not with ``presorted``): the rows that count
    where they are not the first ``n`` (a row mask rides the sort): a row
    it drops sorts with the padding, and ``n`` is the number it keeps."""
    idx = jnp.arange(cap, dtype=jnp.int32)
    live = idx < n
    if presorted:
        new_run, spays, slanes = rows_differ(key_cols, cap), payloads, None
    else:
        lanes = canonical_row_lanes(
            key_cols, live if keep is None else keep, fuse=fuse
        )  # msb first
        new_run, spays, slanes = sorted_runs_payload(lanes, payloads)
    # the first padding row opens a run of its own whatever it holds, so
    # the last live row closes one
    new_run = new_run | (idx == n)
    run_end = jnp.concatenate([new_run[1:], jnp.ones((1,), bool)])
    return new_run & live, run_end, list(spays), slanes


def factorize_two(
    l_cols: Sequence[KeyCol],
    r_cols: Sequence[KeyCol],
    nl: jax.Array,
    nr: jax.Array,
    cap_l: int,
    cap_r: int,
    fuse=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Joint factorization of two tables' key rows onto one dense id space.

    Replaces the reference's ``TwoTableRowIndexHash/EqualTo`` (MSB-tagged
    two-table hash maps, arrow/arrow_comparator.hpp + util::SetBit tricks).
    Returns (l_ids [cap_l], r_ids [cap_r], num_groups). Padding rows get id
    ``cap_l + cap_r``. Equal key tuples across the two tables share an id.

    ``fuse``: sort-word fusion plan over the CONCATENATED key columns —
    the caller (Table.join) merges both sides' range stats and declines
    on any key-pair dtype mismatch, so the in-kernel promotion below is a
    no-op whenever a plan is present.
    """
    cap = cap_l + cap_r
    cat_cols: list[KeyCol] = []
    for (ld, lv), (rd, rv) in zip(l_cols, r_cols):
        if ld.dtype == rd.dtype:
            common = ld.dtype
        else:
            from ..dtypes import promote_key_dtypes

            common = promote_key_dtypes(ld.dtype, rd.dtype)
        data = jnp.concatenate([ld.astype(common), rd.astype(common)])
        if lv is None and rv is None:
            valid = None
        else:
            lvm = jnp.ones((cap_l,), bool) if lv is None else lv
            rvm = jnp.ones((cap_r,), bool) if rv is None else rv
            valid = jnp.concatenate([lvm, rvm])
        cat_cols.append((data, valid))
    # left live rows are [0, nl); right live rows are [cap_l, cap_l+nr):
    # the class lane sorts ALL live rows first, so in sorted order live rows
    # occupy the [0, nl+nr) prefix. Scatter-free and gather-free: the
    # canonical lanes ride the chained sort (run boundaries come from the
    # SORTED lanes), and the ids return to original row order through one
    # payload sort keyed by the carried original index.
    idx = jnp.arange(cap, dtype=jnp.int32)
    live = (idx < nl) | ((idx >= cap_l) & (idx < cap_l + nr))
    lanes = canonical_row_lanes(cat_cols, live, fuse=fuse)  # msb first
    order, diff = sorted_runs(lanes, idx)
    n_live = nl + nr
    live_sorted = idx < n_live
    ids_sorted = jnp.cumsum(diff.astype(jnp.int32)) - 1
    num_groups = jnp.where(
        n_live > 0, ids_sorted[jnp.maximum(n_live - 1, 0)] + 1, 0
    ).astype(jnp.int32)
    ids_sorted = jnp.where(live_sorted, ids_sorted, cap)
    (ids,) = sentinel_compact(order, [ids_sorted])  # back to original order
    return ids[:cap_l], ids[cap_l:], num_groups
