"""Sort-based equi-join kernels.

Reference analog: cpp/src/cylon/join/ — hash join (hash_join.cpp:309-346,
multimap build/probe) and sort join (sort_join.cpp, argsort + merge with run
detection). On TPU, scatter-heavy hash multimaps are hostile to the memory
system while sorts are native, so the single algorithm here is:

  1. ``factorize_two``: both tables' key tuples -> one dense id space
     (replaces TwoTableRowIndexHash maps);
  2. sort right ids, ``searchsorted`` each left id for its match run
     (replaces the multimap probe);
  3. count phase -> exact output size (host syncs once);
  4. emit phase: ``jnp.repeat`` + gather produce (left_idx, right_idx) pairs
     with -1 marking the null side of outer joins
     (reference emits via probe_hash_map_no_fill/with_fill/outer,
     hash_join.cpp:21-90, and build_final_table join_utils.cpp:28-160).

Join types: INNER/LEFT/RIGHT/FULL_OUTER (join/join_config.hpp:26-45).
All functions are static-shaped and jit-safe; the count->emit split is the
only host round-trip.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import stages as _stages
from .factorize import factorize_two
from .sort import KeyCol, fit_slots


def _inv_perm(p: jax.Array) -> jax.Array:
    """Inverse of a permutation via a second argsort. On TPU this beats the
    scatter-based rank construction jax's searchsorted(method='sort') uses
    (sorts are near-memory-bandwidth on v5e; scatters pay per-element)."""
    return jnp.argsort(p, stable=True).astype(jnp.int32)


def _merged_counts(
    l_ids: jax.Array,
    r_ids: jax.Array,
    nl: jax.Array,
    nr: jax.Array,
    cap_l: int,
    cap_r: int,
    need_rcnt: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(lo, cnt, r_cnt) of the equi-join probe from ONE merged kv-sort.

    ``l_ids``/``r_ids`` are canonical orderable ids of one integer dtype
    whose padding rows (index >= n) hold a value that sorts >= every live id
    (uint32 MAXU on the fast path, ``cap_l + cap_r`` after factorize).

    Replaces the earlier double-argsort searchsorted (7 argsorts of up to
    cap_l+cap_r pairs): one stable kv-sort of [r_ids ++ l_ids] with an iota
    payload, then O(n) scans. Within an equal-key run the stable sort places
    rights before lefts (rights precede in the concatenation), so for a left
    at sorted position p, the run's live rights ALL precede p:

      lo[p]  = live rights before p's run  = cummax of run-start prefix sums
      cnt[p] = live rights inside the run  = prefix_sum[p] - lo[p]

    and compaction back to original row order is ONE more stable sort keyed
    by (is_left ? payload : BIG) — the payload of a left IS cap_r + its
    original index, so ascending payload = original order. r_cnt uses the
    mirror: in reversed order lefts precede rights within a run, so the same
    run-start formula on flipped arrays counts each run's live lefts.
    Sorts run near memory bandwidth on TPU while big gathers/scatters pay
    per-element, hence everything here is sort + scan only. Measured 2.6x
    over the double-argsort probe (4Mx4M keys, v5e).

    ``lo`` is only meaningful where ``cnt > 0`` (emit clips it elsewhere);
    padding rows report cnt == 0 / r_cnt == 0.
    """
    from .sort import (
        kv_sort,
        run_count_from,
        run_count_upto,
        run_start_broadcast,
        sentinel_compact,
    )

    with jax.named_scope(_stages.JOIN_PROBE):
        keys = jnp.concatenate([r_ids, l_ids])  # rights FIRST (tie order matters)
        pay = jnp.arange(cap_r + cap_l, dtype=jnp.int32)
        skey, spay = kv_sort(keys, pay)
        is_r_live = spay < nr
        is_l = spay >= cap_r
        rl = is_r_live.astype(jnp.int32)
        r_excl = jnp.cumsum(rl) - rl  # live rights strictly before each position
        new_run = jnp.concatenate([jnp.ones((1,), bool), skey[1:] != skey[:-1]])
        lo_run = run_start_broadcast(new_run, r_excl)  # r_excl @ run start
        cnt_p = run_count_upto(new_run, is_r_live)  # live rights in run up to p
        big = jnp.int32(2**31 - 1)
        lo_c, cnt_c = sentinel_compact(
            jnp.where(is_l, spay, big), [lo_run, cnt_p]
        )
        idx_l = jnp.arange(cap_l, dtype=jnp.int32)
        lo = lo_c[:cap_l]
        cnt = jnp.where(idx_l < nl, cnt_c[:cap_l], 0)
        if not need_rcnt:
            return lo, cnt, jnp.zeros((cap_r,), jnp.int32)
        # lefts come after rights within a run, so counting "at/after me" from
        # a right position sees exactly the run's live lefts
        is_l_live = is_l & (spay < cap_r + nl)
        rcnt_p = run_count_from(new_run, is_l_live)
        (rcnt_c,) = sentinel_compact(jnp.where(~is_l, spay, big), [rcnt_p])
        idx_r = jnp.arange(cap_r, dtype=jnp.int32)
        r_cnt = jnp.where(idx_r < nr, rcnt_c[:cap_r], 0)
        return lo, cnt, r_cnt


def _key_order_emit(
    l_ids: jax.Array,
    r_ids: jax.Array,
    l_cols: Sequence[KeyCol],
    r_sorted_cols: Sequence[KeyCol],
    nl: jax.Array,
    nr: jax.Array,
    how: int,
    cap_out: int,
    cap_l: int,
    cap_r: int,
) -> Tuple[list, jax.Array, jax.Array]:
    """Probe + emit with output rows in GROUPED-KEY order, straight out of
    the merged kv-sort — the order-establishing join emit the planner's
    ``order_reuse`` rewrite lowers to.

    Where :func:`_merged_counts` pays a second (compaction) sort to return
    the per-left-row probe state to ORIGINAL left order, the key-order emit
    wants exactly the order the merged sort already produced: the repeat
    runs over sorted space directly, and per-output bookkeeping (run base,
    match count, original left row) comes back through one narrow gather.
    ONE sort total (plus the right ride sort the caller provides) versus
    the left-order path's two — fewer sort passes AND the output carries a
    canonical ordering descriptor downstream ops consume.

    At a left position p inside a run, rights all precede (stable sort of
    [rights ++ lefts]), so ``run_count_upto`` at p is the run's full live
    right count and the run-start right prefix sum is the match window
    base. Left columns keep mask-free-ness (``all_valid=True`` — every -1
    lands on a padding output row for INNER/LEFT).

    Returns (out_cols = left ++ right, exact total, float32 overflow
    shadow). INNER/LEFT only — the unmatched-right append of RIGHT/FULL
    has no key-ordered formulation here."""
    from .gather import pack_gather
    from .sort import kv_sort, run_count_upto, run_start_broadcast

    with jax.named_scope(_stages.JOIN_PROBE):
        cap_cat = cap_r + cap_l
        keys = jnp.concatenate([r_ids, l_ids])  # rights FIRST (tie order matters)
        pay = jnp.arange(cap_cat, dtype=jnp.int32)
        skey, spay = kv_sort(keys, pay)
        is_l = spay >= cap_r
        is_l_live = is_l & (spay < cap_r + nl)
        is_r_live = (~is_l) & (spay < nr)
        rl = is_r_live.astype(jnp.int32)
        r_excl = jnp.cumsum(rl) - rl
        new_run = jnp.concatenate([jnp.ones((1,), bool), skey[1:] != skey[:-1]])
        lo_run = run_start_broadcast(new_run, r_excl)
        cnt_p = run_count_upto(new_run, is_r_live)
        cnt = jnp.where(is_l_live, cnt_p, 0)
        shadow = jnp.sum(cnt.astype(jnp.float32))
        if how == LEFT:
            cnt_adj = jnp.where(is_l_live & (cnt == 0), 1, cnt)
        else:
            cnt_adj = cnt
        ends = jnp.cumsum(cnt_adj)
        offs = ends - cnt_adj
        total = ends[-1].astype(jnp.int32)
        base = lo_run - offs

    with jax.named_scope(_stages.JOIN_EMIT):
        li = _repeat_ss(ends, cap_out)  # sorted-space position per output row
        out_pos = jnp.arange(cap_out, dtype=jnp.int32)
        in_out = out_pos < total
        li = jnp.where(in_out, li, -1)
        safe_li = jnp.clip(li, 0, cap_cat - 1)
        book = jnp.stack(
            [base, cnt, spay - jnp.int32(cap_r)], axis=1
        )[safe_li]  # one narrow [cap_out, 3] gather
        base_g, cnt_g, orig_g = book[:, 0], book[:, 1], book[:, 2]
        orig_li = jnp.where(li >= 0, orig_g, -1)
        out_l, _ = pack_gather(l_cols, orig_li, all_valid=True)

        has_match = in_out & (cnt_g > 0)
        rpos = jnp.where(has_match, jnp.clip(base_g + out_pos, 0, cap_r - 1), -1)
        out_r, _ = pack_gather(r_sorted_cols, rpos)
        return list(out_l) + list(out_r), total, shadow


def impl_tag() -> tuple:
    """Env-selected kernel-impl choices, as a cache-key component.

    ``CYLON_TPU_REPEAT_IMPL`` / ``CYLON_TPU_SEGSUM_IMPL`` /
    ``CYLON_TPU_EMIT_IMPL`` / ``CYLON_TPU_EXPAND_GATHER`` are read at TRACE
    time, so any kernel cached by an env-independent key (ctx._jit_cache via
    engine.get_kernel) would silently keep the impl it was first compiled
    with after a mid-process env flip. Join-family cache keys append this
    tag so an A/B flip recompiles instead of reusing the stale program.
    The analyzer (cylon_tpu/analysis) treats a call to this function inside
    a key expression as the keyed carrier of all four knobs."""
    from ..utils import envgate as _eg

    return (
        _eg.REPEAT_IMPL.get(),
        _eg.SEGSUM_IMPL.get(),
        _eg.EMIT_IMPL.get(),
        _eg.EXPAND_GATHER.get(),
    )


def _repeat_ss(ends: jax.Array, cap_out: int) -> jax.Array:
    """``jnp.repeat(arange(n), counts, total_repeat_length=cap_out)``.

    Default: the scatter+cummax variant — row index i lands at its start
    offset, cummax forward-fills the run. Decided on real v5e hardware by
    benchmarks/micro_bench.py (r03, with the emit DCE-proofed): 2.4x the
    isolated repeat and 1.11x the full 32M-row join vs the argsort trick.

    ``CYLON_TPU_REPEAT_IMPL=sort`` selects the argsort trick instead —
    li[k] = #(ends <= k) with ends = inclusive cumsum of counts; the arange
    queries are already sorted so their rank is the identity, and one
    combined double-argsort replaces the repeat's scatter+cumsum lowering.
    (Kept selectable: round-2 measurements showed XLA TPU scatters can lose
    to sorts in other fusion contexts.)"""
    from ..utils import envgate as _eg

    n = ends.shape[0]
    if _eg.REPEAT_IMPL.get() == "scatter":
        starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
        cnt = ends - starts
        rows = jnp.arange(n, dtype=jnp.int32)
        tgt = jnp.where(cnt > 0, starts, cap_out).astype(jnp.int32)
        fill = jnp.full((cap_out + 1,), -1, jnp.int32)
        # distinct targets (strictly increasing among cnt>0 rows): plain set
        fill = fill.at[tgt].set(rows, mode="drop")
        return jax.lax.cummax(fill[:cap_out])
    pos = jnp.arange(cap_out, dtype=ends.dtype)
    comb = _inv_perm(jnp.argsort(jnp.concatenate([ends, pos]), stable=True))
    return (comb[n:] - pos).astype(jnp.int32)


INNER, LEFT, RIGHT, FULL_OUTER, SEMI, ANTI = 0, 1, 2, 3, 4, 5
_JOIN_TYPES = {"inner": INNER, "left": LEFT, "right": RIGHT, "fullouter": FULL_OUTER,
               "outer": FULL_OUTER, "full_outer": FULL_OUTER,
               "semi": SEMI, "left_semi": SEMI, "anti": ANTI, "left_anti": ANTI}
#: the types that keep or drop LEFT rows by whether they have a partner and
#: emit nothing of the right side (EXISTS / NOT EXISTS): :func:`semi_rows`
SEMI_TYPES = (SEMI, ANTI)


def join_type_id(how: str) -> int:
    try:
        return _JOIN_TYPES[how.replace("-", "_").lower()]
    except KeyError:
        raise ValueError(f"unknown join type {how!r}") from None


#: the sides a distributed join may hold WHOLE on every chip, by join type
#: and by nothing else. A replicated side's row meets every chip's part of
#: the other side, so a row of it that finds no partner would come out
#: once a chip: only a side whose unmatched rows emit nothing may be
#: replicated. The right side of inner, left, semi and anti; the left side
#: of inner and right; neither side of a full outer join.
REPLICABLE_SIDES = {
    INNER: ("right", "left"), LEFT: ("right",), RIGHT: ("left",),
    SEMI: ("right",), ANTI: ("right",), FULL_OUTER: (),
}


def replicate_side(how: str, left, right, world: int) -> Optional[str]:
    """The route of a distributed join over ``world`` chips: ``"right"`` or
    ``"left"``, the side that is gathered whole to every chip while the
    other stays where it lies, or ``None``: both sides are hash-shuffled.

    ``left`` / ``right`` are ``(rows, row_bytes)`` of a side as the host
    holds them, or ``None`` where its row count is not host-known: then
    the answer is ``None`` and nothing is fetched to decide. A side is
    replicated where the join type allows it (:data:`REPLICABLE_SIDES`)
    and the whole of it is at most one part in
    ``config.REPLICATE_JOIN_MIN_RATIO`` of one chip's share of the other
    side; the right side is asked first. The one copy of the rule:
    ``Table.distributed_join`` and the planner's physicalize pass
    (``plan.nodes.Join.route``) both ask here."""
    from ..config import REPLICATE_JOIN_MIN_RATIO

    if world <= 1 or left is None or right is None:
        return None
    size = {"left": left[0] * left[1], "right": right[0] * right[1]}
    for side in REPLICABLE_SIDES[join_type_id(how)]:
        other = "left" if side == "right" else "right"
        if size[side] * REPLICATE_JOIN_MIN_RATIO * world <= size[other]:
            return side
    return None


class _Probe(NamedTuple):
    lo: jax.Array         # [cap_l] first match position in sorted right keys
    cnt: jax.Array        # [cap_l] match count per live left row
    r_order: jax.Array    # [cap_r] argsort of right keys (stable)
    r_cnt: jax.Array      # [cap_r] match count per live right row


def _fast_path_ok(cols: Sequence[KeyCol]) -> bool:
    """Single key column, no validity mask, <=32-bit physical value: the key
    canonicalizes to one uint32 lane (ops.sort.orderable_key), no factorize
    needed."""
    if len(cols) != 1:
        return False
    data, valid = cols[0]
    if valid is not None:
        return False
    dt = data.dtype
    return dt == jnp.bool_ or (
        (jnp.issubdtype(dt, jnp.integer) or dt in (jnp.float32, jnp.float16))
        and np.dtype(dt).itemsize <= 4
    )


def _canonical_ids(
    l_key_cols: Sequence[KeyCol],
    r_key_cols: Sequence[KeyCol],
    nl: jax.Array,
    nr: jax.Array,
    cap_l: int,
    cap_r: int,
    fuse=None,
) -> Tuple[jax.Array, jax.Array]:
    """Canonical comparable key ids for both tables, one integer dtype,
    padding rows holding a value that sorts >= every live id.

    ``fuse``: stats-driven sort-word fusion plan for the factorize lanes
    (Table.join derives it from both sides' merged range stats); the
    single-uint32-key fast path is already one lane and ignores it."""
    with jax.named_scope(_stages.JOIN_KEY_IDS):
        idx_l = jnp.arange(cap_l, dtype=jnp.int32)
        idx_r = jnp.arange(cap_r, dtype=jnp.int32)
        # promote key dtypes to a common type first: orderable_key lanes are only
        # comparable within one dtype (int32 vs uint32 canonicalize differently)
        if (
            len(l_key_cols) == 1
            and len(r_key_cols) == 1
            and l_key_cols[0][0].dtype != r_key_cols[0][0].dtype
        ):
            from ..dtypes import promote_key_dtypes

            common = promote_key_dtypes(l_key_cols[0][0].dtype, r_key_cols[0][0].dtype)
            l_key_cols = [(l_key_cols[0][0].astype(common), l_key_cols[0][1])]
            r_key_cols = [(r_key_cols[0][0].astype(common), r_key_cols[0][1])]
        if _fast_path_ok(l_key_cols) and _fast_path_ok(r_key_cols):
            # Single <=32-bit key, no nulls: stay entirely in uint32 (no int64
            # emulation on TPU). Padding rows take the value UINT32_MAX; because
            # tables are front-packed (padding indices >= n) and the merged sort
            # is stable, live rows with a real MAX key still sort BEFORE padding
            # inside the equal run, and _merged_counts counts live rights only.
            from .sort import orderable_key

            MAXU = np.uint32(0xFFFFFFFF)
            lk = orderable_key(l_key_cols[0][0])
            rk = orderable_key(r_key_cols[0][0])
            l_ids = jnp.where(idx_l < nl, lk, MAXU)
            r_ids = jnp.where(idx_r < nr, rk, MAXU)
        else:
            l_ids, r_ids, _ = factorize_two(
                l_key_cols, r_key_cols, nl, nr, cap_l, cap_r, fuse=fuse
            )
            big = jnp.int32(cap_l + cap_r)  # sorts after every live dense id
            l_ids = jnp.where(idx_l < nl, l_ids, big)
            r_ids = jnp.where(idx_r < nr, r_ids, big)
        return l_ids, r_ids


def _right_order(r_ids: jax.Array) -> jax.Array:
    """Stable argsort of the canonical right ids."""
    with jax.named_scope(_stages.JOIN_RIGHT_SORT), \
            jax.named_scope(_stages.SORT_ENGINE):
        return jnp.argsort(r_ids, stable=True).astype(jnp.int32)


def _probe(
    l_key_cols: Sequence[KeyCol],
    r_key_cols: Sequence[KeyCol],
    nl: jax.Array,
    nr: jax.Array,
    cap_l: int,
    cap_r: int,
    need_rcnt: bool = True,
    fuse=None,
) -> _Probe:
    l_ids, r_ids = _canonical_ids(
        l_key_cols, r_key_cols, nl, nr, cap_l, cap_r, fuse=fuse
    )
    r_order = _right_order(r_ids)
    lo, cnt, r_cnt = _merged_counts(
        l_ids, r_ids, nl, nr, cap_l, cap_r, need_rcnt
    )
    return _Probe(lo, cnt, r_order, r_cnt)


def probe_arrays(
    l_key_cols, r_key_cols, nl, nr, cap_l: int, cap_r: int,
    how: int = FULL_OUTER, r_presorted: bool = False, key_fuse=None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Phase-1 kernel surface: returns the static-shaped probe state
    (lo, cnt, r_order, r_cnt) so the emit phase need not recompute the sorts.
    For INNER/LEFT joins r_cnt is unused downstream and is returned as zeros,
    skipping one sort and two sorted searches.

    ``r_presorted=True``: the caller proves (via the right table's ordering
    descriptor) that the right rows are already canonically ordered by the
    join key, so the right argsort collapses to the identity permutation —
    the sorted-run-reuse fast path."""
    if r_presorted:
        l_ids, r_ids = _canonical_ids(
            l_key_cols, r_key_cols, nl, nr, cap_l, cap_r, fuse=key_fuse
        )
        r_order = jnp.arange(cap_r, dtype=jnp.int32)
        lo, cnt, r_cnt = _merged_counts(
            l_ids, r_ids, nl, nr, cap_l, cap_r,
            need_rcnt=how in (RIGHT, FULL_OUTER),
        )
        return (lo, cnt, r_order, r_cnt)
    p = _probe(
        l_key_cols, r_key_cols, nl, nr, cap_l, cap_r,
        need_rcnt=how in (RIGHT, FULL_OUTER), fuse=key_fuse,
    )
    return (p.lo, p.cnt, p.r_order, p.r_cnt)


def count_from_probe(cnt, r_cnt, nl, nr, how: int) -> jax.Array:
    with jax.named_scope(_stages.JOIN_PROBE):
        cap_l = cnt.shape[0]
        cap_r = r_cnt.shape[0]
        inner = jnp.sum(cnt)
        total = inner
        if how in (LEFT, FULL_OUTER):
            total = total + jnp.sum((cnt == 0) & (jnp.arange(cap_l) < nl))
        if how in (RIGHT, FULL_OUTER):
            total = total + jnp.sum((r_cnt == 0) & (jnp.arange(cap_r) < nr))
        return total.astype(jnp.int32)


def count_overflow_check(cnt, r_cnt) -> jax.Array:
    """float32 shadow of the inner-join total: the int32 count wraps silently
    past 2^31 (e.g. 65536^2 matches on one key wraps to 0); the float32 sum
    keeps the right magnitude, so ``shadow > 2^31`` (or a negative int32
    total) detects the wrap. Outputs that large can't be allocated anyway —
    callers raise."""
    with jax.named_scope(_stages.JOIN_PROBE):
        return jnp.sum(cnt.astype(jnp.float32))


def emit_from_probe(
    lo, cnt, r_order, r_cnt, nl, nr, how: int, cap_out: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Phase-2: join row indices from the phase-1 probe state."""
    with jax.named_scope(_stages.JOIN_EMIT):
        cap_l = lo.shape[0]
        cap_r = r_order.shape[0]
        idx_l = jnp.arange(cap_l, dtype=jnp.int32)
        live_l = idx_l < nl
        if how in (LEFT, FULL_OUTER):
            cnt_adj = jnp.where(live_l & (cnt == 0), 1, cnt)
        else:
            cnt_adj = cnt
        ends = jnp.cumsum(cnt_adj)
        offs = ends - cnt_adj
        total_l = ends[-1].astype(jnp.int32)

        li = _repeat_ss(ends, cap_out)
        # rpos = lo[li] + (k - offs[li]) = (lo - offs)[li] + k: one gather of the
        # precombined base instead of a second repeat + a second gather
        base = lo - offs
        has_match = cnt[li] > 0
        rpos = jnp.clip(base[li] + jnp.arange(cap_out, dtype=jnp.int32), 0, cap_r - 1)
        ri = jnp.where(has_match, r_order[rpos], -1)
        out_pos = jnp.arange(cap_out, dtype=jnp.int32)
        in_left_part = out_pos < total_l
        li = jnp.where(in_left_part, li, -1)
        ri = jnp.where(in_left_part, ri, -1)

        n_out = total_l
        if how in (RIGHT, FULL_OUTER):
            idx_r = jnp.arange(cap_r, dtype=jnp.int32)
            r_un = (r_cnt == 0) & (idx_r < nr)
            r_un_rank = jnp.cumsum(r_un.astype(jnp.int32)) - 1
            n_r_un = jnp.sum(r_un).astype(jnp.int32)
            dest = jnp.where(r_un, total_l + r_un_rank, cap_out)
            ri = ri.at[dest].set(idx_r, mode="drop")
            li = li.at[dest].set(-1, mode="drop")
            n_out = total_l + n_r_un
        return li, ri, n_out.astype(jnp.int32)


def join_count(
    l_key_cols: Sequence[KeyCol],
    r_key_cols: Sequence[KeyCol],
    nl: jax.Array,
    nr: jax.Array,
    cap_l: int,
    cap_r: int,
    how: int,
) -> jax.Array:
    """Exact number of output rows for the given join type (scalar int32)."""
    p = _probe(
        l_key_cols, r_key_cols, nl, nr, cap_l, cap_r,
        need_rcnt=how in (RIGHT, FULL_OUTER),
    )
    return count_from_probe(p.cnt, p.r_cnt, nl, nr, how)


def join_emit(
    l_key_cols: Sequence[KeyCol],
    r_key_cols: Sequence[KeyCol],
    nl: jax.Array,
    nr: jax.Array,
    cap_l: int,
    cap_r: int,
    how: int,
    cap_out: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Emit join row indices.

    Returns (left_idx [cap_out], right_idx [cap_out], n_out scalar). Index -1
    means "no row on this side" (outer joins). Rows >= n_out are padding.
    ``cap_out`` must be >= the corresponding :func:`join_count`.
    """
    p = _probe(
        l_key_cols, r_key_cols, nl, nr, cap_l, cap_r,
        need_rcnt=how in (RIGHT, FULL_OUTER),
    )
    return emit_from_probe(p.lo, p.cnt, p.r_order, p.r_cnt, nl, nr, how, cap_out)


def emit_gather(
    lo, cnt, r_order, r_cnt,
    l_cols: Sequence[KeyCol],
    r_cols: Sequence[KeyCol],
    nl, nr, how: int, cap_out: int,
    emit_impl: str = "gather",
) -> Tuple[list, jax.Array]:
    """Fused emit + payload gather: produce the joined output columns with a
    minimal number of XLA gathers (the TPU bottleneck — see ops/gather.py).

    INNER/LEFT fast path (:func:`_emit_inner_left`) holds two forms of its
    left half under one ``cond`` and one right half. Where some left row
    emits twice or not at all it does exactly three big row-addressed
    operations: the ``jnp.repeat`` for li, one packed left-row gather
    (payload + base/cnt lanes), and one packed right-row gather against the
    r_order-permuted right payload. A float64 payload is two lanes of those
    gathers and no gather of its own: for an int64 key and a float64 value a
    side the two are ``s32[cap_out, 6]`` (key 2, base, cnt, value 2) and
    ``s32[cap_out, 4]`` (key 2, value 2), and the compiled program holds no
    other gather of ``cap_out`` rows. Where every live left row emits
    exactly once (a table joined to another by that one's unique key) the
    left columns are handed through and the right gather is the one
    row-addressed operation that runs. RIGHT/FULL_OUTER falls back to
    :func:`emit_from_probe` indices + two packed gathers (the
    unmatched-right scatter does not fuse).

    Returns (out_cols = left ++ right as (data, valid), n_out scalar).
    """
    from .gather import pack_gather

    if how in (RIGHT, FULL_OUTER):
        with jax.named_scope(_stages.JOIN_EMIT):
            li, ri, n_out = emit_from_probe(
                lo, cnt, r_order, r_cnt, nl, nr, how, cap_out
            )
            out_l, _ = pack_gather(l_cols, li)
            out_r, _ = pack_gather(r_cols, ri)
            return out_l + out_r, n_out

    # permute right payload into key-sorted order once (cap_r rows).
    # r_order is a permutation (all indices >= 0), so columns that had no
    # validity mask stay mask-free — don't let the all-True ok lane ride
    # through the second (hot, cap_out-sized) gather.
    with jax.named_scope(_stages.JOIN_RIGHT_SORT):
        r_sorted_cols, _ = pack_gather(r_cols, r_order)
        r_sorted_cols = [
            (d, None if rv is None else v)
            for (d, v), (_, rv) in zip(r_sorted_cols, r_cols)
        ]
    out, n_out, _handed = _emit_inner_left(
        lo, cnt, l_cols, r_sorted_cols, nl, how, cap_out, r_order.shape[0],
        emit_impl,
    )
    return out, n_out


def emit_impl_for(world_size: int, platform: str) -> str:
    """Resolve the emit implementation for a mesh: windowed only when the
    env opts in AND the Pallas expand can actually run there. CPU meshes
    get 'windowed_interp' (interpret-mode pallas — the MESH platform
    decides, not jax.default_backend(): on a TPU host driving a CPU-device
    mesh the two disagree and a compiled Mosaic kernel would crash);
    accelerator meshes get compiled 'windowed' at EVERY world size.

    Multi-chip history: round 3 found compiled pallas recursing at trace
    time under ``jit(shard_map(...))`` and gated world>1 off. The trigger
    was the NESTED jit (`expand_rows` carried its own @jax.jit inside the
    shard_map-wrapped kernel); the emit path now calls the unjitted
    `expand_rows_raw`, the same construction `dryrun_multichip` executes on
    multi-device meshes (interpret) and `benchmarks/shardmap_pallas_probe.py`
    validates compiled-on-hardware under shard_map. The whole path stays
    opt-in behind CYLON_TPU_EMIT_IMPL=windowed, so the default join never
    depends on it."""
    from ..utils import envgate as _eg

    if _eg.EMIT_IMPL.get() != "windowed":
        return "gather"
    from .pallas_gather import expand_available

    if not expand_available():
        return "gather"
    if platform == "cpu":
        return "windowed_interp"
    return "windowed"


def emit_impl_kwargs(ctx) -> Tuple[str, dict]:
    """(emit_impl, engine.get_kernel kwargs) for a context — ONE home for
    the invariant: a windowed emit embeds a pallas_call, whose outputs trip
    shard_map's vma checker (check_vma=False). 1-device meshes skip
    shard_map entirely (it is a no-op there and skipping it also sidesteps
    any residual pallas-under-shard_map fragility on the headline path);
    multi-device meshes run the pallas_call per-shard inside shard_map,
    UNJITTED (expand_rows_raw) — the nested jit was the round-3 recursion
    trigger."""
    from ..utils import envgate as _eg

    impl = emit_impl_for(ctx.world_size, ctx.platform)
    if not impl.startswith("windowed"):
        return impl, {}
    # CYLON_TPU_FORCE_SHARD_MAP=1 keeps shard_map on a 1-device mesh: the
    # hardware probe (benchmarks/shardmap_pallas_probe.py) uses it to run
    # the exact multi-chip construction — compiled pallas inside
    # jit(shard_map) — on the single real chip (get_kernel keys include the
    # wrapping flags, so this cannot alias the unwrapped program)
    # lint: key=CYLON_TPU_FORCE_SHARD_MAP -- threaded via get_kernel's
    # wrapping-flag key components (use_shard_map/check_vma join every key)
    force_sm = _eg.FORCE_SHARD_MAP.get() == "1"
    return impl, {
        "check_vma": False,
        "use_shard_map": ctx.world_size > 1 or force_sm,
    }


def _emit_inner_left(
    lo, cnt,
    l_cols: Sequence[KeyCol],
    r_sorted_cols: Sequence[KeyCol],
    nl, how: int, cap_out: int, cap_r: int,
    emit_impl: str = "gather",
    mask_free: bool = False,
) -> Tuple[list, jax.Array, jax.Array]:
    """INNER/LEFT emit against an ALREADY key-sorted right payload. Its
    left half has two forms, and the program holds both under one
    ``jax.lax.cond`` whose predicate is read from the probe's counts on the
    device (one reduction over ``cnt``; inside ``shard_map`` a decision a
    shard; no caller says which):

    - :func:`_left_gathered`, for any counts: the ``jnp.repeat`` for li and
      one packed left-row gather (payload + base/cnt lanes);
    - :func:`_left_handed_through`, where every live left row emits exactly
      once (LEFT: no left row has two partners; INNER: every left row has
      exactly one; a fact table joined to a dimension table by its key):
      output row ``k`` is left row ``k``, so the left columns pass as they
      lie and none of the first form's work runs.

    Live rows agree bit for bit between the forms; rows at or past the
    total are padding in both. One packed right-row gather at the run
    positions follows the ``cond`` in either case.

    Returns (out_cols = left ++ right, the emit's total, int32 1 where the
    left side was handed through and 0 where it was gathered).

    ``emit_impl='windowed'``/``'windowed_interp'`` (via
    :func:`emit_impl_for`) swaps the left gather for the Pallas streamed
    expand (ops/pallas_gather), unless the table is wide enough that the
    expand's VMEM footprint (~L * 3 windows * 4 B at T=4096) would
    overflow — wide tables keep the XLA gather.

    ``mask_free`` (INNER only; the join of semi-reduced sides asks for it):
    every output row has a row on both sides, so a column that had no
    validity lane gets none (``pack_gather(all_valid=True)``) and a
    following join on it keeps the single-lane key path. A LEFT join's
    LEFT columns get none either: every row it emits repeats a left row
    (the -1 positions all lie past the total), so its nulls are the right
    side's alone."""
    mask_free = mask_free and how == INNER
    if emit_impl.startswith("windowed"):
        # VMEM gate: lanes = data lanes (2 for 64-bit) + validity lanes +
        # 5 bookkeeping; scratch+out ≈ lanes * (2*4224 + 4096) * 4 B.
        # 200 lanes ≈ 10 MB — comfortably under the ~16 MB VMEM budget.
        est_lanes = 5 + sum(
            (2 if np.dtype(d.dtype).itemsize == 8 else 1)
            + (1 if v is not None else 0)
            for d, v in l_cols
        )
        if est_lanes <= 200:
            return _emit_inner_left_windowed(
                lo, cnt, l_cols, r_sorted_cols, nl, how, cap_out, cap_r,
                interpret=emit_impl == "windowed_interp",
            )
    from .gather import pack_gather

    with jax.named_scope(_stages.JOIN_EMIT):
        live_l = jnp.arange(lo.shape[0], dtype=jnp.int32) < nl
        if how == LEFT:
            cnt_adj = jnp.where(live_l & (cnt == 0), 1, cnt)
        else:
            cnt_adj = cnt
        all_valid = mask_free or how == LEFT
        # one pass over the counts decides, on the device and a shard
        once = jnp.all(jnp.where(live_l, cnt_adj == 1, True))
        out_l, rpos, total_l = jax.lax.cond(
            once,
            lambda: _left_handed_through(
                lo, cnt, l_cols, nl, cap_out, cap_r, all_valid
            ),
            lambda: _left_gathered(
                lo, cnt, cnt_adj, l_cols, cap_out, cap_r, all_valid
            ),
        )
        # the barrier holds the right gather apart from the cond: free to be
        # scheduled among its neighbours, it shifted the chip compiler's
        # memory assignment, and two sorts of a join that takes the gather
        # branch left fast memory (join-w1: +5.1 ms of 243.5 a query;
        # PERF.md section 6, PR 49)
        rpos = jax.lax.optimization_barrier(rpos)
        out_r, _ = pack_gather(r_sorted_cols, rpos, all_valid=mask_free)
        return out_l + list(out_r), total_l, once.astype(jnp.int32)


def _left_gathered(
    lo, cnt, cnt_adj, l_cols: Sequence[KeyCol],
    cap_out: int, cap_r: int, all_valid: bool,
) -> Tuple[list, jax.Array, jax.Array]:
    """The left half of :func:`_emit_inner_left` for any counts: left row
    ``i`` repeated ``cnt_adj[i]`` times (the run expansion ``li``), the
    columns and the ``base`` / ``cnt`` lanes fetched by ONE packed gather.

    Returns (left output columns, ``rpos`` [cap_out]: each output row's
    position in the key-sorted right payload or -1, the emit's total)."""
    from .gather import pack_gather

    ends = jnp.cumsum(cnt_adj)
    offs = ends - cnt_adj
    total_l = ends[-1].astype(jnp.int32)
    base = lo - offs

    li = _repeat_ss(ends, cap_out)
    out_pos = jnp.arange(cap_out, dtype=jnp.int32)
    li = jnp.where(out_pos < total_l, li, -1)
    out_l, (base_g, cnt_g) = pack_gather(
        l_cols, li, extra_lanes=[base, cnt], all_valid=all_valid,
    )

    has_match = (li >= 0) & (cnt_g > 0)
    rpos = jnp.where(has_match, jnp.clip(base_g + out_pos, 0, cap_r - 1), -1)
    return list(out_l), rpos, total_l


def _left_handed_through(
    lo, cnt, l_cols: Sequence[KeyCol], nl,
    cap_out: int, cap_r: int, all_valid: bool,
) -> Tuple[list, jax.Array, jax.Array]:
    """What :func:`_left_gathered` returns where every live left row emits
    exactly once, with no gather: ``li`` is then ``0 .. nl-1`` and
    ``offs[k]`` is ``k``, so output row ``k`` IS left row ``k`` and its
    right position is ``lo[k]``. Each column is brought to ``cap_out`` slots
    as it lies (a float64 is never split into lanes); the slots from ``nl``
    on are padding, as they are in the other form."""
    total_l = jnp.asarray(nl, jnp.int32)
    in_out = jnp.arange(cap_out, dtype=jnp.int32) < total_l

    def valid_of(v):
        if v is None:
            return None if all_valid else in_out
        v = fit_slots(v.astype(jnp.bool_), cap_out)
        return v if all_valid else in_out & v

    out_l = [(fit_slots(d, cap_out), valid_of(v)) for d, v in l_cols]
    has_match = in_out & (fit_slots(cnt, cap_out) > 0)
    rpos = jnp.where(
        has_match, jnp.clip(fit_slots(lo, cap_out), 0, cap_r - 1), -1
    )
    return out_l, rpos, total_l


def _emit_inner_left_windowed(
    lo, cnt,
    l_cols: Sequence[KeyCol],
    r_sorted_cols: Sequence[KeyCol],
    nl, how: int, cap_out: int, cap_r: int,
    interpret: bool = False,
) -> Tuple[list, jax.Array, jax.Array]:
    """INNER/LEFT emit with the left gather replaced by the Pallas windowed
    expand (docs/GATHER_DESIGN.md; VERDICT r3 item 1). It has the one form
    and hands nothing through: the flag it returns is 0.

    The left per-element gather becomes: ONE row scatter compacting emitting
    rows to the front (sorted destinations — for LEFT joins this is the
    identity on live rows), then a streamed expand whose emit indices are
    ``repeat(arange(m), counts)`` — non-decreasing, step <= 1 — so each
    128-output group reads one 128-wide VMEM window (ops/pallas_gather).
    Bookkeeping lanes (lo, cnt, original row id, output offset) ride the
    same scatter/expand, reconstructing the right-side run positions without
    any second repeat. The right gather is unchanged (its positions are not
    monotone in original-left emit order)."""
    from ..utils import envgate as _eg
    from .gather import pack_cols, pack_gather, unpack_cols
    from .pallas_gather import expand_rows_raw

    with jax.named_scope(_stages.JOIN_EMIT):
        impl = _eg.EXPAND_GATHER.get()
        cap_l = lo.shape[0]
        idx_l = jnp.arange(cap_l, dtype=jnp.int32)
        live_l = idx_l < nl
        if how == LEFT:
            cnt_adj = jnp.where(live_l & (cnt == 0), 1, cnt)
        else:
            cnt_adj = cnt
        emitting = live_l & (cnt_adj > 0)
        em32 = emitting.astype(jnp.int32)
        slot = jnp.cumsum(em32) - em32  # dense compaction slot (order-preserving)
        dest = jnp.where(emitting, slot, cap_l)

        plan, lanes, passthrough = pack_cols(l_cols)
        n_payload = len(lanes)
        lanes = list(lanes) + [lo, cnt, cnt_adj.astype(jnp.int32), idx_l]
        packed = jnp.stack(lanes, axis=1)  # [cap_l, LA]
        LA = packed.shape[1]
        packed_c = jnp.zeros((cap_l, LA), jnp.int32).at[dest].set(
            packed.astype(jnp.int32), mode="drop"
        )

        cnt_adj_c = packed_c[:, n_payload + 2]
        ends_c = jnp.cumsum(cnt_adj_c)
        total = ends_c[-1].astype(jnp.int32)
        offs_c = (ends_c - cnt_adj_c).astype(jnp.int32)
        li_c = _repeat_ss(ends_c, cap_out)  # raw non-decreasing (no -1 masking)

        srcT = jnp.concatenate(
            [packed_c.T, offs_c[None, :]], axis=0
        )  # [LA+1, cap_l]
        # unjitted on purpose: this call site is always inside the engine's
        # jit / jit(shard_map); wrapping the pallas_call in its own jit was the
        # round-3 unbounded-recursion trigger under shard_map on compiled TPU
        outT = expand_rows_raw(srcT, li_c, impl=impl, interpret=interpret)
        g_lanes = [outT[j] for j in range(LA + 1)]
        out_pos = jnp.arange(cap_out, dtype=jnp.int32)
        in_out = out_pos < total
        lo_g = g_lanes[n_payload]
        cnt_g = g_lanes[n_payload + 1]
        orig_g = g_lanes[n_payload + 3]
        offs_g = g_lanes[LA]

        def make_valid(lane):
            return in_out if lane is None else (in_out & lane.astype(jnp.bool_))

        out_l, _ = unpack_cols(
            plan,
            g_lanes[:n_payload],
            # f64 columns have no lanes in pack_cols' format: gather them by
            # the expanded original row id (their validity lane rode the expand)
            lambda ci: passthrough[ci][jnp.clip(orig_g, 0, cap_l - 1)],
            make_valid,
        )

        has_match = in_out & (cnt_g > 0)
        rpos = jnp.where(
            has_match, jnp.clip(lo_g - offs_g + out_pos, 0, cap_r - 1), -1
        )
        out_r, _ = pack_gather(r_sorted_cols, rpos)
        return list(out_l) + list(out_r), total, jnp.int32(0)


def ride_right(r_ids: jax.Array, r_cols: Sequence[KeyCol]) -> list:
    """The right table in the order of its canonical key ids: one stable
    sort keyed by ``r_ids`` with every column (data and validity lanes, a
    64-bit one as its two 32-bit halves) riding it as a payload, in
    batches past ``ops.sort.RIDE_LANES`` lanes (:func:`ops.sort.ride_sort`).
    No order is carried and nothing is gathered by one."""
    from .sort import flatten_cols, ride_sort, sentinel_compact, unflatten_cols

    _ids, rode = ride_sort(
        lambda pays: (None, sentinel_compact(r_ids, pays)), flatten_cols(r_cols)
    )
    return unflatten_cols(r_cols, rode)


def spec_join(
    l_key_cols: Sequence[KeyCol],
    r_key_cols: Sequence[KeyCol],
    l_cols: Sequence[KeyCol],
    r_cols: Sequence[KeyCol],
    nl: jax.Array,
    nr: jax.Array,
    how: int,
    cap_out: int,
    emit_impl: str = "gather",
    r_presorted: bool = False,
    emit_key_order: bool = False,
    key_fuse=None,
    mask_free: bool = False,
) -> Tuple[list, jax.Array, jax.Array, jax.Array]:
    """Single-dispatch speculative join: probe + count + emit + gather in one
    program with the minimal pass count.

    ``key_fuse``: stats-driven sort-word fusion plan for the multi-key /
    masked factorize lanes (see _canonical_ids).

    On the INNER/LEFT path the right payload RIDES the key sort — one stable
    multi-operand ``lax.sort`` keyed by the canonical right ids yields the
    key-sorted right table directly, replacing the separate
    ``argsort(r_ids)`` + packed permute gather of :func:`emit_gather` (and
    mask-free columns stay mask-free with no lane codec at all). Every
    right column rides, a 64-bit one as its two 32-bit halves, and no order
    is carried: at 4,194,304 rows the sort of the ids with an int64 and a
    float64 riding takes 15.6 ms on a v5e, where the argsort and their
    gathers by its order took 83.7 (PERF.md section 6, PR 30); past ``ops.sort.RIDE_LANES`` lanes the columns ride in
    batches of the same stable sort (:func:`ops.sort.ride_sort`).
    RIGHT/FULL_OUTER composes the probe + emit pieces unchanged.

    ``r_presorted=True`` (right rows provably key-ordered already — ordering
    descriptor): the right ride sort collapses to the identity, one fewer
    multi-operand sort. ``emit_key_order=True`` (INNER/LEFT only): probe +
    emit run straight off the merged kv-sort with NO compaction sort
    (:func:`_key_order_emit`) — one sort fewer than the left-order path —
    and output rows come out GROUPED BY KEY, so downstream ops on the key
    skip their own lexsort.

    Returns (out_cols = left ++ right, exact total, float32 overflow shadow,
    int32 1 where the emit handed its left side through and 0 where it
    gathered it: :func:`_emit_inner_left`; 0 from the key-order and the
    RIGHT/FULL_OUTER emits, which have the one form). The caller compares
    ``total`` against ``cap_out`` on the host and falls back to the exact
    two-phase path on overflow (table.py speculative join).
    """
    cap_l = l_key_cols[0][0].shape[0]
    cap_r = r_key_cols[0][0].shape[0]
    need_rcnt = how in (RIGHT, FULL_OUTER)
    emit_key_order = emit_key_order and how in (INNER, LEFT)
    l_ids, r_ids = _canonical_ids(
        l_key_cols, r_key_cols, nl, nr, cap_l, cap_r, fuse=key_fuse
    )
    if how in (INNER, LEFT):
        with jax.named_scope(_stages.JOIN_RIGHT_SORT):
            # sorted-run reuse: presorted rows ARE the key-sorted payload
            r_sorted = list(r_cols) if r_presorted else ride_right(r_ids, r_cols)
        if emit_key_order:
            # probe + emit in one sorted-space pass, no compaction sort
            out_cols, total, shadow = _key_order_emit(
                l_ids, r_ids, l_cols, r_sorted, nl, nr, how, cap_out,
                cap_l, cap_r,
            )
            return out_cols, total, shadow, jnp.int32(0)
        lo, cnt, r_cnt = _merged_counts(
            l_ids, r_ids, nl, nr, cap_l, cap_r, need_rcnt
        )
        total = count_from_probe(cnt, r_cnt, nl, nr, how)
        shadow = count_overflow_check(cnt, r_cnt)
        out_cols, _n_out, handed = _emit_inner_left(
            lo, cnt, l_cols, r_sorted, nl, how, cap_out, cap_r, emit_impl,
            mask_free=mask_free,
        )
    else:
        lo, cnt, r_cnt = _merged_counts(
            l_ids, r_ids, nl, nr, cap_l, cap_r, need_rcnt
        )
        total = count_from_probe(cnt, r_cnt, nl, nr, how)
        shadow = count_overflow_check(cnt, r_cnt)
        if r_presorted:
            r_order = jnp.arange(cap_r, dtype=jnp.int32)
        else:
            r_order = _right_order(r_ids)
        out_cols, _n_out = emit_gather(
            lo, cnt, r_order, r_cnt, l_cols, r_cols, nl, nr, how, cap_out,
            emit_impl,
        )
        handed = jnp.int32(0)
    return out_cols, total, shadow, handed


#: bit of the merged sort's payload that marks a row that is not live (a
#: padding slot or a row its table's mask dropped); the position is below
_SEMI_DEAD = 1 << 30


def semi_capable(cap_l: int, cap_r: int) -> bool:
    """Whether both sides' positions fit under the dead bit."""
    return cap_l + cap_r < _SEMI_DEAD


def _semi_scan(
    l_ids: jax.Array, r_ids: jax.Array, l_live: jax.Array, r_live: jax.Array,
    need_right: bool, wide: bool = False,
):
    """What :func:`semi_hits` (both sides' hits, in front of an INNER
    join) and :func:`semi_rows` (the semi and anti join themselves) share,
    in sorted order: one sort of [right ids ++ left ids] with the row's
    position as the second key (the order of :func:`_merged_counts`'
    stable kv-sort; a row that is not live, a padding slot or one its
    mask dropped, carries the dead bit and counts for nothing) and the
    blocked run scans (:func:`ops.sort.run_reduce`) for the live partners
    of each row. Called inside the caller's ``join.semi`` scope.

    Returns ``(pos, l_liv, r_liv, cnt, l_after)``: a sorted slot's
    position (a left's is ``cap_r`` plus its row), whether it is a live
    left / right, a live left's count of live rights in its run (0
    elsewhere), and a right's count of live lefts (``None`` without
    ``need_right``: the scan is not run). ``wide`` (positions that do not
    fit under the dead bit, :func:`semi_capable`): the dead flag rides the
    sort as an operand of its own."""
    from .sort import run_reduce

    cap_l, cap_r = l_ids.shape[0], r_ids.shape[0]
    keys = jnp.concatenate([r_ids, l_ids])  # rights FIRST, as the probe's
    live = jnp.concatenate([r_live, l_live])
    if wide:
        pay = jnp.arange(cap_r + cap_l, dtype=jnp.int32)
        with jax.named_scope(_stages.SORT_ENGINE):
            skey, pos, s_dead = jax.lax.sort(
                (keys, pay, ~live), num_keys=2, is_stable=False
            )
        s_live = ~s_dead
    else:
        dead = jnp.int32(_SEMI_DEAD)
        pay = jnp.arange(cap_r + cap_l, dtype=jnp.int32) | jnp.where(
            live, jnp.int32(0), dead
        )
        # both operands are keys and the sort is not stable: a row's
        # position is its own, so no two rows tie, the order inside a run
        # is the stable one (rows that are not live after the live ones,
        # which counts for nothing), and the stable sort's own iota, a
        # third operand, is not carried
        with jax.named_scope(_stages.SORT_ENGINE):
            skey, spay = jax.lax.sort(
                (keys, pay), num_keys=2, is_stable=False
            )
        s_live = spay < dead
        pos = spay & (dead - 1)
    is_l = pos >= cap_r
    l_liv, r_liv = s_live & is_l, s_live & ~is_l
    new_run = jnp.concatenate(
        [jnp.ones((1,), bool), skey[1:] != skey[:-1]]
    )
    # rights precede lefts inside a run: a right sees every live left
    # of its run at or after it, and a left every live right at or
    # before it, which is the same scan over the flipped order (a
    # run's start is its end there). The blocked scan and no
    # ``jnp.cumsum``: its passes carry this scope's name into the
    # trace, a prefix sum's ``reduce-window`` pieces carry none
    l_after = None
    if need_right:
        run_end = jnp.concatenate([new_run[1:], jnp.ones((1,), bool)])
        (l_after,) = run_reduce(run_end, [l_liv.astype(jnp.int32)], ["sum"])
    (r_upto,) = run_reduce(
        jnp.flip(new_run), [jnp.flip(r_liv.astype(jnp.int32))], ["sum"]
    )
    cnt = jnp.where(l_liv, jnp.flip(r_upto), 0)
    return pos, l_liv, r_liv, cnt, l_after


def semi_hits(
    l_ids: jax.Array, r_ids: jax.Array, l_live: jax.Array, r_live: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """The rows of an INNER join that have a partner, found from the key
    ids alone, before any payload moves: the semi-reduction in front of a
    selective join (stage ``join.semi``).

    The merged sort and the two run scans of :func:`_semi_scan`, and
    ONE single-operand sort that brings the positions of the
    rows with a partner to the front: the rights' ascending, then the lefts' (a left's
    position is ``cap_r`` plus its row, so it sorts after every right).

    Returns (``hits`` [cap_l + cap_r] int32, ``stats`` [4] int32: rights
    with a partner, lefts with a partner, the join's exact row count, and
    the float32 shadow of that count's bits, see
    :func:`count_overflow_check`)."""
    with jax.named_scope(_stages.JOIN_SEMI):
        pos, _l_liv, r_liv, cnt, l_after = _semi_scan(
            l_ids, r_ids, l_live, r_live, need_right=True
        )
        r_hit = r_liv & (l_after > 0)
        l_hit = cnt > 0
        big = jnp.int32(2**31 - 1)
        hits = _sort_one(jnp.where(l_hit | r_hit, pos, big))
        stats = jnp.stack([
            jnp.sum(r_hit).astype(jnp.int32),
            jnp.sum(l_hit).astype(jnp.int32),
            jnp.sum(cnt).astype(jnp.int32),
            jax.lax.bitcast_convert_type(
                jnp.sum(cnt.astype(jnp.float32)), jnp.int32
            ),
        ])
        return hits, stats


def semi_rows(
    l_ids: jax.Array, r_ids: jax.Array, l_live: jax.Array, r_live: jax.Array,
    anti: bool, as_mask: bool, wide: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """The semi join (``anti``: the anti join) as an operator: which LEFT
    rows are live and have a live partner (``anti``: are live and have
    none), from the key ids alone. Nothing of the right side comes back
    and no row moves: no right hits, no join count, no payload.

    The keys-only work is :func:`semi_hits`' (:func:`_semi_scan`, with the
    one scan a left needs; stage ``join.semi``). It leaves the verdicts in
    sorted order; ONE single-operand sort brings them back to row order
    (stage ``join.semi_mask``), in the form the caller asks for:

    - ``as_mask``: a bool mask in the padded row layout (a left's word is
      twice its row plus its verdict; every left slot has one, so the
      first ``cap_l`` sorted words are the rows in order). What a planned
      aggregate above the join takes as its row mask (``semi_as_mask``);
    - else the kept rows' positions ascending, ``cap_l`` past the last:
      what a compaction at ``round_cap`` of the count gathers by, with no
      sort of its own.

    Returns (the mask or the positions [cap_l], the kept count int32)."""
    cap_l, cap_r = l_ids.shape[0], r_ids.shape[0]
    with jax.named_scope(_stages.JOIN_SEMI):
        pos, l_liv, _r_liv, cnt, _ = _semi_scan(
            l_ids, r_ids, l_live, r_live, need_right=False, wide=wide
        )
        keep = (l_liv & (cnt == 0)) if anti else (cnt > 0)
        kept = jnp.sum(keep).astype(jnp.int32)
    with jax.named_scope(_stages.JOIN_SEMI_MASK):
        lrow = (pos - cap_r).astype(jnp.uint32)
        big = jnp.uint32(0xFFFFFFFF)
        if as_mask:
            word = jnp.where(
                pos >= cap_r, (lrow << 1) | keep.astype(jnp.uint32), big
            )
            return (_sort_one(word)[:cap_l] & 1).astype(bool), kept
        rows = _sort_one(jnp.where(keep, lrow, big))[:cap_l]
        return jnp.minimum(rows, jnp.uint32(cap_l)).astype(jnp.int32), kept


def _sort_one(key: jax.Array) -> jax.Array:
    """One operand, sorted: the cheapest sort the chip has (not stable:
    equal values are interchangeable, and a stable sort carries an iota)."""
    with jax.named_scope(_stages.SORT_ENGINE):
        return jax.lax.sort(key, is_stable=False)


def reduce_by_hits(
    hits: jax.Array,
    stats: jax.Array,
    l_cols: Sequence[KeyCol],
    r_cols: Sequence[KeyCol],
    cap_lo: int,
    cap_ro: int,
) -> Tuple[list, list]:
    """Both sides cut to their rows with a partner, in row order, at the
    capacities the host chose from :func:`semi_hits`' counts: two gathers
    over ``cap_lo`` and ``cap_ro`` slots, not over the inputs'."""
    from .gather import pack_gather

    cap_r = r_cols[0][0].shape[0]
    with jax.named_scope(_stages.JOIN_SEMI):
        n_r, n_l = stats[0], stats[1]
        r_idx = jnp.where(
            jnp.arange(cap_ro, dtype=jnp.int32) < n_r, hits[:cap_ro], -1
        )
        # the lefts follow the rights: a slice that starts where they end,
        # over a tail long enough that the start is never clamped
        tail = jnp.concatenate([hits, jnp.zeros((cap_lo,), jnp.int32)])
        l_pos = jax.lax.dynamic_slice(tail, (n_r,), (cap_lo,))
        l_idx = jnp.where(
            jnp.arange(cap_lo, dtype=jnp.int32) < n_l, l_pos - cap_r, -1
        )
        out_l, _ = pack_gather(list(l_cols), l_idx, all_valid=True)
        out_r, _ = pack_gather(list(r_cols), r_idx, all_valid=True)
        return list(out_l), list(out_r)


def gather_column(
    data: jax.Array, valid, idx: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Gather one column by (possibly -1) row indices.

    Replaces the reference's typed gather ``copy_array_by_indices``
    (util/copy_arrray.cpp). -1 indices produce null outputs.
    """
    safe = jnp.clip(idx, 0, data.shape[0] - 1)
    out = data[safe]
    ok = idx >= 0
    if valid is None:
        return out, ok
    return out, ok & valid[safe]


def join_sum_by_key_pushdown(
    l_key_cols: Sequence[KeyCol],
    r_key_cols: Sequence[KeyCol],
    l_val: KeyCol,
    nl: jax.Array,
    nr: jax.Array,
    group_cap: int,
    return_reps: bool = False,
):
    """INNER join + groupby-SUM(left column) BY the join key, fused into the
    probe sort itself — no join emit, no groupby sort.

    The query-optimizer pushdown the reference never does (it always
    materializes the join, then groups: groupby/groupby.cpp:33-91). Within
    one equal-key run of the merged probe sort every live left row pairs
    with every live right row, so the group's sum of the left value over
    the JOIN RESULT is ``count(live rights) * sum(left values)`` and the
    group's join-row count is ``c_l * c_r`` — all computable with run scans
    and segment scatter-adds. Cost: ONE merged kv-sort
    (value riding as a payload lane) + ONE compaction sort, vs ~8-9 sorts
    for join-then-groupby; the roofline model prices that at >3x.

    Returns (group sums [group_cap] float, ng UNCLAMPED, n_join,
    overflow_groups) — plus, with ``return_reps``, per-group representative
    LEFT row indices [group_cap] (the first live left row of each group's
    key run; the planner's fused node gathers the group-key VALUES through
    it, which this sums-only kernel otherwise discards) and per-group
    VALID-left-value counts (the caller rebuilds the generic SUM's all-null
    -> null validity from them). ``ng`` may exceed ``group_cap`` (the caller
    detects
    truncation, mirroring the generic group-by's contract); ``n_join``
    saturates to 2^31-1 on int32 wrap (a float32 shadow mirrors the count,
    exactly like join_shard's count_overflow_check policy). Null/padding
    values contribute 0 (SUM skip-null). Intended for floating aggregate
    columns; the caller keeps the generic path for ints.

    Per-group accumulation is SEGMENT SCATTER-ADD, not prefix-sum
    differences: differencing a global float32 running sum would give every
    group an absolute error scaling with the GLOBAL total (catastrophic at
    the 16M-row target), while scatter-add error scales with each group's
    own magnitude — the same reason the groupby float kernels kept
    scatter-add.
    """
    from .sort import run_count_from

    cap_l = l_key_cols[0][0].shape[0]
    cap_r = r_key_cols[0][0].shape[0]
    cap_cat = cap_r + cap_l
    l_ids, r_ids = _canonical_ids(l_key_cols, r_key_cols, nl, nr, cap_l, cap_r)

    vd, vv = l_val
    acc = vd if jnp.issubdtype(vd.dtype, jnp.floating) else vd.astype(jnp.float32)
    live_l_row = jnp.arange(cap_l, dtype=jnp.int32) < nl
    vok = live_l_row if vv is None else (live_l_row & vv)
    vsafe = jnp.where(vok, acc, jnp.zeros_like(acc))

    keys = jnp.concatenate([r_ids, l_ids])  # rights FIRST (matches probe)
    pay = jnp.arange(cap_cat, dtype=jnp.int32)
    ride = jnp.concatenate([jnp.zeros((cap_r,), vsafe.dtype), vsafe])
    skey, spay, sval = jax.lax.sort(
        (keys, pay, ride), num_keys=1, is_stable=True
    )
    is_l = spay >= cap_r
    is_l_live = is_l & (spay < cap_r + nl)
    is_r_live = (~is_l) & (spay < nr)
    new_run = jnp.concatenate([jnp.ones((1,), bool), skey[1:] != skey[:-1]])

    # run-start totals decide which runs are GROUPS (>=1 live left AND right)
    c_r = run_count_from(new_run, is_r_live)
    c_l = run_count_from(new_run, is_l_live)
    group_start = new_run & (c_l > 0) & (c_r > 0)
    # broadcast the start's verdict over its whole run (monotone gather by
    # the run-start index) and number the groups in key order
    iota = jnp.arange(cap_cat, dtype=jnp.int32)
    start_idx = jax.lax.cummax(jnp.where(new_run, iota, 0))
    ok_run = group_start[start_idx]
    gid = jnp.cumsum(group_start.astype(jnp.int32)) - 1  # constant per run
    ng = jnp.sum(group_start).astype(jnp.int32)

    # segment scatter-adds into group slots; rows past group_cap drop (the
    # unclamped ng reveals the truncation to the caller)
    from ..utils import envgate as _eg

    if _eg.SEGSUM_IMPL.get() == "sorted":
        # gid is monotone non-decreasing over sorted space, so the scatter
        # indices are sorted — XLA's TPU lowering can then accumulate
        # sequentially instead of the general scatter path. Non-group rows
        # carry gid of the PREVIOUS group, so their contributions must be
        # zeroed (not redirected); gid=-1 before the first group would WRAP
        # (negative .at indices are numpy-style even under mode="drop"),
        # breaking both the value and the sortedness claim -> clamp to 0,
        # where the zeroed contribution is harmless. gid>=group_cap past
        # the cap is out-of-bounds -> mode="drop".
        tgt = jnp.maximum(gid, 0)
        grp = ok_run
        kw = dict(mode="drop", indices_are_sorted=True)
    else:
        tgt = jnp.where(ok_run, gid, group_cap)
        grp = jnp.ones_like(ok_run)
        kw = dict(mode="drop")
    sums = jnp.zeros((group_cap + 1,), vsafe.dtype).at[tgt].add(
        jnp.where(grp & is_l_live, sval, jnp.zeros_like(sval)), **kw
    )
    cntr = jnp.zeros((group_cap + 1,), jnp.int32).at[tgt].add(
        (grp & is_r_live).astype(jnp.int32), **kw
    )
    cntl = jnp.zeros((group_cap + 1,), jnp.int32).at[tgt].add(
        (grp & is_l_live).astype(jnp.int32), **kw
    )
    s = sums[:group_cap] * cntr[:group_cap].astype(vsafe.dtype)

    nj_i = jnp.sum(cntl[:group_cap] * cntr[:group_cap]).astype(jnp.int32)
    nj_f = jnp.sum(
        cntl[:group_cap].astype(jnp.float32) * cntr[:group_cap].astype(jnp.float32)
    )
    wrapped = (nj_i < 0) | (nj_f > jnp.float32(2**31))
    n_join = jnp.where(wrapped, jnp.int32(2**31 - 1), nj_i)
    overflow_groups = jnp.maximum(ng - group_cap, 0)
    if not return_reps:
        return s, ng, n_join, overflow_groups
    # representative LEFT row per group: segment-min of the left row index
    # over the same (tgt, grp) scatter discipline as the sums — every group
    # has >= 1 live left row by construction, so slots < ng are always real
    lrow = spay - jnp.int32(cap_r)  # left row index in sorted space
    reps = jnp.full((group_cap + 1,), cap_l, jnp.int32).at[tgt].min(
        jnp.where(grp & is_l_live, lrow, jnp.int32(cap_l)), **kw
    )
    # per-group count of VALID left values, so the caller can mirror the
    # generic groupby_aggregate SUM validity (all-null group -> null)
    vok_s = vok[jnp.clip(lrow, 0, cap_l - 1)] & is_l_live
    vcnt = jnp.zeros((group_cap + 1,), jnp.int32).at[tgt].add(
        (grp & vok_s).astype(jnp.int32), **kw
    )
    return s, ng, n_join, overflow_groups, reps[:group_cap], vcnt[:group_cap]
