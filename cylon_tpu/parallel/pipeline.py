"""Fully-jittable distributed pipelines with static capacities.

The eager Table ops use a count->emit two-phase with one host sync per op
(exact sizes, zero overflow). This module is the second execution mode — the
analog of the reference's streaming op-DAG engine (cpp/src/cylon/ops/:
DisJoinOP builds partition->shuffle->join graphs executed without
materializing intermediates, dis_join_op.cpp:26-71): the WHOLE
partition -> all_to_all -> join -> aggregate chain is one XLA program under
shard_map, with user-supplied capacity factors instead of host syncs. XLA
fuses and overlaps the stages (async collectives) the way the reference's
cooperative scheduler interleaves op execution (ops/execution/execution.hpp).

Capacities: ``bucket_cap`` bounds rows any shard sends to any one target
(reference sidesteps this with byte-streaming, arrow_all_to_all.cpp:83-141 —
impossible under XLA static shapes); ``join_cap`` bounds per-shard join
output. Each step also returns an ``overflow`` flag so callers can detect
undersized capacities and re-run with bigger ones (two-round respill,
SURVEY.md §7 hard-parts plan).

Skew: the in-graph respill rounds absorb MODERATE skew (a bucket up to
(1+respill) x cap) with zero host syncs; extreme skew — where padding
every respill round to the hot bucket would dominate the wire — is the
eager engine's job, whose measured-count planner splits heavy-bucket
tails onto the host relay instead (parallel/spill.plan_schedule). The
fused path reports its padded exchange volume through the same
``shuffle.exchanged_bytes`` counter via :func:`fused_exchange_bytes` so
the two regimes stay comparable in BENCH/EXPLAIN output.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from ..compat import pvary, shard_map
from ..engine import on_mesh
from ..ops import join as _j
from ..ops import partition as _p
from ..ops.sort import KeyCol
from . import shuffle as _sh
from . import topo as _topo


class ShardTable(NamedTuple):
    """Per-shard view: list of (data, valid) columns + live-row count."""

    cols: Tuple[KeyCol, ...]
    n: jax.Array  # scalar int32


def fused_exchange_bytes(
    world: int,
    bucket_cap: int,
    respill: int,
    row_bytes_l: int,
    row_bytes_r: int,
    num_slices: int = 1,
) -> int:
    """Global padded exchange bytes of one fused join/q3 step: each side
    ships ``num_slices x (1 + respill)`` header-augmented all_to_all
    buffers of ``world x (cap + 1)`` rows per shard. The fused-path twin
    of the eager planner's ``shuffle.exchanged_bytes`` accounting (one
    formula, so the eager and fused regimes compare like-for-like)."""
    rows = world * world * (bucket_cap + _sh.HEADER_ROWS)
    per_side = num_slices * (1 + respill) * rows
    return per_side * (row_bytes_l + row_bytes_r)


def fused_axis_bytes(
    world: int,
    bucket_cap: int,
    respill: int,
    row_bytes: int,
    topo: Optional[_topo.Topology],
    num_slices: int = 1,
) -> Tuple[int, int]:
    """(intra, inter) collective bytes of one side's fused shuffles — the
    fused twin of ``topo.axis_coll_bytes`` feeding the same
    ``shuffle.coll_bytes.{intra,inter}`` counters. The STRUCTURED two-hop
    (``topo.exchange_buffer_structured``) keeps cap-sized chunks, so the
    cross-outer volume equals the flat exchange's — the win is message
    aggregation ((outer - 1) combined transfers instead of (P - inner)
    small ones over the slow fabric) — while the inner hop re-ships every
    chunk across the fast links once more. Flat on a declared 2-D mesh
    splits by destination group; no topology counts everything inter."""
    k = max(num_slices * (1 + respill), 1)
    rows_chunk = bucket_cap + _sh.HEADER_ROWS
    if topo is None:
        return 0, k * world * (world - 1) * rows_chunk * row_bytes
    o, i = topo
    intra = k * world * (i - 1) * o * rows_chunk * row_bytes
    inter = k * world * (o - 1) * i * rows_chunk * row_bytes
    return intra, inter


def _shuffle_rounds(
    st: ShardTable,
    cnt: jax.Array,
    dest_fn,
    world: int,
    bucket_cap: int,
    axis_name: str,
    respill: int,
    quant=None,
    topo: Optional[_topo.Topology] = None,
) -> Tuple[ShardTable, jax.Array]:
    """The shared respill-round loop: ``dest_fn(r) -> (dest, leftover)``
    supplies each round's send slots (plain hash shuffle or one hash
    slice of a SlicePlan); everything else — header-fused exchange, mask
    accumulation, compaction, overflow psum — is identical machinery and
    lives ONCE here. The per-round receive counts ride the payload
    collective's header lanes (shuffle.exchange_columns_fused), so each
    round is ONE all_to_all instead of a count exchange + a payload
    exchange — half the collectives per fused shuffle.

    Wire narrowing: a fully fused program has no host stats step, so only
    the STATIC narrowings engage here — validity masks and bool data pack
    to 1 bit/row, f16/bf16 ship native 16 bits, and (under ``quant``, the
    per-column lossy-codec spec from ops.quant.quant_spec) float payload
    columns ride the quantized tier, whose block scales travel in the
    exchange headers and need no host step either
    (gather.static_wire_plan); remaining value lanes ride full width.
    The eager chunked engine (table._shuffle_many) does the stats-driven
    narrowing."""
    from ..ops.gather import static_wire_plan

    wire = static_wire_plan(st.cols, quant=quant)
    rounds = 1 + respill
    parts = [[] for _ in st.cols]  # per column: one [P*cap] block per round
    masks = []
    total = jnp.int32(0)
    leftover = jnp.int32(0)
    for r in range(rounds):
        dest, leftover = dest_fn(r)
        got, recv_counts = _sh.exchange_columns_fused(
            st.cols, dest, _sh.round_counts(cnt, bucket_cap, r),
            world, bucket_cap, axis_name, wire=wire, topo=topo,
        )
        for ci, dv in enumerate(got):
            parts[ci].append(dv)
        mask_r, total_r = _sh.received_row_mask(recv_counts, world, bucket_cap)
        masks.append(mask_r)
        total = total + total_r
    cols_cat = []
    for ci, (_, valid) in enumerate(st.cols):
        d = jnp.concatenate([p[0] for p in parts[ci]])
        v = None if valid is None else jnp.concatenate([p[1] for p in parts[ci]])
        cols_cat.append((d, v))
    out_cols = _sh.compact_received(cols_cat, jnp.concatenate(masks))
    overflow = jax.lax.psum(leftover, axis_name)
    return ShardTable(tuple(out_cols), total), overflow


def shuffle_shard(
    st: ShardTable,
    key_idx: Sequence[int],
    world: int,
    bucket_cap: int,
    axis_name: str,
    respill: int = 1,
    quant=None,
    topo: Optional[_topo.Topology] = None,
) -> Tuple[ShardTable, jax.Array]:
    """Static-capacity hash shuffle of one table (per-shard code).

    ``respill`` extra exchange rounds drain buckets hotter than
    ``bucket_cap`` without any host sync (SURVEY.md §7 two-round-respill
    plan): round r moves each bucket's rows [r*cap, (r+1)*cap), so the
    overflow flag only trips when a bucket exceeds (1+respill)*cap.

    Returns (shuffled shard table [(1+respill)*world*bucket_cap rows],
    overflow count = rows still unsent after the final round, psum'd).
    """
    keys = [st.cols[i] for i in key_idx]
    pid = _p.hash_partition_ids(keys, st.n, world)
    cnt = _sh.bucket_counts(pid, world)
    return _shuffle_rounds(
        st, cnt,
        lambda r: _sh.build_send_slots_round(pid, cnt, world, bucket_cap, r),
        world, bucket_cap, axis_name, respill, quant=quant, topo=topo,
    )


# slice bits live at hash_shift=24 (bits 24..31): shard pid uses the low
# bits, the out-of-core bucket split uses bits 16..23 (ooc subpart
# hash_shift=16, up to 256 buckets) — reusing shift 16 here would make
# every ooc bucket land in ONE slice (bucket b fixes those bits), turning
# K-1 slice rounds into empty work and the live one into guaranteed
# capacity overflow. 8 bits also caps num_slices at 256.
SLICE_HASH_SHIFT = 24
MAX_SLICES = 256


def sliced_shuffle_shard(
    st: ShardTable,
    plan: "_sh.SlicePlan",
    slice_idx,
    world: int,
    bucket_cap: int,
    axis_name: str,
    respill: int = 1,
    quant=None,
    topo: Optional[_topo.Topology] = None,
) -> Tuple[ShardTable, jax.Array]:
    """One hash-slice's shuffle, driven by the precomputed
    :class:`shuffle.SlicePlan` (one combined sort serves every slice —
    this adds only elementwise slot math + the exchanges). ``slice_idx``
    may be a traced scalar: one compiled body serves all K slices."""
    cnt = _sh.slice_counts(plan, slice_idx)
    return _shuffle_rounds(
        st, cnt,
        lambda r: _sh.slice_round_dest(plan, slice_idx, bucket_cap, r),
        world, bucket_cap, axis_name, respill, quant=quant, topo=topo,
    )


def join_shard(
    left: ShardTable,
    right: ShardTable,
    l_key_idx: Sequence[int],
    r_key_idx: Sequence[int],
    how: int,
    join_cap: int,
) -> Tuple[ShardTable, jax.Array]:
    """Static-capacity local join (per-shard). Returns (joined table
    [join_cap rows] = left cols ++ right cols, overflow count)."""
    lk = [left.cols[i] for i in l_key_idx]
    rk = [right.cols[i] for i in r_key_idx]
    # spec_join fuses probe + count + emit with the minimal pass count (the
    # right payload rides the key sort on INNER/LEFT); its exact total both
    # sizes the overflow lane and equals the emitted row count
    out, needed, shadow, _handed = _j.spec_join(
        lk, rk, list(left.cols), list(right.cols),
        left.n, right.n, how, join_cap,
    )
    # int32-wrap guard (the shadow is a float32 mirror of the inner count):
    # a shard with > 2^31 matches wraps `needed` — report saturated overflow
    # and an empty shard instead of silently bogus counts (the eager path
    # raises via _check_join_count; here the flag is the only channel)
    wrapped = (needed < 0) | (shadow > jnp.float32(2**31))
    overflow = jnp.where(
        wrapped, jnp.int32(2**31 - 1), jnp.maximum(needed - join_cap, 0)
    )
    n_out = jnp.where(wrapped, 0, jnp.minimum(needed, join_cap))
    return ShardTable(tuple(out), n_out), overflow


def make_distributed_join_step(
    mesh: Mesh,
    axis_name: str,
    l_key_idx: Sequence[int],
    r_key_idx: Sequence[int],
    how: int,
    bucket_cap: int,
    join_cap: int,
    respill: int = 1,
    num_slices: int = 1,
    quant_l=None,
    quant_r=None,
    topo: Optional[_topo.Topology] = None,
):
    """Build the jittable distributed-join step over the mesh.

    ``quant_l`` / ``quant_r``: optional per-column lossy-codec specs
    (ops.quant.quant_spec over each side's dtypes with its key columns
    excluded) — float payload lanes then ride the quantized wire tier
    through each fused shuffle, block scales in the exchange headers.
    Static build parameters: the caller's kernel cache key must include
    them (table._fused_join appends the pair).

    ``topo``: the effective 2-D topology (parallel/topo.effective) — each
    fused shuffle's exchange then routes as the structured two-hop
    (inner grouped all_to_all, then outer; topo.exchange_buffer_
    structured) with an output layout identical to the flat collective.
    Static build parameter like the quant specs: it joins the caller's
    cache key, and the CYLON_TPU_NO_TOPO differential passes None here.

    Signature of the returned fn (global, row-sharded arrays):
      (l_cols, l_counts[P], r_cols, r_counts[P]) ->
      (out_cols [P*num_slices*join_cap], out_counts [P], overflow [2P])
    where overflow carries TWO lanes per shard — reshape(-1, 2) gives
    [:, 0] = rows the shuffle could not send (bucket_cap exceeded after all
    respill rounds) and [:, 1] = join rows past the PER-SLICE join_cap
    (exact shortfall, so a retry can size join_cap in one step).

    ``num_slices = K > 1`` runs the join as K hash-slice rounds (PARITY.md
    north-star lever 1): round k shuffles + joins only slice k's rows, so
    every probe sort works on ~n/K elements — passes drop from log^2(n)
    to log^2(n/K) while total shuffle volume is unchanged. The K slice
    outputs are compacted to one live prefix with a single extra
    sort+gather over the output. Requires world > 1 (the slice filter
    rides the shuffle's send-slot builder).

    This is the whole reference DistributedJoin call stack (SURVEY.md §3.2)
    as ONE compiled XLA program: hash -> scatter -> all_to_all -> sort-join
    -> gather, with collectives over the mesh axis.
    """
    world = mesh.shape[axis_name]
    if num_slices > 1 and world <= 1:
        raise ValueError(
            "num_slices > 1 requires a multi-device mesh (slice selection "
            "rides the shuffle)"
        )
    if num_slices > MAX_SLICES:
        raise ValueError(
            f"num_slices is capped at {MAX_SLICES} (8 slice hash bits; "
            "see SLICE_HASH_SHIFT)"
        )

    def step(dp, rep):
        (l_cols, l_counts, r_cols, r_counts) = dp
        lt0 = ShardTable(tuple(l_cols), l_counts[0])
        rt0 = ShardTable(tuple(r_cols), r_counts[0])
        if world == 1:
            jt, ovj = join_shard(lt0, rt0, l_key_idx, r_key_idx, how, join_cap)
            overflow = jnp.stack([jnp.int32(0), ovj])
            return list(jt.cols), jt.n.reshape(1), overflow
        if num_slices == 1:
            lt, ovl = shuffle_shard(
                lt0, l_key_idx, world, bucket_cap, axis_name, respill,
                quant=quant_l, topo=topo,
            )
            rt, ovr = shuffle_shard(
                rt0, r_key_idx, world, bucket_cap, axis_name, respill,
                quant=quant_r, topo=topo,
            )
            jt, ovj = join_shard(lt, rt, l_key_idx, r_key_idx, how, join_cap)
            overflow = jnp.stack([ovl + ovr, ovj])
            return list(jt.cols), jt.n.reshape(1), overflow
        # sliced: ONE combined (slice, pid) sort per side serves all K
        # slice rounds (shuffle.SlicePlan), and ONE lax.scan body serves
        # all K slices — program size and compile time stay O(1) in K
        # (an unrolled loop would emit K copies of the shuffle + sort-join
        # and 2K(1+respill) collectives in a single program)
        plans = []
        for st_, key_idx in ((lt0, l_key_idx), (rt0, r_key_idx)):
            keys = [st_.cols[i] for i in key_idx]
            pid = _p.hash_partition_ids(keys, st_.n, world)
            sid = _p.hash_partition_ids(
                keys, st_.n, num_slices, hash_shift=SLICE_HASH_SHIFT
            )
            plans.append(_sh.build_slice_plan(pid, sid, world, num_slices))
        plan_l, plan_r = plans

        valid_flags: list = []  # per-column validity presence (trace-time)

        def slice_body(carry, s):
            ov_sh, ov_j = carry
            lt, ovl = sliced_shuffle_shard(
                lt0, plan_l, s, world, bucket_cap, axis_name, respill,
                quant=quant_l, topo=topo,
            )
            rt, ovr = sliced_shuffle_shard(
                rt0, plan_r, s, world, bucket_cap, axis_name, respill,
                quant=quant_r, topo=topo,
            )
            jt, ovj = join_shard(lt, rt, l_key_idx, r_key_idx, how, join_cap)
            # validity presence is a STATIC per-column property (identical
            # across slices); scan traces this body once, so record it here
            # and stack data always, validity lanes only where present
            if not valid_flags:
                valid_flags.extend(v is not None for _d, v in jt.cols)
            ys = (
                tuple(d for d, _v in jt.cols),
                tuple(v for _d, v in jt.cols if v is not None),
                jt.n,
            )
            return (ov_sh + ovl + ovr, jnp.maximum(ov_j, ovj)), ys

        # the carry must match the body outputs' varying-manual-axes type
        # under shard_map: mark the unvarying zero initializers as varying
        # over the mesh axis
        zero = pvary(jnp.int32(0), axis_name)
        (ov_shuffle, ov_join), (ds, vs, ns) = jax.lax.scan(
            slice_body,
            (zero, zero),
            jnp.arange(num_slices, dtype=jnp.int32),
        )
        # reassemble the [K, join_cap]-stacked outputs into flat columns and
        # compact the K live prefixes into ONE (a segment mask + one stable
        # sort + one packed gather — the only output-sized cost of slicing)
        total = jnp.sum(ns).astype(jnp.int32)
        seg_pos = jnp.tile(jnp.arange(join_cap, dtype=jnp.int32), num_slices)
        seg_n = jnp.repeat(ns, join_cap)
        mask = seg_pos < seg_n
        cols_cat = []
        vi = 0
        for ci in range(len(ds)):
            d = ds[ci].reshape(num_slices * join_cap)
            if valid_flags[ci]:
                v = vs[vi].reshape(num_slices * join_cap)
                vi += 1
            else:
                v = None
            cols_cat.append((d, v))
        assert vi == len(vs)
        out_cols = _sh.compact_received(cols_cat, mask)
        overflow = jnp.stack([ov_shuffle, ov_join])
        return list(out_cols), total.reshape(1), overflow

    return jax.jit(
        shard_map(
            on_mesh(mesh, step),
            mesh=mesh,
            in_specs=(PartitionSpec(axis_name), PartitionSpec()),
            out_specs=PartitionSpec(axis_name),
        )
    )


def make_join_groupby_step(
    mesh: Mesh,
    axis_name: str,
    l_key_idx: Sequence[int],
    r_key_idx: Sequence[int],
    agg_col_idx: int,
    how: int,
    bucket_cap: int,
    join_cap: int,
    group_cap: int,
    respill: int = 1,
    quant_l=None,
    quant_r=None,
    quant_tol: float = 0.0,
    topo: Optional[_topo.Topology] = None,
):
    """Distributed join followed by groupby-sum on the join key and a global
    psum'd total — the TPC-H Q3-ish fused step used by benchmarks and the
    multi-chip dry run.

    ``quant_l`` / ``quant_r`` thread the lossy wire tier through the two
    fused shuffles (see :func:`make_distributed_join_step`);
    ``quant_tol`` additionally quantizes the grand-total psum — each
    shard's partial of the fused join->groupby-SUM overflow reduction is
    bf16-rounded before an exact reduction when the tolerance covers one
    2^-9 crossing per partial (ops.quant.QB16_TOL). All three are static
    build parameters the caller's cache key must include."""
    from ..ops import groupby as _g
    from ..ops.quant import QB16_TOL

    world = mesh.shape[axis_name]

    def step(dp, rep):
        (l_cols, l_counts, r_cols, r_counts) = dp
        lt = ShardTable(tuple(l_cols), l_counts[0])
        rt = ShardTable(tuple(r_cols), r_counts[0])
        if world > 1:
            lt, _ = shuffle_shard(
                lt, l_key_idx, world, bucket_cap, axis_name, respill,
                quant=quant_l, topo=topo,
            )
            rt, _ = shuffle_shard(
                rt, r_key_idx, world, bucket_cap, axis_name, respill,
                quant=quant_r, topo=topo,
            )
        # group key == join key and SUM over a floating LEFT column: the
        # whole join+groupby collapses into the probe sort (per key run,
        # sum = c_r * sum(v_l)) — ops/join.join_sum_by_key_pushdown. ~2
        # sorts instead of ~8-9; the reference always materializes the join
        # first (groupby/groupby.cpp:33-91).
        agg_is_left = agg_col_idx < len(lt.cols)
        agg_dtype = (lt.cols if agg_is_left else rt.cols)[
            agg_col_idx if agg_is_left else agg_col_idx - len(lt.cols)
        ][0].dtype
        if (
            how == _j.INNER
            and agg_is_left
            and jnp.issubdtype(agg_dtype, jnp.floating)
            and np.dtype(agg_dtype).itemsize <= 4
            # f64 takes the generic path: a gate on a rule PR 30 retired
            # (a 64-bit lane does ride a TPU sort), kept with the
            # planner's twin of it until ROADMAP D15 lifts both
        ):
            lk = [lt.cols[i] for i in l_key_idx]
            rk = [rt.cols[i] for i in r_key_idx]
            s, ng, n_join, _og = _j.join_sum_by_key_pushdown(
                lk, rk, lt.cols[agg_col_idx], lt.n, rt.n, group_cap
            )
        else:
            jt, _ = join_shard(lt, rt, l_key_idx, r_key_idx, how, join_cap)
            # group on the (left) join key, sum the aggregate column
            keys = [jt.cols[i] for i in l_key_idx]
            _k, ((s, _sv),), ng = _g.groupby_aggregate(
                keys, [jt.cols[agg_col_idx]], [(_g.SUM, 0)], jt.n, group_cap
            )
            n_join = jt.n
        total = s.sum()
        if world > 1:
            if quant_tol >= QB16_TOL and jnp.issubdtype(
                total.dtype, jnp.floating
            ):
                # quantized psum: each shard's grand-total PARTIAL is
                # bf16-quantized (one RNE crossing per partial, rel err
                # <= 2^-9 of the partial magnitudes) and the reduction
                # itself runs exactly in the original dtype — reducing
                # IN bf16 would compound (world-1) rounding steps and
                # break the single-crossing error budget
                q = total.astype(jnp.bfloat16).astype(total.dtype)
                total = jax.lax.psum(q, axis_name)
            else:
                total = jax.lax.psum(total, axis_name)
        return s, ng.reshape(1), n_join.reshape(1), total.reshape(1)

    return jax.jit(
        shard_map(
            on_mesh(mesh, step),
            mesh=mesh,
            in_specs=(PartitionSpec(axis_name), PartitionSpec()),
            out_specs=PartitionSpec(axis_name),
        )
    )
