"""The all-to-all shuffle: the heart of every Distributed* op.

Reference analog: the whole L0-L2 stack — MPIChannel's nonblocking pairwise
messages (cpp/src/cylon/net/mpi/mpi_channel.cpp:30-233), the buffer-level
AllToAll with per-target queues + FIN protocol (net/ops/all_to_all.cpp:64-177)
and the Arrow-aware table reassembly (arrow/arrow_all_to_all.cpp:68-231).

TPU-native design: none of that machinery survives. The exchange is a
CHUNKED pipeline of bounded-size ``lax.all_to_all`` rounds (Exoshuffle's
composable-rounds thesis, PAPERS.md): the host sizes ``bucket_cap`` from a
per-round BYTE BUDGET (:func:`plan_rounds`; config.py) so peak exchange
memory is O(budget) instead of O(max-shard padding), hot buckets drain over
``ceil(count/cap)`` rounds, and each round's per-destination send counts
ride HEADER ROWS of the packed lane buffer (:func:`pack_by_sort` /
:func:`split_header`) — one collective per round moves the payload AND the
counts, so a distributed join issues 2 collectives, not 4. The pack moves
rows by a sort keyed by destination, never by a row-sized scatter.
"Reassembly" is a lane-level front-pack: what one hop leaves is ``P``
equal chunks, each a live prefix, and each is written as one block at the
running offset of the received counts (:func:`front_pack_chunks`, handed
to :func:`compact_received_lanes` as :func:`chunk_front`; no sort, no
gather). The two-hop receive and the ring relay, whose chunks are not
equal, keep a compaction argsort and a gather an array
(:func:`order_front`). The round scheduler and double-buffered dispatch
live in ``table.py _shuffle_many``.

The scatter chain that the sorted pack replaced stays for two reasons and
no other: :func:`build_send_slots_round`, :func:`pack_lane_buffer`,
:func:`scatter_send` and the row-space :func:`quant_chunk_scales` are the
bit-identity reference ``tests/test_shuffle_pack_sorted.py`` holds
:func:`pack_by_sort` to, and they are still the pack of the paths that
have not been converted: the relay and ring kernels of ``table.py``
(:func:`scatter_send` over :func:`relay_send_slots`) and the fused
in-program pipeline (``parallel/pipeline.py`` through
:func:`exchange_columns_fused`).

Runs inside ``shard_map``; every function here is per-shard code.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import stages as _stages
from ..ops import sort as _sort
from ..ops.gather import (
    lane_plan,
    pack_cols,
    pack_gather,
    unpack_cols,
    wire_pack_cols,
    wire_q8_cols,
    wire_unpack_cols,
)
from ..ops.partition import bin_counts

Cols = Sequence[Tuple[jax.Array, Optional[jax.Array]]]

# one header row per (src, dst) chunk of the lane buffer carries that
# round's send count in lane 0 — the count exchange rides the payload
# all_to_all instead of being its own collective (2 collectives per
# distributed join instead of 4)
HEADER_ROWS = 1

# dispatch-count bound for extreme skew: past this many rounds plan_rounds
# raises bucket_cap (over the byte budget) rather than exploding round
# count. NOTE this raise is GLOBAL — a single over-budget bucket inflates
# every bucket's cap — which is exactly the case the skew-adaptive
# schedule (parallel/spill.plan_schedule) removes: heavy-bucket tails
# leave the collective through the host relay and the cap stays sized for
# the cold histogram.
DEFAULT_MAX_ROUNDS = 16


def ordering_after_shuffle(kind: str):
    """Order property of a shuffled table (cylon_tpu/ordering.py): always
    ``None``. A hash/task shuffle reroutes rows by placement; a range
    shuffle co-locates key ranges but leaves shards internally unordered
    (the caller's local sort re-establishes — and upgrades to global
    scope). Crucially, even a single-key range shuffle destroys the
    WITHIN-shard property across the chunked engine's K rounds: each round
    lands as one contiguous block per source shard (`compact_received_lanes`
    front-packs arrival order: source-major, round-major after the
    table-level concat), so two rounds' key ranges interleave — sortedness
    must never be claimed to "survive" the exchange, at any K."""
    if kind not in ("hash", "range", "task"):
        raise ValueError(f"unknown shuffle kind {kind!r}")
    return None


def bucket_counts(pid: jax.Array, num_partitions: int) -> jax.Array:
    """Rows per target partition on this shard -> [P] int32 (padding pid==P
    is dropped): :func:`ops.partition.bin_counts`, which for the handful of
    partitions a mesh has is a compare against the partition ids and a sum
    over the rows, not a scatter-add."""
    return bin_counts(pid, num_partitions)


def exchange_counts(counts: jax.Array, axis_name: str) -> jax.Array:
    """all_to_all the [P] send-counts -> [P] receive-counts (entry s = rows
    arriving from source shard s)."""
    with jax.named_scope(_stages.SHUFFLE_ALL_TO_ALL):
        return jax.lax.all_to_all(
            counts.reshape(-1, 1), axis_name, split_axis=0, concat_axis=0, tiled=False
    ).reshape(-1)


def shuffle_gather_order(pid: jax.Array) -> jax.Array:
    """Stable order grouping rows by target partition: the native stable
    argsort of the id lane (the padding/dropped sentinel, the number of
    partitions, is the largest id, so it groups last)."""
    with jax.named_scope(_stages.SORT_ENGINE):
        return jnp.argsort(pid, stable=True).astype(jnp.int32)


def build_send_slots_round(
    pid: jax.Array,
    counts: jax.Array,
    num_partitions: int,
    bucket_cap: int,
    round_idx,
) -> Tuple[jax.Array, jax.Array]:
    """Destination slot in the [P * bucket_cap] send buffer for every row
    whose within-bucket position falls in round ``round_idx``'s window
    [r*cap, (r+1)*cap); rows of other rounds are dropped (they are exchanged
    in their own round — the skew/respill mechanism: a hot bucket drains
    over ceil(count/cap) rounds instead of forcing a global max-sized cap).

    ``round_idx`` may be a traced scalar, so ONE compiled program serves
    every round. Returns (dest [cap] int32 with P*bucket_cap meaning
    not-this-round, leftover scalar = rows still unsent AFTER this round).

    The round windows double as the skew-adaptive schedule's bucket-slice
    clamp (parallel/spill.RoundSchedule): a K-round plan ships exactly the
    first ``K * bucket_cap`` rows of every bucket — rows past that quota
    fall outside every round's window here (and outside every round's
    header count in :func:`round_counts`), and the adaptive planner routes
    them through the host relay (:func:`relay_send_slots`) instead of
    padding the cap up to the hottest bucket.
    """
    with jax.named_scope(_stages.SHUFFLE_PACK):
        cap = pid.shape[0]
        order = shuffle_gather_order(pid)
        spid = pid[order]
        starts = jnp.cumsum(counts) - counts  # exclusive prefix per partition
        safe_pid = jnp.clip(spid, 0, num_partitions - 1)
        pos = jnp.arange(cap, dtype=jnp.int32) - starts[safe_pid]  # pos in bucket
        r = jnp.asarray(round_idx, jnp.int32)
        slot = pos - r * bucket_cap
        ok = (spid < num_partitions) & (slot >= 0) & (slot < bucket_cap)
        dest_sorted = jnp.where(
            ok, safe_pid * bucket_cap + slot, num_partitions * bucket_cap
        )
        dest = jnp.full((cap,), num_partitions * bucket_cap, jnp.int32).at[order].set(
            dest_sorted
        )
        leftover = jnp.sum(
            (spid < num_partitions) & (pos >= (r + 1) * bucket_cap)
        ).astype(jnp.int32)
        return dest, leftover


class SlicePlan(NamedTuple):
    """Precomputed state for hash-SLICED shuffles (PARITY.md north-star
    lever 1): ONE stable sort by the combined (slice, pid) id serves every
    slice round — per-slice send slots are derived with elementwise
    arithmetic only, so K slices cost K exchanges but still just one
    slot-building sort per table (a per-slice argsort would multiply the
    shuffle's sort work by K and eat the probe-depth saving slicing
    exists to buy)."""

    order: jax.Array   # [cap] stable argsort of comb
    scomb: jax.Array   # [cap] comb[order]
    bounds: jax.Array  # [K*(world+1)+1] per-(slice,pid) starts (sorted space)
    world: int
    num_slices: int


def build_slice_plan(
    pid: jax.Array, sid: jax.Array, world: int, num_slices: int
) -> SlicePlan:
    """pid: [cap] target shard (padding = world); sid: [cap] hash slice
    (padding = num_slices). comb = sid*(world+1)+pid sorts padding last."""
    comb = (sid * jnp.int32(world + 1) + pid).astype(jnp.int32)
    order = jnp.argsort(comb, stable=True).astype(jnp.int32)
    scomb = comb[order]
    qs = jnp.arange(num_slices * (world + 1) + 1, dtype=jnp.int32)
    bounds = jnp.searchsorted(scomb, qs).astype(jnp.int32)
    return SlicePlan(order, scomb, bounds, world, num_slices)


def slice_counts(plan: SlicePlan, slice_idx) -> jax.Array:
    """Per-target-pid counts [world] of slice ``slice_idx`` (traced ok)."""
    world = plan.world
    base = jnp.asarray(slice_idx, jnp.int32) * jnp.int32(world + 1)
    starts = jax.lax.dynamic_slice(plan.bounds, (base,), (world,))
    return jax.lax.dynamic_slice(plan.bounds, (base + 1,), (world,)) - starts


def slice_round_dest(
    plan: SlicePlan, slice_idx, bucket_cap: int, round_idx
) -> Tuple[jax.Array, jax.Array]:
    """(dest [cap], leftover) for one slice+round — the
    :func:`build_send_slots_round` formula evaluated inside slice
    ``slice_idx``'s contiguous span of the plan's sorted space. Rows of
    other slices (and padding) get the dropped destination. Both
    ``slice_idx`` and ``round_idx`` may be traced scalars, so ONE compiled
    program serves every (slice, round)."""
    world = plan.world
    cap = plan.order.shape[0]
    s = jnp.asarray(slice_idx, jnp.int32)
    base = s * jnp.int32(world + 1)
    starts = jax.lax.dynamic_slice(plan.bounds, (base,), (world,))
    idx = jnp.arange(cap, dtype=jnp.int32)
    lo_s = starts[0]
    hi_s = jax.lax.dynamic_slice(plan.bounds, (base + jnp.int32(world),), (1,))[0]
    in_slice = (idx >= lo_s) & (idx < hi_s)
    spid = jnp.clip(plan.scomb - base, 0, world - 1)
    pos = idx - starts[spid]
    r = jnp.asarray(round_idx, jnp.int32)
    slot = pos - r * bucket_cap
    ok = in_slice & (slot >= 0) & (slot < bucket_cap)
    dest_sorted = jnp.where(
        ok, spid * bucket_cap + slot, world * bucket_cap
    )
    dest = jnp.full((cap,), world * bucket_cap, jnp.int32).at[
        plan.order
    ].set(dest_sorted)
    leftover = jnp.sum(
        in_slice & (pos >= (r + 1) * bucket_cap)
    ).astype(jnp.int32)
    return dest, leftover


def round_counts(counts: jax.Array, bucket_cap: int, round_idx) -> jax.Array:
    """Per-bucket send counts for one round: clip(counts - r*cap, 0, cap)."""
    r = jnp.asarray(round_idx, jnp.int32)
    return jnp.clip(counts - r * bucket_cap, 0, bucket_cap)


def relay_send_slots(
    pid: jax.Array,
    counts: jax.Array,
    num_partitions: int,
    quota,
    relay_cap: int,
    sel: Optional[jax.Array] = None,
) -> jax.Array:
    """Destination slot in the [relay_cap] RELAY buffer for every row whose
    within-bucket position is past the collective quota — the skew-split
    tail of the adaptive schedule (parallel/spill.plan_schedule): heavy
    buckets ship their first ``quota = K * bucket_cap`` rows through the
    K padded all_to_all rounds and the remainder through ONE host-mediated
    relay extraction, so a one-hot distribution costs O(rows) bytes
    instead of world x the padded rounds.

    ``quota`` may be a traced scalar (one compiled program serves every
    schedule at a given relay_cap). Relay rows keep the stable
    destination-major order of :func:`shuffle_gather_order`, so the host
    splits each source's buffer into per-destination runs with the
    planner's own [src, dst] relay counts — no count lane needed. Rows
    under quota (and padding) get the dropped slot ``relay_cap``.

    ``sel``: optional [P] bool per-DESTINATION selector — the two-hop
    engine splits one relay tail into the device ppermute ring (same
    outer group) and the host relay (cross-outer) by running this twice
    with complementary selectors. Selection keeps a subsequence of the
    destination-major order, so the host's per-destination-run split
    still works against the selector-masked relay count matrix.
    """
    cap = pid.shape[0]
    order = shuffle_gather_order(pid)
    spid = pid[order]
    starts = jnp.cumsum(counts) - counts
    safe_pid = jnp.clip(spid, 0, num_partitions - 1)
    pos = jnp.arange(cap, dtype=jnp.int32) - starts[safe_pid]
    q = jnp.asarray(quota, jnp.int32)
    ok = (spid < num_partitions) & (pos >= q)
    if sel is not None:
        ok = ok & sel[safe_pid]
    slot_sorted = jnp.where(
        ok, jnp.cumsum(ok.astype(jnp.int32)) - 1, relay_cap
    ).astype(jnp.int32)
    return jnp.full((cap,), relay_cap, jnp.int32).at[order].set(slot_sorted)


# ----------------------------------------------------------------------
# chunked-round planning (the byte-budget knob, config.py)
# ----------------------------------------------------------------------

def exchange_row_bytes(cols: Cols) -> int:
    """Bytes one row occupies in the round exchange buffers: 4 per int32
    lane of the packed codec (incl. validity lanes and the hi/lo split of
    64-bit ints), 8 per f64 passthrough column. This is what converts the
    per-round byte budget into a bucket capacity."""
    total = 0
    for tag, n_lanes, has_valid in lane_plan(cols):
        total += 8 if tag is None else 4 * n_lanes
        total += 4 if has_valid else 0
    return max(total, 1)


def budget_bucket_cap(
    row_bytes: int, num_partitions: int, byte_budget: int, max_cap: int
) -> int:
    """Largest power-of-two bucket_cap (<= max_cap) whose per-round send
    buffer ``P * cap * row_bytes`` fits the budget. Floor 8 (the engine
    minimum) — a budget below the floor's footprint is satisfied as closely
    as static shapes allow."""
    cap = 8
    while 2 * cap <= max_cap and (
        num_partitions * 2 * cap * row_bytes <= byte_budget
    ):
        cap *= 2
    return cap


def budget_for_rounds(
    max_bucket: int, k: int, num_partitions: int, row_bytes: int
) -> int:
    """Inverse of the budget bound: the byte budget that targets
    ``bucket_cap = round_cap(max(ceil(max_bucket / k), 8))`` and hence
    ~k rounds over a hottest bucket of ``max_bucket`` rows. The single
    source of the arithmetic used by benchmarks/tests/fuzz to sweep K —
    if :func:`plan_rounds`' floor or rounding changes, this moves with it."""
    from ..engine import round_cap

    cap = round_cap(max(-(-max_bucket // max(k, 1)), 8))
    return num_partitions * cap * row_bytes


def plan_rounds(
    send_counts: np.ndarray,
    row_bytes: int,
    num_partitions: int,
    byte_budget: int,
    max_rounds: int = DEFAULT_MAX_ROUNDS,
) -> Tuple[int, int]:
    """(bucket_cap, n_rounds) for the chunked exchange.

    bucket_cap is the tightest of three bounds: the full hot-bucket cap
    (one round, no chunking), the skew-balancing cap (4x the mean bucket —
    a hot bucket drains over rounds instead of inflating every bucket),
    and the BYTE-BUDGET cap (peak per-round exchange memory is
    O(P * cap * row_bytes) <= budget, so a table K times the budget
    shuffles in K bounded rounds without the full padded buffer ever
    materializing). n_rounds = ceil(hottest bucket / cap), bounded by
    ``max_rounds`` (beyond it the cap grows past the budget — dispatch
    count is the scarcer resource under extreme skew). That raise is
    GLOBAL: one over-budget bucket inflates every bucket's cap — the
    skew-adaptive planner (parallel/spill.plan_schedule) wraps this
    function to keep non-skewed plans byte-identical while routing
    heavy-bucket tails through the host relay instead of raising the cap.
    """
    from ..engine import round_cap

    max_cnt = int(send_counts.max()) if send_counts.size else 0
    mean_bucket = -(-int(send_counts.sum()) // max(send_counts.size, 1))
    c_full = round_cap(max_cnt)
    cap = c_full
    c_balanced = round_cap(4 * max(mean_bucket, 1))
    if c_balanced < cap:
        cap = c_balanced
    c_budget = budget_bucket_cap(row_bytes, num_partitions, byte_budget, c_full)
    if c_budget < cap:
        cap = c_budget
    n_rounds = max(-(-max_cnt // cap), 1)
    if n_rounds > max_rounds:
        cap = round_cap(-(-max_cnt // max_rounds))
        n_rounds = max(-(-max_cnt // cap), 1)
    return cap, n_rounds


# ----------------------------------------------------------------------
# send-side pack / collective / receive-side split (the three phases of a
# chunked round; the fused pipeline composes them in one program, by the
# scatter chain, the eager engine dispatches them as separate overlapped
# programs and packs by sort)
# ----------------------------------------------------------------------

def scatter_send(
    data: jax.Array, dest: jax.Array, num_partitions: int, bucket_cap: int
) -> jax.Array:
    """Scatter one column into its padded [P * bucket_cap, *trailing] send
    buffer: the relay and ring kernels' pack, the fused pipeline's float64
    columns, and the reference of :func:`pack_by_sort`'s ``pts``."""
    with jax.named_scope(_stages.SHUFFLE_PACK):
        trailing = data.shape[1:]
        return jnp.zeros((num_partitions * bucket_cap, *trailing), data.dtype).at[
            dest
    ].set(data, mode="drop")


def wire_header_rows(wplan) -> int:
    """Header rows one chunk of a wire-narrowed exchange needs: the round
    send count plus one f32 block scale per 'q8' field (the quantized
    tier, ops/quant.py), packed into the plan's L word lanes. Plans with
    no q8 fields keep today's single header row."""
    nq8 = len(wire_q8_cols(wplan)) if wplan is not None else 0
    if nq8 == 0:
        return HEADER_ROWS
    return max(1, -(-(1 + nq8) // wplan.n_words))


def header_slots(
    dest: jax.Array,
    num_partitions: int,
    bucket_cap: int,
    n_header: int = HEADER_ROWS,
) -> jax.Array:
    """Remap plain send slots into the header-augmented buffer layout
    [P * (bucket_cap + n_header)]: each chunk's data rows shift down by
    its header row(s); the dropped sentinel follows along."""
    pid = dest // bucket_cap  # == num_partitions for the dropped sentinel
    return jnp.where(
        dest >= num_partitions * bucket_cap,
        num_partitions * (bucket_cap + n_header),
        dest + (pid + 1) * n_header,
    ).astype(jnp.int32)


def _header_values(
    counts_round: jax.Array, header_extra: Optional[jax.Array], width: int
) -> jax.Array:
    """[P, width] int32: what a chunk's header rows hold, flattened: the
    round send count in lane 0, then ``header_extra``, zeros after."""
    hv = jnp.zeros((counts_round.shape[0], width), jnp.int32)
    hv = hv.at[:, 0].set(counts_round.astype(jnp.int32))
    if header_extra is not None:
        E = header_extra.shape[1]
        hv = hv.at[:, 1 : 1 + E].set(header_extra.astype(jnp.int32))
    return hv


def pack_lane_buffer(
    lanes: List[jax.Array],
    dest: jax.Array,
    counts_round: jax.Array,
    num_partitions: int,
    bucket_cap: int,
    header_extra: Optional[jax.Array] = None,
    n_header: int = HEADER_ROWS,
) -> jax.Array:
    """The fused pipeline's pack and the reference of :func:`pack_by_sort`'s
    ``head``: stack the int32 lanes and scatter them into the header-augmented
    send buffer [P * (bucket_cap + n_header), L]; the header rows of each
    destination chunk carry this shard's round send count for that
    destination (lane 0) followed by ``header_extra`` — [P, E] int32
    per-chunk metadata (the quantized tier's bitcast block scales) —
    wrapped across ``n_header`` rows (the fused count/scale exchange)."""
    with jax.named_scope(_stages.SHUFFLE_PACK):
        packed = jnp.stack(lanes, axis=1)  # [cap, L]
        L = packed.shape[1]
        rows = bucket_cap + n_header
        buf = jnp.zeros((num_partitions * rows, L), jnp.int32)
        if header_extra is None and n_header == 1:
            buf = buf.at[
                jnp.arange(num_partitions, dtype=jnp.int32) * rows, 0
            ].set(counts_round.astype(jnp.int32))
        else:
            hv = _header_values(counts_round, header_extra, n_header * L)
            hidx = (
                jnp.arange(num_partitions, dtype=jnp.int32)[:, None] * rows
                + jnp.arange(n_header, dtype=jnp.int32)[None, :]
            ).reshape(-1)
            buf = buf.at[hidx].set(hv.reshape(num_partitions * n_header, L))
        return buf.at[
            header_slots(dest, num_partitions, bucket_cap, n_header)
    ].set(packed, mode="drop")


def _round_windows(
    counts: jax.Array, num_partitions: int, bucket_cap: int, round_idx
):
    """``windows(x [cap]) -> [P, bucket_cap]``: of rows sorted by
    destination (the stable order of :func:`shuffle_gather_order`), the
    rows that round ``round_idx`` sends to each destination, zero past
    that destination's :func:`round_counts`. Destination ``p``'s rows are
    the contiguous ``[starts[p] + r*cap, ... + rc[p])`` of the sorted
    array, so each window is one ``dynamic_slice``; ``x`` is padded by a
    window's length, so that a slice near the end is never shifted back
    (one past the end reads the padding and is masked anyway)."""
    starts = jnp.cumsum(counts) - counts
    offs = starts + jnp.asarray(round_idx, jnp.int32) * bucket_cap
    rc = round_counts(counts, bucket_cap, round_idx)
    live = jnp.arange(bucket_cap, dtype=jnp.int32)[None, :] < rc[:, None]

    def windows(x: jax.Array) -> jax.Array:
        padded = jnp.concatenate([x, jnp.zeros((bucket_cap,), x.dtype)])
        w = jnp.stack([
            jax.lax.dynamic_slice(padded, (offs[p],), (bucket_cap,))
            for p in range(num_partitions)
        ])
        return jnp.where(live, w, jnp.zeros((), x.dtype))

    return windows, rc


def _ride_by_pid(pid: jax.Array, payloads: Sequence[jax.Array]) -> list:
    """``payloads`` in the stable order of their rows' destination (the
    dropped sentinel last): they ride one sort keyed by ``pid``, in
    batches when there are many (:func:`~cylon_tpu.ops.sort.ride_sort`)."""
    def by_pid(pays):
        with jax.named_scope(_stages.SORT_ENGINE):
            out = jax.lax.sort((pid, *pays), num_keys=1, is_stable=True)
        return out[0], list(out[1:])

    return _sort.ride_sort(by_pid, payloads)[1]


def pack_by_sort(
    lanes: List[jax.Array],
    passthrough: Sequence[jax.Array],
    pid: jax.Array,
    counts: jax.Array,
    num_partitions: int,
    bucket_cap: int,
    round_idx,
    header_extra: Optional[jax.Array] = None,
    n_header: int = HEADER_ROWS,
) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """The pack phase of a chunked round without a row-sized scatter or
    gather: every int32 lane and every passthrough column rides ONE stable
    sort keyed by the row's destination ``pid`` (a sort moves a row in
    2-4 ns on a v5e where a scatter moves it in 80), and each
    destination's chunk of round ``round_idx`` (a traced scalar: one
    program serves every round) is a contiguous window of the sorted rows
    (:func:`_round_windows`).

    Returns ``(head, pts)`` bit for bit as :func:`build_send_slots_round`
    + :func:`pack_lane_buffer` + :func:`scatter_send` lay them: ``head``
    the header-augmented lane buffer ``[P * (bucket_cap + n_header), L]``
    (``round_counts`` itself when there is no lane), ``pts`` one
    ``[P * bucket_cap]`` send buffer a passthrough column. The stable sort
    keeps the arrival order inside a bucket that the slots gave."""
    with jax.named_scope(_stages.SHUFFLE_PACK):
        windows, rc = _round_windows(
            counts, num_partitions, bucket_cap, round_idx
        )
        rode = _ride_by_pid(pid, list(lanes) + list(passthrough))
        pts = tuple(windows(x).reshape(-1) for x in rode[len(lanes):])
        if not lanes:
            return rc, pts
        L = len(lanes)
        hv = _header_values(rc, header_extra, n_header * L)
        data = jnp.stack([windows(x) for x in rode[:L]], axis=2)
        head = jnp.concatenate(
            [hv.reshape(num_partitions, n_header, L), data], axis=1
        )
        return head.reshape(-1, L), pts


def exchange_buffer(buf: jax.Array, num_partitions: int, axis_name: str) -> jax.Array:
    """all_to_all a [P * rows, *trailing] send buffer; chunk s of the output
    holds what source shard s sent."""
    with jax.named_scope(_stages.SHUFFLE_ALL_TO_ALL):
        trailing = buf.shape[1:]
        rows = buf.shape[0] // num_partitions
        return jax.lax.all_to_all(
            buf.reshape(num_partitions, rows, *trailing),
            axis_name,
            split_axis=0,
            concat_axis=0,
            tiled=False,
    ).reshape(num_partitions * rows, *trailing)


def split_header(
    got: jax.Array, num_partitions: int, n_header: int = HEADER_ROWS
) -> Tuple[jax.Array, jax.Array]:
    """Strip the header rows off a received lane buffer: (data rows
    [P * bucket_cap, L], recv_counts [P] — entry s = rows source shard s
    sent this round)."""
    rows = got.shape[0] // num_partitions
    g = got.reshape(num_partitions, rows, *got.shape[1:])
    recv_counts = g[:, 0, 0].astype(jnp.int32)
    data = g[:, n_header:].reshape(
        num_partitions * (rows - n_header), *got.shape[1:]
    )
    return data, recv_counts


def split_header_scales(
    got: jax.Array, num_partitions: int, n_header: int, nq8: int
) -> jax.Array:
    """[P, nq8] f32 per-source-chunk block scales from a received
    buffer's header rows (written by :func:`pack_lane_buffer`'s
    ``header_extra`` — lane positions 1..nq8 of the flattened header)."""
    rows = got.shape[0] // num_partitions
    L = got.shape[1]
    g = got.reshape(num_partitions, rows, L)
    flat = g[:, :n_header].reshape(num_partitions, n_header * L)
    return jax.lax.bitcast_convert_type(
        flat[:, 1 : 1 + nq8], jnp.float32
    )


# ----------------------------------------------------------------------
# quantized-tier block scales (ops/quant.py): one f32 max-abs scale per
# (destination chunk, q8 field) computed at pack, shipped in the header
# rows, broadcast back per received row at compact
# ----------------------------------------------------------------------

def _q8_magnitudes(cols: Cols, wplan) -> List[jax.Array]:
    """The finite magnitude (f32, 0 for NaN and infinities) of every q8
    column in field order: what a block scale is the max of."""
    mags = []
    for ci, _dt in wire_q8_cols(wplan):
        x = cols[ci][0].astype(jnp.float32)
        mags.append(jnp.where(jnp.isfinite(x), jnp.abs(x), jnp.float32(0.0)))
    return mags


def quant_chunk_scales(
    cols: Cols, wplan, dest: jax.Array, num_partitions: int,
    bucket_cap: int,
) -> jax.Array:
    """[P, nq8] strictly-positive f32 block scales from row-space slots
    (the fused pipeline's, and the reference of
    :func:`quant_chunk_scales_sorted`): the finite max-abs of
    every q8 column over THIS round's rows bound for each destination
    chunk (rows outside the round window carry the dropped sentinel and
    never contribute — their magnitudes belong to their own round's or
    the relay's block)."""
    from ..ops import quant as _q

    chunk = dest // bucket_cap  # sentinel rows -> num_partitions (dropped)
    scales = []
    for mag in _q8_magnitudes(cols, wplan):
        bm = jnp.zeros((num_partitions,), jnp.float32).at[chunk].max(
            mag, mode="drop"
        )
        scales.append(_q.safe_scale(bm))
    return jnp.stack(scales, axis=1)


def quant_chunk_scales_sorted(
    cols: Cols, wplan, pid: jax.Array, counts: jax.Array,
    num_partitions: int, bucket_cap: int, round_idx,
) -> jax.Array:
    """:func:`quant_chunk_scales` for :func:`pack_by_sort`, which has no
    row-space ``dest``: the q8 columns' magnitudes ride the same stable
    sort by destination and each chunk's scale is the max over its round
    window (the same rows, so the same scale)."""
    from ..ops import quant as _q

    windows, _rc = _round_windows(counts, num_partitions, bucket_cap, round_idx)
    return jnp.stack(
        [
            _q.safe_scale(jnp.max(windows(mag), axis=1))
            for mag in _ride_by_pid(pid, _q8_magnitudes(cols, wplan))
        ],
        axis=1,
    )


def send_row_scales(
    scales: jax.Array, dest: jax.Array, bucket_cap: int
) -> jax.Array:
    """[cap, nq8] per-row scales for :func:`~cylon_tpu.ops.gather
    .wire_pack_cols`: each row reads its destination chunk's scale
    (dropped rows clamp to the last chunk — they never ship)."""
    chunk = jnp.clip(dest // bucket_cap, 0, scales.shape[0] - 1)
    return scales[chunk]


def recv_row_scales(
    scales_recv: jax.Array, num_partitions: int, bucket_cap: int
) -> jax.Array:
    """[P * bucket_cap, nq8] per-row scales on the receive side: row i of
    the stripped data buffer came from source chunk i // bucket_cap."""
    src = (
        jnp.arange(num_partitions * bucket_cap, dtype=jnp.int32)
        // bucket_cap
    )
    return scales_recv[src]


def exchange_columns_fused(
    cols: Cols,
    dest: jax.Array,
    counts_round: jax.Array,
    num_partitions: int,
    bucket_cap: int,
    axis_name: str,
    wire=None,
    bases: Optional[jax.Array] = None,
    topo=None,
) -> Tuple[List[Tuple[jax.Array, Optional[jax.Array]]], jax.Array]:
    """Exchange every column in one packed scatter and ONE all_to_all (the
    fused pipeline's round; float64 columns, which have no 32-bit lane
    route on TPU, take a scatter and a collective each) with the COUNT
    EXCHANGE FUSED into the payload collective: the per-destination round
    send counts ride the header row of the packed lane buffer, so one
    all_to_all moves the whole table AND the counts (vs a dedicated count
    collective per round — this is what takes a distributed join from 4
    collectives to 2).

    ``wire``: an optional :class:`~cylon_tpu.ops.gather.WirePlan` — the
    exchanged lanes are then the plan's bit-packed words (validity masks
    at 1 bit/row, values at their measured width) instead of full int32
    lanes; ``bases`` carries the global rebase words (None = every
    narrowed field is static-base, the stats-free plan). A wire plan
    with quantized 'q8' fields is self-contained: the per-chunk block
    scales are computed here at pack time and ride the (widened) header
    rows beside the counts, so the fused pipeline quantizes with no host
    stats step.

    Returns (received cols, recv_counts [P]). Tables with no int32 lanes at
    all (pure f64, no validity masks) fall back to a dedicated tiny count
    exchange — there is no lane buffer for the header to ride.

    ``topo``: an optional :class:`~cylon_tpu.parallel.topo.Topology` —
    each payload collective then routes as the STRUCTURED two-hop
    (:func:`~cylon_tpu.parallel.topo.exchange_buffer_structured`):
    identical received layout (recv_counts, chunk order, headers all
    unchanged), but same-outer-group rows never cross the outer links.
    """
    if topo is not None:
        from . import topo as _topo

        def _xchg(buf):
            return _topo.exchange_buffer_structured(buf, topo, axis_name)
    else:
        def _xchg(buf):
            return exchange_buffer(buf, num_partitions, axis_name)
    qrows = None
    header_extra = None
    nq8 = len(wire_q8_cols(wire)) if wire is not None else 0
    n_header = wire_header_rows(wire) if wire is not None else HEADER_ROWS
    if wire is not None:
        if nq8:
            scales = quant_chunk_scales(
                cols, wire, dest, num_partitions, bucket_cap
            )
            qrows = send_row_scales(scales, dest, bucket_cap)
            header_extra = jax.lax.bitcast_convert_type(scales, jnp.int32)
        lanes, passthrough = wire_pack_cols(cols, wire, bases, qscales=qrows)
        plan = list(wire.plan)
    else:
        plan, lanes, passthrough = pack_cols(cols)
    out_lanes: List[jax.Array] = []
    qsc_rows = None
    if lanes:
        buf = pack_lane_buffer(
            lanes, dest, counts_round, num_partitions, bucket_cap,
            header_extra=header_extra, n_header=n_header,
        )
        got = _xchg(buf)
        data, recv_counts = split_header(got, num_partitions, n_header)
        if nq8:
            qsc_rows = recv_row_scales(
                split_header_scales(got, num_partitions, n_header, nq8),
                num_partitions, bucket_cap,
            )
        out_lanes = [data[:, j] for j in range(data.shape[1])]
    else:
        recv_counts = exchange_counts(counts_round, axis_name)

    def handle_pt(ci):
        return _xchg(
            scatter_send(passthrough[ci], dest, num_partitions, bucket_cap)
        )

    def make_valid(lane):
        return None if lane is None else lane.astype(jnp.bool_)

    if wire is not None:
        out = wire_unpack_cols(
            out_lanes, wire, bases, handle_pt, make_valid, qscales=qsc_rows
        )
    else:
        out, _ = unpack_cols(plan, out_lanes, handle_pt, make_valid)
    return out, recv_counts


def received_row_mask(
    recv_counts: jax.Array, num_partitions: int, bucket_cap: int
) -> Tuple[jax.Array, jax.Array]:
    """(live mask [P*bucket_cap], total received scalar int32)."""
    slot = jnp.arange(num_partitions * bucket_cap, dtype=jnp.int32) % bucket_cap
    src = jnp.arange(num_partitions * bucket_cap, dtype=jnp.int32) // bucket_cap
    mask = slot < recv_counts[src]
    return mask, jnp.sum(recv_counts).astype(jnp.int32)


def front_pack_chunks(x: jax.Array, recv_counts: jax.Array) -> jax.Array:
    """Front-pack a received buffer ``x`` ``[P * bucket_cap, *trailing]``
    that is ``P`` equal chunks (``recv_counts`` is ``[P]``), chunk ``s`` a
    live prefix of ``recv_counts[s]`` rows and a dead tail (what a one-hop
    all-to-all leaves): chunk ``s`` is written whole, as ONE block, at the
    running offset of the counts before it, chunks in rising order. Its
    dead tail lands where chunk ``s + 1``'s block overwrites it, or past
    the total;
    ``off_s + bucket_cap <= (s + 1) * bucket_cap``, so no start is ever
    clamped and the buffer needs no overhang. Chunk 0 lies where it
    belongs. The live rows come out source by source in each source's
    order, as the stable argsort of the liveness mask puts them; rows
    past the total are whatever the last writes left. No sort, no gather,
    no scatter: no row is addressed on its own."""
    num_partitions = recv_counts.shape[0]
    bucket_cap = x.shape[0] // num_partitions
    zeros = (jnp.int32(0),) * (x.ndim - 1)
    off = jnp.int32(0)
    out = x
    for s in range(1, num_partitions):
        off = off + recv_counts[s - 1].astype(jnp.int32)
        out = jax.lax.dynamic_update_slice(
            out, x[s * bucket_cap:(s + 1) * bucket_cap], (off, *zeros)
        )
    return out


def chunk_front(recv_counts: jax.Array):
    """``front(x)`` of a one-hop receive, for :func:`compact_received_lanes`
    / :func:`compact_received_wire`: ``x``'s ``P`` equal chunks reach the
    front by :func:`front_pack_chunks` (the flat mesh's compact,
    ``table._shuffle_state.build_compact``)."""
    return lambda x: front_pack_chunks(x, recv_counts)


def replicate_cols(cols, count: jax.Array, axis_name: str, out_cap: int):
    """Every shard's live rows of ``cols`` (``(data, valid | None)`` pairs
    of ``cap`` slots, the first ``count`` live), on every shard: what the
    replicate route of a distributed join gathers of its small side
    (``Table._replicated``). One ``all_gather`` a lane and one of the
    counts, then :func:`front_pack_chunks`: the gathered buffer IS ``P``
    equal chunks with a live prefix each, so the rows reach the front
    shard by shard, each shard's in its own order, by ``P - 1`` block
    writes. A float64 lane crosses as the two 32-bit halves the chip holds
    it in (the collective moves bits and adds nothing, as
    ``ops/groupby.dense_combine``'s gather of its partials). The result is
    cut (or padded) to ``out_cap`` slots, which must hold the total.
    Stage ``join.replicate``."""
    with jax.named_scope(_stages.JOIN_REPLICATE):
        counts = jax.lax.all_gather(count, axis_name).reshape(-1)

        def whole(x):
            every = jax.lax.all_gather(x, axis_name)  # [P, cap, ...]
            flat = every.reshape((-1,) + every.shape[2:])
            flat = front_pack_chunks(flat, counts)
            if out_cap <= flat.shape[0]:
                return flat[:out_cap]
            pad = [(0, out_cap - flat.shape[0])] + [(0, 0)] * (flat.ndim - 1)
            return jnp.pad(flat, pad)

        return [(whole(d), None if v is None else whole(v)) for d, v in cols]


def order_front(mask: jax.Array):
    """``front(x)`` of a general liveness ``mask``: one stable argsort of
    the mask and a per-element gather an array. What the receives whose
    chunks are not equal keep (the two-hop exchange, the ring relay)."""
    with jax.named_scope(_stages.SHUFFLE_COMPACT):
        order = jnp.argsort(~mask, stable=True).astype(jnp.int32)
    return lambda x: x[order]


def compact_received_lanes(
    plan,
    lane_rows: Optional[jax.Array],
    pt_cols: dict,
    front,
) -> List[Tuple[jax.Array, Optional[jax.Array]]]:
    """Receive-side compaction straight at the LANE level: the
    already-packed [rows, L] lane matrix and every f64 passthrough column
    reach the front by ``front`` (:func:`chunk_front`: a block write a
    source chunk; :func:`order_front`: a liveness sort and a gather an
    array), then unpack. The chunked engine's compact phase uses this
    instead of :func:`compact_received`, which would re-pack rows that
    arrived packed."""
    with jax.named_scope(_stages.SHUFFLE_COMPACT):
        out_lanes: List[jax.Array] = []
        if lane_rows is not None and lane_rows.shape[1]:
            g = front(lane_rows)
            out_lanes = [g[:, j] for j in range(g.shape[1])]
        fronted_pt = {ci: front(d) for ci, d in pt_cols.items()}
        out, _ = unpack_cols(
            plan,
            out_lanes,
            lambda ci: fronted_pt[ci],
            lambda lane: None if lane is None else lane.astype(jnp.bool_),
        )
        return out


def compact_received_wire(
    wire,
    bases: Optional[jax.Array],
    lane_rows: jax.Array,
    pt_cols: dict,
    front,
    qscale_rows: Optional[jax.Array] = None,
) -> List[Tuple[jax.Array, Optional[jax.Array]]]:
    """:func:`compact_received_lanes` for a wire-narrowed exchange: the
    received rows ARE packed words, so ``front`` moves the narrow
    [rows, n_words] matrix and the bit-unpack happens once, on the
    compacted rows. ``qscale_rows``: [rows, nq8] per-row block scales
    of the quantized fields (broadcast from the headers BEFORE the rows
    move: they ride the same ``front``, so each row dequantizes with its
    own source chunk's scale)."""
    with jax.named_scope(_stages.SHUFFLE_COMPACT):
        g = front(lane_rows)
        word_lanes = [g[:, j] for j in range(g.shape[1])]
        fronted_pt = {ci: front(d) for ci, d in pt_cols.items()}
        return wire_unpack_cols(
            word_lanes,
            wire,
            bases,
            lambda ci: fronted_pt[ci],
            lambda lane: None if lane is None else lane.astype(jnp.bool_),
            qscales=None if qscale_rows is None else front(qscale_rows),
        )


def compact_received(
    cols: List[Tuple[jax.Array, Optional[jax.Array]]],
    mask: jax.Array,
) -> List[Tuple[jax.Array, Optional[jax.Array]]]:
    """Front-pack received rows (stable), restoring the live-prefix
    invariant. All columns ride ONE packed row gather (see ops/gather)."""
    with jax.named_scope(_stages.SHUFFLE_COMPACT):
        order = jnp.argsort(~mask, stable=True).astype(jnp.int32)
        gathered, _ = pack_gather(cols, order)
        # pack_gather merges ok=order>=0 (always True here) into validity; keep
        # mask-free columns mask-free
        return [
            (d, None if ov is None else v)
            for (d, v), (_, ov) in zip(gathered, cols)
        ]


def reassemble_blocks(
    parts: Sequence[Cols], counts: Sequence[jax.Array], cap_out: int
) -> List[Tuple[jax.Array, Optional[jax.Array]]]:
    """Row-wise concat of K same-schema parts, one shard's view: part k's
    whole buffer is written as ONE block at the running offset of the
    ``counts`` (the parts' live rows) before it, in order. A part's live
    rows are a contiguous prefix of its buffer (the compact kernel leaves
    them so), so its dead tail lands where the next part's block
    overwrites it, or past the total; no row is addressed on its own.

    A column's dtype is the promotion of the parts'; it has a validity
    lane where any part's has (blocks of ones for the parts without).
    Slots past the total are zero, and invalid."""
    with jax.named_scope(_stages.SHUFFLE_REASSEMBLE):
        offs, total = [], jnp.int32(0)
        for n in counts:
            offs.append(total)
            total = total + n
        live = jnp.arange(cap_out, dtype=jnp.int32) < total

        def place(blocks, dtype):
            # dynamic_update_slice CLAMPS a start so that the update
            # fits: the scratch overhangs the output by the largest part,
            # so no start (at most the total, itself at most ``cap_out``)
            # is ever moved. The mask zeroes what the last block's dead
            # tail left past the total.
            over = max(b.shape[0] for b in blocks)
            buf = jnp.zeros((cap_out + over,), dtype)
            for b, off in zip(blocks, offs):
                buf = jax.lax.dynamic_update_slice(
                    buf, b.astype(dtype), (off,)
                )
            return jnp.where(live, buf[:cap_out], jnp.zeros((), dtype))

        out = []
        for cols in zip(*parts):
            common = jnp.result_type(*[d.dtype for d, _v in cols])
            data = place([d for d, _v in cols], common)
            valid = None
            if any(v is not None for _d, v in cols):
                valid = place(
                    [
                        jnp.ones(d.shape, jnp.bool_) if v is None else v
                        for d, v in cols
                    ],
                    jnp.bool_,
                )
            out.append((data, valid))
        return out
