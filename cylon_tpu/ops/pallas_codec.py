"""Fused Pallas shuffle codec engine (ISSUE 20).

The chunked shuffle's per-round codec is a chain of separate XLA ops,
each round-tripping its intermediate through HBM. Send side (the PACK
stage, priced 3.0 row passes by the profiler's calibration table;
``pallas_pid`` in the pass tables below is the pid-input pack mode —
one XLA pid pass plus one kernel pass):
murmur hash over the key columns, a bucket histogram
(``shuffle.bucket_counts``: a compare against the partition ids and a sum
over the rows, ``ops.partition.bin_counts``), a stable grouping sort
(``shuffle.shuffle_gather_order``), a ``pid``
gather through that order and a scatter back to row order just to learn
each row's destination slot. Receive side (the COMPACT stage): a
liveness mask, a stable argsort by it, and a 400x-priced gather of the
whole received lane matrix just to front-pack live rows. Kernel fusion
deletes the materialized intermediates (Exoshuffle's application-level
codec argument; the redistribution-fusion payoff model of arxiv
2112.01075):

  kernel 1 (**fused pack**, one ``pallas_call`` over ``cap // TILE``
      row tiles): per tile, the murmur3 chain of ops/hash.py is
      replayed in VMEM over the prefetched key words, the partition id
      is reduced, a [TILE, P] one-hot is built IN VMEM and
      inclusive-scanned for stable in-tile ranks, and a VMEM-resident
      [1, P] running histogram (the sequential grid's carry) turns them
      into exact global bucket positions — emitting the per-row send
      slot ``dest`` and the full bucket histogram in a single pass.
      The hash pass, the histogram pass, the grouping sort, and both
      permutation round-trips are gone.
  kernel 2 (**fused compact**, one ``pallas_call`` over the P source
      chunks): the received chunk counts/starts ride scalar prefetch;
      each chunk's [bc, L] block is copied once into its front-packed
      live window and its dead tail window with masked read-modify-
      write stores (dynamic-start ``pl.ds`` windows — write order is
      irrelevant because every store only changes its own rows). The
      liveness mask, the stable argsort, and the 400x-priced row
      gather are gone; the emitted buffer is the XLA path's gather
      result bit-for-bit, dead rows included.

Implementation selection; every resolver step is shape-static:

1. ``CYLON_TPU_NO_PALLAS_CODEC=1`` — kill switch, XLA codec
   everywhere. Its ``disabled()`` context manager IS the differential
   oracle: the codec is bit-lossless by contract on non-quant lanes,
   so tests diff EXACT buffers against it.
2. ``CYLON_TPU_CODEC_IMPL`` in {xla, pallas} forces.
3. The autopilot's per-shape ``Decisions.codec_impl``
   (plan/feedback.py), visible through the applying() contextvar.
4. Default ``auto``: the XLA lowering of both stages, on every
   platform (CPU tests and the chip take the same path). The default
   selects no kernel the TPU compiler refuses, and Mosaic (jax 0.9.0 /
   libtpu 0.0.34, compiled for v5e) refuses the pack kernel twice over:
   its ``(1, TILE)`` blocks over ``[n_tiles, TILE]`` operands ("the
   last two dimensions of your block shape are divisible by 8 and 128
   respectively, or be equal to the respective dimensions of the
   overall array") and, behind that, its body ("Unimplemented primitive
   in Pallas TPU lowering: cumsum", plus lane->sublane transposes of
   the pid vector). The compact kernel does compile for v5e
   (tests/test_tpu_compile.py keeps that true) but has not run on a
   chip, so it follows the same default until a run on the four-chip
   mesh shows it matching the XLA compact and winning. Forcing
   ``pallas`` engages each kernel wherever its structural predicate
   accepts (``pack_supported`` / ``compact_supported``; declines are
   per stage and exact); on a TPU mesh a forced kernel that does not
   lower raises the compiler's error — it never declines to XLA or to
   interpret mode there.

``impl_tag()`` is the cache-key carrier: every shuffle-family kernel
key appends it, so a knob (or tuned-decision) flip recompiles exactly
once and never aliases a stale program. ``gate_state()`` is the plan-
fingerprint component (plan/lazy.py). interpret=True only where the
MESH's devices are CPUs (the call sites in table.py decide from the
context's mesh, not from the process's backend), raw functions only —
no nested jit (see ops/pallas_gather.py tail note).

Deviation from the plan of record, stated plainly: the pack kernel
emits ``dest`` + histogram and the ONE collision-free lane-buffer
scatter stays in XLA (``shuffle.pack_lane_buffer``), because
Mosaic cannot vector-scatter VMEM and the scatter is the one
intermediate-free op in the chain. Likewise the compact kernel moves
rows and the elementwise wire/quant decode (``gather.wire_unpack_cols``)
stays an XLA epilogue over the already-compacted rows: decode reads
each word exactly once, so fusing it buys no HBM traffic. The pack
kernel runs in two modes: hash-fused (non-semi hash partitionings —
the murmur chain replays in-kernel, all three XLA row passes fold into
one) and pid-input (range/task partitionings and semi-filtered packs,
whose partition id needs sampling collectives or a sketch probe the
kernel cannot replay — XLA computes the pid lane, the kernel fuses the
remaining histogram + rank + slot passes: 3 passes become 2). It
declines quantized (multi-header) wire plans and non-power-of-two
worlds (Mosaic's uint32 modulo is not worth the legalization risk for
a case the mesh never produces); the compact kernel declines the
two-hop topo branch and chunks whose move matrix would not fit VMEM.
Every decline falls back to the XLA lowering of just that stage,
bit-exactly.

x64 discipline: every scalar constant in kernel code is an explicit
np.int32/np.uint32 — weak python ints under jax_enable_x64 recurse at
trace time, and i64 index-map returns fail Mosaic legalization.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import envgate as _eg
from ..utils.envgate import env_gate

try:  # pallas is in jax.experimental on every jax in this image
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pl = None
    pltpu = None

#: rows per pack-kernel grid tile: the [TILE, P] one-hot stays under
#: 512 KB VMEM at P <= 256
TILE = 512

#: compact-kernel VMEM budget for the resident move matrix (the whole
#: [P*bc + bc, LM] i32 working set): chunks past this decline to XLA
COMPACT_VMEM_BUDGET = 6 * 1024 * 1024

IMPLS = ("xla", "pallas")

#: row passes one send-side pack costs per scanned row, per impl — the
#: cost-model twin of obs/prof.py's stage weights and the
#: analysis/contracts.py census pins (codec-smoke cross-checks all
#: three). The XLA pack walks each row three times (hash + grouping
#: sort + scatter chain); the fused kernel once.
PACK_ROW_PASSES = {"xla": 3, "pallas": 1, "pallas_pid": 2}

#: receive-side compact row passes per impl: both lowerings read each
#: received row once — the pallas win is the deleted 400x-priced gather
#: and sort traffic, not the pass count
COMPACT_ROW_PASSES = {"xla": 1, "pallas": 1}

# kill switch + differential oracle (CYLON_TPU_NO_PALLAS_CODEC=1 -> XLA
# codec everywhere; tests diff exact buffers against it)
enabled, disabled = env_gate(
    "CYLON_TPU_NO_PALLAS_CODEC",
    keyed_via="ops.pallas_codec.impl_tag appended to every shuffle-family "
    "kernel cache key; plan fingerprints carry ops.pallas_codec.gate_state",
    note="=1 disables the fused Pallas shuffle codec (XLA pack/compact "
    "everywhere) — the bit-exact differential oracle for codec tests",
)


def codec_available() -> bool:
    return pl is not None


def resolved_impl() -> str:
    """The selected codec impl for the CURRENT trace: kill switch, then
    the forcing env, then the autopilot's applied per-shape decision,
    then the ``auto`` default — XLA on every platform (the pack kernel
    does not lower for TPU; see the module doc). Host env/contextvar
    reads only — shape-static, cache-key safe."""
    if not enabled() or pl is None:
        return "xla"
    forced = _eg.CODEC_IMPL.get()
    if forced and forced != "auto":
        return forced if forced in IMPLS else "xla"
    from ..plan import feedback as _fb

    tuned = _fb.tuned_codec_impl()
    if tuned in IMPLS:
        return tuned
    return "xla"


def impl_tag() -> tuple:
    """Cache-key component every shuffle-family kernel key appends: the
    resolved impl (which transitively reads CYLON_TPU_NO_PALLAS_CODEC,
    CYLON_TPU_CODEC_IMPL and the tuned decision) plus the tile width,
    so an impl flip or a tile change recompiles instead of aliasing.
    The analyzer treats a call to this function inside a key expression
    as the keyed carrier of both knobs."""
    return ("codec_impl", resolved_impl(), TILE)


def kernel_kwargs() -> dict:
    """Extra engine.get_kernel kwargs for shuffle-family kernels: a
    pallas codec embeds pallas_calls, which have no shard_map
    replication rule — same check_vma=False discipline as the windowed
    emit (ops/join.emit_impl_kwargs). get_kernel keys include the
    wrapping flags, so this cannot alias the checked program."""
    if resolved_impl() == "pallas":
        return {"check_vma": False}
    return {}


def gate_state() -> tuple:
    """Plan-fingerprint component (plan/lazy.gated_fingerprint): the
    kill switch + the forcing env. The tuned per-shape decision rides
    the fingerprint's feedback component, not this one — the store keys
    profiles by the base fingerprint, which must NOT move when a
    decision flips."""
    return (enabled(), _eg.CODEC_IMPL.get())


# ----------------------------------------------------------------------
# structural engagement predicates (shape-static; shared by the trace-
# time builders and the dispatch-time stage clocks so both sides agree)
# ----------------------------------------------------------------------

def pack_supported(
    kind: str, semi: bool, has_lanes: bool, n_header: int, world: int
) -> bool:
    """Can the fused pack kernel serve this shuffle? Needs a lane buffer
    to aim at, the single-header (non-quant) wire layout, and a
    power-of-two world <= 1024 (in-kernel ``h & (P-1)`` and the [TILE,P]
    one-hot sizing). ``kind``/``semi`` no longer decline — they pick the
    kernel MODE (:func:`pack_fuses_hash`): non-semi hash packs replay
    the murmur chain in-kernel; range/task/semi packs feed the XLA pid
    lane in and still fuse histogram + rank + slot (the dead-row
    ``pid == P`` sentinel is shared by all three partitioners, so the
    kernel's one-hot drops those rows with no extra masking)."""
    return (
        pl is not None
        and has_lanes
        and n_header == 1
        and 1 <= world <= 1024
        and world & (world - 1) == 0
    )


def pack_fuses_hash(kind: str, semi: bool) -> bool:
    """True when the engaged pack kernel replays the murmur chain itself
    (3 XLA row passes -> 1). False selects pid-input mode: XLA computes
    the partition ids (range sampling collectives / task-map lookup /
    the semi sketch-probe rewrite cannot replay in Mosaic) and the
    kernel fuses the remaining passes (3 -> 2, impl key ``pallas_pid``
    in the pass/weight tables)."""
    return kind == "hash" and not semi


def pack_cols_supported(key_cols) -> bool:
    """Per-column guard: every key column must have a word encoding the
    kernel can replay (ops/hash._to_words handles every dtype, but the
    f64 double-float split needs f64 arithmetic the XLA prologue does —
    so all dtypes pass; the hook exists for future decliners)."""
    return len(key_cols) >= 1


def compact_supported(
    has_lanes: bool, topo: bool, world: int, bucket_cap: int,
    n_move_lanes: int,
) -> bool:
    """Can the fused compact kernel serve this receive side? A lane
    matrix to move, no two-hop topo branch (its received layout is
    assembled by a different kernel body), and a move working set —
    the VMEM-resident [P*bc, LM] output plus one [bc, LM] input block —
    inside the VMEM budget."""
    if pl is None or topo or not has_lanes:
        return False
    if world < 1 or bucket_cap < 1 or n_move_lanes < 1:
        return False
    vmem = (world + 1) * bucket_cap * n_move_lanes * 4
    return vmem <= COMPACT_VMEM_BUDGET


def pack_engaged(
    kind: str, semi: bool, has_lanes: bool, n_header: int, world: int
) -> bool:
    return resolved_impl() == "pallas" and pack_supported(
        kind, semi, has_lanes, n_header, world
    )


def compact_engaged(
    has_lanes: bool, topo: bool, world: int, bucket_cap: int,
    n_move_lanes: int,
) -> bool:
    return resolved_impl() == "pallas" and compact_supported(
        has_lanes, topo, world, bucket_cap, n_move_lanes
    )


def move_lane_count(plan_sig, wire, n_pt: int) -> int:
    """Columns of the compact move matrix for a shuffle's static plan:
    the received word lanes, the bitcast q8 scale rows, and one carried
    row-index lane when f64 passthrough columns need an XLA gather by
    the emitted order. The dispatch-time stage clock and the trace-time
    builder both size the VMEM check with this."""
    from .gather import wire_q8_cols

    if wire is not None:
        n = wire.n_words + len(wire_q8_cols(wire))
    else:
        n = sum(nl for _tag, nl, _hv in plan_sig)
        n += sum(1 for _tag, _nl, hv in plan_sig if hv)
    return n + (1 if n_pt else 0)


def pack_row_passes(impl: str, fuse_hash: bool = True) -> int:
    """Pack-stage row passes under ``impl`` (census helper; the
    contracts.py pins and the prof stage weights must agree). A pallas
    pack in pid-input mode costs the ``pallas_pid`` row: one XLA pid
    pass plus the kernel pass."""
    if impl == "pallas" and not fuse_hash:
        return PACK_ROW_PASSES["pallas_pid"]
    return PACK_ROW_PASSES[impl]


def compact_row_passes(impl: str) -> int:
    return COMPACT_ROW_PASSES[impl]


# ----------------------------------------------------------------------
# kernel 1: fused hash -> partition -> dest/histogram
# ----------------------------------------------------------------------

def hash_operands(key_cols) -> Tuple[List[jax.Array], List[jax.Array], tuple]:
    """XLA prologue of the pack kernel: re-express every key column as
    the exact two uint32 words ops/hash.murmur3_column hashes (the f64
    double-float split and float canonicalization happen HERE, where
    wide arithmetic is legal) plus the null masks. Returns (word lanes
    [cap] uint32, valid lanes [cap] uint32, has_valid flags)."""
    from . import hash as _h

    words: List[jax.Array] = []
    valids: List[jax.Array] = []
    has_valid = []
    for data, valid in key_cols:
        w0, w1 = _h._to_words(data)
        words.append(w0)
        words.append(w1)
        if valid is not None:
            valids.append(valid.astype(jnp.uint32))
        has_valid.append(valid is not None)
    return words, valids, tuple(has_valid)


def _mix_word(h, k):
    """In-kernel murmur3_x86_32 body round — bit-identical to
    ops/hash._mix_word (uint32 wraparound arithmetic only)."""
    k = k * np.uint32(0xCC9E2D51)
    k = (k << np.uint32(15)) | (k >> np.uint32(17))
    k = k * np.uint32(0x1B873593)
    h = h ^ k
    h = (h << np.uint32(13)) | (h >> np.uint32(19))
    return h * np.uint32(5) + np.uint32(0xE6546B64)


def _fmix32(h):
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def _pack_kernel(
    meta_ref, *refs, nk: int, nv: int, has_valid: tuple, world: int,
    bucket_cap: int, tile: int, use_pid: bool = False,
):
    """One row tile of the fused pack: replay the murmur chain over the
    prefetched words (hash mode) or read the XLA-computed partition ids
    (pid-input mode), then turn the tile's one-hot scan plus the
    VMEM-resident running histogram (``cnt_ref``, the sequential grid's
    carry) into exact send slots."""
    n_in = 1 if use_pid else 2 * nk + nv
    dest_ref = refs[n_in]
    cnt_ref = refs[n_in + 1]
    t = pl.program_id(0)

    @pl.when(t == np.int32(0))
    def _zero():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    n = meta_ref[0]
    r = meta_ref[1]

    if use_pid:
        # pid-input mode: the single operand lane carries the partition
        # ids; dropped/filtered rows already hold the pid == P sentinel
        # (all three partitioners and the semi probe rewrite share that
        # contract), so the one-hot below is identically zero for them
        pid = refs[0][0, :]  # [tile] int32
    else:
        w_refs = refs[: 2 * nk]
        v_refs = refs[2 * nk : 2 * nk + nv]
        h = None
        vi = 0
        for c in range(nk):
            # ops/hash.murmur3_column over the column's two words, seed 0
            hc = _mix_word(
                jnp.zeros((tile,), jnp.uint32), w_refs[2 * c][0, :]
            )
            hc = _mix_word(hc, w_refs[2 * c + 1][0, :])
            hc = hc ^ np.uint32(8)  # len footer: 4 * 2 words
            hc = _fmix32(hc)
            if has_valid[c]:
                hc = jnp.where(
                    v_refs[vi][0, :] != np.uint32(0), hc, np.uint32(0)
                )
                vi += 1
            # hash_columns chain: h = 31*h + col_hash
            h = hc if h is None else h * np.uint32(31) + hc
        # power-of-two world by pack_supported: the reference fast path
        pid = (h & np.uint32(world - 1)).astype(jnp.int32)  # [tile]

    # [tile, P] one-hot, zeroed on padding rows (rowid >= n) — those
    # rows count nowhere and take the dropped sentinel, exactly
    # partition.hash_partition_ids' pid == P contract
    bucket = jax.lax.broadcasted_iota(jnp.int32, (tile, world), 1)
    rowid = (
        jax.lax.broadcasted_iota(jnp.int32, (tile, world), 0)
        + t * np.int32(tile)
    )
    eq = jnp.where(
        rowid < n, (pid[:, None] == bucket).astype(jnp.int32), np.int32(0)
    )
    csum = jnp.cumsum(eq, axis=0, dtype=jnp.int32)  # stable in-tile ranks
    seen = cnt_ref[0, :]  # [P] bucket counts in earlier tiles
    # one-hot select of each row's global 0-based stable bucket position
    pos = jnp.sum(
        eq * (seen[None, :] + csum - np.int32(1)), axis=1, dtype=jnp.int32
    )  # [tile]
    cnt_ref[0, :] = seen + jnp.sum(eq, axis=0, dtype=jnp.int32)

    live = jnp.sum(eq, axis=1, dtype=jnp.int32) > np.int32(0)
    slot = pos - r * np.int32(bucket_cap)
    ok = live & (slot >= np.int32(0)) & (slot < np.int32(bucket_cap))
    dest_ref[0, :] = jnp.where(
        ok,
        pid * np.int32(bucket_cap) + slot,
        np.int32(world * bucket_cap),
    )


def fused_pack_dest(
    words: Sequence[jax.Array],
    valids: Sequence[jax.Array],
    has_valid: tuple,
    n: jax.Array,
    round_idx,
    world: int,
    bucket_cap: int,
    pid: Optional[jax.Array] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(dest [cap] int32, bucket counts [P] int32) for one pack round —
    the fused replacement for the hash_partition_ids -> bucket_counts ->
    build_send_slots_round chain, bit-identical by construction (same
    stable within-bucket ranks, same dropped sentinel ``P * cap``).
    ``n`` (live rows) and ``round_idx`` may be traced scalars — they
    ride scalar prefetch, so ONE compiled program serves every round.
    Passing ``pid`` ([cap] int32, dead rows == P) selects pid-input
    mode: ``words``/``valids`` are ignored and the kernel fuses only
    histogram + rank + slot. Caller guards with :func:`pack_supported`."""
    use_pid = pid is not None
    if use_pid:
        cap = pid.shape[0]
        nk = 0
        valids = []
    else:
        cap = words[0].shape[0]
        nk = len(words) // 2
    tile = min(TILE, cap)
    n_tiles = cap // tile
    if use_pid:
        ops = [pid.astype(jnp.int32).reshape(n_tiles, tile)]
    else:
        ops = [w.reshape(n_tiles, tile) for w in words]
        ops += [v.reshape(n_tiles, tile) for v in valids]
    meta = jnp.stack(
        [jnp.asarray(n, jnp.int32), jnp.asarray(round_idx, jnp.int32)]
    )

    try:
        vma = jax.typeof(ops[0]).vma
        dest_shape = jax.ShapeDtypeStruct((n_tiles, tile), jnp.int32, vma=vma)
        cnt_shape = jax.ShapeDtypeStruct((1, world), jnp.int32, vma=vma)
    except (AttributeError, TypeError):
        dest_shape = jax.ShapeDtypeStruct((n_tiles, tile), jnp.int32)
        cnt_shape = jax.ShapeDtypeStruct((1, world), jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda t, m: (t, np.int32(0)))
            for _ in ops
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda t, m: (t, np.int32(0))),
            # constant index map: the running histogram stays VMEM-
            # resident across the sequential grid (the carry)
            pl.BlockSpec(
                (1, world), lambda t, m: (np.int32(0), np.int32(0))
            ),
        ],
    )
    dest, cnt = pl.pallas_call(
        functools.partial(
            _pack_kernel,
            nk=nk,
            nv=len(valids),
            has_valid=has_valid,
            world=world,
            bucket_cap=bucket_cap,
            tile=tile,
            use_pid=use_pid,
        ),
        grid_spec=grid_spec,
        out_shape=[dest_shape, cnt_shape],
        interpret=interpret,
    )(meta, *ops)
    return dest.reshape(cap), cnt.reshape(world)


# ----------------------------------------------------------------------
# kernel 2: fused header-split -> front-pack move
# ----------------------------------------------------------------------

def _compact_kernel(meta_ref, m_ref, out_ref, *, world: int, bucket_cap: int):
    """One source chunk of the fused compact: copy the chunk's [bc, LM]
    block into its live window (front-packed at this chunk's exclusive
    count start) and its dead-tail window with masked read-modify-write
    stores. Every store changes only its own rows, so overlapping
    windows across the sequential grid never clobber placed rows."""
    p = pl.program_id(0)
    c = meta_ref[p]
    ls = meta_ref[world + p]
    ds = meta_ref[2 * world + p]
    chunk = m_ref[...]  # [bc, LM]
    j = jax.lax.broadcasted_iota(jnp.int32, (bucket_cap, 1), 0)

    cur = out_ref[pl.ds(ls, bucket_cap), :]
    out_ref[pl.ds(ls, bucket_cap), :] = jnp.where(j < c, chunk, cur)

    sd = ds - c
    cur2 = out_ref[pl.ds(sd, bucket_cap), :]
    out_ref[pl.ds(sd, bucket_cap), :] = jnp.where(j >= c, chunk, cur2)


def fused_compact_move(
    move: jax.Array,
    recv_counts: jax.Array,
    world: int,
    bucket_cap: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(moved [P*bc, LM], total received scalar int32): reproduce
    ``move[argsort(~mask, stable)]`` — live rows front-packed in
    (chunk, slot) order, dead rows behind them in the same order —
    without materializing the mask, the argsort, or the gather.

    Window bounds are proven from the clipped counts: with
    ``c = clip(recv, 0, bc)``, ``ls_p + bc <= P*bc`` (every earlier
    chunk contributes at most bc), ``ds_p - c_p >= p*bc >= 0`` and
    ``ds_p - c_p + bc <= P*bc`` (later chunks contribute at most bc
    each) — every dynamic-start window is in range. Caller guards with
    :func:`compact_supported`."""
    c = jnp.clip(recv_counts, 0, bucket_cap).astype(jnp.int32)
    ls = jnp.cumsum(c, dtype=jnp.int32) - c
    total_c = jnp.sum(c, dtype=jnp.int32)
    ds = (
        total_c
        + jnp.arange(world, dtype=jnp.int32) * np.int32(bucket_cap)
        - ls
    )
    meta = jnp.concatenate([c, ls, ds])

    try:
        vma = jax.typeof(move).vma
        out_shape = jax.ShapeDtypeStruct(move.shape, jnp.int32, vma=vma)
    except (AttributeError, TypeError):
        out_shape = jax.ShapeDtypeStruct(move.shape, jnp.int32)

    lm = move.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(world,),
        in_specs=[
            pl.BlockSpec((bucket_cap, lm), lambda p, m: (p, np.int32(0)))
        ],
        # the whole output stays VMEM-resident (constant index map):
        # chunks write into each other's windows, masked
        out_specs=pl.BlockSpec(
            (world * bucket_cap, lm),
            lambda p, m: (np.int32(0), np.int32(0)),
        ),
    )
    moved = pl.pallas_call(
        functools.partial(
            _compact_kernel, world=world, bucket_cap=bucket_cap
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(meta, move)
    # total matches received_row_mask's raw sum (counts are pre-clipped
    # at pack, so raw == clipped on every well-formed exchange)
    return moved, jnp.sum(recv_counts).astype(jnp.int32)
