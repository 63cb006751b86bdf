"""Pallas windowed expand: the emit-gather attack (VERDICT round-3 item 1).

The join emit spends ~0.6 s of the 1.07 s 16M-row kernel in two XLA
per-element gathers (docs/GATHER_DESIGN.md; reference analog: the emit loop
of join/join_utils.cpp:28-160 + util/copy_arrray.cpp — the gather IS the
reference's emit too). The byte-roofline for those gathers is ~2 ms: the
cost is per-element address generation, not bytes.

The structural escape: the left emit index sequence ``li`` is
``repeat(arange(m), counts)`` over compacted emitting rows — non-decreasing
with step <= 1 — so any 128 consecutive outputs read at most 128 consecutive
source rows. That turns the gather into a *streamed expand*:

1. XLA side: pack all column lanes into one [L, cap] int32 matrix
   (ops/gather lane codec), compact emitting rows to the front with ONE
   scatter (sorted indices), and transpose to lane-major [L, cap].
2. Pallas kernel, grid over output tiles of T columns: DMA the source
   window [L, T+128] that tile t can touch from HBM into VMEM (its start =
   li[t*T], a scalar-prefetch lookup), then for each 128-output group
   re-slice a [L, 128] sub-window at the group's own start so the gather
   indices are LOCAL (< 128) — exactly Mosaic's supported single-vreg
   dynamic-gather case ("Multiple source vregs along gather dimension" is
   the measured blocker this sidesteps).
3. ``impl='onehot'`` is the instruction-independent fallback: the [128]
   local gather becomes two exact f32 MXU matmuls against a one-hot matrix
   (int32 split into 16-bit halves, each < 2^24 so f32 is exact).

x64 discipline: every scalar constant
in kernel code is an explicit np.int32 — weak python ints under
jax_enable_x64 recurse at trace time, and i64 index-map returns fail Mosaic
legalization.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

try:  # pallas is in jax.experimental on every jax in this image
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pl = None
    pltpu = None

GROUP = 128  # outputs per in-kernel gather group (one lane vreg)


def _expand_kernel(
    gstarts_ref,  # [n_groups_total] i32 in SMEM (scalar prefetch)
    src_ref,      # [L, cap] i32 in ANY/HBM
    li_ref,       # [G, 128] i32 VMEM block (this tile's emit indices)
    out_ref,      # [L, T] i32 VMEM block
    scratch_ref,  # [L, win] i32 VMEM scratch
    sem,          # DMA semaphore
    *,
    G: int,
    win: int,
    cap: int,
    impl: str,
):
    t = pl.program_id(0)
    # clamp so the DMA window stays inside the source; all index math below
    # re-clamps, so degenerate inputs (empty table: li == -1) stay in-bounds
    # and only produce garbage in rows the caller already knows are dead
    start_c = _tile_start(gstarts_ref, t, G, win, cap)
    copy = pltpu.make_async_copy(
        src_ref.at[:, pl.ds(start_c, win)], scratch_ref, sem
    )
    copy.start()
    copy.wait()
    _compute_tile(
        gstarts_ref, li_ref, out_ref, scratch_ref, t, start_c,
        G=G, win=win, impl=impl,
    )


def _tile_start(gstarts_ref, t, G: int, win: int, cap: int):
    start = gstarts_ref[t * np.int32(G)]
    return jnp.clip(start, np.int32(0), np.int32(cap - win))


def _group_gather(window, idx, impl: str):
    """One 128-output gather from a [L, 128] VMEM window; local idx < 128."""
    if impl == "take":
        return jnp.take(window, idx, axis=1, indices_are_sorted=True)
    # exact one-hot MXU gather: onehot[s, d] = (idx[d] == s); int32
    # split into 16-bit halves keeps every matmul operand < 2^24,
    # so the f32 products/sums are exact
    iota = jax.lax.broadcasted_iota(jnp.int32, (GROUP, GROUP), 0)
    onehot = (iota == idx[None, :]).astype(jnp.float32)
    hi = jax.lax.shift_right_logical(window, np.int32(16))
    lo = window & np.int32(0xFFFF)
    hi_g = jax.lax.dot(
        hi.astype(jnp.float32), onehot, preferred_element_type=jnp.float32
    )
    lo_g = jax.lax.dot(
        lo.astype(jnp.float32), onehot, preferred_element_type=jnp.float32
    )
    return (
        jax.lax.shift_left(hi_g.astype(jnp.int32), np.int32(16))
        | lo_g.astype(jnp.int32)
    )


def _compute_tile(
    gstarts_ref, li_ref, out_ref, buf_ref, t, start_c, *,
    G: int, win: int, impl: str,
):
    gi0 = t * np.int32(G)
    for g in range(G):  # static unroll: G is small (T/128)
        gs = gstarts_ref[gi0 + np.int32(g)]
        off = jnp.clip(gs - start_c, np.int32(0), np.int32(win - GROUP))
        window = buf_ref[:, pl.ds(off, GROUP)]  # [L, 128]
        idx = li_ref[g, :] - start_c - off      # [128] local indices
        idx = jnp.clip(idx, np.int32(0), np.int32(GROUP - 1))
        out_ref[:, g * GROUP : (g + 1) * GROUP] = _group_gather(
            window, idx, impl
        )


def _expand_kernel_db(
    gstarts_ref,
    src_ref,
    li_ref,
    out_ref,
    buf0_ref,
    buf1_ref,
    sem0,
    sem1,
    *,
    G: int,
    win: int,
    cap: int,
    impl: str,
    n_tiles: int,
):
    """Double-buffered variant: tile t+1's window DMA is started BEFORE
    tile t's compute, so transfer rides under the gather work. Two static
    buffers selected by tile parity (a traced buffer index would need a
    dynamic ref slice, which Mosaic dislikes); the compute body is shared
    source (`_compute_tile`) instantiated per branch."""
    t = pl.program_id(0)
    even = (t % np.int32(2)) == np.int32(0)

    def copy_for(tile, buf_ref, sem):
        start_c = _tile_start(gstarts_ref, tile, G, win, cap)
        return pltpu.make_async_copy(
            src_ref.at[:, pl.ds(start_c, win)], buf_ref, sem
        )

    @pl.when(t == np.int32(0))
    def _():
        copy_for(np.int32(0), buf0_ref, sem0).start()

    nxt = t + np.int32(1)
    has_next = nxt < np.int32(n_tiles)

    @pl.when(has_next & even)
    def _():
        copy_for(nxt, buf1_ref, sem1).start()

    @pl.when(has_next & ~even)
    def _():
        copy_for(nxt, buf0_ref, sem0).start()

    start_c = _tile_start(gstarts_ref, t, G, win, cap)

    @pl.when(even)
    def _():
        copy_for(t, buf0_ref, sem0).wait()
        _compute_tile(
            gstarts_ref, li_ref, out_ref, buf0_ref, t, start_c,
            G=G, win=win, impl=impl,
        )

    @pl.when(~even)
    def _():
        copy_for(t, buf1_ref, sem1).wait()
        _compute_tile(
            gstarts_ref, li_ref, out_ref, buf1_ref, t, start_c,
            G=G, win=win, impl=impl,
        )


def expand_rows_raw(
    srcT: jax.Array,
    li: jax.Array,
    T: int = 4096,
    impl: str = "take",
    interpret: bool = False,
) -> jax.Array:
    """Windowed expand: ``srcT[:, li]`` for non-decreasing step<=1 ``li``.

    srcT: [L, cap] int32 lane-major source; li: [n_out] int32 emit indices.

    CONTRACT: li must be non-decreasing with li[k+1] <= li[k] + 1 — the
    ``repeat(arange(m), counts)`` shape with every count >= 1. Zero-count
    rows create jumps > 1 that silently overflow a group's 128-wide window
    (wrong values, no error): COMPACT them away first, as
    ops/join._emit_inner_left_windowed does. Values outside [0, cap) are
    tolerated (clamped; callers mask those output positions). One tolerated
    exception to step<=1: a jump PAST THE LAST LIVE output position (the
    padded tail jumping from the final live index to cap, as
    CYLON_TPU_REPEAT_IMPL=sort's _repeat_ss emits) — every output at or
    beyond such a jump lands outside its window and is garbage, which is
    fine exactly because callers must mask all positions >= total anyway.
    Returns [L, n_out] int32.
    """
    if pl is None:  # pragma: no cover
        raise RuntimeError("pallas unavailable")
    L, cap = srcT.shape
    n_out = li.shape[0]
    win = T + GROUP
    if cap < win:
        # tiny sources: the whole table fits one window; pad so the single
        # DMA is well-formed
        srcT = jnp.pad(srcT, ((0, 0), (0, win - cap)))
        cap = win
    n_pad = -n_out % T
    if n_pad:
        # pad with the last index: keeps the non-decreasing invariant
        li = jnp.concatenate([li, jnp.broadcast_to(li[-1:], (n_pad,))])
    n_tot = n_out + n_pad
    G = T // GROUP
    n_tiles = n_tot // T
    li2d = li.reshape(n_tot // GROUP, GROUP)
    # column 0 of the reshape, NOT li[::GROUP]: the strided slice lowers to
    # a gather (which the roofline model prices at per-element rates and
    # XLA executes as one), the column slice to a plain slice
    gstarts = li2d[:, 0]

    if impl not in ("take", "onehot", "take_db", "onehot_db"):
        # impl comes straight from an env var: a typo must not silently
        # run a different kernel than the user believes they selected
        raise ValueError(f"unknown expand impl {impl!r}")
    db = impl.endswith("_db")
    gather_impl = impl[:-3] if db else impl
    if db:
        # double-buffered: two window buffers + two DMA semaphores; tile
        # t+1's copy rides under tile t's gather compute
        scratch = [
            pltpu.VMEM((L, win), jnp.int32),
            pltpu.VMEM((L, win), jnp.int32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ]
        kern = functools.partial(
            _expand_kernel_db, G=G, win=win, cap=cap, impl=gather_impl,
            n_tiles=n_tiles,
        )
    else:
        scratch = [
            pltpu.VMEM((L, win), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ]
        kern = functools.partial(
            _expand_kernel, G=G, win=win, cap=cap, impl=gather_impl
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((G, GROUP), lambda t, g_ref: (t, np.int32(0))),
        ],
        out_specs=pl.BlockSpec((L, T), lambda t, g_ref: (np.int32(0), t)),
        scratch_shapes=scratch,
    )
    try:
        # under shard_map with vma checking the output must declare how it
        # varies across mesh axes: same as the (per-shard) inputs
        vma = jax.typeof(srcT).vma
        out_shape = jax.ShapeDtypeStruct((L, n_tot), jnp.int32, vma=vma)
    except (AttributeError, TypeError):
        out_shape = jax.ShapeDtypeStruct((L, n_tot), jnp.int32)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(gstarts, srcT, li2d)
    return out[:, :n_out]


# Jitted wrapper for STANDALONE use (tests, gather_ab's isolated rows).
# In-kernel callers (ops/join, already traced under the engine's jit or
# jit(shard_map)) must use expand_rows_raw: a nested jit around the
# pallas_call was the construction that hit jax's unbounded-recursion bug
# under jit(shard_map) on compiled TPU (round-3 finding; VERDICT r4 item 3).
expand_rows = jax.jit(
    expand_rows_raw, static_argnames=("T", "impl", "interpret")
)


def expand_available() -> bool:
    return pl is not None
