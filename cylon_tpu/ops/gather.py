"""Packed multi-column row gather.

Replaces the reference's per-type gather loop ``copy_array_by_indices``
(cpp/src/cylon/util/copy_arrray.cpp) — and, on TPU, replaces N independent
XLA gathers with ONE: per-element address-generation overhead dominates TPU
gather cost, so gathering a [cap, L]-packed matrix of all L column lanes at
once costs about the same as gathering a single column (measured ~4x faster
than 4 separate 8.4M-row gathers on v5e).

Packing discipline: every column is re-expressed as one or more int32 lanes
(bitcast for 32-bit types, widening for narrower ints/bools, f16->f32->bitcast,
hi/lo split for 64-bit ints) plus one lane per validity mask; all lanes are
stacked into a [cap, L] matrix, gathered by row index, and unpacked
losslessly. A float64 has no lanes in the EXCHANGE's format (``lane_plan`` /
``pack_cols``, the wire codec, the spill's host unpack: there it is a
``passthrough`` column, moved whole), but it has two in the device-side row
gather: :func:`pack_gather` carries it in the matrix as the two float32 the
chip holds it as (:func:`_f64_to_lanes`), unless the gather is selective
(:data:`F64_PACK_RATIO`).
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..engine import mesh_platform
from ..obs.trace import bump

KeyCol = Tuple[jax.Array, Optional[jax.Array]]


def _to_lanes(data: jax.Array) -> Tuple[List[jax.Array], str]:
    """Encode one column as int32 lanes + a decode tag."""
    dt = data.dtype
    size = np.dtype(dt).itemsize
    if dt == jnp.bool_:
        return [data.astype(jnp.int32)], "bool"
    if dt in (jnp.float16, jnp.bfloat16):
        f32 = data.astype(jnp.float32)  # exact widening
        return [jax.lax.bitcast_convert_type(f32, jnp.int32)], str(dt)
    if size == 4:
        if dt == jnp.int32:
            return [data], "int32"
        return [jax.lax.bitcast_convert_type(data, jnp.int32)], str(dt)
    if size < 4:
        return [data.astype(jnp.int32)], str(dt)
    # 64-bit ints: split into hi/lo 32-bit lanes via arithmetic only (the TPU
    # X64-rewrite pass cannot lower 64-bit bitcast_convert; shifts/masks on
    # emulated u64 are fine). A float64 never comes here: no bitcast of one
    # lowers for a TPU, so the exchange moves it whole (the caller's
    # passthrough) and the row gather splits it by value (_f64_to_lanes).
    u = data.astype(jnp.uint64)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
    lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    return [
        jax.lax.bitcast_convert_type(hi, jnp.int32),
        jax.lax.bitcast_convert_type(lo, jnp.int32),
    ], str(dt)


def _from_lanes(lanes: List[jax.Array], tag: str) -> jax.Array:
    if tag == "bool":
        return lanes[0].astype(jnp.bool_)
    if tag in ("float16", "bfloat16"):
        f32 = jax.lax.bitcast_convert_type(lanes[0], jnp.float32)
        return f32.astype(jnp.dtype(tag))
    dt = jnp.dtype(tag)
    size = np.dtype(dt).itemsize
    if size == 4:
        if tag == "int32":
            return lanes[0]
        return jax.lax.bitcast_convert_type(lanes[0], dt)
    if size < 4:
        return lanes[0].astype(dt)
    hi = jax.lax.bitcast_convert_type(lanes[0], jnp.uint32).astype(jnp.uint64)
    lo = jax.lax.bitcast_convert_type(lanes[1], jnp.uint32).astype(jnp.uint64)
    u = (hi << jnp.uint64(32)) | lo
    if tag == "float64":
        return jax.lax.bitcast_convert_type(u, jnp.float64)
    return u.astype(dt)


def _f64_two_float(platform: str) -> bool:
    """Whether a float64 of a mesh of ``platform`` is a pair of float32.

    A TPU holds one as (high, low) float32 whose sum is the value (about 48
    bits of mantissa, float32's exponent range) and takes no bitcast of
    it; every other backend holds an IEEE double, which two float32 cannot
    carry and whose bits a bitcast gives."""
    return platform == "tpu"


def _f64_to_lanes(data: jax.Array, platform: str) -> List[jax.Array]:
    """One float64 column as two int32 lanes for the row gather of a mesh
    of ``platform``, value for value: :func:`_f64_from_lanes` gives back
    what it was given (every finite value, both zeros, both infinities;
    a NaN stays a NaN), but for a low half that is a float32 subnormal,
    which :func:`_f64_low_half_may_flush` is there to find.

    On a TPU the lanes are the two float32 the chip holds the value as,
    had by arithmetic: the nearest float32, and what is left (exact: the
    error of rounding a sum of two float32 to one is a float32). Elsewhere
    they are the two words of its bits, as a 64-bit integer's are. The
    lanes never leave the device that split them: a host cannot rebuild
    an IEEE double from the chip's halves by the other rule."""
    if not _f64_two_float(platform):
        return _to_lanes(jax.lax.bitcast_convert_type(data, jnp.uint64))[0]
    hi = data.astype(jnp.float32)
    lo = (data - hi.astype(jnp.float64)).astype(jnp.float32)
    # inf - inf is a NaN: an infinity (and a NaN) is its high half alone
    lo = jnp.where(jnp.isfinite(hi), lo, jnp.float32(0))
    return [
        jax.lax.bitcast_convert_type(hi, jnp.int32),
        jax.lax.bitcast_convert_type(lo, jnp.int32),
    ]


#: biased exponent of a float32 high half under which the low half of a
#: float64 may be a float32 SUBNORMAL. A double loaded from a host has 53
#: bits, so what the high half leaves is 0 or at least 2^-52 of it: a normal
#: float32 (2^-126 and up) from a high half of 2^-74 on, biased exponent 53.
#: A high half that is itself subnormal or zero (exponent field 0) leaves
#: nothing, and the chip's own arithmetic makes no subnormal at all.
_F64_LOW_NORMAL_EXP = 53


def _f64_low_half_may_flush(hi_lane: jax.Array) -> jax.Array:
    """Rows of a two-float split whose low half the chip's arithmetic may
    have flushed, read from the HIGH half's bits alone.

    The TPU's float32 arithmetic takes a subnormal for zero, coming and
    going, and the low half of :func:`_f64_to_lanes` is had by arithmetic
    (the high half is a plain conversion, which is one of the two words as
    it lies): a float64 under about 5e-23 whose low half is a float32
    subnormal (1.1% of 4,194,304 draws over sixty decades; none of the
    cells') would come back with the low half gone, equal by every
    comparison the chip can make and 2^-24 off to the host that fetches
    it. Nothing on the chip can see such a low half, but only a row with a
    small high half can have one."""
    exp = (hi_lane >> 23) & 0xFF
    return (exp > 0) & (exp < _F64_LOW_NORMAL_EXP)


def _f64_from_lanes(lanes: List[jax.Array], platform: str) -> jax.Array:
    """Inverse of :func:`_f64_to_lanes` under the same ``platform``."""
    if not _f64_two_float(platform):
        return _from_lanes(lanes, "float64")
    hi = jax.lax.bitcast_convert_type(lanes[0], jnp.float32).astype(jnp.float64)
    lo = jax.lax.bitcast_convert_type(lanes[1], jnp.float32)
    # -0.0 + 0.0 is +0.0: a value with no low half is its high half as is
    return jnp.where(lo == 0, hi, hi + lo.astype(jnp.float64))


def lane_plan(cols: Sequence[KeyCol]):
    """The lane-codec PLAN of a column set from dtypes alone (no device
    work): (tag-or-None, n_lanes, has_valid) per column — a None tag marks
    an f64 column, which has no lanes in this format and is transported
    separately. This is the EXCHANGE's and the host's format (the shuffle's
    collectives, the wire codec, the spill's host unpack); the row gather
    on one device carries an f64 in its matrix all the same
    (:func:`pack_gather`). Kernels that receive already-packed lane
    buffers (the chunked shuffle's compact phase) rebuild the plan with
    this instead of re-encoding the columns."""
    plan = []
    for data, valid in cols:
        dt = data.dtype
        if dt == jnp.float64:
            plan.append((None, 0, valid is not None))
        elif np.dtype(dt).itemsize == 8:
            plan.append((str(dt), 2, valid is not None))  # hi/lo split
        elif dt == jnp.bool_:
            plan.append(("bool", 1, valid is not None))
        elif dt == jnp.int32:
            plan.append(("int32", 1, valid is not None))
        else:
            plan.append((str(dt), 1, valid is not None))
    return plan


def pack_cols(cols: Sequence[KeyCol]):
    """Shared lane-plan builder: encode every column (+ validity) as int32
    lanes. Returns (plan, lanes, passthrough) where plan entries follow
    :func:`lane_plan` and passthrough maps column position -> its raw f64
    data, which the caller moves as its medium allows (a collective of its
    own, a fetch of its own, two more lanes of :func:`pack_gather`'s
    matrix). NOTE: an f64 column's VALIDITY lane still rides ``lanes``."""
    plan = lane_plan(cols)
    lanes: List[jax.Array] = []
    passthrough = {}
    for ci, (data, valid) in enumerate(cols):
        if plan[ci][0] is None:
            passthrough[ci] = data
        else:
            dl, _tag = _to_lanes(data)
            lanes.extend(dl)
        if valid is not None:
            lanes.append(valid.astype(jnp.int32))
    return plan, lanes, passthrough


def unpack_cols(plan, out_lanes, handle_passthrough, make_valid):
    """Shared unpack loop for :func:`pack_cols` plans.

    ``handle_passthrough(ci)`` transports one f64 column;
    ``make_valid(valid_lane_or_None)`` shapes the output validity."""
    out: List[KeyCol] = []
    pos = 0
    for ci, (tag, nl, has_valid) in enumerate(plan):
        if tag is None:
            data = handle_passthrough(ci)
        else:
            data = _from_lanes(out_lanes[pos : pos + nl], tag)
            pos += nl
        if has_valid:
            v = make_valid(out_lanes[pos])
            pos += 1
        else:
            v = make_valid(None)
        out.append((data, v))
    return out, pos


# ----------------------------------------------------------------------
# bit-width-adaptive WIRE codec (ops/stats.py range stats drive it)
#
# The plain lane codec above ships every value as full int32 lanes (and
# every validity mask as a whole lane). For the shuffle exchange that
# width is pure wire cost: a column whose measured range fits 12 bits
# ships 12 bits, a validity mask ships 1 bit/row, a bool 1 bit — rebased
# by a GLOBAL per-column base (both sides of the collective must agree,
# so the base comes from host-folded global stats and rides the kernels
# as a tiny replicated operand, never baked in as a recompiling
# constant). Only BIT-LOSSLESS encodings participate (int families +
# bool + dictionary codes; floats canonicalize -0.0/NaN and ride plain).
# ----------------------------------------------------------------------

class WireField(NamedTuple):
    """One bit-field of the wire layout, in column-major field order.

    ``kind``: 'enc' (stats-rebased orderable encoding), 'lane' (one plain
    32-bit lane of an un-narrowed column), 'valid' (1-bit validity),
    'h16' (lossless native 16-bit float bits — f16/bf16 ship at their
    real width instead of the widened f32 lane), 'q' (a LOSSY
    quantized-tier field, ops/quant.py — opt-in via the tolerance knob).
    ``off``: for 'lane', the lane index within the column's plain codec
    lanes. ``cls``: the encoding class of an 'enc' field; for 'h16' the
    source float dtype; for 'q' the ``"<codec>:<dtype>"`` pair (codec
    q8/qb16/qf32 + the column's physical dtype the decode restores)."""

    col: int
    kind: str
    off: int
    bits: int
    cls: str


class WirePlan(NamedTuple):
    """Static wire-narrowing plan: hashable (quantized widths only, no
    data-dependent bounds), part of the pack/compact kernel cache keys.
    ``plan`` is the logical :func:`lane_plan` it narrows."""

    plan: tuple
    fields: Tuple[WireField, ...]
    n_words: int
    n_plain: int


def wire_plan(cols_plan, stats_list, quant=None) -> Optional[WirePlan]:
    """Build the wire layout for a column set.

    ``stats_list``: per column ``(enc_class, field_bits)`` from measured
    global range stats, or None (unknown). Columns with lossless narrow
    encodings use 'enc' fields (bool needs no stats — it is statically 1
    bit with base 0); f16/bf16 ship their native 16 bits as lossless
    'h16' fields (no stats needed — the widened f32 lane doubled their
    wire bytes for nothing); everything else keeps its plain 32-bit
    lanes as 'lane' fields; f64 stays passthrough; every validity mask
    narrows to a 1-bit field unconditionally.

    ``quant``: optional per-column lossy codec tags from
    :func:`cylon_tpu.ops.quant.quant_spec` (None entries = exact). A
    quantized column — including f64, which thereby LEAVES the
    per-column passthrough collective — ships a 'q' field at the codec
    width instead of its plain lanes. A quantized f64 column counts as
    two virtual plain lanes in the engagement compare (it would have
    shipped 8 passthrough bytes).

    Returns None when there is nothing to pack or packing does not
    strictly reduce the word count."""
    from .quant import CODEC_BITS
    from .stats import wire_narrowable

    fields: List[WireField] = []
    n_plain = 0
    for ci, (tag, nl, has_valid) in enumerate(cols_plan):
        qc = quant[ci] if quant is not None else None
        if tag is not None:
            n_plain += nl
            st = stats_list[ci]
            if qc is not None:
                fields.append(
                    WireField(ci, "q", 0, CODEC_BITS[qc], f"{qc}:{tag}")
                )
            elif tag == "bool":
                fields.append(WireField(ci, "enc", 0, 1, "bool"))
            elif tag in ("float16", "bfloat16"):
                fields.append(WireField(ci, "h16", 0, 16, tag))
            elif st is not None and wire_narrowable(st[0]):
                fields.append(WireField(ci, "enc", 0, int(st[1]), st[0]))
            else:
                for j in range(nl):
                    fields.append(WireField(ci, "lane", j, 32, ""))
        elif qc is not None:
            # quantized f64: rides the packed words, not the passthrough
            n_plain += 2
            fields.append(
                WireField(ci, "q", 0, CODEC_BITS[qc], f"{qc}:float64")
            )
        if has_valid:
            n_plain += 1
            fields.append(WireField(ci, "valid", 0, 1, ""))
    if not fields:
        return None
    total = sum(f.bits for f in fields)
    n_words = max(-(-total // 32), 1)
    if n_words >= n_plain:
        return None
    return WirePlan(tuple(cols_plan), tuple(fields), n_words, n_plain)


def static_wire_plan(
    cols: Sequence[KeyCol], quant=None
) -> Optional[WirePlan]:
    """Stats-free wire plan: only the STATIC narrowings (bool data,
    validity masks to 1 bit/row, native-width f16/bf16, and — when the
    caller passes a ``quant`` spec — the lossy quantized fields, whose
    block scales ride the exchange headers and need no host stats step
    either). Safe inside a single compiled program (the fused pipeline);
    the eager chunked engine does the stats-driven narrowing too."""
    from .stats import enabled

    if not enabled():
        return None
    plan = lane_plan(cols)
    return wire_plan(plan, [None] * len(plan), quant=quant)


def wire_row_bytes(wplan: WirePlan) -> int:
    """Bytes one row occupies in a wire-narrowed exchange buffer: 4 per
    packed word + 8 per f64 passthrough column (the narrowed counterpart
    of :func:`cylon_tpu.parallel.shuffle.exchange_row_bytes`). Quantized
    f64 columns ride the packed words, not the passthrough."""
    qcols = {f.col for f in wplan.fields if f.kind == "q"}
    total = 4 * wplan.n_words
    total += sum(
        8
        for ci, (tag, _nl, _hv) in enumerate(wplan.plan)
        if tag is None and ci not in qcols
    )
    return max(total, 1)


def wire_q8_cols(wplan: WirePlan) -> Tuple[Tuple[int, str], ...]:
    """(col, dtype) of every block-scaled 'q8' field in field order —
    the fields whose per-block scales ride the exchange header rows."""
    out = []
    for f in wplan.fields:
        if f.kind == "q" and f.cls.startswith("q8:"):
            out.append((f.col, f.cls.split(":", 1)[1]))
    return tuple(out)


def wire_has_quant(wplan: Optional[WirePlan]) -> bool:
    return wplan is not None and any(
        f.kind == "q" for f in wplan.fields
    )


def wire_pt_order(wplan: WirePlan, pt_order) -> tuple:
    """The EFFECTIVE passthrough order under a wire plan: f64 columns
    captured by a 'q' field no longer ship a passthrough collective."""
    qcols = {f.col for f in wplan.fields if f.kind == "q"}
    return tuple(ci for ci in pt_order if ci not in qcols)


def wire_bases(wplan: WirePlan, stats_by_col: dict) -> np.ndarray:
    """[n_enc, 2] uint32 (hi, lo) base words for the plan's 'enc' fields,
    in field order — the tiny replicated operand both the pack and the
    compact kernel rebase with. 'bool' fields (and absent stats) use
    base 0."""
    rows = []
    for f in wplan.fields:
        if f.kind != "enc":
            continue
        st = stats_by_col.get(f.col)
        lo = 0 if (f.cls == "bool" or st is None) else int(st.lo)
        rows.append(((lo >> 32) & 0xFFFFFFFF, lo & 0xFFFFFFFF))
    return np.asarray(rows, np.uint32).reshape(-1, 2)


def _enc_base(bases: Optional[jax.Array], ei: int, wide: bool):
    """Base scalar for 'enc' field ``ei``: uint64 when the field's
    encoding is 64-bit, else uint32. ``bases=None`` means every enc field
    is static-base-0 (the stats-free plan)."""
    if bases is None:
        return jnp.uint64(0) if wide else jnp.uint32(0)
    hi = bases[ei, 0]
    lo = bases[ei, 1]
    if wide:
        return (hi.astype(jnp.uint64) << jnp.uint64(32)) | lo.astype(
            jnp.uint64
        )
    return lo


def wire_pack_cols(
    cols: Sequence[KeyCol],
    wplan: WirePlan,
    bases: Optional[jax.Array],
    qscales: Optional[jax.Array] = None,
):
    """Encode every column into the plan's bit-packed word lanes.

    Returns (word lanes [cap] int32 each, passthrough {col -> f64 data}).
    'enc' fields clamp to their width: live values always fit when the
    stats were sound bounds (masked values were measured too — they ride
    the wire like any payload), and unwritten buffer slots never ship
    live rows, so the clamp is a corruption firewall, not a data path.

    ``qscales``: [cap, n_q8] per-row f32 block scales for the plan's
    'q8' fields in field order (the caller broadcasts each row's
    destination-chunk scale; scales themselves ride the exchange header
    rows: shuffle.quant_chunk_scales_sorted, and the row-space
    shuffle.quant_chunk_scales in the fused pipeline)."""
    from . import quant as _q
    from .stats import assemble_words, encode_enc, layout_words

    qcols = {f.col for f in wplan.fields if f.kind == "q"}
    field_vals: List[jax.Array] = []
    bits_list: List[int] = []
    passthrough: Dict[int, jax.Array] = {}
    ei = 0
    qi = 0
    for f in wplan.fields:
        data, valid = cols[f.col]
        if f.kind == "enc":
            enc = encode_enc(data, f.cls)
            wide = enc.dtype == jnp.uint64
            base = _enc_base(bases, ei, wide)
            ei += 1
            if f.bits == 0:
                v = jnp.zeros(data.shape, jnp.uint32)
            else:
                from .stats import mask_of

                maxf = mask_of(min(f.bits, 64 if wide else 32), enc.dtype)
                v = jnp.minimum(enc - base, maxf)
        elif f.kind == "h16":
            v = jax.lax.bitcast_convert_type(data, jnp.uint16).astype(
                jnp.uint32
            )
        elif f.kind == "q":
            codec = f.cls.split(":", 1)[0]
            scale = None
            if codec == "q8":
                scale = qscales[:, qi]
                qi += 1
            v = _q.encode_field(codec, data, scale)
        elif f.kind == "lane":
            lane = _to_lanes(data)[0][f.off]
            v = jax.lax.bitcast_convert_type(lane, jnp.uint32)
        else:  # valid
            v = valid.astype(jnp.uint32)
        field_vals.append(v)
        bits_list.append(f.bits)
    for ci, (tag, _nl, _hv) in enumerate(wplan.plan):
        if tag is None and ci not in qcols:
            passthrough[ci] = cols[ci][0]
    words = assemble_words(field_vals, layout_words(bits_list, False))
    return [
        jax.lax.bitcast_convert_type(w, jnp.int32) for w in words
    ], passthrough


def wire_unpack_cols(
    word_lanes: Sequence[jax.Array],
    wplan: WirePlan,
    bases: Optional[jax.Array],
    handle_passthrough,
    make_valid,
    qscales: Optional[jax.Array] = None,
):
    """Decode :func:`wire_pack_cols` word lanes back into columns —
    the wire counterpart of :func:`unpack_cols` (same callback contract).
    ``qscales``: [rows, n_q8] per-row f32 block scales for the 'q8'
    fields, in field order (the receive side broadcasts each row's
    source-chunk scale from the exchange headers)."""
    from . import quant as _q
    from .stats import decode_enc, extract_fields, layout_words

    bits_list = [f.bits for f in wplan.fields]
    words = [
        jax.lax.bitcast_convert_type(w, jnp.uint32) for w in word_lanes
    ]
    vals = extract_fields(words, layout_words(bits_list, False), bits_list)
    # regroup fields by column (fields are column-major by construction),
    # carrying each enc/q8 field's POSITIONAL scale-slot index
    per_col: Dict[int, list] = {}
    ei = 0
    qi = 0
    for f, v in zip(wplan.fields, vals):
        slot = -1
        if f.kind == "enc":
            slot = ei
            ei += 1
        elif f.kind == "q" and f.cls.startswith("q8:"):
            slot = qi
            qi += 1
        per_col.setdefault(f.col, []).append((f, v, slot))
    out: List[KeyCol] = []
    for ci, (tag, nl, has_valid) in enumerate(wplan.plan):
        entries = per_col.get(ci, [])
        data = None
        vlane = None
        lane_frags: List[jax.Array] = []
        for f, v, slot in entries:
            if f.kind == "enc":
                # widen by CLASS, not by field width: a 64-bit column whose
                # measured span fits 32 bits extracts a uint32 field but
                # still rebases against a full 64-bit base
                from .stats import is64

                wide = is64(f.cls)
                base = _enc_base(bases, slot, wide)
                if wide:
                    v = v.astype(jnp.uint64)
                data = decode_enc(v + base, f.cls, np.dtype(tag))
            elif f.kind == "h16":
                data = jax.lax.bitcast_convert_type(
                    v.astype(jnp.uint16), jnp.dtype(f.cls)
                )
            elif f.kind == "q":
                codec, out_dt = f.cls.split(":", 1)
                scale = qscales[:, slot] if codec == "q8" else None
                data = _q.decode_field(codec, v, scale, out_dt)
            elif f.kind == "lane":
                lane_frags.append(
                    jax.lax.bitcast_convert_type(
                        v.astype(jnp.uint32), jnp.int32
                    )
                )
            else:
                vlane = v.astype(jnp.int32)
        if data is None and tag is None:
            data = handle_passthrough(ci)
        elif data is None:
            data = _from_lanes(lane_frags, tag)
        out.append((data, make_valid(vlane) if has_valid else make_valid(None)))
    return out


# ----------------------------------------------------------------------
# spill-aware HOST lane codec (parallel/spill.py)
#
# Spilled shuffle rounds and skew-relay tails leave the device as the
# ALREADY-PACKED [rows, L] int32 lane matrix — one transfer for every
# int32-lane column (+ one per f64 passthrough) — and decode on the host
# with these numpy mirrors of the device codec, instead of paying one
# device round-trip per column. The encodings are bit-identical to
# :func:`_from_lanes`, so a spilled row restages losslessly.
# ----------------------------------------------------------------------

def np_from_lanes(lanes: List[np.ndarray], tag: str) -> np.ndarray:
    """numpy mirror of :func:`_from_lanes`: int32 host lanes -> physical
    column values. Lanes must be contiguous (callers slice with
    ``np.ascontiguousarray``) so the 32-bit bitcasts are pure views."""
    if tag == "bool":
        return lanes[0].astype(np.bool_)
    if tag in ("float16", "bfloat16"):
        f32 = lanes[0].view(np.float32)
        out_dt = np.float16 if tag == "float16" else jnp.bfloat16
        return f32.astype(out_dt)
    dt = np.dtype(tag)
    if dt.itemsize == 4:
        return lanes[0] if tag == "int32" else lanes[0].view(dt)
    if dt.itemsize < 4:
        return lanes[0].astype(dt)
    hi = lanes[0].view(np.uint32).astype(np.uint64)
    lo = lanes[1].view(np.uint32).astype(np.uint64)
    u = (hi << np.uint64(32)) | lo
    return u.view(dt) if dt.kind in ("i", "u") else u.astype(dt)


def quant_lane_parts(plan, qspec):
    """The quantized host-crossing layout of a column set: plan entries
    for quantized columns are rewritten to ``("q8:<dtype>", 0,
    has_valid)`` — their DATA leaves the int32 lane matrix for a uint8
    code matrix (1 byte/row over PCIe and in the spill arenas instead of
    4-8) while their validity lane stays in the matrix. Only the 'q8'
    codec stages through host crossings (bf16/qf32 are wire-only tiers).
    Returns (qplan, q_cols) with q_cols = [(col, dtype_str)] in plan
    order."""
    qplan = []
    q_cols = []
    for ci, (tag, nl, has_valid) in enumerate(plan):
        qc = qspec[ci] if qspec is not None else None
        if qc == "q8":
            dt = tag if tag is not None else "float64"
            qplan.append((f"q8:{dt}", 0, has_valid))
            q_cols.append((ci, dt))
        else:
            qplan.append((tag, nl, has_valid))
    return tuple(qplan), tuple(q_cols)


def pack_cols_quant(cols: Sequence[KeyCol], qplan, q_cols, live=None):
    """Device twin of :func:`pack_cols` under a :func:`quant_lane_parts`
    layout: quantized columns' data is diverted to uint8 q8 codes with
    ONE block scale per column (finite max-abs over the live rows —
    ``live`` is an optional [cap] bool mask keeping garbage rows past
    the live count out of the scale). Returns (lanes, passthrough,
    qcodes [cap, nq] uint8, qscales [1, nq] f32)."""
    from . import quant as _q

    qset = {ci for ci, _dt in q_cols}
    lanes: List[jax.Array] = []
    passthrough = {}
    codes = []
    scales = []
    for ci, (data, valid) in enumerate(cols):
        if ci in qset:
            s = _q.safe_scale(_q.block_maxabs(data, live))
            codes.append(
                _q.encode_q8(data, s).astype(jnp.uint8)
            )
            scales.append(s)
        elif qplan[ci][0] is None:
            passthrough[ci] = data
        else:
            dl, _tag = _to_lanes(data)
            lanes.extend(dl)
        if valid is not None:
            lanes.append(valid.astype(jnp.int32))
    cap = cols[0][0].shape[0] if cols else 0
    if codes:
        qcodes = jnp.stack(codes, axis=1)
        qscales = jnp.stack(scales).reshape(1, len(scales))
    else:
        qcodes = jnp.zeros((cap, 0), jnp.uint8)
        qscales = jnp.zeros((1, 0), jnp.float32)
    return lanes, passthrough, qcodes, qscales


def host_unpack_cols_quant(
    qplan, lane_cols, handle_passthrough, handle_quant
):
    """Host twin of :func:`host_unpack_cols` for a quantized layout:
    ``handle_quant(ci, dtype_str)`` supplies a quantized column — either
    still-encoded ``(codes_u8, scale)`` (the arena staging path keeps
    bytes quantized) or already-decoded data. Validity lanes of
    quantized columns still ride ``lane_cols``."""
    out = []
    pos = 0
    for ci, (tag, nl, has_valid) in enumerate(qplan):
        if tag is not None and tag.startswith("q8:"):
            data = handle_quant(ci, tag.split(":", 1)[1])
        elif tag is None:
            data = handle_passthrough(ci)
        else:
            data = np_from_lanes(lane_cols[pos : pos + nl], tag)
            pos += nl
        valid = None
        if has_valid:
            valid = lane_cols[pos].astype(np.bool_)
            pos += 1
        out.append((data, valid))
    return out


def host_unpack_cols(plan, lane_cols, handle_passthrough):
    """Host twin of :func:`unpack_cols` over fetched numpy lanes:
    ``lane_cols`` are contiguous int32 arrays in plan order;
    ``handle_passthrough(ci)`` supplies an f64 column's fetched data.
    Returns [(data, valid-or-None)] in physical encoding."""
    out = []
    pos = 0
    for ci, (tag, nl, has_valid) in enumerate(plan):
        if tag is None:
            data = handle_passthrough(ci)
        else:
            data = np_from_lanes(lane_cols[pos : pos + nl], tag)
            pos += nl
        valid = None
        if has_valid:
            valid = lane_cols[pos].astype(np.bool_)
            pos += 1
        out.append((data, valid))
    return out


#: index rows one packed gather emits. Out of a source much shorter than
#: the index vector the TPU lays the gathered ``[rows, L]`` matrix out with
#: its L lanes padded to a tile's 128: 512 bytes a row whatever L is, and as
#: much again for each lane's ``[rows, 1]`` slice. That is 4 GiB a buffer at
#: this many rows and 8 GiB at the 2^24 slots that the fullest shard of a
#: skewed join of 32,000,000 rows gives every chip, where the compiler
#: refuses the program (16.13 GB of a v5e's 15.75). A longer index vector is
#: gathered block by block; up to this length the program is the one gather
#: it always was.
PACK_GATHER_BLOCK = 1 << 23

#: source rows per index row up to which a float64 column rides the packed
#: gather as two lanes. Packing stacks the source: the ``[cap, L]`` matrix is
#: written once a call, which is nothing where the index vector is of the
#: source's order and everything where the gather is selective. The join
#: emits draw 4,194,304 slots from 4,194,304 rows (``join-w1``), 8,388,608
#: from 8,388,608 and from 262,144 (``join-skew-w4``), about 1M from 1-2M
#: (``join-w4``): a ratio of 2 at most. ``reduce_by_hits`` in ``tpch-q3-w1``
#: draws 524,288 rows from 67,108,864-row lineitem columns, a ratio of 128:
#: a ``[67108864, 5]`` int32 matrix is 1.3 GB a query compact and 34 GB with
#: its lanes padded to a tile. 8 lies a factor of 4 from the one and 16 from
#: the other; past it the column is gathered alone, as it always was.
F64_PACK_RATIO = 8


def _gather_packed(packed: jax.Array, safe: jax.Array) -> List[jax.Array]:
    """The lanes of ``packed[safe]``, each ``[len(safe)]``: ONE gather, or
    one a block of ``PACK_GATHER_BLOCK`` index rows where there are more."""
    n, n_lanes = safe.shape[0], packed.shape[1]

    def rows(idx):
        g = packed[idx]
        return [g[:, j] for j in range(n_lanes)]

    if n <= PACK_GATHER_BLOCK:
        return rows(safe)
    pad = -n % PACK_GATHER_BLOCK
    blocks = jnp.pad(safe, (0, pad)).reshape(-1, PACK_GATHER_BLOCK)
    return [g.reshape(-1)[:n] for g in jax.lax.map(rows, blocks)]


def pack_gather(
    cols: Sequence[KeyCol],
    idx: jax.Array,
    extra_lanes: Sequence[jax.Array] = (),
    all_valid: bool = False,
) -> Tuple[List[KeyCol], List[jax.Array]]:
    """Gather every column (and any extra int32 lanes) by row index in ONE
    XLA gather.

    ``idx`` entries of -1 mean "no source row" (outer-join null side): the
    output value is gathered from a clamped index but its validity is False.
    Returns (gathered cols with merged validity, gathered extra lanes).

    ``all_valid=True``: the caller guarantees every -1 index lands on a
    PADDING output row (rows past the live count), so the -1 nulling mask is
    skipped and mask-free source columns stay mask-free — the key-order join
    emit uses this to keep the output key columns' sortedness descriptor
    usable by downstream mask-sensitive fast paths.

    A float64 column rides the same gather as two more lanes of the matrix
    (:func:`_f64_to_lanes`, by the rule of the mesh the kernel is traced
    for) where the source has at most :data:`F64_PACK_RATIO` rows an index
    row; where the gather is more selective than that it is gathered alone,
    which the chip does as one gather a float32 half. On a TPU mesh the
    program holds both forms under one ``cond`` and takes the lone gathers
    where a column holds a value whose low half the split could lose
    (:func:`_f64_low_half_may_flush`: magnitudes under 5e-23 that are not 0;
    a pass over the high halves' bits decides). The rollup counters
    ``gather.f64.packed`` / ``gather.f64.alone`` (``rows=`` the float64
    columns) say which a run's programs hold.
    """
    cap = cols[0][0].shape[0] if cols else extra_lanes[0].shape[0]
    plan, lanes, passthrough = pack_cols(cols)
    lanes = lanes + list(extra_lanes)
    n_own = len(lanes)
    safe = jnp.clip(idx, 0, cap - 1)
    ok = idx >= 0

    def make_valid(lane):
        if all_valid:
            return None if lane is None else lane.astype(jnp.bool_)
        return ok if lane is None else (ok & lane.astype(jnp.bool_))

    def gathered(f64_lanes, platform=None):
        """(columns, extras) with the float64 columns as ``f64_lanes`` of
        the matrix, two each in column order, or each gathered alone
        where there are none."""
        stacked = lanes + f64_lanes
        if len(stacked) == 1:
            g_cols = [stacked[0][safe]]
        elif stacked:
            g_cols = _gather_packed(jnp.stack(stacked, axis=1), safe)  # [cap, L]
        else:
            g_cols = []
        if f64_lanes:
            f64 = {
                ci: _f64_from_lanes(g_cols[at : at + 2], platform)
                for at, ci in zip(range(n_own, len(g_cols), 2), passthrough)
            }
        else:
            f64 = {ci: data[safe] for ci, data in passthrough.items()}
        out, pos = unpack_cols(plan, g_cols, f64.__getitem__, make_valid)
        return out, g_cols[pos:n_own]

    f64_packed = bool(passthrough) and cap <= F64_PACK_RATIO * idx.shape[0]
    if passthrough:
        bump(
            "gather.f64.packed" if f64_packed else "gather.f64.alone",
            rows=len(passthrough),
        )
    if not f64_packed:
        return gathered([])
    platform = mesh_platform()
    f64_lanes = [
        lane
        for data in passthrough.values()
        for lane in _f64_to_lanes(data, platform)
    ]
    if not _f64_two_float(platform):
        return gathered(f64_lanes, platform)
    # the two-float split is by arithmetic, which cannot see a low half the
    # chip flushes: a table that holds such a value keeps the lone gathers
    flushes = functools.reduce(
        jnp.logical_or,
        [jnp.any(_f64_low_half_may_flush(hi)) for hi in f64_lanes[::2]],
    )
    return jax.lax.cond(
        flushes, lambda: gathered([]), lambda: gathered(f64_lanes, platform)
    )
