"""Column range statistics + the bit-layout engine behind lane packing.

BENCH.md prices TPU wall time by traced sort-pass volume (`sort GB`), and
after the ordering (ISSUE 3) and semi-filter (ISSUE 4) work removed
redundant sorts and partnerless rows, the remaining cost is the WIDTH of
every surviving lane: a dictionary code that fits 12 bits, an int key
spanning 0..50k and a 1-bit validity mask each occupy a full uint32 word
in every lexsort pass and every all_to_all payload. This module is the
stats facility that lets both consumers narrow those lanes:

* :func:`enc_class` / :func:`encode_enc` / :func:`decode_enc` — ONE
  monotone-encoding classifier and codec shared by the sort-word fusion
  planner (ops/sort.py), the wire codec (ops/gather.py) and the semi-join
  range gate (ops/sketch.py — previously its own duplicated
  ``range_class``/``_range_enc``), so range gating and lane packing can
  never disagree on an encoding family. The value encodings themselves
  are :func:`cylon_tpu.ops.sort.orderable_key` — the engine's one
  canonical order-preserving representation.
* :class:`ColStat` — per-column [lo, hi] bounds of the orderable
  encoding over LIVE rows (masked values INCLUDED: null rows' payload
  still rides sort lanes and wire fields, so the bounds must cover it).
  Carried on ``Table`` like the ``Ordering`` descriptor: established by
  kernels that touch the data anyway (the shuffle count pass measures
  every statable column and the bounds ride its one existing fetch;
  ``Table.ensure_stats`` computes them on demand for sort/groupby/join),
  carried by row-subset ops (bounds are conservative), invalidated by
  in-place mutation, and part of every consuming kernel's cache key via
  :func:`field_bits`-quantized signatures.
* :func:`layout_words` / :func:`assemble_words` / :func:`extract_fields`
  — the shared bit-packing engine: a list of field widths is sliced into
  the fewest uint32/uint64 words, most-significant field first, so
  word-lexicographic order equals field-lexicographic order (fields may
  straddle word boundaries; a split field's (hi, lo) fragments compare
  exactly like the number). Sort fusion packs key lanes through it; the
  wire codec packs payload lanes through it.

``CYLON_TPU_NO_LANE_PACK=1`` disables every consumer (sort-word fusion,
canonical-lane fusion, wire narrowing, stats establishment); the chosen
path is always part of the kernel cache key, so flips recompile, never
alias. ``disabled()`` is the differential-testing oracle toggle
(tools/fuzz_campaign.py --profile packing).
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.envgate import env_gate
from .sort import KeyCol, orderable_key

# the CYLON_TPU_NO_LANE_PACK=1 kill switch (shared machinery with the
# ordering/semi-filter toggles — utils/envgate.py)
enabled, disabled = env_gate(
    "CYLON_TPU_NO_LANE_PACK",
    keyed_via="stat_cols / quantized fuse plans / WirePlan statics join "
    "every consumer kernel cache key; the plan fingerprint includes the "
    "gate (plan/lazy.py)",
)

_MAXU64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def enc_class(np_dtype) -> Optional[str]:
    """Monotone orderable-encoding family of a physical dtype, or None when
    the dtype has no packable unsigned lane:

    - ``bool``/``u32``/``i32``: 32-bit-or-narrower ints and bools — the
      orderable lane is a bijective uint32 (dictionary CODES qualify via
      their int32 physical dtype);
    - ``i64``/``u64``: 64-bit ints — bijective uint64, only when X64 is
      live (without it the emulated u64 lane does not exist);
    - ``f32``: every sub-64-bit float (f16/bf16/f32 — orderable_key
      widens the halfs to f32 exactly) — MONOTONE uint32, order-exact, so
      sort fusion may use it, but lossy at the bit level (-0.0 and NaN
      payloads canonicalize), so the wire codec must not
      (:func:`wire_narrowable`);
    - ``None``: f64 (no bit-level 32-bit lane route on TPU), anything else.
    """
    dt = np.dtype(np_dtype)
    if dt == np.bool_:
        return "bool"
    if dt == np.float64:
        return None
    if np.issubdtype(dt, np.floating):
        return "f32"
    if np.issubdtype(dt, np.signedinteger):
        if dt.itemsize <= 4:
            return "i32"
        return "i64" if jax.config.jax_enable_x64 else None
    if np.issubdtype(dt, np.unsignedinteger):
        if dt.itemsize <= 4:
            return "u32"
        return "u64" if jax.config.jax_enable_x64 else None
    return None


def wire_narrowable(cls: Optional[str]) -> bool:
    """Classes whose encoding is BIT-LOSSLESS and therefore sound for the
    wire codec (floats are order-exact but canonicalize -0.0/NaN)."""
    return cls in ("bool", "u32", "i32", "i64", "u64")


def is64(cls: str) -> bool:
    return cls in ("i64", "u64")


def encode_enc(data: jax.Array, cls: str) -> jax.Array:
    """Orderable encoding lane for a classified column: uint32 for 32-bit
    classes, uint64 for 64-bit. Identical to ``orderable_key`` on every
    class (ONE encoding definition — the unification the range gate and
    the packers share)."""
    enc = orderable_key(data)
    assert enc.dtype in (jnp.uint32, jnp.uint64), cls
    return enc


def decode_enc(enc: jax.Array, cls: str, np_dtype) -> jax.Array:
    """Exact inverse of :func:`encode_enc` for the wire-narrowable classes
    (int families + bool; float classes are not bit-lossless and are never
    wire-narrowed)."""
    dt = jnp.dtype(np_dtype)
    if cls == "bool":
        return enc.astype(jnp.bool_)
    if cls == "u32":
        return enc.astype(dt)
    if cls == "i32":
        raw = jax.lax.bitcast_convert_type(
            enc.astype(jnp.uint32) ^ np.uint32(0x80000000), jnp.int32
        )
        return raw.astype(dt)
    if cls == "u64":
        return enc.astype(dt)
    if cls == "i64":
        return (enc ^ (jnp.uint64(1) << jnp.uint64(63))).astype(dt)
    raise ValueError(f"class {cls!r} has no lossless decode")


class ColStat(NamedTuple):
    """[lo, hi] bounds of one column's orderable encoding over LIVE rows
    (values under null included), as Python ints of the uint64-widened
    encoding. Bounds are conservative: any superset range stays sound, so
    row-subset ops carry the descriptor forward unchanged."""

    lo: int
    hi: int
    cls: str

    def merge(self, other: "ColStat") -> Optional["ColStat"]:
        if other is None or other.cls != self.cls:
            return None
        return ColStat(min(self.lo, other.lo), max(self.hi, other.hi), self.cls)


def dictionary_stat(size: int) -> ColStat:
    """The range of a dictionary column's int32 codes, known without a
    measurement: code 0 in the orderable encoding, ``size`` codes."""
    lo = 1 << 31
    return ColStat(lo, lo + max(1, int(size)) - 1, "i32")


def field_bits(stat: ColStat) -> int:
    """QUANTIZED field width of a stat's span: exact for 0-2 bits, else
    rounded up to a multiple of 4 (cap 64). Quantization is what keeps the
    kernel cache warm across small range drifts — the bits, not the raw
    bounds, enter every consumer's cache key."""
    b = int(stat.hi - stat.lo).bit_length()
    if b <= 2:
        return b
    return min(64, -(-b // 4) * 4)


# ----------------------------------------------------------------------
# stat measurement (kernel side) + host fold
# ----------------------------------------------------------------------

def stat_words(col: KeyCol, n: jax.Array) -> jax.Array:
    """[4] int32 per-shard stat vector of one statable column:
    [min_hi, min_lo, max_hi, max_lo] uint32 words of the uint64-widened
    encoding bounds over live rows. An empty shard reports the inverted
    window (min=MAX, max=0); the host fold treats a globally inverted
    window as "no rows". One elementwise pass + two reductions — cheap
    enough to ride any kernel that touches the data anyway."""
    data, _valid = col
    cap = data.shape[0]
    live = jnp.arange(cap, dtype=jnp.int32) < n
    enc = orderable_key(data)
    if enc.dtype == jnp.uint64:
        lo = jnp.min(jnp.where(live, enc, _MAXU64))
        hi = jnp.max(jnp.where(live, enc, jnp.uint64(0)))
        words = jnp.stack([
            (lo >> jnp.uint64(32)).astype(jnp.uint32),
            (lo & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
            (hi >> jnp.uint64(32)).astype(jnp.uint32),
            (hi & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
        ])
    else:
        lo = jnp.min(jnp.where(live, enc, np.uint32(0xFFFFFFFF)))
        hi = jnp.max(jnp.where(live, enc, jnp.uint32(0)))
        z = jnp.uint32(0)
        words = jnp.stack([z, lo, z, hi])
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def fold_stat_words(per_shard: np.ndarray, cls: str) -> ColStat:
    """Fold [P, 4] per-shard stat words into one global :class:`ColStat`.
    A globally empty column folds to the degenerate (0, 0) stat (no rows
    ride any lane, so any bounds are vacuously sound)."""
    w = (per_shard.astype(np.int64) & 0xFFFFFFFF).astype(np.uint64)
    lo = int((w[:, 0] << np.uint64(32) | w[:, 1]).min())
    hi = int((w[:, 2] << np.uint64(32) | w[:, 3]).max())
    if lo > hi:  # inverted window: every shard was empty
        return ColStat(0, 0, cls)
    return ColStat(lo, hi, cls)


# ----------------------------------------------------------------------
# the shared bit-layout engine
# ----------------------------------------------------------------------

# a word layout: [(width_bits, [(field_idx, frag_lo, frag_bits, shift)])]
# most-significant word first; frag_lo is the fragment's offset inside the
# FIELD, shift its offset inside the WORD
WordLayout = List[Tuple[int, List[Tuple[int, int, int, int]]]]


def layout_words(bits_list: Sequence[int], allow64: bool) -> WordLayout:
    """Slice a most-significant-first list of field widths into the fewest
    physical words (uint64 where ``allow64`` and >32 bits remain, else
    uint32). Fields may straddle word boundaries: a split field's (hi, lo)
    fragments in adjacent words compare exactly like the whole number, so
    word-lexicographic order == field-lexicographic order by construction.
    Unused bits sit at the BOTTOM of the last word (constant-zero tie
    padding). Zero-width fields occupy no bits."""
    total = sum(bits_list)
    if total == 0:
        # every field is zero-width (constant/empty columns): still emit
        # one constant-zero word so callers that sized buffers/flags off
        # "fields exist => lanes exist" (the shuffle's has_lanes) hold
        return [(32, [])]
    widths: List[int] = []
    remaining = total
    while remaining > 0:
        w = 64 if (allow64 and remaining > 32) else 32
        widths.append(w)
        remaining -= w
    padded = sum(widths)
    # field positions in the padded global bit space (msb at padded-1)
    fpos = []
    top = padded
    for b in bits_list:
        fpos.append((top - b, top))
        top -= b
    layout: WordLayout = []
    wtop = padded
    for w in widths:
        wlo = wtop - w
        frags = []
        for fi, (flo, fhi) in enumerate(fpos):
            take_lo = max(flo, wlo)
            take_hi = min(fhi, wtop)
            if take_hi <= take_lo:
                continue
            frags.append((fi, take_lo - flo, take_hi - take_lo, take_lo - wlo))
        layout.append((w, frags))
        wtop = wlo
    return layout


def mask_of(bits: int, dtype) -> np.ndarray:
    """Width mask of a ``bits``-wide field in ``dtype`` (uint32/uint64) —
    the ONE copy of the bits>=32 special case shared by the layout engine,
    sort-word fusion and the wire codec."""
    if dtype == jnp.uint64:
        return np.uint64((1 << bits) - 1)
    return np.uint32((1 << bits) - 1 if bits < 32 else 0xFFFFFFFF)


def assemble_words(
    fields: Sequence[jax.Array], layout: WordLayout
) -> List[jax.Array]:
    """Pack per-row field value arrays (uint32/uint64, already clamped to
    their widths) into word lanes per ``layout``. Returns words
    most-significant first; 32-bit words come back as uint32, 64-bit as
    uint64."""
    out = []
    for width, frags in layout:
        wdt = jnp.uint64 if width == 64 else jnp.uint32
        acc = None
        for fi, frag_lo, frag_bits, shift in frags:
            f = fields[fi]
            if frag_lo:
                f = f >> f.dtype.type(frag_lo)
            f = (f & mask_of(frag_bits, f.dtype)).astype(wdt)
            if shift:
                f = f << wdt(shift)
            acc = f if acc is None else (acc | f)
        if acc is None:
            acc = jnp.zeros(fields[0].shape if fields else (), wdt)
        out.append(acc)
    return out


def extract_fields(
    words: Sequence[jax.Array], layout: WordLayout, bits_list: Sequence[int]
) -> List[jax.Array]:
    """Inverse of :func:`assemble_words`: per-field value arrays (uint64
    for >32-bit fields, uint32 otherwise)."""
    fields: List[Optional[jax.Array]] = [None] * len(bits_list)
    for (width, frags), word in zip(layout, words):
        for fi, frag_lo, frag_bits, shift in frags:
            fdt = jnp.uint64 if bits_list[fi] > 32 else jnp.uint32
            v = word
            if shift:
                v = v >> v.dtype.type(shift)
            v = (v & mask_of(frag_bits, v.dtype)).astype(fdt)
            if frag_lo:
                v = v << fdt(frag_lo)
            prev = fields[fi]
            fields[fi] = v if prev is None else (prev | v)
    return [
        f if f is not None
        else jnp.zeros(words[0].shape, jnp.uint64 if b > 32 else jnp.uint32)
        for f, b in zip(fields, bits_list)
    ]
