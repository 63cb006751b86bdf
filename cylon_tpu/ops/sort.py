"""Multi-column ordering primitives.

Reference analog: the argsort kernels (``SortIndices`` / multi-column
lexicographic sort, cpp/src/cylon/arrow/arrow_kernels.hpp:95-143, introsort in
util/sort.hpp:127-144). On TPU the native primitive is ``jax.lax.sort`` /
``jnp.lexsort`` — a bitonic/stable sort that XLA lowers to the hardware — so
every ordering here is expressed as one lexsort over normalized key lanes.
This is the only sort engine: a width-adaptive LSD radix engine lost to the
native sort on a v5e in every stage that sorts (42-166 times slower, 9 times
at 2-3 bit lanes: its passes are per-element gathers and scatters at 17 ns a
row) and was deleted (PERF.md section 6, PRs 26 and 29).

Padding discipline: all kernels receive fixed-capacity arrays where only rows
``[0, n)`` are live. A most-significant "row class" lane forces
live < null < padding ordering so padding can never interleave with data.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import stages as _stages

KeyCol = Tuple[jax.Array, Optional[jax.Array]]  # (data, valid-or-None)



def wide_float():
    """float64 when X64 is enabled, else float32 — avoids the noisy
    jax truncation warning under CYLON_TPU_NO_X64 pipelines."""
    import jax

    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def wide_int():
    import jax

    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def orderable_key(data: jax.Array) -> jax.Array:
    """Map a numeric column to a canonical sort/equality lane.

    For everything except float64 the lane is an unsigned integer where plain
    unsigned ordering == value ordering (total-order float semantics for f32:
    -inf < ... < -0 == +0 < ... < +inf < NaN, all NaNs equal). float64 keeps a
    canonicalized *float* lane (-0 -> +0): the TPU X64-rewrite pass cannot
    lower 64-bit ``bitcast_convert``, and XLA's float sort comparator is
    already a total order with all NaNs greatest. Because the f64 lane is a
    float, equality checks on lanes must go through :func:`lanes_differ`
    (NaN-aware) rather than ``!=``.

    This is THE canonical key representation: every sort lane, run-detect
    equality, and join probe uses it, so NaN==NaN and -0.0==+0.0 behave
    identically across all ops (pandas semantics).
    """
    dt = data.dtype
    if dt == jnp.bool_:
        return data.astype(jnp.uint32)
    if jnp.issubdtype(dt, jnp.floating):
        if dt == jnp.float16 or dt == jnp.bfloat16:
            data = data.astype(jnp.float32)
            dt = jnp.dtype(jnp.float32)
        # canonicalize: -0.0 -> +0.0
        data = jnp.where(data == 0, jnp.zeros_like(data), data)
        if dt == jnp.float64:
            return data
        b = jax.lax.bitcast_convert_type(data, jnp.uint32)
        b = jnp.where(jnp.isnan(data), np.uint32(0x7FC00000), b)
        return jnp.where((b >> np.uint32(31)) == 0, b | np.uint32(0x80000000), ~b)
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        if np.dtype(dt).itemsize <= 4:
            return data.astype(jnp.uint32)
        return data.astype(jnp.uint64)
    # signed integers: flip the sign bit into unsigned order (64-bit path via
    # wrapping convert — bit pattern preserved — since TPU can't bitcast x64)
    if np.dtype(dt).itemsize <= 4:
        return (
            jax.lax.bitcast_convert_type(data.astype(jnp.int32), jnp.uint32)
            ^ np.uint32(0x80000000)
        )
    return data.astype(jnp.uint64) ^ (jnp.uint64(1) << jnp.uint64(63))


def lanes_differ(a: jax.Array, b: jax.Array) -> jax.Array:
    """Elementwise lane inequality; NaN == NaN on float (f64) lanes."""
    d = a != b
    if jnp.issubdtype(a.dtype, jnp.floating):
        d = d & ~(jnp.isnan(a) & jnp.isnan(b))
    return d


def _norm_key(data: jax.Array, ascending: bool) -> jax.Array:
    """Normalize one key column into a lane where plain ascending ordering
    matches the requested order (see orderable_key)."""
    lane = orderable_key(data)
    if not ascending:
        if jnp.issubdtype(lane.dtype, jnp.floating):
            # f64 lane: negate; NaNs remain greatest under XLA's comparator
            # so they sort last in either direction
            lane = -lane
        else:
            lane = ~lane
            if jnp.issubdtype(data.dtype, jnp.floating):
                # bit-inversion would send the canonical-NaN lane near the
                # bottom; pin NaNs to the top so f32 matches the f64 rule
                # (NaN last in either direction)
                lane = jnp.where(jnp.isnan(data), np.uint32(0xFFFFFFFF), lane)
    return lane


def row_class(
    n: jax.Array,
    cap: int,
    valid: Optional[jax.Array] = None,
    nulls_last: bool = True,
) -> jax.Array:
    """Most-significant sort lane: 0 = live value, 1 = null, 2 = padding."""
    idx = jnp.arange(cap, dtype=jnp.int32)
    cls = jnp.where(idx < n, jnp.int8(0), jnp.int8(2))
    if valid is not None:
        nullcls = jnp.int8(1) if nulls_last else jnp.int8(-1)
        cls = jnp.where((idx < n) & ~valid, nullcls, cls)
    return cls


def lexsort_with_payload(
    lanes: Sequence[jax.Array],
    payloads: Sequence[jax.Array],
    keep_lanes: bool = True,
) -> Tuple[list, list]:
    """``jnp.lexsort``-equivalent (lanes least-significant FIRST) as CHAINED
    stable 1-key sorts, carrying ``payloads`` through every pass.

    TPU rationale: XLA's multi-key sort comparator blows up compile time
    super-linearly in the key count (measured on v5e at 4M rows: 1 key 13 s,
    3 keys 148 s) while warm time is no better than k chained 1-key passes
    (80 ms vs 76 ms). Sorting by the least significant lane first, with
    every pass stable, reproduces the multi-key order exactly (verified
    element-identical).

    ``keep_lanes=False`` drops each lane after the pass it keys (a consumed
    lane is never read again), saving ~k/2 lanes of memory-bandwidth-bound
    traffic per pass for callers that only want the payloads.

    Returns (sorted_lanes | None, sorted_payloads).
    """
    if not keep_lanes and not payloads:
        return None, []  # nothing is wanted back: no pass
    with jax.named_scope(_stages.SORT_ENGINE):
        k = len(lanes)
        if not keep_lanes:
            pending = list(lanes)  # least-significant first; index 0 keys next
            carry = list(payloads)
            for _ in range(k):
                key, *pending = pending
                out = jax.lax.sort(
                    tuple([key] + pending + carry), num_keys=1, is_stable=True
                )
                pending = list(out[1 : 1 + len(pending)])
                carry = list(out[1 + len(pending) :])
            return None, carry
        ops = list(lanes) + list(payloads)
        for i in range(k):  # least significant first
            rest = [ops[j] for j in range(len(ops)) if j != i]
            out = jax.lax.sort(tuple([ops[i]] + rest), num_keys=1, is_stable=True)
            ops = [None] * len(ops)
            ops[i] = out[0]
            rj = 1
            for j in range(len(ops)):
                if ops[j] is None:
                    ops[j] = out[rj]
                    rj += 1
        return ops[:k], ops[k:]


def lexsort_indices(lanes: Sequence[jax.Array], cap: int) -> jax.Array:
    """Permutation that stably lexsorts ``lanes`` (least-significant first):
    the chained-pass replacement for ``jnp.lexsort``."""
    with jax.named_scope(_stages.SORT_PERM):
        iota = jnp.arange(cap, dtype=jnp.int32)
        _, pays = lexsort_with_payload(lanes, [iota], keep_lanes=False)
        return pays[0]


# ---------------------------------------------------------------------------
# bit-width-adaptive sort-word fusion (ops/stats.py range stats drive it)
#
# Every chained pass streams one lane; a 12-bit dictionary code, a 16-bit
# int key and a 1-bit null flag each occupy a full word today. The fusion
# planner bit-packs multiple narrow orderable_key lanes (rebased by their
# in-kernel minimum — the range STATS only fix the static field widths,
# so data drift never corrupts, it just recompiles on a quantized-bits
# change) into the fewest physical sort words. Order-preserving by
# construction: orderable encodings are monotone, rebasing by a uniform
# per-column scalar preserves order, and msb-first field concatenation
# makes word-lexicographic order equal lane-lexicographic order.
# ---------------------------------------------------------------------------

class FusePlan(NamedTuple):
    """Static sort-word fusion plan — part of every consuming kernel's
    cache key (hashable; carries QUANTIZED widths, never raw bounds).

    ``fields``: msb-first ``(kind, key_pos, bits, ascending)`` with kind in
    {'pad', 'prefix', 'null', 'value'}. ``allow64``: whether the layout
    may use one uint64 word (only when the WHOLE plan fits a single word —
    a 64-bit word may be a sort KEY but must never ride another pass as a
    variadic-sort operand, which the TPU X64 rewriter has no audited
    lowering for). ``n_words`` / ``n_plain``: fused vs unfused lane
    counts (the gate: fusion engages only when strictly fewer)."""

    fields: Tuple[Tuple[str, int, int, bool], ...]
    allow64: bool
    n_words: int
    n_plain: int


def plan_lane_fusion(
    key_specs: Sequence[Optional[Tuple[str, int, bool, bool]]],
    pad_bits: int,
    prefix_bits: int,
    allow64: bool,
) -> Optional["FusePlan"]:
    """Build a :class:`FusePlan` for key columns with measured range stats.

    ``key_specs``: per key ``(enc_class, field_bits, has_valid, ascending)``
    or None when the key has no usable stats (unknown range, f64, 64-bit
    without X64). ``pad_bits``: width of the most-significant padding/live
    class field (2 for the lexsort row-class, 1 for the canonical live
    flag). ``prefix_bits``: width of the sorted-run-reuse prefix lane (0 =
    absent). Returns None when any key is unplannable, when a float key
    sorts DESCENDING (the unpacked path pins NaN last in both directions;
    a rebased descending float field cannot), or when fusion would not
    strictly reduce the pass count.
    """
    from .stats import layout_words

    if any(s is None for s in key_specs) or not key_specs:
        return None
    fields: list = [("pad", -1, pad_bits, True)]
    if prefix_bits:
        fields.append(("prefix", -1, prefix_bits, True))
    n_plain = 1 + (1 if prefix_bits else 0)
    for pos, (cls, bits, has_valid, asc) in enumerate(key_specs):
        if cls == "f32" and not asc:
            return None  # NaN-last pinning has no rebased-field encoding
        if bits > 32 and not allow64:
            return None
        if has_valid:
            fields.append(("null", pos, 1, True))
            n_plain += 1
        fields.append(("value", pos, bits, bool(asc)))
        n_plain += 1
    bits_list = [b for _k, _p, b, _a in fields]
    # a 64-bit word is legal only as THE single sort word (key-only, never
    # a variadic operand of another pass) — see FusePlan docstring
    layout = layout_words(bits_list, allow64)
    use64 = allow64 and len(layout) == 1
    if not use64:
        layout = layout_words(bits_list, False)
    n_words = len(layout)
    if n_words >= n_plain:
        return None
    return FusePlan(tuple(fields), use64, n_words, n_plain)


def _field_base(enc: jax.Array, live: jax.Array, asc: bool) -> jax.Array:
    """What a fused value field is rebased by: the live rows' smallest
    orderable encoding (their largest for a descending field). Encode
    (:func:`fused_key_words`) and decode (:func:`fused_key_decode`) both
    take it from here."""
    from .stats import mask_of

    wide = enc.dtype == jnp.uint64
    if asc:
        enc_max = mask_of(64 if wide else 32, enc.dtype)
        return jnp.min(jnp.where(live, enc, enc_max))
    zero = np.uint64(0) if wide else np.uint32(0)
    return jnp.max(jnp.where(live, enc, zero))


def fused_key_words(
    plan: "FusePlan",
    key_cols: Sequence[KeyCol],
    live: jax.Array,
    nulls_last: bool = True,
    prefix_lane: Optional[jax.Array] = None,
    zero_null_values: bool = False,
) -> list:
    """The fused sort words (msb-first) for one plan.

    Each value field is the key's orderable encoding REBASED by its
    in-kernel live-row minimum and clamped to the field width: stats only
    chose the static width, so live values always fit whenever the stats
    were sound bounds, and padding-row garbage clamps instead of
    corrupting neighboring fields (padding order is don't-care — the pad
    field dominates). Null-masked rows' PAYLOAD values are measured into
    the stats too, so with ``zero_null_values=False`` (lexsort semantics:
    null rows order by their masked payload) the field is exact;
    ``zero_null_values=True`` reproduces canonical_row_lanes' zeroed
    value-under-null (null == null runs)."""
    with jax.named_scope(_stages.SORT_KEYS):
        fields = []
        bits_list = []
        for kind, pos, bits, asc in plan.fields:
            if kind == "pad":
                v = jnp.where(
                    live, jnp.uint32(0), np.uint32((1 << bits) - 1)
                )
            elif kind == "prefix":
                v = jnp.clip(
                    prefix_lane, 0, (1 << bits) - 1
                ).astype(jnp.uint32)
            elif kind == "null":
                _data, valid = key_cols[pos]
                flag = ~valid if nulls_last else valid
                v = flag.astype(jnp.uint32)
            else:  # value
                data, valid = key_cols[pos]
                enc = orderable_key(data)
                fdt = enc.dtype
                if bits == 0:
                    v = jnp.zeros(data.shape, jnp.uint32)
                else:
                    from .stats import mask_of

                    wide = fdt == jnp.uint64
                    maxf = mask_of(min(bits, 64 if wide else 32), fdt)
                    base = _field_base(enc, live, asc)
                    v = jnp.minimum(enc - base if asc else base - enc, maxf)
                if zero_null_values and valid is not None:
                    v = jnp.where(valid, v, jnp.zeros_like(v))
            fields.append(v)
            bits_list.append(bits)
        from .stats import assemble_words, layout_words

        return assemble_words(fields, layout_words(bits_list, plan.allow64))


def fused_decodable(plan: "FusePlan", key_cols: Sequence[KeyCol]) -> list:
    """Per key column: whether :func:`fused_key_decode` gives its values
    back from the plan's words bit for bit (an ascending field of an
    integer, bool or dictionary-code column; a float field canonicalizes
    -0.0 and NaN and is not)."""
    from .stats import enc_class, wire_narrowable

    ok = [False] * len(key_cols)
    for kind, pos, _bits, asc in plan.fields:
        if kind == "value" and asc:
            ok[pos] = wire_narrowable(enc_class(key_cols[pos][0].dtype))
    return ok


def fused_key_decode(
    plan: "FusePlan",
    words: Sequence[jax.Array],
    key_cols: Sequence[KeyCol],
    live: jax.Array,
) -> list:
    """The :func:`fused_decodable` key columns of some rows, read back
    out of those rows' fused ``words`` (:func:`fused_key_words` with
    ``zero_null_values=True``, msb first; any row subset or order):
    ``[(data, valid | None) | None]`` by key position, so a consumer that
    carries the sort words (the group-by) need not carry the keys beside
    them. ``key_cols`` / ``live`` are the UNSORTED inputs the words were
    made of: each field's base is their live minimum, as it was there."""
    from .stats import decode_enc, enc_class, extract_fields, layout_words

    bits_list = [b for _k, _p, b, _a in plan.fields]
    fields = extract_fields(
        list(words), layout_words(bits_list, plan.allow64), bits_list
    )
    decodable = fused_decodable(plan, key_cols)
    out: list = [None] * len(key_cols)
    nulls = {}
    for (kind, pos, _bits, _asc), field in zip(plan.fields, fields):
        if kind == "null":
            nulls[pos] = field == 0
        elif kind == "value" and decodable[pos]:
            data, _valid = key_cols[pos]
            cls = enc_class(data.dtype)
            enc = orderable_key(data)
            base = _field_base(enc, live, True)
            out[pos] = (
                decode_enc(base + field.astype(enc.dtype), cls, data.dtype),
                nulls.get(pos),
            )
    return out


# ---------------------------------------------------------------------------
# run (equal-key segment) scans over a sorted order — shared by the join
# probe (ops/join._merged_counts) and the set algebra (ops/setops): ONE
# implementation of the subtle prefix-scan idioms.
# ---------------------------------------------------------------------------

def run_start_broadcast(new_run: jax.Array, prefix: jax.Array) -> jax.Array:
    """Broadcast each run's first ``prefix`` value over the whole run.

    Valid only for NON-DECREASING ``prefix`` (e.g. a cumsum): cummax of the
    run-start-masked values then reproduces the start value everywhere."""
    return jax.lax.cummax(jnp.where(new_run, prefix, 0))


def run_count_upto(new_run: jax.Array, flag: jax.Array) -> jax.Array:
    """[cap] int32: how many ``flag`` positions MY run has at/before me."""
    f = flag.astype(jnp.int32)
    excl = jnp.cumsum(f) - f
    return excl + f - run_start_broadcast(new_run, excl)


def run_count_from(new_run: jax.Array, flag: jax.Array) -> jax.Array:
    """[cap] int32: how many ``flag`` positions MY run has at/after me.

    Mirror of :func:`run_count_upto` on flipped arrays (a run's end is the
    flipped run's start). At a run START this is the run's total count."""
    f_r = jnp.flip(flag.astype(jnp.int32))
    run_end = jnp.concatenate([new_run[1:], jnp.ones((1,), bool)])
    new_run_r = jnp.flip(run_end)
    excl_r = jnp.cumsum(f_r) - f_r
    start_r = jax.lax.cummax(jnp.where(new_run_r, excl_r, 0))
    return jnp.flip(excl_r + f_r - start_r)


#: rows a block of :func:`run_reduce`'s scan (one vector register's lanes)
SCAN_BLOCK = 128

_COMBINE = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def scan_identity(kind: str, dtype):
    """The value a ``"sum" | "min" | "max"`` reduction starts from."""
    if kind == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if kind == "min" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if kind == "min" else info.min, dtype)


def _shift_up(x: jax.Array, k: int, fill) -> jax.Array:
    """``y[..., i] = x[..., i + k]``, ``fill`` past the end."""
    pad = jnp.full(x.shape[:-1] + (k,), fill, x.dtype)
    return jnp.concatenate([x[..., k:], pad], axis=-1)


def _scan_steps(closed: jax.Array, vals: list, kinds: Sequence[str]):
    """Shift-and-combine steps along the last axis: afterwards ``vals[i]``
    reduces its row and the rows after it up to the first ``closed`` one
    (or the axis' end), and ``closed[i]`` says whether there is one."""
    k, width = 1, closed.shape[-1]
    while k < width:
        vals = [
            jnp.where(closed, v, _COMBINE[kind](
                v, _shift_up(v, k, scan_identity(kind, v.dtype))
            ))
            for v, kind in zip(vals, kinds)
        ]
        closed = closed | _shift_up(closed, k, False)
        k *= 2
    return closed, vals


def run_reduce(
    run_end: jax.Array, vals: Sequence[jax.Array], kinds: Sequence[str]
) -> list:
    """Reduce every run of rows, for each ``vals[i]`` with its own
    ``kinds[i]`` of ``"sum" | "min" | "max"``: row i gets the reduction of
    rows i .. the end of its run, so a run's FIRST row holds the run's
    total. ``run_end`` [n] bool marks each run's last row.

    A segmented scan: a run adds (or compares) only its own values, so a
    float sum's error scales with that run's magnitude and not with the
    prefix before it, as a difference of prefix sums would have it. No
    scatter, no gather and no sort: blocks of :data:`SCAN_BLOCK`
    consecutive rows scan themselves by log2(block) shift-and-combine
    passes, the blocks' heads scan the same way one level up, and one
    pass hands every open row the total that follows its block."""
    vals, kinds = list(vals), list(kinds)
    n = run_end.shape[0]
    if n <= SCAN_BLOCK:
        return _scan_steps(run_end, vals, kinds)[1]
    blocks = -(-n // SCAN_BLOCK)
    pad = blocks * SCAN_BLOCK - n

    def tile(x, fill):
        if pad:
            x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
        return x.reshape(blocks, SCAN_BLOCK)

    closed, tiles = _scan_steps(
        tile(run_end, True),
        [tile(v, scan_identity(k, v.dtype)) for v, k in zip(vals, kinds)],
        kinds,
    )
    # a block's first row holds the block's reduction up to its first run
    # end; what follows block b is the scan of the heads from b + 1 on
    heads = run_reduce(closed[:, 0], [t[:, 0] for t in tiles], kinds)
    out = []
    for t, head, kind in zip(tiles, heads, kinds):
        follows = _shift_up(head, 1, scan_identity(kind, head.dtype))
        t = jnp.where(closed, t, _COMBINE[kind](t, follows[:, None]))
        out.append(t.reshape(-1)[:n])
    return out


def canonical_row_lanes(
    cols: Sequence[KeyCol], live: jax.Array, fuse: Optional["FusePlan"] = None
) -> list:
    """Canonical key lanes for one combined row ordering, most significant
    first: [padding-last class, per column: (null lane, value lane)].

    Value lanes are zeroed under null so that a run of nulls is ONE run
    regardless of the masked payload (rows_differ semantics: null == null).
    Shared by the set algebra and factorize.

    ``fuse``: a stats-driven :class:`FusePlan` (pad_bits=1 — the live
    flag) bit-packs the whole lane stack into fewer physical words; sorted
    ORDER and run boundaries of live rows are identical by construction
    (monotone rebased fields, value zeroed under null), so factorize ids
    come out exactly equal to the unfused path's."""
    if fuse is not None:
        return fused_key_words(
            fuse, cols, live, nulls_last=True, zero_null_values=True
        )
    lanes: list = [(~live).astype(jnp.uint8)]
    for data, valid in cols:
        vlane = orderable_key(data)
        if valid is not None:
            lanes.append((~valid).astype(jnp.uint8))
            vlane = jnp.where(valid, vlane, jnp.zeros_like(vlane))
        lanes.append(vlane)
    return lanes


def lane_runs_differ(sorted_lanes: Sequence[jax.Array]) -> jax.Array:
    """Row-differs-from-predecessor over SORTED canonical lanes (row 0 True);
    NaN == NaN on float (f64) lanes. The lane-space analog of
    :func:`rows_differ` — equivalent because canonical lanes encode exactly
    (value order, null flag) with nulls' value lanes zeroed."""
    cap = sorted_lanes[0].shape[0]
    diff = jnp.zeros((cap,), bool)
    for lane in sorted_lanes:
        prev = jnp.roll(lane, 1)
        diff = diff | lanes_differ(lane, prev)
    return diff.at[0].set(True)


#: 32-bit lanes of payload that one sort carries. The TPU's compiler takes
#: longer than linearly in a sort's operands (a group-by of 1 / 2 / 4 / 8
#: float64 sums compiled in 44 / 59 / 142 / 382 s for a described v5e,
#: PERF.md section 6, PR 28), so more lanes than this ride in batches.
RIDE_LANES = 8


def _carrier(dtype) -> np.dtype:
    """What a payload of ``dtype`` rides a batched sort as: itself when it
    is 64 bits wide, else uint32, so that one batch holds one dtype."""
    dtype = np.dtype(dtype)
    return dtype if dtype.itemsize == 8 else np.dtype(np.uint32)


def _to_carrier(x: jax.Array) -> jax.Array:
    size = np.dtype(x.dtype).itemsize
    if size == 8:
        return x
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint32)
    bits = jax.lax.bitcast_convert_type(x, np.dtype(f"uint{8 * size}"))
    return bits.astype(jnp.uint32)


def _from_carrier(x: jax.Array, dtype) -> jax.Array:
    size = np.dtype(dtype).itemsize
    if size == 8:
        return x
    if np.dtype(dtype) == np.bool_:
        return x != 0
    bits = x.astype(np.dtype(f"uint{8 * size}"))
    return jax.lax.bitcast_convert_type(bits, dtype)


def _ride_stacks(dtypes: Sequence) -> dict:
    """How payloads of ``dtypes`` ride in batches: ``{carrier dtype:
    (their positions, payloads a batch)}``, a batch :data:`RIDE_LANES`
    lanes wide at most."""
    stacks: dict = {}
    for i, dtype in enumerate(dtypes):
        stacks.setdefault(_carrier(dtype), []).append(i)
    return {
        carrier: (at, min(RIDE_LANES // (carrier.itemsize // 4), len(at)))
        for carrier, at in stacks.items()
    }


def ride_census(dtypes: Sequence) -> Tuple[int, int]:
    """``(lanes, batches)`` of a :func:`ride_sort` over payloads of
    ``dtypes``: the 32-bit lanes that ride, and the sorts that carry them
    (1 when they fit one sort; the sort of the keys alone is not one).
    What the host counts at dispatch (``sort.ride_lanes``,
    ``sort.ride_batches``), from the rule the ride itself follows."""
    lanes = sum(max(1, np.dtype(d).itemsize // 4) for d in dtypes)
    if lanes <= RIDE_LANES:
        return lanes, 1
    return lanes, sum(
        -(-len(at) // per) for at, per in _ride_stacks(dtypes).values()
    )


def ride_sort(sort_fn, payloads: Sequence[jax.Array]):
    """``sort_fn(payloads) -> (sorted keys, sorted payloads)`` for any
    number of payloads: up to :data:`RIDE_LANES` lanes ride the one sort,
    more ride in batches of that many, each batch the same sort of the
    same keys run again by ``jax.lax.map`` over the stacked lanes (one
    stack a 64-bit dtype, one for everything narrower, bit for bit as
    uint32). The program then holds one sort a stack whatever the number
    of columns, and every batch is permuted alike: the sort is stable, or
    its keys are distinct where the rows matter."""
    payloads = list(payloads)
    dtypes = [p.dtype for p in payloads]
    if ride_census(dtypes)[0] <= RIDE_LANES:
        return sort_fn(payloads)
    keys_out, _none = sort_fn([])
    out: list = [None] * len(payloads)
    for at, per in _ride_stacks(dtypes).values():
        arrs = [_to_carrier(payloads[i]) for i in at]
        arrs += [arrs[0]] * (-len(arrs) % per)
        stacked = jnp.stack(arrs).reshape((-1, per) + arrs[0].shape)
        rode = jax.lax.map(
            lambda batch: jnp.stack(
                sort_fn([batch[j] for j in range(per)])[1]
            ),
            stacked,
        ).reshape((-1,) + arrs[0].shape)
        for j, i in enumerate(at):
            out[i] = _from_carrier(rode[j], payloads[i].dtype)
    return keys_out, out


def sorted_runs(
    lanes_msb_first: Sequence[jax.Array], pay: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Stable row ordering + run boundaries over canonical lanes.

    Returns (spay [cap] original indices in sorted order, new_run [cap]).
    """
    new_run, (spay,), _lanes = sorted_runs_payload(lanes_msb_first, [pay])
    return spay, new_run


def sorted_runs_payload(
    lanes_msb_first: Sequence[jax.Array], payloads: Sequence[jax.Array]
) -> Tuple[jax.Array, list, list]:
    """Stable row ordering + run boundaries over canonical lanes, with
    ``payloads`` (any dtype; a 64-bit operand rides as its two 32-bit
    halves, which the TPU's X64 rewriter makes of it) riding the chained
    sort, in batches when there are many (:func:`ride_sort`). The single
    implementation of the reversed-lanes chained sort + run-detect idiom
    shared by factorize and the set algebra.

    Returns (new_run [cap], sorted payloads, sorted lanes msb first)."""
    lanes = list(lanes_msb_first)
    slanes, pays = ride_sort(
        lambda pays: lexsort_with_payload(list(reversed(lanes)), pays), payloads
    )
    slanes = list(reversed(slanes))
    return lane_runs_differ(slanes), pays, slanes


def fit_slots(x: jax.Array, cap_out: int) -> jax.Array:
    """The first ``cap_out`` slots of ``x``, zero-padded when it has fewer."""
    if x.shape[0] >= cap_out:
        return x[:cap_out]
    return jnp.pad(x, (0, cap_out - x.shape[0]))


def flatten_cols(cols: Sequence[KeyCol]) -> list:
    """The arrays of ``cols``, each column's data then its validity: what
    rides a sort as its payloads."""
    return [a for d, v in cols for a in ((d,) if v is None else (d, v))]


def unflatten_cols(cols: Sequence[KeyCol], flat: Sequence[jax.Array]) -> list:
    """:func:`flatten_cols` undone: ``flat`` in the column structure of
    ``cols`` (a sort permutes rows, so mask-free columns stay mask-free)."""
    it = iter(flat)
    return [(next(it), None if v is None else next(it)) for _d, v in cols]


def kv_sort(keys: jax.Array, pay: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Stable 1-key sort of ``pay`` by ``keys`` (the join probe's merged
    sort). Returns (skey, spay)."""
    with jax.named_scope(_stages.SORT_ENGINE):
        return jax.lax.sort((keys, pay), num_keys=1, is_stable=True)


def sentinel_compact(key: jax.Array, payloads: Sequence[jax.Array]) -> list:
    """Stable 1-key sort of ``payloads`` by ``key``: rows to keep carry an
    ordering key (e.g. their original index), dropped rows a BIG sentinel
    that pushes them past every kept row. The scatter-free compaction used
    by the join probe and every set-op emit."""
    with jax.named_scope(_stages.SORT_ENGINE):
        out = jax.lax.sort(tuple([key] + list(payloads)), num_keys=1, is_stable=True)
        return list(out[1:])


#: slots from which a pass of :func:`step_compact` moves the rows by two
#: bits of their distance and not by one. A pass of one bit reads a lane
#: twice and writes it once; a pass of two bits reads it four times, so two
#: bits cost five crossings of the lane against six, and selects among
#: three shifted views against one. Where the lanes stream from HBM the
#: crossings decide, under that the selects. On a v5e chip
#: (``benchmarks/compact_bench.py``; PERF.md section 6, PR 46), a uint32
#: word and a float64 / with two int64 beside them, one bit against two a
#: pass, ms: 2^20 slots 1.1 - 1.3 / 1.5 - 1.7; 2^22 2.2 - 2.8 / 7.4 - 7.0;
#: 2^24 29.3 - 26.5 / 59.0 - 49.4; 2^26 158.0 - 139.6 / 298.5 - 255.5 (the
#: sort they replace: 12.7 / 24.0 at 2^22, 653 for the wider lanes at 2^26).
STEP_TWO_BITS_MIN_SLOTS = 1 << 24


def step_passes(cap: int) -> list:
    """The passes of :func:`step_compact` over ``cap`` slots, lowest bits
    first: ``(first bit, bits)`` a pass. What the host counts at dispatch
    (``groupby.compact.passes``), from the rule the kernel itself
    follows."""
    bits = 2 if cap >= STEP_TWO_BITS_MIN_SLOTS else 1
    return [(at, bits) for at in range(0, (cap - 1).bit_length(), bits)]


def _shift_in(x: jax.Array, k: int) -> jax.Array:
    """``y[i] = x[i + k]``, zeros past the end, as ONE ``pad`` with a
    negative low edge: the TPU's compiler fuses that into the selects that
    read it, where the slice of :func:`_shift_up` is a copy of its own."""
    return jax.lax.pad(x, jnp.zeros((), x.dtype), [(-k, k, 0)])


def step_compact(
    keep: jax.Array, payloads: Sequence[jax.Array]
) -> Tuple[jax.Array, list]:
    """Move the rows whose ``keep`` flag is set to the front, in their
    order, without a sort: an order-preserving log-step compress (Hacker's
    Delight 7-4). A kept row lies as many slots from its target as there
    are dropped rows to its left; that distance never falls along the kept
    rows, so moving every row by the bits of its distance, lowest first,
    never lands two rows on one slot. A pass (:func:`step_passes`) is one
    round of selects over whole lanes, every payload in the dtype it has:
    no sort, no gather, no scatter, and no batches however many payloads.
    Returns (positions [cap]: the kept rows' original positions, then the
    sentinel ``cap``; compacted payloads, whose slots behind the kept rows
    hold whatever a pass left there)."""
    cap = keep.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    # the kept rows at or after me, by the blocked scan over one run
    (after,) = run_reduce(iota == cap - 1, [keep.astype(jnp.int32)], ["sum"])
    dist = jnp.where(keep, iota - (after[0] - after), 0)
    pos = jnp.where(keep, iota, jnp.int32(cap))
    lanes = list(payloads)
    for at, bits in step_passes(cap):
        hop = (dist >> at) & ((1 << bits) - 1)  # in units of 1 << at slots
        hops = [j for j in range(1, 1 << bits) if j << at < cap]
        lands = [_shift_in(hop == j, j << at) for j in hops]

        def moved(x, stays):
            for j, land in zip(hops, lands):
                stays = jnp.where(land, _shift_in(x, j << at), stays)
            return stays

        lanes = [moved(x, x) for x in lanes]
        # a slot its row left and none reached holds no row from here on
        pos = moved(pos, jnp.where(hop != 0, jnp.int32(cap), pos))
        dist = moved(dist, jnp.where(hop != 0, 0, dist))
    return pos, lanes


def lexsort_rows(
    key_cols: Sequence[KeyCol],
    n: jax.Array,
    cap: int,
    ascending: Optional[Sequence[bool]] = None,
    nulls_last: bool = True,
) -> jax.Array:
    """Stable argsort of rows by multiple key columns.

    Returns a permutation [cap] with live rows ordered first, then null-key
    rows (per-column null ordering), then padding: the row positions, as
    the one payload of :func:`lexsort_rows_payload`.
    """
    iota = jnp.arange(cap, dtype=jnp.int32)
    return lexsort_rows_payload(
        key_cols, n, cap, [iota], ascending, nulls_last
    )[0]


def lexsort_rows_payload(
    key_cols: Sequence[KeyCol],
    n: jax.Array,
    cap: int,
    payloads: Sequence[jax.Array],
    ascending: Optional[Sequence[bool]] = None,
    nulls_last: bool = True,
    prefix_lane: Optional[jax.Array] = None,
    fuse: Optional["FusePlan"] = None,
) -> list:
    """``payloads`` in the stable order of the rows by multiple key columns
    (live rows first, then null-key rows, then padding): they ride the sort
    passes, and no permutation is carried beside them.

    Every fixed-width payload rides, a 64-bit one as its two 32-bit halves
    (what the TPU's X64 rewriter makes of a sort operand), bit for bit. On
    a v5e at 4,194,304 rows a 64-bit column adds 5-6 ms to a pass (the
    fused word with an int64 and a float64 sorts in 17.0 ms, with an iota
    alone in 5.8), where its gather by a carried order cost 66 ms (a
    float64) or 18 (an int64, packed), and the order a lane of its own
    (PERF.md section 6, PR 28 and PR 30): riding wins up to about five
    unfused passes for an int64 and twelve for a float64, which takes four
    float64 sort keys and more; one rule, no dispatch. Past
    :data:`RIDE_LANES` lanes the payloads ride in batches
    (:func:`ride_sort`) of the whole chained sort, as in
    :func:`sorted_runs_payload`.

    ``prefix_lane``: optional lane sorted just below the padding class (more
    significant than every key) — the sorted-run-reuse hook: a caller whose
    rows are ALREADY ordered by a key prefix passes the prefix's run ids
    (:func:`prefix_run_lane`) here and supplies only the suffix keys,
    replacing one chained pass per elided prefix lane.

    ``fuse``: a stats-driven :class:`FusePlan` over exactly
    (pad_bits=2, prefix, key_cols in order) — the whole lane stack
    bit-packs into ``fuse.n_words`` physical sort words, so an N-lane
    chained lexsort runs as n_words passes. The resulting order is
    identical on live rows (null rows still order by their masked payload
    — the stats measured those values too); only the don't-care padding
    order may differ.
    """
    if ascending is None:
        ascending = [True] * len(key_cols)
    if fuse is not None:
        words = fused_key_words(
            fuse, list(key_cols),
            jnp.arange(cap, dtype=jnp.int32) < n,
            nulls_last=nulls_last, prefix_lane=prefix_lane,
        )
        lanes = list(reversed(words))  # least-significant first
    else:
        lanes = []  # least-significant first (lexsort convention)
        with jax.named_scope(_stages.SORT_KEYS):
            pad = row_class(n, cap, None)
            for (data, valid), asc in zip(
                reversed(list(key_cols)), list(reversed(list(ascending)))
            ):
                lanes.append(_norm_key(data, asc))
                if valid is not None:
                    null_lane = (~valid).astype(jnp.int8)
                    if not nulls_last:
                        null_lane = -null_lane
                    lanes.append(null_lane)
        if prefix_lane is not None:
            lanes.append(prefix_lane)
        lanes.append(pad)  # most significant: padding always last
    with jax.named_scope(_stages.SORT_PERM):
        return ride_sort(
            lambda pays: lexsort_with_payload(lanes, pays, keep_lanes=False),
            payloads,
        )[1]


def prefix_run_lane(
    prefix_cols: Sequence[KeyCol], n: jax.Array, cap: int
) -> jax.Array:
    """Run-id lane over rows ALREADY ordered by ``prefix_cols``.

    Equal-prefix rows share an id; ids are non-decreasing over the live
    prefix (so sorting by this single int32 lane preserves the existing
    prefix order exactly), and padding rows take an id past every live run.
    Null == null per :func:`rows_differ` — valid for canonically-ordered
    prefixes, where null-key runs are contiguous.
    """
    idx = jnp.arange(cap, dtype=jnp.int32)
    live = idx < n
    boundary = rows_differ(prefix_cols, cap) & live
    ids = jnp.cumsum(boundary.astype(jnp.int32))
    return jnp.where(live, ids, jnp.int32(cap + 1))


def rows_differ(
    sorted_cols: Sequence[KeyCol], cap: int
) -> jax.Array:
    """Bool [cap]: row i differs from row i-1 on any key column (row 0 True).

    Null == null for grouping purposes (pandas merge/groupby semantics; the
    reference's row comparators likewise compare raw values,
    arrow/arrow_comparator.hpp:28-121).
    """
    diff = jnp.zeros((cap,), dtype=bool).at[0].set(True)
    for data, valid in sorted_cols:
        lane = orderable_key(data)
        prev = jnp.roll(lane, 1)
        d = lanes_differ(lane, prev)
        if valid is not None:
            vprev = jnp.roll(valid, 1)
            # null vs value differs; null vs null equal (value lane ignored)
            d = jnp.where(valid & vprev, d, valid != vprev)
        diff = diff | d
    return diff.at[0].set(True)
