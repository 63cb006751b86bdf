"""Sort engine selection, and the width-adaptive LSD radix engine.

Every ordering in this codebase bottoms out in chained stable 1-key
``jax.lax.sort`` passes (ops/sort.py), the chip's native sort, which the
code calls ``"bitonic"``. That is the default: on a v5e a pass of the
radix engine below pays per element for its gathers and its scatter
(about 17 ns a row a pass; PERF.md, PR 25), so seven passes over
4,194,304 rows cost 914 ms where the native sort of the same fused word
takes 5.6 ms (PERF.md, PR 26). The radix engine stays selectable by
force (and by an applied ``Decisions.sort_impl``, which no proposal
sets to a radix tier); nothing selects it by default.

The radix engine: a comparison sort cannot use the one thing the
lane-packing stats engine (ops/stats.py, PR 5) measures: the LIVE BIT
WIDTH of every sort lane. A d-bit key radix-sorts in ceil(d/r) stable
histogram -> exclusive-scan -> scatter passes (r-bit digits), and
per-pass STABILITY makes the multi-lane lexsort just a pass sequence —
the payload-ride machinery (split_ride_cols / merge_ride_cols) is
unchanged, payloads are gathered ONCE by the final permutation instead
of riding every sweep. The pass COUNT is small; the time of a pass on
the chip is not.

The XLA tier (:func:`radix_pass`) carries a permutation, not the data:
per pass it gathers the keyed lane through the current perm, builds the
R-bucket one-hot rank matrix, prefix-scans it for stable within-bucket
ranks + the bucket histogram, and scatters the perm to exact destination
slots (a collision-free scatter — ``pos`` is a permutation by
construction). ``RADIX_BITS = 4`` bounds the one-hot working set to
16 x cap i32 — at 4M rows that is 256 MB of streamed (not resident)
traffic per pass, and a 32-bit lane costs 8 passes where the bitonic
network at that size costs ~230 sweeps.

The Pallas tier (ops/pallas_radix.py) moves the rank matrix into VMEM
tiles (R = 256: one pass per byte) and is selected only by force/tuning
(``radix_pallas``); it declines 64-bit lanes and non-tile-divisible
capacities by falling back to the XLA pass, per-pass — stability makes
mixed-tier pass chains exact.

Implementation selection (every resolver step is shape-static, so the
resolved impl is sound inside kernel cache keys):

1. ``CYLON_TPU_NO_RADIX=1`` — kill switch, everything bitonic. Its
   ``disabled()`` context manager IS the differential oracle the tests
   and the fuzz radix profile diff a FORCED radix run against.
2. ``CYLON_TPU_SORT_IMPL`` in {bitonic, radix, radix_pallas} forces.
3. The autopilot's per-shape ``Decisions.sort_impl`` (plan/feedback.py),
   visible through the applying() contextvar during plan execution.
4. Default ``auto``: ``bitonic``, the native sort, for every lane plan.
   (Under a radix tier a stack with a float lane still declines to it
   at trace time: the f64 total-order lane has no integer digit
   decomposition.)

``impl_tag()`` is the cache-key carrier: every sort-family kernel key
appends it, so a mid-process flip of either knob (or a tuned decision
flip) recompiles exactly once and never aliases a stale program.
``gate_state()`` is the plan-fingerprint component (plan/lazy.py).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import stages as _stages
from ..utils import envgate as _eg
from ..utils.envgate import env_gate

#: digit width of the XLA-tier pass: the one-hot rank matrix is
#: ``2**RADIX_BITS x cap`` i32, so r=4 keeps the per-pass streamed
#: working set at 64 B/row while a 32-bit lane still collapses from
#: ~log^2(n)/2 bitonic sweeps to 8 passes
RADIX_BITS = 4

#: digit width of the Pallas tier: the rank matrix lives in VMEM tiles,
#: so a full byte per pass is free — 4 passes per 32-bit lane
PALLAS_RADIX_BITS = 8

IMPLS = ("bitonic", "radix", "radix_pallas")

# kill switch + differential oracle (CYLON_TPU_NO_RADIX=1 -> bitonic
# everywhere, over any force; tests diff a forced radix run's exact
# emitted order against it)
enabled, disabled = env_gate(
    "CYLON_TPU_NO_RADIX",
    keyed_via="ops.radix.impl_tag appended to every sort-family kernel "
    "cache key; plan fingerprints carry ops.radix.gate_state",
    note="=1 disables the radix sort engine over any force or tuned "
    "decision (bitonic, the default, everywhere) — the differential "
    "oracle for exact emitted-order tests",
)


def resolved_impl() -> str:
    """The selected sort impl for the CURRENT trace: kill switch, then
    the forcing env, then the autopilot's applied per-shape decision,
    then the ``auto`` default: ``"bitonic"``, the chip's native sort.
    Host env/contextvar reads only — shape-static, cache-key safe."""
    if not enabled():
        return "bitonic"
    forced = _eg.SORT_IMPL.get()
    if forced and forced != "auto":
        return forced if forced in IMPLS else "bitonic"
    from ..plan import feedback as _fb

    tuned = _fb.tuned_sort_impl()
    if tuned in IMPLS:
        return tuned
    return "bitonic"


def impl_tag() -> tuple:
    """Cache-key component every sort-family kernel key appends: the
    resolved impl (which transitively reads CYLON_TPU_NO_RADIX,
    CYLON_TPU_SORT_IMPL and the tuned decision) plus the digit widths,
    so an impl flip or a digit-width change recompiles instead of
    aliasing. The analyzer treats a call to this function inside a key
    expression as the keyed carrier of both knobs."""
    return ("sort_impl", resolved_impl(), RADIX_BITS, PALLAS_RADIX_BITS)


def kernel_kwargs() -> dict:
    """Extra engine.get_kernel kwargs for sort-family kernels: a
    radix_pallas sort embeds pallas_calls, which have no shard_map
    replication rule — same check_vma=False discipline as the windowed
    emit (ops/join.emit_impl_kwargs). get_kernel keys include the
    wrapping flags, so this cannot alias the checked program."""
    if resolved_impl() == "radix_pallas":
        return {"check_vma": False}
    return {}


def gate_state() -> tuple:
    """Plan-fingerprint component (plan/lazy.gated_fingerprint): the
    kill switch + the forcing env. The tuned per-shape decision rides
    the fingerprint's feedback component, not this one — the store keys
    profiles by the base fingerprint, which must NOT move when a
    decision flips."""
    return (enabled(), _eg.SORT_IMPL.get())


# ----------------------------------------------------------------------
# lane planning: orderable lane -> (unsigned digit lane, bit span)
# ----------------------------------------------------------------------
#: a lane hint narrows the digit span below the dtype-default width:
#:   ("span", lo, hi)   — values are unsigned with significant bits in
#:                        [lo, hi) (bits below lo are constant across
#:                        rows, e.g. fused-word tie padding)
#:   ("bias", b, bits)  — small signed lane: (lane + b) fits ``bits``
#:                        unsigned bits (null flags, row classes)
Hint = Tuple[str, int, int]

_SPAN = "span"
_BIAS = "bias"


def span_hint(lo: int, hi: int) -> Hint:
    return (_SPAN, int(lo), int(hi))


def bias_hint(bias: int, bits: int) -> Hint:
    return (_BIAS, int(bias), int(bits))


def bound_hint(upper: int) -> Hint:
    """Span hint for a non-negative integer lane with values <= upper."""
    return (_SPAN, 0, max(int(upper).bit_length(), 1))


def _digit_lane(
    lane: jax.Array, hint: Optional[Hint]
) -> Optional[Tuple[jax.Array, int, int]]:
    """(unsigned lane, lo_bit, hi_bit) for one sort lane, or None when
    the lane has no integer digit decomposition (float lanes). Every
    transform here is strictly order-preserving, so radix order over the
    digit lane == stable-sort order over the original lane."""
    dt = lane.dtype
    if hint is not None and hint[0] == _BIAS:
        _, bias, bits = hint
        enc = (lane.astype(jnp.int32) + jnp.int32(bias)).astype(jnp.uint32)
        return enc, 0, int(bits)
    if dt == jnp.bool_:
        return lane.astype(jnp.uint32), 0, 1
    if jnp.issubdtype(dt, jnp.floating):
        return None  # f64 total-order lanes stay bitonic (sort.py)
    if hint is not None and hint[0] == _SPAN:
        _, lo, hi = hint
        if dt in (jnp.uint32, jnp.uint64):
            return lane, int(lo), int(hi)
        # span hints assert non-negative values: plain widening is
        # order-preserving and keeps the declared bit positions
        return lane.astype(jnp.uint32), int(lo), int(hi)
    size = np.dtype(dt).itemsize
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        if size <= 4:
            return lane.astype(jnp.uint32), 0, 8 * size
        return lane, 0, 64
    # signed: shift into unsigned order. Narrow lanes bias (cheap, no
    # bitcast); int32 flips the sign bit; int64 follows orderable_key's
    # wrapping-convert discipline (TPU cannot bitcast x64)
    if size == 1:
        return (lane.astype(jnp.int32) + jnp.int32(128)).astype(jnp.uint32), 0, 8
    if size == 2:
        return (lane.astype(jnp.int32) + jnp.int32(32768)).astype(jnp.uint32), 0, 16
    if size == 4:
        enc = jax.lax.bitcast_convert_type(lane, jnp.uint32) ^ np.uint32(
            0x80000000
        )
        return enc, 0, 32
    return lane.astype(jnp.uint64) ^ (jnp.uint64(1) << jnp.uint64(63)), 0, 64


def plan_lanes(
    lanes: Sequence[jax.Array], hints: Optional[Sequence[Optional[Hint]]] = None
) -> Optional[List[Tuple[jax.Array, int, int]]]:
    """Digit-lane plan for a least-significant-first lane stack, or None
    when ANY lane is radix-ineligible (the whole sort then declines to
    bitonic — mixing radix and bitonic passes would be exact too, but a
    float lane is the only decliner and it dominates the cost anyway)."""
    out: List[Tuple[jax.Array, int, int]] = []
    for i, lane in enumerate(lanes):
        h = hints[i] if hints is not None and i < len(hints) else None
        pl = _digit_lane(lane, h)
        if pl is None:
            return None
        out.append(pl)
    return out


def fuse_word_hints(fuse) -> List[Optional[Hint]]:
    """Least-significant-first span hints for a FusePlan's fused sort
    words: the layout packs unused bits at the BOTTOM of the last
    (least significant) word as constant-zero tie padding, so those
    digit positions sort as no-op passes and are skipped outright."""
    from .stats import layout_words

    bits_list = [b for _k, _p, b, _a in fuse.fields]
    layout = layout_words(bits_list, fuse.allow64)
    widths = [w for w, _ in layout]
    unused = sum(widths) - sum(bits_list)
    hints: List[Optional[Hint]] = [
        span_hint(0, w) for w in reversed(widths)
    ]
    if hints:
        lo, (_, _, hi) = unused, hints[0]
        hints[0] = span_hint(lo, hi)
    return hints


# ----------------------------------------------------------------------
# the pass core
# ----------------------------------------------------------------------
def radix_pass(
    enc: jax.Array, perm: jax.Array, shift: int, bits: int
) -> jax.Array:
    """One stable counting-sort pass over digit ``[shift, shift+bits)``
    of ``enc``, carrying the permutation: returns the perm reordered so
    ``enc[perm]`` is stably sorted by the digit.

    rank  = within-bucket 1-based stable rank (one-hot inclusive scan)
    hist  = bucket sizes (the scan's last column — no second reduction)
    offs  = exclusive bucket offsets
    pos   = offs[digit] + rank - 1   (an exact permutation: scatter is
                                      collision-free by construction)

    Wrapped in a NAMED nested jit (:data:`_PASS`) so the roofline walker
    prices a pass as streamed lane+perm bytes instead of walking the
    one-hot internals (benchmarks/roofline.py special-cases pjit eqns
    named ``radix_pass``, exactly like pallas_call).
    """
    dt = enc.dtype.type
    g = enc[perm]
    d = ((g >> dt(shift)) & dt((1 << bits) - 1)).astype(jnp.int32)
    r = 1 << bits
    eq = (
        d[None, :] == jnp.arange(r, dtype=jnp.int32)[:, None]
    ).astype(jnp.int32)
    csum = jnp.cumsum(eq, axis=1, dtype=jnp.int32)
    rank = jnp.take_along_axis(csum, d[None, :], axis=0)[0]
    hist = csum[:, -1]
    offs = jnp.cumsum(hist, dtype=jnp.int32) - hist
    pos = offs[d] + rank - 1
    return jnp.zeros_like(perm).at[pos].set(perm, unique_indices=True)


#: the named pjit wrapper the roofline walker keys on; static digit
#: params so every (shift, bits) instance shares the ``radix_pass`` name
_PASS = jax.jit(radix_pass, static_argnums=(2, 3))


def passes_for_spans(
    spans: Sequence[Tuple[int, int]], impl: str = "radix"
) -> int:
    """Total radix pass count for a list of (lo, hi) lane bit spans."""
    r = PALLAS_RADIX_BITS if impl == "radix_pallas" else RADIX_BITS
    return sum((hi - lo + r - 1) // r for lo, hi in spans)


def bitonic_passes(cap: int, n_lanes: int) -> int:
    """Modeled bitonic sweep count of the chained lexsort: each of the
    ``n_lanes`` stable 1-key sorts is a ~L(L+1)/2-sweep network at
    L = ceil(log2 cap). The cost-model twin of the radix pass count
    (benchmarks/roofline.py prices sorts with the same formula)."""
    lg = max(int(np.ceil(np.log2(max(int(cap), 2)))), 1)
    return n_lanes * (lg * (lg + 1)) // 2


def sort_pass_census(
    key_cols, cap: int, prefix: bool, fuse=None, impl: str = "radix"
) -> Tuple[int, int]:
    """Host-side ``(radix_passes, bitonic_sweeps)`` estimate for a
    ``lexsort_rows_payload`` lane stack — the per-observation pass
    evidence the autopilot's ``sort_impl`` proposal judges on
    (obs/store.note_sort) and the sort-smoke census rows. Mirrors the
    trace-time lane construction exactly: fused plans count their word
    spans (bottom tie padding skipped), plain stacks one span per
    value/null/prefix/pad lane. ``radix_passes == 0`` means the stack is
    radix-INELIGIBLE (a float lane) — those sorts run bitonic under
    every impl setting."""
    if fuse is not None:
        spans = [(lo, hi) for _t, lo, hi in fuse_word_hints(fuse)]
        return (
            passes_for_spans(spans, impl),
            bitonic_passes(cap, fuse.n_words),
        )
    spans: List[Tuple[int, int]] = [(0, 2)]  # padding row class
    eligible = True
    if prefix:
        spans.append((0, max((cap + 1).bit_length(), 1)))
    for data, valid in key_cols:
        dt = np.dtype(data.dtype)
        if valid is not None:
            spans.append((0, 2))  # null flag lane
        if dt == np.bool_:
            spans.append((0, 1))
        elif dt.kind in "iu":
            spans.append((0, 8 * dt.itemsize))
        else:
            spans.append((0, 8 * dt.itemsize))
            eligible = False  # float lane: whole sort declines
    bit = bitonic_passes(cap, len(spans))
    return (passes_for_spans(spans, impl) if eligible else 0, bit)


def lexsort_perm(
    lanes: Sequence[jax.Array],
    cap: int,
    hints: Optional[Sequence[Optional[Hint]]] = None,
    impl: Optional[str] = None,
) -> Optional[jax.Array]:
    """Stable lexsort permutation over ``lanes`` (least-significant
    FIRST — the ops/sort.py convention) via LSD radix passes, or None
    when the resolved impl is bitonic or any lane is ineligible (caller
    falls back to the chained ``jax.lax.sort`` path).

    The stable-lexsort permutation of a lane stack is UNIQUE, so the
    radix result is bit-identical to the bitonic path's — including the
    padding tail, whose all-equal key rows keep their relative order
    under stability in both impls. That exactness is what the
    ``CYLON_TPU_NO_RADIX`` differential oracle pins.
    """
    if impl is None:
        impl = resolved_impl()
    if impl == "bitonic":
        return None
    with jax.named_scope(_stages.SORT_ENGINE):
        planned = plan_lanes(lanes, hints)
        if planned is None:
            from ..obs import metrics as _metrics

            _metrics.rollup_count("radix.declined")
            return None
        perm = jnp.arange(cap, dtype=jnp.int32)
        r = PALLAS_RADIX_BITS if impl == "radix_pallas" else RADIX_BITS
        n_passes = 0
        for enc, lo, hi in planned:
            shift = lo
            while shift < hi:
                bits = min(r, hi - shift)
                perm = _dispatch_pass(enc, perm, shift, bits, impl)
                n_passes += 1
                shift += bits
        from ..obs import metrics as _metrics

        # trace-time census (one bump per compile, not per execution)
        _metrics.rollup_count("radix.trace_passes", rows=n_passes)
        return perm


def _dispatch_pass(
    enc: jax.Array, perm: jax.Array, shift: int, bits: int, impl: str
) -> jax.Array:
    if impl == "radix_pallas":
        from . import pallas_radix as _pr

        if _pr.pass_supported(enc, perm.shape[0]):
            # interpret only in programs for CPU devices — the mesh being
            # traced for decides (engine.mesh_platform), same rule as the
            # windowed emit. On a TPU mesh the forced tier compiles or
            # raises Mosaic's error; it never declines to XLA there
            from .. import engine as _engine

            return _pr.radix_pass_pallas(
                enc, perm, shift, bits,
                interpret=_engine.mesh_platform() == "cpu",
            )
        # 64-bit lanes / non-tile-divisible caps: per-pass XLA fallback
        # (stability makes mixed-tier chains exact)
    return _PASS(enc, perm, shift, bits)


def argsort_perm(
    lane: jax.Array, hint: Optional[Hint] = None,
    impl: Optional[str] = None,
) -> Optional[jax.Array]:
    """Radix replacement for ``jnp.argsort(lane, stable=True)`` — the
    single-lane case (join r_order, shuffle partition grouping)."""
    return lexsort_perm([lane], lane.shape[0], [hint], impl=impl)


def kv_sort(
    keys: jax.Array,
    pay: jax.Array,
    hint: Optional[Hint] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Stable 1-key kv-sort (the join probe's merged sort): the native
    ``jax.lax.sort``, or radix passes where a radix tier is selected and
    the lane eligible. Returns (skey, spay)."""
    with jax.named_scope(_stages.SORT_ENGINE):
        perm = argsort_perm(keys, hint)
        if perm is not None:
            return keys[perm], pay[perm]
        return jax.lax.sort((keys, pay), num_keys=1, is_stable=True)
