"""Semi-join key sketches: blocked-Bloom + min/max range filters that prune
shuffle payloads BEFORE the all-to-all.

Reference analog: none in the reference C++ — Cylon ships 100% of both
sides' rows through its MPI all-to-all and lets the local join drop the
non-matches. The follow-up paper (arXiv:2212.13732, PAPERS.md) identifies
exactly that inter-worker volume as the scaling bottleneck; Exoshuffle
(arXiv:2203.05072) treats shuffle bytes as the first-order cost. Semi-join
filtering via compact broadcast sketches is the standard fix in
shuffle-based engines: each side summarizes its join keys in a few KB, the
summaries are exchanged once, and every row provably absent from the OTHER
side's summary is dropped before it is packed — false positives only ship
extra rows, never change the answer.

TPU-native design
-----------------
* The Bloom filter is BLOCKED at uint32-lane granularity: a key hashes to
  ONE word of the packed [W] uint32 sketch and to ``PROBE_BITS`` bit
  positions inside that word, so the probe is a single lane-aligned gather
  + bitwise AND per row — no scatters, no multi-word walks on the probe
  path (the build side scatters once into a bit array, off the hot path).
* Word index and bit pattern reuse the vectorized murmur words of
  ops/hash.py under two fixed seeds, so the whole probe is VPU-elementwise
  around the one gather.
* The cross-shard OR-combine is ONE small collective: both sides' local
  sketches ride a single ``all_gather`` (XLA exposes no bitwise-OR
  cross-replica reduction; the gather + local OR fold is the one-collective
  equivalent of a psum-OR, and the per-shard injected bytes — what the
  ``CYLON_TPU_SKETCH_BITS`` knob bounds — are the packed sketch, ~256 KiB
  at the default cap). A per-side key min/max range word rides the same
  collective (fold = max/min instead of OR) and prunes by key range even
  when the Bloom saturates — sound for any dtype whose
  :func:`cylon_tpu.ops.sort.orderable_key` lane is monotone uint32
  (dictionary CODES qualify: code order == value order).
* Null semantics (the audit): this engine's joins AND set ops both treat
  null == null as a match — ``Table.join`` follows pandas ``merge`` (NaN
  keys join each other; the fuzz campaign's pandas oracle pins it) and the
  set algebra's canonical row lanes zero the payload under null
  (ops/sort.canonical_row_lanes). A sketch that dropped null-key rows
  ("they can't match") would therefore DELETE real output rows. So nulls
  are sketched AS VALUES: the validity mask is folded into the probed
  identity — a null key hashes as hash_columns' null-as-zero contribution
  and range-encodes as the nulls-last sentinel on BOTH sides — which keeps
  null rows pruneable exactly when the other side has no null (and no
  hash-colliding) key, and never otherwise.

``CYLON_TPU_NO_SEMI_FILTER=1`` disables every consumer (differential
testing); the adaptive gate in ``table._shuffle_many`` additionally skips
applying a filter whose measured selectivity says it will not pay.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import stages as _stages
from ..utils.envgate import env_gate
from .hash import hash_columns
from .sort import KeyCol, orderable_key

# independent hash streams for (word index, in-word bit pattern); distinct
# from the shuffle's partition hash (seed 0) so sketch bits and routing bits
# stay uncorrelated
_SEED_WORD = 0x5EEDB10C
_SEED_BITS = 0x5EEDB175

# bits set per key inside its block word (k of the blocked-Bloom formula)
PROBE_BITS = 4
# sizing: target bits per build-side key before the CYLON_TPU_SKETCH_BITS
# cap. The sketch's wire cost is GLOBAL-size per shard (every shard
# injects its whole local sketch into the all_gather) while the payload it
# shrinks is per-shard (n/P rows), so the economic sweet spot is small: at
# 4 bits/key a 32-bit block carries ~8 keys -> ~20/32 bits set ->
# ~16% false-positive rate — i.e. ~84% of the ideal pruning for half the
# sketch bytes of an 8-bits/key filter (FPs only ship extra rows; the
# range words prune disjoint key ranges exactly regardless).
BITS_PER_KEY = 4
# trailing uint32 words appended to the W bloom words: [max_enc, min_enc]
RANGE_WORDS = 2

_NULL_ENC = np.uint32(0xFFFFFFFF)  # nulls-last orderable sentinel (set ops)


# the CYLON_TPU_NO_SEMI_FILTER=1 kill switch: enabled() turns every
# sketch consumer off; disabled() is the differential-oracle toggle
# (shared machinery with ordering.py's gate — utils/envgate.py)
enabled, disabled = env_gate(
    "CYLON_TPU_NO_SEMI_FILTER",
    keyed_via="the shuffle key carries the semi statics (probe_row, "
    "use_range) only when a sketch is attached; the plan fingerprint "
    "includes the gate (plan/lazy.py)",
)


def join_filter_sides(how: str) -> Optional[str]:
    """Which shuffle sides may be semi-filtered for a join type, in
    ``table._shuffle_pair`` terms ('a' = the left table is filtered against
    the right sketch, 'b' = the right table against the left sketch):

    - inner: BOTH sides (a row without a partner emits nothing);
    - left:  right side only (every left row emits, matched or not);
    - right: left side only (mirror);
    - full outer: nothing — every row of both sides emits, so
      false-positive-only pruning has nothing it may remove;
    - semi: left side only (a left row without a partner is dropped
      anyway; the right side already ships its keys alone);
    - anti: nothing — a left row WITHOUT a partner is what emits.
    """
    return {
        "inner": "both", "left": "b", "right": "a",
        "semi": "a", "left_semi": "a",
    }.get(how.replace("-", "_").lower())


def setop_filter_sides(op: str) -> Optional[str]:
    """Semi-filter sides for the distributed set ops: intersect is a
    two-sided semi join (a row absent from the other side emits nothing);
    subtract keeps UNMATCHED left rows, so only the right side (whose
    unmatched rows never emit) may be pruned; union emits everything."""
    return {"intersect": "both", "subtract": "b"}.get(op)


def sketch_bits_for(build_rows: int, max_bits: int) -> int:
    """Bloom size (bits, ALWAYS a power of two) for a build side of
    ``build_rows`` keys: BITS_PER_KEY per key (default start 4096),
    capped by ``max_bits`` rounded DOWN to a power of two — the block
    probe masks with ``h1 & (W-1)`` and the build packs ``bits/32``
    words, so a raw non-pow2 cap (CYLON_TPU_SKETCH_BITS is user input)
    must never leak through, and a cap below the default start is
    honored (absolute floor 32, one packed word). Oversizing only wastes
    collective bytes; undersizing only raises the FP rate (missed
    pruning) — never correctness."""
    cap = 32
    while 2 * cap <= int(max_bits):
        cap *= 2
    want = BITS_PER_KEY * max(int(build_rows), 1)
    bits = min(4096, cap)
    while bits < want and bits < cap:
        bits *= 2
    return min(bits, cap)


def sketch_len(bits: int) -> int:
    """uint32 words of one packed sketch vector: bloom words + range tail."""
    return bits // 32 + RANGE_WORDS


def hash_class(np_dtype) -> Optional[str]:
    """Equality-consistent hashing family of a physical key dtype: two
    columns whose classes differ may compare equal in the local op (via
    numeric promotion) while hashing differently — the host gate disables
    the filter for such pairs (ints of any width share a class because
    ops/hash._to_words hashing is width-independent; so do floats)."""
    dt = np.dtype(np_dtype)
    if dt == np.bool_ or np.issubdtype(dt, np.integer):
        return "int"
    if np.issubdtype(dt, np.floating):
        return "float"
    return None


def range_class(np_dtype) -> Optional[str]:
    """Monotone-uint32 encoding family used by the range words, or None
    when the dtype has no sound 32-bit monotone lane (float64's orderable
    lane is a float). Both sides of a pair must share the EXACT class:
    equal values of different widths/signedness encode differently.

    The classifier is SHARED with the lane-packing stats facility
    (:func:`cylon_tpu.ops.stats.enc_class`) so range gating and sort-word
    fusion / wire narrowing can never disagree on an encoding family; the
    64-bit families get a distinct ``...hi`` name here because the range
    lane coarsens them to the orderable hi word."""
    from .stats import enc_class

    cls = enc_class(np_dtype)
    if cls in ("i64", "u64"):
        return cls + "hi"
    return cls


def _range_enc(key: KeyCol) -> jax.Array:
    """Monotone uint32 encoding of the FIRST key column (range_class must be
    non-None). The value encoding is the shared orderable family
    (ops/stats.encode_enc == ops/sort.orderable_key); 64-bit integers
    coarsen to the orderable hi word — a non-strict monotone map, so range
    pruning stays sound. Nulls encode as the nulls-last sentinel on BOTH
    sides (null == null — module doc)."""
    data, valid = key
    enc = orderable_key(data)
    if enc.dtype == jnp.uint64:
        enc = (enc >> jnp.uint64(32)).astype(jnp.uint32)
    enc = enc.astype(jnp.uint32)
    if valid is not None:
        enc = jnp.where(valid, enc, _NULL_ENC)
    return enc


def _word_and_bits(cols: Sequence[KeyCol], n_words: int):
    """(block word index [cap] int32, PROBE_BITS in-word bit positions
    [[cap] uint32, ...]) per row. ``n_words`` must be a power of two."""
    h1 = hash_columns(cols, seed=_SEED_WORD)
    h2 = hash_columns(cols, seed=_SEED_BITS)
    word = (h1 & np.uint32(n_words - 1)).astype(jnp.int32)
    positions = [
        (h2 >> np.uint32(5 * i)) & np.uint32(31) for i in range(PROBE_BITS)
    ]
    return word, positions


def _pattern(positions) -> jax.Array:
    pattern = jnp.zeros_like(positions[0])
    for pos in positions:
        pattern = pattern | (jnp.uint32(1) << pos)
    return pattern


def build_local(
    cols: Sequence[KeyCol],
    n: jax.Array,
    bits: int,
    use_range: bool,
) -> jax.Array:
    """One shard's packed local sketch [sketch_len(bits)] uint32: the
    blocked-Bloom words of every live key (nulls included, as values —
    module doc), then [max_enc, min_enc] of the range lane. Per-shard code
    (runs under shard_map); combine across shards with
    :func:`combine_pair`."""
    with jax.named_scope(_stages.SEMI_SKETCH):
        cap = cols[0][0].shape[0]
        W = bits // 32
        live = jnp.arange(cap, dtype=jnp.int32) < n
        ok = live
        word, positions = _word_and_bits(cols, W)
        # build through a bit ARRAY (scatter-set of PROBE_BITS indices per row,
        # duplicates harmless), then pack to words — the scatter is once per
        # shuffle on the build side; the probe path stays scatter-free
        base = word * jnp.int32(32)
        idxs = [
            jnp.where(ok, base + pos.astype(jnp.int32), jnp.int32(bits))
            for pos in positions
        ]
        flat = jnp.concatenate(idxs)
        bitarr = jnp.zeros((bits,), jnp.bool_).at[flat].set(True, mode="drop")
        words = jnp.sum(
            bitarr.reshape(W, 32).astype(jnp.uint32)
            << jnp.arange(32, dtype=jnp.uint32)[None, :],
            axis=1,
            dtype=jnp.uint32,
        )
        if use_range:
            enc = _range_enc(cols[0])
            max_enc = jnp.max(jnp.where(ok, enc, jnp.uint32(0)))
            min_enc = jnp.min(jnp.where(ok, enc, _NULL_ENC))
        else:
            # disabled range: the widest possible window passes every probe
            max_enc = _NULL_ENC
            min_enc = jnp.uint32(0)
        # an EMPTY build shard contributes max=0 < min=0xFFFFFFFF — after the
        # max/min fold an empty build SIDE keeps that inverted window and the
        # range check prunes everything (correct: nothing can match). An
        # all-NULL shard is different: its rows are live and encode as the
        # 0xFFFFFFFF sentinel, so it contributes max=min=0xFFFFFFFF and
        # probe-side nulls still pass (null == null must survive)
        return jnp.concatenate([words, max_enc[None], min_enc[None]])


def combine_pair(local: jax.Array, axis_name: str, world: int) -> jax.Array:
    """Cross-shard combine of stacked local sketches [S, L] -> global
    [S, L]: ONE ``all_gather`` moves every shard's packed words (the single
    small sketch collective — both sides of a pair ride it together), then
    the fold is local: bitwise OR over the bloom words, max/min over the
    range tail. The unrolled fold is over the STATIC world size."""
    with jax.named_scope(_stages.SEMI_SKETCH):
        g = jax.lax.all_gather(local, axis_name)  # [P, S, L]
        L = local.shape[-1]
        W = L - RANGE_WORDS
        bloom = g[0, :, :W]
        for p in range(1, world):
            bloom = bloom | g[p, :, :W]
        max_enc = jnp.max(g[:, :, W], axis=0)
        min_enc = jnp.min(g[:, :, W + 1], axis=0)
        return jnp.concatenate([bloom, max_enc[:, None], min_enc[:, None]], axis=1)


def probe(
    cols: Sequence[KeyCol],
    sketch: jax.Array,
    use_range: bool,
) -> jax.Array:
    """Row survival mask [cap] against one combined global sketch
    [sketch_len] uint32: True = the row MAY have a partner on the other
    side (false positives possible, false negatives impossible), False =
    provably partnerless. One lane-aligned uint32 gather per row + bitwise
    tests; a null-key row survives exactly when the other side may hold a
    null (null == null — module doc)."""
    with jax.named_scope(_stages.SEMI_SKETCH):
        L = sketch.shape[0]
        W = L - RANGE_WORDS
        words = sketch[:W]
        word, positions = _word_and_bits(cols, W)
        pattern = _pattern(positions)
        got = words[word]  # THE probe gather: one uint32 block per row
        hit = (got & pattern) == pattern
        if use_range:
            enc = _range_enc(cols[0])
            hit = hit & (enc >= sketch[W + 1]) & (enc <= sketch[W])
        return hit
