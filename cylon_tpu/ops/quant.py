"""Block-scaled lossy wire codec for float payload lanes (the quantized
wire tier).

Lane packing (ops/stats.py + the ops/gather wire codec) is bit-lossless,
so f32/f64 payload lanes ride the shuffle wire, the spill staging path
and the skew host relay at full width — and BENCH's ``dist_inner_join``
row declines wire narrowing precisely because its f32 payload dominates
the row. EQuARX (arxiv 2506.17615, PAPERS.md) shows XLA collectives
tolerate aggressive block-scaled quantization with bounded error; this
module is that tier for the dataframe engine: an OPT-IN lossy encoding
for float payload lanes, selected per context by an explicit error
tolerance and applied only to columns that are never join/groupby keys.

Codecs (``codec_for`` picks by dtype + tolerance):

``q8``
    Block-scaled int8: each block (one destination chunk of a shuffle
    round's send buffer, one shard's relay tail, one staged spill batch)
    carries a single f32 max-abs scale and every value ships as an 8-bit
    code. Codes 0 / 1 / 255 are reserved for NaN / -inf / +inf
    (passthrough); finite values quantize to ±126 steps of
    ``scale / 126``, so one crossing's error is <= blockmax/252.
    Engages at ``tol >= Q8_TOL`` (1e-2): two lossy crossings (wire +
    spill restage) stay under the tolerance with margin.
``qb16``
    Round-to-nearest bfloat16: 16-bit lanes, per-value relative error
    <= 2^-9 per crossing, inf/NaN exact (bf16 shares f32's exponent
    range). Engages at ``tol >= QB16_TOL`` (2^-8).
``qf32``
    f64 -> f32 demotion (f64 has no lanes in the exchange's format,
    ``ops.gather.lane_plan``: no bitcast of one lowers for a TPU, so
    it rides a per-column 8-byte passthrough collective): 32-bit
    lanes, relative error <= 2^-24 per crossing; engages at
    ``tol >= QF32_TOL`` (2^-23). Values beyond f32 range saturate to
    inf — the error model assumes representable magnitudes (EQuARX's
    operating regime).

The tolerance is the per-COLUMN end-to-end relative error bound
(``max|x_hat - x| <= tol * max|x|`` over the column), with every codec
sized so that the worst case — two lossy crossings, e.g. a quantized
shuffle wire followed by a quantized spill restage — stays under it.
Join/groupby keys, group identities and integer/bool/string lanes are
NEVER quantized: only the rel-err bound on float payload columns is
relaxed, everything else stays exact.

Gate discipline (the ISSUE 3-5 pattern): ``CYLON_TPU_QUANT_TOL`` (or the
per-context ``quant_tol`` config) turns the tier on; unset = today's
exact behavior, byte-identical on every path. ``CYLON_TPU_NO_QUANT=1``
is the kill switch / differential oracle (tools/fuzz_campaign.py
--profile quant). The decided codec per column rides the WirePlan that
is already part of every pack/compact kernel cache key, and
:func:`gate_state` rides the gated plan fingerprint (plan/lazy.py), so
a tolerance flip recompiles and re-enters the plan cache, never aliases.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.envgate import QUANT_TOL, env_gate

# the CYLON_TPU_NO_QUANT=1 kill switch — the exact-wire oracle toggle
enabled, disabled = env_gate(
    "CYLON_TPU_NO_QUANT",
    keyed_via="the decided per-column codec rides the WirePlan 'q' "
    "fields, which are part of every pack/compact/relay/spill kernel "
    "cache key (table._shuffle_state appends the quant signature; "
    "spill.stage_table keys the quantized pack); the plan fingerprint "
    "carries ops.quant.gate_state (plan/lazy.gated_fingerprint)",
    note="=1 disables the lossy wire tier regardless of the tolerance "
    "(the exact-wire differential oracle)",
)

#: engagement thresholds: each codec engages only when the tolerance
#: covers TWO lossy crossings (shuffle wire + spill restage) with margin
Q8_TOL = 1e-2          # per crossing: err <= blockmax / 252
QB16_TOL = 2.0 ** -8   # per crossing: rel err <= 2^-9 (bf16 RNE)
QF32_TOL = 2.0 ** -23  # per crossing: rel err <= 2^-24 (f32 RNE)

#: wire field width of each codec
CODEC_BITS = {"q8": 8, "qb16": 16, "qf32": 32}

# q8 reserved codes (non-finite passthrough)
_Q8_NAN = 0
_Q8_NEG_INF = 1
_Q8_POS_INF = 255


def tolerance(configured: Optional[object] = None) -> float:
    """The effective lossy-wire tolerance: an explicit per-context value
    wins (INCLUDING an explicit 0.0/'' — a context may opt back into the
    exact wire under a process-wide env tolerance), then the
    CYLON_TPU_QUANT_TOL env var, then 0.0 (off). The CYLON_TPU_NO_QUANT
    kill switch forces 0.0 regardless."""
    if not enabled():
        return 0.0
    if configured is not None:
        return float(configured) if configured != "" else 0.0
    env = QUANT_TOL.get()
    return float(env) if env else 0.0


def gate_state() -> tuple:
    """The quant component of the plan fingerprint
    (plan/lazy.gated_fingerprint): kill switch + effective tolerance.
    Both change which wire plans the lowered shuffles decide, so a flip
    must re-enter the plan cache, never alias a cached executor."""
    return (enabled(), tolerance())


def codec_for(np_dtype, tol: float) -> Optional[str]:
    """The lossy codec a float column of ``np_dtype`` rides under
    tolerance ``tol``, or None (exact). Non-float dtypes never quantize
    (keys, ints, bools, dictionary codes stay exact by construction —
    the caller additionally excludes float JOIN/GROUPBY keys)."""
    dt = np.dtype(np_dtype)
    if tol <= 0.0 or not np.issubdtype(dt, np.floating):
        return None
    if dt.itemsize == 2:
        # f16/bf16 already ship 16 lossless bits (the h16 wire field);
        # only the 8-bit tier is a win
        return "q8" if tol >= Q8_TOL else None
    if dt == np.float32:
        if tol >= Q8_TOL:
            return "q8"
        if tol >= QB16_TOL:
            return "qb16"
        return None
    # float64: no lanes in the exchange's format (lane_plan) — every tier
    # beats the 8-byte passthrough collective
    if tol >= Q8_TOL:
        return "q8"
    if tol >= QB16_TOL:
        return "qb16"
    if tol >= QF32_TOL:
        return "qf32"
    return None


def quant_spec(
    dtypes, key_idx, tol: float
) -> Tuple[Optional[str], ...]:
    """Per-column codec tuple for a column set: float PAYLOAD columns get
    :func:`codec_for`'s pick, key columns (``key_idx``) are never
    quantized. This tuple is the quant signature consumers append to
    kernel cache keys."""
    kset = set(key_idx)
    return tuple(
        None if ci in kset else codec_for(dt, tol)
        for ci, dt in enumerate(dtypes)
    )


# ----------------------------------------------------------------------
# device codecs (uint32 field values in/out — the ops/gather wire codec's
# field contract; assemble_words masks to the declared widths)
# ----------------------------------------------------------------------

def safe_scale(blockmax: jax.Array) -> jax.Array:
    """A strictly positive f32 scale from a (possibly zero) block
    max-abs: zero blocks quantize exactly through scale 1."""
    bm = blockmax.astype(jnp.float32)
    return jnp.where(bm > 0, bm, jnp.float32(1.0))


def encode_q8(data: jax.Array, scale: jax.Array) -> jax.Array:
    """[cap] uint32 q8 codes of a float column under per-row f32
    ``scale`` (broadcastable). Finite values land in codes 2..254
    (offset-128, +-126 steps); NaN/-inf/+inf ride the reserved codes."""
    x = data.astype(jnp.float32)
    s = scale.astype(jnp.float32)
    q = jnp.clip(jnp.round(x / s * 126.0), -126.0, 126.0)
    code = (q + 128.0).astype(jnp.uint32)
    code = jnp.where(jnp.isnan(x), jnp.uint32(_Q8_NAN), code)
    code = jnp.where(
        x == jnp.float32(-jnp.inf), jnp.uint32(_Q8_NEG_INF), code
    )
    code = jnp.where(
        x == jnp.float32(jnp.inf), jnp.uint32(_Q8_POS_INF), code
    )
    return code


def decode_q8(code: jax.Array, scale: jax.Array, np_dtype) -> jax.Array:
    """Inverse of :func:`encode_q8` under the same per-row scale."""
    s = scale.astype(jnp.float32)
    x = (code.astype(jnp.float32) - 128.0) / 126.0 * s
    x = jnp.where(code == _Q8_NAN, jnp.float32(jnp.nan), x)
    x = jnp.where(code == _Q8_NEG_INF, jnp.float32(-jnp.inf), x)
    x = jnp.where(code == _Q8_POS_INF, jnp.float32(jnp.inf), x)
    return x.astype(jnp.dtype(np_dtype))


def encode_qb16(data: jax.Array) -> jax.Array:
    """[cap] uint32 holding the bf16 (RNE) bits of a float column."""
    b = data.astype(jnp.bfloat16)
    return jax.lax.bitcast_convert_type(b, jnp.uint16).astype(jnp.uint32)


def decode_qb16(code: jax.Array, np_dtype) -> jax.Array:
    b = jax.lax.bitcast_convert_type(
        code.astype(jnp.uint16), jnp.bfloat16
    )
    return b.astype(jnp.dtype(np_dtype))


def encode_qf32(data: jax.Array) -> jax.Array:
    """[cap] uint32 holding the f32 (RNE) bits of an f64 column."""
    f = data.astype(jnp.float32)
    return jax.lax.bitcast_convert_type(f, jnp.uint32)


def decode_qf32(code: jax.Array, np_dtype) -> jax.Array:
    f = jax.lax.bitcast_convert_type(code, jnp.float32)
    return f.astype(jnp.dtype(np_dtype))


def encode_field(
    codec: str, data: jax.Array, scale: Optional[jax.Array]
) -> jax.Array:
    if codec == "q8":
        return encode_q8(data, scale)
    if codec == "qb16":
        return encode_qb16(data)
    if codec == "qf32":
        return encode_qf32(data)
    raise ValueError(f"unknown quant codec {codec!r}")


def decode_field(
    codec: str, code: jax.Array, scale: Optional[jax.Array], np_dtype
) -> jax.Array:
    if codec == "q8":
        return decode_q8(code, scale, np_dtype)
    if codec == "qb16":
        return decode_qb16(code, np_dtype)
    if codec == "qf32":
        return decode_qf32(code, np_dtype)
    raise ValueError(f"unknown quant codec {codec!r}")


def block_maxabs(data: jax.Array, live: Optional[jax.Array] = None) -> jax.Array:
    """Scalar f32 max-abs over the FINITE (optionally live-masked) values
    of one column — the single-block scale of the relay / spill paths."""
    x = data.astype(jnp.float32)
    ok = jnp.isfinite(x)
    if live is not None:
        ok = ok & live
    return jnp.max(jnp.where(ok, jnp.abs(x), jnp.float32(0.0)))


# ----------------------------------------------------------------------
# host (numpy) mirrors — the spill arena codec decodes staged q8 bytes
# with these; bit-identical to the device codec
# ----------------------------------------------------------------------

def np_encode_q8(x: np.ndarray, scale: float) -> np.ndarray:
    """numpy mirror of :func:`encode_q8` (uint8 codes, scalar scale)."""
    x32 = np.asarray(x, np.float32)
    s = np.float32(scale if scale > 0 else 1.0)
    with np.errstate(invalid="ignore", over="ignore"):
        q = np.clip(np.round(x32 / s * np.float32(126.0)), -126.0, 126.0)
        code = (q + np.float32(128.0)).astype(np.uint8)
    code[np.isnan(x32)] = _Q8_NAN
    code[x32 == -np.inf] = _Q8_NEG_INF
    code[x32 == np.inf] = _Q8_POS_INF
    return code


def np_decode_q8(code: np.ndarray, scale: float, np_dtype) -> np.ndarray:
    """numpy mirror of :func:`decode_q8`."""
    s = np.float32(scale if scale > 0 else 1.0)
    x = (code.astype(np.float32) - np.float32(128.0)) / np.float32(
        126.0
    ) * s
    x[code == _Q8_NAN] = np.nan
    x[code == _Q8_NEG_INF] = -np.inf
    x[code == _Q8_POS_INF] = np.inf
    return x.astype(np.dtype(np_dtype))


def np_maxabs(x: np.ndarray) -> float:
    """Finite max-abs of a host column (the arena re-encode scale)."""
    x32 = np.asarray(x, np.float32)
    ok = np.isfinite(x32)
    return float(np.abs(x32[ok]).max()) if ok.any() else 0.0
