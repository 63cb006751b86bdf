"""The ways a run's first row may reach its group's slot, for the tests
that hold the group-by's table equal under each of them bit for bit: the
compaction sort that did it until PR 46 (kept here as the oracle, and
nowhere in the library), and the log-step compress at either width of a
pass (``ops.sort.STEP_TWO_BITS_MIN_SLOTS`` decides between them by the slot
count; the tests' tables are small, so they force the wider one)."""
import jax
import jax.numpy as jnp

import cylon_tpu as ct
from cylon_tpu.ops import groupby as _g
from cylon_tpu.ops import sort as _sort

WAYS = ("sort", "bit-a-pass", "two-bits-a-pass")


def sort_compact(keep, payloads):
    """What ``ops.sort.flag_compact`` was: one unstable sort of every slot
    keyed on the row's own position (the sentinel for a dropped row), the
    payloads riding."""
    cap = keep.shape[0]
    key = jnp.where(keep, jnp.arange(cap, dtype=jnp.int32), jnp.int32(cap))
    out = jax.lax.sort(
        tuple([key] + list(payloads)), num_keys=1, is_stable=False
    )
    return out[0], list(out[1:])


def fresh_ctx(monkeypatch, way: str, world: int = 1) -> ct.CylonContext:
    """A context with no cached kernel, whose group-bys compact ``way``
    (until ``monkeypatch`` is undone: build, run and read inside one
    test)."""
    assert way in WAYS, way
    if way == "sort":
        monkeypatch.setattr(_g, "step_compact", sort_compact)
    else:
        monkeypatch.setattr(
            _sort, "STEP_TWO_BITS_MIN_SLOTS",
            0 if way == "two-bits-a-pass" else 1 << 62,
        )
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:world])
    )


def live_bits(table) -> dict:
    """Every column's live rows as bytes (a null reads NaN, a string
    column its values): what "the same table bit for bit" compares."""
    return {
        name: (
            str(arr.dtype),
            tuple(arr.tolist()) if arr.dtype == object else arr.tobytes(),
        )
        for name, arr in table.to_pydict().items()
    }
