"""The sort-and-slice pack against the scatter chain it replaced.

``parallel.shuffle.pack_by_sort`` (rows ride one stable sort keyed by
destination, each destination's chunk a contiguous window of the sorted
rows) must lay the send buffers bit for bit as ``build_send_slots_round``
+ ``pack_lane_buffer`` + ``scatter_send`` do: the collective, the compact
kernel, the two-hop plan and the host's round planning all read that
layout. The old chain stays in the tree (the fused pipeline, the relay and
ring kernels) and is the oracle here.
"""
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cylon_tpu.ops import gather as _g
from cylon_tpu.ops import sort as _sort
from cylon_tpu.parallel import shuffle as _sh

CAP = 512
BC = 32


def _pids(rng, world, live, hot=0.0, dropped=0.0):
    """[CAP] destinations: ``live`` rows, a share ``hot`` of them bound
    for one bucket, a share ``dropped`` carrying the sentinel ``world``
    among them (the semi filter's rows), padding behind."""
    pid = rng.integers(0, world, CAP)
    pid = np.where(rng.random(CAP) < hot, world - 1, pid)
    pid = np.where(rng.random(CAP) < dropped, world, pid)
    pid[live:] = world
    return jnp.asarray(pid, jnp.int32)


def _case(name, world, rng):
    """``(cols, pid, header_extra, n_header)`` of one case."""
    i32 = lambda: jnp.asarray(rng.integers(-2**31, 2**31, CAP), jnp.int32)
    i64 = lambda: jnp.asarray(rng.integers(-2**62, 2**62, CAP), jnp.int64)
    f64 = lambda: jnp.asarray(rng.normal(size=CAP) * 1e9, jnp.float64)
    valid = lambda: jnp.asarray(rng.random(CAP) < 0.7)
    suite = [(i64(), None), (f64(), None)]  # the cells' two columns
    if name == "skewed":
        return suite, _pids(rng, world, CAP - 40, hot=0.8), None, 1
    if name == "semi_sentinel":
        return suite, _pids(rng, world, CAP - 40, dropped=0.4), None, 1
    if name == "no_live_rows":
        return suite, _pids(rng, world, 0), None, 1
    if name == "full":  # no padding row: the last window ends at the cap
        return suite, _pids(rng, world, CAP, hot=0.5), None, 1
    if name == "pure_float64":
        return [(f64(), None), (f64(), None)], _pids(rng, world, 400, hot=0.5), None, 1
    if name == "validity_lanes":
        cols = [(i64(), valid()), (f64(), valid()), (i32(), None)]
        return cols, _pids(rng, world, 400, hot=0.6), None, 1
    if name == "wide_header":
        hx = jnp.asarray(rng.integers(-2**31, 2**31, (world, 3)), jnp.int32)
        return [(i32(), None), (i32(), valid())], _pids(rng, world, 400, hot=0.6), hx, 2
    if name == "many_lanes":  # past RIDE_LANES: the lanes ride in batches
        cols = [(i64(), None) for _ in range(4)] + [(i32(), None), (f64(), None)]
        assert _sort.ride_census([jnp.int32] * 9 + [jnp.float64])[1] > 1
        return cols, _pids(rng, world, 400, hot=0.6), None, 1
    raise AssertionError(name)


def _old_chain(lanes, pts, pid, world, rnd, hx, n_header):
    cnt = _sh.bucket_counts(pid, world)
    dest, _left = _sh.build_send_slots_round(pid, cnt, world, BC, rnd)
    rc = _sh.round_counts(cnt, BC, rnd)
    head = (
        _sh.pack_lane_buffer(
            lanes, dest, rc, world, BC, header_extra=hx, n_header=n_header
        )
        if lanes else rc
    )
    return head, tuple(_sh.scatter_send(p, dest, world, BC) for p in pts)


def _new_pack(lanes, pts, pid, world, rnd, hx, n_header):
    cnt = _sh.bucket_counts(pid, world)
    return _sh.pack_by_sort(
        lanes, pts, pid, cnt, world, BC, rnd, header_extra=hx,
        n_header=n_header,
    )


def _assert_same(old, new):
    (head_o, pts_o), (head_n, pts_n) = old, new
    assert head_o.shape == head_n.shape and head_o.dtype == head_n.dtype
    np.testing.assert_array_equal(np.asarray(head_o), np.asarray(head_n))
    assert len(pts_o) == len(pts_n)
    for o, n in zip(pts_o, pts_n):
        assert o.shape == n.shape and o.dtype == n.dtype
        # bit for bit, so that -0.0 and NaN payloads count too
        np.testing.assert_array_equal(
            np.asarray(o).view(np.uint64), np.asarray(n).view(np.uint64)
        )


CASES = [
    "skewed", "semi_sentinel", "no_live_rows", "full", "pure_float64",
    "validity_lanes", "wide_header", "many_lanes",
]


@pytest.mark.parametrize("world", [2, 4, 8])
@pytest.mark.parametrize("name", CASES)
def test_sorted_pack_equals_the_scatter_chain(name, world):
    rng = np.random.default_rng(1000 * world + CASES.index(name))
    cols, pid, hx, n_header = _case(name, world, rng)
    _plan, lanes, passthrough = _g.pack_cols(cols)
    pts = [passthrough[ci] for ci in sorted(passthrough)]
    assert bool(lanes) == (name != "pure_float64")
    old = jax.jit(partial(_old_chain, world=world, hx=hx, n_header=n_header))
    new = jax.jit(partial(_new_pack, world=world, hx=hx, n_header=n_header))
    # rounds 0 to two past the last of the hottest bucket
    hottest = int(np.bincount(np.asarray(pid), minlength=world + 1)[:world].max())
    last = -(-hottest // BC)
    assert name != "skewed" or last >= 8
    for rnd in range(last + 2):
        r = jnp.asarray(rnd, jnp.int32)
        _assert_same(old(lanes, pts, pid, rnd=r), new(lanes, pts, pid, rnd=r))


@pytest.mark.parametrize("world", [2, 4, 8])
def test_sorted_pack_equals_the_scatter_chain_under_a_q8_wire_plan(world):
    """A wire plan whose three q8 fields widen the header to four rows: the
    per-chunk scales taken from the sorted rows' windows are the scales
    the row-space slots gave, and so are the packed words."""
    rng = np.random.default_rng(77 + world)
    cols = [
        (jnp.asarray(rng.normal(size=CAP) * 10.0 ** k, jnp.float32), None)
        for k in range(3)
    ]
    wire = _g.wire_plan(_g.lane_plan(cols), [None] * 3, quant=("q8",) * 3)
    assert wire is not None and len(_g.wire_q8_cols(wire)) == 3
    n_header = _sh.wire_header_rows(wire)
    assert n_header > 1
    pid = _pids(rng, world, CAP - 40, hot=0.7, dropped=0.1)

    def old(pid, rnd):
        cnt = _sh.bucket_counts(pid, world)
        dest, _left = _sh.build_send_slots_round(pid, cnt, world, BC, rnd)
        scales = _sh.quant_chunk_scales(cols, wire, dest, world, BC)
        qrows = _sh.send_row_scales(scales, dest, BC)
        lanes, _pt = _g.wire_pack_cols(cols, wire, None, qscales=qrows)
        return _sh.pack_lane_buffer(
            lanes, dest, _sh.round_counts(cnt, BC, rnd), world, BC,
            header_extra=jax.lax.bitcast_convert_type(scales, jnp.int32),
            n_header=n_header,
        )

    def new(pid, rnd):
        cnt = _sh.bucket_counts(pid, world)
        scales = _sh.quant_chunk_scales_sorted(
            cols, wire, pid, cnt, world, BC, rnd
        )
        qrows = scales[jnp.clip(pid, 0, world - 1)]
        lanes, _pt = _g.wire_pack_cols(cols, wire, None, qscales=qrows)
        head, _pts = _sh.pack_by_sort(
            lanes, [], pid, cnt, world, BC, rnd,
            header_extra=jax.lax.bitcast_convert_type(scales, jnp.int32),
            n_header=n_header,
        )
        return head

    old, new = jax.jit(old), jax.jit(new)
    hottest = int(np.bincount(np.asarray(pid), minlength=world + 1)[:world].max())
    for rnd in range(-(-hottest // BC) + 1):
        r = jnp.asarray(rnd, jnp.int32)
        np.testing.assert_array_equal(np.asarray(old(pid, r)), np.asarray(new(pid, r)))
