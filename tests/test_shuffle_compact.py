"""The receive side of a shuffle round against a plain numpy partition.

``P`` shards pack with ``parallel.shuffle.pack_by_sort``, the chunks are
swapped as the all-to-all swaps them (numpy, no mesh), and every
destination runs what ``table._shuffle_state.build_compact`` runs:
``split_header`` -> ``received_row_mask`` -> ``compact_received_lanes``
(``compact_received_wire`` under a q8 wire plan). The live rows a
destination ends with must be the rows bound for it, source by source and
in each source's own order, bit for bit, with the received total: that is
all a later operator reads of the layout, so it is what a rewrite of the
compact is held to. Rows past the total are not constrained.

One capacity and one bucket size throughout, so a case compiles its two
programs once and every source, destination and round hits the jit cache.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cylon_tpu.ops import gather as _g
from cylon_tpu.ops import quant as _q
from cylon_tpu.parallel import shuffle as _sh

CAP = 512
BC = 32


def _pids(rng, world, live, hot=0.0, never=None):
    """[CAP] destinations of one source: ``live`` rows, a share ``hot`` of
    them bound for the last shard, none for ``never``; padding (the
    sentinel ``world``) behind."""
    pid = rng.integers(0, world, CAP)
    pid = np.where(rng.random(CAP) < hot, world - 1, pid)
    if never is not None:
        pid = np.where(pid == never, (never + 1) % world, pid)
    pid[live:] = world
    return pid.astype(np.int32)


def _columns(rng, kinds):
    """One source's columns as numpy ``(data, valid-or-None)`` pairs."""
    make = {
        "i32": lambda: rng.integers(-2**31, 2**31, CAP).astype(np.int32),
        "i64": lambda: rng.integers(-2**62, 2**62, CAP).astype(np.int64),
        "f64": lambda: rng.normal(size=CAP) * 1e9,
        "f32": lambda: (rng.normal(size=CAP) * 30).astype(np.float32),
    }
    return [
        (make[k.rstrip("?")](), rng.random(CAP) < 0.7 if k.endswith("?") else None)
        for k in kinds
    ]


#: name -> (column kinds, pids of source ``s``, header rows)
CASES = {
    # every source sends one destination nothing
    "a_source_sends_nothing": (
        ["i64", "f64"],
        lambda rng, w, s: _pids(rng, w, CAP - 40, never=(s + 1) % w), 1,
    ),
    "no_live_rows": (["i64", "f64"], lambda rng, w, s: _pids(rng, w, 0), 1),
    # CAP / world rows a bucket, a multiple of BC: every chunk is full or empty
    "full_chunks": (
        ["i64", "f64"],
        lambda rng, w, s: rng.permutation(np.arange(CAP) % w).astype(np.int32), 1,
    ),
    # several rounds of a hot bucket, its last chunk ragged
    "skewed": (["i64", "f64"], lambda rng, w, s: _pids(rng, w, CAP - 40, hot=0.8), 1),
    # no int32 lane: the counts travel alone, the columns as passthrough
    "pure_float64": (["f64", "f64"], lambda rng, w, s: _pids(rng, w, 400, hot=0.5), 1),
    "validity_lanes": (
        ["i64?", "f64?", "i32"], lambda rng, w, s: _pids(rng, w, 400, hot=0.6), 1,
    ),
    # two header rows a chunk, three words of metadata beside the count
    "wide_header": (["i32", "i32?"], lambda rng, w, s: _pids(rng, w, 400, hot=0.6), 2),
}


def _swap(sent, world):
    """What the all-to-all leaves on destination ``d``: chunk ``d`` of
    every source's buffer, in source order."""
    rows = sent[0].shape[0] // world
    return [
        np.concatenate([buf[d * rows:(d + 1) * rows] for buf in sent])
        for d in range(world)
    ]


def _window(pid, d, rnd):
    """Row indices of one source bound for ``d`` in round ``rnd``, in the
    source's order."""
    return np.flatnonzero(pid == d)[rnd * BC:(rnd + 1) * BC]


def _bits(a):
    a = np.asarray(a)
    if a.dtype.kind != "f":
        return a
    return a.view({4: np.uint32, 8: np.uint64}[a.dtype.itemsize])


def _assert_live_rows(out, total, want_cols, want_total, decoded=()):
    """``decoded``: the columns that arrive as a code decoded on the device,
    held to the numpy decode of the same code within a few ulp (the
    compiler may order the decode's multiply and divide its own way; a
    code is 1/126 of its block's scale, so one code off is 10^4 times
    further)."""
    assert int(total) == want_total
    assert len(out) == len(want_cols)
    for ci, ((data, valid), (wdata, wvalid)) in enumerate(zip(out, want_cols)):
        assert data.shape == (data.shape[0],) and data.dtype == wdata.dtype
        assert (valid is None) == (wvalid is None)
        live = np.asarray(data)[:want_total]
        if ci in decoded:
            np.testing.assert_allclose(live, wdata, rtol=1e-6, atol=0.0)
            continue
        keep = slice(None)
        if wvalid is not None:
            np.testing.assert_array_equal(np.asarray(valid)[:want_total], wvalid)
            keep = wvalid  # a null's data is not part of the contract
        # bit for bit, so that -0.0 and NaN payloads count too
        np.testing.assert_array_equal(_bits(live)[keep], _bits(wdata)[keep])


@pytest.mark.parametrize("world", [2, 4, 8])
@pytest.mark.parametrize("name", list(CASES))
def test_compacted_rows_are_the_partition_in_source_order(name, world):
    kinds, pids_of, n_header = CASES[name]
    rng = np.random.default_rng(1000 * world + list(CASES).index(name))
    pids = [pids_of(rng, world, s) for s in range(world)]
    cols = [_columns(rng, kinds) for _ in range(world)]
    extra = [
        rng.integers(-2**31, 2**31, (world, 3)).astype(np.int32)
        if n_header > 1 else None
        for _ in range(world)
    ]
    plan = _g.lane_plan([
        (jnp.asarray(d), None if v is None else jnp.asarray(v))
        for d, v in cols[0]
    ])
    pt_order = tuple(ci for ci, (tag, _nl, _hv) in enumerate(plan) if tag is None)
    has_lanes = any(tag is not None or hv for tag, _nl, hv in plan)
    assert has_lanes == (name != "pure_float64")

    @jax.jit
    def pack(cols, pid, rnd, hx):
        _plan, lanes, passthrough = _g.pack_cols(cols)
        return _sh.pack_by_sort(
            lanes, [passthrough[ci] for ci in pt_order], pid,
            _sh.bucket_counts(pid, world), world, BC, rnd,
            header_extra=hx, n_header=n_header,
        )

    @jax.jit
    def receive(head, pts):
        if has_lanes:
            lane_rows, recv_counts = _sh.split_header(head, world, n_header)
        else:
            lane_rows, recv_counts = None, head
        mask, total = _sh.received_row_mask(recv_counts, world, BC)
        out = _sh.compact_received_lanes(
            list(plan), lane_rows, dict(zip(pt_order, pts)), mask
        )
        scales = (
            _sh.split_header_scales(head, world, n_header, 3)
            if n_header > 1 else None
        )
        return out, total, recv_counts, scales

    hottest = max(
        int(np.bincount(p, minlength=world + 1)[:world].max()) for p in pids
    )
    last = -(-hottest // BC)
    assert name != "skewed" or last >= 8
    assert name != "full_chunks" or last * BC * world == CAP
    for rnd in range(last + 2):
        sent = [
            pack(cols[s], jnp.asarray(pids[s]), jnp.asarray(rnd, jnp.int32), extra[s])
            for s in range(world)
        ]
        heads = _swap([np.asarray(h) for h, _p in sent], world)
        pts = [
            _swap([np.asarray(p[j]) for _h, p in sent], world)
            for j in range(len(pt_order))
        ]
        for d in range(world):
            out, total, recv_counts, scales = receive(
                jnp.asarray(heads[d]), tuple(jnp.asarray(p[d]) for p in pts)
            )
            rows = [_window(pids[s], d, rnd) for s in range(world)]
            np.testing.assert_array_equal(
                np.asarray(recv_counts), [len(r) for r in rows]
            )
            want = [
                (
                    np.concatenate([cols[s][ci][0][rows[s]] for s in range(world)]),
                    None if cols[0][ci][1] is None else np.concatenate(
                        [cols[s][ci][1][rows[s]] for s in range(world)]
                    ),
                )
                for ci in range(len(kinds))
            ]
            _assert_live_rows(out, total, want, sum(len(r) for r in rows))
            if scales is not None:
                # source s's metadata for this destination, word for word
                np.testing.assert_array_equal(
                    np.asarray(scales).view(np.int32),
                    np.stack([extra[s][d] for s in range(world)]),
                )
    if name == "no_live_rows":
        assert last == 0  # and both rounds above received a total of 0


@pytest.mark.parametrize("world", [2, 4, 8])
def test_compacted_rows_under_a_q8_wire_plan(world):
    """A wire plan of one exact int32 lane and two q8 fields, two header
    rows a chunk, a float64 column beside it as passthrough: the exact
    columns arrive bit for bit, and a quantized value arrives as its code
    under its own (source, destination) chunk's block scale decodes, which
    is the scale the header carried."""
    rng = np.random.default_rng(55 + world)
    kinds = ["i32", "f32", "f32", "f64"]
    pids = [_pids(rng, world, CAP - 40, hot=0.7) for _ in range(world)]
    cols = [_columns(rng, kinds) for _ in range(world)]
    cols[0][1][0][:8] = [np.nan, np.inf, -np.inf, -0.0, 0.0, 1e-30, 3e4, -3e4]
    as_jax = lambda cs: [(jnp.asarray(d), None) for d, _v in cs]
    plan = _g.lane_plan(as_jax(cols[0]))
    wire = _g.wire_plan(plan, [None] * 4, quant=(None, "q8", "q8", None))
    assert wire is not None and len(_g.wire_q8_cols(wire)) == 2
    n_header = _sh.wire_header_rows(wire)
    assert n_header == 2
    pt_order = _g.wire_pt_order(wire, (3,))
    assert pt_order == (3,)

    @jax.jit
    def pack(cols, pid, rnd):
        cnt = _sh.bucket_counts(pid, world)
        scales = _sh.quant_chunk_scales_sorted(cols, wire, pid, cnt, world, BC, rnd)
        lanes, passthrough = _g.wire_pack_cols(
            cols, wire, None, qscales=_sh.send_row_scales(scales, pid, 1)
        )
        return _sh.pack_by_sort(
            lanes, [passthrough[ci] for ci in pt_order], pid, cnt, world, BC,
            rnd, header_extra=jax.lax.bitcast_convert_type(scales, jnp.int32),
            n_header=n_header,
        )

    @jax.jit
    def receive(head, pts):
        lane_rows, recv_counts = _sh.split_header(head, world, n_header)
        scales = _sh.split_header_scales(head, world, n_header, 2)
        mask, total = _sh.received_row_mask(recv_counts, world, BC)
        out = _sh.compact_received_wire(
            wire, None, lane_rows, dict(zip(pt_order, pts)), mask,
            qscale_rows=_sh.recv_row_scales(scales, world, BC),
        )
        return out, total, scales

    hottest = max(
        int(np.bincount(p, minlength=world + 1)[:world].max()) for p in pids
    )
    for rnd in range(-(-hottest // BC) + 1):
        sent = [
            pack(as_jax(cols[s]), jnp.asarray(pids[s]), jnp.asarray(rnd, jnp.int32))
            for s in range(world)
        ]
        heads = _swap([np.asarray(h) for h, _p in sent], world)
        pts = _swap([np.asarray(p[0]) for _h, p in sent], world)
        for d in range(world):
            out, total, scales = receive(jnp.asarray(heads[d]), (jnp.asarray(pts[d]),))
            got_scales = np.asarray(scales)
            rows = [_window(pids[s], d, rnd) for s in range(world)]
            want = []
            for ci, kind in enumerate(kinds):
                parts = []
                for s in range(world):
                    x = cols[s][ci][0][rows[s]]
                    if kind == "f32":
                        block = _q.np_maxabs(x)
                        assert np.float32(block or 1.0) == got_scales[s, ci - 1]
                        code = _q.np_encode_q8(x, block)
                        x = _q.np_decode_q8(code, block, np.float32)
                    parts.append(x)
                want.append((np.concatenate(parts), None))
            _assert_live_rows(
                out, total, want, sum(len(r) for r in rows), decoded=(1, 2)
            )
