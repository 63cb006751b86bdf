"""The receive side of a shuffle round against a plain numpy partition.

``P`` shards pack with ``parallel.shuffle.pack_by_sort``, the chunks are
swapped as the all-to-all swaps them (numpy, no mesh), and every
destination runs what ``table._shuffle_state.build_compact`` runs on a
flat mesh: ``split_header`` -> ``compact_received_lanes``
(``compact_received_wire`` under a q8 wire plan) over ``chunk_front``, each
chunk written as one block at the running offset of the received counts
(PR 47; a liveness argsort and a gather an array until then). The live rows a destination
ends with must be the rows bound for it, source by source and in each
source's own order, bit for bit, with the received total: that is all a
later operator reads of the layout, so it is what a rewrite of the compact
is held to. Rows past the total are not constrained.

The order-and-gather form that the two-hop receive and the ring relay keep
(the same two over ``order_front`` of a general liveness mask) is held to
the same contract at the end of the file, and
the program a mesh really dispatches is read for what it holds.

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
    # the block form's edges. Every row of every source is live, CAP / world
    # of them a bucket: every chunk of every round is full, and the last
    # block ends exactly at the buffer's end
    "every_chunk_full": (
        ["i64", "f64"],
        lambda rng, w, s: np.repeat(np.arange(w), CAP // w).astype(np.int32), 1,
    ),
    # the last chunk empty: the block written last holds no live row
    "last_source_sends_nothing": (
        ["i64", "f64"],
        lambda rng, w, s: _pids(rng, w, 0 if s == w - 1 else 400, hot=0.5), 1,
    ),
    # only the last chunk live: every running offset is 0
    "only_the_last_source_sends": (
        ["i64", "f64"],
        lambda rng, w, s: _pids(rng, w, 400 if s == w - 1 else 0, hot=0.5), 1,
    ),
    # the lane matrix at its narrowest and at six lanes
    "one_lane": (["i32"], lambda rng, w, s: _pids(rng, w, 400, hot=0.6), 1),
    "six_lanes": (
        ["i64", "i64?", "i32", "f64"],
        lambda rng, w, s: _pids(rng, w, 400, hot=0.6), 1,
    ),
}
#: the width L of a case's ``[rows, L]`` lane matrix, where the case is about it
LANES = {"one_lane": 1, "six_lanes": 6, "pure_float64": 0}


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
            assert lane_rows.shape == (world * BC, LANES.get(name, lane_rows.shape[1]))
        else:
            lane_rows, recv_counts = None, head
        out = _sh.compact_received_lanes(
            list(plan), lane_rows, dict(zip(pt_order, pts)),
            _sh.chunk_front(recv_counts),
        )
        scales = (
            _sh.split_header_scales(head, world, n_header, 3)
            if n_header > 1 else None
        )
        return out, jnp.sum(recv_counts), recv_counts, scales

    hottest = max(
        int(np.bincount(p, minlength=world + 1)[:world].max()) for p in pids
    )
    last = -(-hottest // BC)
    assert name != "skewed" or last >= 8
    assert name != "full_chunks" or last * BC * world == CAP
    assert name != "every_chunk_full" or last * BC * world == CAP
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
            if name == "every_chunk_full" and rnd < last:
                assert all(len(r) == BC for r in rows)
            if name == "last_source_sends_nothing":
                assert len(rows[-1]) == 0
            if name == "only_the_last_source_sends":
                assert not any(len(r) for r in rows[:-1])
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
        out = _sh.compact_received_wire(
            wire, None, lane_rows, dict(zip(pt_order, pts)),
            _sh.chunk_front(recv_counts),
            qscale_rows=_sh.recv_row_scales(scales, world, BC),
        )
        return out, jnp.sum(recv_counts), scales

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


# ----------------------------------------------------------------------
# the block writes alone: any trailing shape, any counts
# ----------------------------------------------------------------------

@pytest.mark.parametrize("world", [1, 2, 4, 8])
@pytest.mark.parametrize("trailing", [(), (1,), (6,)])
def test_front_pack_chunks_is_the_stable_argsort_of_the_mask(world, trailing):
    """Over ragged, full and empty chunks the live rows equal those of the
    order-and-gather form, which is what ``front_pack_chunks`` replaced."""
    rng = np.random.default_rng(7 * world + len(trailing))
    x = rng.integers(-2**31, 2**31, (world * BC, *trailing)).astype(np.int32)
    front = jax.jit(_sh.front_pack_chunks)
    for counts in (
        rng.integers(0, BC + 1, world), np.full(world, BC), np.zeros(world),
        np.where(np.arange(world) == world - 1, BC - 3, 0),
        np.where(np.arange(world) == world - 1, 0, BC),
    ):
        counts = counts.astype(np.int32)
        mask, total = _sh.received_row_mask(jnp.asarray(counts), world, BC)
        order = np.argsort(~np.asarray(mask), kind="stable")
        got = np.asarray(front(jnp.asarray(x), jnp.asarray(counts)))
        assert got.shape == x.shape and int(total) == counts.sum()
        np.testing.assert_array_equal(got[:counts.sum()], x[order][:counts.sum()])


# ----------------------------------------------------------------------
# a general mask: the order-and-gather forms, which the two-hop receive
# and the ring relay keep
# ----------------------------------------------------------------------

def _general_mask(rng, rows):
    """Unequal chunks of live prefixes, as ``topo.two_hop_received`` hands
    over, with a stray live row in a dead tail for good measure."""
    mask = np.zeros(rows, bool)
    at = 0
    for size in (rows // 8, rows // 2, rows // 8, rows // 4):
        mask[at:at + rng.integers(0, size + 1)] = True
        at += size
    mask[rows - 3] = True
    return mask


@pytest.mark.parametrize("kinds", [["i64", "f64"], ["f64", "f64"], ["i64?", "f64?", "i32"]])
def test_a_general_mask_is_compacted_by_order(kinds):
    rng = np.random.default_rng(len(kinds) + len(kinds[0]))
    cols = _columns(rng, kinds)
    mask = _general_mask(rng, CAP)
    as_jax = [(jnp.asarray(d), None if v is None else jnp.asarray(v)) for d, v in cols]
    plan = _g.lane_plan(as_jax)
    pt_order = tuple(ci for ci, (tag, _nl, _hv) in enumerate(plan) if tag is None)

    @jax.jit
    def compact(cols, mask):
        _plan, lanes, passthrough = _g.pack_cols(cols)
        return _sh.compact_received_lanes(
            list(plan), jnp.stack(lanes, axis=1) if lanes else None,
            {ci: passthrough[ci] for ci in pt_order}, _sh.order_front(mask),
        )

    out = compact(as_jax, jnp.asarray(mask))
    want = [(d[mask], None if v is None else v[mask]) for d, v in cols]
    _assert_live_rows(out, mask.sum(), want, int(mask.sum()))


def test_a_general_mask_is_compacted_by_order_under_a_q8_wire_plan():
    rng = np.random.default_rng(99)
    kinds = ["i32", "f32", "f32", "f64"]
    cols = _columns(rng, kinds)
    mask = _general_mask(rng, CAP)
    as_jax = [(jnp.asarray(d), None) for d, _v in cols]
    wire = _g.wire_plan(
        _g.lane_plan(as_jax), [None] * 4, quant=(None, "q8", "q8", None)
    )
    assert wire is not None and len(_g.wire_q8_cols(wire)) == 2
    # a block scale a quarter of the rows, as four source chunks would bring
    blocks = {ci: np.split(cols[ci][0], 4) for ci in (1, 2)}
    scales = np.stack([
        np.repeat([np.float32(_q.np_maxabs(c) or 1.0) for c in blocks[ci]], CAP // 4)
        for ci in (1, 2)
    ], axis=1)

    @jax.jit
    def compact(cols, mask, scales):
        lanes, passthrough = _g.wire_pack_cols(cols, wire, None, qscales=scales)
        return _sh.compact_received_wire(
            wire, None, jnp.stack(lanes, axis=1), {3: passthrough[3]},
            _sh.order_front(mask), qscale_rows=scales,
        )

    out = compact(as_jax, jnp.asarray(mask), jnp.asarray(scales))
    decoded = {
        ci: np.concatenate([
            _q.np_decode_q8(
                _q.np_encode_q8(c, _q.np_maxabs(c)), _q.np_maxabs(c), np.float32
            )
            for c in blocks[ci]
        ])
        for ci in (1, 2)
    }
    want = [
        (cols[0][0][mask], None), (decoded[1][mask], None),
        (decoded[2][mask], None), (cols[3][0][mask], None),
    ]
    _assert_live_rows(out, mask.sum(), want, int(mask.sum()), decoded=(1, 2))


# ----------------------------------------------------------------------
# the program a flat mesh dispatches: block writes, nothing by the row
# ----------------------------------------------------------------------

def test_the_one_hop_compact_program_holds_no_sort_and_no_gather():
    """A sort and a join on four shards (an int64 key and a float64 value:
    lanes and a passthrough column): every ``shuffle_compact`` program they
    dispatch holds ``dynamic-update-slice`` under the ``shuffle.compact``
    stage, and neither a sort nor a gather."""
    import cylon_tpu as ct
    from cylon_tpu.obs import stages

    ctx = ct.CylonContext.init_distributed(ct.TPUConfig(devices=jax.devices()[:4]))
    rng = np.random.default_rng(47)
    a = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, 300, 1500).astype(np.int64),
        "v": rng.normal(size=1500),
    })
    b = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, 300, 1500).astype(np.int64),
        "w": rng.normal(size=1500),
        "z": rng.integers(-2**62, 2**62, 1500).astype(np.int64),
    })
    a.distributed_sort("k")
    a.distributed_join(b, on="k", how="inner")
    texts = [
        stages._compiled_text(fn.lower(*spec))
        for _key, fn, spec in stages.dispatched_programs(ctx)
        if fn.__name__ == "shuffle_compact"
    ]
    assert len(texts) >= 2  # a program a table layout
    for text in texts:
        assert " dynamic-update-slice(" in text
        assert not any(w in text for w in (" sort(", " gather(", " scatter("))
        _module, rows = stages.parse_compiled(text)
        named = [op for _t, op in rows if "dynamic_update_slice" in op]
        assert named and all(
            stages.stage_of(op) == stages.SHUFFLE_COMPACT for op in named
        )


# ----------------------------------------------------------------------
# ROADMAP M17: the CPU mesh past 2^15-slot buckets
# ----------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.xfail(
    strict=False,
    reason="ROADMAP M17: on XLA:CPU the one-hop compact's block writes, "
    "each an in-place dynamic-update-slice whose source overlaps its "
    "target, lose rows once a chunk is copied on several threads "
    "(buckets of 2^15 slots and up; right with "
    "--xla_cpu_multi_thread_eigen=false, and on the chip)",
)
def test_a_shuffle_of_400000_rows_over_four_shards_keeps_every_row():
    """``Table.shuffle`` of an int32 key and a float64 value, 100,000 rows
    a shard (buckets of 2^15 slots): the rows that come out are the rows
    that went in. The witness of M17: it fails on the CPU backend since
    PR 47 and no tier-1 test reaches the size."""
    import cylon_tpu as ct

    rows = 400_000
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:4])
    )
    rng = np.random.default_rng(17)
    k = rng.integers(0, rows, rows).astype(np.int32)
    v = rng.random(rows)
    got = ct.Table.from_numpy(ctx, ["k", "v"], [k, v]).shuffle(["k"]).to_pydict()
    assert len(got["k"]) == rows
    want, have = np.lexsort((v, k)), np.lexsort((got["v"], got["k"]))
    np.testing.assert_array_equal(got["k"][have], k[want])
    np.testing.assert_array_equal(got["v"][have], v[want])
