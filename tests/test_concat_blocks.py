"""The K-ary concat by block writes against numpy.

``table._concat_tables`` hands all K same-schema tables to ONE program
(``parallel.shuffle.reassemble_blocks``) that writes each part's buffer as
a block at its running row offset. A shard's output must be the
``numpy.concatenate`` of the parts' live prefixes on that shard, in order,
with the column's promoted dtype, a validity lane where any part has one,
and every slot past the total zero and invalid. The parts here carry
garbage in their dead tails, so a tail that leaks shows.

A shuffle of more than one round reassembles its rounds' outputs through
the same call: round-major, then the relay and the ring table.
"""
from collections import OrderedDict

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import cylon_tpu as ct
from cylon_tpu import table as _table
from cylon_tpu.column import Column
from cylon_tpu.dtypes import DataType, Type
from cylon_tpu.engine import round_cap
from cylon_tpu.parallel import shuffle as _sh
from cylon_tpu.utils.tracing import report, reset_trace

WORLDS = [1, 2, 4]


@pytest.fixture(scope="module", params=WORLDS)
def ctx(request, devices):
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:request.param])
    )


@pytest.fixture(scope="module")
def ctx4(devices):
    return ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:4]))


def _garbage(rng, dtype, shape):
    """Values that are never zero: what a dead tail may hold."""
    if np.dtype(dtype).kind == "f":
        return (rng.random(shape) + 1.0).astype(dtype)
    return rng.integers(1, 1 << 20, shape).astype(dtype)


def _part(ctx, rng, cap, counts, schema, dicts=None):
    """A table of ``cap`` slots a shard with ``counts`` live rows a shard.
    ``schema``: name -> (numpy dtype, nullable); ``dicts``: name -> the
    dictionary of a string column (its codes are the dtype's values)."""
    world = ctx.world_size
    counts = np.asarray(counts, np.int64)
    assert counts.shape == (world,) and counts.max() <= cap
    cols = OrderedDict()
    for name, (dtype, nullable) in schema.items():
        dic = (dicts or {}).get(name)
        if dic is None:
            data = _garbage(rng, dtype, (world, cap))
            dt = DataType.from_numpy_dtype(np.dtype(dtype))
        else:
            data = rng.integers(0, len(dic), (world, cap)).astype(np.int32)
            dt = DataType(Type.STRING)
        valid = None
        if nullable:
            valid = rng.random((world, cap)) < 0.7
            # a dead slot's validity is garbage too
            valid[np.arange(cap)[None, :] >= counts[:, None]] = True
            valid = jax.device_put(valid.reshape(-1), ctx.sharding)
        cols[name] = Column(
            jax.device_put(data.reshape(-1), ctx.sharding), dt, valid, dic
        )
    return ct.Table(ctx, cols, counts, cap)


def _shards(t):
    """Per shard, per column: (the whole buffer, the whole validity lane)."""
    world, cap = t.world_size, t.shard_cap
    return {
        name: (
            np.asarray(c.data).reshape(world, cap),
            None if c.valid is None
            else np.asarray(c.valid).reshape(world, cap),
        )
        for name, c in t._columns.items()
    }


def _assert_concat(out, parts, decode=False):
    """``out`` is, shard by shard and row by row, the concatenation of the
    parts' live prefixes; ``decode`` compares a string column's VALUES
    (the parts' dictionaries differ)."""
    world = out.world_size
    totals = sum(p.row_counts for p in parts)
    np.testing.assert_array_equal(out.row_counts, totals)
    assert out.shard_cap == round_cap(int(totals.max()))
    got, had = _shards(out), [_shards(p) for p in parts]
    for name, col in out._columns.items():
        data, valid = got[name]
        any_valid = any(h[name][1] is not None for h in had)
        assert (valid is not None) == any_valid, name
        src = [p._columns[name] for p in parts]
        if col.dtype.is_dictionary:
            assert all(c.dtype.is_dictionary for c in src)
        else:
            assert data.dtype == np.result_type(
                *[h[name][0].dtype for h in had]
            ), name
        for s in range(world):
            n = int(totals[s])
            blocks, vblocks = [], []
            for p, h, c in zip(parts, had, src):
                k = int(p.row_counts[s])
                d, v = h[name]
                live = d[s, :k]
                if decode and col.dtype.is_dictionary:
                    live = np.asarray(c.dictionary)[live]
                blocks.append(live)
                vblocks.append(np.ones(k, bool) if v is None else v[s, :k])
            want = np.concatenate(blocks)
            have = data[s, :n]
            if decode and col.dtype.is_dictionary:
                have = np.asarray(col.dictionary)[have]
            np.testing.assert_array_equal(have, want, err_msg=f"{name}[{s}]")
            assert not data[s, n:].any(), (name, s, "dead slots not zero")
            if valid is not None:
                np.testing.assert_array_equal(
                    valid[s, :n], np.concatenate(vblocks)
                )
                assert not valid[s, n:].any(), (name, s, "dead slots valid")


SUITE = OrderedDict(
    k=(np.int64, False), v=(np.float64, False), m=(np.int32, True)
)


def _uneven_counts(rng, world, caps):
    """Counts a part a shard: uneven, one part empty on the first shard,
    another full on the last."""
    counts = [rng.integers(0, cap + 1, world) for cap in caps]
    counts[1 % len(caps)][0] = 0
    counts[-1][-1] = caps[-1]
    return counts


@pytest.mark.parametrize("k", [2, 3, 4, 8])
def test_concat_is_the_concatenation_of_the_live_prefixes(ctx, k):
    rng = np.random.default_rng(100 * ctx.world_size + k)
    caps = [(8, 64, 16, 32)[i % 4] for i in range(k)]
    counts = _uneven_counts(rng, ctx.world_size, caps)
    parts = [_part(ctx, rng, c, n, SUITE) for c, n in zip(caps, counts)]
    _assert_concat(_table._concat_tables(parts), parts)


@pytest.mark.parametrize("last_live", [0, 24])
def test_a_total_that_fills_the_output_with_the_last_part_overhanging(
    ctx, last_live
):
    """The clamp case: ``dynamic_update_slice`` moves a start so that the
    update fits. On the first shard the total equals ``cap_out`` exactly
    and the last part's 64 slots overhang it from its offset; written into
    the output itself, that block would land at slot 0."""
    rng = np.random.default_rng(7)
    world = ctx.world_size
    first = np.full(world, 3)
    first[0] = 32 - last_live
    last = np.full(world, 5)
    last[0] = last_live
    parts = [
        _part(ctx, rng, 32, first, SUITE),
        _part(ctx, rng, 64, last, SUITE),
    ]
    out = _table._concat_tables(parts)
    assert out.shard_cap == 32 and int(out.row_counts[0]) == 32
    _assert_concat(out, parts)


@pytest.mark.parametrize(
    "dtypes",
    [
        (np.int32, np.int64), (np.int64, np.int32),
        (np.float32, np.float64), (np.int32, np.float64),
        (np.int32, np.int64, np.float64),
    ],
    ids=lambda d: "+".join(np.dtype(t).name for t in d),
)
def test_mixed_dtypes_are_promoted_a_column(ctx4, dtypes):
    rng = np.random.default_rng(11)
    parts = [
        _part(ctx4, rng, 16, rng.integers(1, 17, 4),
              OrderedDict(a=(dt, False), b=(np.float64, i % 2 == 0)))
        for i, dt in enumerate(dtypes)
    ]
    out = _table._concat_tables(parts)
    assert out._columns["a"].data.dtype == np.result_type(*dtypes)
    _assert_concat(out, parts)


@pytest.mark.parametrize(
    "nullable",
    [(True, False, False), (False, False, True), (False, True, False),
     (False, False, False), (True, True, True)],
    ids=lambda n: "".join("v" if x else "-" for x in n),
)
def test_validity_is_none_only_where_every_parts_is(ctx4, nullable):
    rng = np.random.default_rng(13)
    parts = [
        _part(ctx4, rng, cap, rng.integers(0, cap + 1, 4),
              OrderedDict(a=(np.int64, nv), b=(np.float64, False)))
        for cap, nv in zip((16, 8, 32), nullable)
    ]
    out = _table._concat_tables(parts)
    assert (out._columns["a"].valid is None) == (not any(nullable))
    assert out._columns["b"].valid is None
    _assert_concat(out, parts)


ABC = np.array(["a", "b", "c"])


@pytest.mark.parametrize(
    "dicts",
    [
        (ABC, ABC, ABC),                                   # one object
        (ABC, ABC.copy(), ABC.copy()),                     # equal values
        (ABC, np.array(["b", "d"]), np.array(["a", "e", "f", "g"])),
        (np.array(["x"]), ABC, np.array(["m", "x"])),
    ],
    ids=["same_object", "equal", "differing", "differing_first_small"],
)
def test_dictionary_columns_are_unified_across_all_parts(ctx4, dicts):
    rng = np.random.default_rng(17)
    schema = OrderedDict(s=(np.int32, True), v=(np.float64, False))
    parts = [
        _part(ctx4, rng, 16, rng.integers(1, 17, 4), schema, {"s": d})
        for d in dicts
    ]
    out = _table._concat_tables(parts)
    union = np.unique(np.concatenate(dicts))
    np.testing.assert_array_equal(out._columns["s"].dictionary, union)
    if all(d is ABC for d in dicts):
        assert out._columns["s"].dictionary is ABC
    _assert_concat(out, parts, decode=True)


def test_a_string_table_without_rows_concats(ctx4):
    """An empty dictionary codes no row: its remap looks nothing up."""
    rng = np.random.default_rng(19)
    schema = OrderedDict(s=(np.int32, False))
    full = _part(ctx4, rng, 8, [3, 8, 0, 5], schema, {"s": ABC})
    empty = ct.Table(
        ctx4,
        OrderedDict(s=Column(
            jax.device_put(np.zeros(4 * 8, np.int32), ctx4.sharding),
            DataType(Type.STRING), None, np.array([], dtype="<U1"),
        )),
        np.zeros(4, np.int64), 8,
    )
    for parts in ([full, empty], [empty, full]):
        out = _table._concat_tables(parts)
        np.testing.assert_array_equal(out.row_counts, full.row_counts)
        np.testing.assert_array_equal(
            out.to_pandas()["s"].to_numpy(), full.to_pandas()["s"].to_numpy()
        )


def test_a_string_column_does_not_concat_with_a_numeric_one(ctx4):
    rng = np.random.default_rng(23)
    a = _part(ctx4, rng, 8, [1, 2, 3, 4], OrderedDict(s=(np.int32, False)),
              {"s": ABC})
    b = _part(ctx4, rng, 8, [1, 2, 3, 4], OrderedDict(s=(np.int32, False)))
    with pytest.raises(ValueError, match="string column"):
        _table._concat_tables([a, b])
    c = _part(ctx4, rng, 8, [1, 2, 3, 4], OrderedDict(t=(np.int32, False)))
    with pytest.raises(ValueError, match="identical schemas"):
        _table._concat_tables([b, c])


def test_more_tables_than_the_fan_in_fold_in_groups(ctx4):
    """``CONCAT_FAN_IN`` bounds the part counts a context compiles for, not
    what a caller may pass: a longer list is folded in order."""
    rng = np.random.default_rng(29)
    k = _table.CONCAT_FAN_IN + 3
    parts = [
        _part(ctx4, rng, 8, rng.integers(0, 9, 4), SUITE) for _ in range(k)
    ]
    _assert_concat(_table._concat_tables(parts), parts)
    programs = {
        key[1] for key in ctx4.__dict__["_jit_cache"]
        if key[0] == "shuffle_reassemble"
    }
    assert max(programs) <= _table.CONCAT_FAN_IN
    assert _table._concat_tables(parts[:1]) is parts[0]


def test_public_concat_takes_the_same_path(ctx4):
    rng = np.random.default_rng(31)
    parts = [
        _part(ctx4, rng, 16, rng.integers(0, 17, 4), SUITE) for _ in range(3)
    ]
    for fn in (ct.Table.concat, _table.concat, _table.merge):
        _assert_concat(fn(parts), parts)
    assert not hasattr(_table, "_concat2")


# ----------------------------------------------------------------------
# a shuffle of K rounds: the rounds' outputs in order, and the counters
# ----------------------------------------------------------------------

#: rows of the shuffled table, and the bucket capacity that makes its
#: exchange take K rounds on four shards (``rng`` seed 42; asserted below)
N_ROWS = 3000
CAP_FOR_ROUNDS = {1: 1024, 2: 128, 4: 64}


def _shuffled(ctx4, kind, k, monkeypatch):
    """One ``kind`` shuffle of K rounds: (result, the parts it reassembled,
    its rollup counters)."""
    rng = np.random.default_rng(42)
    t = ct.Table.from_pydict(
        ctx4,
        {"k": rng.integers(0, 1000, N_ROWS).astype(np.int64),
         "v": rng.normal(size=N_ROWS)},
    )
    budget = 4 * CAP_FOR_ROUNDS[k] * _sh.exchange_row_bytes(t._flat_cols())
    seen = []
    real = _table._concat_tables

    def spy(parts):
        seen.append(list(parts))
        return real(parts)

    monkeypatch.setattr(_table, "_concat_tables", spy)
    reset_trace()
    out = t._shuffle_impl(kind=kind, key_names=["k"], byte_budget=budget)
    rep = {**report("shuffle."), **report("host_sync")}
    assert int(rep["shuffle.rounds"]["rows"]) == k
    return t, out, seen, rep


@pytest.mark.parametrize("kind", ["hash", "range"])
@pytest.mark.parametrize("k", [2, 4])
def test_a_shuffle_of_k_rounds_is_its_rounds_in_order(
    ctx4, kind, k, monkeypatch
):
    t, out, seen, rep = _shuffled(ctx4, kind, k, monkeypatch)
    # one reassembly, of the K rounds' outputs (no relay, no ring here)
    assert len(seen) == 1 and len(seen[0]) == k
    parts = seen[0]
    _assert_concat(out, parts)
    assert int(rep["shuffle.reassemble.parts"]["rows"]) == k
    assert int(rep["shuffle.reassemble.rows"]["rows"]) == N_ROWS
    assert rep["shuffle.reassemble.parts"]["count"] == 1
    # round r holds rows r * cap .. (r + 1) * cap of every bucket, so a
    # round's rows on a shard never outnumber the buckets' capacity
    cap = CAP_FOR_ROUNDS[k]
    assert all((p.row_counts <= 4 * cap).all() for p in parts)
    # the rows are the table's, each on the shard a one-round shuffle puts it
    one = t._shuffle_impl(kind=kind, key_names=["k"], byte_budget=1 << 40)
    np.testing.assert_array_equal(out.row_counts, one.row_counts)
    got, want = _shards(out), _shards(one)
    for s in range(4):
        n = int(out.row_counts[s])
        for name in ("k", "v"):
            np.testing.assert_array_equal(
                np.sort(got[name][0][s, :n]), np.sort(want[name][0][s, :n])
            )


@pytest.mark.parametrize("kind", ["hash", "range"])
def test_a_one_round_shuffle_reassembles_nothing(ctx4, kind, monkeypatch):
    _t, _out, seen, rep = _shuffled(ctx4, kind, 1, monkeypatch)
    assert all(len(parts) == 1 for parts in seen)  # handed straight back
    assert "shuffle.reassemble.parts" not in rep
    assert "shuffle.reassemble.rows" not in rep


@pytest.mark.parametrize("kind", ["hash", "range"])
def test_host_syncs_do_not_grow_with_the_rounds(ctx4, kind, monkeypatch):
    """The reassembly fetches nothing: the counts are the sum of the
    rounds', which the host holds."""
    syncs = [
        _shuffled(ctx4, kind, k, monkeypatch)[3]["host_sync"]["count"]
        for k in (1, 2, 4)
    ]
    assert syncs == [2, 2, 2]


# ----------------------------------------------------------------------
# which front-pack a round's compact dispatch ran (PR 47)
# ----------------------------------------------------------------------

def _compact_counters(run):
    """The ``shuffle.compact.*`` counters and the compact dispatches of
    one call (the span ``shuffle.round.compact`` is one a dispatch)."""
    reset_trace()
    run()
    rep = report("shuffle.")
    return (
        {k: v for k, v in rep.items() if k.startswith("shuffle.compact.")},
        rep["shuffle.round.compact"]["count"],
    )


@pytest.mark.parametrize("op", ["sort", "join", "rounds"])
def test_a_flat_mesh_compacts_by_block_writes_alone(ctx4, op):
    """``shuffle.compact.blocks``: one bump a compact dispatch, ``rows`` the
    chunks it placed (the mesh's size); ``shuffle.compact.by_order`` never."""
    from cylon_tpu.obs import metrics as obs_metrics

    rng = np.random.default_rng(47)
    a, b = (
        ct.Table.from_pydict(
            ctx4,
            {"k": rng.integers(0, 1000, N_ROWS).astype(np.int64),
             name: rng.normal(size=N_ROWS)},
        )
        for name in ("v", "w")
    )
    budget = 4 * CAP_FOR_ROUNDS[4] * _sh.exchange_row_bytes(a._flat_cols())
    run, dispatches = {
        "sort": (lambda: a.distributed_sort("k"), 1),
        "join": (lambda: a.distributed_join(b, on="k", how="inner"), 2),
        "rounds": (lambda: a._shuffle_impl(
            kind="hash", key_names=["k"], byte_budget=budget), 4),
    }[op]
    counters, compacts = _compact_counters(run)
    assert compacts == dispatches
    assert set(counters) == {"shuffle.compact.blocks"}
    assert counters["shuffle.compact.blocks"]["count"] == dispatches
    assert int(counters["shuffle.compact.blocks"]["rows"]) == 4 * dispatches
    for name in ("shuffle.compact.blocks", "shuffle.compact.by_order"):
        assert obs_metrics.is_declared(name)
    assert "shuffle.compact." in obs_metrics.STABLE_METRICS


def test_a_two_hop_receive_still_compacts_by_order(devices):
    """The two-hop receive's chunks are not equal: it keeps the liveness
    sort and the gathers, and says so."""
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:8], mesh_shape="4x2")
    )
    rng = np.random.default_rng(48)
    t = ct.Table.from_pydict(
        ctx,
        {"k": rng.integers(0, 1000, N_ROWS).astype(np.int64),
         "v": rng.normal(size=N_ROWS)},
    )
    counters, compacts = _compact_counters(
        lambda: t._shuffle_impl(kind="hash", key_names=["k"])
    )
    assert int(report("shuffle.")["shuffle.coll_bytes.inter"]["rows"]) > 0
    assert set(counters) == {"shuffle.compact.by_order"}
    assert counters["shuffle.compact.by_order"]["count"] == compacts == 1


# ----------------------------------------------------------------------
# the program: block writes, nothing addressed by the row
# ----------------------------------------------------------------------

def test_the_reassembly_program_holds_no_scatter_and_no_gather():
    """The optimised text for four parts of two 64-bit columns (the shapes
    of ``join-skew-w4`` scaled down; ``tests/test_tpu_compile.py`` compiles
    the real ones for a v5e)."""
    from cylon_tpu.obs import stages

    cap, out_cap = 1 << 12, 1 << 14
    sds = jax.ShapeDtypeStruct
    parts = [
        [(sds((cap,), jnp.int64), None), (sds((cap,), jnp.float64), None)]
        for _ in range(4)
    ]
    counts = [sds((), jnp.int32)] * 4
    text = (
        jax.jit(lambda p, n: _sh.reassemble_blocks(p, n, out_cap))
        .lower(parts, counts).compile().as_text()
    )
    assert not any(w in text for w in ("scatter(", "gather("))
    assert text.count("dynamic-update-slice(") >= 6
    _module, rows = stages.parse_compiled(text)
    named = [op for _t, op in rows if "dynamic_update_slice" in op]
    assert named and all(
        stages.stage_of(op) == stages.SHUFFLE_REASSEMBLE for op in named
    )
