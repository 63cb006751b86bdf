"""Stage names on the device (obs/stages.py): every hot-path kernel wraps
its work in ``jax.named_scope`` with a name of one vocabulary, programs are
named after what they do, and ``device_stage_table()`` maps the
instructions of every dispatched program to their stage, from the compiled
text of the program that ran.

Each test builds its own context: a program registers its spec on the
cache MISS of the context that dispatches it.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import engine
from cylon_tpu import table as _table
from cylon_tpu.obs import stages
from cylon_tpu.utils import tracing

ROWS = 2048
#: opcodes that move rows: each must sit under a stage, fused or not
HEAVY = ("gather", "scatter", "sort", "all-to-all")
_OPCODE = re.compile(r"^\S+ = (?:\([^=]*?\)|\S+) ([a-z][\w\-]*)\(")

#: what each program's compiled text must name (CPU mesh, default impls)
EXPECTED = {
    "join_spec": {
        stages.JOIN_KEY_IDS, stages.JOIN_RIGHT_SORT, stages.JOIN_PROBE,
        stages.JOIN_EMIT, stages.SORT_ENGINE,
    },
    "sort": {
        stages.SORT_KEYS, stages.SORT_PERM, stages.SORT_ENGINE,
    },
    "shuffle_count": {stages.SHUFFLE_COUNT},
    "shuffle_pack": {stages.SHUFFLE_PACK, stages.SORT_ENGINE},
    "shuffle_coll": {stages.SHUFFLE_ALL_TO_ALL},
    "shuffle_compact": {stages.SHUFFLE_COMPACT},
    "semi_sketch": {stages.SEMI_SKETCH},
}


def _ctx(world):
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:world])
    )


def _tables(ctx, seed=3):
    rng = np.random.default_rng(seed)
    a = {"k": rng.integers(0, ROWS, ROWS).astype(np.int64), "v": rng.random(ROWS)}
    b = {"k": rng.integers(0, ROWS, ROWS).astype(np.int64), "w": rng.random(ROWS)}
    return (
        ct.Table.from_numpy(ctx, list(a), list(a.values())),
        ct.Table.from_numpy(ctx, list(b), list(b.values())),
    )


def _run(world, op):
    ctx = _ctx(world)
    ta, tb = _tables(ctx)
    if op == "join":
        assert ta.distributed_join(tb, on="k", how="inner").row_count > 0
    else:
        assert ta.distributed_sort("k").row_count == ROWS
    return ctx


def _instructions(text):
    """(opcode, op_name) of every instruction of an HLO text, fused
    computations included."""
    for line in text.splitlines():
        body = stages._METADATA.sub("", line.strip())
        if body.startswith("ROOT "):
            body = body[5:]
        found = _OPCODE.match(body)
        if found:
            op_name = stages._OP_NAME.search(line)
            yield found[1], op_name[1] if op_name else ""


@pytest.fixture(scope="module", params=[("join", 1), ("join", 8), ("sort", 1), ("sort", 8)],
                ids=lambda p: f"{p[0]}-w{p[1]}")
def dispatched(request):
    op, world = request.param
    ctx = _run(world, op)
    programs = {}
    for _key, fn, spec in stages.dispatched_programs(ctx):
        programs.setdefault(fn.__name__, []).append(
            (fn, spec, stages._compiled_text(fn.lower(*spec)))
        )
    return op, world, ctx, programs


# -- (a) the scopes reach the compiled program -------------------------
def test_vocabulary_names_occur_in_compiled_text(dispatched):
    op, world, _ctx_, programs = dispatched
    must = {"join": ["join_spec"], "sort": ["sort"]}[op]
    if world > 1:
        must += ["shuffle_count", "shuffle_pack", "shuffle_coll", "shuffle_compact"]
    if op == "join" and world > 1:
        must.append("semi_sketch")
    for name in must:
        assert name in programs, (name, sorted(programs))
        for _fn, _spec, text in programs[name]:
            named = {
                part for _opc, path in _instructions(text)
                for part in path.split("/") if part in stages.VOCABULARY
            }
            assert EXPECTED[name] <= named, (name, EXPECTED[name] - named)


def test_every_heavy_instruction_has_a_stage(dispatched):
    _op, _world, _ctx_, programs = dispatched
    checked = 0
    for name in (*EXPECTED, "join_probe", "join_emit", "shuffle_relay"):
        for _fn, _spec, text in programs.get(name, []):
            for opcode, path in _instructions(text):
                if opcode in HEAVY:
                    checked += 1
                    assert stages.stage_of(path), (name, opcode, path)
    assert checked > 0


# -- (b) programs are named after what they do -------------------------
def test_no_hot_path_program_is_called_kern(dispatched):
    _op, _world, _ctx_, programs = dispatched
    assert "kern" not in programs
    for name, entries in programs.items():
        assert re.fullmatch(r"[A-Za-z0-9_]+", name), name
        for _fn, _spec, text in entries:
            module, _rows = stages.parse_compiled(text)
            assert module == f"jit_{name}", (module, name)


@pytest.mark.parametrize("key,name,want", [
    (("join", 0, (0,), "bitonic", "spec"), "join_spec", "join_spec"),
    (("shuffle", "hash", (0,), "pack"), None, "shuffle"),
    (("semi_sketch", ((("int64", False),),), 12), None, "semi_sketch"),
    ((3, "task split/sort"), None, "task_split_sort"),
    ((1, 2), None, "kern"),
])
def test_program_name(key, name, want):
    assert engine.program_name(key, name) == want


# -- (c) the stage table ------------------------------------------------
def test_stage_table_has_rows_for_each_program(dispatched):
    _op, _world, ctx, programs = dispatched
    table = stages.device_stage_table(ctx)
    assert table["stale"] == []
    assert table["programs"] == sum(len(v) for v in programs.values())
    modules = {module for module, _text, _op_name in table["rows"]}
    assert modules == {f"jit_{name}" for name in programs}
    staged = [r for r in table["rows"] if stages.stage_of(r[2])]
    assert staged and all("metadata=" not in text for _m, text, _o in table["rows"])
    # the rows are what can run as an operation: no fused computation's body
    names = [text.split(" = ")[0] for m, text, _o in table["rows"] if m == "jit_sort"]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("world", [1, 4])
def test_stored_spec_lowers_to_the_program_that_ran(world, monkeypatch):
    live = []
    real = stages.register_dispatch

    def keeping(ctx, key, fn, args):
        live.append((fn, args))
        real(ctx, key, fn, args)

    monkeypatch.setattr(stages, "register_dispatch", keeping)
    ctx = _run(world, "join")
    assert len(live) == len(stages.dispatched_programs(ctx)) >= (1 if world == 1 else 6)
    for fn, args in live:
        spec = stages.arg_spec(args)
        assert fn.lower(*spec).as_text() == fn.lower(*args).as_text(), fn.__name__
    if world > 1:
        leaves = [
            x for _fn, args in live for x in jax.tree.leaves(stages.arg_spec(args))
        ]
        assert any(getattr(x, "sharding", None) is not None for x in leaves)
        assert any(getattr(x, "sharding", 0) is None for x in leaves)


@pytest.mark.parametrize("how", ["swapped", "stripped"])
def test_stale_compiled_text_is_reported(how, monkeypatch):
    ctx = _run(1, "sort")
    real = stages._compiled_text

    def old_executable(lowered):
        text = real(lowered)
        if how == "swapped":  # the cache's executable is from before a move
            return text.replace(stages.SORT_PERM, stages.JOIN_EMIT)
        return stages._METADATA.sub("", text)

    monkeypatch.setattr(stages, "_compiled_text", old_executable)
    assert stages.device_stage_table(ctx)["stale"] == ["jit_sort"]
    monkeypatch.setattr(stages, "_compiled_text", real)
    assert stages.device_stage_table(ctx)["stale"] == []


@pytest.mark.parametrize("path,stage,engine_in", [
    ("jit(join_probe)/join.right_sort/sort_engine/jit(argsort)/iota", "join.right_sort", True),
    ("jit(shuffle_pack)/shard_map/shuffle.pack/sort_engine/jit(argsort)/sort", "shuffle.pack", True),
    ("jit(join_spec)/join.emit/gather", "join.emit", False),
    ("jit(join_spec)/join.right_sort/sort_engine/sort", "join.right_sort", True),
    ("jit(f)/sort_engine/sort", "sort_engine", True),
    ("jit(shuffle_pack)/shard_map/shuffle.pack/semi.sketch/gather", "shuffle.pack", False),
    ("jit(shuffle_reassemble)/shard_map/shuffle.reassemble/dynamic_update_slice", "shuffle.reassemble", False),
    ("jit(groupby)/shard_map/groupby.partial/groupby.key_ids/sort_engine/sort", "groupby.partial", True),
    ("jit(groupby)/shard_map/groupby.merge/groupby.segment_sum/add", "groupby.merge", False),
    ("jit(join_replicate)/shard_map/join.replicate/all_gather", "join.replicate", False),
    ("jit(join_replicate)/shard_map/join.replicate/dynamic_update_slice", "join.replicate", False),
    ("jit(join_spec)/concatenate", None, False),
    ("", None, False),
])
def test_outermost_name_is_the_stage(path, stage, engine_in):
    assert stages.stage_of(path) == stage
    assert stages.in_sort_engine(path) is engine_in


def test_vocabulary_is_defined_once():
    assert len(set(stages.VOCABULARY)) == len(stages.VOCABULARY) == 24
    constants = {
        v for k, v in vars(stages).items() if k.isupper() and isinstance(v, str)
    }
    assert constants == set(stages.VOCABULARY)


# -- (d) a warm dispatch pays two clock reads ---------------------------
def test_warm_get_kernel_returns_the_cached_timed_program():
    ctx = _ctx(2)
    key = ("unit_stage_names", 1)

    def build():
        def kern(dp, rep):
            return dp + 1

        return kern

    x = jax.device_put(
        jnp.arange(8, dtype=jnp.int32),
        jax.sharding.NamedSharding(ctx.mesh, jax.sharding.PartitionSpec(ctx.axis_name)),
    )
    first = engine.get_kernel(ctx, key, build)
    cached = ctx.__dict__["_jit_cache"][key + (True, True)]
    assert first is not cached  # the miss registers the first call's spec
    assert stages.dispatched_programs(ctx) == []
    np.testing.assert_array_equal(np.asarray(first(x, ())), np.arange(8) + 1)
    ((_k, fn, spec),) = stages.dispatched_programs(ctx)
    assert fn is cached and spec[0].sharding == x.sharding
    assert engine.get_kernel(ctx, key, build) is cached
    assert engine.get_kernel(ctx, key, build) is cached
    assert isinstance(cached, engine.TimedProgram)
    assert cached.__name__ == "unit_stage_names"


def test_registry_dies_with_its_context():
    import gc
    import weakref

    ctx = _run(1, "sort")
    assert stages.dispatched_programs(ctx)
    ref = weakref.ref(ctx)
    del ctx
    gc.collect()
    assert ref() is None


# -- (e) host events on the device trace's clock -------------------------
def test_span_and_fetch_are_host_events_in_a_profiler_session(tmp_path, local_ctx):
    import glob

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("unit.stage_span", rows=3):
            got = _table._fetch(jnp.arange(4), "to_numpy")
    finally:
        jax.profiler.stop_trace()
    assert got.tolist() == [0, 1, 2, 3]
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = {
        e.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
    }
    assert {"unit.stage_span", "host_sync.to_numpy"} <= names


# -- (f) the replicate route of a distributed join ------------------------
def test_replicate_route_dispatches_its_stage_and_no_shuffle():
    """A LEFT join against a side 1/512 the size on four shards: the
    program ``join_replicate`` names stage ``join.replicate`` on its
    collectives and block writes, the local join's program follows, and
    no shuffle program is dispatched at all."""
    ctx = _ctx(4)
    rng = np.random.default_rng(5)
    big = {"k": rng.integers(0, 6, ROWS).astype(np.int32), "v": rng.random(ROWS)}
    small = {"k": np.arange(4, dtype=np.int32), "w": rng.random(4)}
    out = ct.Table.from_numpy(ctx, list(big), list(big.values())).distributed_join(
        ct.Table.from_numpy(ctx, list(small), list(small.values())),
        on="k", how="left",
    )
    assert out.row_count == ROWS
    programs = {}
    for _key, fn, spec in stages.dispatched_programs(ctx):
        programs[fn.__name__] = stages._compiled_text(fn.lower(*spec))
    assert {"join_replicate", "join_spec"} <= set(programs)
    assert not [n for n in programs if n.startswith("shuffle_")]
    staged = [
        (opcode, stages.stage_of(path))
        for opcode, path in _instructions(programs["join_replicate"])
        if opcode in ("all-gather", "dynamic-update-slice")
    ]
    assert staged and all(s == stages.JOIN_REPLICATE for _o, s in staged), staged
