"""Compiles of the main path for a described TPU v5e, without the chip.

The TPU's compiler is installed beside the CPU backend and compiles for a
topology that is described, not attached. Nothing here runs: a compile that
passes says the chip's compiler takes the program, never that it is right
or fast (``chip_smoke.py`` on the chip says that). These tests guard what
the bring-up established: every program the default path is made of lowers
for v5e, on one chip and on the 2x2 mesh.

Which facts need which shape. What a test reads from the compiled text
(which operations the program has, their operands and stages, the branch a
gather stands in) does not depend on the capacity, and is compiled at
``ROWS``: the suite's sort of 2^20 rows takes the compiler 90 s, of 2^14
rows 17 and of 2^13 rows 3, and the text has the same operations, one for
one. Bytes held against the chip's memory and seconds of compile
are facts of a cell's full shard shape: they are compiled at that shape
(``CELL_ROWS``, or the cell's own capacities), and those that take over a
minute are marked ``slow``, beside a test of the structure at the small
shape that stays in tier-1. The chip guards them on every PR, since a cell
whose program does not fit does not compile (ROADMAP.md, under the tier-1
line, says when a builder owes ``-m slow`` here). A new compile test goes in
at ``ROWS`` unless it asserts bytes.

This is the only file that describes a TPU topology, and it does so only
inside the module-scoped ``topo`` fixture: one process at a time may load
the TPU's library, so the call must not run while any module is imported
(each pytest worker imports every test file) and the compiles run in this
process, never in a child. The process that has described the topology
holds ``/tmp/libtpu_lockfile`` until it exits: a second one that tries
meanwhile is refused ("Internal error when accessing libtpu multi-process
lockfile"), which ``topo`` turns into a skip of every test here. Under
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, which the driver's tier-1 command sets and
ROADMAP.md's own line does not, two processes described it and compiled at
once (tried at PR 42). So this stays ONE file, which ``--dist loadfile``
keeps on one worker: split by program it would skip in all workers but one
wherever the variable is not set.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from cylon_tpu.engine import on_mesh
from cylon_tpu.ops import groupby as _g
from cylon_tpu.ops import join as _j
from cylon_tpu.ops import partition as _p
from cylon_tpu.ops import sort as _sort
from cylon_tpu.parallel import shuffle as _sh
from cylon_tpu.parallel.pipeline import make_distributed_join_step

#: rows (a shard, in a program over the mesh) of the compiles that read the
#: program's text: the smallest capacity that keeps every stage of the whole
#: programs (sorts, scans, gathers, collectives; at 2^10 the skewed join's
#: build side is 32 slots and its gathers are others). Four digits at least:
#: ``_assert_pack_rides_a_sort`` tells a row-sized scatter from a
#: ``[WORLD]``-sized one by its digits
ROWS = 1 << 13
#: rows of the compiles that assert bytes or seconds, which are a cell's
#: shard's and not a small shape's
CELL_ROWS = 1 << 20
WORLD = 4


@pytest.fixture(scope="module")
def topo():
    """The described v5e 2x2 host, with the persistent compile cache off:
    a compile for described devices is written to the cache but cannot be
    read back without a chip, so the next run would warn and recompile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to guard
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh1(topo):
    return Mesh(np.array(topo.devices[:1]), ("dp",))


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:WORLD]), ("dp",))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    """Compile ``fn`` for the described devices its specs are placed on,
    traced as a kernel of a mesh of them is (``engine.on_mesh``): what
    follows the MESH's platform and not the process's, the row gather's
    float64 lanes for one, takes its TPU form here as it does on the chip."""
    dev = min(
        jax.tree_util.tree_leaves(specs)[0].sharding.device_set,
        key=lambda d: d.id,
    )
    traced = on_mesh(Mesh(np.array([dev]), ("traced",)), fn)
    return jax.jit(traced).lower(*specs).compile()


# ----------------------------------------------------------------------
# (b) the XLA pack chain the default shuffle sends with
# ----------------------------------------------------------------------

def test_xla_pack_chain_compiles_for_tpu(one_chip):
    bc = ROWS // WORLD

    def pack(key, n, rnd):
        pid = _p.hash_partition_ids([(key, None)], n, WORLD)
        cnt = _sh.bucket_counts(pid, WORLD)
        dest, leftover = _sh.build_send_slots_round(pid, cnt, WORLD, bc, rnd)
        return dest, cnt, leftover

    compiled = _compile(
        pack,
        _spec((ROWS,), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip),
    )
    # the default pack is plain XLA: no Pallas kernel, interpreted or not
    assert "tpu_custom_call" not in compiled.as_text()


def _suite_pack(key, val, n, rnd):
    """One shard's pack at the suite's widths (int64 key, float64 value,
    x64 on) as ``build_pack`` makes it on the default path."""
    from cylon_tpu.ops import gather as _gather

    pid = _p.hash_partition_ids([(key, None)], n, WORLD)
    cnt = _sh.bucket_counts(pid, WORLD)
    _plan, lanes, passthrough = _gather.pack_cols([(key, None), (val, None)])
    return _sh.pack_by_sort(
        lanes, [passthrough[1]], pid, cnt, WORLD, ROWS // WORLD, rnd
    )


def _assert_pack_rides_a_sort(text, rows):
    """The compiled pack of ``rows`` rows a shard: nothing scatters or
    gathers an array of the rows' or the send buffers' size (the bucket
    counts scatter into ``[WORLD]``), and one stable sort under
    ``sort_engine`` within ``shuffle.pack`` carries the key's two lanes
    and the value's two halves behind the partition ids."""
    from cylon_tpu.obs import stages

    moved = re.findall(r"= (\S+) (?:scatter|gather)\(", text)
    assert all(re.match(r"\w+\[\d{1,3}\]", shape) for shape in moved), moved
    _module, parsed = stages.parse_compiled(text)
    sorts = [(t, op) for t, op in parsed if re.search(r"\ssort\(", t)]
    assert len(sorts) == 1, sorts
    sort_text, op_name = sorts[0]
    assert stages.in_sort_engine(op_name), op_name
    assert stages.stage_of(op_name) == stages.SHUFFLE_PACK, op_name
    shape = sort_text.split(" sort(")[0]
    assert shape.count(f"[{rows}]") >= 5 and shape.count(f"f32[{rows}]") == 2, shape


def test_sorted_pack_compiles_for_tpu_without_a_row_sized_scatter(one_chip):
    compiled = _compile(
        _suite_pack,
        _spec((ROWS,), jnp.int64, one_chip),
        _spec((ROWS,), jnp.float64, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip),
    )
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    _assert_pack_rides_a_sort(text, ROWS)
    # the scatter chain held the slots, the order and both buffers' updates,
    # 16 bytes a row and more; the sort holds no temporary of the rows' size:
    # under 4 bytes a row above its scratch, which does not grow with the rows
    # (387,072 bytes at every capacity from 2^13 to 2^20)
    assert compiled.memory_analysis().temp_size_in_bytes < (3 << 17) + 4 * ROWS


def test_sorted_pack_compiles_inside_the_four_chip_shard_map(mesh4):
    rows = NamedSharding(mesh4, PartitionSpec("dp"))

    def kern(key, val, counts, rnd):
        return _suite_pack(key, val, counts[0], rnd[0])

    step = jax.jit(jax.shard_map(
        kern, mesh=mesh4, in_specs=PartitionSpec("dp"),
        out_specs=PartitionSpec("dp"),
    ))
    compiled = step.lower(
        _spec((WORLD * ROWS,), jnp.int64, rows),
        _spec((WORLD * ROWS,), jnp.float64, rows),
        _spec((WORLD,), jnp.int32, rows),
        _spec((WORLD,), jnp.int32, rows),
    ).compile()
    _assert_pack_rides_a_sort(compiled.as_text(), ROWS)


# ----------------------------------------------------------------------
# (b2) the reassembly of a shuffle's rounds: four parts of 2^21 slots of
# the suite's two 64-bit columns into 2^23 (``join-skew-w4``'s shapes)
# ----------------------------------------------------------------------

def test_reassembly_compiles_for_tpu_as_block_writes(one_chip):
    from cylon_tpu.obs import stages

    cap, parts, out_cap = 1 << 21, 4, 1 << 23

    def reassemble(blocks, counts):
        return _sh.reassemble_blocks(blocks, counts, out_cap)

    compiled = _compile(
        reassemble,
        [
            [(_spec((cap,), jnp.int64, one_chip), None),
             (_spec((cap,), jnp.float64, one_chip), None)]
        ] * parts,
        [_spec((), jnp.int32, one_chip)] * parts,
    )
    text = compiled.as_text()
    # nothing is addressed by the row: no scatter and no gather of any size
    assert not re.search(r"\s(scatter|gather)\(", text)
    _module, rows = stages.parse_compiled(text)
    writes = [op for t, op in rows if " dynamic-update-slice(" in t]
    # a 64-bit column is two 32-bit lanes; the first part's offset is 0
    assert len(writes) == 2 * 2 * (parts - 1), len(writes)
    assert all(
        stages.stage_of(op) == stages.SHUFFLE_REASSEMBLE for op in writes
    )
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * mem.output_size_in_bytes


# ----------------------------------------------------------------------
# (b3) the receive side of a one-hop round: four chunks of 2^19 slots of a
# two-lane matrix and a float64 passthrough column (``join-skew-w4``'s and
# ``h2o-q5-w4``'s 2^21 received slots)
# ----------------------------------------------------------------------

def test_one_hop_compact_compiles_for_tpu_as_block_writes(one_chip):
    from cylon_tpu.obs import stages
    from cylon_tpu.ops import gather as _g

    bc = 1 << 19
    plan = _g.lane_plan([
        (_spec((8,), jnp.int64, one_chip), None),
        (_spec((8,), jnp.float64, one_chip), None),
    ])

    def compact(head, pt):
        lane_rows, recv_counts = _sh.split_header(head, WORLD)
        return _sh.compact_received_lanes(
            list(plan), lane_rows, {1: pt}, _sh.chunk_front(recv_counts)
        )

    compiled = _compile(
        compact,
        _spec((WORLD * (bc + _sh.HEADER_ROWS), 2), jnp.int32, one_chip),
        _spec((WORLD * bc,), jnp.float64, one_chip),
    )
    text = compiled.as_text()
    # nothing is sorted and nothing is addressed by the row
    assert not re.search(r"\s(sort|scatter|gather)\(", text)
    _module, rows = stages.parse_compiled(text)
    # (the compiler's own loops for ``split_header``'s reshape of an odd
    # row count write blocks too, under no name)
    writes = [
        op for t, op in rows
        if " dynamic-update-slice(" in t and "dynamic_update_slice" in op
    ]
    # the lane matrix, and the float64 column's two 32-bit halves; the
    # first chunk lies where it belongs
    assert len(writes) == (1 + 2) * (WORLD - 1), len(writes)
    assert all(
        stages.stage_of(op) == stages.SHUFFLE_COMPACT for op in writes
    )
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * mem.output_size_in_bytes


# ----------------------------------------------------------------------
# (c) the sort engine
# ----------------------------------------------------------------------

def test_bitonic_lexsort_compiles_for_tpu(one_chip):
    def lexsort(key):
        return _sort.lexsort_indices([key], ROWS)

    _compile(lexsort, _spec((ROWS,), jnp.int32, one_chip))


def _wide_ops(compiled):
    """``(shape, opcode)`` of the compiled program's operations that write
    a ``[ROWS]`` array (a tuple shape for a sort), and its stage rows."""
    from cylon_tpu.obs import stages

    _module, rows = stages.parse_compiled(compiled.as_text())
    parsed = [
        re.match(r"%\S+ = (\(.*?\)|\S+) ([\w-]+)\(", text) for text, _op in rows
    ]
    return [(m[1], m[2]) for m in parsed if m and f"[{ROWS}" in m[1]], rows


def test_suite_sort_compiles_with_its_64bit_columns_riding(one_chip):
    """``sort-w1``'s own program shape (the int64 key fused with the
    padding class into one sort word, the int64 key and the float64 value
    as payloads, x64 on): one sort whose operands are the word and the two
    columns' four 32-bit halves, and nothing gathers or scatters a row."""
    fuse = _sort.plan_lane_fusion(
        [("i64", 22, False, True)], pad_bits=2, prefix_bits=0, allow64=True
    )
    assert fuse is not None and fuse.n_words == 1

    def sort_rows(key, val, n):
        return _sort.lexsort_rows_payload(
            [(key, None)], n, ROWS, [key, val], fuse=fuse
        )

    compiled = _compile(
        sort_rows,
        _spec((ROWS,), jnp.int64, one_chip),
        _spec((ROWS,), jnp.float64, one_chip),
        _spec((), jnp.int32, one_chip),
    )
    # fused or not, no instruction of the program gathers or scatters
    assert not re.search(r"\s(gather|scatter)\(", compiled.as_text())
    wide, _rows = _wide_ops(compiled)
    sorts = [shape for shape, opcode in wide if opcode == "sort"]
    assert len(sorts) == 1 and sorts[0].count("f32[") == 2, sorts
    assert sorts[0].count("32[") >= 5, sorts  # the word and four halves


def test_join_right_sort_compiles_with_its_64bit_columns_riding(one_chip):
    """The speculative join's right side at the suite's widths (int64 key,
    float64 value): one stable sort keyed by the ids carries both columns,
    and no gather by an order is left."""
    compiled = _compile(
        lambda ids, key, val: _j.ride_right(ids, [(key, None), (val, None)]),
        _spec((ROWS,), jnp.int32, one_chip),
        _spec((ROWS,), jnp.int64, one_chip),
        _spec((ROWS,), jnp.float64, one_chip),
    )
    assert not re.search(r"\s(gather|scatter)\(", compiled.as_text())
    wide, _rows = _wide_ops(compiled)
    sorts = [shape for shape, opcode in wide if opcode == "sort"]
    assert len(sorts) == 1 and sorts[0].count("f32[") == 2, sorts


def test_packed_gather_of_a_skewed_joins_slots_fits_the_chip(
    one_chip, monkeypatch
):
    """The emit's packed gathers at the 2^24 output slots that the fullest
    shard of a skewed join of 32,000,000 rows (twice ``join-skew-w4``'s)
    gives every chip, out of the 2^18-slot build side. A gathered [rows, L]
    matrix is laid out with L padded to 128 lanes, 8 GiB here, and the whole
    join program was refused (16.13 GB of 15.75); in blocks of
    ``PACK_GATHER_BLOCK`` rows it keeps one block's."""
    from cylon_tpu.ops import gather as _gather

    def temp_gib():
        def emit(key, val, base, cnt, idx):
            return _gather.pack_gather(
                [(key, None), (val, None)], idx, extra_lanes=[base, cnt]
            )

        side = 1 << 18
        compiled = _compile(
            emit,
            _spec((side,), jnp.int64, one_chip),
            _spec((side,), jnp.float64, one_chip),
            _spec((side,), jnp.int32, one_chip),
            _spec((side,), jnp.int32, one_chip),
            _spec((1 << 24,), jnp.int32, one_chip),
        )
        return compiled.memory_analysis().temp_size_in_bytes / 2**30

    assert temp_gib() < 5.0
    monkeypatch.setattr(_gather, "PACK_GATHER_BLOCK", 1 << 25)
    assert temp_gib() > 7.5  # what one gather of all the rows would hold


def _skew_join(one_chip, cap_l, cap_r, cap_out):
    """The whole ``jit_join_spec`` of ``join-skew-w4``'s columns (int64 key
    and float64 value a side), compiled at the given probe, build and output
    slots."""

    def join(lk, lv, rk, rv, nl, nr):
        left, right = [(lk, None), (lv, None)], [(rk, None), (rv, None)]
        return _j.spec_join(
            left[:1], right[:1], left, right, nl, nr, _j.INNER, cap_out
        )

    return _compile(
        join,
        _spec((cap_l,), jnp.int64, one_chip),
        _spec((cap_l,), jnp.float64, one_chip),
        _spec((cap_r,), jnp.int64, one_chip),
        _spec((cap_r,), jnp.float64, one_chip),
        _spec((), jnp.int32, one_chip), _spec((), jnp.int32, one_chip),
    )


def _assert_float64_rides_the_packed_gathers(text, cap_out):
    """The float64 halves are lanes of the emit's two packed gathers,
    ``s32[cap_out, 6]`` and ``s32[cap_out, 4]``; the halves' lone
    ``f32[cap_out]`` gathers stand only in the branch the guard takes for a
    table that holds a value whose low half the split would lose
    (``ops.gather._f64_low_half_may_flush``). The left gather with its
    guard, and the run expansion's scatter, stand inside the gather branch
    (0) of the emit's own ``cond`` (``ops.join._emit_inner_left``): a join
    that emits every left row once runs neither."""
    # (shape, the path of the emit's scope it was traced on, the branch of
    # the guard's cond, the innermost: 0 packed, 1 the lone gathers) of
    # every gather
    gathers = re.findall(
        r"= (\S+?\[[0-9,]*\])\S* gather\(.*?op_name=\"[^\"]*?join\.emit/"
        r"([^\"]*cond/branch_(\d)_fun)/gather\"", text,
    )
    packed = sorted(shape for shape, _path, branch in gathers if branch == "0")
    assert packed == [f"s32[{cap_out},4]", f"s32[{cap_out},6]"], gathers
    lone = [shape for shape, _path, branch in gathers if branch == "1"]
    assert lone.count(f"f32[{cap_out}]") == 4, gathers
    assert len(gathers) == len(
        re.findall(r"\sgather\(", text)
    ), "a gather outside the guard's branches"
    # the left side's gathers are the guard's inside the emit's branch 0,
    # the right side's the guard's alone, after the emit's cond
    left = [shape for shape, path, _b in gathers if path.count("cond/") == 2]
    assert sorted(left) == sorted(
        [f"s32[{cap_out},6]", f"s32[{cap_out},4]"] + [f"f32[{cap_out}]"] * 2
    ), gathers
    assert all(
        path.startswith("cond/branch_0_fun/")
        for _s, path, _b in gathers if path.count("cond/") == 2
    ), gathers
    scatters = re.findall(
        r"= \S+ scatter\(.*?op_name=\"[^\"]*?join\.emit/([^\"]*)\"", text
    )
    assert scatters == ["cond/branch_0_fun/scatter"], scatters


def test_skewed_joins_program_gathers_its_float64_packed(one_chip):
    """Which gathers the join's program holds, and in which branch, does not
    depend on the capacity: here at ``join-skew-w4``'s proportions (a build
    side a thirty-second of the probe side and of the output)."""
    compiled = _skew_join(one_chip, ROWS, ROWS >> 5, ROWS)
    _assert_float64_rides_the_packed_gathers(compiled.as_text(), ROWS)


@pytest.mark.slow  # 150-210 s in this sandbox; the cell itself guards it
@pytest.mark.limit(900)
def test_skewed_joins_program_fits_the_chip_with_its_float64_packed(one_chip):
    """At ``join-skew-w4``'s shard shapes (2^23 probe slots and output
    slots, 2^18 build slots) the program with both of the guard's branches
    fits a v5e's 15.75 GB (PERF.md section 6, PR 40). On the chip the cell
    does not compile where it does not fit."""
    cap_out = 1 << 23
    compiled = _skew_join(one_chip, 1 << 23, 1 << 18, cap_out)
    _assert_float64_rides_the_packed_gathers(compiled.as_text(), cap_out)
    mem = compiled.memory_analysis()
    held = (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes
    )
    assert held < 15.75e9 * 0.75, held / 1e9


# ----------------------------------------------------------------------
# (c3) the replicate route of a distributed join, in ``h2o-join-q3-w4``'s
# shapes: the gather of the small side over the four-chip mesh, and the
# LEFT OUTER join of a shard of x against the whole of medium
# ----------------------------------------------------------------------

def _h2o_left_join(one_chip, cap_l, cap_r):
    """``jit_join_spec`` of question 3's columns at the source's widths
    (x: six int32 lanes, three ids and their factor twins' codes, and a
    float64; medium: four int32 lanes and a float64), LEFT, on ``id2``."""

    def join(l1, l2, l3, l4, l5, l6, lv, r1, r2, r4, r5, rv, nl, nr):
        left = [(c, None) for c in (l1, l2, l3, l4, l5, l6, lv)]
        right = [(c, None) for c in (r1, r2, r4, r5, rv)]
        return _j.spec_join(
            left[1:2], right[1:2], left, right, nl, nr, _j.LEFT, cap_l,
        )

    i32l = _spec((cap_l,), jnp.int32, one_chip)
    i32r = _spec((cap_r,), jnp.int32, one_chip)
    n = _spec((), jnp.int32, one_chip)
    return _compile(
        join, *[i32l] * 6, _spec((cap_l,), jnp.float64, one_chip),
        *[i32r] * 4, _spec((cap_r,), jnp.float64, one_chip), n, n,
    )


def test_replicate_route_compiles_for_four_chips(mesh4):
    """``join_replicate`` over the 2x2 mesh, medium's five columns: one
    all-gather a lane (a float64 as the chip holds it) and one of the
    counts, the chunks front-packed by block writes, every row-sized
    operation under stage ``join.replicate``, and nothing gathers or
    scatters a row on its own."""
    from cylon_tpu.obs import stages

    rows = NamedSharding(mesh4, PartitionSpec("dp"))
    cap = ROWS >> 2

    def kern(k1, k2, k4, k5, v, counts):
        return _sh.replicate_cols(
            [(c, None) for c in (k1, k2, k4, k5, v)], counts, "dp",
            WORLD * cap,
        )

    step = jax.jit(jax.shard_map(
        kern, mesh=mesh4, in_specs=PartitionSpec("dp"),
        out_specs=PartitionSpec("dp"),
    ))
    compiled = step.lower(
        *[_spec((WORLD * cap,), jnp.int32, rows)] * 4,
        _spec((WORLD * cap,), jnp.float64, rows),
        _spec((WORLD,), jnp.int32, rows),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"\sall-gather(?:-start)?\(", text)) >= 2
    assert "all-to-all" not in text
    assert not re.search(r"\s(gather|scatter|sort)\(", text)
    wide, rows_ = _wide_ops(compiled)
    staged = [op for _text, op in rows_ if stages.JOIN_REPLICATE in op]
    assert staged and all(
        stages.stage_of(op) == stages.JOIN_REPLICATE for op in staged
    )


def test_left_outer_join_compiles_with_validity_on_the_right_alone(one_chip):
    """Question 3's local join at the small shape (a build side 1/256 of
    the probe side): the chip's compiler takes the LEFT emit, the right
    side's five columns come out with a validity lane each and the left
    side's seven with none."""
    compiled = _h2o_left_join(one_chip, ROWS, ROWS >> 8)
    out, _total, _shadow, _handed = compiled.out_info
    assert [v is None for _d, v in out] == [True] * 7 + [False] * 5
    assert all(v.shape == (ROWS,) and v.dtype == jnp.bool_
               for _d, v in out[7:])
    assert [d.dtype for d, _v in out] == (
        [jnp.int32] * 6 + [jnp.float64] + [jnp.int32] * 4 + [jnp.float64])
    # the probe's merged sort and the build side's ride sort; the emit's
    # packed gathers write the output's slots
    text = compiled.as_text()
    assert len(re.findall(r"\ssort\(", text)) >= 2
    wide, _rows = _wide_ops(compiled)
    assert [shape for shape, opcode in wide if "gather" in opcode or opcode == "fusion"]


@pytest.mark.slow  # a minute or two in this sandbox; the cell itself guards it
@pytest.mark.limit(1800)
def test_left_outer_join_fits_the_chip_at_the_cells_shapes(one_chip):
    """At ``h2o-join-q3-w4``'s shard shapes (2^25 probe and output slots of
    the source's seven and five columns, 2^17 build slots) the LEFT join's
    program fits a v5e's 15.75 GB, x's 1.07 GB resident counted among its
    arguments (PERF.md section 6, PR 48). On the chip the cell does not
    compile where it does not fit."""
    compiled = _h2o_left_join(one_chip, 1 << 25, 1 << 17)
    mem = compiled.memory_analysis()
    held = (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes
    )
    assert held < 15.75e9 * 0.9, held / 1e9


# ----------------------------------------------------------------------
# (d), (e) the local sort join at both dtype widths; (g) the distributed
# join step on the four-chip mesh
# ----------------------------------------------------------------------

def _join_step_specs(mesh, key_dtype, val_dtype):
    world = mesh.size
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    cols = [
        (_spec((world * ROWS,), key_dtype, rows), None),
        (_spec((world * ROWS,), val_dtype, rows), None),
    ]
    counts = _spec((world,), jnp.int32, rows)
    return (cols, counts, cols, counts), ()

def test_semi_reduction_compiles_with_two_sorts_and_no_payload(one_chip):
    """The keys-only program in front of a selective join at TPC-H's key
    width (int32 keys, a mask on the probe side): two sorts, one of the
    merged ids with the row's position as its second key and one of a
    single operand, neither stable (a stable sort would carry an iota),
    and nothing gathers or scatters a row."""

    def semi(lk, rk, nl, nr, r_mask):
        l_ids, r_ids = _j._canonical_ids(
            [(lk, None)], [(rk, None)], nl, nr, ROWS // 8, ROWS
        )
        l_live = jnp.arange(ROWS // 8, dtype=jnp.int32) < nl
        r_live = (jnp.arange(ROWS, dtype=jnp.int32) < nr) & r_mask
        return _j.semi_hits(l_ids, r_ids, l_live, r_live)

    compiled = _compile(
        semi,
        _spec((ROWS // 8,), jnp.int32, one_chip),
        _spec((ROWS,), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip), _spec((), jnp.int32, one_chip),
        _spec((ROWS,), jnp.bool_, one_chip),
    )
    text = compiled.as_text()
    assert not re.search(r"\s(gather|scatter)\(", text)
    sorts = re.findall(r"= (\(.*?\)|\S+) sort\(", text)
    assert len(sorts) == 2, sorts
    merged = ROWS + ROWS // 8
    assert sorted(s.count(f"[{merged}]") for s in sorts) == [1, 2], sorts
    assert "is_stable=true" not in text


def test_semi_reduced_sides_gather_at_their_own_capacity(one_chip):
    """``jit_join_reduce``: the rows with a partner are gathered at the
    capacities the host chose from their counts (here an eighth and a
    sixty-fourth of the probe side's), not at the inputs'."""
    cap_lo, cap_ro = ROWS // 64, ROWS // 8

    def reduce(hits, stats, lkey, rkey, rval):
        return _j.reduce_by_hits(
            hits, stats, [(lkey, None)], [(rkey, None), (rval, None)],
            cap_lo, cap_ro,
        )

    compiled = _compile(
        reduce,
        _spec((ROWS + ROWS // 8,), jnp.int32, one_chip),
        _spec((4,), jnp.int32, one_chip),
        _spec((ROWS // 8,), jnp.int32, one_chip),
        _spec((ROWS,), jnp.int32, one_chip),
        _spec((ROWS,), jnp.float64, one_chip),
    )
    text = compiled.as_text()
    assert not re.search(r"\ssort\(", text)
    gathers = re.findall(r"= (\S+) gather\(", text)
    assert gathers and all(
        f"[{cap_lo}" in g or f"[{cap_ro}" in g for g in gathers
    ), gathers



@pytest.mark.parametrize(
    "key_dtype,val_dtype",
    [(jnp.int32, jnp.float32), (jnp.int64, jnp.float64)],
    ids=["int32-float32", "int64-float64"],
)
def test_local_sort_join_compiles_for_tpu(mesh1, key_dtype, val_dtype):
    step = make_distributed_join_step(
        mesh1, "dp", (0,), (0,), _j.INNER,
        bucket_cap=ROWS, join_cap=2 * ROWS,
    )
    compiled = step.lower(*_join_step_specs(mesh1, key_dtype, val_dtype)).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_distributed_join_step_compiles_for_four_chips(mesh4):
    # a send bucket of what a shard's rows, spread evenly, send one chip
    step = make_distributed_join_step(
        mesh4, "dp", (0,), (0,), _j.INNER,
        bucket_cap=ROWS // WORLD, join_cap=2 * ROWS,
    )
    compiled = step.lower(
        *_join_step_specs(mesh4, jnp.int32, jnp.float32)
    ).compile()
    text = compiled.as_text()
    assert "all-to-all" in text  # the rows cross chips
    assert "tpu_custom_call" not in text
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < 16 * 2**30  # a v5e chip's HBM


def test_range_partition_compiles_for_four_chips(mesh4):
    """The sample-sort's partitioner (distributed_sort, world > 1): its
    extrema cross chips as float64 under x64, and the TPU compiler lowers
    a 64-bit all-reduce only for sums — pmin/pmax there were refused."""
    rows = NamedSharding(mesh4, PartitionSpec("dp"))

    def kern(val, counts):
        return _p.range_partition_ids(
            (val, None), counts[0], WORLD, axis_name="dp"
        )

    step = jax.jit(jax.shard_map(
        kern, mesh=mesh4, in_specs=PartitionSpec("dp"),
        out_specs=PartitionSpec("dp"),
    ))
    step.lower(
        _spec((WORLD * ROWS,), jnp.float32, rows),
        _spec((WORLD,), jnp.int32, rows),
    ).compile()


def _custom_fusions(text):
    """``(shape, op_name)`` of the compiled program's ``kCustom`` fusions:
    what a scatter or a gather becomes on a TPU."""
    return [
        (m[1], m[2])
        for m in re.finditer(
            r"= (\(.*?\)|\S+) fusion\([^\n]*kind=kCustom[^\n]*?"
            r'op_name="([^"]*)"',
            text,
        )
    ]


def _range_count_text(mesh4, num_bins):
    """``sort-w4``'s count: the range partition ids of an int64 key (x64
    on), ``ROWS`` rows a shard, then the bucket counts."""
    rows = NamedSharding(mesh4, PartitionSpec("dp"))

    def kern(key, counts):
        pid = _p.range_partition_ids(
            (key, None), counts[0], WORLD, num_bins=num_bins, axis_name="dp"
        )
        return pid, _sh.bucket_counts(pid, WORLD)

    step = jax.jit(jax.shard_map(
        kern, mesh=mesh4, in_specs=PartitionSpec("dp"),
        out_specs=PartitionSpec("dp"),
    ))
    return step.lower(
        _spec((WORLD * ROWS,), jnp.int64, rows),
        _spec((WORLD,), jnp.int32, rows),
    ).compile().as_text()


def test_range_count_compiles_for_four_chips_without_a_scatter(mesh4):
    """The histogram of 64 bins and the count into four partitions are a
    compare and a sum: no scatter-add of every row (the int64 histogram was
    a ``(u32[64], u32[64])`` variadic one, 62.5 ms a call on a v5e; the
    bucket counts an ``s32[4]`` one, 9.15 ms), and the bin-to-partition
    lookup of 64 entries is no gather either."""
    text = _range_count_text(mesh4, None)
    assert not re.search(r"\s(scatter|gather)\(", text)
    assert _custom_fusions(text) == []


def test_hash_count_compiles_for_tpu_without_a_scatter(one_chip):
    """``join-w4``'s count: the id hash of an int64 key, then the bucket
    counts; under the semi filter the filtered ids are counted too."""
    def count(key, keep, n):
        pid = _p.hash_partition_ids([(key, None)], n, WORLD)
        pid_f = jnp.where(keep, pid, WORLD)
        return jnp.concatenate(
            [_sh.bucket_counts(pid, WORLD), _sh.bucket_counts(pid_f, WORLD)]
        )

    text = _compile(
        count,
        _spec((ROWS,), jnp.int64, one_chip),
        _spec((ROWS,), jnp.bool_, one_chip),
        _spec((), jnp.int32, one_chip),
    ).as_text()
    assert not re.search(r"\s(scatter|gather)\(", text)
    assert _custom_fusions(text) == []


def test_a_histogram_past_the_dense_bound_scatters_in_int32(mesh4):
    """Past ``DENSE_BINS_MAX`` bins the rows are scatter-added, into int32
    and never into the two halves of an int64."""
    text = _range_count_text(mesh4, _p.DENSE_BINS_MAX + 1)
    adds = [
        shape for shape, op in _custom_fusions(text)
        if op.endswith("scatter-add")
    ]
    assert adds and all(
        re.fullmatch(rf"s32\[{_p.DENSE_BINS_MAX + 1}\]\S*", a) for a in adds
    ), adds


# ----------------------------------------------------------------------
# (f) group-by: factorize + segment-sum
# ----------------------------------------------------------------------

def test_groupby_segment_sum_compiles_for_tpu(one_chip):
    def groupby_sum(key, val, n):
        _keys, ((out, _valid),), n_groups = _g.groupby_aggregate(
            [(key, None)], [(val, None)], [(_g.agg_op_id("sum"), 0)], n, ROWS
        )
        return out, n_groups

    _compile(
        groupby_sum,
        _spec((ROWS,), jnp.int32, one_chip),
        _spec((ROWS,), jnp.float32, one_chip),
        _spec((), jnp.int32, one_chip),
    )


def test_groupby_cell_shape_compiles_without_scatter(one_chip):
    """``groupby-w1``'s own shape (int64 key fused into one sort word,
    float64 value, x64 on): the aggregates ride the factorize sort and
    the run heads move by selects, so no operation that writes a
    row-sized array scatters and at most one gathers, and both stages
    name their operations. The first answer,
    without a chip, to whether a 64-bit operand rides ``jax.lax.sort`` on
    a v5e: it compiles, as its two 32-bit halves."""
    from cylon_tpu.obs import stages

    fuse = _sort.plan_lane_fusion(
        [("i64", 22, False, True)], pad_bits=1, prefix_bits=0, allow64=True
    )
    assert fuse is not None and fuse.n_words == 1

    def groupby_sum(key, val, n):
        return _g.groupby_aggregate(
            [(key, None)], [(val, None)], [(_g.agg_op_id("sum"), 0)], n, ROWS,
            fuse=fuse,
        )

    compiled = _compile(
        groupby_sum,
        _spec((ROWS,), jnp.int64, one_chip),
        _spec((ROWS,), jnp.float64, one_chip),
        _spec((), jnp.int32, one_chip),
    )
    wide, rows = _wide_ops(compiled)
    assert wide
    assert not [shape for shape, opcode in wide if "scatter" in opcode]
    assert len([shape for shape, opcode in wide if "gather" in opcode]) <= 1
    # ONE sort, the factorize sort (the run heads reach their slots by
    # ``step_compact``'s moves); a 64-bit operand rides it as two 32-bit
    sorts = [shape for shape, opcode in wide if opcode == "sort"]
    assert len(sorts) == 1 and sorts[0].count("f32[") >= 2
    for name in (stages.GROUPBY_KEY_IDS, stages.GROUPBY_SEGMENT_SUM):
        assert any(name in op.split("/") for _text, op in rows), name


@pytest.mark.slow  # two to three minutes a case in this sandbox
@pytest.mark.limit(1200)  # its own bound on the compile is 900 s
@pytest.mark.parametrize("columns", [16, 32])
def test_groupby_of_many_float64_columns_compiles_in_bounded_time(
    one_chip, columns
):
    """Compile time does not grow with the aggregates: past
    ``ops.sort.RIDE_LANES`` lanes the payloads ride the factorize sort in
    batches under one ``jax.lax.map``, so the program holds the same few
    sorts at 16 float64 sums as at 32 (unbatched, 8 sums took 382 s here
    and the time grew faster than the columns; PERF.md section 6, PR 28),
    and the run heads' moves are selects over every lane at once, which
    the compiler takes in seconds (PR 46)."""
    import time

    fuse = _sort.plan_lane_fusion(
        [("i64", 22, False, True)], pad_bits=1, prefix_bits=0, allow64=True
    )

    def groupby_sums(key, vals, n):
        return _g.groupby_aggregate(
            [(key, None)], [(v, None) for v in vals],
            [(_g.agg_op_id("sum"), j) for j in range(columns)], n, CELL_ROWS,
            fuse=fuse,
        )

    t0 = time.monotonic()
    compiled = _compile(
        groupby_sums,
        _spec((CELL_ROWS,), jnp.int64, one_chip),
        [_spec((CELL_ROWS,), jnp.float64, one_chip)] * columns,
        _spec((), jnp.int32, one_chip),
    )
    seconds = time.monotonic() - t0
    text = compiled.as_text()
    # the keys' own sort and one sort a stack of batches
    assert len(re.findall(r" sort\(", text)) <= 3
    assert "scatter(" not in text
    assert seconds < 900, f"{columns} columns compiled in {seconds:.0f} s"


# ----------------------------------------------------------------------
# (f2) group-by: the dense low-cardinality path (TPC-H Q1's shape: two
# dictionary keys of 3 and 2 codes, float64 values, a row mask)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
def test_groupby_pre_combine_and_combine_compile_for_tpu(one_chip, masked):
    """The two programs either side of a group-by's exchange of partial
    rows, in ``h2o-q5-w4``'s shape (int32 id fused into one sort word,
    int32 and float64 values, x64 on) with the once-a-run state (a count
    beside a sum, a mean as its sum and count): both take the chip's
    compiler, stay in sorted space (one sort, no row-sized scatter), and
    carry their stage outermost with the sort-and-segment stages inside.
    A row mask rides the pre-combine's sort as padding: one more operand
    class, no gather in front."""
    from cylon_tpu.obs import stages

    fuse = _sort.plan_lane_fusion(
        [("i32", 23, False, True)], pad_bits=1, prefix_bits=0, allow64=True
    )
    assert fuse is not None and fuse.n_words == 1
    ops = [(_g.SUM, 0), (_g.COUNT, 0), (_g.MEAN, 1)]
    states, reads = _g.partial_states(ops, [False, True])
    assert states == ((_g.SUM, 0, False), (_g.COUNT, 0, False),
                      (_g.SUM, 1, False), (_g.COUNT, 1, False))

    def pre_combine(key, v1, v3, mask, n):
        with jax.named_scope(stages.GROUPBY_PARTIAL):
            return _g.groupby_aggregate(
                [(key, None)], [(v1, None), (v3, None)],
                [(op, j) for op, j, _w in states], n, ROWS, fuse=fuse,
                mask=mask if masked else None,
            )

    def combine(key, s1, c1, s3, c3, n):
        with jax.named_scope(stages.GROUPBY_MERGE):
            keys, aggs, ng = _g.groupby_aggregate(
                [(key, None)], [(x, None) for x in (s1, c1, s3, c3)],
                _g.combine_ops(states), n, ROWS, fuse=fuse,
            )
            return keys, _g.finish_states(ops, reads, aggs), ng

    i32 = _spec((ROWS,), jnp.int32, one_chip)
    i64 = _spec((ROWS,), jnp.int64, one_chip)
    f64 = _spec((ROWS,), jnp.float64, one_chip)
    n = _spec((), jnp.int32, one_chip)
    programs = {
        stages.GROUPBY_PARTIAL: _compile(
            pre_combine, i32, i32, f64, _spec((ROWS,), jnp.bool_, one_chip), n
        ),
    }
    if not masked:
        programs[stages.GROUPBY_MERGE] = _compile(
            combine, i32, i64, i64, f64, i64, n
        )
    for stage, compiled in programs.items():
        wide, rows = _wide_ops(compiled)
        assert wide
        assert not [shape for shape, opcode in wide if "scatter" in opcode]
        assert len([shape for shape, opcode in wide if opcode == "sort"]) == 1
        staged = [op.split("/") for _text, op in rows if op]
        assert staged and all(
            stages.stage_of("/".join(path)) == stage
            for path in staged if stage in path
        )
        for inner in (stages.GROUPBY_KEY_IDS, stages.GROUPBY_SEGMENT_SUM):
            assert any(
                stage in path and inner in path
                and path.index(stage) < path.index(inner) for path in staged
            ), (stage, inner)


def test_dense_groupby_compiles_for_tpu_without_sort_scatter_or_gather(one_chip):
    from cylon_tpu.obs import stages

    spans, nullable = (3, 2), (False, False)
    meta = (("i32", "int32"), ("i32", "int32"))
    ops = [_g.agg_op_id(o) for o in ("sum", "mean", "count", "sum")]

    def dense(flag, status, qty, price, mask, n, lo0, lo1):
        lo = [lo0, lo1]
        gid = _g.dense_group_ids(
            [(flag, None), (status, None)], lo, spans, n, mask
        )
        slots = _g.dense_slots(spans, nullable)
        aggs = [
            _g.dense_aggregate(o, v, None, gid, slots)
            for o, v in zip(ops, (qty, qty, qty, price))
        ]
        return _g.dense_emit(
            _g.dense_rows(gid, slots), aggs, meta, lo, spans, nullable, 8
        )

    compiled = _compile(
        dense,
        _spec((ROWS,), jnp.int32, one_chip), _spec((ROWS,), jnp.int32, one_chip),
        _spec((ROWS,), jnp.float64, one_chip), _spec((ROWS,), jnp.float64, one_chip),
        _spec((ROWS,), jnp.bool_, one_chip), _spec((), jnp.int32, one_chip),
        _spec((), jnp.uint32, one_chip), _spec((), jnp.uint32, one_chip),
    )
    _module, rows = stages.parse_compiled(compiled.as_text())
    wide = [(text, op) for text, op in rows if f"[{ROWS}]" in text.split("(")[0]]
    # no operation that writes a row-sized array sorts, scatters or gathers;
    # the one that does is the row ids, under its stage name
    assert wide and all(
        not any(w in text.split(" = ")[1].split("(")[0]
                for w in ("sort", "scatter", "gather"))
        for text, _op in wide
    )
    assert any(stages.GROUPBY_KEY_IDS in op.split("/") for _text, op in wide)
    assert any(stages.GROUPBY_DENSE_AGG in op.split("/") for _text, op in rows)
    # the row-sized temporaries are the ids and one column's two halves:
    # the [slots, rows] masks of the reductions are never materialized
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * ROWS


def test_combined_dense_groupby_compiles_for_four_chips(mesh4):
    """``tpch-q1-w4``'s aggregate: every chip's partial slot table, the
    combine over the mesh axis and the finalize in one program. What
    crosses the chips is one int32 all-reduce (the counts) and the float64
    sums gathered as their 32-bit halves; no row does (no all-to-all)."""
    from cylon_tpu.obs import stages

    spans, nullable = (4, 2), (False, False)
    meta = (("i32", "int32"), ("i32", "int32"))
    ops = [_g.agg_op_id(o) for o in ("sum", "mean", "count", "min")]
    slots = _g.dense_slots(spans, nullable)

    def dense(flag, status, qty, price, mask, n, lo0, lo1):
        lo = [lo0, lo1]
        gid = _g.dense_group_ids(
            [(flag, None), (status, None)], lo, spans, n[0], mask
        )
        rows, parts = _g.dense_combine(_g.dense_rows(gid, slots), [
            (o, *_g.dense_partial(o, v, None, gid, slots))
            for o, v in zip(ops, (qty, qty, qty, price))
        ], "dp")
        aggs = [_g.dense_finalize(*part, False) for part in parts]
        out, ng = _g.dense_emit(rows, aggs, meta, lo, spans, nullable, 8)
        return out, jnp.where(jax.lax.axis_index("dp") == 0, ng, 0)[None]

    rows = NamedSharding(mesh4, PartitionSpec("dp"))
    rep = NamedSharding(mesh4, PartitionSpec())
    step = jax.jit(jax.shard_map(
        dense, mesh=mesh4,
        in_specs=(PartitionSpec("dp"),) * 6 + (PartitionSpec(),) * 2,
        out_specs=PartitionSpec("dp"),
    ))
    n = WORLD * ROWS
    compiled = step.lower(
        _spec((n,), jnp.int32, rows), _spec((n,), jnp.int32, rows),
        _spec((n,), jnp.float64, rows), _spec((n,), jnp.float64, rows),
        _spec((n,), jnp.bool_, rows), _spec((WORLD,), jnp.int32, rows),
        _spec((), jnp.uint32, rep), _spec((), jnp.uint32, rep),
    ).compile()
    text = compiled.as_text()
    assert "all-to-all" not in text and "collective-permute" not in text
    _module, table = stages.parse_compiled(text)
    crossing = [
        (ins.split(" = ")[1], op) for ins, op in table
        if re.search(r" all-(reduce|gather)\(", ins)
    ]
    # one psum of the int32 counts, the float64 lanes as two gathers of
    # float32 halves; nothing of a row's size
    assert sorted(c.split("[")[0] for c, _op in crossing) == ["f32", "f32", "s32"]
    assert all(f"{ROWS}" not in c.split("{")[0] for c, _op in crossing)
    assert all(stages.GROUPBY_COMBINE in op.split("/") for _c, op in crossing)

