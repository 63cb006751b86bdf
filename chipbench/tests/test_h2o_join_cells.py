"""CPU rehearsal of the cell of PR 48, ``h2o-join-q3-w4`` (question 3 of
the h2oai/db-benchmark join task over four chips: medium replicated, x
never shuffled, the LEFT OUTER emit): it passes its own check at 4,096
rows on the CPU mesh with every query on the replicate route, questions 2
and 1 once a run included; the control (float32 values) fails the value
numbers and no exact one; six faults driven through the harness come out
not correct (a lost unmatched row, a null filled with 0.0, a matched row
given a null, two re-paired ``v2`` values, float32 values, a factor column
over another dictionary); the generator
follows the source's law; the new manifest entries resolve to files BY
NAME; and the four new readers give a number where there is something to
read and nothing where there is not (the parent commit's program). A pass
here is a rehearsal, never a number."""
import importlib
import os
import time

import jax
import numpy as np
import pytest

import cylon_tpu as ct
from chipbench import control, harness
from cylon_tpu.obs import stages
from cylon_tpu.utils import tracing

ROWS = 4096
CELL = "h2o-join-q3-w4"
CONFIG = "h2o-join-1e8-w4"
NEW = {
    "join_replicate_ms": ("device_trace", "shuffle", "query_p50_ms"),
    "join_moved_share": ("program_counter", "shuffle", "rows_per_s"),
    "join_outer_emit_ms": ("device_trace", "kernels", "query_p50_ms"),
    "join_outer_emit_hbm_share": ("device_trace", "kernels", "rows_per_s"),
}
EVERY_CELL = {
    "host_syncs", "window_compiles", "hbm_roofline_share",
    "device_idle_share", "stage_unattributed_share",
}
EXACT = {
    "window.row_counts_wrong", "h2o_join.rows_gap", "h2o_join.columns_wrong",
    "h2o_join.dictionaries_wrong", "h2o_join.unmatched_gap", "h2o_join.nulls_wrong", "h2o_join.keys_wrong",
    "join.shards_wrong",
}
R_NAMES = ["id1_y", "id2_y", "id4_y", "id5_y", "v2"]
VALUES = {
    "h2o_join.v1_row_relgap", "h2o_join.v2_row_relgap",
    "h2o_join.v1_sum_relgap", "h2o_join.v2_sum_relgap",
    "h2o_join.cross_sum_relgap", "h2o_join.chk_v1_relgap",
    "h2o_join.chk_v2_relgap",
}


def _run(seed=2**31 + 48, **kw):
    cell = harness.Cell(CELL)
    kw.setdefault("max_queries", 3)
    return cell, harness.run_cell(
        cell, jax.devices()[: cell.chips], seed, 1e9, False,
        time.perf_counter(), rows=ROWS, **kw
    )


def _read(name, obs=None):
    reader = importlib.import_module("chipbench.layer_metrics." + name)
    return reader.read(obs if obs is not None else {})


def _failed(result):
    return {n for n, v, limit in result["numbers"] if not v <= limit}


def test_cell_passes_its_own_check_on_the_replicate_route():
    tracing.reset_trace()  # join_moved_share reads the process's rollup
    replicate = tracing.get_count("join.route.replicate")
    shuffle = tracing.get_count("join.route.shuffle")
    exchanges = tracing.get_count("shuffle.exchange")
    cell, result = _run()
    assert result["correct"], result["numbers"]
    assert result["attempted"] == 3 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"
    }
    compared = {n[0] for n in result["numbers"]}
    assert EXACT | VALUES <= compared
    # once a run: the inner join on the same key, and a build side of one
    # row; and the route of every query since the set-up
    for q in ("q2", "q1"):
        assert {f"h2o_join.{q}_rows_gap", f"h2o_join.{q}_nulls_wrong",
                f"h2o_join.{q}_keys_wrong", f"h2o_join.{q}_shards_wrong",
                f"h2o_join.{q}_v2_sum_relgap"} <= compared
    assert {"h2o_join.shuffle_routes",
            "h2o_join.replicate_routes_missing"} <= compared
    # warm-ups, the window, the two questions asked once: all replicated,
    # and not one exchange was run
    calls = tracing.get_count("join.route.replicate") - replicate
    assert calls >= result["attempted"] + 3
    assert tracing.get_count("join.route.shuffle") == shuffle
    assert tracing.get_count("shuffle.exchange") == exchanges
    # 4 of medium's rows to 3 other chips a query of 4,100 rows: 0.29%
    assert 0.0 < _read("join_moved_share") < 1.0


def test_the_shuffle_route_is_held_to_the_same_rows_and_named(monkeypatch):
    """With the rule switched off both sides are shuffled: the result is
    held to the same rows, nulls and values, whatever order and chip they
    came out on, and the one number that fails says which route the
    queries took. (40,000 rows: 40 keys of medium, which a hash spreads
    over every shard; small's one key at this size lies on one shard under
    a hash, the other number the shuffle route cannot meet here. Not
    larger: ROADMAP M17.)"""
    cell = harness.Cell(CELL)
    monkeypatch.setattr(
        "cylon_tpu.ops.join.replicate_side", lambda *a, **k: None
    )
    shuffle = tracing.get_count("join.route.shuffle")
    result = harness.run_cell(
        cell, jax.devices()[: cell.chips], 49, 1e9, False,
        time.perf_counter(), rows=40_000, max_queries=1,
    )
    assert tracing.get_count("join.route.shuffle") > shuffle
    assert _failed(result) == {
        "h2o_join.q1_shards_wrong", "h2o_join.shuffle_routes",
        "h2o_join.replicate_routes_missing",
    }, result["numbers"]
    compared = {n[0] for n in result["numbers"]}
    assert EXACT | VALUES <= compared
    assert {"h2o_join.q2_v2_sum_relgap", "h2o_join.q1_keys_wrong"} <= compared


def test_a_checkout_without_the_replicate_route_is_refused_at_set_up(monkeypatch):
    """The parent of PR 48 would hash-shuffle all of x (on the chip it had
    not finished its set-up after 480 s): the cell refuses such a program
    before its first query, with a message and a non-zero exit."""
    cell = harness.Cell(CELL)
    monkeypatch.setattr(cell.query, "_route_counts", lambda: None)
    joins = tracing.get_count("join.route.shuffle")
    with pytest.raises(SystemExit) as refused:
        harness.run_cell(
            cell, jax.devices()[: cell.chips], 49, 1e9, False,
            time.perf_counter(), rows=ROWS, max_queries=1,
        )
    assert "no replicate route" in str(refused.value)
    assert refused.value.code not in (0, None)
    assert tracing.get_count("join.route.shuffle") == joins


def test_float32_values_fail_the_value_numbers_and_no_exact_one():
    cell = harness.Cell(CELL)
    assert cell.config["guarantees"]["value_precision"] == "float64"
    assert cell.config["lower_precision"] == "float32"
    out = control.readings(
        cell, jax.devices()[: cell.chips], [48, 2**31 + 49], rows=ROWS
    )
    assert [ok for _, side, ok in out["verdicts"] if side == "sound"] == [True] * 2
    assert [ok for _, side, ok in out["verdicts"] if side == "control"] == [False] * 2
    limit = cell.query.VALUE_LIMIT["float64"]
    chk = cell.query.CHK_LIMIT["float64"]
    assert VALUES <= set(out["control"])
    for number, values in out["control"].items():
        if number.endswith("relgap"):
            room = chk if "chk_" in number else limit
            assert min(values) > 3 * room, number
            assert max(out["sound"][number]) < room / 3, number
        else:  # the lower precision fails no exact number
            assert max(values) == 0, number


# -- five faults, each through the harness --------------------------------
# ``cols`` / ``null``: the result as the device holds it (a string column
# as its codes), and which rows of a column are null
def _null_rows(null):
    return np.flatnonzero(null["v2"])


def _lost_unmatched_row(cols, null):
    keep = np.ones(len(cols["v2"]), bool)
    keep[_null_rows(null)[0]] = False
    for c in cols:
        cols[c], null[c] = cols[c][keep], null[c][keep]


def _null_filled_with_zero(cols, null):
    row = _null_rows(null)[0]
    cols["v2"][row], null["v2"][row] = 0.0, False


def _matched_row_given_a_null(cols, null):
    row = np.flatnonzero(~null["v2"])[0]
    for c in R_NAMES:
        null[c][row] = True


def _two_v2_repaired(cols, null):
    """Two matched rows of different keys swap their partners' values."""
    live = np.flatnonzero(~null["v2"])
    a = live[0]
    b = live[np.flatnonzero(cols["id2_x"][live] != cols["id2_x"][a])[0]]
    cols["v2"][a], cols["v2"][b] = cols["v2"][b], cols["v2"][a]


def _float32_values(cols, null):
    for c in ("v1", "v2"):
        cols[c] = cols[c].astype(np.float32).astype(np.float64)


@pytest.mark.parametrize("alter,numbers,sound", [
    (_lost_unmatched_row,
     {"window.row_counts_wrong", "h2o_join.rows_gap",
      "h2o_join.unmatched_gap", "h2o_join.keys_wrong"},
     {"h2o_join.dictionaries_wrong"}),
    (_null_filled_with_zero,
     {"h2o_join.nulls_wrong"},
     {"h2o_join.rows_gap", "h2o_join.keys_wrong",
      "h2o_join.dictionaries_wrong"}),
    (_matched_row_given_a_null,
     {"h2o_join.nulls_wrong", "h2o_join.unmatched_gap"},
     {"h2o_join.rows_gap", "h2o_join.keys_wrong",
      "h2o_join.dictionaries_wrong"}),
    (_two_v2_repaired,
     {"h2o_join.v2_row_relgap", "h2o_join.v2_sum_relgap",
      "h2o_join.cross_sum_relgap"}, EXACT),
    (_float32_values, VALUES - {"h2o_join.chk_v1_relgap"}, EXACT),
], ids=["lost_unmatched_row", "null_filled_with_zero",
        "matched_row_given_a_null", "two_v2_repaired", "float32_values"])
def test_broken_left_join_is_not_correct(monkeypatch, alter, numbers, sound):
    cell = harness.Cell(CELL)
    build = cell.query.build

    def broken_build(tables, params):
        call = build(tables, params)

        def broken():
            out = call()
            cols, null, _ = cell.query._host_columns(out)
            null = {
                c: np.zeros(len(cols[c]), bool) if a is None else a
                for c, a in null.items()
            }
            alter(cols, null)
            # the altered columns as the join's own result has them: the
            # same types and dictionaries, a validity lane where one was
            return ct.Table.from_encoded(out.ctx, {
                c: (cols[c], ~null[c] if null[c].any() else None,
                    out.column(c).dtype, out.column(c).dictionary)
                for c in cols
            })

        return broken

    monkeypatch.setattr(cell.query, "build", broken_build)
    result = harness.run_cell(
        cell, jax.devices()[: cell.chips], 5, 1e9, False,
        time.perf_counter(), rows=ROWS, max_queries=2,
    )
    assert result["correct"] is False
    assert numbers <= _failed(result), _failed(result)
    assert not sound & _failed(result), sound & _failed(result)
    # the once-a-run questions ask the tables themselves and stay sound
    assert not {n for n in _failed(result) if n[9:11] in ("q1", "q2")}


def test_a_dropped_or_foreign_dictionary_is_not_correct(monkeypatch):
    """A factor column that comes out over another dictionary than it was
    loaded with (here: the values that occur, as a re-encode would leave
    it) is a fault of its own, whatever its codes say."""
    cell = harness.Cell(CELL)
    build = cell.query.build

    def broken_build(tables, params):
        call = build(tables, params)

        def broken():
            out = call()
            frame = out.to_pandas()
            return ct.Table.from_pandas(out.ctx, frame)

        return broken

    monkeypatch.setattr(cell.query, "build", broken_build)
    result = harness.run_cell(
        cell, jax.devices()[: cell.chips], 6, 1e9, False,
        time.perf_counter(), rows=ROWS, max_queries=1,
    )
    assert result["correct"] is False
    assert "h2o_join.dictionaries_wrong" in _failed(result)


# -- the configuration and its generator ----------------------------------
def test_the_configuration_is_the_sources_shape():
    cell = harness.Cell(CELL)
    config = cell.config
    assert config["rows"] == config["issue_rows"] == 100_000_000
    assert config["levels"] == {
        "small": 100, "medium": 100_000, "big": 100_000_000}
    # the source's own widths: seven columns, five and three
    assert {t: list(cols) for t, cols in config["tables"].items()} == {
        "x": ["id1", "id2", "id3", "id4", "id5", "id6", "v1"],
        "medium": ["id1", "id2", "id4", "id5", "v2"],
        "small": ["id1", "id4", "v2"],
    }
    for cols in config["tables"].values():
        for c, kind in cols.items():
            assert kind == {"i": "int32", "v": "float64"}.get(
                c[0] if c < "id4" or c[0] == "v" else "", "string"), c
    assert config["reduced"] == ["tables"] and config["chips"] == 4
    assert "rows" not in config["reduced"]  # the halving rule did not bite
    assert config["reduced_from"]["tables"] == ["x", "small", "medium", "big"]
    for word in ("J1_1e8_NA_0_0", "J1_1e8_1e5_0_0", "join-datagen.R",
                 "question 3"):
        assert word in config["source"], word
    for word in ("integer_type", "key_law", "seed_handling", "id3",
                 "partition", "resident_bytes", "factors"):
        assert config["assumed"][word]
    for word in ("M10", "rows: none cut", "columns: none cut"):
        assert word in config["reduced_why"], word
    for word in ("every row of x exactly once", "nulls", "re-paired",
                 "float64", "one copy"):
        assert word in config["guarantees"]["semantics"], word
    params = cell.traffic["params"]
    assert (params["left"], params["right"], params["on"], params["how"]) == (
        "x", "medium", "id2", "left")
    assert [q["name"] for q in params["once_a_run"]] == ["q2", "q1"]
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 1
    data = cell.generator.make(config, 2**31 + 5, ROWS)
    assert cell.query.input_rows(data, params) == ROWS + 4
    ref = cell.query.reference(data, params)
    assert ref["rows"] == ROWS
    assert ref["columns"] == [
        "id1_x", "id2_x", "id3", "id4_x", "id5_x", "id6", "v1"] + R_NAMES
    # 32 bytes a row of x and 24 of medium read once; a result row of both
    # and five validity bytes written once
    assert cell.query.least_bytes(data, params, ROWS) == (
        32 * ROWS + 24 * 4 + 61 * ROWS)


def test_the_generator_follows_the_sources_law():
    cell = harness.Cell(CELL)
    n = 400_000
    data = cell.generator.make(cell.config, 2**31 + 11, n)
    x, medium, small = data["x"], data["medium"], data["small"]
    assert list(x) == ["id1", "id2", "id3", "id4", "id5", "id6", "v1"]
    assert [a.dtype for a in x.values()] == [np.int32] * 6 + [np.float64]
    assert list(medium) == ["id1", "id2", "id4", "id5", "v2"]
    assert list(small) == ["id1", "id4", "v2"]
    # a factor is sprintf("id%d") of its integer twin: codes over the
    # level's sorted dictionary, which the dtype carries
    for cols in data.values():
        for c, of in cell.generator.TWIN_OF.items():
            if c not in cols:
                continue
            words = cols[c].dtype.metadata["dictionary"]
            assert (words[:-1] < words[1:]).all()
            assert (words[cols[c][:5000]] == np.char.add(
                "id", cols[of][:5000].astype(str))).all(), c
    assert x["id5"].dtype.metadata["dictionary"] is (
        medium["id5"].dtype.metadata["dictionary"])
    assert len(x["id6"].dtype.metadata["dictionary"]) == n + n // 10
    assert len(medium["id2"]) == 400 and len(small["id1"]) == 1
    # a right table holds each key of its level once
    assert len(np.unique(medium["id2"])) == 400
    # x holds each of its 400 keys at least once, 360 of them medium's
    keys = np.unique(x["id2"])
    assert len(keys) == 400
    assert np.isin(keys, medium["id2"]).sum() == 360
    assert (~np.isin(medium["id2"], keys)).sum() == 40
    assert 1 <= keys.min() and keys.max() <= 440
    # about 90% of x's rows find a partner
    share = np.isin(x["id2"], medium["id2"]).mean()
    assert 0.88 < share < 0.92
    # the rest uniform over the keys: about 1,000 rows a key
    count = np.bincount(x["id2"])[keys]
    assert 800 < count.min() and count.max() < 1200
    # id3 names the row: N distinct ids of 1..1.1 N
    assert len(np.unique(x["id3"])) == n
    assert 1 <= x["id3"].min() and x["id3"].max() <= n + n // 10
    # medium's id1 is over the small level's right-side keys
    assert np.isin(medium["id1"], small["id1"]).all()
    v1 = x["v1"]
    assert 0.0 <= v1.min() and v1.max() <= 100.0
    assert (np.round(v1, 6) == v1).all() and abs(v1.mean() - 50.0) < 0.3
    # the seed alone decides the rows, block by block
    again = cell.generator.make(cell.config, 2**31 + 11, n)
    other = cell.generator.make(cell.config, 2**31 + 12, n)
    for t in data:
        for c in data[t]:
            assert (again[t][c] == data[t][c]).all(), (t, c)
    assert (other["x"]["id2"] != x["id2"]).any()
    block = cell.generator.BLOCK
    tail = cell.generator.make(cell.config, 7, block + 100)["x"]
    assert len(np.unique(tail["id3"])) == block + 100
    assert (tail["id2"][block:] >= 1).all()
    # the source's levels at the source's size
    assert cell.generator.levels_of(cell.config, 100_000_000) == (100, 100_000)
    assert cell.generator.one_side_only(100_000) == 10_000


# -- the new readers ------------------------------------------------------
def _obs(stage_of_op, chips=4):
    """``obs`` as a traced run hands it to the readers and the program's
    stage table: three operations of 40, 50 and 10 ms over two queries on
    the first of ``chips`` devices, each under the stage path given."""
    rows, ops = [], []
    for i, (path, seconds) in enumerate(zip(stage_of_op, (0.08, 0.10, 0.02))):
        name, shape = f"fusion.{i + 1}", f"s32[{1 << (20 + i)}]"
        rows.append((
            "jit_join_spec",
            f"%{name} = {shape}{{0:T(1024)}} fusion(%p.1), kind=kLoop, "
            "calls=%fused_computation.1",
            f"jit(join_spec)/shard_map/{path}/gather",
        ))
        ops.append((f"{name} {shape} fusion:kLoop", seconds))
    devices = {
        f"/device:TPU:{i}": {
            "busy_s": 0.2, "ops": ops if i == 0 else [("x", 0.2)]}
        for i in range(chips)
    }
    table = {"rows": rows, "stale": [], "programs": 1, "seconds": 0.0}
    obs = {
        "queries": 2, "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"window_s": 1.0, "devices": devices},
    }
    return table, obs


def test_stage_readers_read_the_outermost_stage(monkeypatch):
    table, obs = _obs(["join.emit", "join.right_sort/sort_engine",
                       "join.replicate"])
    monkeypatch.setattr(stages, "device_stage_table", lambda ctx=None: table)
    assert _read("join_outer_emit_ms", obs) == pytest.approx(40.0)
    assert _read("join_replicate_ms", obs) == pytest.approx(10.0)
    # the share: one chip writes a quarter of 1e8 result rows of 61 bytes,
    # reads a quarter of 1e8 left rows of 32 + 8, and the WHOLE build
    # table of 1e5 rows of 24; over 819 GB/s against the chip's 40 ms
    query = importlib.import_module("chipbench.queries.h2o_join")
    monkeypatch.setitem(query._RUN, "shapes", {
        "left_rows": 10**8, "left_row": 32, "right_rows": 10**5,
        "right_row": 24, "out_rows": 10**8, "out_row": 61,
    })
    reader = importlib.import_module(
        "chipbench.layer_metrics.join_outer_emit_hbm_share")
    least = reader.emit_least_bytes(query.emit_shapes(), 4)
    assert least == 10**8 * 61 / 4 + 10**8 * 40 / 4 + 10**5 * 24
    assert _read("join_outer_emit_hbm_share", obs) == pytest.approx(
        100.0 * (least / 819e9) / 0.040)
    assert _read("join_outer_emit_hbm_share", obs) < 100.0
    # the moved share: 1e5 rows to three other chips over a query's input
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "join.route.replicate": {"count": 5, "rows": 5 * (10**8 + 10**5)},
        "join.replicate.rows": {"count": 5, "rows": 5 * 3 * 10**5}})
    assert _read("join_moved_share", obs) == pytest.approx(
        100.0 * 3e5 / (1e8 + 1e5))
    # and on the shuffle route three rows in four cross
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "join.route.shuffle": {"count": 2, "rows": 2 * 1000},
        "shuffle.coll_rows": {"count": 4, "rows": 2 * 1000}})
    assert _read("join_moved_share", obs) == pytest.approx(75.0)


def test_readers_read_nothing_from_the_parents_program(monkeypatch):
    """The parent's program has neither the stage nor the counters; a
    traced run of it still has to print its line: nothing, and no raise.
    (``join.emit`` is the parent's too: that reader reads it there.)"""
    table, obs = _obs(["join.emit", "shuffle.pack/sort_engine",
                       "shuffle.compact"])
    monkeypatch.setattr(stages, "device_stage_table", lambda ctx=None: table)
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "host_sync": {"count": 3, "rows": 0},
        "shuffle.coll_rows": {"count": 3, "rows": 4800}})
    query = importlib.import_module("chipbench.queries.h2o_join")
    monkeypatch.delitem(query._RUN, "shapes", raising=False)
    assert _read("join_replicate_ms", obs) is None
    assert _read("join_moved_share", obs) is None
    assert _read("join_outer_emit_hbm_share", obs) is None
    assert _read("join_outer_emit_ms", obs) == pytest.approx(40.0)
    for reader in NEW:
        for trace in (None, {"window_s": 0.0, "devices": {}}):
            assert _read(reader, {"queries": 3, "trace": trace}) is None, reader


def test_new_manifest_entries_resolve_and_are_looked_up_by_name():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert cells[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "h2o-join-q3-closed",
        "chips": 4, "why": cells[CELL]["why"],
    }
    assert len(cells[CELL]["why"]) <= 200
    # at most half the cells, rounded down, may take four chips: 6 of 14
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= len(manifest["workloads"]) // 2
    config = configs[CONFIG]
    assert config["reduced"] == ["tables"]
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    body = harness.load_json(harness.ROOT, config["file"])
    assert body["name"] == config["name"] and body["source"] == config["source"]
    assert body["reduced"] == config["reduced"] and body["chips"] == 4
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)  # no other configuration's file
    sources = [c["source"] for c in manifest["configs"]]
    assert sources.count(config["source"]) == 1
    for kind, name in (("generators", body["generator"] + ".py"),
                       ("traffic", cells[CELL]["traffic"] + ".json"),
                       ("queries", "h2o_join.py")):
        assert os.path.exists(os.path.join(harness.HERE, kind, name)), name
    for name, (source, layer, moves) in NEW.items():
        assert entries[name] == {
            "name": name, "unit": entries[name]["unit"],
            "better": entries[name]["better"], "source": source,
            "layer": layer, "moves": moves, "workloads": [CELL],
        }
        assert os.path.exists(
            os.path.join(harness.HERE, "layer_metrics", name + ".py")
        )
    assert entries["join_outer_emit_hbm_share"]["unit"] == "%"
    assert entries["join_moved_share"]["better"] == "lower"
    # the metrics that list their cells were not appended to
    for name, entry in entries.items():
        if name not in NEW:
            assert CELL not in entry.get("workloads", []), name
    cell = harness.Cell(CELL)
    assert {"build", "input_rows", "least_bytes", "emit_shapes", "reference",
            "compare"} <= set(dir(cell.query))
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"]
    listed = {m["name"] for m in cell.metrics("per_layer")}
    assert listed == EVERY_CELL | set(NEW)
