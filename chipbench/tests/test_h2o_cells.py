"""CPU rehearsal of the cell of PR 45, ``h2o-q5-w4`` (question 5 of the
h2oai/db-benchmark group-by over four chips: partial aggregate, exchange of
the partial rows at their counted capacity, combine): it passes its own
check at 160,000 rows on the CPU mesh and every query ships partial rows,
the control (float32 values) fails ``h2o.v3_sum_relgap`` and no exact
number, four faults driven through the harness come out not correct (a lost
group, a group left on two shards uncombined, an int32 sum that wrapped, a
float32 ``v3`` sum), the generator follows the source's laws, the new
manifest entries resolve to files BY NAME, and the five new readers give a
number where there is something to read and nothing where there is not (the
parent commit's program). A pass here is a rehearsal, never a number."""
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

#: 1,600 ids: past ``DENSE_MAX_SLOTS``, so the rehearsal takes the cell's route
ROWS = 160_000
IDS = ROWS // 100
CELL = "h2o-q5-w4"
NEW = {
    "groupby_partial_ms": ("device_trace", "kernels", "query_p50_ms"),
    "groupby_merge_ms": ("device_trace", "shuffle", "query_p50_ms"),
    "groupby_shuffled_share": ("program_counter", "shuffle", "rows_per_s"),
    "groupby_partial_slot_fill": ("program_counter", "shuffle", "rows_per_s"),
    "groupby_partial_hbm_share": ("device_trace", "kernels", "rows_per_s"),
}
EVERY_CELL = {
    "host_syncs", "window_compiles", "hbm_roofline_share",
    "device_idle_share", "stage_unattributed_share",
}
COUNTERS = (
    "groupby.precombine.rows_in", "groupby.precombine.rows_out",
    "groupby.precombine.fullest", "groupby.precombine.slots",
    "shuffle.coll_rows",
)


def _run(seed=2**31 + 45, **kw):
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


def _counted():
    snap = tracing.snapshot()
    return [snap.get(name, {}).get("rows", 0) for name in COUNTERS]


def test_cell_passes_its_own_check_and_ships_partial_rows():
    partial = tracing.get_count("groupby.partial_path")
    raw = tracing.get_count("groupby.raw_shuffle_path")
    before = _counted()
    cell, result = _run()
    assert result["correct"], result["numbers"]
    assert result["attempted"] == 3 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"
    }
    compared = {n[0] for n in result["numbers"]}
    assert {
        "window.row_counts_wrong", "h2o.rows_gap", "h2o.columns_wrong",
        "h2o.groups_split", "h2o.keys_wrong", "h2o.shards_wrong",
        "h2o.v1_sum_gap", "h2o.v2_sum_gap", "h2o.v3_sum_relgap",
        # once a run: a count beside a sum, a mean (M13's state)
        "h2o.m13_rows_gap", "h2o.m13_keys_wrong", "h2o.m13_v1_sum_gap",
        "h2o.m13_v1_count_gap", "h2o.m13_v3_mean_relgap",
    } <= compared
    # every query of the run (warm-ups, the window, the once-a-run check)
    # took the partial path, none shipped its rows
    calls = tracing.get_count("groupby.partial_path") - partial
    assert calls >= result["attempted"] + 2
    assert tracing.get_count("groupby.raw_shuffle_path") == raw
    rows_in, rows_out, fullest, slots, moved = (
        a - b for a, b in zip(_counted(), before)
    )
    assert rows_in == calls * ROWS
    # 1,600 ids over four shards of 40,000 rows: every shard holds every id
    assert rows_out == moved == calls * 4 * IDS
    assert fullest == calls * IDS and slots == calls * 2048
    assert _read("groupby_shuffled_share") < 100.0
    assert 50.0 < _read("groupby_partial_slot_fill") <= 100.0


def test_a_checkout_without_the_partial_state_is_not_asked_the_state(monkeypatch):
    """The parent of PR 45 ships every input row for a count beside a sum
    (half a billion at the cell's size): the once-a-run question is the
    change's, and a run of the parent is held to question 5 alone."""
    cell = harness.Cell(CELL)
    monkeypatch.setattr(cell.query, "HAS_PARTIAL_STATE", False)
    _cell, result = _run(seed=46, max_queries=1)
    assert result["correct"], result["numbers"]
    compared = {n[0] for n in result["numbers"]}
    assert "h2o.v3_sum_relgap" in compared
    assert not {n for n in compared if n.startswith("h2o.m13")}


def test_float32_values_fail_the_float_sum_and_no_exact_number():
    cell = harness.Cell(CELL)
    assert cell.config["guarantees"]["value_precision"] == "float64"
    assert cell.config["lower_precision"] == "float32"
    out = control.readings(
        cell, jax.devices()[: cell.chips], [45, 2**31 + 46], rows=ROWS
    )
    assert [ok for _, side, ok in out["verdicts"] if side == "sound"] == [True] * 2
    assert [ok for _, side, ok in out["verdicts"] if side == "control"] == [False] * 2
    limit = cell.query.VALUE_LIMIT["float64"]
    assert "h2o.v3_sum_relgap" in out["control"]
    for number, values in out["control"].items():
        if number.endswith("relgap"):
            assert min(values) > 3 * limit, number
            assert max(out["sound"][number]) < limit / 3, number
        else:  # the lower precision fails no exact number
            assert max(values) == 0, number


# -- four faults, each through the harness -------------------------------
def _lost_group(cols, world):
    keep = cols["id6"] != cols["id6"][0]
    return {c: a[keep] for c, a in cols.items()}


def _left_on_two_shards(cols, world):
    """One group's sums split in two rows, as two shards' partial rows that
    no combine has added would lie."""
    out = {c: np.append(a, a[:1]) for c, a in cols.items()}
    for c in ("v1_sum", "v2_sum"):
        out[c][0], out[c][-1] = 1, cols[c][0] - 1
    out["v3_sum"][0], out["v3_sum"][-1] = 1.0, cols["v3_sum"][0] - 1.0
    return out


def _wrapped_int32(cols, world):
    """The sum of v1 accumulated in the column's own int32."""
    return {**cols, "v1_sum": cols["v1_sum"].astype(np.int32)}


def _float32_sum(cols, world):
    v3 = cols["v3_sum"].astype(np.float32).astype(np.float64)
    return {**cols, "v3_sum": v3}


@pytest.mark.parametrize("alter,numbers", [
    (_lost_group, {"window.row_counts_wrong", "h2o.rows_gap"}),
    (_left_on_two_shards, {"h2o.groups_split", "h2o.rows_gap"}),
    (_wrapped_int32, {"h2o.v1_sum_gap"}),
    (_float32_sum, {"h2o.v3_sum_relgap"}),
], ids=["lost_group", "left_on_two_shards", "wrapped_int32", "float32_sum"])
def test_broken_group_by_is_not_correct(monkeypatch, alter, numbers):
    cell = harness.Cell(CELL)
    build = cell.query.build

    def broken_build(tables, params):
        call = build(tables, params)

        def broken():
            out = call()
            cols = alter(out.to_pydict(), out.ctx.world_size)
            return ct.Table.from_numpy(out.ctx, list(cols), list(cols.values()))

        return broken

    monkeypatch.setattr(cell.query, "build", broken_build)
    result = harness.run_cell(
        cell, jax.devices()[: cell.chips], 5, 1e9, False,
        time.perf_counter(), rows=ROWS, max_queries=2,
    )
    assert result["correct"] is False
    assert numbers <= _failed(result), _failed(result)
    # the once-a-run check asks the table itself and stays sound
    assert not {n for n in _failed(result) if n.startswith("h2o.m13")}


# -- the configuration and its generator ---------------------------------
def test_the_configuration_is_the_sources_shape():
    cell = harness.Cell(CELL)
    config = cell.config
    # ISSUE 45's 500,000,000 rows, halved once by its rule (a query took
    # 3,238 ms at that size: the file's ``reduced_why``)
    assert config["issue_rows"] == 500_000_000 == 2 * config["rows"]
    assert config["rows"] == 250_000_000 and config["K"] == 100
    assert config["ids"] == config["rows"] // config["K"] == 2_500_000
    assert config["tables"] == {"x": {
        "id6": "int32", "v1": "int32", "v2": "int32", "v3": "float64"}}
    assert config["reduced"] == ["rows", "columns"] and config["chips"] == 4
    assert config["reduced_from"] == {"rows": 1_000_000_000, "columns": 9}
    assert "G1_1e9_1e2_0_0" in config["source"] and "id6" in config["source"]
    for word in ("integer_type", "seed_handling", "laws", "partition",
                 "resident_bytes"):
        assert config["assumed"][word]
    for word in ("exactly one group", "exact in int64", "float64",
                 "one copy", "shard order"):
        assert word in config["guarantees"]["semantics"], word
    params = cell.traffic["params"]
    assert params == {"table": "x", "by": "id6",
                      "agg": {"v1": "sum", "v2": "sum", "v3": "sum"}}
    assert params["by"] == config["question"]["by"]
    assert params["agg"] == config["question"]["agg"]
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 1
    assert cell.query.state_agg(params) == {
        "v1": ["sum", "count"], "v3": "mean"}
    data = cell.generator.make(config, 2**31 + 5, ROWS)
    assert cell.query.input_rows(data, params) == ROWS
    ref = cell.query.reference(data, params)
    assert ref["rows"] == IDS and ref["count"].sum() == ROWS
    # 20 bytes a row read once, a key and three 64-bit sums a group written
    assert cell.query.least_bytes(data, params, IDS) == 20 * ROWS + 28 * IDS
    assert cell.query.partial_least_bytes(1600) == 20 * ROWS + 28 * 1600


def test_the_generator_follows_the_sources_laws():
    cell = harness.Cell(CELL)
    n, k = 400_000, cell.config["K"]
    data = cell.generator.make(cell.config, 2**31 + 11, n)
    x = data["x"]
    assert list(x) == ["id6", "v1", "v2", "v3"]
    assert [a.dtype for a in x.values()] == [
        np.int32, np.int32, np.int32, np.float64]
    assert all(len(a) == n for a in x.values())
    # id6 uniform over 1..N/K: every id about K times (K = 100: within 6
    # deviations of a Poisson's 10)
    count = np.bincount(x["id6"], minlength=n // k + 1)
    assert count[0] == 0 and len(count) == n // k + 1
    assert count[1:].min() > 40 and count[1:].max() < 170
    assert abs(count[1:].mean() - k) < 1e-9
    # v1 uniform over 1..5, v2 over 1..15: every value an equal share
    for name, hi in (("v1", 5), ("v2", 15)):
        share = np.bincount(x[name], minlength=hi + 1) / n
        assert share[0] == 0 and len(share) == hi + 1
        assert np.abs(share[1:] - 1 / hi).max() < 0.004, name
    # v3 on the six-decimal grid under 100, uniform
    v3 = x["v3"]
    assert 0.0 <= v3.min() and v3.max() <= 100.0
    assert (np.round(v3, 6) == v3).all()
    assert len(np.unique(v3)) > 0.99 * n  # not a coarser grid
    assert abs(v3.mean() - 50.0) < 0.3
    # the columns are independent draws
    for a, b in (("id6", "v1"), ("v1", "v2"), ("v2", "v3"), ("id6", "v3")):
        assert abs(np.corrcoef(x[a], x[b])[0, 1]) < 0.01, (a, b)
    # the seed alone decides the rows, block by block
    again = cell.generator.make(cell.config, 2**31 + 11, n)["x"]
    other = cell.generator.make(cell.config, 2**31 + 12, n)["x"]
    for c in x:
        assert (again[c] == x[c]).all() and (other[c] != x[c]).any(), c
    block = cell.generator.BLOCK
    tail = cell.generator.make(cell.config, 7, block + 100)["x"]
    assert (tail["id6"][block:] >= 1).all() and (tail["v1"][block:] >= 1).all()
    assert tail["id6"].max() <= (block + 100) // k


# -- the new readers -----------------------------------------------------
def _obs(stage_of_op, chips=4):
    """``obs`` as a traced run hands it to the readers and the program's
    stage table: three operations of 40, 50 and 10 ms over two queries on
    the first of ``chips`` devices, each under the stage path given."""
    rows, ops = [], []
    for i, (path, seconds) in enumerate(zip(stage_of_op, (0.08, 0.10, 0.02))):
        name, shape = f"fusion.{i + 1}", f"s32[{1 << (20 + i)}]"
        rows.append((
            "jit_groupby",
            f"%{name} = {shape}{{0:T(1024)}} fusion(%p.1), kind=kLoop, "
            "calls=%fused_computation.1",
            f"jit(groupby)/shard_map/{path}/sort",
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
    table, obs = _obs([
        "groupby.partial/groupby.key_ids/sort_engine",
        "groupby.partial/groupby.segment_sum", "groupby.merge/groupby.key_ids",
    ])
    monkeypatch.setattr(stages, "device_stage_table", lambda ctx=None: table)
    assert _read("groupby_partial_ms", obs) == pytest.approx(90.0)
    assert _read("groupby_merge_ms", obs) == pytest.approx(10.0)
    # the share: four chips' least bytes are 8.19e9 (read) and 4 x 1e6
    # partial rows of 28 bytes a query; a quarter of them over 819 GB/s
    # against the first chip's 90 ms
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "groupby.precombine.rows_out": {"count": 5, "rows": 5 * 4_000_000}})
    query = importlib.import_module("chipbench.queries.h2o_groupby")
    monkeypatch.setitem(query._RUN, "read_bytes", 8_190_000_000)
    monkeypatch.setitem(query._RUN, "row_bytes", 28)
    least_s = (8_190_000_000 + 4_000_000 * 28) / 4 / 819e9
    assert _read("groupby_partial_hbm_share", obs) == pytest.approx(
        100.0 * least_s / 0.090)
    assert _read("groupby_partial_hbm_share", obs) < 100.0


def test_readers_read_nothing_from_the_parents_program(monkeypatch):
    """The parent's program has neither stage nor any of the counters, a
    traced run of it still has to print its line: nothing, and no raise."""
    table, obs = _obs(
        ["groupby.key_ids/sort_engine", "groupby.segment_sum", "shuffle.pack"]
    )
    monkeypatch.setattr(stages, "device_stage_table", lambda ctx=None: table)
    monkeypatch.setattr(
        tracing, "snapshot", lambda: {
            "host_sync": {"count": 3, "rows": 0},
            "shuffle.coll_rows": {"count": 3, "rows": 4800}}
    )
    for reader in NEW:
        assert _read(reader, obs) is None, reader
        for trace in (None, {"window_s": 0.0, "devices": {}}):
            assert _read(reader, {"queries": 3, "trace": trace}) is None
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "groupby.precombine.rows_in": {"count": 3, "rows": 120_000},
        "groupby.precombine.fullest": {"count": 3, "rows": 1200},
        "groupby.precombine.slots": {"count": 3, "rows": 1536},
        "shuffle.coll_rows": {"count": 3, "rows": 4800}})
    assert _read("groupby_shuffled_share") == pytest.approx(4.0)
    assert _read("groupby_partial_slot_fill") == pytest.approx(78.125)


def test_new_manifest_entries_resolve_and_are_looked_up_by_name():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert cells[CELL] == {
        "name": CELL, "config": "h2o-groupby-5e8-w4",
        "traffic": "h2o-q5-closed", "chips": 4, "why": cells[CELL]["why"],
    }
    assert len(cells[CELL]["why"]) <= 200
    # at most half the cells, rounded down, may take four chips
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= len(manifest["workloads"]) // 2
    config = configs["h2o-groupby-5e8-w4"]
    assert config["reduced"] == ["rows", "columns"]
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
                       ("queries", "h2o_groupby.py")):
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
    assert entries["groupby_partial_hbm_share"]["unit"] == "%"
    assert entries["groupby_shuffled_share"]["better"] == "lower"
    # the metrics that list their cells were not appended to
    for name, entry in entries.items():
        if name not in NEW:
            assert CELL not in entry.get("workloads", []), name
    cell = harness.Cell(CELL)
    assert {"build", "input_rows", "least_bytes", "device_bytes", "reference",
            "compare"} <= set(dir(cell.query))
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"]
    listed = {m["name"] for m in cell.metrics("per_layer")}
    assert listed == EVERY_CELL | set(NEW)
