"""CPU rehearsal of the two cells of PR 33, ``join-skew-w4`` (a fact table
with a Zipf 1.25 foreign key joined to a dimension of unique keys) and
``sort-w4``: each passes its own check at 4,096 rows on the CPU mesh, the
control (float32 values) and one re-paired row fail the join's, the new
manifest names resolve to files and are looked up by name, and the three
new readers give a number where the program has their counters and nothing
where it has not (the parent commit's program). A pass here is a
rehearsal, never a number."""
import importlib
import os
import time

import jax
import numpy as np
import pytest

import cylon_tpu as ct
from chipbench import control, harness
from cylon_tpu.utils import tracing

ROWS = 4096
CELLS = ("join-skew-w4", "sort-w4")
LISTED = {
    "hash_shard_imbalance": "join-skew-w4",
    "shuffle_slot_fill": "join-skew-w4",
    "range_shard_imbalance": "sort-w4",
}
EVERY_CELL = {
    "host_syncs", "window_compiles", "hbm_roofline_share",
    "device_idle_share", "stage_unattributed_share",
}


def _run(name, seed=2**31 + 33, **kw):
    cell = harness.Cell(name)
    return harness.run_cell(
        cell, jax.devices()[: cell.chips], seed, 0.05, False,
        time.perf_counter(), rows=ROWS, **kw
    )


def _read(name, obs=None):
    reader = importlib.import_module("chipbench.layer_metrics." + name)
    return reader.read(obs or {})


def _failed(result):
    return {n for n, v, limit in result["numbers"] if not v <= limit}


COUNTERS = (
    "shuffle.hash.shard_rows_max", "shuffle.hash.shard_rows_mean",
    "shuffle.coll_rows", "shuffle.coll_slots",
    "shuffle.range.shard_rows_max", "shuffle.range.shard_rows_mean",
)


def _counted():
    snap = tracing.snapshot()
    return [snap.get(name, {}).get("rows", 0) for name in COUNTERS]


@pytest.mark.parametrize("name", CELLS)
def test_cell_passes_its_own_check(name):
    before = _counted()
    result = _run(name)
    # this cell's own bumps (the readers read the process, which in a
    # whole run of these tests holds other cells' shuffles too)
    hash_max, hash_mean, rows, slots, range_max, range_mean = (
        a - b for a, b in zip(_counted(), before)
    )
    assert result["correct"], result["numbers"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"
    }
    compared = {n[0] for n in result["numbers"]}
    assert "window.row_counts_wrong" in compared
    if name == "join-skew-w4":
        assert {"join.keys_wrong", "join.shards_wrong", "join.v_sum_relgap",
                "join.w_sum_relgap", "join.cross_sum_relgap"} <= compared
        assert hash_max > 1.5 * hash_mean  # half the rows on one shard
        assert 0.0 < rows < 0.6 * slots and range_mean == 0
        assert _read("hash_shard_imbalance") > 0.0
        assert 0.0 < _read("shuffle_slot_fill") < 100.0
    else:
        assert {"sort.keys_wrong", "sort.v_relgap"} <= compared
        assert range_mean <= range_max < 1.1 * range_mean and hash_mean == 0
        assert 0.0 <= _read("range_shard_imbalance") < 10.0


def test_the_joins_input_is_the_sources_shape():
    cell = harness.Cell("join-skew-w4")
    config = cell.config
    assert config["rows"] == {"left": 16_000_000, "right": 1_000_000}
    assert config["reduced"] == ["rows"] and config["zipf_exponent"] == 1.25
    assert config["reduced_from"]["rows"] == {
        "left": 1 << 28, "right": 1 << 24}
    for word in ("rank_to_key", "zipf_law", "row_order", "seed_handling",
                 "probe_value_distribution", "build_value_distribution"):
        assert config["assumed"][word]
    data = cell.generator.make(config, 2**31 + 5, ROWS)
    params = cell.traffic["params"]
    assert cell.query.input_rows(data, params) == ROWS + ROWS // 16
    ref = cell.query.reference(data, params)
    # a primary-key to foreign-key join: every probe row once
    assert ref["rows"] == ROWS and ref["pairs"].max() > ROWS // 6
    # 16 bytes a row on both sides, 32 a joined row
    assert cell.query.least_bytes(data, params, ROWS) == (
        16 * (ROWS + ROWS // 16) + 32 * ROWS
    )


def test_float32_values_fail_the_joins_check():
    cell = harness.Cell("join-skew-w4")
    out = control.readings(
        cell, jax.devices()[: cell.chips], [41, 2**31 + 42], rows=ROWS
    )
    assert [ok for _, side, ok in out["verdicts"] if side == "sound"] == [True] * 2
    assert [ok for _, side, ok in out["verdicts"] if side == "control"] == [False] * 2
    limit = cell.query.VALUE_LIMIT["float64"]
    for number, values in out["control"].items():
        if number.endswith("relgap"):
            assert min(values) > 3 * limit, number
            assert max(out["sound"][number]) < limit / 3, number
        else:  # the lower precision fails no exact number
            assert max(values) == 0, number


def _re_paired(cols):
    """Two joined rows of different keys swap their build values."""
    k = cols["k_x"]
    j = int(np.argmax(k != k[0]))
    w = cols["w"].copy()
    w[[0, j]] = w[[j, 0]]
    return {**cols, "w": w}


def _lost_row(cols):
    return {c: a[:-1] for c, a in cols.items()}


@pytest.mark.parametrize("alter,number", [
    (_re_paired, "join.w_sum_relgap"), (_lost_row, "join.rows_gap"),
], ids=["re_paired", "lost_row"])
def test_broken_join_is_not_correct(monkeypatch, alter, number):
    cell = harness.Cell("join-skew-w4")
    build = cell.query.build

    def broken_build(tables, params):
        call = build(tables, params)

        def broken():
            out = call()
            cols = alter(out.to_pydict())
            return ct.Table.from_numpy(out.ctx, list(cols), list(cols.values()))

        return broken

    monkeypatch.setattr(cell.query, "build", broken_build)
    result = harness.run_cell(
        cell, jax.devices()[: cell.chips], 3, 0.01, False,
        time.perf_counter(), rows=ROWS,
    )
    assert result["correct"] is False
    assert number in _failed(result), _failed(result)


def test_readers_read_nothing_without_their_counters(monkeypatch):
    """The parent commit bumps no ``shuffle.hash.*`` and no
    ``shuffle.coll_*``, and a join runs no range shuffle: each reader
    returns nothing there and does not raise."""
    monkeypatch.setattr(
        tracing, "snapshot", lambda: {"host_sync": {"count": 3, "rows": 0}}
    )
    for reader in LISTED:
        assert _read(reader) is None, reader
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "shuffle.hash.shard_rows_max": {"rows": 30}, "shuffle.hash.shard_rows_mean": {"rows": 20},
        "shuffle.coll_slots": {"rows": 80}, "shuffle.coll_rows": {"rows": 20},
        "shuffle.range.shard_rows_max": {"rows": 11}, "shuffle.range.shard_rows_mean": {"rows": 10},
    })
    assert _read("hash_shard_imbalance") == pytest.approx(50.0)
    assert _read("shuffle_slot_fill") == pytest.approx(25.0)
    assert _read("range_shard_imbalance") == pytest.approx(10.0)


def test_new_manifest_names_resolve_and_are_looked_up_by_name():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert cells["join-skew-w4"] == {
        "name": "join-skew-w4", "config": "fkjoin-zipf125-w4",
        "traffic": "join-closed", "chips": 4,
        "why": cells["join-skew-w4"]["why"],
    }
    assert cells["sort-w4"]["config"] == "cylon-suite-w4"
    assert cells["sort-w4"]["traffic"] == "sort-closed"
    assert cells["sort-w4"]["chips"] == 4
    # at most half the cells, rounded down, may take four chips
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= len(manifest["workloads"]) // 2
    config = configs["fkjoin-zipf125-w4"]
    assert config["reduced"] == ["rows"] and len(config["source"]) <= 200
    body = harness.load_json(harness.ROOT, config["file"])
    assert body["name"] == config["name"] and body["source"] == config["source"]
    assert body["reduced"] == config["reduced"] and body["chips"] == 4
    assert os.path.exists(
        os.path.join(harness.HERE, "generators", body["generator"] + ".py")
    )
    for name, cell_name in LISTED.items():
        assert entries[name]["workloads"] == [cell_name]
        assert entries[name]["source"] == "program_counter"
        assert entries[name]["layer"] == "shuffle"
        assert entries[name]["moves"] == "rows_per_s"
        assert set(entries[name]) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.exists(
            os.path.join(harness.HERE, "layer_metrics", name + ".py")
        )
    for name in CELLS:
        cell = harness.Cell(name)
        assert {"build", "input_rows", "least_bytes", "reference",
                "compare"} <= set(dir(cell.query))
        assert [m["name"] for m in cell.metrics("end_to_end")] == [
            "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"]
        listed = {m["name"] for m in cell.metrics("per_layer")}
        assert EVERY_CELL <= listed
        assert {n for n, c in LISTED.items() if c == name} <= listed
        assert not {n for n, c in LISTED.items() if c != name} & listed
