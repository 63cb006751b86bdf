"""CPU rehearsal of the two cells of PR 39, ``tpch-q6-w1`` (an aggregate
without keys on one chip) and ``tpch-q1-w4`` (Q1 over four chips, the
shards' partial states combined in place): each passes its own check at
4,096 rows on the CPU mesh, the control (float32 values) fails it, no row
of ``tpch-q1-w4`` is exchanged, the new manifest entries resolve to files
and are looked up by name, and the three new readers give a number from a
synthetic ``obs`` and nothing where the program has no such stage or
counter (the parent commit's). A pass here is a rehearsal, never a
number."""
import importlib
import os
import time

import jax
import numpy as np
import pytest

from chipbench import control, harness
from cylon_tpu.obs import stages
from cylon_tpu.utils import tracing

ROWS = 4096
CELLS = ("tpch-q6-w1", "tpch-q1-w4")
#: the cells BENCHMARK.json lists each new reader under
LISTED = {
    "groupby_combine_ms": ["tpch-q1-w4"],
    "groupby_partial_share": ["tpch-q1-w4"],
    "dense_partial_hbm_share": ["tpch-q6-w1", "tpch-q1-w4"],
}
EVERY_CELL = {
    "host_syncs", "window_compiles", "hbm_roofline_share",
    "device_idle_share", "stage_unattributed_share",
}


def _run(name, seed=2**31 + 39, **kw):
    cell = harness.Cell(name)
    return harness.run_cell(
        cell, jax.devices()[: cell.chips], seed, 0.05, False,
        time.perf_counter(), rows=ROWS, **kw
    )


def _read(name, obs=None):
    reader = importlib.import_module("chipbench.layer_metrics." + name)
    return reader.read(obs if obs is not None else {})


def _rows(name):
    return tracing.snapshot().get(name, {}).get("rows", 0)


@pytest.mark.parametrize("name", CELLS)
def test_cell_passes_its_own_check(name):
    partial = tracing.get_count("groupby.partial_path")
    dense = tracing.get_count("groupby.dense_path")
    kept, moved = _rows("groupby.partial.rows"), _rows("shuffle.coll_rows")
    result = _run(name)
    assert result["correct"], result["numbers"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"
    }
    compared = {n[0] for n in result["numbers"]}
    assert "window.row_counts_wrong" in compared
    calls = tracing.get_count("groupby.dense_path") - dense
    assert calls >= result["attempted"]  # every query on the dense path
    assert _rows("shuffle.coll_rows") == moved, "no row is exchanged"
    if name == "tpch-q6-w1":
        assert {"q6.rows_gap", "q6.revenue_null", "q6.revenue_relgap"} <= compared
        assert tracing.get_count("groupby.partial_path") == partial  # one shard
    else:
        assert {"q1.groups_wrong", "q1.count_order_wrong", "q1.sums_relgap",
                "q1.avgs_relgap"} <= compared
        assert tracing.get_count("groupby.partial_path") - partial == calls
        assert _rows("groupby.partial.rows") - kept == calls * ROWS


@pytest.mark.parametrize("name", CELLS)
def test_float32_values_fail_the_check(name):
    cell = harness.Cell(name)
    out = control.readings(
        cell, jax.devices()[: cell.chips], [39, 2**31 + 40], rows=ROWS
    )
    assert [ok for _, side, ok in out["verdicts"] if side == "sound"] == [True] * 2
    assert [ok for _, side, ok in out["verdicts"] if side == "control"] == [False] * 2
    for number, values in out["control"].items():
        limit = (
            cell.query.AVGS_LIMIT if number == "q1.avgs_relgap"
            else cell.query.VALUE_LIMIT
        )["float64"]
        if number.endswith("relgap"):
            assert min(values) > 3 * limit, number
            assert max(out["sound"][number]) < limit / 3, number
        else:  # the lower precision fails no exact number
            assert max(values) == 0, number


def test_q6_reads_the_rows_the_decimal_query_means():
    cell = harness.Cell("tpch-q6-w1")
    params = cell.traffic["params"]
    assert params == {
        "table": "lineitem", "date": "1994-01-01", "discount": 0.06,
        "quantity": 24,
    }
    b = cell.query.bounds(params)
    # the doubles nearest the decimal bounds, not 0.06 -/+ 0.01 in float64
    assert (b["discount_lo"], b["discount_hi"]) == (0.05, 0.07)
    assert b["discount_hi"] != params["discount"] + 0.01
    assert b["date_hi"] == np.datetime64("1995-01-01")
    data = cell.generator.make(cell.config, 2**31 + 6, 50_000)
    li = data["lineitem"]
    ref = cell.query.reference(data, params)
    cents = np.rint(li["l_discount"] * 100).astype(np.int64)
    exact = (
        (li["l_shipdate"] >= b["date_lo"]) & (li["l_shipdate"] < b["date_hi"])
        & (cents >= 5) & (cents <= 7)
        & (np.rint(li["l_quantity"]).astype(np.int64) < 24)
    )
    assert ref["rows"] == 1 and ref["passing"] == int(exact.sum())
    assert 0.015 < ref["passing"] / 50_000 < 0.023  # about 1.9% pass
    assert cell.query.input_rows(data, params) == 50_000
    # four columns of 8 bytes read once, one float64 out
    assert cell.query.least_bytes(data, params, 1) == 50_000 * 32 + 8


def test_the_sf30_table_is_the_sources_shape():
    cell = harness.Cell("tpch-q1-w4")
    config, sf10 = cell.config, harness.Cell("tpch-q1-w1").config
    assert config["scale_factor"] == 30 and config["rows"] == 179_998_372
    assert config["rows"] == 4 * 44_999_593 and config["partkeys"] == 6_000_000
    assert config["reduced"] == ["columns"] and config["chips"] == 4
    for same in ("tables", "orderdate", "generator", "lower_precision", "load"):
        assert config[same] == sf10[same], same
    assert config["guarantees"]["value_precision"] == "float64"
    assert "fixed order" in config["guarantees"]["semantics"]
    assert config["source"] != sf10["source"]
    # the cell runs tpch-q1-w1's traffic file and query module as they are
    assert cell.traffic is not None and cell.traffic["query"] == "tpch_q1"
    assert cell.query is harness.Cell("tpch-q1-w1").query


# -- the new readers -----------------------------------------------------
#: (name, shape, opcode, fusion kind, seconds over two queries)
OPS = [
    ("fusion.1", "f32[64]", "fusion", "kLoop", 0.004),
    ("fusion.2", "f32[64]", "fusion", "kLoop", 0.005),
    ("all-gather.3", "f32[4,64]", "all-gather", None, 0.001),
]


def _obs(stage_of_op, chips):
    """``obs`` as a traced run hands it to the readers, and the program's
    stage table: three operations of 4, 5 and 1 ms over two queries on the
    first of ``chips`` devices, each under the stage given; the least
    bytes are 1 ms of one chip's HBM."""
    rows, ops = [], []
    for (name, shape, opcode, kind, seconds), stage in zip(OPS, stage_of_op):
        tail = f", kind={kind}, calls=%fused_computation.1" if kind else ""
        rows.append((
            "jit_groupby_dense",
            f"%{name} = {shape}{{0:T(1024)}} {opcode}(%p.1, %p.2){tail}",
            f"jit(groupby_dense)/shard_map/{stage}/reduce",
        ))
        ops.append((f"{name} {shape} {opcode}" + (f":{kind}" if kind else ""),
                    seconds))
    devices = {
        f"/device:TPU:{i}": {"busy_s": 0.010, "ops": ops if i == 0 else [("x", 0.010)]}
        for i in range(chips)
    }
    table = {"rows": rows, "stale": [], "programs": 1, "seconds": 0.0}
    obs = {
        "queries": 2, "least_bytes": 819e9 * 0.001,
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"window_s": 1.0, "devices": devices},
    }
    return table, obs


@pytest.mark.parametrize("chips", [1, 4])
def test_stage_readers_give_a_number_and_the_share_divides_by_the_chips(
    monkeypatch, chips
):
    table, obs = _obs(
        ["groupby.dense_agg", "expr.eval", "groupby.combine"], chips
    )
    monkeypatch.setattr(stages, "device_stage_table", lambda ctx=None: table)
    # 1 ms of combine over two queries
    assert _read("groupby_combine_ms", obs) == pytest.approx(0.5)
    # the three stages took 5 ms a query; the first chip's least time is
    # 1 ms over the chips, since it holds one share of the rows
    assert _read("dense_partial_hbm_share", obs) == pytest.approx(
        100.0 * (1.0 / chips) / 5.0
    )


def test_stage_readers_read_nothing_without_their_stage(monkeypatch):
    """The parent's program has no ``groupby.combine``; a cell on another
    path runs none of the three stages; a run without a device plane has
    no trace to read: nothing, and no raise."""
    table, obs = _obs(["groupby.dense_agg", "expr.eval", "expr.eval"], 4)
    monkeypatch.setattr(stages, "device_stage_table", lambda ctx=None: table)
    assert _read("groupby_combine_ms", obs) is None
    assert _read("dense_partial_hbm_share", obs) == pytest.approx(100.0 / 4 / 5.0)
    table, obs = _obs(["sort.local", "shuffle.pack", "shuffle.all_to_all"], 4)
    monkeypatch.setattr(stages, "device_stage_table", lambda ctx=None: table)
    for reader in ("groupby_combine_ms", "dense_partial_hbm_share"):
        assert _read(reader, obs) is None, reader
        for trace in (None, {"window_s": 0.0, "devices": {}}):
            assert _read(reader, {"queries": 3, "trace": trace}) is None


def test_the_partial_share_is_rows_kept_over_rows_kept_and_moved(monkeypatch):
    monkeypatch.setattr(
        tracing, "snapshot", lambda: {"host_sync": {"count": 3, "rows": 0}}
    )
    assert _read("groupby_partial_share") is None  # the parent: no counter
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "groupby.partial.rows": {"count": 2, "rows": 0}})
    assert _read("groupby_partial_share") is None  # nothing aggregated yet
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "groupby.partial.rows": {"count": 2, "rows": 8192}})
    assert _read("groupby_partial_share") == pytest.approx(100.0)
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "groupby.partial.rows": {"count": 2, "rows": 3000},
        "shuffle.coll_rows": {"count": 1, "rows": 1000}})
    assert _read("groupby_partial_share") == pytest.approx(75.0)


def test_new_manifest_entries_resolve_and_are_looked_up_by_name():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert cells["tpch-q6-w1"] == {
        "name": "tpch-q6-w1", "config": "tpch-sf10-w1",
        "traffic": "tpch-q6-closed", "chips": 1,
        "why": cells["tpch-q6-w1"]["why"],
    }
    assert cells["tpch-q1-w4"] == {
        "name": "tpch-q1-w4", "config": "tpch-sf30-w4",
        "traffic": "tpch-q1-closed", "chips": 4,
        "why": cells["tpch-q1-w4"]["why"],
    }
    assert all(len(cells[c]["why"]) <= 200 for c in CELLS)
    # at most half the cells, rounded down, may take four chips
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= len(manifest["workloads"]) // 2
    config = configs["tpch-sf30-w4"]
    assert config["reduced"] == ["columns"] and len(config["source"]) <= 200
    body = harness.load_json(harness.ROOT, config["file"])
    assert body["name"] == config["name"] and body["source"] == config["source"]
    assert body["reduced"] == config["reduced"] and body["chips"] == 4
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)  # no other configuration's file
    assert os.path.exists(
        os.path.join(harness.HERE, "generators", body["generator"] + ".py")
    )
    for name, listed in LISTED.items():
        assert entries[name]["workloads"] == listed
        assert set(entries[name]) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.exists(
            os.path.join(harness.HERE, "layer_metrics", name + ".py")
        )
    assert entries["groupby_combine_ms"]["source"] == "device_trace"
    assert entries["groupby_combine_ms"]["moves"] == "query_p50_ms"
    assert entries["groupby_partial_share"]["source"] == "program_counter"
    assert entries["dense_partial_hbm_share"]["layer"] == "kernels"
    assert entries["dense_partial_hbm_share"]["unit"] == "%"
    for name in CELLS:
        cell = harness.Cell(name)
        assert {"build", "input_rows", "least_bytes", "reference",
                "compare"} <= set(dir(cell.query))
        assert [m["name"] for m in cell.metrics("end_to_end")] == [
            "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"]
        listed = {m["name"] for m in cell.metrics("per_layer")}
        assert EVERY_CELL <= listed
        assert {n for n, c in LISTED.items() if name in c} <= listed
        assert not {n for n, c in LISTED.items() if name not in c} & listed
