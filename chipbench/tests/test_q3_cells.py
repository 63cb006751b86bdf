"""CPU rehearsal of the cell of PR 31, ``tpch-q3-w1``: it passes its own
check at a tiny size, the control (float32 values) and a lost lineitem row
fail it, the generator follows the specification's population rules, the
new manifest names resolve to files, and the four new readers give a number
where there is something to read and nothing where there is not (the parent
commit's program). A pass here is a rehearsal, never a number. (``sort-w4``
was taken out again: PERF.md section 7 says why and what it waits for.)"""
import importlib
import time

import jax
import numpy as np
import pytest

import cylon_tpu as ct
from chipbench import control, harness, stage_times
from chipbench.trace_reduce import short_name
from cylon_tpu.obs import stages
from cylon_tpu.utils import tracing

ROWS = 4096
CELLS = ("tpch-q3-w1",)
NEW = ("topk_ms", "topk_hbm_share", "join_semi_ms", "join_emit_fill")
LISTED = dict.fromkeys(NEW, "tpch-q3-w1")


def _run(name, **kw):
    cell = harness.Cell(name)
    return harness.run_cell(
        cell, jax.devices()[: cell.chips], 2**31 + 9, 0.05, False,
        time.perf_counter(), rows=ROWS, **kw
    )


def _read(name, obs):
    return importlib.import_module("chipbench.layer_metrics." + name).read(obs)


@pytest.mark.parametrize("name", CELLS)
def test_cell_passes_its_own_check(name):
    before = tracing.get_count("plan.topk")
    result = _run(name)
    assert result["correct"], result["numbers"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"
    }
    compared = {n[0] for n in result["numbers"]}
    assert "window.row_counts_wrong" in compared
    # every group was held to the reference, once, beside the ten rows
    assert {"q3.keys_wrong", "q3.revenue_relgap", "q3.groups_wrong",
            "q3.all_revenue_relgap"} <= compared
    assert tracing.get_count("plan.topk") - before >= result["attempted"]


def test_float32_values_fail_the_check():
    cell = harness.Cell("tpch-q3-w1")
    out = control.readings(cell, jax.devices()[:1], [21, 22, 23], rows=ROWS)
    assert [ok for _, side, ok in out["verdicts"] if side == "sound"] == [True] * 3
    assert [ok for _, side, ok in out["verdicts"] if side == "control"] == [False] * 3
    limit = cell.query.VALUE_LIMIT["float64"]
    for number, values in out["control"].items():
        if number.endswith("relgap"):
            assert min(values) > 3 * limit
            assert max(out["sound"][number]) < limit / 3
        else:  # the lower precision fails no exact number
            assert max(values) == 0


def _broken(monkeypatch, alter):
    cell = harness.Cell("tpch-q3-w1")
    build = cell.query.build

    def broken_build(tables, params):
        call = build(tables, params)

        def broken():
            out = call()
            cols = alter(out.to_pydict())
            return ct.Table.from_numpy(out.ctx, list(cols), list(cols.values()))

        return broken

    monkeypatch.setattr(cell.query, "build", broken_build)
    return harness.run_cell(
        cell, jax.devices()[:1], 3, 0.01, False, time.perf_counter(), rows=ROWS
    )


def _lost_row(cols):
    return {c: a[:-1] for c, a in cols.items()}


def _swapped_rows(cols):
    return {c: np.concatenate([a[1::-1], a[2:]]) for c, a in cols.items()}


def _float32_revenue(cols):
    name = "revenue_sum"
    return {**cols, name: cols[name].astype(np.float32).astype(np.float64)}


@pytest.mark.parametrize("alter,number", [
    (_lost_row, "q3.rows_gap"), (_swapped_rows, "q3.keys_wrong"),
    (_float32_revenue, "q3.revenue_relgap"),
], ids=lambda x: x.__name__.strip("_") if callable(x) else x)
def test_broken_timed_path_is_not_correct(monkeypatch, alter, number):
    result = _broken(monkeypatch, alter)
    assert result["correct"] is False
    failed = {n for n, v, limit in result["numbers"] if not v <= limit}
    assert number in failed, failed


def test_a_lost_lineitem_row_is_not_correct(monkeypatch):
    """A lineitem row that the program never sees (dropped between the
    seeded arrays and the device) takes its revenue out of one group: the
    ten rows may well not show it, the un-limited check does."""
    cell = harness.Cell("tpch-q3-w1")
    date = np.datetime64(cell.traffic["params"]["date"])

    def lose_one(data):
        ref = cell.query.reference(data, cell.traffic["params"])
        li = data["lineitem"]
        # a row that counts: shipped late, of a group outside the ten
        outside = np.setdiff1d(ref["groups"]["l_orderkey"], ref["top"]["l_orderkey"])
        row = np.flatnonzero(
            np.isin(li["l_orderkey"], outside) & (li["l_shipdate"] > date)
        )[0]
        keep = np.arange(len(li["l_orderkey"])) != row
        return {**data, "lineitem": {c: a[keep] for c, a in li.items()}}

    result = harness.run_cell(
        cell, jax.devices()[:1], 2**31 + 3, 0.01, False, time.perf_counter(),
        rows=ROWS, data_filter=lose_one,
    )
    assert result["correct"] is False
    failed = {n for n, v, limit in result["numbers"] if not v <= limit}
    assert failed & {"q3.groups_wrong", "q3.all_revenue_relgap"}, failed
    assert "q3.keys_wrong" not in failed  # the ten rows could not see it


def test_tables_follow_the_population_rules():
    cell = harness.Cell("tpch-q3-w1")
    config = cell.config
    assert config["rows"]["customer"] == 1_500_000
    assert config["rows"]["orders"] == 15_000_000
    assert config["reduced"] == ["columns", "tables"]
    data = cell.generator.make(config, 2**31 + 3, 200_000)
    again = cell.generator.make(config, 2**31 + 3, 200_000)
    for t, cols in data.items():
        assert list(cols) == list(config["tables"][t])
        assert all(np.array_equal(cols[c], again[t][c]) for c in cols)
    cu, od, li = data["customer"], data["orders"], data["lineitem"]
    assert cu["c_custkey"].dtype == od["o_orderkey"].dtype == np.int32
    assert li["l_orderkey"].dtype == od["o_custkey"].dtype == np.int32
    assert set(cu["c_mktsegment"]) == set(cell.generator.SEGMENTS)
    # sparse order keys: the first 8 of every 32
    assert ((od["o_orderkey"] - 1) % 32 < 8).all()
    assert len(np.unique(od["o_orderkey"])) == len(od["o_orderkey"])
    assert (od["o_custkey"] % 3 != 0).all()
    assert od["o_custkey"].min() >= 1 and od["o_custkey"].max() <= len(cu["c_custkey"])
    assert (od["o_shippriority"] == 0).all()
    assert od["o_orderdate"].min() >= np.datetime64("1992-01-01")
    assert od["o_orderdate"].max() <= np.datetime64("1998-08-02")
    # 1 to 7 lines an order, each 1-121 days after its order's date
    keys, lines = np.unique(li["l_orderkey"], return_counts=True)
    assert np.array_equal(keys, od["o_orderkey"])
    assert set(lines) == set(range(1, 8))
    late = li["l_shipdate"] - np.repeat(od["o_orderdate"], lines)
    assert late.min() == np.timedelta64(1, "D") and late.max() == np.timedelta64(121, "D")
    assert set(np.round(np.unique(li["l_discount"]) * 100)) == set(range(11))
    # the last join keeps about 1% of the lineitem rows
    ref = cell.query.reference(data, cell.traffic["params"])
    assert 0.002 < ref["joined_rows"] / len(li["l_orderkey"]) < 0.02
    assert ref["rows"] == 10
    rows = sum(len(next(iter(c.values()))) for c in data.values())
    assert cell.query.input_rows(data, cell.traffic["params"]) == rows
    assert cell.query.least_bytes(data, cell.traffic["params"], 10) == (
        len(cu["c_custkey"]) * 8 + len(od["o_orderkey"]) * 20
        + len(li["l_orderkey"]) * 28 + 240
    )


# -- the new readers -----------------------------------------------------
@pytest.fixture(scope="module")
def q3_obs():
    """``obs`` as a traced run hands it to the readers, made from the stage
    table of the programs the cell really dispatched on a CPU context: two
    milliseconds an instruction, two queries."""
    cell = harness.Cell("tpch-q3-w1")
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:1])
    )
    result = harness.run_cell(
        cell, jax.devices()[:1], 5, 0.01, False, time.perf_counter(),
        rows=ROWS, ctx=ctx, max_queries=2,
    )
    assert result["correct"]
    table = stages.device_stage_table(ctx)
    ops = {}
    for _module, text, _op in table["rows"]:
        ops[short_name(text.removeprefix("ROOT "))] = 0.002
    obs = {
        "queries": 2, "least_bytes": 819e9 * 0.001,
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"window_s": 1.0, "devices": {
            "/device:TPU:0": {"busy_s": 0.002 * len(ops), "ops": list(ops.items())},
        }},
    }
    return cell, table, obs


def test_q3_readers_give_a_number(q3_obs, monkeypatch):
    cell, table, obs = q3_obs
    monkeypatch.setattr(stages, "device_stage_table", lambda ctx=None: table)
    programs = {module for module, _text, _op in table["rows"]}
    assert {"jit_join_semi", "jit_join_reduce", "jit_join_spec", "jit_topk",
            "jit_groupby", "jit_expr_eval"} <= programs
    # no filter compacts a table and nothing sorts every group
    assert "jit_filter" not in programs and "jit_sort" not in programs
    for reader in ("topk_ms", "join_semi_ms"):
        assert _read(reader, obs) > 0
    ref = cell.query._RUN["ref"]
    groups = len(ref["groups"]["revenue_sum"])
    least = cell.query.topk_least_bytes(groups, ref["rows"])
    assert least == groups * 16 + ref["rows"] * 24
    assert _read("topk_hbm_share", obs) == pytest.approx(
        100.0 * least / 819e9 / (_read("topk_ms", obs) / 1e3)
    )
    fill = _read("join_emit_fill", obs)
    assert 25.0 < fill <= 100.0  # every emit was under twice its rows


@pytest.mark.parametrize("reader", ["topk_ms", "topk_hbm_share", "join_semi_ms"])
@pytest.mark.parametrize("trace", [
    None, {"window_s": 0.0, "devices": {}},
], ids=["no-trace", "no-device-plane"])
def test_nothing_to_read_without_a_device_trace(reader, trace):
    assert _read(reader, {"queries": 3, "trace": trace,
                          "peaks": {"hbm_bytes_per_s": 819e9}}) is None


def test_a_program_without_the_scopes_and_counters_reads_nothing(monkeypatch):
    """The parent commit has none of the new scopes and counters: every new
    reader returns nothing and does not raise."""
    rows = [("jit_sort", "%sort.1 = s32[64]{0} sort(%p.1), dimensions={0}",
             "jit(sort)/sort.perm/sort_engine/sort")]
    monkeypatch.setattr(
        stages, "device_stage_table",
        lambda ctx=None: {"rows": rows, "stale": [], "programs": 1, "seconds": 0.0},
    )
    # the readers and this file hold the one ``tracing`` module
    monkeypatch.setattr(
        tracing, "snapshot", lambda: {"host_sync": {"count": 3, "rows": 0}}
    )
    obs = {
        "queries": 1, "least_bytes": 1.0, "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"window_s": 1.0, "devices": {"/device:TPU:0": {
            "busy_s": 0.5, "ops": [("sort.1 s32[64] sort", 0.5)]}}},
    }
    for reader in NEW:
        assert _read(reader, obs) is None, reader


def test_new_manifest_entries_resolve():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert [w["name"] for w in manifest["workloads"]][-1:] == list(CELLS)
    assert cells["tpch-q3-w1"]["chips"] == 1
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(manifest["workloads"]) == 6
    assert manifest["configs"][-1]["name"] == "tpch-sf10-q3-w1"
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-4:] == list(NEW)
    for name in NEW:
        assert entries[name]["workloads"] == [LISTED[name]]
        assert set(entries[name]) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
    for name in CELLS:
        cell = harness.Cell(name)
        assert {"build", "input_rows", "least_bytes", "reference",
                "compare"} <= set(dir(cell.query))
        assert [m["name"] for m in cell.metrics("end_to_end")] == [
            "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"]
        listed = {m["name"] for m in cell.metrics("per_layer")}
        assert {n for n in NEW if LISTED[n] == name} <= listed
        assert not {n for n in NEW if LISTED[n] != name} & listed
