"""CPU rehearsal of the two group-by cells (PR 27): ``tpch-q1-w1`` and
``groupby-w1`` pass their own checks at tiny sizes, the control (float32
values) and a broken timed path fail them, the generator follows the
specification's population rules, and the five new readers give a number
on the stage table of a CPU run and nothing where their stage is absent.
A pass here is a rehearsal, never a number."""
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
CELLS = ("tpch-q1-w1", "groupby-w1")
NEW = (
    "groupby_key_ids_ms", "groupby_segment_ms", "groupby_dense_ms",
    "expr_eval_ms", "groupby_dense_hbm_share",
)
#: the readers that find something to read in each cell
READS = {
    "tpch-q1-w1": {"groupby_key_ids_ms", "groupby_dense_ms", "expr_eval_ms",
                   "groupby_dense_hbm_share"},
    "groupby-w1": {"groupby_key_ids_ms", "groupby_segment_ms"},
}
#: the cells BENCHMARK.json lists each reader under
LISTED = {name: {c for c in CELLS if name in READS[c]} for name in NEW}


def _run(name, **kw):
    cell = harness.Cell(name)
    return harness.run_cell(
        cell, jax.devices()[:1], 2**31 + 9, 0.05, False,
        time.perf_counter(), rows=ROWS, **kw
    )


@pytest.mark.parametrize("name,path", [
    ("tpch-q1-w1", "groupby.dense_path"), ("groupby-w1", "groupby.factorize_path"),
])
def test_cell_passes_its_own_check_on_the_path_expected(name, path):
    other = {"groupby.dense_path", "groupby.factorize_path"} - {path}
    before = {c: tracing.get_count(c) for c in (path, *other)}
    result = _run(name)
    assert result["correct"], result["numbers"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"
    }
    compared = {n[0] for n in result["numbers"]}
    assert "window.row_counts_wrong" in compared
    # every call of the window took the one path: the input decided
    assert tracing.get_count(path) - before[path] >= result["attempted"]
    assert all(tracing.get_count(c) == before[c] for c in other)


@pytest.mark.parametrize("name", CELLS)
def test_float32_values_fail_the_check(name):
    cell = harness.Cell(name)
    out = control.readings(cell, jax.devices()[:1], [21, 22, 23], rows=ROWS)
    assert [ok for _, side, ok in out["verdicts"] if side == "sound"] == [True] * 3
    assert [ok for _, side, ok in out["verdicts"] if side == "control"] == [False] * 3
    for number, values in out["control"].items():
        limit = (
            cell.query.AVGS_LIMIT if number == "q1.avgs_relgap"
            else cell.query.VALUE_LIMIT
        )["float64"]
        if number.endswith("relgap"):
            assert min(values) > 3 * limit
            assert max(out["sound"][number]) < limit / 3
        else:  # the lower precision fails no exact number
            assert max(values) == 0


def _broken(monkeypatch, cell, alter):
    build = cell.query.build

    def broken_build(tables, params):
        call = build(tables, params)

        def broken():
            out = call()
            cols = alter(out.to_pydict(), tables)
            if not isinstance(cols, dict):
                return cols
            return ct.Table.from_numpy(out.ctx, list(cols), list(cols.values()))

        return broken

    monkeypatch.setattr(cell.query, "build", broken_build)
    return harness.run_cell(
        cell, jax.devices()[:1], 3, 0.01, False, time.perf_counter(), rows=ROWS
    )


def _lost_group(cols, _tables):
    return {c: a[1:] for c, a in cols.items()}


def _wrong_count(cols, _tables):
    return {**cols, "l_quantity_count": cols["l_quantity_count"] + [0, 1, 0, 0]}


def _float32_value(cols, _tables):
    name = "charge_sum" if "charge_sum" in cols else "v_sum"
    return {**cols, name: cols[name].astype(np.float32).astype(np.float64)}


def _float32_discount_mean(cols, _tables):
    # what a float32 accumulation of l_discount alone would give: no sum
    # reads that column, so only the averages' limit can see it
    name = "l_discount_mean"
    return {**cols, name: cols[name].astype(np.float32).astype(np.float64)}


def _swapped_groups(cols, _tables):
    return {c: a[::-1].copy() for c, a in cols.items()}


def _input_as_answer(_cols, tables):
    return next(iter(tables.values()))


@pytest.mark.parametrize("name,alter,number", [
    ("tpch-q1-w1", _lost_group, "q1.rows_gap"),
    ("tpch-q1-w1", _wrong_count, "q1.count_order_wrong"),
    ("tpch-q1-w1", _float32_value, "q1.sums_relgap"),
    ("tpch-q1-w1", _float32_discount_mean, "q1.avgs_relgap"),
    ("tpch-q1-w1", _swapped_groups, "q1.groups_wrong"),
    ("tpch-q1-w1", _input_as_answer, "q1.columns_wrong"),
    ("groupby-w1", _lost_group, "groupby.rows_gap"),
    ("groupby-w1", _float32_value, "groupby.v_sum_relgap"),
    ("groupby-w1", _swapped_groups, "groupby.keys_wrong"),
    ("groupby-w1", _input_as_answer, "window.row_counts_wrong"),
], ids=lambda x: x if isinstance(x, str) else x.__name__.strip("_"))
def test_broken_timed_path_is_not_correct(monkeypatch, name, alter, number):
    result = _broken(monkeypatch, harness.Cell(name), alter)
    assert result["correct"] is False
    failed = {n for n, v, limit in result["numbers"] if not v <= limit}
    assert number in failed, failed


def test_lineitem_follows_the_population_rules():
    cell = harness.Cell("tpch-q1-w1")
    config = cell.config
    assert config["rows"] == 59_986_052 and config["reduced"] == ["columns"]
    li = cell.generator.make(config, 2**31 + 3, 50_000)["lineitem"]
    again = cell.generator.make(config, 2**31 + 3, 50_000)["lineitem"]
    assert all(np.array_equal(li[c], again[c]) for c in li)
    assert list(li) == list(config["tables"]["lineitem"])
    qty, price = li["l_quantity"], li["l_extendedprice"]
    assert set(np.unique(qty)) == set(range(1, 51))
    assert set(np.round(np.unique(li["l_discount"]) * 100)) == set(range(11))
    assert set(np.round(np.unique(li["l_tax"]) * 100)) == set(range(9))
    # quantity times a retail price of 900.00 to 2,099.00, in cents
    unit = np.round(price * 100) / qty
    assert unit.min() >= 90000 and unit.max() <= 209900
    assert np.array_equal(unit, np.round(unit))
    ship = li["l_shipdate"]
    assert ship.dtype == "datetime64[D]"
    assert ship.min() >= np.datetime64("1992-01-02")
    assert ship.max() <= np.datetime64("1998-12-01")
    current = np.datetime64("1995-06-17")
    flag, status = li["l_returnflag"], li["l_linestatus"]
    assert set(flag) == {"A", "N", "R"} and set(status) == {"F", "O"}
    assert np.array_equal(status == "O", ship > current)
    # returned or accepted only if received by the current date, which a
    # row shipped after it never is; R and A are an even coin
    assert not ((flag != "N") & (ship > current)).any()
    share = (flag == "R").sum() / ((flag == "R") | (flag == "A")).sum()
    assert 0.48 < share < 0.52
    # Q1 at DELTA = 90: four groups, nearly every row passes
    ref = cell.query.reference({"lineitem": li}, cell.traffic["params"])
    assert ref["rows"] == 4 and 0.97 < ref["count_order"].sum() / len(ship) < 0.995
    assert cell.query.input_rows({"lineitem": li}, cell.traffic["params"]) == 50_000
    assert cell.query.least_bytes(
        {"lineitem": li}, cell.traffic["params"], 4
    ) == 50_000 * 48 + 4 * 80


# -- the new readers -----------------------------------------------------
def _read(name, obs):
    return importlib.import_module("chipbench.layer_metrics." + name).read(obs)


@pytest.fixture(scope="module", params=CELLS)
def cpu_obs(request):
    """``obs`` as a traced run hands it to the readers, made from the stage
    table of the programs the cell really dispatched on a CPU context: one
    millisecond an instruction, two queries."""
    cell = harness.Cell(request.param)
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
    return request.param, table, obs


def test_readers_give_a_number_where_their_stage_ran(cpu_obs, monkeypatch):
    name, table, obs = cpu_obs
    monkeypatch.setattr(stages, "device_stage_table", lambda ctx=None: table)
    programs = {module for module, _text, _op in table["rows"]}
    if name == "tpch-q1-w1":
        assert {"jit_groupby_dense", "jit_expr_eval"} <= programs
        assert "jit_groupby" not in programs and "jit_filter" not in programs
    else:
        assert "jit_groupby" in programs and "jit_groupby_dense" not in programs
    for reader in NEW:
        value = _read(reader, obs)
        if name == "tpch-q1-w1" and reader == "groupby_key_ids_ms":
            # the CPU's compiler fuses the id arithmetic into the
            # reductions that read it; the chip's keeps it a fusion of its
            # own (the compile for a described v5e, PERF.md section 6)
            assert value is None or value > 0
        elif reader in READS[name]:
            assert value is not None and value > 0, reader
        else:
            assert value is None, reader
    found = stage_times.split(obs)
    if name == "tpch-q1-w1":
        dense = found["stages_ms"]["groupby.dense_agg"] + found["stages_ms"]["expr.eval"]
        # least time 1 ms over the two stages' milliseconds
        assert _read("groupby_dense_hbm_share", obs) == pytest.approx(100.0 / dense)


@pytest.mark.parametrize("reader", NEW)
@pytest.mark.parametrize("trace", [
    None, {"window_s": 0.0, "devices": {}},
], ids=["no-trace", "no-device-plane"])
def test_nothing_to_read_without_a_device_trace(reader, trace):
    assert _read(reader, {"queries": 3, "trace": trace}) is None


def test_a_program_without_the_stages_reads_nothing(monkeypatch):
    """The parent commit's stage table has none of the new names: every new
    reader returns nothing and does not raise."""
    rows = [("jit_groupby", "%fusion.1 = f64[64]{0} fusion(%p.1), kind=kLoop",
             "jit(groupby)/scatter-add")]
    monkeypatch.setattr(
        stages, "device_stage_table",
        lambda ctx=None: {"rows": rows, "stale": [], "programs": 1, "seconds": 0.0},
    )
    obs = {
        "queries": 1, "least_bytes": 1.0, "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"window_s": 1.0, "devices": {"/device:TPU:0": {
            "busy_s": 0.5, "ops": [("fusion.1 f64[64] fusion:kLoop", 0.5)]}}},
    }
    for reader in NEW:
        assert _read(reader, obs) is None


def test_new_manifest_entries_resolve():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert [w["name"] for w in manifest["workloads"]][-2:] == list(CELLS)
    assert all(cells[c]["chips"] == 1 for c in CELLS)
    assert manifest["configs"][-1]["name"] == "tpch-sf10-w1"
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-5:] == list(NEW)
    for name in NEW:
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["layer"] == "kernels"
        assert set(entries[name]["workloads"]) == LISTED[name]
    for name in CELLS:
        cell = harness.Cell(name)
        assert {"build", "input_rows", "least_bytes", "reference",
                "compare"} <= set(dir(cell.query))
        assert [m["name"] for m in cell.metrics("end_to_end")] == [
            "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"]
