"""CPU rehearsal of the cells of PR 41, ``tpch-q1-delta-w1`` and
``tpch-q4-w1``: each passes its own check at a tiny size, the control
(dates cut to the month for Q4, float32 values for Q1) fails it, an order's lost
late lines and two swapped rows fail Q4, a Q1 result is held to the
reference of ITS DELTA and fails another's, the DELTA sequence is the
seed's, the generator follows the specification's population rules, the
new manifest names resolve to files BY NAME, and the three new readers give
a number where there is something to read and nothing where there is not
(the parent commit's program). A pass here is a rehearsal, never a
number."""
import importlib
import time

import jax
import numpy as np
import pytest

import cylon_tpu as ct
from chipbench import control, harness
from chipbench.trace_reduce import short_name
from cylon_tpu.obs import stages
from cylon_tpu.utils import tracing

ROWS = 4096
Q4, DELTA = "tpch-q4-w1", "tpch-q1-delta-w1"
NEW = ("semi_join_ms", "semi_join_hbm_share", "semi_payload_rows")


def _run(name, seed=2**31 + 9, **kw):
    cell = harness.Cell(name)
    kw.setdefault("max_queries", 4)
    return cell, harness.run_cell(
        cell, jax.devices()[: cell.chips], seed, 1e9, False,
        time.perf_counter(), rows=ROWS, **kw
    )


def _read(name, obs):
    return importlib.import_module("chipbench.layer_metrics." + name).read(obs)


def _failed(result):
    return {n for n, v, limit in result["numbers"] if not v <= limit}


# -- tpch-q4-w1 -----------------------------------------------------------
def test_q4_cell_passes_its_own_check():
    fired = tracing.get_count("plan.rule.semi_as_mask")
    payload = tracing.snapshot().get("join.semi.payload_rows", {}).get("rows", 0)
    cell, result = _run(Q4)
    assert result["correct"], result["numbers"]
    assert result["attempted"] == 4 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"
    }
    compared = {n[0] for n in result["numbers"]}
    assert {
        "window.row_counts_wrong", "q4.priorities_wrong", "q4.counts_gap",
        "q4.anti_counts_gap", "q4.semi_plus_anti_gap", "q4.eager_rows_gap",
        "q4.eager_columns_wrong", "q4.eager_keys_wrong",
    } <= compared
    # every timed query handed its hit mask over; only the eager check,
    # once and outside the window, compacted rows
    assert tracing.get_count("plan.rule.semi_as_mask") - fired >= 4
    ref = cell.query._RUN["ref"]
    moved = tracing.snapshot()["join.semi.payload_rows"]["rows"] - payload
    assert moved == len(ref["semi_keys"]) > 0


def test_q4_dates_cut_to_the_month_fail_the_check():
    cell = harness.Cell(Q4)
    assert cell.config["guarantees"]["value_precision"] == "datetime64[D]"
    assert cell.config["lower_precision"] == "datetime64[M]"
    out = control.readings(cell, jax.devices()[:1], [21, 22, 23], rows=ROWS)
    assert [ok for _, side, ok in out["verdicts"] if side == "sound"] == [True] * 3
    assert [ok for _, side, ok in out["verdicts"] if side == "control"] == [False] * 3
    assert max(max(v) for v in out["sound"].values()) == 0
    assert min(out["control"]["q4.counts_gap"]) > 0


def test_q4_lost_late_lines_are_not_correct():
    """The late lines of one order of the quarter never reach the device:
    the order leaves the semi side for the anti side."""
    cell = harness.Cell(Q4)
    params = cell.traffic["params"]

    def lose_one(data):
        ref = cell.query.reference(data, params)
        li = data["lineitem"]
        late = li["l_commitdate"] < li["l_receiptdate"]
        keep = ~(late & (li["l_orderkey"] == ref["semi_keys"][0]))
        assert 1 <= (~keep).sum() <= 7
        return {**data, "lineitem": {c: a[keep] for c, a in li.items()}}

    result = harness.run_cell(
        cell, jax.devices()[:1], 2**31 + 3, 1e9, False, time.perf_counter(),
        rows=ROWS, data_filter=lose_one, max_queries=2,
    )
    assert result["correct"] is False
    assert {"q4.counts_gap", "q4.anti_counts_gap", "q4.eager_rows_gap"} <= _failed(result)
    assert "q4.semi_plus_anti_gap" not in _failed(result)  # it only moved


def test_q4_a_swapped_priority_is_not_correct(monkeypatch):
    cell = harness.Cell(Q4)
    build = cell.query.build

    def broken_build(tables, params):
        call = build(tables, params)

        def broken():
            out = call()
            cols = out.to_pydict()
            cols = {c: np.concatenate([a[1::-1], a[2:]]) for c, a in cols.items()}
            return ct.Table.from_numpy(out.ctx, list(cols), list(cols.values()))

        return broken

    monkeypatch.setattr(cell.query, "build", broken_build)
    result = harness.run_cell(
        cell, jax.devices()[:1], 3, 1e9, False, time.perf_counter(),
        rows=ROWS, max_queries=2,
    )
    assert result["correct"] is False
    assert "q4.priorities_wrong" in _failed(result)


def test_q4_tables_follow_the_population_rules():
    cell = harness.Cell(Q4)
    config = cell.config
    assert config["rows"]["orders"] == 15_000_000
    assert config["reduced"] == ["columns", "tables"]
    assert config["reduced_from"] == {"columns": 25, "tables": 8}
    data = cell.generator.make(config, 2**31 + 3, 400_000)
    again = cell.generator.make(config, 2**31 + 3, 400_000)
    other = cell.generator.make(config, 2**31 + 4, 400_000)
    for t, cols in data.items():
        assert list(cols) == list(config["tables"][t])
        assert all(np.array_equal(cols[c], again[t][c]) for c in cols)
    assert not np.array_equal(
        data["orders"]["o_orderdate"], other["orders"]["o_orderdate"]
    )
    od, li = data["orders"], data["lineitem"]
    assert od["o_orderkey"].dtype == li["l_orderkey"].dtype == np.int32
    assert od["o_orderdate"].dtype == li["l_commitdate"].dtype == "datetime64[D]"
    assert set(od["o_orderpriority"]) == set(cell.generator.PRIORITIES)
    assert ((od["o_orderkey"] - 1) % 32 < 8).all()
    assert len(np.unique(od["o_orderkey"])) == len(od["o_orderkey"])
    assert od["o_orderdate"].min() >= np.datetime64("1992-01-01")
    assert od["o_orderdate"].max() <= np.datetime64("1998-08-02")
    keys, lines = np.unique(li["l_orderkey"], return_counts=True)
    assert np.array_equal(keys, od["o_orderkey"])
    assert set(lines) == set(range(1, 8))
    ordered = np.repeat(od["o_orderdate"], lines)
    commit, receipt = li["l_commitdate"] - ordered, li["l_receiptdate"] - ordered
    assert commit.min() == np.timedelta64(30, "D") and commit.max() == np.timedelta64(90, "D")
    assert receipt.min() == np.timedelta64(2, "D") and receipt.max() == np.timedelta64(151, "D")
    # the issue's reckoning: 63.2% of the lines late, 3.8% of the orders in
    # the quarter, 91.7% of those with a late line
    late = (li["l_commitdate"] < li["l_receiptdate"]).mean()
    assert 0.62 < late < 0.645
    ref = cell.query.reference(data, cell.traffic["params"])
    quarter, semi = ref["quarter"][cell.query.COUNT].sum(), ref["semi"][cell.query.COUNT].sum()
    assert 0.03 < quarter / len(od["o_orderkey"]) < 0.046
    assert 0.88 < semi / quarter < 0.95
    assert ref["rows"] == 5
    assert cell.query.quarter("1993-07-01", 3) == (
        np.datetime64("1993-07-01"), np.datetime64("1993-10-01")
    )
    rows = len(od["o_orderkey"]) + len(li["l_orderkey"])
    params = cell.traffic["params"]
    assert cell.query.input_rows(data, params) == rows
    assert cell.query.least_bytes(data, params, 5) == (
        len(od["o_orderkey"]) * 16 + len(li["l_orderkey"]) * 20 + 60
    )
    assert cell.query.semi_least_bytes(data) == rows * 5 + len(od["o_orderkey"])


# -- tpch-q1-delta-w1 -----------------------------------------------------
def test_delta_cell_passes_its_own_check_and_draws_the_seeds_sequence():
    cell, result = _run(DELTA, max_queries=6)
    assert result["correct"], result["numbers"]
    assert result["attempted"] == 6
    compared = {n[0] for n in result["numbers"]}
    assert {"window.row_counts_wrong", "q1.groups_wrong", "q1.sums_relgap",
            "q1.avgs_relgap", "q1_delta.distinct_short"} <= compared
    params = cell.traffic["params"]
    assert params["delta_days"] == "60-120" and params["delta_seed"] == 2401
    drawn = list(cell.query._RUN["drawn"])
    rng = np.random.default_rng(2401)
    assert drawn == [int(rng.integers(60, 121)) for _ in drawn]
    assert len(drawn) >= 7 and len(set(drawn)) > 3  # the warm-ups drew too
    # the same sequence in the next run, whatever its data's seed
    cell2, _ = _run(DELTA, seed=17, max_queries=6)
    assert cell2.query._RUN["drawn"][:7] == drawn[:7]
    # a short window is told how far it got and held to nothing
    short = [n for n in result["numbers"] if n[0] == "q1_delta.distinct_short"]
    assert short[0][2] == float("inf")


def test_delta_result_is_held_to_its_own_delta():
    cell, result = _run(DELTA, max_queries=3)
    q = cell.query
    data, params = q._RUN["data"], q._RUN["params"]
    tables = harness.load_tables(
        ct.CylonContext.init_distributed(ct.TPUConfig(devices=jax.devices()[:1])),
        data,
    )
    call = q.build(tables, params)
    out = call()
    delta = q._RUN["delta_of"][id(out)]
    assert 60 <= delta <= 120
    assert q.cutoff(params, 90) == np.datetime64("1998-09-02")
    ref = q.reference(data, params)
    assert ref["rows"] == 4
    q._RUN["counted"] = True
    assert all(n.ok for n in q.compare(out, ref, cell.config))
    # against the reference of a DELTA a month away it fails the counts
    other = 120 if delta < 90 else 60
    q._RUN["delta_of"][id(out)] = other
    numbers = q.compare(out, ref, cell.config)
    assert {n.name for n in numbers if not n.ok} >= {"q1.count_order_wrong"}
    # and a result of unknown origin is held to nothing: it fails
    del q._RUN["delta_of"][id(out)]
    assert [n.ok for n in q.compare(out, ref, cell.config)] == [False]


def test_delta_window_is_held_to_thirty_distinct_values():
    q = harness.Cell(DELTA).query
    q._RUN.clear()
    q._RUN.update(drawn=[60 + (i % 29) for i in range(q.HELD_FROM)], counted=False,
                  delta_of={}, refs={})

    class _T:
        pass

    # the count is judged once a run, beside the first kept result
    t = _T()
    q._RUN["delta_of"][id(t)] = 60
    q._RUN["data"] = None
    import chipbench.queries.tpch_q1 as q1

    orig = q1.compare, q.reference_of
    q1.compare = lambda table, ref, config: []
    q.reference_of = lambda data, params, delta: {}
    try:
        (n,) = q.compare(t, {"params": {}}, {})
    finally:
        q1.compare, q.reference_of = orig
    assert (n.name, n.value, n.limit, n.ok) == ("q1_delta.distinct_short", 1, 0, False)


def test_delta_float32_values_fail_the_check():
    cell = harness.Cell(DELTA)
    out = control.readings(cell, jax.devices()[:1], [31], rows=ROWS)
    assert [ok for _, _side, ok in out["verdicts"]] == [True, False]
    assert min(out["control"]["q1.sums_relgap"]) > 5e-12


# -- the new readers --------------------------------------------------------
@pytest.fixture(scope="module")
def q4_obs():
    """``obs`` as a traced run hands it to the readers, made from the stage
    table of the programs the cell really dispatched on a CPU context: two
    milliseconds an instruction, two queries."""
    cell = harness.Cell(Q4)
    ctx = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:1])
    )
    result = harness.run_cell(
        cell, jax.devices()[:1], 5, 1e9, False, time.perf_counter(),
        rows=ROWS, ctx=ctx, max_queries=2,
    )
    assert result["correct"]
    table = stages.device_stage_table(ctx)
    ops = {}
    for _module, text, _op in table["rows"]:
        ops[short_name(text.removeprefix("ROOT "))] = 0.002
    data = cell.generator.make(cell.config, 5, ROWS)
    obs = {
        "queries": 2,
        "least_bytes": cell.query.least_bytes(data, cell.traffic["params"], 5),
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"window_s": 1.0, "devices": {
            "/device:TPU:0": {"busy_s": 0.002 * len(ops), "ops": list(ops.items())},
        }},
    }
    return cell, table, obs, data


def test_q4_readers_give_a_number(q4_obs, monkeypatch):
    cell, table, obs, data = q4_obs
    monkeypatch.setattr(stages, "device_stage_table", lambda ctx=None: table)
    programs = {module for module, _text, _op in table["rows"]}
    assert {"jit_join_semi_rows", "jit_groupby_dense", "jit_expr_eval"} <= programs
    ms = _read("semi_join_ms", obs)
    assert ms > 0
    from chipbench import stage_times

    assert ms == pytest.approx(
        stage_times.stage_ms(obs, "join.semi")
        + stage_times.stage_ms(obs, "join.semi_mask")
    )
    least = cell.query.semi_least_bytes(data)
    assert _read("semi_join_hbm_share", obs) == pytest.approx(
        100.0 * least / 819e9 / (ms / 1e3)
    )
    assert _read("semi_payload_rows", obs) >= 0


def test_payload_rows_read_zero_while_the_rule_fires(monkeypatch):
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "join.semi.payload_rows": {"count": 12, "rows": 0},
    })
    assert _read("semi_payload_rows", {}) == 0.0
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "join.semi.payload_rows": {"count": 4, "rows": 2000},
    })
    assert _read("semi_payload_rows", {}) == 500.0


@pytest.mark.parametrize("reader", ["semi_join_ms", "semi_join_hbm_share"])
@pytest.mark.parametrize("trace", [
    None, {"window_s": 0.0, "devices": {}},
], ids=["no-trace", "no-device-plane"])
def test_nothing_to_read_without_a_device_trace(reader, trace):
    assert _read(reader, {"queries": 3, "trace": trace,
                          "peaks": {"hbm_bytes_per_s": 819e9}}) is None


def test_a_program_without_the_scopes_and_counters_reads_nothing(monkeypatch):
    """The parent commit has no ``join.semi_mask`` and no counter: every new
    reader returns nothing and does not raise, also where its program ran
    the semi-REDUCTION (``join.semi`` alone is no operator)."""
    rows = [("jit_join_semi", "%sort.1 = s32[64]{0} sort(%p.1), dimensions={0}",
             "jit(join_semi)/join.semi/sort_engine/sort")]
    monkeypatch.setattr(
        stages, "device_stage_table",
        lambda ctx=None: {"rows": rows, "stale": [], "programs": 1, "seconds": 0.0},
    )
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


# -- the manifest, by name ---------------------------------------------------
def test_new_manifest_entries_resolve_by_name():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert cells[DELTA] == {
        "name": DELTA, "config": "tpch-sf10-w1",
        "traffic": "tpch-q1-delta-closed", "chips": 1,
        "why": cells[DELTA]["why"],
    }
    assert cells[Q4] == {
        "name": Q4, "config": "tpch-sf10-q4-w1", "traffic": "tpch-q4-closed",
        "chips": 1, "why": cells[Q4]["why"],
    }
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(DELTA) < names.index(Q4)  # the kept cell first
    assert all(len(w["why"]) <= 200 for w in manifest["workloads"])
    config = configs["tpch-sf10-q4-w1"]
    assert config["file"] == "chipbench/configs/tpch-sf10-q4-w1.json"
    assert config["reduced"] == ["columns", "tables"]
    assert "clause 2.4.4 Q4" in config["source"] and len(config["source"]) <= 200
    assert harness.load_json(harness.ROOT, config["file"])["source"] == config["source"]
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= len(manifest["workloads"]) // 2
    assert manifest["run_seconds"] == 51
    for name in NEW:
        assert entries[name]["workloads"] == [Q4]
        assert set(entries[name]) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert (entries["semi_join_ms"]["moves"], entries["semi_join_ms"]["source"]) == (
        "query_p50_ms", "device_trace")
    assert entries["semi_join_hbm_share"]["unit"] == "%"
    assert entries["semi_payload_rows"]["source"] == "program_counter"
    for name, traffic in ((DELTA, "tpch_q1_delta"), (Q4, "tpch_q4")):
        cell = harness.Cell(name)
        assert cell.traffic["query"] == traffic
        assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 1
        assert cell.traffic["trace_queries"] == 3
        assert {"build", "input_rows", "least_bytes", "reference",
                "compare"} <= set(dir(cell.query))
        assert [m["name"] for m in cell.metrics("end_to_end")] == [
            "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"]
        listed = {m["name"] for m in cell.metrics("per_layer")}
        assert {"host_syncs", "window_compiles", "hbm_roofline_share",
                "device_idle_share", "stage_unattributed_share"} <= listed
        assert (set(NEW) <= listed) is (name == Q4)
    assert harness.Cell(Q4).traffic["params"] == {"date": "1993-07-01", "months": 3}
    assert "semi_least_bytes" in dir(harness.Cell(Q4).query)
