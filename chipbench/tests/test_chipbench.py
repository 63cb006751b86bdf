"""CPU rehearsal of chipbench: each cell's query passes its own check at
4,096 rows, the control (float32 values) and a broken timed path fail it,
the trace reduction adds up on hand-made events, the manifest resolves to
files, and the harness exits non-zero without a TPU before any set-up."""
import json
import os
import re
import time

import jax
import numpy as np
import pytest

from chipbench import control, harness, trace_reduce
from chipbench.checks import Number, lower_precision, rel_gap

ROWS = 4096
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(autouse=True)
def _v5e_peaks(monkeypatch):
    # the CPU is in no table of peaks (and must not be): the rehearsal
    # borrows the v5e row so that the layer readers run
    table = harness.load_json(harness.HERE, "peaks.json")
    monkeypatch.setattr(harness, "peaks_for", lambda kind: table["TPU v5 lite"])


def _run(name, trace=False, **kw):
    cell = harness.Cell(name)
    return harness.run_cell(
        cell, jax.devices()[: cell.chips], 2**31 + 5, 0.05, trace,
        time.perf_counter(), rows=ROWS, **kw
    )


@pytest.mark.parametrize("name", ["join-w1", "sort-w1", "join-w4"])
def test_cell_passes_its_own_check(name):
    result = _run(name)
    assert result["correct"], result["numbers"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"
    }
    compared = {n[0] for n in result["numbers"]}
    assert "window.row_counts_wrong" in compared
    if name == "join-w4":
        assert "join.shards_wrong" in compared


@pytest.mark.parametrize("name", ["join-w1", "sort-w1", "join-w4"])
def test_float32_values_fail_the_check(name):
    cell = harness.Cell(name)
    out = control.readings(cell, jax.devices()[: cell.chips], [11, 12, 13],
                           rows=ROWS)
    assert [ok for _, side, ok in out["verdicts"] if side == "sound"] == [True] * 3
    assert [ok for _, side, ok in out["verdicts"] if side == "control"] == [False] * 3
    # the lower precision fails the value numbers and no exact one
    for number, values in out["control"].items():
        if number.endswith("relgap"):
            limit = cell.query.VALUE_LIMIT["float64"]
            assert min(values) > 3 * limit
            assert max(out["sound"][number]) < limit / 3
        else:
            assert max(values) == 0


@pytest.mark.parametrize("fault", ["lost_row", "re_paired", "state_unchanged"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    """The rest of a run with the timed path broken underneath: the query's
    call hands back an altered answer, and ``correct`` comes out false."""
    import cylon_tpu as ct

    cell = harness.Cell("join-w1")
    build = cell.query.build

    def broken_build(tables, params):
        call = build(tables, params)

        def broken():
            out = call()
            cols = out.to_pydict()
            if fault == "lost_row":
                cols = {c: a[:-1] for c, a in cols.items()}
            elif fault == "re_paired":
                cols["w"] = np.roll(cols["w"], 1)
            else:  # the input handed back as the answer
                return tables[params["left"]]
            return ct.Table.from_numpy(out.ctx, list(cols), list(cols.values()))

        return broken

    monkeypatch.setattr(cell.query, "build", broken_build)
    result = harness.run_cell(
        cell, jax.devices()[:1], 3, 0.01, False, time.perf_counter(), rows=ROWS
    )
    assert result["correct"] is False
    assert any(v > limit for _, v, limit in result["numbers"])


def test_traced_run_reports_layer_metrics(monkeypatch, tmp_path):
    monkeypatch.setenv("CHIPBENCH_DUMP", str(tmp_path))  # the look by hand
    result = _run("join-w4", trace=True)
    (dump,) = tmp_path.iterdir()
    assert set(json.loads(dump.read_text())) == {"lines", "samples", "reduced"}
    assert result["correct"]
    assert result["attempted"] == harness.Cell("join-w4").traffic["trace_queries"]
    assert result["metrics"]["window_compiles"]["value"] == 0
    assert result["metrics"]["host_syncs"]["value"] > 0
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not os.path.exists(harness.TRACE_DIR)


def test_trace_reduce_on_hand_made_events():
    ms = 1e6  # nanoseconds
    device = [
        ("while.1", 0 * ms, 40 * ms),          # holds the next two
        ("fusion.2", 0 * ms, 10 * ms),
        ("all-to-all.3", 10 * ms, 30 * ms),
        ("sort.4", 35 * ms, 60 * ms),          # overlaps the while's tail
        ("fusion.2", 80 * ms, 90 * ms),        # after a 20 ms gap
        ("fusion.9", 150 * ms, 170 * ms),      # outside the window
    ]
    host = [
        (trace_reduce.WINDOW_EVENT, 0 * ms, 100 * ms),
        ("$table.py:1607 _materialize_counts", 58 * ms, 82 * ms),
        ("chipbench.query", 0 * ms, 95 * ms),
    ]
    out = trace_reduce.reduce_events({"/device:TPU:0": device}, host)
    dev = out["devices"]["/device:TPU:0"]
    assert out["window_s"] == pytest.approx(0.100)
    assert dev["busy_s"] == pytest.approx(0.070)          # [0,60] + [80,90]
    ops = dict(dev["ops"])
    assert ops["while.1"] == pytest.approx(0.005)         # 40 - 10 - 20 - 5
    assert ops["fusion.2"] == pytest.approx(0.020)
    assert ops["sort.4"] == pytest.approx(0.025)
    assert "fusion.9" not in ops
    assert dev["collective_s"] == pytest.approx(0.020)
    gaps = dict(dev["idle_gaps"])
    assert gaps["$table.py:1607 _materialize_counts"] == pytest.approx(0.020)
    assert gaps["chipbench.query"] == pytest.approx(0.010)  # the tail, 90-100
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    top = trace_reduce.breakdown(out)
    assert top["device_ops"][0] == ["sort.4", pytest.approx(0.025)]
    # the layer readers on the same reduction
    obs = {
        "queries": 2, "trace": out, "least_bytes": 819e9 * 0.0035,
        "peaks": {"hbm_bytes_per_s": 819e9}, "counters": {"host_sync": 10},
        "window_compile_events": [],
    }
    from chipbench.layer_metrics import (
        collective_ms, device_idle_share, hbm_roofline_share, host_syncs,
        window_compiles,
    )
    assert device_idle_share.read(obs) == pytest.approx(30.0)
    assert collective_ms.read(obs) == pytest.approx(10.0)
    assert hbm_roofline_share.read(obs) == pytest.approx(10.0)
    assert host_syncs.read(obs) == 5 and window_compiles.read(obs) == 0
    obs["trace"] = None  # nothing to read: the metric is left out
    assert device_idle_share.read(obs) is None and collective_ms.read(obs) is None


def test_manifest_names_resolve_to_files():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert manifest["paths"] == ["chipbench"]
    configs = {c["name"]: c for c in manifest["configs"]}
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files)
    for c in configs.values():
        assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
        body = harness.load_json(harness.ROOT, c["file"])
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] == ["rows"]
        assert os.path.exists(os.path.join(
            harness.HERE, "generators", body["generator"] + ".py"))
    cells = set()
    for w in manifest["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = harness.Cell(w["name"])
        assert cell.config["chips"] == w["chips"]
        assert {"build", "input_rows", "least_bytes", "reference",
                "compare"} <= set(dir(cell.query))
        assert [m["name"] for m in cell.metrics("end_to_end")] == [
            "rows_per_s", "query_p50_ms", "query_p95_ms", "setup_s"]
        assert cell.metrics("per_layer")
        cells.add(w["name"])
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(
        1, len(cells) // 2)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and len(m["layer"]) <= 200
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", m["name"] + ".py"))
    assert len(json.dumps(manifest)) < 64 * 1024


def test_exits_nonzero_on_the_cpu_before_any_set_up(monkeypatch, capsys):
    def ran(*_a, **_k):
        raise AssertionError("set-up ran without a TPU")

    monkeypatch.setattr(harness, "run_cell", ran)
    assert jax.devices()[0].platform == "cpu"
    for name in ("join-w1", "join-w4"):
        rc = harness.main(
            ["--workload", name, "--seed", "1", "--seconds", "1"], 0.0)
        assert rc != 0
    assert capsys.readouterr().out == ""  # no result line
    assert control.main(["--workload", "sort-w1", "--seeds", "1"]) != 0


def test_checks_arithmetic():
    assert rel_gap(np.array([1.0, 2.0]), np.array([1.0, 2.0])) == 0
    assert rel_gap(np.array([1.0, 2.2]), np.array([1.0, 2.0])) == pytest.approx(0.1)
    assert rel_gap(np.array([np.nan]), np.array([1.0])) == float("inf")
    assert rel_gap(np.array([1.0]), np.array([1.0, 2.0])) == float("inf")
    assert Number("x", 0, 0).ok and not Number("x", float("nan"), 1).ok
    config = harness.Cell("sort-w1").config
    data = {"left": {"k": np.arange(4), "v": np.array([0.1, 0.2, 0.3, 0.7])}}
    low = lower_precision(data, config)
    assert low["left"]["v"].dtype == np.float64
    assert np.array_equal(low["left"]["k"], data["left"]["k"])
    assert 1e-9 < rel_gap(low["left"]["v"], data["left"]["v"]) < 1e-7
    assert harness.percentile_nearest_rank(list(range(1, 11)), 0.95) == 10
    assert harness.percentile_nearest_rank(list(range(1, 101)), 0.95) == 95
    assert harness.percentile_nearest_rank([3, 1, 2], 0.5) == 2
