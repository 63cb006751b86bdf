"""CPU rehearsal of PR 49's one reader, ``join_emit_handthrough_share``:
its cases (both counters, one, none: the parent commit's program gives
``None`` and no raise), its ``BENCHMARK.json`` entry, looked up BY NAME,
and what it reads through the harness at 4,096 rows on the CPU mesh in the
two cells that list it and in ``h2o-join-q3-w4`` (not listed: see
``LISTED``): the LEFT OUTER join against a build side unique on its key and
the foreign-key join hand every row through (100), the join of uniform
keys gathers every row (0). A pass here is a rehearsal, never a number."""
import importlib
import os
import time

import jax
import pytest

from chipbench import harness
from cylon_tpu.utils import tracing

NAME = "join_emit_handthrough_share"
ROWS = 4096
#: the cells whose emits the reader can tell apart, and what it reads there
CELLS = {"h2o-join-q3-w4": 100.0, "join-skew-w4": 100.0, "join-w1": 0.0}
#: the cells that list it in ``BENCHMARK.json``. ``h2o-join-q3-w4`` is left
#: out: ``test_h2o_join_cells.py`` pins that cell's per-layer set, and a file
#: the benchmark already has is not this PR's to edit (PERF.md section 7)
LISTED = ["join-skew-w4", "join-w1"]


def _read(obs=None):
    reader = importlib.import_module("chipbench.layer_metrics." + NAME)
    return reader.read(obs if obs is not None else {})


@pytest.mark.parametrize("rollup,want", [
    ({"join.emit.handthrough": {"count": 5, "rows": 5 * 10**8},
      "join.emit.gathered": {"count": 5, "rows": 0}}, 100.0),
    ({"join.emit.handthrough": {"count": 4, "rows": 3000},
      "join.emit.gathered": {"count": 4, "rows": 1000}}, 75.0),
    ({"join.emit.gathered": {"count": 2, "rows": 8_000_000}}, 0.0),
    ({"join.emit.handthrough": {"count": 2, "rows": 16_000_000}}, 100.0),
    # the counters are there and no emit wrote a row: nothing to read
    ({"join.emit.handthrough": {"count": 1, "rows": 0},
      "join.emit.gathered": {"count": 1, "rows": 0}}, None),
    # the parent's program: neither counter
    ({"host_sync": {"count": 3, "rows": 0},
      "join.emit_rows": {"count": 3, "rows": 4800}}, None),
    ({}, None),
])
def test_reader_reads_the_two_counters(monkeypatch, rollup, want):
    monkeypatch.setattr(tracing, "snapshot", lambda: rollup)
    for obs in ({}, {"queries": 3, "trace": None},
                {"queries": 3, "trace": {"window_s": 0.0, "devices": {}}}):
        got = _read(obs)
        assert got == want if want is None else got == pytest.approx(want)


def test_manifest_entry_resolves_and_is_looked_up_by_name():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert entries[NAME] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "query_p50_ms", "workloads": LISTED,
    }
    assert os.path.exists(
        os.path.join(harness.HERE, "layer_metrics", NAME + ".py")
    )
    layers = {m["layer"] for m in manifest["per_layer"] if m["name"] != NAME}
    assert entries[NAME]["layer"] in layers  # a layer the manifest names
    cells = {w["name"] for w in manifest["workloads"]}
    for cell in LISTED:
        assert cell in cells
        listed = {m["name"] for m in harness.Cell(cell).metrics("per_layer")}
        ends = {m["name"] for m in harness.Cell(cell).metrics("end_to_end")}
        assert NAME in listed and entries[NAME]["moves"] in ends
    for cell in cells - set(LISTED):
        listed = {m["name"] for m in harness.Cell(cell).metrics("per_layer")}
        assert NAME not in listed, cell


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_reads_its_share_through_the_harness(monkeypatch, name):
    tracing.reset_trace()  # the reader reads the process's rollup
    cell = harness.Cell(name)
    # where a traced run reads its metrics: after the window and before the
    # comparison, whose own joins (the H2O cell's questions 1 and 2, inner
    # joins that leave rows of x without a partner) are counted after it
    compare, read = harness.compare, []

    def reading_first(*args):
        read.append(_read())
        return compare(*args)

    monkeypatch.setattr(harness, "compare", reading_first)
    result = harness.run_cell(
        cell, jax.devices()[: cell.chips], 2**31 + 49, 1e9, False,
        time.perf_counter(), rows=ROWS, max_queries=2,
    )
    assert result["correct"], result["numbers"]
    assert read == [pytest.approx(CELLS[name])]
