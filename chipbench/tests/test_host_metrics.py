"""The three readers of PR 35 (``host_exposed_ms``, ``host_dispatch_ms``,
``host_plain_ms``) on hand-made records: the mean over the window's last N,
the tail counted, ``None`` where the program has no such records (the parent
commit's program) or fewer than the window's queries; then on the program's
own records of a rehearsed cell; and the three manifest entries, looked up
by name. A pass here is a rehearsal, never a number."""
import importlib
import json
import os
import time

import jax
import pytest

from chipbench import harness
from chipbench.layer_metrics import host_exposed_ms

NAMES = ("host_exposed_ms", "host_dispatch_ms", "host_plain_ms")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _reader(name):
    return importlib.import_module("chipbench.layer_metrics." + name)


def _record(start, end, tail, dispatch, wait, exposed):
    """A record as ``OpRecord.as_dict()`` gives it (the fields read)."""
    return {
        "start_ns": start, "end_ns": end, "tail_ns": tail,
        "dispatch_ns": dispatch, "wait_ns": wait, "exposed_ns": exposed,
        "plain_ns": (end - start) + tail - wait - dispatch,
    }


@pytest.fixture
def records(monkeypatch):
    """Stand in for the program's ring: ``last_ops(n)`` of a hand-made list."""
    import cylon_tpu.obs

    held = []
    monkeypatch.setattr(
        cylon_tpu.obs, "last_ops",
        lambda n=None: held[-n:] if n else list(held), raising=False,
    )
    return held


def test_mean_over_the_windows_last_records(records):
    # a warm-up call (not the window's), then three queries; the second has
    # a tail: a 2 ms deferred count fetch after the call returned
    records.extend([
        _record(0, 900_000_000, 0, 800_000_000, 50_000_000, 40_000_000),
        _record(0, 10_000_000, 0, 1_000_000, 6_000_000, 2_000_000),
        _record(0, 10_000_000, 2_000_000, 1_500_000, 8_000_000, 3_000_000),
        _record(0, 13_000_000, 0, 500_000, 7_000_000, 4_000_000),
    ])
    obs = {"queries": 3}
    assert _reader("host_exposed_ms").read(obs) == pytest.approx(3.0)
    assert _reader("host_dispatch_ms").read(obs) == pytest.approx(1.0)
    # (10 - 6 - 1) + (10 + 2 - 8 - 1.5) + (13 - 7 - 0.5), over three
    assert _reader("host_plain_ms").read(obs) == pytest.approx(11.0 / 3)
    # the warm-up's compile is outside the last three
    assert _reader("host_dispatch_ms").read({"queries": 4}) == pytest.approx(
        (800 + 1 + 1.5 + 0.5) / 4
    )


@pytest.mark.parametrize("name", NAMES)
def test_nothing_without_the_records(name, records, monkeypatch):
    read = _reader(name).read
    assert read({"queries": 3}) is None  # fewer records than queries
    assert read({"queries": 0}) is None and read({}) is None
    import cylon_tpu.obs

    # the parent commit's program: no such function
    monkeypatch.delattr(cylon_tpu.obs, "last_ops")
    assert read({"queries": 1}) is None


def test_readers_on_a_rehearsed_cell():
    """Each of three queries of ``groupby-w1`` leaves one record; the
    deferred count fetch of the harness's ``ready`` is in its tail (and,
    here, where the readers run after the comparison and not before it as
    in a traced run, so are the last result's column fetches)."""
    cell = harness.Cell("groupby-w1")
    result = harness.run_cell(
        cell, jax.devices()[:1], 2**31 + 35, 60.0, False, time.perf_counter(),
        rows=4096, max_queries=3,
    )
    assert result["correct"] and result["attempted"] == 3
    obs = {"queries": 3}
    window = host_exposed_ms.window_records(obs)
    assert [r["name"] for r in window] == ["distributed_groupby"] * 3
    assert all(
        r["sites"]["table.counts"]["n_fetch"] == 1 and r["tail_ns"] > 0
        for r in window
    )
    assert [r["n_fetch"] for r in window[:2]] == [1, 1]
    exposed, dispatch, plain = (_reader(n).read(obs) for n in NAMES)
    assert exposed > 0 and dispatch > 0 and plain > 0
    wall = sum(r["end_ns"] - r["start_ns"] + r["tail_ns"] for r in window)
    waits = sum(r["wait_ns"] for r in window)
    assert dispatch + plain == pytest.approx((wall - waits) / 3 / 1e6)


def test_new_manifest_entries_resolve_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    end_to_end = {m["name"] for m in manifest["end_to_end"]}
    for name in NAMES:
        entry = entries[name]
        assert entry == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_counter", "layer": "table dispatch",
            "moves": "query_p50_ms", "workloads": entry["workloads"],
        }
        assert entry["moves"] in end_to_end
        assert set(entry["workloads"]) <= set(cells)
        assert len(entry["workloads"]) == 8
        assert callable(_reader(name).read)
        # every listed cell reports it
        for cell in entry["workloads"]:
            metrics = harness.Cell(cell, manifest).metrics("per_layer")
            listed = {m["name"] for m in metrics}
            assert name in listed
    assert entries["host_syncs"]["layer"] == "table dispatch"
