"""The per-stage readers on a hand-made ``obs`` and a hand-made stage
table: the outermost-stage rule, ``sort_engine`` counted wherever it
nests, an ambiguous short name and a stale module both unattributed,
nothing to read without a trace, and the manifest's new names resolve to
files."""
import importlib
import json
import os

import pytest

from chipbench import harness, stage_times
from cylon_tpu.obs import stages

NEW = (
    "sort_engine_ms", "join_emit_ms", "shuffle_pack_ms",
    "shuffle_compact_ms", "stage_unattributed_share",
)


def _ins(name, shape, opcode, kind=None):
    """An instruction as ``compiled.as_text()`` prints it, less metadata."""
    tail = f", kind={kind}, calls=%fused_computation.{name[-1]}" if kind else ""
    return f"%{name} = {shape}{{0:T(1024)}} {opcode}(%p.1, %p.2){tail}"


def _short(name, shape, opcode, kind=None):
    return f"{name} {shape} {opcode}" + (f":{kind}" if kind else "")


#: (module, instruction, op_name): two programs share ``fusion.7`` under
#: different stages, and everything of ``jit_old`` is stale
ROWS = [
    ("jit_join_spec", "ROOT " + _ins("fusion.1", "s32[64]", "fusion", "kCustom"),
     "jit(join_spec)/join.probe/sort_engine/jit(radix_pass)/scatter"),
    ("jit_join_spec", _ins("fusion.2", "u32[64]", "fusion", "kCustom"),
     "jit(join_spec)/join.key_ids/sort.perm/sort_engine/jit(radix_pass)/gather"),
    ("jit_join_spec", _ins("fusion.3", "f32[64]", "fusion", "kCustom"),
     "jit(join_spec)/join.emit/gather"),
    ("jit_join_spec", _ins("sort.4", "s32[64]", "sort"),
     "jit(join_spec)/join.probe/sort_engine/sort"),
    ("jit_join_spec", _ins("copy.5", "s32[64]", "copy"), ""),
    ("jit_shuffle_pack", _ins("fusion.6", "s32[64]", "fusion", "kLoop"),
     "jit(shuffle_pack)/shard_map/shuffle.pack/sort_engine/jit(radix_pass)/gather"),
    ("jit_shuffle_pack", _ins("fusion.7", "s32[64]", "fusion", "kLoop"),
     "jit(shuffle_pack)/shard_map/shuffle.pack/scatter"),
    ("jit_shuffle_compact", _ins("fusion.7", "s32[64]", "fusion", "kLoop"),
     "jit(shuffle_compact)/shard_map/shuffle.compact/gather"),
    ("jit_shuffle_compact", _ins("fusion.8", "s32[64]", "fusion", "kLoop"),
     "jit(shuffle_compact)/shard_map/shuffle.compact/gather"),
    ("jit_shuffle_pack", _ins("fusion.8", "s32[64]", "fusion", "kLoop") + " ",
     "jit(shuffle_pack)/shard_map/shuffle.compact/gather"),
    ("jit_old", _ins("fusion.9", "s32[64]", "fusion", "kLoop"),
     "jit(old)/join.emit/gather"),
]
#: seconds over two queries, by the trace's short names
OPS = [
    (_short("fusion.1", "s32[64]", "fusion", "kCustom"), 0.40),
    (_short("fusion.2", "u32[64]", "fusion", "kCustom"), 0.20),
    (_short("fusion.3", "f32[64]", "fusion", "kCustom"), 0.10),
    (_short("sort.4", "s32[64]", "sort"), 0.06),
    (_short("copy.5", "s32[64]", "copy"), 0.02),
    (_short("fusion.6", "s32[64]", "fusion", "kLoop"), 0.04),
    (_short("fusion.7", "s32[64]", "fusion", "kLoop"), 0.08),
    (_short("fusion.8", "s32[64]", "fusion", "kLoop"), 0.03),
    (_short("fusion.9", "s32[64]", "fusion", "kLoop"), 0.05),
    ("broadcast.1 s8[64] broadcast", 0.02),
]


def _obs(ops=OPS, queries=2):
    return {
        "queries": queries,
        "trace": {
            "window_s": 1.2,
            "devices": {
                "/device:TPU:0": {"busy_s": sum(t for _, t in ops), "ops": ops},
                "/device:TPU:1": {"busy_s": 9.0, "ops": [("x", 9.0)]},
            },
        },
    }


@pytest.fixture
def table(monkeypatch):
    calls = []

    def fake(ctx=None):
        calls.append(1)
        return {"rows": ROWS, "stale": ["jit_old"], "programs": 4, "seconds": 0.5}

    monkeypatch.setattr(stages, "device_stage_table", fake)
    return calls


def _read(name, obs):
    return importlib.import_module("chipbench.layer_metrics." + name).read(obs)


def test_outermost_stage_and_sort_engine_wherever_it_nests(table, capsys):
    obs = _obs()
    found = stage_times.split(obs)
    ms = found["stages_ms"]
    assert ms["join.probe"] == pytest.approx(230.0)     # fusion.1 + sort.4
    assert ms["join.key_ids"] == pytest.approx(100.0)   # not sort.perm
    assert ms["join.emit"] == pytest.approx(50.0)
    assert ms["shuffle.pack"] == pytest.approx(20.0)    # fusion.6 alone
    assert ms["shuffle.compact"] == pytest.approx(15.0)  # fusion.8: one answer
    assert "sort.perm" not in ms and "sort_engine" not in ms
    # sort_engine under three stages: 0.40 + 0.06 + 0.20 + 0.04 over 2 queries
    assert found["sort_engine_ms"] == pytest.approx(350.0)
    assert found["sort_engine_in_ms"] == pytest.approx(
        {"join.probe": 230.0, "join.key_ids": 100.0, "shuffle.pack": 20.0}
    )
    assert sum(ms.values()) == pytest.approx(found["busy_ms"]) == pytest.approx(500.0)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["info"] == "stage_split" and line["table"]["stale"] == ["jit_old"]


def test_ambiguous_stale_and_unknown_are_unattributed(table):
    found = stage_times.split(_obs())
    # copy.5 (no stage) + fusion.7 (two stages) + fusion.9 (stale) + broadcast.1
    # (not in the table) = 0.02 + 0.08 + 0.05 + 0.02 of 1.00 s
    assert found["stages_ms"][stage_times.UNATTRIBUTED] == pytest.approx(85.0)
    assert found["unattributed_share"] == pytest.approx(17.0)


@pytest.mark.parametrize("name,want", [
    ("sort_engine_ms", 350.0), ("join_emit_ms", 50.0),
    ("shuffle_pack_ms", 20.0), ("shuffle_compact_ms", 15.0),
    ("stage_unattributed_share", 17.0),
])
def test_readers_share_one_table(table, name, want):
    obs = _obs()
    assert _read(name, obs) == pytest.approx(want)
    for other in NEW:
        _read(other, obs)
    assert len(table) == 1  # built once a run, whatever the readers


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("trace", [
    None, {"window_s": 0.0, "devices": {}},
], ids=["no-trace", "no-device-plane"])
def test_nothing_to_read_without_a_device_trace(table, name, trace):
    assert _read(name, {"queries": 3, "trace": trace}) is None
    assert table == []  # the program was asked for nothing


def test_a_stage_that_did_not_run_reads_nothing(table):
    ops = [op for op in OPS if "fusion.3" not in op[0] and "fusion.8" not in op[0]]
    obs = _obs(ops)
    assert _read("join_emit_ms", obs) is None
    assert _read("shuffle_compact_ms", obs) is None
    assert _read("sort_engine_ms", obs) > 0


def test_a_program_without_stage_names_reads_nothing(monkeypatch):
    # the parent commit has no cylon_tpu.obs.stages: the import fails and
    # every new reader returns nothing instead of raising
    import cylon_tpu.obs as obs_pkg
    import sys

    monkeypatch.delattr(obs_pkg, "stages")
    monkeypatch.setitem(sys.modules, "cylon_tpu.obs.stages", None)
    for name in NEW:
        assert _read(name, _obs()) is None


def test_table_and_trace_are_keyed_by_one_function():
    index = stage_times.stage_index(
        {"rows": ROWS[:1], "stale": []}, stages
    )
    assert index == {"fusion.1 s32[64] fusion:kCustom": ("join.probe", True)}


def test_new_manifest_names_resolve_to_files():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = {w["name"] for w in manifest["workloads"]}
    layers = {m["layer"] for m in manifest["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        assert os.path.exists(
            os.path.join(harness.HERE, "layer_metrics", name + ".py")
        )
        entry = entries[name]
        assert entry["source"] == "device_trace" and entry["better"] == "lower"
        assert entry["layer"] in layers
        assert set(entry.get("workloads", cells)) <= cells
    assert [m["name"] for m in manifest["per_layer"]][-5:] == list(NEW)
