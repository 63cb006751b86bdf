"""Runnable proof of the Java FFM binding's ABI contract (VERDICT round-2
item 5): a C harness performs the byte-identical downcall sequence
java/org/cylondata/cylontpu/Table.java emits — including the round-3
callback surface (select / filter / mapColumn) whose C function-pointer ABIs
match CylonTpu.java's upcall stubs — and asserts the results against pandas
oracles here.

Reference analog: the JNI-backed Java client
(java/src/main/java/org/cylondata/cylon/Table.java + Table.cpp). Note the
reference's filter/mapColumn/hashPartition THROW unSupportedException
(Table.java:156-226); this ABI implements them for real.
"""
import os
import subprocess
import sys
import sysconfig

import numpy as np
import pandas as pd
import pytest

from cylon_tpu import native

_SRC = os.path.join(
    os.path.dirname(native.__file__), "examples", "java_abi_harness.c"
)


def _build(tmp_path) -> str:
    exe = str(tmp_path / "java_abi_harness")
    r = subprocess.run(
        ["gcc", "-O2", _SRC, "-o", exe, "-ldl"],
        capture_output=True, text=True, timeout=120,
    )
    if r.returncode != 0:
        pytest.skip(f"harness build failed: {r.stderr[-300:]}")
    return exe


def _subprocess_env():
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [repo] + [p for p in sys.path if p and p != repo]
    )
    env["CYLON_TPU_PLATFORM"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    )
    env.pop("JAX_PLATFORMS", None)
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    env["LD_LIBRARY_PATH"] = os.pathsep.join(
        filter(None, [libdir, env.get("LD_LIBRARY_PATH", "")])
    )
    return env


def test_java_abi_sequence(tmp_path):
    so = native.build_capi()
    if so is None:
        pytest.skip("capi build failed (no libpython?)")
    exe = _build(tmp_path)

    rng = np.random.default_rng(11)
    l = pd.DataFrame({"k": rng.integers(0, 30, 240), "x": rng.normal(size=240)})
    r = pd.DataFrame({"k": rng.integers(0, 30, 180), "y": rng.normal(size=180)})
    lp, rp = str(tmp_path / "l.csv"), str(tmp_path / "r.csv")
    out = str(tmp_path / "out.csv")
    l.to_csv(lp, index=False)
    r.to_csv(rp, index=False)

    res = subprocess.run(
        [exe, so, lp, rp, out],
        capture_output=True, text=True, timeout=30, env=_subprocess_env(),  # three times its measured run
    )
    assert res.returncode == 0, (
        f"stdout={res.stdout}\nstderr={res.stderr[-2000:]}"
    )
    got = dict(
        line.split("=", 1)
        for line in res.stdout.splitlines()
        if "=" in line and not line.startswith("cylon_tpu.Table")
    )

    exp_join = l.merge(r, on="k")
    assert int(got["join_rows"]) == len(exp_join)
    assert int(got["join_cols"]) == 4  # k_x, x, k_y, y
    assert int(got["select_rows"]) == int((l["k"] % 2 == 0).sum())
    assert int(got["filter_rows"]) == int(got["select_rows"])
    assert int(got["map_rows"]) == len(l)
    assert int(got["partition_total"]) == len(l)
    assert int(got["merge_rows"]) == len(l)
    assert got["ok"] == "1"

    # the written join matches pandas
    written = pd.read_csv(out)
    assert len(written) == len(exp_join)
    assert np.isclose(written["x"].sum(), exp_join["x"].sum())
