"""Foreign-language consumer of the C ABI: a standalone C program drives
read_csv -> distributed_join -> distributed_sort -> project -> write_csv in
its OWN process through dlopen + the embedded interpreter.

Reference analog: the JVM client Table.java
(java/src/main/java/org/cylondata/cylon/Table.java:63-238) driving the C++
core over JNI. The in-process ctypes round-trip lives in
test_native_runtime.py; this test exercises the Py_InitializeEx path a real
FFI consumer hits.
"""
import os
import subprocess
import sys
import sysconfig

import numpy as np
import pandas as pd
import pytest

from cylon_tpu import native

_CLIENT_SRC = os.path.join(
    os.path.dirname(native.__file__), "examples", "capi_client.c"
)


def _build_client(tmp_path) -> str:
    exe = str(tmp_path / "capi_client")
    r = subprocess.run(
        ["gcc", "-O2", _CLIENT_SRC, "-o", exe, "-ldl"],
        capture_output=True,
        text=True,
        timeout=120,
    )
    if r.returncode != 0:
        pytest.skip(f"client build failed: {r.stderr[-300:]}")
    return exe


def test_c_client_end_to_end(tmp_path):
    so = native.build_capi()
    if so is None:
        pytest.skip("capi build failed (no libpython?)")
    exe = _build_client(tmp_path)

    rng = np.random.default_rng(5)
    l = pd.DataFrame(
        {"k": rng.integers(0, 20, 200), "x": rng.normal(size=200)}
    )
    r = pd.DataFrame(
        {"k": rng.integers(0, 20, 150), "y": rng.normal(size=150)}
    )
    lp, rp = str(tmp_path / "l.csv"), str(tmp_path / "r.csv")
    out = str(tmp_path / "out.csv")
    l.to_csv(lp, index=False)
    r.to_csv(rp, index=False)

    env = dict(os.environ)
    # the embedded interpreter must see the repo package and run on the
    # virtual CPU mesh (CYLON_TPU_PLATFORM pins it through jax.config
    # before the first backend touch)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [repo] + [p for p in sys.path if p and p != repo]
    )
    env["CYLON_TPU_PLATFORM"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    )
    env.pop("JAX_PLATFORMS", None)
    # dynamic linker must find libpython for the capi .so
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    env["LD_LIBRARY_PATH"] = os.pathsep.join(
        filter(None, [libdir, env.get("LD_LIBRARY_PATH", "")])
    )

    res = subprocess.run(
        [exe, so, lp, rp, out],
        capture_output=True,
        text=True,
        timeout=30,  # three times its measured run
        env=env,
    )
    assert res.returncode == 0, f"stdout={res.stdout}\nstderr={res.stderr[-2000:]}"
    exp = l.merge(r, on="k")
    assert f"rows={len(exp)}" in res.stdout, res.stdout
    assert "cols=3" in res.stdout, res.stdout

    got = pd.read_csv(out)
    assert list(got.columns) == ["k_x", "x", "y"]
    assert len(got) == len(exp)
    assert (np.diff(got["k_x"].to_numpy()) >= 0).all()  # distributed_sort order
    assert np.isclose(got["x"].sum(), exp["x"].sum())
