"""REAL multi-process execution: 2 OS processes x 2 CPU devices each, global
mesh of 4, collectives over Gloo — the analog of the reference's
``mpirun -np N`` tests (cpp/test/CMakeLists.txt:44-49: N identical processes,
each owning its partition, every Distributed* op a collective all ranks
enter).

Each worker process:
- initializes via ``TPUConfig(coordinator_address=..., num_processes=2,
  process_id=pid)`` (the MPI_Init analog, context.py);
- builds tables via ``Table.from_encoded_shards`` providing ONLY its local
  shards (remote entries None + global counts) — per-rank ingestion, no
  global host buffer;
- runs distributed_join / distributed_sort / scalar aggregates and checks
  results against the pandas oracle (identical on every process).
"""
import os
import socket
import subprocess
import sys
import textwrap

import pytest

_WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    )
    os.environ["CYLON_TPU_PLATFORM"] = "cpu"
    import numpy as np
    import pandas as pd
    from collections import OrderedDict

    import cylon_tpu as ct
    from cylon_tpu.column import Column

    pid = int(sys.argv[1])
    port = sys.argv[2]
    ctx = ct.CylonContext.init_distributed(ct.TPUConfig(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid,
    ))
    import jax
    assert jax.process_count() == 2, jax.process_count()
    world = ctx.world_size
    assert world == 4, world
    assert ctx.rank == pid  # reference GetRank analog

    # deterministic global data, sharded 4 ways; each process ENCODES ONLY
    # the shards its devices own
    rng = np.random.default_rng(99)
    N = 400
    gk = rng.integers(0, 40, N).astype(np.int64)
    gv = rng.normal(size=N)
    g2 = rng.integers(0, 40, N).astype(np.int64)
    gw = rng.normal(size=N)
    counts = np.array([100, 100, 100, 100], np.int64)
    offs = np.concatenate([[0], np.cumsum(counts)])

    devices = list(ctx.mesh.devices.flat)

    def my_shards(cols):
        shards = []
        for i in range(world):
            if devices[i].process_index != jax.process_index():
                shards.append(None)
                continue
            lo, hi = int(offs[i]), int(offs[i + 1])
            shards.append(OrderedDict(
                (name, Column.encode_host(arr[lo:hi])) for name, arr in cols.items()
            ))
        return shards

    ta = ct.Table.from_encoded_shards(ctx, my_shards({"k": gk, "v": gv}), counts=counts)
    tb = ct.Table.from_encoded_shards(ctx, my_shards({"k": g2, "w": gw}), counts=counts)

    a = pd.DataFrame({"k": gk, "v": gv})
    b = pd.DataFrame({"k": g2, "w": gw})
    exp = a.merge(b, on="k")

    j = ta.distributed_join(tb, on="k", how="inner")
    assert j.row_count == len(exp), (j.row_count, len(exp))

    # fused + hash-sliced rounds across REAL process boundaries (the
    # lax.scan body's collectives run over Gloo here)
    jf = ta.distributed_join(tb, on="k", how="inner", mode="fused",
                             num_slices=2)
    assert jf.row_count == len(exp), (jf.row_count, len(exp))

    s = float(ta.sum("v"))
    assert np.isclose(s, gv.sum()), (s, gv.sum())

    srt = ta.distributed_sort("k")
    assert srt.row_count == N

    ctx.barrier()
    print(f"proc {pid} MULTIPROC-OK join={j.row_count}", flush=True)
    """
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_two_process_distributed_ops(tmp_path):
    port = _free_port()
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, str(i), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=540)
            outs.append(out)
    finally:
        # a deadlocked rank (e.g. peer crashed pre-barrier) must not leak
        # orphan processes pinning the coordinator port
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
        assert f"proc {i} MULTIPROC-OK" in out, out[-1500:]
