"""benchmarks/roofline.py analyzer sanity: primitive counting and traffic
math on known-shape programs (the model feeds the bench's %membw column,
so its bookkeeping needs a regression net)."""
import os
import sys

import jax
import jax.numpy as jnp
from cylon_tpu.compat import shard_map
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.roofline import (
    GATHER_PASS_EQ,
    _bitonic_passes,
    analyze,
    model_seconds,
)


def test_counts_one_sort_with_pass_weighting():
    n = 1 << 12

    def f(x, p):
        return jax.lax.sort((x, p), num_keys=1, is_stable=True)

    rep = analyze(
        f,
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
    )
    assert rep.sort_count == 1
    assert rep.sort_bytes_per_pass == 2 * n * 4
    assert rep.sort_pass_bytes == 2 * n * 4 * _bitonic_passes(n)


def test_counts_gather_pass_equivalents():
    n = 1 << 10

    def f(x, idx):
        return x[idx]

    rep = analyze(
        f,
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
    )
    assert rep.sort_count == 0
    assert rep.gather_bytes > 0
    # weighted: in+out bytes x pass-equivalents
    assert rep.gather_bytes == pytest.approx(3 * n * 4 * GATHER_PASS_EQ)


def test_recurses_into_jit_and_shard_map(devices):
    from jax.sharding import Mesh, PartitionSpec

    mesh = Mesh(np.array(devices[:2]), ("dp",))
    n = 256

    def kern(x):
        s, = jax.lax.sort((x,), num_keys=1)
        return s

    f = jax.jit(
        shard_map(
            kern, mesh=mesh,
            in_specs=PartitionSpec("dp"), out_specs=PartitionSpec("dp"),
        )
    )
    rep = analyze(f, jax.ShapeDtypeStruct((2 * n,), jnp.int32))
    assert rep.sort_count == 1  # found through jit -> shard_map nesting


def test_engine_kernel_recording(ctx8, rng):
    """engine.record_kernels captures every get_kernel dispatch (fn, args)
    so eager op chains can be roofline-modeled; disabled leaves dispatch
    untouched."""
    import cylon_tpu as ct
    from cylon_tpu import engine

    t = ct.Table.from_pydict(
        ctx8, {"k": rng.integers(0, 9, 64).astype(np.int32)}
    )
    engine.record_kernels(True)
    try:
        t.unique()
    finally:
        ks = engine.recorded_kernels()
        engine.record_kernels(False)
    assert len(ks) >= 1
    fn, args = ks[0]
    from benchmarks.roofline import analyze

    rep = analyze(fn, *args)
    assert rep.sort_count >= 1  # unique is sort-based

    engine.record_kernels(False)
    assert engine.recorded_kernels() == []


def test_model_seconds_scales_with_bandwidth():
    def f(x, p):
        return jax.lax.sort((x, p), num_keys=1)

    rep = analyze(
        f,
        jax.ShapeDtypeStruct((1 << 16,), jnp.int32),
        jax.ShapeDtypeStruct((1 << 16,), jnp.int32),
    )
    assert model_seconds(rep, 100.0) == pytest.approx(
        2 * model_seconds(rep, 200.0)
    )


def test_pallas_call_priced_streamed_not_recursed():
    """The model must price a pallas_call as one read + one write of its
    operands and must NOT walk the kernel body (whose in-VMEM jnp.take
    would otherwise be priced at the HBM per-element gather rate,
    overstating kernel traffic ~400x)."""
    from cylon_tpu.ops.pallas_gather import expand_available, expand_rows

    if not expand_available():
        pytest.skip("pallas unavailable")
    import jax.numpy as jnp

    m = 4000
    src = jnp.asarray(np.arange(4 * m, dtype=np.int32).reshape(4, m))
    li = jnp.asarray(np.repeat(np.arange(m), 2).astype(np.int32))
    rep = analyze(
        lambda s, l: expand_rows(s, l, impl="take", interpret=False), src, li
    )
    assert rep.gather_bytes == 0, rep.by_prim
    assert "pallas_call" in rep.by_prim
    # streamed pricing: same order as operand+output bytes, nowhere near
    # the ~400x per-element-gather figure
    raw = (4 * m + len(li) + 4 * len(li)) * 4
    assert rep.by_prim["pallas_call"] < 3 * raw


def test_container_prims_not_double_counted():
    """pjit/shard_map containers recurse but must not add their own in/out
    bytes on top of their bodies'."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x * 2.0

    x = jnp.zeros((1024,), jnp.float32)
    rep = analyze(f, x)
    # one multiply: ~in+out = 8KB; a double-counted pjit boundary would
    # add another ~8KB on top
    assert rep.elementwise_bytes <= 3 * 8192, rep.elementwise_bytes


def test_scan_body_scaled_by_trip_count():
    """A scan body executes `length` times — its sorts/collectives must be
    scaled, not counted once (the K-sliced fused join runs K rounds in ONE
    scan; an unscaled walk under-reported its collective volume by K)."""
    import jax
    import jax.numpy as jnp

    K = 7

    @jax.jit
    def f(x):
        def body(carry, _):
            s = jax.lax.sort(carry)
            return s, jnp.sum(s)

        out, sums = jax.lax.scan(body, x, None, length=K)
        return out, sums

    x = jnp.zeros((2048,), jnp.int32)
    rep = analyze(f, x)
    assert rep.sort_count == K, rep.sort_count
    # pass-weighted bytes scale with K too
    one = analyze(jax.jit(lambda x: jax.lax.sort(x)), x)
    assert abs(rep.sort_pass_bytes - K * one.sort_pass_bytes) < 1e-6
