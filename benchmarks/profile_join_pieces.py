"""One-off stage profile of the speculative join at bench shape.

Times cumulative prefixes of the spec_join pipeline (probe sort, repeat,
left gather, right gather, full) on the live backend so optimization
effort lands on the measured bottleneck, not the modeled one. Each stage
is fenced by a dependent-scalar fetch (DCE-proof).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("CYLON_TPU_NO_X64", "1")

import numpy as np


def main():
    n = int(os.environ.get("BENCH_ROWS", 16_000_000))
    if "--cpu" not in sys.argv:
        import bench as _b

        _b.require_tpu()  # --cpu is the only way onto the CPU
    else:
        import __graft_entry__ as ge

        ge._force_cpu_mesh(1)
        n = min(n, 1_000_000)

    import jax
    import jax.numpy as jnp

    from cylon_tpu.ops import join as _j

    rng = np.random.default_rng(0)
    lk = jnp.asarray(rng.integers(0, n, n).astype(np.int32))
    rk = jnp.asarray(rng.integers(0, n, n).astype(np.int32))
    lv = jnp.asarray(rng.normal(size=n).astype(np.float32))
    rv = jnp.asarray(rng.normal(size=n).astype(np.float32))
    cap = 1 << (n - 1).bit_length()  # bench spec_cap = max(cap_l, cap_r)

    def chk(*arrs):
        s = jnp.float32(0)
        for a in arrs:
            s = s + jnp.sum(a.astype(jnp.float32))
        return s

    def probe_only(a, b):
        lo, cnt, r_order, r_cnt = _j.probe_arrays(
            [(a, None)], [(b, None)], jnp.int32(n), jnp.int32(n), n, n,
            _j.INNER,
        )
        return (lo, cnt, r_order, r_cnt)

    stages = {}
    stages["probe"] = jax.jit(lambda a, b, v, w: chk(*probe_only(a, b)))

    def thru_repeat(a, b):
        lo, cnt, r_order, r_cnt = probe_only(a, b)
        ends = jnp.cumsum(cnt)
        li = _j._repeat_ss(ends, cap)
        return li, lo, cnt, r_order

    stages["probe+repeat"] = jax.jit(
        lambda a, b, v, w: chk(*thru_repeat(a, b))
    )

    def thru_lgather(a, b, v):
        from cylon_tpu.ops.gather import pack_gather

        li, lo, cnt, r_order = thru_repeat(a, b)
        out_l, (base_g, cnt_g) = pack_gather(
            [(a, None), (v, None)], li, extra_lanes=[lo, cnt]
        )
        return out_l, base_g, cnt_g

    def _lg(a, b, v, w):
        out_l, base_g, cnt_g = thru_lgather(a, b, v)
        return chk(*[d for d, _ in out_l], base_g, cnt_g)

    stages["probe+repeat+lgather"] = jax.jit(_lg)

    def full(a, b, v, w):
        out, total, shadow, _handed = _j.spec_join(
            [(a, None)], [(b, None)],
            [(a, None), (v, None)], [(b, None), (w, None)],
            jnp.int32(n), jnp.int32(n), _j.INNER, cap,
        )
        return chk(*[d for d, _ in out]) + total.astype(jnp.float32)

    stages["full"] = jax.jit(full)

    # the r4 windowed emit, staged the same way: compact-scatter + expand
    # replacing the left gather, then the full windowed join
    platform = jax.devices()[0].platform
    w_impl = "windowed" if platform == "tpu" else "windowed_interp"

    def _lw(a, b, v, w):
        # the windowed emit computes its own compacted repeat internally,
        # so this stage is probe + (compact scatter + expand + right gather)
        lo, cnt, r_order, _rc = probe_only(a, b)
        from cylon_tpu.ops.gather import pack_gather

        r_sorted, _ = pack_gather([(b, None), (w, None)], r_order)
        r_sorted = [(d, None) for d, _v in r_sorted]
        out_cols, n_out, _handed = _j._emit_inner_left(
            lo, cnt, [(a, None), (v, None)],
            r_sorted, jnp.int32(n), _j.INNER, cap, n, w_impl,
        )
        return chk(*[d for d, _ in out_cols]) + n_out.astype(jnp.float32)

    stages["probe+windowed_emit"] = jax.jit(_lw)

    def full_windowed(a, b, v, w):
        out, total, shadow, _handed = _j.spec_join(
            [(a, None)], [(b, None)],
            [(a, None), (v, None)], [(b, None), (w, None)],
            jnp.int32(n), jnp.int32(n), _j.INNER, cap, w_impl,
        )
        return chk(*[d for d, _ in out]) + total.astype(jnp.float32)

    stages["full_windowed"] = jax.jit(full_windowed)

    for name, fn in stages.items():
        t0 = time.perf_counter()
        float(fn(lk, rk, lv, rv))
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(fn(lk, rk, lv, rv))
            best = min(best, time.perf_counter() - t0)
        print(
            json.dumps(
                {
                    "stage": name,
                    "rows": n,
                    "cap": cap,
                    "warm_s": round(best, 4),
                    "compile_s": round(compile_s, 2),
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
