"""Cold-compile cost profile of the headline join (VERDICT r3 item 3).

The 8M-row speculative join cost ~100 s of XLA compile on first touch
(round-3 capture). This breaks the program into stages and times
``.lower().compile()`` for each at the headline shape, then A/Bs the whole
join under XLA's compile-effort knobs
(jax_exec_time_optimization_effort / jax_memory_fitting_effort = -1.0,
i.e. compile-speed-over-exec-speed) against the default, with a warm-exec
quality check so a compile-time win that costs runtime is visible.

Every configuration's program carries a distinct baked-in salt constant
(see make_full_join) so the backend's executable cache cannot serve the
A/B a 0.0 s "compile"; the process also disables the persistent cache —
the point is the no-cache cold path a new machine pays.

Usage: python benchmarks/compile_profile.py [--rows N] [--cpu]
One JSON line per stage/config.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("CYLON_TPU_NO_X64", "1")
# defeat the persistent cache for THIS process: cold numbers are the point
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=8_000_000)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import __graft_entry__ as ge

    if not args.cpu:
        import bench as _b

        _b.require_tpu()  # --cpu is the only way onto the CPU
    else:
        ge._force_cpu_mesh(1)
        args.rows = min(args.rows, 1_000_000)

    import jax
    import jax.numpy as jnp

    from cylon_tpu.ops import join as _j
    from cylon_tpu.ops.sort import orderable_key

    platform = jax.devices()[0].platform
    n = args.rows
    cap = 1 << (n - 1).bit_length()
    cap_out = 2 * cap
    rng = np.random.default_rng(0)
    lk = jnp.asarray(rng.integers(0, n, cap).astype(np.int32))
    rk = jnp.asarray(rng.integers(0, n, cap).astype(np.int32))
    lv = jnp.asarray(rng.normal(size=cap).astype(np.float32))
    rv = jnp.asarray(rng.normal(size=cap).astype(np.float32))
    nl = jnp.int32(n)
    nr = jnp.int32(n)

    def emit_line(**kw):
        print(json.dumps({"platform": platform, "rows": n, **kw}), flush=True)

    def time_compile(name, fn, *xs, warm_reps=2, **cfg):
        """lower+compile wall + warm exec wall for a jittable fn."""
        try:
            t0 = time.perf_counter()
            lowered = jax.jit(fn).lower(*xs)
            lower_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            out = jax.device_get(compiled(*xs))
            first_s = time.perf_counter() - t0
            best = float("inf")
            for _ in range(warm_reps):
                t0 = time.perf_counter()
                out = jax.device_get(compiled(*xs))
                best = min(best, time.perf_counter() - t0)
            emit_line(stage=name, lower_s=round(lower_s, 2),
                      compile_s=round(compile_s, 2),
                      warm_s=round(best, 4), first_s=round(first_s, 3),
                      **cfg)
            return compile_s, best
        except Exception as e:
            emit_line(stage=name, error=f"{type(e).__name__}: {str(e)[:200]}",
                      **cfg)
            return None, None

    # ---- stage decomposition (default effort) ----
    def probe_only(a, b):
        l_ids, r_ids = _j._canonical_ids(
            [(a, None)], [(b, None)], nl, nr, cap, cap
        )
        lo, cnt, r_cnt = _j._merged_counts(l_ids, r_ids, nl, nr, cap, cap, False)
        return jnp.sum(lo) + jnp.sum(cnt)

    def ride_sort_only(b, w):
        r_ids = jnp.where(jnp.arange(cap) < nr, orderable_key(b),
                          np.uint32(0xFFFFFFFF))
        s = jax.lax.sort((r_ids, w), num_keys=1, is_stable=True)
        return jnp.sum(s[1])

    def repeat_emit_only(cnt_in, v):
        ends = jnp.cumsum(cnt_in)
        li = _j._repeat_ss(ends, cap_out)
        safe = jnp.clip(li, 0, cap - 1)
        return jnp.sum(v[safe])

    def make_full_join(salt: float):
        # the salt bakes a distinct constant into the HLO: without it the
        # effort A/B re-uses the backend's executable cache (compile 0.0 s)
        # and measures nothing
        def full_join(a, b, v, w):
            out, tot, _, _ = _j.spec_join(
                [(a, None)], [(b, None)],
                [(a, None), (v, None)], [(b, None), (w, None)],
                nl, nr, _j.INNER, cap_out,
            )
            s = jnp.float32(salt)
            for d, _v in out:
                s = s + jnp.sum(d.astype(jnp.float32))
            return tot, s

        return full_join

    cnt_in = jnp.asarray(rng.integers(0, 3, cap).astype(np.int32))
    time_compile("probe_sorts", probe_only, lk, rk)
    time_compile("ride_sort", ride_sort_only, rk, rv)
    time_compile("repeat_emit", repeat_emit_only, cnt_in, lv)
    c_full, w_full = time_compile(
        "full_spec_join", make_full_join(0.0), lk, rk, lv, rv
    )

    # ---- whole join under reduced compile effort ----
    jax.config.update("jax_exec_time_optimization_effort", -1.0)
    jax.config.update("jax_memory_fitting_effort", -1.0)
    c_fast, w_fast = time_compile(
        "full_spec_join", make_full_join(1.0), lk, rk, lv, rv,
        effort="-1.0",
    )
    jax.config.update("jax_exec_time_optimization_effort", 0.0)
    jax.config.update("jax_memory_fitting_effort", 0.0)

    if c_full and c_fast:
        emit_line(
            stage="verdict",
            compile_speedup=round(c_full / c_fast, 2),
            warm_slowdown=round(w_fast / w_full, 3),
            recommend_low_effort=bool(
                c_fast < 0.7 * c_full and w_fast < 1.05 * w_full
            ),
        )


if __name__ == "__main__":
    main()
