"""Execution engine: cached jit+shard_map kernel dispatch.

Every relational op is a per-shard, static-shaped kernel run under
``jax.shard_map`` over the context mesh. This module provides:

- capacity rounding (power-of-two buckets so jit's shape-specialized cache
  stays warm across calls with slightly different sizes);
- a per-context cache of jitted shard_map callables keyed by (op, statics) —
  shape specialization inside each entry is handled by jit itself;
- the standard calling convention: ``kernel(dp_args, rep_args) -> dp_outputs``
  where dp_args/outputs are per-shard (row-sharded) pytrees and rep_args are
  replicated (e.g. shape-carrying dummies that tell the kernel its output
  capacity).

Reference analog: this replaces the reference's eager C++ call tree — there,
each op is a hand-written loop nest (cpp/src/cylon/table.cpp); here each op is
one XLA program per (shapes, statics) combination, compiled once and reused.
"""
from __future__ import annotations

import contextvars
import functools
import re
import threading
from time import perf_counter_ns
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec

from .compat import shard_map
from .context import CylonContext
from .obs import stages as _stages
from .obs.trace import note_dispatch
from .utils.tracing import bump

# kernel-invocation recording for roofline analysis (benchmarks/roofline.py):
# when enabled, every get_kernel dispatch appends (compiled_fn, args) so a
# bench can re-trace exactly the programs an eager op chain executed.
# lint: guarded=gil -- single-flag swap + list.append are GIL-atomic; the
# recorder is a single-threaded bench/analysis harness, never a serving path
_KERNEL_RECORD = None

# fallback creator for contexts built before the per-context cache lock
# existed (pickled/duck-typed contexts): serializes ONLY lock creation
_LOCK_FALLBACK = threading.Lock()


def cache_lock(ctx) -> "threading.RLock":
    """The per-context lock guarding every ``ctx.__dict__``-hosted shared
    map (``_jit_cache``, ``_plan_cache``, ``_spec_cap_hints``, the memory
    pool). Created in ``CylonContext.__init__``; the fallback path covers
    foreign context objects without racing the lock's own creation."""
    lock = getattr(ctx, "_cache_lock", None)
    if lock is None:
        with _LOCK_FALLBACK:
            lock = ctx.__dict__.setdefault("_cache_lock", threading.RLock())
    return lock


def record_kernels(enable: bool) -> None:
    global _KERNEL_RECORD
    _KERNEL_RECORD = [] if enable else None


def recorded_kernels():
    return list(_KERNEL_RECORD or [])


def record_dispatch(fn, *args) -> None:
    """Record a kernel dispatch for the roofline analyzer — the ONE copy of
    the recording discipline, used both by get_kernel's wrapper and by
    dispatches that bypass get_kernel (the fused-join step is cached
    directly on the context).

    Records SHAPES, not the live arrays: pinning every dispatched kernel's
    inputs for a whole op chain would hold intermediates XLA otherwise
    frees, inflating peak HBM exactly on the big TPU runs the recorder
    exists to model. The spec (with shardings and weak types, so that it
    lowers to the program that ran) is built by ``obs.stages.arg_spec``,
    the rule's one copy, shared with the stage table's registry."""
    if _KERNEL_RECORD is None:
        return
    spec = _stages.arg_spec(args)
    # lint: guarded=gil -- list.append is GIL-atomic and the recorder is a
    # single-threaded bench/analysis harness, never enabled while serving
    _KERNEL_RECORD.append((fn, spec))


# platform of the devices the kernel being traced is for (None outside
# a kernel trace)
_MESH_PLATFORM: contextvars.ContextVar = contextvars.ContextVar(
    "cylon_tpu_mesh_platform", default=None
)


def mesh_platform() -> str:
    """Platform of the devices the program being traced will run on.

    Inside a kernel wrapped by :func:`on_mesh` (every ``get_kernel``
    program and the fused-join steps) this is the context mesh's platform:
    a lowering for TPU devices is a Mosaic lowering wherever the process
    runs, and a CPU mesh driven from a TPU host interprets. Outside one (a
    bare ``jax.jit`` of an op, no mesh in hand) jit places the program on
    the default device, so that device's platform is the answer."""
    return _MESH_PLATFORM.get() or jax.devices()[0].platform


_NOT_IDENT = re.compile(r"[^A-Za-z0-9_]")


def program_name(key: Tuple, name: Optional[str] = None) -> str:
    """What a program is called in a trace (``jit_<name>`` on the device's
    ``XLA Modules`` line, ``PjitFunction(<name>)`` on the host): ``name``,
    or the cache key's first string part, cut to ``[A-Za-z0-9_]``. The
    name is also part of the persistent compile cache's key."""
    if name is None:
        name = next((p for p in key if isinstance(p, str)), "kern")
    return _NOT_IDENT.sub("_", name) or "kern"


def on_mesh(mesh, kernel: Callable, name: Optional[str] = None) -> Callable:
    """Wrap ``kernel`` so its body sees ``mesh_platform()`` of ``mesh``,
    and call the wrapper ``name`` (``jax.jit`` names a program after the
    function it is given; every builder's is ``kern``). The body of a
    jitted function runs only while it is traced, so the wrapper costs
    nothing per dispatch."""
    platform = mesh.devices.flat[0].platform

    @functools.wraps(kernel)
    def traced(*args):
        token = _MESH_PLATFORM.set(platform)
        try:
            return kernel(*args)
        finally:
            _MESH_PLATFORM.reset(token)

    if name is not None:
        traced.__name__ = traced.__qualname__ = name
    return traced


class TimedProgram:
    """A jitted program as the caches hold it: the call reads the clock
    before and after and hands the difference to ``obs.trace``
    (:func:`~cylon_tpu.obs.trace.note_dispatch`: the rollup span
    ``dispatch.<name>`` and the thread's open record). Built once, where
    the program is; a call allocates nothing. Everything else asked of it
    (``lower``, ``_cache_size``, ``__name__``) is the jitted function's."""

    __slots__ = ("fn", "name", "span")

    def __init__(self, fn: Callable, name: str):
        self.fn = fn
        self.name = name
        self.span = "dispatch." + name

    def __call__(self, *args):
        t0 = perf_counter_ns()
        try:
            return self.fn(*args)
        finally:
            note_dispatch(self, t0, perf_counter_ns())

    def __getattr__(self, attr):
        return getattr(self.fn, attr)


def round_cap(n: int, minimum: int = 8) -> int:
    """Round a capacity up to a power of two (>= minimum)."""
    n = max(int(n), minimum)
    return 1 << (n - 1).bit_length()


def shard_caps(total_rows: int, world: int) -> Tuple[np.ndarray, int]:
    """Even row split of a global table: (per-shard counts [P], shard cap)."""
    base, rem = divmod(int(total_rows), world)
    counts = np.array([base + (1 if i < rem else 0) for i in range(world)], np.int64)
    return counts, round_cap(counts.max() if world else 0)


def get_kernel(
    ctx: CylonContext,
    key: Tuple,
    builder: Callable[[], Callable],
    check_vma: bool = True,
    use_shard_map: bool = True,
    name: Optional[str] = None,
) -> Callable:
    """Fetch (or build+jit) the shard_map-wrapped kernel for this context.

    ``name`` is what the program does (``join_spec``, ``shuffle_pack``);
    the default is the key's first string part (:func:`program_name`).

    ``check_vma=False`` disables shard_map's varying-mesh-axes checker —
    needed by kernels embedding ``pallas_call`` (its output vma interplay
    with unvarying iotas trips the checker).

    ``use_shard_map=False`` jits the kernel directly (caller guarantees a
    1-device mesh, where shard_map is a no-op). Caching and kernel
    recording behave identically either way.

    The kernel body runs with :func:`mesh_platform` set to the platform of
    the context's devices, so code deep inside it (the sort engine's
    Pallas tier) can pick interpret mode from the MESH, not from the
    process's default backend.

    What the cache holds is a :class:`TimedProgram`: every call is timed
    (``dispatch.<program>`` in the rollup, and the public call's record).
    On a cache MISS the callable handed back is a one-shot wrapper that
    registers the first call's argument spec for the device stage table
    (``obs.stages.register_dispatch``); every later call gets the cached
    object itself, so a warm dispatch pays two clock reads and no more."""
    cache = ctx.__dict__.get("_jit_cache")
    if cache is None:
        with cache_lock(ctx):
            cache = ctx.__dict__.setdefault("_jit_cache", {})
    # wrapping flags are part of the identity: same logical key with a
    # different shard_map/vma wrapping must not alias to the first program
    key = key + (bool(use_shard_map), bool(check_vma))
    # the hot path stays lock-cheap: a dict read is GIL-atomic, and an
    # entry is published only AFTER it is fully built (under the lock)
    fn = cache.get(key)
    missed = False
    if fn is None:
        with cache_lock(ctx):
            fn = cache.get(key)  # double-check: lost the build race
            if fn is None:
                pname = program_name(key, name)
                kernel = on_mesh(ctx.mesh, builder(), pname)
                if use_shard_map:
                    fn = jax.jit(
                        shard_map(
                            kernel,
                            mesh=ctx.mesh,
                            in_specs=(
                                PartitionSpec(ctx.axis_name),
                                PartitionSpec(),
                            ),
                            out_specs=PartitionSpec(ctx.axis_name),
                            check_vma=check_vma,
                        )
                    )
                else:
                    fn = jax.jit(kernel)
                fn = cache[key] = TimedProgram(fn, pname)
                missed = True
    if _KERNEL_RECORD is None and not missed:
        return fn

    def noting(*args, _fn=fn, _key=key):
        if missed:
            _stages.register_dispatch(ctx, _key, _fn, args)
        record_dispatch(_fn, *args)
        return _fn(*args)

    return noting


def run(ctx: CylonContext, key: Tuple, builder, dp_args, rep_args=()):
    return get_kernel(ctx, key, builder)(dp_args, rep_args)


# ----------------------------------------------------------------------
# plan-fingerprint executable cache (cylon_tpu/plan)
# ----------------------------------------------------------------------
_PLAN_CACHE_MAX = 256


class PlanEntry(NamedTuple):
    """One cached optimize+lower product. ``hist_key`` is the plan's
    latency-histogram key (``obs.metrics.fingerprint_key``), hoisted here
    so the serving hot loop hashes each fingerprint exactly once — at
    compile time — instead of re-deriving it on every collect
    (``plan.fingerprint.hash`` counts the hashes; test_serving pins it
    flat across cached collects)."""

    opt: Any                  # the optimized (detached) plan
    fired: Tuple[str, ...]    # optimizer rule firings
    fn: Callable              # executor: fn(tables) -> Table
    hist_key: str             # fingerprint_key(fingerprint), precomputed
    #: observation-store profile key (plan/feedback.base_key over the
    #: BASE fingerprint — the identity WITHOUT the tuned-decision
    #: component, so a decision flip keeps feeding the same profile);
    #: "" when the plan layer predates/skips the store
    obs_key: str = ""


def plan_executable(ctx: CylonContext, fingerprint, compile_fn):
    """Per-context cache of optimized+lowered plan executables, keyed by the
    plan's structural fingerprint (node shapes + schemas + world size; NOT
    row counts — jit's shape specialization inside each eager kernel handles
    sizes). A hit skips optimize+lower entirely and every kernel the
    executor dispatches re-uses its ``_jit_cache`` entry, so a repeated
    ``.collect()`` of the same plan shape compiles nothing.

    Returns ``(entry, hit)``; hits/misses are counted in the tracing
    registry (``plan.cache.hit`` / ``plan.cache.miss``) for tests and
    benchmarks to assert on — counter updates are atomic (the tracing
    registry serializes them under its own lock).

    Thread discipline: hits are lock-free (GIL-atomic dict read of a
    fully-published entry); the miss path compiles UNDER the per-context
    lock, so a cache stampede (many threads racing the first compile of
    one fingerprint) compiles exactly once — the losers block, then hit.
    """
    return _cached_compile(
        ctx, "_plan_cache", fingerprint, compile_fn, "plan.cache",
        _PLAN_CACHE_MAX,
    )


def _cached_compile(ctx, attr: str, key, compile_fn, counter: str, cap: int):
    """The ONE copy of the executable-cache discipline shared by the
    plan tier and the serve batch tier: lazy ``ctx.__dict__`` cache
    creation, lock-free hits of fully-published entries, stampedes
    compiling exactly once under the per-context lock, and bounded FIFO
    eviction (literal values ride fingerprints, so a literal sweep must
    not grow an entry per value — dropping one only costs a re-optimize,
    the jitted kernels stay cached). Counted as ``<counter>.hit`` /
    ``<counter>.miss``."""
    cache = ctx.__dict__.get(attr)
    if cache is None:
        with cache_lock(ctx):
            cache = ctx.__dict__.setdefault(attr, {})
    entry = cache.get(key)
    if entry is not None:
        bump(counter + ".hit")
        return entry, True
    with cache_lock(ctx):
        entry = cache.get(key)
        if entry is not None:
            # stampede loser: the winner compiled while we waited
            bump(counter + ".hit")
            return entry, True
        bump(counter + ".miss")
        entry = compile_fn()
        if len(cache) >= cap:
            cache.pop(next(iter(cache)))
        cache[key] = entry
    return entry, False


def plan_cache_stats() -> dict:
    """{hits, misses} of the plan-fingerprint cache (process-wide)."""
    from .utils.tracing import get_count

    return {
        "hits": get_count("plan.cache.hit"),
        "misses": get_count("plan.cache.miss"),
    }


# ----------------------------------------------------------------------
# batched-executor tier (cylon_tpu/serve): compile-once, serve-many over
# B same-fingerprint parameter bindings stacked into ONE device program
# ----------------------------------------------------------------------
_BATCH_CACHE_MAX = 64


def serve_batch_executable(ctx: CylonContext, key, compile_fn):
    """Per-context cache of BATCHED plan executors, keyed by
    ``(fingerprint..., B-bucket)`` — the serving scheduler's second
    executor tier above :func:`plan_executable`.

    The scheduler buckets batch sizes to powers of two (padding the tail
    of a batch with zero-row binding slots), so one fingerprint grows at
    most log2(CYLON_TPU_SERVE_BATCH_MAX) entries here no matter how the
    arrival process mixes batch sizes. Same locking discipline as the
    plan cache (``_cached_compile``): lock-free hits of fully-published
    entries, stampedes compile exactly once under the per-context lock,
    bounded FIFO. Counted as ``serve.batch_cache.hit`` /
    ``serve.batch_cache.miss`` (the test_serving cache pin: B bindings
    -> 1 compile per (fingerprint, B-bucket))."""
    return _cached_compile(
        ctx, "_serve_batch_cache", key, compile_fn, "serve.batch_cache",
        _BATCH_CACHE_MAX,
    )
