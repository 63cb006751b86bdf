"""CylonContext: the entry point owning the device mesh and communicator.

Reference analog: ``cylon::CylonContext`` (cpp/src/cylon/ctx/cylon_context.hpp:29-146)
owns the MPI communicator, a string KV config map and sequence numbers for
concurrent collectives. Here the "communicator" is a ``jax.sharding.Mesh``;
rank/world_size map to process_index/mesh size; Barrier is
``block_until_ready`` on a tiny collective (XLA collectives are themselves
synchronizing, so an explicit barrier is rarely needed).
"""
from __future__ import annotations

import itertools
import os
import threading
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .config import CommConfig, CommType, LocalConfig, TPUConfig

_compile_cache_set = False

#: the compile cache of accelerator contexts when JAX_COMPILATION_CACHE_DIR
#: is not set: a fixed directory inside the checkout (the path is part of
#: the cache's key, so it must not move between runs); .gitignore lists it
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def _enable_compile_cache(platform: str) -> None:
    """Persistent XLA compilation cache, on by default on accelerators.

    The reference compiles its kernels AOT to native code once at build time;
    the XLA analog is this cache — every (program, shapes) combination
    compiles once per machine, not once per process. On TPU the big
    programs cost about a minute each to compile cold, so this is a
    product-level fix, not just a bench convenience.

    One location: where ``JAX_COMPILATION_CACHE_DIR`` is set jax already
    caches there and this function touches nothing; otherwise accelerator
    contexts use :data:`DEFAULT_COMPILE_CACHE`. ``CYLON_TPU_COMPILE_CACHE=0``
    opts out of the default. CPU contexts set no cache: XLA:CPU AOT reloads
    warn (and may SIGILL) across host-feature drift, and CPU compiles are
    cheap anyway."""
    global _compile_cache_set
    if _compile_cache_set:
        return
    _compile_cache_set = True
    from .utils import envgate as _envgate

    if (
        platform == "cpu"
        or os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or _envgate.COMPILE_CACHE.get() == "0"
    ):
        return
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


class CylonContext:
    """Holds the mesh, config KV map, and collective sequence numbers.

    Create via :meth:`init` (local, 1 device) or :meth:`init_distributed`
    (mesh over all visible devices), mirroring ``CylonContext::Init`` /
    ``InitDistributed`` (reference ctx/cylon_context.cpp:25-41).
    """

    def __init__(self, mesh: Mesh, axis_name: str, comm_type: CommType):
        _enable_compile_cache(mesh.devices.flat[0].platform)
        self.mesh = mesh
        self.axis_name = axis_name
        self.comm_type = comm_type
        self._config: Dict[str, str] = {}
        self._sequence = itertools.count()
        self._finalized = False
        # guards every ctx.__dict__-hosted shared map (engine._jit_cache /
        # _plan_cache, the join's _spec_cap_hints, the memory pool) so
        # concurrent query dispatch never races a cache build; cache HITS
        # stay lock-free (engine.py). RLock: a plan compile holding the
        # lock may build kernels through get_kernel on the same context.
        self._cache_lock = threading.RLock()
        # the live ops endpoint: /metrics + /healthz + /queries on
        # CYLON_TPU_METRICS_PORT (idempotent no-op when unset — the
        # server is process-wide, started by whichever context comes up
        # first)
        from .obs.export import ensure_ops_server

        ensure_ops_server()
        # reclaim spill directories orphaned by dead processes (pid-
        # stamped by HostArena._ensure_dir; age-guarded; never raises) —
        # the spill-volume analog of the obs store's dead-writer journal
        # reaping, at the same lifecycle point
        from .parallel.spill import reap_stale_spill

        reap_stale_spill()

    # -- factory ------------------------------------------------------------
    @classmethod
    def init(cls, config: Optional[CommConfig] = None) -> "CylonContext":
        """Local (single-device) context; reference CylonContext::Init."""
        if config is not None and config.comm_type() != CommType.LOCAL:
            return cls.init_distributed(config)
        dev = jax.devices()[0]
        mesh = Mesh(np.array([dev]), ("dp",))
        return cls(mesh, "dp", CommType.LOCAL)

    @classmethod
    def init_distributed(cls, config: CommConfig) -> "CylonContext":
        """Distributed context over a device mesh.

        Reference ``InitDistributed`` accepts only MPI and throws otherwise
        (ctx/cylon_context.cpp:33-41); here we accept mesh-based configs.
        """
        if not isinstance(config, TPUConfig):
            raise ValueError(
                f"distributed init requires TPUConfig/CPUConfig, got {type(config)}"
            )
        if config.coordinator_address is not None:
            # multi-host: one jax process per host, devices global across the
            # mesh (the mpirun-rank analog; reference mpi_communicator.cpp:51
            # lazily calls MPI_Init the same way)
            from .compat import distributed_is_initialized

            if not distributed_is_initialized():
                jax.distributed.initialize(
                    coordinator_address=config.coordinator_address,
                    num_processes=config.num_processes,
                    process_id=config.process_id,
                )
        devices = config.devices if config.devices is not None else jax.devices()
        mesh = Mesh(np.asarray(devices), (config.axis_name,))
        ctx = cls(mesh, config.axis_name, config.comm_type())
        if getattr(config, "mesh_shape", None):
            ctx.add_config("mesh_shape", str(config.mesh_shape))
        return ctx

    # -- identity -----------------------------------------------------------
    def get_world_size(self) -> int:
        return self.mesh.size

    @property
    def world_size(self) -> int:
        return self.mesh.size

    def get_rank(self) -> int:
        # single-controller JAX: the "rank" is the process index (0 except
        # under multi-host jax.distributed).
        return jax.process_index()

    @property
    def rank(self) -> int:
        return self.get_rank()

    def get_neighbours(self, include_self: bool = False):
        """Reference GetNeighbours (ctx/cylon_context.cpp:87)."""
        w = self.get_world_size()
        r = self.get_rank()
        return [i for i in range(w) if include_self or i != r]

    def is_distributed(self) -> bool:
        return self.mesh.size > 1

    @property
    def platform(self) -> str:
        """Platform of the mesh's devices ("tpu", "cpu", ...). Programs for
        this context lower for it — which is not always the process's
        default backend (a TPU host may drive a CPU-device mesh)."""
        return self.mesh.devices.flat[0].platform

    # -- config KV (reference AddConfig/GetConfig, cylon_context.hpp:60-69) --
    def add_config(self, key: str, value: str) -> None:
        self._config[key] = value

    def get_config(self, key: str, default: str = "") -> str:
        return self._config.get(key, default)

    @property
    def shuffle_byte_budget(self) -> int:
        """Effective per-round chunked-shuffle byte budget for this context
        (config KV ``shuffle_byte_budget`` > CYLON_TPU_SHUFFLE_BUDGET env >
        config.DEFAULT_SHUFFLE_BYTE_BUDGET)."""
        from .config import shuffle_byte_budget

        return shuffle_byte_budget(self._config.get("shuffle_byte_budget"))

    @property
    def sketch_bits(self) -> int:
        """Effective semi-join sketch bit cap for this context (config KV
        ``sketch_bits`` > CYLON_TPU_SKETCH_BITS env >
        config.DEFAULT_SKETCH_BITS)."""
        from .config import sketch_bits

        return sketch_bits(self._config.get("sketch_bits"))

    @property
    def topology(self):
        """Declared logical 2-D topology (config KV ``mesh_shape`` >
        CYLON_TPU_MESH env > None = flat), validated against the mesh
        size and resolved once per context. This is the DECLARED shape;
        the per-shuffle decision (which also honors the
        CYLON_TPU_NO_TOPO kill switch and collapses degenerate 1xN/Nx1
        factorizations) is ``parallel.topo.effective(ctx)``."""
        cached = self.__dict__.get("_topology_cache")
        if cached is None:
            from .parallel import topo as _topo

            spec = self._config.get("mesh_shape") or _topo.MESH_ENV.get()
            cached = (_topo.parse_mesh(spec, self.mesh.size),)
            self.__dict__["_topology_cache"] = cached
        return cached[0]

    @property
    def quant_tol(self) -> float:
        """Effective lossy-wire tolerance for this context (config KV
        ``quant_tol`` > CYLON_TPU_QUANT_TOL env > 0.0 = exact wire; the
        CYLON_TPU_NO_QUANT kill switch forces 0.0). See ops/quant.py for
        the codec tiers the tolerance engages."""
        from .ops.quant import tolerance

        return tolerance(self._config.get("quant_tol"))

    # -- sequencing (reference GetNextSequence, cylon_context.cpp:106) ------
    def get_next_sequence(self) -> int:
        return next(self._sequence)

    # -- sharding helpers ---------------------------------------------------
    @property
    def spec(self) -> PartitionSpec:
        """Row-sharded partition spec for table columns."""
        return PartitionSpec(self.axis_name)

    @property
    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec)

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    # -- sync ---------------------------------------------------------------
    def barrier(self) -> None:
        """Reference Barrier (ctx/cylon_context.hpp:143). XLA collectives are
        synchronizing; this blocks the host on an all-device no-op."""
        x = jax.device_put(
            np.zeros(self.mesh.size, np.int32), self.sharding
        )
        jax.block_until_ready(jax.jit(lambda v: v + 1)(x))

    def finalize(self) -> None:
        self._finalized = True

    def is_finalized(self) -> bool:
        return self._finalized

    @property
    def memory_pool(self):
        """Context-owned native arena pool for host staging buffers
        (reference ToArrowPool(ctx), ctx/arrow_memory_pool_utils.hpp; here
        native/runtime.cpp). Lazily created; None if the toolchain is
        unavailable."""
        pool = self.__dict__.get("_memory_pool")
        if pool is None:
            from .native import MemoryPool, available

            if not available():
                return None
            with self._cache_lock:
                pool = self.__dict__.get("_memory_pool")
                if pool is None:
                    pool = self.__dict__["_memory_pool"] = MemoryPool()
        return pool

    def memory_usage(self) -> int:
        """Total live device memory (bytes) across the mesh, best effort."""
        total = 0
        for d in self.mesh.devices.flat:
            try:
                stats = d.memory_stats()
                total += stats.get("bytes_in_use", 0)
            except Exception:
                pass
        return total
