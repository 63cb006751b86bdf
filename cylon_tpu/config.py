"""Communication / context configuration.

Mirrors the reference's ``CommConfig``/``CommType`` layer
(reference: cpp/src/cylon/net/comm_config.hpp:22-36, net/comm_type.hpp) but the
concrete backends are TPU-native:

- ``LOCAL``  -> single device, no collectives (reference CommType::LOCAL)
- ``TPU``    -> a jax.sharding.Mesh over the ICI-connected devices; collectives
               are XLA all_to_all / psum over the mesh axis (replaces the
               reference's MPI backend, net/mpi/mpi_communicator.cpp:51-66).
- ``CPU``    -> same code path on host CPU devices (used by tests via
               ``--xla_force_host_platform_device_count``).
"""
from __future__ import annotations

import enum
from typing import Any, Dict, Optional, Sequence

from .utils import envgate as _envgate

# ----------------------------------------------------------------------
# chunked-shuffle byte budget (parallel/shuffle.py plan_rounds)
# ----------------------------------------------------------------------
# Per-round, per-shard cap on the shuffle exchange buffer: the engine sizes
# bucket_cap so ``world * bucket_cap * row_bytes <= budget`` and drains the
# table over ceil(hottest_bucket / bucket_cap) rounds — peak shuffle memory
# is O(budget), not O(max-shard padding), which is what lets tables far
# larger than the budget shuffle without the full padded buffer ever
# materializing. Override per context via
# ``ctx.add_config("shuffle_byte_budget", str(n))`` / ``TPUConfig
# .add_config``, per call via the ``byte_budget=`` kwarg, or process-wide
# via CYLON_TPU_SHUFFLE_BUDGET.
DEFAULT_SHUFFLE_BYTE_BUDGET = 32 * 1024 * 1024


def shuffle_byte_budget(configured: Optional[object] = None) -> int:
    """Resolve the effective per-round shuffle byte budget: an explicit
    value wins, then the CYLON_TPU_SHUFFLE_BUDGET env var, then the
    module default."""
    if configured:
        return int(configured)
    env = _envgate.SHUFFLE_BUDGET.get()
    if env:
        return int(env)
    return DEFAULT_SHUFFLE_BYTE_BUDGET


# ----------------------------------------------------------------------
# spill tiers (parallel/spill.py; table._shuffle_many)
# ----------------------------------------------------------------------
# The unified spill-tiered round planner extends the byte budget above
# with two more policy knobs, both resolved per shuffle from the measured
# per-bucket counts: CYLON_TPU_SPILL_DEVICE_BUDGET (per-shard staged
# bytes above which rounds stream into host arenas instead of staying
# device-resident — unset keeps today's in-HBM behavior) and
# CYLON_TPU_SPILL_HOST_BUDGET (live host-arena bytes above which arena
# growth promotes to disk-backed memmaps under CYLON_TPU_SPILL_DIR).
# CYLON_TPU_SPILL_TIER forces a tier for tests/differentials and
# CYLON_TPU_NO_SKEW_SPLIT=1 disables skew-adaptive round splitting (the
# padded-plan oracle). Resolvers live in parallel/spill.py beside their
# consumer — this comment is the config map's pointer to them.


# ----------------------------------------------------------------------
# semi-join sketch filter (ops/sketch.py; table._shuffle_pair)
# ----------------------------------------------------------------------
# Cap on the blocked-Bloom size of ONE semi-join key sketch, in bits.
# 2 Mi bits = 256 KiB packed uint32 — the bound on the per-shard bytes each
# side injects into the single sketch collective. The engine sizes the
# actual sketch from the build side's row count (sketch.BITS_PER_KEY per
# key) and only grows to this cap; raise it for very large build sides
# where the default saturates (false positives = missed pruning, never a
# wrong answer). Override per context via
# ``ctx.add_config("sketch_bits", str(n))`` or process-wide via
# CYLON_TPU_SKETCH_BITS.
DEFAULT_SKETCH_BITS = 1 << 21

# Host-side size gate: build sketches only when the filtered sides'
# PER-SHARD exchange payload (rows x row_bytes / world — the same basis
# the traced coll-MB accounting uses, since each shard injects its whole
# local sketch but only its 1/world row slice) is at least this multiple
# of the sketch collective's own bytes. Tables below the line skip the
# sketch entirely — the collective would cost more than perfect pruning
# could save.
SEMI_FILTER_MIN_PAYOFF = 2

# ----------------------------------------------------------------------
# distributed join: which route (ops/join.replicate_side;
# table.Table.distributed_join; plan/rules._physicalize)
# ----------------------------------------------------------------------
# A distributed join replicates one side to every chip, and leaves the
# other side where it lies, where the WHOLE of that side (rows x the bytes
# a row takes on the device) is at most one part in this many of what ONE
# chip holds of the other side (its rows x row bytes / world); anything
# else hash-shuffles both sides. Why a share of a chip's part and not of
# the table: after the gather every chip's local join has the whole small
# side as its build side, so the route's cost on a chip is the local join
# of its own part (which the shuffle route pays as well, after packing,
# exchanging and compacting that part) plus sorting and gathering the
# small side whole. At 1/16 that addition is under a sixteenth of the
# rows the chip sorts anyway and cannot change the result's capacity
# (round_cap(max(cap_l, cap_r))), so the route can only lose what the
# all_gather itself costs. On four chips the line is a table ratio of
# 64:1, on eight of 128:1: the H2O join task's medium (1,000:1) and small
# (1,000,000:1) tables replicate; an equal pair (join-w4, H2O's question 5)
# and the 16:1 foreign-key pair of join-skew-w4 (4:1 a chip) shuffle as
# they did. Where between 16:1 and 1,000:1 the true crossover lies has
# not been measured (PERF.md section 7): the constant is the cautious end.
REPLICATE_JOIN_MIN_RATIO = 16


def sketch_bits(configured: Optional[object] = None) -> int:
    """Resolve the semi-join sketch bit cap: an explicit value wins, then
    the CYLON_TPU_SKETCH_BITS env var, then the module default."""
    if configured:
        return int(configured)
    env = _envgate.SKETCH_BITS.get()
    if env:
        return int(env)
    return DEFAULT_SKETCH_BITS


class CommType(enum.IntEnum):
    LOCAL = 0
    TPU = 1
    CPU = 2


class CommConfig:
    """Base config. Key/value store like reference CommConfig (void* KV)."""

    def __init__(self) -> None:
        self._config: Dict[str, Any] = {}

    def comm_type(self) -> CommType:
        raise NotImplementedError

    def add_config(self, key: str, value: Any) -> None:
        self._config[key] = value

    def get_config(self, key: str, default: Any = None) -> Any:
        return self._config.get(key, default)


class LocalConfig(CommConfig):
    """Single-device execution (no mesh axis)."""

    def comm_type(self) -> CommType:
        return CommType.LOCAL


class TPUConfig(CommConfig):
    """Distributed execution over a device mesh.

    Parameters
    ----------
    devices: explicit device list (default: all ``jax.devices()``).
    axis_name: mesh axis name used by collectives (default ``"dp"``).

    This is the user-visible switch replacing the reference's ``MPIConfig``
    (python/pycylon/net/mpi_config.pyx): ``CylonEnv(config=TPUConfig())``.

    Multi-host: pass ``coordinator_address`` (+ ``num_processes``/
    ``process_id``) to run ``jax.distributed.initialize`` before the mesh is
    built — the analog of mpirun launching N ranks (reference
    net/mpi/mpi_communicator.cpp:51-66, lazy MPI_Init). On TPU pods the three
    values are auto-detected when left None.

    Topology: ``mesh_shape="OxI"`` declares a LOGICAL 2-D factorization
    (outer x inner, product = device count) of the still-1-D mesh —
    device p is (outer group p // inner, inner index p % inner), so an
    inner group is a contiguous device range (ICI neighbors on a TPU
    slice). A 2-D topology makes every shuffle a two-hop exchange
    (parallel/topo.py): inner-axis all_to_all first, combined cross-group
    chunks over the outer axis second. Default None (flat, unchanged);
    env ``CYLON_TPU_MESH`` applies when the config leaves it unset;
    ``CYLON_TPU_NO_TOPO=1`` kills the decomposition at dispatch time.
    """

    def __init__(
        self,
        devices: Optional[Sequence[Any]] = None,
        axis_name: str = "dp",
        coordinator_address: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
        mesh_shape: Optional[str] = None,
    ):
        super().__init__()
        self.devices = devices
        self.axis_name = axis_name
        self.coordinator_address = coordinator_address
        self.num_processes = num_processes
        self.process_id = process_id
        self.mesh_shape = mesh_shape

    def comm_type(self) -> CommType:
        return CommType.TPU


# Alias used by tests / CPU runs; identical semantics, host devices.
class CPUConfig(TPUConfig):
    def comm_type(self) -> CommType:
        return CommType.CPU


# pycylon compatibility alias: reference users write MPIConfig(); here it maps
# onto the mesh-based backend (there is no MPI in the loop).
MPIConfig = TPUConfig
