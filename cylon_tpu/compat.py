"""The JAX names this package uses that have moved between releases.

One installation is supported (the jax 0.9 line ``pyproject.toml`` names),
so nothing here branches on the version: these are the plain spellings,
kept in one module so that the next move is a one-file change.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map  # (f, mesh=, in_specs=, out_specs=, check_vma=)
enable_x64 = jax.enable_x64
distributed_is_initialized = jax.distributed.is_initialized


def pvary(x, axis_name: str):
    """Mark a replicated value as varying over the mesh axis, so a scan
    carry initialised from a constant types like the body's outputs under
    ``shard_map``'s varying-axes checker."""
    return jax.lax.pcast(x, (axis_name,), to="varying")
