"""The one ``shard_map`` entry point for the distributed modules.

The repo targets the installed JAX (0.9), where ``shard_map`` lives at the
top level and replication checking is switched off with ``check_vma``.
Every caller imports from here so that choice lives in one place.
"""

from __future__ import annotations

from jax import shard_map


def shard_map_nocheck(f, *, mesh, in_specs, out_specs):
    """shard_map with replication (VMA) checking disabled."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


__all__ = ["shard_map", "shard_map_nocheck"]
