"""shard_map wrapper used framework-wide.

Replication checking is disabled (``check_vma=False``) because Pallas
calls inside shard_map bodies cannot declare varying-mesh-axes on their
ShapeDtypeStruct outputs; the collectives and model layers are written
rank-centric and manage replication explicitly.
"""
from __future__ import annotations

import jax

__all__ = ["shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
