"""Device-mesh construction for the CEP engine.

One logical axis, ``shards``: the key-partition axis (the analog of Flink
operator parallelism + key routing, SURVEY.md §2.7-(1)(2)). Every shard holds
the full compiled plan; events are routed to shards by group-key hash; state
lives shard-local. Collectives are only needed for re-keying between plans
with incompatible partitions (all-to-all) and for gathering outputs — both
ride ICI when the mesh spans real chips.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

SHARD_AXIS = "shards"


def make_cep_mesh(
    n_shards: Optional[int] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """A 1-D mesh over ``n_shards`` devices (default: all local devices)."""
    if devices is None:
        devices = jax.devices()
    if n_shards is None:
        n_shards = len(devices)
    if n_shards > len(devices):
        raise ValueError(
            f"requested {n_shards} shards but only {len(devices)} devices"
        )
    # Auto axis: the engine shards by NamedSharding + shard_map and
    # never types an array's sharding (jax.make_mesh's own default axis
    # type has changed between releases, so it is stated)
    return jax.make_mesh(
        (n_shards,), (SHARD_AXIS,), axis_types=(AxisType.Auto,),
        devices=devices[:n_shards],
    )
