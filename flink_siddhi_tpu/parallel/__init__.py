"""Multi-device parallelism: mesh, routing, sharded execution.

The TPU re-expression of the reference's parallelism surface (SURVEY.md §2.7):

* Flink operator parallelism (N subtasks, each a full plan copy,
  AbstractSiddhiOperator.java:301-313)  ->  a ``jax.sharding.Mesh`` axis; the
  plan state is stacked per shard and advanced by ONE ``shard_map``-ed step.
* key/group-by partitioning (AddRouteOperator.java:79-92 summed-hash key +
  HashPartitioner.java:22-27 modulo)   ->  host-side vectorized hash routing
  into per-shard tapes (router.py).
* broadcast partitioning for control events (DynamicPartitioner.java:46-52)
  ->  control plane applied identically on every shard's state.
* random/shuffle partitioning (partitionKey -1, DynamicPartitioner.java:53-55)
  ->  round-robin routing.

Cross-shard communication rides XLA collectives over ICI when shards map to
real TPU chips; on one chip the same program runs with a 1-device mesh.
"""

from .mesh import make_cep_mesh, SHARD_AXIS
from .router import Router
from .sharded import ShardedJob

__all__ = [
    "make_cep_mesh",
    "SHARD_AXIS",
    "Router",
    "ShardedJob",
]
