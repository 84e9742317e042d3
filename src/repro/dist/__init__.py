"""Distributed execution subsystem: sharding, collectives, pipelining.

Three modules, one contract — the same model/step code runs unsharded on a
single CPU device and fully sharded on the (pod, data, model) production
meshes:

* :mod:`repro.dist.partition` — MaxText-style logical-axis sharding rules,
  ``shard``/``named_sharding``/``tree_shardings`` resolution, and the
  ``mesh_rules`` context that activates a mesh for a region of code.
* :mod:`repro.dist.collectives` — per-block symmetric int8 gradient
  compression and a compressed ``psum`` for bandwidth-bound reductions.
* :mod:`repro.dist.pipeline` — GPipe-style pipeline parallelism over a mesh
  axis (``pipeline_apply``) plus bubble accounting.
* :mod:`repro.dist.tp` — manual (shard_map) tensor parallelism for the
  serving path: explicit per-layer allreduce seams that can run the
  compressed collective, where GSPMD could only place exact psums.

:mod:`repro.dist.compat` is ``jax.shard_map`` under the one name the
repository calls.
"""

from repro.dist import collectives, partition, pipeline, tp
from repro.dist.compat import shard_map
from repro.dist.partition import (DEFAULT_RULES, mesh_rules, named_sharding,
                                  resolve_spec, shard, tree_shardings)

__all__ = [
    "collectives", "partition", "pipeline", "tp", "shard_map",
    "DEFAULT_RULES", "mesh_rules", "named_sharding", "resolve_spec",
    "shard", "tree_shardings",
]
