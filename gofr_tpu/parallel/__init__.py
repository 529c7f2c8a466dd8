"""Mesh parallelism: dp/tp/sp/pp/ep over jax.sharding.Mesh.

The distributed backend the reference lacks (SURVEY.md §5 "Distributed
communication backend: ABSENT"): intra-slice parallelism is XLA collectives
over ICI generated by the compiler from sharding annotations — pick a mesh,
annotate, let XLA insert psum/all-gather/reduce-scatter/ppermute. Explicit
shard_map is used only where the schedule IS the algorithm (ring attention,
pipeline microbatching).
"""

from .longcontext import make_sp_forward, sp_llama_forward
from .mesh import MeshPlan, make_mesh
from .sharding import (batch_spec, kv_cache_layer_spec, kv_cache_spec,
                       llama_param_specs,
                       serving_param_specs, shard_params, unsharded_like)

__all__ = ["MeshPlan", "make_mesh", "batch_spec", "llama_param_specs",
           "serving_param_specs", "kv_cache_spec", "kv_cache_layer_spec", "shard_params",
           "unsharded_like", "sp_llama_forward", "make_sp_forward"]
