"""Parallelism: device meshes, sharding rules, collectives.

The reference has NO device-collective layer (SURVEY §2.6: its "distributed" is
service-level gRPC). This package is the first-class addition the TPU build
requires: jax.sharding.Mesh over ICI/DCN, GSPMD param/cache shardings for
tensor-parallel inference, data-parallel request fan-out, and ring-attention
sequence parallelism for long context.
"""

from .mesh import MeshConfig, build_mesh, local_device_count
from .sharding import (input_shardings, llama_cache_sharding,
                       llama_page_pool_sharding, llama_param_shardings,
                       replicated, shard_llama_params)

__all__ = [
    "MeshConfig",
    "build_mesh",
    "input_shardings",
    "llama_cache_sharding",
    "llama_page_pool_sharding",
    "llama_param_shardings",
    "local_device_count",
    "replicated",
    "shard_llama_params",
]
