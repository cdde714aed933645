"""Device mesh, shardings, and collectives.

The reference's distributed story is NCCL-free (SURVEY.md §2.9/§5: its
inter-component comms are gRPC/xDS); ours is the TPU-native equivalent —
intra-model collectives are XLA ops emitted by GSPMD from ``jax.sharding``
annotations over an ICI mesh; cross-host coordination is ``jax.distributed``
over DCN; the gateway↔tpuserve boundary stays HTTP exactly like the
reference's Envoy↔vLLM boundary.
"""

from aigw_tpu.parallel.mesh import MeshSpec, make_mesh
from aigw_tpu.parallel.sharding import (
    analytical_ici_bytes_per_token,
    kv_cache_spec,
    llama_param_specs,
    mixtral_param_specs,
    param_sharding_fn,
    shard_params,
)

__all__ = [
    "MeshSpec",
    "analytical_ici_bytes_per_token",
    "kv_cache_spec",
    "llama_param_specs",
    "mixtral_param_specs",
    "make_mesh",
    "param_sharding_fn",
    "shard_params",
]
