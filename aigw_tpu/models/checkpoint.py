"""Checkpoint save/load/import.

The serving engine's only real state is model weights (SURVEY.md §5:
"tpuserve adds real state — model weights load (orbax-style sharded
checkpoint read), KV-cache is ephemeral"). Orbax handles sharded
save/restore; ``import_hf_checkpoint`` converts local HuggingFace
safetensors (Llama/Mixtral layouts) into our flat parameter dict — no
network involved.
"""

from __future__ import annotations

import logging
import os
import re
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)


def save_checkpoint(params: dict[str, jax.Array], path: str) -> None:
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, params)
    ckptr.wait_until_finished()
    logger.info("saved checkpoint to %s", path)


def restore_checkpoint(
    path: str, like: dict[str, jax.Array] | None = None,
    sharding_of=None,
) -> dict[str, jax.Array]:
    """Restore a flat param dict. ``like`` gives shapes and dtypes;
    ``sharding_of(name, shape)`` (parallel.sharding.param_sharding_fn)
    makes orbax restore each tensor directly into its mesh sharding —
    a tp model larger than one chip never lands whole on one."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ckptr = ocp.StandardCheckpointer()
    if like is not None:
        shapes = {
            k: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=(sharding_of(k, x.shape)
                          if sharding_of is not None else None))
            for k, x in like.items()
        }
        return ckptr.restore(path, shapes)
    return ckptr.restore(path)


#: HF tensor name → our flat name (Llama/Mistral layout). Projections are
#: stored [out, in] in HF and transposed to our [in, out] convention.
_HF_MAP = [
    (re.compile(r"^model\.embed_tokens\.weight$"), "embed", False),
    (re.compile(r"^model\.norm\.weight$"), "norm_f", False),
    (re.compile(r"^lm_head\.weight$"), "lm_head", True),
    (re.compile(r"^model\.layers\.(\d+)\.input_layernorm\.weight$"),
     "l{}.attn_norm", False),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.q_proj\.weight$"),
     "l{}.wq", True),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.k_proj\.weight$"),
     "l{}.wk", True),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.v_proj\.weight$"),
     "l{}.wv", True),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.o_proj\.weight$"),
     "l{}.wo", True),
    # Qwen2 QKV biases
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.q_proj\.bias$"),
     "l{}.bq", False),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.k_proj\.bias$"),
     "l{}.bk", False),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.v_proj\.bias$"),
     "l{}.bv", False),
    (re.compile(r"^model\.layers\.(\d+)\.post_attention_layernorm\.weight$"),
     "l{}.mlp_norm", False),
    (re.compile(r"^model\.layers\.(\d+)\.mlp\.gate_proj\.weight$"),
     "l{}.w_gate", True),
    (re.compile(r"^model\.layers\.(\d+)\.mlp\.up_proj\.weight$"),
     "l{}.w_up", True),
    (re.compile(r"^model\.layers\.(\d+)\.mlp\.down_proj\.weight$"),
     "l{}.w_down", True),
    # Mixtral MoE layout: experts are stacked into [E, ...] after loading
    (re.compile(r"^model\.layers\.(\d+)\.block_sparse_moe\.gate\.weight$"),
     "l{}.gate", True),
    (re.compile(
        r"^model\.layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.w1\.weight$"),
     "l{}.w_gate.__expert{}", True),
    (re.compile(
        r"^model\.layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.w3\.weight$"),
     "l{}.w_up.__expert{}", True),
    (re.compile(
        r"^model\.layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.w2\.weight$"),
     "l{}.w_down.__expert{}", True),
]


def import_hf_checkpoint(
    hf_dir: str, dtype: Any = jnp.bfloat16
) -> dict[str, jax.Array]:
    """Read local ``*.safetensors`` shards (Llama layout) → flat params."""
    from safetensors import safe_open

    files = sorted(
        os.path.join(hf_dir, f)
        for f in os.listdir(hf_dir)
        if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {hf_dir}")
    params: dict[str, jax.Array] = {}
    unmapped: list[str] = []
    for path in files:
        with safe_open(path, framework="np") as f:
            for name in f.keys():
                target = None
                transpose = False
                for pattern, fmt, tr in _HF_MAP:
                    m = pattern.match(name)
                    if m:
                        target = fmt.format(*m.groups())
                        transpose = tr
                        break
                if target is None:
                    unmapped.append(name)
                    continue
                arr = f.get_tensor(name)
                if transpose:
                    arr = arr.T
                params[target] = jnp.asarray(
                    np.ascontiguousarray(arr)
                ).astype(dtype)
    if unmapped:
        logger.warning("unmapped HF tensors ignored: %s", unmapped[:8])
    return _stack_experts(params)


def _stack_experts(params: dict[str, jax.Array]) -> dict[str, jax.Array]:
    """Collapse `l{i}.w_*.{__expertE}` staging keys into [E, ...] arrays
    (Mixtral's per-expert HF tensors → our stacked MoE layout)."""
    staged: dict[str, dict[int, jax.Array]] = {}
    out: dict[str, jax.Array] = {}
    for k, v in params.items():
        if ".__expert" in k:
            base, _, e = k.partition(".__expert")
            staged.setdefault(base, {})[int(e)] = v
        else:
            out[k] = v
    for base, experts in staged.items():
        out[base] = jnp.stack([experts[e] for e in sorted(experts)])
    return out
