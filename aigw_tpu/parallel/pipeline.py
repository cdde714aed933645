"""Pipeline parallelism: GPipe-style microbatched stage execution.

Layers are split into ``pp`` contiguous stages, one per device along the
``pp`` mesh axis; microbatches flow through the ring with
``lax.ppermute`` carrying activations stage→stage (ICI neighbor hops).
All devices run the same SPMD program for ``M + pp - 1`` steps; stage 0
injects embedded microbatches, the last stage collects logits.

Low priority for decode serving (SURVEY.md §2.9 — decode is latency-bound),
but first-class for prefill/batch scoring of models too deep for one
chip's HBM; this module is the ``pp`` leg of the mesh story (tp/ep/sp live
in sharding.py / ring_attention.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from aigw_tpu.models import llama
from aigw_tpu.models.llama import LlamaConfig

_STAGE_KEYS = (
    "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
    "w_gate", "w_up", "w_down",
)


def stack_stage_params(
    params: dict[str, jax.Array], cfg: LlamaConfig, pp: int
) -> dict[str, jax.Array]:
    """Flat per-layer dict → per-kind arrays [pp, layers_per_stage, ...]."""
    if cfg.n_layers % pp != 0:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp={pp}")
    lps = cfg.n_layers // pp
    out: dict[str, jax.Array] = {}
    for kind in _STAGE_KEYS:
        stacked = jnp.stack(
            [params[f"l{i}.{kind}"] for i in range(cfg.n_layers)]
        )
        out[kind] = stacked.reshape(pp, lps, *stacked.shape[1:])
    return out


def _stage_forward(stage, cfg: LlamaConfig, x, positions, mask):
    """Run this device's layer stack over activations x [mb, S, D]."""

    def layer(x, w):
        h = llama.rms_norm(x, w["attn_norm"], cfg.norm_eps)
        hd = cfg.head_dim
        B, S, _ = x.shape
        q = (h @ w["wq"]).reshape(B, S, cfg.n_heads, hd)
        k = (h @ w["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
        v = (h @ w["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
        q = llama.rope(q, positions, cfg.rope_theta)
        k = llama.rope(k, positions, cfg.rope_theta)
        x = x + llama._attention(q, k, v, mask) @ w["wo"]
        h = llama.rms_norm(x, w["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(h @ w["w_gate"])
        x = x + (gate * (h @ w["w_up"])) @ w["w_down"]
        return x, None

    x, _ = lax.scan(layer, x, stage)
    return x


@functools.partial(
    jax.jit, static_argnames=("cfg", "mesh", "pp", "microbatch")
)
def pipeline_logits(
    params: dict[str, jax.Array],
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] int32; B % microbatch == 0
    *,
    mesh: Mesh,
    pp: int,
    microbatch: int,
) -> jax.Array:
    """Full-context logits [B, S, V] computed through a pp-stage pipeline."""
    B, S = tokens.shape
    if B % microbatch != 0:
        raise ValueError(f"batch {B} not divisible by microbatch {microbatch}")
    M = B // microbatch
    stages = stack_stage_params(params, cfg, pp)
    embed, norm_f = params["embed"], params["norm_f"]
    head = params["lm_head"] if not cfg.tie_embeddings else params["embed"].T
    mb_tokens = tokens.reshape(M, microbatch, S)

    def local(stage, embed, norm_f, head, mb_tokens):
        # stage arrives as [1, lps, ...] (this device's shard)
        stage = jax.tree.map(lambda a: a[0], stage)
        s_idx = lax.axis_index("pp")
        n = lax.psum(1, "pp")
        positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(
            microbatch, 0
        )
        mask = (positions[:, :, None] >= positions[:, None, :])
        D = embed.shape[1]
        V = head.shape[1]

        def step(carry, t):
            received, outputs = carry
            # stage 0 injects microbatch t (or zeros past the end)
            inject = jnp.take(
                embed, mb_tokens[jnp.clip(t, 0, M - 1)], axis=0
            )
            x_in = jnp.where(s_idx == 0, inject, received)
            y = _stage_forward(stage, cfg, x_in, positions, mask)
            # last stage finalizes microbatch t - (n - 1)
            out_idx = t - (n - 1)
            final = llama.rms_norm(y, norm_f, cfg.norm_eps)
            logits = (final @ head).astype(jnp.float32)
            outputs = lax.cond(
                (s_idx == n - 1) & (out_idx >= 0),
                lambda o: lax.dynamic_update_index_in_dim(
                    o, logits, jnp.clip(out_idx, 0, M - 1), 0
                ),
                lambda o: o,
                outputs,
            )
            received = lax.ppermute(
                y, "pp", [(j, (j + 1) % n) for j in range(n)]
            )
            return (received, outputs), None

        # plain carries: ppermute makes them vary over the manual axis
        # mid-scan, so the shard_map call passes check_vma=False
        received0 = jnp.zeros((microbatch, S, D), embed.dtype)
        outputs0 = jnp.zeros((M, microbatch, S, V), jnp.float32)
        (_, outputs), _ = lax.scan(
            step, (received0, outputs0), jnp.arange(M + n - 1)
        )
        return outputs[None]  # [1, M, mb, S, V] — this stage's view

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P("pp"), stages),
            P(), P(), P(), P(),
        ),
        out_specs=P("pp"),
        check_vma=False,
    )
    out = fn(stages, embed, norm_f, head, mb_tokens)  # [pp, M, mb, S, V]
    # only the last stage's row holds real logits
    return out[-1].reshape(B, S, -1)
