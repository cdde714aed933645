"""Mixtral-family (sparse MoE) transformer, TPU-first.

Same GQA attention/paged-KV skeleton as the Llama family (the attention
internals are imported from models/llama.py — one implementation, two
families); the MLP is a top-2 mixture of experts implemented GShard-style
with **dispatch/combine einsums** and a fixed expert capacity:

    gate probs → top-k → position-in-expert (cumsum) → one-hot dispatch
    [T, E, C] → x_e = einsum(dispatch, x) → batched expert MLP over E →
    combine = einsum(dispatch·weights, y_e)

Everything is static-shaped, so the whole MoE compiles to einsums that the
MXU eats, and **expert parallelism is a sharding annotation**: expert
weights carry PartitionSpec("ep", ...) and GSPMD turns the dispatch /
combine einsums into all-to-alls over the ``ep`` mesh axis
(aigw_tpu/parallel/sharding.py::mixtral_param_specs).

Capacity overflow drops tokens from that expert (they keep their other
top-k expert + the residual path) — the standard trade for static shapes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from aigw_tpu.models import llama
from aigw_tpu.models.llama import LlamaConfig


@dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    n_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 2.0
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_seq_len: int = 32768

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def as_llama(self) -> LlamaConfig:
        """The attention-relevant view consumed by the shared skeleton."""
        return LlamaConfig(
            vocab_size=self.vocab_size,
            dim=self.dim,
            n_layers=self.n_layers,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            ffn_dim=self.ffn_dim,
            rope_theta=self.rope_theta,
            norm_eps=self.norm_eps,
            max_seq_len=self.max_seq_len,
        )


MIXTRAL_8X7B = MixtralConfig()
TINY_MOE = MixtralConfig(
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, n_experts=4, experts_per_token=2, max_seq_len=512,
    rope_theta=10000.0,
)


def init_params(key: jax.Array, cfg: MixtralConfig, dtype=jnp.bfloat16,
                sharding_of=None, finish=None) -> dict[str, jax.Array]:
    """Random-init weights; the placement hooks are
    :class:`llama.ParamBuilder`'s."""
    b = llama.ParamBuilder(key, 4 + cfg.n_layers * 8, dtype, sharding_of,
                           finish)
    b.dense("embed", (cfg.vocab_size, cfg.dim), scale=0.02)
    b.const("norm_f", (cfg.dim,), 1.0)
    b.dense("lm_head", (cfg.dim, cfg.vocab_size))
    hd = cfg.head_dim
    E = cfg.n_experts
    for i in range(cfg.n_layers):
        b.const(f"l{i}.attn_norm", (cfg.dim,), 1.0)
        b.dense(f"l{i}.wq", (cfg.dim, cfg.n_heads * hd))
        b.dense(f"l{i}.wk", (cfg.dim, cfg.n_kv_heads * hd))
        b.dense(f"l{i}.wv", (cfg.dim, cfg.n_kv_heads * hd))
        b.dense(f"l{i}.wo", (cfg.n_heads * hd, cfg.dim))
        b.const(f"l{i}.mlp_norm", (cfg.dim,), 1.0)
        b.dense(f"l{i}.gate", (cfg.dim, E))
        b.dense(f"l{i}.w_gate", (E, cfg.dim, cfg.ffn_dim))
        b.dense(f"l{i}.w_up", (E, cfg.dim, cfg.ffn_dim))
        b.dense(f"l{i}.w_down", (E, cfg.ffn_dim, cfg.dim))
    return b.params


def moe_mlp(p: dict[str, jax.Array], i: int, x: jax.Array,
            cfg: MixtralConfig, tape: list | None = None) -> jax.Array:
    """Top-k sparse MLP over flattened tokens. x: [B, S, D] → [B, S, D].

    ``tape`` is a trace-time accumulator: when a list is passed, each
    layer appends one ``[E + 1]`` int32 vector — per-expert placed
    (token, k) assignments followed by the count the capacity fence
    dropped — which the family entry points stack into the ``[L, E+1]``
    routing-stats leaf behind their ``moe_stats`` kwarg. Counts are
    over every row the program processed, padding included: they are
    truthful to device compute, not to prompt text."""
    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.experts_per_token
    C = max(K, int(math.ceil(T * K / E * cfg.capacity_factor)))
    C = min(C, T)
    xt = x.reshape(T, D)

    with jax.named_scope("layer/moe_route"):
        logits = (xt.astype(jnp.float32)
                  @ p[f"l{i}.gate"].astype(jnp.float32))
        topv, topi = jax.lax.top_k(logits, K)  # [T, K]
        # normalize over chosen experts
        weights = jax.nn.softmax(topv, axis=-1)

        # one-hot expert choice per (token, k): [T, K, E]
        choice = jax.nn.one_hot(topi, E, dtype=jnp.float32)
        # position of each (t, k) within its expert: cumulative count
        # over the flattened (t, k) order
        flat_choice = choice.reshape(T * K, E)
        pos = (jnp.cumsum(flat_choice, axis=0)
               - flat_choice).reshape(T, K, E)
        pos = jnp.sum(pos * choice, axis=-1).astype(jnp.int32)  # [T, K]
        keep = pos < C  # capacity fence
        pos_oh = (jax.nn.one_hot(pos, C, dtype=jnp.float32)
                  * keep[..., None])
        # dispatch [T, E, C]
        dispatch = jnp.einsum("tke,tkc->tec", choice, pos_oh)
        combine = jnp.einsum("tke,tkc,tk->tec", choice, pos_oh, weights)
        if tape is not None:
            # [E]
            placed = jnp.sum(dispatch, axis=(0, 2)).astype(jnp.int32)
            dropped = jnp.sum(~keep).astype(jnp.int32)
            tape.append(jnp.concatenate([placed, dropped[None]]))

    with jax.named_scope("layer/moe_experts"):
        xe = jnp.einsum("tec,td->ecd", dispatch, xt.astype(jnp.float32))
        xe = xe.astype(x.dtype)
        # expert weights resolve through llama._w so W8A16/W4A16 params
        # ([E, in, out] int8 per-channel / int4 group scales) dequantize at
        # the einsum operand — XLA fuses it; HBM streams the packed bytes
        gate = jax.nn.silu(jnp.einsum(
            "ecd,edf->ecf", xe, llama._w(p, f"l{i}.w_gate")))
        up = jnp.einsum("ecd,edf->ecf", xe, llama._w(p, f"l{i}.w_up"))
        ye = jnp.einsum("ecf,efd->ecd", gate * up,
                        llama._w(p, f"l{i}.w_down"))
        out = jnp.einsum("tec,ecd->td", combine, ye.astype(jnp.float32))
    return out.astype(x.dtype).reshape(B, S, D)


def _mlp_fn(cfg: MixtralConfig, tape: list | None = None):
    return lambda p, i, x: moe_mlp(p, i, x, cfg, tape=tape)


def _with_moe(out, tape):
    """(logits, kv) + a traced tape → (logits, kv, [L, E+1] stats)."""
    logits, kv_cache = out
    return logits, kv_cache, jnp.stack(tape)


# Every entry point of the llama skeleton is delegated with the MoE MLP
# plugged in — full feature parity (ragged prefill, chunked suffix
# resume, sequence-parallel prefill, fused decode, spec-decode verify),
# no family rows left in the fallback matrices. The static ``moe_stats``
# kwarg turns on the routing-stats leaf: the engine jits its programs
# with moe_stats=True for MoE families, so per-expert load and
# capacity drops ride the results it already fetches — no extra
# device→host sync. LoRA is llama-family-only for now; the args are
# accepted for interface parity.


def prefill(p, cfg: MixtralConfig, tokens, seq_lens, kv_cache, page_table,
            page_size, lora=None, adapter_idx=None, moe_stats=False):
    tape: list | None = [] if moe_stats else None
    out = llama.prefill(p, cfg.as_llama(), tokens, seq_lens, kv_cache,
                        page_table, page_size, mlp=_mlp_fn(cfg, tape))
    return _with_moe(out, tape) if moe_stats else out


def prefill_suffix(p, cfg: MixtralConfig, tokens, prefix_lens, seq_lens,
                   kv_cache, page_table, page_size, lora=None,
                   adapter_idx=None, moe_stats=False):
    tape: list | None = [] if moe_stats else None
    out = llama.prefill_suffix(p, cfg.as_llama(), tokens, prefix_lens,
                               seq_lens, kv_cache, page_table, page_size,
                               mlp=_mlp_fn(cfg, tape))
    return _with_moe(out, tape) if moe_stats else out


def prefill_sp(p, cfg: MixtralConfig, tokens, seq_lens, kv_cache,
               page_table, page_size, *, mesh, strategy="ring", lora=None,
               adapter_idx=None, moe_stats=False):
    tape: list | None = [] if moe_stats else None
    out = llama.prefill_sp(p, cfg.as_llama(), tokens, seq_lens, kv_cache,
                           page_table, page_size, mesh=mesh,
                           strategy=strategy, mlp=_mlp_fn(cfg, tape))
    return _with_moe(out, tape) if moe_stats else out


def prefill_sp_suffix(p, cfg: MixtralConfig, tokens, prefix_lens, seq_lens,
                      kv_cache, page_table, page_size, *, mesh, lora=None,
                      adapter_idx=None, moe_stats=False):
    tape: list | None = [] if moe_stats else None
    out = llama.prefill_sp_suffix(p, cfg.as_llama(), tokens, prefix_lens,
                                  seq_lens, kv_cache, page_table,
                                  page_size, mesh=mesh,
                                  mlp=_mlp_fn(cfg, tape))
    return _with_moe(out, tape) if moe_stats else out


def prefill_ragged(p, cfg: MixtralConfig, tokens, row_seq, positions,
                   last_rows, kv_cache, page_table, page_size, *,
                   attn_impl="", lora=None, adapter_idx=None,
                   moe_stats=False):
    # the packed [T, 1, D] token stream reuses the per-token rope/matmul
    # helpers; the dispatch/combine einsums are shape-agnostic over the
    # flattened token axis, so MoE rides the ragged stream unchanged
    tape: list | None = [] if moe_stats else None
    out = llama.prefill_ragged(p, cfg.as_llama(), tokens, row_seq,
                               positions, last_rows, kv_cache, page_table,
                               page_size, attn_impl=attn_impl,
                               mlp=_mlp_fn(cfg, tape))
    return _with_moe(out, tape) if moe_stats else out


def decode_step(p, cfg: MixtralConfig, tokens, positions, kv_cache,
                page_table, page_size, active, lora=None, adapter_idx=None,
                attn_impl="", mesh=None, walk=None, moe_stats=False):
    tape: list | None = [] if moe_stats else None
    out = llama.decode_step(p, cfg.as_llama(), tokens, positions, kv_cache,
                            page_table, page_size, active,
                            mlp=_mlp_fn(cfg, tape), attn_impl=attn_impl,
                            mesh=mesh, walk=walk)
    return _with_moe(out, tape) if moe_stats else out


def hidden_states(p, cfg: MixtralConfig, tokens, seq_lens):
    return llama.hidden_states(p, cfg.as_llama(), tokens, seq_lens,
                               mlp=_mlp_fn(cfg))


def verify_step(p, cfg: MixtralConfig, tokens, positions, kv_cache,
                page_table, page_size, active, limits,
                lora=None, adapter_idx=None, attn_impl="",
                moe_stats=False):
    tape: list | None = [] if moe_stats else None
    out = llama.verify_step(p, cfg.as_llama(), tokens, positions, kv_cache,
                            page_table, page_size, active, limits,
                            mlp=_mlp_fn(cfg, tape), attn_impl=attn_impl)
    return _with_moe(out, tape) if moe_stats else out
