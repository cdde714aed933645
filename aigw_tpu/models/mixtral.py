"""Mixtral-family (sparse MoE) transformer, TPU-first.

Same GQA attention/paged-KV skeleton as the Llama family (the attention
internals are imported from models/llama.py — one implementation, two
families); the MLP is a top-2 mixture of experts with a fixed expert
capacity, in two forms that route alike.

A **sequence** (chunks, tails, batched and ragged prefill, the verify
step, ``hidden_states``) and any decode step **under a mesh** run it
GShard-style with **dispatch/combine einsums** (:func:`moe_mlp`):

    gate probs → top-k → position-in-expert (cumsum) → one-hot dispatch
    [T, E, C] → x_e = einsum(dispatch, x) → batched expert MLP over E →
    combine = einsum(dispatch·weights, y_e)

That form is static-shaped, so it compiles to einsums that the MXU
eats, and **expert parallelism is a sharding annotation**: expert
weights carry PartitionSpec("ep", ...) and GSPMD turns the dispatch /
combine einsums into all-to-alls over the ``ep`` mesh axis
(aigw_tpu/parallel/sharding.py::mixtral_param_specs). A chunk's tokens
hit every expert, so streaming all of them is what it needs.

A single-chip **decode step** (:func:`moe_step`) has a handful of live
rows, which pick a few of the experts: it routes over the live rows
alone and runs one trip of a loop for each expert one of them picked,
reading that expert's three matrices once, as stored. An expert nobody
live picked is never read.

Capacity overflow drops tokens from that expert (they keep their other
top-k expert + the residual path) — the standard trade for static
shapes. A decode step counts the fence over its live rows: what a dead
slot holds takes no place in any expert.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

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

    @property
    def decode_tape_width(self) -> int:
        """Columns of a DECODE step's routing-stats row: assignments
        placed on each expert, those the fence dropped, every
        assignment a live row made, and the experts that got at least
        one (the step's loop runs that many trips). The sequence
        programs keep ``n_experts + 1``, and with it their
        compile-cache keys."""
        return self.n_experts + 3

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


def _capacity(T: int, cfg: MixtralConfig) -> int:
    """Places an expert has for ``T`` routed rows: the fence."""
    C = max(cfg.experts_per_token, int(math.ceil(
        T * cfg.experts_per_token / cfg.n_experts * cfg.capacity_factor)))
    return min(C, T)


def _route(p: dict[str, jax.Array], i: int, xt: jax.Array,
           cfg: MixtralConfig, C: int, live: jax.Array | None = None):
    """Router of layer ``i`` over ``xt`` [T, D] → (``choice`` [T, K, E]
    one-hot, ``pos`` [T, K] the place of each (t, k) in its expert,
    ``keep`` [T, K] the capacity fence, ``weights`` [T, K]). With
    ``live`` [T] a row that is not live chooses nothing and takes no
    place."""
    T = xt.shape[0]
    E, K = cfg.n_experts, cfg.experts_per_token
    logits = (xt.astype(jnp.float32)
              @ p[f"l{i}.gate"].astype(jnp.float32))
    topv, topi = jax.lax.top_k(logits, K)  # [T, K]
    # normalize over chosen experts
    weights = jax.nn.softmax(topv, axis=-1)

    # one-hot expert choice per (token, k): [T, K, E]
    choice = jax.nn.one_hot(topi, E, dtype=jnp.float32)
    if live is not None:
        choice = choice * live.astype(jnp.float32)[:, None, None]
    # position of each (t, k) within its expert: cumulative count
    # over the flattened (t, k) order
    flat_choice = choice.reshape(T * K, E)
    pos = (jnp.cumsum(flat_choice, axis=0)
           - flat_choice).reshape(T, K, E)
    pos = jnp.sum(pos * choice, axis=-1).astype(jnp.int32)  # [T, K]
    keep = pos < C  # capacity fence
    return choice, pos, keep, weights


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
    C = _capacity(T, cfg)
    xt = x.reshape(T, D)

    with jax.named_scope("layer/moe_route"):
        choice, pos, keep, weights = _route(p, i, xt, cfg, C)
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


def _expert(p: dict[str, jax.Array], key: str, e: jax.Array) -> jax.Array:
    """Expert ``e``'s ``[in, out]`` matrix of the stacked ``[E, in, out]``
    weight ``key``, through what :func:`llama._w` does for one expert:
    each stored leaf (bf16, or int8 / int4 ``.q`` with its ``.scale``)
    is sliced where it lies and converted at the operand of the product
    that reads it, so HBM streams the slab's packed bytes once and
    nothing dequantised is held."""
    one = {k: lax.dynamic_index_in_dim(p[k], e, 0, keepdims=False)
           for k in (key, key + ".q", key + ".scale") if k in p}
    return llama._w(one, key)


def _layer_experts(p: dict[str, jax.Array], i: int) -> dict[str, jax.Array]:
    """Layer ``i``'s expert leaves as stored, under names without the
    layer: every layer hands :func:`_hit_experts` the same structure."""
    pre = f"l{i}."
    return {k[len(pre):]: p[k] for m in ("w_gate", "w_up", "w_down")
            for k in (pre + m, pre + m + ".q", pre + m + ".scale") if k in p}


@jax.jit
def _hit_experts(experts: dict[str, jax.Array], xt: jax.Array, w: jax.Array,
                 placed: jax.Array, n_hit: jax.Array) -> jax.Array:
    """The mixture of a decode step over the experts that got a kept
    assignment (``placed`` [E] > 0, ``n_hit`` of them): one loop trip
    each, adding ``((silu(x·Wg) * (x·Wu)) * w[:, e]) · Wd`` for all
    ``B`` rows, float32. An expert with nothing placed is never read.

    A function of its own under ``jit`` so that a program traces and
    lowers the loop ONCE and calls it from every layer (XLA inlines the
    calls): unrolled into each of eight layers it made a decode
    program 0.2 s slower to trace and lower than the dispatch form's
    (``PERF.md`` §5, PR 41), which every boot pays for every program."""
    B, D = xt.shape
    E = placed.shape[0]
    # the hit experts' ids, ascending, in the first n_hit places: a hit
    # expert's place is its rank among them. (A compare and a sum over
    # [E, E]; jnp.nonzero would put a scatter and its index arithmetic
    # into every decode program.)
    hit = placed > 0
    rank = jnp.cumsum(hit) - 1
    ids = jnp.arange(E, dtype=jnp.int32)
    ids = jnp.sum(jnp.where(hit & (rank == ids[:, None]), ids, 0), axis=1)

    def trip(j, acc):
        e = ids[j]
        h = (jax.nn.silu(xt @ _expert(experts, "w_gate", e))
             * (xt @ _expert(experts, "w_up", e)))
        h = h.astype(jnp.float32) * lax.dynamic_slice(w, (0, e), (B, 1))
        return acc + jnp.dot(h.astype(xt.dtype),
                             _expert(experts, "w_down", e),
                             preferred_element_type=jnp.float32)

    return lax.fori_loop(0, n_hit, trip, jnp.zeros((B, D), jnp.float32))


def moe_step(p: dict[str, jax.Array], i: int, x: jax.Array,
             cfg: MixtralConfig, active: jax.Array,
             tape: list | None = None) -> jax.Array:
    """The sparse MLP of a decode step. x: [B, 1, D] → [B, 1, D];
    ``active`` [B] marks the live rows.

    Routes as :func:`moe_mlp` does, with the capacity ``B`` rows give,
    but over the live rows alone: a dead slot's stale token picks
    nothing, takes no place in an expert and weighs nothing (no one
    reads its output), so no live row's output depends on what a dead
    slot holds. Then one loop trip for each expert a live row was
    placed on (:func:`_hit_experts`): the trip reads that expert's
    gate, up and down matrices once (:func:`_expert`) and adds its
    weighted output for all rows, float32. The trip count follows the
    input — no live row, no trip; every expert hit, ``E`` trips, which
    read what the dispatch form reads in 3 x E products where that
    makes three (about 6 % slower in this layer on a v5e at E = 8: the
    price of one path).

    ``tape`` gets one ``[decode_tape_width]`` int32 row a layer."""
    B, _, D = x.shape
    K = cfg.experts_per_token
    xt = x.reshape(B, D)

    with jax.named_scope("layer/moe_route"):
        choice, _, keep, weights = _route(p, i, xt, cfg, _capacity(B, cfg),
                                          live=active)
        # an assignment past the fence is dropped as the dispatch form
        # drops it: it weighs nothing and is not placed
        kept = choice * keep[..., None]
        w = jnp.einsum("tke,tk->te", kept, weights)  # [B, E]
        placed = jnp.sum(kept, axis=(0, 1)).astype(jnp.int32)  # [E]
        # ONE value: the tape's hit column and the loop's bound
        n_hit = jnp.sum(placed > 0).astype(jnp.int32)
        if tape is not None:
            routed = (jnp.sum(active) * K).astype(jnp.int32)
            tape.append(jnp.concatenate([
                placed, (routed - jnp.sum(placed))[None], routed[None],
                n_hit[None]]))

    with jax.named_scope("layer/moe_experts"):
        out = _hit_experts(_layer_experts(p, i), xt, w, placed, n_hit)
    return out.astype(x.dtype).reshape(B, 1, D)


def _mlp_fn(cfg: MixtralConfig, tape: list | None = None):
    return lambda p, i, x: moe_mlp(p, i, x, cfg, tape=tape)


def _as_decode_row(row: jax.Array) -> jax.Array:
    """A :func:`moe_mlp` tape row ``[E + 1]`` in a decode step's columns
    (a decode step under a mesh): every row the dense form routed, and
    the experts that got one."""
    placed, dropped = row[:-1], row[-1]
    return jnp.concatenate([
        row, (jnp.sum(placed) + dropped)[None],
        jnp.sum(placed > 0).astype(jnp.int32)[None]])


def _with_moe(out, tape):
    """(logits, kv) + a traced tape → (logits, kv, [L, width] stats)."""
    logits, kv_cache = out
    return logits, kv_cache, jnp.stack(tape)


# Every entry point of the llama skeleton is delegated with the MoE MLP
# plugged in — full feature parity (ragged prefill, chunked suffix
# resume, sequence-parallel prefill, decode, spec-decode verify),
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
    """One decode step. On one chip the MLP is :func:`moe_step` over the
    live rows; under a ``mesh`` the expert weights are sharded over
    ``ep`` and slicing one expert out would gather them all, so the
    step keeps the dispatch einsums (its tape rows widened to a decode
    step's columns)."""
    tape: list | None = [] if moe_stats else None
    if mesh is None:
        def mlp(p, i, x):
            return moe_step(p, i, x, cfg, active, tape=tape)
    else:
        mlp = _mlp_fn(cfg, tape)
    out = llama.decode_step(p, cfg.as_llama(), tokens, positions, kv_cache,
                            page_table, page_size, active, mlp=mlp,
                            attn_impl=attn_impl, mesh=mesh, walk=walk)
    if moe_stats and mesh is not None:
        tape = [_as_decode_row(row) for row in tape]
    return _with_moe(out, tape) if moe_stats else out


def hidden_states(p, cfg: MixtralConfig, tokens, seq_lens):
    return llama.hidden_states(p, cfg.as_llama(), tokens, seq_lens,
                               mlp=_mlp_fn(cfg))


def verify_step(p, cfg: MixtralConfig, tokens, positions, kv_cache,
                page_table, page_size, active, limits,
                lora=None, adapter_idx=None, moe_stats=False):
    tape: list | None = [] if moe_stats else None
    out = llama.verify_step(p, cfg.as_llama(), tokens, positions, kv_cache,
                            page_table, page_size, active, limits,
                            mlp=_mlp_fn(cfg, tape))
    return _with_moe(out, tape) if moe_stats else out
