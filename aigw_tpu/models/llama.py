"""Llama-family transformer as a pure functional JAX program.

TPU-first design decisions (not a port of any torch implementation):

- **bfloat16 everywhere** except RMSNorm accumulation and attention
  softmax, which run in float32 — keeps the MXU fed while preserving
  numerics (pallas_guide.md tiling: bf16 tiles are (16, 128)).
- **Static shapes**: prefill is bucketed by padded sequence length, decode
  is a fixed [max_batch, 1] step — each shape compiles exactly once.
- **Paged KV cache**: the cache is a flat page pool
  ``[L, 2, n_pages * page_size, n_kv_heads, head_dim]``; sequences own
  pages via an int32 page table. Flattening pages makes cache writes one
  scatter and cache reads one gather — both XLA-native ops that fuse well,
  and the same layout the Pallas ragged-prefill kernel consumes
  (PAPERS.md: Ragged Paged Attention for TPU).
- **GQA**: K/V heads are kept un-repeated in the cache (HBM bandwidth is
  the bottleneck); Q heads are grouped over KV heads inside attention.

Weight layout is a flat dict pytree so `jax.sharding` partition specs can
be assigned per-leaf by name (aigw_tpu/parallel/sharding.py).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from aigw_tpu.models import kvq
from aigw_tpu.models.lora import lora_delta


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    # QKV projection bias (the Qwen2 family uses it; Llama doesn't)
    attn_bias: bool = False

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


# Published Llama-3 architecture shapes (public model cards).
LLAMA3_8B = LlamaConfig()
LLAMA3_70B = LlamaConfig(
    dim=8192, n_layers=80, n_heads=64, n_kv_heads=8, ffn_dim=28672
)
# Qwen2 family: Llama skeleton + QKV bias (+ tied embeddings on small
# sizes). Published architecture shapes.
QWEN2_7B = LlamaConfig(
    vocab_size=152064, dim=3584, n_layers=28, n_heads=28, n_kv_heads=4,
    ffn_dim=18944, rope_theta=1e6, max_seq_len=32768, attn_bias=True,
)
QWEN2_05B = LlamaConfig(
    vocab_size=151936, dim=896, n_layers=24, n_heads=14, n_kv_heads=2,
    ffn_dim=4864, rope_theta=1e6, max_seq_len=32768, attn_bias=True,
    tie_embeddings=True,
)

#: Tiny config for tests / CPU fake-chip mode (reference's testupstream role)
TINY = LlamaConfig(
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, max_seq_len=512, rope_theta=10000.0,
)


@functools.lru_cache(maxsize=None)
def _tensor_maker(kind: str, shape: tuple, scale: float, dtype: Any,
                  sharding: Any):
    """One jitted program per (kind, shape, placement): the f32 draw,
    the scale and the cast fuse, and ``out_shardings`` makes each
    device create only its own shard — a tensor never exists whole on
    one device, nor at f32 width in HBM."""
    if kind == "normal":
        def make(key):
            return (jax.random.normal(key, shape, jnp.float32)
                    * scale).astype(dtype)
    else:
        def make(key):
            return jnp.full(shape, scale, dtype)
    return jax.jit(make, out_shardings=sharding)


class ParamBuilder:
    """Creates a family's random weights ONE TENSOR AT A TIME.

    ``sharding_of(name, shape)`` places each tensor as it is created
    (a tp mesh never funnels the model through one chip);
    ``finish(name, w) -> {leaf: array}`` consumes it on the spot (the
    server quantizes there, so a 15 GB bf16 model is never resident on
    a 16 GB chip — at most one bf16 tensor is alive beside the int8
    leaves). Shared by every family's ``init_params``."""

    def __init__(self, key: jax.Array, n_keys: int, dtype: Any,
                 sharding_of=None, finish=None):
        self._keys = iter(jax.random.split(key, n_keys))
        self._dtype = dtype
        self._sharding_of = sharding_of
        self._finish = finish
        self.params: dict[str, jax.Array] = {}

    def _put(self, kind: str, name: str, shape: tuple, scale: float,
             key) -> None:
        sharding = (self._sharding_of(name, shape)
                    if self._sharding_of is not None else None)
        w = _tensor_maker(kind, tuple(shape), float(scale), self._dtype,
                          sharding)(key)
        if self._finish is None:
            self.params[name] = w
        else:
            self.params.update(self._finish(name, w))

    def dense(self, name: str, shape: tuple, scale: float = 0.0) -> None:
        """N(0, scale²) draw; default scale 1/sqrt(fan_in), fan_in =
        the second-to-last axis ([in, out] and [E, in, out])."""
        scale = scale or 1.0 / math.sqrt(shape[-2])
        self._put("normal", name, shape, scale, next(self._keys))

    def const(self, name: str, shape: tuple, value: float) -> None:
        self._put("const", name, shape, value, None)


def init_params(
    key: jax.Array, cfg: LlamaConfig, dtype: Any = jnp.bfloat16,
    sharding_of=None, finish=None,
) -> dict[str, jax.Array]:
    """Random-init weights (testing / ``--weights random`` serving);
    see :class:`ParamBuilder` for the two placement hooks."""
    b = ParamBuilder(key, 4 + cfg.n_layers * 9, dtype, sharding_of,
                     finish)
    b.dense("embed", (cfg.vocab_size, cfg.dim), scale=0.02)
    b.const("norm_f", (cfg.dim,), 1.0)
    if not cfg.tie_embeddings:
        b.dense("lm_head", (cfg.dim, cfg.vocab_size))
    hd = cfg.head_dim
    for i in range(cfg.n_layers):
        b.const(f"l{i}.attn_norm", (cfg.dim,), 1.0)
        b.dense(f"l{i}.wq", (cfg.dim, cfg.n_heads * hd))
        b.dense(f"l{i}.wk", (cfg.dim, cfg.n_kv_heads * hd))
        b.dense(f"l{i}.wv", (cfg.dim, cfg.n_kv_heads * hd))
        if cfg.attn_bias:
            b.const(f"l{i}.bq", (cfg.n_heads * hd,), 0.0)
            b.const(f"l{i}.bk", (cfg.n_kv_heads * hd,), 0.0)
            b.const(f"l{i}.bv", (cfg.n_kv_heads * hd,), 0.0)
        b.dense(f"l{i}.wo", (cfg.n_heads * hd, cfg.dim))
        b.const(f"l{i}.mlp_norm", (cfg.dim,), 1.0)
        b.dense(f"l{i}.w_gate", (cfg.dim, cfg.ffn_dim))
        b.dense(f"l{i}.w_up", (cfg.dim, cfg.ffn_dim))
        b.dense(f"l{i}.w_down", (cfg.ffn_dim, cfg.dim))
    return b.params


def _w(p: dict[str, jax.Array], key: str) -> jax.Array:
    """Resolve a weight that may be stored bf16, int8+per-channel scale
    (W8A16), or int4+group scale (W4A16) — self-describing on q.dtype
    (models/quant.py). The convert-and-scale sits on the matmul operand
    so XLA fuses it; HBM traffic is the packed int8/int4 bytes."""
    q = p.get(key + ".q")
    if q is None:
        return p[key]
    scale = p[key + ".scale"]
    if q.dtype == jnp.int4:
        # group-wise scales along the input axis: scale [..., in/G, out]
        *lead, n_in, n_out = q.shape
        groups = scale.shape[-2]
        wf = q.astype(jnp.bfloat16).reshape(
            *lead, groups, n_in // groups, n_out)
        wf = wf * scale.astype(jnp.bfloat16)[..., :, None, :]
        return wf.reshape(*lead, n_in, n_out)
    return q.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16)


@jax.named_scope("embed")
def _embed_rows(p: dict[str, jax.Array], tokens: jax.Array) -> jax.Array:
    q = p.get("embed.q")
    if q is None:
        return jnp.take(p["embed"], tokens, axis=0)
    rows = jnp.take(q, tokens, axis=0).astype(jnp.bfloat16)
    scales = jnp.take(p["embed.scale"][:, 0], tokens, axis=0)
    return rows * scales[..., None].astype(jnp.bfloat16)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * w


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embeddings. x: [..., S, H, D], positions broadcastable [..., S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = (
        positions.astype(jnp.float32)[..., :, None, None] * freqs[None, None, :]
    )  # [..., S, 1, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., ::2], x[..., 1::2]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = xf1 * cos - xf2 * sin
    o2 = xf2 * cos + xf1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


@jax.named_scope("layer/attn")
def _attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,  # [B, T, Hkv, D]
    mask: jax.Array,  # [B, S, T] bool, True = attend
) -> jax.Array:
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = q.reshape(B, S, Hkv, group, D)
    logits = jnp.einsum(
        "bshgd,bthd->bhgst", qg, k, preferred_element_type=jnp.float32
    )
    logits = logits / math.sqrt(D)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs.astype(v.dtype), v)
    return out.reshape(B, S, H * D)


def _matmul(p: dict[str, jax.Array], key: str, x: jax.Array) -> jax.Array:
    """``x @ weight`` with the W8A16 Pallas fast path.

    For quantized weights at decode shapes (small M, aligned K/N) the
    fused kernel streams int8 and applies the scale to the accumulator
    (ops/pallas/qmatmul.py); other shapes — prefill, unaligned, or
    AIGW_PALLAS_QMATMUL=off — fall back to dequant-then-matmul via
    ``_w`` (XLA fuses the dequant as the matmul's producer)."""
    q = p.get(key + ".q")
    if q is None or q.dtype != jnp.int8 or os.environ.get(
            "AIGW_PALLAS_QMATMUL", "on").lower() in ("0", "false", "off"):
        # int4 carries GROUP-wise scales the per-column W8A16 kernel
        # would silently misapply — int4 always dequants via _w
        return x @ _w(p, key)
    from aigw_tpu.ops.pallas import qmatmul

    lead, k = x.shape[:-1], x.shape[-1]
    m = math.prod(lead)
    n = q.shape[-1]
    if not qmatmul.supported(m, k, n):
        return x @ _w(p, key)
    y = qmatmul.w8a16_matmul(x.reshape(m, k), q, p[key + ".scale"])
    return y.reshape(*lead, n)


@jax.named_scope("layer/kv_gather")
def _gather_kv(kv_cache, i, page_table, page_size):
    """Layer i's K/V window ``[B, P*page, Hkv, D]`` read out of the
    page pool a whole page at a time (``kvq.window_kv``; dequantizing
    when the pool is int8/int4), under a scope of its own: in a trace
    it is what a chunk, a tail or a verify program pays to see what
    its rows have cached."""
    return kvq.window_kv(kv_cache, i, page_table, page_size)


@jax.named_scope("layer/attn")
def _wo_project(p, i, attn, lora=None, adapter_idx=None):
    """Attention out-projection with optional per-slot LoRA delta."""
    out = _matmul(p, f"l{i}.wo", attn)
    d = lora_delta(lora, f"l{i}.wo", attn, adapter_idx)
    return out if d is None else out + d


@jax.named_scope("layer/attn")
def _project_qkv(p, i, x, positions, cfg, lora=None, adapter_idx=None):
    hd = cfg.head_dim
    B, S, _ = x.shape
    q = _matmul(p, f"l{i}.wq", x)
    k = _matmul(p, f"l{i}.wk", x)
    v = _matmul(p, f"l{i}.wv", x)
    for name, ref in (("wq", "q"), ("wk", "k"), ("wv", "v")):
        d = lora_delta(lora, f"l{i}.{name}", x, adapter_idx)
        if d is not None:
            if ref == "q":
                q = q + d
            elif ref == "k":
                k = k + d
            else:
                v = v + d
    if cfg.attn_bias:
        q, k, v = q + p[f"l{i}.bq"], k + p[f"l{i}.bk"], v + p[f"l{i}.bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


@jax.named_scope("layer/mlp")
def _mlp(p, i, x, lora=None, adapter_idx=None):
    def with_delta(y, name, inp):
        d = lora_delta(lora, f"l{i}.{name}", inp, adapter_idx)
        return y if d is None else y + d

    gate = jax.nn.silu(with_delta(_matmul(p, f"l{i}.w_gate", x),
                                  "w_gate", x))
    up = with_delta(_matmul(p, f"l{i}.w_up", x), "w_up", x)
    h = gate * up
    return with_delta(_matmul(p, f"l{i}.w_down", h), "w_down", h)


@jax.named_scope("lm_head")
def _logits(p: dict[str, jax.Array], cfg: LlamaConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return (x @ _w(p, "embed").T).astype(jnp.float32)
    return _matmul(p, "lm_head", x).astype(jnp.float32)


def prefill(
    p: dict[str, jax.Array],
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] int32, right-padded
    seq_lens: jax.Array,  # [B] int32 true lengths
    kv_cache: jax.Array,  # [L, 2, P*page, Hkv, D]
    page_table: jax.Array,  # [B, max_pages] int32 page ids
    page_size: int,
    mlp=None,  # pluggable feed-forward (MoE families override; see mixtral)
    lora=None,
    adapter_idx=None,
) -> tuple[jax.Array, jax.Array]:
    """Process prompts; returns (last-position logits [B, V], updated cache).

    Prompt self-attention never reads the cache (the prompt is
    self-contained); K/V are computed in-registers and scattered into the
    page pool once at the end — one HBM write per layer.
    """
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    valid = positions < seq_lens[:, None]  # [B, S]
    causal = positions[:, :, None] >= positions[:, None, :]
    mask = causal & valid[:, None, :]

    # flat cache slot per (b, s): page_table[b, s // page] * page + s % page
    n_slots = kvq.n_slots(kv_cache)
    slot = (
        jnp.take_along_axis(page_table, positions // page_size, axis=1) * page_size
        + positions % page_size
    )  # [B, S]
    x = _embed_rows(p, tokens)
    for i in range(cfg.n_layers):
        h = rms_norm(x, p[f"l{i}.attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(p, i, h, positions, cfg, lora, adapter_idx)
        # padded positions scatter to an out-of-bounds slot, which
        # mode="drop" discards (negative indices would wrap instead)
        flat = jnp.where(valid, slot, n_slots)
        kv_cache = kvq.scatter_kv(kv_cache, i, flat, k, v)
        attn = _attention(q, k, v, mask)
        x = x + _wo_project(p, i, attn, lora, adapter_idx)
        h = rms_norm(x, p[f"l{i}.mlp_norm"], cfg.norm_eps)
        x = x + (mlp(p, i, h) if mlp is not None
                 else _mlp(p, i, h, lora, adapter_idx))
    x = rms_norm(x, p["norm_f"], cfg.norm_eps)
    last = jnp.take_along_axis(
        x, (seq_lens - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    return _logits(p, cfg, last), kv_cache


def prefill_sp(
    p: dict[str, jax.Array],
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] int32, right-padded; S divisible by sp
    seq_lens: jax.Array,  # [B] int32 true lengths
    kv_cache: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    page_size: int,
    *,
    mesh,  # jax.sharding.Mesh with an "sp" axis
    strategy: str = "ring",  # "ring" | "ulysses"
    mlp=None,
    lora=None,
    adapter_idx=None,
) -> tuple[jax.Array, jax.Array]:
    """Sequence-parallel prefill: context parallelism for prompts whose
    attention working set exceeds one chip's HBM budget (SURVEY.md §5
    long-context). Identical to ``prefill`` except attention runs as ring
    attention over the ``sp`` mesh axis (ops/ring_attention.py) — each
    device holds S/sp of the sequence and K/V blocks rotate over ICI
    neighbors.

    Correctness under right padding: ring attention is causal-only (no
    validity mask), but padding sits at positions >= seq_len, so a valid
    query at position i < seq_len only ever attends keys <= i, all valid.
    Outputs at padded positions are garbage and are never read (logits are
    taken at seq_lens-1; padded K/V scatters are dropped)."""
    from aigw_tpu.ops.ring_attention import ring_attention

    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    valid = positions < seq_lens[:, None]
    n_slots = kvq.n_slots(kv_cache)
    slot = (
        jnp.take_along_axis(page_table, positions // page_size, axis=1)
        * page_size
        + positions % page_size
    )
    x = _embed_rows(p, tokens)
    for i in range(cfg.n_layers):
        h = rms_norm(x, p[f"l{i}.attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(p, i, h, positions, cfg, lora, adapter_idx)
        flat = jnp.where(valid, slot, n_slots)
        kv_cache = kvq.scatter_kv(kv_cache, i, flat, k, v)
        with jax.named_scope("layer/attn"):
            attn = ring_attention(
                q, k.astype(q.dtype), v.astype(q.dtype),
                mesh=mesh, causal=True, strategy=strategy,
            ).astype(x.dtype)
        x = x + _wo_project(p, i, attn, lora, adapter_idx)
        h = rms_norm(x, p[f"l{i}.mlp_norm"], cfg.norm_eps)
        x = x + (mlp(p, i, h) if mlp is not None
                 else _mlp(p, i, h, lora, adapter_idx))
    x = rms_norm(x, p["norm_f"], cfg.norm_eps)
    last = jnp.take_along_axis(
        x, (seq_lens - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    return _logits(p, cfg, last), kv_cache


def prefill_sp_suffix(
    p: dict[str, jax.Array],
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] chunk tokens, right-padded; S % sp == 0
    prefix_lens: jax.Array,  # [B] int32 — tokens already in the cache
    seq_lens: jax.Array,  # [B] int32 — TOTAL length incl. prefix
    kv_cache: jax.Array,
    page_table: jax.Array,  # [B, pages]; pages*page_size % sp == 0
    page_size: int,
    *,
    mesh,  # jax.sharding.Mesh with an "sp" axis
    mlp=None,
    lora=None,
    adapter_idx=None,
) -> tuple[jax.Array, jax.Array]:
    """Sequence-parallel chunked prefill resuming at an arbitrary
    page-aligned offset: ``prefill_suffix`` semantics with ring attention
    over the ``sp`` axis (ops/ring_attention.ring_attention_prefix).

    Per layer the chunk's K/V scatter into the pool first (so the next
    chunk's window pass sees them), then attention runs two ring passes
    under one online-softmax carry: chunk-causal over the in-register
    K/V, plus the gathered page window masked to ``t < prefix_len``.
    With ``prefix_lens == 0`` the window pass is fully masked and this
    degenerates to ``prefill_sp`` over one chunk. Padded queries are
    garbage-out (never read); their scatters drop via the OOB slot.
    """
    from aigw_tpu.ops.ring_attention import ring_attention_prefix

    S = tokens.shape[1]
    n_slots = kvq.n_slots(kv_cache)
    positions = prefix_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    valid = positions < seq_lens[:, None]  # [B, S]

    slot = (
        jnp.take_along_axis(page_table, positions // page_size, axis=1)
        * page_size
        + positions % page_size
    )
    flat = jnp.where(valid, slot, n_slots)  # OOB → dropped by scatter

    x = _embed_rows(p, tokens)
    for i in range(cfg.n_layers):
        h = rms_norm(x, p[f"l{i}.attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(p, i, h, positions, cfg, lora, adapter_idx)
        kv_cache = kvq.scatter_kv(kv_cache, i, flat, k, v)
        k_all, v_all = _gather_kv(kv_cache, i, page_table, page_size)
        with jax.named_scope("layer/attn"):
            attn = ring_attention_prefix(
                q, k.astype(q.dtype), v.astype(q.dtype),
                k_all.astype(q.dtype), v_all.astype(q.dtype),
                prefix_lens, mesh=mesh,
            ).astype(x.dtype)
        x = x + _wo_project(p, i, attn, lora, adapter_idx)
        h = rms_norm(x, p[f"l{i}.mlp_norm"], cfg.norm_eps)
        x = x + (mlp(p, i, h) if mlp is not None
                 else _mlp(p, i, h, lora, adapter_idx))
    x = rms_norm(x, p["norm_f"], cfg.norm_eps)
    last = jnp.take_along_axis(
        x, (seq_lens - prefix_lens - 1)[:, None, None].astype(jnp.int32),
        axis=1,
    )[:, 0]
    return _logits(p, cfg, last), kv_cache


def decode_step(
    p: dict[str, jax.Array],
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B] int32 current token per slot
    positions: jax.Array,  # [B] int32 position of `tokens`
    kv_cache: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    page_size: int,
    active: jax.Array,  # [B] bool slot occupied
    mlp=None,  # pluggable feed-forward (MoE families override)
    lora=None,  # stacked adapters (models/lora.py)
    adapter_idx=None,  # [B] int32 adapter row per slot
    attn_impl: str = "",  # see below
    mesh=None,  # jax Mesh — the walk then runs per head shard
    walk=None,  # this step's paged_walk.PairPlan
) -> tuple[jax.Array, jax.Array]:
    """One continuous-batching decode step; returns (logits [B, V], cache).

    The hot loop: fixed shapes, inactive slots masked (their K/V writes
    drop). ``attn_impl`` selects the decode-attention rung (resolved by
    tpuserve/attention.py from what the engine can observe, never
    directly by users):

    - ``""`` — the page walk (ops/paged_walk.py; ``xla-walk`` on
      /state, ``xla-walk-spmd`` on a mesh): scatter (quantizing
      in-pass), then an online-softmax loop over the whole pages the
      LIVE rows hold — nothing padded is gathered, int8/int4 pages
      dequantize at the read. ``walk`` is this step's
      ``paged_walk.pair_plan`` (made here when the caller has none;
      the engine makes it, to count what the loops read). With
      ``mesh`` the walk runs per head-shard inside shard_map: each
      device walks its LOCAL pool shard — no GSPMD gather.
    - ``"gather"`` — the full padded window [B, T_max] is gathered per
      slot and runs dense attention: the one rung that needs no whole
      head shard per device (a mesh whose head counts do not divide tp).
    """
    B = tokens.shape[0]
    max_pages = page_table.shape[1]
    T = max_pages * page_size
    pos1 = positions[:, None]  # [B, 1]

    n_slots = kvq.n_slots(kv_cache)
    slot = (
        jnp.take_along_axis(page_table, pos1 // page_size, axis=1) * page_size
        + pos1 % page_size
    )  # [B, 1]
    slot = jnp.where(active[:, None], slot, n_slots)  # OOB → dropped

    if attn_impl not in ("", "gather"):
        raise ValueError(f"unknown decode attention rung {attn_impl!r}")
    use_gather = attn_impl == "gather"
    lengths = jnp.where(active, positions + 1, 0)
    if use_gather:
        # gather the full (padded) KV window for each slot
        t_idx = jnp.arange(T, dtype=jnp.int32)[None, :].repeat(B, 0)
        attend = t_idx <= pos1  # causal within the sequence window
    elif walk is None:
        walk = kvq.walk_plan(kv_cache, lengths, page_table, page_size, mesh)

    HD = cfg.n_heads * cfg.head_dim
    x = _embed_rows(p, tokens[:, None])  # [B, 1, dim]
    for i in range(cfg.n_layers):
        h = rms_norm(x, p[f"l{i}.attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(p, i, h, pos1, cfg, lora, adapter_idx)
        kv_cache = kvq.scatter_kv(kv_cache, i, slot, k, v)
        if use_gather:
            k_all, v_all = _gather_kv(kv_cache, i, page_table, page_size)
            attn = _attention(q, k_all, v_all, attend[:, None, :])
        else:
            attn = kvq.walk_kv(kv_cache, i, q[:, 0], page_table,
                               lengths, page_size, walk,
                               mesh).reshape(B, 1, HD)
        x = x + _wo_project(p, i, attn, lora, adapter_idx)
        h = rms_norm(x, p[f"l{i}.mlp_norm"], cfg.norm_eps)
        x = x + (mlp(p, i, h) if mlp is not None
                 else _mlp(p, i, h, lora, adapter_idx))
    x = rms_norm(x, p["norm_f"], cfg.norm_eps)
    return _logits(p, cfg, x[:, 0]), kv_cache


def verify_step(
    p: dict[str, jax.Array],
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] pending token + S-1 draft tokens
    positions: jax.Array,  # [B] int32 position of tokens[:, 0]
    kv_cache: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    page_size: int,
    active: jax.Array,  # [B] bool slot occupied
    limits: jax.Array,  # [B] int32 exclusive max write position
    mlp=None,
    lora=None,
    adapter_idx=None,
) -> tuple[jax.Array, jax.Array]:
    """Speculative-decoding verifier: score S candidate positions in one
    step, returning logits at EVERY position ([B, S, V]) so the engine can
    accept the longest draft prefix that matches the model's own samples.

    KV safety (the reason draft rejection is free on this layout): K/V for
    all S positions are scattered, but a later step re-scatters any
    position it revisits *before* the causal gather (``t <= pos``) can see
    it, so stale writes from rejected drafts are never read. Writes are
    fenced by ``limits`` exactly like the decode step's page-safety fence.
    """
    B, S = tokens.shape
    T = page_table.shape[1] * page_size
    n_slots = kvq.n_slots(kv_cache)
    positions = positions[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    valid = active[:, None] & (positions < limits[:, None])  # [B, S]

    slot = (
        jnp.take_along_axis(page_table, positions // page_size, axis=1)
        * page_size
        + positions % page_size
    )
    flat = jnp.where(valid, slot, n_slots)  # OOB → dropped by scatter

    t_idx = jnp.arange(T, dtype=jnp.int32)[None, :]

    x = _embed_rows(p, tokens)
    for i in range(cfg.n_layers):
        h = rms_norm(x, p[f"l{i}.attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(p, i, h, positions, cfg, lora, adapter_idx)
        kv_cache = kvq.scatter_kv(kv_cache, i, flat, k, v)
        k_all, v_all = _gather_kv(kv_cache, i, page_table, page_size)
        mask = (t_idx[:, None, :] <= positions[:, :, None]) \
            & valid[..., None]
        attn = _attention(q, k_all, v_all, mask)
        x = x + _wo_project(p, i, attn, lora, adapter_idx)
        h = rms_norm(x, p[f"l{i}.mlp_norm"], cfg.norm_eps)
        x = x + (mlp(p, i, h) if mlp is not None
                 else _mlp(p, i, h, lora, adapter_idx))
    x = rms_norm(x, p["norm_f"], cfg.norm_eps)
    return _logits(p, cfg, x), kv_cache


@jax.named_scope("layer/attn")
def _ragged_window_attention(
    q: jax.Array,  # [T, H, D] packed queries (f32/bf16)
    k_pool: jax.Array,  # [n_slots, Hkv, D] (native or int8/int4)
    v_pool: jax.Array,
    pt_rows: jax.Array,  # [T, P] page ids of each token's sequence
    positions: jax.Array,  # [T] absolute position per token
    valid: jax.Array,  # [T] bool — False for padding rows
    page_size: int,
    k_scale: jax.Array | None = None,  # [n_slots, Hkv] (quantized pool)
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """XLA reference for the ragged prefill attention: online softmax
    over the page window, one page per loop step — the same math as the
    Pallas kernel (ops/pallas/paged_attention.ragged_prefill_attention)
    with memory bounded at [T, page] instead of [T, window], so the
    CPU/interpret fallback never materializes the full padded window.
    Returns [T, H * D] in q's dtype."""
    T, H, D = q.shape
    Hkv = k_pool.shape[1]
    grp = H // Hkv
    P = pt_rows.shape[1]
    qf = q.astype(jnp.float32).reshape(T, Hkv, grp, D) / math.sqrt(D)
    offs = jnp.arange(page_size, dtype=jnp.int32)

    def body(p, carry):
        m, l, acc = carry
        slots = pt_rows[:, p][:, None] * page_size + offs[None, :]
        k = k_pool[slots].astype(jnp.float32)  # [T, page, Hkv, D]
        v = v_pool[slots].astype(jnp.float32)
        if k_scale is not None:  # quantized pages: dequant at the read
            k = k * k_scale[slots][..., None]
            v = v * v_scale[slots][..., None]
        logits = jnp.einsum("thgd,tshd->thgs", qf, k)  # [T, Hkv, grp, page]
        kp = p * page_size + offs
        mask = (kp[None, :] <= positions[:, None]) & valid[:, None]
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(logits - m_new)
        l_new = alpha * l + probs.sum(-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("thgs,tshd->thgd", probs, v)
        return m_new, l_new, acc_new

    m0 = jnp.full((T, Hkv, grp, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((T, Hkv, grp, 1), jnp.float32)
    acc0 = jnp.zeros((T, Hkv, grp, D), jnp.float32)
    # traced upper bound: pages past the highest attended position are
    # fully masked — skip them instead of walking the whole window
    # (the XLA analogue of the kernel's ragged DMA skip)
    max_pos = jnp.max(jnp.where(valid, positions, 0))
    p_hi = jnp.minimum(max_pos // page_size + 1, P)
    _, l, acc = lax.fori_loop(0, p_hi, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(T, H * D).astype(q.dtype)


def prefill_ragged(
    p: dict[str, jax.Array],
    cfg: LlamaConfig,
    tokens: jax.Array,  # [T] int32 — PACKED new tokens, all sequences
    row_seq: jax.Array,  # [T] int32 — sequence row per token; >= B = padding
    positions: jax.Array,  # [T] int32 — absolute position per token
    last_rows: jax.Array,  # [B] int32 — packed index of each row's last token
    kv_cache: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    page_size: int,
    *,
    attn_impl: str = "",  # "" = XLA windowed reference; "pallas" = kernel
    mlp=None,
    lora=None,
    adapter_idx=None,  # [B] int32 adapter row per sequence row
) -> tuple[jax.Array, jax.Array]:
    """Ragged prefill: ONE program for any admission-burst geometry.

    The packed layout replaces per-sequence bucket padding: sequence b's
    new tokens occupy a contiguous run of packed rows (grouped and
    ascending in b, padding rows at the tail with ``row_seq >= B``), at
    absolute positions ``positions`` — nonzero first positions make
    offset-resumed prefill (prefix-cache partial hits, chunked-prefill
    continuations) first-class. Per layer the chunk's K/V are scattered
    into the page pool, then every packed query attends its own
    sequence's page window under a global causal mask — semantically
    ``prefill_suffix`` with the batch dimension flattened away. Returns
    (logits at each row's last packed token [B, V], updated cache);
    rows whose segment does not end the prompt carry don't-care logits
    the engine ignores.
    """
    T = tokens.shape[0]
    B, P = page_table.shape
    valid = row_seq < B
    rs = jnp.minimum(row_seq, B - 1)
    n_slots = kvq.n_slots(kv_cache)
    pt_rows = page_table[rs]  # [T, P]
    slot = (
        jnp.take_along_axis(
            pt_rows, (positions // page_size)[:, None], axis=1)[:, 0]
        * page_size
        + positions % page_size
    )
    flat = jnp.where(valid, slot, n_slots)[:, None]  # [T, 1]; OOB drops
    atok = adapter_idx[rs] if adapter_idx is not None else None

    use_pallas = attn_impl == "pallas"
    if use_pallas and kvq.is_quantized(kv_cache):
        raise NotImplementedError(
            "the Pallas ragged-prefill kernel has no quantized-pool "
            "rung — the fallback matrix keeps int8/int4 on the XLA "
            "windowed path")
    if use_pallas:
        from aigw_tpu.ops.pallas._compat import is_tpu_backend
        from aigw_tpu.ops.pallas.paged_attention import (
            ragged_prefill_attention,
        )

        interp = not is_tpu_backend()
        # the kernel's scalar-prefetch metadata, derived from the packed
        # layout (rows grouped and ascending in b, padding at the tail)
        cu = jnp.searchsorted(
            row_seq, jnp.arange(B + 1, dtype=jnp.int32), side="left"
        ).astype(jnp.int32)
        start = positions[jnp.minimum(cu[:B], T - 1)]

    # per-token layout [T, 1, ...]: every existing helper (rope, LoRA
    # deltas, projections) treats the packed rows as batch entries
    x = _embed_rows(p, tokens[:, None])  # [T, 1, dim]
    pos2 = positions[:, None]
    for i in range(cfg.n_layers):
        h = rms_norm(x, p[f"l{i}.attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(p, i, h, pos2, cfg, lora, atok)
        kv_cache = kvq.scatter_kv(kv_cache, i, flat, k, v)
        if use_pallas:
            with jax.named_scope("layer/attn"):
                attn = ragged_prefill_attention(
                    q[:, 0], kv_cache[i, 0], kv_cache[i, 1], page_table,
                    cu, start, page_size=page_size, interpret=interp,
                ).reshape(T, 1, cfg.n_heads * cfg.head_dim)
        else:
            kr, ksc = kvq.layer_pool(kv_cache, i, 0)
            vr, vsc = kvq.layer_pool(kv_cache, i, 1)
            attn = _ragged_window_attention(
                q[:, 0], kr, vr, pt_rows, positions, valid, page_size,
                k_scale=ksc, v_scale=vsc,
            ).reshape(T, 1, -1)
        x = x + _wo_project(p, i, attn, lora, atok)
        h = rms_norm(x, p[f"l{i}.mlp_norm"], cfg.norm_eps)
        x = x + (mlp(p, i, h) if mlp is not None
                 else _mlp(p, i, h, lora, atok))
    x = rms_norm(x, p["norm_f"], cfg.norm_eps)
    last = x[jnp.clip(last_rows, 0, T - 1), 0]  # [B, dim]
    return _logits(p, cfg, last), kv_cache


def hidden_states(
    p: dict[str, jax.Array],
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S]
    seq_lens: jax.Array,  # [B]
    mlp=None,  # pluggable feed-forward (MoE families override)
    lora=None,
    adapter_idx=None,
) -> jax.Array:
    """Mean-pooled final hidden states (the /v1/embeddings path)."""
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    valid = positions < seq_lens[:, None]
    causal = positions[:, :, None] >= positions[:, None, :]
    mask = causal & valid[:, None, :]
    x = _embed_rows(p, tokens)
    for i in range(cfg.n_layers):
        h = rms_norm(x, p[f"l{i}.attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(p, i, h, positions, cfg, lora, adapter_idx)
        x = x + _wo_project(p, i, _attention(q, k, v, mask), lora,
                            adapter_idx)
        h = rms_norm(x, p[f"l{i}.mlp_norm"], cfg.norm_eps)
        x = x + (mlp(p, i, h) if mlp is not None
                 else _mlp(p, i, h, lora, adapter_idx))
    x = rms_norm(x, p["norm_f"], cfg.norm_eps)
    w = valid[..., None].astype(jnp.float32)
    pooled = (x.astype(jnp.float32) * w).sum(1) / jnp.maximum(w.sum(1), 1.0)
    return pooled


def prefill_suffix(
    p: dict[str, jax.Array],
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] suffix tokens, right-padded
    prefix_lens: jax.Array,  # [B] int32 — tokens already in the cache
    seq_lens: jax.Array,  # [B] int32 — TOTAL length incl. prefix
    kv_cache: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    page_size: int,
    mlp=None,
    lora=None,
    adapter_idx=None,
) -> tuple[jax.Array, jax.Array]:
    """Prefill only the suffix of a prompt whose prefix K/V already sits in
    cache pages (prefix caching / chunked prefill). Per layer: suffix K/V
    are scattered into the pool first, then attention gathers the full
    page window — so suffix queries see both the cached prefix and the
    suffix itself under a global causal mask. With ``prefix_lens == 0``
    this degenerates to (a gather-based) full prefill.
    """
    S = tokens.shape[1]
    T = page_table.shape[1] * page_size
    n_slots = kvq.n_slots(kv_cache)
    positions = prefix_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    valid = positions < seq_lens[:, None]  # [B, S]

    slot = (
        jnp.take_along_axis(page_table, positions // page_size, axis=1)
        * page_size
        + positions % page_size
    )
    flat = jnp.where(valid, slot, n_slots)  # OOB → dropped by scatter
    t_idx = jnp.arange(T, dtype=jnp.int32)[None, :]

    x = _embed_rows(p, tokens)
    for i in range(cfg.n_layers):
        h = rms_norm(x, p[f"l{i}.attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(p, i, h, positions, cfg, lora, adapter_idx)
        kv_cache = kvq.scatter_kv(kv_cache, i, flat, k, v)
        k_all, v_all = _gather_kv(kv_cache, i, page_table, page_size)
        # causal over global positions; padded queries masked by `valid`
        mask = (t_idx[:, None, :] <= positions[:, :, None]) & valid[..., None]
        attn = _attention(q, k_all, v_all, mask)
        x = x + _wo_project(p, i, attn, lora, adapter_idx)
        h = rms_norm(x, p[f"l{i}.mlp_norm"], cfg.norm_eps)
        x = x + (mlp(p, i, h) if mlp is not None
                 else _mlp(p, i, h, lora, adapter_idx))
    x = rms_norm(x, p["norm_f"], cfg.norm_eps)
    last = jnp.take_along_axis(
        x, (seq_lens - prefix_lens - 1)[:, None, None].astype(jnp.int32),
        axis=1,
    )[:, 0]
    return _logits(p, cfg, last), kv_cache
