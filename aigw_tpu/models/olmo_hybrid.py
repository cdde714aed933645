"""Olmo-Hybrid family: a dense hybrid of Gated DeltaNet (linear
attention) and plain softmax attention, three layers of the first to one
of the second.

From the published ``config.json`` (``model_type: olmo_hybrid``); the
float32 reference of the same equations is models/reference/
olmo_hybrid_ref.py. ``layer_types`` names each layer's kind. Both kinds
share the Olmo block: ``h = x + N(Mixer(x))``, ``out = h + N(MLP(h))`` —
the norm sits on the sub-layer's OUTPUT, ``N`` an RMSNorm with a plain
weight — and a dense SwiGLU MLP.

- *Gated DeltaNet layer.* The rule, its chunked (WY) form, the causal
  convolution and the decode step's live-row loop are
  models/qwen3_next.py's, imported: what this family adds around them is
  separate q / k / v / gate projections (no key-head repeat: as many key
  heads as value heads), key and value head widths that differ (96 under
  192 at the published size) and ``beta = 2·sigmoid(·)``
  (``linear_allow_neg_eigval``: the state's transition
  ``I − β k kᵀ`` then has eigenvalues down to −1). With ``β`` up to 2 the
  matrix the chunked form inverts is still unit lower triangular.
- *Full-attention layer.* As many key heads as query heads, an RMSNorm
  over the WHOLE query and key projections (not a head at a time), no
  rotary embedding, no gate.

What the family keeps on the device (models/cache.py): pages for the
full-attention layers only, and a per-slot pool — each DeltaNet layer's
``[heads, key width, value width]`` float32 state and the last
``kernel - 1`` inputs of its convolution. That state can be SNAPSHOTTED
at a chunk boundary of a prompt (``CacheSpec.snapshots``): the engine's
prefix cache then serves the family, a hit resuming from a chain node
that holds a snapshot.

Readings of what the published config has no key for (``assumed`` in
the benchmark's configuration file; none changes a shape or a count):
the block's norm placement and the norm over the whole projection
follow Olmo 2 / Olmo 3; ``rope_theta: null`` is read as no rotary
embedding; no convolution bias; plain (not zero-centred) norm weights.
Departures from the checkpoint's tensor layout (a loader permutes): the
three convolutions' weights are one ``[kernel, channels]`` leaf over
q | k | v, and ``a_proj`` / ``b_proj`` are one ``[D, 2H]`` leaf b | a.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from aigw_tpu.models import kvq, llama
from aigw_tpu.models.cache import CacheSpec, StateCache
from aigw_tpu.models.qwen3_next import (
    _gdn_chunk,
    _gdn_conv,
    _gdn_heads,
    _gdn_live_rows,
    _gdn_out,
    _last,
    _logits,
    state_rows,
)

_KINDS = {"linear_attention": "linear", "full_attention": "full"}


@dataclass(frozen=True)
class OlmoHybridConfig:
    # every field is a key of the published config.json
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    #: each layer's kind; empty: three ``linear_attention`` then one
    #: ``full_attention``, repeated
    layer_types: tuple = ()
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True

    def __post_init__(self):
        kinds = tuple(self.layer_types) or tuple(
            "full_attention" if i % 4 == 3 else "linear_attention"
            for i in range(self.num_hidden_layers))
        if len(kinds) != self.num_hidden_layers \
                or any(k not in _KINDS for k in kinds):
            raise ValueError(
                f"layer_types {kinds!r} for {self.num_hidden_layers} layers")
        object.__setattr__(self, "layer_types", kinds)
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("the family has one key head a value head")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("heads do not divide the hidden size")

    # the names the serving stack reads off every family's config
    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def dim(self) -> int:
        return self.hidden_size

    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        return tuple(_KINDS[k] for k in self.layer_types)

    @property
    def n_full_layers(self) -> int:
        return self.layer_kinds.count("full")

    @property
    def n_linear_layers(self) -> int:
        return self.layer_kinds.count("linear")

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def kv_heads_stored(self) -> int:
        """Key heads a page row HOLDS: the key heads, rounded up to the
        16 rows of the chip's bfloat16 tile where they are more than
        one tile (30 -> 32; the two rows more are zeros nobody reads).
        The chip's tiled layout pads a ``[rows, 30, 128]`` pool to 32
        heads anyway — the same bytes — but its compiler then finds the
        padding worth "compressing": every decode step copied the whole
        pool into another layout and back, twice (6 GB each at the
        published size; PERF.md section 6, PR 52). A pool with no
        padding in it is left where it lies."""
        hkv = self.num_key_value_heads
        return hkv if hkv < 16 else -(-hkv // 16) * 16

    def cache_spec(self) -> CacheSpec:
        return CacheSpec(
            self.n_full_layers, self.kv_heads_stored, self.head_dim,
            slot_state=(
                ("gdn_state", self.n_linear_layers,
                 (self.linear_num_value_heads, self.linear_key_head_dim,
                  self.linear_value_head_dim), "float32"),
                ("gdn_conv", self.n_linear_layers,
                 (self.linear_conv_kernel_dim - 1, self.conv_dim),
                 "activation"),
            ),
            # a slot's state at a chunk boundary is the whole of what
            # the DeltaNet layers know of the prefix
            snapshots=True)


#: two periods at toy widths (CPU tests): key and value head widths that
#: differ, neither a multiple of the other's tile
TINY = OlmoHybridConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=4,
    max_position_embeddings=512, linear_num_key_heads=2,
    linear_num_value_heads=2, linear_key_head_dim=24,
    linear_value_head_dim=48,
)


def init_params(key: jax.Array, cfg: OlmoHybridConfig, dtype=jnp.bfloat16,
                sharding_of=None, finish=None) -> dict[str, jax.Array]:
    """Random-init weights; the placement hooks are
    :class:`llama.ParamBuilder`'s. Norm weights are plain (1 is the
    identity scale); ``A_log`` 0 and ``dt_bias`` -6 give a decay of
    about 0.9975 a token, so the state remembers some four hundred
    tokens: what a turn of a session left in it still weighs on the
    next turn's logits, and a state lost between them shows."""
    b = llama.ParamBuilder(key, 3 + cfg.num_hidden_layers * 10, dtype,
                           sharding_of, finish)
    D, F, H = cfg.hidden_size, cfg.intermediate_size, \
        cfg.linear_num_value_heads
    b.dense("embed", (cfg.vocab_size, D), scale=0.02)
    b.const("norm_f", (D,), 1.0)
    b.dense("lm_head", (D, cfg.vocab_size))
    for i, kind in enumerate(cfg.layer_kinds):
        if kind == "full":
            for m in ("q", "k", "v", "o"):
                b.dense(f"l{i}.{m}_proj", (D, D))
            b.const(f"l{i}.q_norm", (D,), 1.0)
            b.const(f"l{i}.k_norm", (D,), 1.0)
        else:
            b.dense(f"l{i}.q_proj", (D, cfg.key_dim))
            b.dense(f"l{i}.k_proj", (D, cfg.key_dim))
            b.dense(f"l{i}.v_proj", (D, cfg.value_dim))
            b.dense(f"l{i}.g_proj", (D, cfg.value_dim))
            b.dense(f"l{i}.ba_proj", (D, 2 * H))
            b.dense(f"l{i}.conv_w",
                    (cfg.linear_conv_kernel_dim, cfg.conv_dim), scale=0.5)
            b.const(f"l{i}.A_log", (H,), 0.0)
            b.const(f"l{i}.dt_bias", (H,), -6.0)
            b.const(f"l{i}.gdn_norm", (cfg.linear_value_head_dim,), 1.0)
            b.dense(f"l{i}.out_proj", (cfg.value_dim, D))
        b.const(f"l{i}.mixer_norm", (D,), 1.0)
        b.dense(f"l{i}.w_gate", (D, F))
        b.dense(f"l{i}.w_up", (D, F))
        b.dense(f"l{i}.w_down", (F, D))
        b.const(f"l{i}.mlp_norm", (D,), 1.0)
    return b.params


def _norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMSNorm in float32 over the last axis, plain weight."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


# -- Gated DeltaNet ---------------------------------------------------------
@jax.named_scope("layer/gdn_proj")
def _gdn_project(p, i, h, cfg):
    """→ mixed [B,S,conv_dim] (q|k|v before the convolution), z
    [B,S,H,dv], beta and g [B,S,H] float32."""
    B, S, _ = h.shape
    H = cfg.linear_num_value_heads
    mixed = jnp.concatenate(
        [llama._matmul(p, f"l{i}.{m}_proj", h) for m in ("q", "k", "v")],
        axis=-1)
    z = llama._matmul(p, f"l{i}.g_proj", h)
    ba = llama._matmul(p, f"l{i}.ba_proj", h).astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :H])
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(p[f"l{i}.A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., H:] + p[f"l{i}.dt_bias"].astype(jnp.float32))
    return mixed, z.reshape(B, S, H, cfg.linear_value_head_dim), beta, g


# -- full attention ---------------------------------------------------------
@jax.named_scope("layer/attn_full")
def _attn_project(p, i, h, cfg):
    """→ q, k, v [B,S,H,hd]: an RMSNorm over the whole projection on q
    and k, no rotary embedding."""
    B, S, _ = h.shape
    H, hd = cfg.num_attention_heads, cfg.head_dim
    q = _norm(llama._matmul(p, f"l{i}.q_proj", h), p[f"l{i}.q_norm"],
              cfg.rms_norm_eps)
    k = _norm(llama._matmul(p, f"l{i}.k_proj", h), p[f"l{i}.k_norm"],
              cfg.rms_norm_eps)
    v = llama._matmul(p, f"l{i}.v_proj", h)
    return (q.reshape(B, S, H, hd),
            k.reshape(B, S, cfg.num_key_value_heads, hd),
            v.reshape(B, S, cfg.num_key_value_heads, hd))


def _stored(x, cfg):
    """[..., Hkv, hd] -> [..., kv_heads_stored, hd]: a page row's heads
    (zeros behind the real ones)."""
    pad = cfg.kv_heads_stored - x.shape[-2]
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)])


@jax.named_scope("layer/attn_full")
def _attend(q, k, v, mask):
    """Softmax attention, a key head a query head. q [B,S,H,hd]; k, v
    [B,T,H,hd]; mask [B,S,T] → [B,S,H*hd]."""
    B, S, H, hd = q.shape
    logits = jnp.einsum("bshd,bthd->bhst", q, k,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    probs = jax.nn.softmax(jnp.where(mask[:, None], logits, -1e30), axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs.astype(v.dtype), v)
    return out.reshape(B, S, H * hd)


@jax.named_scope("layer/attn_full")
def _attn_out(p, i, attn):
    return llama._matmul(p, f"l{i}.o_proj", attn)


# -- the block skeleton -----------------------------------------------------
def _blocks(p, cfg, x, linear, full):
    """Every layer of the stack; ``linear(i, j, x)`` / ``full(i, j, x)``
    mix tokens in layer ``i``, the ``j``-th of its kind (``j`` indexes
    the state pool / the page pool). The norm is on each sub-layer's
    output."""
    n_lin = n_full = 0
    eps = cfg.rms_norm_eps
    for i, kind in enumerate(cfg.layer_kinds):
        if kind == "full":
            mixed = full(i, n_full, x)
            n_full += 1
        else:
            mixed = linear(i, n_lin, x)
            n_lin += 1
        x = x + _norm(mixed, p[f"l{i}.mixer_norm"], eps)
        x = x + _norm(llama._mlp(p, i, x), p[f"l{i}.mlp_norm"], eps)
    return _norm(x, p["norm_f"], eps)


def _sequence(p, cfg, tokens, prefix_lens, seq_lens, cache, page_table,
              page_size, slot_ids, from_pages):
    """A chunk of every row's sequence: tokens [B,S] at positions
    ``prefix_lens + arange(S)``, real where below ``seq_lens``. With a
    cache, DeltaNet layers continue from the row's slot (from zeros
    where ``prefix_lens == 0``; from what the engine restored there
    where a prefix-cache hit resumes) and write it back; full layers
    scatter their keys and values and, ``from_pages``, attend over the
    page window. Returns (final hidden [B,S,D], valid [B,S], cache)."""
    B, S = tokens.shape
    positions = prefix_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    valid = positions < seq_lens[:, None]
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
    vf = valid.astype(jnp.float32)[..., None]
    kv = slots = None
    if cache is not None:
        kv, slots = cache.kv, dict(cache.slots)
        flat = jnp.where(valid, jnp.take_along_axis(
            page_table, positions // page_size, axis=1) * page_size
            + positions % page_size, kvq.n_slots(kv))  # OOB → dropped
        n_state = slots["gdn_state"].shape[1]
        sid = (jnp.arange(B, dtype=jnp.int32) if slot_ids is None
               else slot_ids.astype(jnp.int32))
        # rows with nothing real (a padded group) write nowhere
        wid = jnp.where(n_valid > 0, sid, n_state)
        rid = jnp.clip(sid, 0, n_state - 1)
        fresh = prefix_lens == 0
    if from_pages:
        T = page_table.shape[1] * page_size
        mask = (jnp.arange(T, dtype=jnp.int32)[None, None, :]
                <= positions[:, :, None]) & valid[..., None]
    else:
        rel = jnp.arange(S, dtype=jnp.int32)
        mask = (rel[None, :, None] >= rel[None, None, :]) \
            & valid[:, None, :]
    H = cfg.linear_num_value_heads

    def linear(i, j, h):
        mixed, z, beta, g = _gdn_project(p, i, h, cfg)
        if slots is None:
            state = jnp.zeros((B, H, cfg.linear_key_head_dim,
                               cfg.linear_value_head_dim), jnp.float32)
            tail = jnp.zeros((B, cfg.linear_conv_kernel_dim - 1,
                              cfg.conv_dim), mixed.dtype)
        else:
            state = jnp.where(fresh[:, None, None, None], 0.0,
                              slots["gdn_state"][j][rid])
            tail = jnp.where(fresh[:, None, None], 0,
                             slots["gdn_conv"][j][rid])
        y, tail = _gdn_conv(p, i, mixed, tail, n_valid)
        q, k, v = _gdn_heads(y, cfg)
        o, state = _gdn_chunk(q, k, v, g * vf, beta * vf, state)
        if slots is not None:
            slots["gdn_state"] = slots["gdn_state"].at[j, wid].set(
                state, mode="drop")
            slots["gdn_conv"] = slots["gdn_conv"].at[j, wid].set(
                tail, mode="drop")
        return _gdn_out(p, i, o, z, cfg, h.dtype)

    def full(i, j, h):
        nonlocal kv
        q, k, v = _attn_project(p, i, h, cfg)
        if kv is not None:
            kv = kvq.scatter_kv(kv, j, flat, _stored(k, cfg),
                                _stored(v, cfg))
        if from_pages:
            k, v = (a[:, :, :cfg.num_key_value_heads]
                    for a in llama._gather_kv(kv, j, page_table, page_size))
        return _attn_out(p, i, _attend(
            q, k.astype(q.dtype), v.astype(q.dtype), mask))

    x = _blocks(p, cfg, llama._embed_rows(p, tokens), linear, full)
    return x, valid, (None if cache is None else StateCache(kv, slots))


def prefill(p, cfg: OlmoHybridConfig, tokens, seq_lens, cache, page_table,
            page_size, lora=None, adapter_idx=None, slot_ids=None):
    """Whole prompts [B,S], right-padded; row ``b`` fills decode slot
    ``slot_ids[b]`` (default: its own index). Returns (last-position
    logits [B,V], cache)."""
    x, _, cache = _sequence(
        p, cfg, tokens, jnp.zeros_like(seq_lens), seq_lens, cache,
        page_table, page_size, slot_ids, False)
    return _logits(p, _last(x, seq_lens - 1)), cache


def prefill_suffix(p, cfg: OlmoHybridConfig, tokens, prefix_lens, seq_lens,
                   cache, page_table, page_size, lora=None,
                   adapter_idx=None, slot_ids=None):
    """The next chunk of each row's prompt (chunked prefill, or the
    suffix behind a prefix-cache hit): DeltaNet layers resume from the
    slot's state, full layers attend over the page window.
    ``prefix_lens == 0`` starts the slot afresh."""
    x, _, cache = _sequence(
        p, cfg, tokens, prefix_lens, seq_lens, cache, page_table,
        page_size, slot_ids, True)
    return _logits(p, _last(x, seq_lens - prefix_lens - 1)), cache


def hidden_states(p, cfg: OlmoHybridConfig, tokens, seq_lens):
    """Mean-pooled final hidden states (the /v1/embeddings path)."""
    x, valid, _ = _sequence(
        p, cfg, tokens, jnp.zeros_like(seq_lens), seq_lens, None, None, 0,
        None, False)
    w = valid[..., None].astype(jnp.float32)
    return (x.astype(jnp.float32) * w).sum(1) / jnp.maximum(w.sum(1), 1.0)


def _live_trips(pool, active):
    """(rows a trip, live rows, trips) of a decode step's loops over
    the live rows' state: ONE trip count bounds every DeltaNet layer's
    loop (``qwen3_next._gdn_live_rows``) and is what is counted."""
    Rs = state_rows(active.shape[0],
                    math.prod(pool.shape[2:]) * pool.dtype.itemsize)
    n_live = jnp.sum(active.astype(jnp.int32))
    return Rs, n_live, -(-n_live // Rs)


def state_reads(cache, active):
    """``ModelFns.state_reads``: [2] int32 — slots whose state a
    DeltaNet layer's live-row loop reads in a decode step over
    ``active`` (its trips × rows a trip) and the live rows."""
    Rs, n_live, n_trips = _live_trips(cache.slots["gdn_state"], active)
    return jnp.stack([n_trips * Rs, n_live]).astype(jnp.int32)


def decode_step(p, cfg: OlmoHybridConfig, tokens, positions, cache,
                page_table, page_size, active, lora=None, adapter_idx=None,
                attn_impl="", mesh=None, walk=None):
    """One continuous-batching step; row ``b`` IS decode slot ``b``.
    Inactive rows leave their state, their convolution tail and the
    pages as they are. The full-attention layers read the pool through
    the page walk every family's decode step shares (ops/paged_walk.py;
    ``walk``: this step's plan, made here when the caller has none)."""
    if attn_impl:
        raise NotImplementedError(
            f"olmo_hybrid has no decode attention rung {attn_impl!r}: its "
            "full-attention layers read the pool through the page walk "
            "alone")
    B = tokens.shape[0]
    kv, slots = cache.kv, dict(cache.slots)
    pos1 = positions[:, None]
    slot = jnp.where(active[:, None], jnp.take_along_axis(
        page_table, pos1 // page_size, axis=1) * page_size
        + pos1 % page_size, kvq.n_slots(kv))
    lengths = jnp.where(active, positions + 1, 0)
    if walk is None:
        walk = kvq.walk_plan(kv, lengths, page_table, page_size, mesh)
    n_valid = active.astype(jnp.int32)
    Rs, n_live, n_trips = _live_trips(slots["gdn_state"], active)

    def linear(i, j, h):
        # the pool comes to the layer WITH the layer's input, so that
        # the compiler sees every reader of it run before the next
        # layer updates it in place (models/qwen3_next.py)
        h, slots["gdn_state"] = lax.optimization_barrier(
            (h, slots["gdn_state"]))
        mixed, z, beta, g = _gdn_project(p, i, h, cfg)
        y, tail = _gdn_conv(p, i, mixed, slots["gdn_conv"][j], n_valid)
        q, k, v = _gdn_heads(y, cfg)
        o, slots["gdn_state"] = _gdn_live_rows(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
            slots["gdn_state"], jnp.asarray(j, jnp.int32), walk.order,
            n_live, n_trips, Rs=Rs)
        slots["gdn_conv"] = slots["gdn_conv"].at[j].set(tail)
        return _gdn_out(p, i, o[:, None], z, cfg, h.dtype)

    def full(i, j, h):
        nonlocal kv
        q, k, v = _attn_project(p, i, h, cfg)
        kv = kvq.scatter_kv(kv, j, slot, _stored(k, cfg), _stored(v, cfg))
        # (a query head a stored key head: the zero heads' outputs are
        # dropped)
        attn = kvq.walk_kv(kv, j, _stored(q[:, 0], cfg), page_table,
                           lengths, page_size, walk, mesh)
        attn = attn.reshape(B, cfg.kv_heads_stored, cfg.head_dim)
        return _attn_out(
            p, i, attn[:, :cfg.num_attention_heads].reshape(B, 1, -1))

    x = _blocks(p, cfg, llama._embed_rows(p, tokens[:, None]), linear, full)
    return _logits(p, x[:, 0]), StateCache(kv, slots)
