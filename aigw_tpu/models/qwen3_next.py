"""Qwen3-Next family: a hybrid of Gated DeltaNet (linear attention) and
gated softmax attention, with a many-small-experts MoE in every layer.

From the published ``config.json`` (``model_type: qwen3_next``); the
float32 reference of the same equations is models/reference/
qwen3_next_ref.py. Layer ``i`` (0-based) is full attention when
``(i + 1) % full_attention_interval == 0``, else Gated DeltaNet; every
layer is ``h = x + Mixer(N(x))``, ``out = h + MoE(N(h))`` with ``N`` an
RMSNorm whose weight is stored zero-centred (``1 + w``).

What this family keeps on the device (models/cache.py): pages for the
full-attention layers only, and beside them a per-slot pool — each
DeltaNet layer's ``[value heads, key width, value width]`` float32
state and the last ``kernel - 1`` inputs of its causal convolution. A
sequence's first chunk starts from zeros; later chunks and decode steps
continue from the slot's state; padding tokens and inactive rows
neither decay nor write it.

The expert layer is told which experts it holds (``held_from``,
``num_experts`` of them): it routes over the router's whole published
width, computes the shared expert plus its own experts' weighted
outputs, and leaves out what absent experts would add — one chip's
share of an expert-parallel deployment, with no stand-in for the
exchange. With everything held it is the whole layer. No token is ever
dropped: a sequence runs the held experts densely over every token and
the combine weights select (a sort-and-grouped path is ROADMAP.md's to
add); a decode step loops over the held experts its live rows hit and
reads no other.

Departures from the checkpoint's tensor layout (a loader permutes; the
mathematics is the source's): ``in_proj_qkvz`` holds q | k | v | z as
contiguous blocks rather than interleaved per key head, the
convolution weight is ``[kernel, channels]``, expert matrices are
stored flat (``[D, E*F]``, ``[E*F, D]``) so that each is one plain
matmul operand, and the multi-token-prediction module is a draft
source that is not served (ROADMAP.md M6).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from aigw_tpu.models import kvq, llama
from aigw_tpu.models.cache import CacheSpec, StateCache

#: tokens per block of the chunked (WY) DeltaNet form
GDN_CHUNK = 64

#: state bytes a trip of the decode step's live-row loop should move
#: (``state_rows``)
_STATE_TRIP_BYTES = 4 << 20

_HI = lax.Precision.HIGHEST


@dataclass(frozen=True)
class Qwen3NextConfig:
    # every field is a scalar key of the published config.json …
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    #: experts HELD here (the published count when everything is held)
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    max_position_embeddings: int = 262144
    # … but these two, the program's own: the router's width (0 = the
    # experts held, i.e. nothing is absent) and the first held expert
    router_experts: int = 0
    held_from: int = 0

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
    def n_experts(self) -> int:
        return self.num_experts

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        return tuple(
            "full" if (i + 1) % self.full_attention_interval == 0
            else "linear" for i in range(self.num_hidden_layers))

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
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def moe_tape_width(self) -> int:
        """Columns of one layer's routing-stats row: assignments placed
        on each held expert, then dropped (structurally 0), then every
        assignment the router made (held or absent), then how many held
        experts got at least one."""
        return self.num_experts + 3

    @property
    def decode_tape_width(self) -> int:
        """A DECODE step's row is two columns wider: slots whose state
        the layer's live-row loop read, and live rows (0 in a
        full-attention layer's row). The sequence programs keep
        ``moe_tape_width``, and with it their compile-cache keys."""
        return self.moe_tape_width + 2

    def cache_spec(self) -> CacheSpec:
        return CacheSpec(
            self.n_full_layers, self.num_key_value_heads, self.head_dim,
            slot_state=(
                ("gdn_state", self.n_linear_layers,
                 (self.linear_num_value_heads, self.linear_key_head_dim,
                  self.linear_value_head_dim), "float32"),
                # [kernel-1, channels], channels minor: a trailing axis
                # of 3 would pad to a whole lane tile on the chip
                ("gdn_conv", self.n_linear_layers,
                 (self.linear_conv_kernel_dim - 1, self.conv_dim),
                 "activation"),
            ))


#: two periods of the layer pattern at toy widths (CPU tests): 16
#: experts routed top-4, all held
TINY = Qwen3NextConfig(
    vocab_size=512, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    rope_theta=10000.0, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=16, num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, max_position_embeddings=512,
)


def init_params(key: jax.Array, cfg: Qwen3NextConfig, dtype=jnp.bfloat16,
                sharding_of=None, finish=None) -> dict[str, jax.Array]:
    """Random-init weights; the placement hooks are
    :class:`llama.ParamBuilder`'s. Norm weights are zero-centred (0 is
    the identity scale); ``A_log`` 0 and ``dt_bias`` -3 give a decay of
    about 0.95 a token, so the state remembers tens of tokens."""
    b = llama.ParamBuilder(key, 4 + cfg.num_hidden_layers * 12, dtype,
                           sharding_of, finish)
    D = cfg.hidden_size
    E, F = cfg.num_experts, cfg.moe_intermediate_size
    Fs = cfg.shared_expert_intermediate_size
    hd = cfg.head_dim
    b.dense("embed", (cfg.vocab_size, D), scale=0.02)
    b.const("norm_f", (D,), 0.0)
    b.dense("lm_head", (D, cfg.vocab_size))
    for i, kind in enumerate(cfg.layer_kinds):
        b.const(f"l{i}.in_norm", (D,), 0.0)
        if kind == "full":
            b.dense(f"l{i}.q_proj", (D, cfg.num_attention_heads * 2 * hd))
            b.dense(f"l{i}.k_proj", (D, cfg.num_key_value_heads * hd))
            b.dense(f"l{i}.v_proj", (D, cfg.num_key_value_heads * hd))
            b.const(f"l{i}.q_norm", (hd,), 0.0)
            b.const(f"l{i}.k_norm", (hd,), 0.0)
            b.dense(f"l{i}.o_proj", (cfg.num_attention_heads * hd, D))
        else:
            b.dense(f"l{i}.in_proj_qkvz",
                    (D, 2 * cfg.key_dim + 2 * cfg.value_dim))
            b.dense(f"l{i}.in_proj_ba",
                    (D, 2 * cfg.linear_num_value_heads))
            b.dense(f"l{i}.conv_w",
                    (cfg.linear_conv_kernel_dim, cfg.conv_dim), scale=0.5)
            b.const(f"l{i}.A_log", (cfg.linear_num_value_heads,), 0.0)
            b.const(f"l{i}.dt_bias", (cfg.linear_num_value_heads,), -3.0)
            b.const(f"l{i}.gdn_norm", (cfg.linear_value_head_dim,), 1.0)
            b.dense(f"l{i}.out_proj", (cfg.value_dim, D))
        b.const(f"l{i}.post_norm", (D,), 0.0)
        b.dense(f"l{i}.router", (D, cfg.router_width))
        b.dense(f"l{i}.experts_gate", (D, E * F))
        b.dense(f"l{i}.experts_up", (D, E * F))
        b.dense(f"l{i}.experts_down", (E * F, D), scale=1.0 / math.sqrt(F))
        b.dense(f"l{i}.shared_gate", (D, Fs))
        b.dense(f"l{i}.shared_up", (D, Fs))
        b.dense(f"l{i}.shared_down", (Fs, D))
        b.dense(f"l{i}.shared_expert_gate", (D, 1))
    return b.params


def _norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMSNorm in float32 with the zero-centred weight: ``(1 + w)``."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rope_partial(x: jax.Array, positions: jax.Array, theta: float,
                  rd: int) -> jax.Array:
    """Rotate-half rotary on the first ``rd`` of each head's dimensions.
    x: [B, S, H, D]; positions [B, S]."""
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = positions.astype(jnp.float32)[..., None, None] * inv  # [B,S,1,rd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : rd // 2], xf[..., rd // 2: rd]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xf[..., rd:]], axis=-1)
    return out.astype(x.dtype)


# -- the expert layer -------------------------------------------------------
def _hit_experts(p: dict, i: int, xt: jax.Array, weights: jax.Array,
                 hit: jax.Array, n_hit: jax.Array, cfg) -> jax.Array:
    """The routed mixture of a decode step, one trip of a loop for each
    held expert that ``hit`` [E] marks (``n_hit`` of them): a trip
    reads that expert's ``[D, F]`` gate and up columns and ``[F, D]``
    down rows out of the flat matrices and adds its weighted output for
    all ``T`` rows, float32. An expert nobody picked is never read,
    and none is copied: the slices are operands of their products
    (tests/test_pallas_tpu_aot.py holds the compiled step's temporary
    memory under one expert's bytes)."""
    T, D = xt.shape
    E, F = cfg.num_experts, cfg.moe_intermediate_size
    gate, up, down = (p[f"l{i}.experts_{m}"] for m in ("gate", "up", "down"))
    ids = jnp.nonzero(hit, size=E, fill_value=0)[0].astype(jnp.int32)

    def trip(j, acc):
        e = ids[j]
        g = lax.dynamic_slice(gate, (0, e * F), (D, F))
        u = lax.dynamic_slice(up, (0, e * F), (D, F))
        d = lax.dynamic_slice(down, (e * F, 0), (F, D))
        w = lax.dynamic_slice(weights, (0, e), (T, 1))
        h = (jax.nn.silu(xt @ g) * (xt @ u)).astype(jnp.float32) * w
        return acc + jnp.dot(h.astype(xt.dtype), d,
                             preferred_element_type=jnp.float32)

    return lax.fori_loop(0, n_hit, trip, jnp.zeros((T, D), jnp.float32))


def moe(p: dict, i: int, x: jax.Array, cfg: Qwen3NextConfig,
        valid: jax.Array | None = None,
        tape: list | None = None) -> jax.Array:
    """Shared expert + the held experts' part of the routed mixture:
    this family's router (a softmax over the WHOLE width, top-k,
    renormalised) in front of :func:`held_experts`. x: [B, S, D]."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    with jax.named_scope("layer/moe_route"):
        # the pick is discrete: a rounded logit picks another expert,
        # which is another model, so the router runs at full precision
        logits = jnp.dot(xt.astype(jnp.float32),
                         p[f"l{i}.router"].astype(jnp.float32),
                         precision=_HI)
        probs = jax.nn.softmax(logits, axis=-1)  # over the WHOLE width
        topv, topi = lax.top_k(probs, cfg.num_experts_per_tok)  # [T, K]
        if cfg.norm_topk_prob:
            topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    return held_experts(p, i, xt, topv, topi, cfg, valid, tape,
                        step=S == 1).reshape(B, S, D)


def held_experts(p: dict, i: int, xt: jax.Array, topv: jax.Array,
                 topi: jax.Array, cfg, valid: jax.Array | None = None,
                 tape: list | None = None, step: bool = False,
                 shared_gate: bool | None = True) -> jax.Array:
    """What a family's router leaves to do, for every family whose
    expert layer holds a share (models/axk1.py and models/mimo_v2.py
    call it too): the shared expert (behind its sigmoid gate,
    ``shared_gate``; None: the family has no shared expert) + the
    held experts' part of the mixture ``topv`` [T, K] over expert ids
    ``topi`` [T, K] of the router's whole width. ``cfg`` names the
    experts held (``num_experts`` from ``held_from``), their width
    (``moe_intermediate_size``) and ``num_experts_per_tok``. xt: the
    tokens [T, D]. ``valid`` [T] marks real tokens for the stats
    ``tape`` (one ``[num_experts + 3]`` int32 row a layer).

    A sequence runs every held expert densely over every token and
    lets the combine weights select: a chunk's tokens hit all of them,
    and ``valid`` changes nothing that is computed. A decode ``step``
    computes only the held experts that a ``valid`` row picked
    (:func:`_hit_experts`): a row that is not valid routes nowhere and
    its routed part is zero — nothing reads a dead row's output, and
    routing is dropless, so no live row depends on what a dead row
    holds. The tape's hit column is that loop's trip count."""
    T = xt.shape[0]
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    F = cfg.moe_intermediate_size
    with jax.named_scope("layer/moe_route"):
        # combine weights over the experts held here; an absent
        # expert's id falls outside [0, E) and one-hots to nothing
        held = jax.nn.one_hot(topi - cfg.held_from, E,
                              dtype=jnp.float32)  # [T, K, E]
        weights = jnp.einsum("tke,tk->te", held, topv)
        if tape is not None or step:
            # (traced in the order the chunk programs' compile-cache
            # keys were taken in: tests/test_cache_keys.py)
            real = (jnp.ones((T,), jnp.float32) if valid is None
                    else valid.reshape(T).astype(jnp.float32))
            placed = jnp.einsum("tke,t->e", held, real).astype(jnp.int32)
            dropped = jnp.zeros((1,), jnp.int32)  # there is no fence
            routed = (jnp.sum(real) * K).astype(jnp.int32)[None]
            # ONE value: the tape's hit column and the loop's bound
            n_hit = jnp.sum(placed > 0).astype(jnp.int32)
        if tape is not None:
            tape.append(jnp.concatenate(
                [placed, dropped, routed, n_hit[None]]))
    with jax.named_scope("layer/moe_experts"):
        if step:
            out = _hit_experts(p, i, xt, weights * real[:, None],
                               placed > 0, n_hit, cfg)
        else:
            gate = jax.nn.silu(llama._matmul(p, f"l{i}.experts_gate", xt))
            up = llama._matmul(p, f"l{i}.experts_up", xt)
            h = (gate * up).astype(jnp.float32).reshape(T, E, F)
            h = (h * weights[:, :, None]).astype(xt.dtype).reshape(T, E * F)
            out = jnp.dot(h, p[f"l{i}.experts_down"],
                          preferred_element_type=jnp.float32)
    if shared_gate is None:
        return out.astype(xt.dtype)
    with jax.named_scope("layer/moe_shared"):
        sh = jax.nn.silu(llama._matmul(p, f"l{i}.shared_gate", xt)) \
            * llama._matmul(p, f"l{i}.shared_up", xt)
        sh = jnp.dot(sh, p[f"l{i}.shared_down"],
                     preferred_element_type=jnp.float32)
        if shared_gate:
            sh = sh * jax.nn.sigmoid(jnp.dot(
                xt, p[f"l{i}.shared_expert_gate"],
                preferred_element_type=jnp.float32))
        out = out + sh
    return out.astype(xt.dtype)


# -- Gated DeltaNet ---------------------------------------------------------
@jax.named_scope("layer/gdn_proj")
def _gdn_project(p, i, h, cfg):
    """→ mixed [B,S,conv_dim] (q|k|v before the convolution), z
    [B,S,Hv,dv], beta and g [B,S,Hv] float32."""
    B, S, _ = h.shape
    Hv = cfg.linear_num_value_heads
    qkvz = llama._matmul(p, f"l{i}.in_proj_qkvz", h)
    ba = llama._matmul(p, f"l{i}.in_proj_ba", h).astype(jnp.float32)
    mixed, z = qkvz[..., : cfg.conv_dim], qkvz[..., cfg.conv_dim:]
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p[f"l{i}.A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., Hv:] + p[f"l{i}.dt_bias"].astype(jnp.float32))
    return mixed, z.reshape(B, S, Hv, cfg.linear_value_head_dim), beta, g


@jax.named_scope("layer/gdn_conv")
def _gdn_conv(p, i, mixed, tail, n_valid):
    """Causal depthwise convolution + SiLU over ``mixed`` [B,S,C],
    continuing from ``tail`` [B,K-1,C] (the inputs before this call);
    returns the activations and the new tail: the last K-1 REAL inputs
    of each row (``n_valid`` [B] real tokens, right-padded)."""
    K = tail.shape[1] + 1
    S = mixed.shape[1]
    xx = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=1)
    w = p[f"l{i}.conv_w"].astype(jnp.float32)  # [K, C]
    y = sum(xx[:, j:j + S].astype(jnp.float32) * w[j] for j in range(K))
    idx = n_valid[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    new_tail = jnp.take_along_axis(xx, idx[:, :, None], axis=1)
    return jax.nn.silu(y).astype(mixed.dtype), new_tail.astype(tail.dtype)


def _gdn_heads(y, cfg):
    """Convolved q|k|v [B,S,C] → q, k [B,S,Hv,dk] (L2-normalised per
    head, key heads repeated to the value heads, q scaled) and v
    [B,S,Hv,dv], all float32."""
    B, S, _ = y.shape
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    yf = y.astype(jnp.float32)
    q = yf[..., : cfg.key_dim].reshape(B, S, Hk, dk)
    k = yf[..., cfg.key_dim: 2 * cfg.key_dim].reshape(B, S, Hk, dk)
    v = yf[..., 2 * cfg.key_dim:].reshape(B, S, Hv, dv)
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q = jnp.repeat(q, Hv // Hk, axis=2) * dk ** -0.5
    k = jnp.repeat(k, Hv // Hk, axis=2)
    return q, k, v


@jax.named_scope("layer/gdn_recurrent")
def _gdn_recurrent(q, k, v, g, beta, state):
    """One token of the gated delta rule. q, k [B,H,dk]; v [B,H,dv];
    g, beta [B,H]; state [B,H,dk,dv] float32. Elementwise products and
    sums only: a float32 matmul on the chip would round the state to
    bfloat16 on its way in."""
    state = state * jnp.exp(g)[..., None, None]
    kv = jnp.sum(state * k[..., :, None], axis=-2)  # Sᵀk
    u = (v - kv) * beta[..., None]
    state = state + k[..., :, None] * u[..., None, :]
    return jnp.sum(state * q[..., :, None], axis=-2), state


def state_rows(n_rows: int, row_bytes: int) -> int:
    """Rows a trip of :func:`_gdn_live_rows` takes (``Rs``), from the
    program's shapes alone: the decode rows and the bytes of one slot's
    state in a layer. On a v5e a row of 2 MiB costs 13-15 us (read
    twice, written once) and a trip 3 us of its own; a lane that pads
    a step's last trip costs a row — and two or three live rows of
    thirty-two is the common step — so a trip moves about
    ``_STATE_TRIP_BYTES`` (PERF.md, PR 35)."""
    return max(1, min(n_rows, _STATE_TRIP_BYTES // max(row_bytes, 1)))


@jax.named_scope("layer/gdn_recurrent")
@functools.partial(jax.jit, static_argnames=("Rs",))
def _gdn_live_rows(q, k, v, g, beta, pool, layer, order, n_live, n_trips,
                   *, Rs):
    """One token of the gated delta rule for a decode step's LIVE rows
    only. q, k [B,H,dk]; v [B,H,dv]; g, beta [B,H]; ``pool`` is the
    whole state pool [L,B,H,dk,dv]; ``order`` ranks the ``n_live`` live
    rows first (the step's ``PairPlan.order``) and ``n_trips`` blocks
    of ``Rs`` rows hold them. A trip takes its rows one after another:
    a row's state is read out of ``pool[layer]`` where it lies (the
    slice is an operand of :func:`_gdn_recurrent`'s sums, no copy),
    updated and stored back in place. A lane past the live rows redoes
    the trip's first row and stores what it read. A slot that is not
    live is neither read nor written, and its output row is zero.
    Returns (out [B,H,dv], pool).

    Jitted on its own so that a decode program traces the loop ONCE for
    all its DeltaNet layers (``layer`` is traced), as
    ``paged_walk._walk`` is; and the WHOLE pool goes through the loop,
    viewed as a list of slots: a layer sliced out of it would be a copy
    of that layer's 67 MB."""
    B = q.shape[0]
    L, N = pool.shape[:2]
    flat = pool.reshape(L * N, *pool.shape[2:])

    def trip(t, carry):
        flat, out = carry
        for lane in range(Rs):
            live = t * Rs + lane < n_live
            row = order[jnp.where(live, t * Rs + lane, t * Rs)]
            at = (layer * N + row, 0, 0, 0)
            old = lax.dynamic_slice(flat, at, (1, *flat.shape[1:]))
            *qkvgb, o_old = (lax.dynamic_slice_in_dim(x, row, 1, 0)
                             for x in (q, k, v, g, beta, out))
            o, new = _gdn_recurrent(*qkvgb, old)
            flat = lax.dynamic_update_slice(
                flat, jnp.where(live, new, old), at)
            out = lax.dynamic_update_slice(
                out, jnp.where(live, o, o_old), (row, 0, 0))
        return flat, out

    flat, out = lax.fori_loop(
        0, n_trips, trip, (flat, jnp.zeros((B, *v.shape[1:]), jnp.float32)))
    return out, flat.reshape(pool.shape)


def _unit_lower_inverse(a):
    """``(I + A)⁻¹`` for ``A`` [..., C, C] strictly lower, ``C`` a power
    of two: a blocked inverse, merged from single rows up. Two
    neighbouring diagonal blocks with inverses ``T11``, ``T22`` and
    ``A21`` below the first make ``[[T11, 0], [−T22 · A21 · T11, T22]]``;
    on the whole matrix that is ``T − T · (off · T)``, ``off`` being
    ``A`` where row and column lie in the two halves of one block of the
    next size and ``T`` block-diagonal so far. Blocks of 1 (``T = I``)
    merge without a product; 2 → 4 → … → C take two each: ten dependent
    ``C × C`` products for ``C`` = 64 and nothing else — no loop, no
    slice, no change of layout. A zero row of ``A`` (a padded position)
    stays the identity's row through every merge, exactly.

    Not ``lax.linalg.triangular_solve``: the chip's compiler makes it
    one custom call that takes 0.33 ms for 128 matrices of 64 rows, two
    thirds of ``layer/gdn_chunk``, where these products take 0.11 ms.
    Not forward substitution written out row by row within blocks of
    16 either (ISSUE 43's first form): elementwise it needs a
    coefficient a lane, and the layouts the compiler picks for that
    made the scope slower than the solve (PERF.md, PR 43). And not the
    doubling product ``(I − A)(I + A²)(I + A⁴)…``, the same matrix on
    paper: in float32 it loses the result to cancellation when a
    block's keys share a direction (relative error 2e-2 at ``max|A|``
    0.84 where this form, like substitution, reads 2e-7), and the
    state passes through it.
    """
    C = a.shape[-1]
    if C & (C - 1):
        raise ValueError(f"{C} rows: not a power of two")
    idx = jnp.arange(C)

    def off(R):  # A between the halves (R rows each) of the blocks of 2R
        return jnp.where(
            (idx[:, None] // R) ^ (idx[None, :] // R) == 1, a, 0.0)

    t = jnp.eye(C, dtype=a.dtype) - off(1)
    R = 2
    while R < C:
        t = t - jnp.einsum("...ij,...jk,...kl->...il", t, off(R), t,
                           precision=_HI)
        R *= 2
    return t


@jax.named_scope("layer/gdn_chunk")
def _gdn_chunk(q, k, v, g, beta, state):
    """The same rule over a sequence in blocks of ``GDN_CHUNK`` (the WY
    form): q, k [B,S,H,dk]; v [B,S,H,dv]; g, beta [B,S,H]; state
    [B,H,dk,dv]. Positions with ``beta == 0`` and ``g == 0`` (padding)
    neither decay nor write: such a row of ``A`` is zero, so its row of
    ``T = (I + A)⁻¹`` is the identity's (:func:`_unit_lower_inverse`,
    which also says why ``T`` is formed blockwise and neither solved for
    nor doubled). float32 at the highest matmul precision: the state
    passes through these products. The scan over blocks carries the
    state and nothing else: every factor that does not depend on it is
    made for all blocks at once before the loop."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    C = GDN_CHUNK
    pad = (-S) % C
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    N = (S + pad) // C

    def blocks(a):  # [B, S, H, ...] -> [N, B, H, C, ...]
        a = jnp.moveaxis(a, 2, 1).reshape(B, H, N, C, *a.shape[3:])
        return jnp.moveaxis(a, 2, 0)

    q, k, v, g, beta = (blocks(a) for a in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)  # [N,B,H,C]
    kb, vb = k * beta[..., None], v * beta[..., None]
    lower = jnp.tril(jnp.ones((C, C), bool))
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))  # i >= j
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=_HI)  # noqa: E731
    a_mat = jnp.where(jnp.tril(lower, -1),
                      mm("...id,...jd->...ij", kb, k) * decay, 0.0)
    t_mat = _unit_lower_inverse(a_mat)
    u = mm("...ij,...jd->...id", t_mat, vb)
    w = mm("...ij,...jd->...id", t_mat, kb * jnp.exp(gc)[..., None])
    local = mm("...id,...jd->...ij", q, k) * decay
    last = gc[..., -1:]  # [N,B,H,1]
    q_in = q * jnp.exp(gc)[..., None]  # reads the state a block enters with
    k_out = k * jnp.exp(last - gc)[..., None]  # writes the one it leaves
    keep = jnp.exp(last)[..., None]

    def step(st, xs):
        q_n, k_n, u_n, w_n, keep_n, local_n = xs
        v_new = u_n - mm("...cd,...dv->...cv", w_n, st)
        out = mm("...cd,...dv->...cv", q_n, st) \
            + mm("...ij,...jv->...iv", local_n, v_new)
        st = st * keep_n + mm("...cd,...cv->...dv", k_n, v_new)
        return st, out

    state, out = lax.scan(step, state, (q_in, k_out, u, w, keep, local))
    out = jnp.moveaxis(out, 0, 2).reshape(B, H, N * C, dv)  # [B,H,S,dv]
    return jnp.moveaxis(out, 1, 2)[:, :S], state


def _gdn_out(p, i, o, z, cfg, dtype):
    """RMSNorm over each head's value width (plain weight) · SiLU(z),
    then the output projection. o [B,S,Hv,dv] float32."""
    B, S = o.shape[:2]
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + cfg.rms_norm_eps)
    o = o * p[f"l{i}.gdn_norm"].astype(jnp.float32)
    o = o * jax.nn.silu(z.astype(jnp.float32))
    with jax.named_scope("layer/gdn_proj"):
        return llama._matmul(p, f"l{i}.out_proj",
                             o.astype(dtype).reshape(B, S, cfg.value_dim))


# -- gated attention --------------------------------------------------------
@jax.named_scope("layer/attn_gated")
def _attn_project(p, i, h, positions, cfg):
    """→ q [B,S,H,hd], gate [B,S,H*hd], k, v [B,S,Hkv,hd]: RMSNorm
    ``(1 + w)`` per head on q and k, rotary on the first
    ``rotary_dim`` dimensions."""
    B, S, _ = h.shape
    H, Hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    qg = llama._matmul(p, f"l{i}.q_proj", h).reshape(B, S, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(B, S, H * hd)
    k = llama._matmul(p, f"l{i}.k_proj", h).reshape(B, S, Hkv, hd)
    v = llama._matmul(p, f"l{i}.v_proj", h).reshape(B, S, Hkv, hd)
    q = _norm(q, p[f"l{i}.q_norm"], cfg.rms_norm_eps)
    k = _norm(k, p[f"l{i}.k_norm"], cfg.rms_norm_eps)
    q = _rope_partial(q, positions, cfg.rope_theta, cfg.rotary_dim)
    k = _rope_partial(k, positions, cfg.rope_theta, cfg.rotary_dim)
    return q, gate, k, v


def _gated_out(p, i, attn, gate):
    attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(attn.dtype)
    return llama._matmul(p, f"l{i}.o_proj", attn)


@jax.named_scope("layer/attn_gated")
def _attn_out(p, i, q, k, v, mask, gate):
    return _gated_out(p, i, llama._attention(
        q, k.astype(q.dtype), v.astype(q.dtype), mask), gate)


# -- the block skeleton -----------------------------------------------------
def _blocks(p, cfg, x, linear, full, valid, tape):
    """Every layer of the stack; ``linear(i, j, h)`` / ``full(i, j, h)``
    mix tokens in layer ``i``, the ``j``-th of its kind (``j`` indexes
    the state pool / the page pool)."""
    n_lin = n_full = 0
    for i, kind in enumerate(cfg.layer_kinds):
        h = _norm(x, p[f"l{i}.in_norm"], cfg.rms_norm_eps)
        if kind == "full":
            x = x + full(i, n_full, h)
            n_full += 1
        else:
            x = x + linear(i, n_lin, h)
            n_lin += 1
        h = _norm(x, p[f"l{i}.post_norm"], cfg.rms_norm_eps)
        x = x + moe(p, i, h, cfg, valid, tape)
    return _norm(x, p["norm_f"], cfg.rms_norm_eps)


@jax.named_scope("lm_head")
def _logits(p, x):
    return llama._matmul(p, "lm_head", x).astype(jnp.float32)


def _finish(logits, cache, tape, moe_stats):
    if moe_stats:
        return logits, cache, jnp.stack(tape)
    return logits, cache


def _sequence(p, cfg, tokens, prefix_lens, seq_lens, cache, page_table,
              page_size, slot_ids, from_pages, tape):
    """A chunk of every row's sequence: tokens [B,S] at positions
    ``prefix_lens + arange(S)``, real where below ``seq_lens``. With a
    cache, DeltaNet layers continue from the row's slot (from zeros
    where ``prefix_lens == 0``) and write it back; full layers scatter
    their keys and values and, ``from_pages``, attend over the page
    window. Returns (final hidden [B,S,D], valid [B,S], cache)."""
    B, S = tokens.shape
    positions = prefix_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    valid = positions < seq_lens[:, None]
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
    vf = valid.astype(jnp.float32)[..., None]
    kv = slots = None
    if cache is not None:
        kv, slots = cache.kv, dict(cache.slots)
        n_rows = kvq.n_slots(kv)
        flat = jnp.where(valid, jnp.take_along_axis(
            page_table, positions // page_size, axis=1) * page_size
            + positions % page_size, n_rows)  # OOB → dropped
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
    Hv = cfg.linear_num_value_heads

    def linear(i, j, h):
        mixed, z, beta, g = _gdn_project(p, i, h, cfg)
        if slots is None:
            state = jnp.zeros((B, Hv, cfg.linear_key_head_dim,
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
        q, gate, k, v = _attn_project(p, i, h, positions, cfg)
        if kv is not None:
            kv = kvq.scatter_kv(kv, j, flat, k, v)
        if from_pages:
            k, v = llama._gather_kv(kv, j, page_table, page_size)
        return _attn_out(p, i, q, k, v, mask, gate)

    x = _blocks(p, cfg, llama._embed_rows(p, tokens), linear, full, valid,
                tape)
    return x, valid, (None if cache is None else StateCache(kv, slots))


def _last(x, idx):
    return jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]


def prefill(p, cfg: Qwen3NextConfig, tokens, seq_lens, cache, page_table,
            page_size, lora=None, adapter_idx=None, moe_stats=False,
            slot_ids=None):
    """Whole prompts [B,S], right-padded; row ``b`` fills decode slot
    ``slot_ids[b]`` (default: its own index). Returns (last-position
    logits [B,V], cache[, routing stats])."""
    tape: list | None = [] if moe_stats else None
    x, _, cache = _sequence(
        p, cfg, tokens, jnp.zeros_like(seq_lens), seq_lens, cache,
        page_table, page_size, slot_ids, False, tape)
    return _finish(_logits(p, _last(x, seq_lens - 1)), cache, tape,
                   moe_stats)


def prefill_suffix(p, cfg: Qwen3NextConfig, tokens, prefix_lens, seq_lens,
                   cache, page_table, page_size, lora=None,
                   adapter_idx=None, moe_stats=False, slot_ids=None):
    """The next chunk of each row's prompt (chunked prefill): DeltaNet
    layers resume from the slot's state, full layers attend over the
    page window. ``prefix_lens == 0`` starts the slot afresh."""
    tape: list | None = [] if moe_stats else None
    x, _, cache = _sequence(
        p, cfg, tokens, prefix_lens, seq_lens, cache, page_table,
        page_size, slot_ids, True, tape)
    return _finish(_logits(p, _last(x, seq_lens - prefix_lens - 1)), cache,
                   tape, moe_stats)


def hidden_states(p, cfg: Qwen3NextConfig, tokens, seq_lens):
    """Mean-pooled final hidden states (the /v1/embeddings path)."""
    x, valid, _ = _sequence(
        p, cfg, tokens, jnp.zeros_like(seq_lens), seq_lens, None, None, 0,
        None, False, None)
    w = valid[..., None].astype(jnp.float32)
    return (x.astype(jnp.float32) * w).sum(1) / jnp.maximum(w.sum(1), 1.0)


def decode_step(p, cfg: Qwen3NextConfig, tokens, positions, cache,
                page_table, page_size, active, lora=None, adapter_idx=None,
                attn_impl="", mesh=None, walk=None, moe_stats=False):
    """One continuous-batching step; row ``b`` IS decode slot ``b``.
    Inactive rows leave their state, their convolution tail and the
    pages as they are. The full-attention layers read the pool through
    the page walk every family's decode step shares (ops/paged_walk.py;
    ``walk``: this step's plan, made here when the caller has none);
    ``attn_impl`` may name no other rung: the family has no window
    gather, and a mesh whose tp does not divide its heads must hear so."""
    if attn_impl:
        raise NotImplementedError(
            f"qwen3_next has no decode attention rung {attn_impl!r}: its "
            "full-attention layers read the pool through the page walk "
            "alone")
    tape: list | None = [] if moe_stats else None
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
    # the DeltaNet layers' loop over the live rows' state: the plan
    # ranks them first; ONE trip count bounds every layer's loop and
    # is what the tape counts as read
    pool = slots["gdn_state"]
    Rs = state_rows(B, math.prod(pool.shape[2:]) * pool.dtype.itemsize)
    n_live = jnp.sum(n_valid)
    n_trips = -(-n_live // Rs)
    state_read = jnp.stack([n_trips * Rs, n_live])

    def linear(i, j, h):
        # the state pool comes to the layer WITH the layer's input: the
        # expert loops between two layers hide from the compiler that
        # every reader of the pool runs before the next layer updates
        # it in place, and it would copy the whole pool to be safe
        # (604 MB, twice a step at the published widths;
        # tests/test_pallas_tpu_aot.py)
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
        q, gate, k, v = _attn_project(p, i, h, pos1, cfg)
        kv = kvq.scatter_kv(kv, j, slot, k, v)
        attn = kvq.walk_kv(kv, j, q[:, 0], page_table, lengths, page_size,
                           walk, mesh)
        with jax.named_scope("layer/attn_gated"):
            return _gated_out(p, i, attn.reshape(B, 1, -1), gate)

    x = _blocks(p, cfg, llama._embed_rows(p, tokens[:, None]), linear, full,
                active[:, None], tape)
    if moe_stats:
        # decode_tape_width: what each DeltaNet layer's loop read
        tape = [jnp.concatenate(
            [row, state_read if kind == "linear" else jnp.zeros_like(
                state_read)]) for row, kind in zip(tape, cfg.layer_kinds)]
    return _finish(_logits(p, x[:, 0]), StateCache(kv, slots), tape,
                   moe_stats)
