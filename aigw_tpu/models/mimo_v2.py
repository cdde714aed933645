"""MiMo-V2 family (``model_type: mimo_v2``): softmax attention of TWO
kinds in one stack — global layers over the whole context and
sliding-window layers over the last ``sliding_window`` keys behind a
learned sink — with keys wider than values, a head count a kind, rotary
on a leading part of each head with a theta a kind, a leading dense
layer, then expert layers behind a biased sigmoid router with no shared
expert.

From the published ``config.json``; the float32 reference of the same
equations is models/reference/mimo_v2_ref.py. A layer is ``x +=
Attn(N(x)); x += FFN(N(x))`` with ``N`` a plain-weight RMSNorm.
``[q | k | v] = N(x) W_qkv``: ``num_attention_heads`` query heads and
``Hkv`` key heads of ``head_dim``, ``Hkv`` value heads of
``v_head_dim`` (``Hkv`` is ``num_key_value_heads`` in a global layer,
``swa_num_key_value_heads`` in a window layer); the first
``rotary_dim`` dims of every q and k head are rotated; ``v`` is scaled
by ``attention_value_scale`` before attention. A window layer's query
at ``t`` sees keys ``t - sliding_window < s <= t`` and one more softmax
column a head, the sink ``b_h``, which carries no value: a head's
weights may sum to less than one.

What this family keeps on the device (models/cache.py), two kinds of
memory:

- **global layers: pages**, in the one page table every family uses. A
  token leaves ONE flattened row a layer, ``v | k`` (``Hkv * v_head_dim
  + Hkv * head_dim`` values: 512 | 768 = 1280 at the published widths,
  not one more), down a column of the pool ``[global layers, 1280,
  rows]`` — the column pool of the latent family (``CacheSpec.latent``:
  one row a token a layer, no K/V planes, no head axis), so that the
  page writes, the page reads and the decode walk are that family's.
  Along a column a head's 192 keys start on a multiple of 16 sublanes;
  along a row they would start off the 128 lanes. PERF.md section 6
  (PR 47) has the chip's timing of this form against two planes.
- **window layers: a ring a slot**, per-slot state beside the pages
  (``swa_ring`` ``[window layers, slots, sliding_window, Hkv * (v_head_dim
  + head_dim)]``): the token at position ``t`` lies at ring row ``t %
  sliding_window``, ``v | k`` as in a page, keys stored ROTATED so that
  their order in the ring does not matter. Which rows are valid follows
  from the row's length alone — ring row ``r`` holds the last position
  ``p < length`` with ``p % window == r``, and nothing where that is
  negative — so a slot reused by a shorter sequence cannot read its
  predecessor's keys. A window layer holds nothing else, whatever the
  context.

A decode step walks the live rows' pages in the global layers
(ops/paged_walk.py, the step's ``WalkPlan``: a score product over the
768 key rows of a column, a value product over its 512 value rows) and
loops over the live rows' rings in the window layers
(:func:`_ring_live_rows`: a row's ring is read where it lies, once). A
chunk attends in a global layer, blockwise with an online softmax, over
the page window behind it, and in a window layer, banded, over the
ring's earlier keys and itself, then leaves its last ``sliding_window``
REAL tokens in the ring.

The expert layer is told which experts it holds (``held_from``,
``num_experts`` of them), scores by sigmoid over the router's whole
published width, PICKS by score + bias and WEIGHS by the score alone
(:func:`pick`), and shares the held-expert pass of models/qwen3_next.py.
There is no shared expert: a token none of whose picks is held here
gets nothing from the layer here.

Left out: the vision and audio towers, their projector and the MTP
heads (the source as the catalog has it has no key for any of them).

Departures from the checkpoint's tensor layout (a loader permutes; the
mathematics is the source's): ``wqkv`` holds q | k | v as contiguous
blocks, rotary pairs as halves, expert matrices flat (``[D, E*F]``,
``[E*F, D]``).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from aigw_tpu.models import axk1, kvq, llama, qwen3_next
from aigw_tpu.models.cache import CacheSpec, StateCache
from aigw_tpu.ops import paged_walk

_HI = lax.Precision.HIGHEST
#: a masked logit (finite: an online softmax subtracts it from itself)
_MASKED = -1e30

#: the published pattern's rule: after the first five layers one global
#: layer in six (0 = global, 1 = window)
_PERIOD = 6


@dataclass(frozen=True)
class MiMoV2Config:
    # every field takes a key of the published config.json …
    vocab_size: int = 152576
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    intermediate_size: int = 16384
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    swa_num_key_value_heads: int = 8
    head_dim: int = 192
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    #: 0 = global, 1 = window, a layer; () = the published rule
    hybrid_layer_pattern: tuple = ()
    #: routed experts HELD here (``n_routed_experts`` when all are held)
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    norm_topk_prob: bool = True
    #: ``null`` in the source: no factor
    routed_scaling_factor: float | None = None
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 1048576
    # … but these, the program's own: the router's width (0 = the
    # experts held, i.e. nothing is absent), the first held expert, and
    # the leading dense layers (``moe_layer_freq``'s leading zeros)
    router_experts: int = 0
    held_from: int = 0
    first_dense_layers: int = 1

    def __post_init__(self):
        pattern = tuple(int(k) for k in self.hybrid_layer_pattern) or tuple(
            0 if i == 0 or i % _PERIOD == _PERIOD - 1 else 1
            for i in range(self.num_hidden_layers))
        if len(pattern) != self.num_hidden_layers:
            raise ValueError(
                f"{len(pattern)} entries of hybrid_layer_pattern for "
                f"{self.num_hidden_layers} layers")
        object.__setattr__(self, "hybrid_layer_pattern", pattern)
        for hkv in (self.num_key_value_heads, self.swa_num_key_value_heads):
            if self.num_attention_heads % hkv:
                raise ValueError(
                    f"{self.num_attention_heads} query heads do not "
                    f"split over {hkv} key heads")

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
        """The layers WITH PAGES' head count (what a mesh would split);
        what the cache holds is ``cache_spec()``'s to say, not a head
        count times the depth."""
        return self.num_key_value_heads

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def rms_norm_eps(self) -> float:
        return self.layernorm_epsilon

    @property
    def n_experts(self) -> int:
        return self.num_experts

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def rotary_dim(self) -> int:
        """Leading dims of a head that rotate: ``int(192 * 0.334) = 64``
        (rounded down to a whole number of pairs)."""
        return int(self.head_dim * self.partial_rotary_factor) // 2 * 2

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        return tuple("window" if k else "global"
                     for k in self.hybrid_layer_pattern)

    @property
    def n_global_layers(self) -> int:
        return self.layer_kinds.count("global")

    @property
    def n_window_layers(self) -> int:
        return self.layer_kinds.count("window")

    def kv_heads(self, kind: str) -> int:
        return (self.swa_num_key_value_heads if kind == "window"
                else self.num_key_value_heads)

    def theta(self, kind: str) -> float:
        return self.swa_rope_theta if kind == "window" else self.rope_theta

    def value_width(self, kind: str) -> int:
        """A token's value part of its row in a layer of ``kind``."""
        return self.kv_heads(kind) * self.v_head_dim

    def row_width(self, kind: str) -> int:
        """Values a token leaves in a layer of ``kind``: ``v | k``."""
        return self.kv_heads(kind) * (self.v_head_dim + self.head_dim)

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def tape_extra(self) -> tuple[str, ...]:
        """The EngineStats counters a layer's last columns feed, each
        summed over the layers (``Engine._fold_moe``): real tokens none
        of whose picks is held here (expert layers); in a DECODE step
        the keys a window layer's softmax saw and the keys its rows'
        contexts hold (window layers), and the rings the step's loop
        read and its live rows (the first window layer's row: a
        layer's count, as the hybrid family's); in a PREFILL program
        the keys its real queries attended to in a global layer (global
        layers; a window layer's are bounded by the window). Zero where
        they do not apply."""
        return ("moe_unserved_tokens", "swa_keys_attended",
                "swa_keys_in_context", "decode_state_rows_read",
                "decode_state_rows_live", "prefill_keys_attended")

    @property
    def moe_tape_width(self) -> int:
        """Columns of one layer's stats row: the shared held-expert
        pass's (assignments on each held expert, dropped, every
        assignment routed, held experts hit; zeros in a dense layer's
        row), then ``tape_extra``."""
        return self.num_experts + 3 + len(self.tape_extra)

    def cache_spec(self) -> CacheSpec:
        return CacheSpec(
            self.n_global_layers, 1, self.row_width("global"), latent=True,
            window=self.sliding_window,
            slot_state=(
                ("swa_ring", self.n_window_layers,
                 (self.sliding_window, self.row_width("window")),
                 "activation"),))


#: the first seven layers' pattern (the leading dense global layer, then
#: a period: four window, one global, one window) at toy widths with the
#: published RATIOS — keys 24 over values 16 with 8 rotated (192 : 128 :
#: 64), one global key head to two window ones — and a window of 8,
#: SMALLER than a page and a chunk of the tests; 16 experts top-4, all
#: held
TINY = MiMoV2Config(
    vocab_size=512, hidden_size=64, num_hidden_layers=7,
    intermediate_size=128, num_attention_heads=4, num_key_value_heads=1,
    swa_num_key_value_heads=2, head_dim=24, v_head_dim=16,
    sliding_window=8, hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    max_position_embeddings=512,
)


def init_params(key: jax.Array, cfg: MiMoV2Config, dtype=jnp.bfloat16,
                sharding_of=None, finish=None) -> dict[str, jax.Array]:
    """Random-init weights; the placement hooks are
    :class:`llama.ParamBuilder`'s. Norm weights 1. The sinks and the
    router's selection bias are float32 and drawn, not zero: a sink of
    N(0, 2²) takes a few percent of a full window's weight on average
    and over a third at +4, a bias of N(0, 0.05²) is several times the
    gap between neighbouring scores at the eighth rank of 256 — each
    CAN move a weight or a pick, so neither mechanism goes untested."""
    n_keys = 4 + cfg.num_hidden_layers * 8
    k_main, k_f32 = jax.random.split(key)
    b = llama.ParamBuilder(k_main, n_keys, dtype, sharding_of, finish)
    f = llama.ParamBuilder(k_f32, n_keys, jnp.float32, sharding_of, finish)
    f.params = b.params
    D, H = cfg.hidden_size, cfg.num_attention_heads
    E, F = cfg.num_experts, cfg.moe_intermediate_size
    b.dense("embed", (cfg.vocab_size, D), scale=0.02)
    b.const("norm_f", (D,), 1.0)
    b.dense("lm_head", (D, cfg.vocab_size))
    for i, kind in enumerate(cfg.layer_kinds):
        b.const(f"l{i}.in_norm", (D,), 1.0)
        b.dense(f"l{i}.wqkv", (D, H * cfg.head_dim + cfg.row_width(kind)))
        if kind == "window":
            f.dense(f"l{i}.sink", (H,), scale=2.0)
        b.dense(f"l{i}.wo", (H * cfg.v_head_dim, D))
        b.const(f"l{i}.post_norm", (D,), 1.0)
        if i < cfg.first_dense_layers:
            b.dense(f"l{i}.w_gate", (D, cfg.intermediate_size))
            b.dense(f"l{i}.w_up", (D, cfg.intermediate_size))
            b.dense(f"l{i}.w_down", (cfg.intermediate_size, D))
            continue
        b.dense(f"l{i}.router", (D, cfg.router_width))
        f.dense(f"l{i}.router_bias", (cfg.router_width,), scale=0.05)
        b.dense(f"l{i}.experts_gate", (D, E * F))
        b.dense(f"l{i}.experts_up", (D, E * F))
        b.dense(f"l{i}.experts_down", (E * F, D), scale=1.0 / math.sqrt(F))
    return b.params


# -- the router -------------------------------------------------------------
def pick(s: jax.Array, bias: jax.Array, cfg: MiMoV2Config):
    """``noaux_tc`` with one group: the ``num_experts_per_tok`` largest
    ``s + bias`` are the picks, and a pick's weight is its UNBIASED
    score over the picks' sum (``norm_topk_prob``), times
    ``routed_scaling_factor`` (``null``: 1). s [T, router width], bias
    [router width] → (weights [T, K], expert ids [T, K]).

    Not ``axk1.pick`` with a bias argument: that one zeroes the scores
    of dropped groups and ranks what is left, which takes scores that
    are positive — a biased score need not be — and with one group
    there is nothing to drop; these few lines leave its traced
    programs what they are."""
    topi = lax.top_k(s + bias, cfg.num_experts_per_tok)[1]
    topv = jnp.take_along_axis(s, topi, axis=-1)
    if cfg.norm_topk_prob:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    return topv * (cfg.routed_scaling_factor or 1.0), topi


def route(p: dict, i: int, xt: jax.Array, cfg: MiMoV2Config):
    """Sigmoid scores over the router's WHOLE width, then :func:`pick`.
    The pick is discrete — a rounded score picks another expert, which
    is another model — so the router runs in float32 at the highest
    precision. xt [T, D]."""
    s = jax.nn.sigmoid(jnp.dot(
        xt.astype(jnp.float32), p[f"l{i}.router"].astype(jnp.float32),
        precision=_HI))
    return pick(s, p[f"l{i}.router_bias"], cfg)


def moe(p: dict, i: int, x: jax.Array, cfg: MiMoV2Config,
        valid: jax.Array | None = None,
        tape: list | None = None) -> jax.Array:
    """The held experts' part of the routed mixture and nothing else:
    this family's router in front of the held-expert pass the hybrid
    family shares, with no shared expert. The tape's row gains the real
    tokens none of whose picks is held here."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    with jax.named_scope("layer/moe_route"):
        topv, topi = route(p, i, xt, cfg)
    out = qwen3_next.held_experts(
        p, i, xt, topv, topi, cfg, valid, tape, step=S == 1,
        shared_gate=None).reshape(B, S, D)
    if tape is not None:
        real = (jnp.ones((B * S,), bool) if valid is None
                else valid.reshape(B * S))
        here = (topi >= cfg.held_from) \
            & (topi < cfg.held_from + cfg.num_experts)
        tape[-1] = jnp.concatenate([tape[-1], jnp.sum(
            real & ~jnp.any(here, axis=-1)).astype(jnp.int32)[None]])
    return out


# -- attention: what both kinds share ---------------------------------------
@jax.named_scope("layer/qkv")
def _project(p, i, h, cfg, kind):
    """→ q [B,S,H,dk], k [B,S,Hkv,dk], v [B,S,Hkv,dv] of a layer of
    ``kind``, ``v`` scaled; nothing rotated yet."""
    B, S, _ = h.shape
    H, Hkv = cfg.num_attention_heads, cfg.kv_heads(kind)
    dk, dv = cfg.head_dim, cfg.v_head_dim
    qkv = llama._matmul(p, f"l{i}.wqkv", h)
    q = qkv[..., :H * dk].reshape(B, S, H, dk)
    k = qkv[..., H * dk:(H + Hkv) * dk].reshape(B, S, Hkv, dk)
    v = qkv[..., (H + Hkv) * dk:].reshape(B, S, Hkv, dv)
    return q, k, (v * cfg.attention_value_scale).astype(v.dtype)


@jax.named_scope("layer/rope")
def _rotate(q, k, positions, cfg, kind):
    theta, rd = cfg.theta(kind), cfg.rotary_dim
    return (qwen3_next._rope_partial(q, positions, theta, rd),
            qwen3_next._rope_partial(k, positions, theta, rd))


def _row(k, v):
    """A token's cache row ``v | k``, heads flattened: [B,S,W]."""
    B, S = k.shape[:2]
    return jnp.concatenate(
        [v.reshape(B, S, -1), k.reshape(B, S, -1)], axis=-1)


def _own(H, n_kv, dtype):
    """[H, n_kv]: 1 where a key head is the query head's own."""
    return jax.nn.one_hot(jnp.arange(H) // (H // n_kv), n_kv, dtype=dtype)


def _at_own_head(q, n_kv):
    """Each query head's ``q`` [B,H,dk] at ITS key head's place in a
    flattened key row, zeros at the other heads' → [B,H,n_kv*dk]: one
    product of every head against a whole row then scores each head
    against its own key head, with no row cut up by head."""
    B, H, dk = q.shape
    return (q[:, :, None, :] * _own(H, n_kv, q.dtype)[None, :, :, None]
            ).reshape(B, H, n_kv * dk)


def _own_values(o, n_kv):
    """The reverse for the value product's result ``o`` [B,H,n_kv*dv]:
    each head keeps its own key head's ``dv`` → [B,H,dv]."""
    B, H, W = o.shape
    return jnp.sum(o.reshape(B, H, n_kv, W // n_kv)
                   * _own(H, n_kv, o.dtype)[None, :, :, None], axis=2)


@jax.named_scope("layer/attn_out")
def _attn_out(p, i, o, dtype):
    """The output projection of ``o`` [B,S,H,dv]."""
    B, S = o.shape[:2]
    return llama._matmul(p, f"l{i}.wo", o.astype(dtype).reshape(B, S, -1))


# -- a global layer ---------------------------------------------------------
def _attend_pages(q, block, n_blk, Tb, positions, valid, cfg):
    """Causal attention of a chunk's queries ``q`` [B,S,H,dk] over
    cached columns that come ``Tb`` tokens at a time (``block(j)``
    [B,W,Tb], a column ``v | k``; the first ``n_blk`` blocks hold every
    key a query may see), with an online softmax: nothing ``[heads, S,
    context]`` is live at once. A block is cut by key head along its
    sublanes, where a head's 192 and 128 rows start on a tile, and each
    group of query heads multiplies its own head's rows: a score
    product 192 wide, a value product 128 wide. → [B,S,H,dv] float32."""
    B, S, H, dk = q.shape
    G, dv = cfg.num_key_value_heads, cfg.v_head_dim
    qg = q.reshape(B, S, G, H // G, dk)
    at = jnp.arange(Tb, dtype=jnp.int32)

    def attend(j, carry):
        m, den, acc = carry
        with jax.named_scope("layer/kv_gather"):
            cols = block(j)
        v = cols[:, :G * dv].reshape(B, G, dv, Tb)
        k = cols[:, G * dv:].reshape(B, G, dk, Tb)
        logits = jnp.einsum(
            "bsgnd,bgdt->bsgnt", qg, k,
            preferred_element_type=jnp.float32) * cfg.softmax_scale
        on = ((j * Tb + at)[None, None, :] <= positions[:, :, None]) \
            & valid[:, :, None]
        logits = jnp.where(on[:, :, None, None, :], logits, _MASKED)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        a = jnp.exp(m - m_new)
        e = jnp.exp(logits - m_new[..., None])
        acc = acc * a[..., None] + jnp.einsum(
            "bsgnt,bgvt->bsgnv", e.astype(cols.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, den * a + jnp.sum(e, axis=-1), acc

    with jax.named_scope("layer/attn_global"):
        m, den, acc = lax.fori_loop(0, n_blk, attend, (
            jnp.full((B, S, G, H // G), _MASKED, jnp.float32),
            jnp.zeros((B, S, G, H // G), jnp.float32),
            jnp.zeros((B, S, G, H // G, dv), jnp.float32)))
        return (acc / jnp.maximum(den, 1e-30)[..., None]).reshape(
            B, S, H, dv)


# -- a window layer ---------------------------------------------------------
def _ring_positions(last: jax.Array, window: int) -> jax.Array:
    """The position each ring row holds when the newest token written is
    at ``last`` [B]: the largest ``p <= last`` with ``p % window == r``
    → [B, window]; NEGATIVE where the ring holds nothing of this
    sequence there (``last`` -1: nothing at all). From the length
    alone: what a predecessor left in the slot is never valid."""
    r = jnp.arange(window, dtype=jnp.int32)
    return last[:, None] - jnp.mod(last[:, None] - r[None, :], window)


@jax.named_scope("layer/attn_window")
def _attend_window(q, k, v, ring, prefix_lens, positions, valid, sink, cfg):
    """Banded attention of a chunk's queries ``q`` [B,S,H,dk] over the
    chunk's own ``k`` [B,S,Hkv,dk], ``v`` [B,S,Hkv,dv] and, ``ring``
    [B,window,W] given, the slot's earlier tokens (``v | k`` rows, of
    which those at positions below ``prefix_lens`` count): a query at
    ``t`` sees ``t - window < s <= t``. The softmax runs over the seen
    keys AND the head's sink ``sink`` [H], a column with no value row.
    → [B,S,H,dv] float32."""
    B, S, H, dk = q.shape
    G, dv, Wn = cfg.swa_num_key_value_heads, cfg.v_head_dim, \
        cfg.sliding_window
    kpos, kon = positions, valid
    if ring is not None:
        rpos = _ring_positions(prefix_lens - 1, Wn)
        k = jnp.concatenate(
            [ring[..., G * dv:].reshape(B, Wn, G, dk).astype(k.dtype), k],
            axis=1)
        v = jnp.concatenate(
            [ring[..., :G * dv].reshape(B, Wn, G, dv).astype(v.dtype), v],
            axis=1)
        kpos = jnp.concatenate([rpos, positions], axis=1)
        kon = jnp.concatenate([rpos >= 0, valid], axis=1)
    qg = q.reshape(B, S, G, H // G, dk)
    logits = jnp.einsum("bsgnd,btgd->bsgnt", qg, k,
                        preferred_element_type=jnp.float32) \
        * cfg.softmax_scale
    behind = positions[:, :, None] - kpos[:, None, :]  # [B,S,T]
    on = kon[:, None, :] & (behind >= 0) & (behind < Wn)
    logits = jnp.where(on[:, :, None, None, :], logits, _MASKED)
    b = sink.reshape(G, H // G)[None, None]
    m = jnp.maximum(jnp.max(logits, axis=-1), b)
    e = jnp.exp(logits - m[..., None])
    den = jnp.sum(e, axis=-1) + jnp.exp(b - m)
    out = jnp.einsum("bsgnt,btgv->bsgnv",
                     (e / den[..., None]).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, dv)


@jax.named_scope("layer/ring_write")
def _ring_after(old, rows, prefix_lens, end, window):
    """The ring ``old`` [B,window,W] after a chunk whose tokens' rows
    are ``rows`` [B,S,W] at positions ``prefix_lens + arange(S)``, real
    below ``end`` [B]: each ring row takes the chunk's token that now
    belongs there, if one does — the chunk's last ``window`` REAL
    tokens and no padded one."""
    S = rows.shape[1]
    want = _ring_positions(end - 1, window)  # [B, window]
    idx = want - prefix_lens[:, None]
    new = jnp.take_along_axis(
        rows, jnp.clip(idx, 0, S - 1)[:, :, None], axis=1)
    return jnp.where((idx >= 0)[:, :, None], new.astype(old.dtype), old)


@functools.partial(jax.jit, static_argnames=("n_values", "scale"))
def _ring_live_rows(q, rows, sink, pool, layer, order, n_live, positions,
                    *, n_values, scale):
    """One token of window attention for a decode step's LIVE rows
    only. ``q`` [B,H,Wk]: each head's rotated query at its key head's
    place (:func:`_at_own_head`); ``rows`` [B,W]: the new tokens' ``v |
    k``; ``sink`` [H]; ``pool`` the whole ring pool [L,N,window,W];
    ``order`` ranks the ``n_live`` live rows first (the step's
    ``WalkPlan.order``). A trip takes one row: its new token is stored
    at ring row ``position % window`` in place, the slot's ring is read
    where it lies (the slice is an operand of the two products, no
    copy) and attended over, every ring row that the row's length says
    is this sequence's. A slot that is not live is neither read nor
    written, and its output row is zero. Returns (out [B,H,n_values]
    float32, pool).

    Jitted on its own so that a decode program traces the loop ONCE for
    all its window layers (``layer`` is traced), as ``paged_walk._walk``
    is; and the WHOLE pool goes through the loop, viewed as a list of
    slots: a layer sliced out of it would be a copy."""
    B, H, Wk = q.shape
    L, N, Wn, W = pool.shape
    flat = pool.reshape(L * N, Wn, W)
    r = jnp.arange(Wn, dtype=jnp.int32)

    def trip(t, carry):
        flat, out = carry
        b = order[t]
        pos = positions[b]
        at = layer * N + b
        with jax.named_scope("layer/ring_write"):
            flat = lax.dynamic_update_slice(
                flat, lax.dynamic_slice(rows, (b, 0), (1, W)).astype(
                    flat.dtype)[:, None, :], (at, pos % Wn, 0))
        ring = lax.dynamic_slice(flat, (at, 0, 0), (1, Wn, W))[0]
        qb = lax.dynamic_slice(q, (b, 0, 0), (1, H, Wk))[0]
        s = jnp.einsum("hd,td->ht", qb, ring[:, n_values:].astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        # ring row r holds a token of this sequence iff r <= pos (every
        # row once the sequence is a window long)
        s = jnp.where((r <= pos)[None, :], s, _MASKED)
        m = jnp.maximum(jnp.max(s, axis=-1), sink)
        e = jnp.exp(s - m[:, None])
        den = jnp.sum(e, axis=-1) + jnp.exp(sink - m)
        o = jnp.einsum("ht,tv->hv", (e / den[:, None]).astype(q.dtype),
                       ring[:, :n_values].astype(q.dtype),
                       preferred_element_type=jnp.float32)
        return flat, lax.dynamic_update_slice(out, o[None], (b, 0, 0))

    flat, out = lax.fori_loop(
        0, n_live, trip, (flat, jnp.zeros((B, H, n_values), jnp.float32)))
    return out, flat.reshape(pool.shape)


# -- the block skeleton -----------------------------------------------------
def _blocks(p, cfg, x, window, glob, valid, tape, extra):
    """Every layer of the stack; ``window(i, j, h)`` / ``glob(i, j, h)``
    mix tokens in layer ``i``, the ``j``-th of its kind (``j`` indexes
    the ring pool / the page pool). ``extra(kind, j)`` [5]: the last
    columns of the layer's ``tape`` row (a dense layer's first hold
    zeros)."""
    none = jnp.zeros((cfg.num_experts + 4,), jnp.int32)
    n = {"window": 0, "global": 0}
    for i, kind in enumerate(cfg.layer_kinds):
        h = llama.rms_norm(x, p[f"l{i}.in_norm"], cfg.rms_norm_eps)
        x = x + (window if kind == "window" else glob)(i, n[kind], h)
        h = llama.rms_norm(x, p[f"l{i}.post_norm"], cfg.rms_norm_eps)
        if i < cfg.first_dense_layers:
            x = x + llama._mlp(p, i, h)
            if tape is not None:
                tape.append(none)
        else:
            x = x + moe(p, i, h, cfg, valid, tape)
        if tape is not None:
            tape[-1] = jnp.concatenate([tape[-1], extra(kind, n[kind])])
        n[kind] += 1
    return llama.rms_norm(x, p["norm_f"], cfg.rms_norm_eps)


def _sequence(p, cfg, tokens, prefix_lens, seq_lens, cache, page_table,
              page_size, slot_ids, from_cache, tape):
    """A chunk of every row's sequence: tokens [B,S] at positions
    ``prefix_lens + arange(S)``, real where below ``seq_lens``. With a
    cache, global layers append their rows to the pages and window
    layers leave the chunk's last real tokens in the row's slot's ring;
    ``from_cache``, the queries see the page window and the ring behind
    them, else the chunk alone. Returns (final hidden [B,S,D], valid
    [B,S], cache)."""
    B, S = tokens.shape
    positions = prefix_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    valid = positions < seq_lens[:, None]
    end = jnp.minimum(seq_lens, prefix_lens + S)
    kv = slots = None
    if cache is not None:
        kv, slots = cache.kv, dict(cache.slots)
        n_state = slots["swa_ring"].shape[1]
        sid = (jnp.arange(B, dtype=jnp.int32) if slot_ids is None
               else slot_ids.astype(jnp.int32))
        # rows with nothing real (a padded group) write nowhere
        wid = jnp.where(end > prefix_lens, sid, n_state)
        rid = jnp.clip(sid, 0, n_state - 1)
    if from_cache:
        P = page_table.shape[1]
        G = math.gcd(P, 4)  # pages a block: [S, heads, G*page] logits
        Tb = G * page_size
        n_blk = jnp.minimum(-(-jnp.max(seq_lens) // Tb), P // G)
    else:
        Tb, n_blk = S, 1  # the chunk alone: its positions start at 0

    def window(i, j, h):
        q, k, v = _project(p, i, h, cfg, "window")
        q, k = _rotate(q, k, positions, cfg, "window")
        old = None if slots is None else slots["swa_ring"][j][rid]
        o = _attend_window(q, k, v, old if from_cache else None,
                           prefix_lens, positions, valid,
                           p[f"l{i}.sink"], cfg)
        if slots is not None:
            slots["swa_ring"] = slots["swa_ring"].at[j, wid].set(
                _ring_after(old, _row(k, v), prefix_lens, end,
                            cfg.sliding_window), mode="drop")
        return _attn_out(p, i, o, h.dtype)

    def glob(i, j, h):
        nonlocal kv
        q, k, v = _project(p, i, h, cfg, "global")
        q, k = _rotate(q, k, positions, cfg, "global")
        cols = jnp.swapaxes(_row(k, v), 1, 2)  # [B,W,S]
        if kv is not None:
            kv = axk1._write_chunk(kv, j, cols, prefix_lens, seq_lens,
                                   page_table, page_size)
        if from_cache:
            def block(b):
                return paged_walk.latent_pages(
                    kv, j, lax.dynamic_slice_in_dim(page_table, b * G, G,
                                                    axis=1), page_size)
        else:
            def block(b):
                return cols

        o = _attend_pages(q, block, n_blk, Tb, positions, valid, cfg)
        return _attn_out(p, i, o, h.dtype)

    attended = jnp.sum(jnp.where(valid, positions + 1, 0)).astype(jnp.int32)

    def extra(kind, j):
        return jnp.zeros((5,), jnp.int32).at[4].set(
            attended if kind == "global" else 0)

    x = _blocks(p, cfg, llama._embed_rows(p, tokens), window, glob, valid,
                tape, extra)
    return x, valid, (None if cache is None else StateCache(kv, slots))


@jax.named_scope("lm_head")
def _logits(p, x):
    return llama._matmul(p, "lm_head", x).astype(jnp.float32)


def _finish(logits, cache, tape, moe_stats):
    if moe_stats:
        return logits, cache, jnp.stack(tape)
    return logits, cache


def _last(x, idx):
    return jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]


def prefill(p, cfg: MiMoV2Config, tokens, seq_lens, cache, page_table,
            page_size, lora=None, adapter_idx=None, moe_stats=False,
            slot_ids=None):
    """Whole prompts [B,S], right-padded; row ``b`` fills decode slot
    ``slot_ids[b]`` (default: its own index). Returns (last-position
    logits [B,V], cache[, stats])."""
    tape: list | None = [] if moe_stats else None
    x, _, cache = _sequence(
        p, cfg, tokens, jnp.zeros_like(seq_lens), seq_lens, cache,
        page_table, page_size, slot_ids, False, tape)
    return _finish(_logits(p, _last(x, seq_lens - 1)), cache, tape,
                   moe_stats)


def prefill_suffix(p, cfg: MiMoV2Config, tokens, prefix_lens, seq_lens,
                   cache, page_table, page_size, lora=None,
                   adapter_idx=None, moe_stats=False, slot_ids=None):
    """The next chunk of each row's prompt (chunked prefill): global
    layers attend over the page window behind the chunk, window layers
    over the slot's ring and the chunk. ``prefix_lens == 0`` starts the
    slot afresh: nothing of the ring counts."""
    tape: list | None = [] if moe_stats else None
    x, _, cache = _sequence(
        p, cfg, tokens, prefix_lens, seq_lens, cache, page_table,
        page_size, slot_ids, True, tape)
    return _finish(_logits(p, _last(x, seq_lens - prefix_lens - 1)), cache,
                   tape, moe_stats)


def hidden_states(p, cfg: MiMoV2Config, tokens, seq_lens):
    """Mean-pooled final hidden states (the /v1/embeddings path)."""
    x, valid, _ = _sequence(
        p, cfg, tokens, jnp.zeros_like(seq_lens), seq_lens, None, None, 0,
        None, False, None)
    w = valid[..., None].astype(jnp.float32)
    return (x.astype(jnp.float32) * w).sum(1) / jnp.maximum(w.sum(1), 1.0)


def decode_step(p, cfg: MiMoV2Config, tokens, positions, cache, page_table,
                page_size, active, lora=None, adapter_idx=None,
                attn_impl="", mesh=None, walk=None, moe_stats=False):
    """One continuous-batching step; row ``b`` IS decode slot ``b``.
    Inactive rows leave their ring and the pages as they are and read
    neither. ``walk``: this step's plan (made here when the caller has
    none) — the global layers' walk runs on it, and the window layers'
    loop takes its order of the live rows; ``attn_impl`` may name no
    other rung: the window gather knows one width for keys and values,
    no ring and no sink."""
    if attn_impl:
        raise NotImplementedError(
            f"mimo_v2 has no decode attention rung {attn_impl!r}: only "
            "the page walk and the ring loop know its two widths, its "
            "band and its sink")
    tape: list | None = [] if moe_stats else None
    kv, slots = cache.kv, dict(cache.slots)
    pos1 = positions[:, None]
    slot = jnp.where(active, jnp.take_along_axis(
        page_table, pos1 // page_size, axis=1)[:, 0] * page_size
        + positions % page_size, kv.shape[2])
    lengths = jnp.where(active, positions + 1, 0)
    if walk is None:
        walk = kvq.walk_plan(kv, lengths, page_table, page_size, mesh)
    n_live = jnp.sum(active.astype(jnp.int32))
    Gg, Gw = cfg.num_key_value_heads, cfg.swa_num_key_value_heads

    def window(i, j, h):
        # the ring pool comes to the layer WITH the layer's input, as
        # the hybrid family's state pool does: the expert loops between
        # two layers hide from the compiler that every reader of the
        # pool runs before the next layer updates it in place
        h, slots["swa_ring"] = lax.optimization_barrier(
            (h, slots["swa_ring"]))
        q, k, v = _project(p, i, h, cfg, "window")
        q, k = _rotate(q, k, pos1, cfg, "window")
        with jax.named_scope("layer/attn_window"):
            o, slots["swa_ring"] = _ring_live_rows(
                _at_own_head(q[:, 0], Gw), _row(k, v)[:, 0],
                p[f"l{i}.sink"], slots["swa_ring"],
                jnp.asarray(j, jnp.int32), walk.order, n_live, positions,
                n_values=cfg.value_width("window"),
                scale=cfg.softmax_scale)
            o = _own_values(o, Gw)
        return _attn_out(p, i, o[:, None], h.dtype)

    def glob(i, j, h):
        nonlocal kv
        q, k, v = _project(p, i, h, cfg, "global")
        q, k = _rotate(q, k, pos1, cfg, "global")
        kv = axk1._write_step(kv, j, _row(k, v)[:, 0], slot)
        o = paged_walk.latent_decode_walk(
            _at_own_head(q[:, 0], Gg), kv, j, page_table, lengths,
            page_size=page_size, rank=cfg.value_width("global"),
            scale=cfg.softmax_scale, plan=walk,
            keys_from=cfg.value_width("global"))
        return _attn_out(p, i, _own_values(o, Gg)[:, None], h.dtype)

    # what a window layer's softmax saw against what its rows' contexts
    # hold; the rings the loop read are the live rows', no others
    seen = jnp.stack([jnp.sum(jnp.minimum(lengths, cfg.sliding_window)),
                      jnp.sum(lengths)]).astype(jnp.int32)
    read = jnp.stack([n_live, n_live]).astype(jnp.int32)
    zero = jnp.zeros((5,), jnp.int32)

    def extra(kind, j):
        if kind != "window":
            return zero
        return jnp.concatenate([seen, read if j == 0 else zero[:2],
                                zero[:1]])

    x = _blocks(p, cfg, llama._embed_rows(p, tokens[:, None]), window, glob,
                active[:, None], tape, extra)
    return _finish(_logits(p, x[:, 0]), StateCache(kv, slots), tape,
                   moe_stats)
