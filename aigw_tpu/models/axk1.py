"""A.X-K1 family (``model_type: axk1``): multi-head latent attention
(MLA), leading dense layers, then expert layers behind a group-limited
sigmoid router with an always-on shared expert.

From the published ``config.json``, every key of which the DeepSeek-V3
modelling code defines; the float32 reference of the same equations is
models/reference/axk1_ref.py. A layer is ``x += Attn(N(x)); x +=
FFN(N(x))`` with ``N`` a plain-weight RMSNorm; the first
``first_dense_layers`` layers' FFN is a SwiGLU, the others' the expert
layer.

What this family keeps on the device (models/cache.py): ONE row a token
a layer and no K, V or heads — the normalised latent (``kv_lora_rank``)
and the one rotated key all heads share (``qk_rope_head_dim``), stored
``c_kv | k_rope`` (576 values at the published widths) down a column
of a latent pool ``[layers, 576, rows]``: tokens lie along the lanes,
the layout the chip's compiler gives a 576-wide row whatever it is
handed (576 is no multiple of its 128 lanes; handed ``[rows, 576]`` it
copied the pool into this layout and back in every program).

Attention over cached columns runs in the ABSORBED form in both kinds of
program: ``W_kvb``'s key half is folded into the query, its value half
is applied to the attended latent, so no per-head key or value ever
exists, and every product takes a block of columns as it lies. A decode
step walks the pages its live rows hold (ops/paged_walk.py, the step's
``WalkPlan``), each page read once; a chunk attends, blockwise with an
online softmax, over the page window behind it (and a whole prompt over
itself), so nothing ``[heads, S, context]`` is live at once. The expanded
(per-head) form is the reference's; tests/test_axk1.py holds the two
equal, and PERF.md section 6 (PR 45) has the chip's timing of both for a
chunk and why the slower one stands.

The expert layer is told which experts it holds (``held_from``,
``num_experts`` of them), routes over the router's whole published width
and groups, and shares the held-expert pass of models/qwen3_next.py
(dense over a chunk, a loop over the hit experts in a decode step); only
the scoring is this family's. The shared expert has no gate.

Departures from the checkpoint (a loader permutes; the mathematics is the
source's): rotary pairs as halves rather than interleaved, ``wkv_b`` as
``[c, head, k_nope | v]``, expert matrices flat (``[D, E*F]``,
``[E*F, D]``). ``topk_method: "none"`` is read as: no selection bias,
and a group's score is its largest expert score.

TWO TREES, and the contract between them. :func:`init_params` gives a
CHECKPOINT's tree: per layer ``wq_b`` ``[q_lora, H*(nope+rope)]`` and
``wkv_b`` ``[kv_lora, H*(nope+v)]`` as published (the references, a
restore's ``like`` tree and cellbench/reference_check_latent.py read
those names and shapes). :func:`serving_params` turns it ONCE, where a
replica has loaded its weights (``ModelFns.serving_params``, called by
``tpuserve/server.py`` ``_load_params`` after every weight source), into
the tree the programs read, and drops the two published leaves:

  ``wq_nope`` ``[H, nope, q_lora]``, ``wq_rope`` ``[H, rope, q_lora]``
      (``bsq,hdq->bshd``): ``wq_b`` cut by output columns, head-major
  ``w_uk`` ``[H, kv_lora, nope]`` (``bshd,hcd->bshc``) and
  ``w_uv`` ``[H, v, kv_lora]`` (``bshc,hvc->bshv``): ``wkv_b``'s halves

each stored as its product contracts it, so that no compiled program
re-lays a weight out (read as published, the chip's compiler transposed
both matrices of every layer in EVERY call of every program, 327 MB a
call: tests/test_pallas_tpu_aot.py holds it gone). Every output element
is the same sum of the same products. A loader written later keeps
handing ``init_params``' tree to ``serving_params``; it never builds the
serving leaves itself. ``_mla_q``, :func:`absorb` and ``_mla_out`` read
whichever form the tree they are given holds — which they can see — so
a tree that still has ``wq_b`` / ``wkv_b`` works, slower on the chip.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from aigw_tpu.models import kvq, llama, qwen3_next
from aigw_tpu.models.cache import CacheSpec
from aigw_tpu.ops import paged_walk

_HI = lax.Precision.HIGHEST
#: a masked logit (finite: an online softmax subtracts it from itself)
_MASKED = -1e30

YARN_AXK1 = (("beta_fast", 32), ("beta_slow", 1), ("factor", 32),
             ("mscale", 1), ("mscale_all_dim", 1),
             ("original_max_position_embeddings", 4096), ("type", "yarn"))


@dataclass(frozen=True)
class AXK1Config:
    # every field takes a key of the published config.json …
    vocab_size: int = 163840
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    intermediate_size: int = 18432
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    #: routed experts HELD here (``n_routed_experts`` when all are held)
    num_experts: int = 192
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: the published group, whole (a dict, or its sorted items); None:
    #: plain rotary
    rope_scaling: Any = YARN_AXK1
    max_position_embeddings: int = 131072
    # … but these, the program's own: the router's width (0 = the
    # experts held, i.e. nothing is absent), the first held expert, and
    # the leading dense layers (``first_k_dense_replace``)
    router_experts: int = 0
    held_from: int = 0
    first_dense_layers: int = 1

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):  # hashable, as the rest
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if self.router_width % self.n_group:
            raise ValueError(
                f"{self.router_width} router outputs do not split into "
                f"{self.n_group} groups")

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
        return 1  # one latent row a token: no heads in the cache

    @property
    def head_dim(self) -> int:
        return self.cache_row

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def n_experts(self) -> int:
        return self.num_experts

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        return tuple("dense" if i < self.first_dense_layers else "moe"
                     for i in range(self.num_hidden_layers))

    @property
    def cache_row(self) -> int:
        """Values a token leaves in a layer: latent | rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def tape_extra(self) -> tuple[str, ...]:
        """The EngineStats counters a layer's last columns feed, each
        summed over the layers (``Engine._fold_moe``): of the groups a
        real token kept, those that hold a held expert, and the groups
        kept (expert layers); the keys a prefill program's real queries
        attended to (every layer; a decode window's are its pages)."""
        return ("moe_groups_kept_hits", "moe_group_slots",
                "prefill_keys_attended")

    @property
    def moe_tape_width(self) -> int:
        """Columns of one layer's stats row: the shared held-expert
        pass's (assignments on each held expert, dropped, every
        assignment routed, held experts hit; zeros in a dense layer's
        row), then ``tape_extra``."""
        return self.num_experts + 3 + len(self.tape_extra)

    def cache_spec(self) -> CacheSpec:
        return CacheSpec(self.num_hidden_layers, 1, self.cache_row,
                         latent=True)

    @property
    def softmax_scale(self) -> float:
        s = dict(self.rope_scaling or ())
        scale = self.qk_head_dim ** -0.5
        if s and s.get("mscale_all_dim"):
            m = 0.1 * s["mscale_all_dim"] * math.log(s["factor"]) + 1.0
            scale *= m * m
        return scale


#: one dense layer + three expert layers at toy widths with the
#: published RATIOS (nope : rope : v = 2 : 1 : 2, groups of experts, a
#: leading dense layer): 16 experts in 4 groups of which 2, top-4, all
#: held
TINY = AXK1Config(
    vocab_size=512, hidden_size=64, num_hidden_layers=4,
    intermediate_size=128, num_attention_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=32, n_group=4, topk_group=2,
    max_position_embeddings=512,
    rope_scaling=(("beta_fast", 32), ("beta_slow", 1), ("factor", 32),
                  ("mscale", 1), ("mscale_all_dim", 1),
                  ("original_max_position_embeddings", 64),
                  ("type", "yarn")),
)


def yarn_inv_freq(cfg: AXK1Config) -> np.ndarray:
    """The rotary frequencies of the ``qk_rope_head_dim / 2`` pairs,
    float32: plain below the ramp, divided by ``factor`` above it.
    ``mscale == mscale_all_dim``, so the tables carry no factor of
    their own (it is in ``softmax_scale``)."""
    rd = cfg.qk_rope_head_dim
    f = cfg.rope_theta ** (-np.arange(0, rd, 2, dtype=np.float64) / rd)
    s = dict(cfg.rope_scaling or ())
    if not s:
        return f.astype(np.float32)
    orig = s["original_max_position_embeddings"]

    def dim_of(rotations):
        return rd * math.log(orig / (2 * math.pi * rotations)) \
            / (2 * math.log(cfg.rope_theta))

    lo = max(math.floor(dim_of(s["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(s["beta_slow"])), rd - 1)
    ramp = np.clip((np.arange(rd // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (f * (1 - ramp) + f / s["factor"] * ramp).astype(np.float32)


def init_params(key: jax.Array, cfg: AXK1Config, dtype=jnp.bfloat16,
                sharding_of=None, finish=None) -> dict[str, jax.Array]:
    """Random-init weights; the placement hooks are
    :class:`llama.ParamBuilder`'s. Norm weights 1."""
    b = llama.ParamBuilder(key, 4 + cfg.num_hidden_layers * 16, dtype,
                           sharding_of, finish)
    D, H = cfg.hidden_size, cfg.num_attention_heads
    E, F, Fs = cfg.num_experts, cfg.moe_intermediate_size, cfg.shared_width
    b.dense("embed", (cfg.vocab_size, D), scale=0.02)
    b.const("norm_f", (D,), 1.0)
    b.dense("lm_head", (D, cfg.vocab_size))
    for i, kind in enumerate(cfg.layer_kinds):
        b.const(f"l{i}.in_norm", (D,), 1.0)
        b.dense(f"l{i}.wq_a", (D, cfg.q_lora_rank))
        b.const(f"l{i}.q_norm", (cfg.q_lora_rank,), 1.0)
        b.dense(f"l{i}.wq_b", (cfg.q_lora_rank, H * cfg.qk_head_dim))
        b.dense(f"l{i}.wkv_a", (D, cfg.cache_row))
        b.const(f"l{i}.kv_norm", (cfg.kv_lora_rank,), 1.0)
        b.dense(f"l{i}.wkv_b", (cfg.kv_lora_rank,
                                H * (cfg.qk_nope_head_dim + cfg.v_head_dim)))
        b.dense(f"l{i}.wo", (H * cfg.v_head_dim, D))
        b.const(f"l{i}.post_norm", (D,), 1.0)
        if kind == "dense":
            b.dense(f"l{i}.w_gate", (D, cfg.intermediate_size))
            b.dense(f"l{i}.w_up", (D, cfg.intermediate_size))
            b.dense(f"l{i}.w_down", (cfg.intermediate_size, D))
            continue
        b.dense(f"l{i}.router", (D, cfg.router_width))
        b.dense(f"l{i}.experts_gate", (D, E * F))
        b.dense(f"l{i}.experts_up", (D, E * F))
        b.dense(f"l{i}.experts_down", (E * F, D), scale=1.0 / math.sqrt(F))
        b.dense(f"l{i}.shared_gate", (D, Fs))
        b.dense(f"l{i}.shared_up", (D, Fs))
        b.dense(f"l{i}.shared_down", (Fs, D))
    return b.params


def _rope(x: jax.Array, positions: jax.Array,
          inv_freq: np.ndarray) -> jax.Array:
    """Rotary over the whole last axis, pairs as halves (``x[j]`` with
    ``x[j + d/2]``). x: [B, S, ..., d]; positions [B, S]."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# -- the router -------------------------------------------------------------
def pick(s: jax.Array, cfg: AXK1Config):
    """The group-limited top-k over scores ``s`` [T, router width]: a
    group's score is its LARGEST expert score, the ``topk_group`` best
    groups stay, the ``num_experts_per_tok`` best experts among them
    are the picks, weighted by their score over the picks' sum, times
    ``routed_scaling_factor``. → (weights [T, K], expert ids [T, K],
    the groups kept [T, G] bool)."""
    T = s.shape[0]
    G, K = cfg.n_group, cfg.num_experts_per_tok
    sg = s.reshape(T, G, -1)
    best = lax.top_k(jnp.max(sg, axis=-1), cfg.topk_group)[1]
    kept = jnp.sum(jax.nn.one_hot(best, G, dtype=jnp.int32), axis=1) > 0
    # (sigmoid scores are positive: a dropped group's 0 never wins)
    topv, topi = lax.top_k(
        jnp.where(kept[:, :, None], sg, 0.0).reshape(T, -1), K)
    if cfg.norm_topk_prob:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    return topv * cfg.routed_scaling_factor, topi, kept


def route(p: dict, i: int, xt: jax.Array, cfg: AXK1Config):
    """Sigmoid scores over the router's WHOLE width, then :func:`pick`.
    The pick is discrete — a rounded score picks another expert, which
    is another model — so the router runs in float32 at the highest
    precision. xt [T, D]."""
    return pick(jax.nn.sigmoid(jnp.dot(
        xt.astype(jnp.float32), p[f"l{i}.router"].astype(jnp.float32),
        precision=_HI)), cfg)


def _groups_counted(kept: jax.Array, real: jax.Array,
                    cfg: AXK1Config) -> jax.Array:
    """[kept groups that hold a held expert, groups kept] over the real
    tokens: what the group limit does to this share's load."""
    size = cfg.router_width // cfg.n_group
    g = jnp.arange(cfg.n_group, dtype=jnp.int32)
    holds = ((g + 1) * size > cfg.held_from) \
        & (g * size < cfg.held_from + cfg.num_experts)  # [G]
    on = kept & real[:, None]
    return jnp.stack([jnp.sum(on & holds[None, :]),
                      jnp.sum(on)]).astype(jnp.int32)


def moe(p: dict, i: int, x: jax.Array, cfg: AXK1Config,
        valid: jax.Array | None = None,
        tape: list | None = None) -> jax.Array:
    """The ungated shared expert + the held experts' part of the routed
    mixture: this family's scoring in front of the held-expert pass the
    hybrid family shares (a chunk runs the held experts densely, a
    decode step loops over those its live rows hit)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    with jax.named_scope("layer/moe_route"):
        topv, topi, kept = route(p, i, xt, cfg)
    out = qwen3_next.held_experts(
        p, i, xt, topv, topi, cfg, valid, tape, step=S == 1,
        shared_gate=False).reshape(B, S, D)
    if tape is not None:
        real = (jnp.ones((B * S,), bool) if valid is None
                else valid.reshape(B * S))
        tape[-1] = jnp.concatenate(
            [tape[-1], _groups_counted(kept, real, cfg)])
    return out


# -- latent attention -------------------------------------------------------
@functools.partial(jax.jit, static_argnums=2)
def _relay(wq_b, wkv_b, cfg):
    """One layer's four serving leaves out of its two published ones."""
    H, dn = cfg.num_attention_heads, cfg.qk_nope_head_dim
    q = wq_b.reshape(cfg.q_lora_rank, H, cfg.qk_head_dim)
    kv = wkv_b.reshape(cfg.kv_lora_rank, H, dn + cfg.v_head_dim)
    return (jnp.transpose(q[..., :dn], (1, 2, 0)),
            jnp.transpose(q[..., dn:], (1, 2, 0)),
            jnp.transpose(kv[..., :dn], (1, 0, 2)),
            jnp.transpose(kv[..., dn:], (1, 2, 0)))


def serving_params(p: dict, cfg: AXK1Config) -> dict:
    """The tree the programs read, out of :func:`init_params`' (a
    checkpoint's) tree: every layer's ``wq_b`` and ``wkv_b`` laid out
    ONCE as the four operands their products contract (module
    docstring), a layer at a time, the published pair left out of the
    tree returned (``p`` is not touched: the caller lets go of it, and
    until then one copy of the twelve matrices, 0.33 GB at the
    published widths, stands beside the weights — at load, before any
    pool). A layer whose pair is not there as plain matrices (already
    laid out) stays as it is: the programs read whichever form the
    tree holds."""
    out = dict(p)
    for i in range(cfg.num_hidden_layers):
        wq_b, wkv_b = f"l{i}.wq_b", f"l{i}.wkv_b"
        if wq_b in out and wkv_b in out:
            (out[f"l{i}.wq_nope"], out[f"l{i}.wq_rope"], out[f"l{i}.w_uk"],
             out[f"l{i}.w_uv"]) = _relay(out.pop(wq_b), out.pop(wkv_b), cfg)
    return out


def _kvb(p, i, cfg):
    """``W_kvb`` as [c, head, k_nope | v], of a tree that holds the
    published leaf."""
    return llama._w(p, f"l{i}.wkv_b").reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)


@jax.named_scope("layer/mla_q")
def _mla_q(p, i, h, positions, cfg, inv_freq):
    """→ the ABSORBED query [B,S,H,cache_row]: each head's ``q_nope``
    folded through ``W_kvb``'s key half onto the latent, its rotated
    ``q_rope`` beside it."""
    cq = llama.rms_norm(llama._matmul(p, f"l{i}.wq_a", h),
                        p[f"l{i}.q_norm"], cfg.rms_norm_eps)
    if f"l{i}.wq_nope" in p:
        q_nope = jnp.einsum("bsq,hdq->bshd", cq, p[f"l{i}.wq_nope"])
        q_rope = jnp.einsum("bsq,hdq->bshd", cq, p[f"l{i}.wq_rope"])
    else:
        q = llama._matmul(p, f"l{i}.wq_b", cq).reshape(
            *h.shape[:2], cfg.num_attention_heads, cfg.qk_head_dim)
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_rope = q[..., cfg.qk_nope_head_dim:]
    return absorb(p, i, q_nope, _rope(q_rope, positions, inv_freq), cfg)


def absorb(p, i, q_nope, q_rope, cfg):
    """Each head's ``q_nope`` [B,S,H,nope] folded through ``W_kvb``'s
    key half onto the latent, its rotated ``q_rope`` beside it."""
    if f"l{i}.w_uk" in p:
        eq, w_uk = "bshd,hcd->bshc", p[f"l{i}.w_uk"]
    else:
        eq = "bshd,chd->bshc"
        w_uk = _kvb(p, i, cfg)[..., :cfg.qk_nope_head_dim]
    q_lat = jnp.einsum(eq, q_nope, w_uk,
                       preferred_element_type=jnp.float32).astype(q_nope.dtype)
    return jnp.concatenate([q_lat, q_rope], axis=-1)


@jax.named_scope("layer/mla_kv")
def _mla_kv(p, i, h, positions, cfg, inv_freq):
    """→ a token's page row ``c_kv | k_rope`` [B,S,cache_row]: the
    normalised latent and the one rotated key all heads share."""
    r = cfg.kv_lora_rank
    ckr = llama._matmul(p, f"l{i}.wkv_a", h)
    c_kv = llama.rms_norm(ckr[..., :r], p[f"l{i}.kv_norm"],
                          cfg.rms_norm_eps)
    return jnp.concatenate(
        [c_kv, _rope(ckr[..., r:], positions, inv_freq)], axis=-1)


@jax.named_scope("layer/mla_attn")
def _attend_blocks(q_abs, block, n_blk, Tb, positions, valid, cfg):
    """Absorbed causal attention of a chunk's queries over cached
    columns that come ``Tb`` at a time (``block(j)`` [B,cache_row,Tb];
    the first ``n_blk`` blocks hold every key a query may see), with an
    online softmax: nothing ``[heads, S, context]`` is live at once, and
    every product takes a block as it lies, tokens along the lanes. (A
    block EXPANDED through ``W_kvb`` into per-head keys and values, for
    the chunk's queries to share, was 16 % faster alone on the chip and
    made the compiler re-lay the whole POOL out for it, a copy of it a
    layer: PERF.md section 6, PR 45.) → the attended latent
    [B,S,H,kv_lora_rank] float32."""
    B, S, H, _ = q_abs.shape
    r = cfg.kv_lora_rank
    at = jnp.arange(Tb, dtype=jnp.int32)

    def attend(j, carry):
        m, den, acc = carry
        cols = block(j)
        logits = jnp.einsum(
            "bshc,bct->bsht", q_abs, cols,
            preferred_element_type=jnp.float32) * cfg.softmax_scale
        on = ((j * Tb + at)[None, None, :] <= positions[:, :, None]) \
            & valid[:, :, None]
        logits = jnp.where(on[:, :, None, :], logits, _MASKED)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        a = jnp.exp(m - m_new)
        e = jnp.exp(logits - m_new[..., None])
        acc = acc * a[..., None] + jnp.einsum(
            "bsht,bct->bshc", e.astype(cols.dtype), cols[:, :r],
            preferred_element_type=jnp.float32)
        return m_new, den * a + jnp.sum(e, axis=-1), acc

    m, den, acc = lax.fori_loop(0, n_blk, attend, (
        jnp.full((B, S, H), _MASKED, jnp.float32),
        jnp.zeros((B, S, H), jnp.float32),
        jnp.zeros((B, S, H, r), jnp.float32)))
    return acc / jnp.maximum(den, 1e-30)[..., None]


@jax.named_scope("layer/mla_out")
def _mla_out(p, i, o_lat, cfg, dtype):
    """``W_kvb``'s value half on the attended latent [B,S,H,r], then
    the output projection."""
    B, S = o_lat.shape[:2]
    if f"l{i}.w_uv" in p:
        eq, w_uv = "bshc,hvc->bshv", p[f"l{i}.w_uv"]
    else:
        eq = "bshc,chv->bshv"
        w_uv = _kvb(p, i, cfg)[..., cfg.qk_nope_head_dim:]
    o = jnp.einsum(eq, o_lat.astype(dtype), w_uv,
                   preferred_element_type=jnp.float32).astype(dtype)
    return llama._matmul(p, f"l{i}.wo", o.reshape(B, S, -1))


def _write_chunk(kv, i, cols, prefix_lens, seq_lens, page_table,
                 page_size):
    """Write a chunk's page columns ``cols`` [B,W,S] (tokens
    ``prefix_lens + arange(S)``, real below ``seq_lens``) into layer
    ``i`` of the pool, a whole page at a time: each page the chunk can
    straddle is read, the chunk's real tokens laid over it, and written
    back in place. (A scatter of token columns makes the chip's
    compiler keep the pool in another layout and copy it, whole, in and
    out of every layer: PERF.md section 6, PR 45.)"""
    B, W, S = cols.shape
    P = page_table.shape[1]
    n_touch = -(-S // page_size) + 1  # pages a chunk can straddle
    padded = jnp.pad(cols.astype(kv.dtype),
                     ((0, 0), (0, 0), (page_size, 2 * page_size)))
    at = jnp.arange(page_size, dtype=jnp.int32)
    for b in range(B):
        start = prefix_lens[b]
        end = jnp.minimum(seq_lens[b], start + S)
        for k in range(n_touch):
            lp = start // page_size + k  # the row's k-th page touched
            at_row = page_table[b, jnp.minimum(lp, P - 1)] * page_size
            tok = lp * page_size + at
            on = (tok >= start) & (tok < end) & (lp < P)
            new = lax.dynamic_slice(
                padded[b], (0, page_size + lp * page_size - start),
                (W, page_size))
            old = lax.dynamic_slice(kv, (i, 0, at_row), (1, W, page_size))
            kv = lax.dynamic_update_slice(
                kv, jnp.where(on[None, None, :], new[None], old),
                (i, 0, at_row))
    return kv


def _write_step(kv, i, cols, slot):
    """Write a decode step's columns ``cols`` [B,W] at pool rows
    ``slot`` [B] of layer ``i``, in place, one row at a time. A row
    that is not active names the row past the pool's end and lands, the
    index clamped, on the last column of the pool's dump page, which no
    page table names."""
    for b in range(cols.shape[0]):
        kv = lax.dynamic_update_slice(
            kv, cols[b].astype(kv.dtype)[None, :, None], (i, 0, slot[b]))
    return kv


# -- the block skeleton -----------------------------------------------------
def _blocks(p, cfg, x, attn, valid, tape, attended):
    """Every layer of the stack; ``attn(i, h)`` mixes tokens in layer
    ``i``. ``attended`` [1]: the last column of a layer's ``tape`` row
    (a dense layer's other columns hold zeros)."""
    none = jnp.zeros((cfg.moe_tape_width - 1,), jnp.int32)
    for i, kind in enumerate(cfg.layer_kinds):
        x = x + attn(i, llama.rms_norm(x, p[f"l{i}.in_norm"],
                                       cfg.rms_norm_eps))
        h = llama.rms_norm(x, p[f"l{i}.post_norm"], cfg.rms_norm_eps)
        if kind == "dense":
            x = x + llama._mlp(p, i, h)
            if tape is not None:
                tape.append(none)
        else:
            x = x + moe(p, i, h, cfg, valid, tape)
        if tape is not None:
            tape[-1] = jnp.concatenate([tape[-1], attended])
    return llama.rms_norm(x, p["norm_f"], cfg.rms_norm_eps)


def _sequence(p, cfg, tokens, prefix_lens, seq_lens, kv, page_table,
              page_size, from_pages, tape):
    """A chunk of every row's sequence: tokens [B,S] at positions
    ``prefix_lens + arange(S)``, real where below ``seq_lens``. With a
    cache every layer appends its rows; ``from_pages``, the queries see
    the page window behind them, else the chunk alone. Returns (final
    hidden [B,S,D], valid [B,S], kv)."""
    B, S = tokens.shape
    positions = prefix_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    valid = positions < seq_lens[:, None]
    inv_freq = yarn_inv_freq(cfg)
    if from_pages:
        P = page_table.shape[1]
        G = math.gcd(P, 4)  # pages a block: [S, heads, G*page] logits
        Tb = G * page_size
        n_blk = jnp.minimum(-(-jnp.max(seq_lens) // Tb), P // G)
    else:
        Tb, n_blk = S, 1  # the chunk alone: its positions start at 0

    def attn(i, h):
        nonlocal kv
        q_abs = _mla_q(p, i, h, positions, cfg, inv_freq)
        rows = jnp.swapaxes(
            _mla_kv(p, i, h, positions, cfg, inv_freq), 1, 2)  # [B,W,S]
        if kv is not None:
            kv = _write_chunk(kv, i, rows, prefix_lens, seq_lens,
                              page_table, page_size)
        if from_pages:
            def block(j):
                return paged_walk.latent_pages(
                    kv, i, lax.dynamic_slice_in_dim(page_table, j * G, G,
                                                    axis=1), page_size)
        else:
            def block(j):
                return rows

        o_lat = _attend_blocks(q_abs, block, n_blk, Tb, positions, valid,
                               cfg)
        return _mla_out(p, i, o_lat, cfg, h.dtype)

    attended = jnp.sum(jnp.where(valid, positions + 1, 0))[None].astype(
        jnp.int32)
    x = _blocks(p, cfg, llama._embed_rows(p, tokens), attn, valid, tape,
                attended)
    return x, valid, kv


@jax.named_scope("lm_head")
def _logits(p, x):
    return llama._matmul(p, "lm_head", x).astype(jnp.float32)


def _finish(logits, kv, tape, moe_stats):
    if moe_stats:
        return logits, kv, jnp.stack(tape)
    return logits, kv


def _last(x, idx):
    return jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]


def prefill(p, cfg: AXK1Config, tokens, seq_lens, cache, page_table,
            page_size, lora=None, adapter_idx=None, moe_stats=False):
    """Whole prompts [B,S], right-padded. Returns (last-position logits
    [B,V], cache[, stats])."""
    tape: list | None = [] if moe_stats else None
    x, _, cache = _sequence(
        p, cfg, tokens, jnp.zeros_like(seq_lens), seq_lens, cache,
        page_table, page_size, False, tape)
    return _finish(_logits(p, _last(x, seq_lens - 1)), cache, tape,
                   moe_stats)


def prefill_suffix(p, cfg: AXK1Config, tokens, prefix_lens, seq_lens,
                   cache, page_table, page_size, lora=None,
                   adapter_idx=None, moe_stats=False):
    """The next chunk of each row's prompt (chunked prefill): its
    queries attend over the page window behind them."""
    tape: list | None = [] if moe_stats else None
    x, _, cache = _sequence(
        p, cfg, tokens, prefix_lens, seq_lens, cache, page_table,
        page_size, True, tape)
    return _finish(_logits(p, _last(x, seq_lens - prefix_lens - 1)), cache,
                   tape, moe_stats)


def hidden_states(p, cfg: AXK1Config, tokens, seq_lens):
    """Mean-pooled final hidden states (the /v1/embeddings path)."""
    x, valid, _ = _sequence(
        p, cfg, tokens, jnp.zeros_like(seq_lens), seq_lens, None, None, 0,
        False, None)
    w = valid[..., None].astype(jnp.float32)
    return (x.astype(jnp.float32) * w).sum(1) / jnp.maximum(w.sum(1), 1.0)


def decode_step(p, cfg: AXK1Config, tokens, positions, cache, page_table,
                page_size, active, lora=None, adapter_idx=None,
                attn_impl="", mesh=None, walk=None, moe_stats=False):
    """One continuous-batching step; row ``b`` IS decode slot ``b``.
    Inactive rows append nothing and read nothing. ``walk``: this
    step's plan (made here when the caller has none); ``attn_impl`` may
    name no other rung: the window gather reads K and V rows, and the
    pool holds latent rows."""
    if attn_impl:
        raise NotImplementedError(
            f"axk1 has no decode attention rung {attn_impl!r}: its pool "
            "holds latent rows, which only the page walk reads")
    tape: list | None = [] if moe_stats else None
    kv = cache
    pos1 = positions[:, None]
    slot = jnp.where(active, jnp.take_along_axis(
        page_table, pos1 // page_size, axis=1)[:, 0] * page_size
        + positions % page_size, kv.shape[2])
    lengths = jnp.where(active, positions + 1, 0)
    if walk is None:
        walk = kvq.walk_plan(kv, lengths, page_table, page_size, mesh)
    inv_freq = yarn_inv_freq(cfg)

    def attn(i, h):
        nonlocal kv
        q_abs = _mla_q(p, i, h, pos1, cfg, inv_freq)
        rows = _mla_kv(p, i, h, pos1, cfg, inv_freq)
        kv = _write_step(kv, i, rows[:, 0], slot)
        o_lat = paged_walk.latent_decode_walk(
            q_abs[:, 0], kv, i, page_table, lengths, page_size=page_size,
            rank=cfg.kv_lora_rank, scale=cfg.softmax_scale, plan=walk)
        return _mla_out(p, i, o_lat[:, None], cfg, h.dtype)

    # (a decode window's keys are counted as the pages its walk reads)
    x = _blocks(p, cfg, llama._embed_rows(p, tokens[:, None]), attn,
                active[:, None], tape, jnp.zeros((1,), jnp.int32))
    return _finish(_logits(p, x[:, 0]), kv, tape, moe_stats)
