"""A.X-K1's forward pass, plainly: float32, the highest matmul
precision, one sequence, no cache, no batching, and the EXPANDED form of
latent attention — the latent is decompressed into per-head keys and
values for every token. What the served programs (models/axk1.py: a
latent page, the absorbed form, blocks with an online softmax) are held
to.

Follows the published ``config.json`` (``model_type: axk1``), every key
of which DeepSeek-V3's published modelling code defines; it imports
nothing of the program. ``cfg`` is a mapping of the configuration
dataclass's fields: the published keys plus the share this chip holds of
an expert-parallel deployment — ``num_experts`` experts held, from id
``held_from``, of a router ``router_experts`` wide (0: everything is
held) — and ``first_dense_layers`` (the published
``first_k_dense_replace``). ``forward`` takes the same parameter dict as
the program and the same vocabulary slice (the rows of ``embed`` /
columns of ``lm_head`` that the dict holds).

Departures from the published description, each noted again at its line:
rotary pairs are halves, not interleaved (a permutation of the
projections' columns); YaRN's frequencies apply at every length (the
published code switches them on past the original length only, which a
served deployment always is); ``topk_method: "none"`` is read as no
selection bias and a group's score as its LARGEST expert score; what
absent experts would add is left out, and that partial result goes on to
the next layer.

Every layer: ``h = x + Attn(N(x))``, ``out = h + FFN(N(h))``;
``N(x) = x · rsqrt(mean(x²) + eps) · w``.
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
from jax import lax

f32 = jnp.float32
#: the precision everything is computed in: float32, but for the one
#: reading that shows what a lower precision would give (``forward``'s
#: ``dtype``). Positions and rotary angles stay float32 either way.
_DT = f32


def _p(p, name):
    return p[name].astype(_DT)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_inv_freq(cfg):
    """Frequencies of the ``qk_rope_head_dim / 2`` rotary pairs:
    ``theta^(-2i/d)`` below the ramp, that over ``factor`` above it.
    (The published code applies the correction only when the served
    length exceeds the original one; here it always applies.)
    ``mscale == mscale_all_dim``: the tables carry no factor."""
    rd, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    f = theta ** (-jnp.arange(0, rd, 2, dtype=f32) / rd)
    s = dict(cfg.get("rope_scaling") or ())
    if not s:
        return f
    orig = s["original_max_position_embeddings"]

    def dim_of(rotations):
        return rd * math.log(orig / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    lo = max(math.floor(dim_of(s["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(s["beta_slow"])), rd - 1)
    ramp = jnp.clip((jnp.arange(rd // 2, dtype=f32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / s["factor"] * ramp


def softmax_scale(cfg):
    """``qk_head_dim^-0.5``, times YaRN's ``mscale`` squared."""
    s = dict(cfg.get("rope_scaling") or ())
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if s and s.get("mscale_all_dim"):
        m = 0.1 * s["mscale_all_dim"] * math.log(s["factor"]) + 1.0
        scale *= m * m
    return scale


def rope(x, inv_freq):
    """x [S, ..., d] at positions 0..S-1: rotary on the whole last
    axis, pairs as halves (the published code pairs neighbours: a
    permutation of the projection's columns)."""
    rd = 2 * inv_freq.shape[0]
    ang = jnp.arange(x.shape[0], dtype=f32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (rd,))
    cos, sin = jnp.cos(ang).astype(_DT), jnp.sin(ang).astype(_DT)
    half = jnp.concatenate([-x[..., rd // 2:], x[..., : rd // 2]], -1)
    return x * cos + half * sin


# -- the expert layer -------------------------------------------------------
def route(p, i, cfg, x):
    """x [S, D] → the router's picks over its whole width: weights
    [S, K] and expert ids [S, K]. Sigmoid scores ``s`` in ``n_group``
    groups; a group's score is its LARGEST ``s`` (``topk_method:
    "none"``: no selection bias, and not the sum of its two largest
    that ``noaux_tc`` takes); the ``topk_group`` best groups stay; the
    ``K`` largest ``s`` among them are the token's experts, weighted by
    ``s`` over its sum, times ``routed_scaling_factor``."""
    G, K = cfg["n_group"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ _p(p, f"l{i}.router"))
    sg = s.reshape(x.shape[0], G, -1)
    stays = jnp.zeros(sg.shape[:2], bool).at[
        jnp.arange(x.shape[0])[:, None],
        lax.top_k(jnp.max(sg, -1), cfg["topk_group"])[1]].set(True)
    left = jnp.where(stays[:, :, None], sg, -jnp.inf).reshape(s.shape)
    topi = lax.top_k(left, K)[1]
    topv = jnp.take_along_axis(s, topi, -1)
    if cfg.get("norm_topk_prob", True):
        topv = topv / (jnp.sum(topv, -1, keepdims=True) + 1e-20)
    return topv * cfg["routed_scaling_factor"], topi


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def moe_layer(p, i, cfg, x, held_from=None, num_experts=None):
    """x [S, D] → the part of the routed mixture that experts
    ``held_from .. held_from + num_experts - 1`` give (default: the
    configuration's share). The parameter dict holds the matrices of
    experts ``cfg['held_from'] + arange(cfg['num_experts'])``, flat
    (``[D, E*F]`` / ``[E*F, D]``)."""
    D = x.shape[-1]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    first = cfg.get("held_from", 0)
    held_from = first if held_from is None else held_from
    num_experts = E if num_experts is None else num_experts
    topv, topi = route(p, i, cfg, x)
    wg = _p(p, f"l{i}.experts_gate").reshape(D, E, F)
    wu = _p(p, f"l{i}.experts_up").reshape(D, E, F)
    wd = _p(p, f"l{i}.experts_down").reshape(E, F, D)
    out = jnp.zeros_like(x)
    for e in range(held_from, held_from + num_experts):
        weight = jnp.sum(jnp.where(topi == e, topv, 0.0), -1)  # [S]
        j = e - first  # where the dict keeps expert e
        out = out + weight[:, None] * _swiglu(x, wg[:, j], wu[:, j], wd[j])
    return out


def shared_expert(p, i, x):
    """Always on; no gate on it in this family."""
    return _swiglu(x, _p(p, f"l{i}.shared_gate"), _p(p, f"l{i}.shared_up"),
                   _p(p, f"l{i}.shared_down"))


def dense_mlp(p, i, x):
    return _swiglu(x, _p(p, f"l{i}.w_gate"), _p(p, f"l{i}.w_up"),
                   _p(p, f"l{i}.w_down"))


# -- latent attention -------------------------------------------------------
def latent_rows(p, i, cfg, x, inv_freq):
    """x [S, D] → what a token leaves behind: the normalised latent
    ``c_kv`` [S, r] and the ONE rotated key ``k_rope`` [S, rope] that
    all heads share."""
    r = cfg["kv_lora_rank"]
    ckr = x @ _p(p, f"l{i}.wkv_a")
    return (rms_norm(ckr[:, :r], _p(p, f"l{i}.kv_norm"),
                     cfg["rms_norm_eps"]), rope(ckr[:, r:], inv_freq))


def attention(p, i, cfg, x, block=None):
    """x [S, D] → [S, D], the expanded form. ``block``: queries taken
    at a time (the same numbers; less memory at the published widths)."""
    S = x.shape[0]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    inv_freq = yarn_inv_freq(cfg)
    cq = rms_norm(x @ _p(p, f"l{i}.wq_a"), _p(p, f"l{i}.q_norm"),
                  cfg["rms_norm_eps"])
    q = (cq @ _p(p, f"l{i}.wq_b")).reshape(S, H, -1)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], inv_freq)
    c_kv, k_rope = latent_rows(p, i, cfg, x, inv_freq)
    kvb = _p(p, f"l{i}.wkv_b").reshape(r, H, dn + dv)
    k_nope = jnp.einsum("tc,chd->thd", c_kv, kvb[..., :dn])
    v = jnp.einsum("tc,chd->thd", c_kv, kvb[..., dn:])
    scale = softmax_scale(cfg)
    outs = []
    for t0 in range(0, S, block or S):
        t1 = min(S, t0 + (block or S))
        s = (jnp.einsum("shd,thd->hst", q_nope[t0:t1], k_nope[:t1])
             + jnp.einsum("shd,td->hst", q_rope[t0:t1], k_rope[:t1])) * scale
        causal = jnp.arange(t1)[None, :] <= jnp.arange(t0, t1)[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        outs.append(jnp.einsum("hst,thd->shd", probs, v[:t1]))
    return jnp.concatenate(outs).reshape(S, H * dv) @ _p(p, f"l{i}.wo")


def layer(p, i, cfg, x, block=None):
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, _p(p, f"l{i}.in_norm"), eps)
    x = x + attention(p, i, cfg, h, block)
    h = rms_norm(x, _p(p, f"l{i}.post_norm"), eps)
    if i < cfg["first_dense_layers"]:
        return x + dense_mlp(p, i, h)
    return x + moe_layer(p, i, cfg, h) + shared_expert(p, i, h)


@contextlib.contextmanager
def computed_in(dtype):
    """Everything inside is computed in ``dtype`` (parameters are cast
    to it) at the highest matmul precision: float32 is the reference,
    anything else the reading that a tolerance must tell from it."""
    global _DT
    _DT = dtype
    try:
        with jax.default_matmul_precision("highest"):
            yield
    finally:
        _DT = f32


def forward(p, cfg, tokens, dtype=f32, positions=None, block=None):
    """tokens [S] → logits [S, V] over the vocabulary slice held, or
    at ``positions`` only. ``dtype``: see :func:`computed_in`;
    ``block``: see :func:`attention`."""
    with computed_in(dtype):
        x = p["embed"][jnp.asarray(tokens)].astype(_DT)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(p, i, cfg, x, block)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = rms_norm(x, _p(p, "norm_f"), cfg["rms_norm_eps"])
        return x @ _p(p, "lm_head")
