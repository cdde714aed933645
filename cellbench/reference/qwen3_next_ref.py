"""Qwen3-Next's forward pass, plainly: float32, the highest matmul
precision, one sequence, no cache, no batching, no chunked form — the
Gated DeltaNet recurrence runs token by token. What the served programs
(models/qwen3_next.py) are held to.

Follows the published ``config.json`` (``model_type: qwen3_next``) and
imports nothing of the program. ``cfg`` is a mapping of the published
keys plus the share this chip holds of an expert-parallel deployment:
``num_experts`` experts held, from id ``held_from``, of a router
``router_experts`` wide (0: everything is held). ``forward`` takes the
same parameter dict as the program and the same vocabulary slice (the
rows of ``embed`` / columns of ``lm_head`` that the dict holds).

Departure from the checkpoint, here as in the program: the
multi-token-prediction module is a draft source and is not part of the
logits; what absent experts would add is left out, and that partial
result goes on to the next layer.

Every layer: ``h = x + Mixer(N(x))``, ``out = h + MoE(N(h))``;
``N(x) = x · rsqrt(mean(x²) + eps) · (1 + w)``.
"""

from __future__ import annotations

import contextlib

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
    """Zero-centred weight: scale by (1 + w)."""
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def route(p, i, cfg, x):
    """x [S, D] → the router's picks: weights [S, K] (renormalised to
    sum 1) and expert ids [S, K], over the router's whole width."""
    probs = jax.nn.softmax(x @ _p(p, f"l{i}.router"), axis=-1)
    topv, topi = lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        topv = topv / jnp.sum(topv, -1, keepdims=True)
    return topv, topi


def moe_layer(p, i, cfg, x, held_from=None, held_count=None):
    """x [S, D] → the shared expert plus the held experts' part of the
    routed mixture. ``held_from`` / ``held_count`` default to the
    configuration's share; the parameter dict holds the matrices of
    experts ``cfg['held_from'] + arange(cfg['num_experts'])``, flat
    (``[D, E*F]`` / ``[E*F, D]``)."""
    D = x.shape[-1]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    first = cfg.get("held_from", 0)
    held_from = first if held_from is None else held_from
    held_count = E if held_count is None else held_count
    topv, topi = route(p, i, cfg, x)
    wg = _p(p, f"l{i}.experts_gate").reshape(D, E, F)
    wu = _p(p, f"l{i}.experts_up").reshape(D, E, F)
    wd = _p(p, f"l{i}.experts_down").reshape(E, F, D)
    out = jnp.zeros_like(x)
    for e in range(held_from, held_from + held_count):
        weight = jnp.sum(jnp.where(topi == e, topv, 0.0), -1)  # [S]
        j = e - first  # where the dict keeps expert e
        y = (jax.nn.silu(x @ wg[:, j]) * (x @ wu[:, j])) @ wd[j]
        out = out + weight[:, None] * y
    return out


def shared_expert(p, i, x):
    y = (jax.nn.silu(x @ _p(p, f"l{i}.shared_gate"))
         * (x @ _p(p, f"l{i}.shared_up"))) @ _p(p, f"l{i}.shared_down")
    return y * jax.nn.sigmoid(x @ _p(p, f"l{i}.shared_expert_gate"))


def delta_rule(q, k, v, g, beta, state_dtype=None):
    """The gated delta rule from an empty state, token by token. q, k
    [S, H, dk]; v [S, H, dv]; g, beta [S, H] → (o [S, H, dv], the state
    after the last token [H, dk, dv]). Per token: ``S ← exp(g)·S``;
    ``u = (v − Sᵀk)·β``; ``S ← S + k uᵀ``; ``o = Sᵀq``. Between tokens
    the state is kept in ``state_dtype`` (default: the inputs'): below
    float32 it is the reading that shows what a narrower state costs."""
    dt = q.dtype
    sd = dt if state_dtype is None else state_dtype

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state.astype(dt) * jnp.exp(g_t)[:, None, None]
        u = (v_t - jnp.einsum("hkv,hk->hv", state, k_t)) * b_t[:, None]
        state = (state + k_t[:, :, None] * u[:, None, :]).astype(sd)
        return state, jnp.einsum("hkv,hk->hv", state.astype(dt), q_t)

    with jax.default_matmul_precision("highest"):
        state, o = lax.scan(
            token, jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), sd),
            (q, k, v, g, beta))
    return o, state


def gated_delta_net(p, i, cfg, x):
    """x [S, D] → [S, D]."""
    S = x.shape[0]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K = cfg["linear_conv_kernel_dim"]
    kd, vd = Hk * dk, Hv * dv
    qkvz = x @ _p(p, f"l{i}.in_proj_qkvz")
    ba = x @ _p(p, f"l{i}.in_proj_ba")
    mixed, z = qkvz[:, : 2 * kd + vd], qkvz[:, 2 * kd + vd:]
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(_p(p, f"l{i}.A_log")) * jax.nn.softplus(
        ba[:, Hv:] + _p(p, f"l{i}.dt_bias"))
    # causal depthwise convolution, no bias, then SiLU
    w = _p(p, f"l{i}.conv_w")  # [K, C]
    padded = jnp.concatenate(
        [jnp.zeros((K - 1, mixed.shape[1]), _DT), mixed])
    y = jax.nn.silu(sum(padded[j:j + S] * w[j] for j in range(K)))
    q = y[:, :kd].reshape(S, Hk, dk)
    k = y[:, kd: 2 * kd].reshape(S, Hk, dk)
    v = y[:, 2 * kd:].reshape(S, Hv, dv)
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(q, Hv // Hk, axis=1) * dk ** -0.5
    k = jnp.repeat(k, Hv // Hk, axis=1)

    o, _ = delta_rule(q, k, v, g, beta)
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                      + cfg["rms_norm_eps"]) * _p(p, f"l{i}.gdn_norm")
    o = o * jax.nn.silu(z.reshape(S, Hv, dv))
    return o.reshape(S, vd) @ _p(p, f"l{i}.out_proj")


def rotate_half_rope(x, theta, rd):
    """x [S, H, D]: rotary (rotate-half) on the first ``rd`` dims."""
    S = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=f32) / rd))
    ang = jnp.arange(S, dtype=f32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]  # [S, 1, rd]
    cos, sin = jnp.cos(ang).astype(_DT), jnp.sin(ang).astype(_DT)
    rot, rest = x[..., :rd], x[..., rd:]
    half = jnp.concatenate([-rot[..., rd // 2:], rot[..., : rd // 2]], -1)
    return jnp.concatenate([rot * cos + half * sin, rest], -1)


def gated_attention(p, i, cfg, x):
    S = x.shape[0]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    rd = int(hd * cfg["partial_rotary_factor"])
    qg = (x @ _p(p, f"l{i}.q_proj")).reshape(S, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (x @ _p(p, f"l{i}.k_proj")).reshape(S, Hkv, hd)
    v = (x @ _p(p, f"l{i}.v_proj")).reshape(S, Hkv, hd)
    q = rms_norm(q, _p(p, f"l{i}.q_norm"), cfg["rms_norm_eps"])
    k = rms_norm(k, _p(p, f"l{i}.k_norm"), cfg["rms_norm_eps"])
    q = rotate_half_rope(q, cfg["rope_theta"], rd)
    k = rotate_half_rope(k, cfg["rope_theta"], rd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("hst,thd->shd", probs, v) * jax.nn.sigmoid(gate)
    return out.reshape(S, H * hd) @ _p(p, f"l{i}.o_proj")


def layer(p, i, cfg, x):
    eps = cfg["rms_norm_eps"]
    full = (i + 1) % cfg["full_attention_interval"] == 0
    h = rms_norm(x, _p(p, f"l{i}.in_norm"), eps)
    x = x + (gated_attention if full else gated_delta_net)(p, i, cfg, h)
    h = rms_norm(x, _p(p, f"l{i}.post_norm"), eps)
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


def forward(p, cfg, tokens, dtype=f32, positions=None):
    """tokens [S] → logits [S, V] over the vocabulary slice held, or
    at ``positions`` only. ``dtype``: see :func:`computed_in`."""
    with computed_in(dtype):
        x = p["embed"][jnp.asarray(tokens)].astype(_DT)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(p, i, cfg, x)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = rms_norm(x, _p(p, "norm_f"), cfg["rms_norm_eps"])
        return x @ _p(p, "lm_head")
