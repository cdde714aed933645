"""MiMo-V2's language model, plainly: float32, the highest matmul
precision, one sequence, no cache, no ring, no batching; full ``[S, S]``
masks built from positions, the sink as one more column of the logits.
What the served programs (models/mimo_v2.py: a flattened ``v | k`` page
row, a ring a slot, blocks with an online softmax, a loop over the live
rows) are held to.

Follows the published ``config.json`` (``model_type: mimo_v2``); it
imports nothing of the program. ``cfg`` is a mapping of the
configuration dataclass's fields: the published keys plus the share this
chip holds of an expert-parallel deployment — ``num_experts`` experts
held, from id ``held_from``, of a router ``router_experts`` wide (0:
everything is held) — and ``first_dense_layers`` (the leading zeros of
the published ``moe_layer_freq``). ``forward`` takes the same parameter
dict as the program and the same vocabulary slice.

Readings of the published keys, each noted again at its line:
``sliding_window`` counts the query's own position (``t - s <
window``); ``attention_chunk_size`` (equal to the window) does NOT turn
the sliding window into block-local attention; the sink is one more
softmax column a head, dropped after normalising, in window layers only
(``add_swa_attention_sink_bias`` true, ``add_full_attention_sink_bias``
false); ``attention_value_scale`` multiplies ``v`` before attention;
``int(head_dim * partial_rotary_factor)`` leading dims rotate, pairs as
halves (a permutation of the projection's columns where the checkpoint
pairs neighbours); the router's bias picks and does not weigh, and
``routed_scaling_factor: null`` is 1. What absent experts would add is
left out, and that partial result goes on to the next layer; there is
no shared expert. The vision and audio towers, their projector and the
MTP heads are not here: the source has no key for them.

Every layer: ``h = x + Attn(N(x))``, ``out = h + FFN(N(h))``;
``N(x) = x · rsqrt(mean(x²) + eps) · w``.
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
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_kind(cfg, i):
    """0 in ``hybrid_layer_pattern`` is a global layer, 1 a window
    layer."""
    return "window" if cfg["hybrid_layer_pattern"][i] else "global"


def rotary_dim(cfg):
    """``int(192 x 0.334) = 64``: the leading dims of a head that
    rotate."""
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"]) // 2 * 2


def rope(x, theta, rd, positions=None):
    """x [S, heads, d] at positions 0..S-1 (or ``positions`` [S]):
    rotary on the first ``rd`` dims, pairs as halves (``x[j]`` with
    ``x[j + rd/2]``; the published code's pairing is a permutation of
    the projection's columns); the other dims pass."""
    S = x.shape[0]
    pos = (jnp.arange(S, dtype=f32) if positions is None
           else jnp.asarray(positions, f32))
    inv = theta ** (-jnp.arange(0, rd, 2, dtype=f32) / rd)
    ang = pos[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang).astype(_DT), jnp.sin(ang).astype(_DT)
    x1, x2 = x[..., : rd // 2], x[..., rd // 2: rd]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rd:]], -1)


# -- the expert layer -------------------------------------------------------
def route(p, i, cfg, x):
    """x [S, D] → the router's picks over its whole width: weights
    [S, K] and expert ids [S, K]. ``s = sigmoid(x W_g)``; the picks are
    the ``K`` largest ``s + b`` (``noaux_tc``'s selection bias; one
    group, always kept); a pick's weight is its UNBIASED ``s`` over the
    picks' sum (``norm_topk_prob``), times ``routed_scaling_factor``
    (``null``: 1)."""
    s = jax.nn.sigmoid(x @ _p(p, f"l{i}.router"))
    topi = lax.top_k(s + _p(p, f"l{i}.router_bias"),
                     cfg["num_experts_per_tok"])[1]
    topv = jnp.take_along_axis(s, topi, -1)
    if cfg.get("norm_topk_prob", True):
        topv = topv / (jnp.sum(topv, -1, keepdims=True) + 1e-20)
    return topv * (cfg.get("routed_scaling_factor") or 1.0), topi


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def moe_layer(p, i, cfg, x, held_from=None, num_experts=None):
    """x [S, D] → the part of the routed mixture that experts
    ``held_from .. held_from + num_experts - 1`` give (default: the
    configuration's share); a token none of whose picks is among them
    gets zero. The parameter dict holds the matrices of experts
    ``cfg['held_from'] + arange(cfg['num_experts'])``, flat (``[D,
    E*F]`` / ``[E*F, D]``)."""
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


def dense_mlp(p, i, x):
    return _swiglu(x, _p(p, f"l{i}.w_gate"), _p(p, f"l{i}.w_up"),
                   _p(p, f"l{i}.w_down"))


# -- attention --------------------------------------------------------------
def project(p, i, cfg, x, positions=None):
    """x [S, D] → q [S,H,dk], k [S,Hkv,dk] (both rotated on their
    leading part, with the layer kind's theta) and v [S,Hkv,dv] (scaled
    by ``attention_value_scale``: on ``v``, before attention). The fused
    projection holds q | k | v as contiguous blocks."""
    S = x.shape[0]
    kind = layer_kind(cfg, i)
    H, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], \
        cfg["v_head_dim"]
    Hkv = cfg["swa_num_key_value_heads" if kind == "window"
              else "num_key_value_heads"]
    theta = cfg["swa_rope_theta" if kind == "window" else "rope_theta"]
    qkv = x @ _p(p, f"l{i}.wqkv")
    q = qkv[:, :H * dk].reshape(S, H, dk)
    k = qkv[:, H * dk:(H + Hkv) * dk].reshape(S, Hkv, dk)
    v = qkv[:, (H + Hkv) * dk:].reshape(S, Hkv, dv)
    rd = rotary_dim(cfg)
    return (rope(q, theta, rd, positions), rope(k, theta, rd, positions),
            v * jnp.asarray(cfg["attention_value_scale"], _DT))


def attend(q, k, v, cfg, window=0, sink=None, block=None):
    """q [S,H,dk], k [S,Hkv,dk], v [S,Hkv,dv] at positions 0..S-1 →
    [S,H,dv]. Query head ``h`` reads key head ``h // (H / Hkv)``.
    ``window`` 0: causal, ``s <= t``. Else banded: ``t - window < s <=
    t`` — the window counts the query's own position, and
    ``attention_chunk_size`` does not cut it into blocks. ``sink`` [H]:
    one more column of the softmax a head, with no value row — dropped
    after normalising, so a head's weights may sum to less than one.
    ``block``: queries taken at a time (the same numbers; less memory
    at the published widths)."""
    S, H, dk = q.shape
    Hkv = k.shape[1]
    kr = jnp.repeat(k, H // Hkv, axis=1)
    vr = jnp.repeat(v, H // Hkv, axis=1)
    outs = []
    for t0 in range(0, S, block or S):
        t1 = min(S, t0 + (block or S))
        s0 = max(0, t0 - window + 1) if window else 0
        s = jnp.einsum("shd,thd->hst", q[t0:t1], kr[s0:t1]) * dk ** -0.5
        t = jnp.arange(t0, t1)[:, None]
        u = jnp.arange(s0, t1)[None, :]
        seen = u <= t
        if window:
            seen = seen & (t - u < window)
        s = jnp.where(seen[None], s, -jnp.inf)
        if sink is not None:
            s = jnp.concatenate([s, jnp.broadcast_to(
                sink.astype(_DT)[:, None, None], (H, t1 - t0, 1))], -1)
        probs = jax.nn.softmax(s, -1)
        if sink is not None:
            probs = probs[..., :-1]  # the sink's column has no value row
        outs.append(jnp.einsum("hst,thd->shd", probs, vr[s0:t1]))
    return jnp.concatenate(outs)


def attention(p, i, cfg, x, block=None, windowed=True, sinks=True):
    """x [S, D] → [S, D]. ``windowed`` False reads every window layer as
    a global one and ``sinks`` False drops the sink column: what a
    program that skipped either mechanism would compute (the checks'
    controls; never the model)."""
    S = x.shape[0]
    q, k, v = project(p, i, cfg, x)
    is_window = layer_kind(cfg, i) == "window"
    out = attend(
        q, k, v, cfg,
        window=cfg["sliding_window"] if is_window and windowed else 0,
        sink=p[f"l{i}.sink"] if is_window and sinks else None, block=block)
    return out.reshape(S, -1) @ _p(p, f"l{i}.wo")


def layer(p, i, cfg, x, block=None, windowed=True, sinks=True):
    eps = cfg["layernorm_epsilon"]
    h = rms_norm(x, _p(p, f"l{i}.in_norm"), eps)
    x = x + attention(p, i, cfg, h, block, windowed, sinks)
    h = rms_norm(x, _p(p, f"l{i}.post_norm"), eps)
    if i < cfg["first_dense_layers"]:
        return x + dense_mlp(p, i, h)
    return x + moe_layer(p, i, cfg, h)  # no shared expert


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


def forward(p, cfg, tokens, dtype=f32, positions=None, block=None,
            windowed=True, sinks=True):
    """tokens [S] → logits [S, V] over the vocabulary slice held, or
    at ``positions`` only. ``dtype``: see :func:`computed_in`;
    ``block``: see :func:`attend`; ``windowed`` / ``sinks``: see
    :func:`attention`."""
    with computed_in(dtype):
        x = p["embed"][jnp.asarray(tokens)].astype(_DT)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(p, i, cfg, x, block, windowed, sinks)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = rms_norm(x, _p(p, "norm_f"), cfg["layernorm_epsilon"])
        return x @ _p(p, "lm_head")
