"""Olmo-Hybrid's forward pass, plainly: float32, the highest matmul
precision, one sequence, no cache, no batching, no chunked form — the
Gated DeltaNet recurrence runs token by token. What the served programs
(models/olmo_hybrid.py) are held to.

Follows the published ``config.json`` (``model_type: olmo_hybrid``) and
imports nothing of the program. ``cfg`` is a mapping of the published
keys; ``forward`` takes the same parameter dict as the program.

Three things the published config has no key for are READINGS, by the
Olmo family's convention, not published facts (none changes a shape, a
parameter count, an operation or a byte, and the program shares each):

- ``block``: the norm sits on the sub-layer's output, ``h = x +
  N(Mixer(x))``, ``out = h + N(MLP(h))`` (Olmo 2 / Olmo 3), for both
  kinds of layer;
- ``qk_norm``: the full-attention layer's RMSNorm runs over the whole
  query / key projection, not a head at a time (the same convention);
- ``positional``: ``rope_parameters.rope_theta: null`` is read as no
  rotary embedding in the full-attention layers.

Also readings: no convolution bias, plain (not zero-centred) norm
weights. Departures from the checkpoint's tensor layout, here as in the
program (a loader permutes): the three convolutions' weights are one
``[kernel, channels]`` leaf over q | k | v, ``b_proj`` / ``a_proj`` are
one ``[D, 2H]`` leaf b | a.

``N(x) = x · rsqrt(mean(x²) + eps) · w``. ``wrong`` (``forward``) names
a reading of ANOTHER model, which a comparison must tell from this one:
``beta_sigmoid`` leaves ``β`` at ``σ(·)`` where the config's
``linear_allow_neg_eigval`` makes it ``2σ(·)``.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax import lax

f32 = jnp.float32
#: the precision everything is computed in: float32, but for the one
#: reading that shows what a lower precision would give (``forward``'s
#: ``dtype``)
_DT = f32


def _p(p, name):
    return p[name].astype(_DT)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def delta_rule(q, k, v, g, beta, state=None, state_dtype=None):
    """The gated delta rule token by token, from ``state`` (default:
    empty). q, k [S, H, dk]; v [S, H, dv]; g, beta [S, H] → (o
    [S, H, dv], the state after the last token [H, dk, dv]). Per token:
    ``S ← exp(g)·S``; ``u = (v − Sᵀk)·β``; ``S ← S + k uᵀ``;
    ``o = Sᵀq``. Between tokens the state is kept in ``state_dtype``
    (default: the inputs'): below float32 it is the reading that shows
    what a narrower state costs."""
    dt = q.dtype
    sd = dt if state_dtype is None else state_dtype
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), sd)

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state.astype(dt) * jnp.exp(g_t)[:, None, None]
        u = (v_t - jnp.einsum("hkv,hk->hv", state, k_t)) * b_t[:, None]
        state = (state + k_t[:, :, None] * u[:, None, :]).astype(sd)
        return state, jnp.einsum("hkv,hk->hv", state.astype(dt), q_t)

    with jax.default_matmul_precision("highest"):
        state, o = lax.scan(token, state.astype(sd), (q, k, v, g, beta))
    return o, state


def gdn_inputs(p, i, cfg, x, conv_tail=None, wrong=""):
    """x [S, D] → q, k [S, H, dk] (L2-normalised, q scaled), v
    [S, H, dv], g, beta [S, H], the output gate z [S, H, dv] and the
    convolution's last ``kernel - 1`` inputs."""
    S = x.shape[0]
    H = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K = cfg["linear_conv_kernel_dim"]
    kd = H * dk
    mixed = jnp.concatenate(
        [x @ _p(p, f"l{i}.{m}_proj") for m in ("q", "k", "v")], -1)
    z = (x @ _p(p, f"l{i}.g_proj")).reshape(S, H, dv)
    ba = x @ _p(p, f"l{i}.ba_proj")
    beta = jax.nn.sigmoid(ba[:, :H])
    if cfg.get("linear_allow_neg_eigval", True) and wrong != "beta_sigmoid":
        beta = 2.0 * beta
    g = -jnp.exp(_p(p, f"l{i}.A_log")) * jax.nn.softplus(
        ba[:, H:] + _p(p, f"l{i}.dt_bias"))
    # causal depthwise convolution, no bias, then SiLU
    w = _p(p, f"l{i}.conv_w")  # [K, C]
    if conv_tail is None:
        conv_tail = jnp.zeros((K - 1, mixed.shape[1]), _DT)
    padded = jnp.concatenate([conv_tail.astype(_DT), mixed])
    y = jax.nn.silu(sum(padded[j:j + S] * w[j] for j in range(K)))
    q = y[:, :kd].reshape(S, H, dk)
    k = y[:, kd: 2 * kd].reshape(S, H, dk)
    v = y[:, 2 * kd:].reshape(S, H, dv)
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    return q, k, v, g, beta, z, padded[S:]


def gdn_output(p, i, cfg, o, z):
    S = o.shape[0]
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                      + cfg["rms_norm_eps"]) * _p(p, f"l{i}.gdn_norm")
    return (o * jax.nn.silu(z)).reshape(S, -1) @ _p(p, f"l{i}.out_proj")


def gated_delta_net(p, i, cfg, x, carry=None, wrong=""):
    """x [S, D] → ([S, D], carry): ``carry`` = (state, convolution
    tail) continues a sequence given in blocks."""
    state, tail = carry if carry is not None else (None, None)
    q, k, v, g, beta, z, tail = gdn_inputs(p, i, cfg, x, tail, wrong)
    o, state = delta_rule(q, k, v, g, beta, state)
    return gdn_output(p, i, cfg, o, z), (state, tail)


def full_attention(p, i, cfg, x, past=None):
    """x [S, D] → ([S, D], (keys, values) of everything so far). 30
    heads over 30 key heads at the published size; RMSNorm over the
    whole projection; no rotary embedding; no gate."""
    S = x.shape[0]
    H = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // H
    eps = cfg["rms_norm_eps"]
    q = rms_norm(x @ _p(p, f"l{i}.q_proj"), _p(p, f"l{i}.q_norm"), eps)
    k = rms_norm(x @ _p(p, f"l{i}.k_proj"), _p(p, f"l{i}.k_norm"), eps)
    v = x @ _p(p, f"l{i}.v_proj")
    q, k, v = (a.reshape(S, H, hd) for a in (q, k, v))
    if past is not None:
        k = jnp.concatenate([past[0], k])
        v = jnp.concatenate([past[1], v])
    T = k.shape[0]
    scores = jnp.einsum("shd,thd->hst", q, k) * hd ** -0.5
    causal = (jnp.arange(T)[None, :] <= (T - S) + jnp.arange(S)[:, None])
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("hst,thd->shd", probs, v)
    return out.reshape(S, H * hd) @ _p(p, f"l{i}.o_proj"), (k, v)


def mlp(p, i, x):
    return (jax.nn.silu(x @ _p(p, f"l{i}.w_gate"))
            * (x @ _p(p, f"l{i}.w_up"))) @ _p(p, f"l{i}.w_down")


def layer(p, i, cfg, x, carry=None, wrong=""):
    eps = cfg["rms_norm_eps"]
    if cfg["layer_types"][i] == "full_attention":
        mixed, carry = full_attention(p, i, cfg, x, carry)
    else:
        mixed, carry = gated_delta_net(p, i, cfg, x, carry, wrong)
    x = x + rms_norm(mixed, _p(p, f"l{i}.mixer_norm"), eps)
    return x + rms_norm(mlp(p, i, x), _p(p, f"l{i}.mlp_norm"), eps), carry


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


def forward(p, cfg, tokens, dtype=f32, positions=None, wrong="",
            block=0, state_lost_at=0):
    """tokens [S] → logits [S, V], or at ``positions`` only. ``dtype``:
    see :func:`computed_in`; ``wrong``: see the module's docstring.
    ``block``: run the sequence that many tokens at a time, each layer
    carrying its state or its keys and values on (the same mathematics:
    the recurrence is token by token either way; what it bounds is the
    attention's score matrix, so that a long history fits).
    ``state_lost_at``: another WRONG model — the DeltaNet layers start
    from an empty state and an empty convolution at that token while
    the attention layers keep their keys and values: what a prefix-cache
    hit computes that adopted the pages and not the state's snapshot."""
    tokens = jnp.asarray(tokens)
    S = tokens.shape[0]
    block = block or S
    L = cfg["num_hidden_layers"]
    starts = sorted({*range(0, S, block), state_lost_at} - {S})
    with computed_in(dtype):
        carries = [None] * L
        outs = []
        for s, e in zip(starts, [*starts[1:], S]):
            if state_lost_at and s == state_lost_at:
                carries = [
                    c if cfg["layer_types"][i] == "full_attention" else None
                    for i, c in enumerate(carries)]
            x = p["embed"][tokens[s:e]].astype(_DT)
            for i in range(L):
                x, carries[i] = layer(p, i, cfg, x, carries[i], wrong)
            outs.append(x)
        x = jnp.concatenate(outs)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = rms_norm(x, _p(p, "norm_f"), cfg["rms_norm_eps"])
        return x @ _p(p, "lm_head")
