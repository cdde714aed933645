"""Weight-only quantization: int8 (W8A16) and int4 (W4A16).

Decode on TPU is weight-streaming-bound (every step reads every weight
from HBM); int8 halves that traffic, int4 quarters it, while activations
stay bf16. Inside the jitted step the packed block is converted and
scaled right at the matmul operand, which XLA fuses — HBM sees
int8/int4 bytes, the MXU sees bf16.

- **int8**: symmetric per-output-channel (scale per column; per row for
  the embedding since it is consumed by row gather).
- **int4**: symmetric GROUP-WISE along the input axis (one scale per
  ``GROUP4`` input rows per output channel — per-channel int4 is too
  lossy; group-128 is the standard W4 recipe). XLA's native ``int4``
  dtype packs two nibbles per byte in HBM.

Quantized params replace each matrix ``name`` with ``name.q`` (int8 or
int4) and ``name.scale``; the representation is self-describing (the
model resolver keys on ``q.dtype``). Norms and biases stay bf16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

#: weight-name suffixes eligible for quantization (matmul-path matrices)
_MATRIX_KINDS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

#: int4 group size along the input axis (one scale per group per
#: output channel) — the standard W4 recipe
GROUP4 = 128


@partial(jax.jit, static_argnames=("axis",))
def _quantize_matrix(w: jax.Array, axis: int) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 along ``axis`` (the preserved/output axis).

    Jitted so the f32 upcast fuses into the reduction and the rounding —
    eager dispatch would materialize a full f32 copy (2GB for an 8B
    embedding), which busts HBM when quantizing a 16GB bf16 model in
    place on a 16GB chip."""
    wf = w.astype(jnp.float32)
    reduce_axes = tuple(i for i in range(w.ndim) if i != axis)
    amax = jnp.max(jnp.abs(wf), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


@jax.jit
def _quantize_matrix_int8_channels(
    w: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 with one scale per output channel per LEADING
    index: only the input axis (ndim-2) is reduced, so an [E, in, out]
    expert stack gets per-expert scales [E, 1, out] — one outlier-heavy
    expert must not coarsen every other expert's steps ([in, out]
    matrices reduce to [1, out], identical to before)."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=w.ndim - 2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


@partial(jax.jit, static_argnames=("group",))
def _quantize_matrix_int4(
    w: jax.Array, group: int,
) -> tuple[jax.Array, jax.Array]:
    """Symmetric int4, group-wise along the input axis (ndim-2): one
    f32 scale per ``group`` input rows per output channel. Returns
    (q int4 [..., in, out], scale f32 [..., in/group, out])."""
    wf = w.astype(jnp.float32)
    *lead, n_in, n_out = wf.shape
    g = wf.reshape(*lead, n_in // group, group, n_out)
    amax = jnp.max(jnp.abs(g), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(g / scale), -7, 7).astype(jnp.int4)
    return (q.reshape(*lead, n_in, n_out),
            scale.squeeze(-2).astype(jnp.float32))


def quantize_tensor(name: str, w: jax.Array,
                    mode: str = "int8") -> dict[str, jax.Array]:
    """One named weight → the leaves that replace it: ``{name.q,
    name.scale}`` for the matmul matrices, lm_head and the embedding,
    ``{name: w}`` for everything else (norms, biases, routers)."""
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown quantization mode {mode!r}")
    kind = name.rsplit(".", 1)[-1]
    if (kind in _MATRIX_KINDS and w.ndim >= 2) or name == "lm_head":
        # output channels = last axis for [in, out] (and [E, in, out])
        if mode == "int4" and w.shape[-2] % GROUP4 == 0:
            q, scale = _quantize_matrix_int4(w, GROUP4)
        else:  # int8, or input dim not groupable
            q, scale = _quantize_matrix_int8_channels(w)
    elif name == "embed":
        # consumed by row gather: per-row scales either mode
        q, scale = _quantize_matrix(w, axis=0)
    else:
        return {name: w}
    return {name + ".q": q, name + ".scale": scale}


def quantize_params(
    params: dict[str, jax.Array], consume: bool = False,
    mode: str = "int8",
) -> dict[str, jax.Array]:
    """bf16 param dict → W8A16 / W4A16 dict (un-quantized leaves pass
    through). ``mode`` is "int8" or "int4".

    ``consume=True`` removes each bf16 tensor from ``params`` as soon as
    its quantized replacement is materialized, bounding peak HBM to
    bf16-model + one tensor instead of two full copies. A model whose
    bf16 form does not fit at all is quantized as it is created
    instead (``init_params(finish=quantize_tensor)``).
    """
    out: dict[str, jax.Array] = {}
    for name in list(params):
        w = params.pop(name) if consume else params[name]
        out.update(quantize_tensor(name, w, mode))
    return out


def is_quantized(params: dict[str, jax.Array]) -> bool:
    return any(k.endswith(".q") for k in params)
