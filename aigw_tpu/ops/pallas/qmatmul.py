"""Fused W8A16 matmul Pallas kernel for weight-streaming-bound decode.

Decode reads every weight once per step, so throughput is set by HBM
bytes moved. The XLA path (``models/quant.py`` + ``llama._w``)
dequantizes ``int8 → bf16 * scale`` as a fused producer of the matmul,
but the dequantized operand still round-trips through bf16 tiles ahead
of the MXU. This kernel streams the **int8** tile into VMEM, converts
in-register, runs the MXU on bf16, and applies the per-output-column
scale to the f32 accumulator — per-column scaling commutes with the
contraction, so the multiply happens on the [M, TILE_N] result instead
of the [K, TILE_N] weight (K/M ≈ 500× less scaling work, and the weight
never exists in bf16 anywhere).

Decode-shape oriented: M (batch) is small, K/N are the model matrices
(multiples of 128). Grid is over N tiles; the Pallas pipeline
double-buffers the weight-tile DMA automatically.

Numerics: ≈ the XLA path, slightly better — scale is applied in f32
after accumulation instead of being rounded into bf16 weights first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# int8 weight-tile byte budget per grid step; double-buffered by the
# pipeline, so ~2x this lives in VMEM (16MB/core) alongside x and out.
_TILE_BYTES = 2 * 1024 * 1024


def _pick_tile_n(k: int, n: int) -> int:
    for tile in (512, 384, 256, 128):
        if n % tile == 0 and k * tile <= 2 * _TILE_BYTES:
            return tile
    return 0


def _kernel(x_ref, q_ref, s_ref, o_ref):
    w = q_ref[:].astype(jnp.bfloat16)  # int8 → bf16 in VMEM/registers
    acc = jnp.dot(x_ref[:], w, preferred_element_type=jnp.float32)
    o_ref[:] = (acc * s_ref[:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _w8a16_matmul(x, q, scale, interpret=False):
    m, k = x.shape
    _, n = q.shape
    tile_n = _pick_tile_n(k, n)
    grid = (n // tile_n,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, tile_n), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_n), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((m, tile_n), lambda j: (0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x, q, scale)


def supported(m: int, k: int, n: int) -> bool:
    """Shapes this kernel accepts: decode-sized M, 128-aligned K/N with
    a dividing tile. Everything else falls back to the XLA path."""
    return (
        m <= 64
        and k % 128 == 0
        and _pick_tile_n(k, n) > 0
    )


def w8a16_matmul(x: jax.Array, q: jax.Array,
                 scale: jax.Array) -> jax.Array:
    """``x [M, K] bf16 @ dequant(q [K, N] int8, scale [1, N] f32)``.

    Caller guarantees ``supported(M, K, N)``. Runs interpreted off-TPU
    so CPU tests exercise the same code path."""
    from aigw_tpu.ops.pallas._compat import is_tpu_backend

    return _w8a16_matmul(x, q, scale.reshape(1, -1),
                         interpret=not is_tpu_backend())
