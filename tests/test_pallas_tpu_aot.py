"""Every Pallas kernel must COMPILE for TPU v5 lite — without a chip.

libtpu can describe a TPU topology on a chipless box, so each
``pl.pallas_call`` in ``aigw_tpu/ops/pallas/`` is lowered and compiled
ahead of time by the real Mosaic compiler (``interpret=False``) at the
two served attention geometries. Interpret-mode parity tests cannot see
a Mosaic refusal (block shapes, in-kernel reshapes, unaligned slices):
``fused_paged_decode`` passed them for eight PRs and had never lowered.
Agreement with the XLA twins on real hardware is ``chip_smoke.py``'s
kernels phase; this file only keeps the lowering from regressing.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from aigw_tpu.ops.pallas import qmatmul
from aigw_tpu.ops.pallas.decode_fused import fused_paged_decode
from aigw_tpu.ops.pallas.paged_attention import (
    paged_attention_decode_v2,
    paged_attention_verify,
    ragged_prefill_attention,
)

#: (n_heads, n_kv_heads, dim, ffn_dim, vocab_size)
GEOMETRIES = {
    "qwen2-7b": (28, 4, 3584, 18944, 152064),
    "llama-3-8b": (32, 8, 4096, 14336, 128256),
}
D, PAGE, B, P = 128, 128, 8, 16
N_SLOTS = (B * P + 1) * PAGE  # the engine's pool: pages + the dump page


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one device of a described (not attached) v5e:2x2."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu on this box
        pytest.skip(f"libtpu cannot describe a v5e topology: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes, **kw_shapes):
    def sds(spec):
        shape, dtype = spec
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    jax.jit(fn).lower(
        *(sds(s) for s in shapes),
        **{k: sds(s) for k, s in kw_shapes.items()}).compile()


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_attention_kernels_compile(v5e, geometry):
    H, Hkv = GEOMETRIES[geometry][:2]
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = ((N_SLOTS, Hkv, D), bf16)
    pt = ((B, P), i32)
    _compile(
        functools.partial(paged_attention_decode_v2, page_size=PAGE),
        v5e, ((B, H, D), bf16), pool, pool, pt, ((B,), i32))
    _compile(
        functools.partial(paged_attention_verify, page_size=PAGE),
        v5e, ((B, 5, H, D), bf16), pool, pool, pt, ((B,), i32))
    _compile(
        functools.partial(ragged_prefill_attention, page_size=PAGE),
        v5e, ((256, H, D), bf16), pool, pool, pt, ((B + 1,), i32),
        ((B,), i32))
    fused = functools.partial(fused_paged_decode, rope_theta=1e6,
                              page_size=PAGE)
    new = ((B, Hkv, D), bf16)
    tail = (pt, ((B,), i32), ((B,), jnp.bool_))
    _compile(fused, v5e, ((B, H, D), bf16), new, new, pool, pool, *tail)
    for qdt in (jnp.int8, jnp.int4):
        qpool = ((N_SLOTS, Hkv, D), qdt)
        scale = ((N_SLOTS, Hkv), jnp.float32)
        _compile(fused, v5e, ((B, H, D), bf16), new, new, qpool, qpool,
                 *tail, k_scale=scale, v_scale=scale)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_w8a16_matmul_compiles_at_every_weight_shape(v5e, geometry):
    H, Hkv, dim, ffn, vocab = GEOMETRIES[geometry]
    shapes = {(dim, H * D), (dim, Hkv * D), (H * D, dim),
              (dim, ffn), (ffn, dim), (dim, vocab)}
    for k, n in sorted(shapes):
        assert qmatmul.supported(B, k, n), (k, n)
        _compile(qmatmul._w8a16_matmul, v5e, ((B, k), jnp.bfloat16),
                 ((k, n), jnp.int8), ((1, n), jnp.float32))
