"""Ring / Ulysses sequence-parallel attention vs single-device reference
on the virtual 8-device CPU mesh."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.ops.ring_attention import ring_attention
from aigw_tpu.parallel import MeshSpec, make_mesh


def full_attention(q, k, v, causal=True):
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, S, Hkv, g, D)
    logits = jnp.einsum("bshgd,bthd->bhgst", qg, k,
                        preferred_element_type=jnp.float32) / math.sqrt(D)
    if causal:
        pos = jnp.arange(S)
        mask = pos[:, None] >= pos[None, :]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs.astype(v.dtype), v)
    return out.reshape(B, S, H * D)


@pytest.fixture(scope="module")
def qkv():
    B, S, H, Hkv, D = 2, 64, 4, 2, 32
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, Hkv, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_full(qkv, causal):
    q, k, v = qkv
    mesh = make_mesh(MeshSpec(dp=1, tp=1, sp=8))
    got = ring_attention(q, k, v, mesh=mesh, causal=causal, strategy="ring")
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_full(qkv, causal):
    q, k, v = qkv
    # Ulysses needs n_kv_heads % sp == 0 → sp=2
    mesh = make_mesh(MeshSpec(dp=1, tp=1, sp=2))
    got = ring_attention(q, k, v, mesh=mesh, causal=causal,
                         strategy="ulysses")
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow


def test_ring_production_shape_ab_smoke():
    """A/B smoke at the shape the sp path actually serves — llama-3-8B
    attention extents (H=32, Hkv=8, D=128) at the sp_prefill_min_tokens
    threshold (S=1024) — ring kernel on the virtual 8-device mesh vs
    the single-device XLA reference. Exercises the plain scan carries
    (jax.shard_map(check_vma=False)) at production extents, where a
    varying-axes typing bug would corrupt the online-softmax
    accumulator rather than just failing to trace."""
    B, S, H, Hkv, D = 1, 1024, 32, 8, 128
    key = jax.random.PRNGKey(42)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, Hkv, D), jnp.float32)
    mesh = make_mesh(MeshSpec(dp=1, tp=1, sp=8))
    got = ring_attention(q, k, v, mesh=mesh, causal=True, strategy="ring")
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
