"""Ring attention: context/sequence parallelism over the ``sp`` mesh axis.

For sequences whose KV exceeds one chip's HBM, the sequence dimension is
sharded across the mesh; each device computes attention of its **local Q
shard** against K/V blocks that rotate around the ring via
``lax.ppermute`` (ICI neighbor exchanges — the blockwise/ring-attention
construction; SURVEY.md §5 long-context, PAPERS.md). Online softmax
accumulates across ring steps, so no device ever materializes the full
sequence.

Communication cost: ``sp - 1`` neighbor hops of the local K/V block per
attention call, fully overlapped by XLA with the per-step matmuls. This is
the SPMD equivalent the reference's world has no analogue for (its gateway
never touches model internals) — first-class here per the north star.

An Ulysses-style alternative (all-to-all head-scatter, cheaper when
``n_heads ≥ sp``) shares the entry point via ``strategy="ulysses"``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _ring_attention_local(
    q: jax.Array,  # [B, S_loc, H, D] — this device's query shard
    k: jax.Array,  # [B, S_loc, Hkv, D]
    v: jax.Array,  # [B, S_loc, Hkv, D]
    *,
    axis: str,
    causal: bool,
) -> jax.Array:
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    idx = jax.lax.axis_index(axis)
    n = jax.lax.psum(1, axis)
    scale = 1.0 / math.sqrt(D)

    q_pos = idx * S + jnp.arange(S)  # global positions of local queries
    qg = q.reshape(B, S, Hkv, group, D)

    def block_attend(kb, vb, src):
        """Logits of local q against block kb/vb originating on `src`."""
        k_pos = src * S + jnp.arange(S)
        logits = jnp.einsum(
            "bshgd,bthd->bhgst", qg, kb,
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]  # [S, S]
            logits = jnp.where(mask[None, None, None, :, :], logits, -1e30)
        return logits  # [B, Hkv, group, S, S]

    def step(carry, i):
        acc, m, l, kb, vb = carry
        src = (idx - i) % n  # who produced the block we currently hold
        logits = block_attend(kb, vb, src)
        m_cur = jnp.max(logits, axis=-1)  # [B, Hkv, group, S]
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + probs.sum(-1)
        pv = jnp.einsum("bhgst,bthd->bshgd", probs.astype(vb.dtype), vb)
        acc = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        # rotate the block to the next device on the ring
        perm = [(j, (j + 1) % n) for j in range(n)]
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        return (acc, m_new, l_new, kb, vb), None

    # plain accumulators: ppermute makes them vary over the manual
    # axis mid-scan, so the shard_map call passes check_vma=False
    acc0 = jnp.zeros((B, S, Hkv, group, D), jnp.float32)
    m0 = jnp.full((B, Hkv, group, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, group, S), jnp.float32)
    (acc, m, l, _, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v), jnp.arange(n)
    )
    denom = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    out = (acc / denom).astype(q.dtype)
    return out.reshape(B, S, H * D)


def _ring_prefix_attention_local(
    q: jax.Array,  # [B, S_loc, H, D] — this device's chunk-query shard
    k: jax.Array,  # [B, S_loc, Hkv, D] — chunk keys (in-register)
    v: jax.Array,  # [B, S_loc, Hkv, D]
    kc: jax.Array,  # [B, T_loc, Hkv, D] — cached-context window shard
    vc: jax.Array,  # [B, T_loc, Hkv, D]
    prefix_lens: jax.Array,  # [B] int32 — valid context tokens (global)
    *,
    axis: str,
) -> jax.Array:
    """Ring attention for a prompt CHUNK resuming at an arbitrary offset.

    Two ring passes share one unnormalized online-softmax carry
    (acc, m, l): first the chunk's own K/V blocks under a chunk-relative
    causal mask (the prefix offset cancels on both sides, so the plain
    ``q_pos >= k_pos`` mask of ``_ring_attention_local`` is exact), then
    the gathered context window under ``t_pos < prefix_lens`` (the
    chunk's freshly scattered keys sit at positions >= prefix_len, so
    the window pass never double-counts them). One normalization at the
    end — identical math to a single softmax over [context ++ chunk].

    The chunk pass runs FIRST: its step-0 block is the diagonal (every
    query attends at least itself), which seeds a finite running max
    so a fully masked context (``prefix_lens == 0``) contributes
    ``exp(-1e30 - m) == 0`` instead of poisoning the accumulator.
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    T = kc.shape[1]  # local context block length (T_global / sp)
    idx = jax.lax.axis_index(axis)
    n = jax.lax.psum(1, axis)
    scale = 1.0 / math.sqrt(D)
    perm = [(j, (j + 1) % n) for j in range(n)]

    qg = q.reshape(B, S, Hkv, group, D)
    q_pos = idx * S + jnp.arange(S)  # chunk-relative query positions

    def merge(acc, m, l, logits, vb):
        """Online-softmax merge of one block into the running carry."""
        m_cur = jnp.max(logits, axis=-1)  # [B, Hkv, group, S]
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + probs.sum(-1)
        pv = jnp.einsum("bhgst,bthd->bshgd", probs.astype(vb.dtype), vb)
        acc = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return acc, m_new, l_new

    def chunk_step(carry, i):
        acc, m, l, kb, vb = carry
        src = (idx - i) % n
        k_pos = src * S + jnp.arange(S)
        logits = jnp.einsum(
            "bshgd,bthd->bhgst", qg, kb,
            preferred_element_type=jnp.float32,
        ) * scale
        mask = q_pos[:, None] >= k_pos[None, :]  # [S, S]
        logits = jnp.where(mask[None, None, None, :, :], logits, -1e30)
        acc, m, l = merge(acc, m, l, logits, vb)
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        return (acc, m, l, kb, vb), None

    def ctx_step(carry, i):
        acc, m, l, kb, vb = carry
        src = (idx - i) % n
        t_pos = src * T + jnp.arange(T)  # global window positions
        logits = jnp.einsum(
            "bshgd,bthd->bhgst", qg, kb,
            preferred_element_type=jnp.float32,
        ) * scale
        mask = t_pos[None, :] < prefix_lens[:, None]  # [B, T]
        logits = jnp.where(mask[:, None, None, None, :], logits, -1e30)
        acc, m, l = merge(acc, m, l, logits, vb)
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        return (acc, m, l, kb, vb), None

    acc0 = jnp.zeros((B, S, Hkv, group, D), jnp.float32)
    m0 = jnp.full((B, Hkv, group, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, group, S), jnp.float32)
    (acc, m, l, _, _), _ = jax.lax.scan(
        chunk_step, (acc0, m0, l0, k, v), jnp.arange(n)
    )
    (acc, m, l, _, _), _ = jax.lax.scan(
        ctx_step, (acc, m, l, kc, vc), jnp.arange(n)
    )
    denom = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    out = (acc / denom).astype(q.dtype)
    return out.reshape(B, S, H * D)


def _ulysses_attention_local(
    q: jax.Array, k: jax.Array, v: jax.Array, *, axis: str, causal: bool
) -> jax.Array:
    """Ulysses: all-to-all so each device holds ALL positions for a slice
    of heads, attends locally, then all-to-alls back. Requires
    n_kv_heads % sp == 0."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    n = jax.lax.psum(1, axis)
    group = H // Hkv

    # scatter heads, gather sequence: [B, S, H, D] → [B, S*n, H/n, D]
    def head_scatter(x):
        heads = x.shape[2]
        x = x.reshape(B, S, n, heads // n, D)
        x = jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                               tiled=False)
        return x.reshape(B, S * n, heads // n, D)

    def head_gather(x, heads):
        x = x.reshape(B, n, S, heads // n, D)
        x = jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                               tiled=False)
        return x.reshape(B, S, heads, D)

    qh = head_scatter(q)  # [B, T, H/n, D]
    kh = head_scatter(k)
    vh = head_scatter(v)
    T = S * n
    scale = 1.0 / math.sqrt(D)
    hq = qh.shape[2]
    hkv = kh.shape[2]
    g = hq // hkv
    qg = qh.reshape(B, T, hkv, g, D)
    logits = jnp.einsum("bshgd,bthd->bhgst", qg, kh,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        pos = jnp.arange(T)
        mask = pos[:, None] >= pos[None, :]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs.astype(vh.dtype), vh)
    out = out.reshape(B, T, hq, D)
    out = head_gather(out, H)
    return out.reshape(B, S, H * D)


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "causal", "strategy")
)
def ring_attention(
    q: jax.Array,  # [B, S, H, D] — S sharded over `axis`
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    *,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    strategy: str = "ring",  # "ring" | "ulysses"
) -> jax.Array:
    """Sequence-parallel attention; returns [B, S, H*D] sharded like q."""
    local = (
        _ring_attention_local if strategy == "ring"
        else _ulysses_attention_local
    )
    fn = jax.shard_map(
        functools.partial(local, axis=axis, causal=causal),
        mesh=mesh,
        in_specs=(
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis, None, None),
        ),
        out_specs=P(None, axis, None),
        check_vma=False,
    )
    return fn(q, k, v)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def ring_attention_prefix(
    q: jax.Array,  # [B, S, H, D] — chunk queries; S sharded over `axis`
    k: jax.Array,  # [B, S, Hkv, D] — chunk keys (in-register)
    v: jax.Array,  # [B, S, Hkv, D]
    kc: jax.Array,  # [B, T, Hkv, D] — gathered page window; T sharded
    vc: jax.Array,  # [B, T, Hkv, D]
    prefix_lens: jax.Array,  # [B] int32 — cached tokens ahead of chunk
    *,
    mesh: Mesh,
    axis: str = "sp",
) -> jax.Array:
    """Sequence-parallel chunk attention with cached-prefix resume.

    Requires S % sp == 0 and T % sp == 0 (the engine's chunk rungs are
    rounded up to a multiple of the sp axis, and the page window is a
    whole number of pages with page_size % sp == 0). Ring strategy only:
    Ulysses would all-to-all the full window per layer, defeating the
    point of chunking. Returns [B, S, H*D] sharded like q.
    """
    fn = jax.shard_map(
        functools.partial(_ring_prefix_attention_local, axis=axis),
        mesh=mesh,
        in_specs=(
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None),
        ),
        out_specs=P(None, axis, None),
        check_vma=False,
    )
    return fn(q, k, v, kc, vc, prefix_lens)
