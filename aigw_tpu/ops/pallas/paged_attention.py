"""Pallas TPU kernel: ragged paged-attention prefill.

Queries attend over a paged KV cache. Design (ragged-paged-attention
style, PAPERS.md arxiv 2604.15464 — implementation is original):

- Grid ``(q-blocks, B, P)`` — pages innermost; one grid step
  streams a full pool page (all KV heads). The page table is a
  **scalar-prefetch** argument, so each page's K/V block is DMA'd from
  the HBM pool straight to VMEM by the Pallas pipeline (auto
  double-buffered) using a *data-dependent* index map: page ``p`` of
  sequence ``b`` comes from pool row ``page_table[b, p]``.
- Online softmax across pages: running max / denominator / weighted
  accumulator live in VMEM scratch, carried across the page loop for a
  fixed sequence; the output tile is written on the last page.
- GQA: each KV head's ``group = H // Hkv`` query heads run as plain 2D
  matmuls against that head's lane window of the page (Mosaic-friendly;
  K/V stay un-repeated in HBM).
- **Ragged DMA skip** — the index map *clamps* page indices past a
  sequence's last valid page to the last valid page itself, so
  consecutive grid steps see an unchanged block index and the Pallas
  pipeline skips the re-fetch — HBM traffic scales with the tokens
  actually in the cache, not the padded window. (Compute for those
  steps is already masked by ``pl.when``.)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _flash_update(rows, q, k_h, v_h, mask, m_ref, l_ref, acc_ref):
    """One online-softmax step for a row block: fold this page's
    masked logits into the running (max, denom, accumulator) scratch.
    ``rows`` selects one KV head's query rows of the scratch."""
    D = q.shape[1]
    logits = jax.lax.dot_general(
        q, k_h,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / math.sqrt(D)
    logits = jnp.where(mask, logits, -1e30)
    m_prev = m_ref[rows, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    probs = jnp.exp(logits - m_new)
    l_ref[rows, 0:1] = alpha * l_ref[rows, 0:1] + jnp.sum(
        probs, axis=1, keepdims=True
    )
    pv = jax.lax.dot_general(
        probs, v_h,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[rows, :] = acc_ref[rows, :] * alpha + pv
    m_ref[rows, 0:1] = m_new


def _ragged_prefill_kernel(
    # scalar prefetch
    cu_ref,  # [B + 1] int32 — packed-row offsets: seq b owns [cu[b], cu[b+1])
    start_ref,  # [B] int32 — absolute position of seq b's first packed token
    page_table_ref,  # [B * P] int32
    # blocks
    q_ref,  # [QB, H * D] — one block of the packed query stream
    k_ref,  # [page, Hkv * D] — pool page selected by index map
    v_ref,  # [page, Hkv * D]
    o_ref,  # [QB, H * D]
    # scratch
    m_ref,  # [Hkv * QB * group, 128] f32
    l_ref,  # [Hkv * QB * group, 128] f32
    acc_ref,  # [Hkv * QB * group, D] f32
    *,
    page_size: int,
    n_pages: int,
    n_kv_heads: int,
    head_dim: int,
    q_block: int,
):
    nq = pl.program_id(0)
    b = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when((b == 0) & (p == 0))
    def _init_out():
        # rows owned by no sequence (tail padding) must read as zeros;
        # owned rows are overwritten at their sequence's finalize step
        o_ref[:] = jnp.zeros_like(o_ref)

    @pl.when(p == 0)
    def _init_scratch():
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    lo = cu_ref[b]
    hi = cu_ref[b + 1]
    base = nq * q_block
    own_lo = jnp.maximum(lo - base, 0)  # block-relative owned rows
    own_hi = jnp.minimum(hi - base, q_block)
    overlap = own_hi > own_lo
    # highest query position any owned row of this block reaches: pages
    # entirely past it contribute nothing (and their DMA is skipped by
    # the clamped index map)
    max_pos = start_ref[b] + jnp.minimum(hi, base + q_block) - 1 - lo
    grp = q_ref.shape[1] // (n_kv_heads * head_dim)

    @pl.when(overlap & (p * page_size <= max_pos))
    def _attend():
        D = head_dim
        QB = q_block
        page = k_ref.shape[0]
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (QB * grp, page), 0) // grp  # block row r
        g_idx = base + rows  # global packed row
        owned = (g_idx >= lo) & (g_idx < hi)
        pos = start_ref[b] + g_idx - lo
        col = jax.lax.broadcasted_iota(jnp.int32, (QB * grp, page), 1)
        mask = owned & ((p * page_size + col) <= pos)
        for h in range(n_kv_heads):
            sl = slice(h * QB * grp, (h + 1) * QB * grp)
            q_h = q_ref[:, h * grp * D:(h + 1) * grp * D].astype(
                jnp.float32).reshape(QB * grp, D)
            k_h = k_ref[:, h * D:(h + 1) * D].astype(jnp.float32)
            v_h = v_ref[:, h * D:(h + 1) * D].astype(jnp.float32)
            _flash_update(sl, q_h, k_h, v_h, mask, m_ref, l_ref, acc_ref)

    @pl.when((p == n_pages - 1) & overlap)
    def _finalize():
        D = head_dim
        QB = q_block
        denom = jnp.maximum(l_ref[:, 0:1], 1e-30)
        out = acc_ref[:] / denom  # [Hkv * QB * grp, D]
        row1 = jax.lax.broadcasted_iota(jnp.int32, (QB, 1), 0)
        owned_rows = (row1 >= own_lo) & (row1 < own_hi)  # [QB, 1]
        # the o block is shared by every sequence this q block spans:
        # write only the rows seq b owns, preserve the rest
        for h in range(n_kv_heads):
            sl = slice(h * QB * grp, (h + 1) * QB * grp)
            cols = slice(h * grp * D, (h + 1) * grp * D)
            blk = out[sl].reshape(QB, grp * D).astype(o_ref.dtype)
            o_ref[:, cols] = jnp.where(owned_rows, blk, o_ref[:, cols])


@functools.partial(
    jax.jit, static_argnames=("page_size", "q_block", "interpret"))
def ragged_prefill_attention(
    q: jax.Array,  # [T, H, D] — PACKED variable-length query stream
    k_pool: jax.Array,  # [n_slots, Hkv, D]
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, P] int32
    cu_seqlens: jax.Array,  # [B + 1] int32 packed-row offsets per sequence
    start_pos: jax.Array,  # [B] int32 absolute position of each first row
    *,
    page_size: int,
    q_block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Ragged paged-attention prefill (PAPERS.md arxiv 2604.15464): ONE
    program for any batch geometry. The query stream packs every
    sequence's new tokens back to back (sequence b owns packed rows
    [cu_seqlens[b], cu_seqlens[b+1]), its first row sitting at absolute
    position start_pos[b] — nonzero for offset-resumed prefill: prefix-
    cache partial hits and chunked-prefill continuations), padded only
    to a multiple of ``q_block`` — compute scales with TOTAL tokens, not
    per-sequence buckets. Causal flash attention runs against the paged
    KV pool (prefix pages plus the freshly scattered chunk) with the
    scalar-prefetch page table + ragged DMA skip of the module
    docstring; grid (q-blocks, seqs, pages) revisits each
    query block per overlapping sequence, so a block spanning a sequence
    boundary is handled by masking rather than host-side alignment.
    Returns [T, H, D]."""
    T, H, D = q.shape
    n_slots, Hkv, _ = k_pool.shape
    B, P = page_table.shape
    grp = H // Hkv
    qb = min(q_block, T)
    if T % qb:
        raise ValueError(f"packed length {T} not a multiple of "
                         f"q_block {qb}")
    q2d = q.reshape(T, H * D)
    k2d = k_pool.reshape(n_slots, Hkv * D)
    v2d = v_pool.reshape(n_slots, Hkv * D)
    flat_pt = page_table.reshape(-1)

    def q_index(nq, b, p, cu, st, pt):
        return nq, 0

    def kv_index(nq, b, p, cu, st, pt):
        # ragged DMA skip: pages past the sequence's last attended page
        # clamp to it — unchanged block index ⇒ the pipeline skips the
        # re-fetch (see module docstring)
        last = jnp.maximum(st[b] + cu[b + 1] - cu[b] - 1, 0) // page_size
        return pt[b * P + jnp.minimum(p, last)], 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T // qb, B, P),
        in_specs=[
            pl.BlockSpec((qb, H * D), q_index),
            pl.BlockSpec((page_size, Hkv * D), kv_index),
            pl.BlockSpec((page_size, Hkv * D), kv_index),
        ],
        out_specs=pl.BlockSpec((qb, H * D), q_index),
        scratch_shapes=[
            pltpu.VMEM((Hkv * qb * grp, 128), jnp.float32),
            pltpu.VMEM((Hkv * qb * grp, 128), jnp.float32),
            pltpu.VMEM((Hkv * qb * grp, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _ragged_prefill_kernel, page_size=page_size, n_pages=P,
        n_kv_heads=Hkv, head_dim=D, q_block=qb,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H * D), q.dtype),
        interpret=interpret,
    )(cu_seqlens, start_pos, flat_pt, q2d, k2d, v2d)
    return out.reshape(T, H, D)
