"""Fused Pallas TPU decode step: RoPE + KV append + paged attention in
ONE kernel per dispatch, with optional int8/int4 KV pages dequantized
in-kernel against per-page scale blocks (models/kvq.py layout).

The chained decode path runs, per layer: rope (XLA) → K/V scatter
(XLA) → window gather → dense attention — four HBM round-trips of which
the padded-window gather is the largest. This kernel collapses them:

- Grid ``(B, P)`` — sequence, then pages innermost, exactly the
  ``paged_attention_decode_v2`` walk (scalar-prefetch page table,
  data-dependent index map, ragged DMA skip: pages past a sequence's
  last valid page clamp to it, so the Pallas pipeline skips the
  re-fetch and HBM traffic scales with real cache occupancy).
- **RoPE in-kernel**: per-dispatch interleaved cos/sin tables
  ``[B, D]`` are precomputed once outside (they depend only on the
  positions scalar vector); the rotation itself — the per-head FLOPs —
  runs in VMEM as ``x·cos + (x @ S)·sin`` where ``S`` is the constant
  pair-swap matrix (built from iotas; a [D, D] MXU matmul instead of a
  lane-strided shuffle, which Mosaic lays out poorly).
- **In-kernel append**: the new K/V row (quantized when the pool is
  int8/int4: symmetric absmax per head, the kvq.py recipe bit-for-bit)
  is written into its page through ``input_output_aliases`` on the pool
  buffers — the output block spec targets the append page, which for a
  mid-page append IS the final walk block already in VMEM, so the
  read-modify-write costs one extra block copy-out, not a scatter pass
  over HBM. A page-aligned append starts a fresh page (no prior rows to
  preserve). Inactive slots write a zero row into the pool's LAST page,
  which the engine reserves as a dump page no page table ever
  references (the Pallas output pipeline must write *somewhere*; the
  XLA paths get the same guarantee from OOB-drop scatters).
- **Attention**: online softmax over the walked pages (pool rows
  ``< position``) with the new token's K/V folded in-register at
  finalize — the attended value for the current token is exactly the
  quantize→dequantize round-trip later steps will read back from HBM,
  so a token's view of itself never drifts between steps.
- **In-kernel dequant**: quantized pages multiply by their scale
  column as they stream through VMEM — the packed layout never
  round-trips through HBM at full width.

Semantics match ``ops/paged_walk.paged_decode_walk`` (the XLA page
walk: every family's default decode rung, and what this request runs
off-TPU and, under a mesh, per head-shard inside shard_map):
scatter-then-walk attends pool rows ``<= position`` where
row ``position`` holds the freshly appended (round-tripped) values —
identical numbers to walk-then-fold. Parity is asserted in
tests/test_pallas_ops.py at production shapes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_QMAX = {"int8": 127.0, "int4": 7.0}


def _rope_tables(positions: jax.Array, head_dim: int,
                 rope_theta: float):
    """Interleaved cos/sin tables [B, D] for the kernel's in-VMEM
    rotation: column d carries angle(pos, d // 2)."""
    freqs = 1.0 / (rope_theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    full = jnp.repeat(freqs, 2)  # [D]
    ang = positions.astype(jnp.float32)[:, None] * full[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _swap_matrix(D: int):
    """Constant [D, D] pair-swap-with-sign matrix: (x @ S)[2i] =
    -x[2i+1], (x @ S)[2i+1] = x[2i] — the rotate-pairs half of
    interleaved RoPE as an MXU matmul."""
    r = jax.lax.broadcasted_iota(jnp.int32, (D, D), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (D, D), 1)
    up = ((c == r + 1) & (r % 2 == 0)).astype(jnp.float32)
    dn = ((c == r - 1) & (r % 2 == 1)).astype(jnp.float32)
    return up - dn


def _rope_rows(x: jax.Array, cos: jax.Array, sin: jax.Array):
    """Rotate rows [R, D] by the interleaved tables [1, D] (f32 in/out)."""
    S = _swap_matrix(x.shape[-1])
    rot = jax.lax.dot_general(
        x, S, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return x * cos + rot * sin


def _fused_kernel(
    # scalar prefetch
    pt_ref,  # [B * P] int32 — pool page id per (b, p)
    len_ref,  # [B] int32 — pool rows already written (= position)
    act_ref,  # [B] int32 — 1 when the slot decodes this step
    apg_ref,  # [B] int32 — pool page the new row lands in (dump page
    #           for inactive slots)
    arow_ref,  # [B] int32 — row within that page (position % page)
    # blocks
    q_ref,  # [1, H, D] unroped query
    kn_ref,  # [1, 1, Hkv * D] unroped new key
    vn_ref,  # [1, 1, Hkv * D] new value
    cos_ref,  # [1, 1, D] f32
    sin_ref,  # [1, 1, D] f32
    k_ref,  # [page, Hkv * D] pool page (walk index map)
    v_ref,  # [page, Hkv * D]
    *rest,  # [ks_ref, vs_ref,] o_ref, ko_ref, vo_ref[, kso_ref, vso_ref]
    #         + scratch m_ref, l_ref, acc_ref, qr_ref
    page_size: int,
    n_pages: int,
    n_kv_heads: int,
    head_dim: int,
    qmax: float,
):
    # Mosaic layout discipline (the kernel never lowered before PR 21):
    # every block's last two dims equal the array's or tile (8, 128);
    # heads are addressed as lane windows [.., h*D:(h+1)*D] of 2-D rows
    # — no in-kernel reshape between (H*D,) and (H, D), no 1-D values,
    # no scalar-predicate selects (masks are iota comparisons).
    quant = qmax > 0.0
    if quant:
        (ks_ref, vs_ref, o_ref, ko_ref, vo_ref, kso_ref, vso_ref,
         m_ref, l_ref, acc_ref, qr_ref) = rest
    else:
        o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref, qr_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)
    D = head_dim
    H = q_ref.shape[1]
    grp = H // n_kv_heads
    page = k_ref.shape[0]

    def head_col(ref, h):
        """Column h of a [page, Hkv] scale block as [page, 1] — a
        masked lane reduction (a width-1 lane window at offset h is an
        unaligned load Mosaic may refuse)."""
        lane = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 1)
        return jnp.sum(jnp.where(lane == h, ref[:], 0.0), axis=1,
                       keepdims=True)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # rope q once per sequence; reused (pre-scaled) by every page
        # step and the finalize fold. Round through the model compute
        # dtype exactly like the XLA path (rope() returns x.dtype
        # before attention reads it)
        qr = _rope_rows(q_ref[0].astype(jnp.float32), cos_ref[0],
                        sin_ref[0]).astype(q_ref.dtype).astype(
            jnp.float32)
        qr_ref[:] = qr / math.sqrt(D)

    length = len_ref[b]
    valid = jnp.clip(length - p * page_size, 0, page_size)

    @pl.when(valid > 0)
    def _attend():
        mask = jax.lax.broadcasted_iota(
            jnp.int32, (grp, page), 1) < valid
        for h in range(n_kv_heads):
            rows = slice(h * grp, (h + 1) * grp)
            k_h = k_ref[:, h * D:(h + 1) * D].astype(jnp.float32)
            v_h = v_ref[:, h * D:(h + 1) * D].astype(jnp.float32)
            if quant:
                k_h = k_h * head_col(ks_ref, h)
                v_h = v_h * head_col(vs_ref, h)
            logits = jax.lax.dot_general(
                qr_ref[rows, :], k_h,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # qr is pre-scaled by 1/sqrt(D)
            logits = jnp.where(mask, logits, -1e30)
            m_prev = m_ref[rows, 0:1]
            m_new = jnp.maximum(
                m_prev, jnp.max(logits, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(logits - m_new)
            l_ref[rows, 0:1] = alpha * l_ref[rows, 0:1] + jnp.sum(
                probs, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                probs, v_h,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + pv
            m_ref[rows, 0:1] = m_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        arow = arow_ref[b]
        cos = cos_ref[0]
        sin = sin_ref[0]
        # per KV head, as [1, D] rows: the value later reads see
        # (k_eff/v_eff, f32) and the row the pool stores (k_st/v_st)
        k_eff, v_eff, k_st, v_st, k_sc, v_sc = [], [], [], [], [], []
        for h in range(n_kv_heads):
            cols = slice(h * D, (h + 1) * D)
            kn = _rope_rows(kn_ref[0, :, cols].astype(jnp.float32),
                            cos, sin).astype(kn_ref.dtype).astype(
                jnp.float32)
            vn = vn_ref[0, :, cols].astype(jnp.float32)
            if quant:
                # the kvq.py recipe, bit-for-bit: symmetric absmax per
                # head, round-half-even, qmax-clipped
                k_amax = jnp.max(jnp.abs(kn), axis=1, keepdims=True)
                v_amax = jnp.max(jnp.abs(vn), axis=1, keepdims=True)
                k_s = jnp.where(k_amax > 0.0, k_amax / qmax, 1.0)
                v_s = jnp.where(v_amax > 0.0, v_amax / qmax, 1.0)
                kq = jnp.clip(jnp.round(kn / k_s), -qmax, qmax)
                vq = jnp.clip(jnp.round(vn / v_s), -qmax, qmax)
                # the value every later read dequantizes to — fold THAT
                k_eff.append(kq * k_s)
                v_eff.append(vq * v_s)
                k_st.append(kq)
                v_st.append(vq)
                k_sc.append(k_s)
                v_sc.append(v_s)
            else:
                # the bf16/f32 round-trip the chained scatter+gather pays
                k_eff.append(kn.astype(ko_ref.dtype).astype(jnp.float32))
                v_eff.append(vn.astype(vo_ref.dtype).astype(jnp.float32))
                k_st.append(kn)
                v_st.append(vn)

        @pl.when(act_ref[b] == 1)
        def _fold_new_token():
            for h in range(n_kv_heads):
                rows = slice(h * grp, (h + 1) * grp)
                logit = jnp.sum(qr_ref[rows, :] * k_eff[h], axis=1,
                                keepdims=True)  # [grp, 1]
                m_prev = m_ref[rows, 0:1]
                m_new = jnp.maximum(m_prev, logit)
                alpha = jnp.exp(m_prev - m_new)
                pnew = jnp.exp(logit - m_new)
                l_ref[rows, 0:1] = alpha * l_ref[rows, 0:1] + pnew
                acc_ref[rows, :] = (acc_ref[rows, :] * alpha
                                    + pnew * v_eff[h])
                m_ref[rows, 0:1] = m_new

        denom = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)

        # -- append: rewrite the target page with the new row ----------
        # rows below the append row are the sequence's earlier tokens
        # (the final walk block already in VMEM — a mid-page append IS
        # that block); the append row takes the new values; rows above
        # it are unwritten future positions and come out zero. A
        # page-aligned append (arow == 0) keeps nothing, so the stale
        # walk block it never fetched cannot leak in. Inactive slots
        # land on the dump page, whose content nobody reads.
        row = jax.lax.broadcasted_iota(jnp.int32, (page, D), 0)
        for h in range(n_kv_heads):
            cols = slice(h * D, (h + 1) * D)
            ko_ref[:, cols] = jnp.where(
                row == arow, k_st[h].astype(ko_ref.dtype),
                jnp.where(row < arow, k_ref[:, cols],
                          jnp.zeros_like(k_ref[:, cols])))
            vo_ref[:, cols] = jnp.where(
                row == arow, v_st[h].astype(vo_ref.dtype),
                jnp.where(row < arow, v_ref[:, cols],
                          jnp.zeros_like(v_ref[:, cols])))
        if quant:
            srow = jax.lax.broadcasted_iota(
                jnp.int32, (page, n_kv_heads), 0)
            lane = jax.lax.broadcasted_iota(
                jnp.int32, (1, n_kv_heads), 1)
            ks_new = jnp.zeros((1, n_kv_heads), jnp.float32)
            vs_new = jnp.zeros((1, n_kv_heads), jnp.float32)
            for h in range(n_kv_heads):
                ks_new = jnp.where(lane == h, k_sc[h], ks_new)
                vs_new = jnp.where(lane == h, v_sc[h], vs_new)
            kso_ref[:] = jnp.where(
                srow == arow, ks_new,
                jnp.where(srow < arow, ks_ref[:], 0.0))
            vso_ref[:] = jnp.where(
                srow == arow, vs_new,
                jnp.where(srow < arow, vs_ref[:], 0.0))


@functools.partial(
    jax.jit,
    static_argnames=("rope_theta", "page_size", "interpret"))
def fused_paged_decode(
    q: jax.Array,  # [B, H, D] UNROPED query
    k_new: jax.Array,  # [B, Hkv, D] UNROPED new key
    v_new: jax.Array,  # [B, Hkv, D] new value
    k_rows: jax.Array,  # [n_slots, Hkv, D] pool (native or int8/int4)
    v_rows: jax.Array,
    page_table: jax.Array,  # [B, P] int32
    positions: jax.Array,  # [B] int32 — position of the new token
    active: jax.Array,  # [B] bool
    k_scale: jax.Array | None = None,  # [n_slots, Hkv] f32 (quantized)
    v_scale: jax.Array | None = None,
    *,
    rope_theta: float,
    page_size: int,
    interpret: bool = False,
):
    """One fused decode dispatch. Returns ``(attn [B, H, D] in q's
    dtype, k_rows', v_rows'[, k_scale', v_scale'])`` — the pool leaves
    are updated IN the kernel (input_output_aliases) with the new row
    appended at ``positions``; inactive rows write into the pool's last
    page (the engine-reserved dump page)."""
    B, H, D = q.shape
    n_slots, Hkv, _ = k_rows.shape
    P = page_table.shape[1]
    quant = k_scale is not None
    qdt = str(k_rows.dtype)
    qmax = _QMAX.get(qdt, 0.0) if quant else 0.0

    lengths = jnp.where(active, positions, 0).astype(jnp.int32)
    act = active.astype(jnp.int32)
    dump_page = n_slots // page_size - 1
    app_idx = jnp.clip(positions // page_size, 0, P - 1)
    app_page = jnp.where(
        active,
        jnp.take_along_axis(page_table, app_idx[:, None], axis=1)[:, 0],
        dump_page).astype(jnp.int32)
    app_row = jnp.where(active, positions % page_size, 0).astype(
        jnp.int32)
    cos_t, sin_t = _rope_tables(positions, D, rope_theta)

    kn3d = k_new.reshape(B, 1, Hkv * D)
    vn3d = v_new.reshape(B, 1, Hkv * D)
    k2d = k_rows.reshape(n_slots, Hkv * D)
    v2d = v_rows.reshape(n_slots, Hkv * D)
    flat_pt = page_table.reshape(-1)

    def row_index(b, p, pt, ln, ac, apg, ar):
        return b, 0, 0

    def kv_index(b, p, pt, ln, ac, apg, ar):
        # ragged DMA skip: pages past the last valid page clamp to it
        last = jnp.maximum(ln[b] - 1, 0) // page_size
        return pt[b * P + jnp.minimum(p, last)], 0

    def append_index(b, p, pt, ln, ac, apg, ar):
        return apg[b], 0

    in_specs = [
        pl.BlockSpec((1, H, D), row_index),
        pl.BlockSpec((1, 1, Hkv * D), row_index),
        pl.BlockSpec((1, 1, Hkv * D), row_index),
        pl.BlockSpec((1, 1, D), row_index),
        pl.BlockSpec((1, 1, D), row_index),
        pl.BlockSpec((page_size, Hkv * D), kv_index),
        pl.BlockSpec((page_size, Hkv * D), kv_index),
    ]
    inputs = [q, kn3d, vn3d, cos_t[:, None, :], sin_t[:, None, :],
              k2d, v2d]
    out_specs = [
        pl.BlockSpec((1, H, D), row_index),
        pl.BlockSpec((page_size, Hkv * D), append_index),
        pl.BlockSpec((page_size, Hkv * D), append_index),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, H, D), q.dtype),
        jax.ShapeDtypeStruct(k2d.shape, k2d.dtype),
        jax.ShapeDtypeStruct(v2d.shape, v2d.dtype),
    ]
    # alias indices count ALL flattened operands, scalar-prefetch args
    # included (5 scalars, then q/kn/vn/cos/sin at 5-9, pools at 10+)
    aliases = {10: 1, 11: 2}  # k2d → ko, v2d → vo
    if quant:
        in_specs += [
            pl.BlockSpec((page_size, Hkv), kv_index),
            pl.BlockSpec((page_size, Hkv), kv_index),
        ]
        inputs += [k_scale, v_scale]
        out_specs += [
            pl.BlockSpec((page_size, Hkv), append_index),
            pl.BlockSpec((page_size, Hkv), append_index),
        ]
        out_shape += [
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ]
        aliases[12] = 3  # k_scale → kso
        aliases[13] = 4  # v_scale → vso

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, P),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),  # roped q / sqrt(D)
        ],
    )
    kernel = functools.partial(
        _fused_kernel, page_size=page_size, n_pages=P,
        n_kv_heads=Hkv, head_dim=D, qmax=qmax,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(flat_pt, lengths, act, app_page, app_row, *inputs)
    attn = outs[0]
    k_out = outs[1].reshape(n_slots, Hkv, D)
    v_out = outs[2].reshape(n_slots, Hkv, D)
    if quant:
        return attn, k_out, v_out, outs[3], outs[4]
    return attn, k_out, v_out
