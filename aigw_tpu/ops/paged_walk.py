"""The decode step's attention read: an online-softmax walk over the
pages the live rows hold.

One function for every family's decode step (models/llama.py,
models/qwen3_next.py; mixtral through llama's ``mlp=``). Nothing
padded is materialised: the pool is viewed as a list of pages and
read one whole page per (row, page id), rows that are not live are
not walked, and what is read past the live pages is less than one
trip.

How a step is cut up (once a step, shared by every layer; the pool's
format picks the plan, ``models/kvq.walk_plan``):

- A K/V pool ``[L, 2, n_slots, Hkv, D]`` takes the PAIR plan
  (``pair_plan``): the live (row, page) pairs as ONE flat list — row
  ``b`` gives ``ceil(lengths[b] / page)`` of them in column order, a
  dead row none, the rows in row order — of static length ``B * P``
  (rounded up to whole trips) padded past its end, walked ``N`` pairs
  a trip in one loop. ``N`` is static and follows the program's shapes
  alone (``trip_pairs``: ``_TRIP_BYTES`` over a pair's bytes, 16 on
  qwen2, and no fewer than ``_MIN_TRIP_PAIRS``); ``trips =
  ceil(n_pairs / N)`` is the traced loop bound, so a page bucket is
  still one program. A trip folds each pair's (max, sum,
  values) into float32 per-row statistics carried across trips, through
  the ``[N, B]`` one-hot of the pairs' rows: pairs of one row are
  adjacent, and a row may span trips. No sort and no block rule.
- A latent pool keeps the BLOCK plan (``walk_plan``): the rows sorted
  longest first (dead rows, length 0, last) and walked in blocks of
  ``R`` rows; a block makes as many trips as its longest row needs,
  ``G`` pages of each of its rows a trip (``walk_blocks``: static, from
  the program's shapes). Its per-row accumulator is ``H x rank``
  float32, 128 KB a row and as large as the page it came from, so a
  carried ``[B, H, rank]`` would cost half a trip's bytes again, where
  a K/V pool's ``[B, H, D]`` is 229 KB against a 4 MiB trip. What the
  blocks pad — every trip to its block's longest row — read 1.6 to 3.3
  times the live pages on the K/V pools' cells (PERF.md, PR 49) and
  1.1 to 1.3 on the latent ones.

Either plan's ``pages_read`` bounds the loops and feeds the
``decode_kv_pages_read`` counter (tpuserve/engine.py): the count IS the
trip count.

Precision: K, V and q stay in the pool's dtype as matmul operands,
products accumulate in float32 (``preferred_element_type``), the
softmax statistics (running max, sum) and the output accumulator are
float32 across trips (the fold of a trip's pairs into their rows is a
float32 product with a one-hot at ``Precision.HIGHEST``). int8/int4
pools dequantise at the read exactly as ``kvq.window_kv`` does
(float32 product with the row's scale, rounded to bfloat16).

The matmuls keep K and V pages in the layout they are stored in. The
pool is ``[.., n_slots, Hkv, D]``: a token's heads lie side by side,
and turning a page into per-head ``[page, D]`` matrices is a re-layout
(a copy of every window read, a layer: what a chunk, whose attention
is per-head products over its whole window, still pays after
:func:`window_pages` has read the window's pages — on the pages it
read, never on the pool). Here a page is read as it lies,
``page*Hkv`` rows of (token, head) by ``D``, and every query head is
multiplied against
every row: ``[N, H, D] x [N, page*Hkv, D]^T`` for the logits,
``[N, H, page*Hkv] x [N, page*Hkv, D]`` for the values, with the
rows of the other KV heads masked out of the softmax. With H <= 128
those rows ride in the MXU's padding: no transpose of a page, and the
whole pool goes to the loops as ONE ``[L*2*n_pages, page*Hkv, D]``
list of pages (:func:`page_list`; a layer sliced out of it would be a
copy).

The chunk, tail and verify programs read their window through the
same list (:func:`window_pages`, ISSUE 46): every row's whole page
window ``[B, P*page, Hkv, D]`` as ``B*P`` page reads. Before that they
indexed ``kv[layer, w]`` by token — and what that cost was not the
8192 one-token rows but the layer's K and V sliced out of the pool
first, a copy of 1/L of the pool a layer-call (PERF.md, PR 46).

A latent pool ``[L, W, n_slots]`` (models/cache.py: ``W`` values a token
a layer, down a column, which every head reads; latent attention in its
ABSORBED form) takes the block plan through :func:`latent_decode_walk`: a
page ``[W, page]`` is read ONCE a trip and serves as keys at its whole
width and as values at its first ``rank`` rows, with no rows of other
heads to mask.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

#: K and V bytes a trip should move: enough that the loop's own
#: bookkeeping (a few microseconds a trip) stays under the read itself
_TRIP_BYTES = 4 << 20
#: fewest pairs a trip of the flat walk reads (``trip_pairs``)
_MIN_TRIP_PAIRS = 8


class PairPlan(NamedTuple):
    """How one decode step's walk over a K/V pool is cut up: the live
    (row, page) pairs as one flat list (see the module docstring)."""

    row: jax.Array  # [n] int32 row of each pair; B past the live pairs
    page: jax.Array  # [n] int32 the pair's page (its page-table entry)
    left: jax.Array  # [n] int32 tokens its row holds from this page on
    order: jax.Array  # [B] int32 rows, the live ones first
    trips: jax.Array  # int32: trips the walk of one layer makes
    pairs: int  # N, pairs a trip; static

    @property
    def pages_read(self) -> jax.Array:
        """(row, page) pairs the walk of ONE layer reads this step."""
        return self.trips * self.pairs


class WalkPlan(NamedTuple):
    """How one decode step's walk over a latent pool is cut up: row
    blocks (see the module docstring)."""

    order: jax.Array  # [n_blk * R] int32 rows, longest first; B = no row
    trips: jax.Array  # [n_blk] int32 trips of each block, 0 past the live
    n_blocks: jax.Array  # int32: blocks holding a live row
    rows: int  # R, static
    pages: int  # G, static

    @property
    def pages_read(self) -> jax.Array:
        """(row, page) pairs the walk of ONE layer reads this step."""
        return jnp.sum(self.trips) * (self.rows * self.pages)


def pair_bytes(pool: jax.Array, page_size: int) -> int:
    """K and V bytes one (row, page) pair holds in a layer of this
    pool [L, 2, n_slots, Hkv, D] — or the page's one column a token of
    a latent pool [L, W, n_slots]."""
    if pool.ndim == 3:
        return page_size * pool.shape[1] * pool.dtype.itemsize
    *_, hkv, d = pool.shape
    return 2 * page_size * hkv * d * pool.dtype.itemsize


def walk_blocks(n_rows: int, n_cols: int, pair: int) -> tuple[int, int]:
    """The block plan's (rows a block R, pages a trip G) from the
    program's shapes:
    rows, page bucket, ``pair_bytes`` of the pool a device holds. A
    trip costs about 6 us of bookkeeping and 3 us a MiB on a v5e
    (PERF.md, PR 31), so it moves about ``_TRIP_BYTES``. A row's pages
    come a quarter of its page bucket a trip, at most eight (what a row
    reads is rounded up to whole trips, and a block is done in four),
    and the rest of the trip is rows: a program with a wide page bucket
    serves long rows and reads more pages of fewer rows a trip — the
    rows that pad a step's last block are read for nothing, and two
    live rows of thirty-two is the common step where windows are long
    — a narrow one the reverse."""
    pairs = max(1, _TRIP_BYTES // max(pair, 1))
    g = max(1, min(n_cols // 4, 8, pairs))
    return max(1, min(n_rows, pairs // g)), g


def walk_plan(lengths: jax.Array, n_cols: int, page_size: int,
              pair: int) -> WalkPlan:
    """Cut a step up. ``lengths`` [B]: tokens each row attends to (its
    new one included), 0 for a row that is not live; ``pair``: the
    pool's ``pair_bytes``."""
    B = lengths.shape[0]
    R, G = walk_blocks(B, n_cols, pair)
    n_blk = -(-B // R)
    need = jnp.minimum(-(-lengths // page_size), n_cols).astype(jnp.int32)
    # longest first, ties by row: each row's rank by counting (B is
    # tens: cheaper than a sort, and the sampler's stays the only one)
    i = jnp.arange(B, dtype=jnp.int32)
    ahead = (need[None, :] > need[:, None]) | (
        (need[None, :] == need[:, None]) & (i[None, :] < i[:, None]))
    order = jnp.zeros((B,), jnp.int32).at[
        jnp.sum(ahead, axis=1, dtype=jnp.int32)].set(i)
    pad = n_blk * R - B
    held = jnp.pad(need[order], (0, pad)).reshape(n_blk, R)
    trips = -(-jnp.max(held, axis=1) // G)
    return WalkPlan(
        order=jnp.pad(order, (0, pad), constant_values=B),
        trips=trips.astype(jnp.int32),
        n_blocks=jnp.sum(trips > 0).astype(jnp.int32),
        rows=R, pages=G)


def trip_pairs(pair: int) -> int:
    """(row, page) pairs a trip of the flat walk reads, from the
    ``pair_bytes`` of the pool a device holds: about ``_TRIP_BYTES``
    (and never more than the page table has, ``pair_plan``), and no
    fewer than ``_MIN_TRIP_PAIRS`` however wide a pair is. A trip is
    some thirty device operations of its own whatever it reads (the
    plan's slices, the fold, the loop that gathers its pages), so a
    pool of 32 key heads under a group of one — 2 MiB a pair — walked
    two pairs a trip was two thirds of a decode step's operations for a
    sixth of its time (PERF.md, PR 52)."""
    return max(_MIN_TRIP_PAIRS, _TRIP_BYTES // max(pair, 1))


def pair_plan(lengths: jax.Array, page_table: jax.Array, page_size: int,
              pair: int) -> PairPlan:
    """Cut a step over a K/V pool up. ``lengths`` [B]: tokens each row
    attends to (its new one included), 0 for a row that is not live;
    ``page_table`` [B, P]; ``pair``: the pool's ``pair_bytes``. Row
    ``b`` gives ``ceil(lengths[b] / page)`` pairs, in column order, the
    rows in row order; no sort: a pair's row is the number of rows that
    end at or before it."""
    B, P = page_table.shape
    N = min(trip_pairs(pair), B * P)
    n = -(-(B * P) // N) * N
    need = jnp.minimum(-(-lengths // page_size), P).astype(jnp.int32)
    end = jnp.cumsum(need)
    j = jnp.arange(n, dtype=jnp.int32)
    row = jnp.sum(j[:, None] >= end[None, :], axis=1, dtype=jnp.int32)
    src = jnp.minimum(row, B - 1)
    col = jnp.clip(j - (end - need)[src], 0, P - 1)
    live = row < B
    i = jnp.arange(B, dtype=jnp.int32)
    held = need > 0
    n_held = jnp.cumsum(held, dtype=jnp.int32)
    rank = jnp.where(held, n_held - 1, n_held[-1] + i - n_held)
    return PairPlan(
        row=row,
        page=jnp.where(live, page_table[src, col], 0),
        left=jnp.where(live, lengths[src].astype(jnp.int32)
                       - col * page_size, 0),
        order=jnp.zeros((B,), jnp.int32).at[rank].set(i),
        trips=-(-end[-1] // N), pairs=N)


def pages_live(lengths: jax.Array, page_size: int) -> jax.Array:
    """Pages the live rows hold this step (``decode_kv_pages_live``)."""
    return jnp.sum(-(-lengths // page_size)).astype(jnp.int32)


def page_list(pool: jax.Array, page_size: int) -> tuple[jax.Array, int]:
    """The WHOLE pool ``[L, 2, n_slots, ...]`` (data, or a quantised
    pool's scales) as ONE list of pages ``[L*2*n_pages, page, ...]``,
    and ``n_pages``: page ``i`` of K (``which`` 0) or V (1) of
    ``layer`` is entry ``(layer*2 + which)*n_pages + i``. A reshape of
    leading dimensions, so nothing moves; nothing is sliced out of the
    pool (a slice handed to a loop or a gather is a copy)."""
    L, _, n_slots = pool.shape[:3]
    n_pages = n_slots // page_size
    return pool.reshape(L * 2 * n_pages, page_size, *pool.shape[3:]), n_pages


def window_pages(pool: jax.Array, layer, which: int, page_table: jax.Array,
                 page_size: int) -> jax.Array:
    """Every row's page window of K (``which`` 0) or V (1) of ``layer``:
    pages ``page_table`` [B, P] read whole out of the one list of pages
    → ``[B, P*page, ...]``, token ``t`` of the window at ``t``. What
    ``pool[layer, which][page_table*page + arange(page)]`` gives, bit
    for bit, without the layer slice that indexing copies first.

    The pages read are PINNED to the order they lie in: left free, the
    chip's compiler may choose the order the attention behind the read
    wants for them and then re-lay the read's OPERAND to match — a copy
    of the whole pool a layer-call (seen at B >= 2 with two KV heads of
    256; PERF.md, PR 46). Pinned, that re-layout is of the pages read."""
    B, P = page_table.shape
    pages, n_pages = page_list(pool, page_size)
    x = jnp.take(pages, (layer * 2 + which) * n_pages + page_table, axis=0,
                 mode="clip")  # [B, P, page, ...]
    x = with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))
    return x.reshape(B, P * page_size, *x.shape[3:])


@functools.partial(jax.jit, static_argnames=("page_size", "N"))
def _walk(q, pool, scale, layer, row, page, left, trips, *, page_size, N):
    """The walk itself, jitted on its own so that a decode program
    traces and lowers it ONCE for all its layers (``layer`` is traced):
    a replica traces every program at every boot to look it up in the
    compile cache, and 28 unrolled copies of the block walk's two
    nested loops cost a warm boot half a second a program (PERF.md,
    PR 31)."""
    B, H, D = q.shape
    Hkv = pool.shape[3]
    grp = H // Hkv
    T = page_size * Hkv  # (token, head) rows of a page
    quant = scale is not None
    # operands in the pool's dtype (a quantised pool reads as bfloat16,
    # as at the gather); a float32 q against a bfloat16 pool promotes
    cdt = jnp.promote_types(q.dtype, jnp.bfloat16 if quant else pool.dtype)

    # the WHOLE pool, every layer's K and V pages in one list: nothing
    # is sliced out of it (a slice handed to a loop is a copy), and
    # merging (token, head) is no re-layout where a page's rows lie
    pages, n_pages = page_list(pool, page_size)
    if quant:
        scales, _ = page_list(scale, page_size)

    def read(which, ids):
        ids = (layer * 2 + which) * n_pages + ids
        x = jnp.take(pages, ids, axis=0, mode="clip")  # [N,page,Hkv,D]
        if quant:
            s = jnp.take(scales, ids, axis=0, mode="clip")
            x = (x.astype(jnp.float32) * s[..., None]).astype(jnp.bfloat16)
        return x.reshape(N, T, D).astype(cdt)

    qc = q.astype(cdt)
    at = jnp.arange(T, dtype=jnp.int32)
    # row (token, j) of a page is query head n's to see where j is n's
    # KV head; the other rows ride along in the MXU's padding
    own = (at % Hkv)[None, :] == (
        jnp.arange(H, dtype=jnp.int32) // grp)[:, None]  # [H, T]
    rows_b = jnp.arange(B, dtype=jnp.int32)
    inv = 1.0 / math.sqrt(D)

    def trip(t, carry):
        m, l, acc = carry  # float32 [B, H], [B, H], [B, H, D]
        rows, ids, rem = (lax.dynamic_slice(x, (t * N,), (N,))
                          for x in (row, page, left))
        k = read(0, ids)
        v = read(1, ids)
        s = jnp.einsum("rnd,rtd->rnt", jnp.take(qc, rows, axis=0,
                                                 mode="clip"), k,
                       preferred_element_type=jnp.float32) * inv
        live = (at // Hkv)[None, :] < rem[:, None]  # [N, T]
        s = jnp.where(own[None] & live[:, None, :], s, -1e30)
        # the pairs of a row fold into the row's statistics through the
        # one-hot of their rows (a padding pair names row B: nowhere)
        of = (rows[:, None] == rows_b[None, :])[:, :, None]  # [N, B, 1]
        m_new = jnp.maximum(m, jnp.max(jnp.where(
            of, jnp.max(s, axis=2)[:, None, :], -1e30), axis=0))
        alpha = jnp.exp(m - m_new)
        m_own = jnp.sum(jnp.where(of, m_new[None], 0.0), axis=1)  # [N, H]
        p = jnp.exp(s - m_own[:, :, None])
        l = alpha * l + jnp.sum(jnp.where(
            of, jnp.sum(p, axis=2)[:, None, :], 0.0), axis=0)
        pv = jnp.einsum("rnt,rtd->rnd", p.astype(cdt), v,
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[:, :, None] + jnp.einsum(
            "rb,rnd->bnd", of[:, :, 0].astype(jnp.float32), pv,
            precision=lax.Precision.HIGHEST)
        return m_new, l, acc

    m0 = jnp.full((B, H), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H), jnp.float32)
    acc0 = jnp.zeros((B, H, D), jnp.float32)
    _, l, acc = lax.fori_loop(0, trips, trip, (m0, l0, acc0))
    # a row that is not live was in no pair: zero over the floor
    return (acc / jnp.maximum(l, 1e-30)[:, :, None]).astype(q.dtype)


@jax.named_scope("layer/kv_walk")
def paged_decode_walk(
    q: jax.Array,  # [B, H, D] roped query
    pool: jax.Array,  # [L, 2, n_slots, Hkv, D] (native or int8/int4)
    layer: int,
    page_table: jax.Array,  # [B, P] int32
    lengths: jax.Array,  # [B] int32 — rows to attend (incl. new token)
    *,
    page_size: int,
    scale: jax.Array | None = None,  # [L, 2, n_slots, Hkv] f32 (quantised)
    plan: PairPlan | None = None,
    mesh=None,
) -> jax.Array:
    """Attention of each live row's query over the pages it holds in
    ``layer`` of the pool; the new token's K/V are already scattered
    (``lengths`` includes them). Returns [B, H, D] in q's dtype; rows
    with ``lengths == 0`` are in no pair and come back zero. ``plan``:
    this step's ``pair_plan`` (made here when the caller has none).
    With ``mesh`` the walk runs under shard_map, each device over ITS
    head shard of the pool (heads on ``tp``) — local reads, no
    collective inside attention; H and Hkv must divide the axis
    (tpuserve/attention.resolve_decode_backend guards that)."""
    if plan is None:
        plan = pair_plan(lengths, page_table, page_size,
                         pair_bytes(pool, page_size))
    layer = jnp.asarray(layer, jnp.int32)
    static = dict(page_size=page_size, N=plan.pairs)
    if mesh is None:
        return _walk(q, pool, scale, layer, plan.row, plan.page, plan.left,
                     plan.trips, **static)
    from jax.sharding import PartitionSpec as Ps

    rep, axis = Ps(), "tp"
    scales = () if scale is None else (scale,)

    def local(q_, pool_, *rest):
        *plan_, sc = rest if scales else (*rest, None)
        return _walk(q_, pool_, sc, *plan_, **static)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(Ps(None, axis, None), Ps(None, None, None, axis, None))
        + (rep,) * 5 + (Ps(None, None, None, axis),) * len(scales),
        out_specs=Ps(None, axis, None), check_vma=False,
    )(q, pool, layer, plan.row, plan.page, plan.left, plan.trips, *scales)


def latent_pages(pool: jax.Array, layer, ids: jax.Array,
                 page_size: int) -> jax.Array:
    """Pages ``ids`` [R, G] of ``layer`` of a latent pool [L, W,
    n_slots], each row's ``G`` side by side along the lanes → [R, W,
    G*page]: whole pages sliced out of the WHOLE pool (a layer sliced
    out of it would be a copy), as they lie."""
    W = pool.shape[1]
    R, G = ids.shape
    return jnp.stack([jnp.concatenate([lax.dynamic_slice(
        pool, (layer, 0, ids[r, g] * page_size), (1, W, page_size))[0]
        for g in range(G)], axis=1) for r in range(R)])


@functools.partial(jax.jit, static_argnames=(
    "page_size", "R", "G", "rank", "scale", "keys_from"))
def _walk_latent(q, pool, layer, page_table, lengths, order, trips,
                 n_blocks, *, page_size, R, G, rank, scale, keys_from=0):
    """The walk over a latent pool, by row blocks (the module docstring
    says why not by pairs): ``n_blocks`` blocks of ``R`` rows of
    ``order``, ``trips[blk]`` trips each, an online softmax with float32
    statistics; a trip reads ``G`` pages of each of its ``R`` rows
    ONCE, ``[R, W, G*page]`` as they lie (tokens along the lanes),
    multiplies every head's absorbed query against the whole width for
    the logits and the probabilities against the first ``rank`` rows
    (the latent) for the values. ``keys_from``: a column's keys are its
    rows from there on (a flattened ``v | k`` row, models/mimo_v2.py:
    two products of two widths over one page read), not the whole."""
    B, H, _ = q.shape
    P = page_table.shape[1]
    T = G * page_size
    cdt = jnp.promote_types(q.dtype, pool.dtype)
    qc = q.astype(cdt)
    pt = jnp.pad(page_table, ((0, 0), (0, -P % G)))
    at = jnp.arange(T, dtype=jnp.int32)

    def block(blk, out):
        rows = lax.dynamic_slice(order, (blk * R,), (R,))
        src = jnp.minimum(rows, B - 1)
        qb = qc[src]  # [R, H, W]
        len_r = jnp.where(rows < B, lengths[src], 0)
        pt_r = pt[src]

        def trip(t, carry):
            m, l, acc = carry
            ids = lax.dynamic_slice(pt_r, (0, t * G), (R, G))
            x = latent_pages(pool, layer, ids, page_size).astype(cdt)
            s = jnp.einsum("rnd,rdt->rnt", qb,
                           x[:, keys_from:] if keys_from else x,
                           preferred_element_type=jnp.float32) * scale
            live = (t * T + at)[None, :] < len_r[:, None]  # [R, T]
            s = jnp.where(live[:, None, :], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=2))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[:, :, None])
            l = alpha * l + jnp.sum(p, axis=2)
            pv = jnp.einsum("rnt,rct->rnc", p.astype(cdt), x[:, :rank],
                            preferred_element_type=jnp.float32)
            return m_new, l, acc * alpha[:, :, None] + pv

        m0 = jnp.full((R, H), -1e30, jnp.float32)
        l0 = jnp.zeros((R, H), jnp.float32)
        acc0 = jnp.zeros((R, H, rank), jnp.float32)
        _, l, acc = lax.fori_loop(0, trips[blk], trip, (m0, l0, acc0))
        o = acc / jnp.maximum(l, 1e-30)[:, :, None]
        dst = jnp.where(len_r > 0, rows, B)  # not live: written nowhere
        return out.at[dst].set(o.astype(q.dtype), mode="drop")

    return lax.fori_loop(0, n_blocks, block,
                         jnp.zeros((B, H, rank), q.dtype))


@jax.named_scope("layer/kv_walk")
def latent_decode_walk(
    q: jax.Array,  # [B, H, W] absorbed query: q~ over the latent | q_rope
    pool: jax.Array,  # [L, W, n_slots] latent columns: c_kv | k_rope
    layer: int,
    page_table: jax.Array,  # [B, P] int32
    lengths: jax.Array,  # [B] int32 — rows to attend (incl. new token)
    *,
    page_size: int,
    rank: int,  # the latent's width: a column's value part
    scale: float,  # the softmax scale (not 1/sqrt(W): the family's)
    plan: WalkPlan | None = None,
    keys_from: int = 0,  # a column's key part starts here (q: that wide)
) -> jax.Array:
    """Absorbed latent attention of each live row's query over the
    pages it holds in ``layer``; the new token's column is already
    written. Returns the attended latent [B, H, rank] in q's dtype
    (``W_kvb``'s value half is the caller's to apply); rows with
    ``lengths == 0`` are not walked and come back zero."""
    if plan is None:
        plan = walk_plan(lengths, page_table.shape[1], page_size,
                         pair_bytes(pool, page_size))
    return _walk_latent(
        q, pool, jnp.asarray(layer, jnp.int32), page_table, lengths,
        plan.order, plan.trips, plan.n_blocks, page_size=page_size,
        R=plan.rows, G=plan.pages, rank=rank, scale=float(scale),
        keys_from=keys_from)
