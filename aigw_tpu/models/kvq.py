"""Quantized paged KV pool: int8/int4 pages + per-page scale blocks.

The serving pool (models/llama.py) is a flat page array
``[L, 2, n_slots, Hkv, D]``. With ``kv_cache_dtype`` in
{"int8", "int4"} the pool becomes a TWO-leaf pytree:

    {"q":     int8|int4  [L, 2, n_slots, Hkv, D],
     "scale": float32    [L, 2, n_slots, Hkv]}

Every token row of a page carries one symmetric absmax scale per KV
head — the page's *scale block* ``[page_size, Hkv]`` lives in a pool
paged exactly like the data (same slot axis), so a page and its scales
always move together: spill, revive, migration, cross-replica fetch and
copy-on-write all slice axis 2 and are layout-agnostic (they tree_map
over the leaves). Per-row scales make the append a single quantized row
write — no page-wide requantization, so already-written rows never
re-round as a sequence grows (deterministic, order-independent pages).

Quantization is symmetric round-to-nearest-even in float32:

    scale = absmax / qmax   (1.0 when the row is all-zero)
    q     = clip(round(x / scale), -qmax, qmax)

with qmax 127 (int8) / 7 (int4; -8 unused keeps the grid symmetric).
Dequantization is ``q * scale`` in float32 — done at the read by the
XLA paths — the decode walk's trips (``walk_kv``) and a chunk's, tail's
or verify program's window (``window_kv``), both of which take whole
pages out of the pool viewed as one list of pages — so the quantized
layout never round-trips through HBM at full width.

Byte math per token across the stack (D = head_dim):
    native bf16:  L * 2 * Hkv * D * 2
    int8:         L * 2 * Hkv * (D + 4)      (~0.52x at D=128)
    int4:         L * 2 * Hkv * (D/2 + 4)    (~0.27x at D=128)

The native ("bfloat16"/"float32") pool stays a bare array — every
helper here degenerates to exactly the pre-quantization op sequence, so
native programs and their jit cache keys are unchanged.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from aigw_tpu.ops import paged_walk

#: valid EngineConfig.kv_cache_dtype values
KV_DTYPES = ("bfloat16", "float32", "int8", "int4")
QUANT_DTYPES = ("int8", "int4")

_QMAX = {"int8": 127.0, "int4": 7.0}
_QDTYPE = {"int8": jnp.int8, "int4": jnp.int4}


def is_quantized_dtype(kv_cache_dtype: str) -> bool:
    return kv_cache_dtype in QUANT_DTYPES


def is_quantized(kv: Any) -> bool:
    """True when ``kv`` is the two-leaf quantized pool pytree."""
    return isinstance(kv, dict)


def quant_bits(kv_cache_dtype: str) -> int:
    """Bits per stored KV element (the ``kv_quant_bits`` gauge)."""
    return {"float32": 32, "bfloat16": 16, "int8": 8, "int4": 4}[
        kv_cache_dtype]


def bytes_per_kv_element(kv_cache_dtype: str) -> float:
    """HBM bytes per stored element INCLUDING the amortized scale
    (per-row, per-head f32 → 4/D extra bytes per element; the caller
    multiplies by D so the page math stays exact)."""
    return {"float32": 4.0, "bfloat16": 2.0, "int8": 1.0,
            "int4": 0.5}[kv_cache_dtype]


def compute_dtype(kv_cache_dtype: str):
    """jnp dtype of the DATA leaf."""
    if kv_cache_dtype in _QDTYPE:
        return _QDTYPE[kv_cache_dtype]
    return jnp.float32 if kv_cache_dtype == "float32" else jnp.bfloat16


def make_pool(kv_shape: tuple, kv_cache_dtype: str, mesh=None,
              data_spec=None):
    """Zero-initialized pool: bare array (native) or {"q","scale"}
    pytree (quantized). ``kv_shape`` = [L, 2, n_slots, Hkv, D]. With a
    ``mesh`` the pool is created ALREADY sharded — the data leaf takes
    ``data_spec`` (heads on "tp"), the scale leaf drops that spec's
    trailing head_dim axis — so it never exists whole on one device."""
    data = scale = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        data = NamedSharding(mesh, data_spec)
        scale = NamedSharding(mesh, PartitionSpec(*data_spec[:-1]))
    if not is_quantized_dtype(kv_cache_dtype):
        return jnp.zeros(kv_shape, compute_dtype(kv_cache_dtype),
                         device=data)
    return {
        "q": jnp.zeros(kv_shape, _QDTYPE[kv_cache_dtype], device=data),
        "scale": jnp.zeros(kv_shape[:-1], jnp.float32, device=scale),
    }


def quantize_rows(x: jax.Array, kv_cache_dtype: str):
    """Quantize K or V rows ``[..., Hkv, D]`` → (q same shape,
    scale [..., Hkv] f32). Symmetric absmax per (row, head);
    deterministic (round-half-even in f32)."""
    qmax = _QMAX[kv_cache_dtype]
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0.0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -qmax, qmax)
    return q.astype(_QDTYPE[kv_cache_dtype]), scale


def dequantize_rows(q: jax.Array, scale: jax.Array) -> jax.Array:
    """(q [..., Hkv, D], scale [..., Hkv]) → float32 rows."""
    return q.astype(jnp.float32) * scale[..., None]


# -- model-side pool ops --------------------------------------------------
def n_slots(kv: Any) -> int:
    """Row count of the pool (the OOB scatter-drop target)."""
    return (kv["q"] if is_quantized(kv) else kv).shape[2]


def kv_dtype_of(kv: Any) -> str:
    """The kv_cache_dtype string a live pool was built with (wire/
    validation helper)."""
    d = (kv["q"] if is_quantized(kv) else kv).dtype
    if d == jnp.int8:
        return "int8"
    if d == jnp.int4:
        return "int4"
    return "float32" if d == jnp.float32 else "bfloat16"


def scatter_kv(kv: Any, layer: int, flat: jax.Array, k: jax.Array,
               v: jax.Array) -> Any:
    """Write K/V rows at flat slot indices (mode="drop" — OOB rows are
    padding). Native: the exact pre-quantization scatter. Quantized:
    rows are quantized and land with their scale rows in one pass."""
    if not is_quantized(kv):
        kv = kv.at[layer, 0, flat].set(k, mode="drop")
        return kv.at[layer, 1, flat].set(v, mode="drop")
    dt = kv_dtype_of(kv)
    qk, sk = quantize_rows(k, dt)
    qv, sv = quantize_rows(v, dt)
    pool = kv["q"].at[layer, 0, flat].set(qk, mode="drop")
    pool = pool.at[layer, 1, flat].set(qv, mode="drop")
    scale = kv["scale"].at[layer, 0, flat].set(sk, mode="drop")
    scale = scale.at[layer, 1, flat].set(sv, mode="drop")
    return {"q": pool, "scale": scale}


def window_kv(kv: Any, layer: int, page_table: jax.Array,
              page_size: int):
    """Read every row's page window of ``layer``: (k, v), each
    ``[B, P*page, Hkv, D]`` for ``page_table`` [B, P] — the read of
    the chunk, tail and verify programs. Whole pages out of the pool
    as one list of pages (``paged_walk.window_pages``: the view the
    decode walk reads by), never token rows out of a layer's slice.
    Native: pool dtype out. Quantized: the pages of int rows and of
    their scales are read the same way, dequantized in f32 at the read
    (HBM traffic is the packed bytes) and rounded to bf16 — the serving
    compute dtype, so a quantized pool never silently promotes the
    activation stack to f32."""
    def pages(pool, which):
        return paged_walk.window_pages(pool, layer, which, page_table,
                                       page_size)

    if not is_quantized(kv):
        return pages(kv, 0), pages(kv, 1)
    k = dequantize_rows(pages(kv["q"], 0), pages(kv["scale"], 0))
    v = dequantize_rows(pages(kv["q"], 1), pages(kv["scale"], 1))
    return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)


def walk_plan(kv: Any, lengths: jax.Array, page_table: jax.Array,
              page_size: int, mesh=None):
    """How this decode step's page walk is cut up (one plan a step,
    shared by every layer), by the pool's format: a K/V pool takes the
    flat list of live (row, page) pairs (``paged_walk.PairPlan``), a
    latent pool, whose per-row accumulator is as large as the page it
    came from, row blocks (``paged_walk.WalkPlan``). Sized from the
    shapes of the pool as one device holds it — under a mesh a head
    shard of it."""
    shards = int(mesh.shape.get("tp", 1)) if mesh is not None else 1
    pool = kv["q"] if is_quantized(kv) else kv
    pair = paged_walk.pair_bytes(pool, page_size) // shards
    if pool.ndim == 3:
        return paged_walk.walk_plan(lengths, page_table.shape[1], page_size,
                                    pair)
    return paged_walk.pair_plan(lengths, page_table, page_size, pair)


def walk_kv(kv: Any, layer: int, q: jax.Array, page_table: jax.Array,
            lengths: jax.Array, page_size: int,
            plan: paged_walk.PairPlan, mesh=None) -> jax.Array:
    """The decode step's read of ``layer``: attention of each live
    row's roped ``q`` [B, H, D] over the pages the row holds, its new
    K/V already scattered (ops/paged_walk.py: whole pages, live rows
    only, dequantizing at the read). Returns [B, H, D]."""
    pool, scale = (kv["q"], kv["scale"]) if is_quantized(kv) else (kv, None)
    return paged_walk.paged_decode_walk(
        q, pool, layer, page_table, lengths, page_size=page_size,
        scale=scale, plan=plan, mesh=mesh)


def layer_pool(kv: Any, layer: int, which: int):
    """(rows [n_slots, Hkv, D], scale [n_slots, Hkv] | None) — the flat
    per-layer pool view the ragged prefill kernel consumes."""
    if not is_quantized(kv):
        return kv[layer, which], None
    return kv["q"][layer, which], kv["scale"][layer, which]


# -- host-side page helpers (wire / spill / migration) --------------------
def page_to_host(rows: Any) -> Any:
    """Device page slice → host representation: np array (native) or
    {"q": np, "scale": np} (quantized). Bit-exact — quantized pages
    travel at native dtype + scales, never re-rounded."""
    if is_quantized(rows):
        return {"q": np.asarray(rows["q"]),
                "scale": np.asarray(rows["scale"])}
    return np.asarray(rows)


def page_nbytes(rows: Any) -> int:
    """Byte size of a host-side page (HostKVTier budget accounting).
    np int4 reports 1 byte/element — charge the PACKED size the device
    layout implies, so the host budget mirrors HBM math."""
    if isinstance(rows, dict):
        q = rows["q"]
        qb = q.size // 2 if q.dtype.name == "int4" else q.nbytes
        return int(qb + rows["scale"].nbytes)
    n = getattr(rows, "nbytes", None)
    return int(n) if n is not None else len(rows)


def page_shape_ok(rows: Any, want: tuple) -> bool:
    """Validate an imported page against the engine's
    (L, 2, page_size, Hkv, D) geometry (both layouts)."""
    if isinstance(rows, dict):
        return (tuple(rows["q"].shape) == want
                and tuple(rows["scale"].shape) == want[:-1])
    return tuple(rows.shape) == want


def page_matches_dtype(rows: Any, kv_cache_dtype: str) -> bool:
    """An imported page must match the pool's dtype family — a
    quantized page cannot scatter into a native pool (or vice versa)
    without silently changing its bytes."""
    if isinstance(rows, dict):
        return str(rows["q"].dtype) == kv_cache_dtype
    return not is_quantized_dtype(kv_cache_dtype)
