"""What a model family keeps on the device between steps, as one small
description the engine asks for shapes and bytes.

Two kinds of memory can live side by side:

- the **page pool** ``[kv_layers, 2, rows, n_kv_heads, head_dim]`` of
  models/kvq.py — only the layers that attend over keys and values have
  pages (every layer of the llama and mixtral families; every fourth of
  a hybrid linear-attention family) — or, for a family whose token
  leaves ONE row a layer that every head reads (latent attention,
  models/axk1.py), the **latent pool** ``[kv_layers, width, rows]``:
  no K/V pair and no head axis, a token's values down a column and
  tokens along the lanes (where the width is no multiple of the chip's
  128 lanes its compiler lays a ``[rows, width]`` pool out so anyway,
  copying it there and back in every program: PERF.md section 6, PR 45).
  A family whose keys are wider than its values (models/mimo_v2.py:
  192 over 128) keeps a token's heads flattened in that one row, ``v |
  k`` — 1280 values a token a layer, no plane padded to the other's
  width — in the same column pool: along a column a head's rows start
  on a sublane tile (PERF.md section 6, PR 47);
- **per-slot state**: leaves ``[layers, slots, ...]`` indexed by decode
  slot and not paged — a recurrent layer's state does not grow with the
  context, so a sequence owns exactly one row of each for its lifetime.
  So does what a SLIDING-WINDOW attention layer keeps: the last
  ``window`` tokens' keys and values, a ring a slot written at
  ``position % window`` (models/mimo_v2.py's five window layers of
  seven; ``CacheSpec.window``). Such a layer owns no pages, whatever
  the context.

A family with no per-slot state keeps the bare pool of models/kvq.py:
its programs, their pytrees and their compile-cache keys are what they
were before this description existed. A family with state gets a
:class:`StateCache` — the pool and the state leaves in one pytree that
rides the same donation chain and the decode scan's carry.

What moves pages only (the host KV tier, parking and migration of a live
sequence, fleet fetch, speculative verify) cannot serve a family with
per-slot state: a page without the state that goes with it is half a
sequence — a recurrent state, or a slot's window keys. Nor, yet, a
family whose pages are latent rows: the movers' programs and checks know
K and V planes alone. The engine switches them off by asking
``spec.pinned`` — by what the family is, not by a flag.

The prefix cache is the one mover that a family with recurrent state can
have: where the state at a chunk boundary of a prompt is the whole of
what the state layers know of the prefix (``spec.snapshots``), the
engine copies a slot's state leaves into a row of a **snapshot pool** —
the same leaves as the slot state, ``snapshot_rows`` rows — at chosen
boundaries of a prefill, keyed with the page chain; a later prompt's hit
is valid only at a chain node that holds a snapshot, which is copied
back into the slot before ``prefill_suffix`` resumes there. Everything
else that ``pinned`` names stays off.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, NamedTuple

import jax.numpy as jnp

from aigw_tpu.models import kvq


class StateCache(NamedTuple):
    """The device cache of a family with per-slot state (a pytree)."""

    kv: Any  # the page pool of models/kvq.py (array, or {"q","scale"})
    slots: dict  # name -> [layers, n_slots, ...] per-slot state leaf


@dataclass(frozen=True)
class CacheSpec:
    kv_layers: int  # layers that own pages
    n_kv_heads: int
    head_dim: int
    #: per-slot leaves: (name, layers, shape of one slot's row, dtype
    #: name, or ``activation``)
    slot_state: tuple[tuple[str, int, tuple[int, ...], str], ...] = ()
    #: a token leaves ``head_dim`` values a layer (one column of the
    #: pool), which every head reads: no K/V pair and no head axis
    latent: bool = False
    #: tokens a sliding-window layer attends over, its own among them:
    #: such layers are not among ``kv_layers`` — what they keep is a
    #: ``slot_state`` leaf ``window`` tokens long, whatever the context
    #: (0: every attending layer attends over its whole context)
    window: int = 0
    #: the ``slot_state`` at a chunk boundary of a prompt is all that
    #: the state layers know of the prefix, so a copy of it taken there
    #: lets a later prompt resume at that boundary: the prefix cache
    #: serves the family (a ring of window keys is not such a state
    #: yet: ROADMAP.md M4)
    snapshots: bool = False

    @property
    def stateful(self) -> bool:
        return bool(self.slot_state)

    @property
    def pinned(self) -> str:
        """Why a sequence of this family cannot be moved, shared or
        verified page by page (``features_off``'s reason); empty for
        the K/V pool every page mover knows."""
        if self.window:
            return ("a slot's window keys live beside its pages: the "
                    f"window layers keep a slot's last {self.window} "
                    "tokens and own no pages (ROADMAP.md M2, M4: a ring "
                    "snapshot at a page boundary)")
        if self.snapshots:
            return ("the family keeps per-slot recurrent state beside its "
                    "pages, and only the prefix cache carries a snapshot of "
                    "it with the pages (ROADMAP.md M4: the other movers)")
        if self.stateful:
            return ("the family keeps per-slot recurrent state beside its "
                    "pages (ROADMAP.md M4: state snapshots)")
        if self.latent:
            return ("the family's pages hold one latent row a token, not "
                    "K and V planes, which is all the page movers and the "
                    "verify step know (ROADMAP.md M3)")
        return ""

    def kv_shape(self, n_rows: int) -> tuple[int, ...]:
        if self.latent:
            return (self.kv_layers, self.head_dim, n_rows)
        return (self.kv_layers, 2, n_rows, self.n_kv_heads, self.head_dim)

    def kv_page_bytes(self, page_size: int, kv_cache_dtype: str) -> int:
        """HBM bytes of one page across the layers that have pages
        (quantized pools: packed elements plus the f32 scale rows)."""
        per_elt = kvq.bytes_per_kv_element(kv_cache_dtype)
        scale = 4 if kvq.is_quantized_dtype(kv_cache_dtype) else 0
        rows = 1 if self.latent else 2 * self.n_kv_heads
        return int(self.kv_layers * rows * page_size
                   * (self.head_dim * per_elt + scale))

    def state_bytes_per_slot(self, kv_cache_dtype: str) -> int:
        """Bytes one decode slot's recurrent state holds, whatever the
        length of its context."""
        return sum(layers * math.prod(shape)
                   * _leaf_dtype(dt, kv_cache_dtype).itemsize
                   for _, layers, shape, dt in self.slot_state)

    def snapshot_rows(self, n_slots: int) -> int:
        """Rows of the snapshot pool — THE rule that sizes it, from the
        family's spec and the engine's slot count alone: three a slot.
        A sequence in a slot has at most three boundaries worth keeping
        at a time (the end of a prefix it shares with other sequences,
        the last whole chunk of its own prompt, and the boundary its
        next turn will pass while this one is still referenced); fewer
        and a turn's own snapshot evicts the shared one, more is memory
        the page pool would use better."""
        return 3 * n_slots if self.snapshots else 0

    def make_snapshots(self, n_slots: int, kv_cache_dtype: str) -> dict:
        """The zero-initialised snapshot pool: the ``slot_state``
        leaves with ``snapshot_rows`` rows where a slot pool has
        ``n_slots``."""
        return self._state_leaves(self.snapshot_rows(n_slots),
                                  kv_cache_dtype)

    def _state_leaves(self, n_rows: int, kv_cache_dtype: str) -> dict:
        return {
            name: jnp.zeros((layers, n_rows, *shape),
                            _leaf_dtype(dt, kv_cache_dtype))
            for name, layers, shape, dt in self.slot_state}

    def make(self, n_rows: int, n_slots: int, kv_cache_dtype: str,
             mesh=None, data_spec=None):
        """Zero-initialised device cache: the bare pool, or a
        :class:`StateCache` around it."""
        pool = kvq.make_pool(self.kv_shape(n_rows), kv_cache_dtype, mesh,
                             data_spec)
        if not self.stateful:
            return pool
        return StateCache(pool, self._state_leaves(n_slots, kv_cache_dtype))


def _leaf_dtype(name: str, kv_cache_dtype: str):
    """A state leaf's dtype; ``activation`` follows the pool's compute
    width (float32 with a float32 pool, else bfloat16)."""
    if name == "activation":
        name = "float32" if kv_cache_dtype == "float32" else "bfloat16"
    return jnp.dtype(name)


def spec_of(model_cfg: Any) -> CacheSpec:
    """The family's own description (``model_cfg.cache_spec()``), or
    the uniform pool of a family whose every layer has pages."""
    own = getattr(model_cfg, "cache_spec", None)
    if own is not None:
        return own()
    return CacheSpec(model_cfg.n_layers, model_cfg.n_kv_heads,
                     model_cfg.head_dim)
