"""Shared by the Olmo-Hybrid tests: seeded float32 weights with every
norm weight, ``A_log`` and ``dt_bias`` away from their init values (a
norm weight of 1 would hide a norm that is left out; one decay for
every head would hide heads that are swapped), and the reference's
logits."""

from __future__ import annotations

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from aigw_tpu.models import olmo_hybrid as oh
from aigw_tpu.models.reference import olmo_hybrid_ref as ref

#: two periods; key and value head widths 24 / 48
CFG = oh.TINY


def make_params(cfg=CFG, seed: int = 0, dtype=jnp.float32) -> dict:
    p = oh.init_params(jax.random.PRNGKey(seed), cfg, dtype=dtype)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), len(p)))
    for name in sorted(p):
        leaf = name.split(".")[-1]
        k = next(keys)
        if leaf.endswith("norm") or leaf == "norm_f":
            p[name] = (1.0 + 0.3 * jax.random.normal(k, p[name].shape)
                       ).astype(dtype)
        elif leaf in ("A_log", "dt_bias"):
            p[name] = (p[name] + 0.5 * jax.random.normal(k, p[name].shape)
                       ).astype(dtype)
    return p


def ref_cfg(cfg=CFG) -> dict:
    return dataclasses.asdict(cfg)


@functools.lru_cache(maxsize=None)
def _ref_forward(cfg, **kw):
    # (jitted: the same plain operations, compiled once a length)
    return jax.jit(functools.partial(ref.forward, cfg=ref_cfg(cfg), **kw))


def ref_logits(p, cfg, tokens, **kw) -> np.ndarray:
    """The reference's logits [S, V] for one sequence."""
    return np.asarray(_ref_forward(cfg, **kw)(
        p, tokens=jnp.asarray(np.asarray(tokens, np.int32))))


@functools.lru_cache(maxsize=None)
def programs(cfg=CFG, page_size: int = 0):
    """The family's entry points jitted for ``cfg`` (the engine jits
    them too; eager, every primitive would compile on its own)."""
    kw = dict(cfg=cfg, page_size=page_size)
    return types.SimpleNamespace(
        prefill=jax.jit(functools.partial(oh.prefill, **kw)),
        prefill_suffix=jax.jit(functools.partial(oh.prefill_suffix, **kw)),
        decode_step=jax.jit(functools.partial(oh.decode_step, **kw)),
        hidden_states=jax.jit(functools.partial(oh.hidden_states, cfg=cfg)))


def make_cache(cfg, n_pages: int, page_size: int, n_slots: int = 2,
               dtype: str = "float32"):
    return cfg.cache_spec().make((n_pages + 1) * page_size, n_slots, dtype)
