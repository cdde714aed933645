"""Shared by the MiMo-V2 tests: the tiny share-of-a-deployment
configuration, seeded float32 weights with every norm weight away from
its identity value (a weight of 1 would hide a norm that is left out),
and the reference's logits."""

from __future__ import annotations

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from aigw_tpu.models import mimo_v2 as dv
from aigw_tpu.models.reference import mimo_v2_ref as ref

#: 32-wide router, 8 experts held from id 4: one chip's share, with
#: absent experts on both sides of it — top-4 of 32 leaves a token none
#: of whose picks is held about a third of the time
SHARE = dataclasses.replace(dv.TINY, num_experts=8, router_experts=32,
                            held_from=4)


def make_params(cfg, seed: int = 0, dtype=jnp.float32) -> dict:
    p = dv.init_params(jax.random.PRNGKey(seed), cfg, dtype=dtype)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), len(p)))
    for name in sorted(p):
        leaf = name.split(".")[-1]
        k = next(keys)
        if leaf.endswith("norm") or leaf == "norm_f":
            p[name] = (1.0 + 0.3 * jax.random.normal(k, p[name].shape)
                       ).astype(dtype)
    return p


def ref_cfg(cfg) -> dict:
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
def programs(cfg, page_size: int = 0):
    """The family's entry points jitted for ``cfg`` (the engine jits
    them too; eager, every primitive would compile on its own)."""
    kw = dict(cfg=cfg, page_size=page_size)
    return types.SimpleNamespace(
        prefill=jax.jit(functools.partial(dv.prefill, **kw),
                        static_argnames=("moe_stats",)),
        prefill_suffix=jax.jit(functools.partial(dv.prefill_suffix, **kw),
                               static_argnames=("moe_stats",)),
        decode_step=jax.jit(functools.partial(dv.decode_step, **kw),
                            static_argnames=("moe_stats",)),
        hidden_states=jax.jit(functools.partial(dv.hidden_states, cfg=cfg)))


def make_cache(cfg, n_pages: int, page_size: int, n_slots: int = 2,
               dtype: str = "float32"):
    return cfg.cache_spec().make((n_pages + 1) * page_size, n_slots, dtype)
