"""Shared by the Qwen3-Next tests: the tiny share-of-a-deployment
configuration, seeded float32 weights with every norm weight, ``A_log``
and ``dt_bias`` NON-zero (a zero-centred norm weight of 0 would hide a
``w`` / ``1 + w`` mix-up), and the reference's logits."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from aigw_tpu.models import qwen3_next as qn
from aigw_tpu.models.reference import qwen3_next_ref as ref

#: 16-wide router, 8 experts held from id 4: one chip's share, with
#: absent experts on both sides of it
SHARE = dataclasses.replace(qn.TINY, num_experts=8, router_experts=16,
                            held_from=4)


def make_params(cfg, seed: int = 0, dtype=jnp.float32) -> dict:
    p = qn.init_params(jax.random.PRNGKey(seed), cfg, dtype=dtype)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), len(p)))
    for name in sorted(p):
        leaf = name.split(".")[-1]
        k = next(keys)
        if leaf.endswith("norm") or leaf == "norm_f":
            base = 1.0 if leaf == "gdn_norm" else 0.0
            p[name] = (base + 0.3 * jax.random.normal(k, p[name].shape)
                       ).astype(dtype)
        elif leaf == "A_log":
            p[name] = jax.random.uniform(
                k, p[name].shape, minval=-1.0, maxval=1.0).astype(dtype)
        elif leaf == "dt_bias":
            p[name] = jax.random.uniform(
                k, p[name].shape, minval=-4.0, maxval=-1.0).astype(dtype)
    return p


def ref_logits(p, cfg, tokens) -> np.ndarray:
    """The reference's logits [S, V] for one sequence."""
    return np.asarray(ref.forward(p, dataclasses.asdict(cfg),
                                  np.asarray(tokens, np.int32)))


def make_cache(cfg, n_pages: int, page_size: int, n_slots: int,
               dtype: str = "float32"):
    return cfg.cache_spec().make((n_pages + 1) * page_size, n_slots, dtype)
