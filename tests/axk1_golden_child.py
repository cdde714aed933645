"""The latent family's four programs on one fixed input, at the tiny
configuration, in float32, on the CPU: what tests/test_axk1.py holds
the serving leaves (``axk1.serving_params``) to.

    python tests/axk1_golden_child.py <checkout> <out.npz>

imports the program from ``<checkout>`` and writes its outputs:
tests/data/axk1_programs_be12620.npz was taken so from the parent
commit of ISSUE 50, BEFORE the change, on ``init_params``' own tree.
A module that has ``serving_params`` runs the programs on its tree.
"""

from __future__ import annotations

import sys

PAGE = 16


def outputs(dv) -> dict:
    """{program: its logits (``hidden_states``: the pooled states)} of
    ``dv`` (models/axk1.py of some checkout): a whole prompt of two
    rows, the next chunk of each over the pages behind it, one decode
    step after that, and the embeddings path."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = dv.TINY
    p = dv.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    if hasattr(dv, "serving_params"):
        p = dv.serving_params(p, cfg)
    rng = np.random.default_rng(50)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 48)), jnp.int32)
    more = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    last = jnp.asarray(rng.integers(0, cfg.vocab_size, (2,)), jnp.int32)
    lens = jnp.asarray([48, 31], jnp.int32)
    pt = jnp.asarray([[1, 3, 5, 7], [2, 4, 6, 8]], jnp.int32)
    kv = cfg.cache_spec().make(10 * PAGE, 0, "float32")
    kw = dict(cfg=cfg, page_size=PAGE)
    out = {}
    out["prefill"], kv = jax.jit(functools.partial(dv.prefill, **kw))(
        p, tokens=toks, seq_lens=lens, cache=kv, page_table=pt)
    out["prefill_suffix"], kv = jax.jit(
        functools.partial(dv.prefill_suffix, **kw))(
        p, tokens=more, prefix_lens=lens, seq_lens=lens + jnp.asarray([12, 9]),
        cache=kv, page_table=pt)
    out["decode_step"], kv = jax.jit(
        functools.partial(dv.decode_step, **kw))(
        p, tokens=last, positions=lens + jnp.asarray([12, 9]), cache=kv,
        page_table=pt, active=jnp.asarray([True, True]))
    out["hidden_states"] = jax.jit(
        functools.partial(dv.hidden_states, cfg=cfg))(
        p, tokens=toks, seq_lens=lens)
    return {k: np.asarray(v, np.float32) for k, v in out.items()}


if __name__ == "__main__":
    sys.path.insert(0, sys.argv[1])
    import numpy as np

    from aigw_tpu.models import axk1

    np.savez(sys.argv[2], **outputs(axk1))
