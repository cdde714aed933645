"""Child of tests/test_cache_keys.py: builds the serving engine for a
tiny model of each family (``tiny-random``: llama, ``tiny-moe``:
mixtral, ``tiny-qwen3-next``: the hybrid family; ``tiny-axk1`` and
``tiny-mimo-v2``, the latent and the window-and-global family, for
their decode programs alone), lowers the programs
the engine itself dispatches — its jitted prefill, chunk and
decode-window wrappers, not the model functions — and prints the hash
JAX's persistent compile cache takes of each computation.

    python tests/engine_keys_child.py [<checkout>]

With a checkout it imports the program from there: that is how the
goldens in tests/test_cache_keys.py were taken from the parent commit.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys


def main(argv: list[str]) -> int:
    root = argv[0] if argv else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    from jax._src import cache_key

    from aigw_tpu.models.registry import family_fns, get_model_spec
    from aigw_tpu.tpuserve.engine import Engine, EngineConfig

    def key_of(lowered) -> str:
        h = hashlib.sha256()
        cache_key._hash_computation(
            h, lowered.compiler_ir(), cache_key.IgnoreCallbacks.NO)
        return h.hexdigest()

    out = {"jax": jax.__version__}
    # the latent and the window-and-global family: their decode
    # programs alone (the walk over a latent pool)
    decode_only = ("tiny-axk1", "tiny-mimo-v2")
    for model in ("tiny-random", "tiny-moe", "tiny-qwen3-next",
                  *decode_only):
        spec = get_model_spec(model)
        fns = family_fns(spec.family)
        params = fns.init_params(jax.random.PRNGKey(0), spec.config)
        # as the server does at load (a checkout from before ISSUE 50
        # has no such entry in its table)
        prepare = getattr(fns, "serving_params", None)
        if prepare is not None:
            params = prepare(params, spec.config)
        eng = Engine(params, spec.config, EngineConfig(
            max_batch_size=4, max_seq_len=128, page_size=16,
            min_prefill_bucket=16, decode_steps_per_tick=4), fns=fns)
        G, S, P, V = 2, 32, 8, spec.config.vocab_size
        i32, f32 = jnp.int32, jnp.float32
        sampling = (jnp.zeros((G, 2), jnp.uint32), jnp.zeros((G,), f32),
                    jnp.ones((G,), f32), jnp.zeros((G,), i32),
                    jnp.zeros((G, V), f32), jnp.zeros((G,), i32))
        pt = jnp.zeros((G, P), i32)
        lens = jnp.zeros((G,), i32)
        toks = jnp.zeros((G, S), i32)
        # a per-slot-state family's rows name their decode slots
        slot_kw = eng.slot_kw([], rows=G)
        if model not in decode_only:
            out[f"{model}.prefill"] = key_of(eng._prefill_fn.lower(
                eng.params, eng.lora_params, toks, lens, eng.kv_cache, pt,
                *sampling, **slot_kw))
            out[f"{model}.prefill_suffix"] = key_of(
                eng._prefill_suffix_fn.lower(
                    eng.params, eng.lora_params, toks, lens, lens,
                    eng.kv_cache, pt, *sampling, **slot_kw))
        state = eng._build_device_state(bucket=P)
        for lean in (True, False):
            out[f"{model}.decode.lean={lean}"] = key_of(
                eng._decode_fn_for(4, lean).lower(
                    eng.params, eng.lora_params, eng.kv_cache, state))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
