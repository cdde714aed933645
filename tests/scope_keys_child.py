"""Child of tests/test_device_scopes.py: lowers the decode window and a
prefill step of each model family (tiny widths, W8A16 so the Pallas
matmul is in the program) and prints, per program, the hash JAX's
persistent compile cache takes of the computation, the named scopes
its text carries, which of them hold a loop, and the name stacks of
the operations inside a conditional's branches. With ``--no-scopes`` ``jax.named_scope`` is a no-op
from before the first import of the program, which is the tree without
this PR's scopes. With ``--tpu`` the programs are lowered for a
described (not attached) TPU v5e, Mosaic kernels included.

A child because the scopes are decorators, applied at import: the
parent process cannot take them off the modules it shares with every
other test.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list[str]) -> int:
    import jax

    if "--no-scopes" in argv:
        @contextlib.contextmanager
        def no_scope(name):
            yield

        jax.named_scope = no_scope
    import jax.numpy as jnp
    from jax._src import cache_key

    from aigw_tpu.models import (axk1, llama, mimo_v2, mixtral, quant,
                                 qwen3_next)
    from aigw_tpu.tpuserve.sampling import sample

    sharding = None
    if "--tpu" in argv:
        from jax.experimental import topologies

        from aigw_tpu.ops.pallas import _compat

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
        sharding = jax.sharding.SingleDeviceSharding(topo.devices[0])
        # lower the kernels as the chip would (Mosaic, not interpreted):
        # a Mosaic call carries its own serialized module in the program
        _compat.is_tpu_backend = lambda: True

    B, S, P, PAGE = 4, 16, 4, 16
    fams = {
        "llama": (llama, llama.LlamaConfig(
            vocab_size=512, dim=256, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=512, attn_bias=True)),
        "mixtral": (mixtral, mixtral.MixtralConfig(
            vocab_size=512, dim=256, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=512, n_experts=4, experts_per_token=2)),
        # bfloat16 as served: the family has no quantized weights
        "qwen3_next": (qwen3_next, qwen3_next.TINY),
        # bfloat16 too: latent pages, one row a token a layer, no state
        "axk1": (axk1, axk1.TINY),
        # bfloat16: pages for the global layers, a ring a slot beside
        "mimo_v2": (mimo_v2, mimo_v2.TINY),
    }
    out: dict[str, dict] = {}
    for fam, (mod, cfg) in fams.items():
        if fam in ("qwen3_next", "axk1", "mimo_v2"):
            params = jax.eval_shape(
                lambda: mod.init_params(jax.random.PRNGKey(0), cfg))
            # pages for the full-attention layers, per-slot state beside
            kv = jax.eval_shape(lambda: cfg.cache_spec().make(
                (B * P + 1) * PAGE, B, "bfloat16"))
        else:
            params = jax.eval_shape(
                lambda: quant.quantize_params(
                    mod.init_params(jax.random.PRNGKey(0), cfg),
                    mode="int8"))
            hd = cfg.dim // cfg.n_heads
            kv = jax.ShapeDtypeStruct(
                (cfg.n_layers, 2, (B * P + 1) * PAGE, cfg.n_kv_heads, hd),
                jnp.bfloat16)
        i32 = jnp.int32

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        def place(tree):
            return jax.tree_util.tree_map(
                lambda a: sds(a.shape, a.dtype), tree)

        def decode(p, tokens, positions, kv_cache, pt, active, keys):
            def body(carry, _):
                toks, pos, cache = carry
                logits, cache = mod.decode_step(
                    p, cfg, toks, pos, cache, pt, PAGE, active)
                nxt = sample(logits, keys, jnp.zeros((B,)),
                             jnp.ones((B,)), jnp.zeros((B,), i32), active)
                return (nxt, pos + 1, cache), nxt
            return jax.lax.scan(
                body, (tokens, positions, kv_cache), None, length=2)

        def prefill(p, tokens, lens, kv_cache, pt, keys):
            logits, cache = mod.prefill(
                p, cfg, tokens, lens, kv_cache, pt, PAGE)
            return sample(logits, keys, jnp.zeros((B,)), jnp.ones((B,)),
                          jnp.zeros((B,), i32)), cache

        def suffix(p, tokens, pre, lens, kv_cache, pt, keys):
            logits, cache = mod.prefill_suffix(
                p, cfg, tokens, pre, lens, kv_cache, pt, PAGE)
            return sample(logits, keys, jnp.zeros((B,)), jnp.ones((B,)),
                          jnp.zeros((B,), i32)), cache

        keys = sds((B, 2), jnp.uint32)
        pt = sds((B, P), i32)
        vec = sds((B,), i32)
        programs = {
            "decode": (decode, (place(params), vec, vec, place(kv), pt,
                                sds((B,), jnp.bool_), keys)),
            "prefill": (prefill, (place(params), sds((B, S), i32), vec,
                                  place(kv), pt, keys)),
            "prefill_suffix": (suffix, (place(params), sds((B, S), i32),
                                        vec, vec, place(kv), pt, keys)),
        }
        for name, (fn, args) in programs.items():
            lowered = jax.jit(fn).lower(*args)
            h = hashlib.sha256()
            cache_key._hash_computation(
                h, lowered.compiler_ir(), cache_key.IgnoreCallbacks.NO)
            text = lowered.as_text(debug_info=True)
            # a scope is a component of an operation's name stack
            # ("layer/attn/dot_general", relative inside a scan's body),
            # not a Python function's name in a call-site location
            stacks = re.findall(r'loc\("([^"]*)"', text)
            scopes = sorted({m for st in stacks for m in re.findall(
                r"(?:^|/)(embed|layer/[a-z_]+|lm_head|sample)(?=/)", st)})
            # operations inside a conditional's branches (the sampler's
            # guarded sort): "…/cond/branch_1_fun/jit(sort)"; a bare
            # "cond/branch_1_fun/jit" is a fragment of such a name, not
            # an operation's stack
            in_cond = sorted({
                st for st in stacks
                if re.search(r"(?:^|/)cond/branch_\d+_fun/", st)
                and not st.endswith("_fun/jit")})
            # the scopes that hold a loop of their own: an operation of
            # a body is named "…layer/moe_experts/while/body/dot_general",
            # or the scope calls the one jitted expert loop that every
            # layer of a mixtral decode step shares ("…/jit(_hit_experts)";
            # its body's names are relative to that call)
            loops = sorted({m for st in stacks for m in re.findall(
                r"(?:^|/)(layer/[a-z_]+)/(?:while/body/|jit\(_hit_experts\)$)",
                st)})
            out[f"{fam}.{name}"] = {
                "key": h.hexdigest(), "scopes": scopes,
                "mosaic": "tpu_custom_call" in text,
                "in_cond": in_cond, "loops": loops,
                "sorts": text.count("stablehlo.sort")}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
