"""Checkpoint roundtrip + HF safetensors import (logit-equivalence proof)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import llama
from aigw_tpu.models.checkpoint import (
    import_hf_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)

CFG = llama.TINY


def test_orbax_roundtrip(tmp_path):
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    path = str(tmp_path / "ckpt")
    save_checkpoint(params, path)
    got = restore_checkpoint(path, params)
    for k in params:
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(got[k]))


def test_orbax_restore_lands_sharded(tmp_path):
    """``sharding_of``: each tensor is restored straight into its mesh
    sharding (the path a ``--tp`` model larger than one chip takes),
    not restored whole and resharded afterwards."""
    from aigw_tpu.parallel.mesh import MeshSpec, make_mesh
    from aigw_tpu.parallel.sharding import param_sharding_fn

    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    path = str(tmp_path / "ckpt")
    save_checkpoint(params, path)
    mesh = make_mesh(MeshSpec(tp=2))
    sharding_of = param_sharding_fn(CFG, mesh)
    like = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), CFG))
    got = restore_checkpoint(path, like, sharding_of)
    assert set(got) == set(params)
    for k, x in got.items():
        assert x.sharding.is_equivalent_to(
            sharding_of(k, x.shape), x.ndim), k
        np.testing.assert_array_equal(np.asarray(params[k]), np.asarray(x))
    # a column-parallel matrix: half of it on each of the two devices
    wq = got["l0.wq"]
    assert len(wq.addressable_shards) == 2
    assert {s.data.shape for s in wq.addressable_shards} == {
        (wq.shape[0], wq.shape[1] // 2)}


def test_hf_import_matches_native(tmp_path):
    """Write our params in HF layout (names + [out,in] transposes), import
    them back, and prove identical logits."""
    from safetensors.numpy import save_file

    params = llama.init_params(jax.random.PRNGKey(0), CFG)

    def np32(x):
        # jax bf16 → f32 numpy arrives F-contiguous; safetensors writes the
        # raw buffer assuming C-order, so force C layout or values scramble
        return np.ascontiguousarray(np.asarray(x, np.float32))

    hf = {}
    hf["model.embed_tokens.weight"] = np32(params["embed"])
    hf["model.norm.weight"] = np32(params["norm_f"])
    hf["lm_head.weight"] = np.ascontiguousarray(np32(params["lm_head"]).T)
    for i in range(CFG.n_layers):
        hf[f"model.layers.{i}.input_layernorm.weight"] = np32(
            params[f"l{i}.attn_norm"])
        hf[f"model.layers.{i}.post_attention_layernorm.weight"] = np32(
            params[f"l{i}.mlp_norm"])
        for ours, theirs in [("wq", "self_attn.q_proj"),
                             ("wk", "self_attn.k_proj"),
                             ("wv", "self_attn.v_proj"),
                             ("wo", "self_attn.o_proj"),
                             ("w_gate", "mlp.gate_proj"),
                             ("w_up", "mlp.up_proj"),
                             ("w_down", "mlp.down_proj")]:
            hf[f"model.layers.{i}.{theirs}.weight"] = np.ascontiguousarray(
                np32(params[f"l{i}.{ours}"]).T)
    hf_dir = tmp_path / "hf"
    hf_dir.mkdir()
    save_file(hf, str(hf_dir / "model.safetensors"))

    imported = import_hf_checkpoint(str(hf_dir))
    assert set(imported) == set(params)

    tokens = jnp.array([[7, 8, 9, 10]], jnp.int32)
    pt = jnp.arange(4, dtype=jnp.int32)[None, :]
    cache = jnp.zeros((CFG.n_layers, 2, 64 * 16, CFG.n_kv_heads,
                       CFG.head_dim), jnp.bfloat16)
    la, _ = llama.prefill(params, CFG, tokens, jnp.array([4]), cache, pt, 16)
    lb, _ = llama.prefill(imported, CFG, tokens, jnp.array([4]),
                          jnp.zeros_like(cache), pt, 16)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=2e-2)


@pytest.mark.slow


def test_mixtral_hf_import(tmp_path):
    """Mixtral-layout safetensors (per-expert w1/w2/w3 + router gate) import
    into our stacked [E, ...] MoE params with identical logits."""
    from safetensors.numpy import save_file

    from aigw_tpu.models import mixtral

    cfg = mixtral.MixtralConfig(
        vocab_size=64, dim=32, n_layers=1, n_heads=2, n_kv_heads=2,
        ffn_dim=48, n_experts=2, experts_per_token=1, max_seq_len=64,
        rope_theta=10000.0, capacity_factor=8.0,
    )
    params = mixtral.init_params(jax.random.PRNGKey(0), cfg)

    def c32(x):
        return np.ascontiguousarray(np.asarray(x, np.float32))

    hf = {
        "model.embed_tokens.weight": c32(params["embed"]),
        "model.norm.weight": c32(params["norm_f"]),
        "lm_head.weight": np.ascontiguousarray(c32(params["lm_head"]).T),
        "model.layers.0.input_layernorm.weight": c32(params["l0.attn_norm"]),
        "model.layers.0.post_attention_layernorm.weight": c32(
            params["l0.mlp_norm"]),
        "model.layers.0.block_sparse_moe.gate.weight":
            np.ascontiguousarray(c32(params["l0.gate"]).T),
    }
    for ours, theirs in [("wq", "q_proj"), ("wk", "k_proj"),
                         ("wv", "v_proj"), ("wo", "o_proj")]:
        hf[f"model.layers.0.self_attn.{theirs}.weight"] = \
            np.ascontiguousarray(c32(params[f"l0.{ours}"]).T)
    for e in range(cfg.n_experts):
        hf[f"model.layers.0.block_sparse_moe.experts.{e}.w1.weight"] = \
            np.ascontiguousarray(c32(params["l0.w_gate"][e]).T)
        hf[f"model.layers.0.block_sparse_moe.experts.{e}.w3.weight"] = \
            np.ascontiguousarray(c32(params["l0.w_up"][e]).T)
        hf[f"model.layers.0.block_sparse_moe.experts.{e}.w2.weight"] = \
            np.ascontiguousarray(c32(params["l0.w_down"][e]).T)
    hf_dir = tmp_path / "hf-moe"
    hf_dir.mkdir()
    save_file(hf, str(hf_dir / "model.safetensors"))

    imported = import_hf_checkpoint(str(hf_dir))
    assert set(imported) == set(params)
    assert imported["l0.w_gate"].shape == params["l0.w_gate"].shape

    tokens = jnp.array([[3, 4, 5]], jnp.int32)
    pt = jnp.arange(4, dtype=jnp.int32)[None, :]
    cache = jnp.zeros((1, 2, 16 * 16, cfg.n_kv_heads, cfg.head_dim),
                      jnp.bfloat16)
    la, _ = mixtral.prefill(params, cfg, tokens, jnp.array([3]), cache,
                            pt, 16)
    lb, _ = mixtral.prefill(imported, cfg, tokens, jnp.array([3]),
                            jnp.zeros_like(cache), pt, 16)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=2e-2)
