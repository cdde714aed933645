"""Partition specs for model states (Megatron-style TP via GSPMD).

Column-parallel in-projections (wq/wk/wv, w_gate/w_up) shard their output
dimension over ``tp``; row-parallel out-projections (wo, w_down) shard
their input dimension, so each layer needs exactly ONE all-reduce after
attention and one after the MLP — which GSPMD inserts automatically from
these specs (the "annotate shardings, let XLA insert collectives" recipe).

The paged KV cache shards on the KV-head axis over ``tp`` (Llama-3's 8 KV
heads ÷ TP=8 → one KV head per chip: cache reads/writes are fully local,
no collective in the decode hot loop).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from aigw_tpu.models.llama import LlamaConfig


def llama_param_specs(cfg: LlamaConfig) -> dict[str, P]:
    specs: dict[str, P] = {
        # vocab-sharded embedding + head (logits all-gathered by GSPMD)
        "embed": P("tp", None),
        "norm_f": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
    for i in range(cfg.n_layers):
        specs[f"l{i}.attn_norm"] = P(None)
        specs[f"l{i}.wq"] = P(None, "tp")  # column parallel (heads)
        specs[f"l{i}.wk"] = P(None, "tp")
        specs[f"l{i}.wv"] = P(None, "tp")
        if getattr(cfg, "attn_bias", False):
            specs[f"l{i}.bq"] = P("tp")
            specs[f"l{i}.bk"] = P("tp")
            specs[f"l{i}.bv"] = P("tp")
        specs[f"l{i}.wo"] = P("tp", None)  # row parallel
        specs[f"l{i}.mlp_norm"] = P(None)
        specs[f"l{i}.w_gate"] = P(None, "tp")
        specs[f"l{i}.w_up"] = P(None, "tp")
        specs[f"l{i}.w_down"] = P("tp", None)
    return specs


def param_sharding_fn(cfg, mesh: Mesh):
    """``(name, shape) -> NamedSharding`` for every parameter leaf of a
    family on ``mesh`` — the ONE placement rule both weight creation
    (models' ``init_params(sharding_of=…)``, checkpoint restore) and
    the engine use, so weights are born where they will live.

    Quantized leaves: ``name.q`` shards like the base matrix;
    ``name.scale`` keeps the base spec only on axes it has extent in
    and that the mesh axis divides (int8 keepdims axes of size 1 stay
    unsharded; an int4 group count smaller than the axis replicates
    instead of failing placement)."""
    specs = (mixtral_param_specs(cfg) if hasattr(cfg, "n_experts")
             else llama_param_specs(cfg))

    def sharding_of(name: str, shape: tuple) -> NamedSharding:
        if name.endswith(".q"):
            return NamedSharding(mesh, specs[name[:-2]])
        if name.endswith(".scale"):
            base = specs[name[: -len(".scale")]]
            return NamedSharding(mesh, P(*(
                ax if (ax is not None and shape[i] > 1
                       and shape[i] % mesh.shape[ax] == 0) else None
                for i, ax in enumerate(base))))
        return NamedSharding(mesh, specs[name])

    return sharding_of


def kv_cache_spec() -> P:
    """[L, 2, slots, n_kv_heads, head_dim] — shard KV heads over tp."""
    return P(None, None, None, "tp", None)


def shard_params(
    params: dict[str, jax.Array], cfg: LlamaConfig, mesh: Mesh
) -> dict[str, jax.Array]:
    """Place a host pytree onto the mesh with TP shardings."""
    specs = llama_param_specs(cfg)
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }


def analytical_ici_bytes_per_token(cfg, mesh, dtype_bytes: int = 2) -> int:
    """Analytical ICI collective volume of ONE decoded token on a
    tensor-parallel mesh, in bytes PER DEVICE — the /state
    ``ici_bytes_per_token`` signal the picker's topology term can price
    against real occupancy (SURVEY §2.8/§2.9: "load-balances on TPU
    KV-cache occupancy AND ICI topology").

    The Megatron-via-GSPMD layout above needs, per decoded token:

    - two all-reduces per layer (post-attention ``wo`` and post-MLP
      ``w_down`` row-parallel outputs), each over a [dim] activation —
      a ring all-reduce moves ``2 * (tp-1)/tp`` of the buffer per
      device;
    - one logits all-gather over the vocab-sharded lm_head output —
      ``(tp-1)/tp`` of a [vocab] row per device (fused sampling keeps
      it on device, but the gather itself still crosses ICI);
    - with expert parallelism, a dispatch + combine all-to-all per
      layer, each moving ``(ep-1)/ep`` of a [dim] activation.

    Analytical by design (CPU meshes have no ICI to measure); on-chip
    profiling replaces it, this prices it. 0 when unsharded."""
    if mesh is None:
        return 0
    tp = int(mesh.shape.get("tp", 1))
    ep = int(mesh.shape.get("ep", 1))
    total = 0.0
    if tp > 1:
        ring = 2.0 * (tp - 1) / tp
        total += cfg.n_layers * 2 * cfg.dim * dtype_bytes * ring
        total += cfg.vocab_size * dtype_bytes * (tp - 1) / tp
    if ep > 1 and getattr(cfg, "n_experts", 0):
        total += cfg.n_layers * 2 * cfg.dim * dtype_bytes * (ep - 1) / ep
    return int(total)


def mixtral_param_specs(cfg) -> dict[str, P]:
    """Expert-parallel + tensor-parallel specs for the Mixtral family.

    Expert weights [E, D, F] shard experts over ``ep`` and the FFN width
    over ``tp``; GSPMD turns the dispatch/combine einsums in
    models/mixtral.py into all-to-alls over ``ep`` (SURVEY.md §2.9:
    "mesh axis for experts + all-to-all dispatch").
    """
    specs: dict[str, P] = {
        "embed": P("tp", None),
        "norm_f": P(None),
        "lm_head": P(None, "tp"),
    }
    for i in range(cfg.n_layers):
        specs[f"l{i}.attn_norm"] = P(None)
        specs[f"l{i}.wq"] = P(None, "tp")
        specs[f"l{i}.wk"] = P(None, "tp")
        specs[f"l{i}.wv"] = P(None, "tp")
        specs[f"l{i}.wo"] = P("tp", None)
        specs[f"l{i}.mlp_norm"] = P(None)
        specs[f"l{i}.gate"] = P(None, None)  # router: tiny, replicated
        specs[f"l{i}.w_gate"] = P("ep", None, "tp")
        specs[f"l{i}.w_up"] = P("ep", None, "tp")
        specs[f"l{i}.w_down"] = P("ep", "tp", None)
    return specs
