"""Model registry: name → (family, config, weight source).

The serving engine resolves ``--model`` through this registry. Weight
sources: ``random`` (tiny test models — the fake-chip mode the reference
achieves with testupstream), ``orbax:<path>`` sharded checkpoints, or
``hf:<path>`` local safetensors (no network).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

from aigw_tpu.models import llama


@dataclass(frozen=True)
class ModelSpec:
    name: str
    #: "llama" | "mixtral" | "qwen3_next" | "axk1" | "mimo_v2" |
    #: "olmo_hybrid"
    family: str
    config: Any
    weights: str = "random"  # "random" | "orbax:<dir>" | "hf:<dir>"
    tokenizer: str = "byte"  # "byte" | path to tokenizer.json
    chat_template: str = "llama3"  # "llama3" | "chatml"


@dataclass(frozen=True)
class ModelFns:
    """The functional surface the serving engine drives — uniform across
    model families (prefill/decode share the paged-KV contract)."""

    init_params: Any
    prefill: Any
    decode_step: Any
    hidden_states: Any
    # chunked prefill over cached prefix pages; None disables the engine's
    # prefix cache for the family
    prefill_suffix: Any = None
    # sequence-parallel (ring-attention) prefill for long prompts; None
    # disables the engine's sp prefill path for the family
    prefill_sp: Any = None
    # sequence-parallel chunked prefill resuming at a page-aligned
    # offset (ring attention + cached-window pass); None falls the sp
    # path back to the monolithic full-rung program
    prefill_sp_suffix: Any = None
    # multi-position verifier for speculative decoding; None disables the
    # engine's prompt-lookup speculation for the family
    verify_step: Any = None
    # packed variable-length prefill (one program per token-budget
    # chunk). None falls the attention backend back to xla-bucketed:
    # qwen3_next (its recurrent state would have to reset at every
    # packed segment's start) and hand-built ModelFns
    prefill_ragged: Any = None
    # static kwarg contract: entry points accept ``moe_stats=True`` and
    # return a trailing [L, E+1] int32 routing-stats leaf (per-expert
    # placed counts + capacity drops per layer). The engine turns it on
    # for MoE families (configs carrying ``n_experts``)
    moe_stats: bool = False
    # ``state_reads(cache, active) -> [2] int32``: what a decode step's
    # live-row loops read of the per-slot state pool a layer (slots read,
    # live rows), for a DENSE family with such state — an expert family
    # carries the two on its routing-stats tape. The engine adds them to
    # the page counters its decode window already fetches. None: nothing
    state_reads: Any = None
    # ``serving_params(params, cfg)``: the tree the family's programs
    # read, out of ``init_params``' (a checkpoint's) tree — weights laid
    # out once, at load, for the products that read them. None: the
    # programs read ``init_params``' tree as it is
    serving_params: Any = None


def family_fns(family: str) -> ModelFns:
    if family == "llama":
        return ModelFns(llama.init_params, llama.prefill, llama.decode_step,
                        llama.hidden_states,
                        prefill_suffix=llama.prefill_suffix,
                        prefill_sp=llama.prefill_sp,
                        prefill_sp_suffix=llama.prefill_sp_suffix,
                        verify_step=llama.verify_step,
                        prefill_ragged=llama.prefill_ragged)
    if family == "mixtral":
        from aigw_tpu.models import mixtral

        return ModelFns(mixtral.init_params, mixtral.prefill,
                        mixtral.decode_step, mixtral.hidden_states,
                        prefill_suffix=mixtral.prefill_suffix,
                        prefill_sp=mixtral.prefill_sp,
                        prefill_sp_suffix=mixtral.prefill_sp_suffix,
                        verify_step=mixtral.verify_step,
                        prefill_ragged=mixtral.prefill_ragged,
                        moe_stats=True)
    if family == "qwen3_next":
        from aigw_tpu.models import qwen3_next

        # no verify_step (a rejected draft would need the DeltaNet
        # state rolled back), no sequence-parallel prefill, no ragged
        # prefill: the engine reads each as "off for this family"
        return ModelFns(qwen3_next.init_params, qwen3_next.prefill,
                        qwen3_next.decode_step, qwen3_next.hidden_states,
                        prefill_suffix=qwen3_next.prefill_suffix,
                        moe_stats=True)
    if family == "axk1":
        from aigw_tpu.models import axk1

        # no verify_step (speculation is off for the family), no
        # sequence-parallel and no ragged prefill
        return ModelFns(axk1.init_params, axk1.prefill, axk1.decode_step,
                        axk1.hidden_states,
                        prefill_suffix=axk1.prefill_suffix,
                        moe_stats=True, serving_params=axk1.serving_params)
    if family == "mimo_v2":
        from aigw_tpu.models import mimo_v2

        # no verify_step (speculation is off for the family), no
        # sequence-parallel and no ragged prefill (a packed segment
        # would have to start its ring afresh)
        return ModelFns(mimo_v2.init_params, mimo_v2.prefill,
                        mimo_v2.decode_step, mimo_v2.hidden_states,
                        prefill_suffix=mimo_v2.prefill_suffix,
                        moe_stats=True)
    if family == "olmo_hybrid":
        from aigw_tpu.models import olmo_hybrid

        # dense: no routing stats. No verify_step (a rejected draft
        # would need the DeltaNet state rolled back), no
        # sequence-parallel and no ragged prefill
        return ModelFns(olmo_hybrid.init_params, olmo_hybrid.prefill,
                        olmo_hybrid.decode_step, olmo_hybrid.hidden_states,
                        prefill_suffix=olmo_hybrid.prefill_suffix,
                        state_reads=olmo_hybrid.state_reads)
    raise KeyError(f"unknown model family {family!r}")


_REGISTRY: dict[str, ModelSpec] = {}


def register_model(spec: ModelSpec) -> None:
    _REGISTRY[spec.name] = spec


def get_model_spec(name: str) -> ModelSpec:
    if name in _REGISTRY:
        return _REGISTRY[name]
    raise KeyError(
        f"unknown model {name!r}; registered: {sorted(_REGISTRY)}"
    )


def family_config_class(family: str) -> type:
    """The configuration dataclass of a model family, as a registered
    model of that family carries it: what turns a document's fields
    into a ``ModelSpec.config`` without naming the family's module."""
    for spec in _REGISTRY.values():
        if spec.family == family:
            return type(spec.config)
    raise KeyError(
        f"unknown model family {family!r}; registered: "
        f"{sorted({s.family for s in _REGISTRY.values()})}")


register_model(ModelSpec("tiny-random", "llama", llama.TINY))


def _register_mixtral() -> None:
    from aigw_tpu.models import mixtral

    register_model(ModelSpec("tiny-moe", "mixtral", mixtral.TINY_MOE))
    register_model(ModelSpec("mixtral-8x7b", "mixtral",
                             mixtral.MIXTRAL_8X7B,
                             weights="orbax:checkpoints/mixtral-8x7b"))


_register_mixtral()


def _register_qwen3_next() -> None:
    from aigw_tpu.models import qwen3_next

    register_model(ModelSpec("tiny-qwen3-next", "qwen3_next",
                             qwen3_next.TINY, chat_template="chatml"))


_register_qwen3_next()


def _register_axk1() -> None:
    from aigw_tpu.models import axk1

    register_model(ModelSpec("tiny-axk1", "axk1", axk1.TINY))


_register_axk1()


def _register_mimo_v2() -> None:
    from aigw_tpu.models import mimo_v2

    register_model(ModelSpec("tiny-mimo-v2", "mimo_v2", mimo_v2.TINY))


_register_mimo_v2()


def _register_olmo_hybrid() -> None:
    from aigw_tpu.models import olmo_hybrid

    register_model(ModelSpec("tiny-olmo-hybrid", "olmo_hybrid",
                             olmo_hybrid.TINY, chat_template="chatml"))


_register_olmo_hybrid()
register_model(ModelSpec("llama-3-8b", "llama", llama.LLAMA3_8B,
                         weights="orbax:checkpoints/llama-3-8b"))
register_model(ModelSpec("qwen2-7b", "llama", llama.QWEN2_7B,
                         weights="orbax:checkpoints/qwen2-7b",
                         chat_template="chatml"))
register_model(ModelSpec("qwen2-0.5b", "llama", llama.QWEN2_05B,
                         weights="orbax:checkpoints/qwen2-0.5b",
                         chat_template="chatml"))
register_model(ModelSpec(
    "tiny-qwen", "llama",
    llama.LlamaConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=128, max_seq_len=512,
                      rope_theta=10000.0, attn_bias=True,
                      tie_embeddings=True),
))
register_model(ModelSpec("llama-3-70b", "llama", llama.LLAMA3_70B,
                         weights="orbax:checkpoints/llama-3-70b"))
