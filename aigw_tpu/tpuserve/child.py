"""One tpuserve replica as a child process: what the gateway's
``LocalProcessLauncher`` and ``tools/chaos.py`` start.

    python -m aigw_tpu.tpuserve.child '<json spec>'

The spec registers its own model (seeded random weights, no
checkpoint): ``model`` (name), ``family`` (default ``llama``), ``cfg``
(fields of the family's configuration dataclass), ``batch`` / ``page``
/ ``k`` (``max_batch_size``, ``page_size``, ``decode_steps_per_tick``),
``engine`` (any other ``EngineConfig`` fields), ``quantize``, ``tp``,
``sp``, ``param_dtype`` (``float32`` serves f32 weights: byte-identical
streams on the CPU) and ``lora`` (``adapters`` seeded random adapters
``t0..``, ``rank``, ``targets``, ``slots`` device rows). Prints
``SERVE_PORT=<port>`` once listening; SIGTERM/SIGINT drain
(``AIGW_DRAIN_GRACE_S``) and exit 0. The platform is the one the
launcher named in ``JAX_PLATFORMS``; with none named a TPU is required
(utils/boot.py).
"""

from __future__ import annotations

# first: where there is no /proc, the boot timeline starts at this import
import aigw_tpu.utils.boot  # noqa: F401, I001

import asyncio
import dataclasses
import json
import os
import sys
import time

SPEC_KEYS = ("model", "family", "cfg", "batch", "page", "k", "quantize",
             "engine", "param_dtype", "lora", "tp", "sp")


def build(spec: dict):
    """``(ModelSpec, EngineConfig)`` of a spec; ``ValueError`` names a
    key or an engine field this child does not know."""
    from aigw_tpu.models.registry import ModelSpec, family_config_class
    from aigw_tpu.tpuserve.engine import EngineConfig

    for key in spec:
        if key not in SPEC_KEYS:
            raise ValueError(f"unknown spec key {key!r}")
    engine = dict(spec.get("engine") or {})
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    for key in engine:
        if key not in fields:
            raise ValueError(f"unknown engine field {key!r}")
    family = spec.get("family", "llama")
    cfg = family_config_class(family)(**spec["cfg"])
    return ModelSpec(spec["model"], family, cfg), EngineConfig(**{
        "max_batch_size": spec["batch"], "max_seq_len": cfg.max_seq_len,
        "page_size": spec["page"], "decode_steps_per_tick": spec["k"],
        **engine})


def _lora_zoo(lora: dict, cfg) -> dict:
    import jax

    from aigw_tpu.models.lora import LoRAConfig, init_lora_adapters

    n = int(lora.get("adapters", 4))
    stacked = init_lora_adapters(
        jax.random.PRNGKey(123), cfg,
        LoRAConfig(rank=int(lora.get("rank", 8)), alpha=16.0,
                   targets=tuple(lora.get("targets", ("wq", "wv")))),
        n, random_b=True)
    return {f"t{i}": {k: v[i] for k, v in stacked.items()}
            for i in range(n)}


async def _serve(spec: dict, model_spec, engine_cfg) -> None:
    import jax

    from aigw_tpu.tpuserve.server import TPUServeServer, listen

    lora = spec.get("lora") or {}
    server = TPUServeServer(
        model=model_spec.name, engine_cfg=engine_cfg,
        tp=int(spec.get("tp", 1)), sp=int(spec.get("sp", 1)),
        quantize=spec.get("quantize", ""),
        lora_adapters=_lora_zoo(lora, model_spec.config) if lora else None,
        lora_slots=int(lora.get("slots", 0)))
    if spec.get("param_dtype", "") == "float32":
        server.engine.params = jax.tree_util.tree_map(
            lambda x: x.astype("float32"), server.engine.params)
    runner, port = await listen(server, "127.0.0.1", 0)
    print(f"SERVE_PORT={port}", flush=True)
    stop = asyncio.Event()
    server.install_signal_drain(stop, grace_s=float(
        os.environ.get("AIGW_DRAIN_GRACE_S", "60") or 60))
    await stop.wait()
    await runner.cleanup()


def main(argv: list[str]) -> int:
    from aigw_tpu.models.registry import register_model
    from aigw_tpu.utils.boot import boot_jax

    boot_jax()
    # tools/chaos.py: a slow-start replica stalls here; the launcher
    # must tolerate a child that is long in reporting its port
    time.sleep(float(os.environ.get("AIGW_CHAOS_SLOW_START_S", "0") or 0))
    try:
        spec = json.loads(argv[0])
        model_spec, engine_cfg = build(spec)
    except (IndexError, KeyError, TypeError, ValueError) as e:
        print(f"replica child: bad spec: {e!r}", file=sys.stderr)
        return 2
    register_model(model_spec)
    asyncio.run(_serve(spec, model_spec, engine_cfg))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
