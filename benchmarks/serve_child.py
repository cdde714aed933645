"""tpuserve child process for the bench harness.

The CPU gateway-ratio leg originally ran tpuserve as a *thread* of the
bench process; on a 1-core host the client loop, server loop, and engine
thread then convoy on one GIL and the serve legs' spread hit 27-36%
(r4/r5 instability). Running tpuserve as its own process — exactly how
it deploys — gives the OS scheduler, not the GIL, the arbitration job.

Takes one argv: a JSON object {model, cfg, batch, page, k, quantize}.
Prints ``SERVE_PORT=<port>`` once listening, serves until killed.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _install_trace(trace_path: str) -> None:
    """AIGW_TTFT_TRACE: append (event, t, id) lines for handler arrival,
    engine submit, and first engine emit — TTFT localization only."""
    import time

    from aigw_tpu.tpuserve.engine import Engine

    f = open(trace_path, "a", buffering=1)

    def log(ev: str, tag: object) -> None:
        f.write(json.dumps({"ev": ev, "t": time.time(), "tag": tag}) + "\n")

    orig_submit = Engine.submit

    def submit(self, req):
        tag = req.prompt[:2]
        log("submit", tag)
        seen = [False]
        orig_emit, orig_emit_lp = req.emit, req.emit_lp

        def emit(tok, fin):
            if not seen[0] and tok >= 0:
                seen[0] = True
                log("first_emit", tag)
            return orig_emit(tok, fin)

        req.emit = emit
        if orig_emit_lp is not None:
            def emit_lp(tok, fin, c, t):
                if not seen[0] and tok >= 0:
                    seen[0] = True
                    log("first_emit", tag)
                return orig_emit_lp(tok, fin, c, t)
            req.emit_lp = emit_lp
        return orig_submit(self, req)

    Engine.submit = submit

    from aiohttp import web

    from aigw_tpu.tpuserve import server as srv

    orig_init = srv.TPUServeServer.__init__

    def init(self, *a, **kw):
        orig_init(self, *a, **kw)

        @web.middleware
        async def arrival_mw(request, handler):
            log("arrive", request.path)
            return await handler(request)

        self.app.middlewares.append(arrival_mw)

    srv.TPUServeServer.__init__ = init


def main() -> None:
    # the platform is the one the launcher named in JAX_PLATFORMS; with
    # none named a TPU is required (utils/boot.py) — this child never
    # lands on a CPU nobody asked for
    from aigw_tpu.utils.boot import boot_jax

    boot_jax()

    import jax
    from aiohttp import web

    from aigw_tpu.models import llama
    from aigw_tpu.models.registry import ModelSpec, register_model
    from aigw_tpu.tpuserve.engine import EngineConfig
    from aigw_tpu.tpuserve.server import TPUServeServer

    if os.environ.get("AIGW_TTFT_TRACE"):
        _install_trace(os.environ["AIGW_TTFT_TRACE"])

    # chaos injection (tools/chaos.py): a slow-start replica stalls
    # here — the launcher and controller must tolerate a child that
    # takes arbitrarily long to report its port
    slow = float(os.environ.get("AIGW_CHAOS_SLOW_START_S", "0") or 0)
    if slow > 0:
        import time

        time.sleep(slow)

    spec = json.loads(sys.argv[1])
    family = spec.get("family", "llama")
    if family == "mixtral":
        # the --ab moe leg (ISSUE 18): expert-parallel child on the
        # same serving surface as dense families
        from aigw_tpu.models import mixtral

        cfg = mixtral.MixtralConfig(**spec["cfg"])
    else:
        cfg = llama.LlamaConfig(**spec["cfg"])
    register_model(ModelSpec(spec["model"], family, cfg))
    param_dtype = spec.get("param_dtype", "")

    # multi-LoRA zoo for the --ab lora leg: N random-B adapters named
    # t0..tN-1, `slots` device rows (fewer than N = hot load/evict
    # churn under traffic)
    lora_adapters = None
    lora_slots = 0
    lora_spec = spec.get("lora") or {}
    if lora_spec:
        from aigw_tpu.models.lora import LoRAConfig, init_lora_adapters

        lcfg = LoRAConfig(
            rank=int(lora_spec.get("rank", 8)), alpha=16.0,
            targets=tuple(lora_spec.get("targets", ("wq", "wv"))))
        n = int(lora_spec.get("adapters", 4))
        stacked = init_lora_adapters(
            jax.random.PRNGKey(123), cfg, lcfg, n, random_b=True)
        lora_adapters = {
            f"t{i}": {k: v[i] for k, v in stacked.items()}
            for i in range(n)
        }
        lora_slots = int(lora_spec.get("slots", 0))

    async def run() -> None:
        server = TPUServeServer(
            model=spec["model"],
            lora_adapters=lora_adapters,
            lora_slots=lora_slots,
            # tensor-parallel child for the --ab mesh leg: the parent
            # sets XLA_FLAGS=--xla_force_host_platform_device_count so
            # this process actually has the devices (the flag must be
            # in the env BEFORE jax initializes — which is why the
            # mesh A/B runs through subprocess children at all)
            tp=int(spec.get("tp", 1)),
            # sequence-parallel child for the --ab longctx leg (same
            # XLA_FLAGS device-count contract as tp above)
            sp=int(spec.get("sp", 1)),
            engine_cfg=EngineConfig(
                max_batch_size=spec["batch"],
                max_seq_len=cfg.max_seq_len,
                page_size=spec["page"],
                decode_steps_per_tick=spec["k"],
                # timed reps must never pay a prefill compile for a
                # group shape the warm pass's arrival split missed
                warm_prefill_buckets=2,
                # extra EngineConfig overrides (the gateway_prefix A/B
                # leg toggles enable_prefix_cache / min_prefill_bucket)
                **spec.get("engine", {}),
            ),
            quantize=spec.get("quantize", ""),
        )
        if param_dtype == "float32":
            # CPU-leg fidelity knob: XLA:CPU repacks bf16 weight
            # ARGUMENTS to f32 on every call (~35ms fixed for the tiny
            # model — width-independent, so it buries the padded-width
            # signal the prefix leg measures). bf16 is native on TPU;
            # the CPU ratio harness serves f32 instead of paying an
            # artifact of the fallback backend.
            import jax.numpy as jnp

            server.engine.params = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32), server.engine.params)
        runner = web.AppRunner(server.app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        print(f"SERVE_PORT={port}", flush=True)
        # graceful shutdown (ISSUE 14): SIGTERM/SIGINT drains — refuse
        # new admissions with 503, let live slots finish or migrate —
        # then exits 0 with zero live slots; a second signal skips the
        # drain. kill -9 stays the chaos harness's crash injection.
        stop = asyncio.Event()
        server.install_signal_drain(
            stop, grace_s=float(os.environ.get(
                "AIGW_DRAIN_GRACE_S", "60") or 60))
        await stop.wait()
        await runner.cleanup()

    asyncio.run(run())


if __name__ == "__main__":
    main()
