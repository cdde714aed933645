"""tpuserve HTTP server — the OpenAI-compatible surface over the engine.

Endpoints: /v1/chat/completions (stream + non-stream), /v1/completions,
/v1/embeddings, /tokenize (vLLM-compatible, reference mainlib/main.go:326),
/v1/models, /health, /metrics, and /state — the KV-occupancy/queue-depth
telemetry consumed by the gateway's endpoint picker (the reference's EPP
protocol speaks ext_proc; ours is a plain JSON poll + the same
``x-gateway-destination-endpoint`` contract, internalapi.go:76).

Observability (ISSUE 5): the gateway's ``traceparent`` no longer dies at
the replica hop — each request opens a child span here and the engine
emits lifecycle spans/events under it (queue-wait, admission, prefill,
first-token, decode windows); every request is also recorded in the
in-process flight recorder, served at ``/debug/requests[/{id}]`` with no
tracing backend required, and ``/debug/profile?seconds=N`` captures an
on-demand ``jax.profiler`` trace when enabled. The response carries
``x-aigw-request-id`` so gateway access-log lines join against both.
"""

from __future__ import annotations

import asyncio
import functools
import json
import logging
import os
import tempfile
import time
import uuid
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from aiohttp import web

from aigw_tpu.gateway.costs import TokenUsage, meter_to_tuple
from aigw_tpu.models import llama
from aigw_tpu.models.registry import family_fns, get_model_spec
from aigw_tpu.obs.flight import FlightRecorder, RequestTrace
from aigw_tpu.obs.metrics import (
    GenAIMetrics,
    RequestMetrics,
    render_device_gauges,
    render_engine_gauges,
    render_moe_gauges,
)
from aigw_tpu.obs import xla_events
from aigw_tpu.obs.tracing import SpanContext, Tracer, genai_attributes
from aigw_tpu.schemas import openai as oai
from aigw_tpu.translate.sse import SSEEvent
from aigw_tpu.translate.structured import (
    JSONSchemaError,
    parse_response_format,
)
from aigw_tpu.tpuserve import constrain
from aigw_tpu.utils.boot import BOOT, compile_cache_dir
from aigw_tpu.utils.net import set_tcp_nodelay
from aigw_tpu.tpuserve.engine import (
    Engine,
    EngineConfig,
    EngineOverloadedError,
    GenRequest,
    MigrationError,
    continuation_request,
)
from aigw_tpu.tpuserve.kvcache import page_chain_hashes
from aigw_tpu.tpuserve.sampling import SamplingParams
from aigw_tpu.tpuserve.tokenizer import (
    StreamingDecoder,
    apply_chat_template,
    load_tokenizer,
)

logger = logging.getLogger(__name__)


def encode_wire_page(d) -> dict:
    """Host-side KV page → JSON-able wire dict. Native pages keep the
    PR 8 f32 wire ({b64, shape}); quantized {"q","scale"} pages (ISSUE
    13) add ``dtype`` + ``scale_b64``/``scale_shape`` and travel
    BIT-exactly at native dtype + scales — no re-rounding through f32.
    (int4 serializes one element per byte on the wire — the JSON
    transport is not the packed HBM layout.)"""
    import base64

    if isinstance(d, dict):
        q = np.ascontiguousarray(d["q"])
        s = np.ascontiguousarray(d["scale"], dtype=np.float32)
        return {
            "b64": base64.b64encode(q.tobytes()).decode(),
            "shape": list(q.shape),
            "dtype": str(q.dtype),
            "scale_b64": base64.b64encode(s.tobytes()).decode(),
            "scale_shape": list(s.shape),
        }
    arr = np.asarray(d, np.float32)
    return {"b64": base64.b64encode(arr.tobytes()).decode(),
            "shape": list(arr.shape)}


def decode_wire_page(p: dict):
    """Inverse of :func:`encode_wire_page` (raises KeyError/ValueError
    on malformed input — callers map that to 400)."""
    import base64

    dt = p.get("dtype")
    if dt:
        import ml_dtypes

        np_dt = {"int8": np.int8, "int4": ml_dtypes.int4}[str(dt)]
        q = np.frombuffer(base64.b64decode(p["b64"]),
                          np_dt).reshape(p["shape"])
        scale = np.frombuffer(base64.b64decode(p["scale_b64"]),
                              np.float32).reshape(p["scale_shape"])
        return {"q": q, "scale": scale}
    return np.frombuffer(base64.b64decode(p["b64"]),
                         np.float32).reshape(p["shape"])

#: tenant key header (set by clients or derived/relayed by the gateway
#: from the model's adapter suffix) — feeds the engine's fairness guard
#: and joins the gateway's per-tenant cost/quota accounting
TENANT_HEADER = "x-aigw-tenant"

#: priority class header (ISSUE 19): ``batch`` rides the engine's
#: offline tier — admitted only into slots interactive doesn't want,
#: preempted (window shrink, then host-side park) under interactive
#: pressure, and NEVER 429-shed (the engine's batch queue is
#: unbounded). Anything else (absent, "", "interactive") is the
#: default interactive class.
PRIORITY_HEADER = "x-aigw-priority"

#: sibling replicas ("host:port", comma-separated) the gateway believes
#: hold KV for this request's prompt chain (ISSUE 11): on a prefix miss
#: the server fetches the missing leading pages from them over
#: POST /kv/pages (the PR 8 byte-identical page wire) and imports them
#: as cached chains before admission — Mooncake-style KV-centric
#: serving. Absent/empty = no fetch (cold prefill as before).
KV_PEERS_HEADER = "x-aigw-kv-peers"

#: response header: the first page-chain hash of the served prompt —
#: the gateway learns (prefix-head → chain) from it and prices
#: fleet-hit locality / orders fetch peers on later requests sharing
#: the same prefix head
KV_CHAIN_HEADER = "x-aigw-kv-chain"

#: fleet-fetch bounds: peers tried per request, pages per fetch, and
#: the per-peer HTTP budget — a slow sibling must delay admission by a
#: bounded amount, never hang it (the cold prefill path is always the
#: fallback)
KV_PEERS_MAX = 3
KV_FETCH_MAX_PAGES = 64
KV_FETCH_TIMEOUT_S = 10.0


def _push_all(decoder: StreamingDecoder, toks: list[int]) -> list[str]:
    """Detokenize a burst (runs on the tokenizer pool: a K-token decode
    window lands K tokens at once, and their detokenization must not
    stall every other connection's IO on the event loop)."""
    return [decoder.push(t) for t in toks]


@functools.lru_cache(maxsize=1)
def device_topology() -> dict[str, Any]:
    """What JAX reports about this process's devices, for /state: the
    platform, device kind and device count (so a reader can tell WHERE
    the replica runs without importing jax), the slice the chips belong
    to and the first chip's torus coords. The gateway picker keys its
    same-slice preference (KV/ICI locality on failover) on ``slice``."""
    devices = jax.devices()
    d = devices[0]
    # TPU devices expose slice_index on multislice deployments and
    # coords (the chip's position in the ICI torus); CPU/GPU have
    # neither — they report an empty slice, and the picker falls back
    # to the statically configured slice label.
    slice_idx = getattr(d, "slice_index", None)
    coords = getattr(d, "coords", None)
    return {
        "platform": d.platform,
        "device_kind": d.device_kind,
        "process_device_count": len(devices),
        # the chip(s) the launcher confined this process to
        # (utils/chips.py); "" = every chip of the host. A confined
        # process sees its chip as device 0, so THIS is its identity
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS", ""),
        "slice": (f"{d.platform}-slice-{slice_idx}"
                  if slice_idx is not None else ""),
        "device_coords": list(coords) if coords is not None else [],
    }


def _find_stop(text: str, stop_strs: list[str]) -> int | None:
    """Earliest index where a stop sequence begins, or None."""
    best = None
    for s in stop_strs:
        if not s:
            continue
        i = text.find(s)
        if i >= 0 and (best is None or i < best):
            best = i
    return best


class TPUServeServer:
    def __init__(
        self,
        model: str,
        engine_cfg: EngineConfig,
        metrics: GenAIMetrics | None = None,
        tp: int = 1,
        ep: int = 1,  # expert parallel (MoE families)
        sp: int = 1,  # sequence parallel (ring-attention long prefill)
        quantize: str = "",  # "" | "int8" | "int4" (llama-family only)
        # "random" overrides the registry's weight source ("" keeps
        # it). Full-width models are registered with a checkpoint
        # path: ``random`` serves them from seeded random weights where
        # no checkpoint exists (chip bring-up)
        weights: str = "",
        # name → adapter param dict (un-stacked [r,in]/[out,r] per target);
        # served when a request's model == "<base>:<adapter>" or the bare
        # adapter name. The dict is the ZOO — only lora_slots adapters
        # are device-resident at a time (tpuserve/adapters.py hot
        # load/evict); the rest load on first request.
        lora_adapters: dict[str, dict] | None = None,
        # device rows for resident adapters; 0 = one row per registered
        # adapter (everything fits, loads are lazy, no eviction churn)
        lora_slots: int = 0,
        # per-tenant in-flight decode-slot cap (engine fairness guard);
        # 0 = off
        tenant_slot_cap: int = 0,
        tracer: Tracer | None = None,
        # flight recorder ring size (per-request lifecycle timelines on
        # /debug/requests — always on; the entries are compact)
        flight_entries: int = 256,
        # /debug/profile?seconds=N jax.profiler capture — opt-in: a
        # profiler endpoint on the data port is a DoS/inspection surface
        enable_profile_endpoint: bool = False,
    ):
        # before any weight program compiles, so the process-wide
        # compile / cache-hit counters see those too
        xla_events.install()
        self.model_name = model
        spec = get_model_spec(model)
        self.weights = weights or spec.weights
        self.fns = family_fns(spec.family)
        self.model_cfg = spec.config
        self.tokenizer = load_tokenizer(spec.tokenizer)
        self.chat_template = spec.chat_template
        self.metrics = metrics or GenAIMetrics()
        # env-driven (OTEL_*); service name distinguishes replica spans
        # from the gateway's in a shared collector
        self.tracer = tracer or Tracer(
            service_name=os.environ.get("OTEL_SERVICE_NAME", "")
            or "tpuserve")
        self.flight = FlightRecorder(capacity=flight_entries)
        self._enable_profile = enable_profile_endpoint
        self._profile_lock = asyncio.Lock()
        # replica identity for the gateway's fleet aggregator (ISSUE
        # 12): a fresh id per process boot — the same address with a
        # NEW id is a restart (counters reset), which the fleet health
        # ring records as an event instead of mistaking the zeroed
        # counters for a quiet replica
        self.replica_id = uuid.uuid4().hex[:16]
        self._started_at = time.time()
        # graceful drain (ISSUE 14): when set, NEW generation work is
        # refused with 503+Retry-After while live slots finish or
        # migrate off; /state reports it so the gateway's fleet health
        # machine (and its controller) see the drain on the next poll.
        # Flipped by POST /drain (the controller's retire protocol) or
        # the SIGTERM/SIGINT handler (install_signal_drain).
        self.draining = False

        mesh = None
        if tp > 1 or ep > 1 or sp > 1:
            from aigw_tpu.parallel import MeshSpec, make_mesh

            if ep > 1:
                n_experts = getattr(spec.config, "n_experts", 0)
                if not n_experts:
                    raise ValueError(
                        f"--ep requires a MoE model family; {model!r} "
                        "has no experts")
                if n_experts % ep != 0:
                    raise ValueError(
                        f"n_experts {n_experts} not divisible by ep={ep}")
            if tp > 1 and spec.config.n_kv_heads % tp != 0:
                raise ValueError(
                    f"n_kv_heads {spec.config.n_kv_heads} not divisible "
                    f"by tp={tp}")
            if sp > 1 and self.fns.prefill_sp is None:
                raise ValueError(
                    f"--sp requires a model family with a "
                    f"sequence-parallel prefill; {spec.family!r} has none "
                    "(devices on the sp axis would sit idle)")
            mesh = make_mesh(MeshSpec(dp=1, tp=tp, sp=sp, ep=ep))
            logger.info(
                "parallel serving: tp=%d ep=%d sp=%d over %s", tp, ep, sp,
                [str(d) for d in mesh.devices.flat])
        if quantize and quantize not in ("int8", "int4"):
            raise ValueError(f"unknown quantization {quantize!r}")
        if quantize and spec.family not in ("llama", "mixtral"):
            raise ValueError(
                "weight quantization supports the llama and mixtral "
                "families"
            )
        params = self._load_params(spec, mesh, quantize)
        adapter_store = None
        if lora_adapters:
            if spec.family != "llama":
                raise ValueError("LoRA serving supports the llama family")
            from aigw_tpu.tpuserve.adapters import AdapterStore

            adapter_store = AdapterStore(
                n_slots=lora_slots or len(lora_adapters))
            for name, adapter in lora_adapters.items():
                adapter_store.register(name, adapter)
        self.adapter_store = adapter_store
        engine_cfg.tenant_slot_cap = (
            tenant_slot_cap or engine_cfg.tenant_slot_cap)
        self.engine = Engine(
            params,
            self.model_cfg,
            engine_cfg,
            eos_token_ids=(self.tokenizer.eos_id,),
            mesh=mesh,
            fns=self.fns,
            adapter_store=adapter_store,
            flight=self.flight,
        )
        # jitted embeddings path (bucketed like prefill)
        hidden = self.fns.hidden_states
        self._hidden_fn = jax.jit(
            lambda p, t, l: hidden(p, self.model_cfg, t, l)
        )

        # host-overlap: encode/template/decode run on a worker pool, not
        # the event loop — a long prompt's tokenization (or a big final
        # detokenize) must not stall every other connection's IO. The HF
        # tokenizer is native and releases the GIL, so this is true
        # parallelism for real checkpoints.
        from concurrent.futures import ThreadPoolExecutor

        self._tok_pool = ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="tpuserve-tok"
        )

        # live streaming sessions by response id — the lookup surface of
        # the migration export endpoint (ISSUE 8): the gateway quotes
        # the x-aigw-request-id it already relays
        self._live: dict[str, tuple[GenRequest, dict]] = {}

        # lazy aiohttp session for cross-replica /kv/pages fetches
        # (ISSUE 11) — one pooled session per server, closed on cleanup
        self._kv_session = None

        # offline batch tier (ISSUE 19): in-memory file store (JSONL in,
        # JSONL out) + batch objects and their runner tasks. Batch lines
        # run through the normal submit path at priority="batch" — the
        # engine's unbounded batch queue absorbs any backlog, so the
        # tier never 429-sheds.
        self._files: dict[str, bytes] = {}
        self._batches: dict[str, dict] = {}
        self._batch_lines: dict[str, list] = {}
        self._batch_tasks: dict[str, asyncio.Task] = {}
        self._batch_live: dict[str, list[GenRequest]] = {}

        # body cap sized for /migrate/import: a migrated page chain is
        # megabytes of KV by design (page_bytes × pages on the wire)
        self.app = web.Application(client_max_size=256 * 1024 * 1024)
        # callers holding only the AppRunner (run_tpuserve) reach the
        # server through the app, e.g. to install the drain handler
        self.app["tpuserve_server"] = self
        self.app.router.add_post("/v1/chat/completions", self._chat)
        self.app.router.add_post("/v1/completions", self._completions)
        self.app.router.add_post("/v1/embeddings", self._embeddings)
        self.app.router.add_post("/tokenize", self._tokenize)
        self.app.router.add_get("/v1/models", self._models)
        self.app.router.add_get("/health", self._health)
        self.app.router.add_get("/state", self._state)
        self.app.router.add_get("/metrics", self._metrics)
        self.app.router.add_post("/drain", self._drain)
        # offline batch tier (ISSUE 19): file upload + batch lifecycle
        self.app.router.add_post("/v1/files", self._file_upload)
        self.app.router.add_get("/v1/files/{fid}/content",
                                self._file_content)
        self.app.router.add_post("/v1/batches", self._batch_create)
        self.app.router.add_get("/v1/batches", self._batch_list)
        self.app.router.add_get("/v1/batches/{bid}", self._batch_get)
        self.app.router.add_post("/v1/batches/{bid}/cancel",
                                 self._batch_cancel)
        self.app.router.add_post("/migrate/export", self._migrate_export)
        self.app.router.add_post("/migrate/import", self._migrate_import)
        self.app.router.add_post("/kv/pages", self._kv_pages)
        self.app.router.add_get("/debug/requests", self._debug_requests)
        self.app.router.add_get("/debug/requests/{rid}",
                                self._debug_request)
        self.app.router.add_get("/debug/programs", self._debug_programs)
        self.app.router.add_get("/debug/profile", self._debug_profile)
        self.app.on_startup.append(self._on_start)
        self.app.on_cleanup.append(self._on_stop)

    def _load_params(self, spec, mesh, quantize: str
                     ) -> dict[str, jax.Array]:
        """Create or restore the weights WHERE THEY WILL LIVE: each
        tensor is born with its mesh sharding (a tp model never passes
        whole through one chip), and random weights are quantized
        tensor by tensor as they are created (a bf16 model that does
        not fit the chip never has to). Records the two boot
        observables /state exports."""
        from aigw_tpu.models.quant import quantize_params, quantize_tensor

        sharding_of = None
        if mesh is not None:
            from aigw_tpu.parallel.sharding import param_sharding_fn

            sharding_of = param_sharding_fn(self.model_cfg, mesh)
        key = jax.random.PRNGKey(0)
        quant_s = 0.0
        # the boot timeline's ``weights`` phase is cut where the two
        # observables below are, so that they add up to it
        outer = BOOT.enter("weights")
        t0 = time.monotonic()
        if self.weights == "random":
            logger.info("initializing random weights for %s", spec.name)

            def finish(name: str, w: jax.Array) -> dict:
                nonlocal quant_s
                jax.block_until_ready(w)
                tq = time.monotonic()
                out = jax.block_until_ready(
                    quantize_tensor(name, w, quantize))
                quant_s += time.monotonic() - tq
                return out

            params = self.fns.init_params(
                key, self.model_cfg, sharding_of=sharding_of,
                finish=finish if quantize else None)
        elif self.weights.startswith("orbax:"):
            from aigw_tpu.models.checkpoint import restore_checkpoint

            path = self.weights[len("orbax:"):]
            logger.info("restoring orbax checkpoint %s", path)
            like = jax.eval_shape(
                lambda: self.fns.init_params(key, self.model_cfg))
            params = restore_checkpoint(path, like, sharding_of)
            if quantize:
                tq = time.monotonic()
                params = jax.block_until_ready(quantize_params(
                    params, consume=True, mode=quantize))
                quant_s = time.monotonic() - tq
        else:
            raise ValueError(f"unsupported weight source {self.weights}")
        jax.block_until_ready(params)
        total_s = time.monotonic() - t0
        # the family lays its weights out for the products that read
        # them (models/registry.py ``serving_params``), whatever the
        # source above handed over: a phase of its own
        self.weights_prepared_leaves = 0
        if self.fns.serving_params is not None:
            BOOT.enter("weights_layout")
            t1 = time.monotonic()
            loaded = set(params)
            params = jax.block_until_ready(
                self.fns.serving_params(params, self.model_cfg))
            self.weights_prepared_leaves = len(set(params) - loaded)
            logger.info("weights laid out for serving: %d leaves in %.0f ms",
                        self.weights_prepared_leaves,
                        1e3 * (time.monotonic() - t1))
        BOOT.enter(outer)
        self.weights_init_ms = round(1e3 * (total_s - quant_s), 1)
        self.weights_quantize_ms = round(1e3 * quant_s, 1)
        if quantize:
            logger.info("weights quantized to %s (W%sA16)", quantize,
                        quantize[-1])
        return params

    @property
    def adapter_names(self) -> tuple[str, ...]:
        """The served zoo (registered adapters, resident or not)."""
        if self.adapter_store is None:
            return ()
        return self.adapter_store.names()

    def _resolve_adapter(self, model: str) -> str:
        """`<base>:<adapter>` or bare adapter name → adapter name.
        Raises SchemaError for an unknown colon-suffixed adapter (a typo
        must not silently serve base-model output)."""
        if model.startswith(self.model_name + ":"):
            cand = model[len(self.model_name) + 1 :]
            if cand not in self.adapter_names:
                raise oai.SchemaError(
                    f"unknown LoRA adapter {cand!r}; loaded: "
                    f"{sorted(self.adapter_names)}"
                )
            return cand
        return model if model in self.adapter_names else ""

    async def _on_start(self, _app) -> None:
        # compile the decode program off the request path — and BEFORE
        # the engine loop exists: warmup donates kv_cache through
        # dozens of jit calls, and a live engine thread reading
        # self.kv_cache between a donated dispatch and its reassignment
        # (the idle tick's _refresh_stats does exactly that) hits a
        # deleted array and kills the loop. The startup hook runs
        # before the listener accepts, so nothing is serving yet either
        # way; to_thread only keeps the event loop's signal handling
        # live during the (long) compile.
        BOOT.enter("warmup")
        await asyncio.to_thread(self.engine.warmup)
        BOOT.enter("listen")
        self.engine.start()

    def mark_ready(self) -> None:
        """The listener accepts: ``/health`` would answer ``ok``. Closes
        the boot timeline (utils/boot.py) onto ``/state`` and tells the
        load ledger (obs/xla_events.py) that whatever is loaded from
        here on, a request waits for."""
        BOOT.ready()
        for key, value in BOOT.flat().items():
            setattr(self.engine.stats, key, value)
        xla_events.mark_ready()

    async def _on_stop(self, _app) -> None:
        for task in self._batch_tasks.values():
            task.cancel()
        if self._kv_session is not None:
            await self._kv_session.close()
            self._kv_session = None
        self.engine.stop()
        self._tok_pool.shutdown(wait=False)

    # -- helpers ----------------------------------------------------------
    def _check_logprobs(self, body: dict[str, Any]) -> int:
        """Request logprobs knobs → top-k alternates to return per token
        (-1 = logprobs off, 0 = chosen-token only). Raises SchemaError
        (→400) on unservable asks. Two request dialects (OpenAI parity):
        chat sends `logprobs: bool` + `top_logprobs: int`; legacy
        /v1/completions sends `logprobs: int` (the alternate count,
        0 meaning chosen-only). Caps: min(server --logprobs, 20)."""
        raw = body.get("logprobs")
        try:
            if isinstance(raw, bool) or raw is None:
                want = bool(raw)
                top_n = int(body.get("top_logprobs") or 0)
                if top_n and not want:
                    raise oai.SchemaError(
                        "top_logprobs requires logprobs: true")
            else:  # legacy integer form
                want = True
                top_n = int(raw)
        except (TypeError, ValueError):
            raise oai.SchemaError(
                "logprobs must be a boolean (chat) or integer (legacy); "
                "top_logprobs must be an integer") from None
        if top_n < 0:
            raise oai.SchemaError("logprobs count must be >= 0")
        if not want:
            return -1
        cap = self.engine.cfg.logprobs_topk
        if cap <= 0:
            raise oai.SchemaError(
                "this server was started without --logprobs; "
                "per-token logprobs are unavailable")
        if top_n > min(cap, 20):
            raise oai.SchemaError(
                f"top_logprobs {top_n} exceeds the served maximum "
                f"{min(cap, 20)}")
        return top_n

    def _check_constraints(
        self, body: dict[str, Any], chat: bool, lp_top_n: int, n: int,
    ) -> tuple[Any, dict[str, Any] | None]:
        """Grammar-constrained decoding intake (ISSUE 9): normalize
        ``response_format`` + ``tools``/``tool_choice`` into a compiled
        TokenFSM (or None) and a response-assembly mode. Every
        unsupported or malformed ask raises oai.SchemaError → a clear
        400 — never the old silent free-text 200."""
        try:
            rf = parse_response_format(body)
        except JSONSchemaError as e:
            raise oai.SchemaError(str(e)) from None
        if rf is not None and rf.kind == "text":
            rf = None
        tools = body.get("tools")
        choice = body.get("tool_choice")
        tools_active = bool(tools) and choice != "none"
        if rf is None and not tools_active:
            return None, None
        if not chat:
            raise oai.SchemaError(
                "response_format and tools are only supported on "
                "/v1/chat/completions")
        if not self.engine.cfg.constrained_decoding:
            raise oai.SchemaError(
                "this server was started with --no-constrained-decoding; "
                "response_format json modes and tool calling are "
                "unavailable")
        if lp_top_n >= 0:
            raise oai.SchemaError(
                "logprobs cannot be combined with response_format json "
                "modes or tools (the grammar mask reshapes the "
                "distribution the logprobs would describe)")
        if rf is not None and tools_active:
            raise oai.SchemaError(
                "response_format json modes cannot be combined with "
                "tools on this backend; send one or the other")
        eos = (self.tokenizer.eos_id,)
        V = self.model_cfg.vocab_size
        try:
            if tools_active:
                if n > 1:
                    raise oai.SchemaError(
                        "n > 1 is not supported with tools on this "
                        "backend")
                specs = constrain.parse_tools(tools)
                names = [nm for nm, _s in specs]
                named = ""
                if isinstance(choice, dict):
                    named = str(choice["function"]["name"])
                    if named not in names:
                        raise oai.SchemaError(
                            f"tool_choice names unknown tool {named!r}; "
                            f"tools declare {names}")
                    specs = [t for t in specs if t[0] == named]
                mode = ("named" if named
                        else "required" if choice == "required"
                        else "auto")
                if mode == "auto":
                    # unconstrained generation; the server detects a
                    # tool-call envelope in the output stream (a
                    # grammar that admits ALL text would mask nothing)
                    return None, {"mode": "tool", "choice": "auto",
                                  "names": names}
                fsm = constrain.compile_constraint(
                    self.tokenizer, V, eos, constrain.spec_for_tools(specs))
                return fsm, {"mode": "tool", "choice": mode,
                             "names": [t[0] for t in specs]}
            if rf.kind == "json_schema" and rf.schema is None:
                raise oai.SchemaError(
                    "response_format.json_schema.schema is required for "
                    "constrained decoding")
            fsm = constrain.compile_constraint(
                self.tokenizer, V, eos,
                constrain.spec_for_response_format(rf.kind, rf.schema))
            return fsm, {"mode": "json"}
        except (JSONSchemaError,
                constrain.UnsupportedConstraintError) as e:
            raise oai.SchemaError(str(e)) from None

    def _prefix_hashes_for(self, prompt: list[int]) -> list | None:
        """Roll the prompt's page-chain prefix hashes at the engine's
        page size — called on the tokenizer pool right after encode, so
        the engine's prefix-cache lookup costs no extra prompt pass on
        the admission thread."""
        if self.engine.prefix_cache is None:
            return None
        return page_chain_hashes(prompt, self.engine.cfg.page_size)

    @staticmethod
    def _kv_chain_header(prefix_hashes: list | None) -> dict[str, str]:
        """x-aigw-kv-chain response header (ISSUE 11): the prompt's
        first page-chain hash. The gateway learns (prefix-head → chain)
        from it — its fleet index then knows WHICH chain later requests
        with the same prefix head need, pricing fleet-hit locality into
        the picker and ordering fetch peers. Empty dict for prompts
        without a full page (nothing shareable)."""
        if not prefix_hashes:
            return {}
        return {KV_CHAIN_HEADER: prefix_hashes[0].hex()}

    def _encode_chat(self, msgs) -> tuple[list[int], list | None]:
        """Template+encode a chat AND roll its prefix hashes (one pool
        job — the hash pass rides the encode's executor hop)."""
        prompt = apply_chat_template(msgs, self.tokenizer,
                                     self.chat_template)
        return prompt, self._prefix_hashes_for(prompt)

    def _encode_text(self, text: str) -> tuple[list[int], list | None]:
        prompt = [self.tokenizer.bos_id] + self.tokenizer.encode(text)
        return prompt, self._prefix_hashes_for(prompt)

    def _submit(self, prompt: list[int], body: dict[str, Any],
                lp_top_n: int = -1, prefix_hashes: list | None = None,
                trace: RequestTrace | None = None, tenant: str = "",
                constraint: Any = None, priority: str = "interactive"):
        """Submit to the engine; returns (queue, req, meter_box) — the
        queue yields (token_id, finish_reason, lp) tuples, lp is None
        without logprobs, else (chosen_logprob, [(top_id, top_logprob)]).
        ``lp_top_n`` is the already-validated _check_logprobs value
        (validated once per request; >= 0 attaches logprobs).

        ``meter_box`` is a plain dict the engine fills with the
        request's MeterRecord strictly BEFORE posting the terminal emit
        (same engine thread, same loop.call_soon_threadsafe FIFO), so a
        consumer that dequeued the finish item reads a complete box —
        the engine-truth usage the response's ``aigw_meter`` carries."""
        loop = asyncio.get_running_loop()
        out: asyncio.Queue = asyncio.Queue()
        meter_box: dict[str, Any] = {}

        def emit(tok: int, finish: str | None) -> None:
            loop.call_soon_threadsafe(out.put_nowait, (tok, finish, None))

        def emit_lp(tok: int, finish: str | None, chosen, top) -> None:
            lp = None if chosen is None else (chosen, top)
            loop.call_soon_threadsafe(out.put_nowait, (tok, finish, lp))

        max_tokens = int(
            body.get("max_completion_tokens") or body.get("max_tokens") or 256
        )
        stop_ids: tuple[int, ...] = ()
        adapter = self._resolve_adapter(str(body.get("model", "")))
        req = GenRequest(
            prompt=prompt,
            max_tokens=max_tokens,
            sampling=SamplingParams.from_request(body),
            stop_token_ids=stop_ids,
            emit=emit,
            emit_lp=emit_lp if lp_top_n >= 0 else None,
            adapter=adapter,
            # a tenant header wins; adapter-suffixed traffic without one
            # defaults to per-adapter tenancy (each adapter ≈ a tenant)
            tenant=tenant or adapter,
            priority=priority,
            prefix_hashes=prefix_hashes,
            constraint=constraint,
            trace=trace,
            meter_sink=meter_box.update,
        )
        self.engine.submit(req)
        return out, req, meter_box

    def _usage_from_meter(self, n_prompt: int, n_out: int,
                          box: dict[str, Any] | None) -> TokenUsage:
        """Response usage from the stream-observed counts plus the
        engine's MeterRecord: cached_tokens is the prefix-cache reuse
        the engine actually skipped (satellite: the gateway reads
        cached_input_tokens off self-hosted responses at last), and the
        record itself rides ``usage.aigw_meter``. An empty box (stream
        ended before its record — e.g. a stop-string cancel races the
        engine reap) degrades to plain counts."""
        if not box:
            return TokenUsage(input_tokens=n_prompt, output_tokens=n_out,
                              total_tokens=n_prompt + n_out)
        return TokenUsage(
            input_tokens=n_prompt, output_tokens=n_out,
            total_tokens=n_prompt + n_out,
            cached_input_tokens=int(box.get("prefix_reused", 0) or 0),
            meter=meter_to_tuple(box),
        )

    @staticmethod
    def _merge_meter_boxes(boxes: list[dict]) -> dict[str, Any]:
        """Field-wise sum of the n>1 fan-out's per-choice MeterRecords:
        n choices are n engine requests and n records; the response's
        single usage object carries their total (numeric fields summed,
        identity fields from the first record)."""
        merged: dict[str, Any] = {}
        for b in boxes:
            if not b:
                continue
            for k, v in b.items():
                if k == "schema":
                    merged[k] = v
                elif isinstance(v, bool) or not isinstance(v, (int, float)):
                    merged.setdefault(k, v)
                else:
                    merged[k] = round(merged.get(k, 0) + v, 6)
        return merged

    def _begin_trace(
        self, request: web.Request, rid: str, model: str,
        prompt: list[int], body: dict[str, Any], stream: bool, chat: bool,
    ) -> RequestTrace:
        """Open the replica's request span (child of the caller's trace
        context when a ``traceparent``/B3 header arrived — the gateway
        injects one) and the flight-recorder entry. With tracing
        disabled the caller's trace id is still recorded on the entry,
        so /debug/requests joins against external traces either way."""
        headers = {k.lower(): v for k, v in request.headers.items()}
        parent = self.tracer.propagators.extract(headers)
        span = None
        if self.tracer.enabled:
            op = "chat" if chat else "text_completion"
            span = self.tracer.start_span(f"tpuserve.{op} {model}",
                                          parent)
            span.attributes.update(genai_attributes(
                operation=op, request_model=model,
                response_model=self.model_name, backend="tpuserve",
                streaming=stream))
            span.set("tpuserve.request_id", rid)
        entry = self.flight.begin(
            rid, model=model, prompt_tokens=len(prompt),
            max_tokens=int(body.get("max_completion_tokens")
                           or body.get("max_tokens") or 256),
            stream=stream,
            trace_id=(span.context.trace_id if span is not None
                      else parent.trace_id if parent is not None else ""),
            span_id=(span.context.span_id if span is not None else ""),
        )
        return RequestTrace(entry=entry, tracer=self.tracer, span=span,
                            loop=self.engine.stats.loop)

    def _end_trace(self, trace: RequestTrace, finish: str, n_out: int,
                   n_prompt: int = 0, error: str = "") -> None:
        self._live.pop(trace.entry.rid, None)  # no longer exportable
        self.flight.finish(trace.entry, finish, n_out)
        span = trace.span
        if span is not None:
            span.set("gen_ai.usage.input_tokens", n_prompt)
            span.set("gen_ai.usage.output_tokens", n_out)
            span.set("tpuserve.finish_reason", finish)
            if error:
                span.record_error(error)
            span.end()

    @staticmethod
    def _legacy_logprobs(entries: list[dict[str, Any]]) -> dict[str, Any]:
        """OpenAI legacy /v1/completions logprobs shape from the chat
        content entries (single source for all three response paths)."""
        return {
            "tokens": [e["token"] for e in entries],
            "token_logprobs": [e["logprob"] for e in entries],
            "top_logprobs": [
                {t["token"]: t["logprob"] for t in e["top_logprobs"]}
                for e in entries],
        }

    def _lp_entry(self, piece: str, lp, top_n: int) -> dict[str, Any]:
        """One OpenAI logprobs content entry for an emitted token."""
        chosen, top = lp
        entry: dict[str, Any] = {
            "token": piece,
            "logprob": float(chosen),
            "bytes": list(piece.encode("utf-8")),
        }
        tops = []
        for tid, tval in (top or [])[:top_n]:
            ttext = self.tokenizer.decode([int(tid)])
            tops.append({"token": ttext, "logprob": float(tval),
                         "bytes": list(ttext.encode("utf-8"))})
        entry["top_logprobs"] = tops
        return entry

    # -- endpoints --------------------------------------------------------
    async def _chat(self, request: web.Request) -> web.StreamResponse:
        try:
            body = oai.parse_json_body(await request.read())
            oai.validate_chat_request(body)
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        msgs = body["messages"]
        if self._small_text(msgs):
            # first-token fast path: a short prompt's template+encode is
            # microseconds — the executor round-trip would cost more
            # than it hides AND spread a burst's submits across extra
            # event-loop turns (admission coalescing then waits on the
            # stragglers). Long prompts keep the pool hop. Both paths
            # also roll the prompt's prefix-cache chain hashes here, so
            # engine admission never re-reads the prompt to probe.
            prompt, hashes = self._encode_chat(msgs)
        else:
            prompt, hashes = await self._off(self._encode_chat, msgs)
        return await self._generate(request, body, prompt, chat=True,
                                    prefix_hashes=hashes)

    #: request text below this many chars tokenizes inline on the event
    #: loop (HF tokenizer throughput is ~MB/s; 4KiB is ~ms)
    _INLINE_TOKENIZE_CHARS = 4096

    @classmethod
    def _small_text(cls, msgs) -> bool:
        total = 0
        for m in msgs if isinstance(msgs, list) else [msgs]:
            content = m.get("content") if isinstance(m, dict) else m
            total += len(content) if isinstance(content, str) else \
                len(str(content))
            if total >= cls._INLINE_TOKENIZE_CHARS:
                return False
        return True

    async def _off(self, fn, *args):
        """Run a tokenization-bound callable off the event loop."""
        return await asyncio.get_running_loop().run_in_executor(
            self._tok_pool, fn, *args
        )

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = oai.parse_json_body(await request.read())
            oai.request_model(body)
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        prompt_text = body.get("prompt", "")
        if isinstance(prompt_text, list):
            prompt_text = "".join(prompt_text)
        if len(prompt_text) < self._INLINE_TOKENIZE_CHARS:
            prompt, hashes = self._encode_text(prompt_text)
        else:
            prompt, hashes = await self._off(self._encode_text,
                                             prompt_text)
        return await self._generate(request, body, prompt, chat=False,
                                    prefix_hashes=hashes)

    async def _generate(
        self,
        request: web.Request,
        body: dict[str, Any],
        prompt: list[int],
        chat: bool,
        prefix_hashes: list | None = None,
    ) -> web.StreamResponse:
        if self.draining:
            # graceful drain (ISSUE 14): no NEW sessions while retiring
            # — live ones keep streaming below until they finish or the
            # gateway migrates them off
            return self._drain_refusal()
        stream = bool(body.get("stream", False))
        try:
            # logprobs knobs validate to a client 400 up front — every
            # branch below (incl. n>1) relies on it (the SchemaError
            # catch around _submit is reserved for unknown-adapter → 404)
            lp_top_n = self._check_logprobs(body)
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        tenant = request.headers.get(TENANT_HEADER, "")
        # priority class (ISSUE 19): "batch" rides the engine's offline
        # tier (never 429-shed — its queue is unbounded); anything else
        # is interactive
        priority = ("batch"
                    if request.headers.get(PRIORITY_HEADER, "") == "batch"
                    else "interactive")
        n = int(body.get("n") or 1)
        try:
            # grammar-constrained decoding intake (ISSUE 9): malformed
            # or unsupported response_format/tools asks 400 here — the
            # old behavior (silently serving free text with a 200) is
            # gone on every path below
            constraint, cmode = self._check_constraints(
                body, chat, lp_top_n, n)
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        # fleet KV fetch (ISSUE 11): named siblings may hold this
        # prompt's chain — import their pages before admission so the
        # prefill becomes a resume (covers the n>1 fan-out too: the
        # shared prompt is fetched once)
        await self._maybe_fleet_fetch(request, prompt, prefix_hashes)
        if n > 1:
            if n > self.engine.cfg.max_batch_size:
                return web.Response(
                    status=400,
                    body=oai.error_body(
                        f"n={n} exceeds max_batch_size "
                        f"{self.engine.cfg.max_batch_size}"),
                    content_type="application/json")
            if stream:
                return await self._generate_n_stream(
                    request, body, prompt, chat, n, lp_top_n,
                    prefix_hashes, tenant, constraint, priority)
            return await self._generate_n(body, prompt, chat, n,
                                          lp_top_n, prefix_hashes,
                                          tenant, constraint, priority)
        include_usage = oai.include_stream_usage(body)
        rid = (
            f"chatcmpl-{uuid.uuid4().hex[:24]}"
            if chat
            else f"cmpl-{uuid.uuid4().hex[:24]}"
        )
        created = int(time.time())
        rm = RequestMetrics(
            metrics=self.metrics,
            operation="chat" if chat else "text_completion",
            provider="tpuserve",
            request_model=body.get("model", self.model_name),
            response_model=self.model_name,
        )
        stops = body.get("stop")
        stop_strs: list[str] = (
            [stops] if isinstance(stops, str) else list(stops or [])
        )
        trace = self._begin_trace(request, rid,
                                  str(body.get("model", self.model_name)),
                                  prompt, body, stream, chat)
        try:
            out, gen_req, meter_box = self._submit(
                prompt, body, lp_top_n, prefix_hashes, trace, tenant,
                constraint, priority)
        except EngineOverloadedError as e:
            self._end_trace(trace, "rejected", 0, len(prompt),
                            error=str(e))
            return web.Response(
                status=429,
                body=oai.error_body(str(e), type_="rate_limit_error"),
                headers={"retry-after": "1"},
                content_type="application/json")
        except oai.SchemaError as e:
            self._end_trace(trace, "rejected", 0, len(prompt),
                            error=str(e))
            return web.Response(
                status=404,
                body=oai.error_body(str(e), type_="model_not_found"),
                content_type="application/json")
        except ValueError as e:
            self._end_trace(trace, "rejected", 0, len(prompt),
                            error=str(e))
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        # exportable until a terminal _end_trace: the gateway can hand
        # this session to a decode replica via POST /migrate/export
        # (streaming only — a buffered response has nothing to splice;
        # constrained/tool sessions carry FSM or detector state no wire
        # blob restores, so they stay put)
        if stream and lp_top_n < 0 and constraint is None \
                and cmode is None:
            self._live[rid] = (gen_req, {
                "response_id": rid,
                "model": self.model_name,
                "created": created,
                "chat": chat,
                "include_usage": include_usage,
                "stop_strs": stop_strs,
            })

        n_prompt = len(prompt)
        want_lp = lp_top_n >= 0
        if not stream:
            try:
                text, n_out, finish, lp_content = await self._collect(
                    out, stop_strs, lp_top_n)
            except asyncio.CancelledError:
                gen_req.cancelled.set()
                self._end_trace(trace, "cancelled", 0, n_prompt)
                raise
            usage = self._usage_from_meter(n_prompt, n_out, meter_box)
            rm.finish(usage, error_type="engine" if finish == "error"
                      else "")
            self._end_trace(trace, finish, n_out, n_prompt,
                            error="engine failure"
                            if finish == "error" else "")
            if finish == "error":
                return web.Response(
                    status=500,
                    body=oai.error_body("engine failure", type_="server_error"),
                    content_type="application/json",
                )
            tool_calls = None
            if cmode is not None and cmode["mode"] == "tool":
                env = constrain.parse_tool_envelope(text, cmode["names"])
                if env is not None:
                    name, args = env
                    tool_calls = [{
                        "id": f"call_{uuid.uuid4().hex[:24]}",
                        "type": "function",
                        "function": {"name": name, "arguments": args},
                    }]
                    text = ""
                    if finish == "stop":
                        finish = "tool_calls"
                # auto mode with no envelope: plain content, finish
                # stays as the engine reported; required/named with no
                # envelope only happens on a length truncation — the
                # partial text is returned as content with finish
                # "length" (the OpenAI truncation contract)
            if chat:
                resp = oai.chat_completion_response(
                    model=self.model_name, content=text,
                    finish_reason=finish, usage=usage, response_id=rid,
                    tool_calls=tool_calls,
                )
                if lp_content is not None:
                    resp["choices"][0]["logprobs"] = {
                        "content": lp_content}
            else:
                resp = {
                    "id": rid,
                    "object": "text_completion",
                    "created": created,
                    "model": self.model_name,
                    "choices": [
                        {"index": 0, "text": text, "finish_reason": finish}
                    ],
                    "usage": oai.usage_dict(usage),
                }
                if lp_content is not None:
                    # legacy completions carry token_logprobs/tokens
                    resp["choices"][0]["logprobs"] = \
                        self._legacy_logprobs(lp_content)
            return web.json_response(
                resp, headers={"x-aigw-request-id": rid,
                               **self._kv_chain_header(prefix_hashes)})

        # streaming
        resp = web.StreamResponse(
            status=200,
            headers={"content-type": "text/event-stream",
                     "cache-control": "no-cache",
                     # joins the gateway access log / client against the
                     # flight recorder (/debug/requests/{id}) and spans
                     "x-aigw-request-id": rid,
                     **self._kv_chain_header(prefix_hashes)},
        )
        # first-token fast path: the role frame and the first content
        # delta are two small writes back to back — Nagle must not hold
        # the second until the first is ACKed
        set_tcp_nodelay(request.transport)
        await resp.prepare(request)
        decoder = StreamingDecoder(self.tokenizer)
        emitted = ""
        n_out = 0
        finish = "stop"
        # Pre-serialized SSE chunk envelope: everything except the
        # content string is constant for the request's lifetime, so the
        # hot loop pays one json.dumps of the piece instead of
        # serializing the whole chunk dict per frame. Built by
        # splitting a real stream_chunk_sse frame on a sentinel, so the
        # bytes are identical to the non-template path by construction.
        tmpl_head = tmpl_tail = b""
        if chat:
            sentinel = "\x00aigw-delta-slot\x00"
            tmpl_head, tmpl_tail = oai.stream_chunk_sse(
                response_id=rid, model=self.model_name, created=created,
                delta={"content": sentinel},
            ).split(json.dumps(sentinel).encode())

        # tool-call streaming (ISSUE 9): required/named generations are
        # grammar-forced envelopes — split incrementally into OpenAI
        # tool_calls deltas; auto buffers only while the text is still a
        # viable envelope prefix, then streams as content or tool call
        tool_stream: Any = None
        auto_detect: Any = None
        if cmode is not None and cmode["mode"] == "tool":
            if cmode["choice"] == "auto":
                auto_detect = constrain.AutoToolDetector(cmode["names"])
            else:
                tool_stream = constrain.ToolCallParser()
        tool_call_id = f"call_{uuid.uuid4().hex[:24]}"

        async def write_tool_events(events) -> None:
            for ev in events:
                if ev[0] == "name":
                    await resp.write(oai.stream_chunk_sse(
                        response_id=rid, model=self.model_name,
                        created=created,
                        delta={"tool_calls": [{
                            "index": 0, "id": tool_call_id,
                            "type": "function",
                            "function": {"name": ev[1],
                                         "arguments": ""},
                        }]}))
                elif ev[0] == "args" and ev[1]:
                    await resp.write(oai.stream_chunk_sse(
                        response_id=rid, model=self.model_name,
                        created=created,
                        delta={"tool_calls": [{
                            "index": 0,
                            "function": {"arguments": ev[1]},
                        }]}))

        async def write_piece(piece: str, lp_entries=None) -> None:
            # an empty piece (mid-UTF-8 token) still carries its logprob
            # entries so the streamed list aligns 1:1 with completion
            # tokens; without logprobs, empty pieces emit nothing
            if not piece and not lp_entries:
                return
            if chat:
                if not lp_entries:
                    await resp.write(
                        tmpl_head + json.dumps(piece).encode()
                        + tmpl_tail)
                    return
                await resp.write(
                    oai.stream_chunk_sse(
                        response_id=rid, model=self.model_name,
                        created=created, delta={"content": piece},
                        logprobs={"content": lp_entries},
                    )
                )
            else:
                choice: dict[str, Any] = {"index": 0, "text": piece,
                                          "finish_reason": None}
                if lp_entries:
                    choice["logprobs"] = self._legacy_logprobs(lp_entries)
                await resp.write(
                    SSEEvent(
                        data=json.dumps(
                            {
                                "id": rid,
                                "object": "text_completion",
                                "created": created,
                                "model": self.model_name,
                                "choices": [choice],
                            }
                        )
                    ).encode()
                )

        async def emit_text(piece: str, lp_entries=None) -> None:
            """Route one detokenized burst: content deltas normally,
            tool_calls deltas for grammar-forced envelopes, buffered
            while a tool_choice=auto stream is still ambiguous."""
            nonlocal tool_stream
            if tool_stream is not None:
                await write_tool_events(tool_stream.feed(piece))
                return
            if auto_detect is not None and auto_detect.decided is None:
                decision, text_out = auto_detect.feed(piece)
                if decision is None:
                    return  # still a viable envelope prefix: buffer
                if decision == "tool":
                    tool_stream = constrain.ToolCallParser()
                    await write_tool_events(tool_stream.feed(text_out))
                    return
                piece = text_out  # diverged: flush the buffer as content
            await write_piece(piece, lp_entries)

        try:
            if chat:
                await resp.write(
                    oai.stream_chunk_sse(
                        response_id=rid, model=self.model_name,
                        created=created,
                        delta={"role": "assistant", "content": ""},
                    )
                )
            done_streaming = False

            async def handle_burst(burst: list, inline_detok: bool) -> None:
                """Detokenize + emit one burst as one SSE frame. Big
                bursts detokenize off the event loop (the HF tokenizer
                releases the GIL); tiny ones — and the latency-critical
                FIRST frame (inline_detok) — stay inline: the executor
                hop would cost more than it hides. The decoder is
                stateful per request, so pre-decoding the whole burst
                is safe: tokens past a stop hit are discarded below and
                the decoder is never reused after."""
                nonlocal emitted, n_out, finish, done_streaming
                toks = [t for t, _f, _lp in burst if t >= 0]
                predecoded = (
                    iter(await self._off(_push_all, decoder, toks))
                    if len(toks) >= 4 and not inline_detok else None
                )
                pieces: list[str] = []
                lp_entries: list[dict[str, Any]] = []
                for tok, fin, lp in burst:
                    if tok >= 0:
                        n_out += 1
                        rm.record_tokens_emitted(1)
                        piece = (next(predecoded) if predecoded is not None
                                 else decoder.push(tok))
                        lp_entry = (self._lp_entry(piece, lp, lp_top_n)
                                    if want_lp and lp is not None else None)
                        if piece:
                            emitted += piece
                            hit = _find_stop(emitted, stop_strs)
                            if hit is not None:
                                # trim to just before the stop sequence;
                                # the truncated final token keeps its lp
                                # entry (1:1 token/entry alignment)
                                keep = hit - (len(emitted) - len(piece))
                                pieces.append(piece[:max(keep, 0)])
                                if lp_entry is not None:
                                    lp_entries.append(lp_entry)
                                finish = "stop"
                                gen_req.cancelled.set()
                                done_streaming = True
                                break
                            pieces.append(piece)
                            if lp_entry is not None:
                                lp_entries.append(lp_entry)
                        elif lp_entry is not None:
                            lp_entries.append(lp_entry)
                    if fin is not None:
                        finish = fin
                        if fin not in ("error", "migrated"):
                            # migrated: any held-back partial text is
                            # re-derived by the importing replica's
                            # primed decoder — flushing it here would
                            # duplicate it across the seam
                            pieces.append(decoder.flush())
                        done_streaming = True
                        break
                await emit_text("".join(pieces), lp_entries)

            while not done_streaming:
                # keepalive comments while queued behind prefills so
                # intermediaries don't drop an apparently-idle stream
                while True:
                    try:
                        first = await asyncio.wait_for(
                            out.get(), timeout=10.0)
                        break
                    except asyncio.TimeoutError:
                        await resp.write(b": ping\n\n")
                # Coalesce the burst: a decode window lands K tokens per
                # slot on the queue at once; one SSE frame per burst
                # instead of one per token cuts event-loop wakeups,
                # json dumps, and syscalls ~K× in the serving hot loop
                # (OpenAI deltas are arbitrary strings; logprob entries
                # stay 1:1 with tokens inside the frame's content list).
                burst = [first]
                while True:
                    try:
                        burst.append(out.get_nowait())
                    except asyncio.QueueEmpty:
                        break
                if n_out == 0 and len(burst) > 1:
                    # first-token fast path: the stream's FIRST token
                    # rides its own frame — detokenized inline and on
                    # the wire before the rest of the burst is even
                    # decoded — so a request that waited out a decode
                    # window doesn't pay the whole burst's detokenize/
                    # serialize cost before its first byte
                    await handle_burst(burst[:1], inline_detok=True)
                    if not done_streaming:
                        await handle_burst(burst[1:],
                                           inline_detok=False)
                else:
                    await handle_burst(burst, inline_detok=n_out == 0)
            if auto_detect is not None and tool_stream is None:
                # stream ended while the auto detector was still
                # ambiguous: the held-back prefix was content after all
                decision, text_rem = auto_detect.finish()
                if decision == "content" and text_rem:
                    await write_piece(text_rem)
        except (asyncio.CancelledError, ConnectionResetError):
            # client went away: stop generating, free the slot
            gen_req.cancelled.set()
            self._end_trace(trace, "cancelled", n_out, n_prompt)
            raise
        if tool_stream is not None and tool_stream.completed \
                and finish == "stop":
            finish = "tool_calls"
        usage = self._usage_from_meter(n_prompt, n_out, meter_box)
        rm.finish(usage)
        self._end_trace(trace, finish, n_out, n_prompt)
        if finish == "migrated":
            # the session moved to another replica mid-stream: end THIS
            # stream with no finish frame and no [DONE] — the importing
            # replica's continuation stream (spliced by the gateway)
            # carries the terminal frames under the same response id
            await resp.write_eof()
            return resp
        await resp.write(self._final_stream_frame(
            chat, rid, created, finish,
            usage if include_usage else None))
        await resp.write(SSEEvent(data="[DONE]").encode())
        await resp.write_eof()
        return resp

    def _final_stream_frame(self, chat: bool, rid: str, created: int,
                            finish: str,
                            usage: TokenUsage | None) -> bytes:
        """Terminal SSE frame carrying finish_reason (+ usage when
        requested) in the FRONT schema's chunk shape. Legacy
        /v1/completions streams previously ended with a chat-shaped
        chunk here — the gateway's typed stream validator (correctly)
        rejected it and replaced the stream tail with an error event."""
        if chat:
            return oai.stream_chunk_sse(
                response_id=rid, model=self.model_name, created=created,
                delta={}, finish_reason=finish, usage=usage)
        ev: dict[str, Any] = {
            "id": rid, "object": "text_completion", "created": created,
            "model": self.model_name,
            "choices": [{"index": 0, "text": "",
                         "finish_reason": finish}],
        }
        if usage is not None:
            ev["usage"] = oai.usage_dict(usage)
        return SSEEvent(data=json.dumps(ev)).encode()

    def _submit_n(self, body: dict[str, Any], prompt: list[int], n: int,
                  lp_top_n: int, prefix_hashes: list | None = None,
                  tenant: str = "", constraint: Any = None,
                  priority: str = "interactive"):
        """Fan out n engine submissions with per-choice seeds (shared by
        the buffered and streaming n>1 paths — one copy of the seed
        derivation, overload cleanup, and error mapping). Returns the
        list of (queue, request, meter_box) triples, or an error
        web.Response."""
        sampling = SamplingParams.from_request(body)
        outs: list = []
        try:
            for i in range(n):
                # distinct seeds per choice so samples differ
                # deterministically
                per_choice = dict(body)
                per_choice["seed"] = (sampling.seed or 0) + i if (
                    sampling.seed or sampling.temperature > 0
                ) else 0
                outs.append(self._submit(prompt, per_choice, lp_top_n,
                                         prefix_hashes, tenant=tenant,
                                         constraint=constraint,
                                         priority=priority))
        except EngineOverloadedError as e:
            for _q, req, _b in outs:  # don't orphan already-queued choices
                req.cancelled.set()
            return web.Response(
                status=429,
                body=oai.error_body(str(e), type_="rate_limit_error"),
                headers={"retry-after": "1"},
                content_type="application/json")
        except oai.SchemaError as e:  # unknown adapter → 404, like n=1
            for _q, req, _b in outs:
                req.cancelled.set()
            return web.Response(
                status=404,
                body=oai.error_body(str(e), type_="model_not_found"),
                content_type="application/json")
        except ValueError as e:  # bad sampling params → 400, like n=1
            for _q, req, _b in outs:
                req.cancelled.set()
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        return outs

    async def _generate_n(
        self, body: dict[str, Any], prompt: list[int], chat: bool, n: int,
        lp_top_n: int = -1, prefix_hashes: list | None = None,
        tenant: str = "", constraint: Any = None,
        priority: str = "interactive",
    ) -> web.Response:
        """n>1 choices: fan out n engine requests (continuous batching
        runs them concurrently — same prompt pages shared by the prefix
        cache) and assemble a multi-choice response."""
        stops = body.get("stop")
        stop_strs = [stops] if isinstance(stops, str) else list(stops or [])
        outs = self._submit_n(body, prompt, n, lp_top_n, prefix_hashes,
                              tenant, constraint, priority)
        if isinstance(outs, web.Response):
            return outs
        results = await asyncio.gather(
            *(self._collect(q, stop_strs, lp_top_n)
              for q, _req, _b in outs)
        )
        # single-metering on fan-out (satellite): each choice is one
        # engine request with exactly one MeterRecord; the response's
        # one usage object carries their field-wise sum
        merged_meter = self._merge_meter_boxes([b for _q, _r, b in outs])
        usage = TokenUsage(
            input_tokens=len(prompt),
            output_tokens=sum(r[1] for r in results),
            total_tokens=len(prompt) + sum(r[1] for r in results),
            cached_input_tokens=int(
                merged_meter.get("prefix_reused", 0) or 0),
            meter=meter_to_tuple(merged_meter) if merged_meter else (),
        )
        rid = (f"chatcmpl-{uuid.uuid4().hex[:24]}" if chat
               else f"cmpl-{uuid.uuid4().hex[:24]}")
        if chat:
            choices = []
            for i, (text, _n, finish, lp_content) in enumerate(results):
                c: dict[str, Any] = {
                    "index": i,
                    "message": {"role": "assistant", "content": text},
                    "finish_reason": finish}
                if lp_content is not None:
                    c["logprobs"] = {"content": lp_content}
                choices.append(c)
            resp = {
                "id": rid, "object": "chat.completion",
                "created": int(time.time()), "model": self.model_name,
                "choices": choices, "usage": oai.usage_dict(usage),
            }
        else:
            resp = {
                "id": rid, "object": "text_completion",
                "created": int(time.time()), "model": self.model_name,
                "choices": [
                    {"index": i, "text": text, "finish_reason": finish,
                     **({"logprobs": self._legacy_logprobs(lp_content)}
                        if lp_content is not None else {})}
                    for i, (text, _n, finish, lp_content)
                    in enumerate(results)
                ],
                "usage": oai.usage_dict(usage),
            }
        return web.json_response(resp)

    async def _generate_n_stream(
        self, request: web.Request, body: dict[str, Any],
        prompt: list[int], chat: bool, n: int, lp_top_n: int = -1,
        prefix_hashes: list | None = None, tenant: str = "",
        constraint: Any = None, priority: str = "interactive",
    ) -> web.StreamResponse:
        """Streaming n>1 (OpenAI parity; previously 400): fan out n
        engine requests, merge their token streams, and emit one SSE
        chunk per (choice, burst) carrying that choice's index —
        clients see the standard interleaved multi-choice stream. The
        continuous-batching engine runs the choices concurrently; the
        prefix cache shares their prompt pages."""
        stops = body.get("stop")
        stop_strs = [stops] if isinstance(stops, str) else list(stops or [])
        include_usage = oai.include_stream_usage(body)
        outs = self._submit_n(body, prompt, n, lp_top_n, prefix_hashes,
                              tenant, constraint, priority)
        if isinstance(outs, web.Response):
            return outs

        rid = (f"chatcmpl-{uuid.uuid4().hex[:24]}" if chat
               else f"cmpl-{uuid.uuid4().hex[:24]}")
        created = int(time.time())
        rm = RequestMetrics(
            metrics=self.metrics,
            operation="chat" if chat else "text_completion",
            provider="tpuserve",
            request_model=body.get("model", self.model_name),
            response_model=self.model_name,
        )
        resp = web.StreamResponse(
            status=200,
            headers={"content-type": "text/event-stream",
                     "cache-control": "no-cache"},
        )
        await resp.prepare(request)

        merged: asyncio.Queue = asyncio.Queue()

        async def pump(i: int, q: asyncio.Queue) -> None:
            while True:
                item = await q.get()
                await merged.put((i, item))
                if item[1] is not None:  # finish marker
                    return

        pumps = [asyncio.create_task(pump(i, q))
                 for i, (q, _req, _b) in enumerate(outs)]
        decoders = [StreamingDecoder(self.tokenizer) for _ in range(n)]
        emitted = [""] * n
        counts = [0] * n
        done = [False] * n
        want_lp = lp_top_n >= 0

        async def write_chunk(i: int, piece: str, lp_entries=None,
                              finish: str | None = None) -> None:
            if chat:
                delta = {"content": piece} if finish is None else {}
                await resp.write(oai.stream_chunk_sse(
                    response_id=rid, model=self.model_name,
                    created=created, delta=delta, index=i,
                    finish_reason=finish,
                    logprobs={"content": lp_entries}
                    if lp_entries else None,
                ))
            else:
                choice: dict[str, Any] = {"index": i, "text": piece,
                                          "finish_reason": finish}
                if lp_entries:
                    choice["logprobs"] = self._legacy_logprobs(lp_entries)
                await resp.write(SSEEvent(data=json.dumps({
                    "id": rid, "object": "text_completion",
                    "created": created, "model": self.model_name,
                    "choices": [choice],
                })).encode())

        try:
            if chat:
                for i in range(n):
                    await resp.write(oai.stream_chunk_sse(
                        response_id=rid, model=self.model_name,
                        created=created,
                        delta={"role": "assistant", "content": ""},
                        index=i,
                    ))
            while not all(done):
                while True:
                    try:
                        first = await asyncio.wait_for(merged.get(),
                                                       timeout=10.0)
                        break
                    except asyncio.TimeoutError:
                        await resp.write(b": ping\n\n")
                burst = [first]
                while True:
                    try:
                        burst.append(merged.get_nowait())
                    except asyncio.QueueEmpty:
                        break
                # coalesce per choice within the burst
                pieces: dict[int, list[str]] = {}
                lps: dict[int, list] = {}
                fins: dict[int, str] = {}
                for i, (tok, fin, lp) in burst:
                    if done[i] or i in fins:
                        # post-finish tokens in the same burst (e.g.
                        # after a stop-string hit) must not count
                        # toward usage — the n=1 path breaks there too
                        continue
                    if tok >= 0:
                        counts[i] += 1
                        rm.record_tokens_emitted(1)
                        piece = decoders[i].push(tok)
                        if want_lp and lp is not None:
                            lps.setdefault(i, []).append(
                                self._lp_entry(piece, lp, lp_top_n))
                        if piece:
                            emitted[i] += piece
                            hit = _find_stop(emitted[i], stop_strs)
                            if hit is not None:
                                keep = hit - (len(emitted[i])
                                              - len(piece))
                                pieces.setdefault(i, []).append(
                                    piece[:max(keep, 0)])
                                fins[i] = "stop"
                                outs[i][1].cancelled.set()
                                continue
                            pieces.setdefault(i, []).append(piece)
                    if fin is not None and i not in fins:
                        fins[i] = fin
                        if fin != "error":
                            tail = decoders[i].flush()
                            if tail:
                                pieces.setdefault(i, []).append(tail)
                for i in sorted(set(pieces) | set(lps) | set(fins)):
                    text = "".join(pieces.get(i, ()))
                    if text or lps.get(i):
                        await write_chunk(i, text, lps.get(i))
                    if i in fins:
                        done[i] = True
                        await write_chunk(i, "", None,
                                          finish=fins[i] or "stop")
        except (asyncio.CancelledError, ConnectionResetError):
            for _q, req, _b in outs:
                req.cancelled.set()
            raise
        finally:
            for p in pumps:
                p.cancel()
        merged_meter = self._merge_meter_boxes([b for _q, _r, b in outs])
        usage = TokenUsage(
            input_tokens=len(prompt),
            output_tokens=sum(counts),
            total_tokens=len(prompt) + sum(counts),
            cached_input_tokens=int(
                merged_meter.get("prefix_reused", 0) or 0),
            meter=meter_to_tuple(merged_meter) if merged_meter else (),
        )
        rm.finish(usage)
        if include_usage:
            if chat:
                await resp.write(oai.stream_chunk_sse(
                    response_id=rid, model=self.model_name,
                    created=created, delta=None, usage=usage,
                ))
            else:
                # legacy completions: the usage chunk must keep the
                # text_completion shape (choices present, possibly
                # empty) or the gateway's typed validator drops it
                await resp.write(SSEEvent(data=json.dumps({
                    "id": rid, "object": "text_completion",
                    "created": created, "model": self.model_name,
                    "choices": [],
                    "usage": oai.usage_dict(usage),
                })).encode())
        await resp.write(SSEEvent(data="[DONE]").encode())
        await resp.write_eof()
        return resp

    async def _collect(
        self, out: asyncio.Queue, stop_strs: list[str],
        lp_top_n: int = -1,
    ) -> tuple[str, int, str, list | None]:
        """Drain a generation to completion (non-streaming path).
        ``lp_top_n >= 0`` also collects OpenAI logprobs content entries
        (engine must run with logprobs_topk > 0)."""
        decoder = StreamingDecoder(self.tokenizer)
        text = ""
        n_out = 0
        finish = "stop"
        lp_content: list | None = [] if lp_top_n >= 0 else None
        while True:
            tok, fin, lp = await out.get()
            if tok >= 0:
                n_out += 1
                piece = decoder.push(tok)
                text += piece
                if lp_content is not None and lp is not None:
                    lp_content.append(
                        self._lp_entry(piece, lp, lp_top_n))
                hit = _find_stop(text, stop_strs)
                if hit is not None:
                    return text[:hit], n_out, "stop", lp_content
            if fin is not None:
                finish = fin
                if fin != "error":
                    text += decoder.flush()
                return text, n_out, finish, lp_content

    async def _embeddings(self, request: web.Request) -> web.Response:
        try:
            body = oai.parse_json_body(await request.read())
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        raw = body.get("input")
        if isinstance(raw, str):
            items: list = [raw]
        elif isinstance(raw, list) and raw and all(
            isinstance(x, int) for x in raw
        ):
            items = [raw]  # a single pre-tokenized input
        elif isinstance(raw, list):
            items = list(raw)
        else:
            items = []
        if not items:
            return web.Response(
                status=400,
                body=oai.error_body(
                    "input must be a string, array of strings, or array of "
                    "token ids"
                ),
                content_type="application/json",
            )
        max_len = self.engine.cfg.max_seq_len
        # encode all string items concurrently on the tokenizer pool
        str_jobs = {
            idx: self._off(self.tokenizer.encode, it)
            for idx, it in enumerate(items) if isinstance(it, str)
        }
        str_results = dict(zip(
            str_jobs, await asyncio.gather(*str_jobs.values())
        ))
        encoded = []
        for idx, it in enumerate(items):
            if isinstance(it, str):
                encoded.append(str_results[idx][:max_len])
            elif isinstance(it, list) and all(isinstance(x, int) for x in it):
                encoded.append([x % self.model_cfg.vocab_size for x in it][:max_len])
            else:
                return web.Response(
                    status=400,
                    body=oai.error_body("invalid embeddings input element"),
                    content_type="application/json",
                )
        S = max(8, max(len(e) for e in encoded))
        S = 1 << (S - 1).bit_length()  # pow2 bucket to bound compiles
        toks = np.zeros((len(encoded), S), np.int32)
        lens = np.zeros((len(encoded),), np.int32)
        for i, e in enumerate(encoded):
            toks[i, : len(e)] = e
            lens[i] = len(e)
        hidden = await asyncio.to_thread(
            lambda: np.asarray(
                self._hidden_fn(self.engine.params, jnp.asarray(toks),
                                jnp.asarray(lens))
            )
        )
        n_tokens = int(lens.sum())
        usage = TokenUsage(input_tokens=n_tokens, total_tokens=n_tokens)
        return web.json_response(
            oai.embeddings_response(
                model=self.model_name,
                vectors=[h.tolist() for h in hidden],
                usage=usage,
            )
        )

    async def _tokenize(self, request: web.Request) -> web.Response:
        try:
            body = oai.parse_json_body(await request.read())
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        if isinstance(body.get("messages"), list):
            ids = await self._off(apply_chat_template, body["messages"],
                                  self.tokenizer, self.chat_template)
        else:
            ids = await self._off(self.tokenizer.encode,
                                  str(body.get("prompt", "")))
        return web.json_response(
            {
                "count": len(ids),
                "max_model_len": self.engine.cfg.max_seq_len,
                "tokens": ids,
            }
        )

    async def _models(self, _request: web.Request) -> web.Response:
        # capability flags (ISSUE 9): clients (and the gateway's merged
        # /v1/models) discover which structured-output / tool-calling
        # workloads this replica enforces natively
        caps = (dict(constrain.CAPABILITIES)
                if self.engine.cfg.constrained_decoding else None)
        extra = {"capabilities": caps} if caps else None
        entries: list[tuple] = [(self.model_name, "tpuserve", 0, extra)]
        entries += [
            (f"{self.model_name}:{a}", "tpuserve-lora", 0, extra)
            for a in self.adapter_names
        ]
        return web.json_response(oai.models_response(entries))

    # -- offline batch tier (ISSUE 19) ------------------------------------
    #: request lines accepted per batch file (a replica-local in-memory
    #: store, not a durable object store — bound the blast radius)
    _BATCH_MAX_LINES = 10_000

    async def _file_upload(self, request: web.Request) -> web.Response:
        """POST /v1/files — accept a raw JSONL batch input body and
        return a file id. Intentionally raw-body (not multipart): the
        gateway forwards bytes verbatim and the batch surface is the
        only consumer."""
        if self.draining:
            return self._drain_refusal()
        raw = await request.read()
        if not raw.strip():
            return web.Response(
                status=400,
                body=oai.error_body("empty file body; POST the JSONL "
                                    "batch input as the request body"),
                content_type="application/json")
        fid = f"file-{uuid.uuid4().hex[:24]}"
        self._files[fid] = raw
        return web.json_response({
            "id": fid, "object": "file", "bytes": len(raw),
            "created_at": int(time.time()), "purpose": "batch",
        })

    async def _file_content(self, request: web.Request) -> web.Response:
        raw = self._files.get(request.match_info["fid"])
        if raw is None:
            return web.Response(
                status=404, body=oai.error_body("unknown file id"),
                content_type="application/json")
        return web.Response(body=raw,
                            content_type="application/jsonl")

    def _parse_batch_lines(self, raw: bytes,
                           endpoint: str) -> list[tuple[str, dict]]:
        """Validate the whole JSONL input up front — every malformed
        shape is a 400 naming its line BEFORE any engine work runs (a
        half-executed batch that then 400s would strand its output).
        Raises oai.SchemaError."""
        lines: list[tuple[str, dict]] = []
        seen: set[str] = set()
        for i, ln in enumerate(raw.splitlines(), start=1):
            if not ln.strip():
                continue
            try:
                obj = json.loads(ln)
            except ValueError:
                raise oai.SchemaError(
                    f"line {i}: not valid JSON") from None
            if not isinstance(obj, dict):
                raise oai.SchemaError(
                    f"line {i}: each line must be a JSON object")
            cid = obj.get("custom_id")
            if not isinstance(cid, str) or not cid:
                raise oai.SchemaError(
                    f"line {i}: custom_id must be a non-empty string")
            if cid in seen:
                raise oai.SchemaError(
                    f"line {i}: duplicate custom_id {cid!r}")
            seen.add(cid)
            if obj.get("method", "POST") != "POST":
                raise oai.SchemaError(
                    f"line {i}: method must be POST")
            url = obj.get("url", endpoint)
            if url != endpoint:
                raise oai.SchemaError(
                    f"line {i}: url {url!r} does not match the batch "
                    f"endpoint {endpoint!r}")
            body = obj.get("body")
            if not isinstance(body, dict):
                raise oai.SchemaError(
                    f"line {i}: body must be a JSON object")
            if body.get("stream"):
                raise oai.SchemaError(
                    f"line {i}: stream is not supported in batches")
            lines.append((cid, body))
        if not lines:
            raise oai.SchemaError("batch input has no request lines")
        if len(lines) > self._BATCH_MAX_LINES:
            raise oai.SchemaError(
                f"batch input has {len(lines)} lines; this replica "
                f"caps a batch at {self._BATCH_MAX_LINES}")
        return lines

    async def _batch_create(self, request: web.Request) -> web.Response:
        """POST /v1/batches — validate the input file, register the
        batch object, and start the runner. Batch work is NEVER
        429-shed: lines enter the engine's unbounded batch queue and
        soak idle decode slots at strict low priority."""
        if self.draining:
            return self._drain_refusal()
        try:
            body = oai.parse_json_body(await request.read())
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        endpoint = str(body.get("endpoint", ""))
        if endpoint not in ("/v1/chat/completions", "/v1/completions"):
            return web.Response(
                status=400,
                body=oai.error_body(
                    "endpoint must be /v1/chat/completions or "
                    "/v1/completions"),
                content_type="application/json")
        fid = str(body.get("input_file_id", ""))
        raw = self._files.get(fid)
        if raw is None:
            return web.Response(
                status=404,
                body=oai.error_body(f"unknown input_file_id {fid!r}"),
                content_type="application/json")
        try:
            lines = self._parse_batch_lines(raw, endpoint)
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        bid = f"batch_{uuid.uuid4().hex[:24]}"
        self._batches[bid] = {
            "id": bid, "object": "batch", "endpoint": endpoint,
            "input_file_id": fid, "status": "in_progress",
            "output_file_id": None, "created_at": int(time.time()),
            "request_counts": {"total": len(lines), "completed": 0,
                               "failed": 0},
        }
        self._batch_lines[bid] = lines
        self._batch_live[bid] = []
        self._batch_tasks[bid] = asyncio.create_task(
            self._run_batch(bid))
        return web.json_response(self._batches[bid])

    async def _batch_list(self, _request: web.Request) -> web.Response:
        return web.json_response({
            "object": "list",
            "data": sorted(self._batches.values(),
                           key=lambda b: b["created_at"]),
        })

    async def _batch_get(self, request: web.Request) -> web.Response:
        b = self._batches.get(request.match_info["bid"])
        if b is None:
            return web.Response(
                status=404, body=oai.error_body("unknown batch id"),
                content_type="application/json")
        return web.json_response(b)

    async def _batch_cancel(self, request: web.Request) -> web.Response:
        """POST /v1/batches/{id}/cancel — stop submitting new lines and
        cancel the in-flight ones; the runner finalizes to
        ``cancelled`` with the lines that DID finish in the output."""
        bid = request.match_info["bid"]
        b = self._batches.get(bid)
        if b is None:
            return web.Response(
                status=404, body=oai.error_body("unknown batch id"),
                content_type="application/json")
        if b["status"] == "in_progress":
            b["status"] = "cancelling"
            for req in self._batch_live.get(bid, ()):
                req.cancelled.set()
        return web.json_response(b)

    async def _batch_one(self, bid: str, body: dict[str, Any],
                         chat: bool) -> tuple[int, dict[str, Any]]:
        """Run ONE batch line through the normal submit path at
        priority="batch" (non-streaming). Returns (status_code,
        response body) — per-line failures are output lines, never a
        batch-level error."""
        try:
            if chat:
                oai.validate_chat_request(body)
                prompt, hashes = await self._off(self._encode_chat,
                                                 body["messages"])
            else:
                oai.request_model(body)
                text_in = body.get("prompt", "")
                if isinstance(text_in, list):
                    text_in = "".join(text_in)
                prompt, hashes = await self._off(self._encode_text,
                                                 text_in)
            lp_top_n = self._check_logprobs(body)
            tenant = str(body.get("user", ""))
            out, gen_req, meter_box = self._submit(
                prompt, body, lp_top_n, hashes,
                tenant=tenant, priority="batch")
        except oai.SchemaError as e:
            return 400, json.loads(oai.error_body(str(e)))
        except ValueError as e:
            return 400, json.loads(oai.error_body(str(e)))
        self._batch_live[bid].append(gen_req)
        stops = body.get("stop")
        stop_strs = ([stops] if isinstance(stops, str)
                     else list(stops or []))
        try:
            text, n_out, finish, lp_content = await self._collect(
                out, stop_strs, lp_top_n)
        finally:
            self._batch_live[bid].remove(gen_req)
        if finish == "error":
            return 500, json.loads(oai.error_body(
                "engine failure", type_="server_error"))
        # /v1/batches output lines carry full usage incl. the engine
        # meter (satellite) — a parked/resumed line's record spans the
        # whole spliced session including host-spill residency
        usage = self._usage_from_meter(len(prompt), n_out, meter_box)
        if chat:
            resp = oai.chat_completion_response(
                model=self.model_name, content=text,
                finish_reason=finish, usage=usage,
                response_id=f"chatcmpl-{uuid.uuid4().hex[:24]}")
            if lp_content is not None:
                resp["choices"][0]["logprobs"] = {"content": lp_content}
        else:
            resp = {
                "id": f"cmpl-{uuid.uuid4().hex[:24]}",
                "object": "text_completion",
                "created": int(time.time()),
                "model": self.model_name,
                "choices": [{"index": 0, "text": text,
                             "finish_reason": finish}],
                "usage": oai.usage_dict(usage),
            }
            if lp_content is not None:
                resp["choices"][0]["logprobs"] = \
                    self._legacy_logprobs(lp_content)
        return 200, resp

    async def _run_batch(self, bid: str) -> None:
        """The batch runner: drive every line at priority="batch" with
        bounded concurrency (one engine's worth — backlog beyond that
        sits in the replica, not as thousands of parked asyncio
        queues), assemble the JSONL output file, finalize the batch
        object."""
        b = self._batches[bid]
        lines = self._batch_lines.pop(bid)
        chat = b["endpoint"] == "/v1/chat/completions"
        sem = asyncio.Semaphore(max(2, self.engine.cfg.max_batch_size))
        out_lines: list[bytes | None] = [None] * len(lines)

        async def one(i: int, cid: str, body: dict[str, Any]) -> None:
            async with sem:
                if b["status"] != "in_progress":
                    return  # cancelled before this line started
                status, resp = await self._batch_one(bid, body, chat)
                entry = {
                    "id": f"batch_req_{uuid.uuid4().hex[:16]}",
                    "custom_id": cid,
                    "response": {"status_code": status, "body": resp},
                    "error": None,
                }
                if status == 200:
                    b["request_counts"]["completed"] += 1
                else:
                    b["request_counts"]["failed"] += 1
                out_lines[i] = json.dumps(entry).encode()

        try:
            await asyncio.gather(*(one(i, cid, body)
                                   for i, (cid, body) in enumerate(lines)))
        except asyncio.CancelledError:
            for req in self._batch_live.get(bid, ()):
                req.cancelled.set()
            raise
        ofid = f"file-{uuid.uuid4().hex[:24]}"
        self._files[ofid] = b"\n".join(
            ln for ln in out_lines if ln is not None) + b"\n"
        b["output_file_id"] = ofid
        b["status"] = ("cancelled" if b["status"] == "cancelling"
                       else "completed")
        self._batch_tasks.pop(bid, None)

    # -- graceful drain (ISSUE 14) ----------------------------------------
    def _drain_refusal(self) -> web.Response:
        """503 + Retry-After for new work on a draining replica: the
        gateway's pre-first-byte failover retries the next-ranked
        sibling; a direct client backs off and re-resolves."""
        return web.Response(
            status=503,
            body=oai.error_body(
                "replica is draining (shutting down or being retired); "
                "retry against another replica",
                type_="server_error"),
            headers={"retry-after": "2", "x-aigw-draining": "1"},
            content_type="application/json")

    async def _drain(self, request: web.Request) -> web.Response:
        """POST /drain — the control plane's retire protocol: flips the
        draining flag (``{"on": false}`` un-drains, e.g. a cancelled
        rolling update) and reports what's still live. Admissions are
        refused from the moment the flag is up; live slots keep
        serving until they finish or the gateway migrates them off."""
        try:
            raw = await request.read()
            body = oai.parse_json_body(raw) if raw.strip() else {}
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        self.draining = bool(body.get("on", True))
        s = self.engine.stats
        return web.json_response({
            "draining": self.draining,
            "active_slots": s.active_slots,
            "queued": s.queued,
            "batch_queued": s.batch_queued,
            "batch_active": s.batch_active,
            "live_streams": len(self._live),
            "migratable_slots": s.migratable_slots,
        })

    async def drain(self, timeout_s: float = 60.0,
                    poll_s: float = 0.1) -> bool:
        """Drain to empty: refuse new admissions and wait until the
        engine holds zero active slots and an empty queue (sessions
        finish naturally or the gateway migrates them away). Returns
        True when fully drained within the budget — the graceful-exit
        criterion (exit 0 with zero live slots)."""
        self.draining = True
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            s = self.engine.stats
            # batch backlog (queued + parked) must clear too: a retired
            # replica's in-memory batch state is gone — scale-in waits
            # for the soak to finish before pulling the plug
            if (s.active_slots == 0 and s.queued == 0
                    and s.batch_queued == 0):
                return True
            await asyncio.sleep(poll_s)
        s = self.engine.stats
        return (s.active_slots == 0 and s.queued == 0
                and s.batch_queued == 0)

    def install_signal_drain(self, stop_event: asyncio.Event,
                             grace_s: float = 30.0) -> None:
        """SIGTERM/SIGINT → graceful drain, then set ``stop_event`` so
        the caller can cleanup + exit 0. A second signal skips the
        drain (operator insisting). Call from within the running
        loop."""
        loop = asyncio.get_running_loop()

        def _handle() -> None:
            if self.draining:
                stop_event.set()  # second signal: immediate
                return

            async def _go() -> None:
                drained = await self.drain(grace_s)
                logger.info("drain %s; shutting down",
                            "complete" if drained else "timed out")
                stop_event.set()

            logger.info("signal received: draining (grace %.0fs)",
                        grace_s)
            self._drain_task = loop.create_task(_go())

        import signal as _signal

        for sig in (_signal.SIGTERM, _signal.SIGINT):
            loop.add_signal_handler(sig, _handle)

    async def _health(self, _request: web.Request) -> web.Response:
        if not self.engine.healthy:
            return web.json_response(
                {"status": "error", "model": self.model_name,
                 "error": self.engine.last_error},
                status=503,
            )
        return web.json_response({"status": "ok", "model": self.model_name})

    async def _state(self, _request: web.Request) -> web.Response:
        """Endpoint-picker telemetry (KV occupancy, queue depth, and the
        queue-latency / adaptive-window signals the picker scores).

        Drift contract (rule ``gauge-drift``, make lint): every literal
        key below must be an ENGINE_GAUGES attr or carry a STATE_ONLY
        exemption in analysis/manifest.py, and every non-exempt gauge
        attr must appear here — keep new fields literal string keys so
        the static pass sees them (** spreads carry only the dynamic
        topology surface)."""
        s = self.engine.stats
        store = self.adapter_store
        tenant_slots = self.engine._tenant_slots()
        # live, not the engine thread's per-tick snapshot: the backlog
        # must stay visible to the picker while that thread compiles
        queued, queue_wait_ms = self.engine.queue_depth()
        return web.json_response(
            {
                "model": self.model_name,
                # replica identity/uptime (ISSUE 12): the fleet
                # aggregator keys restart detection on replica_id and
                # displays uptime per replica
                "replica_id": self.replica_id,
                "started_at": round(self._started_at, 3),
                "uptime_s": round(time.time() - self._started_at, 3),
                # graceful drain (ISSUE 14): the gateway's fleet health
                # machine honors this as the control-plane overlay —
                # the picker stops routing here on the next poll
                "draining": self.draining,
                # cumulative TTFT histogram buckets — the gateway's
                # live SLO burn-rate monitor (obs/slomon.py) computes
                # windowed goodput from the deltas of this field, off
                # the /state poll the picker already makes
                "ttft_hist_buckets":
                    self.engine.phases.hists["ttft"].cumulative(),
                # adapter serving subsystem (ISSUE 7): the zoo, device
                # residency, load/evict churn, and in-flight adapter
                # slots — the gateway picker's adapter-affinity signal
                # and the capacity dashboard for row sizing
                "adapters_registered": sorted(self.adapter_names),
                "adapters_resident": (store.resident_names()
                                      if store is not None else []),
                "adapter_rows": (store.n_slots if store is not None
                                 else 0),
                "adapter_loads": s.adapter_loads,
                "adapter_evictions": s.adapter_evictions,
                "adapter_slots": s.adapter_slots,
                # multi-tenant fairness surface: who holds decode slots
                # right now, and how often the per-tenant cap deferred
                # an admission
                "tenant_slots": {t or "(anonymous)": c
                                 for t, c in sorted(tenant_slots.items())},
                "tenants_active": s.tenants_active,
                "tenant_max_slots": s.tenant_max_slots,
                "tenant_deferrals": s.tenant_deferrals,
                "tenant_slot_cap": self.engine.cfg.tenant_slot_cap,
                # prefill/decode disaggregation (ISSUE 8): sessions
                # moved in/out, the KV pages that traveled with them,
                # and the live migration-eligibility count (prefill
                # done, decode young) the gateway's orchestrator reads
                "migrations_out": s.migrations_out,
                "migrations_in": s.migrations_in,
                "migration_pages_out": s.migration_pages_out,
                "migration_pages_in": s.migration_pages_in,
                "migratable_slots": s.migratable_slots,
                # KV memory hierarchy (ISSUE 11): host-spill-tier
                # occupancy/churn, cross-replica fetch traffic, and the
                # resident+spilled chain digest the gateway's fleet
                # index polls (chain-hash → replica routing)
                "kv_spills": s.kv_spills,
                "kv_revives": s.kv_revives,
                "kv_spill_evictions": s.kv_spill_evictions,
                "kv_spilled_pages": s.kv_spilled_pages,
                "kv_spill_bytes": s.kv_spill_bytes,
                "kv_host_bytes": s.kv_host_bytes,
                "kv_fetches_out": s.kv_fetches_out,
                "kv_fetches_in": s.kv_fetches_in,
                "kv_fetch_pages_out": s.kv_fetch_pages_out,
                "kv_fetch_pages_in": s.kv_fetch_pages_in,
                "kv_chains": list(self.engine.kv_chain_digest()),
                # grammar-constrained decoding (ISSUE 9): the
                # capability flag the gateway merges into /v1/models,
                # live constrained slots, window rollbacks (grammar
                # cuts), device mask patches, and the compiled-grammar
                # cache size
                "constrained_decoding":
                    self.engine.cfg.constrained_decoding,
                "capabilities": (dict(constrain.CAPABILITIES)
                                 if self.engine.cfg.constrained_decoding
                                 else {}),
                "constrained_slots": s.constrained_slots,
                "constraint_requests": s.constraint_requests,
                "constraint_rollbacks": s.constraint_rollbacks,
                "constraint_mask_updates": s.constraint_mask_updates,
                "constraint_grammars": s.constraint_grammars,
                # measured per-device memory (ISSUE 9 satellite): live
                # jax memory_stats() bytes (0 off-TPU) + KV-pool byte
                # occupancy — with `slice` below, the picker's
                # per-slice memory signal
                "device_bytes_in_use": s.device_bytes_in_use,
                "device_bytes_limit": s.device_bytes_limit,
                "device_memory_frac": s.device_memory_frac,
                "kv_pool_bytes": s.kv_pool_bytes,
                "kv_bytes_in_use": s.kv_bytes_in_use,
                # quantized KV pages (ISSUE 13): bits per stored
                # element, bytes one cached token costs across layers
                # (scales included), and the configured pool dtype —
                # the capacity math behind int8 ≈ 0.52x / int4 ≈ 0.27x
                # of the bf16 pool at head_dim 128
                "kv_quant_bits": s.kv_quant_bits,
                "kv_bytes_per_token": s.kv_bytes_per_token,
                "kv_cache_dtype": self.engine.cfg.kv_cache_dtype,
                # MoE serving surface (ISSUE 18): router placement /
                # capacity-drop scalars plus the per-expert token list
                # the picker prices (worst-expert discipline — a
                # replica is as fast as its hottest expert shard) and
                # the per-layer drop list. All-zero / empty on dense
                # families
                "moe_tokens_routed": s.moe_tokens_routed,
                "moe_tokens_dropped": s.moe_tokens_dropped,
                "moe_dropped_frac": s.moe_dropped_frac,
                "moe_expert_imbalance": s.moe_expert_imbalance,
                "moe_local_assignments": s.moe_local_assignments,
                "moe_total_assignments": s.moe_total_assignments,
                "moe_held_hits_decode": s.moe_held_hits_decode,
                # the device cache beside the weights: layers that own
                # pages, the per-slot recurrent state of a hybrid
                # family, and what is off because pages alone are half
                # of such a family's sequence (feature -> why)
                "kv_layers": s.kv_layers,
                "state_bytes_per_slot": s.state_bytes_per_slot,
                "state_bytes_total": s.state_bytes_total,
                "features_off": self.engine.features_off,
                "moe_expert_load": self.engine.moe_expert_load(),
                "moe_layer_drops": self.engine.moe_layer_drops(),
                # mesh serving (ISSUE 10): real per-device signals —
                # the mesh topology (axis → size; {} off-mesh), EVERY
                # local device's memory/KV/param share (not just
                # device 0), the worst-device memory fraction the
                # picker scores, the measured per-device parameter
                # bytes (≈ total/tp under tensor parallelism:
                # tests/test_mesh_serving.py), and the analytical ICI
                # collective volume per decoded token
                # long-context serving surface: the advertised context
                # length + sp axis (the gateway picker's over-length
                # filter rejects prompts no replica can hold, and its
                # predicted-TTFT model prices prompt length with the
                # measured per-token prefill rate), the sp prefill mode
                # actually routing, and the chunked/resume counters
                "max_seq_len": self.engine.cfg.max_seq_len,
                "sp": self.engine._sp,
                "sp_prefill_mode": (
                    "chunked"
                    if self.engine._prefill_sp_suffix_fn is not None
                    else "monolithic"
                    if self.engine._prefill_sp_fn is not None
                    else "off"),
                "sp_chunked_prefills": s.sp_chunked_prefills,
                "sp_resume_prefills": s.sp_resume_prefills,
                "sp_interactive_admits": s.sp_interactive_admits,
                "prefill_ms_per_token": round(
                    s.prefill_ms_per_token(), 4),
                "mesh_axes": self.engine.mesh_axes(),
                "mesh_devices": s.device_count,
                "devices": self.engine.device_stats,
                "device_count": s.device_count,
                "device_memory_frac_worst": s.device_memory_frac_worst,
                "param_bytes_total": sum(
                    self.engine.param_bytes_by_device.values()),
                "param_bytes_per_device": {
                    str(k): v for k, v in sorted(
                        self.engine.param_bytes_by_device.items())},
                "ici_bytes_per_token": s.ici_bytes_per_token,
                "ici_bytes_total": s.ici_bytes_total,
                # the resolved attention choices + WHY (the fallback
                # matrix, tpuserve/attention.py) and the migration
                # capability flag the gateway _Migrator respects
                "attention_backend_reason": getattr(
                    self.engine, "attn_reason", ""),
                "decode_attn_impl": self.engine.decode_attn_impl,
                "decode_attn_reason": self.engine.decode_attn_reason,
                "migration": self.engine.migratable,
                "active_slots": s.active_slots,
                "max_slots": self.engine.cfg.max_batch_size,
                "queued": queued,
                # priority-tiered serving (ISSUE 19): the offline class's
                # footprint. ``queued``/``queue_wait_ms`` above stay
                # interactive-only by construction (batch rides its own
                # engine queue) — the picker's predicted_ttft_ms never
                # prices batch backlog; its batch routing and the
                # controller's retire-drain read these instead
                "batch_queued": s.batch_queued,
                "batch_active": s.batch_active,
                "batch_preemptions": s.batch_preemptions,
                "batch_resumed": s.batch_resumed,
                "batch_tokens": s.batch_tokens,
                "batch_slot_frac": self.engine.cfg.batch_slot_frac,
                "queue_wait_ms": round(queue_wait_ms, 3),
                "kv_pages_free": s.kv_pages_free,
                "kv_occupancy": s.kv_occupancy,
                "tokens_generated": s.tokens_generated,
                "decode_steps": s.decode_steps,
                "sample_sort_steps": s.sample_sort_steps,
                "decode_kv_pages_read": s.decode_kv_pages_read,
                "decode_kv_pages_live": s.decode_kv_pages_live,
                "decode_state_rows_read": s.decode_state_rows_read,
                "decode_state_rows_live": s.decode_state_rows_live,
                "moe_groups_kept_hits": s.moe_groups_kept_hits,
                "moe_group_slots": s.moe_group_slots,
                "prefill_keys_attended": s.prefill_keys_attended,
                "moe_unserved_tokens": s.moe_unserved_tokens,
                "swa_keys_attended": s.swa_keys_attended,
                "swa_keys_in_context": s.swa_keys_in_context,
                "decode_window": s.decode_window,
                "prefill_ms": round(s.prefill_ms, 3),
                "transfer_ms": round(s.transfer_ms, 3),
                "emit_ms": round(s.emit_ms, 3),
                "first_emit_ms": round(s.first_emit_ms, 3),
                # prefill attention backend + its padding tax (ISSUE 6):
                # real prompt tokens vs tokens the padded program
                # geometry processed; the ragged backend's claim is
                # padded_frac ≈ chunk residue instead of bucket residue
                "attention_backend": self.engine.attn.name,
                "prefill_tokens_real": s.prefill_tokens_real,
                "prefill_tokens_padded": s.prefill_tokens_padded,
                "prefill_padded_frac": s.prefill_padded_frac,
                # cold-start observables: wall time of warmup() and the
                # compiled hot-path program count it left behind
                "warmup_ms": s.warmup_ms,
                "warm_programs": s.warm_programs,
                # prefix-cache surface: the picker's prefix-affinity
                # scoring and capacity dashboards read these
                "prefix_cache_hit_rate": round(s.prefix_cache_hit_rate, 4),
                "prefix_pages_resident": s.prefix_pages_resident,
                "prefix_pages_pinned": s.prefix_pages_pinned,
                "prefix_bytes_pinned": (
                    s.prefix_pages_pinned * self.engine.kv_page_bytes),
                "prefix_cache_hits": s.prefix_cache_hits,
                "prefix_cache_misses": s.prefix_cache_misses,
                "prefix_cache_evictions": s.prefix_cache_evictions,
                "prefix_tokens_reused": s.prefix_tokens_reused,
                # a family whose prefix cache resumes from a snapshot
                # of its per-slot state (0 elsewhere): snapshots saved
                # / restored / evicted least recently used, the pool's
                # bytes, and tokens cached by pages that prefilled
                # again for want of a snapshot
                "state_snapshots_saved": s.state_snapshots_saved,
                "state_snapshots_restored": s.state_snapshots_restored,
                "state_snapshots_evicted": s.state_snapshots_evicted,
                "state_snapshot_bytes_total": s.state_snapshot_bytes_total,
                "prefix_tokens_unrestorable": s.prefix_tokens_unrestorable,
                # speculative decoding surface: acceptance telemetry
                # for dashboards
                "spec_accepted": s.spec_accepted,
                "spec_drafted": s.spec_drafted,
                "spec_accept_rate": round(s.spec_accept_rate, 4),
                "spec_draft_len": s.spec_draft_len,
                "spec_rung_ups": s.spec_rung_ups,
                "spec_rung_downs": s.spec_rung_downs,
                "spec_lookahead_slots": s.spec_lookahead_slots,
                # engine-truth usage metering (ISSUE 20): cumulative
                # MeterRecord totals — the gateway's usage ledger
                # reconciles its per-tenant sums against these counters
                # token-for-token (they only move inside _meter_emit,
                # the single record funnel)
                "meter_records": s.meter_records,
                "meter_prefill_tokens": s.meter_prefill_tokens,
                "meter_prefill_padded_tokens": s.meter_prefill_padded_tokens,
                "meter_prefix_reused_tokens": s.meter_prefix_reused_tokens,
                "meter_decode_tokens": s.meter_decode_tokens,
                "meter_spec_drafted": s.meter_spec_drafted,
                "meter_spec_accepted": s.meter_spec_accepted,
                "meter_hbm_page_byte_s": s.meter_hbm_page_byte_s,
                "meter_host_page_byte_s": s.meter_host_page_byte_s,
                "state_rebuilds": s.state_rebuilds,
                # XLA compile tracker (obs/xla_events.py): nonzero
                # growth after warmup = a hot-path compile regression
                "xla_compiles": s.xla_compiles,
                "xla_compile_ms": s.xla_compile_ms,
                # a compile event is a LOAD when the persistent cache
                # (utils/boot.py) served it — hits vs misses say which,
                # process-wide so weight-init programs count too
                "xla_cache_hits": s.xla_cache_hits,
                "xla_cache_misses": s.xla_cache_misses,
                # the load ledger's totals by stage, process-wide
                # (trace, lowering, and of the backend span the cache
                # read + deserialize_and_load), and the loads that came
                # after ready: what requests waited for. The table and
                # the log behind them are on /debug/programs
                "xla_trace_ms": s.xla_trace_ms,
                "xla_lower_ms": s.xla_lower_ms,
                "xla_retrieval_ms": s.xla_retrieval_ms,
                "xla_late_loads": s.xla_late_loads,
                "xla_late_ms": s.xla_late_ms,
                "xla_late_trace_ms": s.xla_late_trace_ms,
                "xla_late_lower_ms": s.xla_late_lower_ms,
                "xla_late_retrieval_ms": s.xla_late_retrieval_ms,
                "compile_cache_dir": compile_cache_dir(),
                # boot observables: where the weights came from and
                # what creating / quantizing them cost
                "weights": self.weights,
                "weights_init_ms": self.weights_init_ms,
                "weights_quantize_ms": self.weights_quantize_ms,
                # leaves the family's ``serving_params`` laid out at
                # load (0: the family has none)
                "weights_prepared_leaves": self.weights_prepared_leaves,
                # the boot timeline (utils/boot.py): self time per
                # phase from the process's start to ready, and the sum
                "boot_import_ms": s.boot_import_ms,
                "boot_backend_ms": s.boot_backend_ms,
                "boot_weights_ms": s.boot_weights_ms,
                "boot_weights_layout_ms": s.boot_weights_layout_ms,
                "boot_engine_ms": s.boot_engine_ms,
                "boot_warmup_ms": s.boot_warmup_ms,
                "boot_listen_ms": s.boot_listen_ms,
                "boot_ready_ms": s.boot_ready_ms,
                # serving-phase latency distributions (p50/p95/p99 per
                # ENGINE_HISTOGRAMS phase; -1 = no observations yet):
                # the picker's TTFT prediction reads them
                # (gateway/picker.py)
                "phase_percentiles": self.engine.phases.percentiles(),
                # ICI topology: the picker's same-slice preference term
                # (gateway/picker.py) keys on this
                **device_topology(),
                # what the engine loop did (obs/flight.py LoopLedger),
                # flat and cumulative: loop_<phase>_ns / _n, loop_ns,
                # loop_busy_ns, and the counters cut to the profiler
                # captures taken so far (capture_*); the keys are
                # obs/metrics.LOOP_GAUGES
                **s.loop.flat(),
            }
        )

    async def _metrics(self, _request: web.Request) -> web.Response:
        # info-style gauge for the RESOLVED decode rung (the fallback
        # matrix outcome is a string; dashboards select on the label)
        impl_info = (
            "# TYPE tpuserve_decode_attn_impl gauge\n"
            f'tpuserve_decode_attn_impl{{impl='
            f'"{self.engine.decode_attn_impl}"}} 1\n').encode()
        body = (self.metrics.export()
                + render_engine_gauges(self.engine.stats)
                + impl_info
                + render_device_gauges(self.engine.device_stats)
                + render_moe_gauges(self.engine.moe_expert_load(),
                                    self.engine.moe_layer_drops())
                + self.engine.phases.render())
        return web.Response(body=body, content_type="text/plain")

    # -- KV memory hierarchy: cross-replica page fetch (ISSUE 11) ----------
    async def _kv_pages(self, request: web.Request) -> web.Response:
        """Serve KV pages by content chain hash to a sibling replica:
        resident pages travel through the pinned device→host export
        path, host-spilled pages straight from the spill tier — both on
        the PR 8 f32 page wire (b64 rows + shape). Keys this replica
        does not hold are simply absent from the response; the fetcher
        imports the leading contiguous run it got."""
        import base64

        try:
            body = oai.parse_json_body(await request.read())
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        raw_keys = body.get("keys")
        if not isinstance(raw_keys, list) or not raw_keys:
            return web.Response(
                status=400,
                body=oai.error_body("keys must be a non-empty list of "
                                    "hex chain hashes"),
                content_type="application/json")
        try:
            keys = [bytes.fromhex(str(k)) for k in
                    raw_keys[:KV_FETCH_MAX_PAGES]]
        except ValueError as e:
            return web.Response(
                status=400,
                body=oai.error_body(f"malformed chain hash: {e}"),
                content_type="application/json")
        try:
            out = await asyncio.to_thread(self.engine.kv_export_pages,
                                          keys)
        except (MigrationError, TimeoutError) as e:
            return web.Response(
                status=409, body=oai.error_body(str(e)),
                content_type="application/json")
        pages = [dict(encode_wire_page(d), key=k.hex()) for k, d in out]
        return web.json_response({
            "model": self.model_name,
            "page_size": self.engine.cfg.page_size,
            "pages": pages,
        })

    async def _maybe_fleet_fetch(self, request: web.Request,
                                 prompt: list[int],
                                 hashes: list | None) -> None:
        """Cross-replica KV fetch ahead of admission: when the gateway
        named sibling replicas that hold this prompt's chain
        (x-aigw-kv-peers) and the leading pages are missing locally,
        fetch them over /kv/pages and import them as cached chains —
        the admission probe then resumes instead of re-prefilling.
        Strictly best-effort: any failure falls back to cold prefill."""
        peers_hdr = request.headers.get(KV_PEERS_HEADER, "")
        eng = self.engine
        if (not peers_hdr or not hashes or eng.prefix_cache is None
                or "kv_fleet_fetch" in eng.features_off):
            return
        ps = eng.cfg.page_size
        # the wire rule (PR 8): only pages whose every row is written KV
        # travel — cap at the prompt's fully-written coverage
        usable = min(len(hashes), (len(prompt) - 1) // ps)
        present = set(eng.kv_chain_digest())
        miss = 0
        while miss < usable and hashes[miss].hex() in present:
            miss += 1
        if miss >= usable:
            return
        want = [h.hex() for h in hashes[miss:usable]]
        peers = [p.strip() for p in peers_hdr.split(",")
                 if p.strip()][:KV_PEERS_MAX]
        for peer in peers:
            got = await self._fetch_pages_from(peer, want)
            run: list[np.ndarray] = []
            for h in want:
                rows = got.get(h)
                if rows is None:
                    break  # leading contiguous run only
                run.append(rows)
            if not run:
                continue
            try:
                await asyncio.to_thread(eng.kv_import_pages, prompt,
                                        run, miss)
            except (MigrationError, TimeoutError) as e:
                logger.info("fleet KV import from %s failed: %s",
                            peer, e)
                return
            logger.info("fleet-fetched %d KV pages from %s", len(run),
                        peer)
            return

    async def _fetch_pages_from(self, peer: str,
                                keys_hex: list[str]) -> dict:
        """POST /kv/pages to one sibling; returns {key_hex: np rows}
        ({} on any error — the fetch is best-effort)."""
        import base64

        import aiohttp

        base = peer if "://" in peer else f"http://{peer}"
        if self._kv_session is None or self._kv_session.closed:
            self._kv_session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=KV_FETCH_TIMEOUT_S))
        try:
            async with self._kv_session.post(
                    base + "/kv/pages", json={"keys": keys_hex}) as resp:
                if resp.status != 200:
                    return {}
                data = await resp.json()
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError):
            return {}
        out: dict = {}
        try:
            for p in data.get("pages") or ():
                out[str(p["key"])] = decode_wire_page(p)
        except (KeyError, TypeError, ValueError):
            return {}
        return out

    # -- prefill/decode disaggregation: KV page migration (ISSUE 8) --------
    async def _migrate_export(self, request: web.Request) -> web.Response:
        """Cut a live streaming session and return its wire blob: full
        KV pages (device→host via the engine's async-transfer path),
        chain hashes, and the slot's sampling/penalty/key state. The
        session's SSE stream ends without terminal frames; the caller
        splices the importing replica's continuation stream. A failed
        export leaves the session serving exactly as it was (409)."""
        import base64

        try:
            body = oai.parse_json_body(await request.read())
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        rid = str(body.get("request_id", ""))
        live = self._live.get(rid)
        if live is None:
            return web.Response(
                status=404,
                body=oai.error_body(
                    f"request {rid!r} is not an exportable live stream"),
                content_type="application/json")
        gen_req, meta = live
        try:
            out = await asyncio.to_thread(self.engine.migrate_export,
                                          gen_req)
        except (MigrationError, TimeoutError) as e:
            # the session keeps serving on this replica — 409 tells the
            # orchestrator "not now", not "broken"
            return web.Response(
                status=409, body=oai.error_body(str(e)),
                content_type="application/json")
        blob = out["blob"]
        blob["meta"] = meta
        pages = [encode_wire_page(d) for d in out["data"]]
        return web.json_response({"blob": blob, "pages": pages})

    async def _migrate_import(
        self, request: web.Request) -> web.StreamResponse:
        if self.draining:
            # a draining replica must not ADOPT sessions either — the
            # migration orchestrator reads 503 as "pick someone else"
            return self._drain_refusal()
        """Adopt an exported page chain and stream the session's
        continuation. The pages enter this replica's pool through the
        prefix-cache registration path (parked evictable, normal
        refcount/CoW discipline); the continuation request then admits
        as an offset resume against them — warm path end to end (the
        page scatters and resume programs are pre-compiled by
        warmup()). Frames carry the ORIGINAL response id, and usage
        counts the whole session (generated-so-far offset), so the
        gateway can splice this stream where the exporter's stopped."""
        import base64

        try:
            body = oai.parse_json_body(await request.read())
        except oai.SchemaError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        blob = body.get("blob") or {}
        try:
            tokens = [int(t) for t in blob["tokens"]]
            pages = [decode_wire_page(p)
                     for p in (body.get("pages") or ())]
        except (KeyError, TypeError, ValueError) as e:
            return web.Response(
                status=400,
                body=oai.error_body(f"malformed migration blob: {e}"),
                content_type="application/json")
        try:
            await asyncio.to_thread(self.engine.migrate_import, tokens,
                                    pages)
        except (MigrationError, TimeoutError) as e:
            if "OutOfPages" in str(e):
                # page pressure rides the normal overload contract
                return web.Response(
                    status=503, body=oai.error_body(str(e)),
                    headers={"retry-after": "1"},
                    content_type="application/json")
            return web.Response(
                status=400, body=oai.error_body(str(e)),
                content_type="application/json")

        meta = blob.get("meta") or {}
        rid = str(meta.get("response_id")
                  or f"chatcmpl-{uuid.uuid4().hex[:24]}")
        chat = bool(meta.get("chat", True))
        created = int(meta.get("created") or time.time())
        stop_strs = [s for s in (meta.get("stop_strs") or ())
                     if isinstance(s, str)]
        include_usage = bool(meta.get("include_usage", False))
        n_prev = int(blob.get("generated", 0))
        orig_len = int(blob.get("orig_prompt_len", len(tokens)))

        loop = asyncio.get_running_loop()
        out_q: asyncio.Queue = asyncio.Queue()
        meter_box: dict[str, Any] = {}

        def emit(tok: int, fin: str | None) -> None:
            loop.call_soon_threadsafe(out_q.put_nowait, (tok, fin))

        creq = continuation_request(blob, emit=emit)
        # single-metering across the splice (satellite): the exporter
        # emitted NO record at the cut; this continuation's terminal
        # record — fed by the blob's meter carry — covers the WHOLE
        # session, so the gateway's spliced stream meters exactly once
        creq.meter_sink = meter_box.update
        creq.prefix_hashes = self._prefix_hashes_for(creq.prompt)
        entry = self.flight.begin(
            rid, model=self.model_name, prompt_tokens=len(tokens),
            max_tokens=creq.max_tokens, stream=True)
        creq.trace = RequestTrace(entry=entry, tracer=self.tracer,
                                  span=None, loop=self.engine.stats.loop)
        rm = RequestMetrics(
            metrics=self.metrics,
            operation="chat" if chat else "text_completion",
            provider="tpuserve", request_model=self.model_name,
            response_model=self.model_name)
        try:
            self.engine.submit(creq)
        except EngineOverloadedError as e:
            return web.Response(
                status=429,
                body=oai.error_body(str(e), type_="rate_limit_error"),
                headers={"retry-after": "1"},
                content_type="application/json")
        except ValueError as e:
            return web.Response(status=400, body=oai.error_body(str(e)),
                                content_type="application/json")
        # the continuation itself is exportable again (chained moves)
        self._live[rid] = (creq, meta)

        resp = web.StreamResponse(
            status=200,
            headers={"content-type": "text/event-stream",
                     "cache-control": "no-cache",
                     "x-aigw-request-id": rid})
        set_tcp_nodelay(request.transport)
        await resp.prepare(request)
        decoder = StreamingDecoder(self.tokenizer)
        # prime the detokenizer with the generated-so-far tail: UTF-8
        # characters and stop strings spanning the migration seam
        # resolve exactly as they would have on the exporting replica
        emitted = ""
        for t in tokens[orig_len:]:
            emitted += decoder.push(t)

        async def write_piece(piece: str) -> None:
            if not piece:
                return
            if chat:
                await resp.write(oai.stream_chunk_sse(
                    response_id=rid, model=self.model_name,
                    created=created, delta={"content": piece}))
            else:
                await resp.write(SSEEvent(data=json.dumps({
                    "id": rid, "object": "text_completion",
                    "created": created, "model": self.model_name,
                    "choices": [{"index": 0, "text": piece,
                                 "finish_reason": None}],
                })).encode())

        n_out = 0
        finish = "stop"
        try:
            done = False
            while not done:
                first = await out_q.get()
                burst = [first]
                while True:
                    try:
                        burst.append(out_q.get_nowait())
                    except asyncio.QueueEmpty:
                        break
                pieces: list[str] = []
                for tok, fin in burst:
                    if tok >= 0:
                        n_out += 1
                        rm.record_tokens_emitted(1)
                        piece = decoder.push(tok)
                        if piece:
                            emitted += piece
                            hit = _find_stop(emitted, stop_strs)
                            if hit is not None:
                                keep = hit - (len(emitted) - len(piece))
                                pieces.append(piece[:max(keep, 0)])
                                finish = "stop"
                                creq.cancelled.set()
                                done = True
                                break
                            pieces.append(piece)
                    if fin is not None:
                        finish = fin
                        if fin not in ("error", "migrated"):
                            pieces.append(decoder.flush())
                        done = True
                        break
                await write_piece("".join(pieces))
        except (asyncio.CancelledError, ConnectionResetError):
            creq.cancelled.set()
            self._end_trace(creq.trace, "cancelled", n_out, orig_len)
            raise
        usage = self._usage_from_meter(orig_len, n_prev + n_out,
                                       meter_box)
        rm.finish(usage)
        self._end_trace(creq.trace, finish, n_out, orig_len)
        if finish == "migrated":
            await resp.write_eof()  # moved again: next replica finishes
            return resp
        await resp.write(self._final_stream_frame(
            chat, rid, created, finish,
            usage if include_usage else None))
        await resp.write(SSEEvent(data="[DONE]").encode())
        await resp.write_eof()
        return resp

    # -- debug surface (flight recorder + profiler) -----------------------
    async def _debug_requests(self, _request: web.Request) -> web.Response:
        """Recent + slow request timelines from the flight recorder —
        answerable on any replica with no tracing backend attached."""
        return web.json_response(self.flight.snapshot())

    async def _debug_request(self, request: web.Request) -> web.Response:
        entry = self.flight.get(request.match_info["rid"])
        if entry is None:
            return web.Response(
                status=404,
                body=oai.error_body("unknown request id"),
                content_type="application/json")
        return web.json_response(entry.detail())

    async def _debug_programs(self, _request: web.Request) -> web.Response:
        """What every program of this process cost to get: the load
        ledger's table by program name, its log of load events (the
        newest 256: stage times, cache hit, late, loop phase) and the
        totals ``/state`` carries (obs/xla_events.py)."""
        return web.json_response(xla_events.LEDGER.snapshot())

    #: hard cap on one /debug/profile capture window
    _PROFILE_MAX_SECONDS = 30.0

    async def _debug_profile(self, request: web.Request) -> web.Response:
        """On-demand ``jax.profiler`` capture: trace device+host activity
        for ?seconds=N into a fresh directory and return its path.
        Opt-in (``--enable-profile-endpoint``): a profiler on the data
        port is an inspection/DoS surface, so it 404s when disabled."""
        if not self._enable_profile:
            return web.Response(
                status=404,
                body=oai.error_body(
                    "profiling endpoint disabled (start tpuserve with "
                    "--enable-profile-endpoint)"),
                content_type="application/json")
        try:
            seconds = float(request.query.get("seconds", "2"))
        except ValueError:
            return web.Response(
                status=400, body=oai.error_body("seconds must be a number"),
                content_type="application/json")
        seconds = min(max(seconds, 0.1), self._PROFILE_MAX_SECONDS)
        if self._profile_lock.locked():
            return web.Response(
                status=409,
                body=oai.error_body("a profile capture is already running"),
                content_type="application/json")
        async with self._profile_lock:
            out_dir = tempfile.mkdtemp(prefix="tpuserve-profile-")
            loop = self.engine.stats.loop
            captured: dict = {}
            # the device planes and the host's TraceMe events (the
            # ledger's engine/<phase> spans) are all a reader of this
            # trace uses: no interpreter frames, no HLO protos beside
            # them. (Neither is what makes stop_trace slow on a TPU —
            # PERF.md section 5 — but both are dead weight.)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            options.enable_hlo_proto = False

            def capture() -> None:
                jax.profiler.start_trace(out_dir, profiler_options=options)
                try:
                    # the flag goes up once the trace runs and down
                    # before it stops, so every span and every counted
                    # window or prefill call lies inside it
                    loop.capture_begin()
                    time.sleep(seconds)
                    captured.update(loop.capture_end())
                finally:
                    t_stop = time.monotonic()
                    jax.profiler.stop_trace()
                    captured["write_out_s"] = round(
                        time.monotonic() - t_stop, 3)

            try:
                await asyncio.to_thread(capture)
            except Exception as e:  # noqa: BLE001 — profiler quirks must
                # surface as a client error, not a crashed replica
                return web.Response(
                    status=500,
                    body=oai.error_body(f"profiler capture failed: {e}",
                                        type_="server_error"),
                    content_type="application/json")
        logger.info("profile capture: %.1fs traced, %.1fs to write out",
                    seconds, captured.get("write_out_s", -1.0))
        return web.json_response(
            {"profile_dir": out_dir, "seconds": seconds, **captured})


async def run_tpuserve(
    model: str,
    host: str = "127.0.0.1",
    port: int = 8011,
    max_batch_size: int = 8,
    max_seq_len: int = 2048,
    page_size: int = 128,
    hbm_pages: int = 0,
    tp: int = 1,
    ep: int = 1,
    sp: int = 1,
    quantize: str = "",
    weights: str = "",
    lora_adapters: dict | None = None,
    lora_slots: int = 0,
    tenant_slot_cap: int = 0,
    decode_steps_per_tick: int = 8,
    enable_prefix_cache: bool = True,
    sp_prefill_min_tokens: int = 1024,
    prefill_chunk_tokens: int = 256,
    spec_tokens: int = 0,
    spec_adaptive: bool = True,
    attention_backend: str = "xla-bucketed",
    kv_cache_dtype: str = "bfloat16",
    ragged_chunk_tokens: int = 256,
    logprobs_topk: int = 0,
    adaptive_decode_window: bool = True,
    warm_prefill_buckets: int = 0,
    warm_decode_buckets: int = 0,
    prefill_bucket_rungs: int = 2,
    flight_entries: int = 256,
    enable_profile_endpoint: bool = False,
    migration_young_tokens: int = 64,
    constrained_decoding: bool = True,
    kv_host_bytes: int = 0,
) -> web.AppRunner:
    server = TPUServeServer(
        model,
        EngineConfig(
            max_batch_size=max_batch_size,
            max_seq_len=max_seq_len,
            page_size=page_size,
            num_pages=hbm_pages,
            decode_steps_per_tick=decode_steps_per_tick,
            enable_prefix_cache=enable_prefix_cache,
            sp_prefill_min_tokens=sp_prefill_min_tokens,
            prefill_chunk_tokens=prefill_chunk_tokens,
            spec_tokens=spec_tokens,
            spec_adaptive=spec_adaptive,
            attention_backend=attention_backend,
            kv_cache_dtype=kv_cache_dtype,
            ragged_chunk_tokens=ragged_chunk_tokens,
            logprobs_topk=logprobs_topk,
            adaptive_decode_window=adaptive_decode_window,
            warm_prefill_buckets=warm_prefill_buckets,
            warm_decode_buckets=warm_decode_buckets,
            prefill_bucket_rungs=prefill_bucket_rungs,
            tenant_slot_cap=tenant_slot_cap,
            migration_young_tokens=migration_young_tokens,
            constrained_decoding=constrained_decoding,
            kv_host_bytes=kv_host_bytes,
        ),
        tp=tp,
        ep=ep,
        sp=sp,
        quantize=quantize,
        weights=weights,
        lora_adapters=lora_adapters,
        lora_slots=lora_slots,
        flight_entries=flight_entries,
        enable_profile_endpoint=enable_profile_endpoint,
    )
    runner, port = await listen(server, host, port)
    logger.info("tpuserve listening on %s:%d (model=%s)", host, port, model)
    return runner


async def listen(server: TPUServeServer, host: str, port: int
                 ) -> tuple[web.AppRunner, int]:
    """Warm the server up (the app's start-up hook), bind it and call it
    ready; returns the runner and the port bound (``port`` 0 asks for a
    free one). What both entry points end their boot with."""
    runner = web.AppRunner(server.app)
    await runner.setup()
    site = web.TCPSite(runner, host, port)
    await site.start()
    server.mark_ready()
    return runner, site._server.sockets[0].getsockname()[1]
