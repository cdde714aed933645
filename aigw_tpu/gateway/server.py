"""The native gateway data-plane server.

Replaces the reference's Envoy + ext_proc pair (internal/extproc/server.go,
processor_impl.go) with one native server that keeps the reference's
deepest design insight — the **two-phase processor**:

  Phase 1 (route selection): parse the body only enough to extract the
  model, stamp the model header, match a route. The original parsed body is
  captured. (≈ routerProcessor.ProcessRequestBody, processor_impl.go:213)

  Phase 2 (upstream, per attempt): against the finally-chosen backend,
  translate the captured body to the backend schema, apply header/body
  mutations, inject credentials, send. A retry/fallover constructs a fresh
  translator and re-translates from the captured body — which is what makes
  fallback *across schemas* work (processor_impl.go:73-131,334-339).

Streaming responses flow through the translator chunk-by-chunk with token
usage mined mid-stream; cost metadata is produced at end-of-stream and fed
to the quota/rate-limit engine (≈ Envoy dynamic metadata consumed by the
rate-limit filter, filterconfig.go:84-87).
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import time
from typing import Any, Callable

import aiohttp
from aiohttp import web

from aigw_tpu.config.model import (
    Config,
    DESTINATION_ENDPOINT_HEADER,
    MODEL_NAME_HEADER,
    ORIGINAL_PATH_HEADER,
    APISchemaName,
)
from aigw_tpu.config.runtime import RuntimeBackend, RuntimeConfig
from aigw_tpu.gateway.auth import AuthError
from aigw_tpu.gateway.circuit import CircuitBreaker
from aigw_tpu.gateway.controller import (
    ControllerConfig,
    FleetController,
    build_launcher,
)
from aigw_tpu.gateway.costs import TokenUsage
from aigw_tpu.gateway.fleetstate import (
    DecisionRing,
    merge_rollups,
    relabel_exposition,
)
from aigw_tpu.gateway.mutators import apply_body_mutation, apply_header_mutation
from aigw_tpu.gateway.picker import (
    ADAPTER_HEADER,
    AFFINITY_HEADER,
    KV_CHAIN_HEADER,
    KV_PEERS_HEADER,
    PREFIX_HEADER,
    PRIORITY_HEADER,
    PROMPT_TOKENS_HEADER,
    TENANT_HEADER,
    ContextLengthError,
    Endpoint as PickerEndpoint,
    EndpointPicker,
    SLOShedError,
)
from aigw_tpu.gateway.router import (
    BackendSelector,
    NoRouteError,
    match_route,
    split_model,
)
from aigw_tpu.gateway.usage import UsageLedger
from aigw_tpu.obs.metrics import (
    GenAIMetrics,
    RequestMetrics,
    render_controller_gauges,
    render_fleet_gauges,
    render_usage_gauges,
)
from aigw_tpu.obs.tracing import (
    DEFAULT_HEADER_ATTRIBUTES,
    Tracer,
    genai_attributes,
    header_attributes,
    parse_header_attribute_mapping,
)
from aigw_tpu.schemas import anthropic as anth
from aigw_tpu.schemas import openai as oai
from aigw_tpu.schemas import typed as typed_schemas
from aigw_tpu.schemas import typed_response
from aigw_tpu.translate import Endpoint, TranslationError, get_translator
from aigw_tpu.utils import native

logger = logging.getLogger(__name__)

#: endpoint path → (Endpoint, front schema, metrics operation)
_ENDPOINTS: dict[str, tuple[Endpoint, APISchemaName, str]] = {
    Endpoint.CHAT_COMPLETIONS.value: (
        Endpoint.CHAT_COMPLETIONS, APISchemaName.OPENAI, "chat"),
    Endpoint.COMPLETIONS.value: (
        Endpoint.COMPLETIONS, APISchemaName.OPENAI, "text_completion"),
    Endpoint.EMBEDDINGS.value: (
        Endpoint.EMBEDDINGS, APISchemaName.OPENAI, "embeddings"),
    Endpoint.MESSAGES.value: (
        Endpoint.MESSAGES, APISchemaName.ANTHROPIC, "chat"),
    Endpoint.TOKENIZE.value: (
        Endpoint.TOKENIZE, APISchemaName.OPENAI, "tokenize"),
    Endpoint.RESPONSES.value: (
        Endpoint.RESPONSES, APISchemaName.OPENAI, "responses"),
    Endpoint.IMAGES_GENERATIONS.value: (
        Endpoint.IMAGES_GENERATIONS, APISchemaName.OPENAI, "image_generation"),
    Endpoint.RERANK.value: (
        Endpoint.RERANK, APISchemaName.COHERE, "rerank"),
    Endpoint.AUDIO_SPEECH.value: (
        Endpoint.AUDIO_SPEECH, APISchemaName.OPENAI, "audio_speech"),
    Endpoint.AUDIO_TRANSCRIPTIONS.value: (
        Endpoint.AUDIO_TRANSCRIPTIONS, APISchemaName.OPENAI,
        "audio_transcription"),
    Endpoint.AUDIO_TRANSLATIONS.value: (
        Endpoint.AUDIO_TRANSLATIONS, APISchemaName.OPENAI,
        "audio_translation"),
}

#: endpoints whose request body is multipart/form-data, not JSON — these
#: pass through untranslated (model extracted from the form part; the
#: reference's ParseMultipartBody, endpointspec.go)
_MULTIPART_ENDPOINTS = {
    Endpoint.AUDIO_TRANSCRIPTIONS,
    Endpoint.AUDIO_TRANSLATIONS,
}


def _conversation_affinity_key(body: dict) -> str:
    """Key a conversation by its STABLE head — the system prompt(s) plus
    the first user message. Unlike the growing message prefix, the head is
    identical on every turn of one chat, so the picker can pin the
    conversation to the replica whose prefix cache holds it; distinct
    conversations differ in their first user message."""
    import hashlib as _hashlib
    import json as _json

    messages = body.get("messages")
    if not isinstance(messages, list) or not messages:
        return ""
    head: list = []
    first_user = None
    for m in messages:
        if not isinstance(m, dict):
            return ""
        role = m.get("role")
        if role in ("system", "developer"):
            head.append(m)
        elif role == "user":
            first_user = m
            break
        else:
            break
    if first_user is None:
        return ""
    head.append(first_user)
    blob = _json.dumps(head, sort_keys=True).encode()
    return _hashlib.blake2b(blob, digest_size=12).hexdigest()


def _prefix_hash_key(body: dict) -> str:
    """Key the request's SHARED prompt prefix — the system/developer
    messages only. Unlike the conversation key (which includes the first
    user message and so is unique per chat), every request templated
    from the same system prompt shares this hash, so the picker can
    steer them toward the replica whose KV prefix cache already holds
    those pages (soft cache-affinity routing, gateway/picker.py)."""
    import hashlib as _hashlib
    import json as _json

    messages = body.get("messages")
    if not isinstance(messages, list) or not messages:
        return ""
    head: list = []
    for m in messages:
        if not isinstance(m, dict):
            return ""
        if m.get("role") in ("system", "developer"):
            head.append(m)
        else:
            break
    if not head:
        return ""
    blob = _json.dumps(head, sort_keys=True).encode()
    return _hashlib.blake2b(blob, digest_size=12).hexdigest()


def _prompt_token_estimate(body: dict) -> int:
    """Conservative prompt-token estimate for the picker's
    context-length filter and prompt-priced TTFT model (long-context
    satellite). An explicit x-aigw-prompt-tokens header wins upstream
    of this; the estimate only needs the right order of magnitude:
    bytes/4 approximates BPE tokens and UNDER-estimates byte-level
    tokenizers, so a borderline prompt never draws a spurious gateway
    400 — it routes, and the replica's own over-length check still
    guards, exactly as before this filter existed."""
    n = 0
    prompt = body.get("prompt")
    if isinstance(prompt, str):
        n += len(prompt.encode("utf-8", errors="ignore"))
    messages = body.get("messages")
    if isinstance(messages, list):
        for m in messages:
            if not isinstance(m, dict):
                continue
            c = m.get("content")
            if isinstance(c, str):
                n += len(c.encode("utf-8", errors="ignore"))
            elif isinstance(c, list):
                for part in c:
                    if (isinstance(part, dict)
                            and isinstance(part.get("text"), str)):
                        n += len(part["text"].encode(
                            "utf-8", errors="ignore"))
    return n // 4


def _multipart_model(raw: bytes, content_type: str) -> str:
    """Extract the `model` form field from a multipart body without
    touching the (possibly large) audio parts. Boundary parsing is
    shared with the rewrite path (translate/multipart.py) so the
    extract and rewrite sides can never disagree on the framing."""
    from aigw_tpu.translate.multipart import parse_multipart_boundary

    b = parse_multipart_boundary(content_type)
    if not b:
        return ""
    boundary = b"--" + b.encode()
    for part in raw.split(boundary):
        header_end = part.find(b"\r\n\r\n")
        if header_end < 0:
            continue
        headers = part[:header_end]
        if b'name="model"' in headers:
            return (
                part[header_end + 4 :]
                .rstrip(b"\r\n-")
                .decode("utf-8", errors="replace")
                .strip()
            )
    return ""

#: upstream statuses that trigger failover to the next backend
_RETRIABLE_STATUS = {429, 500, 502, 503, 504}

CostSink = Callable[[dict[str, int], dict[str, str]], Any]


class _RawBody:
    """Non-JSON (multipart) request carried through phase 2 untranslated."""

    def __init__(self, raw: bytes, content_type: str, model: str):
        self.raw = raw
        self.content_type = content_type
        self.model = model


class GatewayServer:
    """aiohttp application hosting the full data plane."""

    def __init__(
        self,
        runtime: RuntimeConfig,
        *,
        metrics: GenAIMetrics | None = None,
        cost_sink: CostSink | None = None,
        tracer: Tracer | None = None,
    ):
        self._runtime = runtime
        self.metrics = metrics or GenAIMetrics()
        self.tracer = tracer or Tracer()
        # request-header → span-attribute mapping (reference
        # requestheaderattrs; default agent-session-id:session.id)
        self._header_attrs = parse_header_attribute_mapping(
            os.environ.get("AIGW_HEADER_ATTRIBUTES",
                           DEFAULT_HEADER_ATTRIBUTES)
        )
        self._cost_sink = cost_sink
        # OpenInference privacy knobs + structured access log (reference:
        # openinference/config.go env vars; Envoy access-log enrichment)
        from aigw_tpu.obs.accesslog import AccessLogger
        from aigw_tpu.obs.openinference import TraceConfig as OITraceConfig

        self._oi_config = OITraceConfig.from_env()
        self.access_log = AccessLogger()
        # circuit breaker unified with the fleet health machine (ISSUE
        # 14): keyed by backend name for logical backends AND by
        # replica address for picked endpoints; every open/close lands
        # in the replica's fleet event ring, and the picker's merged
        # routability view consults is_open — one failure-evidence
        # surface, not two that can disagree
        self.circuit = CircuitBreaker(
            on_transition=self._on_circuit_transition)
        #: optional () -> {key: condition} of NOT-Accepted objects, wired
        #: by the CLI when the config source is a reconciled manifest dir
        self.conditions_fn = None
        self._session: aiohttp.ClientSession | None = None
        self.app = web.Application(client_max_size=64 * 1024 * 1024)
        for path in _ENDPOINTS:
            self.app.router.add_post(path, self._handle)
        self.app.router.add_get("/v1/models", self._handle_models)
        self.app.router.add_get("/health", self._handle_health)
        self.app.router.add_get("/metrics", self._handle_metrics)
        # engine-truth usage metering (ISSUE 20): the per-tenant token
        # & KV-residency cost ledger + its query/export API
        self.app.router.add_get("/usage", self._handle_usage)
        self.usage_ledger = self._build_usage_ledger(runtime)
        # fleet observability plane (ISSUE 12): one pane of glass over
        # every picker-polled replica pool — aggregated health/SLO
        # state, Prometheus federation, and the routing-decision audit
        # ring (always on, like tpuserve's flight recorder: decisions
        # are the gateway's timelines and carry no credentials)
        self.app.router.add_get("/fleet/state", self._handle_fleet_state)
        self.app.router.add_get("/fleet/metrics",
                                self._handle_fleet_metrics)
        self.app.router.add_get("/debug/decisions",
                                self._handle_decisions)
        # offline batch tier (ISSUE 19): file upload + batch lifecycle
        # forwarded to a picker-chosen replica (batch priority — most
        # idle capacity); later polls follow the id → replica map so
        # submit/poll/fetch land on the replica that holds the state
        self.app.router.add_post("/v1/files", self._handle_file_upload)
        self.app.router.add_get("/v1/files/{fid}/content",
                                self._handle_batch_forward)
        self.app.router.add_post("/v1/batches",
                                 self._handle_batch_create)
        self.app.router.add_get("/v1/batches/{bid}",
                                self._handle_batch_forward)
        self.app.router.add_post("/v1/batches/{bid}/cancel",
                                 self._handle_batch_forward)
        self._batch_replica: dict[str, str] = {}
        self.decisions = DecisionRing(
            capacity=int(os.environ.get("AIGW_DECISION_RING", "512")))
        # debug/admin surface (reference: pprof :6060 + admin server on a
        # separate local port, internal/pprof/pprof.go:18-40). Off by
        # default on the data-plane port — any API client could otherwise
        # read thread stacks and config topology; opt in with
        # AIGW_ENABLE_DEBUG=true (e.g. when bound to localhost).
        if os.environ.get("AIGW_ENABLE_DEBUG", "").lower() == "true":
            self.app.router.add_get("/debug/config", self._handle_debug_config)
            self.app.router.add_get("/debug/stacks", self._handle_debug_stacks)
        self._pickers: dict[str, EndpointPicker] = {}
        self._picker_tasks: set[asyncio.Task] = set()
        # fleet control plane (ISSUE 14): one lifecycle manager per
        # backend pool that configures a `controller` block
        self._controllers: dict[str, FleetController] = {}
        self._build_pickers(runtime)
        self.app.on_startup.append(self._start_pickers)
        # MCP proxy is always registered (default path /mcp) so a config
        # hot-reload can add/change backends, filters, and authz without a
        # restart — only the HTTP *path* is fixed once the router freezes
        # (the reference hot-reloads MCPConfig through the same filterapi
        # bundle watcher as routes).
        from aigw_tpu.mcp import MCPConfig, MCPProxy
        from aigw_tpu.obs.metrics import MCPMetrics

        self.mcp = MCPProxy(
            MCPConfig.parse(runtime.config.mcp or {}),
            metrics=MCPMetrics(self.metrics.registry),
        )
        self.mcp.register(self.app)
        self.app.on_cleanup.append(self._cleanup)

    # -- lifecycle --------------------------------------------------------
    @property
    def runtime(self) -> RuntimeConfig:
        return self._runtime

    @staticmethod
    def _build_usage_ledger(runtime: RuntimeConfig) -> UsageLedger | None:
        """The metering ledger from the config's ``usage`` block.
        Metering is ON by default (no block = in-memory ledger with
        defaults); ``usage: {enabled: false}`` is the A/B off leg."""
        from aigw_tpu.config.model import _thaw

        raw = _thaw(runtime.config.usage) or {}
        if not isinstance(raw, dict):
            raw = {}
        if not raw.get("enabled", True):
            return None
        journal = str(raw.get("journal", "") or "")
        budgets = raw.get("budgets") or {}
        kwargs = dict(
            window_s=float(raw.get("window_s", 60.0)),
            retain_windows=int(raw.get("retain_windows", 64)),
            budgets={str(k): float(v) for k, v in budgets.items()},
            burn_windows=int(raw.get("burn_windows", 3)),
        )
        if journal:
            # crash-safe resume: replay what survived, keep appending
            return UsageLedger.replay(journal, **kwargs)
        return UsageLedger(**kwargs)

    def set_runtime(self, rc: RuntimeConfig) -> None:
        """Hot-swap config (called by ConfigWatcher). Pickers whose
        endpoint pools are unchanged are reused so telemetry and session
        affinity survive reloads."""
        if rc.config.usage != self._runtime.config.usage:
            # metering knobs changed: rebuild (a journal-backed ledger
            # replays itself, so totals survive the swap)
            old_ledger = self.usage_ledger
            self.usage_ledger = self._build_usage_ledger(rc)
            if old_ledger is not None:
                old_ledger.close()
        self._runtime = rc
        from aigw_tpu.mcp import MCPConfig

        self.mcp.update_config(MCPConfig.parse(rc.config.mcp or {}))
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        old = self._pickers
        old_ctl = self._controllers
        self._build_pickers(rc)
        if loop is not None:
            for name, ctl in old_ctl.items():
                if self._controllers.get(name) is not ctl:
                    self._spawn(loop, ctl.stop())
            for name, picker in old.items():
                if self._pickers.get(name) is not picker:
                    self._spawn(loop, picker.stop())
            for name, picker in self._pickers.items():
                if old.get(name) is not picker:
                    self._spawn(loop, picker.start())
            for name, ctl in self._controllers.items():
                if old_ctl.get(name) is not ctl:
                    self._spawn(loop, ctl.start())

    def _spawn(self, loop: asyncio.AbstractEventLoop, coro) -> None:
        # the loop holds tasks weakly; retain refs until completion
        task = loop.create_task(coro)
        self._picker_tasks.add(task)
        task.add_done_callback(self._picker_tasks.discard)

    def _build_pickers(self, rc: RuntimeConfig) -> None:
        from aigw_tpu.config.model import _thaw

        pickers: dict[str, EndpointPicker] = {}
        for name, rb in rc.backends.items():
            b = rb.backend
            if not b.endpoints:
                continue
            prev = self._pickers.get(name)
            key = (b.endpoints, b.picker_poll_interval, b.picker_mode,
                   b.slo_ttft_ms, b.fleet_obs, b.slo_objective,
                   b.slo_window_s, b.slo_burn_windows)
            if prev is not None and getattr(prev, "_config_key", None) == key:
                pickers[name] = prev  # unchanged pool: keep state
                continue
            picker = EndpointPicker(
                [PickerEndpoint.parse(_thaw(e)) for e in b.endpoints],
                poll_interval=b.picker_poll_interval,
                mode=b.picker_mode,
                slo_ttft_ms=b.slo_ttft_ms,
                fleet_obs=b.fleet_obs,
                slo_objective=b.slo_objective,
                slo_window_s=b.slo_window_s,
                slo_burn_windows=b.slo_burn_windows,
            )
            picker._config_key = key  # type: ignore[attr-defined]
            pickers[name] = picker
        self._pickers = pickers
        # the merged routability view: the picker consults the SAME
        # breaker the attempt loop feeds, keyed by replica address
        for picker in self._pickers.values():
            picker.breaker = self.circuit
        self._build_controllers(rc)

    def _build_controllers(self, rc: RuntimeConfig) -> None:
        from aigw_tpu.config.model import _thaw

        controllers: dict[str, FleetController] = {}
        for name, rb in rc.backends.items():
            raw = rb.backend.controller
            picker = self._pickers.get(name)
            if raw is None or picker is None:
                continue
            cfg = ControllerConfig.parse(_thaw(raw))
            if not cfg.enabled:
                continue
            prev = self._controllers.get(name)
            if (prev is not None and prev.picker is picker
                    and getattr(prev, "_config_raw", None) == raw):
                controllers[name] = prev  # unchanged: keep its state
                continue
            ctl = FleetController(
                picker=picker, cfg=cfg,
                launcher=build_launcher(cfg.launcher),
                decisions=self.decisions, backend=name)
            ctl._config_raw = raw  # type: ignore[attr-defined]
            controllers[name] = ctl
        self._controllers = controllers

    async def _start_pickers(self, _app) -> None:
        for picker in self._pickers.values():
            await picker.start()
        for ctl in self._controllers.values():
            await ctl.start()

    def _on_circuit_transition(self, key: str, opened: bool,
                               failures: int) -> None:
        """Breaker open/close → the fleet event ring of whichever pool
        knows this key as a replica address (ISSUE 14 unification).
        Backend-name keys have no replica entry and are skipped."""
        for picker in self._pickers.values():
            if key in picker.state:
                picker.fleet.mark_breaker(key, opened, failures)

    async def _get_session(self) -> aiohttp.ClientSession:
        if self._session is None or self._session.closed:
            self._session = aiohttp.ClientSession(
                auto_decompress=True,
                timeout=aiohttp.ClientTimeout(total=None),
            )
        return self._session

    async def _cleanup(self, _app: web.Application) -> None:
        for ctl in self._controllers.values():
            # stops the control loop AND terminates launcher-owned
            # replica processes — shutdown must not orphan children
            await ctl.stop()
        for picker in self._pickers.values():
            await picker.stop()
        if self._session is not None and not self._session.closed:
            await self._session.close()
        if self.usage_ledger is not None:
            self.usage_ledger.close()

    # -- admin endpoints --------------------------------------------------
    async def _handle_health(self, _request: web.Request) -> web.Response:
        payload = {
            "status": "ok",
            "uuid": self._runtime.config.uuid,
            "circuit": self.circuit.snapshot(),
            # which SSE / event-stream scanner this process runs: the
            # C++ one when native/libaigw_native.so was built (`make -C
            # native`), else the pure-Python twin — visible, because the
            # library is a build product and two checkouts can differ
            "native_scanner": ("loaded" if native.available()
                               else "python"),
        }
        # reconciling control plane: surface quarantined objects so an
        # operator doesn't have to know to cat aigw-status.json (the
        # reference shows the same conditions via `kubectl get`)
        if self.conditions_fn is not None:
            bad = self.conditions_fn()
            payload["objects_not_accepted"] = len(bad)
            if bad:
                payload["not_accepted"] = sorted(bad)
        return web.json_response(payload)

    async def _handle_metrics(self, _request: web.Request) -> web.Response:
        body = self.metrics.export()
        if self.usage_ledger is not None:
            body += render_usage_gauges(self.usage_ledger.snapshot())
        return web.Response(body=body, content_type="text/plain")

    async def _handle_usage(self, request: web.Request) -> web.Response:
        """``GET /usage`` (ISSUE 20): the metering ledger's windowed
        per-tenant/per-model view. Query params: ``since`` (unix ts),
        ``tenant``, ``model`` filter the windows; ``export=jsonl``
        streams the filtered windows as JSON lines instead (the bulk
        export a billing pipeline ingests)."""
        if self.usage_ledger is None:
            return web.json_response(
                {"error": "usage metering disabled"}, status=404)
        try:
            since = float(request.query.get("since", "0") or 0.0)
        except ValueError:
            since = 0.0
        payload = self.usage_ledger.query(
            since=since,
            tenant=request.query.get("tenant", ""),
            model=request.query.get("model", ""),
        )
        if request.query.get("export", "") == "jsonl":
            body = "".join(json.dumps(w, sort_keys=True) + "\n"
                           for w in payload["windows"])
            return web.Response(body=body.encode(),
                                content_type="application/jsonl")
        return web.json_response(payload)

    # -- offline batch tier (ISSUE 19) ------------------------------------
    #: bound on the (file/batch id → replica) routing map
    _BATCH_MAP_MAX = 10_000

    def _batch_pick(self) -> str | None:
        """A replica for NEW batch state: the first configured pool's
        batch-priority pick — most idle capacity, never SLO-shed (the
        picker's batch branch skips admission control entirely)."""
        for _name, picker in sorted(self._pickers.items()):
            dest = picker.pick({PRIORITY_HEADER: "batch"})
            if dest:
                return dest
        return None

    def _remember_batch(self, obj_id: str, addr: str) -> None:
        self._batch_replica[obj_id] = addr
        while len(self._batch_replica) > self._BATCH_MAP_MAX:
            self._batch_replica.pop(next(iter(self._batch_replica)))

    async def _proxy_batch(self, request: web.Request, addr: str,
                           raw: bytes | None = None
                           ) -> tuple[int, bytes, str]:
        """Forward one batch-surface request to its replica verbatim;
        (status, body, content_type) — upstream failures map to 502."""
        session = await self._get_session()
        if raw is None:
            raw = await request.read()
        try:
            async with session.request(
                    request.method, f"http://{addr}{request.path}",
                    data=raw,
                    headers={"content-type": request.headers.get(
                        "content-type", "application/json")},
                    timeout=aiohttp.ClientTimeout(total=60.0)) as resp:
                return (resp.status, await resp.read(),
                        resp.content_type or "application/json")
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            return (502,
                    error_body(f"batch replica {addr} unreachable: {e}",
                               type_="server_error"),
                    "application/json")

    async def _handle_file_upload(self, request: web.Request
                                  ) -> web.Response:
        dest = self._batch_pick()
        if dest is None:
            return web.Response(
                status=503,
                body=error_body("no replica available for batch work",
                                type_="server_error"),
                content_type="application/json")
        status, body, ctype = await self._proxy_batch(request, dest)
        if status == 200:
            try:
                fid = str(json.loads(body).get("id", ""))
            except ValueError:
                fid = ""
            if fid:
                self._remember_batch(fid, dest)
        return web.Response(status=status, body=body,
                            content_type=ctype)

    async def _handle_batch_create(self, request: web.Request
                                   ) -> web.Response:
        """POST /v1/batches — lands on the replica already holding the
        input file (the id → replica map); a miss falls back to a fresh
        batch pick, where the unknown file id 404s honestly."""
        raw = await request.read()
        try:
            fid = str(json.loads(raw).get("input_file_id", ""))
        except ValueError:
            fid = ""
        dest = self._batch_replica.get(fid) or self._batch_pick()
        if dest is None:
            return web.Response(
                status=503,
                body=error_body("no replica available for batch work",
                                type_="server_error"),
                content_type="application/json")
        status, body, ctype = await self._proxy_batch(request, dest,
                                                      raw=raw)
        if status == 200:
            try:
                bid = str(json.loads(body).get("id", ""))
            except ValueError:
                bid = ""
            if bid:
                self._remember_batch(bid, dest)
        return web.Response(status=status, body=body,
                            content_type=ctype)

    async def _handle_batch_forward(self, request: web.Request
                                    ) -> web.Response:
        """Poll / cancel / output fetch — follows the id → replica
        map (batch state is replica-local by design)."""
        oid = (request.match_info.get("bid")
               or request.match_info.get("fid") or "")
        dest = self._batch_replica.get(oid)
        if dest is None:
            return web.Response(
                status=404,
                body=error_body(f"unknown batch object {oid!r}"),
                content_type="application/json")
        status, body, ctype = await self._proxy_batch(request, dest)
        if status == 200 and request.match_info.get("bid"):
            # learn the output file id from poll bodies so the later
            # GET /v1/files/{ofid}/content resolves to the same replica
            try:
                ofid = str(json.loads(body).get("output_file_id")
                           or "")
            except ValueError:
                ofid = ""
            if ofid:
                self._remember_batch(ofid, dest)
        return web.Response(status=status, body=body,
                            content_type=ctype)

    # -- fleet observability plane (ISSUE 12) -----------------------------
    async def _handle_fleet_state(self, _request: web.Request
                                  ) -> web.Response:
        """Aggregated fleet snapshot: per-replica health machine state
        + event rings + staleness stamps + key gauges, per-backend
        rollups, and the live SLO burn-rate windows — one pane of glass
        over every picker-polled pool."""
        backends = {
            name: picker.fleet.snapshot(picker.state)
            for name, picker in self._pickers.items()
        }
        for name, ctl in self._controllers.items():
            if name in backends:
                # lifecycle manager state (ISSUE 14): scaling decisions,
                # drains in progress, and the bounded action ring
                backends[name]["controller"] = ctl.snapshot()
        return web.json_response({
            "ts": round(time.time(), 3),
            "backends": backends,
            "fleet": merge_rollups(
                [b["rollup"] for b in backends.values()]),
            "decisions_recorded": self.decisions.recorded,
        })

    async def _handle_fleet_metrics(self, _request: web.Request
                                    ) -> web.Response:
        """Prometheus federation: every replica's ``tpuserve_*``
        samples re-exported with a ``replica`` label (histograms,
        per-device gauges and exemplars included) plus the
        ``aigw_fleet_*`` rollup gauges — one scrape covers the fleet."""
        session = await self._get_session()
        chunks: list[bytes] = []
        seen: set = set()
        errors = 0

        async def scrape(addr: str) -> str | None:
            try:
                async with session.get(
                    f"http://{addr}/metrics",
                    timeout=aiohttp.ClientTimeout(total=2.0),
                ) as resp:
                    if resp.status != 200:
                        return None
                    return (await resp.read()).decode(
                        "utf-8", errors="replace")
            except (aiohttp.ClientError, asyncio.TimeoutError):
                return None

        for name, picker in self._pickers.items():
            addrs = [e.address for e in picker.endpoints
                     if picker.fleet.health_of(e.address) != "down"]
            texts = await asyncio.gather(*(scrape(a) for a in addrs))
            for addr, text in zip(addrs, texts):
                if text is None:
                    errors += 1
                    continue
                chunks.append(
                    relabel_exposition(text, addr, seen).encode())
            label = name if len(self._pickers) > 1 else ""
            chunks.append(render_fleet_gauges(
                picker.fleet.rollup(picker.state), backend=label))
            ctl = self._controllers.get(name)
            if ctl is not None:
                chunks.append(render_controller_gauges(
                    ctl.gauge_values(), backend=label))
        chunks.append(
            b"# TYPE aigw_fleet_scrape_errors gauge\n"
            b"aigw_fleet_scrape_errors %d\n" % errors)
        return web.Response(body=b"".join(chunks),
                            content_type="text/plain")

    async def _handle_decisions(self, request: web.Request
                                ) -> web.Response:
        """The routing-decision audit ring: every pick's full explain
        (candidates, scores, predicted-TTFT map, affinity terms), shed
        events with their Retry-After, and migration stamps — filter
        with ``?rid=<x-aigw-request-id>`` to join one decision against
        the serving replica's /debug/requests/{id} timeline."""
        rid = request.query.get("rid", "")
        try:
            limit = max(1, min(1000, int(
                request.query.get("limit", "100"))))
        except ValueError:
            limit = 100
        return web.json_response({
            "capacity": self.decisions.capacity,
            "recorded": self.decisions.recorded,
            "decisions": self.decisions.snapshot(rid=rid, limit=limit),
        })

    async def _handle_models(self, request: web.Request) -> web.Response:
        """/v1/models — configured models, host-scoped like the
        reference's ModelsByHost (models_processor.go:30-150): models whose
        serving routes are restricted to other hostnames are hidden.
        The listing also carries the model ZOO (ISSUE 7): every
        ``<base>:<adapter>`` name the picker-polled tpuserve replicas
        report on /state whose base model routes here — so clients
        discover servable adapters without per-adapter config entries."""
        rc = self._runtime
        host = request.host.split(":")[0].lower()
        visible_rules = [
            rule for route in rc.routes_for_host(host) for rule in route.rules
        ]

        def visible(name: str) -> bool:
            base = split_model(name)[0]
            for probe_name in ({name, base}):
                probe = {MODEL_NAME_HEADER: probe_name}
                if any(r.matches(probe) for r in visible_rules):
                    return True
            return False

        # structured-output / tool-calling capability flags (ISSUE 9):
        # replicas that enforce constraints natively report them on
        # /state; the merged listing carries them per served base model
        caps_by_model: dict[str, dict] = {}
        for picker in self._pickers.values():
            for st in picker.state.values():
                if st.healthy and st.model and st.capabilities:
                    caps_by_model[st.model] = dict(st.capabilities)

        def extra_for(name: str):
            caps = caps_by_model.get(split_model(name)[0])
            return {"capabilities": caps} if caps else None

        entries: list[tuple] = [
            (m.name, m.owned_by, m.created_at, extra_for(m.name))
            for m in rc.config.models
            if visible(m.name)
        ]
        seen = {e[0] for e in entries}
        for picker in self._pickers.values():
            for st in picker.state.values():
                if not (st.healthy and st.model):
                    continue
                for adapter in st.adapters_registered:
                    name = f"{st.model}:{adapter}"
                    if name not in seen and visible(name):
                        seen.add(name)
                        entries.append((name, "aigw-tpu-lora", 0,
                                        extra_for(name)))
        return web.json_response(oai.models_response(entries))

    async def _handle_debug_config(self, _request: web.Request) -> web.Response:
        """Redacted view of the live config (credentials masked)."""
        import json as _json

        from aigw_tpu.utils.redaction import SENSITIVE_HEADERS  # noqa: F401

        cfg = self._runtime.config.to_dict()
        for b in cfg.get("backends", ()):
            if "auth" in b:
                b["auth"] = {"kind": b["auth"].get("kind", "?"),
                             "credentials": "[REDACTED]"}
        if "mcp" in cfg and isinstance(cfg["mcp"], dict):
            cfg["mcp"] = dict(cfg["mcp"])
            cfg["mcp"].pop("session_seed", None)
            cfg["mcp"].pop("session_fallback_seed", None)
        return web.json_response(cfg)

    async def _handle_debug_stacks(self, _request: web.Request) -> web.Response:
        """Thread stack dump — the pprof-goroutine equivalent."""
        import sys as _sys
        import traceback as _tb

        out = []
        for tid, frame in _sys._current_frames().items():
            out.append(f"--- thread {tid} ---")
            out.extend(_tb.format_stack(frame))
        return web.Response(text="\n".join(out),
                            content_type="text/plain")

    def _log_rejection(
        self, request: web.Request, status: int, started: float,
        model: str = "", reason: str = "",
    ) -> None:
        """Access-log line for requests rejected before the attempt loop
        (schema 400s, unknown-model 404s) — the lines operators grep for
        when debugging client misconfiguration."""
        if not self.access_log.enabled:
            return
        from aigw_tpu.obs.openinference import error_type_for_status

        self.access_log.log(
            method=request.method,
            path=request.path,
            status=status,
            duration_ms=(time.monotonic() - started) * 1000.0,
            model=model,
            error_type=reason or error_type_for_status(status),
            client=request.remote or "",
            request_id=request.headers.get("x-request-id", ""),
        )

    # -- the data plane ---------------------------------------------------
    async def _handle(self, request: web.Request) -> web.StreamResponse:
        endpoint, front_schema, operation = _ENDPOINTS[request.path]
        rc = self._runtime  # pin the config for this request
        started = time.monotonic()
        error_body = (
            anth.error_body
            if front_schema is APISchemaName.ANTHROPIC
            else oai.error_body
        )
        try:
            raw = await request.read()
        except (aiohttp.web.RequestPayloadError,
                aiohttp.http_exceptions.HttpProcessingError) as e:
            # e.g. a corrupt gzip request body fails the server-side
            # inflater mid-read — that's the client's 400, not our 500
            self._log_rejection(request, 400, started,
                                reason="bad_request_body")
            return web.Response(
                status=400,
                body=error_body(f"unreadable request body: {e}"),
                content_type="application/json")
        # compressed request bodies (reference: extproc decodes encoded
        # bodies before translation, util.go decodeContentIfNeeded; the
        # inference-extension conformance drives gzipped JSON).
        # aiohttp's server layer transparently inflates supported
        # codings and 400s unsupported/corrupt ones at read time (the
        # try/except above); this fallback only fires when gzip bytes
        # reach us undecoded (magic 1f 8b — e.g. behind a raw
        # transport). The translated upstream body is re-serialized, so
        # the encoding is consumed and never forwarded.
        enc = request.headers.get("content-encoding", "").lower().strip()
        if enc == "gzip" and raw[:2] == b"\x1f\x8b":
            import gzip as _gzip
            import zlib as _zlib

            try:
                raw = _gzip.decompress(raw)
            except (OSError, EOFError, _zlib.error):
                self._log_rejection(request, 400, started,
                                    reason="bad_encoding")
                return web.Response(
                    status=400,
                    body=error_body("invalid gzip request body"),
                    content_type="application/json")
        elif enc and enc not in ("identity", "gzip", "deflate"):
            # aiohttp transparently inflates gzip/deflate (and br when
            # the Brotli package exists); any OTHER declared coding
            # reaches this handler UNDECODED on this aiohttp — parsing
            # those raw bytes as JSON would be a silent mis-read, so
            # it's the client's 400 (the inference-extension
            # conformance contract: undecodable encodings are 400s,
            # never 500s or accidental 200s)
            try:
                from aiohttp.compression_utils import HAS_BROTLI
            except ImportError:  # pragma: no cover — old aiohttp
                HAS_BROTLI = False
            if not (enc == "br" and HAS_BROTLI):
                self._log_rejection(request, 400, started,
                                    reason="bad_encoding")
                return web.Response(
                    status=400,
                    body=error_body(
                        f"unsupported content-encoding: {enc}"),
                    content_type="application/json")
        # ---- phase 1: route selection ----------------------------------
        if endpoint in _MULTIPART_ENDPOINTS:
            ctype = request.headers.get("content-type", "")
            model = _multipart_model(raw, ctype)
            if not model:
                self._log_rejection(request, 400, started,
                                    reason="missing_model")
                return web.Response(
                    status=400,
                    body=error_body("missing 'model' form field"),
                    content_type="application/json")
            body: Any = _RawBody(raw, ctype, model)
        else:
            try:
                body = oai.parse_json_body(raw)
                model = oai.request_model(body)
                if endpoint is Endpoint.MESSAGES:
                    anth.validate_messages_request(body)
                else:
                    # typed per-endpoint schemas incl. chat vendor fields
                    # (schemas/typed.py; reference apischema rejects
                    # malformed bodies before any upstream traffic)
                    typed_schemas.validate_request(endpoint.value, body)
            except oai.SchemaError as e:
                self._log_rejection(request, 400, started,
                                    reason="invalid_request")
                return web.Response(
                    status=400, body=error_body(str(e)),
                    content_type="application/json")
        client_headers = {k.lower(): v for k, v in request.headers.items()}
        # multi-tenant accounting key (ISSUE 7): an explicit tenant
        # header wins; adapter-suffixed zoo names ("llama-3-8b:tenant-a")
        # default to per-adapter tenancy. Injected into client_headers so
        # tenant-keyed quota rules (client_key_header: x-aigw-tenant),
        # the end-of-stream cost sink, and the upstream relay all key on
        # ONE consistent tenant.
        tenant = client_headers.get(TENANT_HEADER, "") or \
            split_model(model)[1]
        if tenant:
            client_headers[TENANT_HEADER] = tenant
        match_headers = {
            **client_headers,
            MODEL_NAME_HEADER: model,
            ORIGINAL_PATH_HEADER: request.path,
        }
        try:
            match = match_route(rc, request.host, match_headers)
        except NoRouteError:
            self._log_rejection(request, 404, started, model=model,
                                reason="model_not_found")
            return web.Response(
                status=404,
                body=error_body(
                    f"model {model!r} is not served by this gateway",
                    type_="model_not_found" if front_schema is APISchemaName.OPENAI
                    else "not_found_error",
                ),
                content_type="application/json",
            )

        req_metrics = RequestMetrics(
            metrics=self.metrics, operation=operation, request_model=model
        )
        selector = BackendSelector(rule=match.rule, circuit=self.circuit)
        route_name = match.route.name

        # tracing: continue the caller's trace, span per gateway request
        # (reference: router processor starts the span and injects headers,
        # processor_impl.go:289-295)
        span = None
        if self.tracer.enabled:
            # OTEL_PROPAGATORS-configured extraction (W3C + B3 variants)
            parent = self.tracer.propagators.extract(client_headers)
            span = self.tracer.start_span(f"{operation} {model}", parent)
            span.attributes.update(
                header_attributes(client_headers, self._header_attrs)
            )
            if isinstance(body, dict):
                span.attributes.update(
                    self._openinference_request_attrs(endpoint, body, raw)
                )

        # ---- phase 2: upstream attempts --------------------------------
        status = 500
        try:
            resp_out = await self._attempt_loop(
                request, endpoint, front_schema, selector, rc, body,
                req_metrics, route_name, error_body, client_headers, span,
            )
            status = resp_out.status
            return resp_out
        finally:
            if span is not None:
                span.attributes.update(
                    genai_attributes(
                        operation=operation,
                        request_model=model,
                        response_model=req_metrics.response_model,
                        backend=req_metrics.provider,
                        input_tokens=req_metrics.final_usage.input_tokens,
                        output_tokens=req_metrics.final_usage.output_tokens,
                        streaming=req_metrics.tokens_seen > 0,
                    )
                )
                if req_metrics.error_type:
                    span.record_error(req_metrics.error_type)
                span.end()
            if self.access_log.enabled:
                from aigw_tpu.obs.openinference import error_type_for_status

                err = req_metrics.error_type
                if err.isdigit():
                    err = error_type_for_status(int(err))
                self.access_log.log(
                    method=request.method,
                    path=request.path,
                    status=status,
                    duration_ms=(time.monotonic()
                                 - req_metrics.start) * 1000.0,
                    route=route_name,
                    backend=req_metrics.provider,
                    model=model,
                    response_model=req_metrics.response_model,
                    stream=req_metrics.tokens_seen > 0,
                    input_tokens=req_metrics.final_usage.input_tokens,
                    output_tokens=req_metrics.final_usage.output_tokens,
                    total_tokens=req_metrics.final_usage.total_tokens,
                    cached_tokens=(
                        req_metrics.final_usage.cached_input_tokens),
                    costs=req_metrics.costs,
                    error_type=err,
                    client=request.remote or "",
                    trace_id=(span.context.trace_id
                              if span is not None else ""),
                    span_id=(span.context.span_id
                             if span is not None else ""),
                    request_id=client_headers.get("x-request-id", ""),
                    upstream_request_id=req_metrics.upstream_request_id,
                    attempts=req_metrics.attempts,
                    decision=req_metrics.decision,
                )

    def _openinference_request_attrs(
        self, endpoint: Endpoint, body: dict[str, Any], raw: bytes
    ) -> dict[str, Any]:
        from aigw_tpu.obs import openinference as oi

        try:
            if endpoint is Endpoint.CHAT_COMPLETIONS:
                return oi.chat_request_attributes(
                    body, raw, self._oi_config)
            if endpoint is Endpoint.MESSAGES:
                return oi.chat_request_attributes(
                    body, raw, self._oi_config,
                    system=oi.LLM_SYSTEM_ANTHROPIC)
            if endpoint is Endpoint.EMBEDDINGS:
                return oi.embeddings_request_attributes(
                    body, raw, self._oi_config)
            if endpoint is Endpoint.COMPLETIONS:
                return oi.completion_request_attributes(
                    body, raw, self._oi_config)
            if endpoint is Endpoint.RERANK:
                return oi.rerank_request_attributes(
                    body, raw, self._oi_config)
        except Exception:  # noqa: BLE001 — telemetry must never 500
            logger.debug("openinference request attrs failed",
                         exc_info=True)
        return {}

    def _oi_response_builder(self, endpoint: Endpoint):
        """One endpoint→builder dispatch for both the unary and
        streaming span-attribute paths (endpoint MESSAGES ⇔ the
        Anthropic front)."""
        from aigw_tpu.obs import openinference as oi

        return {
            Endpoint.CHAT_COMPLETIONS: oi.chat_response_attributes,
            Endpoint.MESSAGES: oi.anthropic_response_attributes,
            Endpoint.EMBEDDINGS: oi.embeddings_response_attributes,
            Endpoint.COMPLETIONS: oi.completion_response_attributes,
            Endpoint.RERANK: oi.rerank_response_attributes,
        }.get(endpoint)

    def _openinference_response_attrs(
        self, span, endpoint: Endpoint, payload: bytes,
    ) -> None:
        builder = self._oi_response_builder(endpoint)
        if builder is None:
            return
        try:
            resp = json.loads(payload)
            if not isinstance(resp, dict):
                return
            span.attributes.update(builder(resp, self._oi_config))
        except Exception:  # noqa: BLE001 — telemetry must never 500
            logger.debug("openinference response attrs failed",
                         exc_info=True)

    async def _attempt_loop(
        self, request, endpoint, front_schema, selector, rc, body,
        req_metrics, route_name, error_body, client_headers, span,
    ) -> web.StreamResponse:
        last_error: tuple[int, bytes] = (
            502,
            error_body("all upstream backends failed",
                       type_="upstream_error"),
        )
        attempt = 0
        while True:
            ref = selector.next_backend()
            if ref is None:
                break
            rb = rc.backends[ref.backend]
            if attempt > 0:
                self.metrics.retries_total.labels(route_name, rb.backend.name).inc()
            attempt += 1
            req_metrics.attempts = attempt
            req_metrics.provider = rb.backend.name
            try:
                result = await self._attempt(
                    request, endpoint, front_schema, rb, body,
                    req_metrics, route_name, error_body, client_headers,
                    span,
                )
            except _RetriableUpstreamError as e:
                logger.warning(
                    "backend %s failed (%s), trying next", rb.backend.name, e
                )
                if e.count_failure:
                    self.circuit.record_failure(rb.backend.name)
                last_error = (e.status, e.client_body)
                self.metrics.requests_total.labels(
                    route_name, rb.backend.name, str(e.status)
                ).inc()
                continue
            except AuthError as e:
                req_metrics.finish(TokenUsage(), error_type="auth")
                return web.Response(
                    status=401, body=error_body(str(e), type_="authentication_error"),
                    content_type="application/json")
            except (TranslationError, oai.SchemaError) as e:
                req_metrics.finish(TokenUsage(), error_type="translation")
                status = getattr(e, "status", 400)  # NotFoundError → 404
                return web.Response(
                    status=status,
                    body=error_body(
                        str(e),
                        type_="not_found" if status == 404
                        else "invalid_request_error"),
                    content_type="application/json")
            self.circuit.record_success(rb.backend.name)
            return result

        req_metrics.finish(TokenUsage(), error_type="upstream_exhausted")
        return web.Response(
            status=last_error[0], body=last_error[1],
            content_type="application/json")

    async def _attempt(
        self,
        request: web.Request,
        endpoint: Endpoint,
        front_schema: APISchemaName,
        rb: RuntimeBackend,
        body: dict[str, Any],
        req_metrics: RequestMetrics,
        route_name: str,
        error_body: Callable[..., bytes],
        client_headers: dict[str, str],
        span=None,
    ) -> web.StreamResponse:
        backend = rb.backend
        # explicit None check: aiohttp's web.Response is a MutableMapping
        # over its (empty) per-request state, so a fresh 429 Response is
        # FALSY — a bare walrus truthiness test silently dropped the
        # quota rejection and let the request through
        rc_limited = await self._check_quota(client_headers, rb,
                                             req_metrics, error_body)
        if rc_limited is not None:
            return rc_limited
        if isinstance(body, _RawBody):
            # multipart passthrough: no translation, original bytes forward
            from aigw_tpu.translate.base import RequestTx as _RequestTx

            translator = get_translator(
                Endpoint.CHAT_COMPLETIONS,  # response side is passthrough
                APISchemaName.OPENAI,
                APISchemaName.OPENAI,
            )
            path = request.path
            if backend.schema.name is APISchemaName.AZURE_OPENAI:
                from aigw_tpu.translate.openai_azure import (
                    DEFAULT_API_VERSION,
                    _ENDPOINT_SUFFIX,
                )
                import urllib.parse as _up2

                dep = _up2.quote(
                    backend.model_name_override or body.model, safe="")
                path = (
                    f"/openai/deployments/{dep}/"
                    f"{_ENDPOINT_SUFFIX[endpoint]}"
                    f"?api-version="
                    f"{backend.schema.version or DEFAULT_API_VERSION}"
                )
            out_body = body.raw
            out_ctype = body.content_type
            if (backend.model_name_override
                    and backend.model_name_override != body.model):
                # the reference rewrites the model form field when the
                # backend overrides the model name, every other part
                # verbatim (multipart_helper.go:16-66)
                from aigw_tpu.translate.multipart import (
                    rewrite_multipart_model,
                )

                out_body, out_ctype = rewrite_multipart_model(
                    body.raw, body.content_type,
                    backend.model_name_override)
            tx = _RequestTx(body=out_body, path=path)
            headers = {
                "content-type": out_ctype,
                "accept": "application/json",
            }
        else:
            translator = get_translator(
                endpoint,
                front_schema,
                backend.schema.name,
                model_name_override=backend.model_name_override,
                out_version=backend.schema.version,
            )
            # Retry safety: translators are contractually read-only over
            # the captured body (they build fresh structures — the
            # reference's sjson no-in-place rule, translator.go:140-153),
            # so each attempt can re-translate without a deep copy.
            if self._translator_blocks(endpoint):
                # /v1/responses with a file-backed transcript store:
                # previous_response_id resolution reads disk — off the loop
                tx = await asyncio.to_thread(translator.request, body)
            else:
                tx = translator.request(body)
            out_body = apply_body_mutation(tx.body, backend.body_mutation)

            headers = {
                "content-type": "application/json",
                "accept": "text/event-stream" if tx.stream
                else "application/json",
            }
        # Endpoint-picker support: an externally pre-selected destination
        # (the reference's x-gateway-destination-endpoint + ORIGINAL_DST
        # contract, post_cluster_modify.go:67-80) wins; otherwise the
        # in-process picker chooses a replica from the backend's pool.
        dest = request.headers.get(DESTINATION_ENDPOINT_HEADER, "")
        prefix_key_used = ""
        decision: dict[str, Any] | None = None
        pick_headers = client_headers
        if not dest and backend.name in self._pickers:
            if backend.picker_content_affinity and isinstance(body, dict):
                derived = {}
                if AFFINITY_HEADER not in client_headers:
                    key = _conversation_affinity_key(body)
                    if key:
                        derived[AFFINITY_HEADER] = key
                if PREFIX_HEADER not in client_headers:
                    # shared system-prompt hash → soft cache-affinity:
                    # the picker prefers the replica whose prefix cache
                    # this prompt head was recently routed to
                    pkey = _prefix_hash_key(body)
                    if pkey:
                        derived[PREFIX_HEADER] = pkey
                if derived:
                    pick_headers = dict(client_headers) | derived
            # adapter-affinity (ISSUE 7): an adapter-suffixed zoo name
            # prefers replicas whose /state reports the LoRA row already
            # resident (soft — any replica can hot-load it)
            adapter = split_model(req_metrics.request_model)[1]
            if adapter and ADAPTER_HEADER not in pick_headers:
                pick_headers = dict(pick_headers) | {
                    ADAPTER_HEADER: adapter}
            # long-context satellite: prompt length is a routing input —
            # an explicit client header wins, else estimate from the
            # prompt bytes so the picker can filter replicas whose
            # advertised max_seq_len the prompt exceeds and price the
            # prefill into its predicted TTFT
            if (PROMPT_TOKENS_HEADER not in pick_headers
                    and isinstance(body, dict)):
                est = _prompt_token_estimate(body)
                if est:
                    pick_headers = dict(pick_headers) | {
                        PROMPT_TOKENS_HEADER: str(est)}
            # explain is ALWAYS computed now (ISSUE 12): the decision
            # audit ring records every pick, traced or not — the span
            # attrs below still only render when tracing is on
            explain: dict[str, Any] = {}
            try:
                dest = self._pickers[backend.name].pick(
                    pick_headers, explain=explain) or ""
            except SLOShedError as e:
                # SLO admission control (ISSUE 8): every candidate's
                # predicted TTFT blows the budget — shed with
                # 429 + Retry-After instead of queueing into collapse
                self.metrics.slo_sheds_total.labels(
                    route_name, backend.name).inc()
                self.metrics.requests_total.labels(
                    route_name, backend.name, "429").inc()
                req_metrics.finish(TokenUsage(), error_type="slo_shed")
                if backend.fleet_obs:
                    # shed events land in the audit ring too — "why
                    # did my request 429" is a routing decision
                    req_metrics.decision = self.decisions.record(
                        route=route_name, backend=backend.name,
                        model=req_metrics.request_model,
                        request_id=client_headers.get(
                            "x-request-id", ""),
                        shed=True,
                        retry_after_s=e.retry_after_s,
                        pick=dict(explain))
                if span is not None:
                    span.set("aigw.pick.shed", True)
                    span.set("aigw.pick.predicted_ttft_ms",
                             round(e.predicted_ms, 1))
                return web.Response(
                    status=429,
                    body=error_body(str(e), type_="rate_limit_error"),
                    headers={"retry-after": str(e.retry_after_s)},
                    content_type="application/json")
            except ContextLengthError as e:
                # long-context satellite: the prompt exceeds EVERY
                # fresh candidate's advertised context length — answer
                # a clean 400 at the gateway instead of collecting the
                # replica's over-length error after a routed admission
                self.metrics.requests_total.labels(
                    route_name, backend.name, "400").inc()
                req_metrics.finish(
                    TokenUsage(), error_type="context_length")
                if backend.fleet_obs:
                    req_metrics.decision = self.decisions.record(
                        route=route_name, backend=backend.name,
                        model=req_metrics.request_model,
                        request_id=client_headers.get(
                            "x-request-id", ""),
                        context_rejected=True,
                        prompt_tokens=e.prompt_tokens,
                        max_ctx=e.max_ctx,
                        pick=dict(explain))
                if span is not None:
                    span.set("aigw.pick.context_rejected", True)
                    span.set("aigw.pick.prompt_tokens", e.prompt_tokens)
                    span.set("aigw.pick.max_ctx", e.max_ctx)
                return web.Response(
                    status=400,
                    body=error_body(
                        str(e), type_="invalid_request_error"),
                    content_type="application/json")
            if dest and backend.fleet_obs:
                decision = self.decisions.record(
                    route=route_name, backend=backend.name,
                    model=req_metrics.request_model,
                    request_id=client_headers.get("x-request-id", ""),
                    chosen=dest,
                    pick=dict(explain))
                req_metrics.decision = decision
            if span is not None and dest:
                # why the picker chose this replica — the span-level
                # answer to "which endpoint served me, and was it
                # cache/session affinity or load" (slo mode adds the
                # per-endpoint predicted TTFTs behind the decision)
                span.set("aigw.endpoint", dest)
                for k, v in (explain or {}).items():
                    span.set(f"aigw.pick.{k}",
                             json.dumps(v) if isinstance(v, dict) else v)
            prefix_key_used = pick_headers.get(PREFIX_HEADER, "")
            if dest and backend.kv_fleet:
                # KV memory hierarchy (ISSUE 11): name the siblings the
                # fleet index says hold this request's chain — a prefix
                # miss on the chosen replica then becomes a page fetch
                # over /kv/pages instead of a re-prefill
                peers = self._pickers[backend.name].kv_peers(
                    dest, pick_headers)
                if peers:
                    headers[KV_PEERS_HEADER] = ",".join(peers)
                    if decision is not None:
                        decision["kv_peers"] = list(peers)
        base_url = f"http://{dest}" if dest else backend.url
        if not base_url:
            raise _RetriableUpstreamError(
                502, error_body(f"backend {backend.name} has no url"),
                "missing url")
        headers.update(tx.headers)
        if span is not None:
            self.tracer.propagators.inject(span.context, headers)
        else:
            # tracing disabled at the gateway: still RELAY the caller's
            # trace context verbatim so the replica hop can parent its
            # spans / flight-recorder entries on the caller's trace
            for h in ("traceparent", "b3", "x-b3-traceid",
                      "x-b3-spanid", "x-b3-sampled"):
                if h in client_headers:
                    headers[h] = client_headers[h]
        if TENANT_HEADER in client_headers:
            # the replica's fairness guard keys on the SAME tenant the
            # gateway accounts/ratelimits by
            headers[TENANT_HEADER] = client_headers[TENANT_HEADER]
        if PRIORITY_HEADER in client_headers:
            # priority class (ISSUE 19): the replica's two-class
            # scheduler must see the SAME class the picker routed by
            headers[PRIORITY_HEADER] = client_headers[PRIORITY_HEADER]
        headers = apply_header_mutation(headers, backend.header_mutation)
        import urllib.parse as _up

        headers["host"] = _up.urlsplit(base_url).netloc
        path = tx.path or request.path
        headers, path = rb.auth_handler.apply(headers, out_body, path)

        if logger.isEnabledFor(logging.DEBUG):
            from aigw_tpu.utils.redaction import redact_body, redact_headers

            logger.debug(
                "upstream attempt backend=%s path=%s headers=%s body=%s",
                backend.name, path, redact_headers(headers),
                redact_body(body) if not isinstance(body, _RawBody)
                else f"[multipart {len(body.raw)} bytes]",
            )
        session = await self._get_session()
        timeout = aiohttp.ClientTimeout(
            total=backend.request_timeout,
            sock_connect=min(10.0, backend.request_timeout),
            sock_read=backend.stream_idle_timeout if tx.stream else None,
        )
        #: this request went through the picker (an external
        #: x-gateway-destination-endpoint pin is NOT failed over —
        #: the pinner chose that exact replica on purpose)
        picked = bool(dest) and backend.name in self._pickers

        def _move_dest(nxt: str) -> None:
            # pre-first-byte failover (ISSUE 14): re-aim the SAME
            # translated request at a sibling replica. Only the
            # destination-derived pieces change; the translated body,
            # auth, and mutations were all destination-independent.
            nonlocal dest, base_url
            if decision is not None:
                decision.setdefault("failover_from", []).append(dest)
                decision["chosen"] = nxt
            if span is not None:
                span.set("aigw.pick.failover_from", dest)
            if KV_PEERS_HEADER in headers:
                peers = [p for p in headers[KV_PEERS_HEADER].split(",")
                         if p and p != nxt]
                if peers:
                    headers[KV_PEERS_HEADER] = ",".join(peers)
                else:
                    del headers[KV_PEERS_HEADER]
            dest = nxt
            base_url = f"http://{dest}"
            headers["host"] = _up.urlsplit(base_url).netloc

        def _sibling(tried: set[str]) -> str | None:
            picker = self._pickers.get(backend.name)
            if picker is None:
                return None
            try:
                nxt = picker.pick(pick_headers, exclude=frozenset(tried))
            except (SLOShedError, ContextLengthError):
                return None
            return nxt if nxt and nxt not in tried else None

        # at most ONE sibling retry, and only before any stream byte has
        # been relayed: a connect error or an immediate retriable 5xx
        # from a picked replica re-picks the next-ranked sibling instead
        # of surfacing the dead replica's error to the client
        failed_over = not picked
        breaker_counted: set[str] = set()
        while True:
            try:
                resp = await session.post(
                    base_url + path, data=out_body, headers=headers,
                    timeout=timeout
                )
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                if picked:
                    # per-replica breaker evidence: the dead process is
                    # condemned by address, not just its whole backend
                    self.circuit.record_failure(dest)
                if not failed_over:
                    nxt = _sibling({dest})
                    if nxt is not None:
                        failed_over = True
                        logger.warning(
                            "pre-first-byte failover %s -> %s (%s)",
                            dest, nxt, e)
                        _move_dest(nxt)
                        continue
                raise _RetriableUpstreamError(
                    502, error_body(f"upstream connect error: {e}",
                                    type_="upstream_error"),
                    str(e) or type(e).__name__,
                ) from None
            if (not failed_over and resp.status in (500, 502, 503, 504)):
                self.circuit.record_failure(dest)
                breaker_counted.add(dest)
                nxt = _sibling({dest})
                if nxt is not None:
                    failed_over = True
                    logger.warning(
                        "pre-first-byte failover %s -> %s (status %d)",
                        dest, nxt, resp.status)
                    resp.release()
                    _move_dest(nxt)
                    continue
            break

        async with _closing(resp):
            if resp.status >= 400:
                try:
                    err = await resp.read()
                except (aiohttp.ClientError, asyncio.TimeoutError):
                    err = b""
                client_err = translator.response_error(resp.status, err)
                if resp.status in _RETRIABLE_STATUS:
                    if picked and dest not in breaker_counted:
                        self.circuit.record_failure(dest)
                    raise _RetriableUpstreamError(resp.status, client_err,
                                                  f"status {resp.status}")
                req_metrics.finish(TokenUsage(), error_type=str(resp.status))
                self.metrics.requests_total.labels(
                    route_name, backend.name, str(resp.status)
                ).inc()
                return web.Response(
                    status=resp.status, body=client_err,
                    content_type="application/json")

            if picked:
                # response started: close the replica-address circuit
                self.circuit.record_success(dest)
            translator.response_headers(
                resp.status, {k.lower(): v for k, v in resp.headers.items()}
            )
            # tpuserve's per-request id: joins this request's access-log
            # line against the replica's /debug/requests/{id} timeline
            req_metrics.upstream_request_id = resp.headers.get(
                "x-aigw-request-id", "")
            if decision is not None and req_metrics.upstream_request_id:
                # the audit-ring join key (ISSUE 12): the decision now
                # resolves straight to the serving replica's
                # flight-recorder timeline under the same id
                decision["upstream_request_id"] = (
                    req_metrics.upstream_request_id)
            if backend.name in self._pickers:
                # learn (prefix-head → KV chain) from the replica's
                # response — the fleet index can then locate this
                # prompt head's chain for later requests (ISSUE 11)
                chain_hex = resp.headers.get(KV_CHAIN_HEADER, "")
                if chain_hex and prefix_key_used:
                    self._pickers[backend.name].note_chain(
                        prefix_key_used, chain_hex)
            ctype = resp.headers.get("content-type", "")
            upstream_streams = tx.stream and (
                "text/event-stream" in ctype
                or "vnd.amazon.eventstream" in ctype
            )
            if upstream_streams:
                migrator = None
                if (backend.migration and dest
                        and backend.name in self._pickers
                        and endpoint in (Endpoint.CHAT_COMPLETIONS,
                                         Endpoint.COMPLETIONS)):
                    # prefill/decode disaggregation (ISSUE 8): this
                    # stream may be handed to a decode-leaning replica
                    # mid-flight if the source's prefill queue backs up
                    migrator = _Migrator(
                        picker=self._pickers[backend.name],
                        backend=backend, src=dest, session=session,
                        decision=decision)
                return await self._stream_response(
                    request, resp, translator, rb, req_metrics, route_name,
                    client_headers, front_schema, span=span,
                    endpoint=endpoint, migrator=migrator,
                )
            try:
                raw = await resp.read()
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                raise _RetriableUpstreamError(
                    502,
                    error_body(f"upstream body read failed: {e}",
                               type_="upstream_error"),
                    str(e) or type(e).__name__,
                ) from None
            if self._translator_blocks(endpoint):
                # end-of-stream persists the transcript to disk
                rx = await asyncio.to_thread(
                    translator.response_body, raw, True)
            else:
                rx = translator.response_body(raw, True)
            # Response-side typed validation (r5): the body the gateway
            # re-emits must carry the front schema's response shape — a
            # malformed upstream body is an upstream failure (reference
            # ResponseError semantics, translator.go:42-77), retriable
            # on the next backend like any other 502.
            if (not isinstance(body, _RawBody)
                    and typed_response.has_spec(endpoint)):
                parsed = rx.parsed
                try:
                    if parsed is None:
                        parsed = json.loads(rx.body or raw)
                    typed_response.validate_response(endpoint, parsed)
                except (json.JSONDecodeError, oai.SchemaError) as e:
                    if (endpoint is Endpoint.RESPONSES
                            and isinstance(parsed, dict)):
                        # the translator persisted a transcript for an
                        # id the client will never see — roll it back
                        rid = parsed.get("id")
                        if isinstance(rid, str) and rid:
                            from aigw_tpu.translate.responses import (
                                RESPONSE_STORE,
                            )

                            if self._translator_blocks(endpoint):
                                await asyncio.to_thread(
                                    RESPONSE_STORE.delete, rid)
                            else:
                                RESPONSE_STORE.delete(rid)
                    raise _RetriableUpstreamError(
                        502,
                        error_body(
                            f"upstream returned a malformed "
                            f"{endpoint.value} response: {e}",
                            type_="upstream_error"),
                        f"malformed upstream body: {e}",
                    ) from None
            usage = rx.usage
            req_metrics.response_model = rx.model
            if span is not None:
                self._openinference_response_attrs(
                    span, endpoint, rx.body or raw)
            req_metrics.finish(usage)
            await self._sink_costs(usage, req_metrics, route_name, client_headers)
            self.metrics.requests_total.labels(
                route_name, backend.name, str(resp.status)
            ).inc()
            upstream_ctype = resp.headers.get(
                "content-type", "application/json")
            out_headers = {}
            if req_metrics.upstream_request_id:
                # relay the replica's request id to the client — the
                # key a bug report can quote straight into the
                # replica's /debug/requests/{id}
                out_headers["x-aigw-request-id"] = (
                    req_metrics.upstream_request_id)
            return web.Response(
                status=resp.status, body=rx.body or raw,
                headers=out_headers,
                content_type=upstream_ctype.split(";")[0])

    async def _stream_response(
        self,
        request: web.Request,
        resp: aiohttp.ClientResponse,
        translator: Any,
        rb: RuntimeBackend,
        req_metrics: RequestMetrics,
        route_name: str,
        client_headers: dict[str, str],
        front_schema: APISchemaName = APISchemaName.OPENAI,
        span=None,
        endpoint: Endpoint | None = None,
        migrator: "_Migrator | None" = None,
    ) -> web.StreamResponse:
        """Proxy the SSE stream through the translator — the hot loop
        (reference processor_impl.go:481-575).

        First-frame latency contract: nothing here buffers beyond ONE
        complete SSE event. ``iter_any`` yields upstream bytes as they
        arrive, the translator re-emits per chunk, and the typed-stream
        validator relays every *complete* event immediately (only the
        partial tail waits for its terminator). Combined with
        TCP_NODELAY below and ``x-accel-buffering: no``, the first
        content delta leaves this hop as soon as tpuserve writes it.
        """
        out = web.StreamResponse(
            status=200,
            headers={
                "content-type": "text/event-stream",
                "cache-control": "no-cache",
                "x-accel-buffering": "no",
            },
        )
        if req_metrics.upstream_request_id:
            # replica request id → client (joins /debug/requests/{id})
            out.headers["x-aigw-request-id"] = (
                req_metrics.upstream_request_id)
        from aigw_tpu.utils.net import set_tcp_nodelay

        set_tcp_nodelay(request.transport)
        await out.prepare(request)
        usage = TokenUsage()
        model = ""
        # span output attrs for streams: reconstruct the response from
        # the front-schema SSE bytes (reference sse_converter.go). Only
        # when tracing is on — the accumulator parses every event.
        acc = None
        if span is not None and endpoint in (
            Endpoint.CHAT_COMPLETIONS, Endpoint.MESSAGES,
            Endpoint.COMPLETIONS,
        ):
            from aigw_tpu.obs.openinference import StreamAccumulator

            acc = StreamAccumulator()
        # Response-side typed validation for streams (r5): every event
        # the gateway re-emits is validated against the front schema's
        # chunk/event spec. Translators may re-emit at arbitrary byte
        # boundaries (passthrough forwards upstream chunks verbatim), so
        # events are reassembled across writes: validated-complete
        # events are relayed, the tail stays buffered, and a malformed
        # event is NEVER relayed — the stream ends with the error event.
        sse_buf = b""
        check_events = typed_response.has_stream_spec(endpoint)

        def _bad_event(raw: bytes) -> "oai.SchemaError | None":
            # field parsing (multi-line data joining, comments, CRLF)
            # delegates to the shared SSE parser — only the framing
            # scan below is local, because verbatim relay needs byte
            # offsets, which SSEParser does not expose
            from aigw_tpu.translate.sse import _parse_event

            ev = _parse_event(raw)
            if ev is None or not ev.data or ev.data.strip() == "[DONE]":
                return None
            try:
                typed_response.validate_stream_event(
                    endpoint, json.loads(ev.data))
            except (json.JSONDecodeError, oai.SchemaError) as e:
                return oai.SchemaError(str(e))
            return None

        def _scan_events(
            buf: bytes,
        ) -> "tuple[bytes, bytes, oai.SchemaError | None]":
            """(relay-able prefix of complete good events, remainder,
            error). On error the bad event stays in the remainder.
            Boundary rules byte-identical to SSEParser.feed: an event
            ends at the first blank line, \\n\\n or \\r\\n\\r\\n."""
            ok_end = pos = 0
            while True:
                sep = -1
                seplen = 0
                for cand in (b"\n\n", b"\r\n\r\n"):
                    i = buf.find(cand, pos)
                    if i != -1 and (sep == -1 or i < sep):
                        sep, seplen = i, len(cand)
                if sep == -1:
                    return buf[:ok_end], buf[ok_end:], None
                err = _bad_event(buf[pos:sep])
                if err is not None:
                    return buf[:ok_end], buf[ok_end:], err
                pos = ok_end = sep + seplen

        async def _relay(body: bytes) -> None:
            nonlocal sse_buf
            if not check_events:
                if acc is not None:
                    acc.feed(body)
                await out.write(body)
                return
            good, sse_buf, err = _scan_events(sse_buf + body)
            if good:
                if acc is not None:
                    acc.feed(good)
                await out.write(good)
            if err is not None:
                raise err

        try:
            async for chunk in resp.content.iter_any():
                rx = translator.response_body(chunk, False)
                usage = usage.merge_override(rx.usage)
                model = rx.model or model
                req_metrics.record_tokens_emitted(rx.tokens_emitted)
                if rx.body:
                    await _relay(rx.body)
                if migrator is not None:
                    # may cut the session at the source: its stream
                    # then ends at a token boundary and this loop runs
                    # to EOF, flushing every pre-cut token first
                    await migrator.maybe_export(
                        req_metrics.tokens_seen,
                        req_metrics.upstream_request_id)
            if migrator is not None and migrator.export is not None:
                # splice the decode replica's continuation: frames carry
                # the SAME response id, terminal frames included — the
                # client sees one uninterrupted stream
                cont = await migrator.start_continuation()
                if cont is None:
                    # resume from the last exported state on another
                    # sibling (ISSUE 14): the blob is in hand and no
                    # continuation byte was relayed yet, so a second
                    # target adopts the chain gap-free
                    cont = await migrator.retry_continuation()
                if cont is None:
                    # the session was cut but nobody resumed it — this
                    # is a real mid-stream loss; surface the SSE error
                    # event via the except path below
                    raise aiohttp.ClientPayloadError(
                        "migration continuation failed after export")
                self.metrics.migrations_total.labels(
                    route_name, rb.backend.name).inc()
                if span is not None:
                    span.set("aigw.migrated_to", migrator.target)
                async with _closing(cont):
                    async for chunk in cont.content.iter_any():
                        rx = translator.response_body(chunk, False)
                        usage = usage.merge_override(rx.usage)
                        model = rx.model or model
                        req_metrics.record_tokens_emitted(
                            rx.tokens_emitted)
                        if rx.body:
                            await _relay(rx.body)
            if self._translator_blocks(endpoint):
                # end-of-stream persists the transcript to disk
                rx = await asyncio.to_thread(
                    translator.response_body, b"", True)
            else:
                rx = translator.response_body(b"", True)
            usage = usage.merge_override(rx.usage)
            model = rx.model or model
            if rx.body:
                await _relay(rx.body)
            if check_events and sse_buf:
                # final event not terminated by a blank line (the same
                # shape SSEParser.flush handles): validate before relay
                # — the malformed-never-relayed invariant holds at EOF
                err = _bad_event(sse_buf)
                if err is not None:
                    raise err
                await out.write(sse_buf)
                sse_buf = b""
        except (aiohttp.ClientError, asyncio.TimeoutError,
                oai.SchemaError) as e:
            # Mid-stream failure: the client already has bytes; surface an
            # SSE error event rather than failing over (the reference's
            # per-try idle timeout only retries before response start).
            # The event is shaped for the *front* schema so the client
            # SDK recognizes it (Anthropic SDKs need `event: error` with
            # an Anthropic error envelope). A SchemaError means the
            # upstream emitted a malformed event — it was NOT relayed;
            # the stream ends with the error event instead.
            malformed = isinstance(e, oai.SchemaError)
            logger.warning("stream from %s %s: %s", rb.backend.name,
                           "emitted malformed event" if malformed
                           else "aborted", e)
            msg = ("upstream emitted a malformed stream event"
                   if malformed else "upstream stream interrupted")
            if front_schema is APISchemaName.ANTHROPIC:
                await out.write(
                    b'event: error\n'
                    b'data: {"type": "error", "error": {"type": '
                    b'"overloaded_error", "message": "'
                    + msg.encode() + b'"}}\n\n'
                )
            else:
                await out.write(
                    b'data: {"error": {"message": "' + msg.encode()
                    + b'", "type": "upstream_error", "code": null}}\n\n'
                )
        req_metrics.response_model = model
        if acc is not None:
            final = acc.response()
            builder = self._oi_response_builder(endpoint)
            if final is not None and builder is not None:
                try:
                    span.attributes.update(
                        builder(final, self._oi_config))
                except Exception:  # noqa: BLE001
                    logger.debug("stream span attrs failed", exc_info=True)
        req_metrics.finish(usage)
        await self._sink_costs(usage, req_metrics, route_name, client_headers)
        self.metrics.requests_total.labels(route_name, rb.backend.name, "200").inc()
        await out.write_eof()
        return out

    @staticmethod
    def _translator_blocks(endpoint: "Endpoint | None") -> bool:
        """True when translator request/end-of-stream calls do disk I/O
        (file-backed /v1/responses transcript store) and must be
        thread-hopped off the event loop — same contract as the quota
        backend below and FileReplayStore.blocking."""
        if endpoint is not Endpoint.RESPONSES:
            return False
        from aigw_tpu.translate.responses import RESPONSE_STORE

        return RESPONSE_STORE.blocking

    async def _check_quota(self, client_headers, rb, req_metrics,
                           error_body):
        """Admission check against token quotas (reference: Envoy
        ratelimit filter with domain ai-gateway-quota,
        extensionserver/quota_ratelimit.go:59). Consumption happens at
        end-of-stream in _sink_costs. A shared (flock'd-file) backend
        can block on cross-worker lock contention, so it runs off the
        event loop; the in-memory limiter is called inline."""
        limiter = self._runtime.rate_limiter
        if limiter is None or not limiter.rules:
            return None
        if limiter.backend is not None:
            ok, rule = await asyncio.to_thread(
                limiter.check,
                req_metrics.request_model, rb.backend.name, client_headers,
            )
        else:
            ok, rule = limiter.check(
                req_metrics.request_model, rb.backend.name, client_headers
            )
        if ok:
            return None
        client_err = error_body(
            f"token quota exceeded (rule {rule.name!r})",
            type_="rate_limit_error",
        )
        if rule.backend:
            # a backend-scoped budget: other backends may still have
            # budget, so fail over — but without a circuit-breaker
            # failure mark (the backend is healthy; a refilled quota
            # window must not find the circuit open)
            raise _RetriableUpstreamError(429, client_err,
                                          f"quota {rule.name}",
                                          count_failure=False)
        req_metrics.finish(TokenUsage(), error_type="429")
        return web.Response(
            status=429,
            body=client_err,
            headers={"retry-after": "1"},
            content_type="application/json",
        )

    async def _sink_costs(
        self,
        usage: TokenUsage,
        req_metrics: RequestMetrics,
        route_name: str,
        client_headers: dict[str, str],
    ) -> None:
        """End-of-stream cost metadata (≈ dynamic metadata for the
        rate-limit filter, extproc/util.go buildDynamicMetadata).

        Quota consumption is keyed by the *request* model — the same value
        _check_quota matched against — so model-scoped budgets enforce
        consistently even when the backend reports a versioned response
        model or a model_name_override rewrote the upstream name.

        ISSUE 20: the usage ledger records here too — EVERY finished
        request, with or without configured cost programs — folding the
        engine MeterRecord (usage.aigw_meter) into the per-tenant
        windowed ledger, reconciling it against the mined token counts,
        and stamping the priced cost onto the request's decision-ring
        entry so /debug/decisions shows what each pick cost."""
        limiter = self._runtime.rate_limiter
        has_quota = limiter is not None and limiter.rules
        ledger = self.usage_ledger
        if (self._cost_sink is None and not has_quota
                and not self.access_log.enabled and ledger is None):
            return
        model = req_metrics.request_model
        backend = req_metrics.provider
        tenant = client_headers.get(TENANT_HEADER, "")
        costs = self._runtime.cost_calculator_for(route_name).calculate(
            usage, model=model, backend=backend, route_name=route_name,
            tenant=tenant,
        )
        if ledger is not None:
            # ledger cost = the summed configured cost metrics (0 when
            # no cost programs are configured — the token/residency
            # columns still accumulate engine truth)
            total_cost = sum(costs.values())
            ledger.record(tenant, model, usage, cost=total_cost)
            req_metrics.decision["cost"] = total_cost
            if costs:
                req_metrics.decision["costs"] = dict(costs)
        if not costs:
            return
        req_metrics.costs = dict(costs)
        if has_quota:
            if limiter.backend is not None:
                # flock'd shared store: contention must not stall the loop
                await asyncio.to_thread(
                    limiter.consume, costs, model, backend, client_headers)
            else:
                limiter.consume(costs, model, backend, client_headers)
        if self._cost_sink is not None:
            self._cost_sink(
                costs,
                {"model": model, "backend": backend, "route": route_name},
            )


class _Migrator:
    """Gateway-side orchestrator for migrating ONE streaming session
    (ISSUE 8 prefill/decode disaggregation). While the gateway relays a
    stream from its source replica it watches the picker's polled
    telemetry; when the source's admission queue is deep (prefill
    pressure), the session is still young, and a decode-leaning sibling
    exists, it cuts the session via the source's ``/migrate/export``
    and splices the target's ``/migrate/import`` continuation stream —
    the client sees one uninterrupted SSE stream under one response id.

    At most one migration attempt per request; a declined or failed
    export leaves the source serving untouched."""

    def __init__(self, picker: EndpointPicker, backend, src: str,
                 session: aiohttp.ClientSession,
                 decision: dict | None = None):
        self.picker = picker
        self.backend = backend
        self.src = src
        self.session = session
        self.attempted = False
        self.export: dict | None = None
        self.target: str | None = None
        #: the request's audit-ring entry (ISSUE 12): a fired migration
        #: is part of the routing decision's afterlife — stamped here
        #: so /debug/decisions shows the trigger next to the pick
        self.decision = decision

    def _drain_requested(self) -> bool:
        """The source replica is draining (controller scale-in/update,
        operator /drain, or its own /state announcement) — every
        migration-capable stream must move off regardless of queue
        pressure or age (ISSUE 14 lossless drain)."""
        h = self.picker.fleet.health.get(self.src)
        return h is not None and h.draining

    def _pick_target(self, force: bool = False,
                     exclude: set | frozenset = frozenset()
                     ) -> str | None:
        src_st = self.picker.state.get(self.src)
        if src_st is None or not src_st.healthy:
            return None
        if not src_st.migration_capable:
            # the replica reports `migration: false` on /state (e.g.
            # prefix cache disabled — no refcounted page export path):
            # stop polling for this stream instead of 409ing an export
            self.attempted = True
            return None
        if not force and src_st.queued < self.backend.migration_queue_depth:
            return None  # no prefill pressure at the source
        now = time.monotonic()
        best: str | None = None
        best_pred = 0.0
        for addr, st in self.picker.state.items():
            if addr == self.src or addr in exclude or not st.healthy:
                continue
            if not self.picker.is_routable(addr):
                continue  # down/draining/breaker-open: not a new home
            if not st.migration_capable:
                continue  # can't adopt a page chain
            if now - st.updated_at >= self.picker.STALE_AFTER:
                continue
            if st.queued > 0 or st.active_slots >= st.max_slots:
                continue  # not decode-leaning: nowhere to put the slot
            p = self.picker.predicted_ttft_ms(st)
            p = 0.0 if p is None else p
            if best is None or p < best_pred:
                best, best_pred = addr, p
        return best

    async def maybe_export(self, tokens_seen: int, rid: str) -> None:
        """Per-chunk check (cheap dict reads until the trigger fires).
        On trigger, POSTs the source's export endpoint — after which the
        source ends its stream at a token boundary and the relay loop
        runs to EOF naturally, flushing every pre-cut token."""
        if self.attempted or not rid or tokens_seen < 1:
            return
        draining = self._drain_requested()
        if not draining and tokens_seen > self.backend.migration_young_tokens:
            self.attempted = True  # matured past migratability
            return
        target = self._pick_target(force=draining)
        if target is None:
            return
        self.attempted = True
        try:
            async with self.session.post(
                f"http://{self.src}/migrate/export",
                json={"request_id": rid},
                timeout=aiohttp.ClientTimeout(total=60),
            ) as r:
                if r.status != 200:
                    # 409 = not now (finished / ineligible): the source
                    # keeps serving, nothing to splice
                    logger.info("migration export declined (%d)",
                                r.status)
                    return
                self.export = await r.json()
            self.target = target
            if self.decision is not None:
                self.decision["migrated_to"] = target
                self.decision["migration_trigger"] = {
                    "src_queued": int(getattr(
                        self.picker.state.get(self.src), "queued", 0)),
                    "tokens_seen": tokens_seen,
                    "drain": draining,
                }
            logger.info("migrating session %s: %s -> %s", rid, self.src,
                        target)
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            logger.warning("migration export failed: %s", e)

    async def retry_continuation(self) -> aiohttp.ClientResponse | None:
        """Resume from the last exported state on a DIFFERENT sibling
        (ISSUE 14 crash failover): the cut already happened and the
        blob is in hand — if the chosen target died or refused the
        import, any other idle migration-capable replica can adopt the
        chain. The client stream stays gap-free by construction: the
        continuation always starts at the export cut, and zero
        continuation bytes were relayed before this retry. Returns None
        when no alternative target exists (the caller degrades to the
        typed error event)."""
        if self.export is None or self.target is None:
            return None
        failed = self.target
        nxt = self._pick_target(force=True, exclude={failed})
        if nxt is None:
            return None
        self.target = nxt
        if self.decision is not None:
            self.decision.setdefault(
                "migration_retargeted_from", []).append(failed)
            self.decision["migrated_to"] = nxt
        logger.info("migration continuation retarget %s -> %s",
                    failed, nxt)
        return await self.start_continuation()

    async def start_continuation(self) -> aiohttp.ClientResponse | None:
        """Hand the blob to the target replica; returns the SSE response
        that continues the client stream (original response id), or
        None when the import failed."""
        if self.export is None or self.target is None:
            return None
        try:
            r = await self.session.post(
                f"http://{self.target}/migrate/import",
                json=self.export,
                timeout=aiohttp.ClientTimeout(
                    total=self.backend.request_timeout,
                    sock_read=self.backend.stream_idle_timeout),
            )
            if r.status != 200:
                body = await r.read()
                r.release()
                logger.warning("migration import failed (%d): %s",
                               r.status, body[:200])
                return None
            return r
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            logger.warning("migration import failed: %s", e)
            return None


class _RetriableUpstreamError(Exception):
    def __init__(self, status: int, client_body: bytes, reason: str,
                 count_failure: bool = True):
        super().__init__(reason)
        self.status = status
        self.client_body = client_body
        #: whether the circuit breaker should count this as a backend
        #: failure; quota rejections fail over without poisoning the
        #: circuit (the backend itself is healthy)
        self.count_failure = count_failure


class _closing:
    def __init__(self, resp: aiohttp.ClientResponse):
        self._resp = resp

    async def __aenter__(self):
        return self._resp

    async def __aexit__(self, *exc):
        self._resp.release()
        return False


async def run_gateway(
    runtime: RuntimeConfig,
    host: str = "127.0.0.1",
    port: int = 1975,
    reuse_port: bool = False,
    **kwargs: Any,
) -> tuple[GatewayServer, web.AppRunner]:
    """Start the gateway; returns (server, runner). Caller owns shutdown.

    ``reuse_port=True`` binds with SO_REUSEPORT so multiple worker
    processes share one listening port, the kernel load-balancing
    accepted connections across them (the multi-worker mode — Envoy's
    role in the reference is a multi-threaded C++ proxy; CPython's GIL
    means horizontal processes, not threads)."""
    server = GatewayServer(runtime, **kwargs)
    # aiohttp's per-request INFO access log is pure hot-path overhead
    # (~4x rps at high concurrency); structured access logging is our
    # own AIGW_ACCESS_LOG pipeline (obs/accesslog.py)
    runner = web.AppRunner(server.app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, host, port, reuse_port=reuse_port or None)
    await site.start()
    logger.info("gateway listening on %s:%d", host, port)
    return server, runner
