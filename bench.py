"""Round benchmark — prints ONE JSON line.

Headline: **tokens/sec through the gateway**: `aigw run` (real CLI
subprocess) in front of the tpuserve engine, driven over streaming
`/v1/chat/completions`, for Llama-3-8B architecture W8A16 int8, batch 8,
paged KV (random weights: throughput is weight-value-agnostic).
``vs_baseline`` is gateway / raw-JAX-decode-ceiling and ``ttft_ms_p50``
is time-to-first-token at the HTTP surface. The engine-only row is kept
as ``engine_tokens_per_sec`` / ``engine_vs_raw``.

The raw ceiling is the best raw loop we can write: a K-step ``lax.scan``
inside one jit (single-step dispatch pays a host round-trip per token
and would flatter the engine).

``python bench.py`` (no ``--ab``) measures on a TPU or fails: the
platform is the one ``JAX_PLATFORMS`` names, else a TPU is required
(aigw_tpu/utils/boot.py) — there is no smaller-model, persisted-result
or CPU fallback, and the result is stamped with the device JAX reports.
One process per chip: this module imports jax, so the measuring process
holds the chip and only the in-thread server (``_start_tpuserve``) can
serve from it; every subprocess server this file can start is pinned to
the CPU. The ``--ab <leg>`` A/Bs therefore run entirely on the CPU and
report COUNTS (byte identity, hot compiles, padded_frac, reconcile
totals); their timings are not device metrics.

    {"metric": "...", "value": gateway_tokens_per_sec, "unit": "tokens/s",
     "vs_baseline": gateway/raw_ceiling, "ttft_ms_p50": ...,
     "engine_tokens_per_sec": ..., "engine_vs_raw": ...,
     "device": {"platform": ..., "kind": ..., "count": ...}}
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import socket
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp

from aigw_tpu.models import llama, mixtral
from aigw_tpu.obs import slomon
from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
from aigw_tpu.tpuserve.sampling import SamplingParams, sample

# small model of the CPU --ab legs and diagnostic tracers
# (benchmarks/ttft_*.py)
CPU_CFG = llama.LlamaConfig(
    vocab_size=8192, dim=512, n_layers=4, n_heads=8, n_kv_heads=4,
    ffn_dim=1536, max_seq_len=512, rope_theta=10000.0,
)
BATCH = 8
PAGE = 128
PROMPT_LEN = 128
GEN_TOKENS = 128
K_STEPS = 16  # matches EngineConfig.decode_steps_per_tick below

#: bf16 peak FLOP/s per chip, keyed by the ``device_kind`` JAX reports.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).
#: A kind that is not listed is an error, never a default: a CPU run
#: must not print an ``mfu``.
PEAK_FLOPS_BY_DEVICE_KIND = {"TPU v5 lite": 197e12}


def peak_flops(device_kind: str) -> float:
    try:
        return PEAK_FLOPS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device kind {device_kind!r} "
            f"(known: {sorted(PEAK_FLOPS_BY_DEVICE_KIND)}) — utilization "
            "is only defined for a listed accelerator") from None


def model_flops_per_token(cfg, context: int) -> float:
    """Analytical decode FLOPs per generated token: 2 FLOPs per matmul
    weight touched per token (q/k/v/o projections, the 3 MLP matrices,
    lm_head — embedding lookups are gathers, not FLOPs) plus the
    attention score/value matmuls, 4·dim FLOPs per cached token per
    layer (QK^T and PV each 2·dim). The PaLM-appendix accounting,
    specialized to GQA shapes."""
    hd = cfg.head_dim
    per_layer = (
        cfg.dim * cfg.n_heads * hd        # wq
        + 2 * cfg.dim * cfg.n_kv_heads * hd  # wk, wv
        + cfg.n_heads * hd * cfg.dim      # wo
        + 3 * cfg.dim * cfg.ffn_dim       # w_gate, w_up, w_down
    )
    matmul_params = cfg.n_layers * per_layer + cfg.dim * cfg.vocab_size
    attn = 4.0 * cfg.n_layers * context * cfg.dim
    return 2.0 * matmul_params + attn


def model_mfu(cfg, tokens_per_sec: float, context: int,
              device_kind: str) -> float:
    """End-to-end model FLOP/s utilization of a decode rate measured on
    one ``device_kind`` chip (raises for a kind with no listed peak)."""
    return (tokens_per_sec * model_flops_per_token(cfg, context)
            / peak_flops(device_kind))


def raw_ceiling_tokens_per_sec(params, cfg, batch=BATCH,
                               prompt_len=PROMPT_LEN,
                               k_steps=K_STEPS) -> float:
    """The ceiling: K decode steps scanned inside one jit — bare model
    math + sampling with dispatch fully amortized; no scheduler, no
    paging bookkeeping, no HTTP."""
    from jax import lax

    ecfg = EngineConfig(max_batch_size=batch, max_seq_len=cfg.max_seq_len,
                        page_size=PAGE)
    kv = jnp.zeros(
        (cfg.n_layers, 2, ecfg.num_pages * PAGE, cfg.n_kv_heads,
         cfg.head_dim), jnp.bfloat16,
    )
    pt = jnp.arange(batch * ecfg.max_pages_per_seq, dtype=jnp.int32).reshape(
        batch, ecfg.max_pages_per_seq
    )
    active = jnp.ones((batch,), bool)
    keys = jnp.zeros((batch, 2), jnp.uint32)
    temp = jnp.zeros((batch,), jnp.float32)
    top_p = jnp.ones((batch,), jnp.float32)
    top_k = jnp.zeros((batch,), jnp.int32)

    def kstep(params, tokens, positions, kv):
        def body(carry, _):
            tokens, positions, kv = carry
            logits, kv = llama.decode_step(
                params, cfg, tokens, positions, kv, pt, PAGE, active
            )
            nxt = sample(logits, keys, temp, top_p, top_k)
            return (nxt, positions + 1, kv), nxt

        (tokens, positions, kv), _ = lax.scan(
            body, (tokens, positions, kv), None, length=k_steps
        )
        return tokens, positions, kv

    kstep = jax.jit(kstep, donate_argnums=(3,))
    tokens = jnp.ones((batch,), jnp.int32)
    positions = jnp.full((batch,), prompt_len, jnp.int32)

    tokens, positions, kv = kstep(params, tokens, positions, kv)  # compile
    jax.block_until_ready(tokens)
    n_ticks = max(1, 64 // k_steps)
    best = 0.0
    for _ in range(2):  # two trials, keep the best (host jitter)
        t0 = time.perf_counter()
        for _ in range(n_ticks):
            tokens, positions, kv = kstep(params, tokens, positions, kv)
        jax.block_until_ready(tokens)
        dt = time.perf_counter() - t0
        best = max(best, batch * k_steps * n_ticks / dt)
    return best


def engine_numbers(params, cfg, batch=BATCH, prompt_len=PROMPT_LEN,
                   gen_tokens=GEN_TOKENS, k_steps=K_STEPS,
                   reps=1) -> tuple[list[tuple[float, float]], dict]:
    """The engine row: same decode through the continuous-batching engine
    (no HTTP). Returns (``reps`` measurements of (tokens/sec, ttft_ms p50
    over the batch), per-phase host-time breakdown in cumulative ms) —
    callers take the median of the runs (r4 verdict: a single rep's
    variance on a loaded 1-core host swamps the quantity reported). The
    phase dict carries ``prefill_ms`` / ``transfer_ms`` / ``emit_ms``
    from EngineStats: where the serving path actually spends its host
    time, so a hot-path regression shows up as a phase, not a vibe."""
    eng = Engine(
        params,
        cfg,
        EngineConfig(max_batch_size=batch,
                     max_seq_len=cfg.max_seq_len, page_size=PAGE,
                     decode_steps_per_tick=k_steps,
                     # reps must never pay a prefill compile for a group
                     # shape an earlier rep's arrival split missed
                     warm_prefill_buckets=2),
    )
    eng.start()
    try:
        eng.warmup()
        # warm the prefill bucket for prompt_len AND both adaptive
        # decode-window programs at the serving page bucket (warmup()
        # compiles them at the idle bucket; the timed reps must not pay
        # the compile): enough tokens to ride the window ladder up
        done = threading.Event()
        eng.submit(GenRequest(
            prompt=[1] * prompt_len, max_tokens=3 * k_steps + 2,
            sampling=SamplingParams(temperature=0.0),
            emit=lambda t, f: done.set() if f else None,
        ))
        done.wait(timeout=600)

        out: list[tuple[float, float]] = []
        for rep in range(reps):
            dones = [threading.Event() for _ in range(batch)]
            counts = [0] * batch
            first_at = [0.0] * batch

            def mk(i):
                def emit(tok, fin):
                    if tok >= 0:
                        if counts[i] == 0:
                            first_at[i] = time.perf_counter()
                        counts[i] += 1
                    if fin is not None:
                        dones[i].set()
                return emit

            t0 = time.perf_counter()
            for i in range(batch):
                # distinct prompts per rep: the refcounted prefix cache
                # must not let rep N reuse rep N-1's prefill pages
                eng.submit(GenRequest(
                    prompt=[1 + i + rep * batch] * prompt_len,
                    max_tokens=gen_tokens,
                    sampling=SamplingParams(temperature=0.0), emit=mk(i),
                ))
            for d in dones:
                d.wait(timeout=600)
            dt = time.perf_counter() - t0
            ttfts = sorted((f - t0) * 1000.0 for f in first_at if f > 0)
            ttft_p50 = ttfts[len(ttfts) // 2] if ttfts else -1.0
            out.append((sum(counts) / dt, ttft_p50))
        phases = {
            "prefill_ms": round(eng.stats.prefill_ms, 1),
            "transfer_ms": round(eng.stats.transfer_ms, 1),
            "emit_ms": round(eng.stats.emit_ms, 1),
            "first_emit_ms": round(eng.stats.first_emit_ms, 1),
        }
        return out, phases
    finally:
        eng.stop()


# -- through-the-gateway leg (the north star's numerator) -----------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _start_tpuserve_subproc(model_name: str, cfg, quantize: str,
                            batch: int, k_steps: int,
                            engine: dict | None = None,
                            page: int = PAGE,
                            param_dtype: str = "",
                            lora: dict | None = None,
                            tp: int = 1,
                            sp: int = 1,
                            env_extra: dict | None = None,
                            family: str = "llama"):
    """Serve `model_name` over the real tpuserve HTTP surface in its own
    process (benchmarks/serve_child.py) — the deployment topology. The
    in-thread variant below shares the bench client's GIL, which on a
    1-core host turns the serve legs into a GIL-convoy measurement
    (spread 27-36% in r4/r5). Returns (base_url, stop_fn).

    CPU-leg only: the child env pins JAX_PLATFORMS=cpu, so wiring this
    into the live-TPU suite would silently serve from CPU while the
    raw/engine legs run on chip — the assert keeps that impossible."""
    assert jax.default_backend() == "cpu", \
        "subproc serve leg is pinned to the CPU backend"
    cfg_keys = ["vocab_size", "dim", "n_layers", "n_heads",
                "n_kv_heads", "ffn_dim", "max_seq_len", "rope_theta"]
    if family == "mixtral":
        # the --ab moe child (ISSUE 18) ships the expert geometry too
        cfg_keys += ["n_experts", "experts_per_token", "capacity_factor"]
    spec = {
        "model": model_name, "family": family,
        "cfg": {k: getattr(cfg, k) for k in cfg_keys},
        "batch": batch, "page": page, "k": k_steps, "quantize": quantize,
        "engine": engine or {}, "param_dtype": param_dtype,
        "lora": lora or {}, "tp": tp, "sp": sp,
    }
    here = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.Popen(
        [sys.executable, os.path.join(here, "benchmarks", "serve_child.py"),
         json.dumps(spec)],
        cwd=here, stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {})),
    )
    import select

    port = None
    deadline = time.time() + 1200
    buf = ""
    fd = proc.stdout.fileno()
    os.set_blocking(fd, False)
    while time.time() < deadline:
        # select-based read: a wedged-but-alive child must trip the
        # deadline, not block readline() forever while holding the lock
        if proc.poll() is not None:
            raise RuntimeError("tpuserve child exited before listening")
        r, _, _ = select.select([fd], [], [], 5.0)
        if not r:
            continue
        buf += os.read(fd, 4096).decode(errors="replace")
        *complete, buf = buf.split("\n")  # parse full lines only — a
        # read boundary can split SERVE_PORT=12345 into a valid-looking
        # truncated number
        for line in complete:
            if line.startswith("SERVE_PORT="):
                port = int(line.split("=", 1)[1])
                break
        if port is not None:
            break
    if port is None:
        proc.kill()
        raise RuntimeError("tpuserve child never reported a port")

    def stop():
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()

    return f"http://127.0.0.1:{port}", stop


def _start_tpuserve(model_name: str, cfg, quantize: str, batch: int,
                    k_steps: int = K_STEPS):
    """Serve `model_name` (registered on the fly, random weights) over
    the real tpuserve HTTP surface in a background thread. Returns
    (base_url, stop_fn).

    The ONLY server in this file that can be on the chip: bench.py
    imports jax, so this process holds the chip, and a chip belongs to
    one process — every subprocess server (``_start_tpuserve_subproc``)
    is pinned to the CPU. The price is a GIL shared with the load
    generator; a benchmark that wants the server in its own process
    must keep its own process off jax (as chip_smoke.py does)."""
    from aiohttp import web

    from aigw_tpu.models.registry import (
        ModelSpec,
        _REGISTRY,
        register_model,
    )
    from aigw_tpu.tpuserve.server import TPUServeServer

    if model_name not in _REGISTRY:
        register_model(ModelSpec(model_name, "llama", cfg))

    holder: dict = {}
    started = threading.Event()
    stopping = threading.Event()

    def run():
        async def main():
            server = TPUServeServer(
                model=model_name,
                engine_cfg=EngineConfig(
                    max_batch_size=batch, max_seq_len=cfg.max_seq_len,
                    page_size=PAGE, decode_steps_per_tick=k_steps,
                ),
                quantize=quantize,
            )
            runner = web.AppRunner(server.app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            holder["port"] = site._server.sockets[0].getsockname()[1]
            started.set()
            while not stopping.is_set():
                await asyncio.sleep(0.2)
            await runner.cleanup()

        asyncio.run(main())

    t = threading.Thread(target=run, daemon=True)
    t.start()
    if not started.wait(timeout=1200):
        raise RuntimeError("tpuserve failed to start within 20min")

    def stop():
        stopping.set()
        t.join(timeout=30)

    return f"http://127.0.0.1:{holder['port']}", stop


def _start_gateway(upstream_url: str):
    """`aigw run` (the real CLI) in a subprocess, routing everything to
    the tpuserve upstream. Forced onto the CPU JAX backend so it can
    never contend for the TPU the engine holds. Returns (url, proc,
    cfg_path)."""
    import tempfile

    import yaml

    cfg = {
        "version": "v1",
        "backends": [
            {"name": "tpuserve", "schema": "OpenAI", "url": upstream_url},
        ],
        "routes": [
            {"name": "bench", "rules": [{"backends": ["tpuserve"]}]},
        ],
    }
    f = tempfile.NamedTemporaryFile("w", suffix=".yaml", delete=False)
    yaml.safe_dump(cfg, f)
    f.close()
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "aigw_tpu", "run", f.name,
         "--port", str(port)],
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
    )
    return f"http://127.0.0.1:{port}", proc, f.name


async def _wait_health(url: str, timeout_s: float = 60.0) -> None:
    import aiohttp

    deadline = time.time() + timeout_s
    async with aiohttp.ClientSession() as s:
        while time.time() < deadline:
            try:
                async with s.get(url + "/health") as r:
                    if r.status == 200:
                        return
            except aiohttp.ClientError:
                pass
            await asyncio.sleep(0.3)
    raise RuntimeError(f"{url}/health never came up")


async def _drive_stream(url: str, model: str, batch: int, prompt_len: int,
                        gen_tokens: int, tag: str = "") -> tuple[float, float]:
    """batch concurrent streaming chats; returns (tokens/sec, ttft_ms_p50).
    TTFT = first content delta on the wire; tok/s = usage-reported
    completion tokens / wall clock for the whole batch. ``tag`` makes
    prompts unique per leg — the engine's refcounted prefix cache would
    otherwise let the second leg reuse the first leg's prefill pages and
    invert the direct-vs-gateway comparison."""
    import aiohttp

    ttfts: list[float] = []
    totals: list[int] = []

    async def one(s: aiohttp.ClientSession, i: int, t0: float) -> None:
        body = (tag + chr(65 + i % 26)) * prompt_len
        payload = {
            "model": model,
            "messages": [
                {"role": "user", "content": body[:prompt_len]}
            ],
            "max_tokens": gen_tokens,
            "temperature": 0.0,
            "stream": True,
            "stream_options": {"include_usage": True},
            # Pin every sampled token to a visible ASCII byte ('a'): with
            # random weights, greedy output is mostly UTF-8 continuation
            # bytes that the windowed StreamingDecoder emits as EMPTY
            # pieces — no SSE chunk on the wire — so "first content
            # delta" TTFT was measured over the lottery subset of
            # requests that happened to produce visible text (the r4
            # "988ms gateway TTFT penalty" was this artifact, not the
            # gateway). The bias rides the real sampling path (engine
            # bias_row), so the measured pipeline is unchanged.
            "logit_bias": {"97": 100},
        }
        first = None
        usage = None
        ntok = 0
        async with s.post(url + "/v1/chat/completions",
                          json=payload) as resp:
            body_preview = b""
            if resp.status != 200:
                body_preview = await resp.read()
            assert resp.status == 200, (resp.status, body_preview[:500])
            while True:
                line = await resp.content.readline()
                if not line:
                    break
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[6:]
                if data == b"[DONE]":
                    break
                ev = json.loads(data)
                if ev.get("usage"):
                    usage = ev["usage"]
                ch = ev.get("choices") or []
                if ch and (ch[0].get("delta") or {}).get("content"):
                    if first is None:
                        first = (time.perf_counter() - t0) * 1000.0
                    ntok += 1
        if first is not None:
            ttfts.append(first)
        totals.append((usage or {}).get("completion_tokens") or ntok)

    timeout = aiohttp.ClientTimeout(total=1200)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        t0 = time.perf_counter()
        await asyncio.gather(*(one(s, i, t0) for i in range(batch)))
        wall = time.perf_counter() - t0
    ttfts.sort()
    p50 = ttfts[len(ttfts) // 2] if ttfts else -1.0
    return sum(totals) / wall, p50


def _median(xs: list[float]) -> float:
    s = sorted(xs)
    return s[len(s) // 2] if s else 0.0


def _spread(xs: list[float]) -> float:
    """(max - min) / median — the r4 verdict's harness-stability gauge.
    With ≥5 reps the extremes are trimmed first: on a 1-core host a
    single background event (log flush, GC pause) poisons one rep,
    and the question is whether the *typical* reps agree."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    if len(xs) >= 5:
        xs = xs[1:-1]
    m = _median(xs)
    return (xs[-1] - xs[0]) / m if m else 0.0


def gateway_numbers(model_name: str, cfg, quantize: str, batch=BATCH,
                    prompt_len=PROMPT_LEN, gen_tokens=GEN_TOKENS,
                    k_steps=K_STEPS, reps=3) -> dict:
    """The north-star numerator: tokens/sec and TTFT through
    `aigw run` → tpuserve → engine over streaming /v1/chat/completions,
    plus the same load sent directly to tpuserve (isolates gateway
    overhead from HTTP-serving overhead). ``reps`` interleaved
    direct/gateway trials; medians + spread (r4 verdict: best-of-2 on a
    loaded host reported noise as signal). tpuserve runs in-thread —
    the only way it can share this process's chip (see
    ``_start_tpuserve``)."""
    serve_url, stop_serve = _start_tpuserve(model_name, cfg, quantize,
                                            batch, k_steps)
    gw_url, proc, cfg_path = _start_gateway(serve_url)

    async def run() -> dict:
        await _wait_health(serve_url, 1200)
        await _wait_health(gw_url, 120)
        # warm every prefill bucket + gateway code path off the clock —
        # long enough to compile BOTH adaptive decode-window programs at
        # the serving page bucket (kmin fires young, K after steady)
        warm_gen = max(4, 3 * k_steps + 2)
        await _drive_stream(serve_url, model_name, batch, prompt_len,
                            warm_gen, tag="w")
        await _drive_stream(gw_url, model_name, batch, prompt_len,
                            warm_gen, tag="x")
        # interleave the legs so slow drift (CPU clocks, cache warmth)
        # cancels instead of flattering whichever leg runs later
        d_tps, d_ttft, g_tps, g_ttft = [], [], [], []
        for trial in range(reps):
            dt, dt_ttft = await _drive_stream(
                serve_url, model_name, batch, prompt_len, gen_tokens,
                tag=f"d{trial}")
            gt, gt_ttft = await _drive_stream(
                gw_url, model_name, batch, prompt_len, gen_tokens,
                tag=f"g{trial}")
            d_tps.append(dt)
            d_ttft.append(dt_ttft)
            g_tps.append(gt)
            g_ttft.append(gt_ttft)
        # server-side phase percentiles straight from the replica's
        # histograms (/state phase_percentiles, ISSUE 5) — p50/p95/p99
        # for TTFT and per-token latency come from the serving path's
        # own distributions, not recomputed from the client's samples
        phase_pct: dict = {}
        warm_fields: dict = {}
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(serve_url + "/state") as r:
                    st = await r.json()
                    phase_pct = st.get("phase_percentiles", {})
                    # warmup cost of the serve replica (ISSUE 6): the
                    # "collapsed compile surface = faster cold start"
                    # claim is measured, not asserted
                    warm_fields = {
                        "serve_warmup_ms": st.get("warmup_ms", 0.0),
                        "serve_warm_programs": st.get(
                            "warm_programs", 0),
                        "serve_attention_backend": st.get(
                            "attention_backend", ""),
                    }
        except aiohttp.ClientError:
            pass
        return {
            "gateway_tps": _median(g_tps),
            "gateway_ttft_ms_p50": _median(g_ttft),
            "direct_tps": _median(d_tps),
            "direct_ttft_ms_p50": _median(d_ttft),
            "gateway_tps_spread": round(_spread(g_tps), 3),
            "direct_tps_spread": round(_spread(d_tps), 3),
            "serve_phase_percentiles": phase_pct,
            **warm_fields,
        }

    try:
        return asyncio.run(run())
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        os.unlink(cfg_path)
        stop_serve()


# -- gateway_prefix leg: prefix-cache cold vs warm TTFT (ISSUE 3) --------

#: ByteTokenizer chat template: "<system>: {sys}\n<user>: " is the
#: token head every request shares — 19 chars of scaffolding + the
#: system prompt. 45 system chars → a 64-token shared prefix, page-
#: aligned at the leg's 16-token pages (4 reusable pages per request).
_PREFIX_SYS = "You are a terse assistant. Reply briefly, no".ljust(45, ".")
_PREFIX_PAGE = 16
_PREFIX_MIN_BUCKET = 32
# Leg model: a notch bigger than CPU_CFG so per-request device compute
# dominates the serving stack's fixed per-request cost (HTTP, probe,
# emit) — the quantity under test is prefill width, not overhead.
_PREFIX_CFG = llama.LlamaConfig(
    vocab_size=8192, dim=768, n_layers=6, n_heads=8, n_kv_heads=4,
    ffn_dim=2048, max_seq_len=512, rope_theta=10000.0,
)


async def _drive_prefix_one(s, url: str, model: str, user: str,
                            gen_tokens: int) -> float:
    """One sequential streaming chat; returns TTFT ms (first content
    delta on the wire — the logit-bias visible-token rig from
    _drive_stream)."""
    payload = {
        "model": model,
        "messages": [
            {"role": "system", "content": _PREFIX_SYS},
            {"role": "user", "content": user},
        ],
        "max_tokens": gen_tokens,
        "temperature": 0.0,
        "stream": True,
        "logit_bias": {"97": 100},
    }
    t0 = time.perf_counter()
    first = -1.0
    async with s.post(url + "/v1/chat/completions", json=payload) as resp:
        assert resp.status == 200, resp.status
        while True:
            line = await resp.content.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[6:]
            if data == b"[DONE]":
                break
            ev = json.loads(data)
            ch = ev.get("choices") or []
            if ch and (ch[0].get("delta") or {}).get("content"):
                if first < 0:
                    first = (time.perf_counter() - t0) * 1000.0
    return first


async def _get_state(s, url: str) -> dict:
    async with s.get(url + "/state") as resp:
        return await resp.json()


def prefix_cache_numbers(reps: int = 3, requests_per_rep: int = 6,
                         gen_tokens: int = 8) -> dict:
    """The ``gateway_prefix`` leg: chat requests sharing a 64-token
    system-prompt head (~96-token prompts, 18-char unique user tails)
    against TWO tpuserve replicas — prefix cache ON (warm: every
    request resumes prefill at the shared 64-token offset) and OFF
    (cold: full-prompt prefill every time). Reps INTERLEAVE the two
    servers (the ``--ab prefix_cache`` capture mode), so the ±15% host
    drift documented for this box cancels out of the warm/cold ratio.
    Sequential requests: the quantity under test is one request's
    prefill, not batch scheduling. Reports TTFT p50 and per-request
    device prefill_ms for both sides plus the warm replica's
    prefix_cache_hit_rate."""
    import aiohttp

    model_name = "bench-prefix-tiny"
    # num_pages sized to the leg (4 slots × ~7 pages + cached prefix +
    # headroom), NOT the auto max_batch×max_seq default: XLA:CPU's K/V
    # scatter walks the whole cache buffer, so an oversized pool buries
    # the padded-width signal under a fixed per-call cost on this host
    # f32 weights + KV on the CPU leg: XLA:CPU repacks bf16 weight
    # arguments to f32 EVERY call — a width-independent ~35ms tax that
    # buries the padded-width signal under test (bf16 is native on TPU)
    engine_common = {"min_prefill_bucket": _PREFIX_MIN_BUCKET,
                     "num_pages": 48, "max_queued_requests": 64,
                     "kv_cache_dtype": "float32"}
    url_on, stop_on = _start_tpuserve_subproc(
        model_name, _PREFIX_CFG, "", batch=4,
        k_steps=int(os.environ.get("AIGW_BENCH_CPU_K", "4")),
        engine=dict(engine_common, enable_prefix_cache=True),
        page=_PREFIX_PAGE, param_dtype="float32")
    url_off, stop_off = _start_tpuserve_subproc(
        model_name, _PREFIX_CFG, "", batch=4,
        k_steps=int(os.environ.get("AIGW_BENCH_CPU_K", "4")),
        engine=dict(engine_common, enable_prefix_cache=False),
        page=_PREFIX_PAGE, param_dtype="float32")

    async def run() -> dict:
        await _wait_health(url_on, 1200)
        await _wait_health(url_off, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off-the-clock warm pass: compiles every shape BOTH legs
            # dispatch (96-wide cold prefill, 32-wide suffix resume,
            # both decode-window programs) and primes the shared
            # prefix pages on the cache-on replica
            for url in (url_on, url_off):
                for i in range(3):
                    await _drive_prefix_one(
                        s, url, model_name, f"warmup tail {i:02d}..",
                        gen_tokens)
            warm_t, cold_t = [], []
            st_on0 = await _get_state(s, url_on)
            st_off0 = await _get_state(s, url_off)
            n = 0
            for rep in range(reps):
                # interleave A/B: cache-on then cache-off within each
                # rep so slow host drift cancels from the ratio
                for i in range(requests_per_rep):
                    user = f"q{rep}{i:02d} tail of chat..."[:18]
                    warm_t.append(await _drive_prefix_one(
                        s, url_on, model_name, user, gen_tokens))
                    cold_t.append(await _drive_prefix_one(
                        s, url_off, model_name, user, gen_tokens))
                    n += 1
            st_on1 = await _get_state(s, url_on)
            st_off1 = await _get_state(s, url_off)
        warm = _median([t for t in warm_t if t > 0])
        cold = _median([t for t in cold_t if t > 0])
        return {
            "prefix_warm_ttft_ms_p50": round(warm, 1),
            "prefix_cold_ttft_ms_p50": round(cold, 1),
            "prefix_warm_vs_cold": round(warm / cold, 4) if cold else 0.0,
            "prefix_warm_prefill_ms": round(
                (st_on1["prefill_ms"] - st_on0["prefill_ms"]) / n, 1),
            "prefix_cold_prefill_ms": round(
                (st_off1["prefill_ms"] - st_off0["prefill_ms"]) / n, 1),
            "prefix_cache_hit_rate": st_on1.get(
                "prefix_cache_hit_rate", 0.0),
            "prefix_warm_ttft_spread": round(_spread(warm_t), 3),
            "prefix_cold_ttft_spread": round(_spread(cold_t), 3),
            "prefix_ab_reps": reps * requests_per_rep,
        }

    try:
        return asyncio.run(run())
    finally:
        stop_on()
        stop_off()


# -- spec_decode leg: speculative decoding on/off A/B (ISSUE 4) ----------

#: the speculative children's max draft rung (ladder {0, 2, 4})
_SPEC_TOKENS = 4
_SPEC_PAGE = 16
# Leg model: the ~200MB-of-f32-weights prefix-leg config, NOT the tiny
# ratio model. Speculation pays when a decode step is dominated by
# streaming weights (the TPU regime, and on this host the regime any
# model bigger than L3 cache is in): a (D+1)-wide verify then costs
# about one step. The 0.02B ratio model fits in cache — compute-bound,
# a 5-wide verify costs ~5 steps, and the measured "speedup" would be
# an artifact of the wrong regime in both directions.


def _spec_ab_fields(st0: dict, st1: dict) -> dict:
    """Acceptance telemetry of the spec-on child over one capture,
    derived from /state deltas (pure — unit-tested by the bench
    smoke). ``accepted_per_step`` is emitted tokens per device decode
    step: plain decode is ≤ 1.0 by construction, accepted drafts push
    it above."""
    drafted = st1.get("spec_drafted", 0) - st0.get("spec_drafted", 0)
    accepted = st1.get("spec_accepted", 0) - st0.get("spec_accepted", 0)
    steps = st1.get("decode_steps", 0) - st0.get("decode_steps", 0)
    toks = (st1.get("tokens_generated", 0)
            - st0.get("tokens_generated", 0))
    return {
        "spec_accept_rate": (round(accepted / drafted, 4)
                             if drafted > 0 else 0.0),
        "drafted_tokens": drafted,
        "accepted_per_step": round(toks / steps, 3) if steps > 0 else 0.0,
        "spec_state_rebuilds": st1.get("state_rebuilds", 0),
    }


async def _drive_spec_one(s, url: str, model: str, content: str,
                          gen_tokens: int, bias: bool) -> tuple:
    """One sequential streaming chat; returns (duration_s, tokens).
    ``bias`` pins every sampled token to 'a' — the repetitive-decode
    workload where drafts fully accept; without it the model free-runs
    and proposed drafts reject (the forced low-acceptance workload)."""
    payload = {
        "model": model,
        "messages": [{"role": "user", "content": content}],
        "max_tokens": gen_tokens,
        "temperature": 0.0,
        "stream": True,
        "stream_options": {"include_usage": True},
    }
    if bias:
        payload["logit_bias"] = {"97": 100}
    t0 = time.perf_counter()
    usage = None
    ntok = 0
    async with s.post(url + "/v1/chat/completions", json=payload) as resp:
        assert resp.status == 200, resp.status
        while True:
            line = await resp.content.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[6:]
            if data == b"[DONE]":
                break
            ev = json.loads(data)
            if ev.get("usage"):
                usage = ev["usage"]
            ch = ev.get("choices") or []
            if ch and (ch[0].get("delta") or {}).get("content"):
                ntok += 1
    dur = time.perf_counter() - t0
    return dur, (usage or {}).get("completion_tokens") or ntok


def spec_decode_numbers(reps: int = 3, requests_per_rep: int = 4,
                        gen_tokens: int = 96) -> dict:
    """The ``spec_decode`` A/B leg: decode-heavy sequential streaming
    chats against THREE tpuserve children — spec-on for the repetitive
    workload, spec-on for the low-acceptance workload, and spec-off
    (serving both workloads as the control). Requests INTERLEAVE
    on/off within each rep (the prefix_cache capture pattern), so host
    drift cancels out of the tok/s ratios.

    Two spec-on children, not one: the engine-wide acceptance prior is
    traffic-dependent by design — mixing workloads through one child
    would measure the prior thrashing between regimes instead of each
    regime's steady state. The three criteria this leg reports against:
    accepted_per_step > 1.3 and spec-on/spec-off tok/s ≥ 1.15 on the
    repetitive leg; spec-on within 3% of spec-off on the forced
    low-acceptance leg (the adaptive ladder collapsed to D=0)."""
    import aiohttp

    model_name = "bench-spec-tiny"
    engine_common = {"min_prefill_bucket": 32, "num_pages": 64,
                     "max_queued_requests": 64,
                     "kv_cache_dtype": "float32"}
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    children = []

    def start(spec: int):
        url, stop = _start_tpuserve_subproc(
            model_name, _PREFIX_CFG, "", batch=4, k_steps=k,
            engine=dict(engine_common, spec_tokens=spec),
            page=_SPEC_PAGE, param_dtype="float32")
        children.append(stop)
        return url

    url_rep = start(_SPEC_TOKENS)   # spec-on, repetitive workload
    url_low = start(_SPEC_TOKENS)   # spec-on, low-acceptance workload
    url_off = start(0)              # control

    # repetitive: 'ababab…' prompt + bias→'a' output = the n-gram
    # source's best case. low-acceptance: the prompt's repeated tail
    # bigram FORCES proposals, the free-running random-weight greedy
    # stream rejects them (no proposals at all would never exercise
    # the ladder).
    rep_content = "ab" * 16
    low_content = "the quick brown fox xq jumps over wp lazy dogs xq"

    async def run() -> dict:
        await _wait_health(url_rep, 1200)
        await _wait_health(url_low, 1200)
        await _wait_health(url_off, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off the clock: compile every dispatched program (plain
            # lean/full, every draft rung the collapse path crosses)
            # and teach each spec child its workload's acceptance
            # prior — the low-acceptance criterion is about the
            # ladder's steady state, not its first-contact cost
            for url, content, b in ((url_rep, rep_content, True),
                                    (url_low, low_content, False),
                                    (url_off, rep_content, True),
                                    (url_off, low_content, False)):
                for _ in range(5):
                    await _drive_spec_one(s, url, model_name, content,
                                          gen_tokens, b)
            st_rep0 = await _get_state(s, url_rep)
            st_low0 = await _get_state(s, url_low)
            on_rep, off_rep, on_low, off_low = [], [], [], []
            for _rep in range(reps):
                for _i in range(requests_per_rep):
                    on_rep.append(await _drive_spec_one(
                        s, url_rep, model_name, rep_content,
                        gen_tokens, True))
                    off_rep.append(await _drive_spec_one(
                        s, url_off, model_name, rep_content,
                        gen_tokens, True))
                    on_low.append(await _drive_spec_one(
                        s, url_low, model_name, low_content,
                        gen_tokens, False))
                    off_low.append(await _drive_spec_one(
                        s, url_off, model_name, low_content,
                        gen_tokens, False))
            st_rep1 = await _get_state(s, url_rep)
            st_low1 = await _get_state(s, url_low)

        def tps(runs):
            return sum(n for _, n in runs) / sum(d for d, _ in runs)

        fields = _spec_ab_fields(st_rep0, st_rep1)
        low = _spec_ab_fields(st_low0, st_low1)
        on, off = tps(on_rep), tps(off_rep)
        lon, loff = tps(on_low), tps(off_low)
        return {
            "spec_on_tps": round(on, 1),
            "spec_off_tps": round(off, 1),
            "spec_speedup": round(on / off, 4) if off else 0.0,
            "spec_low_on_tps": round(lon, 1),
            "spec_low_off_tps": round(loff, 1),
            "spec_low_overhead": (round(1.0 - lon / loff, 4)
                                  if loff else 0.0),
            "spec_low_draft_len": st_low1.get("spec_draft_len", -1),
            "spec_low_accept_rate": low["spec_accept_rate"],
            "spec_ab_reps": reps * requests_per_rep,
            **fields,
        }

    try:
        return asyncio.run(run())
    finally:
        for stop in children:
            stop()


# -- ragged_prefill leg: attention-backend A/B (ISSUE 6) -----------------

# Leg model: tiny llama with a 2048 sequence budget so the mixed-length
# burst can carry a real long prompt. Page 64 keeps the ragged XLA
# fallback's per-page window loop short on the CPU host.
_RAGGED_CFG = llama.LlamaConfig(
    vocab_size=2048, dim=256, n_layers=4, n_heads=8, n_kv_heads=4,
    ffn_dim=512, max_seq_len=2048, rope_theta=10000.0,
)
_RAGGED_PAGE = 64
#: the burst's prompt lengths in TOKENS (byte tokenizer: chars + bos).
#: Five ~97-token chat-sized prompts — on the bucket ladder they share
#: the 128 bucket, so the batched group pads 5 rows to 8 — plus one
#: 1024-token prompt. Total 1509 tokens: the ragged pack runs ONE
#: 1536-wide program (chunk-residue padding only).
_RAGGED_MIX = (97, 97, 97, 97, 97, 1024)


async def _drive_ragged_burst(s, url: str, model: str,
                              gen_tokens: int, tag: str) -> list[float]:
    """Fire the mixed-length burst CONCURRENTLY (one coalesced
    admission) as /v1/completions streams; returns per-request TTFT ms
    (first content delta on the wire)."""

    async def one(n_tokens: int, i: int) -> float:
        text = (f"{tag}{i:02d}" + "x" * n_tokens)[: n_tokens - 1]
        payload = {
            "model": model,
            "prompt": text,
            "max_tokens": gen_tokens,
            "temperature": 0.0,
            "stream": True,
            "logit_bias": {"97": 100},
        }
        t0 = time.perf_counter()
        first = -1.0
        async with s.post(url + "/v1/completions", json=payload) as resp:
            assert resp.status == 200, resp.status
            while True:
                line = await resp.content.readline()
                if not line:
                    break
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[6:]
                if data == b"[DONE]":
                    break
                ev = json.loads(data)
                ch = ev.get("choices") or []
                if ch and ch[0].get("text"):
                    if first < 0:
                        first = (time.perf_counter() - t0) * 1000.0
        return first

    return list(await asyncio.gather(
        *(one(n, i) for i, n in enumerate(_RAGGED_MIX))))


def _ragged_ab_fields(st0: dict, st1: dict, prefix: str) -> dict:
    """One child's padding-tax + compile telemetry over a capture,
    derived from /state deltas (pure — unit-tested by the bench
    smoke)."""
    real = (st1.get("prefill_tokens_real", 0)
            - st0.get("prefill_tokens_real", 0))
    padded = (st1.get("prefill_tokens_padded", 0)
              - st0.get("prefill_tokens_padded", 0))
    return {
        f"{prefix}_padded_frac": (round(1.0 - real / padded, 4)
                                  if padded > 0 else 0.0),
        f"{prefix}_prefill_tokens": real,
        f"{prefix}_warm_programs": st1.get("warm_programs", 0),
        f"{prefix}_warmup_ms": st1.get("warmup_ms", 0.0),
        f"{prefix}_hot_compiles": (st1.get("xla_compiles", 0)
                                   - st0.get("xla_compiles", 0)),
    }


def ragged_prefill_numbers(reps: int = 3, gen_tokens: int = 8) -> dict:
    """The ``ragged_prefill`` A/B leg: the same mixed-length admission
    burst (five ~97-token prompts + one 1024-token prompt, fired
    concurrently so the engine coalesces them) against TWO tpuserve
    children — attention backend pallas-ragged vs xla-bucketed — with
    reps interleaved so host drift cancels. What it measures:

    - ``padded_frac`` per backend from the /state token counters: the
      bucketed ladder pays per-sequence bucket padding PLUS the
      batched group's pow2 row padding (5 same-bucket prompts pad to
      8 rows); the ragged pack pays only the token-budget chunk
      residue of the burst total.
    - warm-path compile surface: ``warm_programs`` after warmup (the
      ragged rung ladder vs every (bucket, group) shape), ``warmup_ms``
      cold-start cost, and zero hot compiles over the timed reps.
    - TTFT medians for reference. NOTE: on this CPU host the ragged
      child runs the XLA windowed fallback, whose page loop walks the
      full 2048-token window — absolute TTFT is NOT the claim here
      (the DMA-skip kernel only exists on TPU); padded compute and
      compile surface are."""
    import aiohttp

    model_name = "bench-ragged-tiny"
    engine_common = {
        "min_prefill_bucket": 32, "num_pages": 56,
        "max_queued_requests": 64, "kv_cache_dtype": "float32",
        "enable_prefix_cache": False,
        # the quantity under test is one coalesced burst's geometry —
        # give the 6 concurrent submits a wider idle-coalesce window so
        # event-loop scheduling jitter can't split the burst (both
        # children identical; the wait cancels from the A/B)
        "admission_coalesce_ms": 20.0,
    }
    url_rag, stop_rag = _start_tpuserve_subproc(
        model_name, _RAGGED_CFG, "", batch=8,
        k_steps=int(os.environ.get("AIGW_BENCH_CPU_K", "4")),
        engine=dict(engine_common, attention_backend="pallas-ragged"),
        page=_RAGGED_PAGE, param_dtype="float32")
    url_bkt, stop_bkt = _start_tpuserve_subproc(
        model_name, _RAGGED_CFG, "", batch=8,
        k_steps=int(os.environ.get("AIGW_BENCH_CPU_K", "4")),
        engine=dict(engine_common, attention_backend="xla-bucketed"),
        page=_RAGGED_PAGE, param_dtype="float32")

    async def run() -> dict:
        await _wait_health(url_rag, 1200)
        await _wait_health(url_bkt, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off-the-clock warm pass: compiles every shape either leg
            # dispatches beyond the warmed ladders (decode page-bucket
            # growth for the 1024-token stream, singleton group shapes)
            for url in (url_rag, url_bkt):
                await _drive_ragged_burst(s, url, model_name,
                                          gen_tokens, "w")
            st_rag0 = await _get_state(s, url_rag)
            st_bkt0 = await _get_state(s, url_bkt)
            rag_t, bkt_t = [], []
            for rep in range(reps):
                rag_t.extend(await _drive_ragged_burst(
                    s, url_rag, model_name, gen_tokens, f"r{rep}"))
                bkt_t.extend(await _drive_ragged_burst(
                    s, url_bkt, model_name, gen_tokens, f"b{rep}"))
            st_rag1 = await _get_state(s, url_rag)
            st_bkt1 = await _get_state(s, url_bkt)
        rag = _median([t for t in rag_t if t > 0])
        bkt = _median([t for t in bkt_t if t > 0])
        return {
            "ragged_ttft_ms_p50": round(rag, 1),
            "bucketed_ttft_ms_p50": round(bkt, 1),
            "ragged_vs_bucketed_ttft": (round(rag / bkt, 4)
                                        if bkt else 0.0),
            "ragged_backend": st_rag1.get("attention_backend", ""),
            "ragged_ttft_spread": round(_spread(rag_t), 3),
            "bucketed_ttft_spread": round(_spread(bkt_t), 3),
            "ragged_ab_reps": reps * len(_RAGGED_MIX),
            **_ragged_ab_fields(st_rag0, st_rag1, "ragged"),
            **_ragged_ab_fields(st_bkt0, st_bkt1, "bucketed"),
        }

    try:
        return asyncio.run(run())
    finally:
        stop_rag()
        stop_bkt()


# -- mesh leg: tensor-parallel serving A/B (ISSUE 10) ---------------------

#: tensor-parallel degree of the mesh child (virtual devices via
#: XLA_FLAGS on the child env — the flag must precede jax init, which
#: is why this leg NEEDS the subprocess topology)
_MESH_TP = 8
#: n_kv_heads divisible by _MESH_TP so the paged KV pool shards on
#: heads (one KV head per virtual device at tp=8)
_MESH_CFG = llama.LlamaConfig(
    vocab_size=2048, dim=256, n_layers=4, n_heads=8, n_kv_heads=8,
    ffn_dim=512, max_seq_len=512, rope_theta=10000.0,
)
_MESH_PAGE = 32
#: the timed burst: mixed prompt lengths in tokens (byte tokenizer),
#: fired concurrently so both children coalesce one admission
_MESH_MIX = (24, 48, 90, 90, 130, 200)


def _mesh_ab_fields(st0: dict, st1: dict, prefix: str) -> dict:
    """One child's mesh telemetry over a capture, derived from /state
    deltas (pure — unit-tested by the bench smoke). The parameter-split
    fraction is worst-device bytes × devices ÷ total: 1.0 = a perfect
    total/tp split, the bench's ±10% memory claim."""
    total = int(st1.get("param_bytes_total", 0) or 0)
    per = st1.get("param_bytes_per_device") or {}
    n = max(1, len(per))
    worst = max((int(v) for v in per.values()), default=0)
    return {
        f"{prefix}_devices": int(st1.get("mesh_devices", 1) or 1),
        f"{prefix}_param_bytes_total": total,
        f"{prefix}_param_bytes_per_device_max": worst,
        f"{prefix}_param_split_frac": (round(worst * n / total, 4)
                                       if total else 0.0),
        f"{prefix}_hot_compiles": (st1.get("xla_compiles", 0)
                                   - st0.get("xla_compiles", 0)),
        f"{prefix}_ici_bytes_per_token": int(
            st1.get("ici_bytes_per_token", 0) or 0),
    }


async def _drive_mesh_burst(s, url: str, model: str, gen_tokens: int,
                            tag: str) -> tuple[list[str], float]:
    """Fire the mixed burst concurrently as streaming /v1/completions;
    returns (per-request full texts in submit order, wall seconds).
    One slot samples (explicit seed — deterministic across children),
    one carries a repetition penalty, the rest run greedy: the mixed-
    feature batch whose streams must be byte-identical mesh vs single."""

    async def one(n_tokens: int, i: int) -> str:
        text = (f"{tag}{i:02d}" + "x" * n_tokens)[: n_tokens - 1]
        payload = {
            "model": model, "prompt": text, "max_tokens": gen_tokens,
            "temperature": 0.0, "stream": True,
        }
        if i == 1:
            payload.update(temperature=0.8, top_p=0.9, seed=1234 + i)
        elif i == 2:
            payload["frequency_penalty"] = 0.6
        out: list[str] = []
        async with s.post(url + "/v1/completions", json=payload) as resp:
            assert resp.status == 200, resp.status
            while True:
                line = await resp.content.readline()
                if not line:
                    break
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[6:]
                if data == b"[DONE]":
                    break
                ev = json.loads(data)
                ch = ev.get("choices") or []
                if ch and ch[0].get("text"):
                    out.append(ch[0]["text"])
        return "".join(out)

    t0 = time.perf_counter()
    texts = list(await asyncio.gather(
        *(one(n, i) for i, n in enumerate(_MESH_MIX))))
    return texts, time.perf_counter() - t0


def mesh_numbers(reps: int = 3, gen_tokens: int = 24) -> dict:
    """The ``mesh`` A/B leg (ISSUE 10): the SAME seeded mixed-feature
    traffic against TWO tpuserve children — tp=8 over 8 virtual CPU
    devices (XLA_FLAGS on the child env) vs single-device — f32 params
    and KV so greedy streams are deterministic. The portable claims:

    - **byte-identity**: every stream matches between the children
      (the sharded engine is the same engine);
    - **memory split**: per-device parameter bytes ≈ total/tp (±10%),
      measured from real shard layouts on /state;
    - **compile surface**: zero hot XLA compiles on the warmed mesh
      path over the timed reps.

    ``mesh_vs_single`` throughput is reported with spreads but is
    INFORMATIONAL on CPU: 8 virtual devices time-slice one host core,
    so the ratio measures partitioning overhead, not ICI speedup."""
    import aiohttp

    model_name = "bench-mesh-tiny"
    engine_common = {
        "min_prefill_bucket": 32, "kv_cache_dtype": "float32",
        "max_queued_requests": 64, "admission_coalesce_ms": 20.0,
        # decode programs re-trace per page bucket: warm the rungs the
        # mixed burst reaches (≤ 8 pages) so the timed reps stay
        # compile-free on BOTH children
        "warm_decode_buckets": 4,
    }
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    url_mesh, stop_mesh = _start_tpuserve_subproc(
        model_name, _MESH_CFG, "", batch=8, k_steps=k,
        engine=dict(engine_common), page=_MESH_PAGE,
        param_dtype="float32", tp=_MESH_TP,
        env_extra={"XLA_FLAGS":
                   f"--xla_force_host_platform_device_count={_MESH_TP}"})
    url_one, stop_one = _start_tpuserve_subproc(
        model_name, _MESH_CFG, "", batch=8, k_steps=k,
        engine=dict(engine_common), page=_MESH_PAGE,
        param_dtype="float32")

    async def run() -> dict:
        await _wait_health(url_mesh, 1200)
        await _wait_health(url_one, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off-the-clock warm pass (page-bucket growth, singleton
            # shapes the warmed ladder doesn't cover)
            for url in (url_mesh, url_one):
                await _drive_mesh_burst(s, url, model_name, gen_tokens,
                                        "w")
            st_mesh0 = await _get_state(s, url_mesh)
            st_one0 = await _get_state(s, url_one)
            identical = True
            mesh_tps, one_tps = [], []
            for rep in range(reps):
                m_texts, m_wall = await _drive_mesh_burst(
                    s, url_mesh, model_name, gen_tokens, f"r{rep}")
                o_texts, o_wall = await _drive_mesh_burst(
                    s, url_one, model_name, gen_tokens, f"r{rep}")
                identical &= m_texts == o_texts
                n_tok = gen_tokens * len(_MESH_MIX)
                mesh_tps.append(n_tok / m_wall)
                one_tps.append(n_tok / o_wall)
            st_mesh1 = await _get_state(s, url_mesh)
            st_one1 = await _get_state(s, url_one)
        m, o = _median(mesh_tps), _median(one_tps)
        return {
            "mesh_tp": _MESH_TP,
            "mesh_byte_identical": identical,
            "mesh_tokens_per_sec": round(m, 2),
            "single_tokens_per_sec": round(o, 2),
            "mesh_vs_single": round(m / o, 4) if o else 0.0,
            "mesh_tps_spread": round(_spread(mesh_tps), 3),
            "single_tps_spread": round(_spread(one_tps), 3),
            "mesh_axes": {a: n for a, n in (
                st_mesh1.get("mesh_axes") or {}).items() if n > 1},
            "mesh_ab_reps": reps * len(_MESH_MIX),
            **_mesh_ab_fields(st_mesh0, st_mesh1, "mesh"),
            **_mesh_ab_fields(st_one0, st_one1, "single"),
        }

    try:
        return asyncio.run(run())
    finally:
        stop_mesh()
        stop_one()


# -- lora leg: multi-LoRA adapter serving A/B (ISSUE 7) -------------------

#: adapters in the child's zoo / device rows for them. rows < zoo so the
#: churn phase exercises a real evict+reload; the TIMED mix rotates only
#: the first `_LORA_ROWS` adapters (all resident after the warm pass) —
#: the parity claim is about the zero-row batch, not LRU thrash.
_LORA_ZOO = 5
_LORA_ROWS = 4


def _lora_ab_fields(st0: dict, st1: dict) -> dict:
    """Adapter-subsystem telemetry over a capture, derived from /state
    deltas (pure — unit-tested by the bench smoke)."""
    return {
        "adapter_loads": (st1.get("adapter_loads", 0)
                          - st0.get("adapter_loads", 0)),
        "adapter_evictions": (st1.get("adapter_evictions", 0)
                              - st0.get("adapter_evictions", 0)),
        "adapters_resident": len(st1.get("adapters_resident") or ()),
        "lora_hot_compiles": (st1.get("xla_compiles", 0)
                              - st0.get("xla_compiles", 0)),
    }


def lora_numbers(reps: int = 3, requests_per_rep: int = 4,
                 gen_tokens: int = 64) -> dict:
    """The ``lora`` A/B leg: ONE tpuserve child serving a 5-adapter zoo
    over 4 device rows; decode-heavy sequential streaming chats
    interleave adapter-mix traffic (model ``<base>:t{i}``, rotating
    adapters so the batch's adapter_idx mix changes every request)
    with base-only traffic (the zero-row control) — host drift cancels
    from the tok/s ratio. The criteria this leg reports against:

    - ``lora_mix_vs_base`` ≥ 0.95: an adapter-mix request stream is
      within 5% tok/s of base-only serving on the SAME engine (one
      compiled program serves any mix; the zero row is an adapter row,
      so the control pays the identical gather).
    - ``lora_hot_compiles`` == 0 over the timed reps AND the churn
      phase (hot load of a non-resident adapter + evict/reload swap a
      row's CONTENT, never its program).
    - ``adapter_loads``/``adapter_evictions`` > 0 in the churn phase:
      the subsystem actually cycled rows, it didn't just serve a
      static stack."""
    import aiohttp

    model_name = "bench-lora-tiny"
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    url, stop = _start_tpuserve_subproc(
        model_name, _PREFIX_CFG, "", batch=4, k_steps=k,
        engine={"min_prefill_bucket": 32, "num_pages": 64,
                "max_queued_requests": 64, "kv_cache_dtype": "float32"},
        page=_SPEC_PAGE, param_dtype="float32",
        lora={"adapters": _LORA_ZOO, "rank": 8, "slots": _LORA_ROWS})
    content = "ab" * 16

    async def run() -> dict:
        await _wait_health(url, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off the clock: hot-load the timed rotation's adapters and
            # compile every dispatched shape (decode page bucket, the
            # prefill rung, the adapter-load row scatters ride warmup)
            for i in range(_LORA_ROWS):
                await _drive_spec_one(s, url, f"{model_name}:t{i}",
                                      content, gen_tokens, True)
            await _drive_spec_one(s, url, model_name, content,
                                  gen_tokens, True)
            st0 = await _get_state(s, url)
            mix, base = [], []
            for _rep in range(reps):
                for i in range(requests_per_rep):
                    mix.append(await _drive_spec_one(
                        s, url, f"{model_name}:t{i % _LORA_ROWS}",
                        content, gen_tokens, True))
                    base.append(await _drive_spec_one(
                        s, url, model_name, content, gen_tokens, True))
            st1 = await _get_state(s, url)
            # churn phase (adapter-mix change): t4 is NOT resident —
            # admitting it hot-loads over the LRU row; re-asking the
            # evicted adapter reloads it. Still zero compiles.
            for m in (f"{model_name}:t{_LORA_ROWS}", f"{model_name}:t0",
                      f"{model_name}:t1"):
                await _drive_spec_one(s, url, m, content,
                                      gen_tokens, True)
            st2 = await _get_state(s, url)

        def tps(runs):
            return sum(n for _, n in runs) / sum(d for d, _ in runs)

        mix_tps, base_tps = tps(mix), tps(base)
        churn = _lora_ab_fields(st1, st2)
        return {
            "lora_mix_tps": round(mix_tps, 1),
            "lora_base_tps": round(base_tps, 1),
            "lora_mix_vs_base": (round(mix_tps / base_tps, 4)
                                 if base_tps else 0.0),
            "lora_mix_spread": round(_spread(
                [n / d for d, n in mix if d > 0]), 3),
            "lora_ab_reps": reps * requests_per_rep,
            "lora_zoo": _LORA_ZOO,
            "lora_rows": st2.get("adapter_rows", 0),
            # timed-rep telemetry: loads/evictions should be ZERO here
            # (the rotation is resident) and compiles zero everywhere
            **_lora_ab_fields(st0, st1),
            "lora_churn_loads": churn["adapter_loads"],
            "lora_churn_evictions": churn["adapter_evictions"],
            "lora_churn_hot_compiles": churn["lora_hot_compiles"],
        }

    try:
        return asyncio.run(run())
    finally:
        stop()


# -- structured leg: grammar-constrained decoding A/B (ISSUE 9) -----------

#: the leg's response_format schema: ONE bounded string field, so the
#: whole output length is structurally bounded (~53 chars) and every
#: completed constrained response MUST parse + validate — and grammar
#: transitions (each ~2 rollback windows on the random-weight model,
#: where the model never anticipates structure) stay a small fraction
#: of the content tokens, which is what a real model's traffic looks
#: like at the window level
_STRUCT_SCHEMA = {
    "type": "object",
    "properties": {"report": {"type": "string", "maxLength": 40}},
    "required": ["report"],
    "additionalProperties": False,
}
#: worst-case constrained output: {"report":"<40>"} = 53 tokens (byte
#: tokenizer) + EOS; plain traffic generates the same volume so the
#: phase throughputs compare token-for-token
_STRUCT_GEN = 54
_STRUCT_MAX = 80


def _structured_ab_fields(st0: dict, st1: dict) -> dict:
    """Constraint telemetry of one timed phase from /state deltas —
    pure so test_bench_smoke can unit-test the field derivation."""
    return {
        "structured_requests": (st1.get("constraint_requests", 0)
                                - st0.get("constraint_requests", 0)),
        "structured_rollbacks": (st1.get("constraint_rollbacks", 0)
                                 - st0.get("constraint_rollbacks", 0)),
        "structured_mask_updates": (
            st1.get("constraint_mask_updates", 0)
            - st0.get("constraint_mask_updates", 0)),
        "structured_hot_compiles": (st1.get("xla_compiles", 0)
                                    - st0.get("xla_compiles", 0)),
        "structured_grammars": st1.get("constraint_grammars", 0),
    }


async def _drive_struct_openloop(s, url: str, model_name: str,
                                 trace: list[dict]) -> tuple:
    """Fire one open-loop arrival schedule of chat requests (items:
    {t, constrained}) — arrival-time-fired regardless of completions.
    Returns (wall_s, total_completion_tokens, [constrained texts])."""
    texts: list = []
    totals: list[int] = []

    async def one(item: dict, t0: float) -> None:
        delay = t0 + item["t"] - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        body = {
            "model": model_name,
            "messages": [{"role": "user",
                          "content": f"arrival {item['i']:03d} hi"}],
            "temperature": 0.0,
            "logit_bias": {"97": 100},
        }
        if item["constrained"]:
            body["max_tokens"] = _STRUCT_MAX
            body["response_format"] = {
                "type": "json_schema",
                "json_schema": {"name": "r", "schema": _STRUCT_SCHEMA}}
        else:
            body["max_tokens"] = _STRUCT_GEN
        async with s.post(url + "/v1/chat/completions",
                          json=body) as resp:
            assert resp.status == 200, (resp.status,
                                        (await resp.read())[:300])
            got = await resp.json()
        totals.append(got["usage"]["completion_tokens"])
        if item["constrained"]:
            texts.append(got["choices"][0]["message"]["content"])

    t0 = time.perf_counter()
    await asyncio.gather(*(one(it, t0) for it in trace))
    wall = time.perf_counter() - t0
    return wall, sum(totals), texts


def structured_numbers(reps: int = 2, arrivals: int = 12,
                       constrained_frac: float = 0.25) -> dict:
    """The ``--ab structured`` leg (ISSUE 9): the same seeded open-loop
    arrival schedule against ONE tpuserve child (speculation on — the
    batch genuinely mixes constrained/plain/speculating slots), once
    with ``constrained_frac`` of arrivals asking for json_schema output
    and once all-plain at matched token volume. Criteria: every
    completed constrained response parses AND validates against the
    requested schema; zero hot XLA compiles across the timed phases;
    mixed/plain throughput ratio prices the constraint bookkeeping
    (mask row updates + rollback windows). Per-request byte-identity of
    unconstrained traffic is the f32-rig test's claim
    (tests/test_constrained_serving.py), not re-measured here."""
    import random as _random

    import aiohttp

    model_name = "bench-struct-tiny"
    # f32 params + f32 KV like the prefix leg: XLA:CPU repacks bf16
    # weight arguments per call, and an f32→bf16 K/V scatter is a
    # deprecated implicit cast (bf16 stays the default on TPU)
    url, stop = _start_tpuserve_subproc(
        model_name, CPU_CFG, "", batch=8,
        k_steps=int(os.environ.get("AIGW_BENCH_CPU_K", "4")),
        engine={"spec_tokens": 4, "kv_cache_dtype": "float32"},
        param_dtype="float32")

    def mk_trace(seed: int, constrained: bool) -> list[dict]:
        # seeded staggered arrivals (~0.25s mean gap): open-loop — the
        # schedule never waits on completions, so slots stay saturated
        # and the ratio measures steady-state per-window overhead. The
        # SAME seed yields the same arrival times for both phases;
        # constrained flags land on a seeded random subset.
        rng = _random.Random(seed)
        times, t = [], 0.0
        for _ in range(arrivals):
            times.append(t)
            t += rng.uniform(0.05, 0.45)
        n_con = round(arrivals * constrained_frac) if constrained else 0
        con = set(rng.sample(range(arrivals), n_con))
        return [{"t": times[i], "i": i, "constrained": i in con}
                for i in range(arrivals)]

    async def run() -> dict:
        await _wait_health(url, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off-the-clock warm pass: compiles the decode page bucket,
            # prefill rung, and the mask-update program; caches the
            # grammar
            await _drive_struct_openloop(s, url, model_name, [
                {"t": 0.0, "i": 0, "constrained": True},
                {"t": 0.0, "i": 1, "constrained": False},
            ])
            st0 = await _get_state(s, url)
            mixed, plain, all_texts = [], [], []
            for rep in range(reps):
                w, n, texts = await _drive_struct_openloop(
                    s, url, model_name, mk_trace(1000 + rep, True))
                mixed.append((w, n))
                all_texts.extend(texts)
                w, n, _ = await _drive_struct_openloop(
                    s, url, model_name, mk_trace(1000 + rep, False))
                plain.append((w, n))
            st1 = await _get_state(s, url)
        ok = sum(1 for t in all_texts if _struct_valid(t))
        ratios = [(nm / wm) / (np_ / wp)
                  for (wm, nm), (wp, np_) in zip(mixed, plain)
                  if wm > 0 and wp > 0 and np_ > 0]
        return {
            "structured_mixed_tps": round(
                sum(n for _, n in mixed) / sum(w for w, _ in mixed), 1),
            "structured_plain_tps": round(
                sum(n for _, n in plain) / sum(w for w, _ in plain), 1),
            "structured_mixed_vs_plain": round(_median(ratios), 4),
            "structured_ratio_spread": round(_spread(ratios), 3),
            "structured_valid_frac": (round(ok / len(all_texts), 4)
                                      if all_texts else 0.0),
            "structured_constrained_responses": len(all_texts),
            "structured_ab_reps": reps,
            **_structured_ab_fields(st0, st1),
        }

    try:
        return asyncio.run(run())
    finally:
        stop()


def _struct_valid(text: str) -> bool:
    from aigw_tpu.tpuserve.constrain import validate_instance

    try:
        return validate_instance(_STRUCT_SCHEMA, json.loads(text))
    except ValueError:
        return False


# -- open-loop load generation + fleet legs (ISSUE 8; ROADMAP 5) ----------

def _poisson_trace(seed: int, n: int, rate_hz: float,
                   prompt_lens=(48, 96, 160), gen_lens=(8, 16, 24),
                   tenants=("",), burst_frac=0.25) -> list[dict]:
    """TokenSim-style open-loop arrival trace: Poisson inter-arrivals
    with a ``burst_frac`` share of zero-gap (bursty) arrivals, mixed
    prompt/output lengths and tenants. Seeded — the SAME trace drives
    both sides of an A/B so the comparison is over identical load."""
    import random

    rng = random.Random(seed)
    t = 0.0
    out = []
    for i in range(n):
        gap = (0.0 if (i > 0 and rng.random() < burst_frac)
               else rng.expovariate(rate_hz))
        t += gap
        out.append({
            "at": t,
            "prompt_len": rng.choice(list(prompt_lens)),
            "gen": rng.choice(list(gen_lens)),
            "tenant": rng.choice(list(tenants)),
            "i": i,
        })
    return out


#: histogram parsing generalized into the live gateway monitor (ISSUE
#: 12, obs/slomon.py) — the bench keeps its old name as an alias; the
#: shared parser additionally tolerates extra labels, so the gateway's
#: replica-labeled /fleet/metrics federation parses with the same code
_parse_hist_buckets = slomon.parse_hist_buckets


def _sum_hists(hists: list[dict]) -> dict[str, int]:
    out: dict[str, int] = {}
    for h in hists:
        for le, c in h.items():
            out[le] = out.get(le, 0) + c
    return out


def _goodput_fields(h0: dict, h1: dict, slo_ms: float, arrivals: int,
                    shed: int, prefix: str) -> dict:
    """Goodput-under-SLO over one capture window, computed from the
    SERVER-SIDE TTFT histograms (cumulative bucket deltas), not client
    clocks: under_slo = requests whose engine-observed TTFT landed in a
    bucket ≤ the SLO. goodput = under_slo / arrivals — shed and
    never-served requests count against goodput by construction.
    The bucket math is the shared slomon implementation the gateway's
    live burn-rate monitor runs on the same histograms."""
    total = h1.get("+Inf", 0) - h0.get("+Inf", 0)
    u = (slomon.under_slo_count(h1, slo_ms)
         - slomon.under_slo_count(h0, slo_ms))
    return {
        f"{prefix}_arrivals": arrivals,
        f"{prefix}_served": total,
        f"{prefix}_shed": shed,
        f"{prefix}_under_slo": u,
        f"{prefix}_goodput": round(u / arrivals, 4) if arrivals else 0.0,
    }


async def _get_text(s, url: str, path: str) -> str:
    async with s.get(url + path) as resp:
        return (await resp.read()).decode()


async def _ttft_hists(s, urls: list[str]) -> dict[str, int]:
    """Summed server-side TTFT histogram over a replica set."""
    hs = []
    for u in urls:
        hs.append(_parse_hist_buckets(
            await _get_text(s, u, "/metrics"), "tpuserve_ttft_hist_ms"))
    return _sum_hists(hs)


async def _drive_openloop(s, url: str, model: str, trace: list[dict],
                          tag: str = "",
                          payload_extra: dict | None = None) -> dict:
    """Fire the trace open-loop (each request at its arrival time, not
    gated on completions) as streaming /v1/completions; returns
    client-side outcome counts. Server-side goodput comes from the
    replica histograms — the client numbers here are for shed
    accounting and sanity, not latency claims. ``payload_extra`` merges
    extra request fields (the metering leg opts streams into the usage
    tail frame with it — the meter rides that frame to the gateway)."""
    res = {"completed": 0, "shed": 0, "shed_retry_after": 0,
           "errors": 0, "client_ttft_ms": []}

    async def one(item: dict, t0: float) -> None:
        delay = t0 + item["at"] - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        n = item["prompt_len"]
        text = (f"{tag}{item['i']:03d}" + "y" * n)[: n - 1]
        payload = {
            "model": model, "prompt": text,
            "max_tokens": item["gen"], "temperature": 0.0,
            "stream": True, "logit_bias": {"97": 100},
        }
        if payload_extra:
            payload.update(payload_extra)
        headers = ({"x-aigw-tenant": item["tenant"]}
                   if item["tenant"] else {})
        sent = time.perf_counter()
        try:
            async with s.post(url + "/v1/completions", json=payload,
                              headers=headers) as resp:
                if resp.status == 429:
                    res["shed"] += 1
                    if resp.headers.get("retry-after"):
                        res["shed_retry_after"] += 1
                    await resp.read()
                    return
                if resp.status != 200:
                    res["errors"] += 1
                    await resp.read()
                    return
                first = -1.0
                async for line in resp.content:
                    line = line.strip()
                    if first < 0 and line.startswith(b"data: ") \
                            and b'"text"' in line:
                        first = 1e3 * (time.perf_counter() - sent)
                res["completed"] += 1
                if first > 0:
                    res["client_ttft_ms"].append(first)
        except (aiohttp.ClientError, asyncio.TimeoutError):
            res["errors"] += 1

    import aiohttp  # noqa: F811 — bench imports lazily by convention
    t0 = time.perf_counter()
    await asyncio.gather(*(one(it, t0) for it in trace))
    return res


def _start_gateway_cfg(backend_extra: dict, endpoints: list[str],
                       top_extra: dict | None = None):
    """`aigw run` subprocess over a replica POOL with arbitrary backend
    knobs (picker_mode / slo_ttft_ms / migration …) plus optional
    TOP-LEVEL config keys (usage block, llm_request_costs). Returns
    (url, stop_fn)."""
    import tempfile

    import yaml

    cfg = {
        "version": "v1",
        "backends": [dict(
            {"name": "pool", "schema": "OpenAI",
             "endpoints": endpoints, "picker_poll_interval": 0.2},
            **backend_extra)],
        "routes": [{"name": "bench", "rules": [{"backends": ["pool"]}]}],
    }
    if top_extra:
        cfg.update(top_extra)
    f = tempfile.NamedTemporaryFile("w", suffix=".yaml", delete=False)
    yaml.safe_dump(cfg, f)
    f.close()
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "aigw_tpu", "run", f.name,
         "--port", str(port)],
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
    )

    def stop():
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        os.unlink(f.name)

    return f"http://127.0.0.1:{port}", stop


def slo_routing_numbers(arrivals: int = 36, reps: int = 3) -> dict:
    """The ``slo_routing`` A/B leg: the SAME seeded open-loop arrival
    trace against two gateway configurations over the same two-replica
    pool — picker_mode "slo" (predictive TTFT routing + shed) vs
    "static" (the classic score sum) — goodput-under-SLO computed from
    the replicas' server-side TTFT histograms. The pool is deliberately
    heterogeneous: replica A is a PREFILL straggler — every prompt pads
    to the full 512-token bucket (one rung, min bucket = max seq: the
    shape a degraded or misconfigured replica takes in production) —
    which static occupancy/queue scoring cannot see until queues have
    already built, while the phase histograms price it into every
    prediction up front. Reps interleave the two gateways over fresh
    trace seeds; both gateways see identical load."""
    import aiohttp

    model_name = "bench-slo-tiny"
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    engine_common = {"num_pages": 64, "max_queued_requests": 64}
    # replica A: the prefill straggler; replica B: the healthy sibling
    url_a, stop_a = _start_tpuserve_subproc(
        model_name, CPU_CFG, "", batch=2, k_steps=k,
        engine=dict(engine_common, min_prefill_bucket=512,
                    prefill_bucket_rungs=1),
        page=16)
    url_b, stop_b = _start_tpuserve_subproc(
        model_name, CPU_CFG, "", batch=2, k_steps=k,
        engine=dict(engine_common, min_prefill_bucket=32),
        page=16)
    addrs = [u[len("http://"):] for u in (url_a, url_b)]

    async def run() -> dict:
        await _wait_health(url_a, 1200)
        await _wait_health(url_b, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # calibrate the SLO budget off the healthy replica's
            # unloaded TTFT (sequential, direct). The same pass also
            # seeds BOTH replicas' phase histograms — the slo gateway
            # must know A is a prefill straggler from its first poll,
            # not discover it by routing the first rep's traffic there
            # (a replica with no data predicts 0 = idle)
            cal = []
            for i in range(3):
                tr = [{"at": 0.0, "prompt_len": 96, "gen": 4,
                       "tenant": "", "i": i}]
                r = await _drive_openloop(s, url_b, model_name, tr,
                                          tag=f"c{i}")
                cal.extend(r["client_ttft_ms"])
                await _drive_openloop(s, url_a, model_name, tr,
                                      tag=f"a{i}")
            # off the clock: drive every prompt/gen shape the timed
            # traces use DIRECTLY at each child, so rep 0 never pays an
            # XLA compile mid-capture (the first capture previously
            # measured compile stalls, not routing)
            for url, tg in ((url_b, "wb"), (url_a, "wa")):
                warm = _poisson_trace(seed=999, n=12, rate_hz=4.0,
                                      gen_lens=(2, 4, 6))
                await _drive_openloop(s, url, model_name, warm, tag=tg)
            base = _median(cal) if cal else 500.0
            slo_ms = max(300.0, 4.0 * base)

            out: dict = {"slo_routing_slo_ms": round(slo_ms, 1),
                         "slo_routing_reps": reps}
            acc: dict[str, list] = {"slo": [], "static": []}
            sheds = {"slo": 0, "static": 0}
            retry_ok = 0
            for rep in range(reps):
                for mode in ("slo", "static"):
                    extra = {"picker_mode": mode} if mode == "slo" \
                        else {}
                    if mode == "slo":
                        extra["slo_ttft_ms"] = slo_ms
                        # short burn windows so the live monitor closes
                        # several during the trace — the fleet fields
                        # below carry real burn data, not -1 sentinels
                        extra["slo_window_s"] = 5.0
                    gw, stop_gw = _start_gateway_cfg(extra, addrs)
                    try:
                        await _wait_health(gw, 120)
                        # let the picker poll real telemetry first
                        await asyncio.sleep(1.0)
                        trace = _poisson_trace(
                            seed=1000 + rep, n=arrivals, rate_hz=1.5,
                            gen_lens=(2, 4, 6))
                        h0 = await _ttft_hists(s, [url_a, url_b])
                        res = await _drive_openloop(
                            s, gw, model_name, trace,
                            tag=f"{mode[0]}{rep}")
                        h1 = await _ttft_hists(s, [url_a, url_b])
                        g = _goodput_fields(
                            h0, h1, slo_ms, arrivals, res["shed"],
                            prefix="x")
                        acc[mode].append(g["x_goodput"])
                        sheds[mode] += res["shed"]
                        retry_ok += res["shed_retry_after"]
                        if mode == "slo" and rep == reps - 1:
                            # fleet observability plane (ISSUE 12):
                            # carry the aggregated fleet snapshot +
                            # live burn-rate fields into the capture
                            async with s.get(gw + "/fleet/state") as r:
                                out.update(_fleet_obs_fields(
                                    await r.json(), "slo_fleet"))
                    finally:
                        stop_gw()
            # PAIRED comparison: rep i's slo and static captures ran
            # the same seeded trace, so per-rep goodput ratios cancel
            # trace difficulty and host drift; the median ratio is the
            # claim, the pooled goodputs are context
            ratios = [s_g / st_g for s_g, st_g in
                      zip(acc["slo"], acc["static"]) if st_g > 0]
            slo_g = sum(acc["slo"]) / len(acc["slo"])
            static_g = sum(acc["static"]) / len(acc["static"])
            out.update({
                "slo_goodput": round(slo_g, 4),
                "static_goodput": round(static_g, 4),
                "slo_vs_static_goodput": (
                    round(_median(ratios), 4) if ratios
                    else (round(slo_g / static_g, 4) if static_g
                          else 0.0)),
                "slo_goodput_by_rep": [round(x, 4) for x in acc["slo"]],
                "static_goodput_by_rep": [round(x, 4)
                                          for x in acc["static"]],
                "slo_shed": sheds["slo"],
                "static_shed": sheds["static"],
                "slo_shed_retry_after": retry_ok,
                "slo_goodput_spread": round(_spread(acc["slo"]), 3),
                "static_goodput_spread": round(
                    _spread(acc["static"]), 3),
            })
            return out

    try:
        return asyncio.run(run())
    finally:
        stop_a()
        stop_b()


# -- fleet observability plane (ISSUE 12) ---------------------------------

def _fleet_obs_fields(snapshot: dict, prefix: str = "fleet") -> dict:
    """Flatten a gateway /fleet/state payload into bench JSON fields —
    future BENCH_r* captures carry fleet-level telemetry (health
    counts, worst pressure, live burn rate), not just client-side
    ratios (unit-tested in tests/test_bench_smoke.py)."""
    ru = snapshot.get("fleet") or {}
    slo: dict = {}
    health: dict[str, str] = {}
    for b in (snapshot.get("backends") or {}).values():
        slo = slo or (b.get("slo") or {})
        for addr, r in (b.get("replicas") or {}).items():
            health[addr] = (r.get("health") or {}).get("state", "?")
    return {
        f"{prefix}_replicas_up": int(ru.get("replicas_up", 0)),
        f"{prefix}_replicas_degraded": int(
            ru.get("replicas_degraded", 0)),
        f"{prefix}_replicas_down": int(ru.get("replicas_down", 0)),
        f"{prefix}_slots_free": int(ru.get("slots_free", 0)),
        f"{prefix}_slots_total": int(ru.get("slots_total", 0)),
        f"{prefix}_kv_occupancy_worst": float(
            ru.get("kv_occupancy_worst", 0.0)),
        f"{prefix}_hbm_frac_worst": float(
            ru.get("device_memory_frac_worst", 0.0)),
        f"{prefix}_goodput": float(slo.get("goodput", -1.0)),
        f"{prefix}_burn_rate": float(slo.get("burn_rate", -1.0)),
        f"{prefix}_overshoot_sustained": bool(
            slo.get("sustained_overshoot", False)),
        f"{prefix}_health": dict(sorted(health.items())),
        f"{prefix}_decisions": int(
            snapshot.get("decisions_recorded", 0)),
    }


def _fleet_fields_from_states(st0s: dict, st1s: dict, slo_ms: float,
                              prefix: str = "fleet") -> dict:
    """Fleet-level fields for the gateway-LESS legs (kv_tier drives
    replicas directly): goodput/burn over the leg window from the
    replicas' cumulative /state ttft_hist_buckets deltas — the same
    slomon math the gateway monitor runs — plus occupancy/slot
    rollups from the closing snapshots."""
    h0 = slomon.sum_buckets(
        (st or {}).get("ttft_hist_buckets") or {} for st in st0s.values())
    h1 = slomon.sum_buckets(
        (st or {}).get("ttft_hist_buckets") or {} for st in st1s.values())
    served = slomon.total_count(h1) - slomon.total_count(h0)
    under = (slomon.under_slo_count(h1, slo_ms)
             - slomon.under_slo_count(h0, slo_ms))
    goodput = under / served if served > 0 else -1.0
    occ = [float((st or {}).get("kv_occupancy", 0.0))
           for st in st1s.values()]
    return {
        f"{prefix}_slo_ms": round(slo_ms, 1),
        f"{prefix}_served": served,
        f"{prefix}_goodput": round(goodput, 4),
        f"{prefix}_burn_rate": (
            round((1.0 - goodput) / 0.05, 4) if goodput >= 0 else -1.0),
        f"{prefix}_kv_occupancy_worst": round(max(occ, default=0.0), 4),
        f"{prefix}_slots_total": sum(
            int((st or {}).get("max_slots", 0)) for st in st1s.values()),
    }


# -- decode_fused leg: fused decode step + quantized KV pages (ISSUE 13) --

# Leg model with head_dim 64 — the smallest serving-shaped head at
# which the int8 capacity claim holds ((64 + 4) / 128 = 0.53; TINY's
# D=16 pays 0.625 because the f32 scale is amortized over too few
# elements and would falsify a true claim).
_FUSED_CFG = llama.LlamaConfig(
    vocab_size=2048, dim=256, n_layers=4, n_heads=4, n_kv_heads=2,
    ffn_dim=512, max_seq_len=1024, rope_theta=10000.0,
)
_FUSED_PAGE = 32


async def _drive_decode_one(s, url: str, model: str, content: str,
                            gen_tokens: int) -> tuple:
    """One greedy sequential streaming chat; returns
    (duration_s, tokens, joined_text) — the text is the
    stream-identity probe."""
    payload = {
        "model": model,
        "messages": [{"role": "user", "content": content}],
        "max_tokens": gen_tokens,
        "temperature": 0.0,
        "stream": True,
        "stream_options": {"include_usage": True},
    }
    t0 = time.perf_counter()
    usage = None
    parts: list[str] = []
    async with s.post(url + "/v1/chat/completions", json=payload) as resp:
        assert resp.status == 200, resp.status
        while True:
            line = await resp.content.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[6:]
            if data == b"[DONE]":
                break
            ev = json.loads(data)
            if ev.get("usage"):
                usage = ev["usage"]
            ch = ev.get("choices") or []
            delta = (ch[0].get("delta") or {}) if ch else {}
            if delta.get("content"):
                parts.append(delta["content"])
    dur = time.perf_counter() - t0
    ntok = (usage or {}).get("completion_tokens") or len(parts)
    return dur, ntok, "".join(parts)


def decode_fused_numbers(reps: int = 3, requests_per_rep: int = 4,
                         gen_tokens: int = 64) -> dict:
    """The ``--ab decode_fused`` leg (ISSUE 13): decode-heavy greedy
    streaming chats against THREE tpuserve children on identical
    seeded traffic, requests interleaved so host drift cancels:

    - **fused vs chained** (both f32 KV): the same prompts must stream
      IDENTICAL text (the f32-rig equivalence, measured over the real
      HTTP surface), zero hot compiles on either child, and the tok/s
      ratio is reported. On this CPU backend the fused child runs the
      XLA page-walk reference, so the ratio is bookkeeping parity —
      what the kernel does to HBM traffic is a chip measurement.
    - **int8-KV fused vs native**: capacity — kv_bytes_per_token and
      the pool-bytes ratio from /state (claim: ≤ 0.55x) — and quality,
      as greedy-token agreement against the native child's streams on
      the same prompts (the PR 9 int4-weight smoke's role, measured
      end-to-end)."""
    import aiohttp

    model_name = "bench-fused-tiny"
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    engine_common = {"min_prefill_bucket": 32, "num_pages": 96,
                     "max_queued_requests": 64,
                     "warm_decode_buckets": 3}
    children = []

    def start(backend: str, kv_dtype: str, pdtype: str):
        url, stop = _start_tpuserve_subproc(
            model_name, _FUSED_CFG, "", batch=4, k_steps=k,
            engine=dict(engine_common, decode_backend=backend,
                        kv_cache_dtype=kv_dtype),
            page=_FUSED_PAGE, param_dtype=pdtype)
        children.append(stop)
        return url

    url_fu = start("fused", "float32", "float32")
    url_ch = start("auto", "float32", "float32")
    url_q8 = start("fused", "int8", "float32")

    prompts = [f"decode fused probe {i} " + "ab" * 24
               for i in range(requests_per_rep)]

    async def run() -> dict:
        await _wait_health(url_fu, 1200)
        await _wait_health(url_ch, 1200)
        await _wait_health(url_q8, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off the clock: compile whatever the warm pass missed
            for url in (url_fu, url_ch, url_q8):
                await _drive_decode_one(s, url, model_name,
                                        prompts[0], gen_tokens)
            st_fu0 = await _get_state(s, url_fu)
            st_ch0 = await _get_state(s, url_ch)
            fu, ch, q8 = [], [], []
            for _rep in range(reps):
                for p in prompts:
                    fu.append(await _drive_decode_one(
                        s, url_fu, model_name, p, gen_tokens))
                    ch.append(await _drive_decode_one(
                        s, url_ch, model_name, p, gen_tokens))
                    q8.append(await _drive_decode_one(
                        s, url_q8, model_name, p, gen_tokens))
            st_fu1 = await _get_state(s, url_fu)
            st_ch1 = await _get_state(s, url_ch)
            st_q8 = await _get_state(s, url_q8)

        def tps(runs):
            return sum(n for _, n, _t in runs) / sum(
                d for d, _n, _t in runs)

        identical = all(a[2] == b[2] for a, b in zip(fu, ch))

        def agree(a: str, b: str) -> float:
            n = max(len(a), len(b), 1)
            same = sum(1 for x, y in zip(a, b) if x == y)
            return same / n

        q8_agree = (sum(agree(a[2], b[2]) for a, b in zip(q8, ch))
                    / max(len(q8), 1))
        ratio = tps(fu) / tps(ch) if tps(ch) else 0.0
        return {
            "decode_fused_tps": round(tps(fu), 1),
            "decode_chained_tps": round(tps(ch), 1),
            "decode_fused_ratio": round(ratio, 4),
            "decode_fused_identical_streams": identical,
            "decode_fused_impl": st_fu1.get("decode_attn_impl", ""),
            "decode_fused_hot_compiles": (
                st_fu1.get("xla_compiles", 0)
                - st_fu0.get("xla_compiles", 0)),
            "decode_chained_hot_compiles": (
                st_ch1.get("xla_compiles", 0)
                - st_ch0.get("xla_compiles", 0)),
            "kv_int8_bytes_per_token": st_q8.get(
                "kv_bytes_per_token", 0),
            "kv_native_bytes_per_token": st_ch1.get(
                "kv_bytes_per_token", 0),
            # native child runs f32 KV (the rig); quote the claim
            # against the SERVING dtype: bf16 = f32 / 2
            "kv_int8_bytes_ratio_vs_bf16": round(
                st_q8.get("kv_bytes_per_token", 0)
                / max(st_ch1.get("kv_bytes_per_token", 1) / 2.0, 1e-9),
                4),
            "kv_int8_greedy_agreement": round(q8_agree, 4),
            "decode_fused_ab_reps": reps * requests_per_rep,
        }

    try:
        return asyncio.run(run())
    finally:
        for stop in children:
            stop()


async def _warm_openloop_shapes(s, url: str, model: str, tag: str,
                                gen_lens=(2, 4, 6)) -> None:
    """Off the clock: compile every shape a timed open-loop trace can
    use — every (prompt_len, gen) combo deterministically, simultaneous
    PAIRS over every prompt-length combination (batch=2 children
    coalesce admissions into group shapes the spaced pass never
    reaches), and a bursty pass for arrival-timing-dependent geometry.
    Shared by the fleet_obs and fleet_ctl legs — their hot-compile
    tripwires must measure the telemetry/control path, not first-use
    compiles."""
    combos = [(pl, g) for pl in (48, 96, 160) for g in gen_lens]
    warm = [{"at": 0.3 * i, "prompt_len": pl, "gen": g,
             "tenant": "", "i": i}
            for i, (pl, g) in enumerate(combos)]
    await _drive_openloop(s, url, model, warm, tag=tag)
    lens = (48, 96, 160)
    duos = [(a, b) for i, a in enumerate(lens) for b in lens[i:]]
    pairs = [{"at": 0.8 * j, "prompt_len": pl, "gen": gen_lens[0],
              "tenant": "", "i": 100 + 2 * j + kk}
             for j, (a, b) in enumerate(duos)
             for kk, pl in enumerate((a, b))]
    await _drive_openloop(s, url, model, pairs, tag=tag + "p")
    burst = _poisson_trace(seed=998, n=10, rate_hz=4.0,
                           gen_lens=gen_lens)
    await _drive_openloop(s, url, model, burst, tag=tag + "b")


def fleet_obs_numbers(reps: int = 3, arrivals: int = 20) -> dict:
    """The ``--ab fleet_obs`` leg (ISSUE 12): observability must be
    ~free. The SAME seeded open-loop trace through two gateway
    configurations over the same healthy two-replica pool — fleet_obs
    ON (decision ring recording every pick + the burn-rate monitor
    chewing polled histograms + a federation scraper hammering
    /fleet/metrics and /fleet/state at 4 Hz throughout) vs fleet_obs
    OFF (no ring, no monitor, no scraping). The claim: throughput
    ratio ≥ 0.95 and ZERO hot XLA compiles from the telemetry path."""
    import aiohttp

    model_name = "bench-fleetobs-tiny"
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    # warm_decode_buckets: decode programs re-trace per pow2 page-table
    # width (the PR 10 lesson) — without pre-compiling the ladder the
    # timed reps pay first-use decode compiles that would masquerade as
    # an observability tax in the hot-compile tripwire
    engine = {"num_pages": 64, "max_queued_requests": 64,
              "min_prefill_bucket": 32, "warm_decode_buckets": 7}
    url_a, stop_a = _start_tpuserve_subproc(
        model_name, CPU_CFG, "", batch=2, k_steps=k, engine=engine,
        page=16)
    url_b, stop_b = _start_tpuserve_subproc(
        model_name, CPU_CFG, "", batch=2, k_steps=k, engine=engine,
        page=16)
    addrs = [u[len("http://"):] for u in (url_a, url_b)]

    async def scrape_loop(s, gw: str, stop_evt: asyncio.Event) -> int:
        n = 0
        while not stop_evt.is_set():
            try:
                async with s.get(gw + "/fleet/metrics") as r:
                    await r.read()
                async with s.get(gw + "/fleet/state") as r:
                    await r.json()
                n += 1
            except (aiohttp.ClientError, asyncio.TimeoutError):
                pass
            await asyncio.sleep(0.25)
        return n

    async def run() -> dict:
        await _wait_health(url_a, 1200)
        await _wait_health(url_b, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off the clock: compile every shape the timed traces use
            # (combos + coalesced pairs + bursty pass — the shared
            # open-loop warm helper)
            for url, tg in ((url_a, "wa"), (url_b, "wb")):
                await _warm_openloop_shapes(s, url, model_name, tg)
            xla0 = -1
            tput: dict[str, list] = {"on": [], "off": []}
            scrapes = 0
            snap: dict = {}
            for rep in range(reps):
                if rep == 1:
                    # compile tripwire anchored AFTER rep 0: the first
                    # on/off pair soaks whatever first-use geometry the
                    # deterministic warm above still missed (arrival-
                    # timing-dependent coalescing), so the steady-state
                    # reps isolate compiles the OBSERVABILITY path adds
                    # — which must be zero
                    xla0 = sum([(await _get_state(s, u)
                                 ).get("xla_compiles", 0)
                                for u in (url_a, url_b)])
                for mode in ("on", "off"):
                    extra = ({"slo_window_s": 2.0} if mode == "on"
                             else {"fleet_obs": False})
                    gw, stop_gw = _start_gateway_cfg(extra, addrs)
                    try:
                        await _wait_health(gw, 120)
                        await asyncio.sleep(1.0)  # first polls land
                        trace = _poisson_trace(
                            seed=1300 + rep, n=arrivals, rate_hz=3.0,
                            gen_lens=(2, 4, 6))
                        stop_evt = asyncio.Event()
                        scraper = (asyncio.create_task(
                            scrape_loop(s, gw, stop_evt))
                            if mode == "on" else None)
                        t0 = time.perf_counter()
                        res = await _drive_openloop(
                            s, gw, model_name, trace,
                            tag=f"{mode[:1]}{rep}")
                        wall = time.perf_counter() - t0
                        stop_evt.set()
                        if scraper is not None:
                            scrapes += await scraper
                            snap = await (await s.get(
                                gw + "/fleet/state")).json()
                        tput[mode].append(res["completed"] / wall)
                    finally:
                        stop_gw()
            xla1 = sum([(await _get_state(s, u)).get("xla_compiles", 0)
                        for u in (url_a, url_b)])
            if xla0 < 0:
                xla0 = xla1  # reps == 1: no steady-state window
        ratios = [a / b for a, b in zip(tput["on"], tput["off"])
                  if b > 0]
        out = {
            "fleet_obs_vs_off": round(_median(ratios), 4) if ratios
            else 0.0,
            "fleet_obs_vs_off_by_rep": [round(r, 4) for r in ratios],
            "fleet_obs_spread": round(_spread(tput["on"]), 3),
            "fleet_off_spread": round(_spread(tput["off"]), 3),
            "fleet_obs_hot_compiles": int(xla1 - xla0),
            "fleet_obs_scrapes": scrapes,
            "fleet_obs_reps": reps,
            "fleet_obs_arrivals": arrivals,
        }
        out.update(_fleet_obs_fields(snap, "fleet_obs"))
        return out

    try:
        return asyncio.run(run())
    finally:
        stop_a()
        stop_b()


def metering_numbers(reps: int = 3, arrivals: int = 20) -> dict:
    """The ``--ab metering`` leg (ISSUE 20): engine-truth usage
    metering must be ~free. The SAME seeded open-loop trace through
    two gateway configurations over the same healthy two-replica pool
    — metering ON (engine MeterRecords journaled into a 2s-window
    ledger, a CostProgram pricing every request through the new meter
    variables, /usage polled at 4 Hz throughout) vs metering OFF
    (``usage: {enabled: false}``, no cost programs). The claim:
    throughput ratio ≥ 0.95 and ZERO hot XLA compiles from the
    metering path; the on-leg also cross-checks ledger totals against
    the replicas' meter_* counters (exact decode-token reconciliation
    rides tier-1 — here it is a live smoke)."""
    import aiohttp

    model_name = "bench-metering-tiny"
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    engine = {"num_pages": 64, "max_queued_requests": 64,
              "min_prefill_bucket": 32, "warm_decode_buckets": 7}
    url_a, stop_a = _start_tpuserve_subproc(
        model_name, CPU_CFG, "", batch=2, k_steps=k, engine=engine,
        page=16)
    url_b, stop_b = _start_tpuserve_subproc(
        model_name, CPU_CFG, "", batch=2, k_steps=k, engine=engine,
        page=16)
    addrs = [u[len("http://"):] for u in (url_a, url_b)]
    #: the on-leg's top-level config: tight ledger windows plus a cost
    #: expression over the NEW meter variables (decode + padded prefill
    #: + residency) so the priced path is on the clock, not a stub
    metering_cfg = {
        "usage": {"window_s": 2.0, "budgets": {"bench": 1e9}},
        "llm_request_costs": [{
            "metadata_key": "tpu_cost",
            "type": "Expression",
            "expression": ("decode_tokens * 2 + prefill_padded_tokens"
                           " + int(kv_page_byte_seconds)"),
        }],
    }

    async def usage_loop(s, gw: str, stop_evt: asyncio.Event) -> int:
        n = 0
        while not stop_evt.is_set():
            try:
                async with s.get(gw + "/usage") as r:
                    await r.json()
                async with s.get(gw + "/metrics") as r:
                    await r.read()
                n += 1
            except (aiohttp.ClientError, asyncio.TimeoutError):
                pass
            await asyncio.sleep(0.25)
        return n

    async def run() -> dict:
        await _wait_health(url_a, 1200)
        await _wait_health(url_b, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            for url, tg in ((url_a, "wa"), (url_b, "wb")):
                await _warm_openloop_shapes(s, url, model_name, tg)
            xla0 = -1
            tput: dict[str, list] = {"on": [], "off": []}
            polls = 0
            usage_snap: dict = {}
            for rep in range(reps):
                if rep == 1:
                    # compile tripwire anchored AFTER rep 0 (same
                    # discipline as fleet_obs: the first pair soaks
                    # arrival-timing-dependent first-use geometry, so
                    # steady-state isolates compiles METERING adds —
                    # which must be zero)
                    xla0 = sum([(await _get_state(s, u)
                                 ).get("xla_compiles", 0)
                                for u in (url_a, url_b)])
                for mode in ("on", "off"):
                    top = (metering_cfg if mode == "on"
                           else {"usage": {"enabled": False}})
                    gw, stop_gw = _start_gateway_cfg({}, addrs,
                                                     top_extra=top)
                    try:
                        await _wait_health(gw, 120)
                        await asyncio.sleep(1.0)  # first polls land
                        trace = _poisson_trace(
                            seed=2000 + rep, n=arrivals, rate_hz=3.0,
                            gen_lens=(2, 4, 6),
                            tenants=("bench", "team-b"))
                        stop_evt = asyncio.Event()
                        poller = (asyncio.create_task(
                            usage_loop(s, gw, stop_evt))
                            if mode == "on" else None)
                        t0 = time.perf_counter()
                        # both legs request the usage tail frame so the
                        # traces stay byte-identical; only the on-leg
                        # has a ledger to mine the meter into
                        res = await _drive_openloop(
                            s, gw, model_name, trace,
                            tag=f"m{mode[:1]}{rep}",
                            payload_extra={"stream_options": {
                                "include_usage": True}})
                        wall = time.perf_counter() - t0
                        stop_evt.set()
                        if poller is not None:
                            polls += await poller
                            usage_snap = await (await s.get(
                                gw + "/usage")).json()
                        tput[mode].append(res["completed"] / wall)
                    finally:
                        stop_gw()
            xla1 = sum([(await _get_state(s, u)).get("xla_compiles", 0)
                        for u in (url_a, url_b)])
            if xla0 < 0:
                xla0 = xla1  # reps == 1: no steady-state window
            # live reconciliation smoke for the LAST on-leg gateway:
            # its ledger's record count must equal the trace size (one
            # MeterRecord per finished request, exactly once)
            totals = (usage_snap.get("totals") or {})
        ratios = [a / b for a, b in zip(tput["on"], tput["off"])
                  if b > 0]
        return {
            "metering_vs_off": round(_median(ratios), 4) if ratios
            else 0.0,
            "metering_vs_off_by_rep": [round(r, 4) for r in ratios],
            "metering_on_spread": round(_spread(tput["on"]), 3),
            "metering_off_spread": round(_spread(tput["off"]), 3),
            "metering_hot_compiles": int(xla1 - xla0),
            "metering_usage_polls": polls,
            "metering_ledger_records": int(totals.get("records", 0)),
            "metering_ledger_decode_tokens": int(
                totals.get("decode_tokens", 0)),
            "metering_ledger_cost": int(totals.get("cost", 0)),
            "metering_records_expected": arrivals,
            "metering_reps": reps,
            "metering_arrivals": arrivals,
        }

    try:
        return asyncio.run(run())
    finally:
        stop_a()
        stop_b()


def _classify_stream(status: int, data_lines: list[bytes],
                     aborted: bool) -> str:
    """Outcome of one streamed request under churn (ISSUE 14):

    - ``complete`` — the stream reached its ``[DONE]`` terminal;
    - ``typed_error`` — a clean, client-parseable failure: a non-200
      JSON error response, or an SSE ``{"error": ...}`` event ending
      the stream (the gateway's mid-stream failure contract);
    - ``torn`` — the connection died (or the stream just stopped)
      without either. Torn streams are the DROPPED count the fleet_ctl
      acceptance criterion requires to be zero.
    """
    if status != 200:
        return "typed_error"
    if any(ln.strip() == b"[DONE]" for ln in data_lines):
        return "complete"
    if aborted:
        return "torn"
    for ln in data_lines:
        try:
            ev = json.loads(ln)
        except (json.JSONDecodeError, ValueError):
            continue
        if isinstance(ev, dict) and "error" in ev:
            return "typed_error"
    return "torn"


async def _drive_openloop_strict(s, url: str, model: str,
                                 trace: list[dict],
                                 tag: str = "") -> dict:
    """Open-loop driver with torn-stream accounting: like
    ``_drive_openloop`` but every arrival is classified complete /
    typed_error / torn via :func:`_classify_stream` — the chaos legs'
    zero-dropped-streams claim is the ``torn`` count staying zero
    while replicas are killed under the trace."""
    import aiohttp  # noqa: F811

    res: dict = {"complete": 0, "typed_error": 0, "torn": 0,
                 "client_ttft_ms": []}

    async def one(item: dict, t0: float) -> None:
        delay = t0 + item["at"] - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        n = item["prompt_len"]
        text = (f"{tag}{item['i']:03d}" + "y" * n)[: n - 1]
        payload = {
            "model": model, "prompt": text,
            "max_tokens": item["gen"], "temperature": 0.0,
            "stream": True, "logit_bias": {"97": 100},
        }
        status = 0
        data_lines: list[bytes] = []
        aborted = False
        first = -1.0
        sent = time.perf_counter()
        try:
            async with s.post(url + "/v1/completions",
                              json=payload) as resp:
                status = resp.status
                if status != 200:
                    await resp.read()
                else:
                    async for line in resp.content:
                        line = line.strip()
                        if not line.startswith(b"data: "):
                            continue
                        d = line[6:]
                        data_lines.append(d)
                        if first < 0 and b'"text"' in d:
                            first = 1e3 * (time.perf_counter() - sent)
        except (aiohttp.ClientError, asyncio.TimeoutError):
            aborted = True
        res[_classify_stream(status, data_lines, aborted)] += 1
        if first > 0:
            res["client_ttft_ms"].append(first)

    t0 = time.perf_counter()
    await asyncio.gather(*(one(it, t0) for it in trace))
    return res


def fleet_ctl_numbers(arrivals: int = 24) -> dict:
    """The ``--ab fleet_ctl`` leg (ISSUE 14): the fleet control plane
    under injected churn. The seeded open-loop trace runs against a
    2-replica pool behind a controller-enabled gateway while the
    harness (1) ``kill -9``s replica A mid-decode — the crash case: the
    controller must detect it, re-route, and launch a replacement
    through the LocalProcessLauncher; (2) floods the survivor until the
    SLO monitor's sustained-overshoot flag trips — the controller must
    scale out. The claims: dropped (torn) streams == 0 — every client
    sees a complete stream or a typed error event — goodput recovers to
    ≥0.9× the pre-event window in a bounded, reported time, and the
    SURVIVING replica pays zero hot XLA compiles throughout."""
    import aiohttp

    from tools import chaos

    model_name = "bench-fleetctl-tiny"
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    engine = {"num_pages": 64, "max_queued_requests": 64,
              "min_prefill_bucket": 32, "warm_decode_buckets": 7}
    child_spec = {
        "model": model_name,
        "cfg": {key: getattr(CPU_CFG, key) for key in (
            "vocab_size", "dim", "n_layers", "n_heads", "n_kv_heads",
            "ffn_dim", "max_seq_len", "rope_theta")},
        "batch": 2, "page": 16, "k": k, "quantize": "",
        "engine": engine, "param_dtype": "", "lora": {}, "tp": 1,
    }
    rep_a = chaos.spawn_replica(child_spec)
    rep_b = chaos.spawn_replica(child_spec)
    gen_lens = (3, 5, 7)

    gw, stop_gw = _start_gateway_cfg({
        "picker_poll_interval": 0.1,
        "migration": True,
        "migration_queue_depth": 2,
        # static picker mode: slo_ttft_ms feeds ONLY the burn-rate
        # monitor (no shedding) — the scale-out predicate's SLO
        "slo_ttft_ms": 150.0,
        "slo_window_s": 1.5,
        "slo_burn_windows": 2,
        "controller": {
            "min_replicas": 2, "max_replicas": 3,
            "tick_s": 0.25, "down_grace_s": 0.5,
            "scale_cooldown_s": 3.0,
            # scale-in disabled for the leg (it would retire the
            # replica the tripwire is anchored on)
            "idle_ticks": 1_000_000,
            "drain_timeout_s": 30.0,
            "launcher": {"kind": "local", "spec": child_spec,
                         "term_grace_s": 5.0},
        },
    }, [rep_a.address, rep_b.address])

    async def run() -> dict:
        await _wait_health(rep_a.url, 1200)
        await _wait_health(rep_b.url, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            for url, tg in ((rep_a.url, "fa"), (rep_b.url, "fb")):
                await _warm_openloop_shapes(s, url, model_name, tg,
                                            gen_lens=gen_lens)
            await _wait_health(gw, 180)
            await asyncio.sleep(1.2)  # first polls land

            async def ctl_state() -> dict:
                snap = await (await s.get(gw + "/fleet/state")).json()
                return (snap["backends"]["pool"].get("controller")
                        or {})

            # the survivor's compile tripwire anchors AFTER its warm
            xla0 = (await _get_state(s, rep_b.url)).get(
                "xla_compiles", 0)

            outcomes = {"complete": 0, "typed_error": 0, "torn": 0}

            def tally(r: dict) -> None:
                for key in outcomes:
                    outcomes[key] += r[key]

            # ---- pre-event window --------------------------------
            pre = await _drive_openloop_strict(
                s, gw, model_name,
                _poisson_trace(seed=1400, n=arrivals, rate_hz=3.0,
                               gen_lens=gen_lens), tag="pr")
            tally(pre)
            goodput_pre = pre["complete"] / arrivals

            # ---- crash injection: kill -9 A mid-decode -----------
            evt_trace = _poisson_trace(seed=1401, n=arrivals,
                                       rate_hz=3.0, gen_lens=gen_lens)
            kill_at = evt_trace[arrivals // 3]["at"] + 0.15
            t_kill = [0.0]

            async def assassin() -> None:
                await asyncio.sleep(kill_at)
                t_kill[0] = time.perf_counter()
                rep_a.kill9()

            evt, _ = await asyncio.gather(
                _drive_openloop_strict(s, gw, model_name, evt_trace,
                                       tag="ev"),
                assassin())
            tally(evt)
            goodput_event = evt["complete"] / arrivals

            # ---- failover: detection + replacement launch --------
            deadline = time.perf_counter() + 900
            ctl: dict = {}
            while time.perf_counter() < deadline:
                ctl = await ctl_state()
                if (ctl.get("counters", {}).get("failovers", 0) >= 1
                        and len(ctl.get("replicas_live") or ()) >= 2):
                    break
                await asyncio.sleep(0.5)
            failovers = ctl.get("counters", {}).get("failovers", 0)
            launched = ctl.get("counters", {}).get("launch_failures", 0)

            # ---- goodput recovery probes -------------------------
            recovery_s = -1.0
            probe_n = 8
            probe_seed = 1500
            while time.perf_counter() - t_kill[0] < 900:
                probe = await _drive_openloop_strict(
                    s, gw, model_name,
                    _poisson_trace(seed=probe_seed, n=probe_n,
                                   rate_hz=4.0, gen_lens=gen_lens),
                    tag=f"p{probe_seed % 100}")
                probe_seed += 1
                tally(probe)
                if probe["complete"] / probe_n >= 0.9 * goodput_pre:
                    recovery_s = time.perf_counter() - t_kill[0]
                    break

            # ---- triggered scale-out: flood past the SLO ---------
            scale_outs = 0
            for flood_round in range(4):
                flood = await _drive_openloop_strict(
                    s, gw, model_name,
                    _poisson_trace(seed=1600 + flood_round, n=20,
                                   rate_hz=12.0, gen_lens=gen_lens),
                    tag=f"fl{flood_round}")
                tally(flood)
                ctl = await ctl_state()
                scale_outs = ctl.get("counters", {}).get(
                    "scale_outs", 0)
                if scale_outs >= 1:
                    break
                await asyncio.sleep(1.6)  # let a window close

            xla1 = (await _get_state(s, rep_b.url)).get(
                "xla_compiles", 0)
            ctl = await ctl_state()
            snap = await (await s.get(gw + "/fleet/state")).json()
        return {
            "fleet_ctl_arrivals": sum(outcomes.values()),
            "fleet_ctl_complete": outcomes["complete"],
            "fleet_ctl_typed_errors": outcomes["typed_error"],
            # the acceptance criterion: zero torn/hung streams — every
            # client saw a complete stream or a typed error event
            "fleet_ctl_dropped_streams": outcomes["torn"],
            "fleet_ctl_goodput_pre": round(goodput_pre, 4),
            "fleet_ctl_goodput_event": round(goodput_event, 4),
            "fleet_ctl_recovery_s": round(recovery_s, 2),
            "fleet_ctl_recovered": recovery_s >= 0,
            "fleet_ctl_failovers": failovers,
            "fleet_ctl_scale_outs": scale_outs,
            "fleet_ctl_launch_failures": launched,
            "fleet_ctl_replicas_live": len(
                ctl.get("replicas_live") or ()),
            "fleet_ctl_lifecycle_events": len(ctl.get("events") or ()),
            "fleet_ctl_survivor_hot_compiles": int(xla1 - xla0),
            "fleet_ctl_fleet_up": snap.get("fleet", {}).get(
                "replicas_up", 0),
        }

    try:
        return asyncio.run(run())
    finally:
        stop_gw()  # gateway cleanup terminates launcher-owned children
        rep_a.kill9()
        rep_b.term(timeout=30)


async def _disagg_migrate_once(s, url_a: str, url_b: str, model: str,
                               prompt_len: int, tag: str) -> dict:
    """One migration rep: stream on A, export after the first tokens,
    import+resume on B. Returns {resume_ttft_ms, tokens_total,
    pages_moved, text}."""
    import aiohttp  # noqa: F811

    n = prompt_len
    text = (tag + "z" * n)[: n - 1]
    payload = {"model": model, "prompt": text, "max_tokens": 40,
               "temperature": 0.0, "stream": True,
               "logit_bias": {"97": 100}}
    pieces: list[str] = []
    rid = ""
    export = None
    async with s.post(url_a + "/v1/completions", json=payload) as resp:
        assert resp.status == 200, resp.status
        rid = resp.headers.get("x-aigw-request-id", "")
        got = 0
        async for line in resp.content:
            line = line.strip()
            if not line.startswith(b"data: ") or line[6:] == b"[DONE]":
                continue
            ev = json.loads(line[6:])
            ch = ev.get("choices") or []
            if ch and ch[0].get("text"):
                pieces.append(ch[0]["text"])
                got += 1
                if got == 2 and export is None:
                    async with s.post(url_a + "/migrate/export",
                                      json={"request_id": rid}) as r:
                        assert r.status == 200, (r.status,
                                                 await r.read())
                        export = await r.json()
        # stream ends at the cut with no terminal frames
    assert export is not None
    t0 = time.perf_counter()
    first = -1.0
    async with s.post(url_b + "/migrate/import", json=export) as r:
        assert r.status == 200, (r.status, await r.read())
        async for line in r.content:
            line = line.strip()
            if not line.startswith(b"data: ") or line[6:] == b"[DONE]":
                continue
            ev = json.loads(line[6:])
            ch = ev.get("choices") or []
            if ch and ch[0].get("text"):
                if first < 0:
                    first = 1e3 * (time.perf_counter() - t0)
                pieces.append(ch[0]["text"])
    return {
        "resume_ttft_ms": first,
        "pages_moved": len(export["pages"]),
        "cont_tokens": len(export["blob"]["tokens"]),
        "text": "".join(pieces),
    }


async def _disagg_cold_ttft(s, url: str, model: str, n_tokens: int,
                            tag: str) -> float:
    """Cold-prefill TTFT control: a fresh prompt of the SAME total
    length the migrated session had at its resume."""
    text = (tag + "q" * n_tokens)[: n_tokens - 1]
    payload = {"model": model, "prompt": text, "max_tokens": 4,
               "temperature": 0.0, "stream": True,
               "logit_bias": {"97": 100}}
    t0 = time.perf_counter()
    async with s.post(url + "/v1/completions", json=payload) as resp:
        assert resp.status == 200, resp.status
        async for line in resp.content:
            line = line.strip()
            if line.startswith(b"data: ") and b'"text"' in line:
                return 1e3 * (time.perf_counter() - t0)
    return -1.0


def disagg_numbers(reps: int = 5, prompt_len: int = 288,
                   arrivals: int = 24) -> dict:
    """The ``disagg`` A/B leg (ISSUE 8), two tpuserve replicas:

    1. **Resume vs cold** (the headline): per interleaved rep, a
       session streams on A, is exported after its first tokens, and
       resumes on B through /migrate/import — resume TTFT (import +
       page adoption + ≤1-page tail recompute + first token) against a
       cold-prefill TTFT for a fresh prompt of the same total length on
       the same replica. Target: resume ≤ 0.6× cold.
    2. **Gateway orchestration under open-loop load**: the same Poisson
       trace through a migration-ON gateway vs a migration-OFF gateway
       over the pool (replica A deliberately slow-queued), reporting
       server-side goodput and the migration counters — proves the
       DECISION loop (deep prefill queue → hand off to the
       decode-leaning sibling) fires under real load."""
    import aiohttp

    model_name = "bench-disagg-tiny"
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    engine_common = {"min_prefill_bucket": 32, "num_pages": 96,
                     "max_queued_requests": 64,
                     "kv_cache_dtype": "float32"}
    # replica A deliberately single-slot: under the open-loop pass its
    # admission queue deepens fast (the disaggregation trigger), while
    # the interleaved resume-vs-cold reps below are sequential and
    # don't care about batch width
    url_a, stop_a = _start_tpuserve_subproc(
        model_name, _PREFIX_CFG, "", batch=1, k_steps=k,
        engine=dict(engine_common), page=_PREFIX_PAGE,
        param_dtype="float32")
    url_b, stop_b = _start_tpuserve_subproc(
        model_name, _PREFIX_CFG, "", batch=2, k_steps=k,
        engine=dict(engine_common), page=_PREFIX_PAGE,
        param_dtype="float32")
    addrs = [u[len("http://"):] for u in (url_a, url_b)]

    async def run() -> dict:
        await _wait_health(url_a, 1200)
        await _wait_health(url_b, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off the clock: warm both children's resume + cold shapes
            await _disagg_migrate_once(s, url_a, url_b, model_name,
                                       prompt_len, "w0")
            await _disagg_cold_ttft(s, url_b, model_name,
                                    prompt_len + 8, "w1")
            resume_t, cold_t, pages = [], [], []
            for rep in range(reps):
                m = await _disagg_migrate_once(
                    s, url_a, url_b, model_name, prompt_len,
                    f"m{rep:02d}")
                if m["resume_ttft_ms"] > 0:
                    resume_t.append(m["resume_ttft_ms"])
                pages.append(m["pages_moved"])
                c = await _disagg_cold_ttft(
                    s, url_b, model_name, m["cont_tokens"],
                    f"k{rep:02d}")
                if c > 0:
                    cold_t.append(c)
            st_a = await _get_state(s, url_a)
            st_b = await _get_state(s, url_b)

            # gateway orchestration under open-loop load, mig on/off:
            # the same seeded trace through a migration-ON gateway and a
            # migration-OFF gateway, goodput from the replicas'
            # server-side TTFT histograms against a 2×cold-TTFT budget
            gw_fields: dict = {}
            gw_slo = 2.0 * _median(cold_t) if cold_t else 1000.0
            gw_fields["disagg_gw_slo_ms"] = round(gw_slo, 1)
            for mig in (True, False):
                extra = {"migration": mig, "migration_queue_depth": 1,
                         "migration_young_tokens": 48}
                gw, stop_gw = _start_gateway_cfg(extra, addrs)
                try:
                    await _wait_health(gw, 120)
                    await asyncio.sleep(1.0)
                    trace = _poisson_trace(
                        seed=77, n=arrivals, rate_hz=2.0,
                        prompt_lens=(96, 160, 224),
                        gen_lens=(16, 24, 32))
                    h0 = await _ttft_hists(s, [url_a, url_b])
                    res = await _drive_openloop(
                        s, gw, model_name, trace,
                        tag="g1" if mig else "g0")
                    h1 = await _ttft_hists(s, [url_a, url_b])
                    gw_fields.update(_goodput_fields(
                        h0, h1, gw_slo, arrivals, res["shed"],
                        prefix="disagg_gw_on" if mig
                        else "disagg_gw_off"))
                finally:
                    stop_gw()
            st_a2 = await _get_state(s, url_a)
            st_b2 = await _get_state(s, url_b)
            gw_fields["disagg_gw_migrations"] = (
                st_a2["migrations_out"] + st_b2["migrations_out"]
                - st_a["migrations_out"] - st_b["migrations_out"])

        resume = _median(resume_t)
        cold = _median(cold_t)
        return {
            "disagg_resume_ttft_ms_p50": round(resume, 1),
            "disagg_cold_ttft_ms_p50": round(cold, 1),
            "disagg_resume_vs_cold": (round(resume / cold, 4)
                                      if cold else 0.0),
            "disagg_resume_spread": round(_spread(resume_t), 3),
            "disagg_cold_spread": round(_spread(cold_t), 3),
            "disagg_pages_moved": _median([float(p) for p in pages]),
            "disagg_migrations_out": st_a["migrations_out"],
            "disagg_migrations_in": st_b["migrations_in"],
            "disagg_ab_reps": reps,
            **gw_fields,
        }

    try:
        return asyncio.run(run())
    finally:
        stop_a()
        stop_b()


# -- kv_tier leg: fleet KV memory hierarchy (ISSUE 11) -------------------

#: Leg model: compute-heavy relative to its KV bytes (wide dim + big
#: ffn, few KV heads) — on the CPU rig the cross-replica fetch pays in
#: page BYTES (b64 wire + import scatter) while the cold prefill pays
#: in COMPUTE, and this shape keeps the two costs in the same relation
#: they have on a real chip (where prefill compute dwarfs DCN page
#: movement). max_seq 512, 16-token pages.
_KVTIER_CFG = llama.LlamaConfig(
    vocab_size=8192, dim=1024, n_layers=6, n_heads=16, n_kv_heads=2,
    ffn_dim=4096, max_seq_len=512, rope_theta=10000.0,
)
_KVTIER_HEAD = 128  # shared-prefix head chars (8 full 16-token pages)


def _kvtier_ab_fields(st0: dict, st1: dict,
                      prefix: str = "kvtier") -> dict:
    """Counter deltas between two /state snapshots — the spill/revive/
    fetch churn and the hot-compile tripwire the kv_tier leg reports
    (unit-tested in tests/test_bench_smoke.py)."""

    def d(k: str) -> int:
        return int(st1.get(k, 0)) - int(st0.get(k, 0))

    return {
        f"{prefix}_spills": d("kv_spills"),
        f"{prefix}_revives": d("kv_revives"),
        f"{prefix}_fetches_in": d("kv_fetches_in"),
        f"{prefix}_fetches_out": d("kv_fetches_out"),
        f"{prefix}_fetch_pages_in": d("kv_fetch_pages_in"),
        f"{prefix}_fetch_pages_out": d("kv_fetch_pages_out"),
        f"{prefix}_hot_compiles": d("xla_compiles"),
    }


async def _kvtier_openloop(s, url: str, model: str, head: str,
                           arrivals: int, headers: dict,
                           tag: str) -> list[float]:
    """Shared-prefix open-loop burst: ``arrivals`` streaming
    completions whose prompts share ``head``, fired at staggered
    arrival times. Returns per-arrival TTFT ms in arrival order —
    arrival 0 pays the fetch (warm fleet) or the full prefill (cold
    fleet); later arrivals hit the replica's own cache either way."""

    async def one(i: int, t0: float) -> float:
        await asyncio.sleep(max(0.0, t0 + 0.08 * i - time.perf_counter()))
        payload = {"model": model,
                   "prompt": head + f" {tag}-u{i:02d}",
                   "max_tokens": 4, "temperature": 0.0,
                   "stream": True, "logit_bias": {"97": 100}}
        ts = time.perf_counter()
        async with s.post(url + "/v1/completions", json=payload,
                          headers=headers) as resp:
            assert resp.status == 200, resp.status
            async for line in resp.content:
                line = line.strip()
                if line.startswith(b"data: ") and b'"text"' in line:
                    return 1e3 * (time.perf_counter() - ts)
        return -1.0

    t0 = time.perf_counter()
    return list(await asyncio.gather(
        *(one(i, t0) for i in range(arrivals))))


def kv_tier_numbers(reps: int = 3, arrivals: int = 4) -> dict:
    """The ``--ab kv_tier`` leg (ISSUE 11), two tpuserve replicas with
    the host spill tier on:

    1. **Warm fleet vs cold fleet** (the headline): per interleaved
       rep, replica A is primed with a fresh shared-prefix head, then
       the same shared-prefix open-loop burst runs against replica B
       twice — once with A named in x-aigw-kv-peers (warm fleet:
       arrival 0 fetches A's pages over /kv/pages and resumes) and
       once with an unprimed head and no peers (cold fleet: arrival 0
       pays the full prefill). Target: first-arrival TTFT ratio ≤ 0.6.
    2. **Spill→revive churn on A** (off the clock): distinct floods
       overflow A's pool so the primed chains spill to host RAM, a
       re-ask revives one — counters prove the tier moved pages both
       ways, and the /state xla_compiles delta across a second churn
       cycle proves the whole spill/revive/fetch path stays off the
       compiler (CompileTracker tripwire)."""
    import aiohttp

    model_name = "bench-kvtier-tiny"
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    engine_common = {"min_prefill_bucket": 32,
                     "kv_cache_dtype": "float32",
                     "kv_host_bytes": 1 << 30,
                     "warm_decode_buckets": 5,
                     "max_queued_requests": 64}
    url_a, stop_a = _start_tpuserve_subproc(
        model_name, _KVTIER_CFG, "", batch=2, k_steps=k,
        engine=dict(engine_common, num_pages=64), page=_PREFIX_PAGE,
        param_dtype="float32")
    url_b, stop_b = _start_tpuserve_subproc(
        model_name, _KVTIER_CFG, "", batch=4, k_steps=k,
        engine=dict(engine_common, num_pages=128), page=_PREFIX_PAGE,
        param_dtype="float32")
    addr_a = url_a[len("http://"):]

    def head_of(tag: str) -> str:
        return (tag + "s" * _KVTIER_HEAD)[:_KVTIER_HEAD]

    async def prime(s, tag: str) -> None:
        payload = {"model": model_name,
                   "prompt": head_of(tag) + " prime",
                   "max_tokens": 2, "temperature": 0.0,
                   "logit_bias": {"97": 100}}
        async with s.post(url_a + "/v1/completions",
                          json=payload) as resp:
            assert resp.status == 200, resp.status

    async def run() -> dict:
        await _wait_health(url_a, 1200)
        await _wait_health(url_b, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            peers = {"x-aigw-kv-peers": addr_a}
            # off the clock: one full warm+cold cycle compiles every
            # shape the timed reps will touch (fetch import rungs and
            # the suffix resume on B, prefill buckets on both)
            await prime(s, "w0")
            # a second identical prime is a partial prefix hit: it
            # compiles A's offset-resume program off the clock (the
            # churn's revive re-ask resumes the same way)
            await prime(s, "w0")
            await asyncio.sleep(1.0)  # A's digest refresh
            await _kvtier_openloop(s, url_b, model_name, head_of("w0"),
                                   arrivals, peers, "w0")
            await _kvtier_openloop(s, url_b, model_name, head_of("wx"),
                                   arrivals, {}, "wx")

            st_b0 = await _get_state(s, url_b)
            st_a0 = await _get_state(s, url_a)
            warm_t, cold_t, warm_rest = [], [], []
            for rep in range(reps):
                await prime(s, f"h{rep:02d}")
                await asyncio.sleep(1.0)
                w = await _kvtier_openloop(
                    s, url_b, model_name, head_of(f"h{rep:02d}"),
                    arrivals, peers, f"w{rep:02d}")
                c = await _kvtier_openloop(
                    s, url_b, model_name, head_of(f"c{rep:02d}"),
                    arrivals, {}, f"c{rep:02d}")
                if w[0] > 0:
                    warm_t.append(w[0])
                warm_rest += [t for t in w[1:] if t > 0]
                if c[0] > 0:
                    cold_t.append(c[0])
            st_b1 = await _get_state(s, url_b)
            st_a1 = await _get_state(s, url_a)
            fields = _kvtier_ab_fields(st_b0, st_b1, "kvtier_b")
            fields.update(_kvtier_ab_fields(st_a0, st_a1, "kvtier_a"))
            # fleet-level telemetry for the capture (ISSUE 12): this
            # leg has no gateway, so the fleet rollup + goodput over
            # the timed window come straight from the replicas'
            # /state histograms via the shared slomon math (1s TTFT
            # reference SLO — a fixed yardstick, not a target)
            fields.update(_fleet_fields_from_states(
                {"a": st_a0, "b": st_b0}, {"a": st_a1, "b": st_b1},
                slo_ms=1000.0, prefix="kvtier_fleet"))

            # spill→revive churn on A (off the clock): overflow the
            # 64-page pool so the primed chains spill, revive one
            for i in range(8):
                await prime(s, f"f{i:02d}")
            st_c0 = await _get_state(s, url_a)
            for i in range(8, 12):
                await prime(s, f"f{i:02d}")
            await prime(s, "h00")  # re-ask: revives if spilled
            st_c1 = await _get_state(s, url_a)
            fields.update(_kvtier_ab_fields(st_c0, st_c1,
                                            "kvtier_churn"))

        warm = _median(warm_t)
        cold = _median(cold_t)
        return {
            "kvtier_warm_ttft_ms_p50": round(warm, 1),
            "kvtier_cold_ttft_ms_p50": round(cold, 1),
            "kvtier_warm_vs_cold": (round(warm / cold, 4)
                                    if cold else 0.0),
            "kvtier_warm_spread": round(_spread(warm_t), 3),
            "kvtier_cold_spread": round(_spread(cold_t), 3),
            # later arrivals of the warm bursts: the replica's own
            # cache serves them — the shared-prefix economics at
            # steady state
            "kvtier_warm_rest_ttft_ms_p50": round(
                _median(warm_rest), 1) if warm_rest else 0.0,
            "kvtier_ab_reps": reps,
            "kvtier_arrivals": arrivals,
            **fields,
        }

    try:
        return asyncio.run(run())
    finally:
        stop_a()
        stop_b()


_LONGCTX_SP = 8
#: page_size % sp == 0 (16 % 8) so the chunked-sp suffix program builds;
#: 4096-token sessions at 16-token pages = 256 pages — long enough that
#: a monolithic sp prefill visibly starves queued short arrivals on the
#: CPU backend, short enough that the leg fits the bench budget
_LONGCTX_CFG = llama.LlamaConfig(
    vocab_size=2048, dim=256, n_layers=4, n_heads=8, n_kv_heads=8,
    ffn_dim=512, max_seq_len=4096, rope_theta=10000.0,
)
_LONGCTX_PAGE = 16
_LONGCTX_LONG = 3500    # long-prompt tokens (byte tokenizer)
_LONGCTX_SHORT = 48     # interactive prompt tokens (< sp_prefill_min)
_LONGCTX_HEAD = 1664    # resume head: 104 full 16-token pages
_LONGCTX_CONT = 512     # continuation ≥ sp_prefill_min → sp offset resume


def _p95(xs: list[float]) -> float:
    s = sorted(xs)
    if not s:
        return 0.0
    return s[min(len(s) - 1, int(round(0.95 * (len(s) - 1))))]


async def _longctx_stream(s, url: str, model: str, prompt: str,
                          max_tokens: int) -> float:
    """One streaming completion; returns TTFT ms (awaits the full
    stream so the caller knows the session's slot is free after)."""
    payload = {"model": model, "prompt": prompt,
               "max_tokens": max_tokens, "temperature": 0.0,
               "stream": True, "logit_bias": {"97": 100}}
    ttft = -1.0
    t0 = time.perf_counter()
    async with s.post(url + "/v1/completions", json=payload) as resp:
        assert resp.status == 200, resp.status
        async for line in resp.content:
            line = line.strip()
            if (line.startswith(b"data: ") and b'"text"' in line
                    and ttft < 0):
                ttft = 1e3 * (time.perf_counter() - t0)
    return ttft


async def _longctx_cycle(s, url: str, model: str, tag: str,
                         arrivals: int) -> tuple[list[float], float]:
    """The decode-liveness probe: fire one long prompt, then — while
    its prefill is in flight — a concurrent burst of short interactive
    streams. Returns (interactive TTFTs ms, long TTFT ms). On the
    chunked child the shorts admit at the next chunk boundary; on the
    monolithic child they wait out the whole sharded prefill."""
    long_prompt = (f"{tag}L" + "x" * _LONGCTX_LONG)[:_LONGCTX_LONG]
    long_task = asyncio.ensure_future(
        _longctx_stream(s, url, model, long_prompt, 4))
    await asyncio.sleep(0.25)  # long prefill underway

    async def one(i: int) -> float:
        text = (f"{tag}i{i:02d} " + "q" * _LONGCTX_SHORT)
        return await _longctx_stream(s, url, model,
                                     text[:_LONGCTX_SHORT], 4)

    ttfts = list(await asyncio.gather(*(one(i)
                                        for i in range(arrivals))))
    long_ttft = await long_task
    return ttfts, long_ttft


async def _longctx_resume_cycle(s, url: str, model: str,
                                tag: str) -> tuple[float, float]:
    """Warm-resume vs cold on the chunked child: prime a page-aligned
    long head, re-ask head+continuation (prefix-cache partial hit →
    the sp chunk loop resumes at the adopted offset, only the ≥512-
    token suffix is computed), vs a cold prompt of the same total
    length. Returns (warm TTFT ms, cold TTFT ms)."""
    head = (f"{tag}h" + "s" * _LONGCTX_HEAD)[:_LONGCTX_HEAD]
    await _longctx_stream(s, url, model, head, 2)  # prime the chain
    warm = await _longctx_stream(
        s, url, model, head + "c" * _LONGCTX_CONT, 4)
    n = _LONGCTX_HEAD + _LONGCTX_CONT
    cold = await _longctx_stream(
        s, url, model, (f"{tag}x" + "z" * n)[:n], 4)
    return warm, cold


def longctx_numbers(reps: int = 3, arrivals: int = 4) -> dict:
    """The ``--ab longctx`` leg (ISSUE 17): the same long-context
    traffic against TWO sp=8 tpuserve children (8 virtual CPU devices)
    — sequence-sharded CHUNKED prefill vs the MONOLITHIC sp path. The
    portable claims:

    - **decode liveness / interactive TTFT**: short streams fired
      mid-long-prefill admit at chunk boundaries on the chunked child
      (``sp_interactive_admits`` counts them) instead of waiting out
      the whole sharded prefill — interactive TTFT p95 target ≥ 2×
      better chunked vs monolithic;
    - **offset resume**: re-asking a primed page-aligned head +
      continuation resumes the chunk loop at the adopted offset
      (``sp_resume_prefills``) — warm/cold TTFT ratio target ≤ 0.6;
    - **padding tax**: the chunk rung ladder keeps the sp path's
      padded_frac < 0.05 while the monolithic path pays the full
      top-rung residue;
    - **compile surface**: zero hot XLA compiles over the timed reps
      at long-context geometry (CompileTracker tripwire).

    Absolute ms is NOT the signal on CPU — ratios and counters are."""
    import aiohttp

    model_name = "bench-longctx-tiny"
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    engine_common = {
        "min_prefill_bucket": 32, "kv_cache_dtype": "float32",
        "max_queued_requests": 64, "num_pages": 768,
        # interactive arrivals must hit the queue immediately — the
        # leg measures chunk-boundary admission, not coalescing
        "admission_coalesce_ms": 0.0,
        # CPU-scale overrides: long prompts chunk at 256 tokens so a
        # 3500-token prefill has ~13 boundaries on a 1-core host
        "sp_prefill_min_tokens": 256, "sp_chunk_tokens": 256,
        "warm_decode_buckets": 4,
        # TTFT is the metric and the off-clock warm cycle absorbs the
        # shape compiles; the spec ladder would only widen the warm
        # surface and add draft nondeterminism to a random-weight rig
        "spec_tokens": 0,
    }
    env = {"XLA_FLAGS":
           f"--xla_force_host_platform_device_count={_LONGCTX_SP}"}
    url_c, stop_c = _start_tpuserve_subproc(
        model_name, _LONGCTX_CFG, "", batch=6, k_steps=k,
        engine=dict(engine_common, sp_prefill_mode="chunked"),
        page=_LONGCTX_PAGE, param_dtype="float32", sp=_LONGCTX_SP,
        env_extra=env)
    url_m, stop_m = _start_tpuserve_subproc(
        model_name, _LONGCTX_CFG, "", batch=6, k_steps=k,
        engine=dict(engine_common, sp_prefill_mode="monolithic"),
        page=_LONGCTX_PAGE, param_dtype="float32", sp=_LONGCTX_SP,
        env_extra=env)

    async def run() -> dict:
        await _wait_health(url_c, 1200)
        await _wait_health(url_m, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off the clock: one full cycle per child compiles every
            # shape the timed reps touch (chunk rungs at each offset,
            # the monolithic top rung, interactive singletons, decode
            # page buckets, and the chunked child's resume suffix)
            await _longctx_cycle(s, url_c, model_name, "w", arrivals)
            await _longctx_cycle(s, url_m, model_name, "w", arrivals)
            await _longctx_resume_cycle(s, url_c, model_name, "w")

            st_c0 = await _get_state(s, url_c)
            st_m0 = await _get_state(s, url_m)
            c_int, m_int = [], []
            c_long, m_long = [], []
            warm_t, cold_t = [], []
            for rep in range(reps):
                ci, cl = await _longctx_cycle(
                    s, url_c, model_name, f"r{rep}", arrivals)
                mi, ml = await _longctx_cycle(
                    s, url_m, model_name, f"r{rep}", arrivals)
                c_int += [t for t in ci if t > 0]
                m_int += [t for t in mi if t > 0]
                c_long.append(cl)
                m_long.append(ml)
                w, c = await _longctx_resume_cycle(
                    s, url_c, model_name, f"r{rep}")
                if w > 0:
                    warm_t.append(w)
                if c > 0:
                    cold_t.append(c)
            st_c1 = await _get_state(s, url_c)
            st_m1 = await _get_state(s, url_m)

        def d(st0: dict, st1: dict, key: str) -> int:
            return int(st1.get(key, 0)) - int(st0.get(key, 0))

        ci95, mi95 = _p95(c_int), _p95(m_int)
        warm, cold = _median(warm_t), _median(cold_t)
        return {
            "longctx_sp": _LONGCTX_SP,
            "longctx_prompt_tokens": _LONGCTX_LONG,
            "longctx_max_seq_len": int(
                st_c1.get("max_seq_len", 0) or 0),
            "longctx_interactive_ttft_ms_p95_chunked": round(ci95, 1),
            "longctx_interactive_ttft_ms_p95_monolithic": round(
                mi95, 1),
            # ≥ 2.0 is the decode-liveness claim
            "longctx_interactive_gain": (round(mi95 / ci95, 4)
                                         if ci95 > 0 else 0.0),
            "longctx_long_ttft_ms_p50_chunked": round(
                _median(c_long), 1),
            "longctx_long_ttft_ms_p50_monolithic": round(
                _median(m_long), 1),
            "longctx_resume_ttft_ms_p50": round(warm, 1),
            "longctx_cold_ttft_ms_p50": round(cold, 1),
            # ≤ 0.6 is the offset-resume claim
            "longctx_resume_vs_cold": (round(warm / cold, 4)
                                       if cold else 0.0),
            "longctx_interactive_spread": round(_spread(c_int), 3),
            "longctx_resume_spread": round(_spread(warm_t), 3),
            "longctx_chunked_prefills": d(
                st_c0, st_c1, "sp_chunked_prefills"),
            "longctx_resume_prefills": d(
                st_c0, st_c1, "sp_resume_prefills"),
            "longctx_interactive_admits": d(
                st_c0, st_c1, "sp_interactive_admits"),
            "longctx_ab_reps": reps,
            "longctx_arrivals": arrivals,
            **_ragged_ab_fields(st_c0, st_c1, "longctx_chunked"),
            **_ragged_ab_fields(st_m0, st_m1, "longctx_monolithic"),
        }

    try:
        return asyncio.run(run())
    finally:
        stop_c()
        stop_m()


def _build_8b_int8():
    from aigw_tpu.models.quant import quantize_params

    cfg = llama.LlamaConfig(max_seq_len=1024)  # LLAMA3_8B shapes
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    params = quantize_params(params, consume=True)
    jax.block_until_ready(params)
    return params, cfg, "llama-3-8b-arch W8A16 int8", "bench-llama3-8b", \
        "int8"


def _suite(params_holder, cfg, desc, model_name, quantize, batch,
           prompt_len, gen_tokens, label, k_steps=K_STEPS,
           reps=3) -> dict:
    """``params_holder`` is a one-element list so THIS frame owns the
    only reference — the caller must del its own binding. The weights
    are freed before the gateway leg's server builds its own copy (the
    8B model fits the chip once, not twice)."""
    params = params_holder.pop()
    raw = raw_ceiling_tokens_per_sec(params, cfg, batch, prompt_len,
                                     k_steps)
    engine_runs, engine_phases = engine_numbers(
        params, cfg, batch, prompt_len, gen_tokens, k_steps, reps=reps)
    engine = _median([r[0] for r in engine_runs])
    engine_ttft = _median([r[1] for r in engine_runs])
    engine_spread = _spread([r[0] for r in engine_runs])
    del params
    gc.collect()
    gw = gateway_numbers(model_name, cfg, quantize, batch, prompt_len,
                         gen_tokens, k_steps, reps=reps)
    device = jax.devices()[0]
    spreads = (engine_spread, gw["direct_tps_spread"],
               gw["gateway_tps_spread"])
    return {
        "metric": (
            f"{label}gateway tokens/sec through `aigw run` → tpuserve "
            f"streaming /v1/chat/completions, {desc}, batch={batch}, "
            f"prompt={prompt_len}, paged KV; vs_baseline = gateway / "
            f"raw-JAX-K-step-scan ceiling (north star: ≥0.9 and "
            f"ttft_ms_p50 < 200); medians of {reps} interleaved reps"
        ),
        "value": round(gw["gateway_tps"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(gw["gateway_tps"] / raw, 4),
        "raw_ceiling": round(raw, 1),
        "ttft_ms_p50": round(gw["gateway_ttft_ms_p50"], 1),
        "engine_tokens_per_sec": round(engine, 1),
        "engine_vs_raw": round(engine / raw, 4),
        "engine_ttft_ms_p50": round(engine_ttft, 1),
        "serve_direct_tokens_per_sec": round(gw["direct_tps"], 1),
        "serve_direct_ttft_ms_p50": round(gw["direct_ttft_ms_p50"], 1),
        "gateway_ttft_minus_direct_ms": round(
            gw["gateway_ttft_ms_p50"] - gw["direct_ttft_ms_p50"], 1),
        "engine_tps_spread": round(engine_spread, 3),
        "direct_tps_spread": gw["direct_tps_spread"],
        "gateway_tps_spread": gw["gateway_tps_spread"],
        # engine-leg host-time phase breakdown (cumulative ms across the
        # warm request + all reps): which serving-path phase moved when
        # the headline does
        "prefill_ms": engine_phases["prefill_ms"],
        "transfer_ms": engine_phases["transfer_ms"],
        "emit_ms": engine_phases["emit_ms"],
        "first_emit_ms": engine_phases["first_emit_ms"],
        # serving-side distribution spreads (ISSUE 5): p50/p95/p99 read
        # from the replica's own phase histograms over the whole capture
        # (warm + all reps) — the interpretable tail behind the
        # client-measured medians above
        "ttft_hist_ms": gw.get("serve_phase_percentiles", {}).get(
            "ttft", {}),
        "per_token_hist_ms": gw.get("serve_phase_percentiles", {}).get(
            "decode_per_token", {}),
        "queue_wait_hist_ms": gw.get("serve_phase_percentiles", {}).get(
            "queue_wait", {}),
        # end-to-end model FLOP/s utilization of the engine leg's
        # decode rate (2·matmul params + attention terms per token ÷
        # the measured device kind's listed peak; raises for a kind
        # with no listed peak, so a CPU run cannot print one)
        "mfu": round(model_mfu(cfg, engine,
                               prompt_len + gen_tokens // 2,
                               device.device_kind), 8),
        "mfu_flops_per_token": round(model_flops_per_token(
            cfg, prompt_len + gen_tokens // 2)),
        "mfu_peak_flops": peak_flops(device.device_kind),
        # where this was measured, as JAX reports it
        "device": {"platform": device.platform,
                   "kind": device.device_kind,
                   "count": len(jax.devices())},
        # the capture is trustworthy when every leg's reps agree within
        # 15% (r4 verdict: the engine leg once measured 44% below the
        # HTTP leg — pure harness variance committed as signal)
        "harness_stable": all(s <= 0.15 for s in spreads),
    }


def run_live() -> dict:
    """One full live measurement on the device ``main`` booted. A model
    that does not fit is an error, not a smaller model."""
    params, cfg, desc, model_name, quantize = _build_8b_int8()
    holder = [params]
    del params  # _suite must hold the only reference to free the HBM
    return _suite(holder, cfg, desc, model_name, quantize, BATCH,
                  PROMPT_LEN, GEN_TOKENS, label="")


# -- moe leg: expert-parallel serving at parity (ISSUE 18) ----------------

#: tiny-moe serving geometry (4 experts top-2, GQA GROUP=2) at bench
#: scale — the family the deleted fallback-matrix rows used to demote
_MOE_CFG = mixtral.MixtralConfig(
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, n_experts=4, experts_per_token=2, max_seq_len=512,
    rope_theta=10000.0,
)
_MOE_PAGE = 16
#: seeded mixed-length admission burst (byte-tokenizer token counts),
#: fired concurrently so both children coalesce one admission. Five
#: ~97-token prompts share the 128 bucket — the bucketed control pads
#: each to 128 AND pads the 5-row group to 8 rows; the ragged pack
#: pays only the chunk residue of the 685-token total
_MOE_MIX = (97, 97, 97, 97, 97, 200)


async def _drive_moe_burst(s, url: str, model: str, gen_tokens: int,
                           tag: str) -> list[tuple[float, str]]:
    """Fire the MoE mixed-length burst concurrently; returns per-request
    (TTFT ms, generated text) — the text feeds the byte-identity check
    between the ragged+fused child and the bucketed+chained control."""

    async def one(n_tokens: int, i: int) -> tuple[float, str]:
        text = (f"{tag}{i:02d}" + "x" * n_tokens)[: n_tokens - 1]
        payload = {
            "model": model,
            "prompt": text,
            "max_tokens": gen_tokens,
            "temperature": 0.0,
            "stream": True,
            "logit_bias": {"97": 100},
        }
        t0 = time.perf_counter()
        first = -1.0
        out: list[str] = []
        async with s.post(url + "/v1/completions", json=payload) as resp:
            assert resp.status == 200, resp.status
            while True:
                line = await resp.content.readline()
                if not line:
                    break
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[6:]
                if data == b"[DONE]":
                    break
                ev = json.loads(data)
                ch = ev.get("choices") or []
                if ch and ch[0].get("text"):
                    if first < 0:
                        first = (time.perf_counter() - t0) * 1000.0
                    out.append(ch[0]["text"])
        return first, "".join(out)

    return list(await asyncio.gather(
        *(one(n, i) for i, n in enumerate(_MOE_MIX))))


def moe_numbers(reps: int = 3, gen_tokens: int = 8) -> dict:
    """The ``--ab moe`` leg (ISSUE 18): the same seeded mixed-length
    burst against TWO tiny-moe tpuserve children — ragged prefill +
    fused decode (the program families the deleted fallback rows now
    admit MoE to) vs the xla-bucketed + chained control — with reps
    interleaved so host drift cancels. The claims:

    - **byte-identical streams**: expert parity is exactness, not
      closeness — both children serve f32 params/KV and greedy
      sampling, so every generated character must match.
    - **padding tax**: the bucketed child pays bucket + pow2 group-row
      padding; the ragged pack pays only chunk residue (per-child
      padded_frac from the /state token counters).
    - **routing surface**: moe_dropped_frac / moe_expert_imbalance /
      moe_tokens_routed off the child's /state — the gauges the
      gateway picker prices (PR 10 worst-device discipline).
    - zero hot compiles on either child over the timed reps. TTFT
      medians are reference only: the CPU host runs the XLA fallbacks,
      not the DMA-skip kernels."""
    import aiohttp

    model_name = "bench-moe-tiny"
    engine_common = {
        "min_prefill_bucket": 32, "num_pages": 112,
        "max_queued_requests": 64, "kv_cache_dtype": "float32",
        "enable_prefix_cache": False,
        # one coalesced admission is the quantity under test (same
        # rationale as the ragged leg; the wait cancels from the A/B)
        "admission_coalesce_ms": 20.0,
    }
    k = int(os.environ.get("AIGW_BENCH_CPU_K", "4"))
    url_moe, stop_moe = _start_tpuserve_subproc(
        model_name, _MOE_CFG, "", batch=8, k_steps=k,
        engine=dict(engine_common, attention_backend="pallas-ragged",
                    decode_backend="fused"),
        page=_MOE_PAGE, param_dtype="float32", family="mixtral")
    url_ctl, stop_ctl = _start_tpuserve_subproc(
        model_name, _MOE_CFG, "", batch=8, k_steps=k,
        engine=dict(engine_common, attention_backend="xla-bucketed"),
        page=_MOE_PAGE, param_dtype="float32", family="mixtral")

    async def run() -> dict:
        await _wait_health(url_moe, 1200)
        await _wait_health(url_ctl, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off-the-clock warm pass: compile whatever shapes the warm
            # ladders missed on either leg
            for url in (url_moe, url_ctl):
                await _drive_moe_burst(s, url, model_name, gen_tokens,
                                       "w")
            st_moe0 = await _get_state(s, url_moe)
            st_ctl0 = await _get_state(s, url_ctl)
            moe_runs, ctl_runs = [], []
            for rep in range(reps):
                moe_runs.extend(await _drive_moe_burst(
                    s, url_moe, model_name, gen_tokens, f"r{rep}"))
                ctl_runs.extend(await _drive_moe_burst(
                    s, url_ctl, model_name, gen_tokens, f"r{rep}"))
            st_moe1 = await _get_state(s, url_moe)
            st_ctl1 = await _get_state(s, url_ctl)
        identical = all(a[1] == b[1]
                        for a, b in zip(moe_runs, ctl_runs))
        mt = _median([t for t, _ in moe_runs if t > 0])
        ct = _median([t for t, _ in ctl_runs if t > 0])
        return {
            "moe_ragged_ttft_ms_p50": round(mt, 1),
            "moe_bucketed_ttft_ms_p50": round(ct, 1),
            "moe_identical_streams": identical,
            "moe_backend": st_moe1.get("attention_backend", ""),
            "moe_decode_impl": st_moe1.get("decode_attn_impl", ""),
            "moe_dropped_frac": st_moe1.get("moe_dropped_frac", 0.0),
            "moe_expert_imbalance": st_moe1.get(
                "moe_expert_imbalance", 0.0),
            "moe_tokens_routed": (st_moe1.get("moe_tokens_routed", 0)
                                  - st_moe0.get("moe_tokens_routed", 0)),
            "moe_ab_reps": reps * len(_MOE_MIX),
            **_ragged_ab_fields(st_moe0, st_moe1, "moe_ragged"),
            **_ragged_ab_fields(st_ctl0, st_ctl1, "moe_bucketed"),
        }

    try:
        return asyncio.run(run())
    finally:
        stop_moe()
        stop_ctl()


def _hist_q_bound(h0: dict, h1: dict, q: float) -> float:
    """Quantile BUCKET BOUND from cumulative-histogram deltas over one
    capture window: the smallest finite bucket upper bound whose
    cumulative delta covers ``q`` of the window's observations. Coarse
    by construction (bucket resolution), but server-side — and for the
    batch tier that is the point: the engine's TTFT histogram only ever
    observes interactive streams, so the mixed-phase delta is already
    batch-free with no client filtering."""
    total = h1.get("+Inf", 0) - h0.get("+Inf", 0)
    if total <= 0:
        return 0.0
    finite = sorted(((float(le), le) for le in h1 if le != "+Inf"))
    for bound, le in finite:
        if h1.get(le, 0) - h0.get(le, 0) >= q * total:
            return bound
    return 2.0 * finite[-1][0] if finite else 0.0


# the identity probe's decodable-alphabet bias: +100 on bytes a–z pins
# greedy INSIDE the byte-decodable range (the tiny model's natural
# argmax lands on ids ≥ 256, which the ByteTokenizer drops — the text
# channel would compare empty strings) while WHICH letter wins each
# step still depends on the full KV content — a real byte-identity
# signal that survives tokenizer decode
_IDENT_BIAS = {str(t): 100 for t in range(97, 123)}


async def _batch_submit(s, url: str, model: str, n_lines: int,
                        max_tokens: int, tag: str,
                        logit_bias: bool = True,
                        bias: dict | None = None) -> str:
    """Upload a JSONL input and create a /v1/completions batch; returns
    the batch id. Asserts the submit path never sheds (the never-429
    claim rides every submission the leg makes)."""
    lines = []
    for i in range(n_lines):
        body = {"model": model,
                "prompt": (f"{tag}{i:03d}" + "b" * 64)[:63],
                "max_tokens": max_tokens, "temperature": 0.0}
        if bias is not None:
            body["logit_bias"] = bias
        elif logit_bias:
            body["logit_bias"] = {"97": 100}
        lines.append(json.dumps({
            "custom_id": f"{tag}-{i:03d}", "method": "POST",
            "url": "/v1/completions", "body": body}))
    raw = ("\n".join(lines) + "\n").encode()
    async with s.post(url + "/v1/files", data=raw) as resp:
        assert resp.status == 200, f"file upload {resp.status}"
        fid = (await resp.json())["id"]
    async with s.post(url + "/v1/batches", json={
            "input_file_id": fid,
            "endpoint": "/v1/completions"}) as resp:
        assert resp.status == 200, f"batch create {resp.status}"
        return (await resp.json())["id"]


async def _batch_poll(s, url: str, bid: str,
                      timeout_s: float = 900.0) -> dict:
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        async with s.get(url + f"/v1/batches/{bid}") as resp:
            b = await resp.json()
        if b["status"] in ("completed", "cancelled"):
            return b
        await asyncio.sleep(0.25)
    raise TimeoutError(f"batch {bid} never finalized")


async def _batch_cancel_drain(s, url: str, bid: str) -> dict:
    """Cancel + wait until the batch finalizes AND its engine-side
    footprint (active slots, queued, parked) is gone — the next phase
    must start from a quiet batch tier."""
    async with s.post(url + f"/v1/batches/{bid}/cancel") as resp:
        await resp.read()
    b = await _batch_poll(s, url, bid)
    while True:
        st = await _get_state(s, url)
        if (not st.get("batch_active", 0)
                and not st.get("batch_queued", 0)):
            return b
        await asyncio.sleep(0.1)


async def _batch_texts(s, url: str, b: dict) -> dict[str, str]:
    """custom_id → generated text from a finalized batch's output
    JSONL file."""
    async with s.get(url + f"/v1/files/{b['output_file_id']}/content") \
            as resp:
        assert resp.status == 200, f"output fetch {resp.status}"
        raw = await resp.read()
    out: dict[str, str] = {}
    for ln in raw.decode().splitlines():
        rec = json.loads(ln)
        body = (rec.get("response") or {}).get("body") or {}
        ch = (body.get("choices") or [{}])[0]
        out[rec["custom_id"]] = ch.get("text", "")
    return out


async def _batch_wait_active(s, url: str, min_tokens: int = 0,
                             timeout_s: float = 120.0) -> dict:
    """Wait until the batch tier holds at least one slot (and has
    generated ``min_tokens`` — a parked slot must have generated ≥ 1,
    so the preemption probe waits for real decode progress)."""
    st0 = await _get_state(s, url)
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        st = await _get_state(s, url)
        if (st.get("batch_active", 0) >= 1
                and (st.get("batch_tokens", 0)
                     - st0.get("batch_tokens", 0)) >= min_tokens):
            return st
        await asyncio.sleep(0.1)
    raise TimeoutError("batch tier never went active")


def batch_tier_numbers(reps: int = 3, arrivals: int = 18) -> dict:
    """The ``--ab batch_tier`` leg (ISSUE 19): ONE f32 tpuserve child,
    three phases per rep over the SAME seeded open-loop interactive
    trace — (a) interactive solo, (b) batch solo (the measured
    idle-slot capacity: the tier's ``batch_slot_frac`` ceiling running
    on an otherwise idle engine), (c) interactive + saturating
    /v1/batches backlog. The portable claims:

    - **interactive TTFT unmoved**: server-side TTFT p95 bucket-bound
      ratio solo/mixed ≥ 0.9. The engine's TTFT histogram never
      observes batch streams, so the mixed-phase delta is already the
      interactive class with no client-side filtering.
    - **idle slots soaked**: mixed-phase batch tokens/s ≥ 0.5× the
      batch-solo capacity — the offline tier keeps earning while the
      interactive trace runs over it.
    - **preempt/resume is exact**: off the clock, a batch stream
      parked mid-decode by an interactive burst (the migration-export
      rung of the preemption ladder) finishes with text identical to
      an uninterrupted run of the same line, with state_rebuilds == 0.
    - zero hot XLA compiles across the timed phases; batch submits
      never see a 429 (asserted on every submission)."""
    import aiohttp

    model_name = "bench-batch-tiny"
    url, stop = _start_tpuserve_subproc(
        model_name, CPU_CFG, "", batch=8,
        k_steps=int(os.environ.get("AIGW_BENCH_CPU_K", "4")),
        engine={"kv_cache_dtype": "float32", "num_pages": 96,
                "max_queued_requests": 64, "batch_slot_frac": 0.5},
        param_dtype="float32")

    def mk_trace(seed: int) -> list[dict]:
        return _poisson_trace(seed, arrivals, rate_hz=4.0,
                              prompt_lens=(48, 96), gen_lens=(8, 16),
                              burst_frac=0.3)

    async def pressured_identity(s) -> dict:
        """The off-clock preempt/resume probe: one alphabet-biased
        greedy batch line (see _IDENT_BIAS) run uninterrupted, then
        the same line parked mid-decode by a zero-gap interactive
        burst. Also the warm pass for the park/resume program shapes —
        it runs BEFORE the compile baseline on purpose."""
        bid = await _batch_submit(s, url, model_name, 1, 40, "idsolo",
                                  bias=_IDENT_BIAS)
        texts_a = await _batch_texts(
            s, url, await _batch_poll(s, url, bid))
        st0 = await _get_state(s, url)
        bid = await _batch_submit(s, url, model_name, 1, 40, "idsolo",
                                  bias=_IDENT_BIAS)
        await _batch_wait_active(s, url, min_tokens=2)
        burst = [{"at": 0.0, "prompt_len": 48, "gen": 8,
                  "tenant": "", "i": i} for i in range(12)]
        await _drive_openloop(s, url, model_name, burst, tag="idp")
        texts_b = await _batch_texts(
            s, url, await _batch_poll(s, url, bid))
        st1 = await _get_state(s, url)
        # custom_ids match across runs (same tag), so compare values
        return {
            "batch_tier_identical_streams": (
                list(texts_a.values()) == list(texts_b.values())
                # the bias alphabet decodes 1 char/token: a full-length
                # text proves the comparison never collapsed to ""
                and all(len(t) >= 40 for t in texts_a.values())),
            "batch_tier_preemptions": (st1.get("batch_preemptions", 0)
                                       - st0.get("batch_preemptions",
                                                 0)),
            "batch_tier_resumed": (st1.get("batch_resumed", 0)
                                   - st0.get("batch_resumed", 0)),
            "batch_tier_state_rebuilds": (st1.get("state_rebuilds", 0)
                                          - st0.get("state_rebuilds",
                                                    0)),
        }

    async def run() -> dict:
        await _wait_health(url, 1200)
        timeout = aiohttp.ClientTimeout(total=1200)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            # off-the-clock warm pass: the interactive buckets, the
            # batch prompt bucket, and (via the identity probe) the
            # park/export + resume/import program shapes
            await _drive_openloop(s, url, model_name, mk_trace(1)[:4],
                                  tag="w")
            bid = await _batch_submit(s, url, model_name, 4, 8, "warm")
            await _batch_poll(s, url, bid)
            ident = await pressured_identity(s)

            st_c0 = await _get_state(s, url)
            ttft_ratios, soak_ratios = [], []
            solo_tps_all, mixed_tps_all = [], []
            cl_solo, cl_mixed = [], []
            shed_solo = shed_mixed = 0
            for rep in range(reps):
                trace = mk_trace(7000 + rep)
                # (a) interactive solo
                h0 = await _ttft_hists(s, [url])
                r_solo = await _drive_openloop(s, url, model_name,
                                               trace, tag=f"s{rep}")
                h1 = await _ttft_hists(s, [url])
                # (b) batch-solo capacity window (idle-slot capacity:
                # the ceiling's slots on an otherwise idle engine)
                bid = await _batch_submit(s, url, model_name, 48, 24,
                                          f"bs{rep}")
                stb0 = await _batch_wait_active(s, url)
                tb0 = time.perf_counter()
                await asyncio.sleep(4.0)
                stb1 = await _get_state(s, url)
                tb1 = time.perf_counter()
                await _batch_cancel_drain(s, url, bid)
                solo_tps = ((stb1.get("batch_tokens", 0)
                             - stb0.get("batch_tokens", 0))
                            / (tb1 - tb0))
                # (c) interactive + saturating batch backlog
                bid = await _batch_submit(s, url, model_name, 48, 24,
                                          f"bm{rep}")
                stm0 = await _batch_wait_active(s, url)
                h2 = await _ttft_hists(s, [url])
                tm0 = time.perf_counter()
                r_mixed = await _drive_openloop(s, url, model_name,
                                                trace, tag=f"m{rep}")
                tm1 = time.perf_counter()
                h3 = await _ttft_hists(s, [url])
                stm1 = await _get_state(s, url)
                await _batch_cancel_drain(s, url, bid)
                mixed_tps = ((stm1.get("batch_tokens", 0)
                              - stm0.get("batch_tokens", 0))
                             / (tm1 - tm0))
                p_solo = _hist_q_bound(h0, h1, 0.95)
                p_mixed = _hist_q_bound(h2, h3, 0.95)
                if p_solo > 0 and p_mixed > 0:
                    ttft_ratios.append(p_solo / p_mixed)
                if solo_tps > 0:
                    soak_ratios.append(mixed_tps / solo_tps)
                solo_tps_all.append(solo_tps)
                mixed_tps_all.append(mixed_tps)
                cl_solo.extend(r_solo["client_ttft_ms"])
                cl_mixed.extend(r_mixed["client_ttft_ms"])
                shed_solo += r_solo["shed"]
                shed_mixed += r_mixed["shed"]
            st_c1 = await _get_state(s, url)
        return {
            "batch_tier_interactive_ttft_p95_ratio": round(
                _median(ttft_ratios), 4),
            "batch_tier_ttft_ratio_spread": round(
                _spread(ttft_ratios), 3),
            "batch_tier_client_ttft_p95_solo_ms": round(
                _p95(cl_solo), 1),
            "batch_tier_client_ttft_p95_mixed_ms": round(
                _p95(cl_mixed), 1),
            "batch_tier_soak_ratio": round(_median(soak_ratios), 4),
            "batch_tier_soak_spread": round(_spread(soak_ratios), 3),
            "batch_tier_batch_solo_tps": round(
                _median(solo_tps_all), 1),
            "batch_tier_batch_mixed_tps": round(
                _median(mixed_tps_all), 1),
            "batch_tier_interactive_shed_solo": shed_solo,
            "batch_tier_interactive_shed_mixed": shed_mixed,
            "batch_tier_slot_frac": st_c1.get("batch_slot_frac", 0.0),
            "batch_tier_hot_compiles": (st_c1.get("xla_compiles", 0)
                                        - st_c0.get("xla_compiles", 0)),
            "batch_tier_ab_reps": reps,
            **ident,
        }

    try:
        return asyncio.run(run())
    finally:
        stop()


def _bench_lock():
    """One bench at a time: two concurrent suites on one host measure
    each other. Tries for 15 min, then proceeds with a warning rather
    than deadlocking the driver."""
    import fcntl

    here = os.path.dirname(os.path.abspath(__file__))
    f = open(os.path.join(here, "benchmarks", ".bench.lock"), "w")
    deadline = time.time() + 900
    while True:
        try:
            fcntl.flock(f, fcntl.LOCK_EX | fcntl.LOCK_NB)
            return f
        except OSError:
            if time.time() > deadline:
                print("bench lock busy for 15min — proceeding anyway",
                      file=sys.stderr)
                return f
            time.sleep(5)


def main() -> None:
    from aigw_tpu.utils.boot import boot_jax

    lock = _bench_lock()  # held for process lifetime  # noqa: F841

    if "--ab" in sys.argv:
        # every --ab leg serves from subprocess children pinned to the
        # CPU (_start_tpuserve_subproc) and reports counts; name the CPU
        # here too so this process can never take a chip it will not use
        boot_jax("cpu")
        idx = sys.argv.index("--ab")
        target = sys.argv[idx + 1] if idx + 1 < len(sys.argv) else ""
        if target == "prefix_cache":
            result = prefix_cache_numbers()
            result["metric"] = (
                "gateway_prefix interleaved A/B — prefix_cache on vs "
                "off, shared 64-token system-prompt head, ~96-token "
                "prompts, sequential streaming chats on the CPU "
                "backend; the warm/cold ratio is the signal, absolute "
                "ms is not")
        elif target == "spec_decode":
            result = spec_decode_numbers()
            result["metric"] = (
                "spec_decode interleaved A/B — speculative decoding on "
                "vs off, decode-heavy sequential streaming chats on "
                "the CPU backend: repetitive leg (n-gram drafts "
                "accept) and forced low-acceptance leg (adaptive "
                "ladder collapses to plain decode); the tok/s ratios "
                "are the signal, absolute tok/s is not")
        elif target == "ragged_prefill":
            result = ragged_prefill_numbers()
            result["metric"] = (
                "ragged_prefill interleaved A/B — attention backend "
                "pallas-ragged vs xla-bucketed on the same "
                "mixed-length admission burst (5×~97 + 1×1024 tokens) "
                "on the CPU backend: padded_frac (padding tax) and the "
                "warm compile surface are the signal; absolute TTFT "
                "is not (the CPU child runs the XLA windowed fallback, "
                "not the DMA-skip kernel)")
        elif target == "lora":
            result = lora_numbers()
            result["metric"] = (
                "lora interleaved A/B — adapter-mix traffic (rotating "
                "LoRA adapters, model '<base>:t{i}') vs base-only "
                "traffic (the zero-row control) on ONE 5-adapter/"
                "4-row tpuserve child, decode-heavy sequential "
                "streaming chats on the CPU backend; the tok/s ratio "
                "(parity), zero hot compiles across mix changes and "
                "the evict/reload churn phase, and the load/eviction "
                "counters are the signal — absolute tok/s is not")
        elif target == "disagg":
            result = disagg_numbers()
            result["metric"] = (
                "disagg interleaved A/B — prefill/decode disaggregation "
                "over two tpuserve replicas: a session streamed on A is "
                "exported after its first tokens and resumed on B via "
                "KV page migration; resume TTFT vs a cold prefill of "
                "the same total length (target ≤ 0.6), plus a gateway "
                "migration-on/off open-loop pass; ratios are the "
                "signal, absolute ms is not (CPU backend)")
        elif target == "slo_routing":
            result = slo_routing_numbers()
            result["metric"] = (
                "slo_routing A/B — the same seeded open-loop Poisson "
                "trace through a picker_mode=slo gateway (predicted-"
                "TTFT routing + 429 shed) vs a static-score gateway "
                "over the same heterogeneous 2-replica pool; goodput-"
                "under-SLO from server-side TTFT histograms is the "
                "signal (CPU backend)")
        elif target == "structured":
            result = structured_numbers()
            result["metric"] = (
                "structured A/B — grammar-constrained decoding (ISSUE "
                "9): the same seeded open-loop arrival schedule against "
                "one speculation-on tpuserve child, 25% of arrivals "
                "asking for json_schema output vs an all-plain control "
                "at matched token volume; 100% schema-valid constrained "
                "responses, zero hot XLA compiles, and the mixed/plain "
                "throughput ratio (constraint bookkeeping price) are "
                "the signal (CPU backend)")
        elif target == "mesh":
            result = mesh_numbers()
            result["metric"] = (
                "mesh A/B — tensor-parallel serving at parity (ISSUE "
                "10): the same seeded mixed-feature streaming traffic "
                "against a tp=8 child (8 virtual CPU devices, params + "
                "paged KV sharded per the TP layout) vs a single-"
                "device child; byte-identical streams, per-device "
                "parameter bytes ≈ total/tp, and zero hot compiles on "
                "the warmed mesh path are the signal — the throughput "
                "ratio is informational on CPU (virtual devices time-"
                "slice one core)")
        elif target == "kv_tier":
            result = kv_tier_numbers()
            result["metric"] = (
                "kv_tier A/B — fleet KV memory hierarchy (ISSUE 11): "
                "shared-prefix open-loop bursts against replica B with "
                "sibling A warm — warm fleet (A named in x-aigw-kv-"
                "peers: arrival 0 fetches A's pages over /kv/pages and "
                "resumes) vs cold fleet (unprimed head, full prefill); "
                "first-arrival TTFT ratio ≤ 0.6 is the claim, plus "
                "spill→revive churn counters on A's host tier and a "
                "zero-hot-compile delta across the churn (CPU backend; "
                "ratios are the signal)")
        elif target == "fleet_obs":
            result = fleet_obs_numbers()
            result["metric"] = (
                "fleet_obs A/B — the fleet observability plane (ISSUE "
                "12) must be ~free: the same seeded open-loop trace "
                "through a gateway with the decision ring + burn-rate "
                "monitor on and a 4Hz /fleet/metrics federation "
                "scraper running, vs everything off; throughput ratio "
                "≥ 0.95 and zero hot XLA compiles are the claim (CPU "
                "backend)")
        elif target == "decode_fused":
            result = decode_fused_numbers()
            result["metric"] = (
                "decode_fused interleaved A/B — fused decode step + "
                "quantized KV pages (ISSUE 13): the same greedy "
                "decode-heavy chats against fused-vs-chained f32 "
                "children (streams must be identical; tok/s ratio is "
                "bookkeeping parity on the CPU backend — the kernel's "
                "HBM win needs the on-chip capture) and an int8-KV "
                "fused child (bytes/token ≤ 0.55x bf16 and greedy "
                "agreement vs the native child are the capacity/"
                "quality signals)")
        elif target == "fleet_ctl":
            result = fleet_ctl_numbers()
            result["metric"] = (
                "fleet_ctl chaos A/B — the fleet control plane (ISSUE "
                "14) under injected churn: the seeded open-loop trace "
                "over a controller-enabled 2-replica pool with one "
                "kill -9 mid-decode (failover: re-route + replacement "
                "launch through the local launcher) and one flood-"
                "triggered scale-out (the SLO monitor's sustained-"
                "overshoot predicate); dropped (torn) streams == 0, "
                "goodput recovery ≥0.9× the pre-event window in a "
                "bounded reported time, and zero hot XLA compiles on "
                "the surviving replica are the claims (CPU backend)")
        elif target == "longctx":
            result = longctx_numbers()
            result["metric"] = (
                "longctx A/B — sequence-sharded chunked prefill "
                "(ISSUE 17): the same long-context traffic against "
                "two sp=8 children (8 virtual CPU devices) — chunked "
                "vs monolithic sp prefill; short interactive streams "
                "fired mid-long-prefill admit at chunk boundaries "
                "(interactive TTFT p95 ≥ 2× better chunked) and a "
                "primed head + continuation resumes the chunk loop "
                "at the adopted page offset (warm/cold TTFT ≤ 0.6); "
                "padded_frac < 0.05 on the chunk rung ladder and "
                "zero hot XLA compiles at long-context geometry are "
                "the guardrails (CPU backend; ratios are the signal)")
        elif target == "moe":
            result = moe_numbers()
            result["metric"] = (
                "moe interleaved A/B — expert-parallel serving at "
                "parity (ISSUE 18): the same seeded mixed-length "
                "burst against a tiny-moe ragged+fused child vs the "
                "xla-bucketed+chained control (the two deleted "
                "fallback-matrix rows); byte-identical streams, the "
                "padded_frac gap, zero hot compiles, and the "
                "moe_dropped_frac / expert-imbalance routing gauges "
                "are the signal — absolute TTFT is not (CPU backend "
                "runs the XLA fallbacks, not the DMA-skip kernels)")
        elif target == "batch_tier":
            result = batch_tier_numbers()
            result["metric"] = (
                "batch_tier A/B — priority-tiered serving (ISSUE 19): "
                "the same seeded open-loop interactive trace against "
                "one f32 child, solo vs over a saturating /v1/batches "
                "backlog; interactive TTFT p95 ratio ≥ 0.9 from the "
                "server-side histogram (which never observes batch "
                "streams), mixed batch tokens ≥ 0.5× the measured "
                "batch-solo idle-slot capacity, zero hot XLA "
                "compiles, never a 429 on batch submits, and an "
                "off-clock preempt-mid-decode/resume run whose text "
                "is identical to the uninterrupted run with "
                "state_rebuilds == 0 (CPU backend; ratios are the "
                "signal)")
        elif target == "metering":
            result = metering_numbers()
            result["metric"] = (
                "metering A/B — engine-truth usage metering (ISSUE "
                "20) must be ~free: the same seeded open-loop trace "
                "through a gateway journaling every MeterRecord into "
                "the windowed per-tenant ledger with a meter-variable "
                "CostProgram pricing each request and a 4Hz /usage + "
                "/metrics poller running, vs usage disabled; "
                "throughput ratio ≥ 0.95, zero hot XLA compiles, and "
                "ledger record count == completed trace requests are "
                "the claims (CPU backend)")
        else:
            print(json.dumps({"error": f"unknown --ab target {target!r}; "
                              "supported: prefix_cache, spec_decode, "
                              "ragged_prefill, lora, disagg, "
                              "slo_routing, structured, mesh, "
                              "kv_tier, fleet_obs, decode_fused, "
                              "fleet_ctl, longctx, moe, batch_tier, "
                              "metering"}))
            return
        print(json.dumps(result))
        return

    # a TPU, or the platform JAX_PLATFORMS names — never a fallback
    boot_jax()
    print(json.dumps(run_live()))


if __name__ == "__main__":
    main()
