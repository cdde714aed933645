#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls:
``python -m aigw_tpu tpuserve`` serving **qwen2-7b at published widths
and full depth (28 layers), W8A16 int8 weights, bf16 KV, seeded random
weights** on one TPU chip, behind ``python -m aigw_tpu run`` (TPUServe
backend, endpoint picker on). It checks what comes out by the repo's own
means and prints, as the LAST line of stdout, one JSON object with
exactly these keys (the driver's contract), the device as JAX reported
it to the serving child::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The line before it is a JSON summary of what was served (model, layers,
widths, weight format, verdict per phase, seconds).

Exit code 0 only if every phase passed. Without an accelerator it exits
non-zero and prints no result: no platform is named to the children, so
``aigw_tpu/utils/boot.py`` requires a TPU and says which platform JAX
found instead.

**This process never imports jax** (asserted before exit). A chip
belongs to one process at a time — a parent that has touched JAX holds
it and a child that needs it then fails or hangs — so everything that
needs the chip is a child started through the normal CLI, one at a
time, and every child is stopped before the next starts.

Phases (one chip; the driver's form)::

    build    make -C native from the committed sources
    serve    tpuserve + gateway: a non-stream chat, an 8-chat streaming
             burst sent twice (the second pass must compile nothing), a
             ~1,500-token prompt (chunked prefill) whose system head is
             then asked again (prefix hit), a /v1/completions straight
             at the replica; /health, /state placement checks; gateway
             /usage totals == the replica's meter_* counters
    kernels  the two Pallas kernels (ragged prefill, W8A16 matmul)
             compiled against their XLA twins
             (python -m aigw_tpu.ops.pallas.parity)
    ragged   a second boot with --attention-backend pallas-ragged: the
             prefill must resolve to its kernel and the decode to the
             page walk on /state, and answer the same burst

``--chips 4`` (run by the builder on a four-chip host) replaces them with
``tp4`` (bf16 weights created already sharded over a tp=4 mesh) and
``replicas`` (four one-chip tpuserve processes, each confined to its own
chip by its environment, behind one gateway: 64 requests, every replica
serves some).

``--platform cpu --model tiny-random`` is the orchestrator's dry run on
a CPU (tests/test_chip_smoke.py); it cannot pass for a TPU. Any
registered model serves the ``build,serve`` phases the same way — a
family's tiny preset among them (``--model tiny-qwen3-next``,
``tiny-axk1``, ``tiny-mimo-v2``, ``tiny-olmo-hybrid`` with
``--no-quantize``: those families refuse ``--quantize``); the repeated
burst then also checks that a family whose prefix cache resumes from a
state snapshot answers it with zero compiles.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import signal
import socket
import statistics
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

#: bf16-free W8A16 bytes of qwen2-7b as compiled ahead of time for a v5e
#: (int8 matrices + f32 scales + bf16 norms/biases), ISSUE 21
QWEN2_7B_W8A16_BYTES = 7.62e9

#: every sampled token pinned to ASCII 'a' through the real sampling
#: path: greedy random-weight output is mostly UTF-8 continuation bytes
#: (no visible SSE delta), and EOS can then never be sampled — every
#: response has exactly max_tokens tokens
PIN_TOKEN = {"97": 100}

# Request sizes. They are constants, not options: serve_flags() warms
# exactly the programs these sizes need, so another value would break
# the zero-compile and prefix checks rather than test anything else.
# Every extra prefill shape is ~20 s of cold compile on the chip.

#: seconds a tpuserve child may take from start to /health (a cold
#: qwen2-7b boot compiles ~20 programs at ~20 s each)
BOOT_TIMEOUT_S = 900.0
#: burst prompt, characters. Byte tokenizer: + 22 template tokens lands
#: in the 128 bucket; each further prefill octave warmed is 4 programs
BURST_PROMPT_CHARS = 100
#: system head of the long prompt: + 11 template tokens = 10 full
#: 128-token pages for the prefix cache to keep
HEAD_CHARS = 1290
#: its user question: ~1,500 prompt tokens in all (chunked prefill), and
#: the suffix behind the 10 cached pages stays in the 256 rung, so the
#: cold chunks, their tail and the warm suffix share ONE [1, 256] program
QUESTION_CHARS = 171
#: tokens per request of the replicas phase: the decode must outlast a
#: picker poll (0.2 s) or the picker never sees load to route around
REPLICA_TOKENS = 64


class PhaseError(AssertionError):
    """A check of the running phase failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


# -- children -----------------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One child process with its output in a log file; stopped with
    SIGTERM (tpuserve drains and exits 0), SIGKILL after a grace."""

    def __init__(self, name: str, argv: list[str], env: dict, out_dir: str):
        self.name = name
        self.log_path = os.path.join(out_dir, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            argv, cwd=HERE, env=env, stdout=self._log,
            stderr=subprocess.STDOUT)
        log(f"started {name} (pid {self.proc.pid}): {' '.join(argv[1:])}")

    def tail(self, n: int = 25) -> str:
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def stop(self, grace_s: float = 20.0) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
        self._log.close()


def child_env(args, extra: dict | None = None) -> dict:
    """Environment of a child that opens the accelerator. No platform
    is named unless the caller named one: boot then requires a TPU."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.update(extra or {})
    return env


def platform_flag(args) -> list[str]:
    return ["--platform", args.platform] if args.platform else []


def on_tpu(args) -> bool:
    return (args.platform or "tpu") == "tpu"


def geometry_flags(args, quantize: bool = True) -> list[str]:
    """8 slots x 2048 tokens; int8 weights unless the phase (or a
    dry run) serves bf16."""
    return [*(["--quantize", "int8"] if quantize and args.quantize else []),
            "--max-batch-size", "8", "--max-seq-len", "2048"]


def wait_health(url: str, child: Child, timeout_s: float) -> dict:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        rc = child.proc.poll()
        if rc is not None:
            raise PhaseError(
                f"{child.name} exited with code {rc} before serving:\n"
                f"{child.tail()}")
        try:
            return http_json("GET", url + "/health", timeout=5)
        except (urllib.error.URLError, OSError, PhaseError):
            time.sleep(0.5)
    raise PhaseError(f"{child.name} not healthy after {timeout_s:.0f}s:\n"
                     f"{child.tail()}")


# -- HTTP (stdlib only) -------------------------------------------------------

def http_json(method: str, url: str, body: dict | None = None,
              timeout: float = 600.0) -> dict:
    """The JSON body of a 2xx answer; anything else fails the phase."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"content-type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raise PhaseError(
            f"{method} {url} -> {e.code}: {e.read()[:500]!r}") from e


def stream_chat(url: str, model: str, content: str, max_tokens: int,
                system: str = "") -> dict:
    """One streaming chat; returns ttft seconds, completion tokens and
    the usage tail frame."""
    messages = ([{"role": "system", "content": system}] if system else [])
    messages.append({"role": "user", "content": content})
    body = {
        "model": model, "messages": messages, "max_tokens": max_tokens,
        "temperature": 0.0, "stream": True,
        "stream_options": {"include_usage": True},
        "logit_bias": PIN_TOKEN,
    }
    req = urllib.request.Request(
        url + "/v1/chat/completions", data=json.dumps(body).encode(),
        method="POST", headers={"content-type": "application/json"})
    t0 = time.monotonic()
    first = None
    usage = None
    pieces = 0
    try:
        resp = urllib.request.urlopen(req, timeout=900)
    except urllib.error.HTTPError as e:
        raise PhaseError(
            f"stream chat -> {e.code}: {e.read()[:500]!r}") from e
    with resp:
        check(resp.status == 200, f"stream chat -> {resp.status}")
        for raw in resp:
            line = raw.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[6:]
            if data == b"[DONE]":
                break
            ev = json.loads(data)
            if ev.get("error"):
                raise PhaseError(f"stream error event: {ev['error']}")
            if ev.get("usage"):
                usage = ev["usage"]
            ch = ev.get("choices") or []
            if ch and (ch[0].get("delta") or {}).get("content"):
                if first is None:
                    first = time.monotonic() - t0
                pieces += 1
    check(usage is not None, "stream carried no usage tail frame")
    check_usage(usage, max_tokens)
    check(first is not None and pieces > 0, "stream carried no content")
    return {"ttft_s": first, "tokens": usage["completion_tokens"],
            "wall_s": time.monotonic() - t0, "usage": usage}


def check_usage(usage: dict, max_tokens: int) -> None:
    check(usage.get("completion_tokens") == max_tokens,
          f"expected exactly {max_tokens} completion tokens, usage says "
          f"{usage.get('completion_tokens')}")
    meter = usage.get("aigw_meter")
    check(isinstance(meter, dict) and
          meter.get("decode_tokens", -1) >= max_tokens - 1,
          f"response usage carries no engine meter: {usage}")


def burst(url: str, model: str, tag: str) -> dict:
    """One chat per slot (8), concurrent and streaming, 64 new tokens
    each, with distinct BURST_PROMPT_CHARS prompts (distinct from the
    first character on: the prefix cache must not turn one pass into
    another's resume)."""
    n = 8

    def one(i: int) -> dict:
        content = (f"{tag}{i:02d} " + chr(65 + i % 26) * BURST_PROMPT_CHARS
                   )[:BURST_PROMPT_CHARS]
        return stream_chat(url, model, content, 64)

    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(n) as pool:
        results = list(pool.map(one, range(n)))
    wall = time.monotonic() - t0
    return {
        "requests": n,
        "ttft_ms_median": round(
            1e3 * statistics.median(r["ttft_s"] for r in results), 1),
        "tokens_per_s": round(sum(r["tokens"] for r in results) / wall, 1),
        "wall_s": round(wall, 2),
    }


# -- the pair under test: tpuserve child(ren) + gateway child ------------------

class Stack:
    """tpuserve replica(s) and the gateway in front of them."""

    def __init__(self, args, out_dir: str, name: str,
                 serve_flags: list[str], replica_envs: list[dict] | None = None):
        self.args = args
        self.children: list[Child] = []
        self.replicas: list[str] = []
        self.gateway = ""
        self.boot_s = 0.0
        self._out = out_dir
        self._name = name
        self._serve_flags = serve_flags
        self._replica_envs = replica_envs or [{}]

    def __enter__(self) -> "Stack":
        try:
            self._boot()
        except BaseException:
            self.__exit__()  # a failed boot leaves no child behind
            raise
        return self

    def _boot(self) -> None:
        args = self.args
        t0 = time.monotonic()
        for i, extra in enumerate(self._replica_envs):
            port = free_port()
            child = Child(
                f"{self._name}-tpuserve{i}",
                [sys.executable, "-m", "aigw_tpu", "tpuserve",
                 "--model", args.model, "--port", str(port),
                 "--weights", "random", *self._serve_flags,
                 *platform_flag(args)],
                child_env(args, extra), self._out)
            self.children.append(child)
            self.replicas.append(f"http://127.0.0.1:{port}")
        for url, child in zip(self.replicas, self.children):
            health = wait_health(url, child, BOOT_TIMEOUT_S)
            check(health.get("status") == "ok",
                  f"{child.name} /health: {health}")
        self.boot_s = time.monotonic() - t0
        log(f"{self._name}: {len(self.replicas)} replica(s) healthy after "
            f"{self.boot_s:.1f}s")
        # the gateway: a config shaped like
        # examples/inference-pool/config.yaml (JSON is YAML)
        cfg_path = os.path.join(self._out, f"{self._name}-gateway.yaml")
        with open(cfg_path, "w") as f:
            json.dump({
                "version": "v1",
                "backends": [{
                    "name": "pool", "schema": "TPUServe",
                    "endpoints": [
                        {"address": u[len("http://"):], "slice": "s0"}
                        for u in self.replicas],
                    "picker_poll_interval": 0.2,
                    "picker_content_affinity": True,
                    # a COLD replica compiles on its first requests
                    # (~20 s per program on the chip): the gateway's
                    # default 120 s per-request budget cuts them off
                    "request_timeout": 900.0,
                }],
                "routes": [{"name": "serving", "rules": [
                    {"model_prefixes": [args.model[:4]],
                     "backends": ["pool"]}]}],
                "models": [args.model],
                "llm_request_costs": [
                    {"metadata_key": "total_tokens",
                     "type": "TotalToken"}],
            }, f, indent=1)
        gw_port = free_port()
        gw = Child(
            f"{self._name}-gateway",
            [sys.executable, "-m", "aigw_tpu", "run", cfg_path,
             "--port", str(gw_port)],
            # the gateway opens no accelerator; it inherits the
            # environment untouched
            dict(os.environ), self._out)
        self.children.append(gw)
        self.gateway = f"http://127.0.0.1:{gw_port}"
        health = wait_health(self.gateway, gw, 60)
        check(health.get("native_scanner") == "loaded",
              f"gateway runs the {health.get('native_scanner')!r} scanner, "
              "not the one built from native/")
        # the picker routes on polled /state: let it see every replica
        time.sleep(1.0)

    def __exit__(self, *exc) -> None:
        for child in reversed(self.children):
            child.stop()

    def check_healthy(self) -> None:
        """/health of every replica after traffic: a read of a donated
        buffer kills the engine thread and leaves a live process with
        healthy=false — 503 here fails the phase."""
        for url in self.replicas:
            health = http_json("GET", url + "/health")
            check(health.get("status") == "ok", f"{url}/health: {health}")

    def state(self, i: int = 0) -> dict:
        # the engine thread refreshes the exported stats every tick
        time.sleep(0.6)
        return http_json("GET", self.replicas[i] + "/state")

    def metric(self, name: str, i: int = 0) -> float:
        """One gauge of a replica's /metrics (counters /state omits)."""
        with urllib.request.urlopen(self.replicas[i] + "/metrics",
                                    timeout=30) as resp:
            for line in resp.read().decode().splitlines():
                if line.startswith(name + " "):
                    return float(line.split()[1])
        raise PhaseError(f"/metrics has no {name}")


def check_placement(st: dict, args, n_devices: int = 1) -> None:
    """Where the replica says it runs, from /state."""
    devices = st.get("devices") or []
    check(len(devices) == n_devices,
          f"/state lists {len(devices)} devices, expected {n_devices}")
    want = args.platform or "tpu"
    for d in devices:
        check(d.get("platform") == want,
              f"device {d.get('id')} is on platform "
              f"{d.get('platform')!r}, not {want!r}")
    if on_tpu(args):
        check(st.get("device_bytes_limit", 0) > 0,
              "device_bytes_limit is 0: no HBM behind this replica")


def observations(st: dict) -> dict:
    return {
        # the device as JAX reports it to the replica
        "device": {"platform": st["platform"], "kind": st["device_kind"],
                   "count": st["process_device_count"]},
        "weights_init_s": round(st["weights_init_ms"] / 1e3, 1),
        "weights_quantize_s": round(st["weights_quantize_ms"] / 1e3, 1),
        "warmup_s": round(st["warmup_ms"] / 1e3, 1),
        "warm_programs": st["warm_programs"],
        "xla_compiles": st["xla_compiles"],
        "xla_cache_hits": st["xla_cache_hits"],
        "xla_cache_misses": st["xla_cache_misses"],
        "compile_cache_dir": st["compile_cache_dir"],
        "peak_hbm_bytes": max(
            (d.get("peak_bytes_in_use", 0) for d in st["devices"]),
            default=0),
        "param_bytes_total": st["param_bytes_total"],
        "attention_backend": st["attention_backend"],
        "decode_attn_impl": st["decode_attn_impl"],
    }


def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


# -- phases ---------------------------------------------------------------------

def phase_build(args, out_dir: str) -> dict:
    """The native scanner is a build product: build it from the
    committed sources so this checkout runs what it ships."""
    subprocess.run(["make", "-C", os.path.join(HERE, "native")],
                   check=True, stdout=subprocess.DEVNULL)
    lib = os.path.join(HERE, "native", "libaigw_native.so")
    check(os.path.exists(lib), f"{lib} was not built")
    return {"native_scanner": "built"}


def serve_flags(args) -> list[str]:
    return [*geometry_flags(args),
            # burst prompts land in the 128 bucket: warm every group
            # size of the pow2 ladder up to it so however the 8 arrivals
            # coalesce, the second pass finds its program compiled
            "--prefill-bucket-rungs", "1", "--warm-prefill-buckets", "2"]


def phase_serve(args, out_dir: str) -> dict:
    model = args.model
    with Stack(args, out_dir, "serve", serve_flags(args)) as stack:
        gw = stack.gateway
        st0 = stack.state()
        check_placement(st0, args)
        if model == "qwen2-7b" and args.quantize:
            got = st0["param_bytes_total"]
            check(abs(got - QWEN2_7B_W8A16_BYTES)
                  <= 0.02 * QWEN2_7B_W8A16_BYTES,
                  f"param_bytes_total {got:.4g} is not within 2% of "
                  f"{QWEN2_7B_W8A16_BYTES:.4g}: not the W8A16 model")
        obs = observations(st0)
        obs["boot_s"] = round(stack.boot_s, 1)

        # 1. one non-stream chat
        body = http_json("POST", gw + "/v1/chat/completions", {
            "model": model, "max_tokens": 16, "temperature": 0.0,
            "messages": [{"role": "user", "content": "hello"}],
            "logit_bias": PIN_TOKEN})
        check_usage(body["usage"], 16)
        log("serve: non-stream chat ok")

        # 2. the 8-chat burst, twice; the second pass compiles nothing
        first = burst(gw, model, "p1")
        log(f"serve: burst pass 1 {first}")
        c0 = stack.state()["xla_compiles"]
        second = burst(gw, model, "p2")
        c1 = stack.state()["xla_compiles"]
        log(f"serve: burst pass 2 {second}")
        check(c1 == c0,
              f"the second burst pass compiled {c1 - c0} program(s)")

        # 3 + 4. one ~1,500-token prompt (chunked prefill) whose system
        # head is then asked again (prefix-cache hit); both run ONE
        # prefill program (see HEAD_CHARS / QUESTION_CHARS)
        head = "H" * HEAD_CHARS
        st = stack.state()
        h0, c0 = st["prefix_cache_hits"], st["xla_compiles"]
        r = stream_chat(gw, model, "first " + "q" * QUESTION_CHARS,
                        32, system=head)
        n_prompt = r["usage"]["prompt_tokens"]
        c1 = stack.state()["xla_compiles"]
        log(f"serve: {n_prompt}-token prompt ok, ttft "
            f"{1e3 * r['ttft_s']:.0f} ms, {c1 - c0} programs compiled")
        check(n_prompt >= HEAD_CHARS + QUESTION_CHARS,
              f"long prompt counted {n_prompt} tokens")
        r = stream_chat(gw, model, "again " + "q" * QUESTION_CHARS,
                        32, system=head)
        st = stack.state()
        check(st["prefix_cache_hits"] - h0 >= 1,
              "the repeated system head never hit the prefix cache")
        check(stack.metric("tpuserve_chunked_prefill_steps_total") >= 5,
              "the long prompt never took the chunked prefill path")
        log(f"serve: prefix hit ok, ttft {1e3 * r['ttft_s']:.0f} ms, "
            f"{st['xla_compiles'] - c1} programs compiled")

        # token accounting from real device counts is the system's
        # claim: the gateway's ledger must equal the engine's counters
        deadline = time.monotonic() + 15
        while True:
            usage = http_json("GET", gw + "/usage")["totals"]
            st = stack.state()
            mismatch = {
                k: (usage.get(k), st["meter_" + k])
                for k in ("records", "prefill_tokens",
                          "prefill_padded_tokens", "prefix_reused_tokens",
                          "decode_tokens", "spec_drafted", "spec_accepted")
                if usage.get(k) != st["meter_" + k]}
            if not mismatch or time.monotonic() > deadline:
                break
            time.sleep(0.5)
        check(not mismatch,
              f"gateway /usage != replica meter_* counters: {mismatch}")
        check(usage["records"] == 1 + 16 + 2,
              f"ledger holds {usage['records']} records, sent 19")
        log(f"serve: /usage reconciles ({usage['records']} records, "
            f"{usage['decode_tokens']} decode tokens)")

        # 5. one /v1/completions straight at the replica
        body = http_json(
            "POST", stack.replicas[0] + "/v1/completions", {
                "model": model, "prompt": "straight to the replica",
                "max_tokens": 8, "temperature": 0.0,
                "logit_bias": PIN_TOKEN})
        check_usage(body["usage"], 8)

        stack.check_healthy()
        st = stack.state()
        obs.update({
            "peak_hbm_bytes": observations(st)["peak_hbm_bytes"],
            "xla_compiles_total": st["xla_compiles"],
            "burst_first_pass": first,
            "burst_second_pass": second,
        })
        return obs


def phase_kernels(args, out_dir: str) -> dict:
    if args.platform and args.platform != "tpu":
        raise PhaseError(
            f"Mosaic compiles for a TPU only; --platform {args.platform} "
            "cannot run the kernels phase (drop it with --phases)")
    log_path = os.path.join(out_dir, "kernels.log")
    with open(log_path, "w") as f:
        rc = subprocess.run(
            [sys.executable, "-m", "aigw_tpu.ops.pallas.parity",
             "--model", args.model, *platform_flag(args)],
            cwd=HERE, env=child_env(args), stdout=f,
            stderr=subprocess.STDOUT, timeout=900).returncode
    results = []
    with open(log_path, errors="replace") as f:
        for line in f:
            if line.startswith("{"):
                results.append(json.loads(line))
    bad = [r for r in results if not r.get("ok")]
    check(rc == 0 and results and not bad,
          f"kernel parity child exit {rc}, failed: {bad or 'see log'}\n"
          + open(log_path, errors="replace").read()[-1500:])
    return {"kernels": [r["kernel"] for r in results]}


def phase_ragged(args, out_dir: str) -> dict:
    flags = [*geometry_flags(args),
             "--attention-backend", "pallas-ragged"]
    with Stack(args, out_dir, "ragged", flags) as stack:
        st = stack.state()
        check_placement(st, args)
        want_reason = ("Pallas kernel (single-chip TPU)" if on_tpu(args)
                       else "XLA windowed fallback: no TPU backend")
        check(st["attention_backend"] == "pallas-ragged"
              and st["attention_backend_reason"] == want_reason,
              f"prefill resolved to {st['attention_backend']!r}: "
              f"{st['attention_backend_reason']!r}")
        check(st["decode_attn_impl"] == "xla-walk",
              f"decode resolved to {st['decode_attn_impl']!r}: "
              f"{st['decode_attn_reason']!r}")
        result = burst(stack.gateway, args.model, "r1")
        stack.check_healthy()
        obs = observations(stack.state())
        obs.update(boot_s=round(stack.boot_s, 1), burst=result)
        return obs


#: the four-chip phases are charged four times over: one decode window
#: instead of the adaptive pair halves their decode programs (each
#: ~20 s of compile, cold). The one-chip phases keep every default.
FOUR_CHIP_ECONOMY = ["--no-adaptive-window"]


def phase_tp4(args, out_dir: str) -> dict:
    """bf16 weights (15.2 GB for qwen2-7b: more than one chip holds)
    created already sharded over a tp=4 mesh."""
    flags = ["--tp", "4", *geometry_flags(args, quantize=False),
             "--prefill-bucket-rungs", "1", *FOUR_CHIP_ECONOMY]
    with Stack(args, out_dir, "tp4", flags) as stack:
        st = stack.state()
        check_placement(st, args, n_devices=4)
        total = st["param_bytes_total"]
        per = st["param_bytes_per_device"]
        check(len(per) == 4, f"params live on {len(per)} devices")
        for dev, b in per.items():
            check(abs(b - total / 4) <= 0.10 * total / 4,
                  f"device {dev} holds {b:.4g} param bytes, total/4 is "
                  f"{total / 4:.4g}")
        result = burst(stack.gateway, args.model, "t1")
        st = stack.state()
        stack.check_healthy()
        if on_tpu(args):
            for d in st["devices"]:
                check(d["peak_bytes_in_use"] <= total / 2,
                      f"device {d['id']} peaked at "
                      f"{d['peak_bytes_in_use']:.4g} bytes — more than "
                      "half the model passed through one chip")
        obs = observations(st)
        obs.update(
            boot_s=round(stack.boot_s, 1), burst=result,
            mesh_axes=st["mesh_axes"],
            # parallel/mesh.py takes jax.devices() in list order:
            # the ids and torus coords that order produced
            mesh_devices=[{"id": d["id"], "coords": d["coords"],
                           "peak_bytes_in_use": d["peak_bytes_in_use"],
                           "param_bytes": d["param_bytes"]}
                          for d in st["devices"]])
        return obs


def phase_replicas(args, out_dir: str) -> dict:
    """Four one-chip replicas behind the picker — the documented shape
    (a process per replica), each confined to its own chip by its
    environment, set before the child imports jax."""
    from aigw_tpu.utils.chips import chip_env

    envs = [chip_env(i) if on_tpu(args) else {} for i in range(4)]
    flags = [*geometry_flags(args),
             "--prefill-bucket-rungs", "1", *FOUR_CHIP_ECONOMY]
    with Stack(args, out_dir, "replicas", flags, envs) as stack:
        states = [stack.state(i) for i in range(4)]
        seen = set()
        for i, st in enumerate(states):
            check_placement(st, args)
            seen.add((st["devices"][0]["id"],
                      tuple(st["devices"][0]["coords"]),
                      st.get("visible_chips", "")))
        if on_tpu(args):
            check(len(seen) == 4,
                  f"four replicas name {len(seen)} distinct devices: "
                  f"{sorted(seen)}")
        before = [st["meter_records"] for st in states]

        # open-loop arrivals, one every 120 ms: the picker routes on
        # POLLED load (no in-flight accounting between polls), so a
        # simultaneous burst lands whole on one replica — staggered
        # arrivals with decodes longer than a poll let it see load
        def arrival(i: int) -> int:
            time.sleep(max(0.0, t_start + 0.12 * i - time.monotonic()))
            stream_chat(stack.gateway, args.model,
                        f"r{i:02d} " + chr(65 + i % 26) * 96,
                        REPLICA_TOKENS)
            return 1

        t_start = time.monotonic() + 0.2
        with concurrent.futures.ThreadPoolExecutor(64) as pool:
            sent = sum(pool.map(arrival, range(64)))
        served = [stack.state(i)["meter_records"] - before[i]
                  for i in range(4)]
        check(sent == 64 and sum(served) == 64,
              f"sent {sent}, replicas metered {served}")
        check(all(n > 0 for n in served),
              f"a replica served nothing: {served}")
        return {
            "device": observations(states[0])["device"],
            "boot_s": round(stack.boot_s, 1),
            "served_per_replica": served,
            "devices": [
                {"id": st["devices"][0]["id"],
                 "coords": st["devices"][0]["coords"],
                 "kind": st["devices"][0]["kind"],
                 "visible_chips": st.get("visible_chips", "")}
                for st in states],
        }


PHASES = {"build": phase_build, "serve": phase_serve,
          "kernels": phase_kernels, "ragged": phase_ragged,
          "tp4": phase_tp4, "replicas": phase_replicas}


def run_phases(args, out_dir: str) -> dict:
    """Run the selected phases in order. Any phase that fails makes the
    run fail; nothing turns a failed phase into a skipped one."""
    report: dict = {"phases": {}}
    ok = True
    for name in args.phases:
        t0 = time.monotonic()
        log(f"phase {name} ...")
        try:
            detail = PHASES[name](args, out_dir)
            verdict = "ok"
        except (PhaseError, subprocess.SubprocessError, OSError,
                urllib.error.URLError, KeyError, ValueError) as e:
            detail = {"error": f"{type(e).__name__}: {e}"[:4000]}
            verdict = "FAILED"
            ok = False
        seconds = round(time.monotonic() - t0, 1)
        report["phases"][name] = {"verdict": verdict, "seconds": seconds,
                                  **detail}
        log(f"phase {name}: {verdict} in {seconds}s")
        if verdict != "ok":
            print(detail["error"], flush=True)
    report["ok"] = ok
    return report


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="qwen2-7b")
    ap.add_argument("--platform", default="",
                    help="name a platform to the children (cpu = the "
                         "orchestrator's dry run); default: none named, "
                         "so a TPU is required")
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--phases", default="",
                    help="comma-separated subset, in order")
    ap.add_argument("--no-quantize", dest="quantize",
                    action="store_false",
                    help="serve bf16 weights (tiny dry-run models)")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"))
    args = ap.parse_args(argv)
    default = (["build", "serve", "kernels", "ragged"] if args.chips == 1
               else ["build", "tp4", "replicas"])
    args.phases = ([p for p in args.phases.split(",") if p]
                   or default)
    unknown = [p for p in args.phases if p not in PHASES]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}; known: {sorted(PHASES)}")
    os.makedirs(args.out, exist_ok=True)

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache")
    entries_before = cache_entries(cache_dir)
    report = run_phases(args, args.out)
    report["compile_cache"] = {
        "dir": cache_dir, "entries_before": entries_before,
        "entries_after": cache_entries(cache_dir)}
    report["seconds"] = round(time.monotonic() - _T0, 1)
    device = next((p["device"] for p in report["phases"].values()
                   if "device" in p), None)
    if report["ok"] and device is None:
        report["ok"] = False
        report["error"] = ("no phase that ran reports the device: "
                           "nothing to put in the result line")
    with open(os.path.join(args.out, "report.json"), "w") as f:
        json.dump(report, f, indent=1)

    assert "jax" not in sys.modules, \
        "chip_smoke.py imported jax: it would hold the chip"
    if not report["ok"]:
        # no result on stdout: the report goes to stderr
        print(json.dumps(report, indent=1), file=sys.stderr, flush=True)
        return 1
    print(json.dumps(report, indent=1), flush=True)
    print(json.dumps({
        "served": args.model, **MODEL_FACTS.get(args.model, {}),
        "weights": ("W8A16 int8, seeded random" if args.quantize
                    else "bf16, seeded random"),
        "kv": "bf16",
        "phases": {n: p["verdict"] for n, p in report["phases"].items()},
        "seconds": report["seconds"],
    }), flush=True)
    # the LAST line: exactly these keys, nothing else (the driver
    # refuses any other shape)
    print(json.dumps({
        "ok": True,
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["kind"]),
                   "count": int(device["count"])},
    }), flush=True)
    return 0


#: what the summary line says about the served configuration (published
#: widths: Qwen2-7B config.json — 28 layers, hidden 3584, 28 heads /
#: 4 KV heads, head_dim 128, FFN 18944, vocab 152064; nothing cut)
MODEL_FACTS = {
    "qwen2-7b": {"layers": 28, "hidden": 3584, "heads": 28, "kv_heads": 4,
                 "ffn": 18944, "vocab": 152064,
                 "widths": "published", "depth": "full"},
}


if __name__ == "__main__":
    sys.exit(main())
