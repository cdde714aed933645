"""The boot rule and the placed compile cache (aigw_tpu/utils/boot.py),
through the entry point a user calls.

The platform is the one somebody named; with none named a TPU is
required and boot fails naming what JAX found. The compile cache lives
where ``JAX_COMPILATION_CACHE_DIR`` says, else at the fixed
``<checkout>/.jax_cache`` — never under a temporary name.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

chipless = pytest.mark.skipif(
    bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*")),
    reason="this box has a TPU: nothing to refuse")


def _env(**extra: str) -> dict:
    """The test process's environment with NO platform and NO cache
    directory named (conftest names the CPU for every other child)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                        "XLA_FLAGS")}
    env.update(extra)
    return env


@chipless
def test_tpuserve_refuses_a_platform_nobody_named():
    proc = subprocess.run(
        [sys.executable, "-m", "aigw_tpu", "tpuserve",
         "--model", "tiny-random", "--port", "0"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    # says which platform it found, and how to ask for it
    assert "found platform 'cpu'" in proc.stderr
    assert "--platform cpu" in proc.stderr
    assert "listening" not in proc.stdout


def test_named_cpu_serves_with_the_cache_in_the_checkout(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    log_path = tmp_path / "tpuserve.log"
    with open(log_path, "w") as log:  # a file: an undrained pipe fills
        proc = subprocess.Popen(
            [sys.executable, "-m", "aigw_tpu", "tpuserve",
             "--model", "tiny-random", "--platform", "cpu",
             "--port", str(port)],
            cwd=REPO, env=_env(), stdout=log, stderr=subprocess.STDOUT)
    base = f"http://127.0.0.1:{port}"
    try:
        deadline = time.monotonic() + 180
        while True:
            assert proc.poll() is None, log_path.read_text()[-2000:]
            try:
                with urllib.request.urlopen(base + "/health",
                                            timeout=2) as r:
                    assert json.loads(r.read())["status"] == "ok"
                break
            except OSError:
                assert time.monotonic() < deadline
                time.sleep(0.3)
        with urllib.request.urlopen(base + "/state", timeout=10) as r:
            st = json.loads(r.read())
        assert st["platform"] == "cpu"
        assert [d["platform"] for d in st["devices"]] == ["cpu"]
        assert st["weights"] == "random"
        # the fixed fallback path — part of the cache key's world
        assert st["compile_cache_dir"] == os.path.join(REPO, ".jax_cache")
        assert os.path.isdir(st["compile_cache_dir"])
        # every compile request of the boot went through the cache
        assert st["xla_cache_hits"] + st["xla_cache_misses"] > 0
        req = urllib.request.Request(
            base + "/v1/completions",
            data=json.dumps({"model": "tiny-random", "prompt": "hi",
                             "max_tokens": 2}).encode(),
            headers={"content-type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert json.loads(r.read())["usage"]["completion_tokens"] >= 1
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
    assert proc.returncode == 0  # graceful drain


def test_cache_dir_named_by_the_environment_is_left_alone(tmp_path):
    named = str(tmp_path / "named-cache")
    out = subprocess.run(
        [sys.executable, "-c",
         "from aigw_tpu.utils.boot import boot_jax, compile_cache_dir;"
         "print(boot_jax('cpu'), compile_cache_dir())"],
        cwd=REPO, env=_env(JAX_COMPILATION_CACHE_DIR=named),
        capture_output=True, text=True, timeout=120, check=True)
    assert out.stdout.split() == ["cpu", named]


# -- the boot timeline (BootLedger) ---------------------------------------

def test_phases_are_contiguous_self_time_and_stand_still_after_ready():
    from aigw_tpu.utils.boot import BOOT_PHASES, BootLedger

    led = BootLedger()
    t0 = led.t0
    assert led.cur == "import" and time.perf_counter_ns() >= t0
    assert led.enter("backend") == "import"
    assert led.enter("engine") == "backend"
    outer = led.enter("weights")      # a phase inside another ...
    time.sleep(0.02)
    assert led.enter("weights_layout") == "weights"  # ... and a third
    assert led.enter(outer) == "weights_layout"  # ... hands it back
    assert led.cur == "engine"
    led.enter("warmup")
    led.enter("listen")
    led.ready()
    end = led.t
    assert set(led.ns) == set(BOOT_PHASES)
    assert all(v >= 0 for v in led.ns.values())
    assert led.ns["weights"] >= 0.02e9
    assert sum(led.ns.values()) == end - t0  # no gap and no overlap
    flat = led.flat()
    assert set(flat) == {f"boot_{p}_ms" for p in BOOT_PHASES} | {
        "boot_ready_ms"}
    assert flat["boot_ready_ms"] == pytest.approx(
        sum(v for k, v in flat.items() if k != "boot_ready_ms"), abs=0.01)
    # a second server of the process adds nothing
    assert led.enter("weights") == "listen"
    led.ready()
    assert led.flat() == flat and led.cur == "listen"


def test_the_timeline_starts_where_the_os_started_the_process():
    from aigw_tpu.utils.boot import BOOT, _process_age_ns

    age_s = _process_age_ns() / 1e9
    assert os.path.exists("/proc/self/stat")  # else 0: the import's time
    # this process is older than its import of the module, and younger
    # than the machine
    with open("/proc/uptime") as f:
        assert 0 < age_s <= float(f.read().split()[0])
    assert BOOT.since_start_ms() / 1e3 == pytest.approx(age_s, abs=0.5)


_TINY_CHILD = {
    "model": "tiny-boot-child", "batch": 2, "page": 16, "k": 2,
    "cfg": {"vocab_size": 512, "dim": 64, "n_layers": 2, "n_heads": 4,
            "n_kv_heads": 2, "ffn_dim": 128, "max_seq_len": 256,
            "rope_theta": 10000.0},
    "engine": {"min_prefill_bucket": 16, "num_pages": 48}}


def _get(url: str):
    with urllib.request.urlopen(url, timeout=30) as r:
        body = r.read()
    return json.loads(body) if body[:1] in (b"{", b"[") else body.decode()


@pytest.fixture(scope="module", params=["cli", "child"])
def booted(request, tmp_path_factory):
    """A CPU replica through each entry point: ``aigw_tpu tpuserve`` and
    the launcher's ``aigw_tpu.tpuserve.child``. Yields its model, its
    URL, the seconds from spawn to the first /health ok, and /state
    as it was before any request."""
    log_path = tmp_path_factory.mktemp("boot") / f"{request.param}.log"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    if request.param == "cli":
        model = "tiny-random"
        argv = [sys.executable, "-m", "aigw_tpu", "tpuserve", "--model",
                model, "--platform", "cpu", "--port", str(port)]
    else:
        model = _TINY_CHILD["model"]
        argv = [sys.executable, "-m", "aigw_tpu.tpuserve.child",
                json.dumps(_TINY_CHILD)]
    t_spawn = time.monotonic()
    with open(log_path, "w") as log:  # a file: an undrained pipe fills
        proc = subprocess.Popen(argv, cwd=REPO, env=env, stdout=log,
                                stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 240
        while True:
            assert proc.poll() is None, log_path.read_text()[-2000:]
            assert time.monotonic() < deadline
            if request.param == "child":
                said = [ln for ln in log_path.read_text().splitlines()
                        if ln.startswith("SERVE_PORT=")]
                if not said:
                    time.sleep(0.2)
                    continue
                port = int(said[0].split("=")[1])
            try:
                assert _get(f"http://127.0.0.1:{port}/health")[
                    "status"] == "ok"
                break
            except OSError:
                time.sleep(0.2)
        up_s = time.monotonic() - t_spawn
        base = f"http://127.0.0.1:{port}"
        yield model, base, up_s, _get(base + "/state")
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)


def test_boot_phases_sum_to_ready_through_both_entry_points(booted):
    from aigw_tpu.utils.boot import BOOT_PHASES

    _model, _base, up_s, st = booted
    phases = [st[f"boot_{p}_ms"] for p in BOOT_PHASES]
    assert all(v >= 0 for v in phases)
    assert sum(phases) == pytest.approx(st["boot_ready_ms"], rel=0.01)
    # every phase of a real boot takes time, and the OS's start of the
    # process is not later than the spawn that this test timed
    for p in ("import", "backend", "weights", "engine", "warmup"):
        assert st[f"boot_{p}_ms"] > 0, p
    assert 0 < st["boot_ready_ms"] / 1e3 <= up_s + 0.05
    assert st["boot_ready_ms"] / 1e3 >= up_s - 5  # /health is polled


def test_weights_phase_is_the_two_weights_observables(booted):
    st = booted[3]
    assert st["boot_weights_ms"] == pytest.approx(
        st["weights_init_ms"] + st["weights_quantize_ms"], abs=1.0)
    # and the warm-up phase holds Engine.warmup()
    assert st["boot_warmup_ms"] >= st["warmup_ms"]
    assert st["boot_warmup_ms"] == pytest.approx(st["warmup_ms"], abs=1000)


def test_a_family_without_serving_params_lays_nothing_out(booted):
    """The ``weights_layout`` phase is the family's ``serving_params``
    (models/registry.py): the llama family has none, so nothing was
    laid out and the phase was never entered."""
    st = booted[3]
    assert st["weights_prepared_leaves"] == 0
    assert st["boot_weights_layout_ms"] == 0


def test_state_and_metrics_carry_the_compile_surfaces_keys(booted):
    from aigw_tpu.analysis import manifest

    _model, base, _up_s, st = booted
    for key in manifest.state_fields("boot"):
        assert key in st, key
    text = _get(base + "/metrics")
    for gauge in manifest.gauge_names("boot"):
        assert f"\n{gauge} " in text, gauge
    assert len(manifest.gauge_names("boot")) >= 19


def test_nothing_is_late_until_a_request_is_and_the_programs_are_named(
        booted):
    model, base, _up_s, st = booted
    assert [st[k] for k in (
        "xla_late_loads", "xla_late_ms", "xla_late_trace_ms",
        "xla_late_lower_ms", "xla_late_retrieval_ms")] == [0] * 5
    assert st["xla_lower_ms"] > 0 and st["xla_trace_ms"] > 0
    programs = _get(base + "/debug/programs")
    assert programs["ready"] and all(not e["late"] for e in programs["log"])
    # every program of the boot under its function's name, by stage
    assert sum(r["requests"] for r in programs["programs"].values()) == \
        programs["totals"]["compiles"] >= st["xla_compiles"] > 0
    assert all(fn.startswith("jit(") for fn in programs["programs"])
    req = urllib.request.Request(
        base + "/v1/completions",
        data=json.dumps({"model": model, "prompt": "boot timeline",
                         "max_tokens": 3}).encode(),
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        rid = r.headers["x-aigw-request-id"]
        assert json.loads(r.read())["usage"]["completion_tokens"] >= 1
    # no prefill program was warmed: the first request loaded one
    late = [e for e in _get(base + "/debug/programs")["log"] if e["late"]]
    assert late and all(e["phase"] for e in late)
    deadline = time.monotonic() + 10  # /state is the engine's last tick
    while True:
        after = _get(base + "/state")
        if after["xla_late_loads"] == len(late) \
                or time.monotonic() > deadline:
            break
        time.sleep(0.1)
    assert after["xla_late_loads"] == len(late)
    assert after["xla_late_ms"] == pytest.approx(sum(
        e["trace_ms"] + e["lower_ms"] + e["backend_ms"] for e in late),
        abs=0.01)
    assert after["boot_ready_ms"] == st["boot_ready_ms"]
    waited = [e["attrs"]["fn"] for e in
              _get(base + f"/debug/requests/{rid}")["events"]
              if e["name"] == "program_load"]
    assert waited == [e["fn"] for e in late]
