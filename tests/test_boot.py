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
