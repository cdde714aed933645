"""The system under test: tpuserve replica(s) behind the gateway.

A copy of ``chip_smoke.py``'s ``Stack``/``Child``/``child_env`` and of
``aigw_tpu/utils/chips.py`` (the one way shown to work on the chip of
starting replicas and the gateway from a parent that never imports
jax), kept here so that a later PR cannot change the yardstick by
editing the smoke. Stdlib only.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

#: seconds a replica may take from start to /health: a cold boot
#: compiles every warmed program (~20-30 s each at 28 layers, PR 21)
BOOT_TIMEOUT_S = 1100.0


class HarnessError(RuntimeError):
    """The stack could not be brought up or a check of it failed: the
    run exits non-zero and prints no result."""


def chip_env(index: int) -> dict[str, str]:
    """Environment that confines a new process to chip ``index``
    (necessary and sufficient on a v5litepod-4 host, PR 21)."""
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(method: str, url: str, body: dict | None = None,
              timeout: float = 60.0) -> dict:
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"content-type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raise HarnessError(
            f"{method} {url} -> {e.code}: {e.read()[:300]!r}") from e


class Child:
    """One child process with its output in a log file (never a pipe: a
    JAX child fills an undrained pipe and blocks); stopped with SIGTERM,
    SIGKILL after a grace, and always waited for."""

    def __init__(self, name: str, argv: list[str], env: dict, out_dir: str):
        self.name = name
        self.log_path = os.path.join(out_dir, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            argv, cwd=CHECKOUT, env=env, stdout=self._log,
            stderr=subprocess.STDOUT)

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
                self.proc.wait(timeout=30)
        self._log.close()


def wait_health(url: str, child: Child, timeout_s: float) -> dict:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        rc = child.proc.poll()
        if rc is not None:
            raise HarnessError(
                f"{child.name} exited with code {rc} before serving:\n"
                f"{child.tail()}")
        try:
            return http_json("GET", url + "/health", timeout=5)
        except (urllib.error.URLError, OSError, HarnessError):
            time.sleep(0.5)
    raise HarnessError(
        f"{child.name} not healthy after {timeout_s:.0f}s:\n{child.tail()}")


def replica_env(extra: dict) -> dict:
    """Environment of a child that opens the accelerator. No platform
    is named here: ``aigw_tpu/utils/boot.py`` then REQUIRES a TPU and
    the child exits naming what JAX found. (A configuration that serves
    on a CPU names it in its own serve flags.)"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.update(extra)
    return env


class Stack:
    """``replicas`` tpuserve children (each through ``serve_child.py``,
    which registers the configuration file's model and calls the normal
    CLI) and ``aigw run`` in front with the endpoint picker on."""

    def __init__(self, config_path: str, model: str, serve_flags: list[str],
                 replicas: int, out_dir: str, log) -> None:
        self.config_path = config_path
        self.model = model
        self.serve_flags = serve_flags
        self.n_replicas = replicas
        self.out_dir = out_dir
        self.log = log
        self.children: list[Child] = []
        self.replicas: list[str] = []
        self.gateway = ""

    def __enter__(self) -> "Stack":
        try:
            self._boot()
        except BaseException:
            self.__exit__()
            raise
        return self

    def _boot(self) -> None:
        os.makedirs(self.out_dir, exist_ok=True)
        # the gateway's SSE scanner is a build product git does not
        # carry: build it from the committed sources, as a deployment
        # (and chip_smoke.py) does, so that every checkout measures the
        # same gateway. A no-op when it is up to date.
        made = subprocess.run(
            ["make", "-C", os.path.join(CHECKOUT, "native")],
            capture_output=True, text=True)
        if made.returncode != 0:
            raise HarnessError(f"make -C native failed:\n{made.stderr[-800:]}")
        for i in range(self.n_replicas):
            port = free_port()
            # one replica owns whatever the machine shows it; several
            # are each confined to their own chip before they import jax
            extra = chip_env(i) if self.n_replicas > 1 else {}
            argv = [sys.executable, os.path.join(HERE, "serve_child.py"),
                    self.config_path, "tpuserve", "--model", self.model,
                    "--port", str(port), "--weights", "random",
                    *self.serve_flags]
            child = Child(f"tpuserve{i}", argv, replica_env(extra),
                          self.out_dir)
            self.log(f"started {child.name} (pid {child.proc.pid}): "
                     + " ".join(argv[2:]))
            self.children.append(child)
            self.replicas.append(f"http://127.0.0.1:{port}")
        for url, child in zip(self.replicas, self.children):
            health = wait_health(url, child, BOOT_TIMEOUT_S)
            if health.get("status") != "ok":
                raise HarnessError(f"{child.name} /health: {health}")
        cfg_path = os.path.join(self.out_dir, "gateway.yaml")
        with open(cfg_path, "w") as f:
            # shaped like examples/inference-pool/config.yaml (JSON is
            # YAML), as chip_smoke.py writes it
            json.dump({
                "version": "v1",
                "backends": [{
                    "name": "pool", "schema": "TPUServe",
                    "endpoints": [
                        {"address": u[len("http://"):], "slice": "s0"}
                        for u in self.replicas],
                    "picker_poll_interval": 0.2,
                    "picker_content_affinity": True,
                    # a cold lead-in compiles on its first requests;
                    # the default 120 s budget would cut them off
                    "request_timeout": 900.0,
                }],
                "routes": [{"name": "serving", "rules": [
                    {"model_prefixes": [self.model],
                     "backends": ["pool"]}]}],
                "models": [self.model],
                "llm_request_costs": [
                    {"metadata_key": "total_tokens", "type": "TotalToken"}],
            }, f, indent=1)
        gw_port = free_port()
        gw = Child("gateway",
                   [sys.executable, "-m", "aigw_tpu", "run", cfg_path,
                    "--port", str(gw_port)],
                   # the gateway opens no accelerator
                   dict(os.environ), self.out_dir)
        self.children.append(gw)
        self.gateway = f"http://127.0.0.1:{gw_port}"
        health = wait_health(self.gateway, gw, 60)
        if health.get("native_scanner") != "loaded":
            raise HarnessError(
                f"gateway runs the {health.get('native_scanner')!r} "
                "scanner, not the one built from native/")
        # the picker routes on polled /state: let it see every replica
        time.sleep(1.0)

    def __exit__(self, *exc) -> None:
        for child in reversed(self.children):
            child.stop()

    def states(self) -> list[dict]:
        return [http_json("GET", u + "/state") for u in self.replicas]
