#!/usr/bin/env python3
"""chaos — fault injection for the fleet control plane (ISSUE 14).

The TokenSim lesson (arxiv 2503.08415): a serving-system claim is only
verified against injected churn, not a quiet pool. This module is the
churn: the primitives the chaos test matrix
(``tests/test_fleet_controller.py``) drives against a live fleet —

- :func:`spawn_replica` / :class:`ReplicaProc` — a tpuserve child
  (``python -m aigw_tpu.tpuserve.child``, the launcher's own) whose pid
  is in hand, so :meth:`ReplicaProc.kill9` can ``SIGKILL`` it mid-decode
  (the crash case: no drain, no goodbye, sockets torn) while
  :meth:`ReplicaProc.term` exercises the graceful-drain path.
- slow-start injection: ``slow_start_s`` stalls the child before it
  boots (the ``AIGW_CHAOS_SLOW_START_S`` hook in the child) — the
  controller's launch path must tolerate replicas that take arbitrarily
  long to report a port without blocking or double-launching.
- :class:`TornStateProxy` — a replica-shaped proxy that forwards
  everything verbatim but, when armed, truncates ``/state`` bodies
  mid-JSON: the poisoned-telemetry case. A correct gateway counts it a
  failed poll (the PR 12 torn-body fix) and a correct controller never
  scores it healthy.

Also a tiny CLI for manual chaos against a running fleet:

    python tools/chaos.py kill --pid 12345 --after 3
    python tools/chaos.py watch http://127.0.0.1:1975

stdlib-only at import time (subprocess/os/json); aiohttp is imported
lazily by the proxy so ``kill`` works in bare environments.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
#: the launcher's own replica child (gateway/controller.py), by module
REPLICA_CHILD = "aigw_tpu.tpuserve.child"


class ReplicaProc:
    """One tpuserve child with its pid in hand — the unit of chaos."""

    def __init__(self, proc: subprocess.Popen, url: str):
        self.proc = proc
        self.url = url
        self.address = url[len("http://"):]

    @property
    def pid(self) -> int:
        return self.proc.pid

    def kill9(self) -> None:
        """SIGKILL — the crash injection: no drain handler runs, live
        decode windows die mid-dispatch, sockets tear. Whatever
        correctness survives this is the failover path's doing."""
        try:
            self.proc.kill()
        except ProcessLookupError:
            pass
        self.proc.wait()

    def term(self, timeout: float = 90.0) -> int:
        """SIGTERM — rides the graceful drain handler; returns the exit
        code (0 = drained clean with zero live slots)."""
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        return self.proc.returncode

    @property
    def returncode(self):
        return self.proc.returncode

    def alive(self) -> bool:
        return self.proc.poll() is None


def spawn_replica(spec: dict, env: dict | None = None,
                  slow_start_s: float = 0.0,
                  boot_timeout_s: float = 1200.0) -> ReplicaProc:
    """Boot a tpuserve child from a replica spec and wait for its
    SERVE_PORT line. ``slow_start_s`` injects a pre-boot stall (the
    slow-start replica case)."""
    child_env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    if slow_start_s > 0:
        child_env["AIGW_CHAOS_SLOW_START_S"] = str(slow_start_s)
    proc = subprocess.Popen(
        [sys.executable, "-m", REPLICA_CHILD, json.dumps(spec)],
        cwd=_REPO, stdout=subprocess.PIPE, text=True, env=child_env,
    )
    import select

    fd = proc.stdout.fileno()
    os.set_blocking(fd, False)
    deadline = time.time() + boot_timeout_s + slow_start_s
    buf = ""
    port = None
    while time.time() < deadline and port is None:
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica child exited rc={proc.returncode} before "
                "listening")
        r, _, _ = select.select([fd], [], [], 2.0)
        if not r:
            continue
        buf += os.read(fd, 4096).decode(errors="replace")
        *complete, buf = buf.split("\n")
        for line in complete:
            if line.startswith("SERVE_PORT="):
                port = int(line.split("=", 1)[1])
                break
    if port is None:
        proc.kill()
        raise RuntimeError("replica child never reported a port")
    return ReplicaProc(proc, f"http://127.0.0.1:{port}")


class TornStateProxy:
    """Replica-shaped proxy that can poison its own telemetry: requests
    forward verbatim to the target replica, but while ``torn`` is set,
    ``/state`` answers 200 with the target's JSON truncated mid-body —
    exactly the stale-lie a half-dead replica tells. The PR 12 picker
    fix must count it a failed poll; the fleet health machine must walk
    it degraded→down while it stays armed."""

    def __init__(self, target_addr: str):
        self.target = target_addr
        self.torn = False
        self.address = ""
        self.url = ""
        self._runner = None
        self._session = None

    async def start(self) -> "TornStateProxy":
        import aiohttp
        from aiohttp import web

        self._session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=30.0))

        async def relay(request: web.Request) -> web.StreamResponse:
            url = f"http://{self.target}{request.path_qs}"
            data = await request.read()
            async with self._session.request(
                    request.method, url, data=data or None,
                    headers={k: v for k, v in request.headers.items()
                             if k.lower() not in ("host",
                                                  "content-length")},
            ) as upstream:
                body = await upstream.read()
                if request.path == "/state" and self.torn:
                    # 200 with a torn JSON body: the poisoned poll
                    body = body[: max(1, len(body) // 2)]
                return web.Response(
                    status=upstream.status, body=body,
                    content_type=(upstream.content_type or
                                  "application/json"))

        app = web.Application(client_max_size=256 * 1024 * 1024)
        app.router.add_route("*", "/{tail:.*}", relay)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        self.address = f"127.0.0.1:{port}"
        self.url = f"http://{self.address}"
        return self

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
        if self._session is not None:
            await self._session.close()
            self._session = None


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_kill = sub.add_parser("kill", help="SIGKILL a replica pid after "
                                         "a delay (crash injection)")
    p_kill.add_argument("--pid", type=int, required=True)
    p_kill.add_argument("--after", type=float, default=0.0,
                        help="seconds to wait before the kill")
    p_watch = sub.add_parser(
        "watch", help="poll /fleet/state and print lifecycle events as "
                      "they land (controller actions, health walks)")
    p_watch.add_argument("url", help="gateway base url")
    p_watch.add_argument("--interval", type=float, default=1.0)
    args = ap.parse_args(argv)

    if args.cmd == "kill":
        if args.after > 0:
            time.sleep(args.after)
        os.kill(args.pid, signal.SIGKILL)
        print(f"killed pid {args.pid}")
        return 0

    # watch: tail controller/health events without a full table
    import urllib.request

    seen: set[tuple] = set()
    while True:
        try:
            with urllib.request.urlopen(
                    args.url.rstrip("/") + "/fleet/state",
                    timeout=5.0) as resp:
                snap = json.loads(resp.read().decode())
        except OSError as e:
            print(f"chaos watch: {e}", file=sys.stderr)
            time.sleep(args.interval)
            continue
        for name, b in sorted((snap.get("backends") or {}).items()):
            ctl = b.get("controller") or {}
            for ev in ctl.get("events", ()):
                key = (name, "ctl", json.dumps(ev, sort_keys=True))
                if key not in seen:
                    seen.add(key)
                    print(f"[{name}] controller {ev}")
            for addr, r in sorted((b.get("replicas") or {}).items()):
                for ev in (r.get("health") or {}).get("events", ()):
                    key = (name, addr, json.dumps(ev, sort_keys=True))
                    if key not in seen:
                        seen.add(key)
                        print(f"[{name}] {addr} {ev}")
        time.sleep(args.interval)


if __name__ == "__main__":
    raise SystemExit(main())
