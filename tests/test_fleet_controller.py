"""Fleet control plane (ISSUE 14): autoscaling, lossless drain, crash
failover — the chaos matrix.

Non-slow tier (`make chaos`): the controller's predicates and
hysteresis against deterministic injected state — sustained-overshoot
scale-out fires at exactly the K-th window and not before, idle
scale-in drains before it retires, a flapping replica never triggers a
launch/kill oscillation — plus the merged routability view (draining /
breaker-open replicas unroutable), dynamic pool membership, the
pre-first-byte failover retry through a real gateway, and the chaos
tool's torn-/state proxy walking the health machine.

Slow tier: live multi-replica rigs over real tpuserve subprocesses —
kill -9 mid-decode (clean typed error, failover event, replacement
launch), drain-then-retire (migrated stream byte-identical to its solo
run, replica exits 0 with zero live slots), SIGTERM graceful shutdown.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import sys
import time

import aiohttp
import pytest
from aiohttp import web

from aigw_tpu.config.model import Config, ConfigError
from aigw_tpu.config.runtime import RuntimeConfig
from aigw_tpu.gateway.circuit import CircuitBreaker
from aigw_tpu.gateway.controller import (
    COUNTERS,
    ControllerConfig,
    FleetController,
    LocalProcessLauncher,
    ReplicaLauncher,
)
from aigw_tpu.gateway.fleetstate import DecisionRing
from aigw_tpu.gateway.picker import Endpoint, EndpointPicker
from aigw_tpu.gateway.server import run_gateway
from aigw_tpu.obs.metrics import CONTROLLER_GAUGES
from aigw_tpu.obs.slomon import SLOMonitor

from test_fleetstate import StubReplica, _wait_for

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, "..", "tools"))

import chaos  # noqa: E402  (tools/chaos.py)


class FakeLauncher(ReplicaLauncher):
    """Deterministic launcher for predicate tests: instant launches of
    synthetic addresses, every action recorded."""

    def __init__(self, fail: bool = False):
        self.launched: list[str] = []
        self.terminated: list[str] = []
        self.fail = fail
        self._n = 0

    async def launch(self) -> str:
        if self.fail:
            raise RuntimeError("injected launch failure")
        self._n += 1
        addr = f"10.99.0.{self._n}:8000"
        self.launched.append(addr)
        return addr

    def owns(self, address: str) -> bool:
        return address in self.launched

    async def terminate(self, address: str) -> None:
        self.terminated.append(address)

    async def close(self) -> None:
        pass


def _picker(addrs, **kw) -> EndpointPicker:
    kw.setdefault("fleet_obs", True)
    return EndpointPicker([Endpoint(a) for a in addrs], **kw)


def _over_buckets(n: int) -> dict:
    """Cumulative TTFT buckets where every one of ``n`` served requests
    blew the 100ms SLO → windowed burn = 20× the 0.95 objective."""
    return {"100": 0, "+Inf": n}


async def _settle(n: int = 4) -> None:
    for _ in range(n):
        await asyncio.sleep(0)


class TestControllerConfig:
    def test_parse_defaults_and_bounds(self):
        cfg = ControllerConfig.parse({})
        assert cfg.enabled and cfg.min_replicas == 1
        with pytest.raises(ValueError):
            ControllerConfig.parse({"min_replicas": 3, "max_replicas": 2})
        with pytest.raises(ValueError):
            ControllerConfig.parse({"tick_s": 0})
        with pytest.raises(ValueError):
            ControllerConfig.parse({"idle_slots_frac": 0.0})
        with pytest.raises(ValueError):
            ControllerConfig.parse({"launcher": {"kind": "k8s"}})

    def test_backend_config_requires_endpoints(self):
        with pytest.raises(ConfigError):
            Config.parse({
                "version": "v1",
                "backends": [{"name": "b", "schema": "OpenAI",
                              "url": "http://x", "controller": {}}],
                "routes": [{"name": "r",
                            "rules": [{"backends": ["b"]}]}],
            })
        c = Config.parse({
            "version": "v1",
            "backends": [{"name": "b", "schema": "OpenAI",
                          "endpoints": ["127.0.0.1:9"],
                          "controller": {"max_replicas": 2}}],
            "routes": [{"name": "r", "rules": [{"backends": ["b"]}]}],
        })
        assert c.backends[0].controller is not None
        assert c.backends[0].to_dict()["controller"] == {
            "max_replicas": 2}

    def test_gauge_drift(self):
        """Every CONTROLLER_GAUGES key must exist in gauge_values();
        every COUNTERS key must be a gauge — the two sides can't
        drift apart silently."""
        picker = _picker(["127.0.0.1:9"])
        ctl = FleetController(picker, ControllerConfig())
        values = ctl.gauge_values()
        for key, _name in CONTROLLER_GAUGES:
            assert key in values, key
        for key in COUNTERS:
            assert key in dict(CONTROLLER_GAUGES), key
        snap = ctl.snapshot()
        assert snap["counters"] == {k: 0 for k in COUNTERS}


class TestScaleOutPredicate:
    def test_launch_at_exactly_k_windows_not_before(self):
        """The autoscale predicate is slomon's sustained flag: K=3
        consecutive over-budget windows → launcher invoked exactly
        once, and never earlier."""

        async def main():
            picker = _picker(["127.0.0.1:9"], slo_ttft_ms=100.0,
                             slo_window_s=1.0, slo_burn_windows=3)
            mon = picker.fleet.slomon
            launcher = FakeLauncher()
            ctl = FleetController(
                picker,
                ControllerConfig.parse({
                    "min_replicas": 1, "max_replicas": 3,
                    "scale_cooldown_s": 5.0, "idle_ticks": 10 ** 6}),
                launcher=launcher, decisions=DecisionRing())
            picker.observe("127.0.0.1:9", max_slots=2)
            mon.observe(SLOMonitor.FLEET_KEY, _over_buckets(0), ts=0.0)
            served = 0
            for i, ts in enumerate((1.01, 2.02, 3.03)):
                served += 5
                mon.observe(SLOMonitor.FLEET_KEY, _over_buckets(served),
                            ts=ts)
                await ctl.tick(now=ts)
                await _settle()
                if i < 2:
                    assert launcher.launched == [], f"window {i}"
                    assert not mon.sustained(SLOMonitor.FLEET_KEY)
            assert mon.sustained(SLOMonitor.FLEET_KEY)
            assert len(launcher.launched) == 1
            assert ctl.counters["scale_outs"] == 1
            # the launched replica joined the pool
            assert launcher.launched[0] in picker.state
            # still sustained, but inside the cooldown: no second launch
            served += 5
            mon.observe(SLOMonitor.FLEET_KEY, _over_buckets(served),
                        ts=4.04)
            await ctl.tick(now=4.04)
            await _settle()
            assert len(launcher.launched) == 1
            # past the cooldown AND still sustained → second launch,
            # then the max_replicas=3 cap holds forever
            served += 5
            mon.observe(SLOMonitor.FLEET_KEY, _over_buckets(served),
                        ts=9.1)
            await ctl.tick(now=9.1)
            await _settle()
            assert len(launcher.launched) == 2
            await ctl.tick(now=20.0)
            await _settle()
            assert len(launcher.launched) == 2  # at max
            # every lifecycle action landed in the decision ring
            kinds = [e.get("lifecycle") for e in
                     ctl.decisions.snapshot(limit=100)]
            assert kinds.count("scale_out") == 2
            assert kinds.count("launch") == 2
            await ctl.stop()

        asyncio.run(main())

    def test_launch_failure_counted_not_fatal(self):
        async def main():
            picker = _picker(["127.0.0.1:9"], slo_ttft_ms=100.0,
                             slo_window_s=1.0, slo_burn_windows=1)
            mon = picker.fleet.slomon
            launcher = FakeLauncher(fail=True)
            ctl = FleetController(
                picker, ControllerConfig.parse(
                    {"max_replicas": 2, "scale_cooldown_s": 0.0,
                     "idle_ticks": 10 ** 6}),
                launcher=launcher)
            mon.observe(SLOMonitor.FLEET_KEY, _over_buckets(0), ts=0.0)
            mon.observe(SLOMonitor.FLEET_KEY, _over_buckets(4), ts=1.1)
            await ctl.tick(now=1.1)
            await _settle()
            assert ctl.counters["launch_failures"] == 1
            assert ctl.counters["scale_outs"] == 1
            # the loop survives and can try again next tick
            await ctl.tick(now=2.2)
            await _settle()
            assert ctl.counters["launch_failures"] == 2
            await ctl.stop()

        asyncio.run(main())


class TestScaleInAndDrain:
    def test_idle_hysteresis_then_drain_and_retire(self):
        """Scale-in needs idle_ticks CONSECUTIVE idle ticks; the victim
        is drained (fleet mark + /drain attempt + wait-for-empty) and
        only then terminated and removed — and never below
        min_replicas."""

        async def main():
            a, b = "127.0.0.1:11", "127.0.0.1:12"
            picker = _picker([a, b])
            launcher = FakeLauncher()
            launcher.launched.append(b)  # owns b
            ctl = FleetController(
                picker, ControllerConfig.parse({
                    "min_replicas": 1, "max_replicas": 2,
                    "idle_ticks": 3, "idle_slots_frac": 0.75,
                    "scale_cooldown_s": 0.0, "drain_timeout_s": 5.0}),
                launcher=launcher, decisions=DecisionRing())
            for addr in (a, b):
                picker.observe(addr, max_slots=2, active_slots=0,
                               queued=0)
            await ctl.tick(now=100.0)
            assert ctl.idle_streak == 1 and not ctl._drains
            # a busy tick RESETS the streak (hysteresis, not a counter)
            picker.observe(a, max_slots=2, active_slots=2, queued=1)
            await ctl.tick(now=101.0)
            assert ctl.idle_streak == 0
            picker.observe(a, max_slots=2, active_slots=0, queued=0)
            for i, now in enumerate((102.0, 103.0, 104.0)):
                await ctl.tick(now=now)
                if i < 2:
                    assert not ctl._drains, f"tick {i}"
            assert ctl.counters["scale_ins"] == 1
            # drain in flight: keep the polled state empty so it
            # completes; the launcher-owned replica is the victim
            for _ in range(100):
                if not ctl._drains:
                    break
                picker.observe(b, max_slots=2, active_slots=0, queued=0)
                await asyncio.sleep(0.05)
            assert launcher.terminated == [b]
            assert b not in picker.state
            assert [e.address for e in picker.endpoints] == [a]
            assert ctl.counters["drains"] == 1
            assert ctl.counters["retires"] == 1
            # below min_replicas now: idle forever, never retires a
            kinds = [ev["action"] for ev in ctl.events]
            assert "drain_start" in kinds and "retire" in kinds
            assert "drain_complete" in kinds
            for now in range(110, 130):
                await ctl.tick(now=float(now))
            assert [e.address for e in picker.endpoints] == [a]
            await ctl.stop()

        asyncio.run(main())

    def test_draining_replica_not_routable(self):
        a, b = "127.0.0.1:21", "127.0.0.1:22"
        picker = _picker([a, b])
        # a is idle (best score), b is loaded — but a is draining
        picker.observe(a, max_slots=4, active_slots=0, queued=0)
        picker.observe(b, max_slots=4, active_slots=3, queued=2)
        assert picker.pick({}) == a
        picker.fleet.mark_draining(a)
        assert not picker.is_routable(a)
        for _ in range(10):
            assert picker.pick({}) == b
        picker.fleet.mark_draining(a, False)
        picker.observe(a, max_slots=4)  # poll clears the overlay
        assert picker.is_routable(a)


class TestFailover:
    def test_down_reroutes_then_replaces_after_grace(self):
        async def main():
            a, b = "127.0.0.1:31", "127.0.0.1:32"
            picker = _picker([a, b])
            launcher = FakeLauncher()
            ctl = FleetController(
                picker, ControllerConfig.parse({
                    "min_replicas": 2, "max_replicas": 3,
                    "down_grace_s": 5.0, "scale_cooldown_s": 0.0,
                    "idle_ticks": 10 ** 6}),
                launcher=launcher, decisions=DecisionRing())
            picker.observe(a, max_slots=2)
            picker.observe(b, max_slots=2)
            picker._affinity["sess-1"] = a
            for _ in range(3):
                picker.fleet.note_poll(a, False)
            assert picker.fleet.health_of(a) == "down"
            await ctl.tick(now=50.0)
            # immediate re-route: the dead replica's affinity is gone
            assert "sess-1" not in picker._affinity
            assert ctl.counters["failovers"] == 0  # grace not passed
            assert launcher.launched == []
            await ctl.tick(now=56.0)
            await _settle()
            assert ctl.counters["failovers"] == 1
            assert len(launcher.launched) == 1  # live 1 < min 2
            kinds = [ev["action"] for ev in ctl.events]
            assert "reroute" in kinds and "failover" in kinds
            # the failover fires ONCE, not every tick
            await ctl.tick(now=57.0)
            await _settle()
            assert ctl.counters["failovers"] == 1
            await ctl.stop()

        asyncio.run(main())

    def test_flapping_replica_no_oscillation(self):
        """down → recovers inside the grace window → no launch, no
        kill; the hysteresis holds across repeated flaps."""

        async def main():
            a, b = "127.0.0.1:41", "127.0.0.1:42"
            picker = _picker([a, b])
            launcher = FakeLauncher()
            ctl = FleetController(
                picker, ControllerConfig.parse({
                    "min_replicas": 2, "max_replicas": 3,
                    "down_grace_s": 5.0, "scale_cooldown_s": 0.0,
                    "idle_ticks": 10 ** 6}),
                launcher=launcher)
            picker.observe(b, max_slots=2)
            for flap in range(3):
                now = 100.0 + flap * 10
                for _ in range(3):
                    picker.fleet.note_poll(a, False)
                await ctl.tick(now=now)
                await ctl.tick(now=now + 2.0)  # inside grace
                # recovery: 2 good polls walk it back up
                picker.fleet.note_poll(a, True, {"replica_id": "r-a"})
                picker.fleet.note_poll(a, True, {"replica_id": "r-a"})
                assert picker.fleet.health_of(a) == "up"
                await ctl.tick(now=now + 4.0)
            assert launcher.launched == []
            assert launcher.terminated == []
            assert ctl.counters["failovers"] == 0
            await ctl.stop()

        asyncio.run(main())


class TestBreakerUnification:
    def test_breaker_open_lands_in_ring_and_blocks_routing(self):
        a, b = "127.0.0.1:51", "127.0.0.1:52"
        picker = _picker([a, b])
        br = CircuitBreaker(
            threshold=2, cooldown=30.0,
            on_transition=lambda k, o, f: picker.fleet.mark_breaker(
                k, o, f))
        picker.breaker = br
        # a idle (best), b loaded — breaker must still exclude a
        picker.observe(a, max_slots=4, active_slots=0)
        picker.observe(b, max_slots=4, active_slots=3)
        assert picker.pick({}) == a
        br.record_failure(a)
        assert picker.is_routable(a)  # below threshold
        br.record_failure(a)
        assert br.is_open(a)
        assert not picker.is_routable(a)
        for _ in range(10):
            assert picker.pick({}) == b
        events = list(picker.fleet.health[a].events)
        assert any(e.get("event") == "breaker_open" for e in events)
        assert picker.fleet.health[a].to_dict()["breaker_open"]
        br.record_success(a)
        assert picker.is_routable(a)
        events = list(picker.fleet.health[a].events)
        assert any(e.get("event") == "breaker_closed" for e in events)
        # transitions fire once per open/close, not per sample
        assert sum(1 for e in events
                   if e.get("event") == "breaker_open") == 1


class TestPoolMembership:
    def test_add_remove_forget(self):
        a = "127.0.0.1:61"
        picker = _picker([a])
        picker.add_endpoint("127.0.0.1:62")
        picker.add_endpoint("127.0.0.1:62")  # idempotent
        assert len(picker.endpoints) == 2
        assert "127.0.0.1:62" in picker.state
        picker.observe("127.0.0.1:62", max_slots=2)
        assert picker.pick({}) == "127.0.0.1:62"
        picker._affinity["s"] = "127.0.0.1:62"
        picker._prefix_affinity["p"] = "127.0.0.1:62"
        picker.remove_endpoint("127.0.0.1:62")
        assert [e.address for e in picker.endpoints] == [a]
        assert "127.0.0.1:62" not in picker.state
        assert "s" not in picker._affinity
        assert "p" not in picker._prefix_affinity
        assert picker.fleet.health_of("127.0.0.1:62") == "unknown"

    def test_pick_exclusion(self):
        a, b = "127.0.0.1:63", "127.0.0.1:64"
        picker = _picker([a, b])
        picker.observe(a, max_slots=4, active_slots=0)
        picker.observe(b, max_slots=4, active_slots=3)
        assert picker.pick({}) == a
        assert picker.pick({}, exclude={a}) == b
        # blind round-robin fallback honors the exclusion too
        picker2 = _picker([a, b])
        for _ in range(4):
            assert picker2.pick({}, exclude={a}) == b


def _gw_config(addrs, poll=30.0, extra=None) -> Config:
    return Config.parse({
        "version": "v1",
        "backends": [dict({
            "name": "pool", "schema": "OpenAI",
            "endpoints": list(addrs),
            "picker_poll_interval": poll,
        }, **(extra or {}))],
        "routes": [{"name": "r", "rules": [
            {"models": ["m1"], "backends": ["pool"]}]}],
        "models": ["m1"],
    })


class TestPreFirstByteRetry:
    def test_connect_error_fails_over_to_sibling(self):
        """A picked replica that refuses the connection never surfaces
        to the client: the gateway re-picks the next-ranked sibling
        once, records failover_from in the decision ring, and feeds
        the per-replica breaker."""

        async def main():
            live = await StubReplica("pfb-live").start()
            # a dead address: bind-then-close so nothing listens
            import socket

            sock = socket.socket()
            sock.bind(("127.0.0.1", 0))
            dead = "127.0.0.1:%d" % sock.getsockname()[1]
            sock.close()
            server, runner = await run_gateway(
                RuntimeConfig.build(_gw_config([dead, live.address])),
                port=0)
            site = list(runner.sites)[0]
            gw = "http://127.0.0.1:%d" % (
                site._server.sockets[0].getsockname()[1])
            picker = server._pickers["pool"]
            try:
                # let the startup poll land FIRST so it can't overwrite
                # the injected telemetry below
                await asyncio.sleep(0.3)
                # fake telemetry: the DEAD replica scores best (idle),
                # the live one looks loaded — the pick must choose
                # dead, hit ECONNREFUSED, and fail over pre-first-byte
                picker.observe(dead, max_slots=4, active_slots=0)
                picker.observe(live.address, max_slots=4,
                               active_slots=3)
                async with aiohttp.ClientSession() as s:
                    async with s.post(
                        gw + "/v1/chat/completions",
                        json={"model": "m1", "messages": [
                            {"role": "user", "content": "hi"}]},
                    ) as r:
                        assert r.status == 200, await r.read()
                        body = await r.json()
                    assert body["choices"][0]["message"]["content"] \
                        == "ok"
                    async with s.get(gw + "/debug/decisions") as r:
                        dec = (await r.json())["decisions"]
                routed = [d for d in dec if d.get("chosen")]
                assert routed, dec
                d = routed[0]
                assert d["chosen"] == live.address
                assert d["failover_from"] == [dead]
                # per-replica breaker evidence accumulated
                assert server.circuit._state(
                    dead).consecutive_failures >= 1
            finally:
                await runner.cleanup()
                await live.stop()

        asyncio.run(main())

    def test_immediate_503_fails_over(self):
        """A replica answering an immediate 503 (e.g. draining) before
        any stream byte retries on the sibling instead of surfacing
        the 503."""

        class Refusing(StubReplica):
            async def start(self):
                app = web.Application()

                async def refuse(_req):
                    return web.json_response(
                        {"error": {"message": "draining"}}, status=503,
                        headers={"retry-after": "2"})

                async def state(_req):
                    return web.json_response(self._state())

                app.router.add_get("/state", state)
                app.router.add_post("/v1/chat/completions", refuse)
                self._runner = web.AppRunner(app)
                await self._runner.setup()
                site = web.TCPSite(self._runner, "127.0.0.1", 0)
                await site.start()
                self.port = site._server.sockets[0].getsockname()[1]
                self.url = f"http://127.0.0.1:{self.port}"
                self.address = f"127.0.0.1:{self.port}"
                return self

        async def main():
            refusing = await Refusing("pfb-503").start()
            live = await StubReplica("pfb-ok").start()
            server, runner = await run_gateway(
                RuntimeConfig.build(
                    _gw_config([refusing.address, live.address])),
                port=0)
            site = list(runner.sites)[0]
            gw = "http://127.0.0.1:%d" % (
                site._server.sockets[0].getsockname()[1])
            picker = server._pickers["pool"]
            try:
                await asyncio.sleep(0.3)  # startup poll lands first
                picker.observe(refusing.address, max_slots=4,
                               active_slots=0)
                picker.observe(live.address, max_slots=4,
                               active_slots=3)
                async with aiohttp.ClientSession() as s:
                    async with s.post(
                        gw + "/v1/chat/completions",
                        json={"model": "m1", "messages": [
                            {"role": "user", "content": "hi"}]},
                    ) as r:
                        assert r.status == 200, await r.read()
                        body = await r.json()
                assert body["choices"][0]["message"]["content"] == "ok"
                assert live.served == 1
            finally:
                await runner.cleanup()
                await refusing.stop()
                await live.stop()

        asyncio.run(main())


class TestTornStateChaos:
    def test_torn_state_counts_as_failed_poll(self):
        """The chaos proxy's truncated /state bodies must walk the
        health machine down (the PR 12 torn-body rule), never leave
        the replica scored healthy on frozen telemetry."""

        async def main():
            backend = await StubReplica("torn-b").start()
            proxy = await chaos.TornStateProxy(backend.address).start()
            picker = _picker([proxy.address], poll_interval=0.05)
            await picker.start()
            try:
                await _wait_for(
                    lambda: picker.fleet.health_of(proxy.address)
                    == "up", what="proxy up")
                proxy.torn = True
                await _wait_for(
                    lambda: picker.fleet.health_of(proxy.address)
                    == "down", what="torn replica down")
                assert picker.state[proxy.address].poll_failures >= 3
                assert not picker.is_routable(proxy.address)
                proxy.torn = False
                await _wait_for(
                    lambda: picker.fleet.health_of(proxy.address)
                    == "up", what="healed")
            finally:
                await picker.stop()
                await proxy.stop()
                await backend.stop()

        asyncio.run(main())


class TestFleetSurface:
    def test_fleet_state_carries_controller_block(self):
        async def main():
            s1 = await StubReplica("ctl-a").start()
            server, runner = await run_gateway(
                RuntimeConfig.build(_gw_config(
                    [s1.address], poll=0.05,
                    extra={"controller": {
                        "min_replicas": 1, "max_replicas": 2,
                        "tick_s": 0.1, "idle_ticks": 10 ** 6}})),
                port=0)
            site = list(runner.sites)[0]
            gw = "http://127.0.0.1:%d" % (
                site._server.sockets[0].getsockname()[1])
            try:
                assert "pool" in server._controllers
                await _wait_for(
                    lambda: server._pickers["pool"].fleet.health_of(
                        s1.address) == "up", what="replica up")
                async with aiohttp.ClientSession() as s:
                    async with s.get(gw + "/fleet/state") as r:
                        snap = await r.json()
                    ctl = snap["backends"]["pool"]["controller"]
                    assert ctl["min_replicas"] == 1
                    assert ctl["counters"]["scale_outs"] == 0
                    assert s1.address in ctl["replicas_live"]
                    async with s.get(gw + "/fleet/metrics") as r:
                        text = (await r.read()).decode()
                    for _key, name in CONTROLLER_GAUGES:
                        assert name in text, name
            finally:
                await runner.cleanup()
                await s1.stop()

        asyncio.run(main())

    def test_fleetwatch_renders_controller(self):
        import importlib.util

        path = os.path.join(_HERE, "..", "tools", "fleetwatch.py")
        spec = importlib.util.spec_from_file_location(
            "fleetwatch", os.path.abspath(path))
        fw = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(fw)
        out = fw.render_table({
            "backends": {"pool": {
                "replicas": {}, "rollup": {}, "slo": {},
                "controller": {
                    "min_replicas": 1, "max_replicas": 4,
                    "replicas_live": ["h:1", "h:2"],
                    "counters": {"scale_outs": 2, "scale_ins": 1,
                                 "drains": 1, "failovers": 3,
                                 "launch_failures": 0},
                    "launches_in_flight": 1,
                    "drains_in_progress": ["h:2"],
                    "events": [{"ts": 1700000000.0,
                                "action": "scale_out",
                                "reason": "sustained overshoot"}],
                },
            }},
        })
        assert "controller [1..4]" in out
        assert "out 2" in out and "failovers 3" in out
        assert "DRAINING h:2" in out
        assert "scale_out" in out


# -- slow tier: live rigs over real tpuserve subprocesses -----------------

_TINY = {
    "vocab_size": 512, "dim": 64, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "ffn_dim": 128, "max_seq_len": 256,
    "rope_theta": 10000.0,
}


def _child_spec(model: str, batch: int = 2) -> dict:
    return {
        "model": model, "cfg": dict(_TINY), "batch": batch,
        "page": 16, "k": 2, "quantize": "",
        "engine": {"min_prefill_bucket": 16, "num_pages": 48,
                   "kv_cache_dtype": "float32",
                   # no stream of a rig pays a prefill compile for a
                   # group shape the warm pass missed
                   "warm_prefill_buckets": 2},
        "param_dtype": "float32", "lora": {}, "tp": 1,
    }


async def _stream_completion(s, url: str, payload: dict,
                             dest: str = "") -> dict:
    """One streamed /v1/completions; returns pieces + outcome flags."""
    headers = {}
    if dest:
        headers["x-gateway-destination-endpoint"] = dest
    out = {"pieces": [], "done": False, "error_event": False,
           "status": 0, "aborted": False, "rid": ""}
    try:
        async with s.post(url + "/v1/completions", json=payload,
                          headers=headers) as resp:
            out["status"] = resp.status
            out["rid"] = resp.headers.get("x-aigw-request-id", "")
            if resp.status != 200:
                await resp.read()
                return out
            async for line in resp.content:
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                d = line[6:]
                if d == b"[DONE]":
                    out["done"] = True
                    break
                ev = json.loads(d)
                if "error" in ev:
                    out["error_event"] = True
                    continue
                ch = ev.get("choices") or []
                if ch and ch[0].get("text"):
                    out["pieces"].append(ch[0]["text"])
    except (aiohttp.ClientError, asyncio.TimeoutError):
        out["aborted"] = True
    return out


@pytest.mark.slow
class TestGracefulShutdownLive:
    def test_drain_endpoint_and_sigterm_exit0(self):
        """POST /drain flips /state draining + 503s new admissions
        while a live stream finishes; SIGTERM then exits 0 with zero
        live slots — the graceful-shutdown satellite end to end."""
        rep = chaos.spawn_replica(_child_spec("tiny-ctl-a"))

        async def main():
            timeout = aiohttp.ClientTimeout(total=600)
            async with aiohttp.ClientSession(timeout=timeout) as s:
                payload = {"model": "tiny-ctl-a", "prompt": "d " * 20,
                           "max_tokens": 24, "temperature": 0.0,
                           "stream": True, "logit_bias": {"97": 100}}
                task = asyncio.ensure_future(
                    _stream_completion(s, rep.url, payload))
                await asyncio.sleep(0.3)
                async with s.post(rep.url + "/drain", json={}) as r:
                    assert r.status == 200
                    d = await r.json()
                    assert d["draining"] is True
                async with s.get(rep.url + "/state") as r:
                    st = await r.json()
                assert st["draining"] is True
                # new admissions refused with 503 + Retry-After
                async with s.post(rep.url + "/v1/completions",
                                  json=dict(payload, stream=False)
                                  ) as r:
                    assert r.status == 503
                    assert r.headers.get("retry-after")
                # the live stream still completes cleanly
                res = await task
                assert res["done"] and not res["aborted"]
                assert len("".join(res["pieces"])) == 24
                # un-drain works (cancelled rolling update)
                async with s.post(rep.url + "/drain",
                                  json={"on": False}) as r:
                    assert (await r.json())["draining"] is False
                async with s.post(rep.url + "/v1/completions",
                                  json=dict(payload, stream=False,
                                            max_tokens=2)) as r:
                    assert r.status == 200

        try:
            asyncio.run(main())
            rc = rep.term(timeout=90)
            assert rc == 0, f"graceful exit code {rc}"
        finally:
            if rep.alive():
                rep.kill9()


@pytest.mark.slow
class TestKill9FailoverLive:
    def test_kill9_mid_decode_typed_error_and_failover(self):
        """kill -9 mid-decode: the in-flight stream ends with a TYPED
        error event (never torn/hanging), the health machine walks the
        replica down, the controller records the failover and launches
        a replacement, and new traffic completes on the survivor."""
        rep_a = chaos.spawn_replica(_child_spec("tiny-ctl-b"))
        rep_b = chaos.spawn_replica(_child_spec("tiny-ctl-b"))

        async def main():
            cfg = Config.parse({
                "version": "v1",
                "backends": [{
                    "name": "pool", "schema": "OpenAI",
                    "endpoints": [rep_a.address, rep_b.address],
                    "picker_poll_interval": 0.1,
                }],
                "routes": [{"name": "r", "rules": [
                    {"model_prefixes": ["tiny"],
                     "backends": ["pool"]}]}],
                "models": ["tiny-ctl-b"],
            })
            server, runner = await run_gateway(RuntimeConfig.build(cfg),
                                               port=0)
            site = list(runner.sites)[0]
            gw = "http://127.0.0.1:%d" % (
                site._server.sockets[0].getsockname()[1])
            picker = server._pickers["pool"]
            launcher = FakeLauncher()
            ctl = FleetController(
                picker, ControllerConfig.parse({
                    "min_replicas": 2, "max_replicas": 3,
                    "tick_s": 0.1, "down_grace_s": 0.3,
                    "scale_cooldown_s": 0.0, "idle_ticks": 10 ** 6}),
                launcher=launcher, decisions=server.decisions,
                backend="pool")
            await ctl.start()
            try:
                await _wait_for(
                    lambda: all(st.healthy
                                for st in picker.state.values()),
                    timeout=60, what="pool healthy")
                timeout = aiohttp.ClientTimeout(total=600)
                async with aiohttp.ClientSession(timeout=timeout) as s:
                    payload = {"model": "tiny-ctl-b",
                               "prompt": "k " * 20,
                               "max_tokens": 120, "temperature": 0.0,
                               "stream": True,
                               "logit_bias": {"97": 100}}
                    task = asyncio.ensure_future(_stream_completion(
                        s, gw, payload, dest=rep_a.address))
                    await asyncio.sleep(0.5)  # mid-decode
                    rep_a.kill9()
                    res = await task
                    # the acceptance contract: a complete stream or a
                    # clean TYPED error event — never a torn stream
                    assert not res["aborted"]
                    assert res["done"] or res["error_event"], res
                    await _wait_for(
                        lambda: picker.fleet.health_of(rep_a.address)
                        == "down", timeout=30, what="A down")
                    await _wait_for(
                        lambda: ctl.counters["failovers"] >= 1,
                        timeout=30, what="failover recorded")
                    await _wait_for(
                        lambda: len(launcher.launched) >= 1,
                        timeout=30, what="replacement launched")
                    kinds = [ev["action"] for ev in ctl.events]
                    assert "reroute" in kinds and "failover" in kinds
                    # lifecycle actions visible in the decision ring
                    lifecycles = [d.get("lifecycle") for d in
                                  server.decisions.snapshot(limit=200)]
                    assert "failover" in lifecycles
                    # new traffic completes on the survivor
                    res2 = await _stream_completion(
                        s, gw, dict(payload, max_tokens=8,
                                    prompt="post " * 10))
                    assert res2["done"], res2
            finally:
                await ctl.stop()
                await runner.cleanup()

        try:
            asyncio.run(main())
        finally:
            if rep_a.alive():
                rep_a.kill9()
            rep_b.term(timeout=60)


@pytest.mark.slow
class TestLosslessDrainLive:
    def test_drain_retire_migrates_stream_byte_identical_exit0(self):
        """The f32 acceptance rig: a stream on the draining replica is
        migrated off client-invisibly (its bytes equal the solo run on
        the survivor), the replica reaches zero live slots, exits 0,
        and leaves the pool."""
        launcher = LocalProcessLauncher(
            _child_spec("tiny-ctl-c", batch=2), term_grace_s=60.0,
            env={"JAX_PLATFORMS": "cpu"})
        rep_b = chaos.spawn_replica(_child_spec("tiny-ctl-c", batch=2))

        async def main():
            addr_a = await launcher.launch()
            cfg = Config.parse({
                "version": "v1",
                "backends": [{
                    "name": "pool", "schema": "OpenAI",
                    "endpoints": [addr_a, rep_b.address],
                    "picker_poll_interval": 0.1,
                    "migration": True,
                    "migration_queue_depth": 2,
                    "migration_young_tokens": 8,
                }],
                "routes": [{"name": "r", "rules": [
                    {"model_prefixes": ["tiny"],
                     "backends": ["pool"]}]}],
                "models": ["tiny-ctl-c"],
            })
            server, runner = await run_gateway(RuntimeConfig.build(cfg),
                                               port=0)
            site = list(runner.sites)[0]
            gw = "http://127.0.0.1:%d" % (
                site._server.sockets[0].getsockname()[1])
            picker = server._pickers["pool"]
            ctl = FleetController(
                picker, ControllerConfig.parse({
                    "min_replicas": 1, "max_replicas": 2,
                    "tick_s": 0.1, "drain_timeout_s": 300.0,
                    "idle_ticks": 10 ** 6}),
                launcher=launcher, decisions=server.decisions,
                backend="pool")
            try:
                await _wait_for(
                    lambda: all(st.healthy
                                for st in picker.state.values()),
                    timeout=120, what="pool healthy")
                timeout = aiohttp.ClientTimeout(total=900)
                async with aiohttp.ClientSession(timeout=timeout) as s:
                    payload = {"model": "tiny-ctl-c",
                               "prompt": "drain me " * 5,
                               "max_tokens": 64, "temperature": 0.0,
                               "stream": True,
                               "logit_bias": {"97": 100}}
                    # solo control on the SURVIVOR (identical weights:
                    # both children init from the same seed/spec)
                    solo = await _stream_completion(s, rep_b.url,
                                                    payload)
                    assert solo["done"]
                    # live stream pinned to A, then drain A
                    task = asyncio.ensure_future(_stream_completion(
                        s, gw, payload, dest=addr_a))
                    await asyncio.sleep(0.8)  # a few tokens in
                    drained = await ctl.drain_and_retire(
                        addr_a, reason="test")
                    res = await task
                    # client-invisible: one complete stream, bytes
                    # equal the solo run (the migration splice)
                    assert res["done"] and not res["error_event"], res
                    assert "".join(res["pieces"]) \
                        == "".join(solo["pieces"])
                    assert drained, "drain timed out with live slots"
                    # the replica left the pool and exited 0
                    assert addr_a not in picker.state
                    assert launcher.returncode(addr_a) == 0
                    kinds = [ev["action"] for ev in ctl.events]
                    assert kinds.count("drain_start") == 1
                    assert "drain_complete" in kinds
                    assert "retire" in kinds
                    # the migration actually carried the stream (the
                    # byte-identity above could not hold otherwise,
                    # but make the mechanism explicit)
                    mets = (await (await s.get(gw + "/metrics")
                                   ).read()).decode()
                    assert "aigw_migrations_total" in mets
                    # every lifecycle action landed in the decision
                    # ring (externally pinned streams carry no routing
                    # entry — the lifecycle entries are the audit)
                    lifecycles = [d.get("lifecycle") for d in
                                  server.decisions.snapshot(limit=200)]
                    for action in ("drain_start", "drain_complete",
                                   "retire"):
                        assert action in lifecycles, action
            finally:
                await ctl.stop()
                await runner.cleanup()

        try:
            asyncio.run(main())
        finally:
            asyncio.run(launcher.close())
            rep_b.term(timeout=60)


def test_local_launcher_gives_each_replica_its_own_chip(tmp_path):
    """One process per chip (ISSUE 21): with ``chips`` = N the launcher
    confines each child to the lowest free chip through its environment
    — set before the child starts — refuses a launch when every chip is
    taken, and hands a chip out again once its replica is gone. The
    child here is a stub that reports the environment it was given."""
    child = tmp_path / "child.py"
    child.write_text(
        "import json, os, sys, time\n"
        "spec = json.loads(sys.argv[1])\n"
        "port = spec['base'] + int(os.environ['TPU_VISIBLE_CHIPS'])\n"
        "open(os.path.join(spec['dir'], str(port)), 'w').write(\n"
        "    json.dumps({k: v for k, v in os.environ.items()\n"
        "                if k.startswith('TPU_')}))\n"
        "print(f'SERVE_PORT={port}', flush=True)\n"
        "time.sleep(60)\n")
    launcher = LocalProcessLauncher(
        {"dir": str(tmp_path), "base": 40000}, child_path=str(child),
        term_grace_s=5.0, chips=2)

    def env_of(addr: str) -> dict:
        return json.loads((tmp_path / addr.rsplit(":", 1)[1]).read_text())

    async def main():
        try:
            a, b = await asyncio.gather(launcher.launch(),
                                        launcher.launch())
            assert sorted(env_of(x)["TPU_VISIBLE_CHIPS"]
                          for x in (a, b)) == ["0", "1"]
            assert env_of(a)["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert env_of(a)["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            with pytest.raises(RuntimeError, match="hold a replica"):
                await launcher.launch()
            freed = env_of(a)["TPU_VISIBLE_CHIPS"]
            await launcher.terminate(a)
            c = await launcher.launch()
            assert env_of(c)["TPU_VISIBLE_CHIPS"] == freed
        finally:
            await launcher.close()

    asyncio.run(main())
