"""The load ledger (aigw_tpu/obs/xla_events.py): what every program of a
process cost to get, by stage, and which of them a request waited for.

Three views: the ledger fed by hand (the order JAX sends its events
in), a real process under a compile cache of its own (cold, then
warm), and a server whose first request has to load a program.
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import threading

import aiohttp
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.obs import xla_events
from aigw_tpu.obs.xla_events import LOG_CAPACITY, LoadLedger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


def _program(led: LoadLedger, fn: str, hit: bool | None,
             trace_s: float = 0.4, lower_s: float = 0.5,
             backend_s: float = 2.0, retrieval_s: float = 1.5) -> None:
    """One program's events as JAX 0.9 sends them: the inner functions'
    traces, its own, a stray one, the lowering, the cache's, the
    backend span."""
    led.on_duration(TRACE, 0.001, fun_name="add")
    led.on_duration(TRACE, trace_s, fun_name=fn)
    led.on_duration(TRACE, 0.002, fun_name="less")
    led.on_duration(LOWER, lower_s, fun_name=f"jit({fn})")
    if hit:
        led.on_event(HIT)
        led.on_duration(SAVED, 20.0)
        led.on_duration(RETRIEVAL, retrieval_s)
    elif hit is False:
        led.on_event(MISS)
    led.on_duration(BACKEND, backend_s, fun_name=f"jit({fn})")


# -- the ledger, fed by hand ----------------------------------------------

def test_three_stages_fold_into_one_record_under_the_programs_name():
    led = LoadLedger()
    _program(led, "decode", hit=True)
    snap = led.snapshot()
    assert list(snap["programs"]) == ["jit(decode)"]
    assert snap["programs"]["jit(decode)"] == {
        "requests": 1, "trace_ms": 400.0, "lower_ms": 500.0,
        "backend_ms": 2000.0, "retrieval_ms": 1500.0, "saved_ms": 20000.0,
        "hits": 1, "misses": 0}
    (entry,) = snap["log"]
    assert entry["fn"] == "jit(decode)" and entry["hit"] is True
    assert not entry["late"] and entry["phase"] == ""
    assert entry["t_ms"] > 0


def test_a_program_whose_jaxpr_was_cached_has_no_trace():
    led = LoadLedger()
    led.on_duration(TRACE, 0.3, fun_name="other")  # nobody lowers it
    led.on_duration(LOWER, 0.5, fun_name="jit(decode)")
    led.on_duration(BACKEND, 1.0, fun_name="jit(decode)")
    rec = led.snapshot()["programs"]["jit(decode)"]
    assert rec["trace_ms"] == 0 and rec["lower_ms"] == 500.0
    # and the cache was not asked: neither a hit nor a miss
    assert rec["hits"] == rec["misses"] == 0
    assert led.snapshot()["log"][0]["hit"] is None


def test_stages_of_two_threads_do_not_mix():
    led = LoadLedger()
    led.on_duration(TRACE, 0.4, fun_name="decode")
    led.on_duration(LOWER, 0.5, fun_name="jit(decode)")
    led.on_event(HIT)
    other = threading.Thread(
        target=_program, args=(led, "prefill", False))
    other.start()
    other.join(timeout=10)
    assert not other.is_alive()
    led.on_duration(BACKEND, 1.0, fun_name="jit(decode)")
    programs = led.snapshot()["programs"]
    assert programs["jit(decode)"]["hits"] == 1
    assert programs["jit(decode)"]["trace_ms"] == 400.0
    assert programs["jit(prefill)"]["misses"] == 1
    assert programs["jit(prefill)"]["retrieval_ms"] == 0


def test_loads_after_ready_are_late_and_the_totals_are_the_tables_sums():
    led = LoadLedger()
    _program(led, "decode", hit=True)
    _program(led, "decode", hit=False)
    assert led.snapshot()["totals"]["xla_late_loads"] == 0
    led.ready = True
    _program(led, "tail", hit=True, trace_s=0.1, lower_s=0.2,
             backend_s=0.7, retrieval_s=0.6)
    snap = led.snapshot()
    t = snap["totals"]
    assert (t["xla_late_loads"], t["xla_late_ms"], t["xla_late_trace_ms"],
            t["xla_late_lower_ms"], t["xla_late_retrieval_ms"]) == \
        (1, 1000.0, 100.0, 200.0, 600.0)
    assert [e["late"] for e in snap["log"]] == [False, False, True]
    table = snap["programs"].values()
    for total, column in (("compiles", "requests"),
                          ("compile_ms", "backend_ms"),
                          ("xla_trace_ms", "trace_ms"),
                          ("xla_lower_ms", "lower_ms"),
                          ("xla_retrieval_ms", "retrieval_ms"),
                          ("xla_cache_hits", "hits"),
                          ("xla_cache_misses", "misses")):
        assert t[total] == sum(r[column] for r in table), total


def test_a_late_load_reaches_the_hook_of_its_own_thread_only():
    led = LoadLedger()
    seen: list[dict] = []

    def hook(load: dict) -> None:
        seen.append(dict(load))
        load["phase"] = "prefill_dispatch"

    led.late_hooks[threading.get_ident()] = hook
    _program(led, "early", hit=True)
    assert seen == []  # not late yet
    led.ready = True
    _program(led, "tail", hit=True)
    other = threading.Thread(target=_program, args=(led, "embed", True))
    other.start()
    other.join(timeout=10)
    assert not other.is_alive()
    assert [s["fn"] for s in seen] == ["jit(tail)"]
    by_fn = {e["fn"]: e for e in led.snapshot()["log"]}
    assert by_fn["jit(tail)"]["phase"] == "prefill_dispatch"
    assert by_fn["jit(embed)"]["late"] and by_fn["jit(embed)"]["phase"] == ""


def test_a_hook_that_raises_loses_nothing():
    led = LoadLedger()
    led.ready = True
    led.late_hooks[threading.get_ident()] = lambda load: 1 / 0
    _program(led, "tail", hit=True)
    assert led.snapshot()["totals"]["xla_late_loads"] == 1


def test_the_log_keeps_the_newest_events_and_the_table_all():
    led = LoadLedger()
    for i in range(LOG_CAPACITY + 9):
        _program(led, f"f{i % 3}", hit=True)
    snap = led.snapshot()
    assert len(snap["log"]) == LOG_CAPACITY == snap["log_capacity"]
    assert sum(r["requests"] for r in snap["programs"].values()) == \
        LOG_CAPACITY + 9
    times = [e["t_ms"] for e in snap["log"]]
    assert times == sorted(times)


# -- this process's ledger, under real JAX --------------------------------

def test_a_jitted_function_gives_one_record_with_all_three_stages():
    assert xla_events.install()

    @jax.jit
    def _xla_events_probe(x):
        return jnp.tanh(x) * 3 + jnp.sum(x)

    before = xla_events.compile_count()
    _xla_events_probe(jnp.ones((3, 5))).block_until_ready()
    rec = xla_events.LEDGER.snapshot()["programs"]["jit(_xla_events_probe)"]
    assert rec["requests"] == 1
    assert rec["trace_ms"] > 0 and rec["lower_ms"] > 0
    assert rec["backend_ms"] > 0
    assert xla_events.compile_count() > before
    # the same shape again is no request; another shape is
    _xla_events_probe(jnp.ones((3, 5))).block_until_ready()
    _xla_events_probe(jnp.ones((4, 5))).block_until_ready()
    rec = xla_events.LEDGER.snapshot()["programs"]["jit(_xla_events_probe)"]
    assert rec["requests"] == 2
    assert not hasattr(xla_events, "_last_compile_at")


_CHILD = """
import json, sys
from aigw_tpu.utils.boot import boot_jax
boot_jax("cpu")
import jax, jax.numpy as jnp
from aigw_tpu.obs import xla_events
tracker = xla_events.CompileTracker()

@jax.jit
def probe(x):
    return jnp.tanh(x) @ x.T

probe(jnp.ones((8, 4))).block_until_ready()
early = tracker.totals()
xla_events.mark_ready()
probe(jnp.ones((16, 4))).block_until_ready()
print(json.dumps({"early": early, "state": tracker.totals(),
                  **xla_events.LEDGER.snapshot()}))
"""


@pytest.fixture(scope="module")
def cold_and_warm(tmp_path_factory):
    """The child above twice under one compile cache of its own."""
    cache = str(tmp_path_factory.mktemp("xla-events-cache"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache, PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _CHILD], cwd=REPO, env=env,
            capture_output=True, text=True, timeout=180)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    return runs


def test_a_cold_process_misses_and_a_warm_one_hits_with_retrieval(
        cold_and_warm):
    cold, warm = (r["programs"]["jit(probe)"] for r in cold_and_warm)
    assert (cold["requests"], cold["misses"], cold["hits"]) == (2, 2, 0)
    assert cold["retrieval_ms"] == 0
    assert (warm["requests"], warm["misses"], warm["hits"]) == (2, 0, 2)
    assert 0 < warm["retrieval_ms"] <= warm["backend_ms"]
    for rec in (cold, warm):
        assert rec["trace_ms"] > 0 and rec["lower_ms"] > 0


def test_a_new_shape_after_ready_is_late_with_its_stage_split(
        cold_and_warm):
    for run in cold_and_warm:
        early, t = run["early"], run["state"]
        assert early["xla_late_loads"] == 0 and early["xla_late_ms"] == 0
        late = [e for e in run["log"] if e["late"]]
        assert late and late[-1]["fn"] == "jit(probe)"
        assert all(not e["late"] for e in run["log"][:-len(late)])
        assert t["xla_late_loads"] == len(late)
        for key, column in (("xla_late_trace_ms", "trace_ms"),
                            ("xla_late_lower_ms", "lower_ms"),
                            ("xla_late_retrieval_ms", "retrieval_ms")):
            assert t[key] == pytest.approx(
                sum(e[column] for e in late), abs=0.01), key
        assert t["xla_late_ms"] == pytest.approx(
            sum(e["trace_ms"] + e["lower_ms"] + e["backend_ms"]
                for e in late), abs=0.01)
        assert t["xla_late_trace_ms"] > 0 and t["xla_late_lower_ms"] > 0
    warm = cold_and_warm[1]["state"]
    assert 0 < warm["xla_late_retrieval_ms"] < warm["xla_late_ms"]
    assert cold_and_warm[0]["state"]["xla_late_retrieval_ms"] == 0


def test_the_totals_a_state_carries_are_the_sums_over_the_table(
        cold_and_warm):
    for run in cold_and_warm:
        t, table = run["state"], run["programs"].values()
        for key, column in (("xla_trace_ms", "trace_ms"),
                            ("xla_lower_ms", "lower_ms"),
                            ("xla_retrieval_ms", "retrieval_ms"),
                            ("xla_cache_hits", "hits"),
                            ("xla_cache_misses", "misses")):
            assert t[key] == pytest.approx(
                sum(r[column] for r in table), abs=0.01), key
        # the engine's two deltas keep their meaning: compile requests
        # since the tracker was made, and their backend milliseconds
        assert t["xla_compiles"] == sum(r["requests"] for r in table)
        assert t["xla_compile_ms"] == pytest.approx(
            sum(r["backend_ms"] for r in table), abs=0.01)


# -- a server whose first request has to load a program -------------------

@pytest.fixture(scope="module")
def ready_serve():
    """tpuserve (tiny-random), brought up the way both entry points do
    (``server.listen``), so that it has called itself ready."""
    from aigw_tpu.tpuserve.engine import EngineConfig
    from aigw_tpu.tpuserve.server import TPUServeServer, listen

    holder = {}
    started = threading.Event()

    def run():
        async def main():
            server = TPUServeServer(
                "tiny-random",
                EngineConfig(max_batch_size=2, max_seq_len=256,
                             page_size=16, min_prefill_bucket=16))
            _runner, holder["port"] = await listen(server, "127.0.0.1", 0)
            # warmup loaded the row update at the idle page bucket (1)
            # alone. Whether a request is admitted by a row update or a
            # full state build is a race between the client's next ask
            # and the engine going idle, so on a loaded machine the
            # row update at the prompt's bucket could first load under
            # the THIRD request (ROADMAP D7). Load it at every bucket,
            # as warmup does at its own, before any request is timed.
            eng = server.engine
            P = 1
            while P <= eng.cfg.max_pages_per_seq:
                eng._row_update_fn_built()(
                    eng._build_device_state(bucket=P), np.int32(0),
                    eng._row_host_values(0, P))
                P *= 2
            holder["loop"] = asyncio.get_running_loop()
            started.set()
            await asyncio.Event().wait()

        try:
            asyncio.run(main())
        except RuntimeError:
            pass

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(timeout=180)
    yield f"http://127.0.0.1:{holder['port']}"
    holder["loop"].call_soon_threadsafe(holder["loop"].stop)


def test_a_request_that_waits_for_a_program_names_it_and_the_next_does_not(
        ready_serve):
    async def ask(s: aiohttp.ClientSession, content: str) -> dict:
        async with s.post(
                ready_serve + "/v1/chat/completions",
                json={"model": "tiny-random", "max_tokens": 4,
                      "temperature": 0,
                      "messages": [{"role": "user", "content": content}]},
        ) as resp:
            assert resp.status == 200
            rid = resp.headers["x-aigw-request-id"]
        async with s.get(ready_serve + f"/debug/requests/{rid}") as r:
            return await r.json()

    async def main():
        async with aiohttp.ClientSession() as s:
            async with s.get(ready_serve + "/state") as r:
                before = await r.json()
            # nothing warmed this prompt's prefill program: the first
            # request loads it; the second finds its prefix cached (a
            # path of its own); the third meets nothing new
            details = [await ask(s, "which programs did I wait for")
                       for _ in range(3)]
            async with s.get(ready_serve + "/state") as r:
                after = await r.json()
            async with s.get(ready_serve + "/debug/programs") as r:
                programs = await r.json()
        return before, details, after, programs

    before, details, after, programs = asyncio.run(main())
    loads = [[e for e in d["events"] if e["name"] == "program_load"]
             for d in details]
    assert loads[0] and loads[2] == []
    for e in loads[0]:
        assert set(e["attrs"]) == {"fn", "ms", "hit"}
        assert e["attrs"]["fn"].startswith("jit(") and e["attrs"]["ms"] > 0
    # what the request was told is what the ledger logged, with the
    # loop phase the engine thread was in
    late = {e["fn"]: e for e in programs["log"] if e["late"]}
    for e in loads[0]:
        assert late[e["attrs"]["fn"]]["phase"] in (
            "admit", "prefill_dispatch", "prefill_block", "state_build",
            "row_update", "decode_dispatch", "window_fetch", "emit")
    assert any(fn.startswith("jit(") for fn in programs["programs"])
    assert after["xla_late_loads"] - before["xla_late_loads"] >= len(loads[0])
    assert after["xla_late_ms"] > before["xla_late_ms"]


# -- what the engine does with a late load (its hook, by hand) ------------

def test_the_engines_hook_names_the_phase_tells_requests_and_marks_a_capture(
        monkeypatch):
    from aigw_tpu.obs import flight
    from aigw_tpu.obs.flight import (
        DECODE_DISPATCH, PREFILL_DISPATCH, FlightRecorder, LoopLedger)
    from aigw_tpu.tpuserve.engine import Engine, EngineStats

    made: list[tuple[str, dict]] = []

    class Span:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(
        flight, "_trace_annotation",
        lambda name, **facts: made.append((name, facts)) or Span())
    # the hook needs the ledger, the recorder and nothing else of an engine
    eng = Engine.__new__(Engine)
    eng._thread = None
    eng.stats = EngineStats()
    eng.stats.loop = loop = LoopLedger()
    eng.flight = rec = FlightRecorder(capacity=8)
    done = rec.begin("finished")
    rec.finish(done, "stop", 1)
    waiting, queued = rec.begin("waiting"), rec.begin("queued")
    load = {"fn": "jit(tail)", "trace_ms": 100.0, "lower_ms": 200.0,
            "backend_ms": 700.0, "retrieval_ms": 600.0, "hit": True,
            "late": True, "phase": ""}
    loop.enter(PREFILL_DISPATCH)
    eng._on_late_load(load)
    assert load["phase"] == "prefill_dispatch"
    for entry in (waiting, queued):
        (event,) = entry.events
        assert event[0] == "program_load"
        assert event[2] == {"fn": "jit(tail)", "ms": 1000.0, "hit": True}
    assert done.events == [] and made == []  # no capture: nothing marked
    # while a capture runs the load is a mark on the profiler's clock
    loop.capture_begin()
    loop.enter(DECODE_DISPATCH)
    eng._on_late_load(dict(load, fn="jit(scan_k)"))
    assert ("xla/late_load", {"fn": "jit(scan_k)", "ms": 1000.0}) in made
    assert [e[2]["fn"] for e in waiting.events] == [
        "jit(tail)", "jit(scan_k)"]
