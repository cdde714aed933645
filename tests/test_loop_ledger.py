"""The engine loop's phase ledger (``obs/flight.py`` ``LoopLedger``):
it partitions the loop, nesting gives self time, the three old timers
are views of it, its flat ``/state`` and ``/metrics`` keys exist and
only grow, a capture cuts the counters to whole windows and whole
prefill calls, and with the capture flag down nothing is constructed
on the profiler's clock. All on the CPU, no skip condition."""

from __future__ import annotations

import asyncio
import json
import threading
import time

import aiohttp
import jax
import pytest

from aigw_tpu.models import llama
from aigw_tpu.models.registry import get_model_spec
from aigw_tpu.obs import flight
from aigw_tpu.obs.flight import (
    ADMIT,
    DECODE_DISPATCH,
    EMIT,
    IDLE,
    OTHER,
    PREFILL_BLOCK,
    PREFILL_DISPATCH,
    WINDOW_FETCH,
    FlightEntry,
    LoopLedger,
    RequestTrace,
)
from aigw_tpu.obs.metrics import (
    CAPTURE_COUNTERS,
    ENGINE_GAUGES,
    LOOP_GAUGES,
    LOOP_PHASES,
    render_engine_gauges,
)
from aigw_tpu.tpuserve.engine import (
    Engine,
    EngineConfig,
    EngineStats,
    GenRequest,
)
from aigw_tpu.tpuserve.sampling import SamplingParams
from aigw_tpu.tpuserve.server import TPUServeServer

_SPEC = get_model_spec("tiny-random")
_PARAMS = llama.init_params(jax.random.PRNGKey(3), _SPEC.config)


# -- the ledger alone, on a clock the test owns ------------------------------

class Clock:
    def __init__(self):
        self.now = 1_000

    def __call__(self) -> int:
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(flight.time, "perf_counter_ns", c)
    return c


class Stub:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts what is
    constructed, entered and left."""

    def __init__(self):
        self.made: list[tuple[str, dict]] = []
        self.open = 0

    def __call__(self, name, **facts):
        self.made.append((name, facts))
        stub = self

        class Span:
            def __enter__(self):
                stub.open += 1

            def __exit__(self, *exc):
                stub.open -= 1

        return Span()


@pytest.fixture
def annotations(monkeypatch):
    stub = Stub()
    monkeypatch.setattr(flight, "_trace_annotation", stub)
    return stub


#: (script of (op, phase, ns later), nanoseconds each phase must hold)
SCRIPTS = {
    "flat": ([("enter", ADMIT, 10), ("enter", DECODE_DISPATCH, 30),
              ("enter", IDLE, 5), ("enter", OTHER, 100)],
             {"other": 10, "admit": 30, "decode_dispatch": 5, "idle": 100}),
    # a decode tick inside a chunked prompt's prefill: the tick's 40 ns
    # are not the prefill's
    "nested": ([("enter", PREFILL_DISPATCH, 0), ("push", DECODE_DISPATCH, 7),
                ("push", WINDOW_FETCH, 3), ("pop", None, 20),
                ("pop", None, 17), ("enter", PREFILL_BLOCK, 11),
                ("enter", OTHER, 50)],
               {"prefill_dispatch": 18, "decode_dispatch": 20,
                "window_fetch": 20, "prefill_block": 50}),
    "same_phase_twice": ([("enter", EMIT, 0), ("enter", EMIT, 4),
                          ("enter", OTHER, 6)], {"emit": 10}),
}


@pytest.mark.parametrize("name", sorted(SCRIPTS))
def test_phases_partition_and_nest_as_self_time(clock, name):
    script, want = SCRIPTS[name]
    led = LoopLedger()
    start, stack = clock.now, []
    for op, phase, later in script:
        clock.now += later
        if op == "enter":
            led.enter(phase)
        elif op == "push":
            stack.append(led.enter(phase))
        else:
            led.resume(stack.pop())
    flat = led.flat()
    got = {p: flat[f"loop_{p}_ns"] for p in LOOP_PHASES
           if flat[f"loop_{p}_ns"]}
    assert got == want
    # every nanosecond belongs to exactly one phase
    assert flat["loop_ns"] == clock.now - start == sum(want.values())
    assert flat["loop_busy_ns"] == flat["loop_ns"] - flat["loop_idle_ns"]


def test_resume_counts_no_entry(clock):
    led = LoopLedger()
    outer = led.enter(ADMIT)
    inner = led.enter(DECODE_DISPATCH)
    led.resume(inner)
    led.resume(outer)
    assert (outer, inner) == (OTHER, ADMIT)
    assert led.n[ADMIT] == 1 and led.n[DECODE_DISPATCH] == 1
    assert led.n[OTHER] == 0 and led.cur == OTHER


def test_flat_keys_are_the_gauge_table():
    flat = LoopLedger().flat()
    assert list(flat) and set(flat) == {k for k, _ in LOOP_GAUGES}
    assert all(isinstance(v, int) for v in flat.values())
    for p in LOOP_PHASES:
        assert f"loop_{p}_ns" in flat and f"loop_{p}_n" in flat
        assert f"capture_loop_{p}_ns" in flat
    names = [n for _, n in (*ENGINE_GAUGES, *LOOP_GAUGES)]
    assert len(names) == len(set(names))  # no family rendered twice


@pytest.mark.parametrize("view,phases", [
    ("prefill_ms", ("prefill_dispatch", "prefill_block")),
    ("transfer_ms", ("window_fetch",)),
    ("emit_ms", ("emit",)),
])
def test_old_timers_are_views_of_the_ledger(view, phases):
    st = EngineStats()
    assert getattr(st, view) == 0.0
    for i, p in enumerate(phases):
        st.loop.ns[LOOP_PHASES.index(p)] = (i + 1) * 2_000_000
    assert getattr(st, view) == pytest.approx(
        sum(2.0 * (i + 1) for i in range(len(phases))))
    text = render_engine_gauges(st).decode()
    gauge = dict(ENGINE_GAUGES)[view]
    assert f"{gauge} {getattr(st, view)}" in text
    with pytest.raises(AttributeError):  # nobody stamps them any more
        setattr(st, view, 1.0)


def test_flag_down_constructs_nothing(clock, annotations):
    led = LoopLedger()
    for phase in (ADMIT, PREFILL_DISPATCH, PREFILL_BLOCK, DECODE_DISPATCH):
        clock.now += 5
        led.enter(phase, {"k": 2})
    led.instant("request/first_token", rid="r1")
    assert annotations.made == [] and led.captured["capture_ns"] == 0


class Counters:
    decode_steps = 0
    tokens_generated = 0
    prefill_tokens_real = 0
    prefill_tokens_padded = 0
    prefill_calls = 0


def one_capture(led, clock, st, windows: int) -> dict:
    """Flag up, ``windows`` decode windows of 4 steps with one prefill
    call, flag down; the counts the engine thread hands back."""
    led.capture_begin()
    for _ in range(windows):
        clock.now += 10
        led.enter(DECODE_DISPATCH, {"k": 4, "slots": 2})
        clock.now += 90
        led.enter(WINDOW_FETCH)
        st.decode_steps += 4
        st.tokens_generated += 8
    clock.now += 20
    led.enter(PREFILL_DISPATCH, {"bucket": 64, "tokens": 50})
    st.prefill_calls += 1
    st.prefill_tokens_real += 50
    st.prefill_tokens_padded += 64
    done: dict = {}
    t = threading.Thread(
        target=lambda: done.update(led.capture_end(timeout_s=5.0)))
    t.start()
    while led.capture != flight.CAPTURE_CLOSING:
        time.sleep(0.001)
    clock.now += 30
    led.enter(OTHER)  # the engine thread's next phase boundary
    t.join()
    return done


def test_capture_cuts_counters_at_phase_boundaries(clock, annotations):
    st = Counters()
    led = LoopLedger(st)
    st.decode_steps, st.tokens_generated = 400, 800  # before the capture
    clock.now += 1000
    led.enter(IDLE)
    first = one_capture(led, clock, st, windows=3)
    assert first["capture_decode_steps"] == 12
    assert first["capture_tokens_generated"] == 24
    assert first["capture_prefill_calls"] == 1
    assert first["capture_prefill_tokens_real"] == 50
    assert first["capture_prefill_tokens_padded"] == 64
    # cut at the first boundary after the flag went up (the first
    # window's dispatch) and the first after it went down
    assert first["capture_ns"] == 3 * 100 - 10 + 20 + 30
    assert sum(first[f"capture_loop_{p}_ns"] for p in LOOP_PHASES) \
        == first["capture_ns"]
    assert first["capture_loop_window_fetch_ns"] == 2 * 10 + 20
    assert first["capture_loop_decode_dispatch_ns"] == 3 * 90
    assert led.capture == flight.CAPTURE_OFF and annotations.open == 0
    # the spans it wrote: every phase entered while the flag was up,
    # under its name, with the facts the call site had
    assert annotations.made[0] == (
        "engine/decode_dispatch", {"k": 4, "slots": 2})
    assert ("engine/prefill_dispatch",
            {"bucket": 64, "tokens": 50}) in annotations.made
    assert {n for n, _ in annotations.made} == {
        "engine/decode_dispatch", "engine/window_fetch",
        "engine/prefill_dispatch"}
    # a second capture adds to the cumulative keys
    second = one_capture(led, clock, st, windows=2)
    flat = led.flat()
    for key in first:
        assert flat[key] == first[key] + second[key], key
    assert second["capture_decode_steps"] == 8
    assert set(first) == {"capture_ns",
                          *(f"capture_{c}" for c in CAPTURE_COUNTERS),
                          *(f"capture_loop_{p}_ns" for p in LOOP_PHASES)}


def test_capture_end_without_an_engine_thread_gives_up(clock, annotations):
    led = LoopLedger(Counters())
    led.capture_begin()
    assert led.capture_end(timeout_s=0.05) == {}


def test_request_marks_only_while_capturing(clock, annotations):
    led = LoopLedger(Counters())
    trace = RequestTrace(FlightEntry(rid="req-7"), loop=led)
    trace.admission(path="single")
    trace.first_token()
    trace.engine_finish("stop")
    assert annotations.made == []
    led.capture_begin()
    led.enter(ADMIT)
    trace.admission(path="single")
    trace.first_token()
    trace.engine_finish("stop")
    marks = [(n, f) for n, f in annotations.made if n.startswith("request/")]
    assert marks == [("request/admitted", {"rid": "req-7"}),
                     ("request/first_token", {"rid": "req-7"}),
                     ("request/finished", {"rid": "req-7"})]
    # a trace with no ledger (the gateway's own tests build such) is fine
    RequestTrace(FlightEntry(rid="x")).first_token()


# -- a CPU engine -------------------------------------------------------------

class _Stream:
    def __init__(self):
        self.toks: list[int] = []
        self.done = threading.Event()

    def emit(self, tok: int, fin: str | None) -> None:
        if tok >= 0:
            self.toks.append(tok)
        if fin is not None:
            self.done.set()


def _req(prompt, n, out):
    return GenRequest(prompt=prompt, max_tokens=n,
                      sampling=SamplingParams(temperature=0.0, seed=0),
                      emit=out.emit)


def _serve(eng, prompts) -> None:
    streams = [_Stream() for _ in prompts]
    for (prompt, n), s in zip(prompts, streams):
        eng.submit(_req(prompt, n, s))
    for s in streams:
        assert s.done.wait(timeout=600)


@pytest.fixture(scope="module")
def engine_run():
    """One engine's life: a chunked prompt with a short one beside it
    (decode ticks nest inside the prefill), a capture around a second
    pair, a third pair after it; what the ledger and the annotation
    stub saw."""
    stub = Stub()
    real = flight._trace_annotation
    flight._trace_annotation = stub
    eng = Engine(_PARAMS, _SPEC.config, EngineConfig(
        max_batch_size=2, max_seq_len=512, page_size=16,
        min_prefill_bucket=16, decode_steps_per_tick=4,
        adaptive_decode_window=False, prefill_chunk_tokens=32))
    out: dict = {"stub": stub}
    try:
        t0 = time.perf_counter_ns()
        eng.start()
        _serve(eng, [(list(range(1, 100)), 12), ([5, 6, 7], 24)])
        out["before"] = eng.stats.loop.flat()
        out["made_before"] = len(stub.made)
        eng.stats.loop.capture_begin()
        _serve(eng, [(list(range(200, 290)), 12), ([9, 8], 16)])
        out["capture"] = eng.stats.loop.capture_end(timeout_s=10.0)
        out["made_in_capture"] = list(stub.made)
        _serve(eng, [([3, 1, 4, 1, 5], 8), ([2, 7], 8)])
        out["made_after"] = len(stub.made)
        eng.stop()
        out["wall_ns"] = time.perf_counter_ns() - t0
        out["flat"] = eng.stats.loop.flat()
        out["stats"] = eng.stats
    finally:
        eng.stop()
        flight._trace_annotation = real
    return out


def test_engine_phases_partition_the_loop(engine_run):
    flat = engine_run["flat"]
    total = sum(flat[f"loop_{p}_ns"] for p in LOOP_PHASES)
    assert total == flat["loop_ns"]
    # ... and the ledger's clock is the wall's: start() to stop()
    assert flat["loop_ns"] == pytest.approx(engine_run["wall_ns"], rel=0.01)
    assert flat["loop_other_ns"] < 0.01 * flat["loop_ns"]


@pytest.mark.parametrize("phase", [
    "reap", "admit", "prefill_dispatch", "prefill_block", "state_build",
    "row_update", "decode_dispatch", "window_fetch", "emit", "idle"])
def test_engine_enters_every_phase_of_this_traffic(engine_run, phase):
    flat = engine_run["flat"]
    assert flat[f"loop_{phase}_n"] > 0 and flat[f"loop_{phase}_ns"] > 0


def test_engine_old_timers_read_what_they_read(engine_run):
    st, flat = engine_run["stats"], engine_run["flat"]
    assert st.prefill_ms == pytest.approx(
        (flat["loop_prefill_dispatch_ns"]
         + flat["loop_prefill_block_ns"]) / 1e6)
    assert st.transfer_ms == pytest.approx(flat["loop_window_fetch_ns"] / 1e6)
    assert st.emit_ms == pytest.approx(flat["loop_emit_ns"] / 1e6)
    assert st.prefill_ms > 0 and st.transfer_ms > 0 and st.emit_ms > 0
    assert st.first_emit_ms > 0
    # the picker's per-token prefill price still comes from the calls
    assert st.prefill_ms_per_token() > 0
    # six requests, one of them in four chunks of 32 (99 tokens), one
    # resuming behind a cached prefix: a call per program dispatched
    assert st.prefill_calls >= 6 + 3
    assert flat["prefill_calls"] == st.prefill_calls
    # one wait per prefill: six, or five when the last pair arrived
    # together and was prefilled as one batched call
    assert flat["loop_prefill_block_n"] in (5, 6)


def test_engine_capture_counts_whole_windows(engine_run):
    cap, flat = engine_run["capture"], engine_run["flat"]
    assert set(cap) == {k for k in flat if k.startswith("capture_")}
    for key in ("capture_ns", "capture_decode_steps",
                "capture_tokens_generated", "capture_prefill_tokens_real",
                "capture_prefill_tokens_padded", "capture_prefill_calls"):
        assert cap[key] > 0, key
        assert flat[key] == cap[key]  # one capture so far
    assert cap["capture_decode_steps"] % 4 == 0  # windows of 4 steps
    assert cap["capture_prefill_tokens_padded"] >= \
        cap["capture_prefill_tokens_real"]
    # two prompts: 90 tokens less a cached page or none, and 2
    assert 2 + 90 - 16 * 5 <= cap["capture_prefill_tokens_real"] <= 92
    assert sum(cap[f"capture_loop_{p}_ns"] for p in LOOP_PHASES) \
        == cap["capture_ns"]
    # the windows the loop dispatched inside the capture, by their
    # annotations' k, against the steps it counted: within one window
    ks = [f["k"] for n, f in engine_run["made_in_capture"]
          if n == "engine/decode_dispatch" and "k" in f]
    assert ks and set(ks) == {4}
    assert abs(sum(ks) - cap["capture_decode_steps"]) <= 4


def test_engine_annotates_only_inside_the_capture(engine_run):
    assert engine_run["made_before"] == 0
    made = engine_run["made_in_capture"]
    assert engine_run["made_after"] == len(made) > 0
    assert engine_run["stub"].open == 0
    names = {n for n, _ in made}
    assert names <= {f"engine/{p}" for p in LOOP_PHASES}
    assert {"engine/decode_dispatch", "engine/window_fetch", "engine/emit",
            "engine/prefill_dispatch", "engine/prefill_block",
            "engine/admit"} <= names
    facts = {n: f for n, f in made if f}
    assert set(facts["engine/window_fetch"]) == {"k", "slots"}
    assert set(facts["engine/prefill_dispatch"]) == {"tokens", "pages"}
    assert set(facts["engine/decode_dispatch"]) == {
        "k", "slots", "draft", "pages"}


def test_engine_ledger_only_grows(engine_run):
    before, after = engine_run["before"], engine_run["flat"]
    assert set(before) == set(after)
    for key in before:
        assert after[key] >= before[key], key


# -- /state, /metrics and /debug/profile -------------------------------------

@pytest.fixture(scope="module")
def served():
    holder: dict = {}
    started = threading.Event()

    def run():
        async def main():
            from aiohttp import web

            server = TPUServeServer(
                "tiny-random",
                EngineConfig(max_batch_size=2, max_seq_len=256,
                             page_size=16, min_prefill_bucket=16),
                enable_profile_endpoint=True)
            runner = web.AppRunner(server.app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            holder["port"] = site._server.sockets[0].getsockname()[1]
            holder["loop"] = asyncio.get_running_loop()
            holder["engine"] = server.engine
            started.set()
            await asyncio.Event().wait()

        try:
            asyncio.run(main())
        except RuntimeError:
            pass

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(timeout=120)
    yield f"http://127.0.0.1:{holder['port']}"
    holder["loop"].call_soon_threadsafe(holder["loop"].stop)
    # no engine thread may be inside JAX when the interpreter exits
    holder["engine"].stop()


async def _chat(http, url, text, n=6):
    async with http.post(url + "/v1/chat/completions", json={
            "model": "tiny-random", "max_tokens": n,
            "messages": [{"role": "user", "content": text}]}) as r:
        assert r.status == 200
        await r.read()


async def _state(http, url) -> dict:
    async with http.get(url + "/state") as r:
        return json.loads(await r.read())


def test_state_and_metrics_carry_the_ledger_and_only_grow(served):
    async def main():
        async with aiohttp.ClientSession() as http:
            await _chat(http, served, "what did the loop do")
            a = await _state(http, served)
            await _chat(http, served, "and what did it do next")
            b = await _state(http, served)
            async with http.get(served + "/metrics") as r:
                return a, b, (await r.read()).decode()

    a, b, text = asyncio.run(main())
    for key, family in LOOP_GAUGES:
        assert isinstance(a[key], int), key  # flat: a top-level number
        assert b[key] >= a[key], key
        assert f"\n{family} " in text, family
    assert b["loop_ns"] > a["loop_ns"]
    assert b["loop_decode_dispatch_n"] > a["loop_decode_dispatch_n"]
    assert b["loop_ns"] == sum(b[f"loop_{p}_ns"] for p in LOOP_PHASES)
    # the views keep their keys and their meaning
    assert b["prefill_ms"] == pytest.approx(
        (b["loop_prefill_dispatch_ns"] + b["loop_prefill_block_ns"]) / 1e6,
        abs=0.01)
    assert "phase_percentiles" in b


def test_profile_reply_carries_the_captures_counts(served):
    async def main():
        async with aiohttp.ClientSession() as http:
            # every program the capture's traffic runs is compiled
            # BEFORE it, by two requests of the same lengths (a prefix
            # miss, then a partial hit on the template's head): a
            # compile inside the capture holds the engine thread in one
            # phase for seconds under a loaded machine, longer than
            # capture_end waits for its next phase boundary — the reply
            # then carried no counts at all (the take-up run's failure)
            for i in (100, 101):
                await _chat(http, served, f"during the capture {i}", 12)
            before = await _state(http, served)

            captured = asyncio.Event()

            async def traffic():
                # requests back to back for as long as the capture runs,
                # however slowly a loaded machine starts the trace; all
                # of one length, no two alike (no full-prefix hit)
                i = 102
                while not captured.is_set():
                    await _chat(http, served, f"during the capture {i}", 12)
                    i += 1

            async def profile():
                try:
                    async with http.get(
                            served + "/debug/profile?seconds=1.5") as r:
                        assert r.status == 200, await r.text()
                        return await r.json()
                finally:
                    captured.set()

            reply, _ = await asyncio.gather(profile(), traffic())
            return before, reply, await _state(http, served)

    before, reply, after = asyncio.run(main())
    assert reply["seconds"] == 1.5 and reply["write_out_s"] >= 0
    assert reply["capture_ns"] >= 1.4e9
    assert reply["capture_decode_steps"] > 0
    assert reply["capture_prefill_calls"] >= 1
    for key, value in reply.items():
        if key.startswith("capture_"):
            assert after[key] - before[key] == value, key
