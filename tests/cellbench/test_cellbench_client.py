"""The load generator against a fake gateway: tokens are counted by
characters, every failure has its status, an open loop sends when due
and times from when due, sessions resend their history."""

import asyncio
import json
import time

import pytest
from aiohttp import web

from cellbench.client import Driver, lateness_ms
from cellbench.traffic import Schedule, Session, Turn


class FakeGateway:
    """Streams ``max_tokens`` 'a's in deltas of two after ``delay_s``;
    a prompt containing a marker misbehaves in that marker's way."""

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s
        self.bodies = []
        self.settled = 0

    async def chat(self, request):
        body = await request.json()
        self.bodies.append(body)
        text = body["messages"][-1]["content"]
        if "REFUSE" in text:
            return web.json_response({"error": "busy"}, status=429)
        resp = web.StreamResponse(
            headers={"content-type": "text/event-stream"})
        await resp.prepare(request)
        await asyncio.sleep(self.delay_s)
        n = body["max_tokens"] - (3 if "SHORT" in text else 0)
        sent = 0
        while sent < n:
            k = min(2, n - sent)
            sent += k
            ev = {"choices": [{"delta": {"content": "a" * k}}]}
            await resp.write(b"data: " + json.dumps(ev).encode() + b"\n\n")
            if "CUT" in text:
                return resp
        tail = {"choices": [{"delta": {}, "finish_reason": "length"}],
                "usage": {"prompt_tokens": 9, "completion_tokens": n}}
        await resp.write(b"data: " + json.dumps(tail).encode() + b"\n\n")
        await resp.write(b"data: [DONE]\n\n")
        # what a gateway does at end of stream (its usage ledger): a
        # client that hung up at [DONE] would make the next write fail
        await asyncio.sleep(0.05)
        await resp.write(b": settled\n\n")
        self.settled += 1
        return resp


async def with_gateway(fake, body):
    app = web.Application()
    app.router.add_post("/v1/chat/completions", fake.chat)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    try:
        async with Driver(f"http://127.0.0.1:{port}", "m") as drv:
            return await body(drv)
    finally:
        await runner.cleanup()


@pytest.mark.parametrize("marker,status,tokens", [
    ("", "ok", 7), ("SHORT", "short", 4), ("CUT", "cut", 2),
    ("REFUSE", "http_429", 0)])
def test_every_outcome_has_its_status(marker, status, tokens):
    async def body(drv):
        return await drv.send(
            [{"role": "user", "content": "hi " + marker}], 7,
            time.monotonic(), "window")

    res = asyncio.run(with_gateway(FakeGateway(), body))
    assert (res.status, res.tokens, res.ok) == (status, tokens,
                                                status == "ok")
    if status == "ok":
        assert res.usage["completion_tokens"] == 7
        assert [k for _, k in res.deltas] == [2, 2, 2, 1]


def test_client_reads_to_the_end_of_the_body_not_to_done():
    """The gateway settles its usage ledger after it has relayed
    ``[DONE]``: a client that closes there races it (seen on the chip as
    'Cannot write to closing transport' in the gateway's log)."""
    fake = FakeGateway()

    async def body(drv):
        return await drv.send([{"role": "user", "content": "x"}], 5,
                              time.monotonic(), "window")

    res = asyncio.run(with_gateway(fake, body))
    assert res.ok and fake.settled == 1


@pytest.mark.parametrize("marker,delay_s,at_first_token", [
    ("", 0.2, True), ("REFUSE", 0.0, False), ("CUT", 0.0, True)])
def test_on_first_is_set_at_the_first_token_or_at_the_end(
        marker, delay_s, at_first_token):
    """The tour sends a step's joiners after the first request's first
    token; a request that dies without one must not strand them."""
    async def body(drv):
        ev = asyncio.Event()
        task = asyncio.ensure_future(drv.send(
            [{"role": "user", "content": "hi " + marker}], 40,
            time.monotonic(), "tour", ev))
        await asyncio.wait_for(ev.wait(), 5)
        seen, running = len(drv.results[0].deltas), not task.done()
        await task
        return seen, running

    seen, running = asyncio.run(with_gateway(FakeGateway(delay_s), body))
    assert (seen >= 1) == at_first_token
    if not marker:
        assert running   # set while the request was still streaming


def test_request_pins_the_token_and_asks_for_usage():
    fake = FakeGateway()

    async def body(drv):
        await drv.send([{"role": "user", "content": "x"}], 3, 0.0, "lead")

    asyncio.run(with_gateway(fake, body))
    b = fake.bodies[0]
    assert b["logit_bias"] == {"97": 100} and b["stream"] is True
    assert b["stream_options"] == {"include_usage": True}
    assert b["temperature"] == 0.0 and b["model"] == "m"


def test_open_loop_times_from_due_and_reports_lateness():
    """A request due in the past (the generator stalled) is sent at
    once; its latency still counts from when it was due."""
    async def body(drv):
        drv.t0, drv.t1 = 0.0, time.monotonic() + 60
        due = time.monotonic() - 0.25
        await drv.run_session(Session(0.0, "", [Turn("late", 4)]), due)
        return drv.results[0]

    res = asyncio.run(with_gateway(FakeGateway(delay_s=0.05), body))
    assert res.ok and res.phase == "window"
    assert 250 <= lateness_ms([res])[0] < 400
    assert 1e3 * (res.first - res.due) >= 300      # 250 late + 50 served
    assert 1e3 * (res.first - res.sent) < 200


def test_open_loop_waits_until_due_on_a_fake_clock():
    """Due-time arithmetic with no real time: the driver sleeps exactly
    until each turn is due and stamps phases by due time."""
    now = [100.0]
    slept = []

    async def sleep(d):
        slept.append(round(d, 6))
        now[0] += d

    drv = Driver("http://unused", "m", clock=lambda: now[0], sleep=sleep)
    sent = []

    async def send(messages, max_tokens, due, phase):
        sent.append((round(due, 6), phase, len(messages)))
        now[0] += 0.5  # the reply takes half a second
        return type("R", (), {"ok": True, "tokens": max_tokens})()

    drv.send = send
    drv.t0, drv.t1 = 101.0, 104.0
    sess = Session(0.0, "sys", [Turn("a", 2), Turn("b", 2, think_s=1.0),
                                Turn("c", 2, think_s=1.0),
                                Turn("d", 2, think_s=1.0)])
    asyncio.run(drv.run_session(sess, 100.5))
    # due 100.5 (lead), then reply end + think: 102.0, 103.5; the
    # fourth would be due at 105.0, after the window, and is not sent
    assert sent == [(100.5, "lead", 2), (102.0, "window", 4),
                    (103.5, "window", 6)]
    assert slept == [0.5, 1.0, 1.0]


def test_sessions_resend_their_history():
    fake = FakeGateway()

    async def body(drv):
        drv.t0, drv.t1 = 0.0, time.monotonic() + 60
        await drv.run_session(
            Session(0.0, "SYS", [Turn("one", 3), Turn("two", 2, 0.01)]),
            time.monotonic())

    asyncio.run(with_gateway(fake, body))
    roles = [[m["role"] for m in b["messages"]] for b in fake.bodies]
    assert roles == [["system", "user"],
                     ["system", "user", "assistant", "user"]]
    assert fake.bodies[1]["messages"][2]["content"] == "aaa"


MIX = {"loop": "open", "rate_per_s": 20.0,
       "arrivals": {"process": "poisson", "zero_gap_share": 0.25},
       "prompt_tokens": {"dist": "fixed", "value": 12},
       "output_tokens": {"dist": "fixed", "value": 4},
       "sharing": {"kind": "none"},
       "lead_in": {"traffic_seconds": 0.5}}


@pytest.mark.parametrize("loop,extra,lead,window", [
    ("open", {}, 10, 20),
    ("closed", {"clients": 3}, None, None)])
def test_whole_schedule_runs_and_drains(loop, extra, lead, window):
    async def body(drv):
        sched = Schedule(dict(MIX, loop=loop, **extra), 2 ** 31 + 5, 1.0)
        await drv.start(sched, time.monotonic() + 0.05)
        assert await drv.drain(20.0) == 0
        return drv

    drv = asyncio.run(with_gateway(FakeGateway(delay_s=0.01), body))
    assert all(r.ok for r in drv.results)
    by = {p: sum(1 for r in drv.results if r.phase == p)
          for p in ("lead", "window", "after")}
    assert by["after"] == 0
    if loop == "open":
        assert (by["lead"], by["window"]) == (lead, window)
        assert max(lateness_ms(drv.results)) < 2000  # a loaded test box
    else:
        assert by["lead"] >= 3 and by["window"] >= 3
