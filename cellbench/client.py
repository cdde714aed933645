"""The load generator: one process, one event loop, every request a
streaming ``/v1/chat/completions`` at the GATEWAY.

Open loop: a session's first turn is sent when it is DUE, whatever the
system is doing, and every time is taken from when the request was due
— so a stalled generator or a queue shows as latency instead of hiding
it; how late each send really was is kept (``sent - due``). Closed
loop: ``clients`` callers each send their next request when the reply
to the last has ended.
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field

import aiohttp

from cellbench.traffic import Schedule, Session

#: every sampled token pinned to ASCII 'a' through the real sampling
#: path (chip_smoke.py's pin): greedy random-weight output is mostly
#: UTF-8 continuation bytes with no visible delta, and EOS can then
#: never be sampled — every reply has exactly max_tokens tokens, one
#: character each, so the client counts tokens by counting characters
PIN_TOKEN = {"97": 100}


@dataclass
class Result:
    due: float
    phase: str
    expected: int
    sent: float = 0.0
    status: str = "cut"
    #: (time, tokens) of every content delta
    deltas: list[tuple[float, int]] = field(default_factory=list)
    usage: dict | None = None

    @property
    def tokens(self) -> int:
        return sum(k for _, k in self.deltas)

    @property
    def first(self) -> float | None:
        return self.deltas[0][0] if self.deltas else None

    @property
    def last(self) -> float | None:
        return self.deltas[-1][0] if self.deltas else None

    @property
    def ok(self) -> bool:
        return self.status == "ok"


class Driver:
    def __init__(self, gateway: str, model: str, clock=time.monotonic,
                 sleep=asyncio.sleep) -> None:
        self.url = gateway + "/v1/chat/completions"
        self.model = model
        self.clock = clock
        self.sleep = sleep
        self.results: list[Result] = []
        self.t0 = 0.0   # window start (monotonic)
        self.t1 = 0.0   # window end
        self._http: aiohttp.ClientSession | None = None
        self._tasks: list[asyncio.Future] = []

    async def __aenter__(self) -> "Driver":
        self._http = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_read=900))
        return self

    async def __aexit__(self, *exc) -> None:
        await self._http.close()

    def phase_of(self, t: float) -> str:
        return "lead" if t < self.t0 else "window" if t < self.t1 else "after"

    async def send(self, messages: list[dict], max_tokens: int,
                   due: float, phase: str,
                   on_first: asyncio.Event | None = None) -> Result:
        """One streamed chat. Never raises: what went wrong is the
        result's ``status`` (``http_<code>``, ``cut``, ``short``).
        ``on_first`` is set at the first content token, or when the
        request ends without one."""
        try:
            return await self._send(messages, max_tokens, due, phase,
                                    on_first)
        finally:
            if on_first is not None:
                on_first.set()

    async def _send(self, messages, max_tokens, due, phase,
                    on_first) -> Result:
        res = Result(due=due, phase=phase, expected=max_tokens)
        self.results.append(res)
        body = {"model": self.model, "messages": messages,
                "max_tokens": max_tokens, "temperature": 0.0, "stream": True,
                "stream_options": {"include_usage": True},
                "logit_bias": PIN_TOKEN}
        res.sent = self.clock()
        done = False
        try:
            async with self._http.post(self.url, json=body) as resp:
                if resp.status != 200:
                    res.status = f"http_{resp.status}"
                    return res
                async for raw in resp.content:
                    line = raw.strip()
                    if not line.startswith(b"data: "):
                        continue
                    data = line[6:]
                    if data == b"[DONE]":
                        # read on to the end of the body: closing at
                        # [DONE] races the gateway's end-of-stream
                        # work (it settles the usage ledger there)
                        done = True
                        continue
                    ev = json.loads(data)
                    if ev.get("error"):
                        break
                    if ev.get("usage"):
                        res.usage = ev["usage"]
                    for ch in ev.get("choices") or []:
                        text = (ch.get("delta") or {}).get("content")
                        if text:
                            res.deltas.append((self.clock(), len(text)))
                            if on_first is not None:
                                on_first.set()
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError):
            return res
        if done:
            res.status = "ok" if res.tokens == max_tokens else "short"
        return res

    async def run_session(self, sess: Session, due: float) -> None:
        """All turns of one session; turn j+1 is due ``think_s`` after
        the last token of turn j and resends the whole history."""
        history: list[dict] = (
            [{"role": "system", "content": sess.system}]
            if sess.system else [])
        for j, turn in enumerate(sess.turns):
            if j:
                due = self.clock() + turn.think_s
            if due >= self.t1:
                return
            wait = due - self.clock()
            if wait > 0:
                await self.sleep(wait)
            history.append({"role": "user", "content": turn.content})
            res = await self.send(list(history), turn.max_tokens, due,
                                  self.phase_of(due))
            if not res.ok:
                return
            history.append({"role": "assistant",
                            "content": "a" * res.tokens})

    async def run_open(self, schedule: Schedule, start: float) -> None:
        """Lead-in segment from ``start``, the window right behind it."""
        self.t0 = start + schedule.lead_s
        self.t1 = self.t0 + schedule.seconds
        tasks = [asyncio.ensure_future(self.run_session(s, start + s.start_s))
                 for s in schedule.lead]
        tasks += [asyncio.ensure_future(
            self.run_session(s, self.t0 + s.start_s))
            for s in schedule.window]
        self._tasks = tasks

    async def run_closed(self, schedule: Schedule, start: float) -> None:
        self.t0 = start + schedule.lead_s
        self.t1 = self.t0 + schedule.seconds
        counter = iter(range(10 ** 9))

        async def client() -> None:
            while self.clock() < self.t1:
                sess = schedule.nth(next(counter))
                await self.run_session(sess, self.clock())

        self._tasks = [asyncio.ensure_future(client())
                       for _ in range(schedule.clients)]

    async def start(self, schedule: Schedule, start: float) -> None:
        await {"open": self.run_open, "closed": self.run_closed}[
            schedule.loop](schedule, start)

    async def drain(self, timeout_s: float) -> int:
        """Wait for every request in flight; returns how many tasks had
        to be cancelled (their requests stay ``cut``)."""
        done, pending = await asyncio.wait(self._tasks, timeout=timeout_s)
        for t in pending:
            t.cancel()
        if pending:
            await asyncio.wait(pending)
        for t in done:
            t.result()  # a bug in the driver must not pass silently
        return len(pending)


def lateness_ms(results: list[Result]) -> list[float]:
    return [1e3 * (r.sent - r.due) for r in results]
