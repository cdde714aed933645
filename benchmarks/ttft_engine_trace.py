"""Engine-side TTFT trace (round-5 VERDICT item #1).

Starts tpuserve in-process (so we can wrap Engine methods), drives one
batch-8 direct leg, and prints per-request: submit→first-emit latency,
plus every decode-window duration and every admit duration, to localize
the multi-second TTFT stalls seen in ttft_profile.py.

    JAX_PLATFORMS=cpu python benchmarks/ttft_engine_trace.py
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

BATCH = 8

EVENTS: list[tuple] = []
T0 = time.perf_counter()


def ts() -> float:
    return round(1e3 * (time.perf_counter() - T0), 1)


def patch_engine() -> None:
    from aigw_tpu.tpuserve.engine import Engine

    orig_submit = Engine.submit
    orig_admit = Engine._admit
    orig_tick = Engine._decode_tick

    def submit(self, req):
        t = ts()
        tag = req.prompt[:3]
        orig_emit = req.emit
        seen = [False]

        def emit(tok, fin):
            if not seen[0] and tok >= 0:
                seen[0] = True
                EVENTS.append(("first_emit", ts(), tag, t))
            return orig_emit(tok, fin)

        req.emit = emit
        EVENTS.append(("submit", t, tag))
        return orig_submit(self, req)

    def _admit(self):
        t = ts()
        r = orig_admit(self)
        if r:
            EVENTS.append(("admit", t, ts()))
        return r

    def _decode_tick(self):
        t = ts()
        r = orig_tick(self)
        d = ts() - t
        if d > 20:
            EVENTS.append(("tick", t, round(d, 1)))
        return r

    Engine.submit = submit
    Engine._admit = _admit
    Engine._decode_tick = _decode_tick


async def drive(url: str, model: str, batch: int, tag: str) -> list[dict]:
    import aiohttp

    rows: list[dict] = []

    async def one(s: aiohttp.ClientSession, i: int, t0: float) -> None:
        body = (tag + chr(65 + i % 26)) * 64
        payload = {
            "model": model,
            "messages": [{"role": "user", "content": body[:64]}],
            "max_tokens": 64,
            "temperature": 0.0,
            "stream": True,
        }
        t_start = time.perf_counter()
        t_first = None
        async with s.post(url + "/v1/chat/completions", json=payload) as resp:
            assert resp.status == 200
            async for raw in resp.content:
                line = raw.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[6:]
                if data == b"[DONE]":
                    break
                ev = json.loads(data)
                ch = ev.get("choices") or []
                if ch and (ch[0].get("delta") or {}).get("content"):
                    if t_first is None:
                        t_first = time.perf_counter()
        rows.append({
            "i": i,
            "sent_at_ms": round(1e3 * (t_start - T0), 1),
            "ttft_ms": round(1e3 * ((t_first or t_start) - t_start), 1),
        })

    timeout = aiohttp.ClientTimeout(total=600)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        await asyncio.gather(*(one(s, i, time.perf_counter())
                               for i in range(batch)))
    rows.sort(key=lambda r: r["i"])
    return rows


def main() -> None:
    patch_engine()
    import bench

    model_name = "bench-cpu-tiny"
    cfg = bench.CPU_CFG
    serve_url, stop_serve = bench._start_tpuserve(model_name, cfg, "", BATCH)

    async def run() -> None:
        await bench._wait_health(serve_url, 600)
        await drive(serve_url, model_name, BATCH, tag="w")
        EVENTS.append(("=== trial start ===", ts()))
        rows = await drive(serve_url, model_name, BATCH, tag="d0")
        print("client:", json.dumps(rows))

    try:
        asyncio.run(run())
    finally:
        stop_serve()
    print("--- engine events (trial window) ---")
    start = next(
        (e[1] for e in EVENTS if e[0].startswith("===")), 0)
    for e in EVENTS:
        if e[1] >= start - 5:
            print(e)


if __name__ == "__main__":
    main()
