"""Serve-process TTFT trace: where do the seconds go between HTTP
arrival and first SSE content byte at batch 8 on the CPU backend?

Patches (in a child tpuserve process, via AIGW_TTFT_TRACE=path):
  - web-handler arrival        (aiohttp middleware)
  - engine submit              (Engine.submit wrap)
  - first engine emit          (emit wrap)
Client side records request start and first content delta.

    JAX_PLATFORMS=cpu python benchmarks/ttft_serve_trace.py
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

BATCH = 8
CFG = {
    "vocab_size": 8192, "dim": 512, "n_layers": 4, "n_heads": 8,
    "n_kv_heads": 4, "ffn_dim": 1536, "max_seq_len": 512,
    "rope_theta": 10000.0,
}


async def drive(url: str, batch: int, tag: str) -> list[dict]:
    import aiohttp

    rows: list[dict] = []

    async def one(s: aiohttp.ClientSession, i: int) -> None:
        body = (tag + chr(65 + i % 26)) * 64
        payload = {
            "model": "bench-cpu-tiny",
            "messages": [{"role": "user", "content": body[:64]}],
            "max_tokens": 64,
            "temperature": 0.0,
            "stream": True,
        }
        t_start = time.time()
        t_first = None
        async with s.post(url + "/v1/chat/completions", json=payload) as r:
            assert r.status == 200
            while True:
                line = await r.content.readline()
                if not line:
                    break
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[6:]
                if data == b"[DONE]":
                    break
                ev = json.loads(data)
                ch = ev.get("choices") or []
                if ch and (ch[0].get("delta") or {}).get("content"):
                    if t_first is None:
                        t_first = time.time()
        rows.append({"i": i, "start": t_start, "first": t_first})

    timeout = aiohttp.ClientTimeout(total=600)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        await asyncio.gather(*(one(s, i) for i in range(batch)))
    rows.sort(key=lambda r: r["i"])
    return rows


def main() -> None:
    import bench

    trace_path = "/tmp/aigw_ttft_trace.jsonl"
    if os.path.exists(trace_path):
        os.unlink(trace_path)
    spec = {"model": "bench-cpu-tiny", "cfg": CFG, "batch": BATCH,
            "page": 128, "k": 4, "quantize": ""}
    here = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.Popen(
        [sys.executable, os.path.join(here, "serve_child.py"),
         json.dumps(spec)],
        cwd=os.path.join(here, ".."), stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 AIGW_TTFT_TRACE=trace_path),
    )
    port = None
    while True:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError("child died")
        if line.startswith("SERVE_PORT="):
            port = int(line.split("=", 1)[1])
            break
    url = f"http://127.0.0.1:{port}"

    async def run() -> None:
        await bench._wait_health(url, 600)
        await drive(url, BATCH, tag="w")  # warm
        t_mark = time.time()
        rows = await drive(url, BATCH, tag="d0")
        print("t_mark", t_mark)
        for r in rows:
            print(json.dumps({
                "i": r["i"],
                "start_ms": round(1e3 * (r["start"] - t_mark), 1),
                "ttft_ms": round(1e3 * ((r["first"] or r["start"])
                                        - r["start"]), 1),
            }))

    try:
        asyncio.run(run())
    finally:
        proc.terminate()
        proc.wait(timeout=10)
    print("--- server trace ---")
    t_mark = None
    with open(trace_path) as f:
        evs = [json.loads(line) for line in f]
    # keep only the trial window (last 3*BATCH*3 events)
    for e in evs[-BATCH * 4:]:
        print(e)


if __name__ == "__main__":
    main()
