"""TTFT localization harness (round-5 VERDICT item #1).

Reproduces the bench's CPU gateway leg with per-request timing splits to
localize the gateway-vs-direct TTFT gap: for every request we record

  t_conn    — POST write complete → response headers received
  t_first   — headers → first SSE content delta
  ttft      — request start → first content delta (what bench.py reports)

for the direct leg (client→tpuserve) and the gateway leg
(client→aigw→tpuserve), interleaved. Run under JAX_PLATFORMS=cpu.

    python benchmarks/ttft_profile.py
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

BATCH = 8
PROMPT_LEN = 64
GEN_TOKENS = 64


async def drive(url: str, model: str, batch: int, tag: str) -> list[dict]:
    import aiohttp

    rows: list[dict] = []

    async def one(s: aiohttp.ClientSession, i: int, t0: float) -> None:
        body = (tag + chr(65 + i % 26)) * PROMPT_LEN
        payload = {
            "model": model,
            "messages": [{"role": "user", "content": body[:PROMPT_LEN]}],
            "max_tokens": GEN_TOKENS,
            "temperature": 0.0,
            "stream": True,
        }
        t_start = time.perf_counter()
        async with s.post(url + "/v1/chat/completions", json=payload) as resp:
            t_headers = time.perf_counter()
            assert resp.status == 200
            t_first = None
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
                    t_first = time.perf_counter()
                    break
            # drain
            async for _ in resp.content:
                pass
        rows.append({
            "i": i,
            "start_off_ms": round(1e3 * (t_start - t0), 1),
            "t_conn_ms": round(1e3 * (t_headers - t_start), 1),
            "t_first_ms": round(1e3 * ((t_first or t_headers) - t_headers), 1),
            "ttft_ms": round(1e3 * ((t_first or t_headers) - t_start), 1),
        })

    timeout = aiohttp.ClientTimeout(total=600)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        t0 = time.perf_counter()
        await asyncio.gather(*(one(s, i, t0) for i in range(batch)))
    rows.sort(key=lambda r: r["i"])
    return rows


def main() -> None:
    import bench

    model_name = "bench-cpu-tiny"
    cfg = bench.CPU_CFG
    serve_url, stop_serve = bench._start_tpuserve(model_name, cfg, "", BATCH)
    gw_url, proc, cfg_path = bench._start_gateway(serve_url)

    async def run() -> None:
        await bench._wait_health(serve_url, 600)
        await bench._wait_health(gw_url, 120)
        # warm prefill bucket + gateway path
        await drive(serve_url, model_name, BATCH, tag="w")
        await drive(gw_url, model_name, BATCH, tag="x")
        for trial in range(2):
            d = await drive(serve_url, model_name, BATCH, tag=f"d{trial}")
            g = await drive(gw_url, model_name, BATCH, tag=f"g{trial}")
            med = lambda rows, k: sorted(r[k] for r in rows)[len(rows) // 2]
            print(f"--- trial {trial} ---")
            print("direct :", json.dumps(d))
            print("gateway:", json.dumps(g))
            print(json.dumps({
                "direct_ttft_p50": med(d, "ttft_ms"),
                "gateway_ttft_p50": med(g, "ttft_ms"),
                "direct_conn_p50": med(d, "t_conn_ms"),
                "gateway_conn_p50": med(g, "t_conn_ms"),
                "direct_first_p50": med(d, "t_first_ms"),
                "gateway_first_p50": med(g, "t_first_ms"),
            }))

    try:
        asyncio.run(run())
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except Exception:
            proc.kill()
        os.unlink(cfg_path)
        stop_serve()


if __name__ == "__main__":
    main()
