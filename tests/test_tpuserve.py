"""tpuserve engine + server tests on the CPU fake-chip (tiny-random model).

Mirrors the reference's data-plane tier: a real server process boundary,
no orchestration (SURVEY.md §4)."""

from __future__ import annotations

import asyncio
import json
import threading
import time

import aiohttp
import jax
import numpy as np
import pytest

from aigw_tpu.models import llama
from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
from aigw_tpu.tpuserve.kvcache import OutOfPagesError, PageAllocator
from aigw_tpu.tpuserve.sampling import SamplingParams
from aigw_tpu.tpuserve.server import TPUServeServer


class TestPageAllocator:
    def test_alloc_free_cycle(self):
        a = PageAllocator(num_pages=8, page_size=16)
        p1 = a.allocate(1, 40)  # 3 pages
        assert len(p1) == 3 and a.free_pages == 5
        p2 = a.allocate(2, 16)
        assert len(p2) == 1 and a.free_pages == 4
        assert set(p1).isdisjoint(p2)
        a.free(1)
        assert a.free_pages == 7
        a.free(2)
        assert a.free_pages == 8

    def test_extend(self):
        a = PageAllocator(num_pages=4, page_size=16)
        a.allocate(1, 10)
        assert a.extend(1, 20) != []  # second page
        assert a.extend(1, 25) == []  # still fits in 2 pages
        assert len(a.pages(1)) == 2

    def test_exhaustion(self):
        a = PageAllocator(num_pages=2, page_size=16)
        a.allocate(1, 32)
        with pytest.raises(OutOfPagesError):
            a.allocate(2, 1)
        assert not a.can_allocate(1)
        assert a.occupancy == 1.0


@pytest.fixture(scope="module")
def engine():
    cfg = EngineConfig(max_batch_size=4, max_seq_len=256, page_size=16,
                       min_prefill_bucket=32)
    params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
    eng = Engine(params, llama.TINY, cfg, eos_token_ids=(257,))
    eng.start()
    yield eng
    eng.stop()


def collect(engine, prompt, max_tokens=8, **sp):
    done = threading.Event()
    toks: list[int] = []
    finish: list[str] = []

    def emit(tok, fin):
        if tok >= 0:
            toks.append(tok)
        if fin is not None:
            finish.append(fin)
            done.set()

    engine.submit(
        GenRequest(prompt=prompt, max_tokens=max_tokens,
                   sampling=SamplingParams(**sp), emit=emit)
    )
    assert done.wait(timeout=120), "generation timed out"
    return toks, finish[0]


class TestEngine:
    def test_greedy_generation(self, engine):
        toks, finish = collect(engine, [1, 2, 3], max_tokens=6,
                               temperature=0.0)
        assert finish in ("stop", "length")
        if finish == "length":
            assert len(toks) == 6
        assert all(0 <= t < llama.TINY.vocab_size for t in toks)

    def test_greedy_is_deterministic(self, engine):
        a, _ = collect(engine, [5, 6, 7], max_tokens=5, temperature=0.0)
        b, _ = collect(engine, [5, 6, 7], max_tokens=5, temperature=0.0)
        assert a == b

    def test_seeded_sampling_deterministic(self, engine):
        a, _ = collect(engine, [9, 9], max_tokens=5, temperature=0.8, seed=42)
        b, _ = collect(engine, [9, 9], max_tokens=5, temperature=0.8, seed=42)
        assert a == b

    def test_concurrent_requests_isolated(self, engine):
        """Continuous batching: concurrent generations must match their
        solo-run outputs exactly (KV pages don't leak across slots)."""
        solo1, _ = collect(engine, [10, 20, 30], max_tokens=5, temperature=0.0)
        solo2, _ = collect(engine, [40, 50, 60], max_tokens=5, temperature=0.0)

        results: dict[int, list[int]] = {0: [], 1: []}
        dones = [threading.Event(), threading.Event()]

        def mk_emit(i):
            def emit(tok, fin):
                if tok >= 0:
                    results[i].append(tok)
                if fin is not None:
                    dones[i].set()
            return emit

        engine.submit(GenRequest(prompt=[10, 20, 30], max_tokens=5,
                                 sampling=SamplingParams(temperature=0.0),
                                 emit=mk_emit(0)))
        engine.submit(GenRequest(prompt=[40, 50, 60], max_tokens=5,
                                 sampling=SamplingParams(temperature=0.0),
                                 emit=mk_emit(1)))
        assert all(d.wait(timeout=120) for d in dones)
        assert results[0] == solo1
        assert results[1] == solo2

    def test_too_long_rejected(self, engine):
        with pytest.raises(ValueError, match="max_seq_len"):
            engine.submit(GenRequest(prompt=[1] * 300, max_tokens=10,
                                     sampling=SamplingParams()))

    def test_queueing_over_capacity(self, engine):
        """More requests than slots: all must finish via the queue."""
        n = 9  # > max_batch_size
        dones = [threading.Event() for _ in range(n)]

        def mk(i):
            def emit(tok, fin):
                if fin is not None:
                    dones[i].set()
            return emit

        for i in range(n):
            engine.submit(GenRequest(prompt=[i + 1, i + 2], max_tokens=3,
                                     sampling=SamplingParams(temperature=0.0),
                                     emit=mk(i)))
        assert all(d.wait(timeout=240) for d in dones)
        # the engine thread frees pages just after signalling completion;
        # poll briefly instead of racing its stats refresh
        deadline = time.monotonic() + 5
        while engine.allocator.occupancy > 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert engine.allocator.occupancy == 0.0  # everything freed


@pytest.fixture(scope="module")
def tpuserve_url():
    """Run a real tpuserve server (tiny-random) in a thread."""
    from aiohttp import web

    holder = {}
    started = threading.Event()

    def run():
        async def main():
            server = TPUServeServer(
                "tiny-random",
                EngineConfig(max_batch_size=2, max_seq_len=256, page_size=16,
                             min_prefill_bucket=32),
            )
            runner = web.AppRunner(server.app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            holder["port"] = site._server.sockets[0].getsockname()[1]
            holder["loop"] = asyncio.get_running_loop()
            started.set()
            await asyncio.Event().wait()

        try:
            asyncio.run(main())
        except RuntimeError:
            pass

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(timeout=60)
    yield f"http://127.0.0.1:{holder['port']}"
    holder["loop"].call_soon_threadsafe(holder["loop"].stop)


#: client budget for every HTTP call in this module. aiohttp's default
#: ClientTimeout is total=300s — under a loaded full-suite 1-core batch
#: a module fixture's FIRST request (fresh engine + warmup compiles
#: competing for the core) can legitimately exceed that, which showed
#: up as 2 TestLogprobs timeouts in PR 10's 18-minute tier-1 run while
#: the same tests pass 8/8 in isolation. The server is local and the
#: suite has its own timeout; a generous client budget cannot hang CI,
#: it only stops load-dependent flakes.
_CLIENT_TIMEOUT = aiohttp.ClientTimeout(total=900)


async def _post(url, path, payload):
    async with aiohttp.ClientSession(timeout=_CLIENT_TIMEOUT) as s:
        async with s.post(url + path, json=payload) as resp:
            return resp.status, await resp.read(), dict(resp.headers)


class TestTPUServeServer:
    def test_chat_completion(self, tpuserve_url):
        status, body, _ = asyncio.run(
            _post(tpuserve_url, "/v1/chat/completions", {
                "model": "tiny-random",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4,
                "temperature": 0,
            })
        )
        assert status == 200
        got = json.loads(body)
        assert got["object"] == "chat.completion"
        assert got["usage"]["completion_tokens"] >= 1
        assert got["model"] == "tiny-random"

    def test_chat_streaming(self, tpuserve_url):
        async def main():
            async with aiohttp.ClientSession(timeout=_CLIENT_TIMEOUT) as s:
                async with s.post(
                    tpuserve_url + "/v1/chat/completions",
                    json={
                        "model": "tiny-random",
                        "messages": [{"role": "user", "content": "hi"}],
                        "max_tokens": 4, "temperature": 0, "stream": True,
                        "stream_options": {"include_usage": True},
                    },
                ) as resp:
                    assert resp.status == 200
                    assert "text/event-stream" in resp.headers["content-type"]
                    return await resp.read()

        raw = asyncio.run(main()).decode()
        assert "[DONE]" in raw
        chunks = [json.loads(x[len("data: "):]) for x in raw.split("\n\n")
                  if x.startswith("data: ") and "[DONE]" not in x]
        finishes = [c["choices"][0]["finish_reason"] for c in chunks
                    if c.get("choices")]
        assert finishes[-1] in ("stop", "length")
        assert any(c.get("usage") for c in chunks)

    def test_embeddings(self, tpuserve_url):
        status, body, _ = asyncio.run(
            _post(tpuserve_url, "/v1/embeddings",
                  {"model": "tiny-random", "input": ["alpha", "beta"]})
        )
        assert status == 200
        got = json.loads(body)
        assert len(got["data"]) == 2
        assert len(got["data"][0]["embedding"]) == llama.TINY.dim
        # embeddings differ for different inputs
        assert got["data"][0]["embedding"] != got["data"][1]["embedding"]

    def test_tokenize(self, tpuserve_url):
        status, body, _ = asyncio.run(
            _post(tpuserve_url, "/tokenize",
                  {"model": "tiny-random", "prompt": "hello"})
        )
        got = json.loads(body)
        assert status == 200 and got["count"] == 5

    def test_metrics_engine_gauges(self, tpuserve_url):
        async def main():
            async with aiohttp.ClientSession(timeout=_CLIENT_TIMEOUT) as s:
                async with s.get(tpuserve_url + "/metrics") as resp:
                    return await resp.text()

        text = asyncio.run(main())
        assert "tpuserve_kv_occupancy" in text
        assert "tpuserve_prefix_cache_hits_total" in text
        assert "gen_ai_server_request_duration_seconds" in text

    def test_state_telemetry(self, tpuserve_url):
        async def main():
            async with aiohttp.ClientSession(timeout=_CLIENT_TIMEOUT) as s:
                async with s.get(tpuserve_url + "/state") as resp:
                    return await resp.json()

        got = asyncio.run(main())
        assert got["max_slots"] == 2
        assert "kv_occupancy" in got and "queued" in got
        # first-token fast-path phase + ICI topology for the picker
        assert "first_emit_ms" in got
        assert "slice" in got and "device_coords" in got


class TestEngineNumerics:
    def test_engine_matches_full_recompute(self, engine):
        """Greedy engine output must equal token-by-token full-context
        recompute through prefill — the strongest end-to-end numerics
        check for the paged-cache decode path."""
        import jax.numpy as jnp

        prompt = [3, 1, 4, 1, 5]
        got, _ = collect(engine, prompt, max_tokens=4, temperature=0.0)

        seq = list(prompt)
        expected = []
        for _ in range(4):
            cache = jnp.zeros(
                (llama.TINY.n_layers, 2, 64 * 16, llama.TINY.n_kv_heads,
                 llama.TINY.head_dim), jnp.bfloat16)
            pt = jnp.arange(8, dtype=jnp.int32)[None, :]
            logits, _ = llama.prefill(
                engine.params, llama.TINY,
                jnp.asarray([seq], jnp.int32),
                jnp.asarray([len(seq)], jnp.int32), cache, pt, 16,
            )
            tok = int(np.asarray(logits[0]).argmax())
            expected.append(tok)
            seq.append(tok)
        assert got == expected


class TestServerRobustness:
    """Regression tests for review findings (nulls, stops, unicode)."""

    def test_null_sampling_params(self, tpuserve_url):
        status, body, _ = asyncio.run(
            _post(tpuserve_url, "/v1/chat/completions", {
                "model": "tiny-random",
                "messages": [{"role": "user", "content": "x"}],
                "max_tokens": 2, "temperature": None, "top_p": None,
                "seed": None,
            })
        )
        assert status == 200

    def test_embeddings_token_ids(self, tpuserve_url):
        status, body, _ = asyncio.run(
            _post(tpuserve_url, "/v1/embeddings",
                  {"model": "tiny-random", "input": [1, 2, 3]})
        )
        assert status == 200
        got = json.loads(body)
        assert len(got["data"]) == 1

    def test_invalid_config_rejected(self):
        with pytest.raises(ValueError, match="multiple"):
            EngineConfig(max_seq_len=1000, page_size=128)

    def test_streaming_decoder_multibyte(self):
        from aigw_tpu.tpuserve.tokenizer import ByteTokenizer, StreamingDecoder

        d = StreamingDecoder(ByteTokenizer())
        emoji = "héllo 🌍".encode("utf-8")
        out = "".join(d.push(b) for b in emoji) + d.flush()
        assert out == "héllo 🌍"

    def test_streaming_decoder_invalid_byte_passes_through(self):
        from aigw_tpu.tpuserve.tokenizer import ByteTokenizer, StreamingDecoder

        d = StreamingDecoder(ByteTokenizer())
        seq = list("ab".encode()) + [0xFF] + list("cd".encode())
        out = "".join(d.push(b) for b in seq) + d.flush()
        assert out == "ab�cd"

    def test_streaming_decoder_is_windowed(self):
        """Per-token decode cost must not grow with stream length (the
        decoder re-decodes a small lagging window, not the full list)."""
        from aigw_tpu.tpuserve.tokenizer import ByteTokenizer, StreamingDecoder

        class Counting(ByteTokenizer):
            max_window = 0

            def decode(self, ids):
                Counting.max_window = max(Counting.max_window, len(ids))
                return super().decode(ids)

        d = StreamingDecoder(Counting())
        for b in ("x" * 5000).encode():
            d.push(b)
        d.flush()
        assert Counting.max_window < 16, Counting.max_window

    def test_streaming_decoder_fffd_run_neither_stalls_nor_grows(self):
        """A stream of invalid bytes (every decode ends in U+FFFD) must
        keep emitting progressively and keep the window bounded."""
        from aigw_tpu.tpuserve.tokenizer import ByteTokenizer, StreamingDecoder

        class Counting(ByteTokenizer):
            max_window = 0

            def decode(self, ids):
                Counting.max_window = max(Counting.max_window, len(ids))
                return super().decode(ids)

        d = StreamingDecoder(Counting())
        out = "".join(d.push(0x80) for _ in range(1000))
        assert len(out) >= 900  # emitted during the stream, not at flush
        out += d.flush()
        assert out == "�" * 1000
        assert Counting.max_window < 40, Counting.max_window


class TestPrefixCache:
    """Automatic prefix caching: shared prompt prefixes skip recompute and
    never corrupt isolation."""

    def make_engine(self):
        cfg = EngineConfig(max_batch_size=2, max_seq_len=256, page_size=16,
                           min_prefill_bucket=16, decode_steps_per_tick=4)
        params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
        eng = Engine(params, llama.TINY, cfg, eos_token_ids=(257,))
        eng.start()
        return eng

    def test_hit_reuses_pages_and_matches_uncached(self):
        eng = self.make_engine()
        try:
            shared = list(range(1, 40))  # 39 tokens → 2 full pages cached
            a, _ = collect(eng, shared + [100], max_tokens=4,
                           temperature=0.0)
            assert eng.stats.prefix_cache_hits == 0
            b, _ = collect(eng, shared + [100], max_tokens=4,
                           temperature=0.0)
            assert eng.stats.prefix_cache_hits == 1
            assert eng.stats.prefix_tokens_reused == 32  # 2 pages × 16
            assert a == b  # identical generation with and without cache

            # diverging continuation after the same prefix also matches a
            # cold run
            c, _ = collect(eng, shared + [200, 201], max_tokens=4,
                           temperature=0.0)
            assert eng.stats.prefix_cache_hits == 2
        finally:
            eng.stop()

    def test_full_hit_cow_isolation(self):
        """Page-aligned identical prompts: the repeat is a FULL hit —
        every page adopted, final page CoW'd, single-token resume. The
        CoW clone must isolate the writer: a THIRD identical request
        still full-hits the untouched shared pages and matches."""
        eng = self.make_engine()
        try:
            prompt = [(11 * i + 5) % 250 + 1 for i in range(64)]  # 4 pages
            a, _ = collect(eng, prompt, max_tokens=4, temperature=0.0)
            b, _ = collect(eng, prompt, max_tokens=4, temperature=0.0)
            c, _ = collect(eng, prompt, max_tokens=4, temperature=0.0)
            assert a == b == c
            assert eng.stats.prefix_full_hits == 2
            assert eng.stats.prefix_cow_copies == 2
            # full hits resume at n-1: 63 tokens reused each, never a
            # whole-prompt prefill
            assert eng.stats.prefix_tokens_reused == 126
        finally:
            eng.stop()

    def test_no_false_hits(self):
        eng = self.make_engine()
        try:
            collect(eng, [1] * 33, max_tokens=2, temperature=0.0)
            # different first page → no hit
            collect(eng, [2] * 33, max_tokens=2, temperature=0.0)
            assert eng.stats.prefix_cache_hits == 0
        finally:
            eng.stop()

    def test_eviction_under_pressure(self):
        """Cached-but-unreferenced pages are reclaimed when fresh requests
        need the pool."""
        cfg = EngineConfig(max_batch_size=2, max_seq_len=64, page_size=16,
                           num_pages=8, min_prefill_bucket=16,
                           decode_steps_per_tick=2)
        params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
        eng = Engine(params, llama.TINY, cfg, eos_token_ids=(257,))
        eng.start()
        try:
            # each request occupies 3 pages (33+max_tokens≤48 → 3 pages)
            for base in range(4):
                prompt = [10 + base] * 33
                collect(eng, prompt, max_tokens=2, temperature=0.0)
            # pool has 8 pages but 4×2 cached pages would exceed it —
            # eviction must have kept allocation working (we got here)
            assert eng.allocator.available_pages > 0
        finally:
            eng.stop()


class TestChatTemplates:
    def test_chatml_template(self):
        from aigw_tpu.tpuserve.tokenizer import (
            HFTokenizer, apply_chat_template,
        )

        class FakeHF:
            bos_id, eos_id = 0, 1

            def encode(self, text):
                self.last = text
                return [1, 2]

            def decode(self, ids):
                return ""

        tok = FakeHF()
        apply_chat_template(
            [{"role": "system", "content": "s"},
             {"role": "user", "content": "u"}], tok, "chatml")
        assert tok.last == (
            "<|im_start|>system\ns<|im_end|>\n"
            "<|im_start|>user\nu<|im_end|>\n<|im_start|>assistant\n")


class TestStopSequences:
    def test_stop_string_truncates(self, tpuserve_url):
        """The OpenAI `stop` parameter cuts generation at the sequence and
        reports finish_reason=stop (reference: vLLM-compatible serving)."""

        async def main():
            async with aiohttp.ClientSession(timeout=_CLIENT_TIMEOUT) as s:
                # run once unconstrained to learn the greedy continuation
                async with s.post(tpuserve_url + "/v1/chat/completions",
                                  json={"model": "tiny-random",
                                        "messages": [{"role": "user",
                                                      "content": "q"}],
                                        "max_tokens": 8,
                                        "temperature": 0}) as resp:
                    base = (await resp.json())["choices"][0]["message"][
                        "content"]
                if len(base) < 2:
                    return  # degenerate tiny-random output; nothing to cut
                stop = base[1]  # second character of the greedy output
                async with s.post(tpuserve_url + "/v1/chat/completions",
                                  json={"model": "tiny-random",
                                        "messages": [{"role": "user",
                                                      "content": "q"}],
                                        "max_tokens": 8, "temperature": 0,
                                        "stop": [stop]}) as resp:
                    got = await resp.json()
                text = got["choices"][0]["message"]["content"]
                assert stop not in text
                assert got["choices"][0]["finish_reason"] == "stop"
                assert len(text) < len(base)

        asyncio.run(main())


class TestNChoices:
    def test_n_choices(self, tpuserve_url):
        status, body, _ = asyncio.run(
            _post(tpuserve_url, "/v1/chat/completions", {
                "model": "tiny-random",
                "messages": [{"role": "user", "content": "pick"}],
                "max_tokens": 4, "n": 2, "temperature": 0.9, "seed": 7,
            })
        )
        assert status == 200
        got = json.loads(body)
        assert [c["index"] for c in got["choices"]] == [0, 1]
        assert got["usage"]["completion_tokens"] >= 2

    def test_n_too_large_rejected(self, tpuserve_url):
        status, body, _ = asyncio.run(
            _post(tpuserve_url, "/v1/chat/completions", {
                "model": "tiny-random",
                "messages": [{"role": "user", "content": "x"}],
                "n": 99,
            })
        )
        assert status == 400

    def test_n_streaming_interleaves_choices(self, tpuserve_url):
        """n>1 + stream (r5: OpenAI parity, previously 400): choices
        stream interleaved with per-chunk indexes; each index gets its
        own finish chunk; reassembled texts match the non-streaming
        n>1 response (greedy, fixed seeds)."""
        async def main():
            payload = {
                "model": "tiny-random",
                "messages": [{"role": "user", "content": "count"}],
                "max_tokens": 6, "temperature": 0.0, "n": 2,
                "stream": True,
                "stream_options": {"include_usage": True},
            }
            async with aiohttp.ClientSession(timeout=_CLIENT_TIMEOUT) as s:
                async with s.post(
                    tpuserve_url + "/v1/chat/completions", json=payload,
                ) as resp:
                    assert resp.status == 200
                    raw = (await resp.read()).decode()
            chunks = [json.loads(x[len("data: "):])
                      for x in raw.split("\n\n")
                      if x.startswith("data: ") and "[DONE]" not in x]
            texts = {0: "", 1: ""}
            finishes = {}
            for c in chunks:
                for ch in c.get("choices", []):
                    i = ch["index"]
                    texts[i] += (ch.get("delta") or {}).get(
                        "content") or ""
                    if ch.get("finish_reason"):
                        finishes[i] = ch["finish_reason"]
            assert set(finishes) == {0, 1}
            assert any(c.get("usage") for c in chunks)
            # parity with the non-streaming n>1 path
            status, body, _ = await _post(
                tpuserve_url, "/v1/chat/completions",
                dict(payload, stream=False, stream_options=None))
            assert status == 200
            solid = json.loads(body)
            for ch in solid["choices"]:
                assert texts[ch["index"]] == ch["message"]["content"]

        asyncio.run(main())


def test_stop_finishes_pending_requests():
    """Engine shutdown must error out queued work, not strand consumers."""
    cfg = EngineConfig(max_batch_size=1, max_seq_len=128, page_size=16,
                       min_prefill_bucket=16, decode_steps_per_tick=2)
    params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
    eng = Engine(params, llama.TINY, cfg)
    eng.start()
    fins = []
    done = threading.Event()

    def emit(tok, fin):
        if fin is not None:
            fins.append(fin)
            done.set()

    # long generation + immediate stop: the request must still resolve
    eng.submit(GenRequest(prompt=[1, 2], max_tokens=64,
                          sampling=SamplingParams(temperature=0.0),
                          emit=emit))
    eng.stop()
    assert done.wait(timeout=30)
    assert fins and fins[0] in ("error", "length", "stop")


@pytest.mark.slow


def test_batched_prefill_matches_sequential():
    """A burst of simple prompts admits through ONE batched prefill
    (r5: [G, S] device call instead of a G-step prefill ladder). The
    batched path must be invisible in outputs: each request's tokens
    equal its solo run, across different prompt lengths (two padded
    buckets → two groups) and sampling configs."""
    cfg = EngineConfig(max_batch_size=4, max_seq_len=256, page_size=16,
                       min_prefill_bucket=16, decode_steps_per_tick=4)
    params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
    eng = Engine(params, llama.TINY, cfg, eos_token_ids=(257,))
    eng.start()
    try:
        prompts = [
            ([11, 12, 13], dict(temperature=0.0)),
            ([21, 22, 23, 24, 25], dict(temperature=0.0)),
            ([31] * 20, dict(temperature=0.0)),  # second bucket (32)
            ([41, 42], dict(temperature=0.9, seed=7)),
        ]
        solos = [collect(eng, p, max_tokens=6, **sp) for p, sp in prompts]

        results: dict[int, list[int]] = {i: [] for i in range(len(prompts))}
        dones = [threading.Event() for _ in prompts]

        def mk(i):
            def emit(tok, fin):
                if tok >= 0:
                    results[i].append(tok)
                if fin is not None:
                    dones[i].set()
            return emit

        before = eng.stats.prefills
        for i, (p, sp) in enumerate(prompts):
            eng.submit(GenRequest(prompt=p, max_tokens=6,
                                  sampling=SamplingParams(**sp),
                                  emit=mk(i)))
        assert all(d.wait(timeout=120) for d in dones)
        assert eng.stats.prefills == before + len(prompts)
        for i, (toks, _fin) in enumerate(solos):
            assert results[i] == toks, f"request {i} diverged"
    finally:
        eng.stop()


@pytest.mark.slow
def test_page_pressure_mid_batch_requeues_everything():
    """When the batched-prefill allocation hits page pressure, every
    request already popped from the queue — the unallocated simple tail
    AND the non-simple ones headed for the per-request path — must be
    requeued, not dropped (r5 review finding: the non-simple `rest` was
    silently lost, hanging its client forever)."""
    cfg = EngineConfig(max_batch_size=4, max_seq_len=64, page_size=16,
                       num_pages=4, min_prefill_bucket=16,
                       decode_steps_per_tick=4, prefill_chunk_tokens=8,
                       enable_prefix_cache=False)
    params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
    eng = Engine(params, llama.TINY, cfg)
    eng.start()
    try:
        dones = [threading.Event() for _ in range(3)]

        def mk(i):
            def emit(tok, fin):
                if fin is not None:
                    dones[i].set()
            return emit

        # A: simple, 3 pages; B: simple, 3 pages (fails after A on the
        # 4-page pool); C: chunked (prompt > prefill_chunk_tokens)
        eng.submit(GenRequest(prompt=[1] * 4, max_tokens=40,
                              sampling=SamplingParams(temperature=0.0),
                              emit=mk(0)))
        eng.submit(GenRequest(prompt=[2] * 4, max_tokens=40,
                              sampling=SamplingParams(temperature=0.0),
                              emit=mk(1)))
        eng.submit(GenRequest(prompt=[3] * 12, max_tokens=8,
                              sampling=SamplingParams(temperature=0.0),
                              emit=mk(2)))
        for i, d in enumerate(dones):
            assert d.wait(timeout=120), f"request {i} never finished"
    finally:
        eng.stop()


@pytest.mark.slow
def test_same_burst_shared_prefix_adopts_not_duplicates():
    """Two same-prompt requests arriving in one burst must still share
    prompt pages: the second is routed through the per-request path and
    adopts the pages the batched prefill inserts in the same admission
    pass (r5 review finding: batching all of them would prefill the
    shared prefix redundantly with per-request page copies)."""
    cfg = EngineConfig(max_batch_size=4, max_seq_len=256, page_size=16,
                       min_prefill_bucket=16, decode_steps_per_tick=4)
    params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
    eng = Engine(params, llama.TINY, cfg, eos_token_ids=(257,))
    eng.start()
    try:
        shared = list(range(10, 50))  # 40 tokens = 2 full pages; fresh
        hits_before = eng.stats.prefix_cache_hits

        results: dict[int, list[int]] = {0: [], 1: [], 2: []}
        dones = [threading.Event() for _ in range(3)]

        def mk(i):
            def emit(tok, fin):
                if tok >= 0:
                    results[i].append(tok)
                if fin is not None:
                    dones[i].set()
            return emit

        prompts = [shared, [7] * 8, shared]
        for i, p in enumerate(prompts):
            eng.submit(GenRequest(prompt=p, max_tokens=5,
                                  sampling=SamplingParams(temperature=0.0),
                                  emit=mk(i)))
        assert all(d.wait(timeout=120) for d in dones)
        # the duplicate adopted the pages its batch-mate inserted in the
        # SAME admission pass, rather than re-prefilling its own copies
        assert eng.stats.prefix_cache_hits > hits_before
        assert results[0] == results[2]
        solo, _ = collect(eng, shared, max_tokens=5, temperature=0.0)
        assert results[0] == solo
    finally:
        eng.stop()


def test_no_zombie_window_after_batch_finishes():
    """When every active slot reaches its token limit within the
    in-flight decode window, the engine must not dispatch another
    window: the extra window is K junk steps that delay the next
    admission by a full window (r5 TTFT fix). max_tokens=9 with K=4
    needs exactly 2 windows after the prefill token — the old pipeline
    dispatched (and later drained) a third. Fixed window: the adaptive
    ladder intentionally spends extra small windows early (1+1+4+4),
    which is not what this test accounts for."""
    cfg = EngineConfig(max_batch_size=2, max_seq_len=128, page_size=16,
                       min_prefill_bucket=16, decode_steps_per_tick=4,
                       adaptive_decode_window=False)
    params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
    eng = Engine(params, llama.TINY, cfg)
    eng.start()
    try:
        done = threading.Event()
        fins = []

        def emit(tok, fin):
            if fin is not None:
                fins.append(fin)
                done.set()

        eng.submit(GenRequest(prompt=[3, 1, 4], max_tokens=9,
                              sampling=SamplingParams(temperature=0.0),
                              emit=emit))
        assert done.wait(timeout=120)
        # let the loop settle (any zombie window would be drained and
        # counted here)
        deadline = time.time() + 10
        while eng.stats.active_slots and time.time() < deadline:
            time.sleep(0.05)
        time.sleep(0.5)
        # 9 tokens = 1 (prefill) + 8 decode = exactly 2 windows of 4;
        # a third (zombie) window would show up as 12
        assert eng.stats.decode_steps <= 8, eng.stats.decode_steps
        if fins and fins[0] == "length":
            assert eng.stats.decode_steps == 8
    finally:
        eng.stop()


def test_queue_overload_raises():
    from aigw_tpu.tpuserve.engine import EngineOverloadedError

    cfg = EngineConfig(max_batch_size=1, max_seq_len=64, page_size=16,
                       min_prefill_bucket=16, decode_steps_per_tick=2,
                       max_queued_requests=2)
    params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
    eng = Engine(params, llama.TINY, cfg)
    # don't start the loop: the queue just fills
    for _ in range(2):
        eng.submit(GenRequest(prompt=[1], max_tokens=1,
                              sampling=SamplingParams()))
    with pytest.raises(EngineOverloadedError):
        eng.submit(GenRequest(prompt=[1], max_tokens=1,
                              sampling=SamplingParams()))
    # the backlog is readable LIVE from any thread — /state serves this,
    # not the engine thread's per-tick snapshot, which goes stale for as
    # long as that thread sits in a compile (here: never started)
    depth, wait_ms = eng.queue_depth()
    assert depth == 2 and wait_ms > 0.0
    assert eng.stats.queued == 0


def test_top_p_temperature_order():
    """OpenAI/vLLM semantics: temperature scaling precedes the nucleus
    cutoff (ADVICE r1 low #3). With temperature=0.1 and logits
    [1.0, 0.9, 0.8, -10], the scaled distribution puts ~66% mass on
    token 0, so top_p=0.5 keeps ONLY token 0 — whereas nucleus
    membership computed on the unscaled distribution keeps {0, 1} and
    token 1 then carries ~27% of the post-scale mass (P[all-zero over
    64 draws] ≈ 2e-9 under the old ordering)."""
    import jax.numpy as jnp

    from aigw_tpu.tpuserve.sampling import sample

    B = 64
    logits = jnp.tile(jnp.array([[1.0, 0.9, 0.8, -10.0]]), (B, 1))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(B))
    toks = sample(
        logits,
        keys,
        temperature=jnp.full((B,), 0.1),
        top_p=jnp.full((B,), 0.5),
        top_k=jnp.zeros((B,), jnp.int32),
    )
    assert (toks == 0).all(), toks


@pytest.fixture(scope="module")
def lp_url():
    """tpuserve with --logprobs 5 (engine logprobs_topk=5)."""
    from aiohttp import web

    holder = {}
    started = threading.Event()

    def run():
        async def main():
            server = TPUServeServer(
                "tiny-random",
                EngineConfig(max_batch_size=2, max_seq_len=256,
                             page_size=16, min_prefill_bucket=32,
                             logprobs_topk=5),
            )
            runner = web.AppRunner(server.app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            holder["port"] = site._server.sockets[0].getsockname()[1]
            holder["loop"] = asyncio.get_running_loop()
            started.set()
            await asyncio.Event().wait()

        try:
            asyncio.run(main())
        except RuntimeError:
            pass

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(timeout=60)
    yield f"http://127.0.0.1:{holder['port']}"
    holder["loop"].call_soon_threadsafe(holder["loop"].stop)


class TestLogprobs:
    """Per-token logprobs (vLLM/OpenAI parity; the last translator-tail
    item from the round-3 verdict: logprobs on the backend that supports
    them — our own)."""

    def test_engine_greedy_chosen_is_top1(self):
        """Greedy sampling: the chosen token's logprob must equal the
        top-1 entry, and the top-1 id must be the sampled token."""
        cfg = EngineConfig(max_batch_size=2, max_seq_len=128, page_size=16,
                           min_prefill_bucket=32, logprobs_topk=3)
        params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
        eng = Engine(params, llama.TINY, cfg, eos_token_ids=(257,))
        eng.start()
        try:
            done = threading.Event()
            rows = []

            def emit_lp(tok, fin, chosen, top):
                if tok >= 0:
                    rows.append((tok, chosen, top))
                if fin is not None:
                    done.set()

            eng.submit(GenRequest(
                prompt=[1, 2, 3] * 12, max_tokens=6,
                sampling=SamplingParams(temperature=0.0),
                emit_lp=emit_lp))
            assert done.wait(timeout=120)
            assert rows
            for tok, chosen, top in rows:
                assert len(top) == 3
                top_ids = [t for t, _ in top]
                top_vals = [v for _, v in top]
                assert tok == top_ids[0]  # greedy = argmax
                assert chosen == pytest.approx(top_vals[0], abs=1e-5)
                assert top_vals == sorted(top_vals, reverse=True)
                assert all(v <= 0.0 for v in top_vals)  # log-probs
        finally:
            eng.stop()

    def test_spec_and_logprobs_exclusive(self):
        with pytest.raises(ValueError):
            EngineConfig(logprobs_topk=3, spec_tokens=2)

    def test_http_logprobs_content(self, lp_url):
        status, body, _ = asyncio.run(_post(lp_url, "/v1/chat/completions", {
            "model": "tiny-random",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 4, "temperature": 0,
            "logprobs": True, "top_logprobs": 2,
        }))
        assert status == 200, body
        got = json.loads(body)
        lp = got["choices"][0]["logprobs"]["content"]
        assert len(lp) >= 1
        for entry in lp:
            assert "logprob" in entry and entry["logprob"] <= 0.0
            assert len(entry["top_logprobs"]) == 2
            assert isinstance(entry["bytes"], list)

    def test_http_streaming_logprobs(self, lp_url):
        async def main():
            async with aiohttp.ClientSession(timeout=_CLIENT_TIMEOUT) as s:
                async with s.post(lp_url + "/v1/chat/completions", json={
                    "model": "tiny-random",
                    "messages": [{"role": "user", "content": "go"}],
                    "max_tokens": 3, "temperature": 0,
                    "stream": True, "logprobs": True,
                }) as resp:
                    assert resp.status == 200
                    return (await resp.read()).decode()

        text = asyncio.run(main())
        chunks = [json.loads(line[6:])
                  for line in text.splitlines()
                  if line.startswith("data: ") and line != "data: [DONE]"]
        lp_chunks = [c for c in chunks
                     if c["choices"] and c["choices"][0].get("logprobs")]
        assert lp_chunks, text
        entry = lp_chunks[0]["choices"][0]["logprobs"]["content"][0]
        assert entry["logprob"] <= 0.0

    def test_logprobs_off_server_400(self, tpuserve_url):
        status, body, _ = asyncio.run(_post(
            tpuserve_url, "/v1/chat/completions", {
                "model": "tiny-random",
                "messages": [{"role": "user", "content": "x"}],
                "max_tokens": 2, "logprobs": True,
            }))
        assert status == 400
        assert "--logprobs" in json.loads(body)["error"]["message"]

    def test_top_logprobs_over_cap_400(self, lp_url):
        status, body, _ = asyncio.run(_post(lp_url, "/v1/chat/completions", {
            "model": "tiny-random",
            "messages": [{"role": "user", "content": "x"}],
            "max_tokens": 2, "logprobs": True, "top_logprobs": 9,
        }))
        assert status == 400
        assert "exceeds" in json.loads(body)["error"]["message"]

    def test_top_logprobs_requires_logprobs(self, lp_url):
        status, body, _ = asyncio.run(_post(lp_url, "/v1/chat/completions", {
            "model": "tiny-random",
            "messages": [{"role": "user", "content": "x"}],
            "max_tokens": 2, "top_logprobs": 2,
        }))
        assert status == 400

    def test_default_path_unchanged(self, tpuserve_url):
        # a server without logprobs still serves plain requests
        status, body, _ = asyncio.run(_post(
            tpuserve_url, "/v1/chat/completions", {
                "model": "tiny-random",
                "messages": [{"role": "user", "content": "x"}],
                "max_tokens": 2, "temperature": 0,
            }))
        assert status == 200


class TestSSEByteTemplate:
    def test_template_frames_byte_identical_to_full_serialization(self):
        """The streaming fast path splits one real stream_chunk_sse
        frame on a sentinel and re-joins around json.dumps(piece); the
        resulting bytes must equal serializing the whole chunk dict —
        for every escaping-relevant piece shape."""
        from aigw_tpu.schemas import openai as oai

        sentinel = "\x00aigw-delta-slot\x00"
        kw = dict(response_id="chatcmpl-abc123", model="tiny-random",
                  created=1700000000)
        head, tail = oai.stream_chunk_sse(
            **kw, delta={"content": sentinel},
        ).split(json.dumps(sentinel).encode())
        for piece in ("hello", 'has "quotes" and \\slashes\\',
                      "newline\nand\ttab", "unicodé ☃",
                      "", "data: [DONE]", "\x07control"):
            assert (head + json.dumps(piece).encode() + tail
                    == oai.stream_chunk_sse(
                        **kw, delta={"content": piece}))
