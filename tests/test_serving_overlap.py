"""Serving-path overlap: chunked-prefill interleave under live decodes
and the adaptive decode window
(engine.py _decode_tick / _choose_window / _apply_row_updates).

CPU-backend engine tests for the round-6 hot-path overhaul:
- a long prompt admitted mid-stream must not stall in-flight decodes
  beyond one chunk (decode ticks interleave the chunk loop),
- the adaptive window shrinks under queue pressure / young streams and
  regrows to the full throughput window when the batch is steady.
"""

from __future__ import annotations

import threading
import time

import jax

from aigw_tpu.models import llama
from aigw_tpu.models.registry import get_model_spec
from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
from aigw_tpu.tpuserve.sampling import SamplingParams
import pytest

_SPEC = get_model_spec("tiny-random")
_PARAMS = llama.init_params(jax.random.PRNGKey(3), _SPEC.config)


def _engine(**over) -> Engine:
    cfg = dict(
        max_batch_size=2, max_seq_len=512, page_size=16,
        min_prefill_bucket=16, decode_steps_per_tick=4,
        prefill_chunk_tokens=32,
    )
    cfg.update(over)
    return Engine(_PARAMS, _SPEC.config, EngineConfig(**cfg))


class _Stream:
    """Token sink with completion event + arrival timestamps."""

    def __init__(self):
        self.toks: list[int] = []
        self.at: list[float] = []
        self.done = threading.Event()
        self.finish: str | None = None

    def emit(self, tok: int, fin: str | None) -> None:
        if tok >= 0:
            self.toks.append(tok)
            self.at.append(time.monotonic())
        if fin is not None:
            self.finish = fin
            self.done.set()


def _req(prompt, n, out: _Stream, seed=0, temp=0.0):
    return GenRequest(
        prompt=prompt, max_tokens=n,
        sampling=SamplingParams(temperature=temp, seed=seed),
        emit=out.emit,
    )


@pytest.mark.slow
def test_long_prompt_does_not_stall_inflight_decode():
    """Admit a long (chunked) prompt while another stream is decoding:
    the live stream must keep emitting between prefill chunks instead of
    stalling for the whole multi-chunk prefill."""
    eng = _engine()
    eng.start()
    try:
        a = _Stream()
        ra = _req([5, 9, 11], 160, a)
        eng.submit(ra)
        # wait until A is demonstrably mid-stream
        deadline = time.monotonic() + 600
        while len(a.toks) < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(a.toks) >= 4, "stream A never started"

        b = _Stream()
        long_prompt = [(7 * i + 3) % 400 + 1 for i in range(200)]  # 6 chunks
        a_before = len(a.toks)
        eng.submit(_req(long_prompt, 4, b))
        assert b.done.wait(timeout=600)
        assert eng.stats.chunked_prefill_steps >= 5
        # B's first token is emitted at admission; count A tokens that
        # arrived while B's prompt was prefilling (before B's first
        # emit). Interleaved chunking keeps A flowing: at least one
        # decode window lands per chunk boundary.
        b_first = b.at[0]
        a_during = sum(1 for t in a.at[a_before:] if t <= b_first)
        assert a_during >= 2, (
            f"stream A stalled behind the long prefill "
            f"(only {a_during} tokens during admission)")
        ra.cancelled.set()  # A served its purpose; don't decode 160 out
    finally:
        eng.stop()


@pytest.mark.slow


def test_lean_decode_identical_to_full():
    """Penalty-free batches dispatch the lean decode program (no counts
    scatter, no penalty terms); forcing the full program on the same
    requests must produce byte-identical streams — zero penalties add
    exactly 0.0 per logit."""
    results: dict[bool, list[list[int]]] = {}
    for force_full in (False, True):
        eng = _engine()
        if force_full:
            eng._lean_decode_ok = lambda: False  # type: ignore
        eng.start()
        try:
            s1, s2 = _Stream(), _Stream()
            eng.submit(_req([6, 2, 8, 3, 1], 20, s1))
            eng.submit(_req([1, 7, 7, 2], 20, s2, seed=99, temp=0.7))
            assert s1.done.wait(timeout=600)
            assert s2.done.wait(timeout=600)
            results[force_full] = [s1.toks, s2.toks]
        finally:
            eng.stop()
    assert results[True] == results[False]
    assert len(results[False][0]) > 0


def test_penalized_request_forces_full_decode():
    """A request with repetition penalties must route through the full
    program (and still stream to completion) — the lean fork must never
    drop penalty bookkeeping for a batch that needs it."""
    eng = _engine()
    eng.start()
    try:
        s = _Stream()
        req = GenRequest(
            prompt=[4, 5, 6], max_tokens=10,
            sampling=SamplingParams(temperature=0.0,
                                    frequency_penalty=0.5),
            emit=s.emit,
        )
        eng.submit(req)
        # engine thread observes the slot as penalized while decoding
        deadline = time.monotonic() + 600
        saw_full = False
        while not s.done.wait(timeout=0.01):
            if not eng._lean_decode_ok():
                saw_full = True
            if time.monotonic() > deadline:
                break
        assert s.done.is_set()
        assert saw_full
        assert len(s.toks) > 0
    finally:
        eng.stop()


@pytest.mark.slow
def test_adaptive_window_shrinks_then_regrows():
    """Queue pressure / young streams force the small window; a steady
    batch regrows to the full decode_steps_per_tick."""
    eng = _engine(decode_steps_per_tick=8, min_decode_steps_per_tick=2)
    eng.start()
    try:
        # phase 1: more requests than slots → queue pressure → shrink
        streams = [_Stream() for _ in range(4)]
        for i, s in enumerate(streams):
            eng.submit(_req([1 + i, 2 + i, 3 + i], 12, s))
        for s in streams:
            assert s.done.wait(timeout=600)
        assert eng.stats.window_shrinks >= 1
        # phase 2: one long steady stream → regrow to the full window
        long = _Stream()
        eng.submit(_req([9, 8, 7], 64, long))
        assert long.done.wait(timeout=600)
        assert eng.stats.window_grows >= 1
        assert eng.stats.decode_window == 8
        assert eng.stats.decode_steps > 0
    finally:
        eng.stop()


def test_fixed_window_when_adaptive_disabled():
    eng = _engine(adaptive_decode_window=False, decode_steps_per_tick=4)
    eng.start()
    try:
        s = _Stream()
        eng.submit(_req([4, 2], 10, s))
        assert s.done.wait(timeout=600)
        assert eng.stats.decode_window == 4
        assert eng.stats.window_shrinks == 0
        assert eng.stats.window_grows == 0
    finally:
        eng.stop()


def test_phase_breakdown_accumulates():
    """The serving-path phase stats (prefill/transfer/emit ms) must
    accumulate — /state surfaces them."""
    eng = _engine()
    eng.start()
    try:
        s = _Stream()
        eng.submit(_req([6, 5, 4, 3], 16, s))
        assert s.done.wait(timeout=600)
        assert eng.stats.prefill_ms > 0
        assert eng.stats.transfer_ms > 0
        assert eng.stats.emit_ms > 0
        assert eng.stats.first_emit_ms > 0
    finally:
        eng.stop()
