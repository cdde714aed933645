"""Prompt-lookup speculative decoding (tpuserve/speculation.py).

The load-bearing property: speculation is an *optimization, not a model
change* — for any seed, spec on/off must produce IDENTICAL token streams
(per-position PRNG keys + longest-matching-prefix acceptance). The
rejection-equivalence tests double as KV-rewind correctness proofs: if a
rejected draft's stale K/V were ever read, later tokens would diverge.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import kvq, llama
from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
from aigw_tpu.tpuserve.sampling import SamplingParams
from aigw_tpu.tpuserve.speculation import accept_counts, ngram_drafts


class TestNgramDrafts:
    def test_basic_match(self):
        # history ... 4 5 6 9 | 4 5  (pending token 5 at pos=5)
        hist = np.zeros((1, 32), np.int32)
        hist[0, :6] = [4, 5, 6, 9, 4, 5]
        d = np.asarray(ngram_drafts(jnp.asarray(hist),
                                    jnp.asarray([5], jnp.int32), 3))
        # last earlier (4,5) starts at t=0 → continuation 6, 9, 4
        assert d.tolist() == [[6, 9, 4]]

    def test_most_recent_match_wins(self):
        # (1,2) occurs twice; continuation of the LATER one is proposed
        hist = np.zeros((1, 32), np.int32)
        hist[0, :9] = [1, 2, 7, 1, 2, 8, 9, 1, 2]
        d = np.asarray(ngram_drafts(jnp.asarray(hist),
                                    jnp.asarray([8], jnp.int32), 2))
        assert d.tolist() == [[8, 9]]

    def test_no_match(self):
        hist = np.zeros((1, 16), np.int32)
        hist[0, :4] = [1, 2, 3, 4]
        d = np.asarray(ngram_drafts(jnp.asarray(hist),
                                    jnp.asarray([3], jnp.int32), 4))
        assert (d == -1).all()

    def test_continuation_clipped_at_history_end(self):
        # match exists but only one real continuation token before `pos`
        hist = np.zeros((1, 16), np.int32)
        hist[0, :5] = [3, 4, 9, 3, 4]
        d = np.asarray(ngram_drafts(jnp.asarray(hist),
                                    jnp.asarray([4], jnp.int32), 3))
        assert d.tolist() == [[9, 3, 4]]

    def test_short_history_proposes_nothing(self):
        hist = np.zeros((2, 8), np.int32)
        hist[:, 0] = 5
        d = np.asarray(ngram_drafts(jnp.asarray(hist),
                                    jnp.asarray([0, 0], jnp.int32), 2))
        assert (d == -1).all()


class TestAcceptCounts:
    def test_prefix_rule(self):
        drafts = jnp.asarray([[7, 8, 9], [7, 8, 9], [1, 2, 3], [-1, -1, -1]])
        sampled = jnp.asarray(
            [[7, 8, 9, 4], [7, 5, 9, 4], [9, 2, 3, 4], [0, 0, 0, 0]]
        )
        got = np.asarray(accept_counts(drafts, sampled))
        # full match / match-then-miss (later match ignored) / miss / poison
        assert got.tolist() == [3, 1, 0, 0]


def _make_engine(spec_tokens: int, **cfg_kw) -> Engine:
    cfg = EngineConfig(max_batch_size=4, max_seq_len=256, page_size=16,
                       min_prefill_bucket=32, spec_tokens=spec_tokens,
                       **cfg_kw)
    params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
    eng = Engine(params, llama.TINY, cfg, eos_token_ids=(257,))
    eng.start()
    return eng


def _collect(engine, prompt, max_tokens=8, **sp):
    done = threading.Event()
    toks: list[int] = []
    finish: list[str] = []

    def emit(tok, fin):
        if tok >= 0:
            toks.append(tok)
        if fin is not None:
            finish.append(fin)
            done.set()

    engine.submit(GenRequest(prompt=prompt, max_tokens=max_tokens,
                             sampling=SamplingParams(**sp), emit=emit))
    assert done.wait(timeout=120), "generation timed out"
    return toks, finish[0]


@pytest.fixture(scope="module")
def spec_engine():
    eng = _make_engine(spec_tokens=3)
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def plain_engine():
    eng = _make_engine(spec_tokens=0)
    yield eng
    eng.stop()


class TestSpecEquivalence:
    """spec on/off must be indistinguishable to the client."""

    @pytest.mark.slow

    def test_greedy_identical(self, spec_engine, plain_engine):
        prompt = [5, 6, 7, 8, 5, 6]  # repeated 2-gram → drafts proposed
        a, fa = _collect(spec_engine, prompt, max_tokens=10, temperature=0.0)
        b, fb = _collect(plain_engine, prompt, max_tokens=10, temperature=0.0)
        assert a == b and fa == fb

    @pytest.mark.slow
    def test_sampled_identical_under_rejection(self, spec_engine,
                                               plain_engine):
        """Random-weight sampling rejects nearly every draft; the streams
        still matching token-for-token proves rejected drafts' stale KV
        writes are never read (the rewind-free property)."""
        prompt = [4, 5, 6, 4, 5, 6, 4, 5]
        a, _ = _collect(spec_engine, prompt, max_tokens=12,
                        temperature=0.9, seed=11)
        b, _ = _collect(plain_engine, prompt, max_tokens=12,
                        temperature=0.9, seed=11)
        assert a == b

    @pytest.mark.slow

    def test_penalty_slots_identical(self, spec_engine, plain_engine):
        prompt = [9, 9, 9, 9]
        kw = dict(max_tokens=8, temperature=0.7, seed=3,
                  frequency_penalty=0.8, presence_penalty=0.2)
        a, _ = _collect(spec_engine, prompt, **kw)
        b, _ = _collect(plain_engine, prompt, **kw)
        assert a == b

    def test_acceptance_happens_and_wins(self, spec_engine):
        """logit_bias pins every sample to one token → history becomes
        pure repetition → drafts fully accepted every step."""
        before = spec_engine.stats.spec_accepted
        steps_before = spec_engine.stats.decode_steps
        toks, finish = _collect(
            spec_engine, [1, 2, 3], max_tokens=24, temperature=0.0,
            logit_bias=((7, 100.0),),
        )
        assert toks == [7] * 24 and finish == "length"
        # with D=3 drafts fully accepted, most of the 24 tokens ride in
        # on accepted drafts rather than one-per-step decode
        accepted = spec_engine.stats.spec_accepted - before
        assert accepted >= 8, accepted
        del steps_before  # window counts include idle dispatched windows

    def test_bias_matches_plain(self, spec_engine, plain_engine):
        kw = dict(max_tokens=12, temperature=0.0, logit_bias=((7, 100.0),))
        a, _ = _collect(spec_engine, [1, 2, 3], **kw)
        b, _ = _collect(plain_engine, [1, 2, 3], **kw)
        assert a == b


class TestSpecEdges:
    def test_eos_mid_burst(self):
        """EOS accepted inside a multi-token burst finishes cleanly with
        no trailing tokens."""
        eng = _make_engine(spec_tokens=3)
        try:
            toks, finish = _collect(
                eng, [2, 3, 4], max_tokens=16, temperature=0.0,
                logit_bias=((257, 100.0),),  # bias straight into EOS
            )
            assert finish == "stop" and toks == []
        finally:
            eng.stop()

    def test_max_tokens_mid_burst(self, spec_engine):
        """A burst overshooting max_tokens is truncated exactly."""
        toks, finish = _collect(
            spec_engine, [3, 1, 3], max_tokens=2, temperature=0.0,
            logit_bias=((9, 100.0),),
        )
        assert finish == "length" and toks == [9, 9]

    def test_concurrent_spec_requests_isolated(self, spec_engine):
        solo1, _ = _collect(spec_engine, [10, 20, 30], max_tokens=5,
                            temperature=0.0)
        solo2, _ = _collect(spec_engine, [40, 50, 60], max_tokens=5,
                            temperature=0.0)
        results: dict[int, list[int]] = {0: [], 1: []}
        dones = [threading.Event(), threading.Event()]

        def mk(i):
            def emit(tok, fin):
                if tok >= 0:
                    results[i].append(tok)
                if fin is not None:
                    dones[i].set()
            return emit

        spec_engine.submit(GenRequest(
            prompt=[10, 20, 30], max_tokens=5,
            sampling=SamplingParams(temperature=0.0), emit=mk(0)))
        spec_engine.submit(GenRequest(
            prompt=[40, 50, 60], max_tokens=5,
            sampling=SamplingParams(temperature=0.0), emit=mk(1)))
        assert all(d.wait(timeout=120) for d in dones)
        assert results[0] == solo1 and results[1] == solo2


class TestVerifyStep:
    @pytest.mark.parametrize("rung,kv_dtype,tol", [
        ("gather", "bfloat16", 2e-2), ("", "bfloat16", 3e-2),
        ("", "int8", 5e-2), ("", "int4", 2e-1)],
        ids=["gather", "walk", "walk-int8", "walk-int4"])
    def test_matches_sequential_decode(self, rung, kv_dtype, tol):
        """verify_step's logits at every position equal running
        decode_step one token at a time over the same inputs: on the
        window gather, which is verify_step's own attention, and — a
        bfloat16 rounding further, since the walk accumulates the
        values in float32 across pages — on the page walk.

        ``[walk-int8]`` / ``[walk-int4]``: verify over a QUANTIZED pool
        ("gather-dequant": ``kvq.window_kv`` dequantizes the window it
        reads), which no tier-1 case held — the deleted
        ``test_verify_production_shape`` stood beside it on a native
        pool. The reference is the walk, whose trips dequantize in code
        of their own (ops/paged_walk.py), so a scale dropped on either
        side shows. Tolerances: int8's grid step is a 127th of a row's
        largest value, below the bfloat16 rounding the two reads
        already differ by (``parity.BF16_TOL``); int4's is a seventh,
        and a row the two sides quantized from values a rounding apart
        may land a step apart (the bound of
        ``test_paged_walk.STEP_TOL``). A dropped scale moves these
        logits by O(1)."""
        cfg = llama.TINY
        params = llama.init_params(jax.random.PRNGKey(1), cfg)
        ps = 16
        n_pages = 8
        kv_shape = (cfg.n_layers, 2, n_pages * ps, cfg.n_kv_heads,
                    cfg.head_dim)
        page_table = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
        prompt = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
        seq_lens = jnp.asarray([5], jnp.int32)
        inputs = [9, 2, 6, 5]  # pending + 3 "drafts"

        # sequential reference
        kv = kvq.make_pool(kv_shape, kv_dtype)
        _, kv = llama.prefill(params, cfg, prompt, seq_lens, kv,
                              page_table, ps)
        seq_logits = []
        for d, tok in enumerate(inputs):
            lg, kv = llama.decode_step(
                params, cfg, jnp.asarray([tok], jnp.int32),
                jnp.asarray([5 + d], jnp.int32), kv, page_table, ps,
                jnp.asarray([True]), attn_impl=rung)
            seq_logits.append(np.asarray(lg[0]))

        # one verify step
        kv = kvq.make_pool(kv_shape, kv_dtype)
        _, kv = llama.prefill(params, cfg, prompt, seq_lens, kv,
                              page_table, ps)
        ver, _ = llama.verify_step(
            params, cfg, jnp.asarray([inputs], jnp.int32),
            jnp.asarray([5], jnp.int32), kv, page_table, ps,
            jnp.asarray([True]), jnp.asarray([64], jnp.int32))
        ver = np.asarray(ver[0])
        for d in range(len(inputs)):
            np.testing.assert_allclose(ver[d], seq_logits[d],
                                       rtol=tol, atol=tol)

    def test_limit_fence_blocks_writes(self):
        """Positions at/past `limits` must not be written (page safety)."""
        cfg = llama.TINY
        params = llama.init_params(jax.random.PRNGKey(2), cfg)
        ps = 16
        kv_shape = (cfg.n_layers, 2, 4 * ps, cfg.n_kv_heads, cfg.head_dim)
        kv = jnp.zeros(kv_shape, jnp.bfloat16)
        page_table = jnp.asarray([[0, 1]], jnp.int32)
        _, kv = llama.verify_step(
            params, cfg, jnp.asarray([[1, 2, 3, 4]], jnp.int32),
            jnp.asarray([14], jnp.int32), kv, page_table, ps,
            jnp.asarray([True]), jnp.asarray([16], jnp.int32))
        kv_np = np.asarray(kv, np.float32)
        # positions 14, 15 written; 16, 17 fenced out
        assert np.abs(kv_np[:, :, 14:16]).sum() > 0
        assert np.abs(kv_np[:, :, 16:18]).sum() == 0


class TestSpecPrefixCacheInterplay:
    """Speculation × prefix-cache regression (ISSUE 3, re-anchored by
    ISSUE 4): speculative admissions used to force a FULL device-state
    rebuild, guarded by ``allocator.repin``. The rebuild is gone —
    admissions ride the incremental row-update path — and the guard is
    replaced by the DIRECT invariant (``truncate_to``: no shared page
    is ever writable by drafts without CoW). The observable property is
    unchanged: a speculative session's adopted prefix pages stay
    pinned while other admissions churn the batch — never orphaned
    into the evictable pool (where a later allocation could steal live
    KV) and never double-freed — and the churn costs ZERO
    pipeline-draining rebuilds."""

    @pytest.mark.slow
    def test_prefix_pages_survive_concurrent_admissions(self):
        eng = _make_engine(spec_tokens=3)
        try:
            assert eng.prefix_cache is not None  # spec + cache coexist
            shared = [(3 * i + 2) % 200 + 1 for i in range(48)]  # 3 pages

            # seed the cache, then hold a speculative session OPEN on an
            # adopted prefix while other admissions force rebuilds
            a, _ = _collect(eng, shared + [7], max_tokens=4,
                            temperature=0.0)

            toks_b: list[int] = []
            done_b = threading.Event()

            def emit_b(tok, fin):
                if tok >= 0:
                    toks_b.append(tok)
                if fin is not None:
                    done_b.set()

            eng.submit(GenRequest(prompt=shared + [7], max_tokens=24,
                                  sampling=SamplingParams(temperature=0.0),
                                  emit=emit_b))
            # wait until B is admitted (prefix adopted, pages pinned)
            deadline = time.monotonic() + 60
            while eng.stats.prefix_cache_hits < 1:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            adopted = [p for p in eng.allocator.pages(1)  # seq B = id 1
                       if eng.prefix_cache.key_of_page(p) is not None]
            assert adopted, "B adopted no cached pages"

            # concurrent admissions: each lands as an incremental row
            # update while B's speculative stream keeps decoding
            for j in range(3):
                _collect(eng, [(11 * i + j) % 150 + 1 for i in range(20)],
                         max_tokens=3, temperature=0.0)
            # the admissions above rode the row-update path: no live
            # pipeline was ever drained for a full state rebuild
            assert eng.stats.state_rebuilds == 0

            if not done_b.is_set():
                # B still live: its adopted pages must still be pinned —
                # refcounted, not parked evictable, not in the free stack
                for p in adopted:
                    assert eng.allocator._refs.get(p, 0) >= 1
                    assert p not in eng.allocator._evictable
                    assert p not in eng.allocator._free
            assert done_b.wait(timeout=120)
            # the stream itself is proof the pages were never stolen:
            # identical prefix+pending → identical greedy continuation
            assert toks_b[:4] == a[:4]
        finally:
            eng.stop()
