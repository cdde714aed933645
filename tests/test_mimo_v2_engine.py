"""MiMo-V2 through the serving ENGINE — the same loop, allocator, decode
windows and sampler as the other families — against the float32
reference: admission, chunked prefill over the pages and the ring behind
each chunk, a batched [G, S] admission, decode windows with idle rows
past several turns of the ring, and a slot reused by a later, SHORTER
request (whose ring rows beyond its length hold its predecessor's keys).
Logits, not tokens: the engine's own programs return the
log-probabilities of their top candidates (``logprobs_topk``), each
compared with the reference's log-softmax at the same position,
teacher-forced with the tokens the engine sampled.

``TOL`` is test_mimo_v2.py's, for its reasons (float32 rounding in
another order); log-softmax adds nothing of note. And what moves pages
only is off for the family, by what the family is."""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest

from aigw_tpu.models import mimo_v2
from aigw_tpu.models.cache import StateCache
from aigw_tpu.models.registry import family_fns, get_model_spec
from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
from aigw_tpu.tpuserve.kvcache import PageAllocator
from aigw_tpu.tpuserve.sampling import SamplingParams
from mimo_v2_util import SHARE, make_params, ref_logits

TOL = 3e-4
TOPK = 8


def _engine(cfg=SHARE, params=None, **over) -> Engine:
    ecfg = dict(max_batch_size=2, max_seq_len=256, page_size=16,
                num_pages=48, min_prefill_bucket=16,
                decode_steps_per_tick=4, prefill_chunk_tokens=32,
                logprobs_topk=TOPK, kv_cache_dtype="float32")
    ecfg.update(over)
    return Engine(params if params is not None else make_params(cfg), cfg,
                  EngineConfig(**ecfg), fns=family_fns("mimo_v2"))


class _Stream:
    """One request and what the engine said of each token it sampled."""

    def __init__(self, cfg, n: int, max_tokens: int, seed: int):
        self.prompt = [int(t) for t in np.random.default_rng(seed).integers(
            0, cfg.vocab_size, n)]
        self.tokens: list[int] = []
        self.tops: list[list] = []
        self.done = threading.Event()
        self.req = GenRequest(
            prompt=self.prompt, max_tokens=max_tokens, emit=lambda *_: None,
            emit_lp=self._emit, sampling=SamplingParams(temperature=0.0))

    def _emit(self, tok, fin, lp, top):
        if tok >= 0:
            self.tokens.append(tok)
            self.tops.append(top)
        if fin is not None:
            self.done.set()

    def worst(self, p, cfg) -> float:
        """Largest |engine log-prob - reference log-prob| over every
        candidate of every sampled position."""
        want = jax.nn.log_softmax(
            ref_logits(p, cfg, self.prompt + self.tokens), axis=-1)
        errs = [abs(float(want[len(self.prompt) - 1 + j, t]) - v)
                for j, top in enumerate(self.tops) for t, v in top]
        assert len(errs) == TOPK * len(self.tokens)
        return max(errs)


_COUNTED = ("moe_unserved_tokens", "swa_keys_attended",
            "swa_keys_in_context", "decode_state_rows_read",
            "decode_state_rows_live", "prefill_keys_attended")


def _settled(st, quiet: float = 1.0) -> dict:
    """The family's counters once no program's tape has been folded for
    ``quiet`` seconds: a window's tape is folded after its tokens are
    emitted, so a finished stream may still have one to come."""
    seen, since = None, time.monotonic()
    while time.monotonic() - since < quiet:
        now = {name: getattr(st, name) for name in _COUNTED}
        if now != seen:
            seen, since = now, time.monotonic()
        time.sleep(0.05)
    return seen


@pytest.fixture(scope="module")
def served():
    """Four requests through one two-slot engine: a long prompt that
    chunks (3 chunks of 32 and a padded tail) beside a short one whose
    answer turns the ring of 8 three times, then — into the slots they
    leave — a batched pair admitted together, one of them shorter than
    the window."""
    cfg = SHARE
    p = make_params(cfg)
    eng = _engine(cfg, p)
    eng.start()
    try:
        first = [_Stream(cfg, 100, 20, seed=1), _Stream(cfg, 21, 28, seed=2)]
        for s in first:
            eng.submit(s.req)
        for s in first:
            assert s.done.wait(600)
        chunks = eng.stats.chunked_prefill_steps
        # both slots are free again: these two arrive together and take
        # the batched [2, S] prefill into the slots just vacated — the
        # second is SHORTER than the window of 8, in a slot whose ring
        # is full of its predecessor's keys
        second = [_Stream(cfg, 27, 18, seed=3), _Stream(cfg, 5, 17, seed=4)]
        for s in second:
            eng.submit(s.req)
        for s in second:
            assert s.done.wait(600)
        counted = _settled(eng.stats)
        return {"cfg": cfg, "p": p, "first": first, "second": second,
                "chunks": chunks, "stats": eng.stats, "counted": counted}
    finally:
        eng.stop()


@pytest.mark.parametrize("which,i", [("first", 0), ("first", 1),
                                     ("second", 0), ("second", 1)])
def test_engine_logprobs_match_the_reference(served, which, i):
    s = served[which][i]
    assert len(s.tokens) == s.req.max_tokens
    assert s.worst(served["p"], served["cfg"]) < TOL


def test_the_long_prompt_ran_in_chunks_and_the_counters_counted(served):
    assert served["chunks"] == 3  # 100 tokens: 32 + 32 + 32, tail of 4
    st = served["stats"]
    cfg = served["cfg"]
    assert st.prefills == 4 and st.prefix_cache_hits == 0
    # every assignment counted, a share of them local, none dropped
    assert st.moe_tokens_dropped == 0
    assert 0 < st.moe_local_assignments < st.moe_total_assignments
    assert st.moe_total_assignments % cfg.num_experts_per_tok == 0
    assert st.moe_held_hits_decode > 0
    c = served["counted"]
    streams = served["first"] + served["second"]
    # the two global layers' prefill programs attend a prompt of n
    # tokens over n (n + 1) / 2 (query, key) pairs
    assert c["prefill_keys_attended"] == cfg.n_global_layers * sum(
        len(s.prompt) * (len(s.prompt) + 1) // 2 for s in streams)
    # real tokens through the six expert layers none of whose 4 picks
    # of 32 is among the 8 held: C(24,4)/C(32,4) = 0.30 under a
    # balanced router
    routed = st.moe_total_assignments // cfg.num_experts_per_tok
    assert 0.15 * routed < c["moe_unserved_tokens"] < 0.5 * routed
    # a decode step reads its live rows' rings and no others
    assert c["decode_state_rows_read"] == c["decode_state_rows_live"] > 0
    # (the first token of an answer is the prefill's; the device may
    # run a step past a request's last)
    assert sum(len(s.tokens) - 1 for s in streams) \
        <= c["decode_state_rows_live"] \
        <= sum(len(s.tokens) for s in streams) + 4 * len(streams)
    # a window layer's softmax saw at most 8 keys a row a step — all 8
    # but in the one answer that starts below the window — whatever
    # the context holds
    W = cfg.sliding_window
    steps = c["decode_state_rows_live"]
    assert c["swa_keys_attended"] <= cfg.n_window_layers * W * steps
    assert c["swa_keys_attended"] > cfg.n_window_layers * (W * steps - 40)
    assert c["swa_keys_in_context"] > 4 * c["swa_keys_attended"]


def test_cache_description_and_what_is_off():
    eng = _engine(kv_host_bytes=1 << 20, spec_tokens=4, logprobs_topk=0)
    cfg = SHARE
    spec = eng.cache_spec
    assert spec.stateful and spec.latent and spec.window == 8
    assert spec.kv_layers == eng.stats.kv_layers == 2  # the global ones
    # pages: ONE flattened row a token a global layer, v 16 | k 24 of
    # the one key head = 40 values, float32 here
    assert eng.stats.kv_bytes_per_token == 2 * 40 * 4
    assert eng.kv_page_bytes == 16 * 2 * 40 * 4
    assert isinstance(eng.kv_cache, StateCache)
    assert tuple(eng.kv_cache.kv.shape) == (2, 40, 49 * 16)
    assert eng.kv_cache.kv.nbytes == 49 * eng.kv_page_bytes
    # rings: 5 window layers x 8 tokens x 2 key heads x 40 values
    ring = eng.kv_cache.slots["swa_ring"]
    assert tuple(ring.shape) == (5, 2, 8, 2 * 40)
    assert eng.stats.state_bytes_per_slot == 5 * 8 * 2 * 40 * 4
    assert eng.stats.state_bytes_total == 2 * eng.stats.state_bytes_per_slot
    assert ring.nbytes == eng.stats.state_bytes_total
    assert cfg.n_kv_heads == 1  # the layers with pages', not the cache's
    assert set(eng.features_off) == {
        "prefix_cache", "kv_host_tier", "migration", "batch_parking",
        "kv_fleet_fetch", "speculation", "lora"}
    assert all("window keys live beside its pages" in why
               for why in eng.features_off.values())
    assert eng.prefix_cache is None and eng.host_tier is None
    assert isinstance(eng.allocator, PageAllocator)
    assert not eng.migratable
    assert eng._spec_rungs == (0,)
    assert eng.attn.name == "xla-bucketed"
    assert eng.decode_attn_impl == "xla-walk"
    # per-slot state: a prefill row names the slot it fills
    assert set(eng.slot_kw([])) == {"slot_ids"}


class _Store:
    base_row = 0


@pytest.mark.parametrize("kwargs", [
    {"lora_params": {"x": 1}, "adapter_names": ("a",)},
    {"adapter_store": _Store()},
])
def test_lora_refuses_at_start_up(kwargs):
    with pytest.raises(ValueError, match="LoRA serving is off"):
        Engine(make_params(SHARE), SHARE, EngineConfig(
            max_batch_size=2, max_seq_len=64, page_size=16),
            fns=family_fns("mimo_v2"), **kwargs)


def test_a_quantized_pool_refuses_at_start_up():
    with pytest.raises(ValueError, match="has no per-head"):
        _engine(kv_cache_dtype="int8")


def test_ragged_backend_request_falls_back_to_bucketed():
    eng = _engine(attention_backend="pallas-ragged")
    assert eng.attn.name == "xla-bucketed"
    assert "no ragged prefill" in eng.attn_reason


def test_registered_preset_and_config_surface():
    spec = get_model_spec("tiny-mimo-v2")
    assert spec.family == "mimo_v2" and spec.config is mimo_v2.TINY
    fns = family_fns("mimo_v2")
    assert fns.moe_stats and fns.prefill_suffix is not None
    assert (fns.verify_step, fns.prefill_sp, fns.prefill_sp_suffix,
            fns.prefill_ragged) == (None,) * 4
    assert mimo_v2.TINY.layer_kinds == (
        "global", "window", "window", "window", "window", "global",
        "window")
