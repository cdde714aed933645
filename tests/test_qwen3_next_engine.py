"""Qwen3-Next through the serving ENGINE — the same loop, allocator,
decode windows and sampler as the other families — against the float32
reference: admission, chunked prefill with the state carried in the
slot, a batched [G, S] admission, decode windows with idle rows, and a
slot reused by a later request. Logits, not tokens: the engine's own
programs return the log-probabilities of their top candidates
(``logprobs_topk``), each compared with the reference's log-softmax at
the same position, teacher-forced with the tokens the engine sampled.

``TOL`` is test_qwen3_next.py's, for its reasons (float32 rounding in
another order); log-softmax adds nothing of note. And what moves pages
only is off for the family, by what the family is."""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest

from aigw_tpu.models import qwen3_next as qn
from aigw_tpu.models.registry import family_fns, get_model_spec
from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
from aigw_tpu.tpuserve.kvcache import PageAllocator
from aigw_tpu.tpuserve.sampling import SamplingParams
from qwen3_next_util import SHARE, make_params, ref_logits

TOL = 5e-4
TOPK = 8


def _engine(cfg=SHARE, params=None, **over) -> Engine:
    ecfg = dict(max_batch_size=2, max_seq_len=256, page_size=16,
                num_pages=48, min_prefill_bucket=16,
                decode_steps_per_tick=4, prefill_chunk_tokens=32,
                logprobs_topk=TOPK, kv_cache_dtype="float32")
    ecfg.update(over)
    return Engine(params if params is not None else make_params(cfg), cfg,
                  EngineConfig(**ecfg), fns=family_fns("qwen3_next"))


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


@pytest.fixture(scope="module")
def served():
    """Four requests through one two-slot engine: a long prompt that
    chunks (3 chunks of 32 and a padded tail) beside a short one, then —
    into the slots they leave — a batched pair admitted together."""
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
        # the batched [2, S] prefill into the slots just vacated
        second = [_Stream(cfg, 27, 18, seed=3), _Stream(cfg, 30, 17, seed=4)]
        for s in second:
            eng.submit(s.req)
        for s in second:
            assert s.done.wait(600)
        stats = eng.stats
        return {"cfg": cfg, "p": p, "first": first, "second": second,
                "chunks": chunks, "stats": stats, "eng": eng}
    finally:
        eng.stop()


@pytest.mark.parametrize("which,i", [("first", 0), ("first", 1),
                                     ("second", 0), ("second", 1)])
def test_engine_logprobs_match_the_reference(served, which, i):
    s = served[which][i]
    assert len(s.tokens) == s.req.max_tokens
    assert s.worst(served["p"], served["cfg"]) < TOL


def test_the_long_prompt_ran_in_chunks_and_slots_were_reused(served):
    assert served["chunks"] == 3  # 100 tokens: 32 + 32 + 32, tail of 4
    st = served["stats"]
    assert st.prefills == 4 and st.prefix_cache_hits == 0
    # every assignment counted, a share of them local, none dropped
    assert st.moe_tokens_dropped == 0
    assert 0 < st.moe_local_assignments < st.moe_total_assignments
    assert st.moe_total_assignments % served["cfg"].num_experts_per_tok == 0
    assert st.moe_held_hits_decode > 0
    assert served["eng"]._slot_of_seq == {}


def test_cache_description_and_what_is_off():
    eng = _engine(kv_host_bytes=1 << 20, spec_tokens=4, logprobs_topk=0)
    spec = eng.cache_spec
    assert spec.stateful and spec.kv_layers == 2
    assert eng.stats.kv_layers == 2
    assert eng.stats.state_bytes_per_slot == spec.state_bytes_per_slot(
        "float32") > 0
    assert eng.stats.state_bytes_total == 2 * eng.stats.state_bytes_per_slot
    # pages of the full-attention layers only: 2 layers x K,V x 2 heads
    # x 32 x 4 bytes a token
    assert eng.stats.kv_bytes_per_token == 2 * 2 * 2 * 32 * 4
    assert set(eng.features_off) == {
        "prefix_cache", "kv_host_tier", "migration", "batch_parking",
        "kv_fleet_fetch", "speculation", "lora"}
    assert eng.prefix_cache is None and eng.host_tier is None
    assert isinstance(eng.allocator, PageAllocator)
    assert not eng.migratable
    assert eng._spec_rungs == (0,)
    assert eng.attn.name == "xla-bucketed"
    assert eng.decode_attn_impl == "xla-walk"


def test_other_families_keep_everything_on():
    spec = get_model_spec("tiny-random")
    eng = Engine(family_fns("llama").init_params(
        jax.random.PRNGKey(0), spec.config), spec.config,
        EngineConfig(max_batch_size=2, max_seq_len=128, page_size=16))
    assert eng.features_off == {} and eng.prefix_cache is not None
    assert not eng.cache_spec.stateful
    assert eng.stats.state_bytes_total == 0
    assert eng.stats.kv_layers == spec.config.n_layers
    assert eng.slot_kw([1, 2]) == {}


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
            fns=family_fns("qwen3_next"), **kwargs)


def test_ragged_backend_request_falls_back_to_bucketed():
    eng = _engine(attention_backend="pallas-ragged")
    assert eng.attn.name == "xla-bucketed"
    assert "no ragged prefill" in eng.attn_reason


def test_registered_preset_and_config_surface():
    spec = get_model_spec("tiny-qwen3-next")
    assert spec.family == "qwen3_next" and spec.config is qn.TINY
    fns = family_fns("qwen3_next")
    assert fns.moe_stats and fns.prefill_suffix is not None
    assert (fns.verify_step, fns.prefill_sp, fns.prefill_sp_suffix,
            fns.prefill_ragged) == (None,) * 4
    cfg = qn.Qwen3NextConfig(num_hidden_layers=12, num_experts=128,
                             router_experts=512, vocab_size=37984)
    assert (cfg.n_layers, cfg.n_experts, cfg.router_width) == (12, 128, 512)
    assert cfg.n_full_layers == 3 and cfg.n_linear_layers == 9
    assert cfg.moe_tape_width == 131


def _settled(st, quiet: float = 1.0) -> tuple[int, int]:
    """(rows_read, rows_live) once no window has been folded for
    ``quiet`` seconds: a window's tape is folded after its tokens are
    emitted, so a finished stream may still have one to come."""
    seen, since = None, time.monotonic()
    while time.monotonic() - since < quiet:
        now = (st.decode_state_rows_read, st.decode_state_rows_live)
        if now != seen:
            seen, since = now, time.monotonic()
        time.sleep(0.05)
    return seen


def test_engine_counts_the_state_rows_its_decode_windows_read(monkeypatch):
    """Four slots, blocks of two rows (``Rs`` forced under the slot
    count). One request alone, then three together. ``rows_live``: a
    live row for every decode step a request took — its first token is
    the prefill's, and the device may run one step past its last.
    ``rows_read``: the loops' trips x 2 — twice the live rows while one
    decodes, between the live rows and a block more than them a step
    while three do."""
    cfg = SHARE
    row = (cfg.linear_num_value_heads * cfg.linear_key_head_dim
           * cfg.linear_value_head_dim * 4)
    monkeypatch.setattr(qn, "_STATE_TRIP_BYTES", 2 * row)
    eng = _engine(cfg, max_batch_size=4, logprobs_topk=0)
    eng.start()
    try:
        alone = _Stream(cfg, 20, 14, seed=5)
        eng.submit(alone.req)
        assert alone.done.wait(600)
        st = eng.stats
        read1, live1 = _settled(st)
        assert live1 in (13, 14) and read1 == 2 * live1
        steps = st.decode_steps
        three = [_Stream(cfg, 18 + i, 9 + 4 * i, seed=6 + i)
                 for i in range(3)]
        for s in three:
            eng.submit(s.req)
        for s in three:
            assert s.done.wait(600)
        read, live = _settled(st)
    finally:
        eng.stop()
    read, live = read - read1, live - live1
    assert [len(s.tokens) for s in three] == [9, 13, 17]
    assert 8 + 12 + 16 <= live <= 9 + 13 + 17
    assert read % 2 == 0 and live <= read <= live + (st.decode_steps - steps)
