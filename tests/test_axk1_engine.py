"""A.X-K1 through the serving ENGINE — the same loop, allocator,
decode windows and sampler as the other families — against the float32
reference: admission, chunked prefill over the latent pages behind each
chunk, a batched [G, S] admission, decode windows with idle rows, and a
slot reused by a later request. Logits, not tokens: the engine's own
programs return the log-probabilities of their top candidates
(``logprobs_topk``), each compared with the reference's log-softmax at
the same position, teacher-forced with the tokens the engine sampled.

``TOL`` is test_axk1.py's, for its reasons (float32 rounding in
another order); log-softmax adds nothing of note. And what moves pages
only is off for the family, by what the family is."""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest

from aigw_tpu.models import axk1
from aigw_tpu.models.registry import family_fns, get_model_spec
from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
from aigw_tpu.tpuserve.kvcache import PageAllocator
from aigw_tpu.tpuserve.sampling import SamplingParams
from axk1_util import SHARE, make_params, ref_logits

TOL = 3e-4
TOPK = 8


def _engine(cfg=SHARE, params=None, **over) -> Engine:
    ecfg = dict(max_batch_size=2, max_seq_len=256, page_size=16,
                num_pages=48, min_prefill_bucket=16,
                decode_steps_per_tick=4, prefill_chunk_tokens=32,
                logprobs_topk=TOPK, kv_cache_dtype="float32")
    ecfg.update(over)
    return Engine(params if params is not None else make_params(cfg), cfg,
                  EngineConfig(**ecfg), fns=family_fns("axk1"))


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


def _settled(st, quiet: float = 1.0) -> tuple[int, int, int]:
    """(group hits, group slots, prefill keys attended) once no
    program's tape has been folded
    for ``quiet`` seconds: a window's tape is folded after its tokens
    are emitted, so a finished stream may still have one to come."""
    seen, since = None, time.monotonic()
    while time.monotonic() - since < quiet:
        now = (st.moe_groups_kept_hits, st.moe_group_slots,
               st.prefill_keys_attended)
        if now != seen:
            seen, since = now, time.monotonic()
        time.sleep(0.05)
    return seen


@pytest.fixture(scope="module")
def served():
    """Four requests through one two-slot engine: a long prompt that
    chunks (3 chunks of 32 and a padded tail) beside a short one, then —
    into the slots they leave — a batched pair admitted together."""
    cfg = SHARE
    p = make_params(cfg)  # the reference's tree; the engine serves the
    eng = _engine(cfg, axk1.serving_params(p, cfg))  # leaves laid out
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
    assert st.prefills == 4 and st.prefix_cache_hits == 0
    # every assignment counted, a share of them local, none dropped
    assert st.moe_tokens_dropped == 0
    assert 0 < st.moe_local_assignments < st.moe_total_assignments
    assert st.moe_total_assignments % served["cfg"].num_experts_per_tok == 0
    assert st.moe_held_hits_decode > 0
    # the family's own columns, summed over the layers. Every layer's
    # prefill programs attend a prompt of n tokens over n (n + 1) / 2
    # (query, key) pairs. Each of the 3 expert layers keeps topk_group
    # groups a real token: a prompt's tokens and a decode step's live
    # row (the first token of an answer is the prefill's; the device
    # may run a step past a request's last), and the share (groups 1
    # and 2 of 4) holds some of them.
    cfg = served["cfg"]
    hits, slots, attended = served["counted"]
    streams = served["first"] + served["second"]
    assert attended == cfg.num_hidden_layers * sum(
        len(s.prompt) * (len(s.prompt) + 1) // 2 for s in streams)
    per = 3 * cfg.topk_group
    lo = per * sum(len(s.prompt) + len(s.tokens) - 1 for s in streams)
    hi = per * sum(len(s.prompt) + len(s.tokens) for s in streams)
    assert lo <= slots <= hi
    assert 0.25 * slots < hits < 0.75 * slots


def test_cache_description_and_what_is_off():
    eng = _engine(kv_host_bytes=1 << 20, spec_tokens=4, logprobs_topk=0)
    spec = eng.cache_spec
    assert not spec.stateful and spec.latent and spec.kv_layers == 4
    assert eng.stats.kv_layers == 4
    assert eng.stats.state_bytes_per_slot == eng.stats.state_bytes_total == 0
    # one row a token a layer: 4 layers x 40 values (latent 32 +
    # rotated key 8) x 4 bytes; no K/V pair, no head axis
    assert eng.stats.kv_bytes_per_token == 4 * 40 * 4
    assert eng.kv_page_bytes == 16 * 4 * 40 * 4
    assert tuple(eng.kv_cache.shape) == (4, 40, 49 * 16)
    assert eng.kv_cache.nbytes == 49 * eng.kv_page_bytes
    assert set(eng.features_off) == {
        "prefix_cache", "kv_host_tier", "migration", "batch_parking",
        "kv_fleet_fetch", "speculation", "lora"}
    assert all("latent row" in why for why in eng.features_off.values())
    assert eng.prefix_cache is None and eng.host_tier is None
    assert isinstance(eng.allocator, PageAllocator)
    assert not eng.migratable
    assert eng._spec_rungs == (0,)
    assert eng.attn.name == "xla-bucketed"
    assert eng.decode_attn_impl == "xla-walk"
    assert eng.slot_kw([1, 2]) == {}  # no per-slot state


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
            fns=family_fns("axk1"), **kwargs)


def test_a_quantized_pool_refuses_at_start_up():
    with pytest.raises(ValueError, match="latent row has no per-head"):
        _engine(kv_cache_dtype="int8")


def test_ragged_backend_request_falls_back_to_bucketed():
    eng = _engine(attention_backend="pallas-ragged")
    assert eng.attn.name == "xla-bucketed"
    assert "no ragged prefill" in eng.attn_reason


def test_registered_preset_and_config_surface():
    spec = get_model_spec("tiny-axk1")
    assert spec.family == "axk1" and spec.config is axk1.TINY
    fns = family_fns("axk1")
    assert fns.moe_stats and fns.prefill_suffix is not None
    assert (fns.verify_step, fns.prefill_sp, fns.prefill_sp_suffix,
            fns.prefill_ragged) == (None,) * 4
    assert axk1.TINY.layer_kinds == ("dense", "moe", "moe", "moe")


@pytest.mark.parametrize("model,leaves", [("tiny-axk1", 16),
                                          ("tiny-random", 0)])
def test_the_server_lays_the_weights_out_at_load(model, leaves):
    """``_load_params`` hands what any weight source gave to the
    family's ``serving_params`` (ISSUE 50): the latent family's engine
    holds the serving leaves and none of the published pair, four a
    layer; a family without the entry keeps ``init_params``' tree."""
    from aigw_tpu.tpuserve.server import TPUServeServer

    server = TPUServeServer(model, EngineConfig(
        max_batch_size=2, max_seq_len=128, page_size=16,
        min_prefill_bucket=16, decode_steps_per_tick=4))
    try:
        params = server.engine.params
        assert server.weights_prepared_leaves == leaves
        spec = get_model_spec(model)
        like = jax.eval_shape(lambda: family_fns(spec.family).init_params(
            jax.random.PRNGKey(0), spec.config))
        if not leaves:
            assert set(params) == set(like)
            return
        assert set(like) - set(params) == {
            f"l{i}.{w}" for i in range(spec.config.num_hidden_layers)
            for w in ("wq_b", "wkv_b")}
        assert len(set(params) - set(like)) == leaves
        assert sum(v.nbytes for v in params.values()) == sum(
            np.prod(v.shape) * v.dtype.itemsize for v in like.values())
    finally:
        server.engine.stop()
