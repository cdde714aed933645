"""Long-context serving: sequence-sharded chunked prefill (ISSUE 17).

The chunked-sp path must be the SAME engine three ways: in the
deterministic f32 rig, a chunked sp=8 engine, a monolithic sp=8 engine,
and a single-device engine must stream BYTE-IDENTICAL tokens across a
mixed-feature burst — greedy, seeded sampling, penalties, speculation,
a grammar-constrained slot, and a partial-prefix-hit resume that enters
the chunk loop at a page-aligned offset — with zero pipeline-draining
state rebuilds.

Plus the kernel itself: ``ring_attention_prefix`` vs a dense reference
at misaligned resume offsets (page-aligned but NOT shard- or chunk-
aligned), including the production llama-3-8B attention extents at 32k
(slow), the decode-liveness mechanism (``_admit_interactive`` serves a
short arrival mid-long-prefill), and the CompileTracker tripwire at
32k geometry (slow).
"""

from __future__ import annotations

import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import llama
from aigw_tpu.ops.ring_attention import ring_attention_prefix
from aigw_tpu.parallel import MeshSpec, make_mesh
from aigw_tpu.tpuserve import constrain
from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
from aigw_tpu.tpuserve.sampling import SamplingParams
from aigw_tpu.tpuserve.tokenizer import ByteTokenizer

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 (virtual) devices")

#: page_size 16 % sp 8 == 0 → the chunked suffix program builds
_CFG = llama.LlamaConfig(
    vocab_size=512, dim=64, n_layers=2, n_heads=8, n_kv_heads=8,
    ffn_dim=128, max_seq_len=256, rope_theta=10000.0,
)
_PARAMS_F32 = llama.init_params(jax.random.PRNGKey(7), _CFG, jnp.float32)
_TOK = ByteTokenizer()
_RNG = np.random.RandomState(29)
_PROMPTS = {L: _RNG.randint(1, 500, L).tolist()
            for L in (9, 24, 120, 150, 200)}


def _mk_engine(sp: int, **over) -> Engine:
    """sp=0 → single-device; sp=8 → sequence-sharded over the virtual
    mesh. CPU-scale chunk geometry: prompts ≥ 96 tokens take the sp
    path in 64-token ring chunks."""
    cfg = dict(max_batch_size=4, max_seq_len=256, page_size=16,
               min_prefill_bucket=16, decode_steps_per_tick=4,
               kv_cache_dtype="float32", spec_tokens=4,
               adaptive_decode_window=False,
               sp_prefill_min_tokens=96, sp_chunk_tokens=64)
    cfg.update(over)
    return Engine(
        _PARAMS_F32, _CFG, EngineConfig(**cfg),
        eos_token_ids=(_TOK.eos_id,),
        mesh=make_mesh(MeshSpec(dp=1, tp=1, sp=sp)) if sp else None)


def _burst(eng: Engine, reqs: list[tuple[list, SamplingParams, object]],
           n: int = 8) -> list[list[int]]:
    events, results = [], []
    for prompt, sp, cn in reqs:
        done = threading.Event()
        toks: list[int] = []

        def emit(t, f, toks=toks, done=done):
            if t >= 0:
                toks.append(t)
            if f is not None:
                done.set()

        eng.submit(GenRequest(prompt=prompt, max_tokens=n, sampling=sp,
                              emit=emit, constraint=cn))
        events.append(done)
        results.append(toks)
    for e in events:
        assert e.wait(timeout=900)
    return results


def _greedy(**kw) -> SamplingParams:
    return SamplingParams(temperature=0.0, **kw)


def _fsm():
    schema = {"type": "object", "properties": {
        "t": {"type": "string", "maxLength": 8},
    }, "required": ["t"], "additionalProperties": False}
    return constrain.compile_constraint(
        _TOK, _CFG.vocab_size, (_TOK.eos_id,),
        constrain.spec_for_response_format("json_schema", schema))


# -- kernel: chunk attention with cached-prefix resume -----------------


def _ref_chunk_attention(q, k, v, kc, vc, prefix_lens):
    """Dense reference: softmax over [context[:pl] ++ chunk] for the
    chunk queries, chunk-causal within the chunk."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    outs = []
    for b in range(B):
        pl = int(prefix_lens[b])
        keys = np.concatenate([kc[b, :pl], k[b]], axis=0)
        vals = np.concatenate([vc[b, :pl], v[b]], axis=0)
        qg = q[b].reshape(S, Hkv, g, D)
        logits = np.einsum("shgd,thd->hgst", qg, keys) / math.sqrt(D)
        jpos = np.arange(pl + S)
        mask = jpos[None, :] <= (pl + np.arange(S))[:, None]
        logits = np.where(mask[None, None], logits, -1e30)
        logits -= logits.max(axis=-1, keepdims=True)
        probs = np.exp(logits)
        probs /= probs.sum(axis=-1, keepdims=True)
        out = np.einsum("hgst,thd->shgd", probs, vals)
        outs.append(out.reshape(S, H * D))
    return np.stack(outs)


@pytest.mark.parametrize("prefix_lens", [(72, 0), (40, 104)])
def test_ring_prefix_matches_reference_misaligned(prefix_lens):
    """ring_attention_prefix at offsets that are page-aligned (8-token
    pages) but NOT multiples of the per-device shard (T_loc = 16) or
    the chunk — the masks, not the layout, must carry the offset. The
    pl=0 row doubles as the accumulator-seeding regression: a fully
    masked context window must contribute exactly nothing."""
    B, S, H, Hkv, D, T = 2, 64, 4, 2, 32, 128
    key = jax.random.PRNGKey(3)
    kq, kk, kv, kkc, kvc = jax.random.split(key, 5)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, Hkv, D), jnp.float32)
    kc = jax.random.normal(kkc, (B, T, Hkv, D), jnp.float32)
    vc = jax.random.normal(kvc, (B, T, Hkv, D), jnp.float32)
    mesh = make_mesh(MeshSpec(dp=1, tp=1, sp=8))
    got = ring_attention_prefix(
        q, k, v, kc, vc, jnp.asarray(prefix_lens, jnp.int32), mesh=mesh)
    want = _ref_chunk_attention(
        np.asarray(q), np.asarray(k), np.asarray(v),
        np.asarray(kc), np.asarray(vc), prefix_lens)
    np.testing.assert_allclose(np.asarray(got), want,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_ring_prefix_production_shape_32k():
    """The shape the long-context path actually serves: llama-3-8B
    attention extents (H=32, Hkv=8, D=128), a 512-token chunk resuming
    at a 32k-scale offset that is 128-token-page-aligned (251 pages =
    32128 tokens) but misaligned vs the 4032-token per-device window
    shard. Reference streams per KV head to bound memory."""
    B, S, H, Hkv, D = 1, 512, 32, 8, 128
    T, pl = 32256, 32128  # window 252 pages; resume at page 251
    assert T % 8 == 0 and pl % 128 == 0 and pl % (T // 8) != 0
    key = jax.random.PRNGKey(17)
    kq, kk, kv, kkc, kvc = jax.random.split(key, 5)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, Hkv, D), jnp.float32)
    kc = jax.random.normal(kkc, (B, T, Hkv, D), jnp.float32)
    vc = jax.random.normal(kvc, (B, T, Hkv, D), jnp.float32)
    mesh = make_mesh(MeshSpec(dp=1, tp=1, sp=8))
    got = np.asarray(ring_attention_prefix(
        q, k, v, kc, vc, jnp.asarray([pl], jnp.int32), mesh=mesh))

    g = H // Hkv
    keys = jnp.concatenate([kc[0, :pl], k[0]], axis=0)  # [pl+S, Hkv, D]
    vals = jnp.concatenate([vc[0, :pl], v[0]], axis=0)
    qg = q[0].reshape(S, Hkv, g, D)
    mask = jnp.arange(pl + S)[None, :] <= (pl + jnp.arange(S))[:, None]
    want = np.empty((S, Hkv, g, D), np.float32)
    for h in range(Hkv):
        logits = jnp.einsum("sgd,td->gst", qg[:, h], keys[:, h],
                            preferred_element_type=jnp.float32)
        logits = jnp.where(mask[None], logits / math.sqrt(D), -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        want[:, h] = np.asarray(
            jnp.einsum("gst,td->sgd", probs, vals[:, h]))
    np.testing.assert_allclose(got[0], want.reshape(S, H * D),
                               rtol=2e-3, atol=2e-3)


# -- engine: three-way byte identity -----------------------------------


def test_three_way_byte_identical_mixed_features():
    """The acceptance batch: chunked-sp, monolithic-sp, and single-
    device engines stream identical tokens across greedy long prompts,
    a speculating slot, seeded sampling and penalties on sp-length
    prompts, a constrained slot, and a partial-hit resume whose suffix
    re-enters the sp chunk loop at the adopted page offset."""
    engines = {"chunked": _mk_engine(8),
               "mono": _mk_engine(8, sp_prefill_mode="monolithic"),
               "single": _mk_engine(0)}
    base = _PROMPTS[200]
    resumed = base[:112] + _PROMPTS[120]  # 7 pages adopted, 120 suffix
    rep = [5, 6, 7, 8] * 14
    out = {}
    for name, eng in engines.items():
        eng.start()
        try:
            first = _burst(eng, [
                (base, _greedy(), None),                    # seeds cache
                (rep, _greedy(), None),                     # speculating
                (_PROMPTS[120], SamplingParams(
                    temperature=0.8, top_p=0.9, seed=1234), None),
                (_PROMPTS[150], _greedy(frequency_penalty=0.7), None),
            ])
            second = _burst(eng, [
                (resumed, _greedy(), None),                 # partial hit
                (_TOK.encode("longctx json"), _greedy(), _fsm()),
                (_PROMPTS[9], _greedy(), None),
                (_PROMPTS[24], _greedy(logit_bias=((42, 3.0),)), None),
            ], n=16)
            out[name] = first + second
            assert eng.healthy, eng.last_error
            assert eng.stats.prefix_cache_hits >= 1, "resume not taken"
            assert eng.stats.spec_drafted > 0
            assert eng.stats.state_rebuilds == 0
        finally:
            eng.stop()
    assert out["chunked"] == out["single"]
    assert out["mono"] == out["single"]
    ch = engines["chunked"].stats
    assert ch.sp_chunked_prefills >= 3   # base + sampled + penalized
    assert ch.sp_resume_prefills >= 1    # the offset resume
    mono = engines["mono"].stats
    assert mono.sp_prefills >= 1 and mono.sp_chunked_prefills == 0
    # the padding tax: the chunk ladder pads a tail rung, the
    # monolithic program the whole top rung, on the same real tokens
    assert ch.prefill_tokens_real == mono.prefill_tokens_real
    assert ch.prefill_tokens_padded < mono.prefill_tokens_padded, (
        ch.prefill_tokens_padded, mono.prefill_tokens_padded)


def test_interactive_admission_mid_prefill():
    """Decode liveness: a short arrival queued while a long chunked-sp
    prefill is in flight must admit at a chunk boundary and stream its
    first token BEFORE the long prompt's. The boundary hook makes
    the ordering deterministic: the engine thread pauses at the first
    chunk boundary until the short request is queued."""
    eng = _mk_engine(8)
    eng.start()
    orig = eng._admit_interactive
    at_boundary, short_queued = threading.Event(), threading.Event()

    def hooked():
        if not at_boundary.is_set():
            at_boundary.set()
            short_queued.wait(timeout=30)
        return orig()

    eng._admit_interactive = hooked
    times: dict[str, float] = {}
    done: dict[str, threading.Event] = {
        "long": threading.Event(), "short": threading.Event()}

    def emit_for(name):
        def emit(t, f):
            if t >= 0 and name not in times:
                times[name] = time.monotonic()
            if f is not None:
                done[name].set()
        return emit

    try:
        eng.submit(GenRequest(prompt=_PROMPTS[200], max_tokens=8,
                              sampling=_greedy(), emit=emit_for("long")))
        assert at_boundary.wait(timeout=60), "chunk loop never ticked"
        eng.submit(GenRequest(prompt=_PROMPTS[24], max_tokens=4,
                              sampling=_greedy(),
                              emit=emit_for("short")))
        short_queued.set()
        assert done["short"].wait(timeout=120)
        assert done["long"].wait(timeout=120)
    finally:
        eng.stop()
    assert eng.healthy, eng.last_error
    assert eng.stats.sp_interactive_admits >= 1
    assert times["short"] < times["long"], times


def test_interactive_stream_survives_long_install():
    """Slot-reservation regression: _admit_one picks its slot index at
    entry but installs the _Slot only after the prefill, and the sp
    chunk loop re-enters admission at boundaries — a short admitted
    mid-prefill must land in a DIFFERENT slot. Without the reservation
    both picked the first free index and the long prefill's install
    orphaned the short mid-stream (client hang, leaked pages). The
    short here outlives the boundary decode budget (max_tokens well
    past the remaining chunk ticks), so it completes only if its slot
    survives the install."""
    eng = _mk_engine(8)
    eng.start()
    orig = eng._admit_interactive
    at_boundary, short_queued = threading.Event(), threading.Event()

    def hooked():
        if not at_boundary.is_set():
            at_boundary.set()
            short_queued.wait(timeout=30)
        return orig()

    eng._admit_interactive = hooked
    done = {"long": threading.Event(), "short": threading.Event()}
    toks = {"long": [], "short": []}

    def emit_for(name):
        def emit(t, f):
            if t >= 0:
                toks[name].append(t)
            if f is not None:
                done[name].set()
        return emit

    try:
        eng.submit(GenRequest(prompt=_PROMPTS[200], max_tokens=8,
                              sampling=_greedy(),
                              emit=emit_for("long")))
        assert at_boundary.wait(timeout=60), "chunk loop never ticked"
        eng.submit(GenRequest(prompt=_PROMPTS[24], max_tokens=32,
                              sampling=_greedy(),
                              emit=emit_for("short")))
        short_queued.set()
        assert done["long"].wait(timeout=120)
        assert done["short"].wait(timeout=120), (
            "short stream orphaned by the long prefill's slot install")
    finally:
        eng.stop()
    assert eng.healthy, eng.last_error
    assert eng.stats.sp_interactive_admits >= 1
    # the short decoded PAST the long's install — the collision window
    assert len(toks["long"]) == 8, toks["long"]
    assert len(toks["short"]) >= 16, len(toks["short"])


@pytest.mark.slow
def test_chunked_sp_zero_hot_compiles_32k_geometry():
    """CompileTracker tripwire at 32k geometry: after warmup() (chunk
    program + tail rungs × eligible page buckets + the pow2 decode
    ladder), a 4.5k-token chunked prefill, an offset resume, a short
    interactive admission, and the decode that follows add ZERO XLA
    compiles — the warm surface stays log-sized instead of warming a
    32k monolithic rung."""
    cfg32 = llama.LlamaConfig(
        vocab_size=512, dim=64, n_layers=2, n_heads=8, n_kv_heads=8,
        ffn_dim=128, max_seq_len=32768, rope_theta=10000.0)
    params = llama.init_params(jax.random.PRNGKey(11), cfg32,
                               jnp.float32)
    eng = Engine(
        params, cfg32,
        EngineConfig(max_batch_size=2, max_seq_len=32768, page_size=128,
                     min_prefill_bucket=64, decode_steps_per_tick=4,
                     kv_cache_dtype="float32", spec_tokens=0,
                     adaptive_decode_window=False, num_pages=320,
                     sp_prefill_min_tokens=1024, sp_chunk_tokens=2048,
                     warm_prefill_buckets=2, warm_decode_buckets=7),
        eos_token_ids=(_TOK.eos_id,),
        mesh=make_mesh(MeshSpec(dp=1, tp=1, sp=8)))
    eng.warmup()
    eng.start()
    long = _RNG.randint(1, 500, 4500).tolist()
    try:
        cp = eng.compile_tracker.checkpoint()
        _burst(eng, [(long, _greedy(), None)], n=4)
        _burst(eng, [
            # 16 pages adopted (2048 tokens), 2452-token sp resume
            (long[:2048] + _RNG.randint(1, 500, 2452).tolist(),
             _greedy(), None),
            (_PROMPTS[24], _greedy(), None),  # interactive singleton
        ], n=4)
        assert eng.healthy, eng.last_error
        assert eng.compile_tracker.compiles_since(cp) == 0, (
            eng.compile_tracker.snapshot())
    finally:
        eng.stop()
    assert eng.stats.sp_chunked_prefills >= 2
    assert eng.stats.sp_resume_prefills >= 1
    assert eng.stats.state_rebuilds == 0
