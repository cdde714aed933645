"""MoE at serving parity (ISSUE 18): tiny-moe through the FULL feature
stack on the modern program families.

The tentpole's verify bar: with both fallback-matrix family rows
deleted, the expert-parallel family must ride the ragged prefill stream
and the decode rung at full parity — byte-identical streams in
the deterministic f32 rig against the bucketed control across
the complete feature mix (speculating + penalized + constrained +
prefix-resume slots sharing one decode window), zero hot XLA compiles
after warmup, zero pipeline-draining state rebuilds. Plus the ISSUE 13
surface on the family: int8/int4 KV pages spill→revive, cross the
/kv/pages wire, and migrate BIT-exactly — the MoE MLP never touches
the paged KV contract, and these tests pin that.

The MoE routing-stats channel (per-expert placed counts + capacity
drops folded off every program) is asserted here too: the same tokens
must be accounted whichever program family served them.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import kvq, mixtral
from aigw_tpu.models.registry import family_fns, get_model_spec
from aigw_tpu.tpuserve import constrain
from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
from aigw_tpu.tpuserve.kvcache import page_chain_hashes
from aigw_tpu.tpuserve.sampling import SamplingParams
from aigw_tpu.tpuserve.tokenizer import ByteTokenizer

_SPEC = get_model_spec("tiny-moe")
CFG = _SPEC.config
TOK = ByteTokenizer()
EOS = (TOK.eos_id,)

_PARAMS_F32 = mixtral.init_params(jax.random.PRNGKey(7), CFG,
                                  jnp.float32)
_PARAMS_BF16 = None


def _params(f32: bool):
    global _PARAMS_BF16
    if f32:
        return _PARAMS_F32
    if _PARAMS_BF16 is None:
        _PARAMS_BF16 = mixtral.init_params(jax.random.PRNGKey(7), CFG)
    return _PARAMS_BF16


def _engine(f32=True, **over) -> Engine:
    cfg = dict(max_batch_size=4, max_seq_len=256, page_size=16,
               min_prefill_bucket=16, decode_steps_per_tick=4,
               prefill_chunk_tokens=64,
               kv_cache_dtype="float32" if f32 else "bfloat16",
               ragged_chunk_tokens=32, ragged_max_chunks=4,
               adaptive_decode_window=False)
    cfg.update(over)
    return Engine(_params(f32), CFG, EngineConfig(**cfg),
                  eos_token_ids=EOS, fns=family_fns("mixtral"))


def _run(eng: Engine, prompt, mt=8, sp=None, constraint=None):
    done = threading.Event()
    toks: list[int] = []

    def emit(t, f):
        if t >= 0:
            toks.append(t)
        if f is not None:
            done.set()

    eng.submit(GenRequest(prompt=list(prompt), max_tokens=mt,
                          sampling=sp or SamplingParams(temperature=0.0),
                          emit=emit, constraint=constraint))
    assert done.wait(timeout=900)
    assert eng.healthy, eng.last_error
    return toks


def _burst(eng: Engine, reqs: list[tuple], n: int = 6):
    """Submit (prompt, sampling, constraint) triples before the engine
    coalesces, wait for all — the slots genuinely share windows."""
    events, results = [], []
    for prompt, sp, cns in reqs:
        done = threading.Event()
        toks: list[int] = []

        def emit(t, f, toks=toks, done=done):
            if t >= 0:
                toks.append(t)
            if f is not None:
                done.set()

        eng.submit(GenRequest(prompt=list(prompt), max_tokens=n,
                              sampling=sp, emit=emit, constraint=cns))
        events.append(done)
        results.append(toks)
    for e in events:
        assert e.wait(timeout=900)
    assert eng.healthy, eng.last_error
    return results


_SCHEMA = {"type": "object", "properties": {
    "t": {"type": "string", "maxLength": 8},
}, "required": ["t"], "additionalProperties": False}


def _fsm():
    return constrain.compile_constraint(
        TOK, CFG.vocab_size, EOS,
        constrain.spec_for_response_format("json_schema", _SCHEMA))


_BASE = [5, 3, 8, 1, 9, 2, 4, 6] * 8  # 64 tokens = 4 full pages


def _full_mix(eng: Engine) -> list[list[int]]:
    """The acceptance window: speculating (repetitive greedy),
    penalized, constrained, and prefix-resume (page-aligned re-ask →
    full-hit 1-token resume) slots submitted as ONE burst."""
    return _burst(eng, [
        ([5, 6, 7, 8] * 10, SamplingParams(temperature=0.0), None),
        ([2, 9, 4, 4, 1, 7, 3], SamplingParams(
            temperature=0.0, frequency_penalty=0.6,
            presence_penalty=0.2), None),
        (TOK.encode("json now"), SamplingParams(
            temperature=0.0, logit_bias=((97, 100.0),)), _fsm()),
        (_BASE, SamplingParams(temperature=0.0), None),
    ], n=10)


def test_moe_ragged_fused_resolve_first_class():
    """Asserted from the resolver outputs: the family lands on
    pallas-ragged prefill and the page walk with no family-shaped
    reason, and the routing-stats channel is on."""
    eng = _engine(attention_backend="pallas-ragged")
    assert eng.attn.name == "pallas-ragged"
    assert eng.decode_attn_impl == "xla-walk"
    assert "family" not in eng.decode_attn_reason
    assert eng._moe and eng.fns.moe_stats
    assert eng._moe_experts == CFG.n_experts


def test_moe_ragged_byte_identical_quick():
    """Tier-1 identity probe on the family: ragged vs bucketed
    prefill, greedy + penalized, no warmup — the full feature mix +
    compile tripwire lives in the slow twin below."""
    control = _engine(attention_backend="xla-bucketed")
    child = _engine(attention_backend="pallas-ragged")
    for e in (control, child):
        e.start()
    try:
        reqs = [([5, 3, 8, 1, 9, 2, 4], SamplingParams(temperature=0.0),
                 None),
                ([7, 7, 2, 9, 4, 4], SamplingParams(
                    temperature=0.0, frequency_penalty=0.5), None)]
        got = _burst(child, reqs, n=5)
        want = _burst(control, reqs, n=5)
        assert got == want
        # the routing-stats channel folded on both program families.
        # Totals include PADDING rows, so bucketed (pads to power-of-2
        # buckets) legitimately counts more than ragged — assert the
        # shared floor (every real token × top-2 × layers, minus
        # capacity drops) instead of cross-backend equality.
        real = sum(len(p) for p, _sp, _c in reqs) + sum(
            max(len(t) - 1, 0) for t in got)
        for e in (child, control):
            placed = int(e._moe_expert_tokens.sum())
            floor = (real * CFG.experts_per_token * CFG.n_layers
                     - int(e._moe_layer_drops.sum()))
            assert placed >= floor, (placed, floor)
        assert int(child._moe_expert_tokens.sum()) <= int(
            control._moe_expert_tokens.sum())
    finally:
        control.stop()
        child.stop()


@pytest.mark.slow
def test_moe_full_mix_byte_identical_zero_hot_compiles():
    """Acceptance (ISSUE 18 tentpole): tiny-moe on ragged prefill
    streams byte-identically with the bucketed control across speculating + penalized + constrained +
    prefix-resume slots in one window, with zero hot compiles after
    warmup and state_rebuilds == 0."""
    control = _engine(attention_backend="xla-bucketed",
                      spec_tokens=3, spec_adaptive=False,
                      warm_prefill_buckets=2, warm_decode_buckets=3)
    child = _engine(attention_backend="pallas-ragged",
                    spec_tokens=3, spec_adaptive=False,
                    warm_prefill_buckets=2, warm_decode_buckets=3)
    assert child.decode_attn_impl == "xla-walk"
    assert control.decode_attn_impl == "xla-walk"
    for e in (control, child):
        e.warmup()
        e.start()
    try:
        # prime the programs warmup() does not own on BOTH engines: the
        # full-prefix hit's CoW copy_page and the constrained path's
        # mask machinery — control first, the compile tracker is
        # process-wide
        for e in (control, child):
            _run(e, _BASE)
            _run(e, _BASE)
            _run(e, TOK.encode("json now"), constraint=_fsm(),
                 sp=SamplingParams(temperature=0.0,
                                   logit_bias=((97, 100.0),)))
        want = _full_mix(control)
        cp = child.compile_tracker.checkpoint()
        got = _full_mix(child)
        assert got == want
        assert child.compile_tracker.compiles_since(cp) == 0, (
            "MoE ragged compiled on the hot path")
        assert child.stats.state_rebuilds == 0
    finally:
        control.stop()
        child.stop()


@pytest.mark.parametrize("qdt", [
    "int8", pytest.param("int4", marks=pytest.mark.slow)])
def test_moe_quantized_pages_serve_and_account(qdt):
    """int8/int4 KV pages on the family (the deleted resolver gate):
    the quantized pool serves end to end and /state's capacity math is
    the same layout formula as dense families'."""
    eng = _engine(f32=False, kv_cache_dtype=qdt, num_pages=24)
    assert eng.decode_attn_impl == "xla-walk"
    eng.start()
    try:
        toks = _run(eng, [4, 8, 15, 16, 23, 42], mt=4)
        assert 1 <= len(toks) <= 4
        eb = {"int8": 1.0, "int4": 0.5}[qdt]
        want = CFG.n_layers * 2 * CFG.n_kv_heads * (
            CFG.head_dim * eb + 4)
        assert eng.stats.kv_bytes_per_token == pytest.approx(want)
        assert eng.stats.kv_quant_bits == {"int8": 8, "int4": 4}[qdt]
    finally:
        eng.stop()


def _quant_engine(**over):
    return _engine(f32=False, kv_cache_dtype="int8", num_pages=24,
                   kv_host_bytes=1 << 24, warm_prefill_buckets=2,
                   **over)


@pytest.mark.slow
def test_moe_quantized_spill_revive_bit_exact():
    """Host-tier spill→revive on the family round-trips int8 pages +
    scales BIT-exactly and the revived chain serves byte-identically."""
    eng = _quant_engine()
    eng.start()
    eng.warmup()
    try:
        shared = [5] * 64  # 4 full pages
        first = _run(eng, shared + [9, 9])
        keys = page_chain_hashes(shared + [9, 9], 16)
        page0 = eng.prefix_cache._by_key[keys[0]]
        before = kvq.page_to_host(eng._export_page_dev(page0))
        for i in range(14):  # flood → spill
            _run(eng, [10 + i] * 48 + [1], mt=2)
        assert eng.host_tier.spills > 0
        spilled = eng.host_tier.get(keys[0])
        assert isinstance(spilled, dict), (
            "quantized page must spill at native dtype + scales")
        np.testing.assert_array_equal(spilled["q"], before["q"])
        np.testing.assert_array_equal(spilled["scale"], before["scale"])
        second = _run(eng, shared + [9, 9])
        assert second == first, "revived quantized chain diverged"
        assert eng.host_tier.revives >= 4
    finally:
        eng.stop()


def _migrate(a: Engine, b: Engine, prompt: list[int], mt: int = 24):
    """Cut a session mid-decode on `a`, import its chain into `b`,
    resume there. Returns (export blob dict, merged token stream)."""
    from aigw_tpu.tpuserve.engine import (
        MigrationError,
        continuation_request,
    )

    for _attempt in range(4):  # export can race the finish
        got: list[int] = []
        cut = threading.Event()
        fin = threading.Event()

        def emit(t, f, got=got, cut=cut, fin=fin):
            if t >= 0:
                got.append(t)
            if len(got) >= 4:
                cut.set()
            if f is not None:
                fin.set()

        req = GenRequest(prompt=list(prompt), max_tokens=mt,
                         sampling=SamplingParams(temperature=0.0),
                         emit=emit)
        a.submit(req)
        assert cut.wait(timeout=900)
        try:
            out = a.migrate_export(req)
            break
        except MigrationError as e:
            assert "finished" in str(e) or "not active" in str(e), e
            assert fin.wait(timeout=900)
    else:
        raise AssertionError("export never won the race")
    b.migrate_import(out["blob"]["tokens"], out["data"])
    done = threading.Event()
    tail: list[int] = []

    def emit2(t, f):
        if t >= 0:
            tail.append(t)
        if f is not None:
            done.set()

    b.submit(continuation_request(out["blob"], emit=emit2))
    assert done.wait(timeout=900)
    assert b.healthy, b.last_error
    return out, out["blob"]["tokens"][len(prompt):] + tail


@pytest.mark.slow
def test_moe_quantized_wire_and_migration_pages_bit_exact():
    """The cross-replica /kv/pages wire and the migration export/import
    path move the family's int8 pages (q + scales) without re-rounding:
    every page that crosses either path lands in the sibling's pool
    bit-identical, and both replicas serve the shared chain the same.

    Deliberately NOT asserted here: solo-vs-migrated STREAM identity on
    int8 engines. The wire rule ships only complete pages; the importer
    recomputes the ≤ one-page token tail via offset resume, and fresh
    quantization of that tail is not bit-stable against decode-written
    rows (the suffix program quantizes activations that attended over
    raw in-suffix K/V, decode attends over dequantized rows — holds for
    llama too, q rows differ by up to 3 LSBs). Stream identity is the
    f32 rig's contract, pinned in the next test."""
    from aigw_tpu.tpuserve.server import decode_wire_page, encode_wire_page

    a, b = _quant_engine(), _quant_engine()
    for e in (a, b):
        e.start()
        e.warmup()
    try:
        # wire round-trip: pages exported by chain hash survive
        # encode/decode bit-exactly and import into a sibling
        shared = [6] * 64
        _run(a, shared + [2, 2])
        keys = page_chain_hashes(shared + [2, 2], 16)
        pages = a.kv_export_pages(keys[:4])
        assert len(pages) == 4
        wired = []
        for _k, host in pages:
            w = decode_wire_page(encode_wire_page(host))
            np.testing.assert_array_equal(w["q"], host["q"])
            np.testing.assert_array_equal(w["scale"], host["scale"])
            wired.append(w)
        assert b.kv_import_pages(shared + [2, 2], wired) == 4
        assert _run(b, shared + [2, 2]) == _run(a, shared + [2, 2])

        out, merged = _migrate(a, b, [4] * 40 + [1, 2, 3])
        assert len(merged) == 24
        # the migrated pages sit in b's pool bit-identical to a's export
        mig_keys = page_chain_hashes(out["blob"]["tokens"], 16)
        for key, host in zip(mig_keys, out["data"]):
            page = b.prefix_cache._by_key[key]
            dev = kvq.page_to_host(b._export_page_dev(page))
            np.testing.assert_array_equal(dev["q"], host["q"])
            np.testing.assert_array_equal(dev["scale"], host["scale"])
    finally:
        for e in (a, b):
            e.stop()


@pytest.mark.slow
def test_moe_migration_resume_byte_identical_f32():
    """In the deterministic rig (f32 params + f32 KV pool) a session cut
    mid-decode on one MoE replica and resumed on another yields the
    byte-identical stream a solo run produces — routing decisions and
    the recomputed partial-page tail both reproduce exactly."""
    mk = lambda: _engine(num_pages=24,  # noqa: E731
                         warm_prefill_buckets=2)
    solo, a, b = mk(), mk(), mk()
    for e in (solo, a, b):
        e.start()
        e.warmup()
    try:
        prompt = [4] * 40 + [1, 2, 3]
        want = _run(solo, prompt, mt=24)
        _out, merged = _migrate(a, b, prompt)
        assert merged == want
    finally:
        for e in (solo, a, b):
            e.stop()


def test_moe_routing_stats_fold_and_refresh():
    """The routing-stats accumulators feed the EngineStats scalars:
    placed totals, dropped totals, the drop fraction, and the
    hottest-expert imbalance ratio — computed after the engine thread
    joins (refresh is engine-thread-only while the loop is live)."""
    eng = _engine(attention_backend="pallas-ragged")
    eng.start()
    try:
        _run(eng, [3, 1, 4, 1, 5, 9, 2, 6] * 4, mt=6)
    finally:
        eng.stop()
    eng._refresh_stats()
    s = eng.stats
    assert s.moe_tokens_routed == int(eng._moe_expert_tokens.sum())
    assert s.moe_tokens_routed > 0
    assert s.moe_tokens_dropped == int(eng._moe_layer_drops.sum())
    total = s.moe_tokens_routed + s.moe_tokens_dropped
    assert s.moe_dropped_frac == pytest.approx(
        s.moe_tokens_dropped / total, abs=1e-6)
    mean = s.moe_tokens_routed / CFG.n_experts
    assert s.moe_expert_imbalance == pytest.approx(
        float(eng._moe_expert_tokens.max()) / mean, abs=1e-3)
    # the list accessors mirror the accumulators ([] on dense families
    # is pinned by the /state drift smoke)
    assert eng.moe_expert_load() == [
        int(x) for x in eng._moe_expert_tokens]
    assert eng.moe_layer_drops() == [
        int(x) for x in eng._moe_layer_drops]


def test_decode_windows_count_the_experts_their_live_rows_hit():
    """ISSUE 41: a single-chip decode step loops over the experts its
    live rows hit, and the trip count rides the decode tape into
    ``moe_held_hits_decode`` (the counter behind /state's key and the
    benchmark's ``moe_held_experts_hit.mixtral``). One request is one
    live row: every layer of every step in which it is live hits its
    top-k experts and nothing else, whatever the other three slots
    hold, and the fence drops nothing."""
    eng = _engine()
    eng.start()
    try:
        toks = _run(eng, [3, 1, 4, 1, 5, 9, 2, 6] * 2, mt=9)
        _run(eng, [2, 7, 1, 8] * 3, mt=5)
    finally:
        eng.stop()  # (a window's fold settles after its last emit)
    st = eng.stats
    K, L = CFG.experts_per_token, CFG.n_layers
    # a request's first token is its prefill's; the others are decode
    # steps of one live row
    assert len(toks) == 9
    assert st.moe_held_hits_decode == K * L * (8 + 4)
    # one live row: the experts hit are the assignments routed, and
    # all were placed
    assert st.moe_total_assignments == st.moe_held_hits_decode
    assert st.moe_local_assignments == st.moe_total_assignments
    assert st.decode_steps >= 12
