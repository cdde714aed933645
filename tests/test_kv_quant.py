"""Quantized KV pages (ISSUE 13).

Two layers under test:

- **quantized pages through the stack** — int8/int4 pools serve,
  spill→revive and the cross-replica /kv/pages wire round-trip pages
  BIT-exactly (scales included), migration moves quantized sessions,
  and the capacity math (kv_bytes_per_token, kv_quant_bits) is what
  /state advertises;
- **quality smoke** — teacher-forced logits through a quantized KV
  pool stay correlated with the native pool (the PR 9 int4-weight
  smoke's bar: structural sanity on worst-case random weights, not
  production quality).
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import kvq, llama
from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
from aigw_tpu.tpuserve.kvcache import page_chain_hashes
from aigw_tpu.tpuserve.sampling import SamplingParams

_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
    return _PARAMS


def _engine(**over) -> Engine:
    cfg = EngineConfig(**{**dict(
        max_batch_size=2, max_seq_len=256, page_size=16,
        min_prefill_bucket=16, decode_steps_per_tick=4,
        kv_cache_dtype="bfloat16",
        adaptive_decode_window=False), **over})
    return Engine(_params(), llama.TINY, cfg, eos_token_ids=(257,))


def _run(eng: Engine, prompt, mt=8, sp=None):
    done = threading.Event()
    toks: list[int] = []

    def emit(t, f):
        if t >= 0:
            toks.append(t)
        if f is not None:
            done.set()

    eng.submit(GenRequest(prompt=list(prompt), max_tokens=mt,
                          sampling=sp or SamplingParams(temperature=0.0),
                          emit=emit))
    assert done.wait(timeout=600)
    assert eng.healthy, eng.last_error
    return toks


@pytest.mark.parametrize("qdt", ["int8", "int4"])
def test_quantized_engine_serves_and_accounts(qdt):
    """int8/int4 pools serve end-to-end; /state capacity math matches
    the layout: bytes/token = L*2*Hkv*(D*b + 4), quant bits exported."""
    eng = _engine(kv_cache_dtype=qdt)
    assert eng.decode_attn_impl == "xla-walk"
    eng.start()
    try:
        toks = _run(eng, [5, 3, 8, 1], mt=6)
        assert len(toks) >= 1
        mc = llama.TINY
        per_elt = {"int8": 1.0, "int4": 0.5}[qdt]
        want = mc.n_layers * 2 * mc.n_kv_heads * (
            mc.head_dim * per_elt + 4)
        assert eng.stats.kv_bytes_per_token == pytest.approx(want)
        assert eng.stats.kv_quant_bits == {"int8": 8, "int4": 4}[qdt]
    finally:
        eng.stop()


def test_int8_bytes_per_token_under_055_of_native():
    """The capacity claim at serving head_dim (>= 64): an int8 page
    (rows + f32 scale blocks) costs <= 0.55x the bf16 page."""
    cfg = llama.LlamaConfig(vocab_size=256, dim=256, n_heads=4,
                            n_kv_heads=2, n_layers=2, ffn_dim=256,
                            max_seq_len=256)
    assert cfg.head_dim == 64

    def bpt(dtype):
        e = Engine(llama.init_params(jax.random.PRNGKey(1), cfg),
                   cfg, EngineConfig(
                       max_batch_size=1, max_seq_len=256, page_size=16,
                       min_prefill_bucket=16, kv_cache_dtype=dtype))
        return e.stats.kv_bytes_per_token

    assert bpt("int8") / bpt("bfloat16") <= 0.55
    assert bpt("int4") / bpt("bfloat16") <= 0.30


@pytest.mark.parametrize("qdt", ["int8", "int4"])
def test_teacher_forced_quality_smoke(qdt):
    """PR 9-style quality smoke: teacher-forced decode logits through
    a quantized KV pool stay correlated with the native pool (random
    gaussian K/V are the worst case for 4-bit; real checkpoints
    quantize far better — the bar is structural sanity)."""
    cfg = llama.TINY
    params = _params()
    ps = 16
    kv_shape = (cfg.n_layers, 2, 9 * ps, cfg.n_kv_heads, cfg.head_dim)
    pt = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    prompts = jnp.asarray(
        [[3, 1, 4, 1, 5, 0, 0, 0], [2, 7, 1, 8, 2, 8, 1, 8]], jnp.int32)
    lens = jnp.asarray([5, 8], jnp.int32)
    native = kvq.make_pool(kv_shape, "bfloat16")
    quant = kvq.make_pool(kv_shape, qdt)
    lf, native = llama.prefill(params, cfg, prompts, lens, native, pt, ps)
    lq, quant = llama.prefill(params, cfg, prompts, lens, quant, pt, ps)
    # teacher-forced: feed the NATIVE pool's greedy continuation to
    # both pools and compare the per-step distributions
    positions = lens
    tok = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    active = jnp.asarray([True, True])
    corrs, top5 = [], []
    for _ in range(8):
        lf, native = llama.decode_step(params, cfg, tok, positions,
                                       native, pt, ps, active)
        lq, quant = llama.decode_step(params, cfg, tok, positions,
                                      quant, pt, ps, active)
        a, b = np.asarray(lf, np.float32), np.asarray(lq, np.float32)
        corrs.append(np.corrcoef(a.ravel(), b.ravel())[0, 1])
        for r in range(a.shape[0]):
            ta = set(np.argsort(a[r])[-5:].tolist())
            tb = set(np.argsort(b[r])[-5:].tolist())
            top5.append(len(ta & tb) / 5.0)
        tok = jnp.argmax(lf, axis=-1).astype(jnp.int32)
        positions = positions + 1
    floor = 0.95 if qdt == "int8" else 0.85
    assert float(np.mean(corrs)) > floor, np.mean(corrs)
    assert float(np.mean(top5)) >= (0.7 if qdt == "int8" else 0.5)


class TestQuantizedRoundTrips:
    """Spill→revive and the cross-replica wire must round-trip
    quantized pages BIT-exactly, scales included."""

    def _quant_engine(self, qdt, **over):
        return _engine(kv_cache_dtype=qdt, num_pages=24,
                       kv_host_bytes=1 << 24,
                       warm_prefill_buckets=2, **over)

    @pytest.mark.parametrize("qdt", [
        "int8", pytest.param("int4", marks=pytest.mark.slow)])
    def test_spill_revive_bit_exact(self, qdt):
        eng = self._quant_engine(qdt)
        eng.start()
        eng.warmup()
        try:
            shared = [5] * 64  # 4 full pages
            _run(eng, shared + [9, 9])  # cold: registers the chain
            # like with like: a stream RESUMED from the resident
            # quantized pages vs one resumed from the revived pages —
            # both attend the dequantized prefix. (A cold prefill
            # attends its prompt's K/V in registers at bf16, so on
            # random weights its stream legitimately differs from
            # either resume: near-tied logits flip on the int8
            # rounding.)
            first = _run(eng, shared + [9, 9])
            keys = page_chain_hashes(shared + [9, 9], 16)
            # snapshot the resident page bytes BEFORE eviction
            page0 = eng.prefix_cache._by_key[keys[0]]
            before = kvq.page_to_host(eng._export_page_dev(page0))
            for i in range(14):  # flood → spill
                _run(eng, [10 + i] * 48 + [1], mt=2)
            assert eng.host_tier.spills > 0
            spilled = eng.host_tier.get(keys[0])
            assert isinstance(spilled, dict), "quantized page must " \
                "spill at native dtype + scales, not re-rounded f32"
            np.testing.assert_array_equal(spilled["q"], before["q"])
            np.testing.assert_array_equal(spilled["scale"],
                                          before["scale"])
            second = _run(eng, shared + [9, 9])
            assert second == first, "revived quantized chain diverged"
            assert eng.host_tier.revives >= 4
            # the revived device page is bit-identical too
            page1 = eng.prefix_cache._by_key[keys[0]]
            after = kvq.page_to_host(eng._export_page_dev(page1))
            np.testing.assert_array_equal(after["q"], before["q"])
            np.testing.assert_array_equal(after["scale"],
                                          before["scale"])
        finally:
            eng.stop()

    @pytest.mark.slow
    def test_wire_roundtrip_bit_exact(self):
        """encode_wire_page/decode_wire_page and the migration import
        path carry int8 pages + scales without re-rounding."""
        from aigw_tpu.tpuserve.server import (
            decode_wire_page,
            encode_wire_page,
        )

        eng = self._quant_engine("int8")
        eng.start()
        eng.warmup()
        try:
            shared = [6] * 64
            _run(eng, shared + [2, 2])
            keys = page_chain_hashes(shared + [2, 2], 16)
            pages = eng.kv_export_pages(keys[:4])
            assert len(pages) == 4
            for _k, host in pages:
                wired = decode_wire_page(encode_wire_page(host))
                np.testing.assert_array_equal(wired["q"], host["q"])
                np.testing.assert_array_equal(wired["scale"],
                                              host["scale"])
            # a second quantized engine imports the chain and serves
            # the identical continuation (fleet-fetch lifecycle)
            sib = self._quant_engine("int8")
            sib.start()
            sib.warmup()
            try:
                n = sib.kv_import_pages(
                    shared + [2, 2],
                    [decode_wire_page(encode_wire_page(h))
                     for _k, h in pages])
                assert n == 4
                assert _run(sib, shared + [2, 2]) == _run(
                    eng, shared + [2, 2])
            finally:
                sib.stop()
        finally:
            eng.stop()

    @pytest.mark.slow
    def test_native_pool_refuses_quantized_page(self):
        """Dtype-mismatch guard: a quantized page must not silently
        scatter into a native pool."""
        from aigw_tpu.tpuserve.engine import MigrationError

        eng = self._quant_engine("int8")
        nat = _engine(num_pages=24)
        eng.start()
        nat.start()
        eng.warmup()
        try:
            shared = [6] * 64
            _run(eng, shared + [2, 2])
            keys = page_chain_hashes(shared + [2, 2], 16)
            pages = eng.kv_export_pages(keys[:2])
            with pytest.raises((MigrationError, TimeoutError)):
                nat.kv_import_pages(shared + [2, 2],
                                    [h for _k, h in pages])
        finally:
            eng.stop()
            nat.stop()


@pytest.mark.slow
def test_quantized_migration_roundtrip():
    """A quantized session migrates between two int8 engines and the
    resumed stream continues byte-identically with a solo run."""
    from aigw_tpu.tpuserve.engine import continuation_request

    def mk():
        return _engine(kv_cache_dtype="int8", num_pages=32,
                       warm_prefill_buckets=2)

    from aigw_tpu.tpuserve.engine import MigrationError

    solo, a, b = mk(), mk(), mk()
    for e in (solo, a, b):
        e.start()
        e.warmup()
    try:
        prompt = [4] * 40 + [1, 2, 3]
        want = _run(solo, prompt, mt=24)

        for attempt in range(4):  # export can race the finish
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

            req = GenRequest(prompt=list(prompt) + [attempt] * 0,
                             max_tokens=24,
                             sampling=SamplingParams(temperature=0.0),
                             emit=emit)
            a.submit(req)
            assert cut.wait(timeout=600)
            try:
                out = a.migrate_export(req)
                break
            except MigrationError as e:
                assert "finished" in str(e) or "not active" in str(e), e
                assert fin.wait(timeout=600)
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

        cont = continuation_request(out["blob"], emit=emit2)
        b.submit(cont)
        assert done.wait(timeout=600)
        assert b.healthy, b.last_error
        merged = out["blob"]["tokens"][len(prompt):] + tail
        assert merged == want
    finally:
        for e in (solo, a, b):
            e.stop()
