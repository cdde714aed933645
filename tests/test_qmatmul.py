"""W8A16 Pallas matmul kernel tests (interpret mode on the CPU fake
chip; the chip smoke's kernels child runs the same parity check
compiled at every served weight shape)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import llama
from aigw_tpu.models.quant import quantize_params
from aigw_tpu.ops.pallas import qmatmul

# dims aligned for the pallas path (all matrices multiples of 128)
ALIGNED = llama.LlamaConfig(
    vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=256, max_seq_len=256, rope_theta=10000.0,
)


class TestKernel:
    @pytest.mark.parametrize("m,k,n", [
        (8, 256, 512), (8, 512, 1536), (16, 256, 384), (1, 128, 128),
    ])
    def test_parity_vs_xla_dequant(self, m, k, n):
        from aigw_tpu.ops.pallas import parity

        parity.check_qmatmul(m, k, n)

    def test_supported_gating(self):
        assert qmatmul.supported(8, 4096, 14336)      # 8B mlp
        assert qmatmul.supported(8, 4096, 128256)     # 8B lm_head
        assert not qmatmul.supported(65, 128, 128)    # prefill-sized M
        assert not qmatmul.supported(8, 100, 128)     # unaligned K
        assert not qmatmul.supported(8, 128, 130)     # unaligned N

    def test_tile_fits_vmem_budget(self):
        for k in (1024, 4096, 8192, 14336, 16384):
            tile = qmatmul._pick_tile_n(k, 128 * 1002)
            assert tile > 0
            assert k * tile <= 2 * qmatmul._TILE_BYTES


class TestDecodeIntegration:
    def _greedy_tokens(self, cfg, params, steps=8):
        from aigw_tpu.tpuserve.engine import EngineConfig

        B, PAGE = 2, 64
        ecfg = EngineConfig(max_batch_size=B, max_seq_len=cfg.max_seq_len,
                            page_size=PAGE)
        kv = jnp.zeros(
            (cfg.n_layers, 2, ecfg.num_pages * PAGE, cfg.n_kv_heads,
             cfg.head_dim), jnp.bfloat16)
        pt = jnp.arange(B * ecfg.max_pages_per_seq,
                        dtype=jnp.int32).reshape(B, -1)
        active = jnp.ones((B,), bool)
        tokens = jnp.array([3, 5], jnp.int32)
        positions = jnp.zeros((B,), jnp.int32)
        out = []
        for i in range(steps):
            logits, kv = llama.decode_step(
                params, cfg, tokens, positions + i, kv, pt, PAGE, active)
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(np.asarray(tokens))
        return np.stack(out)

    def _decode_logits(self, cfg, params, token_seq):
        """Teacher-forced decode: run the SAME token inputs through the
        decode step, returning per-step logits (no compounding)."""
        from aigw_tpu.tpuserve.engine import EngineConfig

        B, PAGE = 2, 64
        ecfg = EngineConfig(max_batch_size=B, max_seq_len=cfg.max_seq_len,
                            page_size=PAGE)
        kv = jnp.zeros(
            (cfg.n_layers, 2, ecfg.num_pages * PAGE, cfg.n_kv_heads,
             cfg.head_dim), jnp.bfloat16)
        pt = jnp.arange(B * ecfg.max_pages_per_seq,
                        dtype=jnp.int32).reshape(B, -1)
        active = jnp.ones((B,), bool)
        positions = jnp.zeros((B,), jnp.int32)
        out = []
        for i, tokens in enumerate(token_seq):
            logits, kv = llama.decode_step(
                params, cfg, jnp.asarray(tokens), positions + i, kv, pt,
                PAGE, active)
            out.append(np.asarray(logits, np.float32))
        return out

    def test_quantized_decode_same_with_kernel_on_off(self, monkeypatch):
        """Kernel-on vs kernel-off decode parity, tie-aware. The old
        form compared an 8-step FREE-RUNNING greedy rollout token for
        token — but scale-after-accumulate vs bf16-dequant differ by a
        few centi-logits, and random-init bf16 logits produce exact
        argmax TIES (observed top-2 gap 0.0 at step 4 for this seed), so
        the rollout was a tie lottery that compounded from the first
        flip (the same artifact class as the chunked-prefill
        post-mortem). Teacher-forcing one token sequence through both
        paths keeps the comparison per-step: logits must agree within
        kernel tolerance everywhere, and argmax must agree wherever the
        decision is not inside the numeric noise floor."""
        params = llama.init_params(jax.random.PRNGKey(0), ALIGNED)
        qp = quantize_params(dict(params))
        monkeypatch.setenv("AIGW_PALLAS_QMATMUL", "off")
        off_toks = self._greedy_tokens(ALIGNED, qp)
        seq = [np.array([3, 5], np.int32)] + [t for t in off_toks[:-1]]
        off_logits = self._decode_logits(ALIGNED, qp, seq)
        monkeypatch.setenv("AIGW_PALLAS_QMATMUL", "on")
        on_logits = self._decode_logits(ALIGNED, qp, seq)
        NOISE = 0.125  # ≳2× the observed on/off max deviation (~0.05)
        for i, (lo, ln) in enumerate(zip(off_logits, on_logits)):
            rel = np.abs(lo - ln).max() / (np.abs(lo).max() + 1e-9)
            assert rel < 0.02, f"step {i}: kernel diverged ({rel:.4f})"
            for b in range(lo.shape[0]):
                srt = np.sort(lo[b])[::-1]
                if srt[0] - srt[1] > NOISE:  # a real decision, not a tie
                    assert lo[b].argmax() == ln[b].argmax(), (
                        f"step {i} row {b}: argmax flipped on a "
                        f"{srt[0] - srt[1]:.3f}-gap decision")

    def test_unaligned_config_falls_back(self, monkeypatch):
        """TINY dims (64) are not kernel-eligible — the quantized model
        must still decode via the XLA fallback."""
        monkeypatch.setenv("AIGW_PALLAS_QMATMUL", "on")
        params = llama.init_params(jax.random.PRNGKey(1), llama.TINY)
        qp = quantize_params(dict(params))
        toks = self._greedy_tokens(llama.TINY, qp, steps=4)
        assert toks.shape == (4, 2)

    def test_prefill_uses_fallback_but_matches(self, monkeypatch):
        """Prefill M is large (kernel unsupported); greedy continuation
        from a quantized prefill must work with the kernel enabled."""
        from aigw_tpu.tpuserve.engine import EngineConfig

        monkeypatch.setenv("AIGW_PALLAS_QMATMUL", "on")
        params = llama.init_params(jax.random.PRNGKey(2), ALIGNED)
        qp = quantize_params(dict(params))
        B, PAGE = 1, 64
        ecfg = EngineConfig(max_batch_size=B,
                            max_seq_len=ALIGNED.max_seq_len,
                            page_size=PAGE)
        kv = jnp.zeros(
            (ALIGNED.n_layers, 2, ecfg.num_pages * PAGE,
             ALIGNED.n_kv_heads, ALIGNED.head_dim), jnp.bfloat16)
        pt = jnp.arange(B * ecfg.max_pages_per_seq,
                        dtype=jnp.int32).reshape(B, -1)
        tokens = jnp.array([[3, 9, 7, 2] + [0] * 4], jnp.int32)
        seq_lens = jnp.array([4], jnp.int32)
        logits, _ = llama.prefill(qp, ALIGNED, tokens, seq_lens, kv, pt,
                                  PAGE)
        assert logits.shape[0] == 1 and np.isfinite(
            np.asarray(logits, np.float32)).all()
