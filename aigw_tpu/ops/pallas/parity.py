"""Pallas kernels against their XLA twins: the references, the cases
and the tolerances — ONE copy, run two ways.

- ``tests/test_pallas_ops.py`` / ``tests/test_qmatmul.py`` call the
  ``check_*`` functions with ``interpret=True`` on the CPU test
  platform (small shapes, plus a few production shapes).
- ``python -m aigw_tpu.ops.pallas.parity --model qwen2-7b`` is the
  chip smoke's kernels child: the same checks COMPILED
  (``interpret=False``) at the served attention geometry and weight
  shapes on the TPU. Prints one JSON line per check and exits non-zero
  if any failed.

Every ``check_*`` raises ``AssertionError`` on disagreement.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from aigw_tpu.ops.pallas import qmatmul
from aigw_tpu.ops.pallas.paged_attention import ragged_prefill_attention

#: bf16 attention outputs: the kernel's online softmax and the dense
#: reference accumulate in different orders — a few bf16 ulps at
#: O(1) magnitudes. Far below any real kernel defect (a wrong page, a
#: wrong mask or a missed row moves outputs by O(1)).
BF16_TOL = 5e-2
#: f32 pools (small-shape tests): reduction order only
F32_TOL = 2e-5
#: W8A16 matmul, relative to the largest reference magnitude: bf16
#: rounding of the dequantized weight (XLA twin) vs f32 scale applied
#: to the accumulator (kernel)
QMATMUL_REL_TOL = 0.02


# -- XLA / numpy references ------------------------------------------------

def xla_reference_ragged(q, k_pool, v_pool, page_table, cu, starts,
                         page_size):
    """Independent dense reference for the ragged prefill kernel: per
    sequence, materialize its key window and run plain causal softmax
    attention over the packed queries (numpy, no online softmax, no
    paging tricks). Padding rows return zeros."""
    T, H, D = q.shape
    B = page_table.shape[0]
    qf = np.asarray(q, np.float32)
    kp = np.asarray(k_pool, np.float32)
    vp = np.asarray(v_pool, np.float32)
    pt = np.asarray(page_table)
    Hkv = kp.shape[1]
    group = H // Hkv
    out = np.zeros((T, H, D), np.float32)
    for b in range(B):
        lo, hi = int(cu[b]), int(cu[b + 1])
        if hi <= lo:
            continue
        L = int(starts[b]) + (hi - lo)  # total attended positions
        slots = [int(pt[b, i // page_size]) * page_size + i % page_size
                 for i in range(L)]
        k = np.repeat(kp[slots], group, axis=1)  # [L, H, D]
        v = np.repeat(vp[slots], group, axis=1)
        qs = qf[lo:hi]  # [Lq, H, D]
        logits = np.einsum("qhd,khd->hqk", qs, k) / math.sqrt(D)
        qpos = int(starts[b]) + np.arange(hi - lo)
        mask = np.arange(L)[None, :] <= qpos[:, None]  # [Lq, L]
        logits = np.where(mask[None], logits, -1e30)
        logits -= logits.max(-1, keepdims=True)
        w = np.exp(logits)
        w /= w.sum(-1, keepdims=True)
        out[lo:hi] = np.einsum("hqk,khd->qhd", w, v)
    return out


# -- cases -------------------------------------------------------------------

def _pools(seed, n_pages, page, Hkv, D, B, P, dtype=jnp.bfloat16):
    """Random K/V pools and a NON-contiguous page table."""
    kq, kk, kv, kp = jax.random.split(jax.random.PRNGKey(seed), 4)
    k_pool = jax.random.normal(
        kk, (n_pages * page, Hkv, D), jnp.float32).astype(dtype)
    v_pool = jax.random.normal(
        kv, (n_pages * page, Hkv, D), jnp.float32).astype(dtype)
    perm = jax.random.permutation(kp, n_pages)[: B * P]
    return kq, k_pool, v_pool, perm.reshape(B, P).astype(jnp.int32)


def _assert_close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def check_ragged(lens, starts, page, q_block, H, Hkv, D, n_pages,
                 dtype=jnp.float32, tol=F32_TOL, interpret=False):
    """Ragged prefill kernel vs the dense numpy reference: packed
    mixed-length sequences, q blocks spanning sequence boundaries,
    misaligned offset-resumed starts, GQA. Tail padding rows must come
    out zero."""
    B = len(lens)
    total = sum(lens)
    T = -(-total // q_block) * q_block
    cu = np.zeros((B + 1,), np.int32)
    for b, L in enumerate(lens):
        cu[b + 1] = cu[b] + L
    P = max(-(-(s + L) // page) for s, L in zip(starts, lens))
    P = max(P, 2)
    kq, k_pool, v_pool, pt = _pools(42, n_pages, page, Hkv, D, B, P,
                                    dtype)
    q = jax.random.normal(kq, (T, H, D), jnp.float32).astype(dtype)
    got = ragged_prefill_attention(
        q, k_pool, v_pool, pt, jnp.asarray(cu),
        jnp.asarray(starts, jnp.int32), page_size=page,
        q_block=q_block, interpret=interpret)
    want = xla_reference_ragged(q, k_pool, v_pool, np.asarray(pt), cu,
                                np.asarray(starts), page)
    _assert_close(np.asarray(got, np.float32)[: cu[-1]], want[: cu[-1]],
                  tol)
    if T > cu[-1]:
        assert not np.asarray(got)[cu[-1]:].any()


def check_qmatmul(m, k, n, interpret=None):
    """W8A16 kernel vs XLA dequant-then-matmul. ``interpret=None``
    takes the serving entry point (compiled on TPU, interpreted on the
    CPU platform a test named)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    q = jnp.asarray(rng.integers(-127, 128, (k, n), dtype=np.int8))
    s = jnp.asarray(rng.random((1, n), np.float32) * 0.02)
    assert qmatmul.supported(m, k, n), (m, k, n)
    if interpret is None:
        y = qmatmul.w8a16_matmul(x, q, s)
    else:
        y = qmatmul._w8a16_matmul(x, q, s, interpret=interpret)
    ref = (x @ (q.astype(jnp.bfloat16) * s.astype(jnp.bfloat16))
           ).astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(y.astype(jnp.float32) - ref))
                / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < QMATMUL_REL_TOL, (m, k, n, rel)


# -- the chip smoke's kernels child -----------------------------------------

def served_checks(cfg, batch: int = 8) -> list[tuple[str, object]]:
    """(name, thunk) for every kernel at ``cfg``'s attention geometry
    and weight shapes, compiled."""
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    checks: list[tuple[str, object]] = [
        ("ragged_prefill_attention",
         lambda: check_ragged(
             lens=[7, 86, 301, 1024], starts=[0, 37, 0, 128], page=128,
             q_block=128, H=H, Hkv=Hkv, D=D, n_pages=48,
             dtype=jnp.bfloat16, tol=BF16_TOL)),
    ]
    shapes = {(cfg.dim, H * D), (cfg.dim, Hkv * D), (H * D, cfg.dim),
              (cfg.dim, cfg.ffn_dim), (cfg.ffn_dim, cfg.dim)}
    if not cfg.tie_embeddings:
        shapes.add((cfg.dim, cfg.vocab_size))
    for k, n in sorted(shapes):
        checks.append((f"w8a16_matmul[{batch}x{k}x{n}]",
                       lambda k=k, n=n: check_qmatmul(
                           batch, k, n, interpret=False)))
    return checks


def main(argv: list[str] | None = None) -> int:
    import argparse
    import json

    from aigw_tpu.models.registry import get_model_spec
    from aigw_tpu.utils.boot import BootError, boot_jax

    ap = argparse.ArgumentParser(
        description="Pallas kernels vs their XLA twins, compiled, at a "
                    "registered model's geometry")
    ap.add_argument("--model", default="qwen2-7b")
    ap.add_argument("--platform", default="")
    args = ap.parse_args(argv)
    try:
        platform = boot_jax(args.platform)
    except BootError as e:
        print(f"parity: {e}", flush=True)
        return 1
    if platform != "tpu":
        # Mosaic only targets the TPU; elsewhere "compiled" cannot be
        # honoured and interpret mode is what the tests already run
        print(f"parity: kernels compile for a TPU only, found "
              f"{platform!r}", flush=True)
        return 1
    cfg = get_model_spec(args.model).config
    failed = 0
    for name, thunk in served_checks(cfg):
        try:
            thunk()
            err = ""
        except AssertionError as e:
            err = str(e)[:600]
            failed += 1
        print(json.dumps({"kernel": name, "ok": not err,
                          **({"error": err} if err else {})}),
              flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
