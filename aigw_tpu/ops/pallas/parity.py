"""Pallas kernels against their XLA twins: the references, the cases
and the tolerances — ONE copy, run two ways.

- ``tests/test_pallas_ops.py`` / ``tests/test_qmatmul.py`` call the
  ``check_*`` functions with ``interpret=True`` on the CPU test
  platform (small shapes, plus a few production shapes).
- ``python -m aigw_tpu.ops.pallas.parity --model qwen2-7b`` is the
  chip smoke's kernels child: the same checks COMPILED
  (``interpret=False``) at the served attention geometry on the TPU,
  plus the one check interpret mode cannot make — several consecutive
  fused decode steps against ``paged_decode_walk``, where the kernel's
  write-back of a page it also prefetches from would show as a stale
  row. Prints one JSON line per check and exits non-zero if any failed.

Every ``check_*`` raises ``AssertionError`` on disagreement.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from aigw_tpu.models import kvq, llama
from aigw_tpu.ops.pallas import qmatmul
from aigw_tpu.ops.paged_walk import paged_decode_walk
from aigw_tpu.ops.pallas.decode_fused import fused_paged_decode
from aigw_tpu.ops.pallas.paged_attention import (
    paged_attention_decode_v2,
    paged_attention_verify,
    ragged_prefill_attention,
)

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

def xla_reference(q, k_pool, v_pool, page_table, lengths, page_size):
    """Mirror of the gather-based decode attention in models/llama.py."""
    B, H, D = q.shape
    P = page_table.shape[1]
    T = P * page_size
    gslot = page_table[:, :, None] * page_size + jnp.arange(page_size)
    gslot = gslot.reshape(B, T)
    k = k_pool[gslot]  # [B, T, Hkv, D]
    v = v_pool[gslot]
    Hkv = k.shape[2]
    group = H // Hkv
    qg = q.reshape(B, Hkv, group, D)
    logits = jnp.einsum("bhgd,bthd->bhgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(D)
    mask = jnp.arange(T)[None, :] < lengths[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgt,bthd->bhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, H, D)


def xla_reference_verify(q, k_pool, v_pool, page_table, positions,
                         page_size):
    """Mirror of the gather-based verify attention in models/llama.py:
    S consecutive query positions per slot under a per-query causal
    mask (t <= pos0 + s)."""
    B, S, H, D = q.shape
    P = page_table.shape[1]
    T = P * page_size
    gslot = page_table[:, :, None] * page_size + jnp.arange(page_size)
    gslot = gslot.reshape(B, T)
    k = k_pool[gslot]  # [B, T, Hkv, D]
    v = v_pool[gslot]
    Hkv = k.shape[2]
    group = H // Hkv
    qg = q.reshape(B, S, Hkv, group, D)
    logits = jnp.einsum("bshgd,bthd->bhgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(D)
    t_idx = jnp.arange(T)[None, None, :]
    qpos = positions[:, None, None] + jnp.arange(S)[None, :, None]
    mask = (t_idx <= qpos) & (positions[:, None, None] > -S)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, D)


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


def _exact_reference():
    """References run at full f32 matmul precision: on a TPU an f32
    einsum otherwise runs in bf16 passes, and the twin's own rounding
    would eat the tolerance meant for the kernel."""
    return jax.default_matmul_precision("highest")


def _assert_appended_row(got, want, exact: bool, what: str):
    """The row the fused kernel appended vs the XLA recipe (rope →
    compute-dtype round). Interpreted on the CPU the two are the same
    program text and must agree bit for bit; compiled, Mosaic and XLA
    may contract ``x·cos + rot·sin`` differently (one f32 rounding),
    which can move a bf16 result by one ulp — still nowhere near a
    wrong row, a wrong position or a missed write (all O(1))."""
    if exact:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=what)
    else:
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2.0 ** -7, atol=2.0 ** -7, err_msg=what)


def check_decode_v2(H, Hkv, D=128, page=128, lengths=(385, 129), P=4,
                    n_pages=8, seed=11, interpret=False):
    """Chained decode kernel vs the gather path; the default lengths
    straddle page boundaries."""
    B = len(lengths)
    kq, k_pool, v_pool, pt = _pools(seed, n_pages, page, Hkv, D, B, P)
    q = jax.random.normal(kq, (B, H, D), jnp.float32).astype(
        jnp.bfloat16)
    lens = jnp.asarray(lengths, jnp.int32)
    got = paged_attention_decode_v2(
        q, k_pool, v_pool, pt, lens, page_size=page, interpret=interpret)
    with _exact_reference():
        want = xla_reference(q, k_pool, v_pool, pt, lens, page)
    _assert_close(got, want, BF16_TOL)


def check_verify(H, Hkv, D=128, page=128, positions=(254, 60), S=5, P=4,
                 n_pages=8, seed=12, interpret=False):
    """Speculative-verify kernel (pending token + S-1 drafts) vs the
    gather path: one slot's window straddles a page boundary, the
    other sits mid-page. Raw bf16 attention outputs are tie-prone
    under argmax; acceptance parity is a model-level test."""
    B = len(positions)
    kq, k_pool, v_pool, pt = _pools(seed, n_pages, page, Hkv, D, B, P)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32).astype(
        jnp.bfloat16)
    pos = jnp.asarray(positions, jnp.int32)
    got = paged_attention_verify(
        q, k_pool, v_pool, pt, pos, page_size=page, interpret=interpret)
    with _exact_reference():
        want = xla_reference_verify(q, k_pool, v_pool, pt, pos, page)
    _assert_close(got, want, BF16_TOL)


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


def _twin_step(q, kn, vn, pools, pt, positions, active, qdt, ps, theta):
    """One decode step the CHAINED way on ``pools`` = (k, v, k_scale,
    v_scale): rope at XLA level, (quantize and) scatter every active
    sequence's new row, then ``paged_decode_walk``. Returns (attention,
    pools', flat slot of each new row, roped new K)."""
    B, H, D = q.shape
    Hkv = kn.shape[1]
    pos2 = positions[:, None]
    qr = llama.rope(q.reshape(B, 1, H, D).astype(jnp.float32),
                    pos2, theta)[:, 0].astype(jnp.bfloat16)
    knr = llama.rope(kn.reshape(B, 1, Hkv, D).astype(jnp.float32),
                     pos2, theta)[:, 0].astype(jnp.bfloat16)
    slot = (jnp.take_along_axis(pt, pos2 // ps, axis=1) * ps
            + pos2 % ps)[:, 0]
    k_pool, v_pool, k_s, v_s = pools
    k_row, v_row = knr, vn
    if qdt:
        k_row, sk = kvq.quantize_rows(knr, qdt)
        v_row, sv = kvq.quantize_rows(vn, qdt)
    for b in range(B):
        if not bool(active[b]):
            continue
        k_pool = k_pool.at[slot[b]].set(k_row[b])
        v_pool = v_pool.at[slot[b]].set(v_row[b])
        if qdt:
            k_s = k_s.at[slot[b]].set(sk[b])
            v_s = v_s.at[slot[b]].set(sv[b])
    with _exact_reference():
        want = paged_decode_walk(
            qr, jnp.stack([k_pool, v_pool])[None], 0, pt,
            jnp.where(active, positions + 1, 0), page_size=ps,
            scale=jnp.stack([k_s, v_s])[None] if qdt else None)
    return want, (k_pool, v_pool, k_s, v_s), slot, knr


def _random_step(key, B, H, Hkv, D):
    """Unroped query, new key and new value of one decode step."""
    kq, k1, k2 = jax.random.split(key, 3)
    return tuple(
        jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)
        for k, shape in ((kq, (B, H, D)), (k1, (B, Hkv, D)),
                         (k2, (B, Hkv, D))))


def _fused_pools(key, n_pages, ps, Hkv, D, B, P, qdt):
    """Random (k, v, k_scale, v_scale) pools in the engine's layout and
    a non-contiguous page table; the LAST pool page stays out of it —
    the engine-reserved dump page inactive appends land in."""
    kk, kv, kp = jax.random.split(key, 3)
    kf = jax.random.normal(kk, (n_pages * ps, Hkv, D), jnp.float32)
    vf = jax.random.normal(kv, (n_pages * ps, Hkv, D), jnp.float32)
    if qdt:
        kq, ks = kvq.quantize_rows(kf, qdt)
        vq, vs = kvq.quantize_rows(vf, qdt)
        pools = (kq, vq, ks, vs)
    else:
        pools = (kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16),
                 None, None)
    perm = jax.random.permutation(kp, n_pages - 1)[: B * P]
    return pools, perm.reshape(B, P).astype(jnp.int32)


def _fused_step(q, kn, vn, pools, pt, positions, active, ps, theta,
                interpret):
    k_pool, v_pool, k_s, v_s = pools
    return fused_paged_decode(
        q, kn, vn, k_pool, v_pool, pt, positions, active,
        k_scale=k_s, v_scale=v_s, rope_theta=theta, page_size=ps,
        interpret=interpret)


def fused_case(B, H, Hkv, D, ps, n_pages, P, positions, active,
               qdt=None, seed=0, theta=10000.0, interpret=False):
    """One fused decode dispatch (RoPE + KV append + paged attention)
    and its twin (``_twin_step``). Returns (kernel outs, reference
    attention, aux) with aux = (page_table, slot, positions, active,
    k_pool, roped new K, new V)."""
    kpool, kstep = jax.random.split(jax.random.PRNGKey(seed))
    pools, pt = _fused_pools(kpool, n_pages, ps, Hkv, D, B, P, qdt)
    q, kn, vn = _random_step(kstep, B, H, Hkv, D)
    positions = jnp.asarray(positions, jnp.int32)
    active = jnp.asarray(active)
    outs = _fused_step(q, kn, vn, pools, pt, positions, active, ps, theta,
                       interpret)
    want, _, slot, knr = _twin_step(q, kn, vn, pools, pt, positions,
                                    active, qdt, ps, theta)
    return outs, want, (pt, slot, positions, active, pools[0], knr, vn)


def assert_active_close(outs, want, active, tol=BF16_TOL):
    for b, act in enumerate(active):
        if bool(act):
            _assert_close(outs[0][b], want[b], tol)


def check_fused(H, Hkv, qdt=None, D=128, ps=128, interpret=False):
    """Fused decode kernel at a production shape: a misaligned mid-page
    append (385 % 128 = 1) and a page-boundary-straddling length. The
    appended K row must be the roped new K — bit-for-bit the XLA
    recipe on native pools, within one quantization step (and an f32
    ulp of scale: FMA contraction in the in-kernel rope) on quantized
    ones."""
    outs, want, aux = fused_case(
        B=2, H=H, Hkv=Hkv, D=D, ps=ps, n_pages=9, P=4,
        positions=[385, 129], active=[True, True], qdt=qdt,
        interpret=interpret)
    assert_active_close(outs, want, [True, True])
    _pt, slot, _pos, _act, _k_pool, knr, vn = aux
    if not qdt:
        _assert_appended_row(outs[1][slot[0]], knr[0], interpret,
                             "appended K row")
        np.testing.assert_array_equal(
            np.asarray(outs[2][slot[1]]), np.asarray(vn[1]))
        return
    qk, sk = kvq.quantize_rows(knr, qdt)
    got_q = np.asarray(outs[1][slot[0]], np.int32)
    assert np.abs(got_q - np.asarray(qk[0], np.int32)).max() <= 1
    np.testing.assert_allclose(np.asarray(outs[3][slot[0]]),
                               np.asarray(sk[0]), rtol=1e-5)


def check_fused_steps(H, Hkv, qdt=None, D=128, ps=128, B=8, P=4,
                      steps=6, theta=1e6, interpret=False):
    """SEVERAL CONSECUTIVE fused decode steps vs scatter +
    ``paged_decode_walk`` carried over the same steps, both sides
    feeding on their OWN pool. The kernel aliases the pool it also
    prefetches from; a write-back racing the next prefetch of the same
    page leaves a stale row that the NEXT step's attention reads —
    visible here, invisible to a single dispatch and to interpret mode.
    Start positions put appends just before, on and after a page
    boundary, on a fresh sequence (position 0), and on an inactive
    slot; every step crosses at least one boundary somewhere."""
    kpool, kstep = jax.random.split(jax.random.PRNGKey(7))
    pools, pt = _fused_pools(kpool, B * P + 1, ps, Hkv, D, B, P, qdt)
    twin = pools  # the twin's own pool, carried like the kernel's
    starts = [ps - 3, ps - 1, ps, 2 * ps - 2, 0, 3 * ps - 4, 5,
              ps // 2 + 1]
    positions = jnp.asarray((starts * B)[:B], jnp.int32)
    active = jnp.asarray([b != B - 2 for b in range(B)])
    for step in range(steps):
        q, kn, vn = _random_step(jax.random.fold_in(kstep, step),
                                 B, H, Hkv, D)
        outs = _fused_step(q, kn, vn, pools, pt, positions, active, ps,
                           theta, interpret)
        pools = ((outs[1], outs[2], outs[3], outs[4]) if qdt
                 else (outs[1], outs[2], None, None))
        want, twin, _, _ = _twin_step(q, kn, vn, twin, pt, positions,
                                      active, qdt, ps, theta)
        try:
            assert_active_close(outs, want, active)
        except AssertionError as e:
            raise AssertionError(f"fused step {step}: {e}") from e
        positions = positions + active.astype(jnp.int32)
    k_pool, v_pool = pools[:2]
    rk, rv = twin[:2]
    # every row the sequences own must match the twin's pool on native
    # pools (V bit for bit; K as far as the rope allows)
    if not qdt:
        for b in range(B):
            if not bool(active[b]):
                continue
            n = int(positions[b])
            rows = np.asarray(
                (pt[b, np.arange(n) // ps] * ps + np.arange(n) % ps))
            _assert_appended_row(
                np.asarray(k_pool)[rows], np.asarray(rk)[rows], interpret,
                f"K pool rows of sequence {b}")
            np.testing.assert_array_equal(
                np.asarray(v_pool)[rows], np.asarray(rv)[rows],
                err_msg=f"V pool rows of sequence {b}")


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
        ("paged_attention_decode_v2",
         lambda: check_decode_v2(H, Hkv, D)),
        ("paged_attention_verify", lambda: check_verify(H, Hkv, D)),
        ("ragged_prefill_attention",
         lambda: check_ragged(
             lens=[7, 86, 301, 1024], starts=[0, 37, 0, 128], page=128,
             q_block=128, H=H, Hkv=Hkv, D=D, n_pages=48,
             dtype=jnp.bfloat16, tol=BF16_TOL)),
    ]
    for qdt in (None, "int8", "int4"):
        tag = qdt or "native"
        checks.append((f"fused_paged_decode[{tag}]",
                       lambda qdt=qdt: check_fused(H, Hkv, qdt, D)))
        checks.append((f"fused_paged_decode[{tag}] x6 steps",
                       lambda qdt=qdt: check_fused_steps(
                           H, Hkv, qdt, D, B=batch,
                           theta=cfg.rope_theta)))
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
