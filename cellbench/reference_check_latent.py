#!/usr/bin/env python3
"""The reference comparison of a latent-attention configuration
(``family: axk1``) at its published widths, on the device the
configuration expects::

    python3 cellbench/reference_check_latent.py
        [--config cellbench/configs/latent/<name>.json]
        [--prompts 2880,2100] [--answers 64] [--parts kernels,served]
        [--judge served|control] [--platform cpu]

Builds the configuration's model as ``serve_child.py`` registers it,
seeded random weights as the server makes them, and compares with the
float32 reference (``cellbench/reference/axk1_ref.py``: the highest
matmul precision, one sequence, the EXPANDED form — per-head keys and
values decompressed from the latent; its queries taken ``BLOCK`` at a
time so that it fits beside the weights) in two parts, as
``reference_check.py`` does for the hybrid family.

``served`` — the programs the server dispatches. The serving ENGINE
with the configuration's own geometry (its ``serve_flags``) and
``logprobs_topk``, so that its own jitted chunk, tail and decode-window
programs hand back the log-probabilities of their top candidates at
every sampled position. Two prompts of the cell's lengths go into
different slots, the first crossing ten chunk boundaries while the
second arrives; each decodes ``--answers`` tokens through the latent
pages. The reference then reads prompt + the tokens the engine sampled,
and every candidate's log-probability is compared. This part tells a
wrong model from the right one — it also reads the same engine output
against the reference WITHOUT the router's group limit (every group
kept), which is what a program that skipped the mechanism would serve,
and that reading has to lie over the limits. It cannot tell precisions
apart: the programs' activations are bfloat16, as the configuration
states.

``kernels`` — what the configuration states beside bfloat16
activations: a bfloat16 latent attended with float32 softmax statistics
and accumulation, and a router that scores and picks in float32. The
rounding of the activations is taken out by giving both sides THE SAME
inputs, made once by the program's own projections from the
configuration's weights in the first expert layer: each head's query,
the cached rows (latent | rotated key), the hidden rows in front of the
router. On three blocks of 256 queries (the first, one in the middle,
the last) against every key behind them:

- ``attn_rel``: the program's absorbed chunk attention over the cached
  rows (the query folded through ``W_kvb``'s key half,
  ``_attend_blocks``, the value half of ``W_kvb``) against the
  reference's per-head attention: largest relative error of a head over
  the block. And ``decode_attn_rel``: the decode step's own path
  (``paged_walk.latent_decode_walk`` over the rows laid out in pages)
  for the last position;
- ``route_moved``: the program's expert layer over the hidden rows, its
  stats tape's count of assignments a held expert, against the
  reference's picks: the share of held assignments that sit elsewhere.

The control is the reference in the nearest precision below the stated
one — a softmax whose logits, exponentials and sums are bfloat16, a
bfloat16 router — judged as if it were the system: it has to come out
NOT ok, by the kernels' limits. ``LIMITS`` lie between the two readings
(PERF.md section 6 has both). Exit code, ``--judge served``: 0 the
system is ok and the control is not; 1 the system is not ok; 2 the
control passes, so the limits hold nothing. ``--judge control``: the
control's own verdict, 0 ok (it must not be) or 1. One process, which
holds the chip."""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

TOPK = 20
BLOCK = 256
#: what a reading may reach. On the chip at the published widths (my
#: chip run, PR 45; 2944 tokens, three blocks of 256 queries) the
#: program's kernels read ``attn_rel`` 3.0e-3 (4.7e-3 the decode walk's
#: own path) where a softmax in bfloat16 reads 8.7e-3, and
#: ``route_moved`` 0 where a bfloat16 router reads 2.0e-2: each limit
#: lies between its two readings, and the control fails both.
#: ``logprob_max`` / ``logprob_mean`` lie between the served programs'
#: readings (0.98 and 0.55; 0.044 and 0.036) and the same output read
#: against the model WITHOUT the router's group limit (1.35 and 1.81;
#: 0.141 and 0.154): they tell a wrong model, not a precision.
LIMITS = {"attn_rel": 6.4e-3, "route_moved": 2e-3, "logprob_max": 1.15,
          "logprob_mean": 0.08}


def kernels(params, cfg, cfgd, ref, tokens):
    """The ``kernels`` part: readings of the program's kernels and of
    the lower-precision control, both against the float32 reference on
    the same inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aigw_tpu.models import axk1, llama
    from aigw_tpu.ops import paged_walk

    i = cfg.layer_kinds.index("moe")
    S = len(tokens)
    r, dr, H = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.num_attention_heads
    dn = cfg.qk_nope_head_dim
    inv = axk1.yarn_inv_freq(cfg)
    f32, bf16 = jnp.float32, jnp.bfloat16

    @jax.jit
    def inputs(p, toks):
        x = llama._embed_rows(p, toks[None])
        h = llama.rms_norm(x, p[f"l{i}.in_norm"], cfg.rms_norm_eps)
        pos = jnp.arange(S, dtype=jnp.int32)[None]
        cq = llama.rms_norm(llama._matmul(p, f"l{i}.wq_a", h),
                        p[f"l{i}.q_norm"], cfg.rms_norm_eps)
        q = llama._matmul(p, f"l{i}.wq_b", cq).reshape(
            1, S, H, cfg.qk_head_dim)
        return (h, q[..., :dn], axk1._rope(q[..., dn:], pos, inv),
                axk1._mla_kv(p, i, h, pos, cfg, inv))

    h, q_nope, q_rope, rows = inputs(params, jnp.asarray(tokens, jnp.int32))
    c_kv, k_rope = rows[0, :, :r], rows[0, :, r:]
    kvb = params[f"l{i}.wkv_b"].astype(f32).reshape(r, H, dn + cfg.v_head_dim)

    def ref_heads(t0, t1, low=False):
        """The reference's per-head attention of queries t0..t1 from the
        same per-head queries and cached rows; ``low``: the control's
        softmax, its logits, exponentials and sums all bfloat16."""
        with jax.default_matmul_precision("highest"):
            lat, kr = c_kv[:t1].astype(f32), k_rope[:t1].astype(f32)
            k_nope = jnp.einsum("tc,chd->thd", lat, kvb[..., :dn])
            v = jnp.einsum("tc,chd->thd", lat, kvb[..., dn:])
            s = (jnp.einsum("shd,thd->hst", q_nope[0, t0:t1].astype(f32),
                            k_nope)
                 + jnp.einsum("shd,td->hst", q_rope[0, t0:t1].astype(f32),
                              kr)) * cfg.softmax_scale
            causal = jnp.arange(t1)[None, :] <= jnp.arange(t0, t1)[:, None]
            s = jnp.where(causal[None], s, -jnp.inf)
            if low:
                s = s.astype(bf16)
                e = jnp.exp(s - jnp.max(s, -1, keepdims=True))
                probs = (e / jnp.sum(e, -1, keepdims=True,
                                     dtype=bf16)).astype(f32)
            else:
                probs = jax.nn.softmax(s, -1)
            return jnp.einsum("hst,thd->shd", probs, v)

    def absorbed(qn, qr):
        return axk1.absorb(params, i, qn, qr, cfg)

    def expand(o_lat):
        return jnp.einsum("...hc,chv->...hv", o_lat.astype(bf16),
                          axk1._kvb(params, i, cfg)[..., dn:],
                          preferred_element_type=f32)

    @jax.jit
    def prog_attn(qn, qr, pos, cached):
        """The chunk path on the cached rows, in blocks of 512 as the
        served program's (4 pages of 128)."""
        T = cached.shape[0]
        Tb = 512 if T % 512 == 0 else T
        blocks = cached.reshape(T // Tb, Tb, -1)
        o = axk1._attend_blocks(
            absorbed(qn[None], qr[None]), lambda j: blocks[j].T[None],
            T // Tb, Tb, pos[None], jnp.ones((1, pos.shape[0]), bool), cfg)
        return expand(o)[0]

    def head_rel(a, b):
        """Largest relative error (Frobenius) of a head."""
        a, b = a.astype(f32), b.astype(f32)
        return float(jnp.max(jnp.sqrt(jnp.sum((a - b) ** 2, (0, 2))
                                      / jnp.sum(b ** 2, (0, 2)))))

    starts = sorted({0, max((S // 2) // BLOCK * BLOCK, 0),
                     max(S - BLOCK, 0)})
    out = {"served": {"attn_rel": 0.0}, "control": {"attn_rel": 0.0}}
    for t0 in starts:
        t1 = min(S, t0 + BLOCK)
        pad = -t1 % 512 if t1 > 512 else 0
        cached = jnp.pad(rows[0, :t1], ((0, pad), (0, 0)))
        heads = ref_heads(t0, t1)
        got = prog_attn(q_nope[0, t0:t1], q_rope[0, t0:t1],
                        jnp.arange(t0, t1, dtype=jnp.int32), cached)
        out["served"]["attn_rel"] = max(out["served"]["attn_rel"],
                                        head_rel(got, heads))
        out["control"]["attn_rel"] = max(
            out["control"]["attn_rel"],
            head_rel(ref_heads(t0, t1, low=True), heads))

    # the decode step's own path for the last position: the rows in
    # pages, the walk over them
    page = 128 if S >= 128 else 16
    P = 1
    while P * page < S:
        P *= 2
    pool = jnp.zeros((cfg.num_hidden_layers, cfg.cache_row, (P + 1) * page),
                     rows.dtype).at[i, :, page:page + S].set(rows[0].T)
    table = jnp.arange(1, P + 1, dtype=jnp.int32)[None]
    o = paged_walk.latent_decode_walk(
        absorbed(q_nope[:, -1:], q_rope[:, -1:])[:, 0], pool, i, table,
        jnp.asarray([S], jnp.int32), page_size=page, rank=r,
        scale=cfg.softmax_scale)
    out["served"]["decode_attn_rel"] = head_rel(
        expand(o), ref_heads(S - 1, S))

    # the router: the program's expert layer over the same bfloat16
    # rows; its tape counts the real tokens' assignments a held expert
    E = cfg.num_experts
    hp = llama.rms_norm(h, params[f"l{i}.post_norm"], cfg.rms_norm_eps)

    @jax.jit
    def placed(p, x):
        tape: list = []
        axk1.moe(p, i, x, cfg, tape=tape)
        return tape[0][:E]

    def counts(dtype):
        with ref.computed_in(dtype):
            _, topi = ref.route(params, i, cfgd, hp[0].astype(dtype))
        held = np.asarray(topi) - cfgd.get("held_from", 0)
        return np.bincount(held[(held >= 0) & (held < E)], minlength=E)

    want = counts(f32)

    def moved(got):
        return float(np.abs(np.asarray(got) - want).sum() / 2
                     / max(want.sum(), 1))

    out["served"]["route_moved"] = moved(placed(params, hp))
    out["control"]["route_moved"] = moved(counts(bf16))
    out.update(layer=i, tokens=S, blocks=starts,
               held_assignments=int(want.sum()))
    return out


def served(params, cfg, cfgd, fns, ref, flags, prompts, answers: int):
    """The ``served`` part: the engine's own programs against the
    reference's log-probabilities."""
    import jax
    import numpy as np

    from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
    from aigw_tpu.tpuserve.sampling import SamplingParams
    from cellbench.reference_check import flag

    eng = Engine(params, cfg, EngineConfig(
        max_batch_size=flag(flags, "--max-batch-size", 8),
        max_seq_len=flag(flags, "--max-seq-len", 2048),
        page_size=flag(flags, "--page-size", 128),
        prefill_bucket_rungs=flag(flags, "--prefill-bucket-rungs", 2),
        prefill_chunk_tokens=flag(flags, "--prefill-chunk-tokens", 256),
        logprobs_topk=TOPK), fns=fns)
    streams = []
    for prompt in prompts:
        s = {"prompt": prompt, "tokens": [], "tops": [],
             "done": threading.Event()}

        def emit(tok, fin, lp, top, s=s):
            if tok >= 0:
                s["tokens"].append(tok)
                s["tops"].append(top)
            if fin is not None:
                s["done"].set()

        s["req"] = GenRequest(
            prompt=prompt, max_tokens=answers,
            emit=lambda *_: None, emit_lp=emit,
            sampling=SamplingParams(temperature=0.0))
        streams.append(s)
    eng.start()
    try:
        for s in streams:
            eng.submit(s["req"])
        for s in streams:
            if not s["done"].wait(3000):
                raise RuntimeError("the engine did not finish a stream")
        st = eng.stats
        out = {"chunk_steps": st.chunked_prefill_steps,
               "decode_steps": st.decode_steps,
               "prefill_keys_attended": st.prefill_keys_attended,
               "kv_bytes_per_token": st.kv_bytes_per_token, "prompts": []}
    finally:
        eng.stop()
    params = eng.params
    del eng  # the cache leaves the device; the weights stay
    gc.collect()
    free = dict(cfgd, topk_group=cfgd["n_group"])  # no group limit
    for s in streams:
        seq = np.asarray(s["prompt"] + s["tokens"], np.int32)
        first = len(s["prompt"]) - 1
        at = first + np.arange(len(s["tokens"]))
        got = {"prompt_tokens": len(s["prompt"]),
               "answers": len(s["tokens"])}
        for name, c in (("", cfgd), ("ungrouped_", free)):
            want = np.asarray(jax.nn.log_softmax(ref.forward(
                params, c, seq, positions=at, block=BLOCK), axis=-1))
            diffs = [abs(float(want[j, t]) - lp)
                     for j, top in enumerate(s["tops"]) for t, lp in top]
            assert len(diffs) == TOPK * answers and first >= 0
            got[name + "logprob_max"] = max(diffs)
            got[name + "logprob_mean"] = sum(diffs) / len(diffs)
        out["prompts"].append(got)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        HERE, "configs", "latent", "a.x-k1-1chip.json"))
    ap.add_argument("--prompts", default="2880,2100")
    ap.add_argument("--answers", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parts", default="kernels,served")
    ap.add_argument("--judge", choices=("served", "control"),
                    default="served")
    ap.add_argument("--platform", default="")
    args = ap.parse_args(argv)
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    import jax
    import numpy as np

    from cellbench import serve_child
    from cellbench.reference import axk1_ref as ref

    with open(args.config) as f:
        doc = json.load(f)
    cb = doc["cellbench"]
    serve_child.register(doc)
    from aigw_tpu.models.registry import family_fns, get_model_spec

    spec = get_model_spec(cb["name"])
    cfg, fns = spec.config, family_fns(spec.family)
    dev = jax.devices()[0]
    want = cb["expect"]["platform"] if not args.platform else args.platform
    if dev.platform != want:
        print(f"reference_check_latent: runs on {dev.platform!r}, wants "
              f"{want!r}", file=sys.stderr)
        return 3
    params = fns.init_params(jax.random.PRNGKey(0), cfg)
    cfgd = dict(dataclasses.asdict(cfg))
    rng = np.random.default_rng(args.seed)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)]
               for n in (int(x) for x in args.prompts.split(","))]
    parts = args.parts.split(",")
    flags = cb["serve_flags"]
    out = {"config": cb["name"], "device": dev.device_kind, "limits": LIMITS}
    ok, control_ok = True, None
    if "kernels" in parts:
        more = [int(t) for t in rng.integers(0, cfg.vocab_size, args.answers)]
        got = out["kernels"] = kernels(params, cfg, cfgd, ref,
                                       prompts[0] + more)

        def within(r):
            return all(r[k] < LIMITS[k]
                       for k in ("attn_rel", "route_moved"))

        ok = within(got["served"]) \
            and got["served"]["decode_attn_rel"] < LIMITS["attn_rel"]
        control_ok = within(got["control"])
    if "served" in parts:
        got = out["served"] = served(params, cfg, cfgd, fns, ref, flags,
                                     prompts, args.answers)
        ok = ok and all(p[k] < LIMITS[k] for p in got["prompts"]
                        for k in ("logprob_max", "logprob_mean"))
    out["ok"], out["control_ok"] = ok, control_ok
    os.makedirs(os.path.join(os.path.dirname(HERE), "chiprun_out"),
                exist_ok=True)
    with open(os.path.join(os.path.dirname(HERE), "chiprun_out",
                           "reference_check_latent.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    if args.judge == "control":
        return 0 if control_ok else 1
    if not ok:
        return 1
    return 2 if control_ok else 0


if __name__ == "__main__":
    sys.exit(main())
