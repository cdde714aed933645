#!/usr/bin/env python3
"""The reference comparison of a window-and-global configuration
(``family: mimo_v2``) at its published widths, on the device the
configuration expects::

    python3 cellbench/reference_check_window.py
        [--config cellbench/configs/window/<name>.json]
        [--prompts 11100,1200] [--answers 160] [--parts kernels,served]
        [--judge served|control] [--platform cpu]

Builds the configuration's model as ``serve_child.py`` registers it,
seeded random weights as the server makes them, and compares with the
float32 reference (``cellbench/reference/mimo_v2_ref.py``: the highest
matmul precision, one sequence, full masks built from positions, the
sink as a concatenated column; its queries taken ``BLOCK`` at a time so
that it fits beside the weights) in two parts, as
``reference_check.py`` and ``reference_check_latent.py`` do for their
families.

``served`` — the programs the server dispatches. The serving ENGINE
with the configuration's own geometry (its ``serve_flags``) and
``logprobs_topk``, so that its own jitted chunk, tail and decode-window
programs hand back the log-probabilities of their top candidates at
every sampled position. Two prompts of the cell's lengths go into
different slots, the first (~11k tokens) crossing over forty chunk
boundaries while the second (~1.2k) arrives and decodes; each decodes
``--answers`` tokens through the global layers' pages and past a full
turn of the window layers' rings. The reference then reads prompt + the
tokens the engine sampled, and every candidate's log-probability is
compared. This part tells a wrong model from the right one — it also
reads the same engine output against the reference WITH THE WINDOW
LAYERS MADE GLOBAL and against the reference WITHOUT THE SINK, which is
what a program that skipped either mechanism would serve, and both
readings have to lie over the limits. It cannot tell precisions apart:
the programs' activations are bfloat16, as the configuration states.

``kernels`` — what the configuration states beside bfloat16
activations: bfloat16 keys and values attended with float32 softmax
statistics and accumulation, and a router that scores and picks in
float32. The rounding of the activations is taken out by giving both
sides THE SAME inputs, made once by the program's own projections from
the configuration's weights: each head's rotated query, the rotated
keys and scaled values of the first window layer and of the second
global layer, the hidden rows in front of the first expert layer's
router. On three blocks of 256 queries (the first, one in the middle,
the last) against every key a query may see:

- ``attn_rel``: the largest relative error of a head over a block, of
  the program's chunk attention of each kind — a window layer's band
  over the slot's ring and the chunk (``_attend_window``, the sink in
  the denominator), a global layer's blocks over the ``v | k`` columns
  (``_attend_pages``: a score product 192 wide, a value product 128
  wide) — against the reference's per-head attention. And
  ``decode_attn_rel``: the decode step's own paths for the last
  position, the loop over a live row's ring (``_ring_live_rows``) and
  the page walk with two widths (``paged_walk.latent_decode_walk``);
- ``route_moved``: the program's expert layer over the hidden rows, its
  stats tape's count of assignments a held expert, against the
  reference's picks (score + bias): the share of held assignments that
  sit elsewhere.

The control is the reference in the nearest precision below the stated
one — a softmax whose logits, sink, exponentials and sums are bfloat16,
a bfloat16 router — judged as if it were the system: it has to come out
NOT ok, by the kernels' limits. ``LIMITS`` lie between the two readings
(PERF.md section 6 has both). Exit code, ``--judge served``: 0 the
system is ok and every control is not; 1 the system is not ok; 2 a
control passes, so the limits hold nothing. ``--judge control``: the
lower-precision control's own verdict, 0 ok (it must not be) or 1. One
process, which holds the chip."""

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
#: chip run, PR 47; 3072 tokens, three blocks of 256 queries, both
#: kinds of layer) the program's kernels read ``attn_rel`` 1.8e-3 (2.5e-3
#: the decode step's own paths) where a softmax in bfloat16 reads
#: 6.8e-3, and ``route_moved`` 0 where a bfloat16 router reads 1.4e-2:
#: each limit lies between its two readings, and the control fails
#: both. ``logprob_max`` / ``logprob_mean`` lie between the served
#: programs' readings (0.24 and 0.20; 0.0098 and 0.0071, an 11100- and
#: a 1200-token prompt, 160 answers each) and the same output read
#: against the model WITHOUT the sink (0.46 and 0.45; 0.085 and 0.097)
#: — with the window layers made global it reads 3.2 and 4.5; 1.21 and
#: 1.48: they tell a wrong model, not a precision.
LIMITS = {"attn_rel": 4.5e-3, "route_moved": 2e-3, "logprob_max": 0.35,
          "logprob_mean": 0.03}


def kernels(params, cfg, cfgd, ref, tokens):
    """The ``kernels`` part: readings of the program's kernels and of
    the lower-precision control, both against the float32 reference on
    the same inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aigw_tpu.models import llama, mimo_v2
    from aigw_tpu.ops import paged_walk

    S = len(tokens)
    Wn, H = cfg.sliding_window, cfg.num_attention_heads
    iw = cfg.layer_kinds.index("window")
    ig = len(cfg.layer_kinds) - 1 - cfg.layer_kinds[::-1].index("global")
    im = cfg.first_dense_layers
    f32, bf16 = jnp.float32, jnp.bfloat16
    pos_all = jnp.arange(S, dtype=jnp.int32)[None]

    def inputs(i, kind):
        @jax.jit
        def make(p, toks):
            x = llama._embed_rows(p, toks[None])
            h = llama.rms_norm(x, p[f"l{i}.in_norm"], cfg.rms_norm_eps)
            q, k, v = mimo_v2._project(p, i, h, cfg, kind)
            q, k = mimo_v2._rotate(q, k, pos_all, cfg, kind)
            return h, q[0], k[0], v[0]
        return make(params, jnp.asarray(tokens, jnp.int32))

    def ref_heads(q, k, v, t0, t1, window, sink, low=False):
        """The reference's per-head attention of queries t0..t1 over the
        same rows; ``low``: the control's softmax, its logits, sink,
        exponentials and sums all bfloat16."""
        with jax.default_matmul_precision("highest"):
            s0 = max(0, t0 - window + 1) if window else 0
            n = H // k.shape[1]
            kr = jnp.repeat(k[s0:t1].astype(f32), n, axis=1)
            vr = jnp.repeat(v[s0:t1].astype(f32), n, axis=1)
            s = jnp.einsum("shd,thd->hst", q[t0:t1].astype(f32), kr) \
                * cfg.softmax_scale
            t = jnp.arange(t0, t1)[:, None]
            u = jnp.arange(s0, t1)[None, :]
            seen = (u <= t) & ((t - u < window) if window else True)
            s = jnp.where(seen[None], s, -jnp.inf)
            if sink is not None:
                s = jnp.concatenate([s, jnp.broadcast_to(
                    sink.astype(f32)[:, None, None], (H, t1 - t0, 1))], -1)
            if low:
                s = s.astype(bf16)
                e = jnp.exp(s - jnp.max(s, -1, keepdims=True))
                probs = (e / jnp.sum(e, -1, keepdims=True,
                                     dtype=bf16)).astype(f32)
            else:
                probs = jax.nn.softmax(s, -1)
            if sink is not None:
                probs = probs[..., :-1]
            return jnp.einsum("hst,thd->shd", probs, vr)

    def head_rel(a, b):
        """Largest relative error (Frobenius) of a head."""
        a, b = a.astype(f32), b.astype(f32)
        return float(jnp.max(jnp.sqrt(jnp.sum((a - b) ** 2, (0, 2))
                                      / jnp.sum(b ** 2, (0, 2)))))

    starts = sorted({0, max((S // 2) // BLOCK * BLOCK, 0),
                     max(S - BLOCK, 0)})
    out = {"served": {"attn_rel": 0.0, "decode_attn_rel": 0.0},
           "control": {"attn_rel": 0.0}}

    def note(who, key, value):
        out[who][key] = max(out[who][key], value)

    # -- a window layer: the band over ring and chunk, then one step ----
    _, q, k, v = inputs(iw, "window")
    sink = params[f"l{iw}.sink"]
    rows = mimo_v2._row(k[None], v[None])[0]  # [S, W]: v | k

    def ring_before(t0):
        """The slot's ring when positions below ``t0`` are in it."""
        ring = jnp.zeros((Wn, rows.shape[-1]), rows.dtype)
        lo = max(0, t0 - Wn)
        if t0 > lo:
            ring = ring.at[jnp.arange(lo, t0) % Wn].set(rows[lo:t0])
        return ring

    @jax.jit
    def chunk_window(q_, k_, v_, ring, t0):
        n = q_.shape[0]
        pos = t0 + jnp.arange(n, dtype=jnp.int32)[None]
        return mimo_v2._attend_window(
            q_[None], k_[None], v_[None], ring[None], t0[None], pos,
            jnp.ones((1, n), bool), sink, cfg)[0]

    for t0 in starts:
        t1 = min(S, t0 + BLOCK)
        want = ref_heads(q, k, v, t0, t1, Wn, sink)
        got = chunk_window(q[t0:t1], k[t0:t1], v[t0:t1], ring_before(t0),
                           jnp.asarray(t0, jnp.int32))
        note("served", "attn_rel", head_rel(got, want))
        note("control", "attn_rel", head_rel(
            ref_heads(q, k, v, t0, t1, Wn, sink, low=True), want))
    Gw = cfg.swa_num_key_value_heads
    pool = jnp.zeros((1, 2, Wn, rows.shape[-1]), rows.dtype).at[0, 1].set(
        ring_before(S - 1))
    o, _ = mimo_v2._ring_live_rows(
        mimo_v2._at_own_head(jnp.stack([q[-1], q[-1]]), Gw),
        jnp.stack([rows[-1], rows[-1]]), sink, pool,
        jnp.asarray(0, jnp.int32), jnp.asarray([1, 0], jnp.int32),
        jnp.asarray(1, jnp.int32), jnp.asarray([0, S - 1], jnp.int32),
        n_values=cfg.value_width("window"), scale=cfg.softmax_scale)
    note("served", "decode_attn_rel", head_rel(
        mimo_v2._own_values(o, Gw)[1:2],
        ref_heads(q, k, v, S - 1, S, Wn, sink)))

    # -- a global layer: blocks of columns, then the walk over pages ----
    _, q, k, v = inputs(ig, "global")
    rows = mimo_v2._row(k[None], v[None])[0]  # [S, 1280]

    @jax.jit
    def chunk_global(q_, pos, cached):
        T = cached.shape[0]
        Tb = 512 if T % 512 == 0 else T
        blocks = cached.reshape(T // Tb, Tb, -1)
        return mimo_v2._attend_pages(
            q_[None], lambda j: blocks[j].T[None], T // Tb, Tb, pos[None],
            jnp.ones((1, pos.shape[0]), bool), cfg)[0]

    for t0 in starts:
        t1 = min(S, t0 + BLOCK)
        pad = -t1 % 512 if t1 > 512 else 0
        want = ref_heads(q, k, v, t0, t1, 0, None)
        got = chunk_global(q[t0:t1], jnp.arange(t0, t1, dtype=jnp.int32),
                           jnp.pad(rows[:t1], ((0, pad), (0, 0))))
        note("served", "attn_rel", head_rel(got, want))
        note("control", "attn_rel", head_rel(
            ref_heads(q, k, v, t0, t1, 0, None, low=True), want))
    page = 128 if S >= 128 else 16
    P = 1
    while P * page < S:
        P *= 2
    Gg, nv = cfg.num_key_value_heads, cfg.value_width("global")
    pool = jnp.zeros((2, rows.shape[-1], (P + 1) * page), rows.dtype).at[
        1, :, page:page + S].set(rows.T)
    o = paged_walk.latent_decode_walk(
        mimo_v2._at_own_head(q[-1:], Gg), pool, 1,
        jnp.arange(1, P + 1, dtype=jnp.int32)[None],
        jnp.asarray([S], jnp.int32), page_size=page, rank=nv,
        scale=cfg.softmax_scale, keys_from=nv)
    note("served", "decode_attn_rel", head_rel(
        mimo_v2._own_values(o, Gg), ref_heads(q, k, v, S - 1, S, 0, None)))

    # -- the router: the program's expert layer over the same bfloat16
    # rows; its tape counts the real tokens' assignments a held expert
    E = cfg.num_experts
    h, *_ = inputs(im, cfg.layer_kinds[im])
    hp = llama.rms_norm(h, params[f"l{im}.post_norm"], cfg.rms_norm_eps)

    @jax.jit
    def placed(p, x):
        tape: list = []
        mimo_v2.moe(p, im, x, cfg, tape=tape)
        return tape[0][:E]

    def counts(dtype):
        with ref.computed_in(dtype):
            _, topi = ref.route(params, im, cfgd, hp[0].astype(dtype))
        held = np.asarray(topi) - cfgd.get("held_from", 0)
        return np.bincount(held[(held >= 0) & (held < E)], minlength=E)

    want = counts(f32)

    def moved(got):
        return float(np.abs(np.asarray(got) - want).sum() / 2
                     / max(want.sum(), 1))

    out["served"]["route_moved"] = moved(placed(params, hp))
    out["control"]["route_moved"] = moved(counts(bf16))
    out.update(layers={"window": iw, "global": ig, "router": im}, tokens=S,
               blocks=starts, held_assignments=int(want.sum()))
    return out


def served(params, cfg, cfgd, fns, ref, flags, prompts, answers: int):
    """The ``served`` part: the engine's own programs against the
    reference's log-probabilities, and against the two models a program
    that skipped a mechanism would serve."""
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
               "kv_bytes_per_token": st.kv_bytes_per_token,
               "state_bytes_per_slot": st.state_bytes_per_slot,
               "decode_state_rows_read": st.decode_state_rows_read,
               "decode_state_rows_live": st.decode_state_rows_live,
               "swa_keys_attended": st.swa_keys_attended,
               "swa_keys_in_context": st.swa_keys_in_context,
               "prompts": []}
    finally:
        eng.stop()
    params = eng.params
    del eng  # the cache leaves the device; the weights stay
    gc.collect()
    readings = (("", {}), ("all_global_", {"windowed": False}),
                ("no_sink_", {"sinks": False}))
    for s in streams:
        seq = np.asarray(s["prompt"] + s["tokens"], np.int32)
        first = len(s["prompt"]) - 1
        at = first + np.arange(len(s["tokens"]))
        got = {"prompt_tokens": len(s["prompt"]),
               "answers": len(s["tokens"])}
        for name, kw in readings:
            want = np.asarray(jax.nn.log_softmax(ref.forward(
                params, cfgd, seq, positions=at, block=BLOCK, **kw),
                axis=-1))
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
        HERE, "configs", "window", "mimo-v2.5-1chip.json"))
    ap.add_argument("--prompts", default="11100,1200")
    ap.add_argument("--answers", type=int, default=160)
    ap.add_argument("--kernel-tokens", type=int, default=3072)
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
    from cellbench.reference import mimo_v2_ref as ref

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
        print(f"reference_check_window: runs on {dev.platform!r}, wants "
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
    ok, controls_fail = True, True
    control_ok = None
    if "kernels" in parts:
        n = min(args.kernel_tokens, len(prompts[0]))
        got = out["kernels"] = kernels(params, cfg, cfgd, ref,
                                       prompts[0][:n])

        def within(r):
            return all(r[k] < LIMITS[k]
                       for k in ("attn_rel", "route_moved"))

        ok = within(got["served"]) \
            and got["served"]["decode_attn_rel"] < LIMITS["attn_rel"]
        control_ok = within(got["control"])
        controls_fail = not control_ok
    if "served" in parts:
        got = out["served"] = served(params, cfg, cfgd, fns, ref, flags,
                                     prompts, args.answers)
        keys = ("logprob_max", "logprob_mean")
        ok = ok and all(p[k] < LIMITS[k] for p in got["prompts"]
                        for k in keys)
        # a program that skipped a mechanism: over a limit on EVERY
        # prompt the mechanism acts on (each is long enough for both)
        for pre in ("all_global_", "no_sink_"):
            fails = all(any(p[pre + k] >= LIMITS[k] for k in keys)
                        for p in got["prompts"])
            out[pre + "ok"] = not fails
            controls_fail = controls_fail and fails
    out["ok"], out["control_ok"] = ok, control_ok
    out["controls_fail"] = controls_fail
    os.makedirs(os.path.join(os.path.dirname(HERE), "chiprun_out"),
                exist_ok=True)
    with open(os.path.join(os.path.dirname(HERE), "chiprun_out",
                           "reference_check_window.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    if args.judge == "control":
        return 0 if control_ok else 1
    if not ok:
        return 1
    return 0 if controls_fail else 2


if __name__ == "__main__":
    sys.exit(main())
