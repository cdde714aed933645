#!/usr/bin/env python3
"""The reference comparison of a hybrid-family configuration at its
published widths, on the device the configuration expects::

    python3 cellbench/reference_check.py [--config cellbench/configs/hybrid/<name>.json]
        [--prompts 1400,600] [--answers 64] [--parts kernels,served]
        [--judge served|control] [--platform cpu]

Builds the configuration's model as ``serve_child.py`` registers it,
seeded random weights as the server makes them, and compares with the
float32 reference (``cellbench/reference/``: the highest matmul
precision, one sequence, token by token) in two parts.

``served`` — the programs the server dispatches. The serving ENGINE
with the configuration's own geometry (its ``serve_flags``) and
``logprobs_topk``, so that its own jitted chunk, tail and decode-window
programs hand back the log-probabilities of their top candidates at
every sampled position. Two prompts of different lengths go into
different slots, the first crossing several chunk boundaries while the
second arrives; each decodes ``--answers`` tokens. The reference then
reads prompt + the tokens the engine sampled, layer by layer, and every
candidate's log-probability is compared. This part tells a wrong model
(a dropped assignment, a missing gate, a lost state) from the right
one. It CANNOT tell precisions apart: the programs' activations are
bfloat16 as the configuration states, that rounding alone reads a
largest difference of 0.4-0.6 and a mean of 0.08-0.09 at these widths,
and the reference computed in bfloat16 THROUGHOUT reads 0.44-0.65 and
0.10 (PERF.md section 6, PR 28): a float32 state is worth less than
the activations' rounding in the logits.

``kernels`` — the precision the configuration states beside bfloat16
activations: a float32 DeltaNet state through float32 products, and a
router that picks in float32. The rounding of the activations is taken
out by giving both sides THE SAME inputs: the first DeltaNet layer's
q, k, v, g and beta of the first prompt (+ ``--answers`` tokens), made
once by the program's own projection, convolution and normalisation
from the configuration's weights. The program's kernels then run as
the served programs call them — ``_gdn_chunk`` over 256-token chunks
with the state carried from chunk to chunk and a padded tail, then
``_gdn_recurrent`` token by token — and the reference's ``delta_rule``
runs token by token in float32. Compared: every token's output and the
state after the last, as the largest relative error of a head. The
router: the program's expert layer over the same bfloat16 hidden rows
(every token's), its stats tape's count of assignments a held expert,
against the reference's picks; the reading is the share of the held
assignments that sit on another expert.

The control is the reference in the nearest precision below the stated
one (bfloat16 throughout: a bfloat16 state, a bfloat16 router), judged
as if it were the system: it has to come out NOT ok, by the kernels'
limits. ``LIMITS`` lie between the two readings (PERF.md section 6 has
both). Exit code, ``--judge served``: 0 the system is ok and the
control is not; 1 the system is not ok; 2 the control passes, so the
limits hold nothing. ``--judge control``: the control's own verdict, 0
ok (it must not be) or 1. One process, which holds the chip."""

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
#: what a reading may reach. On the chip at the published widths (my
#: chip runs, PR 28) the program's kernels read ``gdn_rel`` 6.6e-6
#: (outputs; 2.1e-7 the final state) where a bfloat16 state ALONE reads
#: 2.6e-3 and 4.9e-3 and the all-bfloat16 control 4.8e-3 and 8.1e-3:
#: the limit is over the one by 23 and under the others by 17 and 32.
#: ``route_moved``: the program 0, the bfloat16 router 0.0069.
#: ``logprob_max`` / ``logprob_mean`` are over the served programs'
#: readings (0.60, 0.091) and tell a wrong model, not a precision.
LIMITS = {"gdn_rel": 1.5e-4, "route_moved": 2e-3,
          "logprob_max": 1.0, "logprob_mean": 0.2}


def flag(flags: list[str], name: str, default: int) -> int:
    return int(flags[flags.index(name) + 1]) if name in flags else default


def kernels(params, cfg, cfgd, ref, tokens, chunk: int, answers: int):
    """The ``kernels`` part: readings of the program's kernels and of
    the all-bfloat16 control, both against the float32 reference on the
    same inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aigw_tpu.models import llama, qwen3_next as qn

    i = cfg.layer_kinds.index("linear")
    S = len(tokens)
    n_prompt = S - answers

    @jax.jit
    def inputs(p, toks):
        x = llama._embed_rows(p, toks[None])
        h = qn._norm(x, p[f"l{i}.in_norm"], cfg.rms_norm_eps)
        mixed, _, beta, g = qn._gdn_project(p, i, h, cfg)
        tail = jnp.zeros((1, cfg.linear_conv_kernel_dim - 1, cfg.conv_dim),
                         mixed.dtype)
        y, _ = qn._gdn_conv(p, i, mixed, tail, jnp.full((1,), S, jnp.int32))
        return (h, *qn._gdn_heads(y, cfg), g, beta)

    h, q, k, v, g, beta = inputs(params, jnp.asarray(tokens, jnp.int32))

    # the program, as _sequence and decode_step call it: chunks of the
    # prompt with the state carried, the last one right-padded with
    # tokens that neither decay nor write; then token by token
    chunk_fn = jax.jit(qn._gdn_chunk)
    step_fn = jax.jit(qn._gdn_recurrent)
    state = jnp.zeros((1, cfg.linear_num_value_heads,
                       cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                      jnp.float32)
    outs = []
    for a in range(0, n_prompt, chunk):
        b = min(a + chunk, n_prompt)
        pad = [(0, 0), (0, a + chunk - b)]
        qc, kc, vc = (jnp.pad(t[:, a:b], pad + [(0, 0), (0, 0)],
                              constant_values=1.0) for t in (q, k, v))
        gc_, bc = (jnp.pad(t[:, a:b], pad + [(0, 0)]) for t in (g, beta))
        o, state = chunk_fn(qc, kc, vc, gc_, bc, state)
        outs.append(o[:, : b - a])
    for t in range(n_prompt, S):
        o, state = step_fn(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                           state)
        outs.append(o[:, None])
    o_prog = jnp.concatenate(outs, axis=1)[0]

    def rel(a, b, head_axis):
        """Largest relative error (Frobenius) of a head."""
        axes = tuple(x for x in range(a.ndim) if x != head_axis)
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.sqrt(jnp.sum((a - b) ** 2, axes)
                                      / jnp.sum(b ** 2, axes))))

    seq = (q[0], k[0], v[0], g[0], beta[0])
    o_ref, s_ref = ref.delta_rule(*seq)
    o_low, s_low = ref.delta_rule(*(t.astype(jnp.bfloat16) for t in seq))
    o_st, s_st = ref.delta_rule(*seq, state_dtype=jnp.bfloat16)

    # the router: the program's expert layer over the same bfloat16
    # rows; its tape counts the real tokens' assignments a held expert
    E = cfg.num_experts
    rows = h

    @jax.jit
    def placed(p, x):
        tape: list = []
        qn.moe(p, i, x, cfg, tape=tape)
        return tape[0][:E]

    def counts(dtype):
        with ref.computed_in(dtype):
            _, topi = ref.route(params, i, cfgd, rows[0].astype(dtype))
        held = np.asarray(topi) - cfgd.get("held_from", 0)
        return np.bincount(held[(held >= 0) & (held < E)], minlength=E)

    want = counts(jnp.float32)

    def moved(got):
        return float(np.abs(np.asarray(got) - want).sum() / 2 / want.sum())

    return {
        "layer": i, "tokens": S, "chunked": n_prompt, "recurrent": answers,
        "router_rows": int(rows.shape[1]), "held_assignments": int(want.sum()),
        "served": {
            "gdn_out_rel": rel(o_prog, o_ref, 1),
            "gdn_state_rel": rel(state[0], s_ref, 0),
            "route_moved": moved(placed(params, rows))},
        "control": {
            "gdn_out_rel": rel(o_low, o_ref, 1),
            "gdn_state_rel": rel(s_low, s_ref, 0),
            "route_moved": moved(counts(jnp.bfloat16))},
        # the narrowest fault of its kind: ONLY the state in bfloat16
        "bfloat16_state_only": {
            "gdn_out_rel": rel(o_st, o_ref, 1),
            "gdn_state_rel": rel(s_st, s_ref, 0)},
    }


def served(params, cfg, cfgd, fns, ref, flags, prompts, answers: int):
    """The ``served`` part: the engine's own programs against the
    reference's log-probabilities."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
    from aigw_tpu.tpuserve.sampling import SamplingParams

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
        out = {"chunk_steps": eng.stats.chunked_prefill_steps,
               "decode_steps": eng.stats.decode_steps, "prompts": []}
    finally:
        eng.stop()
    params = eng.params
    del eng  # the cache leaves the device; the weights stay
    gc.collect()
    for s in streams:
        seq = np.asarray(s["prompt"] + s["tokens"], np.int32)
        first = len(s["prompt"]) - 1
        want = np.asarray(jax.nn.log_softmax(ref.forward(
            params, cfgd, seq,
            positions=first + np.arange(len(s["tokens"]))), axis=-1))
        diffs = [abs(float(want[j, t]) - lp)
                 for j, top in enumerate(s["tops"]) for t, lp in top]
        assert len(diffs) == TOPK * answers and first >= 0
        out["prompts"].append({
            "prompt_tokens": len(s["prompt"]), "answers": len(s["tokens"]),
            "logprob_max": max(diffs),
            "logprob_mean": sum(diffs) / len(diffs)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        HERE, "configs", "hybrid", "qwen3-next-80b-a3b-1chip.json"))
    ap.add_argument("--prompts", default="1400,600")
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
    from cellbench.reference import qwen3_next_ref as ref

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
        print(f"reference_check: runs on {dev.platform!r}, wants {want!r}",
              file=sys.stderr)
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
        got = out["kernels"] = kernels(
            params, cfg, cfgd, ref, prompts[0] + more,
            flag(flags, "--prefill-chunk-tokens", 256), args.answers)

        def within(r):
            return (max(r["gdn_out_rel"], r["gdn_state_rel"])
                    < LIMITS["gdn_rel"]
                    and r["route_moved"] < LIMITS["route_moved"])

        ok = within(got["served"])
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
                           "reference_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    if args.judge == "control":
        return 0 if control_ok else 1
    if not ok:
        return 1
    return 2 if control_ok else 0


if __name__ == "__main__":
    sys.exit(main())
