#!/usr/bin/env python3
"""The reference comparison of the Olmo-Hybrid configuration at its
published widths, on the device the configuration expects::

    python3 cellbench/reference_check_olmo_hybrid.py [--config <file>]
        [--system 1024] [--users 384,384,384] [--answers 128]
        [--parts kernels,served] [--judge served|control] [--platform cpu]

Builds the configuration's model as ``serve_child.py`` registers it,
seeded random weights as the server makes them, and compares with the
float32 reference (``cellbench/reference/olmo_hybrid_ref.py``: the
highest matmul precision, one sequence, the recurrence token by token,
the history in blocks so that the attention's scores fit) in two parts.

``served`` — the programs the server dispatches, under the traffic the
cell sends. The serving ENGINE with the configuration's own geometry
(its ``serve_flags``) and ``logprobs_topk``: ONE SESSION of three turns
at the cell's lengths — a system prompt, then a user message a turn,
every turn resending the whole history with the answers the engine
itself sampled. The second and third turn must RESUME FROM A SNAPSHOT
(the prefix cache adopts the pages, the engine copies the slot's
recurrent state back from the snapshot pool, ``prefill_suffix`` goes on
from the chunk boundary): the part fails if they do not. The reference
then reads each turn's whole history, layer by layer, and every
candidate's log-probability at every sampled position is compared.
Read against the right model this tells a wrong program from a right
one — and the same engine output is read against two WRONG models,
both of which must lie over the limits, or the limits hold nothing:
``beta_sigmoid`` (``beta`` left at ``sigmoid``, without the factor 2
that ``linear_allow_neg_eigval`` states) and ``state_lost`` (the
DeltaNet layers start from an empty state at the token where the
turn's prefill resumed: a hit that adopted the pages and ignored the
snapshot). It CANNOT tell precisions apart: the programs' activations
are bfloat16 as the configuration states.

``kernels`` — the precision the configuration states beside bfloat16
activations: a float32 DeltaNet state through float32 products, at this
family's widths (30 heads, keys 96 under values 192) and its ``beta``
up to 2. Both sides get THE SAME inputs: the first DeltaNet layer's
q, k, v, g and beta of the first turn's prompt (+ ``--answers``
tokens), made once by the program's own projections, convolution and
normalisation. The program's kernels run as the served programs call
them — ``_gdn_chunk`` over 256-token chunks with the state carried
from chunk to chunk and a padded tail, then ``_gdn_recurrent`` token by
token — and the reference's ``delta_rule`` runs token by token in
float32. Compared: every token's output and the state after the last,
as the largest relative error of a head. The control is the same rule
with ONLY the state kept in bfloat16 between tokens (the nearest
precision below the stated one), judged as if it were the system: it
has to come out NOT ok. ``LIMITS`` lie between the readings (PERF.md
section 6 has them).

Exit code, ``--judge served``: 0 the system is ok and every wrong
reading is not; 1 the system is not ok; 2 a wrong reading passes, so
the limits hold nothing. ``--judge control``: the kernels' control's
own verdict, 0 ok (it must not be) or 1. One process, which holds the
chip."""

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
#: what a reading may reach; between the system's readings and the
#: wrong ones' on the chip at the published widths (PERF.md section 6,
#: PR 52, has every reading). ``gdn_rel``: the kernels' largest
#: relative error of a head against the float32 rule. ``logprob_max`` /
#: ``logprob_mean``: the served programs' top-20 log-probabilities
#: against the reference's; they tell a wrong model, not a precision.
LIMITS = {"gdn_rel": 1.2e-3, "logprob_max": 0.3, "logprob_mean": 0.07}
#: tokens the reference runs at a time (bounds its score matrix)
BLOCK = 512


def flag(flags: list[str], name: str, default: int) -> int:
    return int(flags[flags.index(name) + 1]) if name in flags else default


def kernels(params, cfg, ref, tokens, chunk: int, answers: int):
    """The ``kernels`` part: readings of the program's kernels and of
    the bfloat16-state control, both against the float32 rule on the
    same inputs."""
    import jax
    import jax.numpy as jnp

    from aigw_tpu.models import llama, olmo_hybrid as oh, qwen3_next as qn

    i = cfg.layer_kinds.index("linear")
    S = len(tokens)
    n_prompt = S - answers

    @jax.jit
    def inputs(p, toks):
        x = llama._embed_rows(p, toks[None])
        # (a few layers in would be nearer to served inputs; the first
        # DeltaNet layer reads the embedding itself: no norm before it)
        mixed, _, beta, g = oh._gdn_project(p, i, x, cfg)
        tail = jnp.zeros((1, cfg.linear_conv_kernel_dim - 1, cfg.conv_dim),
                         mixed.dtype)
        y, _ = qn._gdn_conv(p, i, mixed, tail, jnp.full((1,), S, jnp.int32))
        return (*qn._gdn_heads(y, cfg), g, beta)

    q, k, v, g, beta = inputs(params, jnp.asarray(tokens, jnp.int32))
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
    o_st, s_st = ref.delta_rule(*seq, state_dtype=jnp.bfloat16)
    o_low, s_low = ref.delta_rule(*(t.astype(jnp.bfloat16) for t in seq))
    return {
        "layer": i, "tokens": S, "chunked": n_prompt, "recurrent": answers,
        "beta_over_one_share": float(jnp.mean(beta > 1.0)),
        "served": {"gdn_out_rel": rel(o_prog, o_ref, 1),
                   "gdn_state_rel": rel(state[0], s_ref, 0)},
        # the nearest precision below the stated one: ONLY the state
        "control": {"gdn_out_rel": rel(o_st, o_ref, 1),
                    "gdn_state_rel": rel(s_st, s_ref, 0)},
        "all_bfloat16": {"gdn_out_rel": rel(o_low, o_ref, 1),
                         "gdn_state_rel": rel(s_low, s_ref, 0)},
    }


def served(params, cfg, cfgd, fns, ref, flags, system, users, answers: int):
    """The ``served`` part: one session through the engine's own
    programs against the reference's log-probabilities."""
    import jax
    import numpy as np

    from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
    from aigw_tpu.tpuserve.sampling import SamplingParams

    eng = Engine(params, cfg, EngineConfig(
        max_batch_size=flag(flags, "--max-batch-size", 8),
        max_seq_len=flag(flags, "--max-seq-len", 2048),
        page_size=flag(flags, "--page-size", 128),
        num_pages=flag(flags, "--hbm-pages", 0),
        prefill_bucket_rungs=flag(flags, "--prefill-bucket-rungs", 2),
        prefill_chunk_tokens=flag(flags, "--prefill-chunk-tokens", 256),
        logprobs_topk=TOPK), fns=fns)
    eng.start()
    turns, history = [], list(system)
    try:
        for user in users:
            history = history + list(user)
            s = {"prompt": history, "tokens": [], "tops": [],
                 "done": threading.Event()}

            def emit(tok, fin, lp, top, s=s):
                if tok >= 0:
                    s["tokens"].append(tok)
                    s["tops"].append(top)
                if fin is not None:
                    s["done"].set()

            before = eng.stats.prefix_tokens_reused
            eng.submit(GenRequest(
                prompt=history, max_tokens=answers, emit=lambda *_: None,
                emit_lp=emit, sampling=SamplingParams(temperature=0.0)))
            if not s["done"].wait(3000):
                raise RuntimeError("the engine did not finish a turn")
            s["resumed_at"] = eng.stats.prefix_tokens_reused - before
            turns.append(s)
            history = history + s["tokens"]
        st = eng.stats
        out = {"chunk_steps": st.chunked_prefill_steps,
               "decode_steps": st.decode_steps,
               "snapshots_saved": st.state_snapshots_saved,
               "snapshots_restored": st.state_snapshots_restored,
               "prefix_tokens_reused": st.prefix_tokens_reused,
               "prefix_tokens_unrestorable": st.prefix_tokens_unrestorable,
               "snapshot_rows": eng.prefix_cache.snapshots.n_rows,
               "turns": []}
    finally:
        eng.stop()
    params = eng.params
    del eng  # the cache and the snapshot pool leave the device
    gc.collect()
    out["resumed"] = all(s["resumed_at"] > 0 for s in turns[1:]) \
        and out["snapshots_restored"] >= len(turns) - 1
    for s in turns:
        seq = np.asarray(s["prompt"] + s["tokens"], np.int32)
        first = len(s["prompt"]) - 1
        at = first + np.arange(len(s["tokens"]))
        row = {"prompt_tokens": len(s["prompt"]),
               "answers": len(s["tokens"]), "resumed_at": s["resumed_at"]}
        readings = {"": {}, "beta_sigmoid": {"wrong": "beta_sigmoid"}}
        if s["resumed_at"]:
            readings["state_lost"] = {"state_lost_at": s["resumed_at"]}
        for name, kw in readings.items():
            want = np.asarray(jax.nn.log_softmax(ref.forward(
                params, cfgd, seq, positions=at, block=BLOCK, **kw),
                axis=-1))
            diffs = [abs(float(want[j, t]) - lp)
                     for j, top in enumerate(s["tops"]) for t, lp in top]
            assert len(diffs) == TOPK * answers and first >= 0
            pre = name + "_" if name else ""
            row[pre + "logprob_max"] = max(diffs)
            row[pre + "logprob_mean"] = sum(diffs) / len(diffs)
        out["turns"].append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        HERE, "configs", "hybrid-dense", "olmo-hybrid-7b-1chip.json"))
    ap.add_argument("--system", type=int, default=1024)
    ap.add_argument("--users", default="384,384,384")
    ap.add_argument("--answers", type=int, default=128)
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
    from cellbench.reference import olmo_hybrid_ref as ref

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

    def draw(n):
        return [int(t) for t in rng.integers(0, cfg.vocab_size, n)]

    system = draw(args.system)
    users = [draw(int(n)) for n in args.users.split(",")]
    parts = args.parts.split(",")
    flags = cb["serve_flags"]
    out = {"config": cb["name"], "device": dev.device_kind, "limits": LIMITS}
    ok, wrong_ok, control_ok = True, False, None
    if "kernels" in parts:
        got = out["kernels"] = kernels(
            params, cfg, ref, system + users[0] + draw(args.answers),
            flag(flags, "--prefill-chunk-tokens", 256), args.answers)

        def within(r):
            return max(r["gdn_out_rel"], r["gdn_state_rel"]) \
                < LIMITS["gdn_rel"]

        ok = within(got["served"])
        control_ok = within(got["control"])
        wrong_ok = control_ok
    if "served" in parts:
        got = out["served"] = served(params, cfg, cfgd, fns, ref, flags,
                                     system, users, args.answers)
        keys = ("logprob_max", "logprob_mean")
        ok = ok and got["resumed"] and all(
            t[k] < LIMITS[k] for t in got["turns"] for k in keys)
        # a wrong model passes if ANY turn it could show in reads inside
        # both limits
        for name in ("beta_sigmoid", "state_lost"):
            for t in got["turns"]:
                if name + "_logprob_max" in t and all(
                        t[f"{name}_{k}"] < LIMITS[k] for k in keys):
                    wrong_ok = True
    out["ok"], out["control_ok"], out["wrong_ok"] = ok, control_ok, wrong_ok
    os.makedirs(os.path.join(os.path.dirname(HERE), "chiprun_out"),
                exist_ok=True)
    with open(os.path.join(os.path.dirname(HERE), "chiprun_out",
                           "reference_check_olmo_hybrid.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    if args.judge == "control":
        return 0 if control_ok else 1
    if not ok:
        return 1
    return 2 if wrong_ok else 0


if __name__ == "__main__":
    sys.exit(main())
