"""What the benchmark gained with the hybrid family
(``qwen3-next-80b-a3b-1chip``): the configuration file against the
published keys, the operations-and-bytes functions against hand counts,
the new readers on made-up scrapes (a value where the counters are,
nothing — never an exception — where the program has none), the
reference's copy against its original, and one dry run of a tiny cell
of the family through the whole harness on the CPU."""

import importlib
import json
import os
import subprocess
import sys

import pytest

import cellbench_sandbox as sb

REPO = sb.REPO
sys.path.insert(0, REPO)

from cellbench import roofline_hybrid as rh  # noqa: E402

# in a folder of its own: tests/cellbench/test_cellbench_aot.py compiles
# every file directly under configs/ through the llama skeleton's entry
# points, which this family does not have (its own compile is below)
CONFIG = os.path.join(REPO, "cellbench", "configs", "hybrid",
                      "qwen3-next-80b-a3b-1chip.json")
with open(CONFIG) as _f:
    DOC = json.load(_f)
M = rh.dims(DOC)

#: the source's config.json, every number of it
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4,
    "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512,
    "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_key(key):
    cb = DOC["cellbench"]
    if key in cb["reduced"]:
        assert cb["published"][key] == PUBLISHED[key]
        assert DOC[key] < PUBLISHED[key] and key in cb["assumed"]
    else:
        assert DOC[key] == PUBLISHED[key]


def test_the_cut_is_the_chips_share_of_the_stated_deployment():
    cb = DOC["cellbench"]
    assert cb["reduced"] == ["num_hidden_layers", "num_experts",
                             "vocab_size"]
    # whole periods; a quarter of the experts and of the vocabulary
    assert DOC["num_hidden_layers"] % DOC["full_attention_interval"] == 0
    assert DOC["num_experts"] * 4 == PUBLISHED["num_experts"]
    assert DOC["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert cb["model_fields"] == {"router_experts": 512, "held_from": 0}
    assert "EP4" in cb["deployment"] and "four pipeline stages" \
        in cb["deployment"]
    for said in ("weights", "kv", "state", "mtp", "tokenizer",
                 "chat_template", "idle_share"):
        assert said in cb["assumed"]
    assert "--quantize" not in cb["serve_flags"]


def test_the_program_takes_the_file_and_counts_the_same_parameters():
    import jax

    from aigw_tpu.models import qwen3_next as qn
    from cellbench import serve_child

    assert serve_child.config_class("qwen3_next") is qn.Qwen3NextConfig
    cfg = qn.Qwen3NextConfig(**serve_child.model_kwargs(DOC))
    assert (cfg.router_width, cfg.n_experts, cfg.n_layers) == (512, 128, 12)
    shapes = jax.eval_shape(
        lambda: qn.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert n == rh.param_count(M) == 5423084736
    assert DOC["cellbench"]["expect"]["param_bytes_total"] == 2 * n
    spec = cfg.cache_spec()
    assert spec.state_bytes_per_slot("bfloat16") == rh.state_bytes_per_slot(M)
    assert spec.kv_page_bytes(128, "bfloat16") == 128 * rh.kv_bytes_per_token(M)


def test_hand_counts():
    # in_proj_qkvz 2048 x 12288, in_proj_ba 2048 x 64, out_proj 4096 x 2048
    assert rh.gdn_proj_params(M) == 25165824 + 131072 + 8388608
    # q (query + gate) 2048 x 8192, k and v 2048 x 512 each, o 4096 x 2048
    assert rh.attn_params(M) == 16777216 + 2 * 1048576 + 8388608
    assert rh.expert_params(M) == 3 * 2048 * 512
    assert rh.shared_params(M) == 3 * 2048 * 512 + 2048
    # 9 layers x (2 MiB of float32 state + 48 KiB of bfloat16 tail)
    assert rh.state_bytes_per_slot(M) == 9 * (2 * 2 ** 20 + 48 * 2 ** 10)
    assert rh.kv_bytes_per_token(M) == 6 * 2 ** 10
    assert (M["n_lin"], M["n_full"], M["R"], M["E"]) == (9, 3, 512, 128)


def test_kernel_counts():
    # one token, one live slot: decay, S'k, the write, S'q over 32x128x128
    flops, nbytes = rh.gdn_recurrent(M, 1)
    assert flops == 7 * 32 * 128 * 128 and nbytes == 2 * 32 * 128 * 128 * 4
    # a 256-token chunk places 256 x 10 / 4 = 640 assignments here
    flops, nbytes = rh.moe_experts(M, 256)
    assert flops == 2 * 640 * 3145728
    assert 126.0 < rh.experts_touched(M, 640) < 128.0
    assert nbytes == pytest.approx(2 * rh.experts_touched(M, 640) * 3145728)
    # 32 live rows place 80: about 60 of the 128 held experts are hit
    assert 59.0 < rh.experts_touched(M, 80) < 61.0
    flops, _ = rh.gdn_chunk(M, 256)
    # kk' and qk', the solve, T.v, T.k and the local product, then w.S,
    # q.S and the state's update
    per_block = (4 * 64 * 64 * 128 + 64 ** 3 / 3 + 3 * 2 * 64 * 64 * 128
                 + 3 * 2 * 64 * 128 * 128)
    assert flops == pytest.approx(4 * 32 * per_block)
    assert rh.moe_route(M, 1) == (2 * 2048 * 512, 4 * 2048 * 512)


def test_decode_step_and_prefill_call_bounds():
    weights = 2 * rh.param_count(M)
    full = rh.decode_step_bytes(DOC, 32, 32 * 2600 * 6144, 128.0)
    # everything but the embedding's rows, plus state in and out and KV
    assert weights - 2 * M["V"] * M["D"] * 1.01 < full - 2 * 32 * \
        rh.state_bytes_per_slot(M) - 32 * 2600 * 6144 < weights
    # fewer experts hit, fewer bytes: 60 of 128 saves 68 experts a layer
    less = rh.decode_step_bytes(DOC, 32, 32 * 2600 * 6144, 60.0)
    assert full - less == pytest.approx(12 * 68 * 2 * 3145728)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t256 = rh.prefill_call_seconds(DOC, 256, peaks)
    t64 = rh.prefill_call_seconds(DOC, 64, peaks)
    # both byte-bound (the weights stream): 13 ms at 256 tokens, less at 64 (fewer experts touched)
    assert 0.008 < t64 < t256 < 0.014
    flops, nbytes = rh.prefill_call(DOC, 256, 128)
    assert flops / 197e12 < nbytes / 819e9


def _ctx(states0, states2, trace, rates):
    return {"config": DOC, "device_kind": "TPU v5 lite", "traces": [trace],
            "rates": [rates], "snap0": {"states": [states0]},
            "snap1": None, "snap2": {"states": [states2]}}


def test_roofline_readers_on_a_made_up_capture():
    s0 = dict.fromkeys(
        ["capture_decode_steps", "capture_tokens_generated",
         "capture_moe_held_hits_decode", "capture_prefill_calls",
         "capture_prefill_tokens_padded"], 0)
    s2 = {"capture_decode_steps": 100, "capture_tokens_generated": 2400,
          "capture_moe_held_hits_decode": 100 * 12 * 50,
          "capture_prefill_calls": 40, "capture_prefill_tokens_padded": 9600,
          "state_bytes_per_slot": rh.state_bytes_per_slot(M),
          "state_bytes_total": 32 * rh.state_bytes_per_slot(M)}
    trace = {"devices": 1, "window_s": 4.0,
             "groups": {"decode": {"seconds": 2.0, "runs": 13},
                        "prefill": {"seconds": 1.2, "runs": 40}}}
    rates = {"kv_bytes_in_use": 24 * (rh.state_bytes_per_slot(M)
                                      + 2600 * 6144),
             "decode_steps_per_s": 25.0}
    ctx = _ctx(s0, s2, trace, rates)
    dec = importlib.import_module("cellbench.readers.roofline_hybrid_decode")
    pre = importlib.import_module("cellbench.readers.roofline_hybrid_prefill")
    sv = importlib.import_module("cellbench.readers.state_value")
    want = 100 * rh.decode_step_bytes(
        DOC, 24.0, 24 * 2600 * 6144, 50.0) * 100 / 819e9 / 2.0
    assert dec.read(ctx, {}) == pytest.approx(want)
    assert 20.0 < dec.read(ctx, {}) < 60.0
    assert pre.read(ctx, {}) == pytest.approx(
        100 * 40 * rh.prefill_call_seconds(
            DOC, 240, {"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9}) / 1.2)
    assert sv.read(ctx, {"key": "state_bytes_total", "scale": 1e-9}) == \
        pytest.approx(0.618, rel=0.01)
    # a program from before the counters, and a CPU's trace: nothing
    bare = _ctx({}, {}, trace, rates)
    assert dec.read(bare, {}) is None and pre.read(bare, {}) is None
    assert sv.read(bare, {"key": "state_bytes_total"}) is None
    cpu = _ctx(s0, s2, dict(trace, devices=0), rates)
    assert dec.read(cpu, {}) is None and pre.read(cpu, {}) is None


def test_the_references_copy_is_the_original():
    with open(os.path.join(REPO, "aigw_tpu", "models", "reference",
                           "qwen3_next_ref.py")) as a, \
            open(os.path.join(REPO, "cellbench", "reference",
                              "qwen3_next_ref.py")) as b:
        assert a.read() == b.read()
    with open(os.path.join(REPO, "cellbench", "reference",
                           "qwen3_next_ref.py")) as f:
        assert "aigw_tpu" not in f.read().split('"""', 2)[2]


# -- the cut stays honest: the real programs fit a described v5e -----------
@pytest.fixture(scope="module")
def v5e():
    import jax
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu on this box
        pytest.skip(f"libtpu cannot describe a v5e topology: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_fits_one_v5e_chip(v5e, program):
    """The decode step and one 256-token chunk at the file's widths,
    depth, slots and both pools compile for a described (not attached)
    TPU v5e and fit its 16 GB beside the weights (test_cellbench_aot.py
    does this for the families of the llama skeleton)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from aigw_tpu.models import qwen3_next as qn
    from cellbench import serve_child

    cfg = qn.Qwen3NextConfig(**serve_child.model_kwargs(DOC))
    B, page, P = 32, 128, 32
    shapes = {
        "p": jax.eval_shape(
            lambda: qn.init_params(jax.random.PRNGKey(0), cfg)),
        "cache": jax.eval_shape(lambda: cfg.cache_spec().make(
            (B * P + 1) * page, B, "bfloat16"))}
    i32 = jnp.int32
    if program == "decode":
        fn = functools.partial(qn.decode_step, cfg=cfg, page_size=page)
        shapes.update(
            tokens=jax.ShapeDtypeStruct((B,), i32),
            positions=jax.ShapeDtypeStruct((B,), i32),
            page_table=jax.ShapeDtypeStruct((B, P), i32),
            active=jax.ShapeDtypeStruct((B,), jnp.bool_))
    else:
        fn = functools.partial(qn.prefill_suffix, cfg=cfg, page_size=page)
        shapes.update(
            tokens=jax.ShapeDtypeStruct((1, 256), i32),
            prefix_lens=jax.ShapeDtypeStruct((1,), i32),
            seq_lens=jax.ShapeDtypeStruct((1,), i32),
            page_table=jax.ShapeDtypeStruct((1, P), i32),
            slot_ids=jax.ShapeDtypeStruct((1,), i32))
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e),
        shapes)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        m = jax.jit(fn, donate_argnames=("cache",)).lower(
            **placed).compile().memory_analysis()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert need < 15.75 * 2 ** 30, f"{need / 1e9:.2f} GB does not fit"


# -- a tiny cell of the family through the whole harness, on the CPU -------
TINY = {
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 8,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "num_experts": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "norm_topk_prob": True, "max_position_embeddings": 512,
}
MIX = {
    "name": "t-long", "loop": "open", "rate_per_s": 3.0,
    "arrivals": {"process": "poisson", "zero_gap_share": 0.25},
    # with its answer every request is 2 pages of 128: one page bucket,
    # one chunk program [1,32] and one tail program [1,64] for the tour
    "prompt_tokens": {"dist": "uniform", "min": 130, "max": 150},
    "output_tokens": {"dist": "uniform", "min": 6, "max": 16},
    "sharing": {"kind": "none"}, "serve_flags": [],
    "lead_in": {"tour": [[[140, 40], [135, 8, 0.1]]], "traffic_seconds": 2},
}


def tiny_doc() -> dict:
    doc = dict(TINY)
    doc["cellbench"] = {
        "name": "t-qn", "source": "tests", "family": "qwen3_next",
        "chat_template": "chatml", "reduced": [], "assumed": {},
        "fields": {k: k for k in TINY},
        "model_fields": {"router_experts": 16, "held_from": 4},
        "serve_flags": ["--platform", "cpu", "--max-batch-size", "4",
                        "--max-seq-len", "512", "--page-size", "128",
                        "--prefill-bucket-rungs", "1",
                        "--prefill-chunk-tokens", "32"],
        "module_groups": "xla_default", "replicas": 1, "chips": 1,
        "expect": {"platform": "cpu", "param_bytes_total": 1375904.0},
    }
    return doc


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    dst = sb.make_checkout(str(tmp_path_factory.mktemp("qn")))
    sb.add_file(dst, "cellbench/configs/t-qn.json", tiny_doc())
    sb.add_file(dst, "cellbench/traffic/t-long.json", MIX)
    cell = "t-qn.long"
    layer = [("moe_local_share.t", "%"), ("moe_held_experts_hit.t",
                                          "experts/step"),
             ("state_gb.t", "GB"), ("hybrid_decode_roofline.t", "%"),
             ("hybrid_prefill_roofline.t", "%")]
    sb.add_entries(
        dst,
        configs=[{"name": "t-qn", "source": "tests",
                  "file": "cellbench/configs/t-qn.json", "reduced": [],
                  "why": "test"}],
        workloads=[{"name": cell, "config": "t-qn", "traffic": "t-long",
                    "chips": 1, "why": "test"}],
        end_to_end=[{"name": "tpot_mean_ms.t", "unit": "ms",
                     "better": "lower", "bound": 0.1,
                     "source": "host_clock", "workloads": [cell]}],
        per_layer=[{"name": n, "unit": u, "better": "higher",
                    "source": "program_counter", "layer": "expert layer",
                    "moves": "tpot_mean_ms.t", "workloads": [cell]}
                   for n, u in layer])
    return dst


def test_tiny_cell_end_to_end(checkout):
    rc, last, lines, err = sb.run_cell(checkout, "t-qn.long", 2 ** 31 + 5,
                                       4, 0, timeout=600)
    assert rc == 0, err[-3000:]
    summary = json.loads(lines[-2])
    assert last["correct"] is True, summary
    assert set(last["metrics"]) == {"tpot_mean_ms.t", "setup_s"}
    # the judged mean IS the summary line's (cellbench/run.py prints both)
    assert last["metrics"]["tpot_mean_ms.t"]["value"] == \
        summary["tpot_ms"]["mean"] > 0.0
    assert last["attempted"] >= 8 and last["failed"] == 0
    assert summary["checks"]["ledger_reconciles"]
    assert summary["checks"]["no_compile_in_window"], summary


def test_tiny_cell_traced_reports_the_counter_metrics(checkout):
    rc, last, lines, err = sb.run_cell(checkout, "t-qn.long", 77, 5, 1,
                                       timeout=600)
    assert rc == 0, err[-3000:]
    got = {k: v["value"] for k, v in last["metrics"].items()}
    # 8 of a 16-wide router's experts are held: about half land here
    assert 35.0 < got["moe_local_share.t"] < 65.0
    assert got["state_gb.t"] == pytest.approx(4 * 33792e-9 * 2, rel=0.6)
    assert "moe_held_experts_hit.t" in got
    # a CPU's trace has no device plane: no roofline, and no exception
    assert "hybrid_decode_roofline.t" not in got
    assert "hybrid_prefill_roofline.t" not in got
    assert last["correct"] is True


def _reference_check(cfg, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench",
                                      "reference_check.py"),
         "--config", str(cfg), "--prompts", "100,37", "--answers", "8",
         "--platform", "cpu", *more],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.stdout.strip(), out.stderr[-2000:]
    return out.returncode, json.loads(out.stdout.strip().splitlines()[-1])


def test_reference_check_runs_at_a_tiny_size(tmp_path):
    cfg = tmp_path / "t-qn.json"
    cfg.write_text(json.dumps(tiny_doc()))
    rc, got = _reference_check(cfg)
    # the served programs carry bfloat16 weights here too: 0 or 1 by
    # the log-probabilities, never 2 (a control that passes)
    assert rc in (0, 1) and got["control_ok"] is False
    assert got["ok"] is (rc == 0)
    assert got["served"]["chunk_steps"] == 4
    assert len(got["served"]["prompts"]) == 2
    for p in got["served"]["prompts"]:
        assert p["answers"] == 8
        assert p["logprob_max"] >= p["logprob_mean"] > 0
    k = got["kernels"]
    assert (k["chunked"], k["recurrent"], k["router_rows"]) == (100, 8, 108)
    # the same inputs on both sides: the program's kernels sit on the
    # float32 reference, a bfloat16 state (alone, or with everything
    # else) a factor of thousands away, the limit between them
    limit = got["limits"]["gdn_rel"]
    for key in ("gdn_out_rel", "gdn_state_rel"):
        assert k["served"][key] < limit / 30
        assert k["bfloat16_state_only"][key] > 3 * limit
        assert k["control"][key] > 3 * limit
    assert k["served"]["route_moved"] == 0.0


def test_reference_check_fails_the_lower_precision_control(tmp_path):
    cfg = tmp_path / "t-qn.json"
    cfg.write_text(json.dumps(tiny_doc()))
    rc, got = _reference_check(cfg, "--parts", "kernels", "--judge",
                               "control")
    assert rc == 1 and got["control_ok"] is False and got["ok"] is True
    assert "served" not in got


def test_per_layer_counter_reader_takes_the_depth_from_the_file():
    rd = importlib.import_module("cellbench.readers.loop_counter_per_layer")
    args = {"num": ["moe_held_hits_decode"], "den": ["decode_steps"],
            "layers": "num_hidden_layers"}
    ctx = {"config": {"num_hidden_layers": 12},
           "snap0": {"state": {"moe_held_hits_decode": 10,
                               "decode_steps": 5}},
           "snap1": {"state": {"moe_held_hits_decode": 10 + 12 * 7 * 100,
                               "decode_steps": 105}}}
    assert rd.read(ctx, args) == pytest.approx(7.0)
    assert rd.read(dict(ctx, config={"num_hidden_layers": 8}),
                   args) == pytest.approx(10.5)
    # no depth in the file, a program without the counter, a late scrape
    assert rd.read(dict(ctx, config={}), args) is None
    assert rd.read(dict(ctx, snap0={"state": {"decode_steps": 5}}),
                   args) is None
    assert rd.read(dict(ctx, snap1=None), args) is None
    with open(os.path.join(REPO, "cellbench", "layer_metrics",
                           "moe_held_experts_hit.json")) as f:
        assert json.load(f)["args"] == args
