"""What the benchmark gained with the latent-attention family
(``a.x-k1-1chip``): the configuration file against the published keys,
the operations-and-bytes functions against hand counts, the new readers
on made-up captures (a value where the counters are, nothing — never an
exception — where the program has none), the mix to the byte, the
reference's copy against its original, the real programs compiled for a
described v5e, and one dry run of a tiny cell of the family through the
whole harness on the CPU."""

import hashlib
import importlib
import json
import os
import subprocess
import sys

import pytest

import cellbench_sandbox as sb

REPO = sb.REPO
sys.path.insert(0, REPO)

from cellbench import roofline_latent as rl  # noqa: E402
from cellbench import traffic  # noqa: E402

# in a folder of its own, as configs/hybrid/ is: tests/cellbench/
# test_cellbench_aot.py compiles every file directly under configs/
# through the llama skeleton's entry points (this family's own compile
# is below)
CONFIG = os.path.join(REPO, "cellbench", "configs", "latent",
                      "a.x-k1-1chip.json")
with open(CONFIG) as _f:
    DOC = json.load(_f)
M = rl.dims(DOC)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "a.x-k1.long-both"

#: the source's config.json, every key of the catalog row
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
    "v_head_dim": 128, "vocab_size": 163840,
}
#: a width may never be cut
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
          "num_experts_per_tok", "n_group", "topk_group",
          "routed_scaling_factor")


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_key(key):
    cb = DOC["cellbench"]
    if key in cb["reduced"]:
        assert cb["published"][key] == PUBLISHED[key]
        assert DOC[key] < PUBLISHED[key] and key in cb["assumed"]
        assert key not in WIDTHS
    else:
        assert DOC[key] == PUBLISHED[key]


def test_the_published_widths():
    assert [DOC[k] for k in WIDTHS] == [
        7168, 18432, 2048, 1536, 512, 128, 64, 128, 64, 8, 8, 4, 2.5]


def test_the_cut_is_the_chips_share_of_the_stated_deployment():
    cb = DOC["cellbench"]
    assert cb["reduced"] == ["num_hidden_layers", "n_routed_experts",
                             "vocab_size"]
    assert cb["published"] == {"num_hidden_layers": 61,
                               "n_routed_experts": 192,
                               "vocab_size": 163840}
    # the leading dense layer + five expert layers; a sixteenth of the
    # experts (at least 8), an eighth of the vocabulary
    assert DOC["num_hidden_layers"] - DOC["first_k_dense_replace"] == 5 \
        == DOC["expert_layers"]
    assert DOC["n_routed_experts"] * 16 == PUBLISHED["n_routed_experts"]
    assert DOC["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cb["model_fields"] == {"router_experts": 192, "held_from": 0,
                                  "first_dense_layers": 1}
    assert "EP16" in cb["deployment"] and "pipeline stages" \
        in cb["deployment"]
    for said in ("router", "weights", "kv", "rotary", "tensor_layout",
                 "tokenizer", "chat_template", "slots", "attention_share",
                 "idle_share"):
        assert said in cb["assumed"]
    # the one place where the config's own word is unfamiliar
    router = cb["assumed"]["router"]
    assert "topk_method" in router and "no selection bias" in router \
        and "MAXIMUM" in router and "seq_aux" in router
    assert "sixteen times" in cb["assumed"]["attention_share"]
    assert "--quantize" not in cb["serve_flags"]
    assert cb["serve_flags"] == [
        "--max-batch-size", "16", "--max-seq-len", "8192", "--page-size",
        "128", "--prefill-bucket-rungs", "1"]
    assert cb["family"] == "axk1"
    assert cb["source"] == \
        "https://huggingface.co/skt/A.X-K1/blob/main/config.json"


def test_the_program_takes_the_file_and_counts_the_same_parameters():
    import jax

    from aigw_tpu.models import axk1
    from cellbench import serve_child

    assert serve_child.config_class("axk1") is axk1.AXK1Config
    cfg = axk1.AXK1Config(**serve_child.model_kwargs(DOC))
    assert (cfg.router_width, cfg.n_experts, cfg.n_layers) == (192, 12, 6)
    assert cfg.layer_kinds == ("dense",) + ("moe",) * 5
    assert dict(cfg.rope_scaling) == PUBLISHED["rope_scaling"]
    shapes = jax.eval_shape(
        lambda: axk1.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert n == rl.param_count(M) == 4166294528
    assert DOC["cellbench"]["expect"]["param_bytes_total"] == 2 * n
    # ONE 576-wide bfloat16 row a token a layer: 6 x 1152 bytes
    spec = cfg.cache_spec()
    assert spec.kv_page_bytes(128, "bfloat16") == \
        128 * rl.cache_bytes_per_token(M) == 128 * 6 * 1152
    assert spec.kv_shape(2048) == (6, 576, 2048)


def test_hand_counts():
    # the issue's count, by hand: wq_a 7168 x 1536, wq_b 1536 x (64 x
    # 192); wkv_a 7168 x 576; wkv_b 512 x (64 x 256); wo 8192 x 7168
    assert rl.q_params(M) == 11010048 + 18874368
    assert rl.kv_params(M) == 4128768
    assert rl.kvb_params(M) == 8388608
    assert rl.out_params(M) == 58720256
    assert rl.attn_layer_params(M) == 101138432  # 101.12 M + the norms
    assert rl.dense_params(M) == 3 * 7168 * 18432 == 396361728
    assert rl.expert_params(M) == rl.shared_params(M) == 3 * 7168 * 2048
    # the dense layer 497.5 M, an expert layer with 12 held 675.0 M, the
    # sliced embedding + head 293.6 M: 4,166 M, 8.33 GB
    dense = rl.attn_layer_params(M) + rl.dense_params(M)
    moe = rl.attn_layer_params(M) + 7168 * 192 + 13 * rl.expert_params(M)
    assert round(dense / 1e6, 1) == 497.5 and round(moe / 1e6, 1) == 675.0
    assert rl.param_count(M) == dense + 5 * moe + 2 * 20480 * 7168 + 7168
    assert round(2 * rl.param_count(M) / 1e9, 2) == 8.33
    assert (M["n_dense"], M["n_moe"], M["R"], M["E"]) == (1, 5, 192, 12)
    assert rl.row_width(M) == 576
    assert rl.cache_bytes_per_token(M) == 6912


def test_kernel_counts():
    # a 256-token chunk places 256 x 8 / 16 = 128 assignments here
    flops, nbytes = rl.moe_experts(M, 256)
    assert flops == 2 * 128 * 44040192
    assert 11.99 < rl.experts_touched(M, 128) < 12.0
    assert nbytes == pytest.approx(2 * rl.experts_touched(M, 128) * 44040192)
    # ten live rows place 5: about 4.2 of the 12 held experts are hit
    assert 4.0 < rl.experts_touched(M, 5) < 4.3
    assert rl.moe_route(M, 1) == (2 * 7168 * 192, 2 * 7168 * 192)
    # one decode row over 3200 cached rows: the absorbed form is the
    # cheaper (64 heads x (576 + 512) a pair against 64 x 320 a pair +
    # 3200 rows through W_kvb), and its rows are 576 wide
    flops, nbytes = rl.mla_attn(M, 3200, 3200, 1)
    assert flops == 2 * 3200 * 64 * 1088 and nbytes == 2 * 3200 * 576
    # a chunk of 256 queries at context 4096: expanded is (64 x 320 a
    # pair, 4096 rows decompressed once: the issue's 69 GFLOP)
    pairs = sum(3840 + t + 1 for t in range(256))
    flops, nbytes = rl.mla_attn(M, pairs, 4096, 1)
    assert flops == 2 * pairs * 64 * 320 + 2 * 4096 * 8388608
    assert 2 * 4096 * 8388608 == pytest.approx(68.7e9, rel=1e-2)
    assert nbytes == 2 * 4096 * 576
    # the two halves of W_kvb on a token's heads are mla_out's
    assert rl.mla_out(M, 1) == (2 * (58720256 + 8388608),
                                2 * (58720256 + 8388608))


def test_decode_and_prefill_bounds():
    weights = 2 * rl.param_count(M)
    # a step of 10 rows at 3200 (25 pages each): everything but the
    # embedding's rows and the experts nobody hit streams once, and the
    # rows' pages: bandwidth-bound, ~5.6 ms
    hits = 5 * 4
    t = rl.decode_seconds(DOC, 1, 10, 250, hits, 128, PEAKS)
    own = weights - 2 * M["V"] * M["D"] - 2 * 5 * 12 * rl.expert_params(M)
    context = 6 * 2 * 250 * 128 * 576
    assert t == pytest.approx(
        (own + 2 * hits * rl.expert_params(M) + context
         + 6 * 2 * 10 * 576) / 819e9, rel=1e-3)
    assert 0.005 < t < 0.007
    # its FLOP bound counts a row's last page as one token: 10 rows of
    # 24 full pages + 1
    tokens = 240 * 128 + 10
    least_flops = 6 * 2 * tokens * 64 * 1088
    assert least_flops / 197e12 < t / 20
    # more hits, more bytes; a hundred steps, a hundred times the time
    assert rl.decode_seconds(DOC, 1, 10, 250, 40, 128, PEAKS) > t
    assert rl.decode_seconds(DOC, 100, 1000, 25000, 2000, 128,
                             PEAKS) == pytest.approx(100 * t, rel=1e-3)
    # a 256-token chunk: 8.0 GB of weights stream (9.8 ms) over 0.69
    # TFLOP of matrices (3.5 ms); at context 4096 the attention's 112
    # GFLOP a layer come on top (6.9 ms in all) and its 4096 cached rows
    # a layer: byte-bound still, at a chunk of 256
    long = rl.prefill_seconds(
        DOC, 1, 256, 256, 6 * sum(3840 + t_ + 1 for t_ in range(256)),
        PEAKS)
    short = rl.prefill_seconds(DOC, 1, 256, 256, 6 * 256 * 257 // 2, PEAKS)
    assert 0.0098 < short < long < 0.0099
    assert long - short == pytest.approx(
        6 * 2 * (4096 - 256) * 576 / 819e9, rel=1e-2)
    assert rl.prefill_seconds(DOC, 0, 0, 0, 0, PEAKS) == 0.0
    assert rl.decode_seconds(DOC, 0, 0, 0, 0, 128, PEAKS) == 0.0


def _ctx(states0, states2, trace):
    return {"config": DOC, "device_kind": "TPU v5 lite", "traces": [trace],
            "rates": [{}], "snap0": {"states": [states0], "state": states0},
            "snap1": {"states": [states2], "state": states2},
            "snap2": {"states": [states2]}}


def _spec(name):
    with open(os.path.join(REPO, "cellbench", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    return importlib.import_module(
        "cellbench.readers." + spec["reader"]), spec["args"]


def test_readers_on_a_made_up_capture():
    keys = ["capture_decode_steps", "capture_tokens_generated",
            "capture_moe_held_hits_decode", "capture_decode_kv_pages_live",
            "capture_prefill_calls", "capture_prefill_tokens_padded",
            "capture_prefill_tokens_real", "capture_prefill_keys_attended",
            "moe_groups_kept_hits", "moe_group_slots",
            "moe_held_hits_decode", "decode_steps"]
    s0 = dict.fromkeys(keys, 0)
    # 100 steps of 10 rows at 3200; 80 chunks of 256 (4 tokens of
    # padding each) at a mean context of 3000
    attended = 6 * 80 * 252 * 3000
    s2 = {"capture_decode_steps": 100, "capture_tokens_generated": 1000,
          "capture_moe_held_hits_decode": 2000,
          "capture_decode_kv_pages_live": 25000,
          "capture_prefill_calls": 80,
          "capture_prefill_tokens_padded": 80 * 256,
          "capture_prefill_tokens_real": 80 * 252,
          "capture_prefill_keys_attended": attended,
          "moe_groups_kept_hits": 1250, "moe_group_slots": 10000,
          "moe_held_hits_decode": 2100, "decode_steps": 100}
    trace = {"devices": 1, "window_s": 4.0,
             "groups": {"decode": {"seconds": 1.0, "runs": 50},
                        "prefill": {"seconds": 2.5, "runs": 80}}}
    ctx = _ctx(s0, s2, trace)
    dec, _ = _spec("latent_decode_roofline")
    pre, _ = _spec("latent_prefill_roofline")
    assert dec.read(ctx, {}) == pytest.approx(100 * rl.decode_seconds(
        DOC, 100, 1000, 25000, 2000, 128, PEAKS) / 1.0)
    assert 45.0 < dec.read(ctx, {}) < 65.0
    assert pre.read(ctx, {}) == pytest.approx(100 * rl.prefill_seconds(
        DOC, 80, 80 * 256, 80 * 252, attended, PEAKS) / 2.5)
    assert 30.0 < pre.read(ctx, {}) < 60.0
    share, args = _spec("moe_group_hit_share")
    assert share.read(ctx, args) == pytest.approx(12.5)
    # finding 4: the hit count is averaged over the five EXPERT layers
    hit, args = _spec("moe_held_experts_hit_sparse")
    assert args["layers"] == "expert_layers"
    assert hit.read(ctx, args) == pytest.approx(2100 / 100 / 5)
    # a program from before the counters, and a CPU's trace: nothing
    bare = _ctx({}, {}, trace)
    assert dec.read(bare, {}) is None and pre.read(bare, {}) is None
    assert share.read(bare, _spec("moe_group_hit_share")[1]) is None
    cpu = _ctx(s0, s2, dict(trace, devices=0))
    assert dec.read(cpu, {}) is None and pre.read(cpu, {}) is None


def test_the_references_copy_is_the_original():
    with open(os.path.join(REPO, "aigw_tpu", "models", "reference",
                           "axk1_ref.py")) as a, \
            open(os.path.join(REPO, "cellbench", "reference",
                              "axk1_ref.py")) as b:
        assert a.read() == b.read()
    with open(os.path.join(REPO, "cellbench", "reference",
                           "axk1_ref.py")) as f:
        assert "aigw_tpu" not in f.read().split('"""', 2)[2]


def test_the_manifest_has_the_cell_and_its_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert m["configs"][-1]["name"] == "a.x-k1-1chip"
    assert m["configs"][-1]["file"] == \
        "cellbench/configs/latent/a.x-k1-1chip.json"
    assert m["configs"][-1]["source"] == DOC["cellbench"]["source"]
    assert m["configs"][-1]["reduced"] == DOC["cellbench"]["reduced"]
    assert m["workloads"][-1] == dict(
        m["workloads"][-1], name=CELL, config="a.x-k1-1chip",
        traffic="long-both", chips=1)
    has = {x["name"] for kind in ("end_to_end", "per_layer")
           for x in m[kind] if CELL in x.get("workloads", [])}
    assert has == {
        "tpot_mean_ms", "gap_p90_ms", "prefill_padded_frac",
        "decode_step_ms.open", "moe_local_share", "decode_kv_read_amp.open",
        "engine_host_ms_per_step.latent",
        "prefill_dev_ms_per_captured_ktok.latent", "moe_group_hit_share",
        "moe_held_experts_hit_sparse", "latent_prefill_roofline",
        "latent_decode_roofline"}
    new = [x for x in m["per_layer"] if x.get("workloads") == [CELL]]
    assert [x["name"] for x in new] == [
        "engine_host_ms_per_step.latent",
        "prefill_dev_ms_per_captured_ktok.latent", "moe_group_hit_share",
        "moe_held_experts_hit_sparse", "latent_prefill_roofline",
        "latent_decode_roofline"]
    assert all(x["moves"] == "tpot_mean_ms" for x in new)
    # a new cell is appended: the four the benchmark had come first
    assert [w["name"] for w in m["workloads"]][:4] == [
        "qwen2-7b.chat-steady", "mixtral-8x7b.prompt-heavy",
        "qwen2-7b.decode-closed", "qwen3-next-80b-a3b.long-prompt"]


def _cells_mix():
    with open(os.path.join(REPO, "cellbench", "traffic",
                           "long-both.json")) as f:
        return json.load(f)


def test_the_mix_is_the_issues():
    """ISSUE 45's traffic to the letter: the callers and both lengths."""
    mix = _cells_mix()
    assert {k: v for k, v in mix.items() if k not in ("about", "lead_in")
            } == {
        "name": "long-both", "loop": "closed", "clients": 20,
        "prompt_tokens": {"dist": "lognormal_truncated", "median": 2816,
                          "sigma": 0.3, "min": 1920, "max": 4864},
        "output_tokens": {"dist": "lognormal_truncated", "median": 224,
                          "sigma": 0.3, "min": 128, "max": 384},
        "sharing": {"kind": "none"}, "serve_flags": []}
    assert mix["lead_in"]["traffic_seconds"] == 8


def test_the_schedule_to_the_byte():
    """What the cell's runs of PERF.md (PR 45) were sent: the first 300
    requests of the closed loop at the driver's kind of seed."""
    s = traffic.Schedule(_cells_mix(), 2147483659, 50)
    h = hashlib.sha256()
    lens, outs = [], []
    for k in range(300):
        turns = s.nth(k).turns
        h.update(repr([(t.content, t.max_tokens) for t in turns]).encode())
        lens.append(len(turns[0].content))
        outs.append(turns[0].max_tokens)
    assert h.hexdigest()[:16] == "4bb054f1ebe46bf8"
    assert 1920 <= min(lens) and max(lens) <= 4864
    assert 128 <= min(outs) and max(outs) <= 384
    # the generator's quantiles: a mean prompt near 2992, answer near 230
    assert 2900 < sum(lens) / 300 < 3080 and 222 < sum(outs) / 300 < 238


def test_more_callers_than_slots_so_the_queue_is_never_empty():
    """An admission pass takes as many waiting requests as there are
    free slots, and the decode window stays at its small rung only while
    the queue behind the pass is not empty: with slots to spare the
    window's size was the order's, and the cell too noisy for its bound
    (PERF.md section 6, PR 44). Four over, and the `why` says what
    runs."""
    flags = DOC["cellbench"]["serve_flags"]
    slots = int(flags[flags.index("--max-batch-size") + 1])
    clients = _cells_mix()["clients"]
    assert (clients, slots) == (20, 16)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        why = json.load(f)["workloads"][-1]["why"]
    assert f"{clients} clients on {slots} slots" in why and len(why) <= 200
    assert "16x the experts' share" in why


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
    depth, slots and pool, at the wider page bucket (8192 positions),
    compile for a described (not attached) TPU v5e, hold their peak
    under 14.5 GB beside the weights, and copy neither the pool nor a
    context's per-head expanded keys."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from aigw_tpu.models import axk1 as dv
    from cellbench import serve_child

    cfg = dv.AXK1Config(**serve_child.model_kwargs(DOC))
    flags = DOC["cellbench"]["serve_flags"]
    B = int(flags[flags.index("--max-batch-size") + 1])
    page, P = 128, 64
    shapes = {
        "p": jax.eval_shape(
            lambda: dv.init_params(jax.random.PRNGKey(0), cfg)),
        "cache": jax.eval_shape(lambda: cfg.cache_spec().make(
            (B * P + 1) * page, B, "bfloat16"))}
    i32 = jnp.int32
    if program == "decode":
        fn = functools.partial(dv.decode_step, cfg=cfg, page_size=page,
                               moe_stats=True)
        shapes.update(
            tokens=jax.ShapeDtypeStruct((B,), i32),
            positions=jax.ShapeDtypeStruct((B,), i32),
            page_table=jax.ShapeDtypeStruct((B, P), i32),
            active=jax.ShapeDtypeStruct((B,), jnp.bool_))
    else:
        fn = functools.partial(dv.prefill_suffix, cfg=cfg, page_size=page,
                               moe_stats=True)
        shapes.update(
            tokens=jax.ShapeDtypeStruct((1, 256), i32),
            prefix_lens=jax.ShapeDtypeStruct((1,), i32),
            seq_lens=jax.ShapeDtypeStruct((1,), i32),
            page_table=jax.ShapeDtypeStruct((1, P), i32))
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e),
        shapes)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(fn, donate_argnames=("cache",)).lower(
            **placed).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert need < 14.5e9, f"{need / 1e9:.2f} GB does not fit"
    # the pool (0.91 GB) goes through donated and in place, and one
    # row's 8192-token context expanded into per-head keys and values
    # would be 8192 x 64 x 256 x 2 = 268 MB: a decode step's temporaries
    # stay under a quarter of that, a chunk's under the pool
    limit = 64e6 if program == "decode" else 0.5e9
    assert m.temp_size_in_bytes < limit, m.temp_size_in_bytes


# -- a tiny cell of the family through the whole harness, on the CPU -------
TINY = {
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
    "intermediate_size": 128,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"},
    "max_position_embeddings": 512,
}
MIX = {
    "name": "t-ctx", "loop": "closed", "clients": 3,
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
        "name": "t-ax", "source": "tests", "family": "axk1",
        "chat_template": "llama3", "reduced": [], "assumed": {},
        "fields": {("num_experts" if k == "n_routed_experts" else k): k
                   for k in TINY},
        "model_fields": {"router_experts": 16, "held_from": 4,
                         "first_dense_layers": 1},
        "serve_flags": ["--platform", "cpu", "--max-batch-size", "4",
                        "--max-seq-len", "512", "--page-size", "128",
                        "--prefill-bucket-rungs", "1",
                        "--prefill-chunk-tokens", "32"],
        "module_groups": "xla_default", "replicas": 1, "chips": 1,
        "expect": {"platform": "cpu", "param_bytes_total": 0},
    }
    m = rl.dims(doc)
    doc["cellbench"]["expect"]["param_bytes_total"] = 2.0 * rl.param_count(m)
    return doc


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    dst = sb.make_checkout(str(tmp_path_factory.mktemp("ax")))
    sb.add_file(dst, "cellbench/configs/t-dv.json", tiny_doc())
    sb.add_file(dst, "cellbench/traffic/t-ctx.json", MIX)
    cell = "t-ax.ctx"
    layer = [("moe_local_share.t", "%"), ("moe_group_hit_share.t", "%"),
             ("moe_held_experts_hit_sparse.t", "experts/step"),
             ("latent_decode_roofline.t", "%"),
             ("latent_prefill_roofline.t", "%")]
    sb.add_entries(
        dst,
        configs=[{"name": "t-ax", "source": "tests",
                  "file": "cellbench/configs/t-dv.json", "reduced": [],
                  "why": "test"}],
        workloads=[{"name": cell, "config": "t-ax", "traffic": "t-ctx",
                    "chips": 1, "why": "test"}],
        end_to_end=[{"name": "tpot_mean_ms.t", "unit": "ms",
                     "better": "lower", "bound": 0.1,
                     "source": "host_clock", "workloads": [cell]}],
        per_layer=[{"name": n, "unit": u, "better": "higher",
                    "source": "program_counter", "layer": "attention",
                    "moves": "tpot_mean_ms.t", "workloads": [cell]}
                   for n, u in layer])
    return dst


def test_tiny_cell_end_to_end(checkout):
    rc, last, lines, err = sb.run_cell(checkout, "t-ax.ctx", 2 ** 31 + 5,
                                       4, 0, timeout=600)
    assert rc == 0, err[-3000:]
    summary = json.loads(lines[-2])
    assert last["correct"] is True, summary
    assert set(last["metrics"]) == {"tpot_mean_ms.t", "setup_s"}
    assert last["metrics"]["tpot_mean_ms.t"]["value"] == \
        summary["tpot_ms"]["mean"] > 0.0
    assert last["attempted"] >= 6 and last["failed"] == 0
    assert summary["checks"]["ledger_reconciles"]
    assert summary["checks"]["no_compile_in_window"], summary


def test_tiny_cell_traced_reports_the_counter_metrics(checkout):
    rc, last, lines, err = sb.run_cell(checkout, "t-ax.ctx", 77, 5, 1,
                                       timeout=600)
    assert rc == 0, err[-3000:]
    got = {k: v["value"] for k, v in last["metrics"].items()}
    # 8 of a 16-wide router's experts are held: about half land here
    assert 30.0 < got["moe_local_share.t"] < 70.0
    # experts 4-11 of 16 in groups of 4 are groups 1 and 2 whole: of
    # the 2 groups of 4 a token keeps, about half hold a held expert
    assert 30.0 < got["moe_group_hit_share.t"] < 70.0
    # no ``expert_layers`` key in this file: nothing, and no exception
    assert "moe_held_experts_hit_sparse.t" not in got
    # a CPU's trace has no device plane: no roofline, and no exception
    assert "latent_decode_roofline.t" not in got
    assert "latent_prefill_roofline.t" not in got
    assert last["correct"] is True


def _reference_check(cfg, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench",
                                      "reference_check_latent.py"),
         "--config", str(cfg), "--prompts", "150,90", "--answers", "8",
         "--platform", "cpu", *more],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.stdout.strip(), out.stderr[-2000:]
    return out.returncode, json.loads(out.stdout.strip().splitlines()[-1])


def test_reference_check_runs_at_a_tiny_size(tmp_path):
    cfg = tmp_path / "t-ax.json"
    cfg.write_text(json.dumps(tiny_doc()))
    rc, got = _reference_check(cfg)
    # the served programs carry bfloat16 weights here too: 0 or 1 by
    # the log-probabilities, never 2 (a control that passes)
    assert rc in (0, 1) and got["control_ok"] is False
    assert got["ok"] is (rc == 0)
    s = got["served"]
    assert s["chunk_steps"] == 4 + 2 and len(s["prompts"]) == 2
    # every layer's prefill queries saw their whole causal context
    assert s["prefill_keys_attended"] == 4 * sum(
        n * (n + 1) // 2 for n in (150, 90))
    assert s["kv_bytes_per_token"] == 4 * 40 * 2
    for p in s["prompts"]:
        assert p["answers"] == 8
        assert p["logprob_max"] >= p["logprob_mean"] > 0
        assert p["ungrouped_logprob_mean"] > p["logprob_mean"]
    k = got["kernels"]
    assert (k["tokens"], k["blocks"], k["layer"]) == (158, [0], 1)
    # the same inputs on both sides: the router picks as the
    # reference's where a bfloat16 router moves picks, and the absorbed
    # attention sits on the expanded where a bfloat16 softmax does not
    lim = got["limits"]
    assert k["control"]["route_moved"] > 3 * lim["route_moved"]
    assert k["control"]["attn_rel"] > 2 * k["served"]["attn_rel"]
    assert k["served"]["route_moved"] == 0.0
    assert k["served"]["attn_rel"] < lim["attn_rel"]
    assert k["served"]["decode_attn_rel"] < lim["attn_rel"]


def test_reference_check_fails_the_lower_precision_control(tmp_path):
    cfg = tmp_path / "t-ax.json"
    cfg.write_text(json.dumps(tiny_doc()))
    rc, got = _reference_check(cfg, "--parts", "kernels", "--judge",
                               "control")
    assert rc == 1 and got["control_ok"] is False and got["ok"] is True
    assert "served" not in got
