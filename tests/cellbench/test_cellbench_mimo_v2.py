"""What the benchmark gained with the window-and-global family
(``mimo-v2.5-1chip``): the configuration file against the published
keys, the operations-and-bytes functions against hand counts, the new
readers on made-up captures (a value where the counters are, nothing —
never an exception — where the program has none), the mix to the byte,
the reference's copy against its original, the real programs compiled
for a described v5e, and one dry run of a tiny cell of the family
through the whole harness on the CPU."""

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

from cellbench import roofline_window as rw  # noqa: E402
from cellbench import traffic  # noqa: E402

# in a folder of its own, as configs/hybrid/ and configs/latent/ are:
# tests/cellbench/test_cellbench_aot.py compiles every file directly
# under configs/ through the llama skeleton's entry points (this
# family's own compile is below)
CONFIG = os.path.join(REPO, "cellbench", "configs", "window",
                      "mimo-v2.5-1chip.json")
with open(CONFIG) as _f:
    DOC = json.load(_f)
M = rw.dims(DOC)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "mimo-v2.5.short-long"

#: the source's config.json, every key of the catalog row
PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
PUBLISHED = {
    "attention_bias": False, "attention_chunk_size": 128,
    "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192,
    "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
    "hidden_size": 4096, "hybrid_block_size": None,
    "hybrid_layer_pattern": PATTERN, "intermediate_size": 16384,
    "layernorm_epsilon": 1e-05, "max_position_embeddings": 1048576,
    "model_type": "mimo_v2", "moe_intermediate_size": 2048,
    "moe_layer_freq": [0] + [1] * 47, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": None,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_size": 128, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152576,
}
#: a width may never be cut
WIDTHS = ("hidden_size", "num_attention_heads", "head_dim", "v_head_dim",
          "num_key_value_heads", "swa_num_key_value_heads",
          "sliding_window", "partial_rotary_factor", "rope_theta",
          "swa_rope_theta", "attention_value_scale",
          "moe_intermediate_size", "intermediate_size",
          "num_experts_per_tok", "swa_head_dim", "swa_v_head_dim",
          "swa_num_attention_heads", "attention_chunk_size")
#: per-layer lists, cut with the depth to their first entries
LISTS = ("hybrid_layer_pattern", "moe_layer_freq")


def test_the_catalog_row_is_what_is_pinned_here():
    """Where the guides' catalog is on the machine, the keys above are
    its row's, to the letter."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r.get("name") == "MiMo-V2.5")
    assert row["config"] == PUBLISHED
    assert row["source_url"] == DOC["cellbench"]["source"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_key(key):
    cb = DOC["cellbench"]
    if key in LISTS:
        assert key in cb["reduced"] and key in cb["assumed"]
        assert DOC[key] == PUBLISHED[key][:7] and "48" in cb["published"][key]
    elif key in cb["reduced"]:
        assert cb["published"][key] == PUBLISHED[key]
        assert DOC[key] < PUBLISHED[key] and key in cb["assumed"]
        assert key not in WIDTHS
    else:
        assert DOC[key] == PUBLISHED[key]


def test_the_published_widths():
    """Hidden 4096, 64 heads, keys 192 over values 128, 4 / 8 key
    heads, a window of 128, 64 rotated dims, thetas 1e7 / 1e4, value
    scale 0.707, experts of 2048, a dense layer of 16384, top-8 of 256,
    the seven-entry pattern."""
    assert [DOC[k] for k in WIDTHS] == [
        4096, 64, 192, 128, 4, 8, 128, 0.334, 10000000, 10000, 0.707, 2048,
        16384, 8, 192, 128, 64, 128]
    assert int(DOC["head_dim"] * DOC["partial_rotary_factor"]) == 64
    assert DOC["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert DOC["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert DOC["cellbench"]["model_fields"]["router_experts"] == 256


def test_the_cut_is_the_chips_share_of_the_stated_deployment():
    cb = DOC["cellbench"]
    assert cb["reduced"] == ["num_hidden_layers", "n_routed_experts",
                             "vocab_size", *LISTS]
    assert {k: cb["published"][k] for k in cb["reduced"][:3]} == {
        "num_hidden_layers": 48, "n_routed_experts": 256,
        "vocab_size": 152576}
    # the leading dense layer + a whole period of six: five window
    # layers to one global among the expert layers, the published 5:1;
    # a sixteenth of the experts, an eighth of the vocabulary
    assert DOC["num_hidden_layers"] == 1 + 6 and DOC["expert_layers"] == 6
    assert DOC["hybrid_layer_pattern"][1:].count(1) == 5
    assert DOC["hybrid_layer_pattern"][1:].count(0) == 1
    assert DOC["n_routed_experts"] * 16 == PUBLISHED["n_routed_experts"]
    assert DOC["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cb["model_fields"] == {"router_experts": 256, "held_from": 0,
                                  "first_dense_layers": 1}
    assert "EP16" in cb["deployment"] and "pipeline stages" \
        in cb["deployment"] and "no shared expert" in cb["deployment"]
    for said in ("window", "sink", "value_scale", "rotary", "router",
                 "weights", "kv", "towers", "mtp", "tensor_layout",
                 "tokenizer", "chat_template", "slots", "attention_share",
                 "idle_share"):
        assert said in cb["assumed"]
    a = cb["assumed"]
    assert "counts the query's own position" in a["window"] \
        and "attention_chunk_size" in a["window"]
    assert "WINDOW layers only" in a["sink"]
    assert "UNBIASED" in a["router"] and "null" in a["router"]
    assert "left out" in a["towers"] and "left out" in a["mtp"]
    assert "5120 B a token" in a["kv"] and "3,276,800 B a slot" in a["kv"]
    assert "sixteen times" in a["attention_share"]
    assert "--quantize" not in cb["serve_flags"]
    assert cb["serve_flags"] == [
        "--max-batch-size", "16", "--max-seq-len", "16384", "--page-size",
        "128", "--prefill-bucket-rungs", "1"]
    assert cb["family"] == "mimo_v2"
    assert cb["source"] == \
        "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"


def test_the_program_takes_the_file_and_counts_the_same_parameters():
    import jax

    from aigw_tpu.models import mimo_v2
    from cellbench import serve_child

    assert serve_child.config_class("mimo_v2") is mimo_v2.MiMoV2Config
    cfg = mimo_v2.MiMoV2Config(**serve_child.model_kwargs(DOC))
    assert (cfg.router_width, cfg.n_experts, cfg.n_layers) == (256, 16, 7)
    assert cfg.layer_kinds == ("global", "window", "window", "window",
                               "window", "global", "window")
    assert cfg.routed_scaling_factor is None and cfg.rotary_dim == 64
    shapes = jax.eval_shape(
        lambda: mimo_v2.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(a.size for a in leaves)
    assert n == rw.param_count(M) == 3429955392  # ISSUE 47's hand count
    nbytes = sum(a.size * a.dtype.itemsize for a in leaves)
    assert nbytes == rw.param_bytes(M) == 6859914496
    assert DOC["cellbench"]["expect"]["param_bytes_total"] == nbytes
    # pages: one 1280-wide bfloat16 row a token a GLOBAL layer; rings:
    # 128 rows of 2560 a window layer a slot
    spec = cfg.cache_spec()
    assert spec.kv_page_bytes(128, "bfloat16") == \
        128 * rw.cache_bytes_per_token(M) == 128 * 5120
    assert spec.kv_shape(2048) == (2, 1280, 2048)
    assert spec.state_bytes_per_slot("bfloat16") == \
        rw.ring_bytes_per_slot(M) == 3276800


def test_hand_counts():
    # the issue's count, by hand: the fused projection 4096 x (64 x 192
    # + Hkv x 320): global (Hkv 4) 4096 x 13568, window (Hkv 8) 4096 x
    # 14848; the output projection 8192 x 4096
    assert rw.qkv_params(M, "g") == 4096 * 13568 == 55574528
    assert rw.qkv_params(M, "w") == 4096 * 14848 == 60817408
    assert rw.out_params(M) == 8192 * 4096 == 33554432
    assert rw.dense_params(M) == 3 * 4096 * 16384 == 201326592
    assert rw.expert_params(M) == 3 * 4096 * 2048 == 25165824
    # layer 0 (global + dense) 290.46 M; an expert layer 498.1 M
    # (window) / 492.8 M (global); embedding and head 156.24 M
    norms = 2 * 4096
    layer0 = rw.qkv_params(M, "g") + rw.out_params(M) \
        + rw.dense_params(M) + norms
    moe = 4096 * 256 + 256 + 16 * rw.expert_params(M) + norms
    window = rw.qkv_params(M, "w") + rw.out_params(M) + 64 + moe
    glob = rw.qkv_params(M, "g") + rw.out_params(M) + moe
    assert round(layer0 / 1e6, 2) == 290.46
    assert round(window / 1e6, 1) == 498.1 and round(glob / 1e6, 1) == 492.8
    assert rw.param_count(M) == layer0 + 5 * window + glob \
        + 2 * 19072 * 4096 + 4096 == 3429955392
    assert round(rw.param_bytes(M) / 1e9, 2) == 6.86
    assert (M["n_dense"], M["n_moe"], M["R"], M["E"]) == (1, 6, 256, 16)
    assert (M["Lg"], M["Lw"], M["Hg"], M["Hw"], M["W"]) == (2, 5, 4, 8, 128)
    assert (rw.row_width(M, "g"), rw.row_width(M, "w")) == (1280, 2560)
    assert rw.cache_bytes_per_token(M) == 5120
    assert rw.ring_bytes_per_slot(M) == 5 * 128 * 8 * 320 * 2 == 3276800
    # 16 slots x 16384 positions: 1.34 GB of pages + 0.05 GB of rings;
    # the same contexts with the window layers paged: 8.05 GB
    assert round(16 * 16384 * 5120 / 1e9, 2) == 1.34
    assert round(16 * 3276800 / 1e9, 2) == 0.05
    assert round(16 * 16384 * 6 * 5120 / 1e9, 2) == 8.05


def test_kernel_counts():
    # a 256-token chunk places 256 x 8 / 16 = 128 assignments here
    flops, nbytes = rw.moe_experts(M, 256)
    assert flops == 2 * 128 * 25165824
    assert 15.99 < rw.experts_touched(M, 128) < 16.0
    assert nbytes == pytest.approx(2 * rw.experts_touched(M, 128) * 25165824)
    # seven live rows place 3.5: about 3.2 of the 16 held experts
    assert 3.1 < rw.experts_touched(M, 3.5) < 3.3
    assert rw.moe_route(M, 1) == (2 * 4096 * 256, 2 * 4096 * 256)
    # a pair costs a 192-wide and a 128-wide product a query head; a
    # global row is 1280 values, a ring row 2560
    assert rw.attend(M, 4000, 4000, "g") == (
        2 * 4000 * 64 * 320, 2 * 4000 * 1280)
    assert rw.attend(M, 128, 128, "w") == (2 * 128 * 64 * 320,
                                           2 * 128 * 2560)
    assert rw.qkv(M, 1, "w") == (2 * 60817408, 2 * (60817408 + 2560))
    assert rw.attn_out(M, 256)[0] == 2 * 256 * 33554432


def test_decode_and_prefill_bounds():
    weights = rw.param_bytes(M)
    # a step of 7 rows at 4000 (32 pages each): everything but the
    # embedding's rows, the sinks and biases and the experts nobody hit
    # streams once, the rows' 224 pages a global layer, their rings a
    # window layer: bandwidth-bound, ~3.2 ms
    hits = 6 * 2
    seen = 5 * 7 * 128
    t = rw.decode_seconds(DOC, 1, 7, 224, hits, seen, 128, PEAKS)
    own = 2 * (rw.param_count(M) - M["V"] * M["D"]
               - 6 * 16 * rw.expert_params(M) - 15 * 4096 - 5 * 64
               - 6 * 256)
    pages = 2 * 2 * 224 * 128 * 1280
    rings = 2 * seen * 2560
    fresh = 2 * 7 * (2 * 1280 + 5 * 2560)  # the step's own new rows
    assert t == pytest.approx(
        (own + 2 * hits * rw.expert_params(M) + pages + rings + fresh)
        / 819e9, rel=1e-4)
    assert 0.0030 < t < 0.0034
    assert weights > own
    # its FLOP bound counts a row's last page as one token
    tokens = (224 - 7) * 128 + 7
    least = 2 * 2 * tokens * 64 * 320 + 2 * seen * 64 * 320
    assert least / 197e12 < t / 20
    # more hits, more bytes; a hundred steps, a hundred times the time
    assert rw.decode_seconds(DOC, 1, 7, 224, 24, seen, 128, PEAKS) > t
    assert rw.decode_seconds(DOC, 100, 700, 22400, 100 * hits, 100 * seen,
                             128, PEAKS) == pytest.approx(100 * t, rel=1e-3)
    # a window layer that read its whole context would need more
    assert rw.decode_seconds(DOC, 1, 7, 224, hits, 5 * 7 * 4000, 128,
                             PEAKS) > 1.04 * t
    # a 256-token chunk: 6.7 GB of weights stream (8.2 ms) over 0.48
    # TFLOP of matrices (2.4 ms); at context 12288 the global layers'
    # attention (0.13 TFLOP) and their 12288 cached rows a layer come on
    # top: byte-bound still
    def pairs(ctx):
        return 2 * sum(ctx - 256 + t_ + 1 for t_ in range(256))

    long = rw.prefill_seconds(DOC, 1, 256, 256, pairs(12288), PEAKS)
    short = rw.prefill_seconds(DOC, 1, 256, 256, pairs(256), PEAKS)
    assert 0.0081 < short < long < 0.0084
    assert long - short == pytest.approx(
        2 * 2 * (12288 - 256) * 1280 / 819e9, rel=2e-2)
    assert rw.prefill_seconds(DOC, 0, 0, 0, 0, PEAKS) == 0.0
    assert rw.decode_seconds(DOC, 0, 0, 0, 0, 0, 128, PEAKS) == 0.0


def _ctx(states0, states2, trace, doc=DOC):
    return {"config": doc, "device_kind": "TPU v5 lite", "traces": [trace],
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
    keys = ["capture_decode_steps", "capture_decode_state_rows_live",
            "capture_moe_held_hits_decode", "capture_decode_kv_pages_live",
            "capture_swa_keys_attended",
            "capture_prefill_calls", "capture_prefill_tokens_padded",
            "capture_prefill_tokens_real", "capture_prefill_keys_attended",
            "swa_keys_attended", "swa_keys_in_context",
            "moe_unserved_tokens", "moe_total_assignments",
            "moe_held_hits_decode", "decode_steps"]
    s0 = dict.fromkeys(keys, 0)
    # 100 steps of 7 rows at 4000; 80 chunks of 256 (4 tokens of
    # padding each) at a mean context of 5000
    attended = 2 * 80 * 252 * 5000
    s2 = {"capture_decode_steps": 100,
          "capture_decode_state_rows_live": 700,
          "capture_moe_held_hits_decode": 1200,
          "capture_decode_kv_pages_live": 22400,
          "capture_swa_keys_attended": 5 * 700 * 128,
          "capture_prefill_calls": 80,
          "capture_prefill_tokens_padded": 80 * 256,
          "capture_prefill_tokens_real": 80 * 252,
          "capture_prefill_keys_attended": attended,
          "swa_keys_attended": 5 * 700 * 128,
          "swa_keys_in_context": 5 * 700 * 4000,
          "moe_unserved_tokens": 5900, "moe_total_assignments": 80000,
          "moe_held_hits_decode": 1260, "decode_steps": 100}
    trace = {"devices": 1, "window_s": 4.0,
             "groups": {"decode": {"seconds": 0.55, "runs": 50},
                        "prefill": {"seconds": 1.6, "runs": 80}}}
    ctx = _ctx(s0, s2, trace)
    dec, _ = _spec("window_decode_roofline")
    pre, _ = _spec("window_prefill_roofline")
    assert dec.read(ctx, {}) == pytest.approx(100 * rw.decode_seconds(
        DOC, 100, 700, 22400, 1200, 5 * 700 * 128, 128, PEAKS) / 0.55)
    assert 45.0 < dec.read(ctx, {}) < 65.0
    assert pre.read(ctx, {}) == pytest.approx(100 * rw.prefill_seconds(
        DOC, 80, 80 * 256, 80 * 252, attended, PEAKS) / 1.6)
    assert 30.0 < pre.read(ctx, {}) < 60.0
    share, args = _spec("window_attended_share")
    assert share.read(ctx, args) == pytest.approx(100 * 128 / 4000)
    lost, args = _spec("moe_unserved_share")
    assert args["scale"] == 100.0 * DOC["num_experts_per_tok"]
    assert lost.read(ctx, args) == pytest.approx(59.0)
    # the hit count is averaged over the six EXPERT layers
    hit, args = _spec("moe_held_experts_hit_sparse")
    assert hit.read(ctx, args) == pytest.approx(1260 / 100 / 6)
    # a program from before the counters, and a CPU's trace: nothing
    bare = _ctx({}, {}, trace)
    assert dec.read(bare, {}) is None and pre.read(bare, {}) is None
    assert share.read(bare, _spec("window_attended_share")[1]) is None
    assert lost.read(bare, _spec("moe_unserved_share")[1]) is None
    cpu = _ctx(s0, s2, dict(trace, devices=0))
    assert dec.read(cpu, {}) is None and pre.read(cpu, {}) is None
    # another family's configuration under the same counters: nothing
    other = {k: v for k, v in DOC.items() if k != "hybrid_layer_pattern"}
    assert pre.read(_ctx(s0, s2, trace, other), {}) is None


def test_the_references_copy_is_the_original():
    with open(os.path.join(REPO, "aigw_tpu", "models", "reference",
                           "mimo_v2_ref.py")) as a, \
            open(os.path.join(REPO, "cellbench", "reference",
                              "mimo_v2_ref.py")) as b:
        assert a.read() == b.read()
    with open(os.path.join(REPO, "cellbench", "reference",
                           "mimo_v2_ref.py")) as f:
        assert "aigw_tpu" not in f.read().split('"""', 2)[2]


def test_the_manifest_has_the_cell_and_its_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = next(c for c in m["configs"] if c["name"] == "mimo-v2.5-1chip")
    assert entry["file"] == "cellbench/configs/window/mimo-v2.5-1chip.json"
    assert entry["source"] == DOC["cellbench"]["source"]
    assert entry["reduced"] == DOC["cellbench"]["reduced"]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="mimo-v2.5-1chip",
                        traffic="short-long", chips=1)
    has = {x["name"] for kind in ("end_to_end", "per_layer")
           for x in m[kind] if CELL in x.get("workloads", [])}
    assert has == {
        "tpot_mean_ms", "gap_p90_ms", "prefill_padded_frac",
        "decode_step_ms.open", "moe_local_share", "state_gb",
        "decode_kv_read_amp.open", "decode_state_read_amp.open",
        "engine_host_ms_per_step.window",
        "prefill_dev_ms_per_captured_ktok.window",
        "moe_held_experts_hit_sparse.window", "window_decode_roofline",
        "window_prefill_roofline", "window_attended_share",
        "moe_unserved_share"}
    new = [x for x in m["per_layer"] if x.get("workloads") == [CELL]]
    assert [x["name"] for x in new] == [
        "engine_host_ms_per_step.window",
        "prefill_dev_ms_per_captured_ktok.window",
        "moe_held_experts_hit_sparse.window", "window_decode_roofline",
        "window_prefill_roofline", "window_attended_share",
        "moe_unserved_share"]
    assert all(x["moves"] == "tpot_mean_ms" for x in new)
    assert {x["name"]: x["better"] for x in new}[
        "window_attended_share"] == "lower"
    # new entries are appended: the five the benchmark had come first
    assert [w["name"] for w in m["workloads"]][:5] == [
        "qwen2-7b.chat-steady", "mixtral-8x7b.prompt-heavy",
        "qwen2-7b.decode-closed", "qwen3-next-80b-a3b.long-prompt",
        "a.x-k1.long-both"]
    assert [c["name"] for c in m["configs"]][:4] == [
        "qwen2-7b-1chip", "mixtral-8x7b-1chip",
        "qwen3-next-80b-a3b-1chip", "a.x-k1-1chip"]


def _cells_mix():
    with open(os.path.join(REPO, "cellbench", "traffic",
                           "short-long.json")) as f:
        return json.load(f)


def test_the_mix_is_the_issues():
    """ISSUE 47's traffic to the letter: the callers and both lengths."""
    mix = _cells_mix()
    assert {k: v for k, v in mix.items() if k not in ("about", "lead_in")
            } == {
        "name": "short-long", "loop": "closed", "clients": 20,
        "prompt_tokens": {"dist": "lognormal_truncated", "median": 3072,
                          "sigma": 0.6, "min": 1024, "max": 12288},
        "output_tokens": {"dist": "lognormal_truncated", "median": 192,
                          "sigma": 0.3, "min": 96, "max": 320},
        "sharing": {"kind": "none"}, "serve_flags": []}
    assert mix["lead_in"]["traffic_seconds"] == 8
    # the tour: a lone request and a joiner at each of the four page
    # buckets, then the tail rung the two left out
    assert len(mix["lead_in"]["tour"]) == 8


def test_the_schedule_to_the_byte():
    """What the cell's runs of PERF.md (PR 47) were sent: the first 300
    requests of the closed loop at the driver's kind of seed."""
    s = traffic.Schedule(_cells_mix(), 2147483659, 50)
    h = hashlib.sha256()
    lens, outs = [], []
    for k in range(300):
        turns = s.nth(k).turns
        h.update(repr([(t.content, t.max_tokens) for t in turns]).encode())
        lens.append(len(turns[0].content))
        outs.append(turns[0].max_tokens)
    assert h.hexdigest()[:16] == SCHEDULE_HASH
    assert 1024 <= min(lens) and max(lens) <= 12288
    assert 96 <= min(outs) and max(outs) <= 320
    # a twelvefold range in one queue: under 1300 and over 9000 both
    assert min(lens) < 1300 and max(lens) > 9000
    # the generator's quantiles: a mean prompt near 3650, answer near 196
    assert 3450 < sum(lens) / 300 < 3850 and 188 < sum(outs) / 300 < 204


SCHEDULE_HASH = "78666942aa659d88"


def test_more_callers_than_slots_so_the_queue_is_never_empty():
    """The decode window stays at its small rung only while the
    admission queue is not empty: four more callers than slots."""
    flags = DOC["cellbench"]["serve_flags"]
    slots = int(flags[flags.index("--max-batch-size") + 1])
    clients = _cells_mix()["clients"]
    assert (clients, slots) == (20, 16)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        why = next(w for w in json.load(f)["workloads"]
                   if w["name"] == CELL)["why"]
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
    depth, slots and pools, at the widest page bucket (16384 positions),
    compile for a described (not attached) TPU v5e, hold their peak
    under 14.5 GB beside the weights, and copy neither the page pool
    (1.34 GB) nor the ring pool (52 MB), nor build anything ``[slots,
    context]``-sized in a window layer."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from aigw_tpu.models import mimo_v2 as dv
    from cellbench import serve_child

    cfg = dv.MiMoV2Config(**serve_child.model_kwargs(DOC))
    flags = DOC["cellbench"]["serve_flags"]
    B = int(flags[flags.index("--max-batch-size") + 1])
    page, P = 128, 128
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
            page_table=jax.ShapeDtypeStruct((1, P), i32),
            slot_ids=jax.ShapeDtypeStruct((1,), i32))
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
    # both pools go through donated and in place (1.39 GB aliased)
    assert m.alias_size_in_bytes > 1.39e9
    # a copy of the ring pool would be 52 MB, of the page pool 1.34 GB;
    # a [16 slots, 16384 positions] buffer of 64 heads' float32 logits
    # 67 MB: a decode step's temporaries stay under a quarter of the
    # smallest of them (9 MB as compiled for this PR), a chunk's
    # (its [256, 64, 512] float32 logits a block and the held experts'
    # activations, 41 MB) under a tenth of the page pool
    limit = 13e6 if program == "decode" else 0.13e9
    assert m.temp_size_in_bytes < limit, m.temp_size_in_bytes


# -- a tiny cell of the family through the whole harness, on the CPU -------
TINY = {
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 7,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "head_dim": 24, "v_head_dim": 16, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "sliding_window": 8, "attention_value_scale": 0.707,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
    "n_routed_experts": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "routed_scaling_factor": None, "layernorm_epsilon": 1e-05,
    "max_position_embeddings": 512,
}
MIX = {
    "name": "t-win", "loop": "closed", "clients": 3,
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
        "name": "t-mm", "source": "tests", "family": "mimo_v2",
        "chat_template": "llama3", "reduced": [], "assumed": {},
        "fields": {("num_experts" if k == "n_routed_experts" else k): k
                   for k in TINY if k != "moe_layer_freq"},
        "model_fields": {"router_experts": 32, "held_from": 4,
                         "first_dense_layers": 1},
        "serve_flags": ["--platform", "cpu", "--max-batch-size", "4",
                        "--max-seq-len", "512", "--page-size", "128",
                        "--prefill-bucket-rungs", "1",
                        "--prefill-chunk-tokens", "32"],
        "module_groups": "xla_default", "replicas": 1, "chips": 1,
        "expect": {"platform": "cpu", "param_bytes_total": 0},
    }
    doc["cellbench"]["expect"]["param_bytes_total"] = rw.param_bytes(
        rw.dims(doc))
    return doc


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    dst = sb.make_checkout(str(tmp_path_factory.mktemp("mm")))
    sb.add_file(dst, "cellbench/configs/t-dv.json", tiny_doc())
    sb.add_file(dst, "cellbench/traffic/t-win.json", MIX)
    cell = "t-mm.win"
    layer = [("moe_local_share.t", "%"), ("moe_unserved_share.t", "%"),
             ("window_attended_share.t", "%"),
             ("decode_state_read_amp.t", "x"), ("state_gb.t", "GB"),
             ("moe_held_experts_hit_sparse.t", "experts/step"),
             ("window_decode_roofline.t", "%"),
             ("window_prefill_roofline.t", "%")]
    sb.add_entries(
        dst,
        configs=[{"name": "t-mm", "source": "tests",
                  "file": "cellbench/configs/t-dv.json", "reduced": [],
                  "why": "test"}],
        workloads=[{"name": cell, "config": "t-mm", "traffic": "t-win",
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
    rc, last, lines, err = sb.run_cell(checkout, "t-mm.win", 2 ** 31 + 5,
                                       4, 0, timeout=600)
    assert rc == 0, err[-3000:]
    summary = json.loads(lines[-2])
    assert last["correct"] is True, summary
    assert set(last["metrics"]) == {"tpot_mean_ms.t", "setup_s"}
    assert last["metrics"]["tpot_mean_ms.t"]["value"] == \
        summary["tpot_ms"]["mean"] > 0.0
    assert last["attempted"] >= 6 and last["failed"] == 0
    assert summary["checks"]["ledger_reconciles"]
    assert summary["checks"]["param_bytes"]
    assert summary["checks"]["no_compile_in_window"], summary


def test_tiny_cell_traced_reports_the_counter_metrics(checkout):
    rc, last, lines, err = sb.run_cell(checkout, "t-mm.win", 77, 5, 1,
                                       timeout=600)
    assert rc == 0, err[-3000:]
    got = {k: v["value"] for k, v in last["metrics"].items()}
    # 8 of a 32-wide router's experts are held: about a quarter land
    # here, and about a third of the tokens place none of their 4
    assert 12.0 < got["moe_local_share.t"] < 40.0
    assert 15.0 < got["moe_unserved_share.t"] < 50.0
    # contexts of ~170-190 tokens behind a window of 8
    assert 3.5 < got["window_attended_share.t"] < 6.0
    # only the live rows' rings are read
    assert got["decode_state_read_amp.t"] == 1.0
    # 4 slots x 5 window layers x 8 tokens x 80 values x 2 bytes
    assert got["state_gb.t"] == pytest.approx(4 * 5 * 8 * 80 * 2 / 1e9)
    # no ``expert_layers`` key in this file: nothing, and no exception
    assert "moe_held_experts_hit_sparse.t" not in got
    # a CPU's trace has no device plane: no roofline, and no exception
    assert "window_decode_roofline.t" not in got
    assert "window_prefill_roofline.t" not in got
    assert last["correct"] is True


def _reference_check(cfg, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench",
                                      "reference_check_window.py"),
         "--config", str(cfg), "--prompts", "150,90", "--answers", "20",
         "--platform", "cpu", *more],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.stdout.strip(), out.stderr[-2000:]
    return out.returncode, json.loads(out.stdout.strip().splitlines()[-1])


def test_reference_check_runs_at_a_tiny_size(tmp_path):
    cfg = tmp_path / "t-mm.json"
    cfg.write_text(json.dumps(tiny_doc()))
    rc, got = _reference_check(cfg)
    # the served programs carry bfloat16 weights here too: 0, 1 or 2 by
    # the log-probabilities; the lower-precision control never passes
    assert rc in (0, 1, 2) and got["control_ok"] is False
    assert got["ok"] is (rc != 1)
    s = got["served"]
    assert s["chunk_steps"] == 4 + 2 and len(s["prompts"]) == 2
    assert s["kv_bytes_per_token"] == 2 * 40 * 2
    assert s["state_bytes_per_slot"] == 5 * 8 * 80 * 2
    # only live rows' rings, and 8 keys a row a window layer at the most
    assert s["decode_state_rows_read"] == s["decode_state_rows_live"] > 0
    assert s["swa_keys_attended"] <= 5 * 8 * s["decode_state_rows_live"]
    assert s["swa_keys_in_context"] > 10 * s["swa_keys_attended"]
    for p in s["prompts"]:
        assert p["answers"] == 20  # past two turns of the ring of 8
        assert p["logprob_max"] >= p["logprob_mean"] > 0
        # a window layer read as a global one, and a dropped sink, are
        # other models
        assert p["all_global_logprob_mean"] > 1.5 * p["logprob_mean"]
        assert p["no_sink_logprob_mean"] > 1.5 * p["logprob_mean"]
    k = got["kernels"]
    assert (k["tokens"], k["blocks"]) == (150, [0])
    assert k["layers"] == {"window": 1, "global": 5, "router": 1}
    # the same inputs on both sides: the router picks as the
    # reference's where a bfloat16 router moves picks, and both kinds
    # of attention sit on the reference's where a bfloat16 softmax
    # does not
    lim = got["limits"]
    assert k["control"]["route_moved"] > 3 * lim["route_moved"]
    assert k["control"]["attn_rel"] > 2 * k["served"]["attn_rel"]
    assert k["served"]["route_moved"] == 0.0
    assert k["served"]["attn_rel"] < lim["attn_rel"]
    assert k["served"]["decode_attn_rel"] < lim["attn_rel"]


def test_reference_check_fails_the_lower_precision_control(tmp_path):
    cfg = tmp_path / "t-mm.json"
    cfg.write_text(json.dumps(tiny_doc()))
    rc, got = _reference_check(cfg, "--parts", "kernels", "--judge",
                               "control")
    assert rc == 1 and got["control_ok"] is False and got["ok"] is True
    assert "served" not in got
