"""What the benchmark gained with the Olmo-Hybrid family
(``olmo-hybrid-7b-1chip`` and its cell ``olmo-hybrid-7b.sessions``): the
configuration file against the published keys, the parameter count by
hand, the operations-and-bytes functions against hand counts, the two
new readers on made-up scrapes (a value where the counters are, nothing
— never an exception — where the program has none), the manifest's
entries, the ``sessions`` mix's schedule pinned for one seed, the
reference's copy against its original, the real programs against a
described v5e, and one dry run of a tiny cell of the family — sessions
that hit snapshots and all — through the whole harness on the CPU."""

import importlib
import json
import os
import subprocess
import sys

import pytest

import cellbench_sandbox as sb

REPO = sb.REPO
sys.path.insert(0, REPO)

from cellbench import roofline_olmo_hybrid as ro  # noqa: E402
from cellbench.traffic import Schedule  # noqa: E402

# in a folder of its own: tests/cellbench/test_cellbench_aot.py compiles
# every file directly under configs/ through the llama skeleton's entry
# points, which this family does not have (its own compile is below)
CONFIG = os.path.join(REPO, "cellbench", "configs", "hybrid-dense",
                      "olmo-hybrid-7b-1chip.json")
CELL = "olmo-hybrid-7b.sessions"
with open(CONFIG) as _f:
    DOC = json.load(_f)
with open(os.path.join(REPO, "cellbench", "traffic", "sessions.json")) as _f:
    SESSIONS = json.load(_f)
M = ro.dims(DOC)
PERIOD = ["linear_attention"] * 3 + ["full_attention"]

#: the source's config.json, every key of the catalog's row
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_key(key):
    cb = DOC["cellbench"]
    if key in cb["reduced"]:
        assert cb["published"][key] == PUBLISHED[key]
        assert DOC[key] != PUBLISHED[key] and key in cb["assumed"]
    else:
        assert DOC[key] == PUBLISHED[key]


def test_the_cut_is_depth_alone_in_whole_periods():
    cb = DOC["cellbench"]
    assert cb["reduced"] == ["num_hidden_layers", "layer_types"]
    assert DOC["num_hidden_layers"] == 12
    assert DOC["layer_types"] == PERIOD * 3 == PUBLISHED["layer_types"][:12]
    assert "model_fields" not in cb  # nothing the published keys lack
    assert "pipeline" in cb["deployment"] and "whole periods" \
        in cb["deployment"]
    assert cb["source"] == ("https://huggingface.co/allenai/Olmo-Hybrid-7B"
                            "/blob/main/config.json")
    # the three readings the published config has no key for, and the rest
    for said in ("block", "qk_norm", "positional", "conv_bias", "weights",
                 "kv", "state", "snapshots", "tokenizer", "tensor_layout",
                 "idle_share"):
        assert said in cb["assumed"]
    for reading in ("block", "qk_norm", "positional"):
        assert "READING" in cb["assumed"][reading]
    assert "--quantize" not in cb["serve_flags"]
    assert cb["serve_flags"] == [
        "--max-batch-size", "16", "--max-seq-len", "8192", "--page-size",
        "128", "--prefill-bucket-rungs", "1"]


def test_the_program_takes_the_file_and_counts_the_same_parameters():
    import jax

    from aigw_tpu.models import olmo_hybrid as oh
    from cellbench import serve_child

    assert serve_child.config_class("olmo_hybrid") is oh.OlmoHybridConfig
    cfg = oh.OlmoHybridConfig(**serve_child.model_kwargs(DOC))
    assert (cfg.n_layers, cfg.n_linear_layers, cfg.n_full_layers) \
        == (12, 9, 3)
    shapes = jax.eval_shape(
        lambda: oh.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert n == ro.param_count(M) == 3_268_268_508
    assert DOC["cellbench"]["expect"]["param_bytes_total"] == 2 * n
    spec = cfg.cache_spec()
    assert spec.state_bytes_per_slot("bfloat16") == ro.state_bytes_per_slot(M)
    # a page row STORES 32 key heads (30 and the tile's padding made
    # explicit); the algorithm's bytes count the 30
    assert spec.kv_page_bytes(128, "bfloat16") * 30 \
        == 128 * ro.kv_bytes_per_token(M) * 32
    assert spec.snapshot_rows(16) == 48
    assert 48 * ro.state_bytes_per_slot(M) == 985_374_720  # 0.985 GB


def test_parameter_count_by_hand():
    D, F, V = 3840, 11008, 100352
    swiglu = 3 * D * F
    assert swiglu == 126_812_160
    # q and k 3840 x 2880, v, gate and out 3840 x 5760, b | a 3840 x 60,
    # the convolution 4 x 11520, A_log and dt_bias 30 each, the head
    # norm 192; the block's two norms
    mixer = (2 * D * 2880 + 3 * D * 5760 + D * 60 + 4 * 11520 + 60 + 192)
    assert mixer == 88_750_332
    assert ro.linear_layer_params(M) == mixer + 2 * D + swiglu \
        == 215_570_172
    # q, k, v, o 3840 x 3840, the two whole-projection norms, the
    # block's two norms
    assert ro.full_layer_params(M) == 4 * D * D + 2 * D + 2 * D + swiglu \
        == 185_809_920
    period = 3 * ro.linear_layer_params(M) + ro.full_layer_params(M)
    assert period == 832_520_436
    assert ro.param_count(M) == 3 * period + 2 * V * D + D \
        == 3_268_268_508
    # all 32 layers: 14.86 GB in bfloat16, no room for a cache on 16
    whole = ro.param_count(ro.dims(dict(
        DOC, num_hidden_layers=32, layer_types=PERIOD * 8)))
    assert 14.85e9 < 2 * whole < 14.87e9


def test_hand_counts():
    assert (M["n_lin"], M["n_full"], M["H"], M["dk"], M["dv"], M["hd"]) \
        == (9, 3, 30, 96, 192, 128)
    assert (M["kd"], M["vd"], M["conv"]) == (2880, 5760, 11520)
    assert ro.gdn_proj_params(M) == 3840 * (2 * 2880 + 2 * 5760) \
        + 3840 * 60 + 5760 * 3840
    assert ro.attn_params(M) == 4 * 3840 * 3840
    assert ro.mlp_params(M) == 126_812_160
    # 9 layers x (2,211,840 B of float32 state + 69,120 B of bf16 tail)
    assert ro.state_bytes_per_slot(M) == 9 * (2_211_840 + 69_120) \
        == 20_528_640
    # 3 layers x K and V x 30 heads x 128 x 2 B
    assert ro.kv_bytes_per_token(M) == 46_080


def test_kernel_counts():
    # one token, one live slot: decay, S'k, the write, S'q over 30x96x192
    flops, nbytes = ro.gdn_recurrent(M, 1)
    assert flops == 7 * 30 * 96 * 192 and nbytes == 2 * 2_211_840
    flops, nbytes = ro.gdn_proj(M, 256)
    assert flops == 2 * 256 * ro.gdn_proj_params(M)
    assert nbytes == 2 * ro.gdn_proj_params(M)
    flops, nbytes = ro.gdn_conv(M, 256)
    assert flops == 2 * 256 * 4 * 11520
    assert nbytes == 2 * (4 * 11520 + 2 * 3 * 11520 + 2 * 256 * 11520)
    flops, nbytes = ro.gdn_chunk(M, 256)
    # kk' and qk' (keys 96 wide), the inverse, T.v and the local product
    # (values 192 wide), T.k, then w.S, q.S and the state's update
    per_block = (4 * 64 * 64 * 96 + 64 ** 3 / 3 + 2 * 2 * 64 * 64 * 192
                 + 2 * 64 * 64 * 96 + 3 * 2 * 64 * 96 * 192)
    assert flops == pytest.approx(4 * 30 * per_block)
    assert nbytes == 2 * 2_211_840 + 2 * 256 * (11520 + 5760)
    # 256 tokens over 2048 keys a token: projections and the two score
    # products, 30 heads of 128; the keys' and values' bytes once
    flops, nbytes = ro.attn_full(M, 256, 2048)
    assert flops == 2 * 256 * 4 * 3840 * 3840 + 4 * 256 * 2048 * 3840
    assert nbytes == 2 * 4 * 3840 * 3840 + 2 * 2048 * 3840 * 2
    assert ro.mlp(M, 7) == (2 * 7 * 126_812_160, 2 * 126_812_160)
    # a snapshot copy: the slot's state once read and once written
    assert ro.snapshot_copy(M, 3) == (0.0, 2 * 3 * 20_528_640)


def test_decode_step_and_prefill_call_bounds():
    weights = 2 * ro.param_count(M)
    kv = 10 * 3000 * 46_080
    full = ro.decode_step_bytes(DOC, 10, kv)
    # everything but the embedding's rows and the norms, plus state in
    # and out and KV
    rest = full - 2 * 10 * ro.state_bytes_per_slot(M) - kv
    assert weights - 2 * M["V"] * M["D"] * 1.001 < rest < weights
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # a decode step is 7 ms of weights at the least (5.77 GB: all but
    # the embedding's 0.77)
    assert 0.0070 < rest / 819e9 < 0.0071
    t256 = ro.prefill_call_seconds(DOC, 256, peaks)
    t64 = ro.prefill_call_seconds(DOC, 64, peaks)
    # byte-bound at 64 tokens (the weights stream once) and — just —
    # at 256 too: 7.3 ms of bytes over 6.6 ms of FLOPs (256 x 2 x 2.5e9
    # matrix parameters = 1.3 TFLOP); a chunk sits at the ridge
    for tokens, t in ((64, t64), (256, t256)):
        flops, nbytes = ro.prefill_call(DOC, tokens, tokens / 2)
        assert t == pytest.approx(nbytes / 819e9)
        assert nbytes / 819e9 > flops / 197e12
    assert ro.prefill_call(DOC, 256, 128)[0] / 197e12 > 0.0065
    assert 0.0071 < t64 < t256 < 0.0074
    assert ro.snapshot_copy_seconds(DOC, 2, peaks) == pytest.approx(
        4 * 20_528_640 / 819e9)


def _ctx(states0, states2, trace, rates):
    return {"config": DOC, "device_kind": "TPU v5 lite", "traces": [trace],
            "rates": [rates], "snap0": {"states": [states0]},
            "snap1": None, "snap2": {"states": [states2]}}


def test_roofline_readers_on_a_made_up_capture():
    keys = ["capture_decode_steps", "capture_decode_state_rows_live",
            "capture_prefill_calls", "capture_prefill_tokens_padded",
            "capture_state_snapshots_saved",
            "capture_state_snapshots_restored"]
    s0 = dict.fromkeys(keys, 0)
    s2 = {"capture_decode_steps": 100,
          "capture_decode_state_rows_live": 900,
          "capture_prefill_calls": 40, "capture_prefill_tokens_padded": 9600,
          "capture_state_snapshots_saved": 12,
          "capture_state_snapshots_restored": 10,
          "state_bytes_per_slot": ro.state_bytes_per_slot(M),
          "state_snapshot_bytes_total": 48 * ro.state_bytes_per_slot(M)}
    trace = {"devices": 1, "window_s": 4.0,
             "groups": {"decode": {"seconds": 1.2, "runs": 13},
                        "prefill": {"seconds": 0.6, "runs": 62}}}
    rates = {"kv_bytes_in_use": 9 * (ro.state_bytes_per_slot(M)
                                     + 3000 * 46_080),
             "decode_steps_per_s": 25.0}
    ctx = _ctx(s0, s2, trace, rates)
    dec = importlib.import_module("cellbench.readers.roofline_olmo_decode")
    pre = importlib.import_module("cellbench.readers.roofline_olmo_prefill")
    sv = importlib.import_module("cellbench.readers.state_value")
    want = 100 * ro.decode_step_bytes(
        DOC, 9.0, 9 * 3000 * 46_080) * 100 / 819e9 / 1.2
    assert dec.read(ctx, {}) == pytest.approx(want)
    assert 50.0 < dec.read(ctx, {}) < 100.0
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert pre.read(ctx, {}) == pytest.approx(100 * (
        40 * ro.prefill_call_seconds(DOC, 240, peaks)
        + ro.snapshot_copy_seconds(DOC, 22, peaks)) / 0.6)
    assert 0.0 < pre.read(ctx, {}) < 100.0
    assert sv.read(ctx, {"key": "state_snapshot_bytes_total",
                         "scale": 1e-9}) == pytest.approx(0.985, rel=1e-3)
    # the parent's program (no such counters), and a CPU's trace: nothing
    bare = _ctx({}, {}, trace, rates)
    assert dec.read(bare, {}) is None and pre.read(bare, {}) is None
    assert sv.read(bare, {"key": "state_snapshot_bytes_total"}) is None
    cpu = _ctx(s0, s2, dict(trace, devices=0), rates)
    assert dec.read(cpu, {}) is None and pre.read(cpu, {}) is None


def test_the_share_readers_say_nothing_on_a_program_without_the_counters():
    rd = importlib.import_module("cellbench.readers.loop_counter_ratio")
    with open(os.path.join(REPO, "cellbench", "layer_metrics",
                           "prefix_reused_share.json")) as f:
        reused = json.load(f)["args"]
    with open(os.path.join(REPO, "cellbench", "layer_metrics",
                           "state_snapshot_miss_share.json")) as f:
        miss = json.load(f)["args"]
    s0 = {"prefix_tokens_reused": 1000, "prefill_tokens_real": 500,
          "prefix_tokens_unrestorable": 10}
    s1 = {"prefix_tokens_reused": 4000, "prefill_tokens_real": 1500,
          "prefix_tokens_unrestorable": 160}
    ctx = {"snap0": {"state": s0}, "snap1": {"state": s1}}
    assert rd.read(ctx, reused) == pytest.approx(75.0)
    assert rd.read(ctx, miss) == pytest.approx(100 * 150 / 3150)
    parent = {"snap0": {"state": {"prefill_tokens_real": 500}},
              "snap1": {"state": {"prefill_tokens_real": 1500}}}
    assert rd.read(parent, reused) is None and rd.read(parent, miss) is None


def test_manifest_entries():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = next(c for c in m["configs"]
                 if c["name"] == "olmo-hybrid-7b-1chip")
    assert entry["file"] == os.path.relpath(CONFIG, REPO)
    assert entry["source"] == DOC["cellbench"]["source"]
    assert entry["reduced"] == DOC["cellbench"]["reduced"]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="olmo-hybrid-7b-1chip",
                        traffic="sessions", chips=1)
    assert "12 of 32 layers" in cell["why"] and "host share" in cell["why"]
    assert sum(w["config"] == entry["name"] for w in m["workloads"]) == 1
    e2e = {e["name"] for e in m["end_to_end"]
           if "workloads" not in e or CELL in e["workloads"]}
    assert e2e == {"tpot_mean_ms", "setup_s"}
    layer = {e["name"]: e for e in m["per_layer"]
             if "workloads" in e and CELL in e["workloads"]}
    assert set(layer) == {
        "prefill_padded_frac", "gap_p90_ms", "decode_step_ms.open",
        "decode_kv_read_amp.open", "decode_state_read_amp.open", "state_gb",
        "engine_host_ms_per_step.olmo", "prefill_dev_ms_per_captured_ktok.olmo",
        "prefix_reused_share", "state_snapshot_miss_share",
        "state_snapshot_gb", "olmo_decode_roofline", "olmo_prefill_roofline"}
    assert all(e["moves"] == "tpot_mean_ms" for e in layer.values())
    own = [n for n, e in layer.items() if e["workloads"] == [CELL]]
    assert len(own) == 7
    for name in own:  # each has its quantity's definition file
        assert os.path.exists(os.path.join(
            REPO, "cellbench", "layer_metrics",
            name.split(".", 1)[0] + ".json"))


def test_the_sessions_mix_as_the_issue_sets_it():
    mix = SESSIONS
    assert mix["loop"] == "open" and mix["arrivals"] == {
        "process": "poisson", "zero_gap_share": 0.25}
    assert mix["sharing"] == {
        "kind": "sessions", "system_prompts": 6,
        "system_tokens": {"dist": "fixed", "value": 1024},
        "turns": {"dist": "uniform", "min": 3, "max": 6},
        "think_s": {"dist": "exponential", "mean": 1.0}}
    assert mix["prompt_tokens"] == {
        "dist": "lognormal_truncated", "median": 384, "sigma": 0.5,
        "min": 128, "max": 768}
    assert mix["output_tokens"] == {
        "dist": "lognormal_truncated", "median": 128, "sigma": 0.3,
        "min": 64, "max": 224}
    assert mix["lead_in"]["traffic_seconds"] == 15
    assert 0.2 <= mix["rate_per_s"] <= 2.0


def test_the_sessions_schedule_for_one_seed():
    """Pinned at 0.6 session starts a second, whatever rate the file
    holds: 9 sessions in the 15-second lead-in, 30 in a 50-second
    window; the multiset of lengths is the seed's to shuffle, not to
    resize."""
    s = Schedule(dict(SESSIONS, rate_per_s=0.6), 2147493001, 50.0)
    assert (len(s.lead), len(s.window)) == (9, 30)
    turns = [len(x.turns) for x in s.window]
    assert sum(turns) == 135 and set(turns) == {3, 4, 5, 6}
    assert {len(x.system) for x in s.window} == {1024}
    assert len({x.system for x in s.window}) == 6
    # (the generator draws each segment's six afresh: the lead-in's
    # sessions run on into the window, its system prompts do not)
    assert len({x.system for x in s.lead}) == 6
    assert not {x.system for x in s.lead} & {x.system for x in s.window}
    users = [len(t.content) for x in s.window for t in x.turns]
    outs = [t.max_tokens for x in s.window for t in x.turns]
    assert (min(users), max(users), sum(users)) == (134, 760, 52613)
    assert (min(outs), max(outs), sum(outs)) == (66, 221, 17661)
    sent = resent = longest = 0
    for x in s.window:
        hist = len(x.system)
        for j, t in enumerate(x.turns):
            prompt = hist + len(t.content)
            sent += prompt
            resent += hist if j else 0
            hist = prompt + t.max_tokens
            longest = max(longest, hist)
            assert (t.think_s > 0) == (j > 0)
    # three quarters of what a window sends was sent before by the same
    # session (templates apart), and the first turns share the systems
    assert (sent, resent) == (321120, 237787)
    assert 0.74 < resent / sent < 0.75
    assert longest == 4689 < 7300
    other = Schedule(dict(SESSIONS, rate_per_s=0.6), 7, 50.0)
    assert sorted(len(t.content) for x in other.window for t in x.turns) \
        == sorted(users)


def test_the_references_copy_is_the_original():
    with open(os.path.join(REPO, "aigw_tpu", "models", "reference",
                           "olmo_hybrid_ref.py")) as a, \
            open(os.path.join(REPO, "cellbench", "reference",
                              "olmo_hybrid_ref.py")) as b:
        assert a.read() == b.read()
    with open(os.path.join(REPO, "cellbench", "reference",
                           "olmo_hybrid_ref.py")) as f:
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
def test_fits_one_v5e_chip_and_copies_no_pool(v5e, program):
    """The decode window and one 256-token chunk at the file's widths,
    depth, 16 slots and the whole pool of 1024 pages, at the largest
    page bucket (64), compile for a described (not attached) TPU v5e
    and fit its 16 GB beside the weights AND the snapshot pool. And no
    program copies a pool: with 30 key heads in a page row the chip's
    compiler padded the row to 32 and then "compressed" the whole 6 GB
    pool into another layout and back around every layer's scatter
    (``remat_compressed``; the decode window did not fit at all); with
    the 32 stored explicitly it is left where it lies."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from aigw_tpu.models import olmo_hybrid as oh
    from cellbench import serve_child

    cfg = oh.OlmoHybridConfig(**serve_child.model_kwargs(DOC))
    B, page, P = 16, 128, 64
    shapes = {
        "p": jax.eval_shape(
            lambda: oh.init_params(jax.random.PRNGKey(0), cfg)),
        "cache": jax.eval_shape(lambda: cfg.cache_spec().make(
            (B * P + 1) * page, B, "bfloat16"))}
    snapshots = jax.eval_shape(
        lambda: cfg.cache_spec().make_snapshots(B, "bfloat16"))
    i32 = jnp.int32
    if program == "decode":
        def fn(p, cache, tokens, positions, page_table, active):
            def body(carry, _):
                cache, tokens, positions = carry
                logits, cache = oh.decode_step(
                    p, cfg, tokens, positions, cache, page_table, page,
                    active)
                tokens = jnp.argmax(logits, -1).astype(i32)
                return (cache, tokens, positions + 1), tokens

            return jax.lax.scan(body, (cache, tokens, positions), None,
                                length=2)

        shapes.update(
            tokens=jax.ShapeDtypeStruct((B,), i32),
            positions=jax.ShapeDtypeStruct((B,), i32),
            page_table=jax.ShapeDtypeStruct((B, P), i32),
            active=jax.ShapeDtypeStruct((B,), jnp.bool_))
    else:
        fn = functools.partial(oh.prefill_suffix, cfg=cfg, page_size=page)
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
    # the snapshot pool is no argument of these programs: the state's
    # minor axis of 192 pads to 256 lanes on the chip, a third more
    pool = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(snapshots)) * 4 / 3
    assert need + pool < 15.75 * 2 ** 30, \
        f"{(need + pool) / 1e9:.2f} GB does not fit"
    text = compiled.as_text()
    kv = shapes["cache"].kv.shape
    assert kv == (3, 2, (B * P + 1) * page, 32, 128)
    dims = ",".join(map(str, kv))
    assert f"bf16[{dims}]" in text
    # (the 10 MB pool of convolution tails, three rows to a tile, is
    # still compressed around a chunk: PERF.md section 7)
    assert not [ln for ln in text.splitlines()
                if f"bf16[{dims}]" in ln.split(" = ")[-1][:80]
                and (" copy(" in ln or "compressed" in ln)]
    state = ",".join(map(str, shapes["cache"].slots["gdn_state"].shape))
    assert not [ln for ln in text.splitlines()
                if f"f32[{state}]" in ln.split(" = ")[-1][:80]
                and (" copy(" in ln or "compressed" in ln)]


# -- a tiny cell of the family through the whole harness, on the CPU -------
TINY = {
    "model_type": "olmo_hybrid", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 1024,
    "layer_types": PERIOD * 2, "linear_num_key_heads": 2,
    "linear_num_value_heads": 2, "linear_key_head_dim": 24,
    "linear_value_head_dim": 48, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True,
}
#: two systems of 128 tokens (one page), two or three turns of a short
#: message: every context is 2 to 4 pages of 128 — page buckets 2 and 4
MIX = {
    "name": "t-sessions", "loop": "open", "rate_per_s": 1.0,
    "arrivals": {"process": "poisson", "zero_gap_share": 0.25},
    "prompt_tokens": {"dist": "uniform", "min": 40, "max": 60},
    "output_tokens": {"dist": "uniform", "min": 6, "max": 12},
    "sharing": {"kind": "sessions", "system_prompts": 2,
                "system_tokens": {"dist": "fixed", "value": 128},
                "turns": {"dist": "uniform", "min": 2, "max": 3},
                "think_s": {"dist": "exponential", "mean": 0.2}},
    "serve_flags": [],
    # tails of 64 and of 128 at either bucket, a joiner at either
    "lead_in": {"tour": [[[170, 40], [150, 8, 0.1]], [[195, 20]],
                         [[270, 40], [150, 8, 0.1]], [[330, 40]]],
                "traffic_seconds": 2},
}


def tiny_doc() -> dict:
    doc = dict(TINY)
    doc["cellbench"] = {
        "name": "t-olmo", "source": "tests", "family": "olmo_hybrid",
        "chat_template": "chatml", "reduced": [], "assumed": {},
        "fields": {k: k for k in TINY if k != "model_type"},
        "serve_flags": ["--platform", "cpu", "--max-batch-size", "4",
                        "--max-seq-len", "512", "--page-size", "128",
                        "--prefill-bucket-rungs", "1",
                        "--prefill-chunk-tokens", "128"],
        "module_groups": "xla_default", "replicas": 1, "chips": 1,
        "expect": {"platform": "cpu", "param_bytes_total": 900336.0},
    }
    return doc


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    dst = sb.make_checkout(str(tmp_path_factory.mktemp("olmo")))
    sb.add_file(dst, "cellbench/configs/t-olmo.json", tiny_doc())
    sb.add_file(dst, "cellbench/traffic/t-sessions.json", MIX)
    cell = "t-olmo.sessions"
    layer = [("prefix_reused_share.t", "%"),
             ("state_snapshot_miss_share.t", "%"),
             ("state_snapshot_gb.t", "GB"), ("state_gb.t", "GB"),
             ("decode_state_read_amp.t", "x"),
             ("olmo_decode_roofline.t", "%"),
             ("olmo_prefill_roofline.t", "%")]
    sb.add_entries(
        dst,
        configs=[{"name": "t-olmo", "source": "tests",
                  "file": "cellbench/configs/t-olmo.json", "reduced": [],
                  "why": "tiny"}],
        workloads=[{"name": cell, "config": "t-olmo",
                    "traffic": "t-sessions", "chips": 1, "why": "tiny"}],
        end_to_end=[{"name": "tpot_mean_ms.t", "unit": "ms",
                     "better": "lower", "bound": 0.08,
                     "source": "host_clock", "workloads": [cell]}],
        per_layer=[{"name": n, "unit": u, "better": "higher",
                    "source": "program_counter", "layer": "prefix cache",
                    "moves": "tpot_mean_ms.t", "workloads": [cell]}
                   for n, u in layer])
    return dst


def test_tiny_cell_end_to_end(checkout):
    rc, last, lines, err = sb.run_cell(checkout, "t-olmo.sessions",
                                       2 ** 31 + 5, 6, 0, timeout=600)
    assert rc == 0, err[-3000:]
    summary = json.loads(lines[-2])
    assert last["correct"] is True, (summary, err[-2000:])
    assert set(last["metrics"]) == {"tpot_mean_ms.t", "setup_s"}
    assert last["attempted"] >= 8 and last["failed"] == 0
    # the gateway's usage ledger and the engine's meters agree on the
    # tokens the prefix cache served, too
    assert summary["checks"]["ledger_reconciles"]
    assert summary["checks"]["no_compile_in_window"], summary


def test_tiny_cell_traced_reports_the_counter_metrics(checkout):
    rc, last, lines, err = sb.run_cell(checkout, "t-olmo.sessions", 77, 6,
                                       1, timeout=600)
    assert rc == 0, err[-3000:]
    got = {k: v["value"] for k, v in last["metrics"].items()}
    # every later turn resumes behind the last whole chunk of the turn
    # before, every first turn behind the shared system prompt's page
    assert 30.0 < got["prefix_reused_share.t"] < 95.0
    assert 0.0 <= got["state_snapshot_miss_share.t"] < 50.0
    per_slot = 6 * (2 * 24 * 48 * 4 + 3 * 192 * 2)
    assert got["state_gb.t"] == pytest.approx(4 * per_slot * 1e-9)
    assert got["state_snapshot_gb.t"] == pytest.approx(12 * per_slot * 1e-9)
    assert got["decode_state_read_amp.t"] >= 1.0
    # a CPU's trace has no device plane: no roofline, and no exception
    assert "olmo_decode_roofline.t" not in got
    assert "olmo_prefill_roofline.t" not in got
    assert last["correct"] is True


def _reference_check(cfg, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench",
                                      "reference_check_olmo_hybrid.py"),
         "--config", str(cfg), "--system", "130", "--users", "50,40,40",
         "--answers", "8", "--platform", "cpu", *more],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.stdout.strip(), out.stderr[-2000:]
    return out.returncode, json.loads(out.stdout.strip().splitlines()[-1])


def _check_doc() -> dict:
    doc = tiny_doc()
    flags = doc["cellbench"]["serve_flags"]
    flags[flags.index("--page-size") + 1] = "16"
    flags[flags.index("--prefill-chunk-tokens") + 1] = "32"
    return doc


def test_reference_check_runs_at_a_tiny_size(tmp_path):
    cfg = tmp_path / "t-olmo.json"
    cfg.write_text(json.dumps(_check_doc()))
    rc, got = _reference_check(cfg)
    # the served programs carry bfloat16 weights here too: 0 or 1 by the
    # log-probabilities of the right model, never 2 (a wrong model or
    # the bfloat16-state control passing)
    assert rc in (0, 1) and got["control_ok"] is False
    assert got["wrong_ok"] is False and got["ok"] is (rc == 0)
    s = got["served"]
    assert s["resumed"] and s["snapshots_restored"] == 2
    assert [t["resumed_at"] for t in s["turns"]] == [0, 160, 224]
    assert [t["prompt_tokens"] for t in s["turns"]] == [180, 228, 276]
    for t in s["turns"]:
        assert t["answers"] == 8
        assert t["logprob_max"] >= t["logprob_mean"] > 0
        # beta at sigmoid is another model, by both limits' measure
        assert t["beta_sigmoid_logprob_mean"] > 3 * t["logprob_mean"]
    for t in s["turns"][1:]:
        assert t["state_lost_logprob_mean"] > 3 * t["logprob_mean"]
    k = got["kernels"]
    assert (k["chunked"], k["recurrent"]) == (180, 8)
    assert k["beta_over_one_share"] > 0.2
    # the same inputs on both sides: the program's kernels sit on the
    # float32 rule, a bfloat16 state far away, the limit between them
    limit = got["limits"]["gdn_rel"]
    for key in ("gdn_out_rel", "gdn_state_rel"):
        assert k["served"][key] < limit / 10
        assert k["control"][key] > 3 * limit
        assert k["all_bfloat16"][key] > 3 * limit


def test_reference_check_fails_the_lower_precision_control(tmp_path):
    cfg = tmp_path / "t-olmo.json"
    cfg.write_text(json.dumps(_check_doc()))
    rc, got = _reference_check(cfg, "--parts", "kernels", "--judge",
                               "control")
    assert rc == 1 and got["control_ok"] is False and got["ok"] is True
    assert "served" not in got
