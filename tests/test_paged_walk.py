"""The decode step's page walk (aigw_tpu/ops/paged_walk.py) against a
plain float32 attention over each row's own tokens, and what it must
leave alone: rows that are not live, the pool, a hybrid family's state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import kvq, llama
from aigw_tpu.ops import paged_walk

PAGE = 8


def _pool(rng, B, P, Hkv, D, dtype):
    """A pool of two layers with every page of every row distinct, and
    the page table that says where. Returns (kv, page_table)."""
    n_pages = B * P + 1
    rows = rng.normal(size=(2, 2, n_pages * PAGE, Hkv, D)).astype(np.float32)
    pt = rng.permutation(n_pages - 1)[:B * P].reshape(B, P).astype(np.int32)
    if dtype in kvq.QUANT_DTYPES:
        q, scale = kvq.quantize_rows(jnp.asarray(rows), dtype)
        return {"q": q, "scale": scale}, jnp.asarray(pt)
    return jnp.asarray(rows, dtype), jnp.asarray(pt)


def _plain(q, kv, layer, pt, lengths):
    """float32 softmax attention of row b's query over ITS tokens
    0..lengths[b]-1, read from the pool one token at a time."""
    if kvq.is_quantized(kv):
        rows = kvq.dequantize_rows(kv["q"], kv["scale"]).astype(
            jnp.bfloat16)
    else:
        rows = kv
    rows = np.asarray(rows, np.float32)[layer]
    q = np.asarray(q, np.float32)
    B, H, D = q.shape
    grp = H // rows.shape[2]
    out = np.zeros((B, H, D), np.float32)
    for b in range(B):
        n = int(lengths[b])
        if n == 0:
            continue
        idx = [int(pt[b, t // PAGE]) * PAGE + t % PAGE for t in range(n)]
        k, v = rows[0][idx], rows[1][idx]
        for h in range(H):
            s = k[:, h // grp] @ q[b, h] / math.sqrt(D)
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v[:, h // grp]
    return out


def _walk(q, kv, layer, pt, lengths, **kw):
    return jax.jit(lambda q, kv, pt, ln: kvq.walk_kv(
        kv, layer, q, pt, ln, PAGE,
        kvq.walk_plan(kv, ln, pt.shape[1], PAGE), **kw))(
            q, kv, pt, jnp.asarray(lengths, jnp.int32))


def _ragged(rng, B, P):
    """Ragged lengths with dead rows in the middle of the batch, one
    row on a page edge and one a token past it."""
    lengths = rng.integers(1, P * PAGE + 1, size=B)
    lengths[1] = 0
    lengths[B // 2] = 0
    lengths[2] = 3 * PAGE
    lengths[3] = 3 * PAGE + 1
    lengths[-1] = P * PAGE
    return lengths


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("heads", [(4, 128), (8, 128), (2, 256)],
                         ids=lambda h: f"hkv{h[0]}d{h[1]}")
@pytest.mark.parametrize("P", [8, 16, 32])
def test_walk_is_plain_attention_over_each_rows_own_tokens(P, heads, dtype):
    Hkv, D = heads
    B, grp = 6, 2
    rng = np.random.default_rng(P * 1000 + Hkv)
    kv, pt = _pool(rng, B, P, Hkv, D, dtype)
    q = jnp.asarray(rng.normal(size=(B, Hkv * grp, D)), jnp.bfloat16)
    lengths = _ragged(rng, B, P)
    got = np.asarray(_walk(q, kv, 1, pt, lengths), np.float32)
    want = _plain(q, kv, 1, pt, lengths)
    # bfloat16 operands and output, float32 accumulation and softmax
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert not got[lengths == 0].any()  # dead rows: not walked, zero


@pytest.mark.parametrize("live", ["none", "one", "all"])
def test_walk_with_no_one_and_every_row_live(live):
    B, P, Hkv, D = 5, 8, 4, 128
    rng = np.random.default_rng(7)
    kv, pt = _pool(rng, B, P, Hkv, D, "float32")
    q = jnp.asarray(rng.normal(size=(B, 8, D)), jnp.float32)
    lengths = {"none": np.zeros(B, np.int64),
               "one": np.array([0, 0, 37, 0, 0]),
               "all": rng.integers(1, P * PAGE + 1, size=B)}[live]
    got = np.asarray(_walk(q, kv, 0, pt, lengths))
    np.testing.assert_allclose(got, _plain(q, kv, 0, pt, lengths),
                               atol=1e-5, rtol=1e-5)
    plan = kvq.walk_plan(kv, jnp.asarray(lengths, jnp.int32), P, PAGE)
    held = int(paged_walk.pages_live(jnp.asarray(lengths), PAGE))
    assert held == sum(-(-int(n) // PAGE) for n in lengths)
    # the counter is the loops' bound: trips x rows x pages a trip
    assert int(plan.pages_read) == int(
        np.asarray(plan.trips).sum()) * plan.rows * plan.pages
    assert int(plan.pages_read) >= held
    assert int(plan.n_blocks) == -(-int((lengths > 0).sum()) // plan.rows)
    if live == "none":
        assert int(plan.pages_read) == 0


@pytest.mark.parametrize("at", [PAGE - 1, PAGE, PAGE + 1, 4 * PAGE,
                                4 * PAGE + 1])
def test_walk_at_and_past_a_page_edge(at):
    B, P, Hkv, D = 3, 8, 2, 256
    rng = np.random.default_rng(at)
    kv, pt = _pool(rng, B, P, Hkv, D, "float32")
    q = jnp.asarray(rng.normal(size=(B, 4, D)), jnp.float32)
    lengths = np.array([at, 0, at])
    np.testing.assert_allclose(
        np.asarray(_walk(q, kv, 1, pt, lengths)),
        _plain(q, kv, 1, pt, lengths), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("shapes,want", [
    # (rows B, page bucket P, K+V bytes of a (row, page)) -> (R, G)
    ((16, 8, 256 << 10), (8, 2)),    # qwen2-7b-1chip, bucket 8
    ((16, 4, 256 << 10), (16, 1)),
    ((16, 1, 256 << 10), (16, 1)),
    ((32, 16, 256 << 10), (4, 4)),   # qwen3-next-80b-a3b-1chip
    ((32, 32, 256 << 10), (2, 8)),
    ((16, 16, 512 << 10), (2, 4)),   # mixtral-8x7b-1chip
    ((4, 8, 1 << 10), (4, 2)),       # a tiny pool: the whole batch a block
])
def test_block_sizes_follow_the_programs_shapes(shapes, want):
    assert paged_walk.walk_blocks(*shapes) == want


def test_plan_sorts_longest_first_and_bounds_each_block_by_its_own():
    lengths = jnp.asarray([9, 0, 64, 17, 0, 8, 33, 1], jnp.int32)
    plan = paged_walk.walk_plan(lengths, 16, PAGE, (4 << 20) // 16)
    assert (plan.rows, plan.pages) == (4, 4)
    order = np.asarray(plan.order)
    assert order[:6].tolist() == [2, 6, 3, 0, 5, 7]  # 8, 5, 3, 2, 1, 1 pages
    assert set(order[6:].tolist()) == {1, 4}  # the dead rows, last
    assert np.asarray(plan.trips).tolist() == [2, 1]  # ceil(8/4), ceil(1/4)
    assert int(plan.n_blocks) == 2
    assert int(plan.pages_read) == 3 * 4 * 4


def test_walk_per_head_shard_on_a_mesh_matches_one_device():
    from aigw_tpu.parallel.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(tp=2))
    B, P, Hkv, D = 4, 8, 4, 128
    rng = np.random.default_rng(3)
    kv, pt = _pool(rng, B, P, Hkv, D, "int8")
    q = jnp.asarray(rng.normal(size=(B, 8, D)), jnp.bfloat16)
    lengths = np.array([40, 0, 64, 9])
    one = np.asarray(_walk(q, kv, 0, pt, lengths), np.float32)
    got = np.asarray(_walk(q, kv, 0, pt, lengths, mesh=mesh), np.float32)
    np.testing.assert_array_equal(got, one)


def _tiny_state(B, P, rng):
    pt = jnp.asarray(rng.permutation(B * P).reshape(B, P), jnp.int32)
    tokens = jnp.asarray(rng.integers(1, 50, size=B), jnp.int32)
    positions = jnp.asarray([5, 17, 0, 30][:B], jnp.int32)
    active = jnp.asarray([True, False, True, False][:B])
    return tokens, positions, pt, active


def test_llama_decode_step_leaves_dead_rows_pages_as_they_were():
    cfg = llama.TINY
    rng = np.random.default_rng(0)
    B, P = 4, 4
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    shape = (cfg.n_layers, 2, (B * P + 1) * PAGE, cfg.n_kv_heads,
             cfg.head_dim)
    kv = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    tokens, positions, pt, active = _tiny_state(B, P, rng)
    _, kv2 = llama.decode_step(params, cfg, tokens, positions, kv, pt,
                               PAGE, active)
    before, after = np.asarray(kv, np.float32), np.asarray(kv2, np.float32)
    changed = np.argwhere((before != after).any(axis=(0, 1, 3, 4)))[:, 0]
    want = sorted(int(pt[b, int(positions[b]) // PAGE]) * PAGE
                  + int(positions[b]) % PAGE for b in (0, 2))
    assert sorted(changed.tolist()) == want  # the live rows' new token only
    # and the walk agrees with the window gather on the live rows
    logits_w, _ = llama.decode_step(params, cfg, tokens, positions, kv, pt,
                                    PAGE, active)
    logits_g, _ = llama.decode_step(params, cfg, tokens, positions, kv, pt,
                                    PAGE, active, attn_impl="gather")
    live = np.asarray(active)
    np.testing.assert_allclose(
        np.asarray(logits_w, np.float32)[live],
        np.asarray(logits_g, np.float32)[live], atol=3e-2, rtol=3e-2)


def test_hybrid_decode_step_leaves_dead_rows_state_and_pages():
    from aigw_tpu.models import qwen3_next as qn
    from aigw_tpu.models.registry import get_model_spec

    cfg = get_model_spec("tiny-qwen3-next").config
    rng = np.random.default_rng(1)
    B, P = 4, 4
    params = qn.init_params(jax.random.PRNGKey(0), cfg)
    cache = cfg.cache_spec().make((B * P + 1) * PAGE, B, "bfloat16")
    cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype), cache)
    tokens, positions, pt, active = _tiny_state(B, P, rng)
    _, cache2 = qn.decode_step(params, cfg, tokens, positions, cache, pt,
                               PAGE, active)
    dead = ~np.asarray(active)
    for name in ("gdn_state", "gdn_conv"):
        np.testing.assert_array_equal(
            np.asarray(cache.slots[name])[:, dead],
            np.asarray(cache2.slots[name])[:, dead])
        assert (np.asarray(cache.slots[name])[:, ~dead]
                != np.asarray(cache2.slots[name])[:, ~dead]).any()
    before = np.asarray(cache.kv, np.float32)
    after = np.asarray(cache2.kv, np.float32)
    changed = np.argwhere((before != after).any(axis=(0, 1, 3, 4)))[:, 0]
    want = sorted(int(pt[b, int(positions[b]) // PAGE]) * PAGE
                  + int(positions[b]) % PAGE for b in (0, 2))
    assert sorted(changed.tolist()) == want


# -- the counters that say what the decode programs read ---------------------

@pytest.mark.parametrize("key", [
    "decode_kv_pages_read", "decode_kv_pages_live",
    "decode_state_rows_read", "decode_state_rows_live"])
def test_counter_is_a_gauge_and_a_state_key(key):
    from aigw_tpu.obs.metrics import ENGINE_GAUGES, render_engine_gauges
    from aigw_tpu.tpuserve.engine import EngineStats

    assert (key, f"tpuserve_{key}_total") in ENGINE_GAUGES
    stats = EngineStats()
    setattr(stats, key, 12)
    assert (f"\ntpuserve_{key}_total 12\n".encode()
            in render_engine_gauges(stats))


@pytest.mark.parametrize("metric,read_key,live_key", [
    ("decode_kv_read_amp", "decode_kv_pages_read", "decode_kv_pages_live"),
    ("decode_state_read_amp", "decode_state_rows_read",
     "decode_state_rows_live")])
def test_read_amp_metric_reads_the_two_counters_or_says_nothing(
        metric, read_key, live_key):
    """The per-layer metric as its data file has it, through the reader
    the file names: the ratio of the counters' deltas over the window,
    and nothing (no ``KeyError``) on a /state without them — a commit
    from before the counters, whose traced run must still give a line."""
    import importlib
    import json
    import os

    from aigw_tpu.tpuserve.engine import EngineStats

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "cellbench", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert spec["args"] == {"num": [read_key], "den": [live_key]}
    assert hasattr(EngineStats(), read_key)
    assert hasattr(EngineStats(), live_key)
    reader = importlib.import_module("cellbench.readers." + spec["reader"])

    def ctx(s0, s1):
        return {"snap0": {"state": s0}, "snap1": {"state": s1}}

    got = reader.read(ctx({read_key: 10, live_key: 8},
                          {read_key: 250, live_key: 168}), spec["args"])
    assert got == pytest.approx(1.5)
    assert reader.read(ctx({"decode_steps": 0}, {"decode_steps": 9}),
                       spec["args"]) is None


def _stream(eng, prompt, n):
    import threading

    from aigw_tpu.tpuserve.engine import GenRequest
    from aigw_tpu.tpuserve.sampling import SamplingParams

    toks, done = [], threading.Event()

    def emit(tok, fin):
        if tok >= 0:
            toks.append(tok)
        if fin is not None:
            done.set()

    eng.submit(GenRequest(prompt=list(prompt), max_tokens=n,
                          sampling=SamplingParams(temperature=0.0),
                          emit=emit))
    assert done.wait(timeout=300)
    return toks


@pytest.mark.parametrize("rung", [
    {}, {"decode_backend": "fused"}, {"pallas_attn": True}],
    ids=["xla-walk", "fused-xla", "pallas"])
def test_engine_counts_what_its_decode_programs_read(rung):
    """On the walk the count is the loops' bound (one row of four live:
    its block of rows, its pages rounded up to a trip); on a kernel
    rung it is the [B, P] window the program addresses. The pages the
    live row held are counted the same way on both."""
    from aigw_tpu.models.registry import get_model_spec
    from aigw_tpu.tpuserve.engine import Engine, EngineConfig

    spec = get_model_spec("tiny-random")
    params = llama.init_params(jax.random.PRNGKey(3), spec.config,
                               jnp.float32)
    eng = Engine(params, spec.config, EngineConfig(
        max_batch_size=4, max_seq_len=256, page_size=16,
        min_prefill_bucket=16, decode_steps_per_tick=4, spec_tokens=0,
        kv_cache_dtype="float32", **rung))
    eng.start()
    try:
        toks = _stream(eng, [3, 1, 4, 1, 5, 9, 2, 6], 40)
    finally:
        eng.stop()
    st = eng.stats
    assert len(toks) == 40
    assert st.decode_kv_pages_live > 0
    # positions 8..47 at 16 tokens a page: 1 page until the 16th token,
    # then 2, then 3; junk steps past the request's end hold nothing
    assert st.decode_kv_pages_live <= 3 * st.decode_steps
    walks = eng.decode_attn_impl in ("xla-walk", "fused-xla")
    if walks:
        assert (st.decode_kv_pages_live <= st.decode_kv_pages_read
                < 4 * 16 * st.decode_steps)
    else:
        assert st.decode_kv_pages_read > 4 * st.decode_steps
