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
        kvq.walk_plan(kv, ln, pt, PAGE), **kw))(
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


@pytest.mark.parametrize("dtype", ["bfloat16", "int8", "int4"])
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
    plan = kvq.walk_plan(kv, jnp.asarray(lengths, jnp.int32), pt, PAGE)
    held = int(paged_walk.pages_live(jnp.asarray(lengths), PAGE))
    assert held == sum(-(-int(n) // PAGE) for n in lengths)
    # the counter is the loop's bound: trips x pairs a trip, and what
    # it reads past the live pairs is less than one trip
    assert int(plan.pages_read) == int(plan.trips) * plan.pairs
    assert 0 <= int(plan.pages_read) - held < plan.pairs
    if live == "none":
        assert int(plan.pages_read) == 0


@pytest.mark.parametrize("at", [1, PAGE - 1, PAGE, PAGE + 1, 4 * PAGE,
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


@pytest.mark.parametrize("lengths", [
    [PAGE * 8] * 6,              # 48 pairs: every row spans two trips
    [1, PAGE * 8, 0, 1, PAGE * 3 + 1, PAGE * 8],
    [0, 0, PAGE * 5, PAGE * 8, 0, PAGE * 8],
], ids=["full", "ragged", "dead-first"])
def test_walk_folds_a_row_that_spans_two_trips(lengths):
    """The plan is handed a pair 1 MiB wide, so a trip is 4 pairs:
    rows of 8 pages span two or three trips and share a trip with
    their neighbours, and a row of one token is one pair among them."""
    B, P, Hkv, D = 6, 8, 2, 128
    rng = np.random.default_rng(11)
    kv, pt = _pool(rng, B, P, Hkv, D, "float32")
    q = jnp.asarray(rng.normal(size=(B, 4, D)), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    assert paged_walk.pair_plan(ln, pt, PAGE, 1 << 20).pairs == 4
    got = np.asarray(jax.jit(
        lambda q, kv, pt, ln: paged_walk.paged_decode_walk(
            q, kv, 0, pt, ln, page_size=PAGE,
            plan=paged_walk.pair_plan(ln, pt, PAGE, 1 << 20)))(
                q, kv, pt, ln))
    np.testing.assert_allclose(got, _plain(q, kv, 0, pt, lengths),
                               atol=1e-5, rtol=1e-5)
    assert not got[np.asarray(lengths) == 0].any()


@pytest.mark.parametrize("pair,want", [
    (256 << 10, 16),   # qwen2-7b-1chip, qwen3-next-80b-a3b-1chip: bf16
    (512 << 10, 8),    # mixtral-8x7b-1chip
    (128 << 10, 32),   # qwen2's pool as int8
    (1 << 10, 4096),   # a tiny pool: every pair in one trip
    (8 << 20, 1),      # a pair wider than a trip
])
def test_pairs_a_trip_follow_the_pools_shapes(pair, want):
    assert paged_walk.trip_pairs(pair) == want


@pytest.mark.parametrize("seed", range(8))
def test_pair_plan_lists_the_live_pairs_in_row_and_column_order(seed):
    """The property the walk rests on, for any ``lengths``: a dead row
    gives no pair, row ``b`` gives ``ceil(lengths[b] / page)`` of them,
    adjacent and in column order, the rows in row order; what is read
    past the live pairs is less than one trip."""
    rng = np.random.default_rng(seed)
    B, P = int(rng.integers(1, 12)), int(rng.integers(1, 9))
    pair = (4 << 20) // int(rng.integers(1, 20))
    lengths = rng.integers(0, P * PAGE + 1, size=B)
    lengths[rng.random(B) < 0.3] = 0
    if seed == 0:
        lengths[:] = 0
    pt = rng.permutation(B * P).reshape(B, P).astype(np.int32)
    plan = paged_walk.pair_plan(jnp.asarray(lengths, jnp.int32),
                                jnp.asarray(pt), PAGE, pair)
    N = plan.pairs
    need = [-(-int(n) // PAGE) for n in lengths]
    want = [(b, c) for b in range(B) for c in range(need[b])]
    row, page, left = (np.asarray(x) for x in (plan.row, plan.page,
                                               plan.left))
    assert len(row) % N == 0 and len(row) >= B * P
    assert row[:len(want)].tolist() == [b for b, _ in want]
    assert page[:len(want)].tolist() == [int(pt[b, c]) for b, c in want]
    assert left[:len(want)].tolist() == [
        int(lengths[b]) - c * PAGE for b, c in want]
    assert (row[len(want):] == B).all() and not left[len(want):].any()
    assert int(plan.trips) == -(-len(want) // N)
    assert 0 <= int(plan.pages_read) - len(want) < N
    order = np.asarray(plan.order).tolist()
    live = [b for b in range(B) if need[b]]
    assert order[:len(live)] == live and sorted(order) == list(range(B))


@pytest.mark.parametrize("shapes,want", [
    # the latent walk's row blocks:
    # (rows B, page bucket P, bytes of a (row, page)) -> (R, G)
    ((16, 8, 256 << 10), (8, 2)),
    ((16, 4, 256 << 10), (16, 1)),
    ((16, 1, 256 << 10), (16, 1)),
    ((32, 16, 256 << 10), (4, 4)),
    ((32, 32, 256 << 10), (2, 8)),
    ((16, 16, 512 << 10), (2, 4)),
    ((16, 40, 144 << 10), (3, 8)),   # a.x-k1-1chip: a latent page
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


@pytest.mark.parametrize("latent", [False, True])
def test_the_pools_format_picks_the_plan(latent):
    """``kvq.walk_plan``: a latent pool [L, W, n_slots] keeps the row
    blocks (a carried [B, H, rank] accumulator would be as large as the
    pages it came from), a K/V pool takes the flat list of pairs."""
    ln = jnp.asarray([9, 0, 17], jnp.int32)
    pt = jnp.zeros((3, 4), jnp.int32)
    kv = (jnp.zeros((2, 24, 13 * PAGE), jnp.bfloat16) if latent
          else jnp.zeros((2, 2, 13 * PAGE, 2, 16), jnp.bfloat16))
    plan = kvq.walk_plan(kv, ln, pt, PAGE)
    assert isinstance(plan, paged_walk.WalkPlan if latent
                      else paged_walk.PairPlan)
    assert int(plan.pages_read) >= 5


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
    """On the walk the count is the loop's bound (one row of four live:
    its pairs rounded up to a trip, which at this tiny pool's 4 KiB a
    pair is the page table of the step's page bucket, and no trip once
    nothing is live); on a kernel rung it is the [B, P] window the
    program addresses at every step. The pages the live row held are
    counted the same way on both."""
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
        assert (st.decode_kv_pages_live < st.decode_kv_pages_read
                <= 4 * 16 * st.decode_steps)
    else:
        assert st.decode_kv_pages_read > 4 * st.decode_steps
