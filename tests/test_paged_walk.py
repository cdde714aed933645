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
def test_walk_folds_a_row_that_spans_two_trips(lengths, monkeypatch):
    """The plan is handed a pair 1 MiB wide (and no floor on the pairs
    a trip), so a trip is 4 pairs: rows of 8 pages span two or three
    trips and share a trip with their neighbours, and a row of one
    token is one pair among them."""
    monkeypatch.setattr(paged_walk, "_MIN_TRIP_PAIRS", 1)
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
    (2 << 20, 8),      # olmo-hybrid-7b-1chip: 32 stored key heads, the floor
    (8 << 20, 8),      # a pair wider than a trip: the floor still
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


# -- the one rung, over several steps and as the engine resolves it -----------

def _model_pool(rng, cfg, n_pages, dtype):
    """A pool of ``cfg``'s layers and heads filled with random rows, in
    the format ``dtype`` names."""
    rows = jnp.asarray(rng.normal(size=(
        cfg.n_layers, 2, n_pages * PAGE, cfg.n_kv_heads, cfg.head_dim)),
        jnp.float32)
    if dtype in kvq.QUANT_DTYPES:
        q, scale = kvq.quantize_rows(rows, dtype)
        return {"q": q, "scale": scale}
    return rows.astype(dtype)


def _rows_f32(kv):
    if kvq.is_quantized(kv):
        return np.asarray(kvq.dequantize_rows(kv["q"], kv["scale"]),
                          np.float32)
    return np.asarray(kv, np.float32)


#: walk against gather, logits of one decode step from one pool: the two
#: reads reduce in different orders over bfloat16 operands, a few ulps at
#: these magnitudes (the bound the deleted kernel cases held their
#: kernels to, ``parity.BF16_TOL``). An int4 row's grid step is a seventh
#: of its largest value, and the step reads the row it has just written:
#: where that rounding moved the new row across a step, the next layer
#: sees it. A wrong page, a missed row or a dropped scale moves logits of
#: this size by O(1).
STEP_TOL = {"bfloat16": 5e-2, "int8": 5e-2, "int4": 2e-1}


@pytest.mark.parametrize("dtype", ["bfloat16", "int8", "int4"])
@pytest.mark.parametrize("geometry", ["tiny-random", "tiny-moe"])
def test_decode_steps_carry_the_pool(geometry, dtype):
    """Six consecutive ``decode_step`` calls at B=8 through the family's
    own entry point, the walk carrying its pool from step to step, and
    at every step ``attn_impl="gather"`` from the same pool beside it:
    the logits of the live rows and the pool each hands on. The start
    positions put appends just before, on and after a page boundary, on
    a fresh sequence (position 0, a length of 1) and on an inactive
    slot, and every step crosses a boundary somewhere. Stands for the
    deleted ``TestFusedDecodeKernel`` (consecutive steps, fresh page
    and inactive slot, tiny-moe geometry, quantized pools),
    ``TestDecodeStepPallasAttn`` and ``test_single_token_length``, now
    held of the rung that serves: a quantized pool through
    ``decode_step`` over several steps was held nowhere.

    The pools: layer 0 is written from the tokens alone and must be
    equal bit for bit; a deeper layer's new row comes from attention
    outputs that differ by ``STEP_TOL``'s rounding, so it may differ by
    it — and, quantized, by one step of its row's grid besides."""
    from aigw_tpu.models.registry import family_fns, get_model_spec

    spec = get_model_spec(geometry)
    cfg, fns = spec.config, family_fns(spec.family)
    B, P, steps = 8, 4, 6
    tol = STEP_TOL[dtype]
    rng = np.random.default_rng(11)
    params = fns.init_params(jax.random.PRNGKey(0), cfg)
    pool = _model_pool(rng, cfg, B * P + 1, dtype)
    pt = jnp.asarray(rng.permutation(B * P).reshape(B, P), jnp.int32)
    positions = jnp.asarray([PAGE - 3, PAGE - 1, PAGE, 2 * PAGE - 2, 0,
                             3 * PAGE - 4, 5, PAGE // 2 + 1], jnp.int32)
    active = jnp.asarray([b != B - 2 for b in range(B)])
    live = np.asarray(active)

    def step(impl):
        return jax.jit(lambda tok, pos, kv: fns.decode_step(
            params, cfg, tok, pos, kv, pt, PAGE, active, attn_impl=impl))

    walk, gather = step(""), step("gather")
    kv = pool
    for n in range(steps):
        tok = jnp.asarray(rng.integers(1, cfg.vocab_size, size=B), jnp.int32)
        lg, kv_g = gather(tok, positions, kv)
        lw, kv = walk(tok, positions, kv)
        np.testing.assert_allclose(
            np.asarray(lw, np.float32)[live],
            np.asarray(lg, np.float32)[live], atol=tol, rtol=tol,
            err_msg=f"step {n}")
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a)[0], np.asarray(b)[0]), kv, kv_g)
        grid = (np.asarray(kv_g["scale"], np.float32)[..., None]
                if kvq.is_quantized(kv) else 0.0)
        assert (np.abs(_rows_f32(kv) - _rows_f32(kv_g))
                <= grid * 1.001 + 5e-2).all(), f"step {n}"
        positions = positions + active.astype(jnp.int32)
    # the inactive slot's pages, and the page no table names: as they were
    got, before = _rows_f32(kv), _rows_f32(pool)
    for page in (*np.asarray(pt)[B - 2], B * P):
        rows = slice(page * PAGE, (page + 1) * PAGE)
        np.testing.assert_array_equal(got[:, :, rows], before[:, :, rows])
    # each live row gained exactly ``steps`` tokens in every layer
    changed = (got != before).any(axis=(0, 1, 3, 4)).sum()
    assert changed == steps * int(live.sum())


@pytest.mark.parametrize("model,tp,want", [
    ("tiny-random", 1, "xla-walk"), ("tiny-moe", 1, "xla-walk"),
    ("tiny-qwen3-next", 1, "xla-walk"), ("tiny-axk1", 1, "xla-walk"),
    ("tiny-mimo-v2", 1, "xla-walk"),
    ("tiny-random", 2, "xla-walk-spmd"), ("tiny-moe", 2, "xla-walk-spmd"),
    ("tiny-random", 4, "xla-gather"), ("tiny-moe", 4, "xla-gather")],
    ids=lambda v: str(v))
def test_decode_rung_follows_what_the_engine_can_see(model, tp, want):
    """``resolve_decode_backend`` takes no request: a family on one chip
    walks, whatever its KV dtype, and a quantized pool's reason names
    the dequantizing read; on a mesh the heads decide — 4 query / 2 KV
    heads divide tp=2 (``-spmd``) and not tp=4 (the one ``xla-gather``
    row, "narrowed"). Stands for the deleted
    per-family ``..._fall_back_to_the_walk`` cases and the two
    rung ids of ``test_engine_counts_what_its_decode_programs_read``:
    what they held of five names is held of the three that are left."""
    from aigw_tpu.models.registry import get_model_spec
    from aigw_tpu.parallel.mesh import MeshSpec, make_mesh
    from aigw_tpu.tpuserve.attention import resolve_decode_backend
    from aigw_tpu.tpuserve.engine import EngineConfig

    model_cfg = get_model_spec(model).config
    mesh = make_mesh(MeshSpec(tp=tp)) if tp > 1 else None
    for dtype in ("bfloat16", "int8", "int4"):
        impl, why = resolve_decode_backend(
            EngineConfig(kv_cache_dtype=dtype), model_cfg, mesh)
        assert impl == want, why
        if want == "xla-gather":
            assert "narrowed" in why and f"tp={tp}" in why
            continue
        assert "page walk" in why
        assert (f"{dtype} KV pages dequantize" in why) == (
            dtype != "bfloat16")
        assert ("LOCAL head shard" in why) == (tp > 1)


@pytest.mark.parametrize("family", ["qwen3_next", "axk1", "mimo_v2"])
def test_a_family_without_a_gather_refuses_it(family):
    """What the deleted per-family ``..._fall_back_to_the_walk`` cases
    really protected: a family never silently runs a rung it
    lacks. ``attn_impl="gather"`` — what the engine hands a family on a
    mesh whose tp does not divide its heads — raises, and the message
    names the family and the rung."""
    from aigw_tpu.models.registry import family_fns

    with pytest.raises(NotImplementedError) as e:
        family_fns(family).decode_step(
            None, None, None, None, None, None, PAGE, None,
            attn_impl="gather")
    assert family in str(e.value) and "'gather'" in str(e.value)


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


@pytest.mark.parametrize("kv_dtype", ["float32", "int8", "int4"],
                         ids=["xla-walk", "int8", "int4"])
def test_engine_counts_what_its_decode_programs_read(kv_dtype):
    """The count is the walk's loop bound (one row of four live: its
    pairs rounded up to a trip, which at this tiny pool's bytes a pair
    is the page table of the step's page bucket, and no trip once
    nothing is live). ``[int8]`` / ``[int4]``: the same two counters
    under a quantized pool, whose smaller pairs make longer trips —
    held nowhere before (they take the place of the two kernel-rung
    ids, whose names are gone)."""
    from aigw_tpu.models.registry import get_model_spec
    from aigw_tpu.tpuserve.engine import Engine, EngineConfig

    spec = get_model_spec("tiny-random")
    params = llama.init_params(jax.random.PRNGKey(3), spec.config,
                               jnp.float32)
    eng = Engine(params, spec.config, EngineConfig(
        max_batch_size=4, max_seq_len=256, page_size=16,
        min_prefill_bucket=16, decode_steps_per_tick=4, spec_tokens=0,
        kv_cache_dtype=kv_dtype))
    assert eng.decode_attn_impl == "xla-walk"
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
    assert (st.decode_kv_pages_live < st.decode_kv_pages_read
            <= 4 * 16 * st.decode_steps)
