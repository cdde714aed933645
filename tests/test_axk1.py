"""A.X-K1 (models/axk1.py) against its float32 reference
(models/reference/axk1_ref.py), at a tiny size on the CPU, in float32 —
LOGITS, never tokens. The tiny preset keeps the published RATIOS: nope :
rope : v head dims 2 : 1 : 2, a router in groups of which half stay, a
leading dense layer.

The tolerance. Program and reference compute the same float32
mathematics in another order (the absorbed form over latent rows against
per-head keys and values, an online softmax over blocks of pages against
one softmax, dense experts times combine weights against one expert at a
time): what separates them is float32 rounding through four layers,
observed at 2e-6 to 9e-6 on log-probabilities. The router's pick is
discrete, and a score within rounding of the k-th could flip it; at these
sizes and seeds none does. ``TOL`` leaves the rounding thirty times of
room and is still far under what the cheapest wrong program gives — the
mutation tests at the bottom prove that no group limit, a group scored by
its sum, a dropped scaling factor, a softmax scale without YaRN's factor,
a gate on the shared expert, and bfloat16 where float32 is stated each
fail it.
"""

from __future__ import annotations

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import axk1, kvq, qwen3_next
from aigw_tpu.models.reference import axk1_ref as ref
from aigw_tpu.ops import paged_walk
from axk1_util import (SHARE, make_cache, make_params, programs, ref_cfg,
                       ref_logits)

TOL = 3e-4
PS = 16  # page size
CONFIGS = {"all_held": axk1.TINY, "share_8_of_16": SHARE}
PUBLISHED = axk1.AXK1Config()


def _lp(x):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(x), -1))


def _tokens(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n)


@pytest.fixture(scope="module",
                params=[(c, t) for c in CONFIGS
                        for t in ("published", "serving")],
                ids="-".join)
def model(request):
    """(cfg, the tree the PROGRAMS read, tokens, the reference's
    log-probs, the published tree the REFERENCE reads). The programs
    are held to the reference on both trees they accept: a
    checkpoint's own leaves, and what ``serving_params`` makes of them
    at load (the tree a replica serves)."""
    name, tree = request.param
    cfg = CONFIGS[name]
    raw = make_params(cfg)
    p = axk1.serving_params(raw, cfg) if tree == "serving" else raw
    toks = _tokens(cfg, 100)
    return cfg, p, toks, _lp(ref_logits(raw, cfg, toks)), raw


def _chunked(p, cfg, toks, chunk, P=8, n_pages=32, table=None):
    """Prefill ``toks`` in chunks of ``chunk`` (a padded tail) into
    pages; → (log-probs after each chunk's last token, cache, table,
    the tapes)."""
    kv = make_cache(cfg, n_pages, PS)
    pt = jnp.asarray((np.arange(1, P + 1) if table is None else table)[None],
                     jnp.int32)
    done, outs, tapes = 0, [], []
    while done < len(toks):
        n = min(chunk, len(toks) - done)
        t = np.zeros((1, chunk), np.int32)
        t[0, :n] = toks[done:done + n]
        out, kv, tape = programs(cfg, PS).prefill_suffix(
            p, tokens=jnp.asarray(t), prefix_lens=jnp.asarray([done]),
            seq_lens=jnp.asarray([done + n]), cache=kv, page_table=pt,
            moe_stats=True)
        done += n
        outs.append((done - 1, _lp(out[0])))
        tapes.append(np.asarray(tape))
    return outs, kv, pt, tapes


# -- the configuration ------------------------------------------------------
def test_layer_kinds_cache_spec_and_tape():
    """The cell's configuration: 1 dense + 5 expert layers, 12 of 192
    experts, and ONE 576-wide bfloat16 row a token a layer."""
    cfg = axk1.AXK1Config(num_hidden_layers=6, num_experts=12,
                          router_experts=192, vocab_size=20480)
    assert cfg.layer_kinds == ("dense",) + ("moe",) * 5
    assert (cfg.n_layers, cfg.n_experts, cfg.router_width) == (6, 12, 192)
    assert cfg.cache_row == 512 + 64 == 576
    spec = cfg.cache_spec()
    assert not spec.stateful and spec.latent and spec.pinned
    # no K/V pair, no head axis: a token's 576 values down a column
    assert spec.kv_shape(1024) == (6, 576, 1024)
    assert spec.kv_page_bytes(128, "bfloat16") == 128 * 6 * 1152
    assert spec.kv_page_bytes(128, "bfloat16") / 128 == 6912
    pool = spec.make(256, 0, "bfloat16")
    assert pool.shape == (6, 576, 256) and pool.dtype == jnp.bfloat16
    assert pool.nbytes == 256 * 6912
    assert paged_walk.pair_bytes(pool, 128) == 128 * 1152
    assert kvq.n_slots(pool) == 256
    assert cfg.moe_tape_width == 12 + 3 + 3
    assert cfg.tape_extra == ("moe_groups_kept_hits", "moe_group_slots",
                              "prefill_keys_attended")
    with pytest.raises(ValueError, match="groups"):
        axk1.AXK1Config(num_experts=12, n_group=8)


def test_other_families_keep_their_pool():
    from aigw_tpu.models import llama
    from aigw_tpu.models.cache import spec_of

    spec = spec_of(llama.TINY)
    assert not spec.latent and spec.pinned == ""
    assert spec.kv_shape(64) == (llama.TINY.n_layers, 2, 64,
                                 llama.TINY.n_kv_heads, llama.TINY.head_dim)
    assert spec_of(qwen3_next.TINY).pinned  # per-slot state
    assert not spec_of(qwen3_next.TINY).latent


def test_yarn_frequencies_against_hand_values():
    """The published group: 64 rotary dims, theta 10000, factor 32 over
    4096, beta 32 / 1. ``dim(r) = 64 ln(4096 / (2 pi r)) / (2 ln
    10000)``: dim(32) = 10.47 -> lo 10, dim(1) = 22.51 -> hi 23. Pairs
    up to 10 keep theta^(-2i/64); pairs from 23 on have it over 32; the
    13 between ramp linearly."""
    f = axk1.yarn_inv_freq(PUBLISHED)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    assert math.floor(64 * math.log(4096 / (2 * math.pi * 32))
                      / (2 * math.log(10000))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000))) == 23
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 32, rtol=1e-6)
    i = 16  # ramp (16 - 10) / 13
    r = 6 / 13
    np.testing.assert_allclose(
        f[i], plain[i] * (1 - r) + plain[i] / 32 * r, rtol=1e-6)
    np.testing.assert_allclose(
        f, np.asarray(ref.yarn_inv_freq(ref_cfg(PUBLISHED))), rtol=1e-6)
    # and the softmax scale carries the factor: 192^-0.5 (0.1 ln 32 + 1)^2
    want = 192 ** -0.5 * (0.1 * math.log(32) + 1) ** 2
    assert abs(PUBLISHED.softmax_scale - want) < 1e-9
    assert abs(ref.softmax_scale(ref_cfg(PUBLISHED)) - want) < 1e-9
    # no scaling group: plain rotary, plain scale
    bare = dataclasses.replace(axk1.TINY, rope_scaling=None)
    np.testing.assert_allclose(
        axk1.yarn_inv_freq(bare), 10000.0 ** (-np.arange(4) / 4.0),
        rtol=1e-6)
    assert abs(bare.softmax_scale - 24 ** -0.5) < 1e-9


@pytest.mark.parametrize("position", [0, 4095, 4096, 8191])
def test_yarn_tables_against_float64_by_hand(position):
    """The rotation a token at ``position`` gets, program and reference,
    against a float64 computation written out here: pair j of the 32 is
    (x[j], x[j + 32]) turned by ``position * f_j``; ``mscale ==
    mscale_all_dim`` so the tables carry no factor. The tolerance is
    float32's: an angle near 8191 has an ulp of 4.9e-4 and the float32
    frequency an error of 6e-8 relative, together under 1e-3 on a cosine
    — a bfloat16 angle (ulp 32 at 8191) is off by whole turns."""
    i = np.arange(32, dtype=np.float64)
    plain = 10000.0 ** (-i / 32.0)
    ramp = np.clip((i - 10) / 13.0, 0.0, 1.0)
    freq = plain * (1 - ramp) + plain / 32.0 * ramp
    x = np.random.default_rng(position).standard_normal(64)
    ang = position * freq
    want = np.concatenate([x[:32] * np.cos(ang) - x[32:] * np.sin(ang),
                           x[32:] * np.cos(ang) + x[:32] * np.sin(ang)])
    got = axk1._rope(jnp.asarray(x, jnp.float32)[None, None],
                     jnp.asarray([[position]], jnp.int32),
                     axk1.yarn_inv_freq(PUBLISHED))[0, 0]
    assert np.abs(np.asarray(got, np.float64) - want).max() < 1e-3 * 4
    # the reference's: position is a row index there
    rows = jnp.zeros((position + 1, 64), jnp.float32).at[position].set(
        jnp.asarray(x, jnp.float32))
    with ref.computed_in(jnp.float32):
        rgot = ref.rope(rows, ref.yarn_inv_freq(ref_cfg(PUBLISHED)))[position]
    assert np.abs(np.asarray(rgot, np.float64) - want).max() < 1e-3 * 4
    bf = axk1._rope(
        jnp.asarray(x, jnp.float32)[None, None],
        jnp.asarray([[position]], jnp.bfloat16),
        axk1.yarn_inv_freq(PUBLISHED).astype(jnp.bfloat16))[0, 0]
    if position > 4000:  # (bfloat16 holds 0 and small integers exactly)
        assert np.abs(np.asarray(bf, np.float64) - want).max() > 0.1


# -- the programs against the reference --------------------------------------
def test_one_shot_prefill_matches_reference(model):
    cfg, p, toks, want, _ = model
    B, S = 2, 112  # padded; the second row shorter
    t = np.zeros((B, S), np.int32)
    t[0, :100], t[1, :57] = toks, toks[:57]
    got, _ = programs(cfg).prefill(
        p, tokens=jnp.asarray(t), seq_lens=jnp.asarray([100, 57]),
        cache=None, page_table=None)
    assert np.abs(_lp(got[0]) - want[99]).max() < TOL
    assert np.abs(_lp(got[1]) - want[56]).max() < TOL


@pytest.mark.parametrize("chunk", [40, 64])
def test_chunked_prefill_with_a_padded_tail_matches_reference(model, chunk):
    """100 tokens in chunks of 40 (three, the last padded) and of 64
    (two): every chunk after the first attends over the latent pages
    behind it."""
    cfg, p, toks, want, _ = model
    outs, *_ = _chunked(p, cfg, toks, chunk)
    assert len(outs) == -(-100 // chunk)
    for at, got in outs:
        assert np.abs(got - want[at]).max() < TOL, at


def test_prefill_then_decode_with_idle_rows_matches_reference(model):
    """Two prompts of different lengths in slots 3 and 1 of four (0 and
    2 idle), their pages interleaved in the pool, 20 decode steps
    through the paged latent cache, teacher-forced with drawn tokens;
    the second row stops being active half way."""
    cfg, p, _, _, raw = model
    B, P = 4, 8
    lens = {3: 70, 1: 44}
    table = {3: np.arange(1, 17, 2), 1: np.arange(2, 18, 2)}
    run = programs(cfg, PS)
    kv = make_cache(cfg, 32, PS)
    seqs, want = {}, {}
    for b, n in lens.items():
        seqs[b] = _tokens(cfg, n + 20, seed=10 + b)
        want[b] = _lp(ref_logits(raw, cfg, seqs[b]))
        t = np.zeros((1, 80), np.int32)
        t[0, :n] = seqs[b][:n]
        out, kv = run.prefill(
            p, tokens=jnp.asarray(t), seq_lens=jnp.asarray([n]), cache=kv,
            page_table=jnp.asarray(table[b][None], jnp.int32))
        assert np.abs(_lp(out[0]) - want[b][n - 1]).max() < TOL
    pt = np.zeros((B, P), np.int32)
    for b in lens:
        pt[b] = table[b]
    for step in range(20):
        live = [3, 1] if step < 10 else [3]
        tokens, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        act = np.zeros(B, bool)
        for b in live:
            at = lens[b] + step
            tokens[b], pos[b], act[b] = seqs[b][at], at, True
        out, kv, tape = run.decode_step(
            p, tokens=jnp.asarray(tokens), positions=jnp.asarray(pos),
            cache=kv, page_table=jnp.asarray(pt), active=jnp.asarray(act),
            moe_stats=True)
        for b in live:
            assert np.abs(_lp(out[b]) - want[b][lens[b] + step]).max() \
                < TOL, (step, b)
        # an expert layer's group columns: topk_group slots a live row;
        # a decode step counts no prefill keys
        tape = np.asarray(tape)
        assert tape[1:, -2].tolist() == [cfg.topk_group * len(live)] * 3
        assert not tape[:, -1].any() and not tape[0].any()


def test_hidden_states_is_the_reference_mean(model):
    cfg, p, toks, _, raw = model
    t = np.zeros((1, 64), np.int32)
    t[0, :50] = toks[:50]
    got = programs(cfg).hidden_states(
        p, tokens=jnp.asarray(t), seq_lens=jnp.asarray([50]))
    c = ref_cfg(cfg)
    with ref.computed_in(jnp.float32):
        x = raw["embed"][jnp.asarray(toks[:50])]
        for i in range(cfg.num_hidden_layers):
            x = ref.layer(raw, i, c, x)
        x = ref.rms_norm(x, raw["norm_f"], cfg.rms_norm_eps)
    assert np.abs(np.asarray(got[0]) - np.asarray(x.mean(0))).max() < TOL


# -- the serving leaves (ISSUE 50) --------------------------------------------
GOLDEN_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "axk1_programs_be12620.npz")
PROGRAMS = ("prefill", "prefill_suffix", "decode_step", "hidden_states")


@pytest.fixture(scope="module")
def parent_and_change():
    import axk1_golden_child

    return np.load(GOLDEN_FILE), axk1_golden_child.outputs(axk1)


@pytest.mark.parametrize("program", PROGRAMS)
def test_serving_leaves_give_the_parents_outputs(parent_and_change, program):
    """The four programs on ``serving_params(init_params(...))`` against
    the PARENT's on ``init_params(...)`` (be12620, taken before the
    change by tests/axk1_golden_child.py): the same picks, the same
    logits. 2e-5: every element is the same sum of the same float32
    products, but the CPU's dot sums an operand that lies transposed in
    another order — observed 4.5e-6 on logits as large as 3.2 (1.4e-6
    of them), 5e-7 on the pooled states."""
    parent, change = parent_and_change
    want, got = parent[program], change[program]
    assert got.shape == want.shape
    assert np.array_equal(got.argmax(-1), want.argmax(-1))
    assert np.allclose(got, want, rtol=0, atol=2e-5), \
        np.abs(got - want).max()


def test_serving_params_drops_what_it_laid_out_and_the_pieces_take_both():
    """``serving_params`` leaves no ``wq_b`` / ``wkv_b`` behind (the
    bytes stay what they were), passes a tree it has already laid out
    through, and ``absorb`` / ``_kvb`` still accept the tree that holds
    the published leaves — what cellbench/reference_check_latent.py
    hands them."""
    cfg = axk1.TINY
    raw = make_params(cfg)
    p = axk1.serving_params(raw, cfg)
    L, H = cfg.num_hidden_layers, cfg.num_attention_heads
    assert len(set(p) - set(raw)) == 4 * L
    assert set(raw) - set(p) == {f"l{i}.{w}" for i in range(L)
                                 for w in ("wq_b", "wkv_b")}
    assert sum(v.nbytes for v in p.values()) \
        == sum(v.nbytes for v in raw.values())
    assert p["l1.wq_nope"].shape == (H, cfg.qk_nope_head_dim,
                                     cfg.q_lora_rank)
    assert p["l1.wq_rope"].shape == (H, cfg.qk_rope_head_dim,
                                     cfg.q_lora_rank)
    assert p["l1.w_uk"].shape == (H, cfg.kv_lora_rank,
                                  cfg.qk_nope_head_dim)
    assert p["l1.w_uv"].shape == (H, cfg.v_head_dim, cfg.kv_lora_rank)
    again = axk1.serving_params(p, cfg)
    assert set(again) == set(p) and again["l1.w_uk"] is p["l1.w_uk"]
    # the pieces on both trees
    kvb = axk1._kvb(raw, 1, cfg)
    assert kvb.shape == (cfg.kv_lora_rank, H,
                         cfg.qk_nope_head_dim + cfg.v_head_dim)
    assert np.array_equal(
        np.asarray(p["l1.w_uk"]),
        np.asarray(kvb[..., :cfg.qk_nope_head_dim]).transpose(1, 0, 2))
    assert np.array_equal(
        np.asarray(p["l1.w_uv"]),
        np.asarray(kvb[..., cfg.qk_nope_head_dim:]).transpose(1, 2, 0))
    q_nope = jax.random.normal(jax.random.PRNGKey(1),
                               (2, 3, H, cfg.qk_nope_head_dim))
    q_rope = jax.random.normal(jax.random.PRNGKey(2),
                               (2, 3, H, cfg.qk_rope_head_dim))
    a = axk1.absorb(raw, 1, q_nope, q_rope, cfg)
    b = axk1.absorb(p, 1, q_nope, q_rope, cfg)
    assert a.shape == (2, 3, H, cfg.cache_row)
    assert np.allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-5)


def test_families_without_serving_params_keep_their_tree():
    from aigw_tpu.models.registry import family_fns

    assert family_fns("axk1").serving_params is axk1.serving_params
    for family in ("llama", "mixtral", "qwen3_next", "mimo_v2"):
        assert family_fns(family).serving_params is None


# -- the pieces on the same inputs -------------------------------------------
def _layer_inputs(cfg, p, S=60, i=1, seed=3):
    x = jax.random.normal(jax.random.PRNGKey(seed), (S, cfg.hidden_size))
    return ref.rms_norm(x, p[f"l{i}.in_norm"], cfg.rms_norm_eps)


def _latent_rows(cfg, p, i, h):
    """The SAME latent rows for both forms: the reference's."""
    c = ref_cfg(cfg)
    with ref.computed_in(jnp.float32):
        c_kv, k_rope = ref.latent_rows(p, i, c, h, ref.yarn_inv_freq(c))
    return jnp.concatenate([c_kv, k_rope], -1)


def test_absorbed_form_is_the_expanded_form():
    """One layer's attention on the same latent rows: the program's
    chunk path (absorbed query, blocks, online softmax) and its decode
    walk over pages, against the reference's per-head keys and values.
    1e-4: float32 rounding of sums 32 to 60 long in another order,
    observed under 1e-5."""
    cfg, i = axk1.TINY, 2
    p = make_params(cfg)
    h = _layer_inputs(cfg, p, i=i)
    S = h.shape[0]
    with ref.computed_in(jnp.float32):
        want = np.asarray(ref.attention(p, i, ref_cfg(cfg), h))
    rows = _latent_rows(cfg, p, i, h)
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    inv = axk1.yarn_inv_freq(cfg)
    q_abs = axk1._mla_q(p, i, h[None], pos, cfg, inv)
    mine = axk1._mla_kv(p, i, h[None], pos, cfg, inv)
    assert np.abs(np.asarray(mine[0]) - np.asarray(rows)).max() < 1e-5
    o = axk1._attend_blocks(q_abs, lambda j: rows.T[None], 1, S, pos,
                            jnp.ones((1, S), bool), cfg)
    got = axk1._mla_out(p, i, o, cfg, jnp.float32)[0]
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    # the decode walk: the last query over the rows laid out in pages
    # 3, 1, 4, 2 of layer i of a pool
    n_pages, P = 6, 4
    table = np.asarray([[3, 1, 4, 2]], np.int32)
    pool = np.zeros((cfg.n_layers, cfg.cache_row, n_pages * PS), np.float32)
    for t in range(S):
        pool[i, :, table[0, t // PS] * PS + t % PS] = np.asarray(rows[t])
    o1 = paged_walk.latent_decode_walk(
        q_abs[:, -1], jnp.asarray(pool), i, jnp.asarray(table),
        jnp.asarray([S], jnp.int32), page_size=PS, rank=cfg.kv_lora_rank,
        scale=cfg.softmax_scale)
    assert o1.shape == (1, cfg.n_heads, cfg.kv_lora_rank)
    got1 = axk1._mla_out(p, i, o1[:, None], cfg, jnp.float32)[0, 0]
    assert np.abs(np.asarray(got1) - want[-1]).max() < 1e-4


def _pick_by_hand(s, n_group, topk_group, k, scaling):
    """The selection as a loop in Python over one token's scores."""
    size = len(s) // n_group
    best = sorted(range(n_group), reverse=True,
                  key=lambda g: (max(s[g * size:(g + 1) * size]), -g))
    stay = set(best[:topk_group])
    cand = [e for e in range(len(s)) if e // size in stay]
    picks = sorted(cand, key=lambda e: (-s[e], e))[:k]
    total = sum(s[e] for e in picks)
    return picks, [s[e] / total * scaling for e in picks], stay


def test_router_picks_by_each_groups_largest_score():
    """Scores written by hand, 4 groups of 4, 2 groups stay, top-4. Row
    0: group 1 has the largest SUM (3.0 against 1.0, 1.7, 0.93) but the
    smallest maximum (0.75): it loses by the max rule and would win by
    any sum. Row 1: a near-tie inside a kept group (0.6 against
    0.6 + 1e-6) at the fourth pick. Row 2: random scores."""
    cfg = dataclasses.replace(axk1.TINY, num_experts=16)
    s = np.asarray([
        [0.95, 0.02, 0.02, 0.01,  0.75, 0.75, 0.75, 0.75,
         0.85, 0.80, 0.03, 0.02,  0.78, 0.05, 0.05, 0.05],
        [0.90, 0.6 + 1e-6, 0.6, 0.10,  0.20, 0.10, 0.10, 0.10,
         0.80, 0.50, 0.10, 0.10,  0.30, 0.30, 0.30, 0.30],
        np.random.default_rng(2).uniform(0.05, 0.95, 16),
    ], np.float32)
    w, ids, kept = axk1.pick(jnp.asarray(s), cfg)
    for t in range(3):
        picks, weights, stay = _pick_by_hand(
            [float(v) for v in s[t]], 4, 2, 4, cfg.routed_scaling_factor)
        assert np.asarray(ids[t]).tolist() == picks, t
        np.testing.assert_allclose(np.asarray(w[t]), weights, rtol=1e-6)
        assert set(np.flatnonzero(np.asarray(kept[t])).tolist()) == stay
    assert set(np.flatnonzero(np.asarray(kept[0]))) == {0, 2}  # not 1
    assert np.asarray(ids[0]).tolist() == [0, 8, 9, 10]
    assert np.asarray(ids[1]).tolist() == [0, 8, 1, 2]  # 1 before 2
    # and the reference picks the same (its own code, the same rule)
    p = {"l1.router": jnp.eye(16, dtype=jnp.float32)}
    logit = np.log(s / (1 - s))  # sigmoid's inverse
    with ref.computed_in(jnp.float32):
        rw, ri = ref.route(p, 1, ref_cfg(cfg), jnp.asarray(logit))
    assert np.asarray(ri)[[0, 2]].tolist() == np.asarray(ids)[[0, 2]].tolist()
    np.testing.assert_allclose(np.asarray(rw)[0], np.asarray(w)[0],
                               rtol=1e-5)


def test_router_is_the_references():
    cfg, i = SHARE, 2
    p = make_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.hidden_size))
    with ref.computed_in(jnp.float32):
        wv, wi = ref.route(p, i, ref_cfg(cfg), x)
    gv, gi, kept = axk1.route(p, i, x, cfg)
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert np.abs(np.asarray(gv) - np.asarray(wv)).max() < 1e-6
    # the picks lie in the topk_group groups kept, the weights sum to
    # the factor
    size = cfg.router_width // cfg.n_group
    assert np.asarray(kept).sum(1).tolist() == [cfg.topk_group] * 64
    assert np.take_along_axis(np.asarray(kept), np.asarray(gi) // size,
                              1).all()
    np.testing.assert_allclose(np.asarray(gv).sum(1),
                               cfg.routed_scaling_factor, rtol=1e-5)


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """A 16-wide router's layer cut into sixteen shares of one expert:
    the routed parts the shares compute, plus the shared expert ONCE,
    are the uncut layer's output — program and reference alike. 1e-5:
    float32 sums of sixteen parts in another order."""
    whole = axk1.TINY
    p = make_params(whole)
    i = 1
    E, F, D = 16, whole.moe_intermediate_size, whole.hidden_size
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, D))
    want = axk1.moe(p, i, x, whole)
    with ref.computed_in(jnp.float32):
        uncut = ref.moe_layer(p, i, ref_cfg(whole), x.reshape(-1, D))
        shared = ref.shared_expert(p, i, x.reshape(-1, D))
        assert np.abs(np.asarray(want.reshape(-1, D))
                      - np.asarray(uncut + shared)).max() < 1e-5
        ref_total = sum(
            ref.moe_layer(p, i, ref_cfg(whole), x.reshape(-1, D),
                          held_from=e, num_experts=1) for e in range(E))
        assert np.abs(np.asarray(ref_total) - np.asarray(uncut)).max() < 1e-5
    total = np.zeros((48, D), np.float32)
    for e in range(E):
        cfg = dataclasses.replace(whole, num_experts=1, router_experts=E,
                                  held_from=e)
        pe = dict(p)
        for m in ("gate", "up"):
            pe[f"l{i}.experts_{m}"] = p[f"l{i}.experts_{m}"].reshape(
                D, E, F)[:, e].reshape(D, F)
        pe[f"l{i}.experts_down"] = p[f"l{i}.experts_down"].reshape(
            E, F, D)[e]
        part = axk1.moe(pe, i, x, cfg).reshape(-1, D)
        total += np.asarray(part) - np.asarray(shared)
    assert np.abs(total + np.asarray(shared)
                  - np.asarray(want.reshape(-1, D))).max() < 1e-5


def test_tape_counts_real_tokens_only():
    cfg = SHARE  # experts 4-11 of 16 in groups of 4: groups 1 and 2
    p = make_params(cfg)
    toks = _tokens(cfg, 50)
    outs, _, _, tapes = _chunked(p, cfg, toks, 32)
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    first, tail = tapes
    assert first.shape == (cfg.num_hidden_layers, cfg.moe_tape_width)
    assert not first[0, :-1].any()  # the dense layer routes nothing
    for tape, n, before in ((first, 32, 0), (tail, 18, 32)):
        for row in tape[1:]:
            assert row[E + 1] == n * K  # every assignment of real tokens
            assert 0 < row[:E].sum() < n * K  # a share of them held
            assert row[E] == 0 and row[E + 2] == (row[:E] > 0).sum()
            # the group columns: topk_group slots a real token, of which
            # those on groups 1 and 2 can hold a held expert
            assert row[E + 4] == n * cfg.topk_group
            assert 0 < row[E + 3] < row[E + 4]
        attended = sum(before + t + 1 for t in range(n))
        assert tape[:, -1].tolist() == [attended] * len(tape)


def test_group_hits_follow_the_share():
    """A share that holds whole groups 1 and 2 counts a kept group as a
    hit exactly when it is one of them; a share of one expert of group
    3 when group 3 is kept."""
    kept = jnp.asarray([[True, True, False, False],
                        [False, True, True, False],
                        [False, False, True, True]])
    real = jnp.asarray([True, True, True])
    assert np.asarray(axk1._groups_counted(kept, real, SHARE)).tolist() \
        == [4, 6]
    one = dataclasses.replace(axk1.TINY, num_experts=1, router_experts=16,
                              held_from=13)
    assert np.asarray(axk1._groups_counted(kept, real, one)).tolist() \
        == [1, 6]
    assert np.asarray(axk1._groups_counted(
        kept, jnp.asarray([True, False, False]), one)).tolist() == [0, 2]


def test_decode_walk_reads_live_rows_only():
    """A dead row's page table may name anything: the step's output for
    the live rows does not change, and a dead row's stays zero."""
    cfg = axk1.TINY
    p = make_params(cfg)
    toks = _tokens(cfg, 40, seed=9)
    _, kv, pt, _ = _chunked(p, cfg, toks, 64)
    B, P = 4, 8
    inv = axk1.yarn_inv_freq(cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (B, 1, cfg.hidden_size))
    pos = jnp.asarray([[0], [39], [0], [0]], jnp.int32)
    q_abs = axk1._mla_q(p, 1, h, pos, cfg, inv)
    lengths = jnp.asarray([0, 40, 0, 0], jnp.int32)
    outs = []
    for junk in (0, 31):
        table = np.full((B, P), junk, np.int32)
        table[1] = np.asarray(pt[0])
        outs.append(np.asarray(paged_walk.latent_decode_walk(
            q_abs[:, 0], kv, 1, jnp.asarray(table), lengths, page_size=PS,
            rank=cfg.kv_lora_rank, scale=cfg.softmax_scale,
            plan=kvq.walk_plan(kv, lengths, jnp.asarray(table), PS))))
    assert np.array_equal(outs[0], outs[1])
    assert not outs[0][[0, 2, 3]].any() and outs[0][1].any()


# -- what the tolerance has to catch ---------------------------------------
def _worst(cfg, p, toks, want, chunk=32):
    outs, *_ = _chunked(p, cfg, toks, chunk)
    return max(np.abs(got - want[at]).max() for at, got in outs)


@pytest.fixture(scope="module")
def honest():
    cfg = SHARE
    p = make_params(cfg)
    toks = _tokens(cfg, 100)
    want = _lp(ref_logits(p, cfg, toks))
    assert _worst(cfg, p, toks, want) < TOL
    return cfg, p, toks, want


def test_no_group_limit_fails_the_tolerance(honest):
    cfg, p, toks, want = honest
    free = dataclasses.replace(cfg, topk_group=cfg.n_group)
    assert _worst(free, p, toks, want) > 10 * TOL


def test_a_dropped_scaling_factor_fails_the_tolerance(honest):
    cfg, p, toks, want = honest
    flat = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    assert _worst(flat, p, toks, want) > 10 * TOL


def test_a_softmax_scale_without_yarns_factor_fails_the_tolerance(honest):
    cfg, p, toks, want = honest
    s = dict(cfg.rope_scaling, mscale_all_dim=0)
    assert _worst(dataclasses.replace(cfg, rope_scaling=s), p, toks,
                  want) > 10 * TOL


def _mutated(monkeypatch, target, name, fn, cfg, p, toks, want):
    monkeypatch.setattr(target, name, fn)
    programs.cache_clear()  # trace the mutant, and forget it after
    try:
        return _worst(cfg, p, toks, want)
    finally:
        programs.cache_clear()


def test_a_group_scored_by_its_sum_fails_the_tolerance(honest, monkeypatch):
    """``noaux_tc``'s group score (the sum of a group's two largest) in
    place of the maximum: another set of groups, another model."""
    cfg, p, toks, want = honest
    real = jnp.max

    def top2(x, axis=None, **kw):
        if axis == -1 and x.ndim == 3 and x.shape[1] == cfg.n_group \
                and not kw:
            return jnp.sum(jax.lax.top_k(x, 2)[0], axis=-1)
        return real(x, axis=axis, **kw)

    assert _mutated(monkeypatch, axk1.jnp, "max", top2, cfg, p, toks,
                    want) > 10 * TOL


def test_a_gate_on_the_shared_expert_fails_the_tolerance(honest,
                                                         monkeypatch):
    """The hybrid family's sigmoid gate on the shared expert, which
    this family does not have."""
    cfg, p, toks, want = honest
    real = qwen3_next.held_experts

    def gated(p_, i, *a, **kw):
        kw["shared_gate"] = True
        p_ = dict(p_)
        p_[f"l{i}.shared_expert_gate"] = jnp.zeros((cfg.hidden_size, 1))
        return real(p_, i, *a, **kw)

    assert _mutated(monkeypatch, axk1.qwen3_next, "held_experts", gated,
                    cfg, p, toks, want) > 10 * TOL


def test_bfloat16_where_float32_is_stated_fails_the_tolerance(honest):
    """The reference computed in bfloat16 end to end — the nearest
    precision under the one these tests state — is hundreds of
    tolerances away."""
    cfg, p, toks, want = honest
    low = _lp(ref.forward(p, ref_cfg(cfg), jnp.asarray(toks),
                          dtype=jnp.bfloat16).astype(jnp.float32))
    assert np.abs(low - want).max() > 30 * TOL
