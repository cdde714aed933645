"""MiMo-V2 (models/mimo_v2.py) against its float32 reference
(models/reference/mimo_v2_ref.py), at a tiny size on the CPU, in float32
— LOGITS, never tokens. The tiny preset keeps the published RATIOS: keys
24 over values 16 with 8 rotated (192 : 128 : 64), one global key head
to two window ones (4 : 8), the first seven layers' pattern ``[0, 1, 1,
1, 1, 0, 1]`` behind a leading dense layer — and a window of 8, SMALLER
than a page (16) and than every chunk here, so that every boundary is
crossed many times.

The tolerance. Program and reference compute the same float32
mathematics in another order (a flattened ``v | k`` page column and an
online softmax over blocks of pages against one softmax, a ring read
where it lies against a full ``[S, S]`` mask, the sink folded into the
softmax's denominator against a concatenated column, dense experts
times combine weights against one expert at a time): what separates
them is float32 rounding through seven layers, observed at 2e-6 to 5e-6
on log-probabilities. The router's pick is discrete, and a score within
rounding of the k-th could flip it; at these sizes and seeds none does.
``TOL`` leaves the rounding sixty times of room and is still far under
what the cheapest wrong program gives — the mutation tests at the
bottom prove that window layers left global, a dropped sink, a bias
that weighs, a dropped value scale, one theta for both kinds, a window
one key too long, and bfloat16 where float32 is stated each fail it.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import kvq, mimo_v2, qwen3_next
from aigw_tpu.models.cache import StateCache, spec_of
from aigw_tpu.models.reference import mimo_v2_ref as ref
from aigw_tpu.ops import paged_walk
from mimo_v2_util import (SHARE, make_cache, make_params, programs, ref_cfg,
                          ref_logits)

TOL = 3e-4
PS = 16  # page size
W = mimo_v2.TINY.sliding_window  # 8
CONFIGS = {"all_held": mimo_v2.TINY, "share_8_of_32": SHARE}
PUBLISHED = mimo_v2.MiMoV2Config()
#: the cell's configuration: the first seven published layers, 16 of 256
#: experts, an eighth of the vocabulary
CELL = mimo_v2.MiMoV2Config(
    num_hidden_layers=7, hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
    num_experts=16, router_experts=256, vocab_size=19072)


def _lp(x):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(x), -1))


def _tokens(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n)


@pytest.fixture(scope="module", params=list(CONFIGS))
def model(request):
    cfg = CONFIGS[request.param]
    p = make_params(cfg)
    toks = _tokens(cfg, 100)
    return cfg, p, toks, _lp(ref_logits(p, cfg, toks))


def _chunked(p, cfg, toks, chunk, P=8, n_pages=32, table=None, slot=1,
             cache=None):
    """Prefill ``toks`` in chunks of ``chunk`` (a padded tail) into
    pages and ring ``slot``; → (log-probs after each chunk's last token,
    cache, table, the tapes)."""
    kv = make_cache(cfg, n_pages, PS) if cache is None else cache
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
            moe_stats=True, slot_ids=jnp.asarray([slot]))
        done += n
        outs.append((done - 1, _lp(out[0])))
        tapes.append(np.asarray(tape))
    return outs, kv, pt, tapes


# -- the configuration ------------------------------------------------------
def test_layer_kinds_cache_spec_and_tape():
    """The cell's configuration: two global layers with pages of 1280
    values a token, five window layers with a ring of 128 tokens a slot
    and NO pages."""
    cfg = CELL
    assert cfg.layer_kinds == ("global", "window", "window", "window",
                               "window", "global", "window")
    assert (cfg.n_layers, cfg.n_experts, cfg.router_width) == (7, 16, 256)
    assert (cfg.n_global_layers, cfg.n_window_layers) == (2, 5)
    assert cfg.rotary_dim == int(192 * 0.334) == 64
    assert cfg.row_width("global") == 4 * (128 + 192) == 1280
    assert cfg.row_width("window") == 8 * (128 + 192) == 2560
    assert cfg.value_width("global") == 512
    assert cfg.softmax_scale == 192 ** -0.5
    spec = cfg.cache_spec()
    assert spec.stateful and spec.latent and spec.window == 128
    assert "window keys live beside its pages" in spec.pinned
    # pages: ONE flattened row a token a global layer, and only the
    # global layers: 2 x 2560 B a token
    assert spec.kv_layers == 2
    assert spec.kv_shape(1024) == (2, 1280, 1024)
    assert spec.kv_page_bytes(128, "bfloat16") == 128 * 2 * 2560
    assert spec.kv_page_bytes(128, "bfloat16") / 128 == 5120
    # rings: five layers x 128 tokens x 8 heads x 320 values x 2 bytes
    assert spec.state_bytes_per_slot("bfloat16") \
        == 5 * 128 * 8 * 320 * 2 == 3_276_800
    cache = spec.make(256, 3, "bfloat16")
    assert isinstance(cache, StateCache)
    assert cache.kv.shape == (2, 1280, 256)
    assert cache.kv.dtype == jnp.bfloat16
    assert cache.kv.nbytes == 256 * 5120
    assert cache.slots["swa_ring"].shape == (5, 3, 128, 2560)
    assert cache.slots["swa_ring"].nbytes == 3 * 3_276_800
    assert paged_walk.pair_bytes(cache.kv, 128) == 128 * 2560
    assert kvq.n_slots(cache.kv) == 256
    # were the five window layers paged too, a token would hold six
    # times as much: what the architecture is for
    assert 7 * 2560 + 5 * 2560 == 6 * 5120
    assert cfg.moe_tape_width == 16 + 3 + 6
    assert cfg.tape_extra == (
        "moe_unserved_tokens", "swa_keys_attended", "swa_keys_in_context",
        "decode_state_rows_read", "decode_state_rows_live",
        "prefill_keys_attended")


def test_published_pattern_and_refusals():
    """``hybrid_layer_pattern`` left out is the published rule: layer 0
    and then every sixth are global."""
    kinds = PUBLISHED.hybrid_layer_pattern
    assert len(kinds) == 48 and kinds[:7] == (0, 1, 1, 1, 1, 0, 1)
    assert [i for i, k in enumerate(kinds) if k == 0] \
        == [0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert (PUBLISHED.n_global_layers, PUBLISHED.n_window_layers) == (9, 39)
    # a list from a JSON file is a tuple in the (hashable) config
    assert mimo_v2.MiMoV2Config(
        num_hidden_layers=2, hybrid_layer_pattern=[0, 1]
    ).hybrid_layer_pattern == (0, 1)
    with pytest.raises(ValueError, match="hybrid_layer_pattern"):
        mimo_v2.MiMoV2Config(num_hidden_layers=3,
                             hybrid_layer_pattern=(0, 1))
    with pytest.raises(ValueError, match="key heads"):
        mimo_v2.MiMoV2Config(swa_num_key_value_heads=6)


def test_parameter_count_of_the_cell_is_the_hand_count():
    """Attention 89.13 M (global) / 94.37 M (window), the dense layer's
    feed-forward 201.33 M, an expert layer's 16 experts 402.65 M + the
    router 1.05 M, embedding and head 156.24 M — with the norms (15 x
    4096), sinks (5 x 64) and router biases (6 x 256), 3,429,955,392
    parameters: ISSUE 47's hand count to the unit; bfloat16 but for the
    float32 sinks and biases, 6,859,914,496 bytes."""
    shapes = jax.eval_shape(
        lambda: mimo_v2.init_params(jax.random.PRNGKey(0), CELL))
    n = {k: math.prod(v.shape) for k, v in shapes.items()}
    small = sum(v for k, v in n.items() if k.split(".")[-1] in (
        "in_norm", "post_norm", "norm_f", "sink", "router_bias"))
    assert small == 15 * 4096 + 5 * 64 + 6 * 256
    assert sum(n.values()) == 3_429_955_392
    assert sum(n.values()) - small == (
        4096 * 13568 * 2 + 4096 * 14848 * 5 + 7 * 8192 * 4096  # attention
        + 3 * 4096 * 16384 + 6 * (4096 * 256 + 16 * 3 * 4096 * 2048)
        + 2 * 19072 * 4096)
    assert sum(math.prod(v.shape) * v.dtype.itemsize
               for v in shapes.values()) == 6_859_914_496
    assert n["l0.wqkv"] == 4096 * 13568 and n["l1.wqkv"] == 4096 * 14848
    assert shapes["l1.sink"].dtype == shapes["l1.router_bias"].dtype \
        == jnp.float32
    assert "l0.sink" not in n and "l5.sink" not in n  # global layers
    assert "l0.router" not in n  # the leading dense layer
    assert not any("shared" in k for k in n)  # no shared expert


def test_other_families_keep_their_pool_and_their_reasons():
    from aigw_tpu.models import axk1, llama

    spec = spec_of(llama.TINY)
    assert not spec.latent and not spec.window and spec.pinned == ""
    assert "recurrent state" in spec_of(qwen3_next.TINY).pinned
    assert "latent row" in spec_of(axk1.TINY).pinned
    assert spec_of(mimo_v2.TINY).window == W


# -- rotary, of each kind -----------------------------------------------------
@pytest.mark.parametrize("kind,theta", [("global", 1e7), ("window", 1e4)])
@pytest.mark.parametrize("position", [1, 4097])
def test_rotary_of_each_kind_against_float64_by_hand(kind, theta, position):
    """The published widths: 64 of 192 dims rotate, pairs as halves
    (``x[j]`` with ``x[j + 32]``), ``theta`` by layer kind; the other
    128 pass. 2e-3: float32 angles at position 4097 carry 4097 x 2^-24
    = 2.4e-4 radians of rounding."""
    cfg = PUBLISHED
    assert cfg.theta(kind) == theta
    x = np.random.default_rng(5).normal(size=(1, 1, 2, 192)).astype(
        np.float32)
    pos = jnp.asarray([[position]], jnp.int32)
    got = np.asarray(qwen3_next._rope_partial(
        jnp.asarray(x), pos, cfg.theta(kind), cfg.rotary_dim))[0, 0]
    want = x[0, 0].astype(np.float64).copy()
    for j in range(32):
        ang = position * theta ** (-2.0 * j / 64.0)
        a, b = x[0, 0, :, j].astype(np.float64), \
            x[0, 0, :, j + 32].astype(np.float64)
        want[:, j] = a * math.cos(ang) - b * math.sin(ang)
        want[:, j + 32] = b * math.cos(ang) + a * math.sin(ang)
    assert np.array_equal(got[:, 64:], x[0, 0, :, 64:])  # 128 dims pass
    assert np.abs(got - want).max() < 2e-3
    mine = np.asarray(ref.rope(jnp.asarray(x[0]), theta, 64,
                               positions=[position]))[0]
    assert np.abs(mine - want).max() < 2e-3
    # and the two kinds differ where the angle is not tiny
    other = np.asarray(qwen3_next._rope_partial(
        jnp.asarray(x), pos, 1e4 if theta == 1e7 else 1e7, 64))[0, 0]
    assert np.abs(other - got).max() > 0.1


# -- logits against the reference ---------------------------------------------
def test_one_shot_prefill_matches_reference(model):
    cfg, p, toks, want = model
    for n in (5, 50, 100):  # under the window; over it; over a page
        t = np.zeros((1, 112), np.int32)
        t[0, :n] = toks[:n]
        out, _, tape = programs(cfg, PS).prefill(
            p, tokens=jnp.asarray(t), seq_lens=jnp.asarray([n]),
            cache=make_cache(cfg, 16, PS),
            page_table=jnp.asarray(np.arange(1, 9)[None], jnp.int32),
            moe_stats=True, slot_ids=jnp.asarray([0]))
        assert np.abs(_lp(out[0]) - want[n - 1]).max() < TOL, n
        assert tape.shape == (7, cfg.moe_tape_width)


@pytest.mark.parametrize("chunk", [40, 64])
def test_chunked_prefill_with_a_padded_tail_matches_reference(model, chunk):
    """100 tokens in chunks of 40 (three, the last padded by 20) or 64
    (two, padded by 28): every chunk boundary falls inside a page and
    away from a multiple of the window."""
    cfg, p, toks, want = model
    outs, *_ = _chunked(p, cfg, toks, chunk)
    assert len(outs) == -(-100 // chunk)
    for at, got in outs:
        assert np.abs(got - want[at]).max() < TOL, at


def test_a_padded_tail_does_not_enter_the_ring(model):
    """After a chunk of 40 that holds 20 real tokens, ring row ``r``
    holds the token at the last real position ``p`` with ``p % 8 == r``
    — what a one-shot prefill of the same tokens leaves — and no
    padded one."""
    cfg, p, toks, _ = model
    _, cache, *_ = _chunked(p, cfg, toks, 40)
    t = np.zeros((1, 112), np.int32)
    t[0, :100] = toks
    _, whole, _ = programs(cfg, PS).prefill(
        p, tokens=jnp.asarray(t), seq_lens=jnp.asarray([100]),
        cache=make_cache(cfg, 32, PS),
        page_table=jnp.asarray(np.arange(1, 9)[None], jnp.int32),
        moe_stats=True, slot_ids=jnp.asarray([1]))
    a = np.asarray(cache.slots["swa_ring"])
    b = np.asarray(whole.slots["swa_ring"])
    assert np.abs(a[:, 1] - b[:, 1]).max() < 1e-4 and a[:, 1].any()
    assert not a[:, 0].any() and not b[:, 0].any()  # the other slot
    assert np.abs(np.asarray(cache.kv) - np.asarray(whole.kv)).max() < 1e-4


def test_prefill_then_decode_through_pages_and_ring(model):
    """Two slots of four: one sequence of 37 tokens, one of 5 — SHORTER
    than the window — then 3 x window = 24 decode steps each, through
    the pages and three turns of the ring, with two rows idle and, from
    step 12 on, one of the two finished."""
    cfg, p, _, _ = model
    B, P = 4, 8
    lens = {3: 37, 1: 5}
    seqs = {b: _tokens(cfg, n + 3 * W, seed=20 + b) for b, n in lens.items()}
    want = {b: _lp(ref_logits(p, cfg, s)) for b, s in seqs.items()}
    table = {3: np.arange(1, 9), 1: np.arange(9, 17)}
    kv = make_cache(cfg, 32, PS, n_slots=B)
    run = programs(cfg, PS)
    for b, n in lens.items():
        t = np.zeros((1, 48), np.int32)
        t[0, :n] = seqs[b][:n]
        out, kv, _ = run.prefill(
            p, tokens=jnp.asarray(t), seq_lens=jnp.asarray([n]), cache=kv,
            page_table=jnp.asarray(table[b][None], jnp.int32),
            moe_stats=True, slot_ids=jnp.asarray([b]))
        assert np.abs(_lp(out[0]) - want[b][n - 1]).max() < TOL
    pt = np.zeros((B, P), np.int32)
    for b in lens:
        pt[b] = table[b]
    for step in range(3 * W):
        live = [3, 1] if step < 12 else [1]
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
        # the tape's last columns: what each window layer's softmax saw
        # and what the contexts hold; the rings read are the live rows'
        # (the first window layer's row); no prefill keys in a step
        tape = np.asarray(tape)
        seen = sum(min(lens[b] + step + 1, W) for b in live)
        held = sum(lens[b] + step + 1 for b in live)
        kinds = np.asarray([k == "window" for k in cfg.layer_kinds])
        assert tape[kinds, -5].tolist() == [seen] * 5
        assert tape[kinds, -4].tolist() == [held] * 5
        assert tape[1, -3:-1].tolist() == [len(live)] * 2
        assert not tape[2:, -3:-1].any() and not tape[:, -1].any()
        assert not tape[~kinds, -5:].any()


def test_a_slot_reused_by_a_shorter_sequence(model):
    """A sequence of 60 fills slot 1's ring; a sequence of 3 then takes
    the slot (chunked, from a prefix of 0) and decodes 6 tokens, five
    of them while its ring still holds the predecessor's keys in the
    rows its own tokens have not reached. Validity comes from the
    length alone, so it reads none of them."""
    cfg, p, toks, _ = model
    _, cache, *_ = _chunked(p, cfg, toks[:60], 32)
    assert np.asarray(cache.slots["swa_ring"])[:, 1].all(axis=-1).all()
    seq = _tokens(cfg, 9, seed=31)
    want = _lp(ref_logits(p, cfg, seq))
    outs, cache, pt, _ = _chunked(p, cfg, seq[:3], 32, cache=cache,
                                  table=np.arange(9, 17))
    assert np.abs(outs[0][1] - want[2]).max() < TOL
    table = np.zeros((2, 8), np.int32)
    table[1] = np.asarray(pt[0])
    for step in range(6):
        out, cache, _ = programs(cfg, PS).decode_step(
            p, tokens=jnp.asarray([0, seq[3 + step]], jnp.int32),
            positions=jnp.asarray([0, 3 + step], jnp.int32), cache=cache,
            page_table=jnp.asarray(table),
            active=jnp.asarray([False, True]), moe_stats=True)
        assert np.abs(_lp(out[1]) - want[3 + step]).max() < TOL, step


def test_hidden_states_is_the_reference_mean(model):
    cfg, p, toks, _ = model
    t = np.zeros((1, 64), np.int32)
    t[0, :50] = toks[:50]
    got = programs(cfg).hidden_states(
        p, tokens=jnp.asarray(t), seq_lens=jnp.asarray([50]))
    c = ref_cfg(cfg)
    with ref.computed_in(jnp.float32):
        x = p["embed"][jnp.asarray(toks[:50])]
        for i in range(cfg.num_hidden_layers):
            x = ref.layer(p, i, c, x)
        x = ref.rms_norm(x, p["norm_f"], cfg.rms_norm_eps)
    assert np.abs(np.asarray(got[0]) - np.asarray(x.mean(0))).max() < TOL


# -- the window's edge --------------------------------------------------------
def _qkv(cfg, S, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    Hw = cfg.swa_num_key_value_heads
    return (jax.random.normal(ks[0], (1, S, cfg.n_heads, cfg.head_dim)),
            jax.random.normal(ks[1], (1, S, Hw, cfg.head_dim)),
            jax.random.normal(ks[2], (1, S, Hw, cfg.v_head_dim)))


def _window_out(cfg, q, k, v, sink):
    S = q.shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    return np.asarray(mimo_v2._attend_window(
        q, k, v, None, jnp.zeros((1,), jnp.int32), pos,
        jnp.ones((1, S), bool), sink, cfg))[0]


@pytest.mark.parametrize("which", ["program", "reference"])
def test_the_key_at_t_minus_window_is_out_and_the_next_is_in(which):
    """Move ONE key and watch the last query's output: the key at ``t -
    window`` changes nothing (bit for bit), the key at ``t - window +
    1`` — the oldest of the ``window`` keys, the query's own among them
    — does."""
    cfg = mimo_v2.TINY
    S = 20
    t = S - 1
    q, k, v = _qkv(cfg, S)
    sink = jnp.zeros((cfg.n_heads,))

    def out(k_):
        if which == "program":
            return _window_out(cfg, q, k_, v, sink)[t]
        with ref.computed_in(jnp.float32):
            return np.asarray(ref.attend(q[0], k_[0], v[0], ref_cfg(cfg),
                                         window=W, sink=sink))[t]

    base = out(k)
    assert np.array_equal(out(k.at[0, t - W].add(3.0)), base)
    assert np.abs(out(k.at[0, t - W + 1].add(3.0)) - base).max() > 1e-3
    # and a global layer's query sees the key a window layer's does not
    with ref.computed_in(jnp.float32):
        wide = [np.asarray(ref.attend(q[0], k_[0], v[0], ref_cfg(cfg)))[t]
                for k_ in (k, k.at[0, t - W].add(3.0))]
    assert np.abs(wide[0] - wide[1]).max() > 1e-3


def test_the_rings_edge_in_a_decode_step():
    """The same at a decode step over the ring: position 19's query
    reads ring rows 12..19 (mod 8); the key at position 11 was
    overwritten by position 19's own, and what a predecessor left in a
    row this sequence has not reached (position 3's query: rows 4..7)
    changes nothing."""
    cfg = mimo_v2.TINY
    Hw, dv = cfg.swa_num_key_value_heads, cfg.v_head_dim
    q, k, v = _qkv(cfg, 20)
    rows = mimo_v2._row(k, v)[0]  # [20, W]
    sink = jnp.zeros((cfg.n_heads,))
    want = _window_out(cfg, q, k, v, sink)

    def step(t, junk):
        ring = np.full((1, 2, W, rows.shape[-1]), junk, np.float32)
        for s in range(max(0, t - W + 1), t):
            ring[0, 1, s % W] = np.asarray(rows[s])
        o, pool = mimo_v2._ring_live_rows(
            mimo_v2._at_own_head(jnp.stack([q[0, t], q[0, t]]), Hw),
            jnp.stack([rows[t], rows[t]]), sink, jnp.asarray(ring),
            jnp.asarray(0, jnp.int32), jnp.asarray([1, 0], jnp.int32),
            jnp.asarray(1, jnp.int32), jnp.asarray([0, t], jnp.int32),
            n_values=Hw * dv, scale=cfg.softmax_scale)
        assert np.array_equal(np.asarray(pool)[0, 1, t % W],
                              np.asarray(rows[t]))
        assert np.array_equal(np.asarray(pool)[0, 0], ring[0, 0])
        o = np.asarray(mimo_v2._own_values(o, Hw))
        assert not o[0].any()  # the row that is not live
        return o[1]

    for t in (19, 3):
        a, b = step(t, 0.0), step(t, 50.0)
        assert np.array_equal(a, b), t
        assert np.abs(a - want[t]).max() < 1e-5, t


# -- the sink -----------------------------------------------------------------
def test_the_sink_against_a_float64_softmax_by_hand():
    """A window layer's weights are ``softmax([scores, b_h])`` with the
    last column dropped: they sum to LESS than one. Values that are
    rows of the identity read the weights out; the same by hand in
    float64. 1e-6: float32 rounding of a softmax over nine columns."""
    cfg = mimo_v2.TINY
    S, H, Hw = 8, cfg.n_heads, cfg.swa_num_key_value_heads
    q, k, _ = _qkv(cfg, S, seed=11)
    v = jnp.broadcast_to(jnp.eye(S, cfg.v_head_dim)[None, :, None, :],
                         (1, S, Hw, cfg.v_head_dim))
    sink = jnp.asarray(np.random.default_rng(2).normal(size=H) * 2.0,
                       jnp.float32)
    got = _window_out(cfg, q, k, v, sink)[:, :, :S]  # [t, h, s]
    with ref.computed_in(jnp.float32):
        mine = np.asarray(ref.attend(q[0], k[0], v[0], ref_cfg(cfg),
                                     window=W, sink=sink))[:, :, :S]
    q64, k64 = np.asarray(q[0], np.float64), np.asarray(k[0], np.float64)
    for h in range(H):
        for t in range(S):
            scores = [q64[t, h] @ k64[s, h // (H // Hw)]
                      / math.sqrt(cfg.head_dim) for s in range(t + 1)]
            e = np.exp(np.asarray(scores + [float(sink[h])]))
            w = (e / e.sum())[:-1]
            assert w.sum() < 1.0
            assert np.abs(got[t, h, :t + 1] - w).max() < 1e-6
            assert np.abs(mine[t, h, :t + 1] - w).max() < 1e-6
            assert not got[t, h, t + 1:].any()
    # weights that sum to less than one, and by as much as the sink takes
    total = got.sum(-1)
    assert (total < 1.0).all() and total.min() < 0.8
    # a global layer's weights sum to one
    with ref.computed_in(jnp.float32):
        full = np.asarray(ref.attend(q[0], k[0], v[0], ref_cfg(cfg)))
    assert np.abs(full[:, :, :S].sum(-1) - 1.0).max() < 1e-5


# -- the global layers' page row ------------------------------------------------
def test_the_walk_with_two_widths_is_the_reference():
    """One global layer's attention on the same rotated rows: the
    program's chunk path (a ``v | k`` column cut by key head, blocks,
    online softmax) and its decode walk over pages (a score product
    over a column's 24 key rows, a value product over its 16 value
    rows), against the reference's per-head keys and values. 1e-5:
    float32 rounding of sums up to 60 long in another order."""
    cfg, i = mimo_v2.TINY, 5
    p = make_params(cfg)
    S = 60
    x = jax.random.normal(jax.random.PRNGKey(3), (S, cfg.hidden_size))
    c = ref_cfg(cfg)
    with ref.computed_in(jnp.float32):
        q, k, v = ref.project(p, i, c, x)
        want = np.asarray(ref.attend(q, k, v, c))
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    qm, km, vm = mimo_v2._project(p, i, x[None], cfg, "global")
    qm, km = mimo_v2._rotate(qm, km, pos, cfg, "global")
    assert np.abs(np.asarray(qm[0]) - np.asarray(q)).max() < 1e-5
    rows = mimo_v2._row(km, vm)[0]  # [S, 40]: v 16 | k 24
    assert rows.shape == (S, 40)
    assert np.abs(np.asarray(rows[:, :16]) - np.asarray(v[:, 0])).max() \
        < 1e-5
    assert np.abs(np.asarray(rows[:, 16:]) - np.asarray(k[:, 0])).max() \
        < 1e-5
    o = mimo_v2._attend_pages(qm, lambda j: rows.T[None], 1, S, pos,
                              jnp.ones((1, S), bool), cfg)
    assert np.abs(np.asarray(o[0]) - want).max() < 1e-5
    # the decode walk: the last query over the rows laid out in pages
    # 3, 1, 4, 2 of layer 1 of a pool
    table = np.asarray([[3, 1, 4, 2]], np.int32)
    pool = np.zeros((2, 40, 6 * PS), np.float32)
    for t in range(S):
        pool[1, :, table[0, t // PS] * PS + t % PS] = np.asarray(rows[t])
    o1 = paged_walk.latent_decode_walk(
        mimo_v2._at_own_head(qm[:, -1], 1), jnp.asarray(pool), 1,
        jnp.asarray(table), jnp.asarray([S], jnp.int32), page_size=PS,
        rank=16, scale=cfg.softmax_scale, keys_from=16)
    assert o1.shape == (1, cfg.n_heads, 16)
    assert np.abs(np.asarray(mimo_v2._own_values(o1, 1)[0])
                  - want[-1]).max() < 1e-5


def test_heads_find_their_own_key_head():
    """Four query heads over two key heads: head ``h``'s query lies at
    key head ``h // 2``'s place in the flattened row and its output is
    that head's values."""
    q = jnp.arange(1, 1 + 4 * 3, dtype=jnp.float32).reshape(1, 4, 3)
    at = np.asarray(mimo_v2._at_own_head(q, 2))[0]
    assert at.shape == (4, 6)
    assert np.array_equal(at[0], [1, 2, 3, 0, 0, 0])
    assert np.array_equal(at[3], [0, 0, 0, 10, 11, 12])
    o = jnp.arange(4 * 4, dtype=jnp.float32).reshape(1, 4, 4)
    own = np.asarray(mimo_v2._own_values(o, 2))[0]
    assert np.array_equal(own, [[0, 1], [4, 5], [10, 11], [14, 15]])


def test_decode_walk_reads_live_rows_only():
    """Rows that are not live are not walked: whatever their page-table
    rows name, the live row's output is the same bit for bit."""
    cfg = mimo_v2.TINY
    p = make_params(cfg)
    toks = _tokens(cfg, 40, seed=9)
    _, cache, pt, _ = _chunked(p, cfg, toks, 64)
    B, P = 4, 8
    q = jax.random.normal(jax.random.PRNGKey(1), (B, cfg.n_heads, 24))
    lengths = jnp.asarray([0, 40, 0, 0], jnp.int32)
    outs = []
    for junk in (0, 31):
        table = np.full((B, P), junk, np.int32)
        table[1] = np.asarray(pt[0])
        outs.append(np.asarray(paged_walk.latent_decode_walk(
            mimo_v2._at_own_head(q, 1), cache.kv, 1, jnp.asarray(table),
            lengths, page_size=PS, rank=16, scale=cfg.softmax_scale,
            plan=kvq.walk_plan(cache.kv, lengths, jnp.asarray(table), PS),
            keys_from=16)))
    assert np.array_equal(outs[0], outs[1])
    assert not outs[0][[0, 2, 3]].any() and outs[0][1].any()


# -- the router -----------------------------------------------------------------
def _pick_by_hand(s, b, k):
    """The selection as a loop in Python over one token's scores."""
    order = sorted(range(len(s)), reverse=True,
                   key=lambda e: (s[e] + b[e], -e))[:k]
    total = sum(s[e] for e in order)
    return order, [s[e] / total for e in order]


def test_the_bias_picks_and_the_score_weighs():
    """Scores by hand: expert 5's score is the ninth, and its bias lifts
    it over expert 2's, which had been among the four picks. The picks
    change; the weights are the UNBIASED scores' over their sum — the
    lifted expert's weight is the smallest, not the largest."""
    cfg = dataclasses.replace(mimo_v2.TINY, num_experts=16)
    s = np.asarray([[.10, .20, .60, .30, .15, .55, .90, .80, .25, .70,
                     .05, .35, .40, .45, .12, .18]], np.float32)
    zero = np.zeros(16, np.float32)
    w0, e0 = mimo_v2.pick(jnp.asarray(s), jnp.asarray(zero), cfg)
    assert sorted(np.asarray(e0)[0].tolist()) == [2, 6, 7, 9]
    b = zero.copy()
    b[5] = 0.2  # .55 + .2 = .75 > .70 > .60: expert 5 is now third
    w1, e1 = mimo_v2.pick(jnp.asarray(s), jnp.asarray(b), cfg)
    assert np.asarray(e1)[0].tolist() == [6, 7, 5, 9]
    want = np.asarray([.90, .80, .55, .70]) / (.90 + .80 + .55 + .70)
    assert np.abs(np.asarray(w1)[0] - want).max() < 1e-6
    assert np.asarray(w1)[0].argmin() == 2
    ids, weights = _pick_by_hand(s[0].tolist(), b.tolist(), 4)
    assert ids == [6, 7, 5, 9]
    assert np.abs(np.asarray(weights) - want).max() < 1e-6
    # routed_scaling_factor null is 1; a number multiplies
    w2, _ = mimo_v2.pick(jnp.asarray(s), jnp.asarray(b), dataclasses.replace(
        cfg, routed_scaling_factor=2.5))
    assert np.abs(np.asarray(w2) - 2.5 * np.asarray(w1)).max() < 1e-6


def test_router_is_the_references_and_the_loops():
    """Program, reference and a loop in Python on the layer's own
    router, 200 tokens: the same picks, the same weights (1e-6: the one
    division's rounding) — and the seeded bias DOES move picks."""
    cfg, i = SHARE, 2
    p = make_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (200, cfg.hidden_size))
    topv, topi = mimo_v2.route(p, i, x, cfg)
    with ref.computed_in(jnp.float32):
        rv, ri = ref.route(p, i, ref_cfg(cfg), x)
        s = np.asarray(jax.nn.sigmoid(x @ p[f"l{i}.router"]))
    assert np.array_equal(np.asarray(topi), np.asarray(ri))
    assert np.abs(np.asarray(topv) - np.asarray(rv)).max() < 1e-6
    b = np.asarray(p[f"l{i}.router_bias"])
    moved = 0
    for t in range(200):
        ids, weights = _pick_by_hand(s[t].tolist(), b.tolist(), 4)
        assert ids == np.asarray(topi)[t].tolist(), t
        assert np.abs(np.asarray(weights) - np.asarray(topv)[t]).max() \
            < 1e-6
        plain, _ = _pick_by_hand(s[t].tolist(), [0.0] * len(b), 4)
        moved += set(plain) != set(ids)
    assert moved > 20


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """An expert layer cut sixteen ways: each share routes over the
    whole width of 32 and computes its own two experts' part; the
    sixteen parts add up to the uncut reference's layer output (there
    is no shared expert to count once). 1e-5: float32 sums of sixteen
    parts in another order."""
    whole = dataclasses.replace(mimo_v2.TINY, num_experts=32)
    p = make_params(whole)
    i, D, F = 3, whole.hidden_size, whole.moe_intermediate_size
    x = jax.random.normal(jax.random.PRNGKey(6), (40, D))
    with ref.computed_in(jnp.float32):
        want = np.asarray(ref.moe_layer(p, i, ref_cfg(whole), x))
        parts = sum(np.asarray(ref.moe_layer(
            p, i, ref_cfg(whole), x, held_from=2 * s, num_experts=2))
            for s in range(16))
    assert np.abs(parts - want).max() < 1e-5
    total = np.zeros_like(want)
    unserved = 0
    for s in range(16):
        share = dataclasses.replace(whole, num_experts=2, router_experts=32,
                                    held_from=2 * s)
        ps = dict(p)
        for m in ("gate", "up"):
            ps[f"l{i}.experts_{m}"] = p[f"l{i}.experts_{m}"].reshape(
                D, 32, F)[:, 2 * s:2 * s + 2].reshape(D, 2 * F)
        ps[f"l{i}.experts_down"] = p[f"l{i}.experts_down"].reshape(
            32, F, D)[2 * s:2 * s + 2].reshape(2 * F, D)
        tape = []
        got = np.asarray(mimo_v2.moe(ps, i, x[None], share, tape=tape))[0]
        total += got
        n_unserved = int(tape[0][-1])
        unserved += n_unserved
        # a token none of whose picks is held here gets nothing here
        assert int((np.abs(got).max(-1) == 0).sum()) == n_unserved
        assert int(tape[0][:2].sum()) + 0 <= 40 * 4
    assert np.abs(total - want).max() < 1e-5
    # each token is unserved by exactly the shares none of its 4 picks
    # falls in: 16 less the shares its picks touch (at most 4)
    assert 40 * 12 <= unserved <= 40 * 15


def test_tape_counts_real_tokens_only():
    cfg = SHARE
    p = make_params(cfg)
    toks = _tokens(cfg, 50, seed=5)
    outs, _, _, tapes = _chunked(p, cfg, toks, 64)
    tape = tapes[0]
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    assert tape.shape == (7, E + 3 + 6)
    assert not tape[0, :E + 4].any()  # the dense layer routes nothing
    assert (tape[1:, E + 1] == 50 * K).all()  # routed: real tokens only
    assert (tape[1:, :E].sum(1) <= 50 * K).all()
    assert (tape[1:, E + 3] > 0).all() and (tape[1:, E + 3] < 50).all()
    kinds = np.asarray([k == "global" for k in cfg.layer_kinds])
    assert tape[kinds, -1].tolist() == [50 * 51 // 2] * 2
    assert not tape[~kinds, -1].any() and not tape[:, -5:-1].any()


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


def test_window_layers_left_global_fail_the_tolerance(honest):
    cfg, p, toks, want = honest
    assert _worst(dataclasses.replace(cfg, sliding_window=128), p, toks,
                  want) > 10 * TOL
    # and the reference's own control reads the same way
    wide = _lp(ref_logits(p, cfg, toks, windowed=False))
    assert np.abs(wide - want).max() > 10 * TOL


def test_a_window_one_key_too_long_fails_the_tolerance(honest):
    """``sliding_window`` read as NOT counting the query's own
    position: nine keys instead of eight."""
    cfg, p, toks, want = honest
    assert _worst(dataclasses.replace(cfg, sliding_window=W + 1), p, toks,
                  want) > 10 * TOL


def test_a_dropped_sink_fails_the_tolerance(honest):
    cfg, p, toks, want = honest
    dry = {k: (jnp.full_like(v, -1e9) if k.endswith(".sink") else v)
           for k, v in p.items()}
    assert _worst(cfg, dry, toks, want) > 10 * TOL
    none = _lp(ref_logits(p, cfg, toks, sinks=False))
    assert np.abs(none - want).max() > 10 * TOL


def test_a_dropped_value_scale_fails_the_tolerance(honest):
    cfg, p, toks, want = honest
    assert _worst(dataclasses.replace(cfg, attention_value_scale=1.0), p,
                  toks, want) > 10 * TOL


def test_one_theta_for_both_kinds_fails_the_tolerance(honest):
    cfg, p, toks, want = honest
    assert _worst(dataclasses.replace(cfg, swa_rope_theta=cfg.rope_theta),
                  p, toks, want) > 10 * TOL


def _mutated(monkeypatch, target, name, fn, cfg, p, toks, want):
    monkeypatch.setattr(target, name, fn)
    programs.cache_clear()  # trace the mutant, and forget it after
    try:
        return _worst(cfg, p, toks, want)
    finally:
        programs.cache_clear()


def test_a_bias_that_weighs_fails_the_tolerance(honest, monkeypatch):
    """The picks' weights taken from ``s + b`` instead of ``s``: the
    same experts, another mixture."""
    cfg, p, toks, want = honest

    def weighs(s, bias, cfg_):
        topv, topi = jax.lax.top_k(s + bias, cfg_.num_experts_per_tok)
        return topv / jnp.sum(topv, axis=-1, keepdims=True), topi

    assert _mutated(monkeypatch, mimo_v2, "pick", weighs, cfg, p, toks,
                    want) > 10 * TOL


def test_a_bias_left_out_fails_the_tolerance(honest):
    cfg, p, toks, want = honest
    flat = {k: (jnp.zeros_like(v) if k.endswith(".router_bias") else v)
            for k, v in p.items()}
    assert _worst(cfg, flat, toks, want) > 10 * TOL


def test_bfloat16_where_float32_is_stated_fails_the_tolerance(honest):
    """The reference computed in bfloat16 end to end — the nearest
    precision under the one these tests state — is hundreds of
    tolerances away."""
    cfg, p, toks, want = honest
    low = _lp(ref.forward(p, ref_cfg(cfg), jnp.asarray(toks),
                          dtype=jnp.bfloat16).astype(jnp.float32))
    assert np.abs(low - want).max() > 30 * TOL
