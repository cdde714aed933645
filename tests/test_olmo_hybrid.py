"""Olmo-Hybrid (models/olmo_hybrid.py) against its float32 reference
(models/reference/olmo_hybrid_ref.py), at a tiny size on the CPU, in
float32 — LOGITS, never tokens. Two periods, key and value head widths
24 / 48 (they differ, and neither is a multiple of the other's tile).

The tolerance. Program and reference compute the same float32
mathematics in another order (the chunked WY form against the token
recurrence, a page-window gather and the page walk against a full score
matrix): what separates them is float32 rounding through eight layers
whose every sub-layer output is normalised, observed at 1e-5 to 3e-5 on
logits of magnitude ~4. ``TOL`` leaves that ten times of room and is
still far under what the cheapest wrong program gives — the mutation
tests at the bottom prove that a bfloat16 state and a ``beta`` left at
``sigmoid`` each fail it by more than three times.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import olmo_hybrid as oh
from aigw_tpu.models import qwen3_next as qn
from aigw_tpu.models.cache import StateCache
from aigw_tpu.models.reference import olmo_hybrid_ref as ref
from aigw_tpu.models.registry import family_fns, get_model_spec
from olmo_hybrid_util import (CFG, make_cache, make_params, programs,
                              ref_cfg, ref_logits)

TOL = 3e-4
PS = 16  # page size
#: twenty key heads: a page row stores 32 (``kv_heads_stored``), as the
#: published 30 do
WIDE = dataclasses.replace(CFG, hidden_size=80, num_attention_heads=20,
                           num_key_value_heads=20, num_hidden_layers=4,
                           layer_types=())


@pytest.fixture(scope="module", params=["tiny", "twenty_heads"])
def model(request):
    cfg = {"tiny": CFG, "twenty_heads": WIDE}[request.param]
    return cfg, make_params(cfg)


def _tokens(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n)


def _page_table(rows: list[list[int]], width: int = 16):
    pt = np.zeros((len(rows), width), np.int32)
    for r, pages in enumerate(rows):
        pt[r, :len(pages)] = pages
    return jnp.asarray(pt)


def _err(got, want) -> float:
    return float(np.abs(np.asarray(got) - np.asarray(want)).max())


def test_layer_pattern_and_cache_spec():
    cfg = CFG
    assert cfg.layer_kinds == ("linear", "linear", "linear", "full") * 2
    assert (cfg.n_linear_layers, cfg.n_full_layers) == (6, 2)
    assert cfg.head_dim == 16 and cfg.conv_dim == 2 * 48 + 96
    spec = cfg.cache_spec()
    assert spec.stateful and spec.snapshots and not spec.latent
    assert spec.kv_layers == 2 and spec.n_kv_heads == 4
    assert spec.slot_state == (
        ("gdn_state", 6, (2, 24, 48), "float32"),
        ("gdn_conv", 6, (3, 192), "activation"))
    # THE rule that sizes the snapshot pool: three rows a slot
    assert spec.snapshot_rows(16) == 48
    snaps = spec.make_snapshots(4, "float32")
    assert {k: v.shape for k, v in snaps.items()} == {
        "gdn_state": (6, 12, 2, 24, 48), "gdn_conv": (6, 12, 3, 192)}
    # the published size: 30 key heads are stored as 32, fewer than a
    # tile's 16 as they are
    big = oh.OlmoHybridConfig(num_hidden_layers=12)
    assert big.cache_spec().n_kv_heads == big.kv_heads_stored == 32
    assert big.layer_kinds.count("full") == 3
    assert big.cache_spec().kv_page_bytes(128, "bfloat16") \
        == 3 * 2 * 32 * 128 * 128 * 2
    assert big.cache_spec().state_bytes_per_slot("bfloat16") \
        == 9 * (30 * 96 * 192 * 4 + 3 * 11520 * 2) == 20_528_640
    with pytest.raises(ValueError, match="layer_types"):
        oh.OlmoHybridConfig(num_hidden_layers=3,
                            layer_types=["full_attention"])
    listed = oh.OlmoHybridConfig(
        num_hidden_layers=2,
        layer_types=["full_attention", "linear_attention"])
    assert listed.layer_kinds == ("full", "linear")
    hash(listed)  # a list from a configuration file became a tuple


def test_registered_preset_and_family_surface():
    spec = get_model_spec("tiny-olmo-hybrid")
    assert spec.family == "olmo_hybrid" and spec.config is oh.TINY
    fns = family_fns("olmo_hybrid")
    assert not fns.moe_stats and fns.state_reads is oh.state_reads
    assert fns.prefill_suffix is not None
    assert (fns.verify_step, fns.prefill_sp, fns.prefill_sp_suffix,
            fns.prefill_ragged) == (None,) * 4


def test_one_shot_prefill_matches_reference(model):
    """Two rows of different lengths in one [2, 64] call, into slots 1
    and 0: every row's last-position logits."""
    cfg, p = model
    a, b = _tokens(cfg, 50, 1), _tokens(cfg, 23, 2)
    tokens = np.zeros((2, 64), np.int32)
    tokens[0, :50], tokens[1, :23] = a, b
    got, cache = programs(cfg, PS).prefill(
        p, tokens=jnp.asarray(tokens), seq_lens=jnp.asarray([50, 23]),
        cache=make_cache(cfg, 16, PS), slot_ids=jnp.asarray([1, 0]),
        page_table=_page_table([[1, 2, 3, 4], [5, 6]]))
    assert isinstance(cache, StateCache)
    assert _err(got[0], ref_logits(p, cfg, a)[-1]) < TOL
    assert _err(got[1], ref_logits(p, cfg, b)[-1]) < TOL


def _prefill_then_decode(cfg, p, between=lambda c: c, n=140, steps=10):
    """A 140-token prompt as two chunks of 64 and a padded tail of 12,
    then ``steps`` decode steps in slot 1 of 2 beside an idle row —
    through pages and state; ``between`` may tamper with the cache after
    every program. Returns (worst |logit error| against the reference's
    full forward pass, cache)."""
    toks = _tokens(cfg, n + steps, seed=3)
    want = ref_logits(p, cfg, toks)
    prog = programs(cfg, PS)
    pt = _page_table([list(range(1, 13))])
    sid = jnp.asarray([1])
    cache = make_cache(cfg, 16, PS)
    logits, cache = prog.prefill(
        p, tokens=jnp.asarray(toks[None, :64]), seq_lens=jnp.asarray([64]),
        cache=cache, page_table=pt, slot_ids=sid)
    worst = _err(logits[0], want[63])
    cache = between(cache)
    logits, cache = prog.prefill_suffix(
        p, tokens=jnp.asarray(toks[None, 64:128]),
        prefix_lens=jnp.asarray([64]), seq_lens=jnp.asarray([128]),
        cache=cache, page_table=pt, slot_ids=sid)
    worst = max(worst, _err(logits[0], want[127]))
    cache = between(cache)
    tail = np.zeros((1, 32), np.int32)
    tail[0, :n - 128] = toks[128:n]
    logits, cache = prog.prefill_suffix(
        p, tokens=jnp.asarray(tail), prefix_lens=jnp.asarray([128]),
        seq_lens=jnp.asarray([n]), cache=cache, page_table=pt,
        slot_ids=sid)
    worst = max(worst, _err(logits[0], want[n - 1]))
    pt2 = _page_table([[0] * 12, list(range(1, 13))])
    active = jnp.asarray([False, True])
    for t in range(n, n + steps):
        cache = between(cache)
        logits, cache = prog.decode_step(
            p, tokens=jnp.asarray([0, toks[t]]),
            positions=jnp.asarray([0, t]), cache=cache, page_table=pt2,
            active=active)
        worst = max(worst, _err(logits[1], want[t]))
    return worst, cache


def test_prefill_then_decode_matches_reference(model):
    cfg, p = model
    worst, cache = _prefill_then_decode(cfg, p)
    assert worst < TOL
    # the idle row's state, tail and pages were never written
    assert float(jnp.abs(cache.slots["gdn_state"][:, 0]).max()) == 0.0
    assert float(jnp.abs(cache.slots["gdn_conv"][:, 0]).max()) == 0.0
    assert float(jnp.abs(cache.kv[:, :, :PS]).max()) == 0.0


def test_stored_key_heads_beyond_the_real_ones_stay_zero():
    cfg, p = WIDE, make_params(WIDE)
    assert cfg.kv_heads_stored == 32
    _, cache = _prefill_then_decode(cfg, p, steps=3)
    assert cache.kv.shape[-2:] == (32, 4)
    assert float(jnp.abs(cache.kv[..., 20:, :]).max()) == 0.0
    assert float(jnp.abs(cache.kv[..., :20, :]).max()) > 0.0


def test_hidden_states_pools_real_tokens_only(model):
    cfg, p = model
    toks = _tokens(cfg, 40, seed=5)
    tokens = np.zeros((1, 64), np.int32)
    tokens[0, :40] = toks
    got = programs(cfg, PS).hidden_states(
        p, tokens=jnp.asarray(tokens), seq_lens=jnp.asarray([40]))
    other = tokens.copy()
    other[0, 40:] = 7  # what the padding holds changes nothing
    again = programs(cfg, PS).hidden_states(
        p, tokens=jnp.asarray(other), seq_lens=jnp.asarray([40]))
    assert got.shape == (1, cfg.hidden_size)
    assert np.isfinite(np.asarray(got)).all()
    assert _err(got, again) == 0.0


def _gdn_inputs(rng, B, S, H, dk, dv, beta_max):
    q, k = (rng.normal(size=(B, S, H, dk)).astype(np.float32)
            for _ in range(2))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(B, S, H, dv)).astype(np.float32)
    g = -rng.uniform(0.0, 0.3, (B, S, H)).astype(np.float32)
    beta = rng.uniform(0.0, beta_max, (B, S, H)).astype(np.float32)
    return q, k, v, g, beta


def test_chunked_form_agrees_with_the_recurrence_with_beta_over_one():
    """The WY form over 3 blocks (one padded) against the token rule at
    this family's widths (keys 24 under values 48) and its ``beta`` —
    up to 2, half of them over 1, where ``I − β k kᵀ`` reflects — from
    a non-zero state. With ``β`` over 1 the state's entries grow to
    order 10 here, so float32 rounding shows at 1e-4 where it showed at
    1e-5 under ``β < 1``; padding rows (``beta == g == 0``) neither
    decay nor write: row 1 is real for 70 tokens, and its state after
    150 is the recurrence's after 70."""
    rng = np.random.default_rng(0)
    B, S, H, dk, dv = 2, 150, 3, 24, 48
    q, k, v, g, beta = _gdn_inputs(rng, B, S, H, dk, dv, 2.0)
    assert (beta > 1).mean() > 0.4
    live = (np.arange(S)[None, :] < np.array([S, 70])[:, None])[..., None]
    g, beta = g * live, beta * live
    s0 = rng.normal(size=(B, H, dk, dv)).astype(np.float32)
    out, state = jax.jit(qn._gdn_chunk)(
        *map(jnp.asarray, (q, k, v, g, beta, s0)))
    st = jnp.asarray(s0)
    step = jax.jit(qn._gdn_recurrent)
    at70 = None
    for t in range(S):
        o, st = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], st)
        assert _err(out[0, t], o[0]) < 5e-4
        if t < 70:
            assert _err(out[1, t], o[1]) < 5e-4
        if t == 69:
            at70 = st[1]
    assert _err(state, st) < 5e-4
    # the padded row: its state is what its 70 real tokens left
    assert _err(state[1], at70) < 5e-4
    assert _err(st[1], at70) == 0.0


def test_padding_rows_of_a_chunk_neither_decay_nor_write():
    """A chunk whose tail is padding leaves the slot's state and
    convolution tail exactly where the real tokens left them: the same
    tokens as a padded [1, 64] call and as an exact [1, 40] call."""
    cfg, p = CFG, make_params(CFG)
    toks = _tokens(cfg, 40, seed=9)
    prog = programs(cfg, PS)
    pt = _page_table([[1, 2, 3, 4]])
    padded = np.full((1, 64), 5, np.int32)
    padded[0, :40] = toks
    caches = []
    for tokens in (padded, toks[None]):
        _, cache = prog.prefill(
            p, tokens=jnp.asarray(tokens), seq_lens=jnp.asarray([40]),
            cache=make_cache(cfg, 8, PS), page_table=pt,
            slot_ids=jnp.asarray([0]))
        caches.append(cache)
    for name in ("gdn_state", "gdn_conv"):
        # (float32 rounding of a [64, D] against a [40, D] product, as
        # a share of the state's largest entry; a padding row that
        # decayed the state once would show at 5e-2)
        scale = float(jnp.abs(caches[1].slots[name]).max())
        assert _err(caches[0].slots[name],
                    caches[1].slots[name]) < 1e-4 * scale
        assert float(jnp.abs(caches[0].slots[name][:, 1]).max()) == 0.0


def test_state_reads_count_the_live_rows_loop():
    cfg = CFG
    cache = make_cache(cfg, 8, PS, n_slots=4)
    active = jnp.asarray([True, False, True, True])
    read, live = (int(x) for x in oh.state_reads(cache, active))
    # 4 rows of 2*24*48*4 B fit one trip: the loop reads 4 slots' rows
    assert (read, live) == (4, 3)
    big = oh.OlmoHybridConfig(num_hidden_layers=4).cache_spec()
    pool = jax.eval_shape(lambda: big.make(256, 16, "bfloat16"))
    Rs, _, _ = oh._live_trips(pool.slots["gdn_state"],
                              jnp.ones((16,), bool))
    assert Rs == 1  # a published row is 2.2 MB: one a trip


# -- what the tolerance tells apart ------------------------------------------
def _bf16_state(cache):
    slots = dict(cache.slots)
    slots["gdn_state"] = slots["gdn_state"].astype(jnp.bfloat16).astype(
        jnp.float32)
    return StateCache(cache.kv, slots)


def test_a_bfloat16_state_fails_the_tolerance():
    worst, _ = _prefill_then_decode(CFG, make_params(CFG),
                                    between=_bf16_state)
    assert worst > 3 * TOL


def test_beta_left_at_sigmoid_fails_the_tolerance():
    cfg, p = CFG, make_params(CFG)
    wrong = dataclasses.replace(cfg, linear_allow_neg_eigval=False)
    toks = _tokens(cfg, 60)
    got, _ = programs(wrong, PS).prefill(
        p, tokens=jnp.asarray(toks[None]), seq_lens=jnp.asarray([60]),
        cache=make_cache(cfg, 8, PS, 1), page_table=_page_table([[1, 2, 3, 4]]))
    assert _err(got[0], ref_logits(p, cfg, toks)[-1]) > 3 * TOL
    # and the reference's own reading of that wrong model says the same
    assert _err(ref_logits(p, cfg, toks, wrong="beta_sigmoid")[-1],
                ref_logits(p, cfg, toks)[-1]) > 3 * TOL


def test_reference_in_blocks_is_the_reference():
    """``forward(block=...)`` carries each layer's state, convolution
    tail, keys and values from block to block: the same mathematics
    (float32 rounding in another order), which is what lets the chip's
    comparison run a 7k-token history."""
    cfg, p = CFG, make_params(CFG)
    toks = _tokens(cfg, 150, seed=4)
    # (from the ninth token on: a DeltaNet head's output at a
    # sequence's very first tokens is ``beta v (k.q)`` over an almost
    # empty state, and the head norm divides float32 rounding of a
    # near-zero ``k.q`` up to 7e-4 on the logits of token 0, whatever
    # the blocks)
    assert _err(ref_logits(p, cfg, toks, block=40)[8:],
                ref_logits(p, cfg, toks)[8:]) < TOL
    assert set(ref_cfg(cfg)) >= {"layer_types", "linear_allow_neg_eigval"}
    assert ref.forward.__doc__ and "READINGS" in ref.__doc__
