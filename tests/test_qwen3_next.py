"""Qwen3-Next (models/qwen3_next.py) against its float32 reference
(models/reference/qwen3_next_ref.py), at a tiny size on the CPU, in
float32 — LOGITS, never tokens.

The tolerance. Program and reference compute the same float32
mathematics in another order (the chunked WY form against the token
recurrence, a window gather against a full score matrix, dense experts
times combine weights against one expert at a time): what separates
them is float32 rounding through eight layers, observed at 1e-5 to 5e-5
on logits of magnitude ~3. ``TOL`` leaves that ten times of room and is
still fifty times under what the cheapest wrong program gives — the
mutation tests at the bottom prove that a bfloat16 state, a dropped
expert assignment, rotary on every dimension and a missing output gate
each fail it.
"""

from __future__ import annotations

import dataclasses
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import qwen3_next as qn
from aigw_tpu.models.cache import StateCache
from aigw_tpu.ops import paged_walk
from aigw_tpu.models.reference import qwen3_next_ref as ref
from qwen3_next_util import SHARE, make_cache, make_params, ref_logits

TOL = 5e-4
PS = 16  # page size
CONFIGS = {"all_held": qn.TINY, "share_8_of_16": SHARE}


@pytest.fixture(scope="module", params=list(CONFIGS))
def model(request):
    cfg = CONFIGS[request.param]
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


def _prefill(cfg):
    return jax.jit(partial(qn.prefill, cfg=cfg, page_size=PS))


def _suffix(cfg):
    return jax.jit(partial(qn.prefill_suffix, cfg=cfg, page_size=PS))


def _decode(cfg):
    return jax.jit(partial(qn.decode_step, cfg=cfg, page_size=PS))


def test_layer_pattern_and_cache_spec():
    cfg = qn.Qwen3NextConfig(num_hidden_layers=12)
    assert cfg.layer_kinds == ("linear", "linear", "linear", "full") * 3
    spec = cfg.cache_spec()
    assert spec.stateful and spec.kv_layers == 3
    # 3 layers x (K and V) x 2 heads x 256 x 2 bytes = 6 KiB a token
    assert spec.kv_page_bytes(128, "bfloat16") == 128 * 6 * 1024
    # 9 layers x (32x128x128 float32 + 3x8192 bfloat16)
    assert spec.state_bytes_per_slot("bfloat16") == 9 * (
        32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert cfg.rotary_dim == 64 and cfg.conv_dim == 8192


def test_one_shot_prefill_matches_reference(model):
    """(a) a right-padded [2, S] prefill, rows of different lengths
    into chosen slots, and a padded group row that must write nothing."""
    cfg, p = model
    lens = [100, 37]
    toks = [_tokens(cfg, n, seed=i) for i, n in enumerate(lens)]
    tokens = np.zeros((4, 128), np.int32)
    for r, t in enumerate(toks):
        tokens[r, :len(t)] = t
    cache = make_cache(cfg, 32, PS, 4)
    marker = jax.tree_util.tree_map(lambda a: a + 7, cache.slots)
    cache = StateCache(cache.kv, marker)
    logits, cache = _prefill(cfg)(
        p, tokens=jnp.asarray(tokens),
        seq_lens=jnp.asarray(lens + [0, 0], jnp.int32), cache=cache,
        page_table=_page_table([list(range(1, 9)), list(range(9, 17)),
                                [], []]),
        slot_ids=jnp.asarray([2, 0, 4, 4], jnp.int32))
    for r, t in enumerate(toks):
        assert _err(logits[r], ref_logits(p, cfg, t)[-1]) < TOL
    # slots 1 and 3 were nobody's: the padded rows wrote nothing
    for name, leaf in cache.slots.items():
        for s in (1, 3):
            np.testing.assert_array_equal(
                np.asarray(leaf[:, s]), np.asarray(marker[name][:, s]))


def test_chunked_prefill_matches_reference(model):
    """(b) chunks of 32 through ``prefill_suffix`` — the state carried
    from chunk to chunk in the slot, the full layers attending over the
    page window — and a 4-token tail padded to 16."""
    cfg, p = model
    n, chunk = 100, 32
    toks = _tokens(cfg, n, seed=3)
    want = ref_logits(p, cfg, toks)
    cache = make_cache(cfg, 32, PS, 4)
    pt = _page_table([list(range(5, 13))])
    slot = jnp.asarray([3], jnp.int32)
    step = _suffix(cfg)
    done = 0
    while n - done > chunk:
        logits, cache = step(
            p, tokens=jnp.asarray(toks[None, done:done + chunk]),
            prefix_lens=jnp.asarray([done], jnp.int32),
            seq_lens=jnp.asarray([done + chunk], jnp.int32), cache=cache,
            page_table=pt, slot_ids=slot)
        done += chunk
        assert _err(logits[0], want[done - 1]) < TOL
    tail = np.zeros((1, 16), np.int32)
    tail[0, :n - done] = toks[done:]
    logits, cache = step(
        p, tokens=jnp.asarray(tail),
        prefix_lens=jnp.asarray([done], jnp.int32),
        seq_lens=jnp.asarray([n], jnp.int32), cache=cache, page_table=pt,
        slot_ids=slot)
    assert _err(logits[0], want[-1]) < TOL


def _prefill_then_decode(cfg, p, steps=20, between=None):
    """(c) two prompts of different lengths prefilled into slots 1 and
    3 of four, then ``steps`` teacher-forced decode steps with slots 0
    and 2 inactive. Returns (largest logit error, inactive rows
    untouched)."""
    lens = [90, 37]
    seqs = [_tokens(cfg, n + steps, seed=10 + i)
            for i, n in enumerate(lens)]
    want = [ref_logits(p, cfg, s) for s in seqs]
    cache = make_cache(cfg, 40, PS, 4)
    rows = [[], list(range(1, 9)), [], list(range(9, 17))]
    tokens = np.zeros((2, 96), np.int32)
    for r, (s, n) in enumerate(zip(seqs, lens)):
        tokens[r, :n] = s[:n]
    logits, cache = _prefill(cfg)(
        p, tokens=jnp.asarray(tokens), seq_lens=jnp.asarray(lens, jnp.int32),
        cache=cache, page_table=_page_table([rows[1], rows[3]]),
        slot_ids=jnp.asarray([1, 3], jnp.int32))
    worst = max(_err(logits[r], want[r][lens[r] - 1]) for r in range(2))
    # the idle slots hold a stranger's state: it must come through whole
    slots = {k: v.at[:, 0].add(3.0).at[:, 2].add(5.0)
             for k, v in cache.slots.items()}
    cache = StateCache(cache.kv, slots)
    before = {k: np.asarray(v) for k, v in slots.items()}
    active = jnp.asarray([False, True, False, True])
    pt = _page_table(rows)
    step = _decode(cfg)
    for t in range(steps):
        toks = np.zeros((4,), np.int32)
        pos = np.zeros((4,), np.int32)
        for r, slot in enumerate((1, 3)):
            toks[slot] = seqs[r][lens[r] + t]
            pos[slot] = lens[r] + t
        logits, cache = step(p, tokens=jnp.asarray(toks),
                             positions=jnp.asarray(pos), cache=cache,
                             page_table=pt, active=active)
        if between is not None:
            cache = between(cache)
        for r, slot in enumerate((1, 3)):
            worst = max(worst, _err(logits[slot], want[r][lens[r] + t]))
    untouched = all(
        np.array_equal(np.asarray(v[:, s]), before[k][:, s])
        for k, v in cache.slots.items() for s in (0, 2))
    return worst, untouched


def test_prefill_then_decode_matches_reference(model):
    cfg, p = model
    worst, untouched = _prefill_then_decode(cfg, p)
    assert worst < TOL
    assert untouched, "a decode step wrote an inactive slot's state"


def test_hidden_states_is_the_reference_mean(model):
    cfg, p = model
    toks = _tokens(cfg, 40, seed=5)
    tokens = np.zeros((1, 64), np.int32)
    tokens[0, :40] = toks
    got = qn.hidden_states(p, cfg, jnp.asarray(tokens), jnp.asarray([40]))
    assert got.shape == (1, cfg.hidden_size)
    assert np.isfinite(np.asarray(got)).all()


def test_chunked_form_agrees_with_the_recurrence():
    """The WY form over 3 blocks (one padded) against the token rule,
    from a non-zero state, to float32 rounding: 1e-5 on values of
    order 1."""
    rng = np.random.default_rng(0)
    B, S, H, dk, dv = 2, 150, 3, 16, 8
    q, k = (rng.normal(size=(B, S, H, dk)).astype(np.float32)
            for _ in range(2))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(B, S, H, dv)).astype(np.float32)
    g = -rng.uniform(0.0, 0.3, (B, S, H)).astype(np.float32)
    beta = rng.uniform(0.0, 1.0, (B, S, H)).astype(np.float32)
    # row 1 is real for 70 tokens only
    live = (np.arange(S)[None, :] < np.array([S, 70])[:, None])[..., None]
    g, beta = g * live, beta * live
    s0 = rng.normal(size=(B, H, dk, dv)).astype(np.float32)
    out, state = qn._gdn_chunk(*map(jnp.asarray, (q, k, v, g, beta, s0)))
    st = jnp.asarray(s0)
    for t in range(S):
        o, st = qn._gdn_recurrent(q[:, t], k[:, t], v[:, t], g[:, t],
                                  beta[:, t], st)
        assert _err(out[0, t], o[0]) < 2e-5
        if t < 70:
            assert _err(out[1, t], o[1]) < 2e-5
    assert _err(state, st) < 2e-5


def _gdn_inputs(rng, B, S, H, dk, dv):
    """Random inputs of the delta rule as ``_gdn_heads`` hands them
    over: L2-normalised keys, decay in (-0.3, 0], beta in [0, 1)."""
    q, k = (rng.normal(size=(B, S, H, dk)).astype(np.float32)
            for _ in range(2))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(B, S, H, dv)).astype(np.float32)
    g = -rng.uniform(0.0, 0.3, (B, S, H)).astype(np.float32)
    beta = rng.uniform(0.0, 1.0, (B, S, H)).astype(np.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize("S", [40, 64, 256, 300])
def test_chunked_form_carries_the_state_over_three_chunks(S):
    """Three chunks of ``S`` tokens, the state handed from one to the
    next as ``prefill_suffix`` does, the last with a padded tail in one
    row, against the token rule over the real tokens: S below one
    block, one block, the cell's four, and four and a padded fifth."""
    rng = np.random.default_rng(S)
    B, H, dk, dv = 2, 3, 16, 8
    real = np.array([[S, S, S], [S, S, S - 17]])  # row, chunk
    st_c = st_r = jnp.asarray(
        rng.normal(size=(B, H, dk, dv)).astype(np.float32))
    for c in range(3):
        q, k, v, g, beta = _gdn_inputs(rng, B, S, H, dk, dv)
        live = (np.arange(S)[None, :] < real[:, c, None])[..., None]
        g, beta = g * live, beta * live
        out, st_c = qn._gdn_chunk(
            *map(jnp.asarray, (q, k, v, g, beta)), st_c)
        for t in range(S):
            o, st_r = qn._gdn_recurrent(q[:, t], k[:, t], v[:, t], g[:, t],
                                        beta[:, t], st_r)
            for b in range(B):
                if t < real[b, c]:
                    assert _err(out[b, t], o[b]) < 2e-5, (c, t, b)
        assert _err(st_c, st_r) < 2e-5, c


def _wy_matrix(k, g, beta):
    """``A`` of one block as ``_gdn_chunk`` forms it: strictly lower,
    ``beta_i <k_i, k_j> exp(gc_i - gc_j)``. k [M,C,dk]; g, beta [M,C]."""
    gc = np.cumsum(g, axis=-1)
    a = np.einsum("mid,mjd->mij", k * beta[..., None], k) \
        * np.exp(gc[:, :, None] - gc[:, None, :])
    return np.tril(a, -1).astype(np.float32)


def _inverse_case(case):
    rng = np.random.default_rng(7)
    M, C, dk = 6, qn.GDN_CHUNK, 16
    k = rng.normal(size=(M, C, dk)).astype(np.float32)
    g = -rng.uniform(0.0, 0.3, (M, C)).astype(np.float32)
    beta = rng.uniform(0.0, 1.0, (M, C)).astype(np.float32)
    if case == "shared_direction":
        # the doubling form's failure: correlated keys, little decay
        k = 0.35 * k + rng.normal(size=(M, 1, dk)).astype(np.float32)
        g, beta = g * 0.01, 0.9 + 0.1 * beta
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    if case == "padded_rows":
        g[:, 40:], beta[:, 40:] = 0.0, 0.0
    if case == "all_zero":
        beta[:] = 0.0
    return _wy_matrix(k, g, beta)


@pytest.mark.parametrize("case", ["independent", "shared_direction",
                                  "padded_rows", "all_zero"])
def test_blocked_inverse_is_the_triangular_solve(case):
    """``_unit_lower_inverse`` against ``lax.linalg.triangular_solve``
    on the same float32 ``I + A``: 1e-6 of the largest entry. A padded
    position's row of ``A`` is zero and its row of the inverse is the
    identity's, to the bit."""
    a = _inverse_case(case)
    C = a.shape[-1]
    eye = np.eye(C, dtype=np.float32)
    if case == "shared_direction":
        assert np.abs(a).max() >= 0.8
    want = np.asarray(jax.lax.linalg.triangular_solve(
        jnp.asarray(a + eye), jnp.broadcast_to(eye, a.shape),
        left_side=True, lower=True, unit_diagonal=True))
    got = np.asarray(qn._unit_lower_inverse(jnp.asarray(a)))
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()
    exact = np.linalg.inv((a + eye).astype(np.float64))
    assert np.abs(got - exact).max() <= 1e-6 * np.abs(exact).max()
    assert (np.triu(got, 1) == 0).all()
    if case == "padded_rows":
        assert (got[:, 40:] == eye[40:]).all()
    if case == "all_zero":
        assert (got == eye).all()


def test_the_four_shares_add_up_to_the_whole_layer():
    """What each of four chips computes of one expert layer (its 4 of
    16 experts), plus the shared expert counted once, is the uncut
    reference's MoE output."""
    whole = qn.TINY
    p = make_params(whole, seed=4)
    F = whole.moe_intermediate_size
    i = 2
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 24, whole.hidden_size))
    cfgd = dataclasses.asdict(whole)
    want = ref.moe_layer(p, i, cfgd, x[0]) + ref.shared_expert(p, i, x[0])
    # the shared expert and its gate, alone: a share that holds nothing
    none = dataclasses.replace(whole, num_experts=4, router_experts=16,
                               held_from=16)
    shared = qn.moe(_share_params(p, i, 0, 4, F), i, x, none)[0]
    np.testing.assert_allclose(
        shared, ref.shared_expert(p, i, x[0]), atol=1e-5)
    total = shared
    placed = 0
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(whole, num_experts=4,
                                    router_experts=16, held_from=first)
        tape: list = []
        part = qn.moe(_share_params(p, i, first, 4, F), i, x, share,
                      tape=tape)[0]
        total = total + (part - shared)
        placed += int(tape[0][:4].sum())
        assert int(tape[0][4]) == 0  # dropped: structurally nothing
        assert int(tape[0][5]) == 24 * whole.num_experts_per_tok
        # and the reference given the same share agrees with the part
        np.testing.assert_allclose(
            part - shared,
            ref.moe_layer(p, i, cfgd, x[0], first, 4), atol=1e-5)
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert placed == 24 * whole.num_experts_per_tok  # nothing dropped


def _share_params(p, i, first, count, F):
    q = dict(p)
    cols = slice(first * F, (first + count) * F)
    q[f"l{i}.experts_gate"] = p[f"l{i}.experts_gate"][:, cols]
    q[f"l{i}.experts_up"] = p[f"l{i}.experts_up"][:, cols]
    q[f"l{i}.experts_down"] = p[f"l{i}.experts_down"][cols]
    return q


def test_routing_stats_tape_counts_real_tokens_only():
    cfg = SHARE
    p = make_params(cfg)
    toks = _tokens(cfg, 20)
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :20] = toks
    cache = make_cache(cfg, 8, PS, 2)
    _, _, stats = jax.jit(partial(
        qn.prefill, cfg=cfg, page_size=PS, moe_stats=True))(
        p, tokens=jnp.asarray(tokens), seq_lens=jnp.asarray([20]),
        cache=cache, page_table=_page_table([[1, 2]]))
    stats = np.asarray(stats)
    E = cfg.num_experts
    assert stats.shape == (cfg.num_hidden_layers, cfg.moe_tape_width)
    assert (stats[:, E] == 0).all()
    assert (stats[:, E + 1] == 20 * cfg.num_experts_per_tok).all()
    assert (stats[:, :E].sum(1) <= stats[:, E + 1]).all()
    assert ((stats[:, :E] > 0).sum(1) == stats[:, E + 2]).all()


# -- what the tolerance has to catch ---------------------------------------
def _bf16_state(cache):
    slots = dict(cache.slots)
    slots["gdn_state"] = slots["gdn_state"].astype(
        jnp.bfloat16).astype(jnp.float32)
    return StateCache(cache.kv, slots)


def test_a_bfloat16_state_fails_the_tolerance():
    cfg = qn.TINY
    worst, _ = _prefill_then_decode(cfg, make_params(cfg),
                                    between=_bf16_state)
    assert worst > 3 * TOL


def test_rotary_on_every_dimension_fails_the_tolerance():
    cfg = qn.TINY
    p = make_params(cfg)
    toks = _tokens(cfg, 60)
    wrong = dataclasses.replace(cfg, partial_rotary_factor=1.0)
    got, _ = _prefill(wrong)(
        p, tokens=jnp.asarray(toks[None]), seq_lens=jnp.asarray([60]),
        cache=make_cache(cfg, 8, PS, 1), page_table=_page_table([[1, 2, 3, 4]]))
    assert _err(got[0], ref_logits(p, cfg, toks)[-1]) > 3 * TOL


def test_a_missing_output_gate_fails_the_tolerance(monkeypatch):
    cfg = qn.TINY
    p = make_params(cfg)
    toks = _tokens(cfg, 60)

    def ungated(p, i, q, k, v, mask, gate):
        attn = qn.llama._attention(q, k.astype(q.dtype), v.astype(q.dtype),
                                   mask)
        return qn.llama._matmul(p, f"l{i}.o_proj", attn)

    monkeypatch.setattr(qn, "_attn_out", ungated)
    got, _ = qn.prefill(
        p, cfg, jnp.asarray(toks[None]), jnp.asarray([60]),
        make_cache(cfg, 8, PS, 1), _page_table([[1, 2, 3, 4]]), PS)
    assert _err(got[0], ref_logits(p, cfg, toks)[-1]) > 3 * TOL


def _lax_with(**over):
    """``jax.lax`` as the model sees it, with some functions replaced."""
    shim = types.SimpleNamespace(**{n: getattr(jax.lax, n)
                                    for n in dir(jax.lax)})
    vars(shim).update(over)
    return shim


def test_a_dropped_assignment_fails_the_tolerance(monkeypatch):
    """One token's largest expert assignment lost in every layer, as a
    capacity fence would lose it: after the renormalisation, so nothing
    else moves."""
    cfg = qn.TINY
    p = make_params(cfg)
    toks = _tokens(cfg, 60)

    def top_k(x, k):
        vals, idx = jax.lax.top_k(x, k)
        return vals, idx.at[59, 0].set(10 ** 6)  # an id nobody holds

    monkeypatch.setattr(qn, "lax", _lax_with(top_k=top_k))
    got, _ = qn.prefill(
        p, cfg, jnp.asarray(toks[None]), jnp.asarray([60]),
        make_cache(cfg, 8, PS, 1), _page_table([[1, 2, 3, 4]]), PS)
    assert _err(got[0], ref_logits(p, cfg, toks)[-1]) > 3 * TOL


# -- the decode step computes the experts its live rows hit, and no other --
#: every row picks all four experts of a four-wide router
ALL_HIT = dataclasses.replace(qn.TINY, num_experts=4, router_experts=4)
#: four experts "held" beyond a 16-wide router: no pick ever lands here
NONE_HIT = dataclasses.replace(qn.TINY, num_experts=4, router_experts=16,
                               held_from=16)


def _dense_moe(p, i, x, cfg, valid=None, tape=None, _moe=qn.moe):
    """A decode step's rows through the dense pass (as one sequence of
    B tokens): every held expert for every row, live or not — what the
    decode program computed before it looped."""
    B = x.shape[0]
    return _moe(p, i, x.reshape(1, B, -1), cfg,
                None if valid is None else valid.reshape(1, B),
                tape).reshape(x.shape)


@pytest.mark.parametrize("case,cfg", [
    ("dead_rows_route_elsewhere", SHARE), ("no_held_expert_hit", NONE_HIT),
    ("every_held_expert_hit", ALL_HIT)])
def test_decode_step_loops_over_the_experts_live_rows_hit(
        case, cfg, monkeypatch):
    """Slots 1 and 3 of four decode; 0 and 2 are dead and hold tokens
    and a state of their own. Live logits: the reference's and the
    dense pass's, and the same to the bit whatever the dead rows hold;
    the loop's trips a layer are the tape's hit column are the held
    experts that LIVE rows picked."""
    p = make_params(cfg)
    lens, live = [40, 23], (1, 3)
    seqs = [_tokens(cfg, n + 1, seed=20 + i) for i, n in enumerate(lens)]
    rows = [[], [1, 2, 3], [], [4, 5, 6]]
    tokens = np.zeros((2, 48), np.int32)
    for r, (s, n) in enumerate(zip(seqs, lens)):
        tokens[r, :n] = s[:n]
    _, cache = _prefill(cfg)(
        p, tokens=jnp.asarray(tokens), seq_lens=jnp.asarray(lens, jnp.int32),
        cache=make_cache(cfg, 8, PS, 4),
        page_table=_page_table([rows[1], rows[3]], 4),
        slot_ids=jnp.asarray(live, jnp.int32))
    cache = StateCache(cache.kv, {
        k: v.at[:, 0].add(3.0).at[:, 2].add(5.0)
        for k, v in cache.slots.items()})
    pos = np.zeros((4,), np.int32)
    pos[list(live)] = lens
    trips: list[int] = []

    def fori_loop(lower, upper, body, init):
        # (the DeltaNet layers' live-row loop is jitted on its own: its
        # bound is a tracer here, and not this test's)
        if not isinstance(upper, jax.core.Tracer):
            trips.append(int(upper))
        return jax.lax.fori_loop(lower, upper, body, init)

    def step(dead_tokens, active, moe=None):
        toks = np.zeros((4,), np.int32)
        toks[[0, 2]] = dead_tokens
        toks[list(live)] = [s[n] for s, n in zip(seqs, lens)]
        with monkeypatch.context() as m:
            m.setattr(qn, "lax", _lax_with(fori_loop=fori_loop))
            if moe is not None:
                m.setattr(qn, "moe", moe)
            del trips[:]
            logits, _, stats = qn.decode_step(
                p, cfg, jnp.asarray(toks), jnp.asarray(pos), cache,
                _page_table(rows, 4), PS, jnp.asarray(active),
                moe_stats=True)
        return (np.asarray(logits)[list(live)], np.asarray(stats),
                list(trips))

    only_live = [False, True, False, True]
    got, stats, looped = step([7, 300], only_live)
    E = cfg.num_experts
    for r in range(2):
        assert _err(got[r], ref_logits(p, cfg, seqs[r])[-1]) < TOL
    dense, dense_stats, none = step([7, 300], only_live, moe=_dense_moe)
    assert none == [] and _err(got, dense) < 2e-5
    np.testing.assert_array_equal(stats, dense_stats)
    # other tokens in the dead rows: not a bit of a live row moves
    again, stats_again, looped_again = step([411, 52], only_live)
    np.testing.assert_array_equal(got, again)
    np.testing.assert_array_equal(stats, stats_again)
    assert looped == looped_again == list(stats[:, E + 2]) == list(
        (stats[:, :E] > 0).sum(1))
    assert (stats[:, E + 1] == 2 * cfg.num_experts_per_tok).all()
    if case == "dead_rows_route_elsewhere":
        # were they live, the dead rows would add experts to the list
        _, everyone, more = step([7, 300], [True] * 4)
        assert sum(more) > sum(looped) > 0
        assert ((everyone[:, :E] > 0) >= (stats[:, :E] > 0)).all()
    elif case == "no_held_expert_hit":
        assert looped == [0] * cfg.num_hidden_layers  # the shared expert
    else:
        assert looped == [E] * cfg.num_hidden_layers


# -- the decode step updates the state of its live rows, and no other ------
def _pool_wide(q, k, v, g, beta, pool, layer, order, n_live, n_trips, *,
               Rs):
    """The formulation before ISSUE 35: ``_gdn_recurrent`` over every
    slot of the layer, a row that is not live decayed by ``exp(0)`` and
    given ``0``, and the whole layer stored back."""
    B = q.shape[0]
    act = jnp.zeros((B + 1,), jnp.float32).at[order].set(
        (jnp.arange(order.shape[0]) < n_live).astype(jnp.float32))[:B, None]
    o, state = qn._gdn_recurrent(q, k, v, g * act, beta * act, pool[layer])
    return o, pool.at[layer].set(state)


LIVE_SETS = {"none": [], "one": [5], "three_scattered": [6, 0, 3],
             "five": [1, 2, 4, 6, 7], "all": list(range(8))}


@pytest.mark.parametrize("Rs", [1, 2, 3])
@pytest.mark.parametrize("live", list(LIVE_SETS))
def test_live_row_update_is_the_pool_wide_pass_on_live_rows(live, Rs):
    """Eight slots, three layers of state; the rows in ``live`` decode.
    Their new state and output are the pool-wide pass's, every other
    slot of every layer keeps its bits, and the loop makes
    ``ceil(live / Rs)`` trips (five rows are no multiple of 2 or 3)."""
    L, B, H, dk, dv = 3, 8, 4, 16, 16
    rows = LIVE_SETS[live]
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    q, k = (jax.random.normal(ks[i], (B, H, dk), jnp.float32) * 0.3
            for i in (0, 1))
    v = jax.random.normal(ks[2], (B, H, dv), jnp.float32)
    g = -jax.random.uniform(ks[3], (B, H), jnp.float32)
    beta = jax.random.uniform(ks[4], (B, H), jnp.float32)
    pool = jax.random.normal(ks[5], (L, B, H, dk, dv), jnp.float32)
    lengths = np.zeros((B,), np.int32)
    lengths[rows] = [17 + 29 * r for r in rows]  # the plan ranks by these
    plan = paged_walk.pair_plan(
        jnp.asarray(lengths), jnp.zeros((B, 16), jnp.int32), PS, 1 << 12)
    n_live = jnp.asarray(len(rows), jnp.int32)
    n_trips = -(-n_live // Rs)
    layer = jnp.asarray(1, jnp.int32)
    args = (q, k, v, g, beta, pool, layer, plan.order, n_live, n_trips)
    want_o, want = _pool_wide(*args, Rs=Rs)
    got_o, got = qn._gdn_live_rows(*args, Rs=Rs)
    got, got_o, before = np.asarray(got), np.asarray(got_o), np.asarray(pool)
    dead = [r for r in range(B) if r not in rows]
    np.testing.assert_allclose(got[1, rows], np.asarray(want)[1, rows],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(got_o[rows], np.asarray(want_o)[rows],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(got[1, dead], before[1, dead])
    np.testing.assert_array_equal(got[[0, 2]], before[[0, 2]])
    assert not got_o[dead].any()
    if rows:  # a live row did move
        assert np.abs(got[1, rows] - before[1, rows]).max() > 1e-3


def test_state_rows_follow_the_shapes():
    """``Rs`` from the decode rows and one slot's bytes a layer: 2 at
    the published widths (2 MiB a slot), never more than the rows."""
    pub = qn.Qwen3NextConfig()
    row = (pub.linear_num_value_heads * pub.linear_key_head_dim
           * pub.linear_value_head_dim * 4)
    assert row == 2 << 20 and qn.state_rows(32, row) == 2
    assert qn.state_rows(32, 8 * row) == 1
    assert qn.state_rows(4, 4096) == 4


@pytest.mark.parametrize("active", [
    [False, True, True, True], [False, False, False, True],
    [True, True, True, True], [False, False, False, False]],
    ids=["three_of_four", "one", "all", "none"])
def test_decode_steps_update_live_slots_only_and_decode_the_same_tokens(
        active, monkeypatch):
    """Four slots prefilled, ``active`` of them decode twelve greedy
    steps in blocks of two rows (``Rs`` forced under the slot count:
    three live rows are no multiple of it). Against the pool-wide
    pass: the same tokens, logits within float32 rounding, the live
    slots' state too; a slot that does not decode keeps its state and
    its convolution tail to the bit; the tape's two state columns are
    the loop's trips x ``Rs`` and the live rows in every DeltaNet
    layer's row, and 0 in a full-attention layer's."""
    cfg = SHARE
    p = make_params(cfg)
    lens = [21, 40, 9, 33]
    tokens = np.zeros((4, 48), np.int32)
    for r, n in enumerate(lens):
        tokens[r, :n] = _tokens(cfg, n, seed=30 + r)
    rows = [list(range(1 + 4 * r, 5 + 4 * r)) for r in range(4)]
    logits, cache0 = _prefill(cfg)(
        p, tokens=jnp.asarray(tokens), seq_lens=jnp.asarray(lens, jnp.int32),
        cache=make_cache(cfg, 16, PS, 4), page_table=_page_table(rows, 4))
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    row_bytes = int(np.prod(cache0.slots["gdn_state"].shape[2:])) * 4
    monkeypatch.setattr(qn, "_STATE_TRIP_BYTES", 2 * row_bytes)
    act = jnp.asarray(active)
    n_live, E = sum(active), cfg.num_experts

    def run(steps=12):
        step = jax.jit(partial(qn.decode_step, cfg=cfg, page_size=PS,
                               moe_stats=True))
        cache, toks, pos = cache0, first, jnp.asarray(lens, jnp.int32)
        out = []
        for _ in range(steps):
            logits, cache, tape = step(
                p, tokens=toks, positions=pos, cache=cache,
                page_table=_page_table(rows, 4), active=act)
            toks = jnp.where(act, jnp.argmax(logits, -1).astype(jnp.int32),
                             toks)
            pos = pos + act
            out.append((np.asarray(toks), np.asarray(logits),
                        np.asarray(tape)))
        return out, cache

    got, cache = run()
    monkeypatch.setattr(qn, "_gdn_live_rows", _pool_wide)
    want, parent = run()
    for (t, lg, tape), (t_w, lg_w, _) in zip(got, want):
        np.testing.assert_array_equal(t, t_w)
        if n_live:
            assert _err(lg[np.asarray(active)],
                        lg_w[np.asarray(active)]) < 2e-5
        assert tape.shape == (cfg.num_hidden_layers, cfg.decode_tape_width)
        lin = np.asarray([kind == "linear" for kind in cfg.layer_kinds])
        assert (tape[lin, E + 3] == 2 * -(-n_live // 2)).all()
        assert (tape[lin, E + 4] == n_live).all()
        assert not tape[~lin, E + 3:].any()
    for name in ("gdn_state", "gdn_conv"):
        new, old, ref_ = (np.asarray(c.slots[name])
                          for c in (cache, cache0, parent))
        for s, live in enumerate(active):
            if live:
                # rounding of twelve steps through eight layers
                np.testing.assert_allclose(new[:, s], ref_[:, s],
                                           rtol=1e-4, atol=2e-5)
                assert not np.array_equal(new[:, s], old[:, s])
            else:
                np.testing.assert_array_equal(new[:, s], old[:, s])
