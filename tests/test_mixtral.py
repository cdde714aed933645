"""Mixtral MoE correctness + expert-parallel sharding tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from aigw_tpu.models import llama, mixtral
from aigw_tpu.parallel import (
    MeshSpec,
    kv_cache_spec,
    make_mesh,
    mixtral_param_specs,
)

CFG = mixtral.TINY_MOE
PAGE = 16


@pytest.fixture(scope="module")
def params():
    return mixtral.init_params(jax.random.PRNGKey(0), CFG)


def fresh_cache(n_pages=64):
    return jnp.zeros(
        (CFG.n_layers, 2, n_pages * PAGE, CFG.n_kv_heads, CFG.head_dim),
        jnp.bfloat16,
    )


def test_single_expert_equals_dense():
    """With 1 expert and k=1 the MoE must reduce to a plain dense MLP —
    the routing/dispatch machinery proves itself against the closed form."""
    cfg = mixtral.MixtralConfig(
        vocab_size=64, dim=32, n_layers=1, n_heads=2, n_kv_heads=2,
        ffn_dim=64, n_experts=1, experts_per_token=1, capacity_factor=8.0,
        max_seq_len=64, rope_theta=10000.0,
    )
    p = mixtral.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.dim),
                          jnp.bfloat16)
    got = mixtral.moe_mlp(p, 0, x, cfg)
    gate = jax.nn.silu(x @ p["l0.w_gate"][0])
    want = (gate * (x @ p["l0.w_up"][0])) @ p["l0.w_down"][0]
    np.testing.assert_allclose(
        np.asarray(got, jnp.float32), np.asarray(want, jnp.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_topk_weights_normalized(params):
    """Combine weights per token must sum to 1 across chosen experts when
    no tokens overflow capacity."""
    cfg = CFG
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, cfg.dim),
                          jnp.bfloat16)
    # direct check through the routing math
    xt = x.reshape(-1, cfg.dim)
    logits = xt.astype(jnp.float32) @ params["l0.gate"].astype(jnp.float32)
    topv, _ = jax.lax.top_k(logits, cfg.experts_per_token)
    w = jax.nn.softmax(topv, axis=-1)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)


@pytest.mark.slow


def test_prefill_decode_consistency(params):
    """The MoE path preserves the paged-KV decode invariant."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 20), 0,
                                CFG.vocab_size)
    pt = jnp.arange(4, dtype=jnp.int32)[None, :]
    full, _ = mixtral.prefill(
        params, CFG, tokens, jnp.array([20]), fresh_cache(), pt, PAGE
    )
    logits, cache = mixtral.prefill(
        params, CFG, tokens[:, :12], jnp.array([12]), fresh_cache(), pt, PAGE
    )
    for pos in range(12, 20):
        logits, cache = mixtral.decode_step(
            params, CFG, tokens[:, pos], jnp.array([pos], jnp.int32),
            cache, pt, PAGE, jnp.array([True]),
        )
    np.testing.assert_allclose(
        np.asarray(full), np.asarray(logits), rtol=5e-2, atol=5e-2
    )


# -- the decode step's loop over the experts its live rows hit (ISSUE 41) --

#: 16 slots, 8 experts, top-2: the cell's fence (8 places an expert)
STEP_CFG = mixtral.MixtralConfig(
    vocab_size=64, dim=64, n_layers=1, n_heads=2, n_kv_heads=2,
    ffn_dim=128, n_experts=8, experts_per_token=2, max_seq_len=64,
    rope_theta=10000.0,
)
SLOTS = 16


def _step_params(mode=None):
    from aigw_tpu.models.quant import quantize_params

    p = mixtral.init_params(jax.random.PRNGKey(0), STEP_CFG)
    return quantize_params(p, mode=mode) if mode else p


def _step_input(seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (SLOTS, 1, STEP_CFG.dim), jnp.bfloat16)


def _mask(rows):
    live = np.zeros(SLOTS, bool)
    live[list(rows)] = True
    return live


def _picked(p, x, live):
    """The distinct experts the live rows' top-k names (no fence)."""
    logits = (np.asarray(x[:, 0], np.float32)
              @ np.asarray(p["l0.gate"], np.float32))
    top = np.argsort(-logits, axis=-1)[:, :STEP_CFG.experts_per_token]
    return sorted(set(top[live].ravel().tolist()))


@pytest.mark.parametrize("rows", [(1, 4, 5, 9, 13), tuple(range(SLOTS))],
                         ids=["five-live", "all-live"])
@pytest.mark.parametrize("mode", [None, "int8", "int4"],
                         ids=["bf16", "int8", "int4"])
def test_decode_step_matches_dispatch_over_the_live_rows_alone(mode, rows):
    """``moe_step`` on 16 slots == ``moe_mlp`` on a batch of the live
    rows alone (with the capacity 16 slots give): same routing, same
    fence — to the tolerance ``test_prefill_decode_consistency`` uses,
    for every way the expert weights are stored. Five live rows hit
    some experts and loop over them (the products in another order);
    sixteen hit all eight: eight trips, and still the dispatch form's
    answer — the loop is the step's one path at every hit count."""
    import dataclasses

    p, x = _step_params(mode), _step_input()
    live = _mask(rows)
    tape: list = []
    got = mixtral.moe_step(p, 0, x, STEP_CFG, jnp.asarray(live), tape)
    E = STEP_CFG.n_experts
    assert (int(tape[0][-1]) == E) == (len(rows) == SLOTS)
    same_fence = dataclasses.replace(
        STEP_CFG,
        capacity_factor=STEP_CFG.capacity_factor * SLOTS / live.sum())
    want = mixtral.moe_mlp(p, 0, x[live], same_fence)
    np.testing.assert_allclose(
        np.asarray(got[live], np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2)
    assert not np.asarray(got[~live], np.float32).any()


def test_dead_slot_changes_no_live_row():
    """The same live rows over different garbage in the dead slots (NaN
    included) give bit-identical outputs and the same tape row. The dispatch over all
    slots (the decode step until ISSUE 41) fails this: a dead slot's
    stale token took a place in an expert's 8, so with eleven or more
    dead rows it could push a live row's assignment past the fence."""
    p, x = _step_params(), _step_input()
    live = _mask([0, 7, 15])
    outs, tapes = [], []
    for fill in (0.0, 3.0, -50.0, float("nan")):
        # every dead row alike: under the old count they would all route
        # to one pair of experts and fill its places before row 15's
        xg = jnp.where(jnp.asarray(live)[:, None, None], x,
                       jnp.full_like(x, fill))
        tape: list = []
        outs.append(np.asarray(mixtral.moe_step(
            p, 0, xg, STEP_CFG, jnp.asarray(live), tape)[live]))
        tapes.append(np.asarray(tape[0]))
    for out, row in zip(outs[1:], tapes[1:]):
        assert (out == outs[0]).all()
        assert (row == tapes[0]).all()


def test_fence_counts_live_rows_and_still_drops():
    """Twelve live rows whose first choice is expert 0 (their second
    choices spread): expert 0 places its 8, the tape says 4 dropped of
    24 routed — the fence of ``capacity_factor`` stands at a decode
    step, counted over live rows. The four dead rows, routed the same
    way, take no place and are not counted."""
    E = STEP_CFG.n_experts
    p = dict(_step_params())
    gate = np.zeros((STEP_CFG.dim, E), np.float32)
    gate[0, 0] = 10.0
    for j in range(1, E):
        gate[j, j] = 1.0
    p["l0.gate"] = jnp.asarray(gate, jnp.bfloat16)
    x = np.zeros((SLOTS, 1, STEP_CFG.dim), np.float32)
    for r in range(SLOTS):
        x[r, 0, 0] = 1.0
        x[r, 0, 1 + r % (E - 1)] = 0.5
    live = _mask(range(2, 14))
    tape: list = []
    out = mixtral.moe_step(p, 0, jnp.asarray(x, jnp.bfloat16), STEP_CFG,
                           jnp.asarray(live), tape)
    row = np.asarray(tape[0])
    placed, dropped, routed, n_hit = row[:E], row[E], row[E + 1], row[E + 2]
    assert placed[0] == 8 and dropped == 4 and routed == 24
    assert placed.sum() == 20 and n_hit == E
    assert row.shape == (STEP_CFG.decode_tape_width,)
    # rows 2..9 hold expert 0's places; rows 10..13 keep their second
    # choice alone, so their output is not zero either
    assert np.asarray(out[live], np.float32).any(axis=-1).all()


@pytest.mark.parametrize("rows", [(), (3,), (0, 7, 15), tuple(range(16))],
                         ids=["none", "one", "three", "all"])
def test_loop_trips_are_the_experts_live_rows_picked(rows):
    """The tape's hit column == the distinct experts the live rows
    picked == the loop's trips: every OTHER expert's weights are NaN, and
    a trip that read one (even at weight zero) would poison the sum; the
    dispatch over all experts does exactly that. With no row live there
    is no trip at all — every expert is NaN and the output is zero.
    With all sixteen live every expert is hit (none is NaN): eight
    trips."""
    E = STEP_CFG.n_experts
    p, x = dict(_step_params()), _step_input(seed=5)
    live = _mask(rows)
    clean = mixtral.moe_step(p, 0, x, STEP_CFG, jnp.asarray(live))
    picked = _picked(p, x, live)
    unhit = np.asarray([e not in picked for e in range(E)])
    for name in ("w_gate", "w_up", "w_down"):
        w = p[f"l0.{name}"]
        p[f"l0.{name}"] = jnp.where(unhit[:, None, None], jnp.nan, w)
    tape: list = []
    out = mixtral.moe_step(p, 0, x, STEP_CFG, jnp.asarray(live), tape)
    assert int(tape[0][-1]) == len(picked)
    assert (len(picked) == 0) == (not rows) and len(picked) <= E
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert (np.asarray(out) == np.asarray(clean)).all()
    if not rows:
        assert not np.asarray(out, np.float32).any()
        assert not np.asarray(tape[0]).any()


def test_decode_step_tape_and_path_by_entry_point(params):
    """On one chip ``decode_step`` loops (a call of the one ``while`` a
    layer in its jaxpr, and no ``cond`` beside it: the loop is the one
    path); under
    a mesh it keeps the dispatch einsums (no ``while``: slicing one
    expert out of weights sharded over ``ep`` would gather them all).
    Either way the tape is ``[L, decode_tape_width]``; the sequence
    programs keep ``[L, E + 1]``."""
    mesh = make_mesh(MeshSpec(dp=1, tp=2, ep=4))
    tok = jnp.zeros((4,), jnp.int32)
    pt = jnp.arange(16, dtype=jnp.int32).reshape(4, 4)
    act = jnp.array([True, False, True, False])

    def step(mesh):
        # the window gather: no loop of the attention's own in the way
        return lambda p, kv: mixtral.decode_step(
            p, CFG, tok, tok + 3, kv, pt, PAGE, act, attn_impl="gather",
            mesh=mesh, moe_stats=True)

    for m, loops in ((None, CFG.n_layers), (mesh, 0)):
        jaxpr = jax.make_jaxpr(step(m))(params, fresh_cache(16))
        text = str(jaxpr)
        # the loop is one jitted function: traced once a program,
        # called once a layer
        assert text.count("jit[name=_hit_experts") == loops
        assert text.count("while[") == bool(loops)
        assert "cond[" not in text
        assert jaxpr.out_avals[-1].shape == (
            CFG.n_layers, CFG.decode_tape_width)
    *_, moe = mixtral.prefill(
        params, CFG, jnp.zeros((1, 16), jnp.int32), jnp.array([16]),
        fresh_cache(), pt[:1], PAGE, moe_stats=True)
    assert moe.shape == (CFG.n_layers, CFG.n_experts + 1)
    # a mesh step's row: the dense form's counts in the decode columns
    *_, moe = step(mesh)(params, fresh_cache(16))
    E = CFG.n_experts
    moe = np.asarray(moe)
    assert (moe[:, E + 1] == moe[:, :E].sum(-1) + moe[:, E]).all()
    assert (moe[:, E + 2] == (moe[:, :E] > 0).sum(-1)).all()


@pytest.mark.slow


def test_expert_parallel_matches_single_device(params):
    """EP×TP sharded prefill == unsharded (all-to-alls preserve math)."""
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                                CFG.vocab_size)
    lens = jnp.array([16, 9])
    pt = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)

    def run(p, kv):
        return mixtral.prefill(p, CFG, tokens, lens, kv, pt, PAGE)

    kv0 = fresh_cache(16)
    ref_logits, _ = jax.jit(run)(params, kv0)

    mesh = make_mesh(MeshSpec(dp=1, tp=2, ep=4))
    specs = mixtral_param_specs(CFG)
    sharded = {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }
    kv_sh = jax.device_put(kv0, NamedSharding(mesh, kv_cache_spec()))
    ep_logits, _ = jax.jit(run)(sharded, kv_sh)
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(ep_logits), atol=7e-2
    )
    assert (np.asarray(ref_logits).argmax(-1)
            == np.asarray(ep_logits).argmax(-1)).all()


@pytest.mark.slow


def test_engine_serves_tiny_moe():
    """The continuous-batching engine drives the MoE family end to end."""
    import threading

    from aigw_tpu.models.registry import family_fns
    from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
    from aigw_tpu.tpuserve.sampling import SamplingParams

    params = mixtral.init_params(jax.random.PRNGKey(0), CFG)
    eng = Engine(
        params, CFG,
        EngineConfig(max_batch_size=2, max_seq_len=128, page_size=16,
                     min_prefill_bucket=16, decode_steps_per_tick=4),
        eos_token_ids=(257,),
        fns=family_fns("mixtral"),
    )
    eng.start()
    try:
        done = threading.Event()
        toks: list[int] = []

        def emit(tok, fin):
            if tok >= 0:
                toks.append(tok)
            if fin is not None:
                done.set()

        eng.submit(GenRequest(prompt=[3, 5, 7], max_tokens=4,
                              sampling=SamplingParams(temperature=0.0),
                              emit=emit))
        assert done.wait(timeout=240)
        assert 1 <= len(toks) <= 4
    finally:
        eng.stop()
