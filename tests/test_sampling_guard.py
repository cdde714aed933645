"""The sampler's vocabulary-wide sort runs only when a live row asks for
it (``tpuserve/sampling.py`` ``sample``): same tokens as the function it
replaced, one conditional with the one sort inside it, and a counter
(``sample_sort_steps``) that says how many decode steps paid for it.

On the CPU, no skip condition."""

from __future__ import annotations

import asyncio
import json
import threading

import aiohttp
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.analysis.manifest import expected_state_keys
from aigw_tpu.models import llama
from aigw_tpu.models.registry import get_model_spec
from aigw_tpu.obs.metrics import ENGINE_GAUGES, render_engine_gauges
from aigw_tpu.tpuserve.engine import (
    Engine,
    EngineConfig,
    EngineStats,
    GenRequest,
)
from aigw_tpu.tpuserve.sampling import SamplingParams, sample
from aigw_tpu.tpuserve.server import TPUServeServer

B, V = 16, 4099


def sample_frozen(logits, keys, temperature, top_p, top_k):
    """``sample`` as it stood before the guard (commit de8c511), kept
    here as the reference: every row pays the sort."""
    V = logits.shape[-1]
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_k - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_logits, k_idx[:, None], axis=-1)
    keep_k = (top_k[:, None] <= 0) | (logits >= kth)
    inv_t = 1.0 / jnp.maximum(temperature[:, None], 1e-6)
    probs_sorted = jax.nn.softmax(sorted_logits * inv_t, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    cutoff_mass = cum - probs_sorted
    keep_sorted = cutoff_mass < top_p[:, None]
    last_kept = jnp.sum(keep_sorted.astype(jnp.int32), axis=-1) - 1
    thresh = jnp.take_along_axis(
        sorted_logits, jnp.clip(last_kept, 0, V - 1)[:, None], axis=-1
    )
    keep_p = (top_p[:, None] >= 1.0) | (logits >= thresh)
    masked = jnp.where(keep_k & keep_p, logits, -jnp.inf)
    scaled = masked / jnp.maximum(temperature[:, None], 1e-6)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


def _rows(seed: int, temps, top_ps, top_ks):
    """A batch whose row i draws its parameters from the three lists."""
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(B, V)) * 4.0, jnp.float32)
    keys = jnp.asarray(rng.integers(0, 2**31, size=(B, 2)), jnp.uint32)

    def col(values, dtype):
        return jnp.asarray(rng.choice(values, size=B), dtype)

    return (logits, keys, col(temps, jnp.float32),
            col(top_ps, jnp.float32), col(top_ks, jnp.int32))


#: name -> (temperatures, top_p values, top_k values) the rows draw from
MIXES = {
    "all_greedy": ([0.0], [1.0], [0]),
    "temperature_without_truncation": ([0.6, 1.0, 1.4], [1.0], [0]),
    "top_k_only": ([0.0, 0.8, 1.2], [1.0], [1, 7, 50]),
    "top_p_only": ([0.0, 0.8, 1.2], [0.3, 0.9, 0.999], [0]),
    "mixed_rows": ([0.0, 0.7, 1.0, 1.3], [1.0, 0.9, 0.5], [0, 0, 5, 50]),
    "top_p_exactly_one": ([0.9], [1.0], [0]),
    # values a client can send that neither test of the masks calls off:
    # the guard is their exact complement, so these rows sort as before
    "edge_values": ([1.0], [float("nan"), 1.5, 0.0, -1.0], [-3, 0, V + 9]),
}


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("seed", [0, 1])
def test_same_tokens_as_the_unguarded_sampler(mix, seed):
    args = _rows(seed, *MIXES[mix])
    want = jax.jit(sample_frozen)(*args)
    assert np.array_equal(jax.jit(sample)(*args), want)
    live = jnp.ones((B,), jnp.bool_)
    assert np.array_equal(jax.jit(sample)(*args, live), want)


@pytest.mark.parametrize("seed", [0, 1])
def test_truncating_row_that_is_not_live_forces_no_sort(seed):
    logits, keys, temp, top_p, top_k = _rows(
        seed, [0.0, 0.9, 1.2], [1.0], [0])
    dead = 5
    temp = temp.at[dead].set(50.0)  # near uniform: truncation shows
    top_p = top_p.at[dead].set(0.2)
    top_k = top_k.at[dead].set(3)
    live = jnp.ones((B,), jnp.bool_).at[dead].set(False)
    got = np.asarray(
        jax.jit(sample)(logits, keys, temp, top_p, top_k, live))
    want = np.asarray(sample_frozen(logits, keys, temp, top_p, top_k))
    alive = np.asarray(live)
    assert np.array_equal(got[alive], want[alive])
    # the dead row was sampled from its whole distribution: what the
    # unguarded sampler gives with that row's truncation turned off
    open_row = sample_frozen(logits, keys, temp, top_p.at[dead].set(1.0),
                             top_k.at[dead].set(0))
    assert np.array_equal(got, open_row)
    assert want[dead] != got[dead]  # the truncation would have shown
    # alive again, it is truncated again
    assert np.array_equal(
        jax.jit(sample)(logits, keys, temp, top_p, top_k), want)


@pytest.mark.parametrize("mix", ["all_greedy", "mixed_rows"])
def test_same_tokens_under_the_verify_bodys_vmap(mix):
    """The speculative verify body maps ``sample`` over draft positions
    with ``temperature``, ``top_p``, ``top_k`` and ``live`` closed over."""
    logits, keys, temp, top_p, top_k = _rows(3, *MIXES[mix])
    live = jnp.ones((B,), jnp.bool_)
    D1 = 3
    logits_d = jnp.stack([logits + d for d in range(D1)])
    keys_d = jnp.stack([keys.at[:, 1].add(d) for d in range(D1)])
    got = jax.jit(jax.vmap(
        lambda l, k: sample(l, k, temp, top_p, top_k, live)))(
            logits_d, keys_d)
    want = jax.vmap(
        lambda l, k: sample_frozen(l, k, temp, top_p, top_k))(
            logits_d, keys_d)
    assert np.array_equal(got, want)


# -- the program's shape ------------------------------------------------------

def _count(jaxpr, name: str, *, into_cond: bool) -> int:
    """Equations of primitive ``name`` in ``jaxpr`` and every jaxpr
    nested in it — a ``cond``'s branches only if ``into_cond``."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, name, into_cond=into_cond)
    return n


def _plain(logits, keys, temp, top_p, top_k, live):
    return sample(logits, keys, temp, top_p, top_k, live)


def _vmapped(logits, keys, temp, top_p, top_k, live):
    return jax.vmap(lambda l, k: sample(l, k, temp, top_p, top_k, live))(
        jnp.stack([logits, logits]), jnp.stack([keys, keys]))


@pytest.mark.parametrize("form", [_plain, _vmapped],
                         ids=["plain", "vmapped"])
def test_one_conditional_with_the_one_sort_inside_it(form):
    args = (*_rows(0, *MIXES["mixed_rows"]), jnp.ones((B,), jnp.bool_))
    jaxpr = jax.make_jaxpr(form)(*args).jaxpr
    assert _count(jaxpr, "cond", into_cond=True) == 1
    assert _count(jaxpr, "sort", into_cond=False) == 0
    assert _count(jaxpr, "sort", into_cond=True) == 1
    assert _count(jaxpr, "cumsum", into_cond=False) == 0
    lowered = jax.jit(form).lower(*args)
    text = lowered.as_text()
    assert text.count("stablehlo.case") == 1
    assert text.count("stablehlo.sort") == 1
    hlo = lowered.compile().as_text()
    assert hlo.count(" conditional(") == 1
    assert hlo.count(" sort(") == 1


# -- sample_sort_steps --------------------------------------------------------

def test_counter_is_a_gauge_and_a_state_key():
    assert ("sample_sort_steps",
            "tpuserve_sample_sort_steps_total") in ENGINE_GAUGES
    assert "sample_sort_steps" in expected_state_keys()
    stats = EngineStats()
    stats.sample_sort_steps = 12
    assert (b"\ntpuserve_sample_sort_steps_total 12\n"
            in render_engine_gauges(stats))


@pytest.fixture(scope="module")
def eng():
    spec = get_model_spec("tiny-random")
    params = llama.init_params(jax.random.PRNGKey(3), spec.config,
                               jnp.float32)
    e = Engine(params, spec.config, EngineConfig(
        max_batch_size=4, max_seq_len=256, page_size=16,
        min_prefill_bucket=16, decode_steps_per_tick=4, spec_tokens=0,
        kv_cache_dtype="float32"))
    e.start()
    yield e
    e.stop()


def _submit(eng: Engine, prompt, n, **sp):
    toks: list[int] = []
    done = threading.Event()

    def emit(tok, fin):
        if tok >= 0:
            toks.append(tok)
        if fin is not None:
            done.set()

    eng.submit(GenRequest(prompt=list(prompt), max_tokens=n,
                          sampling=SamplingParams(**sp), emit=emit))
    return toks, done


@pytest.mark.parametrize("sp", [
    dict(temperature=0.0),
    dict(temperature=0.9, seed=7),
    dict(temperature=1.0, top_p=1.0, top_k=0, seed=7),
], ids=["greedy", "temperature", "defaults_spelled_out"])
def test_counter_stays_still_without_truncation(eng, sp):
    sorts0, steps0 = eng.stats.sample_sort_steps, eng.stats.decode_steps
    _, done = _submit(eng, [3, 1, 4, 1, 5], 24, **sp)
    assert done.wait(timeout=300)
    assert eng.stats.decode_steps > steps0
    assert eng.stats.sample_sort_steps == sorts0


@pytest.mark.parametrize("sp", [
    dict(temperature=0.9, top_p=0.9, seed=7),
    dict(temperature=0.9, top_k=5, seed=7),
], ids=["top_p", "top_k"])
def test_counter_moves_while_a_truncating_request_is_live(eng, sp):
    K = eng.cfg.decode_steps_per_tick
    sorts0, steps0 = eng.stats.sample_sort_steps, eng.stats.decode_steps
    long_toks, long_done = _submit(eng, [2, 7, 1, 8], 160, temperature=0.0)
    _, short_done = _submit(eng, [3, 1, 4, 1, 5], 6, **sp)
    assert short_done.wait(timeout=300)
    sorts1, steps1 = eng.stats.sample_sort_steps, eng.stats.decode_steps
    assert long_done.wait(timeout=300)
    assert len(long_toks) == 160
    sorts2, steps2 = eng.stats.sample_sort_steps, eng.stats.decode_steps
    assert sorts1 > sorts0  # it moved while the request held a slot
    # ...and stopped when the slot was released: at most the window in
    # flight at that moment and the one being settled still count
    assert sorts2 - sorts1 <= 2 * K
    assert steps2 - steps1 >= 10 * K  # while the greedy stream went on
    assert sorts2 - sorts0 <= steps2 - steps0
    # the greedy neighbour sampled what it samples alone
    alone, done = _submit(eng, [2, 7, 1, 8], 160, temperature=0.0)
    assert done.wait(timeout=300)
    assert alone == long_toks
    assert eng.stats.sample_sort_steps == sorts2


# -- /state and /metrics ------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    holder: dict = {}
    started = threading.Event()

    def run():
        async def main():
            from aiohttp import web

            server = TPUServeServer(
                "tiny-random",
                EngineConfig(max_batch_size=2, max_seq_len=256,
                             page_size=16, min_prefill_bucket=16))
            runner = web.AppRunner(server.app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            holder["port"] = site._server.sockets[0].getsockname()[1]
            holder["loop"] = asyncio.get_running_loop()
            holder["engine"] = server.engine
            started.set()
            await asyncio.Event().wait()

        try:
            asyncio.run(main())
        except RuntimeError:
            pass

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(timeout=120)
    yield f"http://127.0.0.1:{holder['port']}"
    holder["loop"].call_soon_threadsafe(holder["loop"].stop)
    # no engine thread may be inside JAX when the interpreter exits
    holder["engine"].stop()


def test_state_and_metrics_carry_the_counter(served):
    async def chat(http, **sampling):
        async with http.post(served + "/v1/chat/completions", json={
                "model": "tiny-random", "max_tokens": 12,
                "messages": [{"role": "user", "content": "hello"}],
                **sampling}) as r:
            assert r.status == 200
            await r.read()

    async def read(http):
        async with http.get(served + "/state") as r:
            state = json.loads(await r.read())
        async with http.get(served + "/metrics") as r:
            return state, (await r.read()).decode()

    async def main():
        async with aiohttp.ClientSession() as http:
            await chat(http)  # an SDK's defaults: temperature 1, top_p 1
            await chat(http, temperature=0.0)
            before = await read(http)
            await chat(http, top_p=0.9)
            return before, await read(http)

    (state0, text0), (state1, text1) = asyncio.run(main())
    assert state0["decode_steps"] > 0
    assert state0["sample_sort_steps"] == 0
    assert "\ntpuserve_sample_sort_steps_total 0\n" in text0
    assert 0 < state1["sample_sort_steps"] <= (
        state1["decode_steps"] - state0["decode_steps"])
    assert (f"\ntpuserve_sample_sort_steps_total "
            f"{state1['sample_sort_steps']}\n") in text1
