"""Tier-1 smoke for the prefix-cache observability surface (ISSUE 3).

Two tripwires that previously only fired at round-end:
- the prefix gauges must actually appear on ``/state`` and ``/metrics``
  (a renamed EngineStats field silently drops a dashboard signal);
- ``warm_prefill_buckets`` must still pre-compile EVERY tail-width rung
  of the prefill ladder — a hot-path XLA compile for a rung the warmup
  missed is exactly the class of TTFT regression PR 1/2 removed.
"""

from __future__ import annotations

import asyncio
import json
import threading

import aiohttp
import jax
import jax.numpy as jnp
import pytest

from aigw_tpu.analysis import manifest
from aigw_tpu.models import llama
from aigw_tpu.obs.metrics import ENGINE_GAUGES
from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
from aigw_tpu.tpuserve.sampling import SamplingParams
from aigw_tpu.tpuserve.server import TPUServeServer

PREFIX_STATE_FIELDS = manifest.state_fields("prefix")

PREFIX_GAUGES = manifest.gauge_names("prefix")

# speculative-decoding surface (ISSUE 4): a renamed EngineStats field
# must not silently drop a dashboard signal
SPEC_STATE_FIELDS = manifest.state_fields("spec")

SPEC_GAUGES = manifest.gauge_names("spec")


@pytest.fixture(scope="module")
def smoke_url():
    holder = {}
    started = threading.Event()

    def run():
        async def main():
            from aiohttp import web

            server = TPUServeServer(
                "tiny-random",
                EngineConfig(max_batch_size=2, max_seq_len=256,
                             page_size=16, min_prefill_bucket=16),
            )
            runner = web.AppRunner(server.app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            holder["port"] = site._server.sockets[0].getsockname()[1]
            holder["loop"] = asyncio.get_running_loop()
            started.set()
            await asyncio.Event().wait()

        try:
            asyncio.run(main())
        except RuntimeError:
            pass

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(timeout=120)
    yield f"http://127.0.0.1:{holder['port']}"
    holder["loop"].call_soon_threadsafe(holder["loop"].stop)


async def _get(url: str, path: str):
    async with aiohttp.ClientSession() as s:
        async with s.get(url + path) as resp:
            assert resp.status == 200
            return await resp.read()


def test_state_exports_prefix_gauges(smoke_url):
    async def main():
        # one chat first so the stats are live, not just defaults
        async with aiohttp.ClientSession() as s:
            async with s.post(smoke_url + "/v1/chat/completions", json={
                "model": "tiny-random",
                "messages": [{"role": "user",
                              "content": "smoke prefix state " * 3}],
                "max_tokens": 2,
            }) as resp:
                assert resp.status == 200
        return json.loads(await _get(smoke_url, "/state"))

    state = asyncio.run(main())
    for field in PREFIX_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    assert state["prefix_cache_hits"] + state["prefix_cache_misses"] >= 1
    assert state["prefix_bytes_pinned"] >= 0


def test_metrics_export_prefix_gauges(smoke_url):
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for gauge in PREFIX_GAUGES:
        assert gauge in text, f"/metrics lost {gauge}"


def test_state_and_metrics_export_spec_gauges(smoke_url):
    """Every tpuserve_spec_* gauge must appear on /state and /metrics —
    even with speculation off (constant 0), so dashboards never
    silently lose the surface."""
    state = json.loads(asyncio.run(_get(smoke_url, "/state")))
    for field in SPEC_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for gauge in SPEC_GAUGES:
        assert gauge in text, f"/metrics lost {gauge}"


def test_engine_gauges_map_matches_engine_stats():
    """Every ENGINE_GAUGES attr must exist on EngineStats — a renamed
    stat otherwise exports a silent constant 0."""
    from aigw_tpu.tpuserve.engine import EngineStats

    stats = EngineStats()
    for attr, _name in ENGINE_GAUGES:
        assert hasattr(stats, attr), attr


def test_prefill_rate_decays_to_recent_mix():
    """The advertised prefill_ms_per_token must track a traffic-mix
    change (token-decayed mean), not the process-lifetime average: a
    long steady history at one rate converges to a NEW rate within a
    few half-lives of tokens — and falls back to the lifetime mean
    before any call is observed."""
    from aigw_tpu.obs.flight import PREFILL_BLOCK
    from aigw_tpu.tpuserve.engine import EngineStats

    st = EngineStats()
    # 500 ms of prefill on the loop ledger (prefill_ms is its view)
    st.loop.ns[PREFILL_BLOCK], st.prefill_tokens_real = 500_000_000, 100_000
    assert st.prefill_ms_per_token() == pytest.approx(0.005)
    # 1M tokens at 0.005 ms/tok, then 3 half-lives at 0.05 ms/tok
    for _ in range(100):
        st.note_prefill_call(0.005 * 10_000, 10_000)
    for _ in range(3):
        st.note_prefill_call(0.05 * 16_384, 16_384)
    rate = st.prefill_ms_per_token()
    assert 0.04 < rate <= 0.05, rate  # lifetime mean would sit ≈ 0.007
    st.note_prefill_call(10.0, 0)  # zero-token calls never divide


def test_engine_histograms_match_engine_phases():
    """Histogram-surface drift check (ISSUE 5): every ENGINE_HISTOGRAMS
    phase must exist in EnginePhases under its declared Prometheus
    family name, render as a histogram, and surface in the /state
    percentile summary — a renamed phase otherwise silently drops a
    dashboard distribution."""
    from aigw_tpu.obs.metrics import ENGINE_HISTOGRAMS, EnginePhases

    phases = EnginePhases()
    for key, name in ENGINE_HISTOGRAMS:
        assert key in phases.hists, key
        assert phases.hists[key].name == name
    text = phases.render().decode()
    pct = phases.percentiles()
    for key, name in ENGINE_HISTOGRAMS:
        assert f"# TYPE {name} histogram" in text, name
        assert f'{name}_bucket{{le="+Inf"}}' in text, name
        assert set(pct[key]) == {"p50", "p95", "p99"}


def test_state_and_metrics_export_phase_histograms(smoke_url):
    """/state must carry phase_percentiles + the XLA compile counters,
    and /metrics must serve every phase histogram family — with
    NON-EMPTY buckets for the phases a completed request must have
    exercised (queue_wait/prefill/ttft/first_emit)."""
    from aigw_tpu.obs.metrics import ENGINE_HISTOGRAMS

    state = json.loads(asyncio.run(_get(smoke_url, "/state")))
    assert "xla_compiles" in state and "xla_compile_ms" in state
    pct = state["phase_percentiles"]
    for key, _name in ENGINE_HISTOGRAMS:
        assert key in pct, f"/state phase_percentiles lost {key}"
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for _key, name in ENGINE_HISTOGRAMS:
        assert f"# TYPE {name} histogram" in text, name
    # the module-scoped server has answered chats by now: these phases
    # must hold real observations (+Inf cumulative count > 0)
    for name in ("tpuserve_queue_wait_hist_ms",
                 "tpuserve_prefill_hist_ms",
                 "tpuserve_first_emit_hist_ms",
                 "tpuserve_ttft_hist_ms"):
        for line in text.splitlines():
            if line.startswith(f'{name}_bucket{{le="+Inf"}}'):
                assert int(line.split()[1]) > 0, line
                break
        else:
            raise AssertionError(f"{name} +Inf bucket missing")


@pytest.mark.slow


def test_warm_prefill_buckets_covers_every_rung():
    """Compile-on-hot-path tripwire: with warm_prefill_buckets=N, every
    rung of the first N octaves (x1, x1.5 at rungs=2) must be compiled
    at warmup for every pow2 group size — admitting a prompt at any of
    those widths afterwards must NOT add a prefill compile. Compile
    accounting goes through the engine's shared CompileTracker
    (obs/xla_events.py), not ad-hoc jit-cache spelunking."""
    spec_cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), spec_cfg)
    eng = Engine(params, spec_cfg, EngineConfig(
        max_batch_size=2, max_seq_len=256, page_size=16,
        min_prefill_bucket=16, decode_steps_per_tick=2,
        warm_prefill_buckets=2, prefill_bucket_rungs=2,
        enable_prefix_cache=False))
    eng.warmup()
    rungs = sorted(set(eng._bucket_rungs(0) + eng._bucket_rungs(1)))
    assert rungs == [16, 24, 32, 48]
    warmed = eng.compile_tracker.programs()["prefill"]
    # 4 rungs × group sizes {1, 2} — every (G2, S) shape pre-compiled
    assert warmed == len(rungs) * 2, warmed

    eng.start()
    try:
        for width in rungs:
            done = threading.Event()
            eng.submit(GenRequest(
                prompt=[1 + width] * width, max_tokens=1,
                sampling=SamplingParams(temperature=0.0),
                emit=lambda t, f, d=done: d.set() if f else None))
            assert done.wait(timeout=300)
        assert eng.compile_tracker.programs()["prefill"] == warmed, (
            "a prompt at a warmed rung width still paid an XLA "
            "prefill compile on the hot path")
    finally:
        eng.stop()


@pytest.mark.slow


def test_spec_verify_ladder_warm_no_hot_compiles():
    """Compile-on-hot-path tripwire for the speculative ladder (ISSUE
    4): after warmup(), traffic that climbs to the top draft rung,
    collapses to plain decode through the middle rung, and mixes in a
    penalized slot must add ZERO XLA compiles — every verify-scan
    shape, both plain variants, and the row-update scatters are
    pre-compiled. One 64-token page keeps the decode bucket at the
    warmup size, so any compile counted here is a real ladder gap, not
    page-bucket growth. The assertion runs on the engine's shared
    CompileTracker checkpoint (every hot-path program is registered
    there — ISSUE 5 replaced the per-test counting helpers)."""
    spec_cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), spec_cfg)
    eng = Engine(params, spec_cfg, EngineConfig(
        max_batch_size=2, max_seq_len=256, page_size=64,
        min_prefill_bucket=16, decode_steps_per_tick=4,
        spec_tokens=4, warm_prefill_buckets=2,
        enable_prefix_cache=False))
    eng.warmup()
    checkpoint = eng.compile_tracker.checkpoint()
    fns = set(eng._decode_fns)
    # the full ladder exists up front: {kmin, K} × ({lean, full} plain
    # + every nonzero rung)
    assert {k for k, _, _ in fns} == {1, 4}
    assert {d for _, _, d in fns} == {0, 2, 4}

    eng.start()
    try:
        cases = [
            # climbs to and stays at the top rung (D=4 dispatches)
            dict(prompt=[1, 2, 3], max_tokens=24,
                 sampling=SamplingParams(temperature=0.0,
                                         logit_bias=((7, 100.0),))),
            # proposes-and-rejects: collapses 4 → 2 → 0 (D=2 and both
            # plain programs dispatch)
            dict(prompt=[9, 8, 9, 8, 5, 4, 9, 8], max_tokens=24,
                 sampling=SamplingParams(temperature=0.0)),
            # penalized slot: the full (non-lean) plain program
            dict(prompt=[6, 6, 6], max_tokens=8,
                 sampling=SamplingParams(temperature=0.6, seed=3,
                                         frequency_penalty=0.5)),
        ]
        for kw in cases:
            done = threading.Event()
            eng.submit(GenRequest(
                emit=lambda t, f, d=done: d.set() if f else None, **kw))
            assert done.wait(timeout=300)
        assert eng.stats.spec_drafted > 0  # the ladder actually ran
        assert eng.stats.state_rebuilds == 0
        assert set(eng._decode_fns) == fns, "new program key on hot path"
        assert eng.compile_tracker.compiles_since(checkpoint) == 0, (
            "speculative traffic paid an XLA compile after warmup")
    finally:
        eng.stop()


# -- ragged attention backend (ISSUE 6) ----------------------------------

RAGGED_STATE_FIELDS = manifest.state_fields("ragged")

RAGGED_GAUGES = manifest.gauge_names("ragged")


# -- adapter serving + tenancy (ISSUE 7) ---------------------------------

ADAPTER_STATE_FIELDS = manifest.state_fields("adapter")

ADAPTER_GAUGES = manifest.gauge_names("adapter")


def test_state_and_metrics_export_adapter_gauges(smoke_url):
    """The adapter/tenant surface (ISSUE 7) must appear on /state and
    /metrics even with no adapters loaded (constant 0 / empty lists) —
    dashboards read these."""
    state = json.loads(asyncio.run(_get(smoke_url, "/state")))
    for field in ADAPTER_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for gauge in ADAPTER_GAUGES:
        assert gauge in text, f"/metrics lost {gauge}"


@pytest.mark.slow
def test_adapter_mix_changes_zero_hot_compiles():
    """Compile-on-hot-path tripwire for the adapter subsystem (ISSUE
    7): after warmup() (which pre-compiles the hot-load row scatters
    alongside the decode/prefill surface), traffic that admits a
    NON-RESIDENT adapter (hot load), switches the batch's adapter mix,
    mixes adapter and base slots, and forces an eviction+reload must
    add ZERO XLA compiles — one program family serves any mix. One
    64-token page keeps the decode bucket at the warmup size."""
    from aigw_tpu.models.lora import LoRAConfig, init_lora_adapters
    from aigw_tpu.tpuserve.adapters import AdapterStore

    spec_cfg = llama.TINY
    lora_cfg = LoRAConfig(rank=4, alpha=8.0, targets=("wq", "wv"))
    stacked = init_lora_adapters(jax.random.PRNGKey(5), spec_cfg,
                                 lora_cfg, 3, random_b=True)
    store = AdapterStore(n_slots=2)
    for i in range(3):
        store.register(f"ad{i}", {k: v[i] for k, v in stacked.items()})
    params = llama.init_params(jax.random.PRNGKey(0), spec_cfg)
    eng = Engine(params, spec_cfg, EngineConfig(
        max_batch_size=2, max_seq_len=256, page_size=64,
        min_prefill_bucket=16, decode_steps_per_tick=4,
        warm_prefill_buckets=2, enable_prefix_cache=False),
        adapter_store=store)
    eng.warmup()
    checkpoint = eng.compile_tracker.checkpoint()
    eng.start()
    try:
        # mixes: base-only, hot-load ad0, hot-load ad1, concurrent
        # ad0+base (LRU revival), then ad2 (evicts ad1) and ad1 again
        # (reloads over the parked ad0)
        for adapters in (("",), ("ad0",), ("ad1",), ("ad0", ""),
                         ("ad2",), ("ad1",)):
            events = []
            for ad in adapters:
                done = threading.Event()
                eng.submit(GenRequest(
                    prompt=[7, 8, 9], max_tokens=3,
                    sampling=SamplingParams(temperature=0.0),
                    emit=lambda t, f, d=done: d.set() if f else None,
                    adapter=ad))
                events.append(done)
            for e in events:
                assert e.wait(timeout=300)
        # ad0/ad1/ad2 first loads + ad1's reload after its eviction
        assert eng.stats.adapter_loads >= 4
        assert eng.stats.adapter_evictions >= 2
        assert eng.compile_tracker.compiles_since(checkpoint) == 0, (
            f"adapter-mix change paid an XLA compile after warmup: "
            f"{eng.compile_tracker.programs()}")
    finally:
        eng.stop()


def test_state_and_metrics_export_padding_fields(smoke_url):
    """The padding-tax + cold-start surface (ISSUE 6) must appear on
    /state and /metrics — a renamed EngineStats field silently drops
    the ragged backend's headline observable."""
    state = json.loads(asyncio.run(_get(smoke_url, "/state")))
    for field in RAGGED_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    assert state["attention_backend"] in ("xla-bucketed",
                                          "pallas-ragged")
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for gauge in RAGGED_GAUGES:
        assert gauge in text, f"/metrics lost {gauge}"


def test_ragged_backend_zero_hot_compiles_any_geometry():
    """Compile-on-hot-path tripwire for the ragged backend (ISSUE 6):
    after warmup() compiles the token-budget rung ladder, mixed-length
    admissions at ANY geometry under the warmed budget — lone short
    prompts, coalesced mixed bursts, totals crossing a budget boundary
    mid-sequence — must add ZERO XLA/Mosaic compiles. One 64-token
    page keeps the decode bucket at the warmup size, so any compile
    counted here is a real rung-ladder gap, not page-bucket growth."""
    spec_cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), spec_cfg)
    eng = Engine(params, spec_cfg, EngineConfig(
        max_batch_size=4, max_seq_len=64, page_size=64,
        min_prefill_bucket=16, decode_steps_per_tick=4,
        attention_backend="pallas-ragged", ragged_chunk_tokens=16,
        ragged_max_chunks=3, warm_prefill_buckets=1,
        enable_prefix_cache=False))
    assert eng.attn.name == "pallas-ragged"
    eng.warmup()
    assert eng.stats.warm_programs > 0
    assert eng.stats.warmup_ms > 0
    checkpoint = eng.compile_tracker.checkpoint()
    eng.start()
    try:
        # distinct geometries: lone tiny prompt, mixed burst, a burst
        # whose 88-token total crosses the 48-token budget twice
        # (mid-sequence continuations), and a repeat shape
        bursts = [
            [[7, 8, 9]],
            [[1, 2, 3, 4, 5], [9] * 17, [4] * 29],
            [[3] * 40, [5] * 31, [6] * 11, [7] * 6],
            [[2] * 23],
        ]
        for prompts in bursts:
            events = []
            for p in prompts:
                done = threading.Event()
                eng.submit(GenRequest(
                    prompt=p, max_tokens=4,
                    sampling=SamplingParams(temperature=0.0),
                    emit=lambda t, f, d=done: d.set() if f else None))
                events.append(done)
            for e in events:
                assert e.wait(timeout=300)
        assert eng.stats.prefill_tokens_padded > 0
        assert eng.compile_tracker.compiles_since(checkpoint) == 0, (
            f"ragged admissions paid a compile after warmup: "
            f"{eng.compile_tracker.programs()}")
    finally:
        eng.stop()


# prefill/decode disaggregation surface (ISSUE 8): a renamed field here
# silently breaks the gateway's migration orchestrator (polls
# migratable_slots)
MIGRATION_STATE_FIELDS = manifest.state_fields("migration")

MIGRATION_GAUGES = manifest.gauge_names("migration")


def test_state_and_metrics_export_migration_gauges(smoke_url):
    """The migration surface must appear on /state and /metrics even on
    a replica that has never migrated anything (constant 0)."""
    state = json.loads(asyncio.run(_get(smoke_url, "/state")))
    for field in MIGRATION_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for gauge in MIGRATION_GAUGES:
        assert gauge in text, f"/metrics lost {gauge}"


# grammar-constrained decoding surface (ISSUE 9): a renamed field here
# silently breaks the gateway's capability merge (constrained_decoding/capabilities),
# or the picker's measured memory signal (device_memory_frac)
CONSTRAINT_STATE_FIELDS = manifest.state_fields("constraint")

CONSTRAINT_GAUGES = manifest.gauge_names("constraint")

MEMORY_STATE_FIELDS = manifest.state_fields("memory")

MEMORY_GAUGES = (manifest.gauge_names("memory")
                 + manifest.EXTRA_METRICS["memory"])


def test_state_and_metrics_export_constraint_gauges(smoke_url):
    """The constrained-decoding surface must appear on /state and
    /metrics even when no constrained request has been served
    (constant 0 / capability flags)."""
    state = json.loads(asyncio.run(_get(smoke_url, "/state")))
    for field in CONSTRAINT_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    assert state["constrained_decoding"] is True
    assert state["capabilities"].get("tools") is True
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for gauge in CONSTRAINT_GAUGES:
        assert gauge in text, f"/metrics lost {gauge}"


def test_state_and_metrics_export_memory_signals(smoke_url):
    """The measured per-device memory signals (jax memory_stats() +
    KV-pool bytes) must appear on /state and /metrics — the picker's
    first measured signal must not silently rot. On CPU the jax bytes
    are 0; the KV-pool bytes must be real."""
    async def prime():
        # one chat so the engine has ticked and refreshed the gauges
        # (this test must hold even when run in isolation)
        async with aiohttp.ClientSession() as s:
            async with s.post(smoke_url + "/v1/chat/completions", json={
                "model": "tiny-random",
                "messages": [{"role": "user", "content": "mem smoke"}],
                "max_tokens": 2,
            }) as resp:
                assert resp.status == 200

    asyncio.run(prime())
    state = json.loads(asyncio.run(_get(smoke_url, "/state")))
    for field in MEMORY_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    assert state["kv_pool_bytes"] > 0
    assert 0.0 <= state["device_memory_frac"] <= 1.0
    # ISSUE 13 capacity fields: native bf16 default on the smoke
    # server — 16 bits/element, bytes/token = L*2*Hkv*D*2
    assert state["kv_quant_bits"] == 16
    assert state["kv_bytes_per_token"] > 0
    assert state["kv_cache_dtype"] == "bfloat16"
    assert state["decode_attn_impl"] == "xla-walk"
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for gauge in MEMORY_GAUGES:
        assert gauge in text, f"/metrics lost {gauge}"


# mesh serving surface (ISSUE 10): topology + per-device signals must
# export even on a single-device replica (empty axes, one device) so
# the picker's worst-device scoring degrades cleanly off-mesh
MESH_STATE_FIELDS = manifest.state_fields("mesh")

MESH_GAUGES = manifest.gauge_names("mesh")


def test_state_and_metrics_export_mesh_signals(smoke_url):
    """The mesh-serving surface on a SINGLE-device replica: topology
    empty, exactly one per-device entry carrying the full key set the
    per-device gauges render from, migration capability true (prefix
    cache on), and the decode-attn resolution fields populated."""
    from aigw_tpu.obs.metrics import DEVICE_GAUGES

    state = json.loads(asyncio.run(_get(smoke_url, "/state")))
    for field in MESH_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    assert state["mesh_axes"] == {}
    assert state["device_count"] == 1
    assert len(state["devices"]) == 1
    dev = state["devices"][0]
    for key, _name in DEVICE_GAUGES:
        assert key in dev, f"per-device entry lost {key}"
    assert state["param_bytes_total"] > 0
    assert state["param_bytes_per_device"]
    assert state["ici_bytes_per_token"] == 0  # unsharded: no ICI
    assert state["migration"] is True
    assert state["decode_attn_impl"] == "xla-walk"
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for gauge in MESH_GAUGES:
        assert gauge in text, f"/metrics lost {gauge}"
    # labeled per-device gauges render for every authoritative entry
    for _key, name in DEVICE_GAUGES:
        assert f'{name}{{device="' in text, f"/metrics lost {name}"


def test_device_gauges_map_matches_engine_device_stats():
    """Every DEVICE_GAUGES key must exist in the engine's per-device
    stats dicts — a renamed key silently drops a labeled gauge."""
    from aigw_tpu.models.registry import get_model_spec
    from aigw_tpu.obs.metrics import DEVICE_GAUGES

    spec = get_model_spec("tiny-random")
    params = llama.init_params(jax.random.PRNGKey(0), spec.config)
    eng = Engine(params, spec.config, EngineConfig(
        max_batch_size=2, max_seq_len=256, page_size=16,
        min_prefill_bucket=16))
    assert eng.device_stats, "per-device stats empty at construction"
    for dev in eng.device_stats:
        for key, _name in DEVICE_GAUGES:
            assert key in dev, (
                f"DEVICE_GAUGES key {key!r} missing from device_stats")


# KV memory hierarchy surface (ISSUE 11): a renamed field here silently
# breaks the gateway's fleet index (polls kv_chains) or the fleet-fetch
# presence probe
KVTIER_STATE_FIELDS = manifest.state_fields("kvtier")

KVTIER_GAUGES = manifest.gauge_names("kvtier")


def test_state_and_metrics_export_kvtier_gauges(smoke_url):
    """The KV-tier surface must appear on /state and /metrics even on a
    replica without a host tier configured (constant 0 / empty digest
    list — kv_chains still lists the RESIDENT chains)."""
    state = json.loads(asyncio.run(_get(smoke_url, "/state")))
    for field in KVTIER_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    assert isinstance(state["kv_chains"], list)
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for gauge in KVTIER_GAUGES:
        assert gauge in text, f"/metrics lost {gauge}"


@pytest.mark.slow
def test_kv_tier_churn_zero_hot_compiles():
    """Compile-on-hot-path tripwire for the KV memory hierarchy (ISSUE
    11): after warmup() compiled the page export/import programs and
    one suffix resume warmed the offset-resume prefill, a full
    spill→revive→resume churn cycle — evictions demoting pages to the
    host tier, a prefix hit promoting them back, the resumed prefill —
    must add ZERO XLA compiles."""
    spec_cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), spec_cfg)
    eng = Engine(params, spec_cfg, EngineConfig(
        max_batch_size=2, max_seq_len=256, page_size=16,
        min_prefill_bucket=16, num_pages=24, warm_prefill_buckets=4,
        # pre-compile the decode ladder + row scatters at every page
        # bucket this traffic reaches: admission-order-dependent
        # bucket growth must not masquerade as a tier compile
        warm_decode_buckets=4,
        kv_host_bytes=1 << 24))
    assert eng.host_tier is not None
    eng.start()
    eng.warmup()

    def run(prompt, mt=4):
        done = threading.Event()
        eng.submit(GenRequest(
            prompt=prompt, max_tokens=mt,
            sampling=SamplingParams(temperature=0.0),
            emit=lambda t, f, d=done: d.set() if f else None))
        assert done.wait(timeout=300)

    try:
        shared = [5] * 64
        run(shared + [9, 9])
        # warm the partial-hit suffix-resume program (first offset
        # resume compiles regardless of the tier — PR 3 behavior) and
        # the flood geometry's prefill/row-update shapes: the compiles
        # under test must be the TIER's, not first-use page-bucket
        # growth the flood itself would pay tier or no tier
        run(shared + [9, 9])
        run([200] * 48 + [1], mt=2)
        checkpoint = eng.compile_tracker.checkpoint()
        # churn: flood evicts + spills the shared chain, the re-ask
        # revives it and resumes
        for i in range(14):
            run([10 + i] * 48 + [1], mt=2)
        assert eng.host_tier.spills > 0, "flood never spilled"
        run(shared + [9, 9])
        assert eng.host_tier.revives > 0, "re-ask never revived"
        assert eng.compile_tracker.compiles_since(checkpoint) == 0, (
            f"KV-tier churn paid a compile after warmup: "
            f"{eng.compile_tracker.programs()}")
    finally:
        eng.stop()


# fleet observability surface (ISSUE 12): a renamed field here silently
# blinds the gateway's fleet aggregator — replica identity feeds the
# restart-detecting health ring, ttft_hist_buckets feeds the live SLO
# burn-rate monitor (obs/slomon.py)
FLEETOBS_STATE_FIELDS = manifest.state_fields("fleetobs")


def test_state_exports_fleet_identity_and_ttft_buckets(smoke_url):
    """Replica identity/uptime + the cumulative TTFT bucket dict must
    export on /state, and the bucket dict must agree with the phase
    histogram the /metrics exposition renders (same cumulative counts,
    same ladder, +Inf included)."""
    from aigw_tpu.obs.metrics import PHASE_BUCKETS_MS
    from aigw_tpu.obs.slomon import parse_hist_buckets

    state = json.loads(asyncio.run(_get(smoke_url, "/state")))
    for field in FLEETOBS_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    assert len(state["replica_id"]) >= 8
    assert state["uptime_s"] > 0
    buckets = state["ttft_hist_buckets"]
    assert set(buckets) == {f"{b:g}" for b in PHASE_BUCKETS_MS} | {
        "+Inf"}
    # cumulative: monotone along the ladder
    ladder = [buckets[f"{b:g}"] for b in PHASE_BUCKETS_MS]
    assert ladder == sorted(ladder)
    assert buckets["+Inf"] >= ladder[-1]
    # and consistent with the /metrics histogram (no traffic runs
    # between the two fetches in this test, so counts are identical)
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    rendered = parse_hist_buckets(text, "tpuserve_ttft_hist_ms")
    assert rendered == buckets


# MoE serving surface (ISSUE 18): the scalar routing gauges export
# everywhere (constant 0 on dense families) so dashboards and the
# picker's imbalance term never hit a missing key; the labeled
# per-expert/per-layer twins render only on MoE families
MOE_STATE_FIELDS = manifest.state_fields("moe")

MOE_GAUGES = manifest.gauge_names("moe")


def test_state_and_metrics_export_moe_gauges(smoke_url):
    """The MoE surface on a DENSE replica: every scalar field/gauge
    present (constant 0), the per-expert/per-layer lists empty, and
    the labeled twins absent (zero rendered bytes) — the drift
    contract still covers them via render_moe_gauges below."""
    state = json.loads(asyncio.run(_get(smoke_url, "/state")))
    for field in MOE_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    assert state["moe_tokens_routed"] == 0
    assert state["moe_dropped_frac"] == 0.0
    assert state["moe_expert_imbalance"] == 0.0
    assert state["moe_expert_load"] == []
    assert state["moe_layer_drops"] == []
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for gauge in MOE_GAUGES:
        assert gauge in text, f"/metrics lost {gauge}"
    for labeled in manifest.EXTRA_METRICS["moe"]:
        assert labeled not in text, (
            f"dense replica rendered MoE labeled gauge {labeled}")


def test_moe_labeled_gauges_render_for_moe_accumulators():
    """render_moe_gauges (the labeled /metrics twins of the /state
    moe_expert_load / moe_layer_drops lists) must carry every
    EXTRA_METRICS['moe'] substring the MoE drift group asserts on —
    same index order as the lists."""
    from aigw_tpu.obs.metrics import render_moe_gauges

    text = render_moe_gauges([5, 9, 2, 0], [1, 0]).decode()
    for labeled in manifest.EXTRA_METRICS["moe"]:
        assert labeled in text, f"render_moe_gauges lost {labeled}"
    assert 'tpuserve_moe_expert_load{expert="1"} 9' in text
    assert 'tpuserve_moe_layer_drops{layer="0"} 1' in text
    assert render_moe_gauges([], []) == b""


def test_fleet_gauges_map_matches_rollup():
    """Every FLEET_GAUGES key must exist in FleetState.rollup() output
    — a renamed rollup key silently drops an aggregate gauge from the
    /fleet/metrics federation scrape."""
    from aigw_tpu.gateway.picker import Endpoint, EndpointPicker
    from aigw_tpu.obs.metrics import FLEET_GAUGES, render_fleet_gauges

    p = EndpointPicker([Endpoint("a:1")])
    p.observe("a:1", kv_occupancy=0.2, max_slots=4)
    rollup = p.fleet.rollup(p.state)
    for key, _name in FLEET_GAUGES:
        assert key in rollup, f"rollup missing FLEET_GAUGES key {key}"
    text = render_fleet_gauges(rollup).decode()
    for _key, name in FLEET_GAUGES:
        assert name in text, f"render_fleet_gauges lost {name}"


# engine-truth usage metering surface (ISSUE 20): the tpuserve_meter_*
# counters are the reconciliation baseline the gateway ledger is audited
# against — a renamed field silently breaks exact cost attribution
METER_STATE_FIELDS = manifest.state_fields("meter")

METER_GAUGES = manifest.gauge_names("meter")


def test_state_and_metrics_export_meter_gauges(smoke_url):
    """Every tpuserve_meter_* counter must appear on /state and
    /metrics, and after at least one completed request the record
    counter and decode-token counter must have moved — the engine is
    the metering source of truth, so a dead counter means the whole
    ledger under-bills silently."""

    async def main():
        # one chat first so the counters are live, not just defaults
        async with aiohttp.ClientSession() as s:
            async with s.post(smoke_url + "/v1/chat/completions", json={
                "model": "tiny-random",
                "messages": [{"role": "user",
                              "content": "smoke meter state " * 3}],
                "max_tokens": 2,
            }) as resp:
                assert resp.status == 200
        return json.loads(await _get(smoke_url, "/state"))

    state = asyncio.run(main())
    for field in METER_STATE_FIELDS:
        assert field in state, f"/state lost {field}"
    assert state["meter_records"] >= 1
    assert state["meter_decode_tokens"] >= 1
    assert state["meter_prefill_tokens"] >= 1
    assert state["meter_hbm_page_byte_s"] >= 0.0
    text = asyncio.run(_get(smoke_url, "/metrics")).decode()
    for gauge in METER_GAUGES:
        assert gauge in text, f"/metrics lost {gauge}"


def test_usage_gauges_map_matches_ledger_snapshot():
    """Every USAGE_GAUGES key must exist in UsageLedger.snapshot()
    output — a renamed snapshot key silently drops an aigw_usage_*
    family from the gateway /metrics exposition (the staticcheck
    gauge-drift pass enforces the same contract on literal keys)."""
    from aigw_tpu.gateway.usage import UsageLedger
    from aigw_tpu.obs.metrics import USAGE_GAUGES, render_usage_gauges

    led = UsageLedger(window_s=60.0)
    snap = led.snapshot()
    for key, _name in USAGE_GAUGES:
        assert key in snap, f"snapshot missing USAGE_GAUGES key {key}"
    text = render_usage_gauges(snap).decode()
    for _key, name in USAGE_GAUGES:
        assert name in text, f"render_usage_gauges lost {name}"
