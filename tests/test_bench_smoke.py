"""Bench-harness smoke: one short engine bench iteration runs in tier-1.

Hot-path regressions (engine hangs, broken pipelining, phase-stat
plumbing) previously only surfaced at round-end when the driver ran the
full bench.py capture. This marker-tagged smoke runs the same harness
functions on the tiny model for a few seconds so tier-1 catches them.
Run just this layer with ``pytest -m bench_smoke``.
"""

from __future__ import annotations

import jax
import pytest

import bench
from aigw_tpu.models import llama
from aigw_tpu.models.registry import get_model_spec


@pytest.mark.bench_smoke
def test_bench_engine_iteration_smoke():
    spec = get_model_spec("tiny-random")
    params = llama.init_params(jax.random.PRNGKey(0), spec.config)
    raw = bench.raw_ceiling_tokens_per_sec(
        params, spec.config, batch=2, prompt_len=16, k_steps=4)
    assert raw > 0
    runs, phases = bench.engine_numbers(
        params, spec.config, batch=2, prompt_len=16, gen_tokens=8,
        k_steps=4, reps=1)
    assert len(runs) == 1
    tps, ttft_p50 = runs[0]
    assert tps > 0
    assert ttft_p50 > 0
    # the phase breakdown the bench JSON line now carries must be live
    assert set(phases) == {"prefill_ms", "transfer_ms", "emit_ms",
                           "first_emit_ms"}
    assert phases["prefill_ms"] > 0
    assert phases["emit_ms"] >= 0
    # TTFT regression tripwire (no full bench run needed): the
    # first-token phase must be live and SMALL — the fast path's whole
    # point is that the host residual between a prefill's sampled token
    # and its emit callback is a sliver of the prefill itself. A
    # pipeline regression that re-routes token 0 through a decode
    # window or adds host work here blows this ratio long before it
    # shows in a round-end capture.
    assert phases["first_emit_ms"] > 0
    assert phases["first_emit_ms"] < phases["prefill_ms"]
    # sanity ceiling: nothing in a 2-request tiny-model rep legitimately
    # spends a second on first-token emission
    assert phases["first_emit_ms"] < 1000.0


@pytest.mark.bench_smoke
def test_bench_median_and_spread_helpers():
    assert bench._median([3.0, 1.0, 2.0]) == 2.0
    assert bench._spread([]) == 0.0
    assert bench._spread([1.0, 1.0, 1.0]) == 0.0


@pytest.mark.bench_smoke
def test_bench_mfu_analytical():
    """The mfu field's FLOPs accounting: ≈ 2×(matmul params) at zero
    context, plus the attention term; scales linearly with tok/s."""
    spec = get_model_spec("tiny-random")
    cfg = spec.config
    f0 = bench.model_flops_per_token(cfg, 0)
    hd = cfg.head_dim
    per_layer = (cfg.dim * cfg.n_heads * hd
                 + 2 * cfg.dim * cfg.n_kv_heads * hd
                 + cfg.n_heads * hd * cfg.dim
                 + 3 * cfg.dim * cfg.ffn_dim)
    assert f0 == 2.0 * (cfg.n_layers * per_layer
                        + cfg.dim * cfg.vocab_size)
    # attention term grows with context
    assert bench.model_flops_per_token(cfg, 512) > f0
    # mfu is linear in throughput and normalized by the peak listed
    # for the device kind the rate was measured on
    m1 = bench.model_mfu(cfg, 100.0, 128, "TPU v5 lite")
    assert m1 > 0
    assert abs(bench.model_mfu(cfg, 200.0, 128, "TPU v5 lite")
               - 2 * m1) < 1e-12
    # a kind with no listed peak is an error, never a default: a CPU
    # run cannot print a utilization
    with pytest.raises(ValueError, match="cpu"):
        bench.model_mfu(cfg, 100.0, 128, jax.devices()[0].device_kind)


@pytest.mark.bench_smoke
def test_bench_spec_ab_fields():
    """The --ab spec_decode JSON derives its acceptance telemetry from
    /state deltas through this pure helper: spec_accept_rate must be
    present and sane (in [0, 1]), accepted_per_step must reflect
    multi-token emission, and a regression that renames the /state
    fields shows up here instead of at round-end."""
    st0 = {"spec_drafted": 100, "spec_accepted": 40,
           "decode_steps": 50, "tokens_generated": 60,
           "state_rebuilds": 0}
    st1 = {"spec_drafted": 300, "spec_accepted": 220,
           "decode_steps": 150, "tokens_generated": 310,
           "state_rebuilds": 0}
    f = bench._spec_ab_fields(st0, st1)
    assert f["drafted_tokens"] == 200
    assert f["spec_accept_rate"] == 0.9
    assert 0.0 <= f["spec_accept_rate"] <= 1.0
    assert f["accepted_per_step"] == 2.5  # > 1: drafts actually landed
    assert f["spec_state_rebuilds"] == 0
    # empty capture degrades to zeros, never a ZeroDivisionError
    z = bench._spec_ab_fields(st1, st1)
    assert z["spec_accept_rate"] == 0.0 and z["accepted_per_step"] == 0.0


@pytest.mark.bench_smoke
def test_bench_spec_engine_stats_live():
    """A short speculative engine run on the tiny model: the stats the
    A/B leg consumes (drafted/accepted/accept_rate) must be live and
    the speculative path must not rebuild device state."""
    import threading

    from aigw_tpu.tpuserve.engine import Engine, EngineConfig, GenRequest
    from aigw_tpu.tpuserve.sampling import SamplingParams

    spec = get_model_spec("tiny-random")
    params = llama.init_params(jax.random.PRNGKey(0), spec.config)
    eng = Engine(params, spec.config, EngineConfig(
        max_batch_size=2, max_seq_len=128, page_size=16,
        min_prefill_bucket=16, decode_steps_per_tick=4, spec_tokens=4))
    eng.start()
    try:
        done = threading.Event()
        eng.submit(GenRequest(
            prompt=[1, 2, 3], max_tokens=16,
            sampling=SamplingParams(temperature=0.0,
                                    logit_bias=((7, 100.0),)),
            emit=lambda t, f: done.set() if f else None))
        assert done.wait(timeout=300)
        assert eng.stats.spec_drafted > 0
        assert eng.stats.spec_accepted > 0
        assert 0.0 < eng.stats.spec_accept_rate <= 1.0
        assert eng.stats.state_rebuilds == 0
    finally:
        eng.stop()


@pytest.mark.bench_smoke
def test_bench_ragged_ab_fields():
    """The --ab ragged_prefill JSON derives its padding-tax + compile
    telemetry from /state deltas through this pure helper: padded_frac
    must come from the token-counter deltas (not absolutes), warmup
    fields pass through, and an empty capture degrades to zeros."""
    st0 = {"prefill_tokens_real": 1000, "prefill_tokens_padded": 1200,
           "xla_compiles": 7, "warm_programs": 11, "warmup_ms": 900.0}
    st1 = {"prefill_tokens_real": 2509, "prefill_tokens_padded": 2736,
           "xla_compiles": 7, "warm_programs": 11, "warmup_ms": 900.0}
    f = bench._ragged_ab_fields(st0, st1, "ragged")
    assert f["ragged_prefill_tokens"] == 1509
    assert f["ragged_padded_frac"] == round(1.0 - 1509 / 1536, 4)
    assert f["ragged_hot_compiles"] == 0
    assert f["ragged_warm_programs"] == 11
    assert f["ragged_warmup_ms"] == 900.0
    z = bench._ragged_ab_fields(st1, st1, "b")
    assert z["b_padded_frac"] == 0.0 and z["b_prefill_tokens"] == 0


@pytest.mark.bench_smoke
def test_bench_mesh_ab_fields():
    """The --ab mesh JSON derives its memory-split + compile telemetry
    from /state deltas through this pure helper: the split fraction is
    worst-device bytes × devices ÷ total (1.0 = perfect total/tp
    split — the ±10% claim checks this field), hot compiles are the
    xla-counter delta, and an empty capture degrades to zeros."""
    st0 = {"xla_compiles": 9}
    st1 = {"xla_compiles": 9, "mesh_devices": 8,
           "param_bytes_total": 800,
           "param_bytes_per_device": {str(i): 100 for i in range(8)},
           "ici_bytes_per_token": 3584}
    f = bench._mesh_ab_fields(st0, st1, "mesh")
    assert f["mesh_devices"] == 8
    assert f["mesh_param_bytes_total"] == 800
    assert f["mesh_param_bytes_per_device_max"] == 100
    assert f["mesh_param_split_frac"] == 1.0
    assert f["mesh_hot_compiles"] == 0
    assert f["mesh_ici_bytes_per_token"] == 3584
    # a skewed split prices the worst device, not the mean
    skew = dict(st1, param_bytes_per_device={
        "0": 200, **{str(i): 600 / 7 for i in range(1, 8)}})
    assert bench._mesh_ab_fields(st0, skew, "m")["m_param_split_frac"] \
        == 2.0
    z = bench._mesh_ab_fields({}, {}, "z")
    assert z["z_param_split_frac"] == 0.0 and z["z_devices"] == 1


@pytest.mark.bench_smoke
def test_bench_lora_ab_fields():
    """The --ab lora JSON derives its adapter-subsystem telemetry from
    /state deltas through this pure helper: load/eviction counters must
    be capture deltas (not absolutes), residency is the current count,
    and hot compiles come from the xla counter delta."""
    st0 = {"adapter_loads": 4, "adapter_evictions": 0,
           "adapters_resident": ["t0", "t1", "t2", "t3"],
           "xla_compiles": 12}
    st1 = {"adapter_loads": 7, "adapter_evictions": 3,
           "adapters_resident": ["t0", "t1", "t3", "t4"],
           "xla_compiles": 12}
    f = bench._lora_ab_fields(st0, st1)
    assert f["adapter_loads"] == 3
    assert f["adapter_evictions"] == 3
    assert f["adapters_resident"] == 4
    assert f["lora_hot_compiles"] == 0
    z = bench._lora_ab_fields(st1, st1)
    assert z["adapter_loads"] == 0 and z["adapter_evictions"] == 0


@pytest.mark.bench_smoke
def test_bench_openloop_trace_and_goodput_helpers():
    """Pure helpers behind the open-loop legs (ISSUE 8): the seeded
    Poisson trace is deterministic and shaped right, histogram parsing
    survives OpenMetrics exemplar suffixes, and goodput derives from
    cumulative bucket deltas (shed requests count against goodput)."""
    t1 = bench._poisson_trace(seed=7, n=20, rate_hz=5.0,
                              tenants=("a", "b"))
    t2 = bench._poisson_trace(seed=7, n=20, rate_hz=5.0,
                              tenants=("a", "b"))
    assert t1 == t2  # same seed → same trace (the A/B contract)
    assert len(t1) == 20
    assert all(t1[i]["at"] <= t1[i + 1]["at"] for i in range(19))
    assert {it["tenant"] for it in t1} <= {"a", "b"}
    assert bench._poisson_trace(seed=8, n=20, rate_hz=5.0) != t1

    text = (
        'tpuserve_ttft_hist_ms_bucket{le="100"} 3 # {trace_id="ab"} 42\n'
        'tpuserve_ttft_hist_ms_bucket{le="250"} 7\n'
        'tpuserve_ttft_hist_ms_bucket{le="+Inf"} 9\n'
        "tpuserve_ttft_hist_ms_sum 1234\n")
    h1 = bench._parse_hist_buckets(text, "tpuserve_ttft_hist_ms")
    assert h1 == {"100": 3, "250": 7, "+Inf": 9}
    h0 = {"100": 1, "250": 1, "+Inf": 1}
    g = bench._goodput_fields(h0, h1, slo_ms=250.0, arrivals=10,
                              shed=2, prefix="x")
    assert g["x_served"] == 8
    assert g["x_under_slo"] == 6  # Δ of the 250 bucket
    assert g["x_shed"] == 2
    assert g["x_goodput"] == 0.6  # under_slo / ARRIVALS, not served
    z = bench._goodput_fields(h1, h1, 250.0, 0, 0, "z")
    assert z["z_goodput"] == 0.0  # empty capture, no ZeroDivisionError


@pytest.mark.bench_smoke
@pytest.mark.slow
def test_bench_openloop_gateway_smoke():
    """Open-loop smoke (ISSUE 8 satellite): ~50 Poisson arrivals
    through a real gateway (picker over one tpuserve child) — the
    load generator and its goodput fields must stay live between bench
    rounds, and SLO shedding must return 429 + Retry-After."""
    import asyncio
    import threading

    import aiohttp
    from aiohttp import web

    from aigw_tpu.config.model import Config
    from aigw_tpu.config.runtime import RuntimeConfig
    from aigw_tpu.gateway.server import run_gateway
    from aigw_tpu.tpuserve.engine import EngineConfig
    from aigw_tpu.tpuserve.server import TPUServeServer

    holder = {}
    started = threading.Event()

    def run_replica():
        async def main():
            server = TPUServeServer(
                "tiny-random",
                EngineConfig(max_batch_size=2, max_seq_len=256,
                             page_size=16, min_prefill_bucket=16,
                             decode_steps_per_tick=2))
            runner = web.AppRunner(server.app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            holder["addr"] = (
                f"127.0.0.1:{site._server.sockets[0].getsockname()[1]}")
            holder["loop"] = asyncio.get_running_loop()
            started.set()
            await asyncio.Event().wait()

        try:
            asyncio.run(main())
        except RuntimeError:
            pass

    t = threading.Thread(target=run_replica, daemon=True)
    t.start()
    assert started.wait(timeout=300)
    addr = holder["addr"]

    async def main():
        cfg = Config.parse({
            "version": "v1",
            "backends": [{"name": "pool", "schema": "OpenAI",
                          "endpoints": [addr],
                          "picker_poll_interval": 0.2,
                          "picker_mode": "slo",
                          "slo_ttft_ms": 60000.0}],
            "routes": [{"name": "bench",
                        "rules": [{"backends": ["pool"]}]}],
            "models": ["tiny-random"],
        })
        server, runner = await run_gateway(RuntimeConfig.build(cfg),
                                           port=0)
        site = list(runner.sites)[0]
        gw = f"http://127.0.0.1:{site._server.sockets[0].getsockname()[1]}"
        try:
            picker = server._pickers["pool"]
            for _ in range(100):
                if picker.state[addr].healthy:
                    break
                await asyncio.sleep(0.1)
            async with aiohttp.ClientSession() as s:
                trace = bench._poisson_trace(
                    seed=3, n=50, rate_hz=25.0,
                    prompt_lens=(24, 48), gen_lens=(2, 3),
                    tenants=("", "tA"))
                h0 = await bench._ttft_hists(s, [f"http://{addr}"])
                res = await bench._drive_openloop(
                    s, gw, "tiny-random", trace, tag="sm")
                h1 = await bench._ttft_hists(s, [f"http://{addr}"])
                g = bench._goodput_fields(h0, h1, slo_ms=60000.0,
                                          arrivals=len(trace),
                                          shed=res["shed"], prefix="ol")
                # the generator drove real load and the fields are live
                assert res["errors"] == 0, res
                assert res["completed"] + res["shed"] == 50
                assert g["ol_served"] >= res["completed"]
                assert set(g) == {"ol_arrivals", "ol_served", "ol_shed",
                                  "ol_under_slo", "ol_goodput"}
                assert g["ol_goodput"] > 0.0  # a 60s SLO is met on CPU

                # force the shed path: with live histograms and an
                # absurd 0.01ms SLO every prediction is blown → every
                # request sheds with 429 + Retry-After
                picker.slo_ttft_ms = 0.01
                shed_trace = bench._poisson_trace(
                    seed=4, n=6, rate_hz=50.0, prompt_lens=(24,),
                    gen_lens=(2,))
                res2 = await bench._drive_openloop(
                    s, gw, "tiny-random", shed_trace, tag="sh")
                assert res2["shed"] >= 1, res2
                assert res2["shed_retry_after"] == res2["shed"], (
                    "shed responses must carry Retry-After")
        finally:
            await runner.cleanup()
            holder["loop"].call_soon_threadsafe(holder["loop"].stop)

    asyncio.run(main())


@pytest.mark.bench_smoke
def test_bench_structured_ab_fields():
    """The --ab structured JSON derives its constraint telemetry from
    /state deltas through this pure helper: request/rollback/mask
    counters must be deltas, the hot-compile tripwire a delta of
    xla_compiles, and a renamed /state field shows up here instead of
    at round-end."""
    st0 = {"constraint_requests": 2, "constraint_rollbacks": 10,
           "constraint_mask_updates": 40, "xla_compiles": 30,
           "constraint_grammars": 1}
    st1 = {"constraint_requests": 8, "constraint_rollbacks": 64,
           "constraint_mask_updates": 300, "xla_compiles": 30,
           "constraint_grammars": 2}
    f = bench._structured_ab_fields(st0, st1)
    assert f["structured_requests"] == 6
    assert f["structured_rollbacks"] == 54
    assert f["structured_mask_updates"] == 260
    assert f["structured_hot_compiles"] == 0
    assert f["structured_grammars"] == 2
    # a missing field degrades to 0, never a KeyError at round-end
    z = bench._structured_ab_fields({}, {})
    assert z["structured_requests"] == 0


@pytest.mark.bench_smoke
def test_bench_structured_schema_is_bounded_and_validates():
    """The leg's schema must structurally bound the output below the
    constrained max_tokens (otherwise length-truncation breaks the
    100%-valid criterion by construction) and the leg's validator must
    accept exactly the emitted shape."""
    schema = bench._STRUCT_SCHEMA
    ml = schema["properties"]["report"]["maxLength"]
    worst = len('{"report":""}') + ml
    assert worst < bench._STRUCT_MAX
    assert bench._STRUCT_GEN == worst + 1  # matched plain token volume
    assert bench._struct_valid('{"report":"' + "a" * ml + '"}')
    assert not bench._struct_valid('{"report":123}')
    assert not bench._struct_valid('{"report":"' + "a" * 99 + '"}')
    assert not bench._struct_valid("not json")


@pytest.mark.bench_smoke
def test_bench_kvtier_ab_fields():
    """The --ab kv_tier JSON derives its spill/revive/fetch telemetry
    from /state deltas through this pure helper (ISSUE 11): every
    field must be a capture DELTA (counters are cumulative on the
    replica), the hot-compile tripwire is the xla counter delta, and
    an empty capture degrades to zeros."""
    st0 = {"kv_spills": 10, "kv_revives": 2, "kv_fetches_in": 1,
           "kv_fetches_out": 4, "kv_fetch_pages_in": 5,
           "kv_fetch_pages_out": 20, "xla_compiles": 50}
    st1 = {"kv_spills": 18, "kv_revives": 6, "kv_fetches_in": 3,
           "kv_fetches_out": 4, "kv_fetch_pages_in": 15,
           "kv_fetch_pages_out": 20, "xla_compiles": 50}
    f = bench._kvtier_ab_fields(st0, st1, "kvt")
    assert f["kvt_spills"] == 8
    assert f["kvt_revives"] == 4
    assert f["kvt_fetches_in"] == 2
    assert f["kvt_fetches_out"] == 0
    assert f["kvt_fetch_pages_in"] == 10
    assert f["kvt_fetch_pages_out"] == 0
    assert f["kvt_hot_compiles"] == 0
    # a compile during the capture window trips the field
    assert bench._kvtier_ab_fields(
        st0, dict(st1, xla_compiles=52), "k")["k_hot_compiles"] == 2
    z = bench._kvtier_ab_fields({}, {}, "z")
    assert all(v == 0 for v in z.values())


@pytest.mark.bench_smoke
def test_bench_fleet_obs_fields():
    """Fleet observability fields (ISSUE 12): the --ab legs flatten a
    gateway /fleet/state payload (and, for gateway-less legs, raw
    replica states) into the bench JSON line through these pure
    helpers — BENCH_r* captures then carry fleet-level telemetry."""
    snap = {
        "ts": 1.0,
        "decisions_recorded": 42,
        "fleet": {"replicas_up": 2, "replicas_degraded": 1,
                  "replicas_down": 0, "slots_free": 3,
                  "slots_total": 8, "kv_occupancy_worst": 0.6,
                  "device_memory_frac_worst": 0.4},
        "backends": {"pool": {
            "slo": {"goodput": 0.9, "burn_rate": 2.0,
                    "sustained_overshoot": True},
            "replicas": {
                "h:1": {"health": {"state": "up"}},
                "h:2": {"health": {"state": "degraded"}},
            }}},
    }
    f = bench._fleet_obs_fields(snap, "fx")
    assert f["fx_replicas_up"] == 2
    assert f["fx_replicas_degraded"] == 1
    assert f["fx_slots_free"] == 3
    assert f["fx_kv_occupancy_worst"] == 0.6
    assert f["fx_goodput"] == 0.9
    assert f["fx_burn_rate"] == 2.0
    assert f["fx_overshoot_sustained"] is True
    assert f["fx_health"] == {"h:1": "up", "h:2": "degraded"}
    assert f["fx_decisions"] == 42
    # an empty snapshot degrades to sentinels, not KeyErrors
    z = bench._fleet_obs_fields({}, "z")
    assert z["z_replicas_up"] == 0 and z["z_goodput"] == -1.0

    # gateway-less legs: burn/goodput from raw /state bucket deltas
    st0 = {"a": {"ttft_hist_buckets": {"500": 2, "+Inf": 3}},
           "b": {"ttft_hist_buckets": {"500": 1, "+Inf": 1}}}
    st1 = {"a": {"ttft_hist_buckets": {"500": 8, "+Inf": 11},
                 "kv_occupancy": 0.5, "max_slots": 2},
           "b": {"ttft_hist_buckets": {"500": 4, "+Inf": 4},
                 "kv_occupancy": 0.2, "max_slots": 4}}
    g = bench._fleet_fields_from_states(st0, st1, slo_ms=1000.0,
                                        prefix="kf")
    assert g["kf_served"] == 11  # (11-3) + (4-1)
    assert g["kf_goodput"] == round(9 / 11, 4)  # under: (8-2)+(4-1)
    assert g["kf_kv_occupancy_worst"] == 0.5
    assert g["kf_slots_total"] == 6
    # empty window: the -1 sentinel, not a ZeroDivisionError
    e = bench._fleet_fields_from_states(st1, st1, 1000.0, "e")
    assert e["e_goodput"] == -1.0 and e["e_burn_rate"] == -1.0
