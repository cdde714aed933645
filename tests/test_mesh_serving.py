"""Mesh-native serving at parity (ISSUE 10).

The tensor-parallel engine must be the SAME engine: in the
deterministic f32 rig, a tp=8 mesh over 8 virtual CPU devices (the
suite-wide conftest sets ``--xla_force_host_platform_device_count=8``
before jax initializes — the same topology the driver's
``dryrun_multichip`` uses) must stream BYTE-IDENTICAL tokens to a single-device engine across
the whole mixed-feature batch — greedy, seeded sampling, repetition
penalties, speculating slots, prefix-cache resume, and a
grammar-constrained slot — with ZERO pipeline-draining state rebuilds
and ZERO hot-path XLA compiles after warmup.

Plus the mesh observability surface: real per-device parameter/KV
bytes on /state, the worst-device memory fraction, the analytical ICI
bytes/token counter, the migration capability flag, and sharded-pool
page migration (export gathers all head shards; import re-shards on
write) proving the wire format is layout-independent.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import llama
from aigw_tpu.parallel import MeshSpec, make_mesh
from aigw_tpu.tpuserve import constrain
from aigw_tpu.tpuserve.engine import (
    Engine,
    EngineConfig,
    GenRequest,
    MigrationError,
    continuation_request,
)
from aigw_tpu.tpuserve.sampling import SamplingParams
from aigw_tpu.tpuserve.tokenizer import ByteTokenizer

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 (virtual) devices")

#: n_kv_heads divisible by tp=8 → the paged KV pool shards one head per
#: virtual device; head_dim 8 keeps every projection divisible too
_CFG = llama.LlamaConfig(
    vocab_size=512, dim=64, n_layers=2, n_heads=8, n_kv_heads=8,
    ffn_dim=128, max_seq_len=256, rope_theta=10000.0,
)
_PARAMS_F32 = llama.init_params(jax.random.PRNGKey(7), _CFG, jnp.float32)
_TOK = ByteTokenizer()

_RNG = np.random.RandomState(23)
_PROMPTS = {L: _RNG.randint(1, 500, L).tolist()
            for L in (9, 24, 40, 60, 90)}


def _mk_engine(mesh: bool, **over) -> Engine:
    cfg = dict(max_batch_size=4, max_seq_len=256, page_size=16,
               min_prefill_bucket=16, decode_steps_per_tick=4,
               kv_cache_dtype="float32", spec_tokens=4,
               adaptive_decode_window=False)
    cfg.update(over)
    return Engine(
        _PARAMS_F32, _CFG, EngineConfig(**cfg),
        eos_token_ids=(_TOK.eos_id,),
        mesh=make_mesh(MeshSpec(dp=1, tp=8)) if mesh else None)


def _burst(eng: Engine, reqs: list[tuple[list, SamplingParams, object]],
           n: int = 8) -> list[list[int]]:
    """Submit (prompt, sampling, constraint) triples together, wait."""
    events, results = [], []
    for prompt, sp, cn in reqs:
        done = threading.Event()
        toks: list[int] = []

        def emit(t, f, toks=toks, done=done):
            if t >= 0:
                toks.append(t)
            if f is not None:
                done.set()

        eng.submit(GenRequest(prompt=prompt, max_tokens=n, sampling=sp,
                              emit=emit, constraint=cn))
        events.append(done)
        results.append(toks)
    for e in events:
        assert e.wait(timeout=900)
    return results


def _fsm():
    schema = {"type": "object", "properties": {
        "t": {"type": "string", "maxLength": 8},
    }, "required": ["t"], "additionalProperties": False}
    return constrain.compile_constraint(
        _TOK, _CFG.vocab_size, (_TOK.eos_id,),
        constrain.spec_for_response_format("json_schema", schema))


@pytest.fixture(scope="module")
def pair():
    """(single, mesh) f32 engines, speculation on — every equivalence
    case in this module runs the same traffic through both."""
    engines = [_mk_engine(False), _mk_engine(True)]
    for e in engines:
        e.start()
    try:
        yield engines
    finally:
        for e in engines:
            e.stop()


def _greedy(**kw) -> SamplingParams:
    return SamplingParams(temperature=0.0, **kw)


def test_mixed_batch_byte_identical_mesh_vs_single(pair):
    """The acceptance-criteria batch: two coalesced bursts covering
    greedy, seeded sampling, repetition penalties, a speculating slot,
    a prefix-cache resume, a logit-biased slot, and a grammar-
    constrained slot — token streams must match the single-device
    engine byte for byte, and the mesh path must stay rebuild-free
    (incremental [B,V]-row scatters survive sharding)."""
    base = _PROMPTS[90]
    resumed = base[:48] + _PROMPTS[24][:10]
    rep = [5, 6, 7, 8] * 14  # n-gram friendly → drafts propose

    out = {}
    for eng in pair:
        first = _burst(eng, [
            (base, _greedy(), None),                       # seeds cache
            (rep, _greedy(), None),                        # speculating
            (_PROMPTS[40], SamplingParams(
                temperature=0.8, top_p=0.9, seed=1234), None),
            (_PROMPTS[60], _greedy(frequency_penalty=0.7), None),
        ])
        second = _burst(eng, [
            (resumed, _greedy(), None),                    # partial hit
            (_TOK.encode("mesh json"), _greedy(), _fsm()),  # constrained
            (_PROMPTS[9], _greedy(), None),
            (_PROMPTS[24], _greedy(logit_bias=((42, 3.0),)), None),
        ], n=16)
        out[eng.mesh is not None] = first + second
        assert eng.healthy, eng.last_error
        assert eng.stats.prefix_cache_hits >= 1, "resume not taken"
        assert eng.stats.constraint_requests >= 1
        assert eng.stats.spec_drafted > 0, "no drafts proposed"
    assert out[True] == out[False]
    mesh_eng = pair[1]
    assert mesh_eng.stats.state_rebuilds == 0
    assert mesh_eng.stats.device_count == 8
    assert mesh_eng.mesh_axes().get("tp") == 8


def test_param_and_kv_bytes_split_across_devices(pair):
    """Measured memory split: every device holds ≈ total/8 of the
    parameters and exactly 1/8 of the head-sharded KV pool (n_kv_heads
    8 ÷ tp 8) — the /state signal of the memory split."""
    single, mesh = pair
    per = mesh.param_bytes_by_device
    assert len(per) == 8
    total = sum(per.values())
    for b in per.values():
        assert abs(b * 8 - total) / total < 0.10, per
    # the mesh total exceeds the single-device total only by the
    # replicated norm vectors (tiny — everything matmul-shaped shards)
    single_total = sum(single.param_bytes_by_device.values())
    assert len(single.param_bytes_by_device) == 1
    assert 0 <= total - single_total < 0.05 * single_total
    # the per-device /state map carries the KV pool split too. The
    # stats refresh is engine-thread-only (AIGW_TSAN asserts on it)
    # and the fixture engine is live: defeat the memory-poll throttle
    # and let the idle engine loop (which refreshes every tick) pick
    # it up instead of forcing a cross-thread refresh.
    mesh._mem_next = 0.0
    deadline = time.monotonic() + 10
    while not mesh.device_stats and time.monotonic() < deadline:
        time.sleep(0.05)
    devs = mesh.device_stats
    assert len(devs) == 8
    kv = {d["kv_pool_bytes"] for d in devs}
    assert len(kv) == 1, "head-sharded pool must split evenly"
    # +1: the fused decode kernel's reserved dump page (ISSUE 13)
    # lives in HBM but outside the allocator's capacity accounting
    assert kv.pop() * 8 == (mesh.cfg.num_pages + 1) * mesh.kv_page_bytes


@pytest.mark.slow
def test_mesh_warm_path_zero_hot_compiles():
    """CompileTracker tripwire on the mesh: after warmup() (prefill
    rungs × group sizes, decode lean/full × spec verify rungs × page
    buckets, row/mask scatters, page movers), admission + decode +
    speculation + constrained traffic adds ZERO XLA compiles."""
    eng = _mk_engine(True, warm_prefill_buckets=2, warm_decode_buckets=3)
    eng.warmup()
    eng.start()
    try:
        cp = eng.compile_tracker.checkpoint()
        _burst(eng, [
            ([5, 6, 7, 8] * 8, _greedy(), None),          # speculating
            (_PROMPTS[24], _greedy(frequency_penalty=0.5), None),
            (_TOK.encode("warm json"), _greedy(), _fsm()),  # constrained
            (_PROMPTS[40], SamplingParams(
                temperature=0.7, seed=9), None),
        ], n=6)
        assert eng.healthy, eng.last_error
        assert eng.compile_tracker.compiles_since(cp) == 0, (
            eng.compile_tracker.snapshot())
    finally:
        eng.stop()
    assert eng.stats.warm_programs > 0
    assert eng.stats.warmup_ms > 0


def test_sharded_pool_migration_byte_identical(pair):
    """Migration across layouts: export from the tp=8 engine (the page
    gather assembles all 8 head shards into full wire pages), import
    into the single-device engine, resume — the stitched stream must
    equal a solo single-device run. The wire format is
    layout-independent by construction; this proves it."""
    single, mesh = pair
    assert mesh.migratable and single.migratable
    prompt = _PROMPTS[40]
    sampling = _greedy(logit_bias=((7, 50.0),))
    # long enough that the export job wins the race against the
    # fixed-K window pipeline (adaptive windows are off in this rig,
    # so tokens land 4 at a time)
    solo = _burst(single, [(prompt, sampling, None)], n=60)[0]

    for _attempt in range(4):
        toks_a: list[int] = []
        cut_ready = threading.Event()
        done_a = threading.Event()

        def emit_a(tok, fin, toks_a=toks_a, cut_ready=cut_ready,
                   done_a=done_a):
            if tok >= 0:
                toks_a.append(tok)
            if len(toks_a) >= 2:
                cut_ready.set()
            if fin is not None:
                done_a.set()

        req = GenRequest(prompt=prompt, max_tokens=60, sampling=sampling,
                         emit=emit_a)
        mesh.submit(req)
        assert cut_ready.wait(timeout=900)
        try:
            out = mesh.migrate_export(req)
        except MigrationError as e:
            assert "finished" in str(e), e
            assert done_a.wait(timeout=900)
            continue  # raced to completion — deterministic, retry
        break
    else:
        raise AssertionError("export never won the race")
    assert done_a.wait(timeout=60)
    assert out["data"], "no pages on the wire"
    # full unsharded pages on the wire regardless of source layout
    mc = _CFG
    assert out["data"][0].shape == (mc.n_layers, 2, 16, mc.n_kv_heads,
                                    mc.head_dim)
    single.migrate_import(out["blob"]["tokens"], out["data"])

    toks_b: list[int] = []
    done_b = threading.Event()

    def emit_b(tok, fin):
        if tok >= 0:
            toks_b.append(tok)
        if fin is not None:
            done_b.set()

    creq = continuation_request(out["blob"], emit=emit_b)
    single.submit(creq)
    assert done_b.wait(timeout=900)
    assert toks_a + toks_b == solo
    assert mesh.stats.migrations_out >= 1
    assert single.stats.migrations_in >= 1


def test_ragged_backend_runs_on_mesh_byte_identical(pair):
    """The PR-6 fallback (mesh → xla-bucketed) is lifted: pallas-ragged
    resolves on a mesh to the XLA windowed program (the fallback
    matrix's documented row — the Pallas kernel stays single-chip TPU)
    and streams the same bytes as the bucketed ladder."""
    eng = _mk_engine(True, attention_backend="pallas-ragged",
                     ragged_chunk_tokens=32, ragged_max_chunks=4,
                     spec_tokens=0)
    assert eng.attn.name == "pallas-ragged"
    assert "windowed" in eng.attn_reason
    assert eng._ragged_impl == ""  # XLA program, not the kernel
    eng.start()
    try:
        out = _burst(eng, [
            (_PROMPTS[9], _greedy(), None),
            (_PROMPTS[60], _greedy(), None),
            (_PROMPTS[24], _greedy(logit_bias=((42, 3.0),)), None),
        ])
        assert eng.healthy, eng.last_error
    finally:
        eng.stop()
    ref = _burst(pair[0], [
        (_PROMPTS[9], _greedy(), None),
        (_PROMPTS[60], _greedy(), None),
        (_PROMPTS[24], _greedy(logit_bias=((42, 3.0),)), None),
    ])
    assert out == ref


def test_prefill_bucket_divisibility_guard(pair):
    """The 1.5×S rung ladder on a sharded axis: the guard rounds the
    CHOSEN rung up to the axis multiple instead of abandoning the
    intermediate rungs (a 90-token prompt on sp=8 pads to 96, not
    128)."""
    eng = pair[0]
    assert eng._prefill_bucket(90) == 96
    assert eng._prefill_bucket(90, multiple_of=8) == 96
    assert eng._prefill_bucket(20, multiple_of=8) == 24
    assert eng._prefill_bucket(20, multiple_of=7) == 28
    assert eng._prefill_bucket(40, multiple_of=6) == 48


def test_decode_attn_resolution_exported(pair):
    """The rung is the page walk (ISSUE 31), on a mesh each device over
    its local head shard, exported with its reason. The narrowed row —
    heads not divisible by tp — still gathers, with its own reason."""
    single, mesh = pair
    assert mesh.decode_attn_impl == "xla-walk-spmd"
    assert "LOCAL head shard" in mesh.decode_attn_reason
    assert single.decode_attn_impl == "xla-walk"
    assert mesh.ici_bytes_per_token > 0
    assert single.ici_bytes_per_token == 0  # unsharded: no ICI
    # the narrowed row: TINY's 2 KV heads don't divide tp=8
    from aigw_tpu.parallel import MeshSpec, make_mesh
    from aigw_tpu.tpuserve.attention import resolve_decode_backend

    impl, why = resolve_decode_backend(
        EngineConfig(), llama.TINY, make_mesh(MeshSpec(dp=1, tp=8)))
    assert impl == "xla-gather" and "narrowed" in why


def test_gateway_migrator_respects_capability_flag():
    """The gateway's _Migrator must honor the /state ``migration``
    capability: an incapable SOURCE ends the stream's migration watch
    (attempted, no export 409 spam); an incapable sibling is never
    picked as target — a capable one appearing later still can be."""
    from aigw_tpu.config.model import APISchema, Backend
    from aigw_tpu.gateway.picker import Endpoint, EndpointPicker
    from aigw_tpu.gateway.server import _Migrator

    p = EndpointPicker([Endpoint("a:1"), Endpoint("b:1")])
    backend = Backend(name="x", schema=APISchema("OpenAI", ""),
                      migration=True, migration_queue_depth=1)
    p.observe("a:1", queued=5, max_slots=2)  # prefill pressure
    p.observe("b:1")                          # idle sibling
    p.state["a:1"].migration_capable = False
    m = _Migrator(picker=p, backend=backend, src="a:1", session=None)
    assert m._pick_target() is None
    assert m.attempted is True  # stop watching: the source can't export

    p.state["a:1"].migration_capable = True
    m2 = _Migrator(picker=p, backend=backend, src="a:1", session=None)
    p.state["b:1"].migration_capable = False
    assert m2._pick_target() is None
    assert m2.attempted is False  # keep watching for a capable sibling
    p.state["b:1"].migration_capable = True
    assert m2._pick_target() == "b:1"


class TestMeshServerState:
    """tpuserve HTTP surface on a real mesh (tp=2 over the stock TINY
    config keeps it cheap): /state must export the mesh topology, the
    per-device map, and the capability/resolution fields."""

    @pytest.fixture(scope="class")
    def mesh_url(self):
        from aiohttp import web

        from aigw_tpu.tpuserve.server import TPUServeServer

        holder: dict = {}
        started = threading.Event()

        def run():
            async def main():
                server = TPUServeServer(
                    "tiny-random",
                    EngineConfig(max_batch_size=2, max_seq_len=256,
                                 page_size=16, min_prefill_bucket=16),
                    tp=2,
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
        assert started.wait(timeout=300)
        yield f"http://127.0.0.1:{holder['port']}"
        holder["loop"].call_soon_threadsafe(holder["loop"].stop)

    def test_state_and_metrics_export_mesh_surface(self, mesh_url):
        import aiohttp

        async def main():
            async with aiohttp.ClientSession() as s:
                async with s.post(
                    mesh_url + "/v1/completions",
                    json={"model": "tiny-random", "prompt": "mesh state",
                          "max_tokens": 2, "temperature": 0.0},
                ) as resp:
                    assert resp.status == 200
                async with s.get(mesh_url + "/state") as resp:
                    state = json.loads(await resp.read())
                async with s.get(mesh_url + "/metrics") as resp:
                    metrics = (await resp.read()).decode()
            return state, metrics

        state, metrics = asyncio.run(main())
        assert state["mesh_axes"].get("tp") == 2
        assert state["mesh_devices"] == 2
        assert state["device_count"] == 2
        devs = state["devices"]
        assert len(devs) == 2
        for d in devs:
            assert {"id", "memory_frac", "kv_pool_bytes", "kv_occupancy",
                    "param_bytes"} <= set(d)
        per = state["param_bytes_per_device"]
        assert len(per) == 2
        assert sum(per.values()) == state["param_bytes_total"] > 0
        assert state["ici_bytes_per_token"] > 0
        assert state["migration"] is True
        assert state["attention_backend_reason"]
        assert state["decode_attn_impl"] == "xla-walk-spmd"
        # per-device labeled gauges render next to the scalar set
        assert 'tpuserve_device_param_bytes{device="0"}' in metrics
        assert 'tpuserve_device_param_bytes{device="1"}' in metrics
