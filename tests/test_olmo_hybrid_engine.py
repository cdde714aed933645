"""Olmo-Hybrid through the serving ENGINE — the same loop, allocator,
decode windows and sampler as the other families — and the prefix cache
that serves it: a family with recurrent state whose hit resumes from a
SNAPSHOT of the slot's state at a chunk boundary, keyed with the page
chain. Against the float32 reference: logits, not tokens — the engine's
own programs return the log-probabilities of their top candidates
(``logprobs_topk``), each compared with the reference's log-softmax at
the same position, teacher-forced with the tokens the engine sampled.

``TOL`` is test_olmo_hybrid.py's, for its reasons (float32 rounding in
another order); log-softmax adds nothing of note.

Hit against cold: a hit resumes on a chunk boundary, so its suffix runs
the chunk partition a cold prefill of the same prompt runs, from a
state that is a bit-for-bit copy of the one the cold prefill would
carry there — ``test_a_hit_is_the_cold_prefill_bit_for_bit`` says the
two agree TO THE LAST BIT, not merely to float32 summation order.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest

from aigw_tpu.models.cache import StateCache
from aigw_tpu.models.registry import family_fns
from aigw_tpu.tpuserve.engine import (SNAPSHOT_EVERY_CHUNKS, Engine,
                                      EngineConfig, GenRequest,
                                      MigrationError)
from aigw_tpu.tpuserve.kvcache import (PrefixCache, RefcountedAllocator,
                                       StateSnapshots)
from aigw_tpu.tpuserve.sampling import SamplingParams
from olmo_hybrid_util import CFG, make_params, ref_logits

TOL = 3e-4
TOPK = 8
PS, CHUNK = 16, 32


def _engine(params=None, **over) -> Engine:
    ecfg = dict(max_batch_size=2, max_seq_len=512, page_size=PS,
                num_pages=80, min_prefill_bucket=16,
                decode_steps_per_tick=4, prefill_chunk_tokens=CHUNK,
                logprobs_topk=TOPK, kv_cache_dtype="float32")
    ecfg.update(over)
    return Engine(params if params is not None else make_params(CFG), CFG,
                  EngineConfig(**ecfg), fns=family_fns("olmo_hybrid"))


class _Stream:
    """One request and what the engine said of each token it sampled."""

    def __init__(self, prompt, max_tokens: int):
        self.prompt = [int(t) for t in prompt]
        self.tokens: list[int] = []
        self.tops: list[list] = []
        self.done = threading.Event()
        self.req = GenRequest(
            prompt=self.prompt, max_tokens=max_tokens, emit=lambda *_: None,
            emit_lp=self._emit, sampling=SamplingParams(temperature=0.0))

    def _emit(self, tok, fin, lp, top):
        if tok >= 0:
            self.tokens.append(tok)
            self.tops.append(top)
        if fin is not None:
            self.done.set()

    def worst(self, p, cfg=CFG, **kw) -> float:
        """Largest |engine log-prob - reference log-prob| over every
        candidate of every sampled position."""
        want = jax.nn.log_softmax(
            ref_logits(p, cfg, self.prompt + self.tokens, **kw), axis=-1)
        errs = [abs(float(want[len(self.prompt) - 1 + j, t]) - v)
                for j, top in enumerate(self.tops) for t, v in top]
        assert len(errs) == TOPK * len(self.tokens)
        return max(errs)


def _serve(eng, prompt, max_tokens=12) -> _Stream:
    s = _Stream(prompt, max_tokens)
    eng.submit(s.req)
    assert s.done.wait(600)
    assert len(s.tokens) == max_tokens
    return s


def _rand(rng, n):
    return [int(t) for t in rng.integers(0, CFG.vocab_size, n)]


def _counts(st) -> dict:
    return {k: getattr(st, k) for k in (
        "state_snapshots_saved", "state_snapshots_restored",
        "state_snapshots_evicted", "prefix_tokens_reused",
        "prefix_tokens_unrestorable", "prefix_cache_hits",
        "prefill_tokens_real", "chunked_prefill_steps")}


@pytest.fixture(scope="module")
def session():
    """One engine, one session of three turns behind a 130-token system
    prompt, then a second session behind the same system prompt; the
    counters after each. Chunks of 32 on pages of 16: a prompt's
    boundaries lie at multiples of 32, snapshots at every fourth (128,
    256, ...) and at its last."""
    p = make_params(CFG)
    eng = _engine(p)
    eng.warmup()
    warm = eng.compile_tracker.program_count()
    eng.start()
    try:
        rng = np.random.default_rng(0)
        system = _rand(rng, 130)
        out, turns, prompt = {}, [], system + _rand(rng, 50)
        for _ in range(3):
            s = _serve(eng, prompt)
            turns.append((s, _counts(eng.stats)))
            prompt = s.prompt + s.tokens + _rand(rng, 40)
        other = _serve(eng, system + _rand(rng, 70))
        out.update(p=p, eng=eng, turns=turns, other=other,
                   after_other=_counts(eng.stats), warm=warm,
                   programs=eng.compile_tracker.program_count(),
                   reads=_settled_reads(eng.stats))
        yield out
    finally:
        eng.stop()


def _settled_reads(st, quiet: float = 1.0):
    seen, since = None, time.monotonic()
    while time.monotonic() - since < quiet:
        now = (st.decode_state_rows_read, st.decode_state_rows_live,
               st.decode_kv_pages_read, st.decode_kv_pages_live)
        if now != seen:
            seen, since = now, time.monotonic()
        time.sleep(0.05)
    return seen


@pytest.mark.parametrize("turn", [0, 1, 2])
def test_every_turn_matches_the_reference_over_the_whole_history(
        session, turn):
    s, _ = session["turns"][turn]
    assert s.worst(session["p"]) < TOL


def test_the_first_turn_is_cold_and_saves_two_snapshots(session):
    _, c = session["turns"][0]
    # 180 tokens: chunks end at 32 ... 160; saved at 128 (the fourth
    # boundary) and at 160 (the last whole-chunk boundary)
    assert SNAPSHOT_EVERY_CHUNKS == 4
    assert c["chunked_prefill_steps"] == 5
    assert c["state_snapshots_saved"] == 2
    assert c["state_snapshots_restored"] == c["prefix_tokens_reused"] == 0
    assert c["prefill_tokens_real"] == 180


def test_later_turns_resume_from_the_last_boundary_of_the_turn_before(
        session):
    (_, c0), (s1, c1), (s2, c2) = session["turns"]
    # turn 2 (232 tokens) finds 11 pages of turn 1's 180-token prompt
    # cached and a snapshot at 160, ten pages in: 160 tokens are not
    # prefilled again, the eleventh page's 16 are, for want of one
    assert len(s1.prompt) == 232
    assert c1["state_snapshots_restored"] == 1
    assert c1["prefix_tokens_reused"] == 160
    assert c1["prefix_tokens_unrestorable"] == 16
    assert c1["prefill_tokens_real"] - c0["prefill_tokens_real"] == 232 - 160
    # its own boundaries: 192, 224 — 224 is its last; 256 is no fourth
    assert c1["state_snapshots_saved"] - c0["state_snapshots_saved"] == 1
    # turn 3 (284 tokens) resumes at 224; saves at 256 (a fourth AND
    # its last)
    assert len(s2.prompt) == 284
    assert c2["prefix_tokens_reused"] - c1["prefix_tokens_reused"] == 224
    assert c2["prefix_tokens_unrestorable"] == 16 + 0  # 224 is 14 pages
    assert c2["state_snapshots_saved"] - c1["state_snapshots_saved"] == 1
    assert c2["prefix_cache_hits"] == 2


def test_a_second_session_shares_the_system_prompts_snapshot(session):
    c2, c3 = session["turns"][2][1], session["after_other"]
    # the other session shares 130 tokens = 8 whole pages = 128 tokens,
    # where the first session's first turn left a snapshot
    assert c3["prefix_tokens_reused"] - c2["prefix_tokens_reused"] == 128
    assert c3["state_snapshots_restored"] - c2["state_snapshots_restored"] == 1
    assert session["other"].worst(session["p"]) < TOL
    assert c3["state_snapshots_evicted"] == 0


def test_both_copy_programs_were_compiled_in_warm_up(session):
    # serving four requests with hits and saves compiled the chunk,
    # tail and decode shapes warm-up does not cover, and neither copy:
    # each is ONE program for any (slot, row), there since warm-up
    programs = session["eng"].compile_tracker.programs()
    assert programs["state_snapshot"] == programs["state_restore"] == 1
    assert session["warm"] <= session["programs"]
    fresh = _engine()
    fresh.warmup()
    warmed = fresh.compile_tracker.programs()
    assert warmed["state_snapshot"] == warmed["state_restore"] == 1


def test_the_decode_windows_counted_their_state_reads(session):
    read, live, kv_read, kv_live = session["reads"]
    streams = [s for s, _ in session["turns"]] + [session["other"]]
    # one live row of two, two rows a trip at this tiny size
    assert read == 2 * live > 0
    assert sum(len(s.tokens) - 1 for s in streams) <= live \
        <= sum(len(s.tokens) for s in streams) + 4 * len(streams)
    assert kv_read >= kv_live > 0


def test_a_hit_is_the_cold_prefill_bit_for_bit():
    """The same second turn served from a snapshot and served cold (by
    an engine that never saw the first turn): the first token's top
    log-probabilities agree to the last bit — the suffix runs the same
    chunk partition from a bit-for-bit copy of the state — and so does
    every decoded token's."""
    p = make_params(CFG)
    rng = np.random.default_rng(5)
    first = _rand(rng, 150)
    second = first + _rand(rng, 12) + _rand(rng, 45)
    warm_eng, cold_eng = _engine(p), _engine(p)
    for eng in (warm_eng, cold_eng):
        eng.start()
    try:
        _serve(warm_eng, first)
        hit = _serve(warm_eng, second)
        cold = _serve(cold_eng, second)
        assert warm_eng.stats.prefix_tokens_reused == 128
        assert cold_eng.stats.prefix_tokens_reused == 0
        assert hit.tokens == cold.tokens
        assert hit.tops == cold.tops  # floats compared with ==
    finally:
        warm_eng.stop()
        cold_eng.stop()


def test_a_hit_with_the_snapshot_ignored_fails_the_tolerance():
    """What the snapshot is for: pages adopted and the suffix resumed
    WITHOUT the state copied back — here, from a snapshot pool zeroed
    behind the engine's back — is another model, far over ``TOL``."""
    p = make_params(CFG)
    rng = np.random.default_rng(6)
    first = _rand(rng, 150)
    eng = _engine(p)
    eng.start()
    try:
        _serve(eng, first)
        eng._snap_pool = jax.tree_util.tree_map(
            lambda a: a * 0, eng._snap_pool)
        hit = _serve(eng, first + _rand(rng, 50))
        assert eng.stats.state_snapshots_restored == 1
        assert hit.worst(p) > 30 * TOL
    finally:
        eng.stop()


def test_pages_cached_but_snapshot_evicted_prefills_again():
    """A hit is refused where the pages are cached and the snapshot is
    gone: the tokens prefill again, the counter says so, and the
    answer is still the reference's."""
    p = make_params(CFG)
    rng = np.random.default_rng(7)
    first = _rand(rng, 100)  # boundaries 32, 64, 96: one save, at 96
    eng = _engine(p)
    eng.start()
    try:
        _serve(eng, first)
        assert eng.stats.state_snapshots_saved == 1
        snap = eng.prefix_cache.snapshots
        (key,) = list(snap._row_of)
        snap.drop(key)
        again = _serve(eng, first + _rand(rng, 40))
        st = eng.stats
        assert st.prefix_tokens_reused == 0 and st.prefix_cache_hits == 0
        assert st.prefix_tokens_unrestorable == 96  # six pages cached
        assert st.state_snapshots_restored == 0
        assert again.worst(p) < TOL
    finally:
        eng.stop()


def test_a_full_pool_evicts_least_recently_used_and_counts_it():
    """Two slots: six rows. Distinct prompts of 70 tokens save one
    snapshot each (at 64); the seventh evicts the first's, the one used
    least recently — unless a hit used it since."""
    p = make_params(CFG)
    rng = np.random.default_rng(8)
    eng = _engine(p)
    eng.start()
    try:
        prompts = [_rand(rng, 70) for _ in range(7)]
        for q in prompts[:6]:
            _serve(eng, q, 4)
        assert eng.stats.state_snapshots_saved == 6
        assert eng.stats.state_snapshots_evicted == 0
        # use the FIRST prompt's snapshot: it becomes the newest
        _serve(eng, prompts[0] + _rand(rng, 20), 4)
        assert eng.stats.state_snapshots_restored == 1
        _serve(eng, prompts[6], 4)
        st = eng.stats
        assert st.state_snapshots_saved == 7 and st.state_snapshots_evicted == 1
        # the second prompt's went, not the first's
        before = st.prefix_tokens_reused
        _serve(eng, prompts[0] + _rand(rng, 21), 4)
        assert st.prefix_tokens_reused - before == 64
        _serve(eng, prompts[1] + _rand(rng, 21), 4)
        assert st.prefix_tokens_unrestorable == 64
    finally:
        eng.stop()


def test_snapshot_bookkeeping_alone():
    """tpuserve/kvcache.py ``StateSnapshots``: LRU, holds, drops."""
    snap = StateSnapshots(2)
    a, b, c = b"a", b"b", b"c"
    assert snap.claim(a) == 0 and snap.claim(b) == 1
    assert snap.claim(a) is None  # has one: only touched
    assert snap.longest([a, c, b], 3) == 3 and snap.longest([a, c], 2) == 1
    assert snap.longest([c], 1) == 0 and snap.longest([a, b], 1) == 1
    # a is the newer now: c evicts b
    assert snap.claim(c) == 1 and b not in snap and snap.evicted == 1
    # a snapshot held by an admission in flight is not the victim
    snap.hold(a)
    snap.hold(c)
    assert snap.claim(b) is None and snap.saved == 3
    snap.release(c)
    assert snap.claim(b) == 1 and c not in snap
    snap.release(a)
    # a page's eviction takes the snapshot with it, uncounted
    alloc = RefcountedAllocator(4, 16)
    cache = PrefixCache(alloc, 16, snap)
    alloc.allocate(1, 16)
    cache.insert([a], alloc.pages(1))
    alloc.free(1)
    for seq in range(2, 6):
        alloc.allocate(seq, 16)  # the fourth takes a's page back
    assert a not in snap and len(snap) == 1 and snap.evicted == 2
    assert snap.restore_row(b) == 1 and snap.restored == 1


def test_a_prefill_cut_short_leaves_no_snapshot_behind():
    """A snapshot lives no longer than its chain's pages: a prompt
    cancelled between chunks never registers its pages, and what its
    prefill saved is dropped with it."""
    p = make_params(CFG)
    eng = _engine(p)
    rng = np.random.default_rng(9)
    s = _Stream(_rand(rng, 300), 4)
    real_tick = eng._decode_tick

    def tick_then_cancel():
        if eng.stats.state_snapshots_saved:
            s.req.cancelled.set()
        return real_tick()

    eng._decode_tick = tick_then_cancel
    eng.start()
    try:
        eng.submit(s.req)
        deadline = time.monotonic() + 120
        while eng._snap_admissions or not eng.stats.state_snapshots_saved:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert eng.stats.state_snapshots_saved >= 1
        assert len(eng.prefix_cache.snapshots) == 0
        assert eng.prefix_cache.resident_entries == 0
    finally:
        eng.stop()


def test_cache_description_and_what_is_off():
    eng = _engine(kv_host_bytes=1 << 20, spec_tokens=4, logprobs_topk=0)
    spec = eng.cache_spec
    assert spec.stateful and spec.snapshots and not spec.latent
    assert spec.kv_layers == eng.stats.kv_layers == 2
    assert isinstance(eng.kv_cache, StateCache)
    assert eng.stats.state_bytes_per_slot == 6 * (2 * 24 * 48 * 4
                                                  + 3 * 192 * 4)
    # the snapshot pool: three rows a slot, the slot state's leaves
    assert eng.prefix_cache.snapshots.n_rows == 6
    assert eng.stats.state_snapshot_bytes_total \
        == 6 * eng.stats.state_bytes_per_slot
    assert sum(a.nbytes for a in eng._snap_pool.values()) \
        == eng.stats.state_snapshot_bytes_total
    # exactly what is still off, each with its reason — and not the
    # prefix cache
    assert set(eng.features_off) == {
        "kv_host_tier", "migration", "batch_parking", "kv_fleet_fetch",
        "speculation", "lora"}
    assert all("only the prefix cache carries a snapshot" in why
               for why in eng.features_off.values())
    assert isinstance(eng.prefix_cache, PrefixCache)
    assert isinstance(eng.allocator, RefcountedAllocator)
    assert eng.host_tier is None and eng._spec_rungs == (0,)
    assert eng.attn.name == "xla-bucketed"
    assert eng.decode_attn_impl == "xla-walk"
    assert set(eng.slot_kw([])) == {"slot_ids"}
    # the movers that share the allocator refuse by what the family is
    eng._refresh_kv_digest()
    assert eng.kv_chain_digest() == () and eng._do_fetch([b"k"]) == []
    with pytest.raises(MigrationError, match="whose pages are all"):
        eng._do_import(list(range(40)), [np.zeros(1)])
    with pytest.raises(MigrationError, match="migration is off"):
        eng._do_export(GenRequest(
            prompt=[1], max_tokens=1, emit=lambda *_: None,
            sampling=SamplingParams(temperature=0.0)))


def test_a_chunk_that_is_no_multiple_of_the_page_saves_nothing():
    p = make_params(CFG)
    eng = _engine(p, prefill_chunk_tokens=24)
    eng.start()
    try:
        rng = np.random.default_rng(10)
        first = _rand(rng, 100)
        _serve(eng, first, 4)
        again = _serve(eng, first + _rand(rng, 30), 4)
        st = eng.stats
        assert st.state_snapshots_saved == 0 and st.prefix_cache_hits == 0
        assert st.prefix_tokens_unrestorable == 96
        assert again.worst(p) < TOL
    finally:
        eng.stop()


class _Store:
    base_row = 0


def test_lora_refuses_at_start_up():
    with pytest.raises(ValueError, match="LoRA serving is off"):
        Engine(make_params(CFG), CFG, EngineConfig(
            max_batch_size=2, max_seq_len=64, page_size=16),
            fns=family_fns("olmo_hybrid"), adapter_store=_Store())
