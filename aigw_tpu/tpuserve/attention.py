"""Attention-backend interface: how the engine turns admitted prompts
into prefill device programs.

Two backends (selected by ``EngineConfig.attention_backend`` /
``--attention-backend``), behind one interface so the engine's admission
logic is geometry-agnostic:

- **xla-bucketed** (default): the classic ladder — prompts right-pad to
  per-sequence buckets (pow2 + 1.5×S rungs), same-bucket bursts batch
  into one [G2, S] call (G2 = pow2 group), long prompts run the
  fixed-chunk ``prefill_suffix`` loop. Compiled-program surface:
  rungs × octaves × group sizes.

- **pallas-ragged**: the ragged paged-attention prefill (PAPERS.md
  arxiv 2604.15464). A mixed-length admission burst packs into ONE
  program sized by TOTAL tokens, padded only to a token-budget chunk
  rung (multiples of ``ragged_chunk_tokens``; the padding tax collapses
  from per-sequence bucket residue to per-burst chunk residue).
  Per-sequence start offsets make offset-resumed prefill (prefix-cache
  partial hits, chunked continuations) first-class: a resume is just a
  packed segment whose first position is nonzero. Bursts larger than
  ``ragged_chunk_tokens × ragged_max_chunks`` split into budget-sized
  calls with decode ticks interleaved (the chunked-prefill liveness
  property, kept). On TPU the attention runs the Pallas kernel
  (ops/pallas/paged_attention.ragged_prefill_attention, scalar-prefetch
  page table + ragged DMA skip); off-TPU it auto-falls back to an XLA
  windowed online-softmax reference with identical semantics (interpret
  mode is far too slow for a serving loop). Compiled-program surface: a
  handful of token-budget rungs — which is also why ``warmup()``
  collapses from warming every (bucket, group) shape to warming the
  rung ladder.

Both backends account real vs padded prefill tokens into
``EngineStats.prefill_tokens_real/_padded`` — the ``prefill_padded_frac``
gauge on /state and /metrics is the padding-tax claim, observable per
replica.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Any, TYPE_CHECKING

import jax.numpy as jnp
import numpy as np

from aigw_tpu.obs.flight import ADMIT, PREFILL_BLOCK, PREFILL_DISPATCH

if TYPE_CHECKING:  # pragma: no cover
    from aigw_tpu.tpuserve.engine import Engine, GenRequest

logger = logging.getLogger(__name__)

#: valid EngineConfig.attention_backend values
BACKENDS = ("xla-bucketed", "pallas-ragged")


@dataclass
class GroupResult:
    """One admitted request's prefill outcome on the batched path."""

    req: Any
    seq_id: int
    n: int
    total: int
    tok: int
    first_lp: tuple | None
    page_row: np.ndarray
    adapter_row: int


class AttentionBackend:
    """Owns the engine's prefill programs and their geometry policy.

    Compile discipline (rule ``jit-registry``, make lint): any jitted
    program a backend constructs must flow into the engine's
    ``compile_tracker.register(...)`` so ``warm()`` and the
    zero-hot-compile tripwires see it — an unregistered program is an
    unwarmable one (the PR 6 capped-rung bug class). Module-level
    Pallas kernels a backend dispatches are declared in
    ``analysis/registry.py::JIT_WARM_SURFACE`` instead.
    """

    name = "base"
    #: True when the batched-admission path may take prompts longer
    #: than prefill_chunk_tokens (the ragged packer splits them at
    #: token-budget boundaries itself)
    packs_long_prompts = False

    def __init__(self, engine: "Engine") -> None:
        self.eng = engine

    def warm(self) -> None:
        """Pre-compile the backend's prefill programs (gated by
        ``warm_prefill_buckets > 0``)."""
        raise NotImplementedError

    def group_prefill(self, items: list, chain_by_req: dict) -> list:
        """Batched-admission prefill for ``items`` (list of
        (req, seq_id, n, total) with pages already allocated). Emits
        queue-wait/admission/prefill phases + traces, returns
        GroupResults in item order; the engine creates slots."""
        raise NotImplementedError

    def single_prefill(self, req, seq_id: int, suffix: list[int],
                       prefix_len: int, n: int, total: int,
                       pt: np.ndarray, bucket: int, sampling_args: tuple):
        """Per-request prefill (prefix-cache resume offsets, long
        prompts). Returns (next_tok_device_output, info dict) or an
        abort status string ("stop" | "stop_consumed" | "skipped") —
        the engine frees pages and requeues on abort. ``info`` carries
        consumed/bucket/chunks/padded_frac for stats+traces. Runs under
        the caller's ``prefill_dispatch`` phase of the loop ledger; the
        decode ticks between chunks suspend it."""
        raise NotImplementedError

    # -- shared helpers ---------------------------------------------------
    def _account(self, real: int, padded: int, calls: int = 1) -> None:
        st = self.eng.stats
        st.prefill_tokens_real += real
        st.prefill_tokens_padded += padded
        st.prefill_calls += calls

    def _observe_admission(self, items: list, chain_by_req: dict,
                           bucket_of) -> None:
        """queue-wait phases + batched admission trace events for a
        group, shared by both backends (``bucket_of(item)`` supplies
        the backend-specific geometry attribute, or None)."""
        eng = self.eng
        t0 = time.monotonic()
        burst_id, burst_size = eng._cur_burst
        for item in items:
            req, _sid, n, _tt = item
            qw = 1e3 * (t0 - req.enqueued_at)
            eng.phases.observe(
                "queue_wait", qw,
                req.trace.trace_id if req.trace is not None else "")
            if req.trace is not None:
                req.trace.queue_wait(qw)
                extra = {}
                b = bucket_of(item)
                if b is not None:
                    extra = {"bucket": b,
                             "padded_frac": round(1.0 - n / b, 3)}
                req.trace.admission(
                    path="batched", burst_id=burst_id,
                    burst_size=burst_size,
                    prefix="miss" if chain_by_req.get(id(req)) else "off",
                    **extra)


class XlaBucketedBackend(AttentionBackend):
    """The bucket-ladder prefill the engine has always run — extracted
    behind the interface, behavior-preserving (token streams are
    byte-identical to the pre-refactor engine)."""

    name = "xla-bucketed"

    def warm(self) -> None:
        eng = self.eng
        cfg = eng.cfg
        warmed: set[int] = set()
        for b in range(cfg.warm_prefill_buckets):
            # octave 0 always warms (its rungs cap to max_seq_len even
            # when min_prefill_bucket exceeds it). Later octaves stop
            # only once the PREVIOUS base rung reached max_seq_len —
            # the first octave whose base exceeds the cap still
            # contributes its capped rung (e.g. min=16, max=208:
            # _prefill_bucket(193) selects the capped 208 from the
            # 256-base octave, which must be warmable)
            if b > 0 and (cfg.min_prefill_bucket << (b - 1)
                          >= cfg.max_seq_len):
                break
            for S in eng._bucket_rungs(b):
                if S not in warmed:  # capped rungs dedupe across octaves
                    warmed.add(S)
                    eng._warm_prefill_shapes(S)

    def group_prefill(self, items: list, chain_by_req: dict) -> list:
        # group by padded bucket so each group is one compiled shape
        eng = self.eng
        groups: dict[int, list] = {}
        for item in items:
            groups.setdefault(eng._prefill_bucket(item[2]),
                              []).append(item)
        by_id: dict[int, GroupResult] = {}
        for S, group in groups.items():
            for r in self._prefill_group(S, group, chain_by_req):
                by_id[id(r.req)] = r
        return [by_id[id(item[0])] for item in items]

    def _prefill_group(self, S: int, items: list,
                       chain_by_req: dict) -> list:
        """One [G2, S] prefill for a same-bucket group; G2 = G padded to
        a power of two (compile-shape discipline: log2 batch shapes per
        bucket, not one per group size). Padded rows have seq_len 0 —
        their K/V scatters are dropped and their sampled token ignored."""
        eng = self.eng
        cfg = eng.cfg
        G = len(items)
        G2 = 1
        while G2 < G:
            G2 *= 2
        P = cfg.max_pages_per_seq
        V = eng.model_cfg.vocab_size
        tokens = np.zeros((G2, S), np.int32)
        seq_lens = np.zeros((G2,), np.int32)
        pt = np.zeros((G2, P), np.int32)
        keys = np.zeros((G2, 2), np.uint32)
        temp = np.zeros((G2,), np.float32)
        top_p = np.ones((G2,), np.float32)
        top_k = np.zeros((G2,), np.int32)
        bias = np.zeros((G2, V), np.float32)
        adapter = np.full((G2,), eng._base_row, np.int32)
        loop = eng.stats.loop
        ns0 = loop.prefill_ns()
        outer = loop.enter(
            PREFILL_DISPATCH,
            {"bucket": S, "group": G,
             "tokens": sum(it[2] for it in items)}
            if loop.capture else None)
        self._observe_admission(items, chain_by_req, lambda it: S)
        for g, (req, seq_id, n, _total) in enumerate(items):
            tokens[g, :n] = req.prompt
            seq_lens[g] = n
            pages = eng.allocator.pages(seq_id)
            pt[g, : len(pages)] = pages
            keys[g, 0] = np.uint32(
                (req.sampling.seed or seq_id) & 0xFFFFFFFF)
            temp[g] = req.sampling.temperature
            top_p[g] = req.sampling.top_p
            top_k[g] = req.sampling.top_k
            for tok_id, b in req.sampling.logit_bias:
                if 0 <= tok_id < V:
                    bias[g, tok_id] = b
            adapter[g] = eng._adapter_row_of(req)
        next_tok, eng.kv_cache, moe = eng._prefill_fn(
            eng.params, eng.lora_params, jnp.asarray(tokens),
            jnp.asarray(seq_lens), eng.kv_cache, jnp.asarray(pt),
            jnp.asarray(keys), jnp.asarray(temp), jnp.asarray(top_p),
            jnp.asarray(top_k), jnp.asarray(bias), jnp.asarray(adapter),
            **eng.slot_kw([it[1] for it in items], G2))
        # token 0's device→host copy starts at dispatch and overlaps
        # the prefill's remaining on-device compute
        eng._start_host_copy(next_tok)
        loop.enter(PREFILL_BLOCK)  # host blocked on the sampled tokens
        lp_data = None
        if cfg.logprobs_topk and isinstance(next_tok, tuple):
            next_tok, chosen, tk_ids, tk_vals = next_tok
            lp_data = (np.asarray(chosen), np.asarray(tk_ids),
                       np.asarray(tk_vals))
        toks = np.asarray(next_tok)
        # token fetch above already synced the program; the fold is a
        # free host-side np add on the settled routing-stats leaf
        eng._fold_moe(moe)
        self._account(int(seq_lens.sum()), G2 * S)
        loop.resume(outer)
        prefill_ms = (loop.prefill_ns() - ns0) / 1e6
        eng.stats.note_prefill_call(prefill_ms, int(seq_lens.sum()))
        results = []
        for g, (req, seq_id, n, total) in enumerate(items):
            eng.phases.observe(
                "prefill", prefill_ms,
                req.trace.trace_id if req.trace is not None else "")
            if req.trace is not None:
                req.trace.prefill(prefill_ms, bucket=S, group=G)
            first_lp = None
            if lp_data is not None:
                chosen, tk_ids, tk_vals = lp_data
                first_lp = (
                    float(chosen[g]),
                    [(int(t), float(v)) for t, v in zip(
                        tk_ids[g], tk_vals[g])],
                )
            results.append(GroupResult(
                req=req, seq_id=seq_id, n=n, total=total,
                tok=int(toks[g]), first_lp=first_lp, page_row=pt[g],
                adapter_row=int(adapter[g])))
        logger.debug("batched prefill G=%d S=%d %.1fms", G, S,
                     prefill_ms)
        return results

    def single_prefill(self, req, seq_id, suffix, prefix_len, n, total,
                       pt, bucket, sampling_args):
        eng = self.eng
        cfg = eng.cfg
        ns = len(suffix)
        # chunked prefill: long prompts run as fixed-size suffix
        # steps so no giant bucket is ever compiled and a decode
        # tick runs between chunks — active streams keep emitting
        # behind a long prompt instead of stalling for its whole
        # prefill (vLLM-style chunked prefill; the prefill_suffix
        # kernel with prefix_lens=consumed IS the chunk step)
        chunk = cfg.prefill_chunk_tokens
        consumed = 0
        # chunk-step routing-stats leaves settle with their programs;
        # fold them only at the end so the host never syncs mid-loop
        # (the decode interleave between chunks stays pipelined)
        moes: list = []
        slot_kw = eng.slot_kw([seq_id])
        if (chunk > 0 and eng.fns.prefill_suffix is not None
                and ns > chunk):
            # loop-invariant device uploads hoisted; each boundary
            # is also a cancellation/shutdown yield point — exactly
            # what chunking exists to provide
            pt_dev = jnp.asarray(pt[:, :bucket])
            while ns - consumed > chunk:
                if req.cancelled.is_set() or eng._stop.is_set():
                    if eng._stop.is_set():
                        if not req.cancelled.is_set():
                            return "stop"
                        return "stop_consumed"
                    return "skipped"
                # a buffer of its own for every chunk: on the CPU the
                # device array may alias the host's, and the program
                # that reads it runs after this loop has moved on
                ctokens = np.asarray(
                    suffix[consumed:consumed + chunk], np.int32)[None]
                _, eng.kv_cache, cmoe = eng._prefill_suffix_fn(
                    eng.params,
                    eng.lora_params,
                    jnp.asarray(ctokens),
                    jnp.asarray([prefix_len + consumed], jnp.int32),
                    jnp.asarray([prefix_len + consumed + chunk],
                                jnp.int32),
                    eng.kv_cache,
                    pt_dev,
                    *sampling_args,
                    **slot_kw,
                )
                moes.append(cmoe)
                consumed += chunk
                self._account(chunk, chunk)
                eng.stats.chunked_prefill_steps += 1
                if req.trace is not None:
                    req.trace.event("prefill_chunk", tokens=chunk,
                                    consumed=prefix_len + consumed)
                # (a family with state snapshots may save one here)
                eng.chunk_boundary(seq_id, prefix_len + consumed, n)
                # interleave: active streams keep decoding between
                # chunks (their windows overlap this chunk's compute)
                eng._decode_tick()

        eff_prefix = prefix_len + consumed
        tail = suffix[consumed:]
        ns_tail = len(tail)
        # bucketed padded length for the remaining tokens
        S = eng._prefill_bucket(ns_tail)
        tokens = np.zeros((1, S), np.int32)
        tokens[0, :ns_tail] = tail
        if eff_prefix:
            next_tok, eng.kv_cache, moe = eng._prefill_suffix_fn(
                eng.params,
                eng.lora_params,
                jnp.asarray(tokens),
                jnp.asarray([eff_prefix], jnp.int32),
                jnp.asarray([n], jnp.int32),
                eng.kv_cache,
                jnp.asarray(pt[:, :bucket]),
                *sampling_args,
                **slot_kw,
            )
        else:
            next_tok, eng.kv_cache, moe = eng._prefill_fn(
                eng.params,
                eng.lora_params,
                jnp.asarray(tokens),
                jnp.asarray([n], jnp.int32),
                eng.kv_cache,
                jnp.asarray(pt),
                *sampling_args,
                **slot_kw,
            )
        moes.append(moe)
        # everything is dispatched: from here the host waits (an MoE
        # family's routing-stats fold already blocks on the programs)
        eng.stats.loop.enter(PREFILL_BLOCK)
        for m in moes:
            eng._fold_moe(m)
        self._account(ns_tail, S)
        return next_tok, {
            "consumed": consumed, "bucket": S,
            "chunks": consumed // chunk if chunk else 0,
            "padded_frac": round(1.0 - ns_tail / S, 3) if S else 0.0,
        }


def sp_chunked_prefill(eng, req, seq_id: int, suffix: list[int],
                       prefix_len: int, n: int, pt: np.ndarray,
                       bucket: int, sampling_args: tuple):
    """Sequence-sharded chunked prefill — the long-context sp path.

    The ``single_prefill`` chunk-loop discipline composed with ring
    attention: fixed ``sp_chunk_tokens``-sized ``prefill_sp_suffix``
    steps (chunk rung rounded up to a multiple of the sp axis), a
    decode tick between chunks so live streams keep emitting behind a
    128k prefill, resume at the page-aligned ``prefix_len`` a prefix
    hit / migration continuation left in the pool, and a bucketed tail
    rung — sp-path padding collapses from full-rung residue to tail
    residue.

    Module-level (not a backend method): the sp route preempts the
    attention backend's ``single_prefill`` for long suffixes whichever
    backend is configured. Same return contract as ``single_prefill``
    ("stop" | "stop_consumed" | "skipped" | (next_tok, info))."""
    cfg = eng.cfg
    sp = eng._sp
    ns = len(suffix)
    chunk = max(cfg.sp_chunk_tokens, sp)
    chunk = -(-chunk // sp) * sp  # ring shards the chunk over sp
    consumed = 0
    # the gather window of every chunk step: the pow2 page bucket
    # covering the sequence (page_size % sp == 0 is build-gated, so
    # the window shards evenly)
    pt_dev = jnp.asarray(pt[:, :bucket])
    # folded only after the tail call — no mid-loop host sync (the
    # interactive admits + decode ticks between chunks stay pipelined)
    moes: list = []
    if ns > chunk:
        while ns - consumed > chunk:
            # chunk boundaries are cancellation/shutdown yield points —
            # exactly what chunking exists to provide
            if req.cancelled.is_set() or eng._stop.is_set():
                if eng._stop.is_set():
                    if not req.cancelled.is_set():
                        return "stop"
                    return "stop_consumed"
                return "skipped"
            # (its own buffer: see XlaBucketedBackend.single_prefill)
            ctokens = np.asarray(
                suffix[consumed:consumed + chunk], np.int32)[None]
            _, eng.kv_cache, cmoe = eng._prefill_sp_suffix_fn(
                eng.params,
                eng.lora_params,
                jnp.asarray(ctokens),
                jnp.asarray([prefix_len + consumed], jnp.int32),
                jnp.asarray([prefix_len + consumed + chunk], jnp.int32),
                eng.kv_cache,
                pt_dev,
                *sampling_args,
            )
            moes.append(cmoe)
            consumed += chunk
            eng.stats.prefill_tokens_real += chunk
            eng.stats.prefill_tokens_padded += chunk
            eng.stats.prefill_calls += 1
            eng.stats.chunked_prefill_steps += 1
            if req.trace is not None:
                req.trace.event("prefill_chunk", tokens=chunk,
                                consumed=prefix_len + consumed, sp=True)
            # interleave: SHORT queued arrivals admit into free slots
            # (their own fast prefill emits their first token NOW, not
            # after this long prefill drains), then live streams — the
            # just-admitted one included — take a decode tick
            outer = eng.stats.loop.enter(ADMIT)
            eng._admit_interactive()
            eng.stats.loop.resume(outer)
            eng._decode_tick()
    tail = suffix[consumed:]
    ns_tail = len(tail)
    S = eng._prefill_bucket(ns_tail, multiple_of=sp)
    tokens = np.zeros((1, S), np.int32)
    tokens[0, :ns_tail] = tail
    next_tok, eng.kv_cache, moe = eng._prefill_sp_suffix_fn(
        eng.params,
        eng.lora_params,
        jnp.asarray(tokens),
        jnp.asarray([prefix_len + consumed], jnp.int32),
        jnp.asarray([n], jnp.int32),
        eng.kv_cache,
        pt_dev,
        *sampling_args,
    )
    moes.append(moe)
    eng.stats.loop.enter(PREFILL_BLOCK)  # dispatched: the host waits
    for m in moes:
        eng._fold_moe(m)
    eng.stats.prefill_tokens_real += ns_tail
    eng.stats.prefill_tokens_padded += S
    eng.stats.prefill_calls += 1
    return next_tok, {
        "consumed": consumed, "bucket": S,
        "chunks": consumed // chunk,
        "padded_frac": round(1.0 - ns_tail / S, 3) if S else 0.0,
    }


@dataclass
class _Seg:
    """One sequence's packed-prefill work item."""

    g: int  # device row (slot in the sampling/page-table arrays)
    req: Any
    tokens: list[int]  # suffix tokens still to prefill
    start: int  # absolute position of tokens[0]
    page_row: np.ndarray  # [max_pages_per_seq] int32
    done: int = 0  # tokens already packed into earlier calls


class RaggedPrefillBackend(AttentionBackend):
    """Token-budget-packed prefill over the ragged paged-attention
    program — one compiled shape per chunk rung, any batch geometry."""

    name = "pallas-ragged"
    packs_long_prompts = True

    def __init__(self, engine: "Engine") -> None:
        super().__init__(engine)
        self.impl = engine._ragged_impl  # "pallas" on TPU, "" = XLA ref
        logger.info(
            "attention backend pallas-ragged: %s attention, chunk=%d, "
            "budget=%d tokens, rungs=%s",
            "Pallas kernel" if self.impl == "pallas"
            else "XLA windowed fallback (off-TPU)",
            engine.cfg.ragged_chunk_tokens,
            engine.cfg.ragged_chunk_tokens * engine.cfg.ragged_max_chunks,
            self.rungs())

    # -- token-budget ladder ----------------------------------------------
    def rungs(self) -> list[int]:
        """Padded packed-length rungs: two sub-chunk rungs (so a lone
        short prompt or a 1-token full-hit resume doesn't pay a whole
        chunk) plus every chunk multiple up to the per-call budget.
        Each rung is ONE compiled program for any batch geometry."""
        c = self.eng.cfg.ragged_chunk_tokens
        budget = c * self.eng.cfg.ragged_max_chunks
        rungs = {max(8, c // 4), max(8, c // 2)}
        r = c
        while r <= budget:
            rungs.add(r)
            r += c
        return sorted(rungs)

    def _rung_for(self, t: int) -> int:
        for r in self.rungs():
            if r >= t:
                return r
        return self.rungs()[-1]

    @property
    def budget(self) -> int:
        return (self.eng.cfg.ragged_chunk_tokens
                * self.eng.cfg.ragged_max_chunks)

    def warm(self) -> None:
        """Compile every rung of the token-budget ladder with a
        zero-token dummy pack (all rows invalid → no K/V scatters) —
        after this, ANY admission geometry whose packed total fits the
        budget reuses a warmed program: the bucket ladder's
        rungs × octaves × group-sizes compile surface collapses to
        len(rungs) programs."""
        if self.eng.cfg.warm_prefill_buckets <= 0:
            return
        eng = self.eng
        B = eng.cfg.max_batch_size
        P = eng.cfg.max_pages_per_seq
        V = eng.model_cfg.vocab_size
        dummy = (
            jnp.zeros((B, 2), jnp.uint32),
            jnp.zeros((B,), jnp.float32),
            jnp.ones((B,), jnp.float32),
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B, V), jnp.float32),
            jnp.full((B,), eng._base_row, jnp.int32),
        )
        for T in self.rungs():
            _, eng.kv_cache, _ = eng._prefill_ragged_fn(
                eng.params, eng.lora_params,
                jnp.zeros((T,), jnp.int32),
                jnp.full((T,), B, jnp.int32),  # all padding rows
                jnp.zeros((T,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                eng.kv_cache,
                jnp.zeros((B, P), jnp.int32),
                *dummy,
            )

    # -- packing core ------------------------------------------------------
    def _run_packed(self, segs: list[_Seg], sampling_args: tuple,
                    cancellable: Any = None):
        """Run the segments through budget-sized packed calls. Returns
        ({row g → device output of the call that finished g}, info) or
        an abort status string (only when ``cancellable`` — the single
        path's request — is set)."""
        eng = self.eng
        cfg = eng.cfg
        B = cfg.max_batch_size
        P = cfg.max_pages_per_seq
        V = eng.model_cfg.vocab_size
        pt = np.zeros((B, P), np.int32)
        for s in segs:
            pt[s.g] = s.page_row[:P]
        pt_dev = jnp.asarray(pt)
        final_out: dict[int, Any] = {}
        # MoE routing-stats leaves, one per packed call; folded after the
        # loop so no mid-loop host sync stalls the packed stream
        moes: list = []
        calls = 0
        real = padded = 0
        last_rung = 0
        while True:
            call: list[tuple[_Seg, int]] = []  # (seg, take)
            t_used = 0
            for s in segs:
                rem = len(s.tokens) - s.done
                if rem <= 0:
                    continue
                take = min(rem, self.budget - t_used)
                if take <= 0:
                    break
                call.append((s, take))
                t_used += take
                if t_used >= self.budget:
                    break
            if not call:
                break
            if calls > 0:
                # budget boundary: cancellation/shutdown yield point +
                # decode interleave, exactly like the chunk loop
                if cancellable is not None and (
                        cancellable.cancelled.is_set()
                        or eng._stop.is_set()):
                    if eng._stop.is_set():
                        if not cancellable.cancelled.is_set():
                            return "stop"
                        return "stop_consumed"
                    return "skipped"
                eng._decode_tick()
            T = self._rung_for(t_used)
            last_rung = T
            tokens = np.zeros((T,), np.int32)
            row_seq = np.full((T,), B, np.int32)
            positions = np.zeros((T,), np.int32)
            last_rows = np.zeros((B,), np.int32)
            o = 0
            for s, take in call:
                tokens[o:o + take] = s.tokens[s.done:s.done + take]
                row_seq[o:o + take] = s.g
                positions[o:o + take] = s.start + s.done + np.arange(
                    take, dtype=np.int32)
                last_rows[s.g] = o + take - 1
                s.done += take
                o += take
            next_tok, eng.kv_cache, moe = eng._prefill_ragged_fn(
                eng.params, eng.lora_params,
                jnp.asarray(tokens), jnp.asarray(row_seq),
                jnp.asarray(positions), jnp.asarray(last_rows),
                eng.kv_cache, pt_dev, *sampling_args,
            )
            moes.append(moe)
            calls += 1
            real += t_used
            padded += T
            finished = False
            for s, _take in call:
                if s.done == len(s.tokens):
                    final_out[s.g] = next_tok
                    finished = True
                elif s.req.trace is not None:
                    s.req.trace.event(
                        "prefill_chunk", tokens=_take,
                        consumed=s.start + s.done)
            if finished:
                eng._start_host_copy(next_tok)
        # intermediate budget-boundary device steps ride the same gauge
        # as the bucketed chunk loop
        eng.stats.chunked_prefill_steps += max(0, calls - 1)
        eng.stats.loop.enter(PREFILL_BLOCK)  # dispatched: the host waits
        for m in moes:
            eng._fold_moe(m)
        self._account(real, padded, calls)
        return final_out, {
            "bucket": last_rung, "chunks": calls - 1,
            "padded_frac": (round(1.0 - real / padded, 3) if padded
                            else 0.0),
            "calls": calls, "real": real, "padded": padded,
        }

    def _sampling_rows(self, by_row: dict[int, Any]) -> tuple:
        """[B]-wide sampling arrays from ``row → (req, seq_id)``."""
        eng = self.eng
        B = eng.cfg.max_batch_size
        V = eng.model_cfg.vocab_size
        keys = np.zeros((B, 2), np.uint32)
        temp = np.zeros((B,), np.float32)
        top_p = np.ones((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        bias = np.zeros((B, V), np.float32)
        adapter = np.full((B,), eng._base_row, np.int32)
        for g, (req, seq_id) in by_row.items():
            keys[g, 0] = np.uint32(
                (req.sampling.seed or seq_id) & 0xFFFFFFFF)
            temp[g] = req.sampling.temperature
            top_p[g] = req.sampling.top_p
            top_k[g] = req.sampling.top_k
            for tok_id, b in req.sampling.logit_bias:
                if 0 <= tok_id < V:
                    bias[g, tok_id] = b
            adapter[g] = eng._adapter_row_of(req)
        return (jnp.asarray(keys), jnp.asarray(temp), jnp.asarray(top_p),
                jnp.asarray(top_k), jnp.asarray(bias),
                jnp.asarray(adapter))

    def _unpack_row(self, out: Any, g: int):
        """(tok, first_lp) for row g of one packed call's output."""
        first_lp = None
        if self.eng.cfg.logprobs_topk and isinstance(out, tuple):
            out, chosen, tk_ids, tk_vals = out
            first_lp = (
                float(np.asarray(chosen)[g]),
                [(int(t), float(v)) for t, v in zip(
                    np.asarray(tk_ids)[g], np.asarray(tk_vals)[g])],
            )
        return int(np.asarray(out)[g]), first_lp

    # -- interface ---------------------------------------------------------
    def group_prefill(self, items: list, chain_by_req: dict) -> list:
        eng = self.eng
        loop = eng.stats.loop
        ns0 = loop.prefill_ns()
        outer = loop.enter(
            PREFILL_DISPATCH,
            {"group": len(items), "tokens": sum(it[2] for it in items)}
            if loop.capture else None)
        self._observe_admission(items, chain_by_req, lambda it: None)
        segs = []
        by_row = {}
        for g, (req, seq_id, n, _total) in enumerate(items):
            pages = eng.allocator.pages(seq_id)
            page_row = np.zeros((eng.cfg.max_pages_per_seq,), np.int32)
            page_row[: len(pages)] = pages
            segs.append(_Seg(g=g, req=req, tokens=req.prompt, start=0,
                             page_row=page_row))
            by_row[g] = (req, seq_id)
        sampling_args = self._sampling_rows(by_row)
        final_out, info = self._run_packed(segs, sampling_args)
        unpacked = [self._unpack_row(final_out[s.g], s.g) for s in segs]
        loop.resume(outer)
        prefill_ms = (loop.prefill_ns() - ns0) / 1e6
        eng.stats.note_prefill_call(prefill_ms, info["real"])
        results = []
        for s, (req, seq_id, n, total), (tok, first_lp) in zip(
                segs, items, unpacked):
            eng.phases.observe(
                "prefill", prefill_ms,
                req.trace.trace_id if req.trace is not None else "")
            if req.trace is not None:
                req.trace.prefill(
                    prefill_ms, bucket=info["bucket"], group=len(items),
                    padded_frac=info["padded_frac"],
                    chunks=info["chunks"])
            results.append(GroupResult(
                req=req, seq_id=seq_id, n=n, total=total, tok=tok,
                first_lp=first_lp, page_row=s.page_row,
                adapter_row=int(np.asarray(sampling_args[5])[s.g])))
        logger.debug("ragged prefill G=%d tokens=%d padded=%d calls=%d",
                     len(items), info["real"], info["padded"],
                     info["calls"])
        return results

    def single_prefill(self, req, seq_id, suffix, prefix_len, n, total,
                       pt, bucket, sampling_args):
        # sampling_args are already [1]-wide rows built by _admit_one —
        # widen to the packed call's [B] layout at row 0
        eng = self.eng
        B = eng.cfg.max_batch_size
        V = eng.model_cfg.vocab_size
        keys1, temp1, top_p1, top_k1, bias1, adapter1 = sampling_args
        keys = np.zeros((B, 2), np.uint32)
        keys[0] = np.asarray(keys1)[0]
        temp = np.zeros((B,), np.float32)
        temp[0] = float(np.asarray(temp1)[0])
        top_p = np.ones((B,), np.float32)
        top_p[0] = float(np.asarray(top_p1)[0])
        top_k = np.zeros((B,), np.int32)
        top_k[0] = int(np.asarray(top_k1)[0])
        bias = np.zeros((B, V), np.float32)
        bias[0] = np.asarray(bias1)[0]
        adapter = np.full((B,), eng._base_row, np.int32)
        adapter[0] = int(np.asarray(adapter1)[0])
        wide = (jnp.asarray(keys), jnp.asarray(temp), jnp.asarray(top_p),
                jnp.asarray(top_k), jnp.asarray(bias),
                jnp.asarray(adapter))
        page_row = np.asarray(pt[0], np.int32)
        seg = _Seg(g=0, req=req, tokens=suffix, start=prefix_len,
                   page_row=page_row)
        res = self._run_packed([seg], wide, cancellable=req)
        if isinstance(res, str):
            return res
        final_out, info = res
        info["consumed"] = 0  # packing already ran the whole suffix
        tok_out = final_out[0]
        return tok_out, info


def resolve_attention_backend(engine: "Engine") -> tuple[str, str]:
    """The prefill fallback matrix (ISSUE 10): (resolved backend name,
    WHY), exported verbatim on /state so an operator can see which
    program family a replica actually runs and the reason — never a
    silent behavior change.

    | requested     | mesh | TPU | kv dtype  | resolved      | attention impl      |
    |---------------|------|-----|-----------|---------------|---------------------|
    | xla-bucketed  | any  | any | any       | xla-bucketed  | XLA dense (bucketed)|
    | pallas-ragged | no   | yes | native    | pallas-ragged | Pallas kernel       |
    | pallas-ragged | no   | yes | int8/int4 | pallas-ragged | XLA windowed (dequant at read) |
    | pallas-ragged | no   | no  | any       | pallas-ragged | XLA windowed        |
    | pallas-ragged | yes  | any | any       | pallas-ragged | XLA windowed (SPMD) |

    | pallas-ragged | any  | any | any, qwen3_next, axk1 | xla-bucketed | XLA dense (bucketed) |

    The llama and mixtral families provide a ragged prefill entry
    point. ``qwen3_next`` does not (its DeltaNet state would have to
    reset at every packed segment's start and its convolution would
    have to stop at it; ROADMAP.md M4), nor does ``axk1`` (its chunk
    attention walks ONE row's page window; ROADMAP.md M3), so the last
    row routes them to the bucketed backend — as it does hand-built
    ``ModelFns`` with ``prefill_ragged=None``.

    The Pallas kernel itself stays single-chip TPU (its scalar-prefetch
    page walk addresses one local pool); a mesh keeps the RAGGED
    geometry — token-budget packing, offset resumes, the collapsed
    warm surface — through the XLA windowed program, which runs SPMD
    with the KV pool sharded on heads."""
    name = engine.cfg.attention_backend
    if name != "pallas-ragged":
        return "xla-bucketed", "requested"
    if engine._prefill_ragged_fn is None:
        return ("xla-bucketed",
                "pallas-ragged requested but this family's ModelFns "
                "have no ragged prefill entry point")
    # engine._ragged_reason explains the kernel-vs-windowed choice
    return "pallas-ragged", engine._ragged_reason


def resolve_decode_backend(cfg, model_cfg, mesh) -> tuple[str, str]:
    """The DECODE half of the fallback matrix: (resolved decode-attention
    impl, WHY), exported verbatim on /state as ``decode_attn_impl`` /
    ``decode_attn_reason``. Nothing is requested: the rung follows what
    the engine can observe — the head counts, the mesh's ``tp`` axis
    and (for the reason) the KV dtype.

    | mesh | heads % tp | resolved      |
    |------|------------|---------------|
    | no   | —          | xla-walk      |
    | yes  | 0          | xla-walk-spmd |
    | yes  | != 0       | xla-gather (narrowed) |

    ``xla-walk`` is the rung of every family: after the scatter, an
    online-softmax loop over the whole pages the LIVE rows hold
    (ops/paged_walk.py) — no padded window is gathered, int8/int4
    pages dequantize at the read, and ``decode_kv_pages_read`` /
    ``decode_kv_pages_live`` on /state say what it read. On a mesh it
    runs inside shard_map, each device over its LOCAL head shard of the
    pool (``-spmd``). ``xla-gather`` — the padded-window gather — is
    left with one row, geometric: head counts that do not divide the tp
    axis (the shard_map walk needs whole head shards per device; the
    GSPMD gather keeps reads head-local). MoE families run the same
    rung as dense ones — the expert einsums live in the MLP, outside
    attention. The speculative VERIFY step and the prefill chunk/tail
    programs read their page window a page at a time at every rung
    ([B, D+1] and [1, S] queries: another shape of problem; quantized
    pools run gather-dequant)."""
    quant = cfg.kv_cache_dtype in ("int8", "int4")
    tp = int(mesh.shape.get("tp", 1)) if mesh is not None else 1
    if tp > 1 and (model_cfg.n_heads % tp or model_cfg.n_kv_heads % tp):
        return ("xla-gather",
                f"heads ({model_cfg.n_heads}q/{model_cfg.n_kv_heads}kv) "
                f"do not divide tp={tp}: the shard_map local walk needs "
                "whole head shards per device; the GSPMD gather keeps "
                "reads head-local (narrowed row)")
    why = ("the page walk reads the whole pages the live rows hold"
           + (f"; {cfg.kv_cache_dtype} KV pages dequantize against "
              "their per-page scales at the read" if quant else ""))
    if mesh is not None:
        return ("xla-walk-spmd",
                f"{why}, each device its LOCAL head shard of the "
                "pool inside shard_map")
    return "xla-walk", why


def make_attention_backend(engine: "Engine") -> AttentionBackend:
    """Resolve EngineConfig.attention_backend through the fallback
    matrix above and build the backend (logged — never silent)."""
    resolved, reason = resolve_attention_backend(engine)
    engine.attn_reason = reason
    if resolved == "pallas-ragged":
        return RaggedPrefillBackend(engine)
    if engine.cfg.attention_backend != resolved:
        logger.warning("attention backend %s falls back to %s: %s",
                       engine.cfg.attention_backend, resolved, reason)
    return XlaBucketedBackend(engine)
