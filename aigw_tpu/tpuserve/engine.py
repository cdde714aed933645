"""Continuous-batching engine.

The TPU-native scheduler design (not a vLLM port):

- **Fixed decode geometry**: decode runs a single jit-compiled program of
  shape [max_batch, 1] every tick; finished slots are masked, not removed,
  so there is exactly ONE compiled decode program for the engine lifetime.
- **Bucketed prefill**: prompts are right-padded to power-of-two buckets so
  the number of compiled prefill programs is log(max_seq_len).
- **Sampling fused into the step**: logits never leave the device — each
  tick transfers only [max_batch] int32 sampled tokens to the host.
- **Donated cache**: the paged KV pool is donated through every step, so
  XLA updates it in place (no per-tick HBM copy of the cache).
- **Engine thread**: the loop runs in its own thread; JAX dispatch is
  async, so the thread overlaps host bookkeeping with device compute.
  Tokens flow back to asyncio consumers via loop.call_soon_threadsafe.

- **What a family keeps beside its pages** (models/cache.py): a family
  with per-slot state, latent pages or a window ring has everything that
  moves pages alone switched off by what it is (``features_off``: host
  KV tier, migration, parking, fleet fetch, speculation, LoRA — and the
  prefix cache). A family whose recurrent state can be SNAPSHOTTED with
  the page chain keeps the prefix cache: a prefill saves the slot's
  state into a fixed pool (three rows a slot, ``CacheSpec.snapshot_rows``
  — the one rule that sizes it) at every ``SNAPSHOT_EVERY_CHUNKS``-th
  chunk boundary of its prompt and at its last, a hit resumes at the
  deepest cached chain node that holds one, and
  ``state_snapshots_saved`` / ``_restored`` / ``_evicted``,
  ``state_snapshot_bytes_total`` and ``prefix_tokens_unrestorable`` say
  what it did.

Telemetry (KV occupancy, queue depth, active slots) feeds the endpoint
picker — the reference's EPP signal (SURVEY.md §3.4).
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import logging
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from aigw_tpu.analysis.registry import engine_thread_only
from aigw_tpu.models import kvq, llama
from aigw_tpu.models.cache import StateCache, spec_of
from aigw_tpu.ops import paged_walk
from aigw_tpu.obs.flight import (
    ADMIT,
    ADMIT_WAIT,
    DECODE_DISPATCH,
    EMIT,
    IDLE,
    OTHER,
    PREFILL_BLOCK,
    PREFILL_DISPATCH,
    REAP,
    ROW_UPDATE,
    STATE_BUILD,
    WINDOW_FETCH,
    LoopLedger,
)
from aigw_tpu.obs.metrics import LOOP_PHASES, EnginePhases
from aigw_tpu.obs import xla_events
from aigw_tpu.obs.xla_events import CompileTracker
from aigw_tpu.tpuserve import constrain, speculation
from aigw_tpu.tpuserve.kvcache import (
    OutOfPagesError,
    PageAllocator,
    PrefixCache,
    RefcountedAllocator,
    StateSnapshots,
    page_chain_hashes,
)
from aigw_tpu.tpuserve.sampling import (
    SamplingParams,
    apply_penalties,
    sample,
    spec_accept,
)

logger = logging.getLogger(__name__)


#: a prefill saves a snapshot of the slot's state at every this-many-th
#: chunk boundary of its prompt, and at the last (``Engine.chunk_boundary``)
SNAPSHOT_EVERY_CHUNKS = 4


@dataclass
class _SnapAdmission:
    """What an admission in flight knows of its snapshots."""

    chain_keys: list
    #: keys this admission's prefill saved a snapshot under
    saved: list = field(default_factory=list)
    #: the key of the snapshot it resumed from, held until it is done
    held: bytes | None = None


class EngineOverloadedError(Exception):
    """Admission queue full — callers should surface 429/503."""


def device_memory_stats() -> tuple[int, int]:
    """Live (bytes_in_use, bytes_limit) of device 0 from jax
    memory_stats() — the MEASURED per-device HBM signal /state exports
    (VERDICT r5: the topology-aware picker consumed labels, not
    signals). (0, 0) on backends without memory stats (CPU)."""
    try:
        ms = jax.local_devices()[0].memory_stats() or {}
    except Exception:  # noqa: BLE001 — telemetry must never raise
        return 0, 0
    return (int(ms.get("bytes_in_use", 0) or 0),
            int(ms.get("bytes_limit", 0) or 0))


def device_memory_stats_all() -> list[dict]:
    """Identity (id, platform, kind, coords) and live memory_stats()
    bytes (in use, limit, peak) for EVERY local device — the
    mesh-serving fix for PR 9's device-0-only poll (a sharded engine's
    hottest device is rarely device 0). Zero bytes on backends without
    memory stats (CPU); the list itself is still real so per-device
    KV/param accounting has a device to hang off."""
    out: list[dict] = []
    try:
        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — telemetry must never raise
        return out
    for d in devices:
        try:
            ms = d.memory_stats() or {}
        except Exception:  # noqa: BLE001
            ms = {}
        out.append({
            "id": int(d.id),
            "platform": str(getattr(d, "platform", "")),
            "kind": str(getattr(d, "device_kind", "")),
            "coords": list(getattr(d, "coords", None) or ()),
            "bytes_in_use": int(ms.get("bytes_in_use", 0) or 0),
            "bytes_limit": int(ms.get("bytes_limit", 0) or 0),
            "peak_bytes_in_use": int(
                ms.get("peak_bytes_in_use", 0) or 0),
        })
    return out


def _per_device_bytes(tree: Any) -> dict[int, int]:
    """Bytes each device holds of ``tree``'s array leaves, from the
    arrays' real shard layout (an unsharded array is one shard on one
    device): under tensor parallelism each device holds ≈ total/tp
    (tests/test_mesh_serving.py)."""
    per: dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        if getattr(leaf, "is_deleted", lambda: False)():
            # a donated-away buffer (mid-reassignment on another
            # thread) is a stats gap, not an engine-loop fatality
            continue
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            continue
        for sh in shards:
            d = int(sh.device.id)
            per[d] = per.get(d, 0) + int(sh.data.nbytes)
    return per


class MigrationError(Exception):
    """A migration export/import could not be performed (request not
    active, finished during the cut, prefix cache disabled, malformed
    blob). The session is left exactly as it was — a failed export
    never kills the stream it tried to move."""


@dataclass
class EngineConfig:
    max_batch_size: int = 8
    max_seq_len: int = 2048
    page_size: int = 128
    num_pages: int = 0  # 0 = auto: enough for max_batch full sequences
    min_prefill_bucket: int = 64
    # Decode steps executed per host round-trip (lax.scan inside one jitted
    # program). Amortizes host↔device latency; tokens sampled after a
    # sequence's EOS within a window are discarded by the host.
    decode_steps_per_tick: int = 8
    # Automatic prefix caching: full prompt pages are content-addressed and
    # shared across requests (chat-history reuse → TTFT win).
    enable_prefix_cache: bool = True
    # Admission cap: waiting requests beyond this are rejected at submit
    # (the server surfaces 429 + retry-after) instead of growing an
    # unbounded queue.
    max_queued_requests: int = 256
    # Sequence-parallel prefill: prompts at least this long run through
    # the ring-attention path when the mesh has an sp axis > 1 (context
    # parallelism for prompts whose attention working set exceeds one
    # chip). Shorter prompts use the plain prefill — the ICI rotation
    # only pays for itself on long sequences.
    sp_prefill_min_tokens: int = 1024
    # Sequence-parallel chunked prefill (the long-context path):
    # "chunked" runs sp prompts as sp_chunk_tokens-sized ring-attention
    # chunk steps (models.<family>.prefill_sp_suffix) with a decode
    # tick between chunks — the chunked-prefill liveness guarantee
    # holds on the sp path too, and the path resumes at page-aligned
    # prefix-cache / migration offsets. "monolithic" restores the
    # single full-rung ring-attention program (no interleaving, no
    # resume — prefix hits fall back to the single-device chunk loop).
    # The chunked path additionally requires page_size % sp == 0 (the
    # gathered page window is sharded over sp); other geometries fall
    # back to monolithic automatically.
    sp_prefill_mode: str = "chunked"  # "chunked" | "monolithic"
    # Chunk size for the sp chunked path, rounded up to a multiple of
    # the sp axis at use. Larger than prefill_chunk_tokens by default:
    # each sp chunk step re-gathers the sequence's page window, so
    # chunks amortize the window pass while staying small enough that
    # decode ticks interleave every few hundred ms at 32k-128k.
    sp_chunk_tokens: int = 2048
    # Chunked prefill: prompts longer than this run as fixed-size
    # prefill_suffix steps with a decode tick between chunks — bounding
    # both the largest compiled bucket and how long active streams
    # stall behind a long prompt. 0 disables (whole-prompt prefill).
    # Default ON: a long prompt must never stall in-flight decodes for
    # its whole prefill (model families without prefill_suffix fall
    # back to whole-prompt prefill automatically).
    prefill_chunk_tokens: int = 256
    # Adaptive decode windows: shrink the per-tick window to
    # min_decode_steps_per_tick while the admission queue is non-empty
    # or a stream just started (TTFT-/admission-latency-sensitive), and
    # regrow to decode_steps_per_tick once the batch is steady
    # (throughput-sensitive). Each window size is its own compiled
    # program; the ladder is {min, max} so at most two decode programs
    # exist per page bucket.
    adaptive_decode_window: bool = True
    # Small window used under pressure. 0 = auto: max(1, K // 4).
    min_decode_steps_per_tick: int = 0
    # Idle-burst coalescing: when the engine is COMPLETELY idle and a
    # request arrives, wait this long for the rest of its burst before
    # admitting, so B near-simultaneous arrivals prefill as ONE batched
    # [G, S] call instead of a 1+(B-1) split (a burst's submits span a
    # few ms of event-loop scheduling). Busy engines never wait —
    # arrivals already coalesce between decode windows. 0 disables.
    admission_coalesce_ms: float = 3.0
    # Pre-compile the batched-prefill programs for the N smallest
    # prompt buckets at warmup (all power-of-two group sizes up to
    # max_batch_size): a traffic burst must not pay an XLA prefill
    # compile for a group shape the warm traffic happened not to hit.
    # 0 = off (each (group, bucket) shape compiles on first use).
    warm_prefill_buckets: int = 0
    # Pre-compile the decode-window ladder (lean/full × window sizes ×
    # spec verify rungs) AND the row-update scatters at the first N
    # pow2 PAGE buckets, not just the quiesced bucket-1 state (ISSUE
    # 10): the decode program re-traces per page-table width, so the
    # first admission whose sequence needs a bucket the warmup never
    # visited pays an XLA compile (and a pipeline-draining rebuild) on
    # the hot path — the CompileTracker showed exactly this at first
    # mesh admission. 0 keeps the old single-bucket warm (cheapest
    # cold start); N warms buckets 1, 2, …, 2^(N-1) capped at
    # max_pages_per_seq.
    warm_decode_buckets: int = 0
    # Prefill bucket rungs per octave: 1 keeps the classic power-of-two
    # ladder (worst-case padding ≈ 2× the prompt); 2 adds a 1.5×S rung
    # between octaves (worst-case padding 1.5×); 4 adds 1.25×/1.5×/1.75×
    # rungs (worst-case 1.25×). Prefill compute scales with the PADDED
    # length, so padding waste is paid directly in TTFT — a ~90-token
    # chat prompt on the pow2 ladder runs a 128-wide prefill, ~35%
    # slower than the 96-wide rung. Compiled-program count stays
    # bounded: rungs × log2(max_seq/min_bucket) shapes per group size.
    prefill_bucket_rungs: int = 2
    # Speculative decoding: the maximum draft tokens verified per decode
    # step (0 = off). Each draft-length rung of the adaptive ladder
    # ({0, 2, 4, 8}-style, capped here) is one fixed-shape [B, D+1]
    # verify program; a step advances by the accepted count — see
    # tpuserve/speculation.py.
    spec_tokens: int = 0
    # Adaptive draft length: per-slot controllers walk the rung ladder
    # on a rolling acceptance EWMA, collapsing to D=0 (plain decode,
    # zero overhead) on adversarial traffic and re-probing
    # occasionally. False pins every eligible slot at spec_tokens —
    # the fixed-D A/B and determinism knob.
    spec_adaptive: bool = True
    # Prefill attention backend (tpuserve/attention.py):
    # "xla-bucketed" — the classic per-sequence bucket ladder with
    # batched same-bucket groups; "pallas-ragged" — a mixed-length
    # admission burst packs into ONE ragged paged-attention program
    # sized by TOTAL tokens (padded to a token-budget chunk rung, not
    # per-sequence buckets), with per-sequence start offsets making
    # prefix-cache resumes and chunked continuations first-class.
    # pallas-ragged auto-falls back per the fallback matrix in
    # tpuserve/attention.py: the Pallas kernel on single-chip TPU, the
    # XLA windowed program off-TPU AND on a mesh (it runs SPMD with KV
    # sharded on heads), xla-bucketed only for model families without a
    # ragged prefill entry point; /state exports the resolution + why.
    attention_backend: str = "xla-bucketed"
    # Ragged backend geometry: packed totals pad to multiples of this
    # chunk (plus two sub-chunk rungs for short tails/resumes)...
    ragged_chunk_tokens: int = 256
    # ...and one packed call carries at most chunk × this many tokens;
    # larger bursts split at budget boundaries with decode ticks
    # interleaved (chunked-prefill liveness, kept). The compiled
    # prefill surface is the rung ladder: ~(ragged_max_chunks + 2)
    # programs for ANY batch geometry.
    ragged_max_chunks: int = 8
    # KV cache element dtype: "bfloat16" (serving default), "float32"
    # (doubles KV HBM but removes the bf16 rounding that lets near-tied
    # logits argmax-flip between mathematically equivalent schedules —
    # the deterministic-equivalence test mode), or "int8"/"int4"
    # (ISSUE 13, models/kvq.py): pages store quantized rows plus
    # per-page scale blocks (one f32 absmax scale per token row × KV
    # head), dequantized at the read — ~0.52x / ~0.27x
    # the bf16 KV bytes at head_dim 128, which is the
    # concurrent-sessions-per-chip lever. Quantized pages ride the
    # whole stack (spill/revive, migration + fleet fetch at native
    # dtype + scales, spec verify, CoW).
    kv_cache_dtype: str = "bfloat16"
    # Multi-tenant fairness guard (ISSUE 7): the maximum decode slots
    # any one tenant (GenRequest.tenant; "" is one anonymous tenant) may
    # hold concurrently. Admissions beyond the cap are deferred (left at
    # the queue head, arrival order kept) until the tenant frees a slot,
    # so one tenant's burst can never occupy the whole batch while
    # another tenant's single request starves. Admission is additionally
    # deficit-weighted whenever multiple tenants are queued: tenants
    # holding fewer in-flight slots admit first. 0 disables the cap
    # (weighted ordering still applies).
    tenant_slot_cap: int = 0
    # Prefill/decode disaggregation (ISSUE 8): a slot whose prefill is
    # done but whose decode is still young (generated <= this) counts
    # toward the /state ``migratable_slots`` gauge — the gateway's
    # signal for handing completed-prefill sessions to a decode-leaning
    # replica. 0 counts every decoding slot as eligible. Export itself
    # is not gated by this (the orchestrator owns the policy).
    migration_young_tokens: int = 64
    # Grammar-constrained decoding (ISSUE 9, tpuserve/constrain.py):
    # structured outputs (response_format json_object / json_schema) and
    # tool-call envelopes enforced on-device by composing a per-slot
    # [V] token mask into the existing logit-bias row. False makes the
    # server 400 such requests instead (the pre-subsystem contract,
    # minus the silent free-text 200).
    constrained_decoding: bool = True
    # KV memory hierarchy (ISSUE 11, tpuserve/kvhost.py): byte budget of
    # the host-RAM spill tier. When > 0 (and the prefix cache is on), a
    # cache-registered page reclaimed under pool pressure is copied
    # device→host and parked in a bounded LRU keyed by its content
    # chain hash instead of being dropped; a later prefix hit on a
    # spilled chain revives the pages through the warmed batched import
    # scatters (no recompute, no hot XLA compile). 0 disables the tier
    # (classic eviction). The budget counts page bytes in the pool's
    # native KV dtype.
    kv_host_bytes: int = 0
    # Priority-tiered serving (ISSUE 19): ceiling on the fraction of
    # decode slots the offline batch class may occupy at once (at least
    # one slot when > 0). Batch requests admit only when the
    # interactive queue is empty and stay under this footprint, so a
    # saturating /v1/batches backlog can never crowd interactive
    # admissions out of the batch — interactive pressure additionally
    # preempts batch sessions (window shrink, then park) to reclaim
    # slots. 1.0 lets batch soak every idle slot; interactive still
    # evicts it on arrival.
    batch_slot_frac: float = 0.5
    # Per-token logprobs (vLLM/OpenAI parity): when > 0, the decode scan
    # also returns the chosen token's log-probability and the top-k
    # (ids, values) per step, and requests may set want_logprobs. Static
    # at trace time — 0 keeps the default decode program byte-identical.
    # Mutually exclusive with spec_tokens (the verify step emits a
    # variable number of tokens per step; logprob bookkeeping for
    # rejected drafts is not worth the complexity).
    logprobs_topk: int = 0

    def __post_init__(self) -> None:
        if self.logprobs_topk > 0 and self.spec_tokens > 0:
            raise ValueError(
                "logprobs_topk and spec_tokens are mutually exclusive")
        from aigw_tpu.tpuserve.attention import BACKENDS

        if self.attention_backend not in BACKENDS:
            raise ValueError(
                f"attention_backend must be one of {BACKENDS} "
                f"(got {self.attention_backend!r})")
        if self.ragged_chunk_tokens < 8 or self.ragged_max_chunks < 1:
            raise ValueError(
                "ragged_chunk_tokens must be >= 8 and ragged_max_chunks "
                ">= 1")
        if not 0.0 < self.batch_slot_frac <= 1.0:
            raise ValueError(
                f"batch_slot_frac must be in (0, 1] "
                f"(got {self.batch_slot_frac})")
        if self.prefill_bucket_rungs not in (1, 2, 4):
            raise ValueError(
                f"prefill_bucket_rungs must be 1, 2, or 4 "
                f"(got {self.prefill_bucket_rungs})")
        from aigw_tpu.models import kvq

        if self.kv_cache_dtype not in kvq.KV_DTYPES:
            raise ValueError(
                f"kv_cache_dtype must be one of {kvq.KV_DTYPES} "
                f"(got {self.kv_cache_dtype!r})")
        if self.min_decode_steps_per_tick == 0:
            self.min_decode_steps_per_tick = max(
                1, self.decode_steps_per_tick // 4)
        if self.min_decode_steps_per_tick > self.decode_steps_per_tick:
            raise ValueError(
                f"min_decode_steps_per_tick "
                f"({self.min_decode_steps_per_tick}) exceeds "
                f"decode_steps_per_tick ({self.decode_steps_per_tick})")
        if self.max_seq_len % self.page_size != 0:
            raise ValueError(
                f"max_seq_len ({self.max_seq_len}) must be a multiple of "
                f"page_size ({self.page_size})"
            )
        if self.num_pages == 0:
            self.num_pages = (
                self.max_batch_size * self.max_seq_len // self.page_size
            )

    @property
    def max_pages_per_seq(self) -> int:
        return self.max_seq_len // self.page_size


@dataclass
class GenRequest:
    prompt: list[int]
    max_tokens: int
    sampling: SamplingParams
    stop_token_ids: tuple[int, ...] = ()
    # (token_id, finish_reason): token_id < 0 means no token, just finish
    emit: Callable[[int, str | None], None] = lambda t, f: None
    id: int = 0
    enqueued_at: float = field(default_factory=time.monotonic)
    # set by the consumer to abandon the request (client disconnect / stop
    # sequence hit); the engine frees the slot at the next tick
    cancelled: threading.Event = field(default_factory=threading.Event)
    # LoRA adapter name ("" = base model)
    adapter: str = ""
    # Tenant key for fairness + accounting ("" = anonymous). The server
    # derives it from the x-aigw-tenant header (relayed by the gateway)
    # or the adapter suffix of the requested model name.
    tenant: str = ""
    # Priority class (ISSUE 19): "interactive" rides the normal
    # admission queue; "batch" rides the never-shed offline queue,
    # admits only into slots interactive doesn't want (ceiling:
    # batch_slot_frac), and may be preempted — parked host-side and
    # resumed later byte-identically — when interactive arrivals need
    # its slot. The server derives it from the x-aigw-priority header
    # or the /v1/batches surface.
    priority: str = "interactive"
    # Per-token logprobs: when set (and the engine was built with
    # logprobs_topk > 0), emit_lp is called INSTEAD of emit with
    # (token, finish, logprob, top) where top = [(token_id, logprob)]
    # of the engine's top-k (callers slice to the request's own k).
    emit_lp: "Callable[[int, str | None, float | None, list | None], None] | None" = None
    # Pre-computed page-chain prefix hashes (kvcache.page_chain_hashes
    # over this prompt at the ENGINE's page size) — the server's
    # tokenizer pool rolls them during encode so admission-time lookup
    # costs no extra pass over the prompt. None (or a stale length —
    # defensive) falls back to hashing at classification time.
    prefix_hashes: list | None = None
    # Migration continuation (ISSUE 8): set on requests that RESUME a
    # session exported by another replica. The prompt then carries the
    # original prompt PLUS every token generated so far; this dict
    # restores the slot state the continuation must inherit to stay
    # byte-identical with a solo-served run:
    #   orig_prompt_len — where the original prompt ended (tokens past
    #       it are generated history: they seed the repetition-penalty
    #       counts and are EXCLUDED from usage input accounting),
    #   generated — tokens already emitted upstream (usage offset),
    #   key_seed / key_counter — the sampling key state at the cut, so
    #       the first resumed token samples with the exact key the solo
    #       run would have used at that position.
    # None everywhere else; continuation requests always take the
    # per-request admission path (never the batched prefill).
    import_state: dict | None = None
    # Grammar constraint (ISSUE 9): a compiled, shared
    # constrain.TokenFSM — the slot builds its own ConstraintState
    # cursor at admission. None = unconstrained (the only path touched
    # for such requests is an `is None` check, keeping unconstrained
    # streams byte-identical with the subsystem compiled in).
    constraint: Any = None
    # Request-lifecycle sink (obs.flight.RequestTrace or None): the
    # engine reports queue-wait, admission classification, prefill
    # geometry, first-token, decode windows, and EOS/cancel through it
    # into the flight recorder + the request's span tree. Duck-typed and
    # optional — None costs one attribute check per call site.
    trace: Any = None
    # Usage metering sink (ISSUE 20): called EXACTLY ONCE per request
    # lifetime with the engine-truth MeterRecord dict, on the engine
    # thread, strictly before the terminal emit — so a consumer that
    # dequeues the finish item observes the record. Migrated/parked
    # continuations do NOT fire it at the cut; the accumulated meter
    # rides the export blob and the resumed slot's record covers the
    # whole spliced stream. None = metering off for this request.
    meter_sink: "Callable[[dict], None] | None" = None


@dataclass
class _Slot:
    req: GenRequest
    # Position at which the *pending input token* will be written by the
    # next decode step. After prefilling a prompt of length n, the first
    # sampled token is the pending input at position n.
    pos: int
    generated: int
    key_seed: int
    pending_token: int = 0
    limit: int = 0  # exclusive max write position (page-safety fence)
    page_row: np.ndarray | None = None
    # generated-token histogram (repetition penalties survive state
    # rebuilds across admissions)
    token_counts: dict[int, int] = field(default_factory=dict)
    adapter_row: int = 0
    # ordered generated tokens (the slot's device history row is built
    # from prompt + these — uploaded by the incremental row update, not
    # a full state rebuild)
    gen_tokens: list[int] = field(default_factory=list)
    # speculative decoding (spec-eligible slots only): the adaptive
    # draft-length controller, the prefix-cache continuation lookahead
    # (tokens + the absolute position of tokens[0]), and the draft_len
    # value currently live on device (to skip no-op row patches)
    ctrl: Any = None  # speculation.DraftController | None
    la_base: int = 0
    la_tokens: list[int] = field(default_factory=list)
    dev_draft_len: int = 0
    # monotonic time of the slot's first emitted token (feeds the
    # decode-per-token histogram at finish)
    first_emit_at: float = 0.0
    # grammar-constrained decoding (ISSUE 9): the slot's FSM cursor and
    # its rollback epoch — windows capture the epoch at dispatch, and a
    # drain whose captured epoch trails the slot's discards that
    # window's tokens (they were sampled past a grammar violation)
    cn: Any = None  # constrain.ConstraintState | None
    cn_epoch: int = 0
    # usage metering accumulators (ISSUE 20) — engine-truth per-request
    # counts folded into the MeterRecord at the terminal emit. Residency
    # is integrated piecewise: m_res_bytes is the slot's current KV
    # page·bytes and m_res_t0 the wall clock it last changed, so
    # HBM page·byte·seconds accrue as sum(bytes × dwell) across segments.
    m_prefill_real: int = 0
    m_prefill_padded: int = 0
    m_prefix_reused: int = 0
    m_spec_drafted: int = 0
    m_spec_accepted: int = 0
    m_res_t0: float = 0.0
    m_res_bytes: int = 0
    m_hbm_pbs: float = 0.0
    # carry imported from a migration/park export blob: the meter
    # accumulated by earlier segments of this spliced stream
    m_carry: dict | None = None


@dataclass
class EngineStats:
    active_slots: int = 0
    queued: int = 0
    kv_pages_free: int = 0
    kv_occupancy: float = 0.0
    tokens_generated: int = 0
    # extra tokens landed by accepted speculative drafts (beyond the one
    # token per step the plain decode path yields)
    spec_accepted: int = 0
    # draft tokens proposed to the verifier (per-slot draft length ×
    # steps the slot was live in a speculative window)
    spec_drafted: int = 0
    # cumulative accepted / drafted (refreshed each tick)
    spec_accept_rate: float = 0.0
    # draft width of the most recent dispatch (0 = plain decode — the
    # adaptive ladder is collapsed or speculation is off)
    spec_draft_len: int = 0
    # adaptive-ladder transitions (includes rung-0 re-probes as ups)
    spec_rung_ups: int = 0
    spec_rung_downs: int = 0
    # admissions whose draft source includes a prefix-cache
    # continuation lookahead (repeated-traffic free drafts)
    spec_lookahead_slots: int = 0
    # full device-state rebuilds that drained a LIVE pipeline (page-
    # bucket growth only — speculative admission no longer forces one;
    # from-idle builds are not counted). The zero-rebuild acceptance
    # criterion asserts on this.
    state_rebuilds: int = 0
    # adapter serving subsystem (ISSUE 7, tpuserve/adapters.py): hot
    # loads into device rows, LRU evictions under row pressure, the
    # resident-adapter count, and how many live slots currently decode
    # through a non-base adapter row
    adapter_loads: int = 0
    adapter_evictions: int = 0
    adapter_resident: int = 0
    adapter_slots: int = 0
    # multi-tenant fairness surface: distinct tenants holding decode
    # slots, the largest per-tenant in-flight count, and admissions
    # deferred by the per-tenant slot cap (each deferral = one pass a
    # request waited because its tenant was at cap)
    tenants_active: int = 0
    tenant_max_slots: int = 0
    tenant_deferrals: int = 0
    # priority-tiered serving (ISSUE 19): the offline batch class.
    # batch_queued counts waiting batch work (the never-shed queue plus
    # host-parked preempted sessions), batch_active the decode slots it
    # holds now (always <= the batch_slot_frac ceiling),
    # batch_preemptions the sessions parked off-device because an
    # interactive arrival wanted the slot, batch_resumed the parked
    # sessions re-admitted (byte-identical continuation), batch_tokens
    # the tokens the class has generated (the volume the idle slots
    # soaked up).
    batch_queued: int = 0
    batch_active: int = 0
    batch_preemptions: int = 0
    batch_resumed: int = 0
    batch_tokens: int = 0
    # prefill/decode disaggregation (ISSUE 8): sessions exported to /
    # imported from other replicas, the KV pages that moved with them,
    # and the live count of migration-eligible slots (prefill done,
    # decode young — the gateway's disaggregation signal)
    migrations_out: int = 0
    migrations_in: int = 0
    migration_pages_out: int = 0
    migration_pages_in: int = 0
    migratable_slots: int = 0
    # grammar-constrained decoding (ISSUE 9, tpuserve/constrain.py):
    # live constrained slots, requests admitted with a constraint,
    # window rollbacks (a decode window ran past a grammar boundary —
    # tokens after the violation were discarded and the slot's row
    # re-uploaded, the spec-decode rejection discipline), mask-row
    # device patches, and the compiled-grammar cache size
    constrained_slots: int = 0
    constraint_requests: int = 0
    constraint_rollbacks: int = 0
    constraint_mask_updates: int = 0
    constraint_grammars: int = 0
    # real per-device memory signals (ISSUE 9 satellite, VERDICT r5
    # residue): live jax memory_stats() bytes (0 on backends without
    # them, e.g. CPU) + the KV pool's byte occupancy — the picker's
    # first MEASURED memory signal
    device_bytes_in_use: int = 0
    device_bytes_limit: int = 0
    device_memory_frac: float = 0.0
    kv_pool_bytes: int = 0
    kv_bytes_in_use: int = 0
    # mesh serving (ISSUE 10): REAL per-device signals. device_count is
    # the engine's local device population (1 off-mesh);
    # device_memory_frac_worst is the max memory_stats fraction across
    # them — the picker scores the WORST device, not device 0 (one hot
    # shard saturates the whole tensor-parallel step). The ICI pair is
    # the analytical per-device collective volume of the TP/EP layout
    # (parallel/sharding.analytical_ici_bytes_per_token): bytes one
    # decoded token moves over ICI, and its cumulative total
    device_count: int = 1
    device_memory_frac_worst: float = 0.0
    ici_bytes_per_token: int = 0
    ici_bytes_total: int = 0
    # quantized KV pages (ISSUE 13, models/kvq.py): bits per stored KV
    # element (32/16 native, 8/4 quantized) and the all-layer HBM bytes
    # one cached token costs INCLUDING its per-page scale share — the
    # capacity-planning pair behind "half the KV bytes = twice the
    # concurrent sessions per chip"
    kv_quant_bits: int = 16
    kv_bytes_per_token: float = 0.0
    # KV memory hierarchy (ISSUE 11): the host-RAM spill tier and the
    # cross-replica page fetch surface. Spills/revives/spill-evictions
    # mirror the HostKVTier counters (pages demoted to host RAM on
    # eviction, pages promoted back by a prefix hit, pages the host
    # LRU budget dropped); the live pair is what the tier holds NOW.
    # Fetches count cross-replica /kv/pages traffic: _out = page sets
    # this replica served to siblings, _in = page sets imported from a
    # sibling ahead of a local prefill.
    kv_spills: int = 0
    kv_revives: int = 0
    kv_spill_evictions: int = 0
    kv_spilled_pages: int = 0
    kv_spill_bytes: int = 0
    kv_host_bytes: int = 0
    kv_fetches_out: int = 0
    kv_fetches_in: int = 0
    kv_fetch_pages_out: int = 0
    kv_fetch_pages_in: int = 0
    prefills: int = 0
    sp_prefills: int = 0  # prefills routed through ring attention
    # long-context sp surface: sp prefills that ran as chunked
    # ring-attention steps (vs one monolithic full-rung program), and
    # how many of those resumed at a nonzero cached offset (prefix-
    # cache partial hit / migration continuation on the sp path)
    sp_chunked_prefills: int = 0
    sp_resume_prefills: int = 0
    # short requests admitted AT a chunk boundary of a running sp
    # chunked prefill — the decode-liveness counter: each one is a
    # first token that did not wait out a long prefill
    sp_interactive_admits: int = 0
    chunked_prefill_steps: int = 0  # intermediate chunk device steps
    decode_steps: int = 0
    # decode steps dispatched while an occupied slot's request truncates
    # (top_k > 0 or top_p < 1): the steps in which `sample` pays its
    # vocabulary-wide sort. Over decode_steps: the share that pay it
    sample_sort_steps: int = 0
    # what the decode programs read of the page pool, a layer, counted
    # on the device per step (Engine._kv_pages): (row, page) pairs the
    # step's program reads — on the page walk the loop's own trip
    # bound x pairs a trip, elsewhere the whole [B, P] window —
    # and the pages its live rows hold. read / live is the read
    # amplification; 1.0 reads exactly what is live
    decode_kv_pages_read: int = 0
    decode_kv_pages_live: int = 0
    # what the decode programs of a per-slot-state family read of the
    # state pool, a layer, counted on the device per step: slots whose
    # state a DeltaNet layer's live-row loop read (its trips x rows a
    # trip: ONE value bounds the loop and is counted) and the live
    # rows. read / live is the state read amplification (0 elsewhere)
    decode_state_rows_read: int = 0
    decode_state_rows_live: int = 0
    # a family's own routing-tape columns (``model_cfg.tape_extra``),
    # each summed over its layers (0 elsewhere). A group-limited router
    # over a share of its experts: of the groups real tokens kept
    # (``moe_group_slots``), those that hold a held expert — hits /
    # slots is what the group limit does to this share's load. A latent
    # family's prefill programs: (query, key) pairs their real queries
    # attended to, what their attention's work grows with
    moe_groups_kept_hits: int = 0
    moe_group_slots: int = 0
    prefill_keys_attended: int = 0
    # a share with no shared expert (models/mimo_v2.py): real tokens
    # none of whose picks is held here, summed over the expert layers —
    # they leave the layer unchanged here (over moe_total_assignments /
    # picks a token). Sliding-window layers, per decode step, summed
    # over live rows and window layers: keys a window layer's softmax
    # saw / keys the row's context holds — the share rises the moment
    # a window layer reads beyond its window
    moe_unserved_tokens: int = 0
    swa_keys_attended: int = 0
    swa_keys_in_context: int = 0
    prefix_cache_hits: int = 0
    prefix_tokens_reused: int = 0
    # a family whose prefix cache resumes from a SNAPSHOT of its
    # per-slot state (models/cache.py ``CacheSpec.snapshots``; all 0
    # elsewhere): snapshots copied into the pool at a chunk boundary of
    # a prefill / copied back into a slot by a hit / dropped least
    # recently used to make room for a newer one; the pool's bytes (a
    # constant); and prompt tokens whose pages were cached but had to
    # be prefilled again because no snapshot stood at or behind them
    state_snapshots_saved: int = 0
    state_snapshots_restored: int = 0
    state_snapshots_evicted: int = 0
    state_snapshot_bytes_total: int = 0
    prefix_tokens_unrestorable: int = 0
    # prefix-cache surface (ISSUE 3): misses counted over page-eligible
    # prompts (≥ one full page of potential reuse), so hit_rate is
    # hits / (hits + misses) over prompts the cache could have served
    prefix_cache_misses: int = 0
    prefix_cache_evictions: int = 0
    # full-prefix hits: the whole prompt's KV was cached — admission
    # skips the prompt prefill and runs a single-token resume against a
    # copy-on-write'd final page
    prefix_full_hits: int = 0
    prefix_cow_copies: int = 0
    # gauges refreshed from the cache/allocator each tick
    prefix_pages_resident: int = 0
    prefix_pages_pinned: int = 0
    prefix_cache_hit_rate: float = 0.0
    # adaptive decode window: the K chosen for the most recent dispatch
    # and how often the policy moved it (obs/metrics.py exports these)
    decode_window: int = 0
    window_shrinks: int = 0
    window_grows: int = 0
    # MoE routing surface (ISSUE 18, MoE families only — constant 0 on
    # dense models): cumulative (token, k) expert assignments placed /
    # dropped by the capacity fence across every layer, the resulting
    # drop fraction, and the hottest-expert load imbalance (max
    # per-expert tokens / mean — 1.0 is perfectly balanced). Counts are
    # over rows the programs processed, padding included. The picker
    # prices imbalance with the PR 10 worst-device discipline: a
    # replica is as fast as its hottest expert shard.
    moe_tokens_routed: int = 0
    moe_tokens_dropped: int = 0
    moe_dropped_frac: float = 0.0
    moe_expert_imbalance: float = 0.0
    # a family that holds one chip's SHARE of its experts (routes over
    # the router's whole width, computes what its own experts give):
    # assignments the router made anywhere / those that landed on a
    # held expert (real tokens only; their ratio is the local share,
    # 1/ep under a balanced router), and, summed over layers and decode
    # steps, how many held experts got at least one assignment (over
    # decode_steps x layers: the held experts a decode step touches).
    # moe_tokens_dropped stays structurally 0: there is no fence.
    moe_local_assignments: int = 0
    moe_total_assignments: int = 0
    moe_held_hits_decode: int = 0
    # the device cache beside the weights (models/cache.py): layers
    # that own pages, and the per-slot recurrent state of a hybrid
    # family — bytes a slot holds whatever its context, and the pool's
    # total (0 for families whose every layer has pages)
    kv_layers: int = 0
    state_bytes_per_slot: int = 0
    state_bytes_total: int = 0
    # serving-path phase breakdown (cumulative milliseconds).
    # prefill_ms / transfer_ms / emit_ms are VIEWS of the loop ledger
    # (properties below); first_emit_ms = host time from a prefill's
    # sampled token being host-available to its first-token emit
    # callback returning (the fast path's residual: slot setup +
    # prefix-cache insert + emit)
    first_emit_ms: float = 0.0
    # prefill padding tax (ISSUE 6): real prompt tokens vs tokens the
    # padded program geometry actually processed (bucket/batch padding
    # on xla-bucketed, chunk-rung residue on pallas-ragged);
    # padded_frac = 1 - real/padded, refreshed per tick — the
    # per-replica observable behind the ragged backend's claim
    prefill_tokens_real: int = 0
    prefill_tokens_padded: int = 0
    prefill_padded_frac: float = 0.0
    # prefill programs dispatched (a batched group, a chunk and a tail
    # are one call each): the unit the capture's counters are cut to
    prefill_calls: int = 0
    # warmup cost: wall time of the last warmup() and the compiled
    # hot-path program count it left behind (compile tracker) — the
    # "collapsed compile surface = faster cold start" observables
    warmup_ms: float = 0.0
    warm_programs: int = 0
    # age of the oldest queued request (picker queue-latency signal)
    queue_wait_ms: float = 0.0
    # XLA compile tracker (obs/xla_events.py): backend compiles observed
    # since the engine came up and their total wall time — refreshed per
    # tick; a post-warmup delta is a hot-path compile regression
    xla_compiles: int = 0
    xla_compile_ms: float = 0.0
    # persistent compile cache outcome, process-wide (weights compile
    # before the engine exists): of all compile requests, how many were
    # LOADED from the cache utils/boot.py placed and how many were built
    xla_cache_hits: int = 0
    xla_cache_misses: int = 0
    # the load ledger's process-wide totals by stage (trace, lowering,
    # and of the backend span the cache read + deserialize_and_load),
    # and what requests paid for: programs loaded after the server
    # called itself ready, with their milliseconds by stage
    xla_trace_ms: float = 0.0
    xla_lower_ms: float = 0.0
    xla_retrieval_ms: float = 0.0
    xla_late_loads: int = 0
    xla_late_ms: float = 0.0
    xla_late_trace_ms: float = 0.0
    xla_late_lower_ms: float = 0.0
    xla_late_retrieval_ms: float = 0.0
    # the process's boot timeline (utils/boot.py BootLedger), written
    # once by the server when it is ready: self time per phase from
    # the process's start, and their sum
    boot_import_ms: float = 0.0
    boot_backend_ms: float = 0.0
    boot_weights_ms: float = 0.0
    boot_weights_layout_ms: float = 0.0
    boot_engine_ms: float = 0.0
    boot_warmup_ms: float = 0.0
    boot_listen_ms: float = 0.0
    boot_ready_ms: float = 0.0
    # prefill rate the gateway prices prompt length with (/state
    # prefill_ms_per_token): a token-decayed average rather than the
    # process-lifetime mean, so a traffic-mix change (chunked-sp long
    # prompts start arriving) re-prices within roughly one half-life
    # of prefilled tokens instead of lagging forever. Both
    # accumulators decay by 0.5 ** (tokens / half_life) per observed
    # prefill call, so the ratio is an exponentially weighted mean
    # over the most recent ~PREFILL_RATE_HALF_LIFE_TOKENS tokens.
    prefill_ms_decayed: float = 0.0
    prefill_tokens_decayed: float = 0.0
    # usage metering (ISSUE 20): engine-truth accounting counters,
    # incremented ONLY inside _meter_emit — i.e. exactly when a
    # MeterRecord is handed to the request's sink — so the gateway's
    # ledger totals reconcile against these token-for-token by
    # construction. meter_records counts records emitted;
    # meter_*_tokens mirror the per-record token dimensions; the
    # page_byte_s pair integrates KV residency (HBM + host-parked)
    # in page·byte·seconds, the TPU-native cost dimension.
    meter_records: int = 0
    meter_prefill_tokens: int = 0
    meter_prefill_padded_tokens: int = 0
    meter_prefix_reused_tokens: int = 0
    meter_decode_tokens: int = 0
    meter_spec_drafted: int = 0
    meter_spec_accepted: int = 0
    meter_hbm_page_byte_s: float = 0.0
    meter_host_page_byte_s: float = 0.0

    # where the engine loop's time went (obs/flight.py LoopLedger:
    # self time per phase of obs/metrics.LOOP_PHASES, /state loop_*);
    # the engine thread is its one writer
    loop: LoopLedger = field(init=False, repr=False, compare=False)

    PREFILL_RATE_HALF_LIFE_TOKENS = 16384

    def __post_init__(self) -> None:
        self.loop = LoopLedger(self)

    @property
    def prefill_ms(self) -> float:
        """Host time building, dispatching and blocked on prefill
        device calls (the ledger's prefill_dispatch + prefill_block)."""
        return self.loop.prefill_ns() / 1e6

    @property
    def transfer_ms(self) -> float:
        """Host time blocked fetching window tokens (window_fetch)."""
        return self.loop.ns[WINDOW_FETCH] / 1e6

    @property
    def emit_ms(self) -> float:
        """Host time distributing window tokens to consumers (emit)."""
        return self.loop.ns[EMIT] / 1e6

    def note_prefill_call(self, ms: float, tokens: int) -> None:
        """Fold one prefill device call (``ms`` host-blocked time over
        ``tokens`` real prompt tokens) into the decayed rate."""
        if tokens <= 0:
            return
        decay = 0.5 ** (tokens / self.PREFILL_RATE_HALF_LIFE_TOKENS)
        self.prefill_ms_decayed = self.prefill_ms_decayed * decay + ms
        self.prefill_tokens_decayed = (
            self.prefill_tokens_decayed * decay + tokens)

    def prefill_ms_per_token(self) -> float:
        """The advertised per-token prefill rate: the decayed mean once
        any call has been observed, else the lifetime mean (0 cold)."""
        if self.prefill_tokens_decayed > 0:
            return self.prefill_ms_decayed / self.prefill_tokens_decayed
        return self.prefill_ms / max(1, self.prefill_tokens_real)


@dataclass
class _Window:
    """One dispatched decode window: the on-device sampled tokens plus
    everything the host needs to settle it at drain time."""

    sampled: Any  # jax array / tuple of arrays (logprobs, speculation)
    # (slot index, request) pairs the window computes for — slots
    # admitted after dispatch are not in here, so their rows' junk
    # samples are never emitted; a (i, req) pair whose slot has been
    # freed (or re-admitted to a new request) since dispatch is skipped
    members: tuple[tuple[int, GenRequest], ...]
    k: int  # window length actually dispatched
    # sequence ids whose pages become safe to recycle once this window
    # completes (every window dispatched while they were active has
    # then finished — nothing on device can still write their pages)
    frees: list[int]
    # speculative dispatch width (0 = plain decode window) and the
    # per-slot draft lengths at dispatch time ((slot, D_slot) pairs) —
    # the drain-side controller update needs what was actually offered
    draft: int = 0
    draft_lens: tuple[tuple[int, int], ...] = ()
    # an occupied slot truncated (top_k / top_p) at dispatch: the
    # window's steps count into sample_sort_steps when it settles
    sorts: bool = False
    # constrained slots at DISPATCH time: (slot, rollback epoch, the
    # mask row live on device for the window). A drain whose captured
    # epoch trails the slot's current one discards that slot's tokens
    # (the window was computed past a grammar cut and its row has since
    # been rolled back); the captured mask is the window's sampling
    # distribution — tokens are accepted only while the slot's CURRENT
    # state demands the very same mask, which makes accepted streams
    # bit-identical to true per-step constrained decoding
    cn_epochs: tuple[tuple[int, int, Any], ...] = ()
    # MoE routing stats for the whole window (device [L, E+1] int32 —
    # per-expert placed counts + capacity drops, summed over the k
    # scan steps; None on dense families). Folded into the host
    # accumulators at DRAIN, when the window's results are fetched
    # anyway — reading it at dispatch would force a device sync
    moe: Any = None
    # device [2] int32: (pages the window's steps read a layer, pages
    # their live rows held), folded at drain like ``moe``
    kv_pages: Any = (0, 0)


class Engine:
    """One model instance on one chip/slice."""

    def __init__(
        self,
        params: dict[str, jax.Array],
        model_cfg: Any,  # LlamaConfig / MixtralConfig (shared attributes)
        cfg: EngineConfig,
        eos_token_ids: tuple[int, ...] = (),
        mesh: Any = None,
        fns: Any = None,  # models.registry.ModelFns; default = llama
        lora_params: dict[str, jax.Array] | None = None,
        adapter_names: tuple[str, ...] = (),
        # adapter serving subsystem (tpuserve/adapters.py): dynamic
        # row residency (hot load / refcounted LRU evict) over the
        # registered zoo. Mutually exclusive with the static
        # lora_params/adapter_names form above (kept for fixed-stack
        # deployments and tests).
        adapter_store: Any = None,
        # the server's obs.flight.FlightRecorder: a program loaded late
        # on the engine thread is written into the requests in flight
        flight: Any = None,
    ):
        from aigw_tpu.models.registry import family_fns

        self.fns = fns or family_fns("llama")
        self.flight = flight
        # multi-LoRA: stacked adapters + name→row map; the LAST row of the
        # stack is the all-zeros base-model row (models/lora.py). With an
        # AdapterStore the stack and the name→row map are DYNAMIC — the
        # lora_params property reads the store fresh at every dispatch
        # (hot loads replace the stacked arrays).
        if adapter_store is not None and (lora_params or adapter_names):
            raise ValueError(
                "pass either adapter_store or lora_params/adapter_names, "
                "not both")
        self._adapter_store = adapter_store
        self._lora_static = lora_params
        if adapter_store is not None:
            self.adapter_rows = {}  # dynamic: resolved via the store
            self._base_row = adapter_store.base_row
        else:
            self.adapter_rows = {n: i for i, n in enumerate(adapter_names)}
            self._base_row = len(adapter_names)
        self.mesh = mesh
        self.params = params
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.eos = eos_token_ids
        # what the family keeps on the device (models/cache.py): pages
        # for the layers that attend over keys and values and, for a
        # family with recurrent layers, per-slot state beside them.
        # What moves PAGES ONLY cannot serve such a family — a page
        # without the state that goes with it is half a sequence, be
        # the state recurrent or a slot's sliding-window keys — nor,
        # yet, a family whose page holds one latent row a token and no
        # K and V planes, so it is off by what the family is
        # (``CacheSpec.pinned``): the host KV tier, parking, migration
        # and fleet fetch, speculation (a rejected draft would need the
        # state rolled back; no verify step reads a latent row), LoRA
        # and — but for a family whose state can be SNAPSHOTTED with
        # the page chain (``CacheSpec.snapshots``) — prefix-cache hits.
        # Such a family keeps the content-addressed allocator and the
        # prefix cache, a hit resuming from a chain node that holds a
        # snapshot (``_probe_prefix``, ``chunk_boundary``); everything
        # else that needs that allocator asks ``_pinned`` as well.
        # Chunked prefill stays: a chunk resumes from the slot's
        # state, or from the rows behind it.
        self.cache_spec = spec_of(model_cfg)
        self._stateful = self.cache_spec.stateful
        why = self.cache_spec.pinned
        self._pinned = bool(why)
        self.features_off: dict[str, str] = {}
        if self._pinned:
            self.features_off = dict.fromkeys(
                ("prefix_cache", "kv_host_tier", "migration",
                 "batch_parking", "kv_fleet_fetch", "speculation", "lora"),
                why)
            if self.cache_spec.snapshots:
                del self.features_off["prefix_cache"]
            if lora_params or adapter_names or adapter_store is not None:
                raise ValueError(f"LoRA serving is off: {why}")
            if mesh is not None:
                raise ValueError(
                    "mesh serving is off for this family: its cache and "
                    "expert share have no partition specs yet")
            if kvq.is_quantized_dtype(cfg.kv_cache_dtype) \
                    and self.cache_spec.latent:
                raise ValueError(
                    f"kv_cache_dtype {cfg.kv_cache_dtype!r} is off for "
                    "this family: a latent row has no per-head scale")
            if cfg.kv_host_bytes > 0 or cfg.spec_tokens > 0:
                logger.warning(
                    "kv_host_bytes / spec_tokens ignored: %s", why)
        if (cfg.enable_prefix_cache and "prefix_cache" not in self.features_off
                and self.fns.prefill_suffix is not None):
            self.allocator = RefcountedAllocator(cfg.num_pages, cfg.page_size)
            self.prefix_cache = PrefixCache(
                self.allocator, cfg.page_size,
                StateSnapshots(self.cache_spec.snapshot_rows(
                    cfg.max_batch_size))
                if self.cache_spec.snapshots else None)
        else:
            self.allocator = PageAllocator(cfg.num_pages, cfg.page_size)
            self.prefix_cache = None
        # KV memory hierarchy (ISSUE 11, tpuserve/kvhost.py): the
        # host-RAM spill tier. Eviction demotes registered pages into it
        # (device→host through the warmed page-export program); a prefix
        # hit on a spilled chain revives them through the warmed batched
        # import scatters. Requires the refcounted prefix-cache
        # allocator — without content addressing there is nothing to
        # key the tier by.
        self.host_tier = None
        if (cfg.kv_host_bytes > 0 and self.prefix_cache is not None
                and not self._pinned):
            from aigw_tpu.tpuserve.kvhost import HostKVTier

            self.host_tier = HostKVTier(cfg.kv_host_bytes)
            self.prefix_cache.spill_sink = self._spill_page
        # resident+spilled chain-hash digest, refreshed (throttled) on
        # the engine thread and read lock-free by /state and the fleet
        # fetch's presence probe (an atomic tuple swap — a slightly
        # stale digest costs at most one redundant fetch, which the
        # import path dedupes)
        self._kv_digest: tuple[str, ...] = ()
        self._kv_digest_next = 0.0
        self.stats = EngineStats()
        self.stats.kv_quant_bits = kvq.quant_bits(cfg.kv_cache_dtype)
        self.stats.kv_bytes_per_token = round(
            self.kv_page_bytes / cfg.page_size, 3)
        self.stats.kv_layers = self.cache_spec.kv_layers
        self.stats.state_bytes_per_slot = (
            self.cache_spec.state_bytes_per_slot(cfg.kv_cache_dtype))
        self.stats.state_bytes_total = (
            self.stats.state_bytes_per_slot * cfg.max_batch_size)
        # serving-phase latency histograms (queue_wait/prefill/ttft/…)
        # with trace-id exemplars — /metrics renders them, /state
        # summarizes p50/p95/p99 (obs/metrics.py ENGINE_HISTOGRAMS)
        self.phases = EnginePhases()
        # shared XLA compile tracker: jax.monitoring compile events plus
        # per-program jit-cache accounting over every hot-path callable
        # registered below (obs/xla_events.py — the tripwire surface)
        self.compile_tracker = CompileTracker()
        if self._adapter_store is not None:
            # the hot-load row scatter runs on the admission path: it is
            # part of the tripwire surface and warmed by warmup()
            self._adapter_store._load_fn = self.compile_tracker.register(
                "adapter_load", self._adapter_store._make_load_fn())
        self.healthy = True
        self.last_error: str | None = None

        B = cfg.max_batch_size
        self._slots: list[_Slot | None] = [None] * B
        # slot indices picked by an in-flight _admit_one whose _Slot is
        # not installed yet (the prefill runs between pick and install).
        # sp_chunked_prefill re-enters admission at chunk boundaries —
        # without the reservation a nested _admit_one would pick the
        # same first-None index and the outer install would orphan it.
        self._reserved_slots: set[int] = set()
        # per-slot-state families: the decode slot each sequence being
        # prefilled will take (its state is written there by the
        # prefill itself), from the pick until the _Slot is installed
        self._slot_of_seq: dict[int, int] = {}
        self._queue: "queue.Queue[GenRequest]" = queue.Queue()
        # priority-tiered serving (ISSUE 19): the offline batch class.
        # Its queue is SEPARATE (and unbounded — batch never sheds) so
        # every interactive signal stays batch-free for free: the
        # window-shrink pressure predicate, queue_wait_ms, /state
        # ``queued``, and the chunk-boundary interactive admission all
        # read only self._queue. Parked sessions are preempted batch
        # streams cut off-device through the migration export path
        # ({"blob", "data", emit/cancelled/trace}), resumed (oldest
        # first) into slots interactive doesn't want.
        self._batch_q: "queue.Queue[GenRequest]" = queue.Queue()
        self._parked_batch: list[dict] = []
        self._seq_ids = itertools.count()
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None

        # device state. With a mesh, weights/cache are laid out with
        # tensor/expert-parallel shardings and every jitted step runs SPMD
        # (GSPMD inserts the collectives; SURVEY.md §2.9). The pool
        # carries ONE extra page past the allocator's range: never
        # allocated, never referenced by a page table, excluded from
        # capacity accounting. Every program's shapes stand on it, and
        # axk1 clamps a dead row's write into it (models/axk1.py).
        kv_rows = (cfg.num_pages + 1) * cfg.page_size
        if mesh is not None:
            from aigw_tpu.parallel.sharding import (
                kv_cache_spec,
                param_sharding_fn,
            )

            # a no-op for weights the server created in place
            # (init_params(sharding_of=…)); a reshard for the rest
            sharding_of = param_sharding_fn(model_cfg, mesh)
            self.params = {
                k: jax.device_put(v, sharding_of(k, v.shape))
                for k, v in params.items()
            }
            self.kv_cache = self.cache_spec.make(
                kv_rows, cfg.max_batch_size, cfg.kv_cache_dtype, mesh,
                kv_cache_spec())
        else:
            self.kv_cache = self.cache_spec.make(
                kv_rows, cfg.max_batch_size, cfg.kv_cache_dtype)
        # Per-slot decode state lives ON DEVICE between ticks (uploaded
        # only when membership/sampling changes) — the decode hot loop
        # transfers just the sampled [K, B] tokens per round-trip.
        self._device_state: dict[str, jax.Array] | None = None
        # Incremental device-state maintenance: membership changes mark
        # individual rows dirty and are scattered into the live state
        # with a tiny jitted row update — no pipeline drain, no full
        # [B, V] re-upload. The speculative history/lookahead rows ride
        # the SAME path (a [H] row upload per admission), so a full
        # rebuild happens only when the page bucket grows or on first
        # use — never because a slot speculates.
        self._dirty_rows: set[int] = set()
        # live slots whose adaptive draft rung moved: patched on device
        # by a draft_len-ONLY scatter (_apply_spec_row_updates). A live
        # slot's full row must never be re-uploaded mid-pipeline — the
        # host's positions lag the in-flight window — but draft_len is
        # position-independent and safe to patch any time.
        self._spec_dirty: set[int] = set()
        # constrained slots whose FSM advanced: their bias row (user
        # bias + the new state's token mask) is patched on device by a
        # bias-ONLY scatter before the next dispatch. Like draft_len,
        # the bias row is position-independent — safe mid-pipeline.
        self._cn_dirty: set[int] = set()
        self._cn_update_fn = None
        # jax memory_stats() polling throttle (a per-tick native call
        # is cheap but pointless at engine-tick frequency)
        self._mem_next = 0.0
        self._need_rebuild = True
        self._state_bucket = 0  # page bucket the live state was built at
        self._row_update_fn = None
        self._spec_update_fn = None
        # copy-on-write page clone (full-prefix hits): one compiled
        # program regardless of src/dst ids (dynamic slice indices)
        self._copy_page_fn = None
        # a family whose prefix cache resumes from a snapshot of its
        # per-slot state: the bookkeeping (tpuserve/kvcache.py), the
        # device pool and the two copy programs (``_init_snapshots``),
        # and what each admission in flight knows of its own, by slot
        self._snap = (self.prefix_cache.snapshots
                      if self.prefix_cache is not None else None)
        self._snap_pool = None
        self._snap_admissions: dict[int, _SnapAdmission] = {}
        if self._snap is not None:
            self._init_snapshots()
        # migration page movers (ISSUE 8): device→host page gather and
        # host→device page scatter, each ONE compiled program for any
        # page id (dynamic indices) — pre-compiled by warmup() so an
        # import/resume never compiles on the hot path
        self._export_page_fn = None
        self._import_page_fn = None
        # migration control queue: export/import jobs posted by server
        # threads, executed on the engine thread (which owns kv_cache's
        # donation chain and the slot table)
        self._mig_q: "queue.Queue[tuple]" = queue.Queue()
        # 1-deep pipeline: the window dispatched to the device while the
        # host processes the previous window's tokens.
        self._inflight: _Window | None = None
        # pages owned by finished sequences are recycled only after
        # every window dispatched while they were active completes (an
        # in-flight window may still write into them). Frees discovered
        # here are captured by the NEXT dispatch and applied when that
        # window drains.
        self._pending_frees: list[int] = []
        # adaptive decode window state
        self._cur_window = cfg.decode_steps_per_tick
        self._steady_ticks = 0

        # per-device accounting (ISSUE 10): bytes of model weights each
        # device actually holds (measured from shard layouts:
        # ≈ total/tp under tensor parallelism), the analytical
        # per-device ICI collective volume of one decoded token, and
        # the rolling per-device stats list _refresh_stats maintains
        self.param_bytes_by_device = _per_device_bytes(self.params)
        from aigw_tpu.parallel.sharding import (
            analytical_ici_bytes_per_token,
        )

        act_bytes = 2
        for v in self.params.values():
            act_bytes = jnp.dtype(v.dtype).itemsize
            break
        self.ici_bytes_per_token = analytical_ici_bytes_per_token(
            model_cfg, mesh, act_bytes)
        self.stats.ici_bytes_per_token = self.ici_bytes_per_token
        self.device_stats: list[dict] = []

        mc, ps = model_cfg, cfg.page_size
        K = cfg.decode_steps_per_tick
        # decode attention rung, from what the engine can observe
        # (tpuserve/attention.resolve_decode_backend has the table;
        # resolve_attention_backend documents the prefill half)
        from aigw_tpu.tpuserve.attention import resolve_decode_backend

        self.decode_attn_impl, self.decode_attn_reason = (
            resolve_decode_backend(cfg, model_cfg, mesh))
        # decode_step's attn_impl argument: "" is the page walk. Either
        # rung hands the mesh on: the -spmd walk runs under it, and a
        # family whose decode MLP differs under one must see it
        attn_impl = "gather" if self.decode_attn_impl == "xla-gather" else ""

        model_prefill = self.fns.prefill
        model_decode = self.fns.decode_step

        # MoE routing stats (ISSUE 18): MoE families (ModelFns with
        # moe_stats=True) take a static ``moe_stats=True`` kwarg and
        # return a trailing [L, E+1] int32 routing-stats leaf —
        # per-expert placed (token, k) counts + capacity drops per
        # layer. Every jitted wrapper below returns that leaf in a
        # uniform trailing position (None on dense families: a leafless
        # pytree node, so the llama programs stay byte-identical) and
        # the host call sites fold it into the numpy accumulators via
        # _fold_moe. No extra device→host sync: the leaf rides the
        # result fetches the host already makes.
        self._moe = bool(getattr(self.fns, "moe_stats", False))
        is_moe = self._moe
        moe_kw = {"moe_stats": True} if is_moe else {}
        self._moe_experts = (int(getattr(model_cfg, "n_experts", 0))
                             if is_moe else 0)
        # columns of a layer's routing-stats row: E placed counts and
        # the drops; a family that holds a share of its experts adds
        # (every assignment routed, held experts hit) behind them
        self._moe_tape_width = int(getattr(
            model_cfg, "moe_tape_width", self._moe_experts + 1))
        self._moe_expert_tokens = np.zeros(
            max(self._moe_experts, 1), np.int64)
        self._moe_layer_drops = np.zeros(
            max(int(model_cfg.n_layers), 1), np.int64)

        tape_width = self._moe_tape_width
        # a decode step's row may be wider than a sequence program's
        # (a per-slot-state family adds what its state loops read)
        decode_tape_width = int(getattr(
            model_cfg, "decode_tape_width", tape_width))
        # the counters that a family's own columns behind the share's
        # three feed, each summed over its layers (the hybrid family
        # names none: its decode rows' two state columns are a layer's)
        self._tape_extra = tuple(getattr(model_cfg, "tape_extra", ()))
        # per-slot-state families: each prefill row names the decode
        # slot whose state it continues (a leafless None elsewhere, so
        # the other families' programs and cache keys are unchanged)
        stateful = self._stateful

        def _slot_kw(slot_ids):
            return {"slot_ids": slot_ids} if stateful else {}

        def _moe_split(out):
            """Normalize a model-entry-point result to
            (logits, kv, moe-or-None)."""
            if is_moe:
                return out
            logits, kv = out
            return logits, kv, None

        # Mesh jit-cache discipline (ISSUE 10): the per-slot decode
        # state chains through donated programs, and GSPMD is free to
        # give output leaves shardings that differ from the host-built
        # state's placement — the NEXT dispatch then misses the jit
        # cache on layout alone and compiles ON THE HOT PATH (the
        # CompileTracker caught the verify ladder doing exactly this at
        # second dispatch). Pinning every state leaf to one canonical
        # sharding — replicated; the state is small next to params/KV —
        # both at build time (device_put) and at every program output
        # (with_sharding_constraint inside the jitted fn) makes the
        # cache key a pure function of shape, exactly like single-chip.
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            state_sharding = NamedSharding(mesh, PartitionSpec())

            def _pin_state(st: dict) -> dict:
                return {
                    k: jax.lax.with_sharding_constraint(v, state_sharding)
                    for k, v in st.items()
                }
        else:
            state_sharding = None

            def _pin_state(st: dict) -> dict:
                return st

        self._state_sharding = state_sharding
        self._pin_state = _pin_state

        def _sample_maybe_lp(logits, keys, temp, top_p, top_k):
            """Sample; with logprobs enabled also return (chosen, top-k
            ids/vals) over the distribution actually sampled from."""
            sampled = sample(logits, keys, temp, top_p, top_k)
            if not cfg.logprobs_topk:
                return sampled
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            chosen = logp[jnp.arange(sampled.shape[0]), sampled]
            tk_vals, tk_ids = jax.lax.top_k(logp, cfg.logprobs_topk)
            return sampled, chosen, tk_ids, tk_vals

        def _prefill_step(params, lora, tokens, seq_lens, kv, page_table,
                          keys, temp, top_p, top_k, bias, adapter_idx,
                          slot_ids=None):
            logits, kv, moe = _moe_split(model_prefill(
                params, mc, tokens, seq_lens, kv, page_table, ps,
                lora=lora, adapter_idx=adapter_idx, **moe_kw,
                **_slot_kw(slot_ids)))
            return _sample_maybe_lp(logits + bias, keys, temp, top_p,
                                    top_k), kv, moe

        model_prefill_suffix = self.fns.prefill_suffix

        def _prefill_suffix_step(params, lora, tokens, prefix_lens,
                                 seq_lens, kv, page_table, keys, temp,
                                 top_p, top_k, bias, adapter_idx,
                                 slot_ids=None):
            logits, kv, moe = _moe_split(model_prefill_suffix(
                params, mc, tokens, prefix_lens, seq_lens, kv, page_table,
                ps, lora=lora, adapter_idx=adapter_idx, **moe_kw,
                **_slot_kw(slot_ids)))
            return _sample_maybe_lp(logits + bias, keys, temp, top_p,
                                    top_k), kv, moe

        # sequence-parallel (ring attention) prefill for long prompts on
        # an sp mesh (SURVEY §2.9 context parallelism)
        self._sp = int(mesh.shape.get("sp", 1)) if mesh is not None else 1
        self._prefill_sp_fn = None
        if self._sp > 1 and self.fns.prefill_sp is not None:
            model_prefill_sp = self.fns.prefill_sp

            def _prefill_sp_step(params, lora, tokens, seq_lens, kv,
                                 page_table, keys, temp, top_p, top_k,
                                 bias, adapter_idx):
                logits, kv, moe = _moe_split(model_prefill_sp(
                    params, mc, tokens, seq_lens, kv, page_table, ps,
                    mesh=mesh, lora=lora, adapter_idx=adapter_idx,
                    **moe_kw))
                return _sample_maybe_lp(logits + bias, keys, temp, top_p,
                                        top_k), kv, moe

            self._prefill_sp_fn = jax.jit(_prefill_sp_step,
                                          donate_argnums=(4,))

        # sequence-sharded CHUNKED prefill: the prefill_suffix contract
        # (resume at a page-aligned offset, full-window gather) with
        # ring attention per chunk — the long-context path. Requires
        # page_size % sp == 0 so the gathered page window shards evenly
        # over the sp axis; other geometries (e.g. sp=6, page 128) fall
        # back to the monolithic program above.
        self._prefill_sp_suffix_fn = None
        if (self._sp > 1 and self.fns.prefill_sp_suffix is not None
                and cfg.sp_prefill_mode == "chunked"
                and ps % self._sp == 0):
            model_prefill_sp_suffix = self.fns.prefill_sp_suffix

            def _prefill_sp_suffix_step(params, lora, tokens,
                                        prefix_lens, seq_lens, kv,
                                        page_table, keys, temp, top_p,
                                        top_k, bias, adapter_idx):
                logits, kv, moe = _moe_split(model_prefill_sp_suffix(
                    params, mc, tokens, prefix_lens, seq_lens, kv,
                    page_table, ps, mesh=mesh, lora=lora,
                    adapter_idx=adapter_idx, **moe_kw))
                return _sample_maybe_lp(logits + bias, keys, temp,
                                        top_p, top_k), kv, moe

            self._prefill_sp_suffix_fn = jax.jit(
                _prefill_sp_suffix_step, donate_argnums=(5,))

        walks = not attn_impl  # the default rung: the page walk
        state_reads = self.fns.state_reads
        n_counts = 2 if state_reads is None else 4

        def _kv_pages(kv, st, act, pages, walks=walks):
            """One decode step's KV read, counted on the device:
            ``pages`` [2] gains (pages the step's program reads a
            layer, pages its live rows hold). On the walk rung the
            first IS the loops' bound — the plan returned here goes to
            the model's decode_step as ``walk`` — on the others
            (window gather, the verify step: no plan) it is the whole
            [B, P] window they address. A dense family with per-slot
            state adds two more (``ModelFns.state_reads``: slots whose
            state its live-row loops read a layer, live rows): it has
            no routing-stats tape for them to ride, and the window's
            one fetch of this vector brings them along."""
            lengths = jnp.where(act, st["positions"] + 1, 0)
            B, P = st["page_table"].shape
            plan = kvq.walk_plan(kv.kv if stateful else kv, lengths,
                                 st["page_table"], ps, mesh) if walks else None
            read = plan.pages_read if walks else B * P
            counts = [jnp.asarray(read, jnp.int32),
                      paged_walk.pages_live(lengths, ps)]
            if state_reads is not None:
                counts += list(state_reads(kv, act))
            return plan, pages + jnp.stack(counts)

        def _decode_scan(k: int, lean: bool = False):
            """Factory: k fused decode+sample steps; sampled tokens feed
            forward on-device (no host round-trip inside the window).
            Each window length is one compiled program (the adaptive
            ladder is {min, max} so at most two exist per bucket).

            ``lean``: compiled WITHOUT the repetition-penalty ops (the
            per-step [B, V] counts scatter-add and both penalty terms —
            logit bias stays). Dispatched whenever no active slot uses
            penalties: zero penalties contribute exactly 0.0 to every
            logit, so lean and full windows sample bit-identical tokens
            while the lean program drops the most expensive non-matmul
            ops from the hot loop. Device-side counts go stale for
            penalty-free slots during lean windows — harmless (their
            penalty coefficients are zero) and refreshed from the
            host-side token_counts whenever a penalized admission
            switches the engine back to the full program."""
            lp_k = cfg.logprobs_topk

            def body(params, lora, carry):
                kv, st, macc, pages = carry
                act = st["active"] & (st["positions"] < st["limits"])
                walk, pages = _kv_pages(kv, st, act, pages)
                logits, kv, moe = _moe_split(model_decode(
                    params, mc, st["tokens"], st["positions"], kv,
                    st["page_table"], ps, act,
                    lora=lora, adapter_idx=st["adapter_idx"],
                    attn_impl=attn_impl, mesh=mesh, walk=walk,
                    **moe_kw))
                macc = macc if moe is None else macc + moe
                if lean:
                    logits = logits + st["bias"]
                else:
                    logits = apply_penalties(
                        logits, st["counts"], st["freq_pen"],
                        st["pres_pen"], st["bias"],
                    )
                sampled = sample(logits, st["keys"], st["temp"],
                                 st["top_p"], st["top_k"], act)
                step = act.astype(jnp.uint32)
                B = sampled.shape[0]
                counts = (st["counts"] if lean
                          else st["counts"].at[
                              jnp.arange(B), sampled
                          ].add(act.astype(st["counts"].dtype)))
                new = dict(
                    st,
                    tokens=jnp.where(act, sampled, st["tokens"]),
                    positions=jnp.where(act, st["positions"] + 1,
                                        st["positions"]),
                    keys=st["keys"].at[:, 1].add(step),
                    counts=counts,
                )
                if lp_k:  # static: 0 compiles the exact round-3 program
                    # logprobs over the PENALIZED distribution — the one
                    # the token was actually sampled from
                    logp = jax.nn.log_softmax(
                        logits.astype(jnp.float32), axis=-1)
                    chosen = logp[jnp.arange(B), sampled]
                    tk_vals, tk_ids = jax.lax.top_k(logp, lp_k)
                    return (kv, new, macc, pages), (
                        sampled, chosen, tk_ids, tk_vals)
                return (kv, new, macc, pages), sampled

            def scan_k(params, lora, kv, state):
                macc0 = (jnp.zeros((mc.n_layers, decode_tape_width),
                                   jnp.int32) if is_moe else None)
                (kv, state, macc, pages), sampled = jax.lax.scan(
                    lambda c, _: body(params, lora, c),
                    (kv, state, macc0, jnp.zeros((n_counts,), jnp.int32)),
                    None, length=k
                )
                return sampled, _pin_state(state), kv, macc, pages

            return scan_k

        # speculative decoding (tpuserve/speculation.py): a rung ladder
        # of [B, D+1] verify programs replaces the [B, 1] decode step
        # whenever an eligible slot's adaptive controller holds a
        # nonzero draft length; a step advances by the accepted draft
        # count. Same fixed-geometry contract — one compiled program
        # per rung, warmed like the prefill ladder.
        self._spec_rungs = (
            speculation.draft_rungs(cfg.spec_tokens)
            if cfg.spec_tokens > 0 and self.fns.verify_step is not None
            else (0,)
        )
        self._spec_max = self._spec_rungs[-1]
        self._accept_prior = speculation.AcceptancePrior()
        model_verify = self.fns.verify_step
        V = model_cfg.vocab_size
        H = cfg.max_seq_len

        def _spec_scan(k_steps: int, D: int):
            """Factory: k speculative steps at draft rung D; outputs
            (sampled [k, B, D+1], n_emit [k, B]) — the host emits
            sampled[k, b, :n_emit[k, b]]. Slots whose per-slot
            ``draft_len`` row sits below D get the excess candidate
            positions poisoned on device: they still advance ≥1
            model-exact token per step, just without the extra
            drafts."""
            D1 = D + 1

            def body(params, lora, carry):
                kv, st, macc, pages = carry
                act = st["active"] & (st["positions"] < st["limits"])
                _, pages = _kv_pages(kv, st, act, pages, walks=False)
                # penalty and sampling slots advance exactly one token
                # per step (see speculation.py module docstring):
                # poison their drafts
                elig = ((st["freq_pen"] == 0.0)
                        & (st["pres_pen"] == 0.0)
                        & (st["temp"] <= 0.0))
                # multi-source drafts: prefix-cache continuation where
                # the lookahead buffer covers the position, n-gram
                # prompt lookup everywhere else
                ng = speculation.ngram_drafts(
                    st["history"], st["positions"], D)
                la = speculation.lookahead_drafts(
                    st["lookahead"], st["la_base"], st["la_len"],
                    st["positions"], D)
                drafts = speculation.combine_drafts(la, ng)
                d_off = jnp.arange(D, dtype=jnp.int32)[None, :]
                ok = elig[:, None] & (d_off < st["draft_len"][:, None])
                drafts = jnp.where(ok, drafts, -1)
                inputs = jnp.concatenate(
                    [st["tokens"][:, None], jnp.maximum(drafts, 0)], axis=1
                )
                logits_all, kv, moe = _moe_split(model_verify(
                    params, mc, inputs, st["positions"], kv,
                    st["page_table"], ps, act, st["limits"],
                    lora=lora, adapter_idx=st["adapter_idx"],
                    **moe_kw))  # [B, D1, V]
                macc = macc if moe is None else macc + moe
                # counts are window-start values: exact at d=0, and later
                # positions only accept on penalty-free slots where the
                # count term is zero anyway
                lT = logits_all.transpose(1, 0, 2)  # [D1, B, V]
                lT = jax.vmap(
                    lambda l: apply_penalties(
                        l, st["counts"], st["freq_pen"], st["pres_pen"],
                        st["bias"],
                    )
                )(lT)
                # per-position keys [seed, pos+d] — the same key the
                # non-speculative path would use at that position, so
                # accepted tokens are bit-identical to plain decoding
                offs = jnp.arange(D1, dtype=jnp.uint32)
                keys_d = (
                    jnp.broadcast_to(st["keys"], (D1,) + st["keys"].shape)
                    .at[:, :, 1].add(offs[:, None])
                )
                sampled = jax.vmap(
                    lambda l, k: sample(l, k, st["temp"], st["top_p"],
                                        st["top_k"], act)
                )(lT, keys_d).T  # [B, D1]
                n_emit, emit_mask = spec_accept(
                    drafts, sampled, act,
                    st["limits"] - st["positions"])
                B = sampled.shape[0]
                rows = jnp.arange(B)
                new_pending = sampled[rows, jnp.clip(n_emit - 1, 0, D)]
                d_idx = jnp.arange(D1, dtype=jnp.int32)[None, :]
                # sampled[d] is the token at position pos+1+d
                wpos = jnp.where(emit_mask,
                                 st["positions"][:, None] + 1 + d_idx, H)
                history = st["history"].at[rows[:, None], wpos].set(
                    sampled, mode="drop"
                )
                counts = st["counts"].at[
                    rows[:, None], jnp.where(emit_mask, sampled, V)
                ].add(1, mode="drop")
                new = dict(
                    st,
                    tokens=jnp.where(n_emit > 0, new_pending, st["tokens"]),
                    positions=st["positions"] + n_emit,
                    keys=st["keys"].at[:, 1].add(n_emit.astype(jnp.uint32)),
                    counts=counts,
                    history=history,
                )
                # draft tokens actually OFFERED this step (the longest
                # non-poisoned prefix) — the host-side controllers
                # distinguish proposed-and-rejected from nothing-to-
                # propose, and spec_drafted counts real proposals
                n_prop = jnp.sum(jnp.cumprod(
                    (drafts >= 0).astype(jnp.int32), axis=1), axis=1)
                n_prop = jnp.where(act, n_prop, 0)
                return (kv, new, macc, pages), (sampled, n_emit, n_prop)

            def scan_k(params, lora, kv, state):
                macc0 = (jnp.zeros((mc.n_layers, tape_width),
                                   jnp.int32) if is_moe else None)
                (kv, state, macc, pages), out = jax.lax.scan(
                    lambda c, _: body(params, lora, c),
                    (kv, state, macc0, jnp.zeros((n_counts,), jnp.int32)),
                    None, length=k_steps)
                return out, _pin_state(state), kv, macc, pages

            return scan_k

        self._prefill_fn = self.compile_tracker.register(
            "prefill", jax.jit(_prefill_step, donate_argnums=(4,)))
        self._prefill_suffix_fn = self.compile_tracker.register(
            "prefill_suffix",
            jax.jit(_prefill_suffix_step, donate_argnums=(5,)))
        if self._prefill_sp_fn is not None:
            self.compile_tracker.register("prefill_sp",
                                          self._prefill_sp_fn)
        if self._prefill_sp_suffix_fn is not None:
            self.compile_tracker.register("prefill_sp_chunked",
                                          self._prefill_sp_suffix_fn)
        # ragged packed prefill (the pallas-ragged backend's single
        # program family — one compiled shape per token-budget rung).
        # Attention impl: the Pallas kernel on TPU, the XLA windowed
        # reference elsewhere (auto-fallback; AIGW_RAGGED_PREFILL_IMPL
        # in {xla, pallas} overrides for A/B and parity tests).
        self._prefill_ragged_fn = None
        self._ragged_impl = ""
        self._ragged_reason = ("no ragged prefill entry point "
                               "(hand-built ModelFns)")
        model_prefill_ragged = self.fns.prefill_ragged
        if model_prefill_ragged is not None:
            from aigw_tpu.ops.pallas._compat import is_tpu_backend

            impl = os.environ.get("AIGW_RAGGED_PREFILL_IMPL", "").lower()
            if impl not in ("xla", "pallas"):
                impl = ("pallas" if is_tpu_backend() and mesh is None
                        else "xla")
            if impl == "pallas" and mesh is not None:
                # the kernel's scalar-prefetch page walk addresses ONE
                # local pool — honor the explicit override only where
                # it can run
                impl = "xla"
            quant_kv = kvq.is_quantized_dtype(cfg.kv_cache_dtype)
            if impl == "pallas" and quant_kv:
                # narrowed matrix row: the ragged prefill kernel has no
                # quantized-pool rung — the XLA windowed program
                # dequantizes prefix pages at the read
                impl = "xla"
            self._ragged_impl = "" if impl == "xla" else "pallas"
            if self._ragged_impl == "pallas":
                self._ragged_reason = "Pallas kernel (single-chip TPU)"
            elif quant_kv:
                self._ragged_reason = (
                    f"XLA windowed fallback: {cfg.kv_cache_dtype} KV "
                    "pages — the ragged prefill kernel has no "
                    "quantized-pool rung; the windowed program "
                    "dequantizes prefix pages at the read")
            elif mesh is not None:
                self._ragged_reason = (
                    "XLA windowed fallback: the Pallas ragged-prefill "
                    "kernel is single-chip (scalar-prefetch page walk "
                    "over one local pool); the windowed program runs "
                    "SPMD with KV sharded on heads")
            else:
                self._ragged_reason = (
                    "XLA windowed fallback: no TPU backend")
            ragged_impl = self._ragged_impl

            def _prefill_ragged_step(params, lora, tokens, row_seq,
                                     positions, last_rows, kv,
                                     page_table, keys, temp, top_p,
                                     top_k, bias, adapter_idx):
                logits, kv, moe = _moe_split(model_prefill_ragged(
                    params, mc, tokens, row_seq, positions, last_rows,
                    kv, page_table, ps, attn_impl=ragged_impl,
                    lora=lora, adapter_idx=adapter_idx, **moe_kw))
                return _sample_maybe_lp(logits + bias, keys, temp,
                                        top_p, top_k), kv, moe

            self._prefill_ragged_fn = self.compile_tracker.register(
                "prefill_ragged",
                jax.jit(_prefill_ragged_step, donate_argnums=(6,)))
        self._decode_scan_factory = _decode_scan
        self._spec_scan_factory = _spec_scan
        self._decode_fns: dict[tuple[int, bool, int], Callable] = {}
        # admission burst bookkeeping for lifecycle traces: (id, size)
        # of the burst currently being admitted
        self._burst_seq = itertools.count(1)
        self._cur_burst: tuple[int, int] = (0, 0)
        # reentrancy latch for chunk-boundary admission: a short
        # request admitted mid-chunk-loop may itself run a chunked
        # (non-sp) prefill whose boundaries must NOT admit again
        self._in_chunk_admit = False
        # prefill attention backend (tpuserve/attention.py): owns the
        # prefill programs + geometry policy behind _admit's dispatch
        from aigw_tpu.tpuserve.attention import make_attention_backend

        self.attn = make_attention_backend(self)
        # populate the per-device /state surface before any traffic
        # (telemetry consumers poll a freshly booted replica)
        self._refresh_stats()

    def _decode_fn_for(self, k: int, lean: bool = False,
                       draft: int = 0):
        """Jitted decode program for window length k at draft rung
        ``draft`` (0 = plain decode; cached; jit itself caches per
        page-bucket shape). ``lean`` selects the penalty-free plain
        variant (verify programs have no lean variant — their
        draft-eligibility logic reads the penalty fields)."""
        if draft:
            lean = False
        fn = self._decode_fns.get((k, lean, draft))
        if fn is None:
            scan = (self._spec_scan_factory(k, draft) if draft
                    else self._decode_scan_factory(k, lean))
            fn = jax.jit(scan, donate_argnums=(2, 3))
            self._decode_fns[(k, lean, draft)] = fn
            self.compile_tracker.register(
                f"decode[k={k},lean={lean},d={draft}]", fn)
        return fn

    # -- adapter rows (tpuserve/adapters.py) -------------------------------
    @property
    def lora_params(self):
        """The stacked LoRA arrays for the NEXT dispatch. With an
        AdapterStore this must be read fresh every dispatch — hot loads
        replace the stacked arrays (donated row writes)."""
        if self._adapter_store is not None:
            return self._adapter_store.params or None
        return self._lora_static

    def _adapter_known(self, name: str) -> bool:
        if self._adapter_store is not None:
            return self._adapter_store.knows(name)
        return name in self.adapter_rows

    def _acquire_adapter(self, name: str) -> int:
        """Resolve an adapter name to its device row for a new slot,
        pinning (and hot-loading, when non-resident) the row in store
        mode. Raises adapters.UnknownAdapterError for names outside the
        zoo and adapters.AdapterCapacityError when every row is pinned
        (caller requeues, like KV page pressure)."""
        if self._adapter_store is not None:
            return self._adapter_store.acquire(name)
        row = self.adapter_rows.get(name)
        if row is None:
            from aigw_tpu.tpuserve.adapters import UnknownAdapterError

            raise UnknownAdapterError(name)
        return row

    def _release_adapter_row(self, row: int) -> None:
        """Drop a slot's pin on its adapter row. Safe at slot-free time
        even with a window in flight: a freed slot's window outputs are
        discarded at drain (members check), and device-side reads of a
        subsequently rewritten row are ordered behind the in-flight
        computation by the normal JAX dependency chain."""
        if self._adapter_store is not None and row != self._base_row:
            self._adapter_store.release(row)

    def _adapter_row_of(self, req: GenRequest) -> int:
        """Device row for an ADMITTED request (the attention backends'
        sampling-row builder). In store mode the row was acquired at
        admission, so the lookup must succeed — a missing name here is
        an acquire-ordering bug, not routine miss traffic."""
        if not req.adapter:
            return self._base_row
        if self._adapter_store is not None:
            return self._adapter_store.row_of(req.adapter)
        return self.adapter_rows.get(req.adapter, self._base_row)

    # -- tenant fairness ----------------------------------------------------
    def _tenant_slots(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for s in self._slots:
            if s is not None:
                t = s.req.tenant
                counts[t] = counts.get(t, 0) + 1
        return counts

    def _fair_admission(
        self, pending: list[GenRequest], free: int,
    ) -> tuple[list[GenRequest], list[GenRequest], int]:
        """(admit_now, requeue, n_capped): the fairness guard over one
        admission pass. The per-tenant slot cap defers requests whose
        tenant already holds (or would reach) ``tenant_slot_cap``
        in-flight slots; remaining requests are deficit-ordered —
        tenants with fewer live slots admit first, arrival order kept
        within a tenant — so a multi-tenant burst splits the batch
        instead of first-come-take-all. ``requeue`` preserves arrival
        order (deferred + past-``free`` overflow). Single-tenant
        traffic with nothing live passes through untouched."""
        cap = self.cfg.tenant_slot_cap
        live = self._tenant_slots()
        if cap <= 0 and len({r.tenant for r in pending} | set(live)) <= 1:
            return pending[:free], pending[free:], 0
        taken: dict[str, int] = {}
        eligible: list[GenRequest] = []
        capped: list[GenRequest] = []
        for req in pending:
            t = req.tenant
            if cap > 0 and live.get(t, 0) + taken.get(t, 0) >= cap:
                capped.append(req)
                continue
            taken[t] = taken.get(t, 0) + 1
            eligible.append(req)
        if len({r.tenant for r in eligible}) > 1:
            # deficit round-robin in ONE pass (ISSUE 19 satellite — the
            # old scan re-walked the whole remainder per admission,
            # O(n²) on the queue bound): per-tenant FIFOs + a heap
            # keyed (live-slot count, head arrival index). Only a
            # tenant's HEAD can ever win the old min-scan (same count,
            # earlier position than its followers), and comparing head
            # positions across tenants is comparing arrival indices —
            # so popping the heap min and re-pushing the tenant at
            # count+1 with its next head reproduces the old order
            # exactly (tests/test_batch_tier.py holds the old loop as
            # the property-test oracle).
            fifos: dict[str, list[tuple[int, GenRequest]]] = {}
            for j, req in enumerate(eligible):
                fifos.setdefault(req.tenant, []).append((j, req))
            heap = [(live.get(t, 0), lst[0][0], t)
                    for t, lst in fifos.items()]
            heapq.heapify(heap)
            heads = dict.fromkeys(fifos, 0)
            ordered: list[GenRequest] = []
            while heap:
                cnt, _, t = heapq.heappop(heap)
                lst, h = fifos[t], heads[t]
                ordered.append(lst[h][1])
                heads[t] = h + 1
                if h + 1 < len(lst):
                    heapq.heappush(heap, (cnt + 1, lst[h + 1][0], t))
            eligible = ordered
        admit = eligible[:free]
        left = set(map(id, capped)) | set(map(id, eligible[free:]))
        requeue = [r for r in pending if id(r) in left]  # arrival order
        return admit, requeue, len(capped)

    def _lean_decode_ok(self) -> bool:
        """True when no active slot uses repetition penalties — the
        lean decode program samples bit-identical tokens (zero
        penalties add exactly 0.0 per logit). Only consulted for
        plain-decode dispatches (draft rung 0)."""
        return all(
            s is None
            or (s.req.sampling.frequency_penalty == 0.0
                and s.req.sampling.presence_penalty == 0.0)
            for s in self._slots
        )

    def _sample_sorts(self) -> bool:
        """True when an occupied slot's request truncates — host-side
        twin of the predicate `sample` guards its vocabulary-wide sort
        with (an upper bound: the device also leaves out dead rows)."""
        return any(
            s is not None
            and (s.req.sampling.top_k > 0
                 or not s.req.sampling.top_p >= 1.0)
            for s in self._slots
        )

    def _prefill_bucket(self, n: int, multiple_of: int = 1) -> int:
        """Smallest prefill-ladder rung covering ``n`` prompt tokens.
        Rungs are powers of two of min_prefill_bucket plus, with
        prefill_bucket_rungs > 1, intermediate rungs at 1.5×S (and
        1.25×/1.75×S at 4) — prefill compute scales with the padded
        length, so a tighter rung is a direct TTFT cut.

        ``multiple_of`` is the mesh divisibility guard (ISSUE 10): a
        program whose padded length an axis shards (ring attention over
        ``sp``) must divide that axis, but the 1.5×S rungs usually
        don't — the guard rounds the CHOSEN rung up to the next
        multiple instead of abandoning the intermediate ladder, so mesh
        prompts keep the sub-pow2 rungs (a 96-token prompt on sp=8
        pads to 96, not 128)."""
        cfg = self.cfg
        S = cfg.min_prefill_bucket
        while S < n:
            if cfg.prefill_bucket_rungs >= 4 and n <= S + S // 4:
                S += S // 4
                break
            if cfg.prefill_bucket_rungs >= 2 and n <= S + S // 2:
                S += S // 2
                break
            if cfg.prefill_bucket_rungs >= 4 and n <= S + 3 * S // 4:
                S += 3 * S // 4
                break
            S *= 2
        S = min(S, cfg.max_seq_len)
        if multiple_of > 1 and S % multiple_of:
            S = -(-S // multiple_of) * multiple_of
        return S

    def _bucket_rungs(self, octave: int) -> list[int]:
        """The prefill-ladder rungs of one octave (octave 0 starts at
        min_prefill_bucket), ascending, capped at max_seq_len."""
        S = self.cfg.min_prefill_bucket << octave
        quarters = {1: (4,), 2: (4, 6), 4: (4, 5, 6, 7)}[
            self.cfg.prefill_bucket_rungs]
        return sorted({
            min(S * q // 4, self.cfg.max_seq_len) for q in quarters
        })

    def _copy_page_dev(self, src: int, dst: int) -> None:
        """Clone one KV page on-device (copy-on-write for full-prefix
        hits). Dynamic slice indices: ONE compiled program for any
        (src, dst) pair; the kv_cache donation chain orders the copy
        after every already-dispatched window that reads ``src``."""
        if self._copy_page_fn is None:
            ps = self.cfg.page_size

            def _cp(kv, src_page, dst_page):
                # tree_map: the quantized pool's scale leaf pages on
                # the same slot axis, so a page copy moves its scale
                # block with it
                def cp_leaf(leaf):
                    rows = jax.lax.dynamic_slice_in_dim(
                        leaf, src_page * ps, ps, axis=2)
                    return jax.lax.dynamic_update_slice_in_dim(
                        leaf, rows, dst_page * ps, axis=2)

                return jax.tree_util.tree_map(cp_leaf, kv)

            self._copy_page_fn = self.compile_tracker.register(
                "copy_page", jax.jit(_cp, donate_argnums=(0,)))
        self.kv_cache = self._copy_page_fn(
            self.kv_cache, jnp.int32(src), jnp.int32(dst))

    def _export_page_dev(self, page: int):
        """Gather one KV page off the pool (device side of a migration
        export). Dynamic page index: ONE compiled program for any page;
        the caller starts the device→host copy asynchronously so the
        per-page transfers overlap (the async-transfer machinery)."""
        if self._export_page_fn is None:
            ps = self.cfg.page_size

            def _ex(kv, pg):
                return jax.tree_util.tree_map(
                    lambda leaf: jax.lax.dynamic_slice_in_dim(
                        leaf, pg * ps, ps, axis=2), kv)

            self._export_page_fn = self.compile_tracker.register(
                "page_export", jax.jit(_ex))
        return self._export_page_fn(self.kv_cache, jnp.int32(page))

    def _import_rungs(self) -> list[int]:
        """Page-count rungs of the batched import program: powers of
        two covering 1..max_pages_per_seq — one compiled program per
        rung for ANY destination page set."""
        rungs = []
        r = 1
        while True:
            rungs.append(r)
            if r >= self.cfg.max_pages_per_seq:
                return rungs
            r *= 2

    def _import_pages_dev(self, page_ids: list[int],
                          rows_np: list) -> None:
        """Scatter ``len(page_ids)`` host-side KV pages into the pool in
        ONE donated device call (a fori_loop of dynamic row updates).
        The page count pads to a pow2 rung by REPEATING the last
        (page, rows) pair — an idempotent rewrite, so no mask branch is
        compiled. One program per rung; all rungs pre-compiled by
        warmup(). Batching matters: per-page donated calls copy the
        whole pool once per page on backends without buffer donation."""
        k = len(page_ids)
        if k == 0:
            return
        ps = self.cfg.page_size
        if self._import_page_fn is None:

            def _im(kv, pages, rows):
                def body(i, kv):
                    return jax.tree_util.tree_map(
                        lambda leaf, r: jax.lax.dynamic_update_slice_in_dim(
                            leaf, r[i], pages[i] * ps, axis=2),
                        kv, rows)

                return jax.lax.fori_loop(0, pages.shape[0], body, kv)

            self._import_page_fn = self.compile_tracker.register(
                "page_import", jax.jit(_im, donate_argnums=(0,)))
        R = 1
        while R < k:
            R *= 2
        pages = np.full((R,), page_ids[-1], np.int32)
        pages[:k] = page_ids
        # rows_np: a LIST of host-side pages — np [L, 2, ps, Hkv, D]
        # arrays (native pools) or {"q","scale"} dicts (quantized) —
        # stacked per leaf; the pow2 rung pads with idempotent
        # rewrites of the last page
        dt = self.cfg.kv_cache_dtype
        host = list(rows_np) + [rows_np[-1]] * (R - k)
        if kvq.is_quantized_dtype(dt):
            stacked = {
                "q": jnp.asarray(np.stack([h["q"] for h in host]),
                                 kvq.compute_dtype(dt)),
                "scale": jnp.asarray(
                    np.stack([h["scale"] for h in host]), jnp.float32),
            }
        else:
            stacked = jnp.asarray(np.stack(host),
                                  kvq.compute_dtype(dt))
        self.kv_cache = self._import_page_fn(
            self.kv_cache, jnp.asarray(pages), stacked)

    # -- KV memory hierarchy: host spill tier + fleet fetch (ISSUE 11) ----
    @engine_thread_only
    def _spill_page(self, key: bytes, page: int) -> None:
        """Spill sink wired into PrefixCache eviction: copy the
        about-to-be-reclaimed page's K/V rows device→host and park them
        in the host tier under the chain key. Runs synchronously inside
        the allocator's _pop_page on the ENGINE thread — the page is
        never handed to its new owner before the copy resolves, and the
        export program is pre-compiled by warmup() (zero hot XLA
        compiles across spill churn). The evicted page is refcount-0
        with every window that could write it already drained, so its
        device rows are stable."""
        rows = self._export_page_dev(page)
        self._start_host_copy([rows])
        self.host_tier.put(key, kvq.page_to_host(rows))

    @engine_thread_only
    def _revive_chain(self, chain_keys: list) -> int:
        """Promote the longest spilled run extending the resident
        prefix back into the pool: allocate pages, scatter the host
        rows in ONE warmed batched import call, and register them in
        the prefix cache (parked evictable — the caller's probe adopts
        them like any cached prefix). Returns pages revived; 0 under
        page pressure (the rows are put back and the cold prefill path
        proceeds)."""
        tier = self.host_tier
        resident = len(self.prefix_cache.probe(chain_keys))
        take: list = []
        while (resident + len(take) < len(chain_keys)
               and tier.contains(chain_keys[resident + len(take)])):
            take.append(chain_keys[resident + len(take)])
        if not take:
            return 0
        # remove from the tier FIRST: an interleaved spill during the
        # allocation below can never LRU-drop the rows mid-revive
        rows = []
        for k in take:
            r = tier.take(k)
            if r is None:  # raced away (defensive) — revive what's left
                break
            rows.append(r)
        take = take[: len(rows)]
        if not rows:
            return 0
        seq_id = next(self._seq_ids)
        try:
            self.allocator.allocate_extra(seq_id, len(rows))
        except OutOfPagesError:
            self.allocator.free(seq_id)
            for k, r in zip(take, rows):  # hand the rows back
                tier.put(k, r)
            return 0
        page_ids = self.allocator.pages(seq_id)
        self._import_pages_dev(page_ids, rows)
        self.prefix_cache.insert(take, page_ids)
        # park evictable: the admission that triggered the revive
        # re-probes and adopts under the normal refcount discipline
        self.allocator.free(seq_id)
        logger.debug("revived %d spilled pages", len(rows))
        return len(rows)

    def _purge_spilled(self, keys: list) -> None:
        """Strict tiering: a chain that just became resident through a
        fresh prefill insert must not also occupy the host budget (a
        stale copy can linger when an earlier chain key was budget-
        dropped, so no revive fired on the re-ask)."""
        if self.host_tier is not None:
            for k in keys:
                self.host_tier.discard(k)

    def kv_chain_digest(self) -> tuple:
        """Hex digest of the chain hashes this replica can serve KV for
        (resident prefix-cache entries + host-spilled pages) — exported
        on /state, polled into the gateway's fleet index, and consumed
        by the fleet fetch's local presence probe. Lock-free: an atomic
        read of the tuple the engine thread refreshes."""
        return self._kv_digest

    #: digest size FLOOR: a replica always advertises at least this
    #: many chain keys (the pre-long-context flat bound)
    KV_DIGEST_MAX = 4096

    #: full-length chains the geometry-aware digest bound guarantees
    #: room for (kv_digest_max below)
    KV_DIGEST_MIN_CHAINS = 8

    def kv_digest_max(self) -> int:
        """Geometry-aware digest bound: ``max(KV_DIGEST_MAX,
        KV_DIGEST_MIN_CHAINS * max_pages_per_seq)``. Chain keys are
        per-PAGE hashes, so a single 128k chain at 128-token pages is
        1024 keys — the flat 4096 bound silently truncated the
        advertisement to ~4 long chains, making every later chain
        invisible to the fleet KV index (unfetchable cross-replica)
        even though this replica held its pages. The gateway-side
        mirror is KVIndex.MAX_KEYS_PER_REPLICA (gateway/kvindex.py)."""
        return max(self.KV_DIGEST_MAX,
                   self.KV_DIGEST_MIN_CHAINS * self.cfg.max_pages_per_seq)

    @engine_thread_only
    def _refresh_kv_digest(self) -> None:
        """Engine-thread digest rebuild (throttled by _refresh_stats):
        the only thread that mutates _by_key and the host tier's key
        set, so iteration here is race-free. (A pinned family
        publishes none: no sibling could use its pages alone.)"""
        if self.prefix_cache is None or self._pinned:
            return
        keys = list(self.prefix_cache._by_key.keys())
        if self.host_tier is not None:
            keys.extend(self.host_tier.keys())
        out: list[str] = []
        seen: set = set()
        bound = self.kv_digest_max()
        for k in keys:
            if k not in seen:
                seen.add(k)
                out.append(k.hex())
                if len(out) >= bound:
                    break
        self._kv_digest = tuple(out)

    def kv_export_pages(self, keys: list, timeout: float = 30.0) -> list:
        """Serve KV pages by chain hash for a sibling replica's fetch
        (the /kv/pages endpoint): resident pages are pinned and gathered
        device→host through the migration export program; spilled pages
        are served straight from the host tier. Returns [(key, np f32
        rows)] for every key this replica holds — missing keys are
        simply absent (the fetcher imports the leading contiguous run).
        Engine-thread execution via the migration control queue."""
        box: dict = {"evt": threading.Event()}
        self._mig_q.put(("fetch", keys, box))
        self._wake.set()
        if not box["evt"].wait(timeout):
            raise TimeoutError("kv page fetch timed out")
        if "error" in box:
            raise MigrationError(box["error"])
        return box["result"]

    @engine_thread_only
    def _do_fetch(self, keys: list) -> list:
        if self.prefix_cache is None or self._pinned:
            return []
        # the wire rule for quantized pools: pages travel at NATIVE
        # dtype + their scale blocks, bit-exactly (re-rounding through
        # f32 would silently change what the importer serves); native
        # pools keep the PR 8 f32 wire
        quant = kvq.is_quantized_dtype(self.cfg.kv_cache_dtype)

        def wire(rows):
            host = kvq.page_to_host(rows)
            return host if quant else np.asarray(host, np.float32)

        out: list = []
        resident: list = []
        for k in keys:
            page = self.prefix_cache._by_key.get(k)
            if page is not None:
                resident.append((k, page))
            elif self.host_tier is not None:
                rows = self.host_tier.get(k)  # peek — the rung stays
                if rows is not None:
                    out.append((k, rows if quant
                                else np.asarray(rows, np.float32)))
        if resident:
            # pin for the duration of the device→host copy — the same
            # export discipline as migration (nothing may free/evict/
            # CoW these pages mid-transfer)
            pin = self.allocator.begin_export([p for _, p in resident])
            try:
                exported = [(k, self._export_page_dev(p))
                            for k, p in resident]
                self._start_host_copy([e for _, e in exported])
                out.extend((k, wire(e)) for k, e in exported)
            finally:
                self.allocator.end_export(pin)
        if out:
            self.stats.kv_fetches_out += 1
            self.stats.kv_fetch_pages_out += len(out)
        return out

    def kv_import_pages(self, tokens: list[int], pages: list,
                        start: int = 0, timeout: float = 30.0) -> int:
        """Adopt KV pages fetched from a sibling replica: pages hold
        chain depths [start, start+len) of ``tokens``'s page chain and
        are registered as cached (non-live) pages — exactly the
        migration-import lifecycle, counted as fleet fetches instead.
        Raises MigrationError / TimeoutError like migrate_import."""
        box: dict = {"evt": threading.Event()}
        self._mig_q.put(("import", (tokens, pages, start, "fetch"), box))
        self._wake.set()
        if not box["evt"].wait(timeout):
            raise TimeoutError("kv page import timed out")
        if "error" in box:
            raise MigrationError(box["error"])
        return box["result"]

    @property
    def kv_page_bytes(self) -> int:
        """HBM bytes of one KV page (the /state bytes-pinned signal).
        Quantized pools count the packed element bytes PLUS the page's
        f32 scale block (one scale per token row × KV head per k/v)."""
        return self.cache_spec.kv_page_bytes(self.cfg.page_size,
                                             self.cfg.kv_cache_dtype)

    def mesh_axes(self) -> dict[str, int]:
        """Mesh axis name → size ({} off-mesh) — the /state topology
        export the picker's ICI term reads."""
        if self.mesh is None:
            return {}
        return {k: int(v) for k, v in self.mesh.shape.items()}

    @property
    def migratable(self) -> bool:
        """Whether this engine serves /migrate/export|import (needs the
        refcounted prefix-cache allocator). Layout-independent: on a
        mesh the page movers gather/scatter the head-sharded pool
        through the same full-page wire format (the gather assembles
        all head shards; the scatter re-shards on write) — /state
        exports this as the ``migration`` capability flag the gateway
        _Migrator respects."""
        return isinstance(self.allocator, RefcountedAllocator)

    @staticmethod
    def _start_host_copy(tree: Any) -> None:
        """Begin the device→host copy of every array leaf now
        (copy_to_host_async): the transfer overlaps the remaining
        on-device compute instead of serializing after it."""
        for leaf in jax.tree_util.tree_leaves(tree):
            copy = getattr(leaf, "copy_to_host_async", None)
            if copy is not None:
                copy()

    def _window_ladder(self) -> list[int]:
        """Window sizes the adaptive policy may dispatch."""
        K = self.cfg.decode_steps_per_tick
        if not self.cfg.adaptive_decode_window:
            return [K]
        kmin = min(self.cfg.min_decode_steps_per_tick, K)
        return [K] if kmin == K else [kmin, K]

    @engine_thread_only
    def _choose_window(self) -> int:
        """Adaptive decode window: shrink to the small program while
        latency matters (requests waiting for admission, or a stream so
        young its first decode burst hasn't landed), regrow to the full
        throughput window after two consecutive steady ticks."""
        K = self.cfg.decode_steps_per_tick
        ladder = self._window_ladder()
        if len(ladder) == 1:
            self.stats.decode_window = K
            return K
        kmin = ladder[0]
        # pressure is an INTERACTIVE signal (ISSUE 19): batch rides its
        # own queue (never in self._queue) and a freshly admitted batch
        # stream has no TTFT stake — only interactive arrivals and
        # young interactive streams shrink the window. This is the
        # first preemption rung: a waiting interactive request cuts the
        # dispatch window under every live batch slot immediately.
        pressured = self._queue.qsize() > 0 or any(
            s is not None and s.generated <= 1
            and s.req.priority != "batch" for s in self._slots
        )
        if pressured:
            self._steady_ticks = 0
            chosen = kmin
        else:
            self._steady_ticks += 1
            chosen = K if self._steady_ticks >= 2 else self._cur_window
        if chosen < self._cur_window:
            self.stats.window_shrinks += 1
        elif chosen > self._cur_window:
            self.stats.window_grows += 1
        self._cur_window = chosen
        self.stats.decode_window = chosen
        return chosen

    # -- public API -------------------------------------------------------
    def queue_depth(self) -> tuple[int, float]:
        """(interactive queue depth, age in ms of its oldest request),
        read LIVE — callable from any thread. /state serves these
        instead of the per-tick snapshot: while the engine thread sits
        in an XLA compile (~20 s per program on the chip) nothing
        refreshes ``stats``, and a picker routing on a stale ``queued``
        of 0 piles a whole cold fleet's traffic onto one replica (seen
        on four chips, PR 21: 56 of 56 requests on replica 0). Peeking
        the underlying deque is safe: entries are only appended by
        other threads, and a request popped between the qsize check and
        the peek just yields a fresher head."""
        depth = self._queue.qsize()
        try:
            head = self._queue.queue[0]
        except IndexError:
            return depth, 0.0
        return depth, 1e3 * (time.monotonic() - head.enqueued_at)

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._run, name="tpuserve-engine", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        """Stop the loop; any still-pending requests finish with
        "error" so waiting consumers never hang."""
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        self._abort_all("engine stopped")

    def submit(self, req: GenRequest) -> None:
        if len(req.prompt) + req.max_tokens > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt+max_tokens {len(req.prompt)}+{req.max_tokens} exceeds "
                f"max_seq_len {self.cfg.max_seq_len}"
            )
        if req.priority == "batch":
            # the offline tier never sheds: batch work QUEUES under
            # pressure (unbounded — the /v1/batches surface bounds
            # in-flight lines host-side) instead of 429ing, and admits
            # only into slots interactive doesn't want
            self._batch_q.put(req)
            self._wake.set()
            return
        if self._queue.qsize() >= self.cfg.max_queued_requests:
            raise EngineOverloadedError(
                f"queue full ({self.cfg.max_queued_requests} waiting)"
            )
        self._queue.put(req)
        self._wake.set()

    # -- state snapshots: the prefix cache of a family with state -----------
    def _init_snapshots(self) -> None:
        """The snapshot pool — the family's ``slot_state`` leaves with
        ``CacheSpec.snapshot_rows`` rows, a fixed size — and the two
        copy programs, each ONE compiled program for any (slot, row)
        pair (dynamic indices), compiled in warm-up. They are named as
        prefill programs are: what they cost is part of what a prefill
        costs, in a trace's module groups too."""
        cfg = self.cfg
        self._snap_pool = self.cache_spec.make_snapshots(
            cfg.max_batch_size, cfg.kv_cache_dtype)
        self.stats.state_snapshot_bytes_total = (
            self._snap.n_rows
            * self.cache_spec.state_bytes_per_slot(cfg.kv_cache_dtype))

        def _move(dst, src, to, frm):
            row = jax.lax.dynamic_slice_in_dim(src, frm, 1, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(dst, row, to, axis=1)

        def _prefill_state_snapshot(pool, slots, slot, row):
            return {k: _move(pool[k], slots[k], row, slot) for k in pool}

        def _prefill_state_restore(cache, pool, row, slot):
            return StateCache(cache.kv, {
                k: _move(cache.slots[k], pool[k], slot, row) for k in pool})

        self._snap_save_fn = self.compile_tracker.register(
            "state_snapshot",
            jax.jit(_prefill_state_snapshot, donate_argnums=(0,)))
        self._snap_restore_fn = self.compile_tracker.register(
            "state_restore",
            jax.jit(_prefill_state_restore, donate_argnums=(0,)))

    def _save_snapshot_dev(self, slot: int, row: int) -> None:
        """Copy ``slot``'s state into pool row ``row``: dispatched
        behind the chunk that left the state there, and the host does
        not wait for it (the next chunk's donation of the cache orders
        itself behind this read)."""
        with self.stats.loop.span("engine/state_snapshot", row=row):
            self._snap_pool = self._snap_save_fn(
                self._snap_pool, self.kv_cache.slots, np.int32(slot),
                np.int32(row))

    def _restore_snapshot_dev(self, row: int, slot: int) -> None:
        with self.stats.loop.span("engine/state_restore", row=row):
            self.kv_cache = self._snap_restore_fn(
                self.kv_cache, self._snap_pool, np.int32(row),
                np.int32(slot))

    def _probe_prefix(self, chain_keys: list, n: int
                      ) -> tuple[list[int], int]:
        """(pages of the longest cached prefix a prompt of ``n`` tokens
        can resume behind, prompt tokens cached beyond it that must
        prefill again). The second is 0 for a family whose pages are
        all there is to a prefix; a family with snapshots resumes only
        at a chain node that holds one, and never behind its whole
        prompt: the last token's logits need the state BEFORE it, which
        a snapshot at the prompt's end is not."""
        ps = self.cfg.page_size
        pages = self.prefix_cache.probe(chain_keys)
        hits = min(len(pages), n // ps)
        if self._snap is None:
            return pages[:hits], 0
        depth = self._snap.longest(chain_keys, min(hits, (n - 1) // ps))
        return pages[:depth], (hits - depth) * ps

    def _begin_snapshots(self, slot: int, chain_keys: list,
                         depth: int) -> None:
        """An admission into ``slot`` starts its prefill behind
        ``depth`` cached pages: copy the snapshot at that chain node
        into the slot's state rows — held, so that this prompt's own
        saves cannot evict it, until ``_end_snapshots`` — and note the
        chain for ``chunk_boundary``."""
        adm = _SnapAdmission(chain_keys)
        self._snap_admissions[slot] = adm
        if depth:
            adm.held = chain_keys[depth - 1]
            self._snap.hold(adm.held)
            self._restore_snapshot_dev(
                self._snap.restore_row(adm.held), slot)
            self.stats.state_snapshots_restored = self._snap.restored

    def chunk_boundary(self, seq_id: int, done: int, n: int) -> None:
        """The chunk loop has dispatched the first ``done`` tokens of
        sequence ``seq_id``'s ``n``-token prompt. THE rule that picks
        the boundaries a snapshot is taken at, for a family that has
        them: every ``SNAPSHOT_EVERY_CHUNKS``-th chunk boundary of the
        prompt (where prompts that share a long head — a system prompt
        — part ways is unknown, so the head is covered at a fixed
        stride) and its last whole-chunk boundary (where the next turn
        of the same session resumes). Boundaries are multiples of the
        chunk from the prompt's start, so a hit's suffix runs the chunk
        partition a cold prefill runs; a chunk that is no multiple of
        the page has no page-aligned boundary and nothing is saved."""
        if self._snap is None:
            return
        slot = self._slot_of_seq[seq_id]
        adm = self._snap_admissions.get(slot)
        chunk, ps = self.cfg.prefill_chunk_tokens, self.cfg.page_size
        if adm is None or chunk % ps or done % chunk or done >= n:
            return
        if (n - done > chunk
                and (done // chunk) % SNAPSHOT_EVERY_CHUNKS):
            return
        key = adm.chain_keys[done // ps - 1]
        row = self._snap.claim(key)
        if row is not None:
            adm.saved.append(key)
            self._save_snapshot_dev(slot, row)
            self.stats.state_snapshots_saved = self._snap.saved
            self.stats.state_snapshots_evicted = self._snap.evicted

    def _end_snapshots(self, slot: int) -> None:
        """The admission into ``slot`` is over, installed or not: let
        go of the snapshot it resumed from, and drop those it saved
        whose pages never got registered (a prefill cut short: a
        snapshot lives no longer than its chain's pages)."""
        adm = self._snap_admissions.pop(slot, None)
        if adm is None:
            return
        if adm.held is not None:
            self._snap.release(adm.held)
        for key in adm.saved:
            if key not in self.prefix_cache._by_key:
                self._snap.drop(key)

    def warmup(self) -> None:
        """Compile every decode-window program in the adaptive ladder —
        plain (lean + full) AND every nonzero draft rung of the
        speculative ladder — and, with warm_prefill_buckets > 0, the
        attention backend's prefill surface (every (bucket, group)
        rung on xla-bucketed; the handful of token-budget chunk rungs
        on pallas-ragged — fewer programs, faster cold start) — before
        traffic arrives (the first burst then pays zero XLA compiles,
        and a mid-stream draft-rung transition never compiles a verify
        program on the hot path). Records warmup_ms + the compiled
        program count on EngineStats (/state: cold-start observables)."""
        t0 = time.monotonic()
        for P in self._warm_page_buckets():
            for k in self._window_ladder():
                for lean in (True, False):
                    state = self._build_device_state(bucket=P)
                    _, _, self.kv_cache, _, _ = self._decode_fn_for(
                        k, lean)(
                        self.params, self.lora_params, self.kv_cache,
                        state
                    )
                for d in self._spec_rungs:
                    if d == 0:
                        continue
                    state = self._build_device_state(bucket=P)
                    _, _, self.kv_cache, _, _ = self._decode_fn_for(
                        k, False, d)(
                        self.params, self.lora_params, self.kv_cache,
                        state
                    )
            # the incremental row-update scatters also run on the hot
            # path (admission / EOS / rung moves) and re-trace per
            # page-bucket state shape: compile them on a throwaway
            # state at THIS bucket so the first membership change at
            # any warmed bucket pays nothing. The throwaway stays a
            # LOCAL — warmup runs on the server thread while the
            # engine loop is already live, and publishing it through
            # self._device_state raced the loop's quiesce path (no
            # active slots → _device_state = None) into the middle of
            # this warm sequence (observed as warmup crashing on a
            # None state under slow compiles).
            state = self._build_device_state(bucket=P)
            state = self._row_update_fn_built()(
                state, np.int32(0), self._row_host_values(0, P))
            if self._spec_max:
                state = self._spec_update_fn_built()(
                    state, np.int32(0), np.int32(0))
            # the constrained-decoding bias-row scatter also runs on
            # the hot path (every FSM advance of a constrained slot)
            if self.cfg.constrained_decoding:
                V = self.model_cfg.vocab_size
                state = self._cn_update_fn_built()(
                    state, np.int32(0), np.zeros((V,), np.float32))
        if self._adapter_store is not None:
            # the hot-load row scatters run on the admission path: the
            # first non-resident adapter admission (or any later mix
            # change) must not pay an XLA compile
            self._adapter_store.warm()
        self.attn.warm()
        if self.cfg.warm_prefill_buckets > 0:
            # the sequence-sharded chunked-prefill ladder is engine-
            # owned (it preempts the backend for long suffixes), so the
            # backend warm above never covers it
            self._warm_sp_prefill_shapes()
        # migration page movers: a page export (device→host gather) or
        # an import at ANY page-count rung must never compile
        # mid-traffic — round-trip page 0 through the host exactly as a
        # real migration does (idempotent rewrites of page 0's own
        # content; nothing is serving yet)
        # (nothing moves pages alone for a pinned family)
        if not self._pinned:
            rows = kvq.page_to_host(self._export_page_dev(0))
            for r in self._import_rungs():
                self._import_pages_dev([0] * r, [rows] * r)
        if self._snap is not None:
            # both snapshot copies run on the admission path: row 0 of
            # the (empty) pool to slot 0 and back, nothing serving yet
            self._restore_snapshot_dev(0, 0)
            self._save_snapshot_dev(0, 0)
        # NOTE: warm passes discard program results wholesale, so the
        # MoE routing accumulators stay at zero here — the exported
        # stats count real traffic only (folds happen at the traffic
        # call sites, on the engine thread)
        self.stats.warmup_ms = round(1e3 * (time.monotonic() - t0), 3)
        self.stats.warm_programs = self.compile_tracker.program_count()

    def _warm_page_buckets(self) -> list[int]:
        """Page buckets warmup() compiles the decode ladder at:
        [current quiesced bucket] classically, or — with
        ``warm_decode_buckets`` = N — the pow2 rungs 1, 2, …, 2^(N-1)
        capped at max_pages_per_seq, so a first admission at ANY
        covered sequence length never compiles a decode program (or
        the matching row-update scatter) on the hot path."""
        n = self.cfg.warm_decode_buckets
        if n <= 0:
            return [self._decode_bucket_pages()]
        buckets: list[int] = []
        b = 1
        for _ in range(n):
            buckets.append(min(b, self.cfg.max_pages_per_seq))
            if b >= self.cfg.max_pages_per_seq:
                break
            b *= 2
        return sorted(set(buckets))

    def _warm_prefill_shapes(self, S: int) -> None:
        """Run the prefill program for every power-of-two group size at
        prompt bucket S with all-zero seq_lens: padded-row semantics
        drop every K/V scatter, so nothing is written — the call exists
        only to populate the jit cache for that shape."""
        V = self.model_cfg.vocab_size
        P = self.cfg.max_pages_per_seq
        G2 = 1
        while G2 <= self.cfg.max_batch_size:
            _, self.kv_cache, _ = self._prefill_fn(
                self.params, self.lora_params,
                jnp.zeros((G2, S), jnp.int32),
                jnp.zeros((G2,), jnp.int32),
                self.kv_cache,
                jnp.zeros((G2, P), jnp.int32),
                jnp.zeros((G2, 2), jnp.uint32),
                jnp.zeros((G2,), jnp.float32),
                jnp.ones((G2,), jnp.float32),
                jnp.zeros((G2,), jnp.int32),
                jnp.zeros((G2, V), jnp.float32),
                jnp.full((G2,), self._base_row, jnp.int32),
                **self.slot_kw([], G2),
            )
            G2 *= 2

    def _warm_sp_prefill_shapes(self) -> None:
        """Compile the sequence-sharded chunked-prefill surface: the
        chunk program plus every tail rung at or below it, at each warm
        page bucket large enough to ever host an sp prefill (the gather
        window covers prompt+max_tokens >= sp_prefill_min_tokens, so
        smaller buckets can never see the path). All-zero seq_lens:
        padded-row semantics drop every K/V scatter and the last-index
        gather clamps, so the calls only populate the jit cache. The
        surface stays log-sized — (tail rungs <= chunk) x (eligible
        pow2 buckets) — which is what keeps zero-hot-compile tripwires
        green at 32k-128k geometry without warming a 128k monolithic
        rung."""
        if self._prefill_sp_suffix_fn is None:
            return
        cfg = self.cfg
        sp = self._sp
        chunk = max(cfg.sp_chunk_tokens, sp)
        chunk = -(-chunk // sp) * sp
        rungs = {chunk}
        for t in range(1, chunk + 1):
            rungs.add(self._prefill_bucket(t, multiple_of=sp))
        min_need = -(-cfg.sp_prefill_min_tokens // cfg.page_size)
        V = self.model_cfg.vocab_size
        for P in self._warm_page_buckets():
            if P < min_need:
                continue
            for S in sorted(rungs):
                _, self.kv_cache, _ = self._prefill_sp_suffix_fn(
                    self.params, self.lora_params,
                    jnp.zeros((1, S), jnp.int32),
                    jnp.zeros((1,), jnp.int32),
                    jnp.zeros((1,), jnp.int32),
                    self.kv_cache,
                    jnp.zeros((1, P), jnp.int32),
                    jnp.zeros((1, 2), jnp.uint32),
                    jnp.zeros((1,), jnp.float32),
                    jnp.ones((1,), jnp.float32),
                    jnp.zeros((1,), jnp.int32),
                    jnp.zeros((1, V), jnp.float32),
                    jnp.full((1,), self._base_row, jnp.int32),
                )

    # -- prefill/decode disaggregation: KV page migration (ISSUE 8) --------
    def migrate_export(self, req: GenRequest,
                       timeout: float = 30.0) -> dict:
        """Cut a live session and serialize its page chain for transfer
        to another replica: full KV pages (device→host), the chained
        content hashes identifying them, and the slot's sampling /
        penalty / key state. Callable from any thread — the cut itself
        runs on the engine thread at the next tick, after the in-flight
        decode window settles, so the wire state is exactly a token
        boundary. Returns {"blob": <json-able dict>, "data": [np page
        arrays]}. Raises MigrationError (session untouched on failure)
        or TimeoutError."""
        box: dict = {"evt": threading.Event()}
        self._mig_q.put(("export", req, box))
        self._wake.set()
        if not box["evt"].wait(timeout):
            raise TimeoutError("migration export timed out")
        if "error" in box:
            raise MigrationError(box["error"])
        return box["result"]

    def migrate_import(self, tokens: list[int], pages: list[np.ndarray],
                       timeout: float = 30.0) -> int:
        """Adopt another replica's exported page chain: scatter the
        host-side pages into this pool and register them in the prefix
        cache under their chain hashes — the imported pages then live
        under the NORMAL refcount/CoW/eviction discipline (parked
        evictable until the continuation request adopts them; pool
        pressure can reclaim them like any cached prefix). Returns the
        number of pages imported. Raises MigrationError / TimeoutError;
        OutOfPagesError surfaces as MigrationError("…pages…") so the
        caller can requeue like admission pressure."""
        box: dict = {"evt": threading.Event()}
        self._mig_q.put(("import", (tokens, pages, 0, "migration"), box))
        self._wake.set()
        if not box["evt"].wait(timeout):
            raise TimeoutError("migration import timed out")
        if "error" in box:
            raise MigrationError(box["error"])
        return box["result"]

    @engine_thread_only
    def _process_migrations(self) -> None:
        """Run queued export/import jobs on the engine thread (the only
        thread allowed to touch kv_cache's donation chain and the slot
        table). Errors are reported to the waiting caller, never raised
        into the engine loop."""
        while True:
            try:
                kind, payload, box = self._mig_q.get_nowait()
            except queue.Empty:
                return
            try:
                if kind == "export":
                    box["result"] = self._do_export(payload)
                elif kind == "fetch":
                    box["result"] = self._do_fetch(payload)
                else:
                    box["result"] = self._do_import(*payload)
            except Exception as e:  # noqa: BLE001 — relayed to caller
                box["error"] = f"{type(e).__name__}: {e}"
            finally:
                box["evt"].set()

    @engine_thread_only
    def _do_export(self, req: GenRequest) -> dict:
        """Engine-thread half of migrate_export. Wire rule: only COMPLETE
        pages whose every row is written KV travel — k = (m-1) // page
        pages for m total tokens (the last token's K/V is the pending
        decode input and not yet written). The ≤ one-page token tail is
        recomputed by the importer's offset resume, so the imported
        pages are always safe to share under the chain-hash contract
        ("this page holds ALL of positions [i·ps, (i+1)·ps)")."""
        if not isinstance(self.allocator, RefcountedAllocator):
            raise MigrationError(
                "migration requires the prefix cache "
                "(refcounted page allocator)")
        if self._pinned:
            raise MigrationError(
                f"migration is off: {self.features_off['migration']}")
        if req.emit_lp is not None:
            raise MigrationError(
                "logprobs sessions are not migratable")
        if req.constraint is not None:
            # the wire blob carries no FSM cursor; a resumed constrained
            # stream would decode unconstrained — refuse instead
            raise MigrationError(
                "grammar-constrained sessions are not migratable")
        idx = next((i for i, s in enumerate(self._slots)
                    if s is not None and s.req is req), None)
        if idx is None:
            raise MigrationError(
                "request is not active (finished, cancelled, or not "
                "yet admitted)")
        # settle the in-flight window: it may still write this
        # sequence's pages, and its tokens must land before the cut so
        # the exported state is a clean token boundary
        self._drain_inflight()
        self._apply_frees()
        s = self._slots[idx]
        if s is None or s.req is not req:
            raise MigrationError("request finished during the export cut")
        if s.generated < 1:
            raise MigrationError("prefill not finished (no token yet)")
        # the cut: finish the slot with "migrated" — pages free under
        # the normal refcount discipline (cache-registered prompt pages
        # park evictable; the export pin already released)
        if req.trace is not None:
            req.trace.engine_finish("migrated")
        out = self._export_cut(idx)
        req.emit(-1, "migrated")
        self.stats.migrations_out += 1
        self.stats.migration_pages_out += len(out["data"])
        logger.info("exported seq %d: %d tokens, %d pages", req.id,
                    len(out["blob"]["tokens"]), len(out["data"]))
        return out

    # -- usage metering (ISSUE 20) ---------------------------------------
    #
    # One MeterRecord per request LIFETIME, emitted on the engine thread
    # strictly before the terminal emit (FIFO + the consumer's queue make
    # it visible when the finish item is dequeued). Migration/park cuts
    # never emit — the accumulated meter rides the export blob and the
    # resumed slot's terminal record covers the whole spliced stream.
    # EngineStats.meter_* counters are incremented ONLY in _meter_emit,
    # so a ledger built from the records reconciles against /state
    # token-for-token by construction.

    _METER_SUM_KEYS = ("prefill_real", "prefill_padded", "prefix_reused",
                       "decode_tokens", "spec_drafted", "spec_accepted",
                       "segments")

    @engine_thread_only
    def _meter_fold(self, s: "_Slot") -> dict:
        """Fold slot accumulators + any imported carry into one meter
        dict (no finish/schema — the terminal record adds those; the
        same dict rides an export blob as the continuation carry).
        HBM residency integrates the current dwell segment at the
        slot's PRESENT page footprint: pages × kv_page_bytes × dwell_s."""
        req = s.req
        now = time.monotonic()
        bytes_now = s.m_res_bytes
        try:
            bytes_now = (len(self.allocator.pages(req.id))
                         * self.kv_page_bytes)
        except Exception:
            pass
        hbm = s.m_hbm_pbs
        if s.m_res_t0 > 0.0:
            hbm += (now - s.m_res_t0) * bytes_now
        rec = {
            "prefill_real": s.m_prefill_real,
            "prefill_padded": s.m_prefill_padded,
            "prefix_reused": s.m_prefix_reused,
            "decode_tokens": s.generated,
            "spec_drafted": s.m_spec_drafted,
            "spec_accepted": s.m_spec_accepted,
            "hbm_page_byte_s": round(hbm, 6),
            "host_page_byte_s": 0.0,
            "segments": 1,
            "tenant": req.tenant,
            "priority": req.priority,
        }
        c = s.m_carry
        if c:
            for key in self._METER_SUM_KEYS:
                rec[key] += int(c.get(key, 0))
            rec["hbm_page_byte_s"] = round(
                rec["hbm_page_byte_s"]
                + float(c.get("hbm_page_byte_s", 0.0)), 6)
            rec["host_page_byte_s"] = round(
                float(c.get("host_page_byte_s", 0.0)), 6)
        return rec

    @engine_thread_only
    def _meter_emit(self, rec: dict, sink) -> None:
        """THE single point where meter counters move and a record
        reaches its sink — every emission path funnels here."""
        st = self.stats
        st.meter_records += 1
        st.meter_prefill_tokens += rec["prefill_real"]
        st.meter_prefill_padded_tokens += rec["prefill_padded"]
        st.meter_prefix_reused_tokens += rec["prefix_reused"]
        st.meter_decode_tokens += rec["decode_tokens"]
        st.meter_spec_drafted += rec["spec_drafted"]
        st.meter_spec_accepted += rec["spec_accepted"]
        st.meter_hbm_page_byte_s = round(
            st.meter_hbm_page_byte_s + rec["hbm_page_byte_s"], 6)
        st.meter_host_page_byte_s = round(
            st.meter_host_page_byte_s + rec["host_page_byte_s"], 6)
        if sink is not None:
            try:
                sink(rec)
            except Exception:
                logger.exception("meter sink failed")

    @engine_thread_only
    def _meter_finish(self, s: "_Slot", finish: str) -> None:
        """Terminal record for a live slot (EOS/length/cancel/error)."""
        rec = self._meter_fold(s)
        rec["schema"] = 1
        rec["finish"] = finish
        self._meter_emit(rec, s.req.meter_sink)

    @engine_thread_only
    def _meter_zero(self, req: GenRequest, finish: str) -> None:
        """Terminal record for a request that never held a slot
        (cancelled/errored in a queue, unknown adapter). Usually all
        zeros; a queued CONTINUATION still carries its segments' meter."""
        c = (req.import_state or {}).get("meter_carry") or {}
        rec = {
            "schema": 1,
            "finish": finish,
            "prefill_real": int(c.get("prefill_real", 0)),
            "prefill_padded": int(c.get("prefill_padded", 0)),
            "prefix_reused": int(c.get("prefix_reused", 0)),
            "decode_tokens": int(c.get("decode_tokens", 0)),
            "spec_drafted": int(c.get("spec_drafted", 0)),
            "spec_accepted": int(c.get("spec_accepted", 0)),
            "hbm_page_byte_s": round(float(c.get("hbm_page_byte_s", 0.0)), 6),
            "host_page_byte_s": round(
                float(c.get("host_page_byte_s", 0.0)), 6),
            "segments": int(c.get("segments", 0)),
            "tenant": req.tenant,
            "priority": req.priority,
        }
        self._meter_emit(rec, req.meter_sink)

    @engine_thread_only
    def _meter_parked(self, park: dict, finish: str) -> None:
        """Terminal record for a host-parked session that will never
        resume (cancelled while parked / engine abort): the exported
        carry plus the host-spill residency accrued while parked."""
        blob = park["blob"]
        c = dict(blob.get("meter") or {})
        now = time.monotonic()
        host = (float(c.get("host_page_byte_s", 0.0))
                + (now - park.get("parked_at", now))
                * park.get("park_bytes", 0))
        rec = {
            "schema": 1,
            "finish": finish,
            "prefill_real": int(c.get("prefill_real", 0)),
            "prefill_padded": int(c.get("prefill_padded", 0)),
            "prefix_reused": int(c.get("prefix_reused", 0)),
            "decode_tokens": int(c.get("decode_tokens", 0)),
            "spec_drafted": int(c.get("spec_drafted", 0)),
            "spec_accepted": int(c.get("spec_accepted", 0)),
            "hbm_page_byte_s": round(float(c.get("hbm_page_byte_s", 0.0)), 6),
            "host_page_byte_s": round(host, 6),
            "segments": int(c.get("segments", 0)),
            "tenant": str(blob.get("tenant", "")),
            "priority": str(blob.get("priority", "batch")),
        }
        self._meter_emit(rec, park.get("meter_sink"))

    @engine_thread_only
    def _export_cut(self, idx: int) -> dict:
        """Serialize slot ``idx``'s session at the (already settled)
        token boundary and free the slot — the shared engine-thread cut
        behind both the migration export (wire transfer to a sibling)
        and the batch-preemption park (host-side stash on THIS
        replica). Wire rule unchanged: only complete written pages
        travel; the ≤ one-page tail is recomputed by the resume's
        offset prefill. The CALLER owns emit/trace/counter semantics —
        migration finishes the stream, a park keeps the consumer
        attached. Returns {"blob": <json-able>, "data": [np pages]}."""
        s = self._slots[idx]
        assert s is not None
        req = s.req
        ps = self.cfg.page_size
        tokens = list(req.prompt) + list(s.gen_tokens)
        m = len(tokens)
        k = (m - 1) // ps
        pages = self.allocator.pages(req.id)[:k]
        # pin the chain for the duration of the device→host transfer:
        # nothing may free/evict/CoW these pages while the copy (or the
        # wire transfer the caller performs next) is in flight
        pin = self.allocator.begin_export(pages)
        try:
            outs = [self._export_page_dev(p) for p in pages]
            self._start_host_copy(outs)  # per-page copies overlap
            data = [kvq.page_to_host(o) for o in outs]
        finally:
            self.allocator.end_export(pin)
        ims = req.import_state or {}
        sp = req.sampling
        blob = {
            "tokens": tokens,
            "page_size": ps,
            "chain": [h.hex() for h in
                      page_chain_hashes(tokens, ps)[:k]],
            "kv_dtype": self.cfg.kv_cache_dtype,
            "orig_prompt_len": ims.get("orig_prompt_len",
                                       len(req.prompt)),
            "generated": ims.get("generated", 0) + s.generated,
            "max_tokens": req.max_tokens - s.generated,
            "key_seed": s.key_seed,
            "adapter": req.adapter,
            "tenant": req.tenant,
            "priority": req.priority,
            "stop_token_ids": list(req.stop_token_ids),
            "sampling": {
                "temperature": sp.temperature, "top_p": sp.top_p,
                "top_k": sp.top_k, "seed": sp.seed,
                "frequency_penalty": sp.frequency_penalty,
                "presence_penalty": sp.presence_penalty,
                "logit_bias": [[t, b] for t, b in sp.logit_bias],
            },
            # usage metering (ISSUE 20): the cut emits NO MeterRecord —
            # this carry (slot accumulators + upstream segments, HBM
            # residency integrated to the cut) rides to the resume so
            # the spliced stream meters exactly once at its real end
            "meter": self._meter_fold(s),
        }
        self._pending_frees.append(req.id)
        self._release_adapter_row(s.adapter_row)
        self._slots[idx] = None
        self._dirty_rows.add(idx)
        self._wake.set()
        return {"blob": blob, "data": data}

    @engine_thread_only
    def _park_batch_slot(self, idx: int) -> bool:
        """Preemption rung (ii): cut one live BATCH slot off the device
        through the migration export machinery and stash it host-side
        (pages + blob + the still-attached consumer callback); the
        batch tier resumes it byte-identically once interactive stops
        wanting the slot. Returns True when the slot is free afterward
        (parked, or found finished by the settle), False when the
        session is not parkable — no token yet, logprobs/constrained
        (the blob carries neither), or no refcounted allocator — and
        the caller should try another victim."""
        s = self._slots[idx]
        if s is None:
            return True
        req = s.req
        if (not isinstance(self.allocator, RefcountedAllocator)
                or self._pinned
                or req.emit_lp is not None
                or req.constraint is not None
                or s.generated < 1):
            return False
        # settle the in-flight window so the cut is a token boundary
        self._drain_inflight()
        self._apply_frees()
        s = self._slots[idx]
        if s is None or s.req is not req:
            return True  # finished during the settle — slot is free
        if req.trace is not None:
            req.trace.engine_finish("parked")
        entry = self._export_cut(idx)
        entry["emit"] = req.emit
        entry["cancelled"] = req.cancelled
        # metering: the parked dwell accrues HOST page·byte·seconds
        # (pages live in host RAM, not HBM) — folded into the carry at
        # resume, or into the terminal record if it never resumes
        entry["meter_sink"] = req.meter_sink
        entry["parked_at"] = time.monotonic()
        entry["park_bytes"] = len(entry["data"]) * self.kv_page_bytes
        self._parked_batch.append(entry)
        self.stats.batch_preemptions += 1
        logger.info("parked batch seq %d (%d pages) for interactive "
                    "admission", req.id, len(entry["data"]))
        return True

    @engine_thread_only
    def _preempt_batch(self) -> bool:
        """Park live batch slots so WAITING interactive requests can
        admit — called by _admit when every slot is taken. Parks at
        most as many sessions as requests are waiting. Returns True
        when at least one slot freed."""
        want = self._queue.qsize()
        if want <= 0:
            return False
        freed = 0
        for i, s in enumerate(self._slots):
            if freed >= want:
                break
            if (s is not None and s.req.priority == "batch"
                    and self._park_batch_slot(i)):
                freed += 1
        return freed > 0

    @engine_thread_only
    def _do_import(self, tokens: list[int],
                   pages_data: list[np.ndarray], start: int = 0,
                   source: str = "migration") -> int:
        """Engine-thread half of migrate_import / kv_import_pages:
        allocate pages, scatter the imported rows, register the chain in
        the prefix cache, then release — the pages park evictable
        (revivable) until an admission probe adopts them. No new page
        lifecycle: from here on they are ordinary cached prefix pages.
        ``start`` offsets the chain depth the pages land at (a fleet
        fetch extends an already-resident prefix); ``source`` picks the
        counters (migration vs cross-replica fetch)."""
        if self.prefix_cache is None or self._pinned:
            raise MigrationError(
                "migration import requires the prefix cache of a family "
                "whose pages are all there is to a prefix")
        ps = self.cfg.page_size
        k = len(pages_data)
        if k == 0:
            return 0
        if start < 0 or start + k > (len(tokens) - 1) // ps:
            raise MigrationError(
                f"pages [{start}, {start + k}) exceed the written-KV "
                f"coverage of {len(tokens)} tokens")
        mc = self.model_cfg
        want = (mc.n_layers, 2, ps, mc.n_kv_heads, mc.head_dim)
        for rows in pages_data:
            if not kvq.page_matches_dtype(rows,
                                          self.cfg.kv_cache_dtype):
                raise MigrationError(
                    "page dtype does not match this engine's "
                    f"kv_cache_dtype={self.cfg.kv_cache_dtype!r} "
                    "(quantized pages only scatter into a matching "
                    "quantized pool)")
            if not kvq.page_shape_ok(rows, want):
                raise MigrationError(
                    f"page shape != expected {want} "
                    "(mismatched model or page size)")
        keys = page_chain_hashes(tokens, ps)[start:start + k]
        seq_id = next(self._seq_ids)
        self.allocator.allocate_extra(seq_id, k)  # OutOfPages → caller
        page_ids = self.allocator.pages(seq_id)
        self._import_pages_dev(page_ids, pages_data)
        self.prefix_cache.insert(keys, page_ids)
        self._purge_spilled(keys)
        # release: registered pages park evictable (adopted by the
        # continuation's probe); pages whose chain key was ALREADY
        # cached locally were skipped by insert and return to the free
        # stack immediately
        self.allocator.free(seq_id)
        if source == "fetch":
            self.stats.kv_fetches_in += 1
            self.stats.kv_fetch_pages_in += k
        elif source == "parked":
            # batch park/resume is intra-replica: it rides the
            # batch_preemptions / batch_resumed pair, not the
            # cross-replica migration counters
            pass
        else:
            self.stats.migrations_in += 1
            self.stats.migration_pages_in += k
        logger.info("imported %d pages for a %d-token chain (%s)", k,
                    len(tokens), source)
        return k

    # -- engine loop ------------------------------------------------------
    def _run(self) -> None:
        logger.info("engine loop started (batch=%d, pages=%d×%d)",
                    self.cfg.max_batch_size, self.cfg.num_pages,
                    self.cfg.page_size)
        # every nanosecond from here to the loop's end belongs to one
        # phase of the ledger (obs/flight.py): the calls below open
        # theirs, what they enter inside suspends them, and the
        # remainder is ``other``
        loop = self.stats.loop
        loop.start()
        # a program this thread has to load from here on is one a
        # request waits for (obs/xla_events.py)
        xla_events.LEDGER.late_hooks[threading.get_ident()] = (
            self._on_late_load)
        while not self._stop.is_set():
            try:
                loop.enter(REAP)
                self._reap_cancelled()
                self._process_migrations()
                loop.enter(ADMIT)
                admitted = self._admit()
                # the offline tier soaks whatever interactive left idle
                admitted |= self._admit_batch_tier()
                worked = self._decode_tick()
                if self._stop.is_set():
                    self._drain_inflight()
                    self._apply_frees()
            except Exception as e:  # never die silently: fail loudly and
                # error out every in-flight request instead of hanging them
                logger.exception("engine tick failed")
                self.healthy = False
                self.last_error = f"{type(e).__name__}: {e}"
                self._abort_all(str(e))
                loop.enter(OTHER)
                return
            if not admitted and not worked:
                loop.enter(IDLE)
                self._wake.wait(timeout=0.05)
                self._wake.clear()
            loop.enter(OTHER)
        # deliver any tokens still in flight before exiting
        try:
            self._drain_inflight()
            self._apply_frees()
        except Exception:
            pass
        loop.enter(OTHER)  # settles the last phase (and closes its span)
        logger.info("engine loop stopped")

    @engine_thread_only
    def _on_late_load(self, load: dict) -> None:
        """A program was loaded on this thread after the server was
        ready: name the loop phase it fell in, tell every request in
        flight what it waited for, and mark a running capture."""
        loop = self.stats.loop
        load["phase"] = LOOP_PHASES[loop.cur]
        ms = round(load["trace_ms"] + load["lower_ms"]
                   + load["backend_ms"], 3)
        loop.instant("xla/late_load", fn=load["fn"], ms=ms)
        if self.flight is not None:
            for entry in self.flight.in_flight():
                entry.event("program_load", fn=load["fn"], ms=ms,
                            hit=load["hit"])

    @engine_thread_only
    def _abort_all(self, reason: str) -> None:
        if self._inflight is not None:
            # the in-flight window's captured frees must not leak pages
            self._pending_frees.extend(self._inflight.frees)
            self._inflight = None
        self._apply_frees()
        self._device_state = None
        self._need_rebuild = True
        self._dirty_rows.clear()
        self._spec_dirty.clear()
        for i, s in enumerate(self._slots):
            if s is not None:
                self._meter_finish(s, "error")
                s.req.emit(-1, "error")
                self.allocator.free(s.req.id)
                self._release_adapter_row(s.adapter_row)
                self._slots[i] = None
        try:
            while True:
                req = self._queue.get_nowait()
                self._meter_zero(req, "error")
                req.emit(-1, "error")
        except queue.Empty:
            pass
        # the batch tier's queue and parked sessions have waiting
        # consumers too (never-shed ≠ never-finished on engine death)
        try:
            while True:
                req = self._batch_q.get_nowait()
                self._meter_zero(req, "error")
                req.emit(-1, "error")
        except queue.Empty:
            pass
        for park in self._parked_batch:
            self._meter_parked(park, "error")
            park["emit"](-1, "error")
        self._parked_batch.clear()
        # waiting migration callers must not hang until their timeout
        try:
            while True:
                _kind, _payload, box = self._mig_q.get_nowait()
                box["error"] = f"engine aborted: {reason}"
                box["evt"].set()
        except queue.Empty:
            pass

    @engine_thread_only
    def _reap_cancelled(self) -> None:
        for i, s in enumerate(self._slots):
            if s is not None and s.req.cancelled.is_set():
                if s.req.trace is not None:
                    s.req.trace.engine_finish("cancel")
                # a cancelled stream still has a waiting consumer (the
                # batch runner's _collect, a non-streaming handler):
                # reaping the slot without a terminal event would hang
                # it forever — a /v1/batches cancel must finalize
                self._meter_finish(s, "cancelled")
                s.req.emit(-1, "cancelled")
                self._pending_frees.append(s.req.id)
                self._release_adapter_row(s.adapter_row)
                self._slots[i] = None
                self._dirty_rows.add(i)

    def _free_slot_index(self) -> int | None:
        for i, s in enumerate(self._slots):
            if s is None and i not in self._reserved_slots:
                return i
        return None

    def slot_kw(self, seq_ids: list[int], rows: int = 0) -> dict:
        """The extra argument of a prefill program of a per-slot-state
        family: the decode slot of each row's sequence, padding rows
        (up to ``rows``) out of range so that they write nowhere.
        Nothing for the other families."""
        if not self._stateful:
            return {}
        ids = np.full((max(rows, len(seq_ids)),), self.cfg.max_batch_size,
                      np.int32)
        for r, seq_id in enumerate(seq_ids):
            ids[r] = self._slot_of_seq[seq_id]
        return {"slot_ids": jnp.asarray(ids)}

    def _free_slot_count(self) -> int:
        return sum(1 for i, s in enumerate(self._slots)
                   if s is None and i not in self._reserved_slots)

    @engine_thread_only
    def _admit(self) -> bool:
        """Admit queued requests: prefill + first token.

        Simple prompts (plain full prefill — no prefix-cache hit, not
        chunked, not sequence-parallel) that are queued together are
        prefilled in ONE batched [G, S] device call instead of G serial
        [1, S] calls: a batch-B burst's first tokens arrive after one
        large MXU-friendly pass rather than a B-step prefill ladder
        (vLLM-style batched admission, TPU-first shape discipline —
        padded rows carry seq_len 0, whose K/V scatters drop). Everything
        else takes the per-request path below."""
        admitted = False
        while True:
            free = self._free_slot_count()
            if free == 0:
                # interactive arrivals under a full batch reclaim slots
                # from the offline class (ISSUE 19): rung (i) — the
                # shrunk dispatch window — already bounded the wait;
                # rung (ii) parks batch sessions host-side
                if not self._preempt_batch():
                    break
                free = self._free_slot_count()
                if free == 0:
                    break
            pending: list[GenRequest] = []
            try:
                while len(pending) < free:
                    pending.append(self._queue.get_nowait())
            except queue.Empty:
                pass
            if not pending:
                break
            if (self.cfg.admission_coalesce_ms > 0
                    and len(pending) < free
                    and self._inflight is None
                    and all(s is None for s in self._slots)):
                # completely idle + partial burst: a batch of concurrent
                # arrivals spans a few ms of event-loop scheduling —
                # wait once so the whole burst prefills as ONE batched
                # call instead of a 1+(B-1) split. Under the first-token
                # fast path a LONE arrival does not ride the full timer:
                # it probes 1ms for burst evidence (a second queued
                # request) and otherwise goes straight to prefill —
                # single-request TTFT stops paying for burst insurance,
                # while real bursts (which surface a second submit
                # within the probe) still coalesce fully.
                wait_ms = self.cfg.admission_coalesce_ms
                loop = self.stats.loop
                outer = loop.enter(ADMIT_WAIT)
                if len(pending) == 1:
                    probe = min(1.0, wait_ms)
                    time.sleep(probe / 1e3)
                    try:
                        while len(pending) < free:
                            pending.append(self._queue.get_nowait())
                    except queue.Empty:
                        pass
                    wait_ms = 0.0 if len(pending) == 1 else \
                        max(0.0, wait_ms - probe)
                if wait_ms > 0 and len(pending) < free:
                    time.sleep(wait_ms / 1e3)
                    try:
                        while len(pending) < free:
                            pending.append(self._queue.get_nowait())
                    except queue.Empty:
                        pass
                loop.resume(outer)
            # fairness guard (ISSUE 7): per-tenant slot cap + deficit
            # ordering over the popped window. Deferred requests must
            # not occlude admissible tenants still queued behind them,
            # so when the cap left slots unused the scan extends over
            # the rest of the queue (bounded by max_queued_requests).
            admit, fair_requeue, capped = self._fair_admission(
                pending, free)
            if fair_requeue and len(admit) < free:
                more: list[GenRequest] = []
                try:
                    while True:
                        more.append(self._queue.get_nowait())
                except queue.Empty:
                    pass
                if more:
                    admit, fair_requeue, capped = self._fair_admission(
                        pending + more, free)
            self.stats.tenant_deferrals += capped
            pending = admit
            fair_stop = bool(fair_requeue)
            if not pending:
                # everything at cap: back to the queue head (arrival
                # order kept) until a tenant frees a slot
                self._requeue_front_many(fair_requeue)
                break
            # one coalesced-admission burst id per pass — lifecycle
            # traces carry it so a trace/flight reader can see which
            # requests shared a batched prefill
            self._cur_burst = (next(self._burst_seq), len(pending))
            # Classify once (prompt hashes computed here are reused all
            # the way to the post-prefill cache insert), then admit in
            # STRICT arrival order: contiguous runs of ≥2 simple requests
            # go through the batched prefill, everything else through the
            # per-request path — so pages are always allocated in arrival
            # order and a requeued head-of-line request can never be
            # starved by later simple arrivals grabbing its pages.
            items: list[tuple[GenRequest, bool, list]] = []
            seen_chain_heads: set = set()
            for req in pending:
                if req.cancelled.is_set():
                    # consumed without a slot — still meters (zeros)
                    self._meter_zero(req, "cancelled")
                    continue
                ok, chain = self._classify(req)
                if ok and chain:
                    head = chain[0]
                    if head in seen_chain_heads:
                        # a batch-mate shares its first prompt page: the
                        # batched path would prefill the shared prefix
                        # redundantly with its own page copies — route it
                        # through the per-request path, which adopts the
                        # pages the batch inserts in this same pass
                        ok = False
                    else:
                        seen_chain_heads.add(head)
                items.append((req, ok, chain))
            stop = False
            unhandled: list[GenRequest] = []
            i = 0
            while i < len(items):
                req, simple, chain = items[i]
                if simple:
                    j = i
                    while j < len(items) and items[j][1]:
                        j += 1
                    if j - i >= 2:
                        run = items[i:j]
                        done, leftover = self._admit_batch(
                            [it[0] for it in run],
                            {id(it[0]): it[2] for it in run})
                        admitted |= done > 0
                        if leftover is not None:  # page pressure
                            unhandled.extend(leftover)
                            unhandled.extend(it[0] for it in items[j:])
                            stop = True
                            break
                        i = j
                        continue
                r = self._admit_one(req, chain)
                if r == "admitted":
                    admitted = True
                elif r in ("stop", "stop_consumed"):
                    if r == "stop":
                        unhandled.append(req)
                    unhandled.extend(it[0] for it in items[i + 1:])
                    stop = True
                    break
                i += 1
            if unhandled or fair_requeue:
                # single requeue: page-pressure leftovers first (they
                # were at the admission head), then fairness deferrals
                self._requeue_front_many(unhandled + fair_requeue)
            if stop or fair_stop:
                # a fairness deferral must end the pass — looping would
                # re-pop the deferred head and spin until a slot frees
                break
        return admitted

    @engine_thread_only
    def _admit_interactive(self) -> bool:
        """Chunk-boundary admission (long-context decode liveness):
        called by ``sp_chunked_prefill`` between chunk steps. Pops the
        queue, admits SHORT requests — below sp_prefill_min_tokens,
        so they can never re-enter the sp chunk loop — into free slots
        through the normal per-request path, and requeues everything
        else in arrival order. An interactive request that arrives
        behind a 128k prefill gets its first token at the next chunk
        boundary (its own short prefill) and keeps streaming through
        the boundary decode ticks, instead of waiting out the whole
        long prefill. The fairness guard runs over the short subset,
        so tenant caps hold at boundaries too. Reentrancy-latched: a
        short admission's own chunked (non-sp) prefill must not admit
        again from its boundaries."""
        if self._in_chunk_admit:
            return False
        free = self._free_slot_count()
        if free == 0:
            return False
        backlog: list[GenRequest] = []
        try:
            while True:
                backlog.append(self._queue.get_nowait())
        except queue.Empty:
            pass
        if not backlog:
            return False
        shorts = [r for r in backlog
                  if len(r.prompt) < self.cfg.sp_prefill_min_tokens]
        admitted = False
        handled: set[int] = set()
        self._in_chunk_admit = True
        try:
            admit, _fair_rq, capped = self._fair_admission(shorts, free)
            self.stats.tenant_deferrals += capped
            for req in admit:
                if req.cancelled.is_set():
                    self._meter_zero(req, "cancelled")
                    handled.add(id(req))
                    continue
                _ok, chain = self._classify(req)
                r = self._admit_one(req, chain)
                if r == "stop":
                    break  # shutdown: leave it (and the rest) queued
                handled.add(id(req))
                if r == "admitted":
                    admitted = True
                    self.stats.sp_interactive_admits += 1
        finally:
            self._in_chunk_admit = False
        self._requeue_front_many(
            [r for r in backlog if id(r) not in handled])
        return admitted

    def _batch_ceiling(self) -> int:
        """Most decode slots the batch class may hold at once."""
        return max(1, int(self.cfg.batch_slot_frac
                          * self.cfg.max_batch_size))

    def _batch_active(self) -> int:
        return sum(1 for s in self._slots
                   if s is not None and s.req.priority == "batch")

    @engine_thread_only
    def _admit_batch_tier(self) -> bool:
        """Admit offline work into slots interactive doesn't want: runs
        AFTER the interactive admission pass, only while the
        interactive queue is empty, and never past the batch_slot_frac
        ceiling — the priority generalization of the deficit-weighted
        tenant scan (which still orders WITHIN the class). Parked
        (preempted) sessions resume first, oldest first: their pages
        re-import through the migration scatter path, the continuation
        admission adopts them from the prefix cache, and the resumed
        stream is byte-identical to an uninterrupted run
        (tests/test_batch_tier.py's f32 rig)."""
        admitted = False
        while True:
            if self._queue.qsize() > 0:
                break  # interactive wants the slots — yield
            room = min(self._free_slot_count(),
                       self._batch_ceiling() - self._batch_active())
            if room <= 0:
                break
            if self._parked_batch:
                park = self._parked_batch[0]
                if park["cancelled"].is_set():
                    # dropping a parked session is a cancel FINISH, not
                    # a silent vanish — its _collect is still waiting
                    self._meter_parked(park, "cancelled")
                    park["emit"](-1, "cancelled")
                    self._parked_batch.pop(0)
                    continue
                try:
                    self._do_import(
                        [int(t) for t in park["blob"]["tokens"]],
                        park["data"], 0, "parked")
                except (MigrationError, OutOfPagesError):
                    break  # pool pressure: retry at a later pass
                # close the parked dwell: host-spill residency accrued
                # while off-device joins the carry the resume inherits
                carry = park["blob"].get("meter")
                if carry is not None:
                    now = time.monotonic()
                    carry["host_page_byte_s"] = round(
                        float(carry.get("host_page_byte_s", 0.0))
                        + (now - park.get("parked_at", now))
                        * park.get("park_bytes", 0), 6)
                    # a failed admission re-parks this entry: re-anchor
                    # so the next fold never double-charges this dwell
                    park["parked_at"] = now
                req = continuation_request(park["blob"],
                                           emit=park["emit"])
                req.cancelled = park["cancelled"]
                req.meter_sink = park.get("meter_sink")
                self._parked_batch.pop(0)
                _ok, chain = self._classify(req)
                r = self._admit_one(req, chain)
                if r == "admitted":
                    admitted = True
                    self.stats.batch_resumed += 1
                elif r == "stop":
                    # page pressure mid-admission: the imported pages
                    # stay cached (evictable) — re-park, retry later
                    self._parked_batch.insert(0, park)
                    break
                elif r == "stop_consumed":
                    break
                continue
            pending: list[GenRequest] = []
            try:
                while len(pending) < room:
                    pending.append(self._batch_q.get_nowait())
            except queue.Empty:
                pass
            if not pending:
                break
            admit, requeue, capped = self._fair_admission(pending, room)
            self.stats.tenant_deferrals += capped
            stop = False
            unhandled: list[GenRequest] = []
            for j, req in enumerate(admit):
                if req.cancelled.is_set():
                    # popped from _batch_q with a consumer still
                    # draining its queue — finalize, don't drop
                    self._meter_zero(req, "cancelled")
                    req.emit(-1, "cancelled")
                    continue
                _ok, chain = self._classify(req)
                r = self._admit_one(req, chain)
                if r == "admitted":
                    admitted = True
                elif r in ("stop", "stop_consumed"):
                    if r == "stop":
                        unhandled.append(req)
                    unhandled.extend(admit[j + 1:])
                    stop = True
                    break
            if unhandled or requeue:
                self._requeue_batch_front(unhandled + requeue)
            if stop or requeue:
                break
        return admitted

    def _requeue_batch_front(self, reqs: list[GenRequest]) -> None:
        items = list(reqs)
        if not items:
            return
        try:
            while True:
                items.append(self._batch_q.get_nowait())
        except queue.Empty:
            pass
        for it in items:
            self._batch_q.put(it)

    def _classify(self, req: GenRequest) -> tuple[bool, list]:
        """(simple, chain_keys): simple = eligible for the batched
        prefill (whole-prompt, no cached prefix to adopt, below the
        sequence-parallel and chunking thresholds, resolvable adapter).
        chain_keys are the prompt's content hashes — taken from
        req.prefix_hashes when the server's tokenizer pool pre-rolled
        them during encode, else computed ONCE here — and reused by
        both paths; only the cheap cache *probe* is redone at adoption
        time (cache state moves within a pass)."""
        n = len(req.prompt)
        if n < 1:
            return False, []
        chain: list = []
        if self.prefix_cache is not None and n > 1:
            ps = self.cfg.page_size
            if (req.prefix_hashes is not None
                    and len(req.prefix_hashes) == n // ps):
                chain = req.prefix_hashes
            else:
                chain = self.prefix_cache.chain_keys(req.prompt)
            hits = len(self._probe_prefix(chain, n)[0])
            if hits > 0:
                return False, chain
            if (self.host_tier is not None and hits < n // ps
                    and self.host_tier.contains(chain[hits])):
                # the chain extends into the host spill tier: the
                # per-request path revives the spilled pages and
                # resumes instead of re-prefilling
                return False, chain
        if ((self._prefill_sp_fn is not None
             or self._prefill_sp_suffix_fn is not None)
                and n >= self.cfg.sp_prefill_min_tokens):
            return False, chain
        chunk = self.cfg.prefill_chunk_tokens
        if (not self.attn.packs_long_prompts
                and chunk > 0 and self.fns.prefill_suffix is not None
                and n > chunk):
            # the ragged backend packs long prompts itself (budget-split
            # calls with decode ticks interleaved), so they stay
            # batch-eligible there
            return False, chain
        if req.adapter and not self._adapter_known(req.adapter):
            return False, chain  # singleton path surfaces the error
        if req.constraint is not None:
            # constrained admissions need the grammar's initial mask in
            # their prefill bias row — the per-request path builds it
            return False, chain
        if req.import_state is not None:
            # migration continuations restore key/count state that only
            # the per-request path knows how to thread into the slot
            return False, chain
        return True, chain

    @engine_thread_only
    def _admit_batch(
        self, reqs: list[GenRequest], chain_by_req: dict[int, list],
    ) -> tuple[int, list[GenRequest] | None]:
        """Allocate + batch-prefill ``reqs`` (all simple). Returns
        (admitted count, leftover): leftover is None without pressure,
        else the unallocated tail for the CALLER to requeue (alongside
        anything else it popped, in arrival order)."""
        from aigw_tpu.tpuserve.adapters import AdapterCapacityError

        prepared: list[tuple[GenRequest, int, int, int]] = []
        leftover: list[GenRequest] | None = None
        for i, req in enumerate(reqs):
            n = len(req.prompt)
            total = min(n + req.max_tokens, self.cfg.max_seq_len)
            seq_id = next(self._seq_ids)
            try:
                self.allocator.allocate(seq_id, total)
            except OutOfPagesError:
                self.allocator.free(seq_id)
                leftover = reqs[i:]
                break
            if req.adapter:
                # pin (and hot-load, when non-resident) the adapter row
                # BEFORE the batched prefill builds its sampling rows;
                # the pin transfers to the slot. All-rows-pinned is the
                # adapter analogue of page pressure: requeue and wait
                # for a generation to finish (classify already vetted
                # the name against the zoo).
                try:
                    self._acquire_adapter(req.adapter)
                except AdapterCapacityError:
                    self.allocator.free(seq_id)
                    leftover = reqs[i:]
                    break
            req.id = seq_id
            prepared.append((req, seq_id, n, total))
        count = 0
        if prepared and self._stateful:
            # results install in item order, each into the first free
            # slot: the k-th item's state goes to the k-th free slot
            free = [i for i, x in enumerate(self._slots)
                    if x is None and i not in self._reserved_slots]
            for item, i in zip(prepared, free):
                self._slot_of_seq[item[1]] = i
        if prepared:
            # the attention backend owns grouping + device calls
            # (bucket groups on xla-bucketed, one token-budget pack on
            # pallas-ragged); the engine owns slots + emission
            results = self.attn.group_prefill(prepared, chain_by_req)
            t_first = time.monotonic()
            for r in results:
                slot_idx = self._free_slot_index()
                assert slot_idx is not None  # len(items) <= free slots
                planned = self._slot_of_seq.pop(r.seq_id, slot_idx)
                assert planned == slot_idx, (planned, slot_idx)
                chain = chain_by_req.get(id(r.req), [])
                if self.prefix_cache is not None and chain:
                    # batched path = classified with no reusable prefix
                    self.stats.prefix_cache_misses += 1
                    self.prefix_cache.insert(
                        chain, self.allocator.pages(r.seq_id),
                        tokens=r.req.prompt)
                    self._purge_spilled(chain)
                self._slots[slot_idx] = _Slot(
                    req=r.req, pos=r.n - 1, generated=0,
                    key_seed=r.req.sampling.seed or r.seq_id,
                    limit=r.total, page_row=r.page_row,
                    adapter_row=r.adapter_row,
                    ctrl=self._make_ctrl(r.req),
                    # metering: the batched path exposes no per-request
                    # padding geometry — charge the real prompt volume
                    # (padding shows up in the aggregate prefill_tokens_*
                    # pair, not the per-request record) and start the
                    # HBM residency clock at the admitted footprint
                    m_prefill_real=r.n, m_prefill_padded=r.n,
                    m_res_t0=time.monotonic(),
                    m_res_bytes=(len(self.allocator.pages(r.seq_id))
                                 * self.kv_page_bytes
                                 + self.stats.state_bytes_per_slot),
                )
                self.stats.prefills += 1
                self._mark_admitted(slot_idx)
                t_m = time.monotonic()
                self._emit_token(slot_idx, r.tok, r.first_lp)
                self.phases.observe(
                    "first_emit", 1e3 * (time.monotonic() - t_m),
                    r.req.trace.trace_id if r.req.trace is not None
                    else "")
            self.stats.first_emit_ms += 1e3 * (
                time.monotonic() - t_first)
            count = len(results)
        return count, leftover

    @engine_thread_only
    def _mark_admitted(self, i: int) -> None:
        """Mark slot i for an incremental row upload into the live
        device state — including its speculation history/lookahead
        rows, so admissions never drain the pipeline. Falls back to a
        full rebuild only when the decode page bucket must grow (new
        compiled shape)."""
        self._dirty_rows.add(i)
        self._spec_dirty.discard(i)  # the full row carries draft_len
        self._cn_dirty.discard(i)  # …and the bias row incl. the mask
        if (self._device_state is not None and not self._need_rebuild
                and self._decode_bucket_pages() > self._state_bucket):
            self._need_rebuild = True

    @engine_thread_only
    def _admit_one(self, req: GenRequest, chain: list | None = None) -> str:
        """Per-request admission (prefix-cache adoption, chunked and
        sequence-parallel prefills, adapter errors). Returns "admitted",
        "skipped" (request consumed without a slot), "stop" (page
        pressure / engine stopping — the CALLER must requeue the request
        and stop admitting), or "stop_consumed" (stop admitting; the
        request needs no requeue). ``chain`` = prompt chain keys already
        hashed by _classify (the probe below stays fresh — an earlier
        admission this pass may have inserted or evicted pages)."""
        slot_idx = self._free_slot_index()
        if slot_idx is None:  # defensive: caller bounds by free slots
            return "stop"
        # the _Slot is not installed until AFTER the prefill, and
        # sp_chunked_prefill re-enters admission (_admit_interactive)
        # at chunk boundaries: reserve the index so a nested admission
        # cannot pick it and get clobbered when this install lands.
        # The finally also covers every abort return below.
        self._reserved_slots.add(slot_idx)
        try:
            return self._admit_one_reserved(req, slot_idx, chain)
        finally:
            self._reserved_slots.discard(slot_idx)
            for sid in [k for k, v in self._slot_of_seq.items()
                        if v == slot_idx]:
                del self._slot_of_seq[sid]
            if self._snap is not None:
                self._end_snapshots(slot_idx)

    @engine_thread_only
    def _admit_one_reserved(self, req: GenRequest, slot_idx: int,
                            chain: list | None) -> str:
        n = len(req.prompt)
        total = min(n + req.max_tokens, self.cfg.max_seq_len)
        seq_id = next(self._seq_ids)
        ps = self.cfg.page_size
        if self._stateful:
            self._slot_of_seq[seq_id] = slot_idx

        # prefix cache: adopt the longest cached page-prefix. A FULL
        # prefix hit (every prompt page cached, prompt page-aligned)
        # adopts everything, copy-on-writes the final page into a
        # private clone, and resumes with a single-token step — the
        # prompt prefill dispatch is skipped entirely; the resume rides
        # the first-token fast path like any prefill's sampled token.
        # Partial hits must leave at least one suffix token to produce
        # first logits, which page-granular hashing gives for free.
        cached_pages: list[int] = []
        chain_keys: list = []
        full_hit = False
        unrestorable = 0
        if self.prefix_cache is not None and n > 1:
            chain_keys = (chain if chain is not None
                          else self.prefix_cache.chain_keys(req.prompt))
            if self.host_tier is not None:
                # KV hierarchy revive (ISSUE 11): promote any spilled
                # run extending the resident prefix back into the pool
                # BEFORE the probe — the adoption below then sees the
                # revived pages as ordinary cached prefix
                self._revive_chain(chain_keys)
            # (a family with snapshots: the pages a snapshot lets it
            # resume behind — never the whole prompt — and the tokens
            # cached beyond them that prefill again)
            cached_pages, unrestorable = self._probe_prefix(chain_keys, n)
            hits = len(cached_pages)
            full_hit = hits > 0 and hits * ps == n
        prefix_len = len(cached_pages) * ps
        if full_hit:
            # re-run only the last prompt token: its forward pass
            # yields the first-token logits; its (bit-recomputed) K/V
            # lands in the CoW'd private page, never the shared one
            prefix_len = n - 1

        try:
            if cached_pages:
                self.allocator.adopt(seq_id, cached_pages)
                extra = self.allocator.pages_for(total) - len(cached_pages)
                if extra > 0:
                    self.allocator.allocate_extra(seq_id, extra)
                if full_hit:
                    shared_last = cached_pages[-1]
                    fresh = self.allocator.cow_page(seq_id, shared_last)
                    self._copy_page_dev(shared_last, fresh)
                    self.stats.prefix_full_hits += 1
                    self.stats.prefix_cow_copies += 1
            else:
                self.allocator.allocate(seq_id, total)
        except OutOfPagesError:
            self.allocator.free(seq_id)
            # the caller puts it back (in arrival order) to wait for
            # a slot to free pages
            return "stop"
        if self._spec_max:
            # direct speculative-safety invariant (replaces the old
            # repin-on-rebuild guard): no page overlapping the slot's
            # writable tail [n, limit) may be shared — draft K/V
            # (including rejected drafts') scatters there. Healthy
            # layouts pass by construction; a violation is CoW-repaired
            # and logged, never silently corrupted.
            trunc = getattr(self.allocator, "truncate_to", None)
            if trunc is not None:
                for old_pg, new_pg, needs_copy in trunc(seq_id, n):
                    logger.warning(
                        "speculative admission CoW'd shared tail page "
                        "%d->%d for seq %d", old_pg, new_pg, seq_id)
                    if needs_copy:
                        self._copy_page_dev(old_pg, new_pg)
                        self.stats.prefix_cow_copies += 1
        pages = self.allocator.pages(seq_id)
        req.id = seq_id

        qw = 1e3 * (time.monotonic() - req.enqueued_at)
        self.phases.observe(
            "queue_wait", qw,
            req.trace.trace_id if req.trace is not None else "")
        if req.trace is not None:
            burst_id, burst_size = self._cur_burst
            req.trace.queue_wait(qw)
            req.trace.admission(
                path="single", burst_id=burst_id, burst_size=burst_size,
                prefix=("off" if not chain_keys
                        else "full" if full_hit
                        else "partial" if cached_pages else "miss"),
                pages_adopted=len(cached_pages),
                prefix_tokens=prefix_len)

        suffix = req.prompt[prefix_len:]
        ns = len(suffix)
        # sp routing: the chunked path (ring-attention chunk steps with
        # offset resume + decode interleaving) takes every long suffix;
        # the monolithic full-rung program remains only for geometries
        # the chunked program can't shard (page_size % sp != 0) or when
        # sp_prefill_mode="monolithic" — and it still can't resume, so
        # prefix hits there fall through to the single-device loop.
        use_sp_chunked = (
            self._prefill_sp_suffix_fn is not None
            and ns >= self.cfg.sp_prefill_min_tokens
        )
        use_sp = (
            not use_sp_chunked
            and self._prefill_sp_fn is not None
            and prefix_len == 0
            and ns >= self.cfg.sp_prefill_min_tokens
        )
        pt = np.zeros((1, self.cfg.max_pages_per_seq), np.int32)
        pt[0, : len(pages)] = pages

        adapter_row = self._base_row
        if req.adapter:
            from aigw_tpu.tpuserve.adapters import (
                AdapterCapacityError,
                UnknownAdapterError,
            )

            try:
                # pins (and hot-loads, when non-resident) the row; the
                # pin transfers to the slot below and is released when
                # the slot frees
                adapter_row = self._acquire_adapter(req.adapter)
            except UnknownAdapterError:
                self._meter_zero(req, "error")
                req.emit(-1, "error")
                self.allocator.free(seq_id)
                return "skipped"
            except AdapterCapacityError:
                # every row pinned by live slots: wait like page
                # pressure (caller requeues in arrival order)
                self.allocator.free(seq_id)
                return "stop"
        # migration continuation (ISSUE 8): resume with the sampling-key
        # state the solo run would have at this position — the prefill's
        # sampled token must be the exact token the exporting replica
        # would have decoded next (key counter m-1 = the position of the
        # pending input token at the cut)
        ims = req.import_state or {}
        key_seed = int(ims.get("key_seed") or
                       (req.sampling.seed or seq_id))
        key_counter = int(ims.get("key_counter", 0))
        key = np.array([[key_seed & 0xFFFFFFFF, key_counter]], np.uint32)
        # grammar constraint (ISSUE 9): the slot's FSM cursor; its
        # initial-state token mask composes into the prefill bias row so
        # the FIRST sampled token is already grammar-valid
        cn = None
        if req.constraint is not None:
            cn = req.constraint.new_state()
        bias_row = np.zeros((1, self.model_cfg.vocab_size), np.float32)
        for tok_id, b in req.sampling.logit_bias:
            if 0 <= tok_id < self.model_cfg.vocab_size:
                bias_row[0, tok_id] = b
        if cn is not None:
            bias_row[0] += cn.mask_row()
        sampling_args = (
            jnp.asarray(key),
            jnp.asarray([req.sampling.temperature], jnp.float32),
            jnp.asarray([req.sampling.top_p], jnp.float32),
            jnp.asarray([req.sampling.top_k], jnp.int32),
            jnp.asarray(bias_row),
            jnp.asarray([adapter_row], jnp.int32),
        )
        t0 = time.monotonic()
        if self._snap is not None:
            # the slot's state rows become the cached prefix's; the
            # chunk loop's boundaries save this prompt's own
            self._begin_snapshots(slot_idx, chain_keys, len(cached_pages))
        # pow2 page bucket covering the sequence — the gather window
        # of suffix/chunked steps, not the full max_seq_len window
        need = self.allocator.pages_for(total)
        bucket = 1
        while bucket < need:
            bucket *= 2
        bucket = min(bucket, self.cfg.max_pages_per_seq)
        # host building + dispatching the prefill call(s); the decode
        # ticks a chunked prompt interleaves suspend it (self time)
        loop = self.stats.loop
        ns0 = loop.prefill_ns()
        outer = loop.enter(
            PREFILL_DISPATCH,
            {"tokens": ns, "pages": bucket} if loop.capture else None)

        if use_sp_chunked:
            # sequence-sharded chunked prefill: ring-attention chunk
            # steps resuming at the cached page-aligned offset, decode
            # ticks at the boundaries — the long-context path
            # (tpuserve/attention.sp_chunked_prefill)
            from aigw_tpu.tpuserve.attention import sp_chunked_prefill

            res = sp_chunked_prefill(
                self, req, seq_id, suffix, prefix_len, n, pt, bucket,
                sampling_args)
            if isinstance(res, str):
                loop.resume(outer)
                self._release_adapter_row(adapter_row)
                self.allocator.free(seq_id)
                return res
            next_tok, info = res
            self.stats.sp_prefills += 1
            self.stats.sp_chunked_prefills += 1
            if prefix_len:
                self.stats.sp_resume_prefills += 1
        elif use_sp:
            # ring attention shards the padded length over sp — the
            # divisibility guard rounds the chosen rung up to a
            # multiple of sp (non-power-of-two sp like 6 must not
            # silently disable the path, and intermediate rungs stay)
            S = self._prefill_bucket(ns, multiple_of=self._sp)
            tokens = np.zeros((1, S), np.int32)
            tokens[0, :ns] = suffix
            self.stats.sp_prefills += 1
            next_tok, self.kv_cache, moe = self._prefill_sp_fn(
                self.params,
                self.lora_params,
                jnp.asarray(tokens),
                jnp.asarray([n], jnp.int32),
                self.kv_cache,
                jnp.asarray(pt),
                *sampling_args,
            )
            loop.enter(PREFILL_BLOCK)  # dispatched: the host waits
            self._fold_moe(moe)
            self.stats.prefill_tokens_real += ns
            self.stats.prefill_tokens_padded += S
            self.stats.prefill_calls += 1
            info = {"consumed": 0, "bucket": S, "chunks": 0,
                    "padded_frac": round(1.0 - ns / S, 3) if S else 0.0}
        else:
            # the attention backend runs the prompt: bucketed chunk
            # loop + padded tail on xla-bucketed, token-budget packed
            # calls on pallas-ragged — both resume at prefix_len and
            # interleave decode ticks at their boundaries
            res = self.attn.single_prefill(
                req, seq_id, suffix, prefix_len, n, total, pt, bucket,
                sampling_args)
            if isinstance(res, str):
                # cancelled / engine stopping mid-prompt: hand it back
                # like an OutOfPages retry ("stop") or consume it —
                # the adapter pin never made it to a slot
                loop.resume(outer)
                self._release_adapter_row(adapter_row)
                self.allocator.free(seq_id)
                return res
            next_tok, info = res
        eff_prefix = prefix_len + info["consumed"]

        if prefix_len:
            self.stats.prefix_cache_hits += 1
            self.stats.prefix_tokens_reused += prefix_len
        elif chain_keys:
            # page-eligible prompt, nothing reusable cached
            self.stats.prefix_cache_misses += 1
        self.stats.prefix_tokens_unrestorable += unrestorable
        # start token 0's host copy under the prefill's compute
        self._start_host_copy(next_tok)
        # (every branch above left the ledger in prefill_block: host
        # blocked on the sampled token)
        first_lp = None
        if self.cfg.logprobs_topk and isinstance(next_tok, tuple):
            next_tok, chosen, tk_ids, tk_vals = next_tok
            first_lp = (
                float(np.asarray(chosen)[0]),
                [(int(t), float(v)) for t, v in zip(
                    np.asarray(tk_ids)[0], np.asarray(tk_vals)[0])],
            )
        tok = int(next_tok[0])
        loop.resume(outer)
        self.stats.prefills += 1
        # this request's share of the ledger's two prefill phases
        prefill_ms = (loop.prefill_ns() - ns0) / 1e6
        self.stats.note_prefill_call(prefill_ms, ns)
        self.phases.observe(
            "prefill", prefill_ms,
            req.trace.trace_id if req.trace is not None else "")
        if req.trace is not None:
            req.trace.prefill(
                prefill_ms, bucket=info["bucket"],
                padded_frac=info["padded_frac"],
                chunks=info["chunks"],
                resumed_at=eff_prefix, sp=use_sp or use_sp_chunked)
        t_first = time.monotonic()
        if self.prefix_cache is not None and chain_keys:
            self.prefix_cache.insert(chain_keys, pages,
                                     tokens=req.prompt)
            self._purge_spilled(chain_keys)
        logger.debug("prefill seq=%d len=%d prefix=%d bucket=%d %.1fms",
                     seq_id, n, prefix_len, info["bucket"],
                     1e3 * (time.monotonic() - t0))

        # speculative draft sources for the new slot: the adaptive
        # controller, plus — when the radix chain remembers what
        # followed this prefix last time — one page of continuation
        # tokens as the lookahead draft buffer (repeated chat traffic's
        # free high-acceptance source)
        ctrl = self._make_ctrl(req)
        la_base = 0
        la_tokens: list[int] = []
        if (ctrl is not None and self.prefix_cache is not None
                and chain_keys):
            cont = self.prefix_cache.continuation(chain_keys)
            if cont is not None and cont[0] * ps + len(cont[1]) > n:
                la_base = cont[0] * ps
                la_tokens = cont[1]
                self.stats.spec_lookahead_slots += 1

        # migration continuation: generated-so-far tokens ride in the
        # prompt tail — they must keep counting toward the repetition
        # penalties exactly as they did on the exporting replica
        counts: dict[int, int] = {}
        for t in req.prompt[int(ims.get("orig_prompt_len", n)):]:
            counts[t] = counts.get(t, 0) + 1
        # usage metering (ISSUE 20): prefill attribution + the HBM
        # residency clock. Padded volume is geometry-derived from the
        # backend's padded_frac (= 1 - real/processed), so all three
        # prefill paths report through one formula.
        pf = float(info.get("padded_frac") or 0.0)
        m_padded = int(round(ns / (1.0 - pf))) if 0.0 < pf < 1.0 else ns
        # pos=n-1: _emit_token advances it to n, the write position of
        # the just-sampled first token.
        self._slots[slot_idx] = _Slot(
            req=req, pos=n - 1, generated=0,
            key_seed=key_seed,
            limit=total, page_row=pt[0], adapter_row=adapter_row,
            token_counts=counts,
            ctrl=ctrl, la_base=la_base, la_tokens=la_tokens,
            cn=cn,
            m_prefill_real=ns, m_prefill_padded=m_padded,
            m_prefix_reused=prefix_len,
            m_res_t0=time.monotonic(),
            m_res_bytes=(len(pages) * self.kv_page_bytes
                         + self.stats.state_bytes_per_slot),
            m_carry=ims.get("meter_carry"),
        )
        self._mark_admitted(slot_idx)
        if cn is not None:
            # counted at ADMISSION (not FSM creation): a page-pressure
            # requeue must not double-count the request
            self.stats.constraint_requests += 1
            # the prefill's sampled token is mask-guaranteed valid;
            # advance the FSM so the first decode window dispatches
            # with the POST-first-token mask (marked dirty by the full
            # row upload _mark_admitted scheduled)
            cn.advance(tok)
        self._emit_token(slot_idx, tok, first_lp)
        first_emit_ms = 1e3 * (time.monotonic() - t_first)
        self.stats.first_emit_ms += first_emit_ms
        self.phases.observe(
            "first_emit", first_emit_ms,
            req.trace.trace_id if req.trace is not None else "")
        return "admitted"

    def _requeue_front_many(self, reqs: list[GenRequest]) -> None:
        # queue.Queue has no push-front; use a tiny shim list
        items = list(reqs)
        if not items:
            return
        try:
            while True:
                items.append(self._queue.get_nowait())
        except queue.Empty:
            pass
        for it in items:
            self._queue.put(it)

    def _decode_bucket_pages(self) -> int:
        """Smallest power-of-two page count covering every active slot's
        allocation — the decode gather window shrinks to what the batch
        actually needs (short sequences don't pay max_seq_len attention).
        jax.jit compiles one program per bucket shape."""
        P = self.cfg.max_pages_per_seq
        need = 1
        for s in self._slots:
            if s is not None:
                need = max(need, -(-s.limit // self.cfg.page_size))
        bucket = 1
        while bucket < need:
            bucket *= 2
        return min(bucket, P)

    def _build_device_state(
            self, bucket: int | None = None) -> dict[str, jax.Array]:
        """Upload the FULL per-slot state (first build, page-bucket
        growth, speculation). Ordinary membership changes go through
        the incremental row update in _apply_row_updates instead.
        ``bucket`` pins the page-table width (warmup pre-compiling the
        ladder at buckets traffic hasn't reached yet).

        PURE builder — it must not publish anything through self:
        warmup() calls it from the server thread while the engine loop
        is live, and a side-effecting write here (this method used to
        set self._state_bucket) raced _mark_admitted's bucket-growth
        check into skipping a rebuild the live batch needed. The
        engine-thread caller in _decode_tick records the bucket."""
        B = self.cfg.max_batch_size
        P = bucket if bucket is not None else self._decode_bucket_pages()
        tokens = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        limits = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        page_table = np.zeros((B, P), np.int32)
        keys = np.zeros((B, 2), np.uint32)
        temp = np.ones((B,), np.float32)
        top_p = np.ones((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        freq_pen = np.zeros((B,), np.float32)
        pres_pen = np.zeros((B,), np.float32)
        V = self.model_cfg.vocab_size
        counts = np.zeros((B, V), np.int32)
        bias = np.zeros((B, V), np.float32)
        adapter_idx = np.full((B,), self._base_row, np.int32)
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            tokens[i] = s.pending_token
            positions[i] = s.pos
            limits[i] = s.limit
            active[i] = True
            page_table[i] = s.page_row[:P]
            keys[i, 0] = np.uint32(s.key_seed & 0xFFFFFFFF)
            keys[i, 1] = np.uint32(s.pos)
            temp[i] = s.req.sampling.temperature
            top_p[i] = s.req.sampling.top_p
            top_k[i] = s.req.sampling.top_k
            freq_pen[i] = s.req.sampling.frequency_penalty
            pres_pen[i] = s.req.sampling.presence_penalty
            for tok_id, cnt in s.token_counts.items():
                if 0 <= tok_id < V:
                    counts[i, tok_id] = cnt
            for tok_id, b in s.req.sampling.logit_bias:
                if 0 <= tok_id < V:
                    bias[i, tok_id] = b
            if s.cn is not None:
                bias[i] += s.cn.mask_row()
            adapter_idx[i] = s.adapter_row
        state_extra: dict[str, jax.Array] = {}
        if self._spec_max:
            # speculation rows: token history (prompt + generated,
            # valid through the pending token's position), the per-slot
            # adaptive draft length, and the prefix-cache continuation
            # lookahead. The row update uploads the same fields
            # per-slot, so admissions never force this full build.
            L = self.cfg.page_size
            history = np.zeros((B, self.cfg.max_seq_len), np.int32)
            draft_len = np.zeros((B,), np.int32)
            lookahead = np.zeros((B, L), np.int32)
            la_base = np.zeros((B,), np.int32)
            la_len = np.zeros((B,), np.int32)
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                pr = s.req.prompt
                history[i, : len(pr)] = pr
                history[i, len(pr): len(pr) + len(s.gen_tokens)] = (
                    s.gen_tokens
                )
                if s.ctrl is not None:
                    draft_len[i] = s.ctrl.draft_len()
                    s.dev_draft_len = int(draft_len[i])
                if s.la_tokens:
                    lookahead[i, : len(s.la_tokens)] = s.la_tokens
                    la_base[i] = s.la_base
                    la_len[i] = len(s.la_tokens)
            state_extra["history"] = jnp.asarray(history)
            state_extra["draft_len"] = jnp.asarray(draft_len)
            state_extra["lookahead"] = jnp.asarray(lookahead)
            state_extra["la_base"] = jnp.asarray(la_base)
            state_extra["la_len"] = jnp.asarray(la_len)
        state = state_extra | {
            "tokens": jnp.asarray(tokens),
            "positions": jnp.asarray(positions),
            "limits": jnp.asarray(limits),
            "active": jnp.asarray(active),
            "page_table": jnp.asarray(page_table),
            "keys": jnp.asarray(keys),
            "temp": jnp.asarray(temp),
            "top_p": jnp.asarray(top_p),
            "top_k": jnp.asarray(top_k),
            "freq_pen": jnp.asarray(freq_pen),
            "pres_pen": jnp.asarray(pres_pen),
            "counts": jnp.asarray(counts),
            "bias": jnp.asarray(bias),
            "adapter_idx": jnp.asarray(adapter_idx),
        }
        if self._state_sharding is not None:
            # canonical placement: fresh builds and program outputs
            # (pinned by _pin_state) share ONE layout, so a dispatch is
            # never a layout-only jit-cache miss on the mesh
            state = jax.device_put(state, self._state_sharding)
        return state

    def _row_host_values(self, i: int, P: int) -> dict[str, np.ndarray]:
        """Host-side row i of the device state (cleared when the slot is
        empty). Shapes/dtypes mirror _build_device_state exactly."""
        V = self.model_cfg.vocab_size
        s = self._slots[i]
        row = {
            "tokens": np.int32(0),
            "positions": np.int32(0),
            "limits": np.int32(0),
            "active": np.bool_(False),
            "page_table": np.zeros((P,), np.int32),
            "keys": np.zeros((2,), np.uint32),
            "temp": np.float32(1.0),
            "top_p": np.float32(1.0),
            "top_k": np.int32(0),
            "freq_pen": np.float32(0.0),
            "pres_pen": np.float32(0.0),
            "counts": np.zeros((V,), np.int32),
            "bias": np.zeros((V,), np.float32),
            "adapter_idx": np.int32(self._base_row),
        }
        if self._spec_max:
            L = self.cfg.page_size
            row["history"] = np.zeros((self.cfg.max_seq_len,), np.int32)
            row["draft_len"] = np.int32(0)
            row["lookahead"] = np.zeros((L,), np.int32)
            row["la_base"] = np.int32(0)
            row["la_len"] = np.int32(0)
        if s is None:
            return row
        row["tokens"] = np.int32(s.pending_token)
        row["positions"] = np.int32(s.pos)
        row["limits"] = np.int32(s.limit)
        row["active"] = np.bool_(True)
        row["page_table"] = np.asarray(s.page_row[:P], np.int32)
        row["keys"] = np.array(
            [s.key_seed & 0xFFFFFFFF, s.pos], np.uint32)
        row["temp"] = np.float32(s.req.sampling.temperature)
        row["top_p"] = np.float32(s.req.sampling.top_p)
        row["top_k"] = np.int32(s.req.sampling.top_k)
        row["freq_pen"] = np.float32(s.req.sampling.frequency_penalty)
        row["pres_pen"] = np.float32(s.req.sampling.presence_penalty)
        for tok_id, cnt in s.token_counts.items():
            if 0 <= tok_id < V:
                row["counts"][tok_id] = cnt
        for tok_id, b in s.req.sampling.logit_bias:
            if 0 <= tok_id < V:
                row["bias"][tok_id] = b
        if s.cn is not None:
            row["bias"] += s.cn.mask_row()
        row["adapter_idx"] = np.int32(s.adapter_row)
        if self._spec_max:
            pr = s.req.prompt
            row["history"][: len(pr)] = pr
            row["history"][len(pr): len(pr) + len(s.gen_tokens)] = (
                s.gen_tokens)
            if s.ctrl is not None:
                row["draft_len"] = np.int32(s.ctrl.draft_len())
                s.dev_draft_len = int(row["draft_len"])
            if s.la_tokens:
                row["lookahead"][: len(s.la_tokens)] = s.la_tokens
                row["la_base"] = np.int32(s.la_base)
                row["la_len"] = np.int32(len(s.la_tokens))
        return row

    def _row_update_fn_built(self):
        if self._row_update_fn is None:
            def _upd(state, i, row):
                return self._pin_state({
                    k: (state[k].at[i].set(row[k]) if k in row
                        else state[k])
                    for k in state
                })

            self._row_update_fn = self.compile_tracker.register(
                "row_update", jax.jit(_upd, donate_argnums=(0,)))
        return self._row_update_fn

    @engine_thread_only
    def _apply_row_updates(self) -> None:
        """Scatter dirty slot rows into the LIVE device state — no
        pipeline drain, no full re-upload. JAX chains the update after
        the in-flight window's scan, so admission/finish no longer
        stalls the decode pipeline for a whole window."""
        self._row_update_fn_built()
        P = self._state_bucket
        loop = self.stats.loop
        outer = loop.enter(
            ROW_UPDATE,
            {"pages": P, "rows": len(self._dirty_rows)}
            if loop.capture else None)
        for i in sorted(self._dirty_rows):
            self._device_state = self._row_update_fn(
                self._device_state, np.int32(i),
                self._row_host_values(i, P))
        self._dirty_rows.clear()
        loop.resume(outer)

    def _spec_update_fn_built(self):
        if self._spec_update_fn is None:
            def _sup(state, i, d):
                return self._pin_state(dict(
                    state, draft_len=state["draft_len"].at[i].set(d)))

            self._spec_update_fn = self.compile_tracker.register(
                "spec_row_update", jax.jit(_sup, donate_argnums=(0,)))
        return self._spec_update_fn

    @engine_thread_only
    def _apply_spec_row_updates(self) -> None:
        """Patch live slots' on-device ``draft_len`` after an adaptive
        rung move. Unlike the full row update this touches ONLY the
        draft length — a live slot's positions/history on device run
        ahead of the host's view while a window is in flight, so
        re-uploading its full row mid-pipeline would rewind it, but
        the draft length is position-independent and safe to patch at
        any time."""
        self._spec_update_fn_built()
        outer = self.stats.loop.enter(ROW_UPDATE)
        for i in sorted(self._spec_dirty):
            s = self._slots[i]
            d = (s.ctrl.draft_len()
                 if s is not None and s.ctrl is not None else 0)
            self._device_state = self._spec_update_fn(
                self._device_state, np.int32(i), np.int32(d))
            if s is not None:
                s.dev_draft_len = d
        self._spec_dirty.clear()
        self.stats.loop.resume(outer)

    def _cn_bias_row(self, s: _Slot) -> np.ndarray:
        """Host-side bias row of a constrained slot: the request's
        logit_bias plus the FSM state's token mask."""
        V = self.model_cfg.vocab_size
        row = np.zeros((V,), np.float32)
        for tok_id, b in s.req.sampling.logit_bias:
            if 0 <= tok_id < V:
                row[tok_id] = b
        row += s.cn.mask_row()
        return row

    def _cn_update_fn_built(self):
        if self._cn_update_fn is None:
            def _bup(state, i, row):
                return self._pin_state(dict(
                    state, bias=state["bias"].at[i].set(row)))

            self._cn_update_fn = self.compile_tracker.register(
                "cn_mask_update", jax.jit(_bup, donate_argnums=(0,)))
        return self._cn_update_fn

    @engine_thread_only
    def _apply_cn_row_updates(self) -> None:
        """Patch live constrained slots' on-device bias rows after an
        FSM advance. Like the draft_len patch, the bias row is
        position-independent — safe to scatter mid-pipeline; a full row
        upload (_apply_row_updates) already carries the mask, so rows
        in _dirty_rows are skipped here."""
        fn = self._cn_update_fn_built()
        outer = self.stats.loop.enter(ROW_UPDATE)
        for i in sorted(self._cn_dirty):
            s = self._slots[i]
            if s is None or s.cn is None or i in self._dirty_rows:
                continue
            self._device_state = fn(
                self._device_state, np.int32(i), self._cn_bias_row(s))
            self.stats.constraint_mask_updates += 1
        self._cn_dirty.clear()
        self.stats.loop.resume(outer)

    @engine_thread_only
    def _cn_verify(self, i: int, s: _Slot, tok: int,
                   dispatch_mask) -> bool:
        """Verify + advance slot i's constraint FSM with ``tok``, which
        the window sampled under ``dispatch_mask``. True = emit.

        Acceptance rule: a token counts only while the slot's CURRENT
        FSM state demands exactly the mask the window was dispatched
        with — then the on-device sample was drawn from precisely the
        distribution a per-step-masked decode would have used (same
        bias row, same per-position key), so accepted streams are
        bit-identical to true single-step constrained decoding. The
        moment the FSM advance changes the mask, the window is cut and
        the slot ROLLED BACK to its last accepted token, exactly as a
        rejected speculative draft: the host state never advanced, so
        re-uploading the row (position / key / counts / history / mask)
        restores the device to the cut point, and the epoch bump makes
        the drain of the one window already in flight discard this
        slot's tokens. Stale KV past the cut is rewritten by subsequent
        decode steps — the spec-decode rejection discipline."""
        cur = s.cn.mask_row()
        if cur is not dispatch_mask and not np.array_equal(
                cur, dispatch_mask):
            self._cn_rollback(i, s)
            return False
        if s.cn.advance(tok):
            if tok not in self.eos:
                self._cn_dirty.add(i)
            return True
        # defensive: a mask-allowed token must be grammar-valid; treat
        # any disagreement as a cut rather than corrupting the stream
        self._cn_rollback(i, s)
        return False

    @engine_thread_only
    def _cn_rollback(self, i: int, s: _Slot) -> None:
        s.cn_epoch += 1
        self._dirty_rows.add(i)
        self._cn_dirty.discard(i)
        self.stats.constraint_rollbacks += 1
        if s.req.trace is not None:
            s.req.trace.constraint_rollback()

    def _make_ctrl(self, req: GenRequest):
        """Adaptive draft controller for a fresh slot — or None when
        the request is ineligible (sampling / penalties: those slots
        fall back to plain decode and never lift the dispatch width)."""
        sp = req.sampling
        if (not self._spec_max or sp.temperature > 0.0
                or sp.frequency_penalty != 0.0
                or sp.presence_penalty != 0.0):
            return None
        return speculation.DraftController(
            self._spec_rungs, self._accept_prior, self.cfg.spec_adaptive)

    @engine_thread_only
    def _choose_draft_len(self) -> int:
        """Dispatch draft width: the max of the active eligible slots'
        adaptive rungs. 0 dispatches the PLAIN decode program —
        default-on speculation costs nothing once every ladder has
        collapsed. Ticking the controllers here also runs the rung-0
        re-probe policy; any rung move is patched on device before the
        dispatch that follows."""
        if not self._spec_max:
            return 0
        d = 0
        for i, s in enumerate(self._slots):
            if s is None or s.ctrl is None:
                continue
            before = s.ctrl.draft_len()
            nd = s.ctrl.tick()
            if nd > before:
                self.stats.spec_rung_ups += 1  # rung-0 re-probe
            if nd != s.dev_draft_len and i not in self._dirty_rows:
                self._spec_dirty.add(i)
            d = max(d, nd)
        self.stats.spec_draft_len = d
        return d

    @engine_thread_only
    def _process_window(self, toks: np.ndarray, lp,
                        members: tuple,
                        cn_epochs: dict | None = None) -> None:
        """Distribute one decode window's host-side tokens. Only slots
        that were members of the window at DISPATCH time (and still hold
        the same request) receive tokens — rows admitted after dispatch
        carry junk samples for this window and are skipped; a
        constrained slot whose rollback epoch moved past the window's
        captured epoch is skipped the same way (the window computed
        past a grammar violation)."""
        K = toks.shape[0]
        ce = cn_epochs or {}
        self.stats.decode_steps += K
        for k in range(K):
            for i, req in members:
                s = self._slots[i]
                if s is None or s.req is not req:
                    continue  # finished earlier in this window / re-used
                if s.cn is not None:
                    ent = ce.get(i)
                    if ent is None or ent[0] != s.cn_epoch:
                        continue  # stale window for a rolled-back slot
                    if not self._cn_verify(i, s, int(toks[k, i]),
                                           ent[1]):
                        continue  # mask boundary: rolled back here
                step_lp = None
                if lp is not None:
                    chosen, tk_ids, tk_vals = lp
                    step_lp = (
                        float(chosen[k, i]),
                        [(int(t), float(v))
                         for t, v in zip(tk_ids[k, i], tk_vals[k, i])],
                    )
                self._emit_token(i, int(toks[k, i]), step_lp)

    @engine_thread_only
    def _process_spec_window(self, toks: np.ndarray, counts: np.ndarray,
                             props: np.ndarray, members: tuple,
                             draft_lens: tuple = (),
                             cn_epochs: dict | None = None) -> None:
        """Speculative window: sampled [K, B, D+1], n_emit [K, B],
        n_prop [K, B] — the leading n_emit tokens of each row are
        model-exact; the rest are conditioned on rejected drafts and
        discarded. Afterwards each surviving slot's adaptive controller
        observes the window's proposed/accepted counts and may move its
        rung (patched on device by the draft_len-only row update before
        the next dispatch)."""
        K = toks.shape[0]
        ce = cn_epochs or {}
        self.stats.decode_steps += K
        dl = dict(draft_lens)
        proposed = dict.fromkeys(dl, 0)
        accepted = dict.fromkeys(dl, 0)
        live = dict.fromkeys(dl, False)
        for k in range(K):
            for i, req in members:
                s = self._slots[i]
                if s is None or s.req is not req:
                    continue
                if s.cn is not None:
                    ent = ce.get(i)
                    if ent is None or ent[0] != s.cn_epoch:
                        continue  # stale window for a rolled-back slot
                n = int(counts[k, i])
                if n > 0:
                    proposed[i] = proposed.get(i, 0) + int(props[k, i])
                    live[i] = True
                    # meter attribution BEFORE the emit loop: a slot
                    # that finishes mid-step carries this step's drafts
                    # in its terminal record
                    s.m_spec_drafted += int(props[k, i])
                emitted = 0
                for d in range(n):
                    cur = self._slots[i]
                    if cur is None or cur.req is not req:
                        break  # EOS/stop consumed the slot mid-burst
                    if cur.cn is not None and not self._cn_verify(
                            i, cur, int(toks[k, i, d]), ce[i][1]):
                        break  # mask boundary: rolled back here
                    if emitted > 0:
                        # every token past the first is a landed draft;
                        # credited before its emit so a finish on the
                        # accepted token itself still meters it
                        cur.m_spec_accepted += 1
                    self._emit_token(i, int(toks[k, i, d]))
                    emitted += 1
                if emitted > 1:
                    self.stats.spec_accepted += emitted - 1
                    accepted[i] = accepted.get(i, 0) + emitted - 1
        for i, req in members:
            # only slots that decoded under a nonzero draft width this
            # window carry a controller signal
            if not live.get(i, False) or dl.get(i, 0) <= 0:
                continue
            self.stats.spec_drafted += proposed.get(i, 0)
            if req.trace is not None:
                req.trace.spec_window(proposed.get(i, 0),
                                      accepted.get(i, 0))
            s = self._slots[i]
            if s is None or s.req is not req or s.ctrl is None:
                continue
            move = s.ctrl.observe_window(proposed.get(i, 0),
                                         accepted.get(i, 0))
            if move:
                if move > 0:
                    self.stats.spec_rung_ups += 1
                else:
                    self.stats.spec_rung_downs += 1
                if i not in self._dirty_rows:
                    self._spec_dirty.add(i)

    @engine_thread_only
    def _drain_inflight(self) -> None:
        """Settle the in-flight window: resolve its (already started)
        device→host copy, emit tokens, and apply
        the page frees it was carrying."""
        w, self._inflight = self._inflight, None
        if w is None:
            return
        loop = self.stats.loop
        ns0 = loop.ns[WINDOW_FETCH]
        outer = loop.enter(
            WINDOW_FETCH,
            {"k": w.k, "slots": len(w.members)} if loop.capture else None)
        host = jax.tree_util.tree_map(np.asarray, w.sampled)
        loop.enter(EMIT)
        tr_ms = (loop.ns[WINDOW_FETCH] - ns0) / 1e6
        ex = ""
        for _i, _req in w.members:
            if _req.trace is not None:
                _req.trace.transfer(tr_ms)
                ex = ex or _req.trace.trace_id
        self.phases.observe("transfer", tr_ms, ex)
        ce = ({i: (ep, m) for i, ep, m in w.cn_epochs}
              if w.cn_epochs else None)
        if w.sorts:
            self.stats.sample_sort_steps += w.k
        if w.draft:
            self._process_spec_window(host[0], host[1], host[2],
                                      w.members, w.draft_lens, ce)
        elif isinstance(host, tuple):  # logprobs window
            toks, chosen, tk_ids, tk_vals = host
            self._process_window(toks, (chosen, tk_ids, tk_vals),
                                 w.members, ce)
        else:
            self._process_window(host, None, w.members, ce)
        loop.resume(outer)
        # the window's routing-stats leaf settles with the window — a
        # dispatch-time read would sync against the running program
        self._fold_moe(w.moe, decode=True)
        # (_kv_pages: pages read and live, then a dense stateful
        # family's state rows read and live)
        read, live, *state = np.asarray(w.kv_pages, np.int64)
        self.stats.decode_kv_pages_read += int(read)
        self.stats.decode_kv_pages_live += int(live)
        if state:
            self.stats.decode_state_rows_read += int(state[0])
            self.stats.decode_state_rows_live += int(state[1])
        for seq_id in w.frees:
            self.allocator.free(seq_id)

    @engine_thread_only
    def _fold_moe(self, moe, decode: bool = False) -> None:
        """Fold one program's [L, width] routing-stats leaf (per-expert
        placed counts + capacity drops per layer; a family that holds a
        share of its experts adds every assignment routed and the held
        experts hit, then either the family's own columns,
        ``tape_extra``, or in the hybrid's decode window the slots whose
        state its loops read and the live rows) into the numpy
        accumulators behind
        the /state MoE surface. ``decode``: the leaf is a decode
        window's. No-op (None) on dense families — call sites stay
        uniform."""
        if moe is None:
            return
        arr = np.asarray(moe, np.int64)
        E = self._moe_experts
        self._moe_expert_tokens += arr[:, :E].sum(axis=0)
        self._moe_layer_drops += arr[:, E]
        st = self.stats
        if arr.shape[1] > E + 1:
            st.moe_local_assignments += int(arr[:, :E].sum())
            st.moe_total_assignments += int(arr[:, E + 1].sum())
            if decode:
                st.moe_held_hits_decode += int(arr[:, E + 2].sum())
        if self._tape_extra:
            for name, col in zip(self._tape_extra,
                                 arr[:, E + 3:].sum(axis=0)):
                setattr(st, name, getattr(st, name) + int(col))
        elif arr.shape[1] > E + 3:
            # a decode window's two state columns: every DeltaNet
            # layer's loop ran the step's one trip count, so the
            # largest row is a layer's (the others hold 0)
            st.decode_state_rows_read += int(arr[:, E + 3].max())
            st.decode_state_rows_live += int(arr[:, E + 4].max())

    def moe_expert_load(self) -> list[int]:
        """Per-expert placed-token totals [E] for /state and the
        labeled /metrics twins; [] on dense families. Read-only
        snapshot — safe off the engine thread (int64 element reads are
        GIL-atomic; a torn read is one fold stale, like every gauge)."""
        if not self._moe:
            return []
        return [int(x) for x in self._moe_expert_tokens]

    def moe_layer_drops(self) -> list[int]:
        """Per-layer capacity-drop totals [L]; [] on dense families."""
        if not self._moe:
            return []
        return [int(x) for x in self._moe_layer_drops]

    @engine_thread_only
    def _apply_frees(self) -> None:
        """Recycle pages of finished sequences. Only safe with NO window
        in flight (callers drain first): an in-flight window dispatched
        while the sequence was active may still write into its pages."""
        assert self._inflight is None
        for seq_id in self._pending_frees:
            self.allocator.free(seq_id)
        self._pending_frees.clear()

    @engine_thread_only
    def _decode_tick(self) -> bool:
        """One tick of ``_tick`` under the ledger's ``decode_dispatch``
        phase: the tick's bookkeeping and the dispatch itself are that
        phase's self time; state builds, row updates, the fetch of the
        in-flight window and its emits open their own inside it. The
        caller's phase (``admit`` from the loop, ``prefill_dispatch``
        between a long prompt's chunks) resumes when the tick is done."""
        loop = self.stats.loop
        outer = loop.enter(DECODE_DISPATCH)
        try:
            return self._tick()
        finally:
            loop.resume(outer)

    @engine_thread_only
    def _tick(self) -> bool:
        """Pipelined: dispatch window N+1, then process window N while
        the device runs. Membership changes are scattered into the live
        device state as row updates (chained asynchronously after the
        in-flight window), so admissions and completions no longer drain
        the pipeline; only page-bucket growth / speculation force a full
        drain + state rebuild."""
        active_idx = [i for i, s in enumerate(self._slots) if s is not None]
        if not active_idx:
            self._drain_inflight()
            self._apply_frees()
            # quiesced: drop the state so the next admission rebuilds it
            # right-sized (free here — nothing in flight — and an
            # oversized page bucket a departed long sequence forced is
            # released instead of taxing the next batch's gathers)
            self._device_state = None
            self._dirty_rows.clear()
            self._cn_dirty.clear()
            self.stats.active_slots = 0
            self._refresh_stats()
            return False

        if self._need_rebuild or self._device_state is None:
            if self._need_rebuild and self._device_state is not None:
                # a LIVE pipeline is drained for a full rebuild — only
                # page-bucket growth lands here now; the speculative
                # path must never (the zero-rebuild acceptance
                # criterion asserts on this counter)
                self.stats.state_rebuilds += 1
            # finish the window computed under the old state first
            self._drain_inflight()
            self._apply_frees()
            # that drain may have emitted stop/length finishes: rebuild
            # membership from the slots that actually survived (a stale
            # tick-entry index here dereferenced a freed slot and threw
            # the whole engine into _abort_all)
            active_idx = [i for i, s in enumerate(self._slots)
                          if s is not None]
            if not active_idx:
                self._device_state = None
                self._dirty_rows.clear()
                self._spec_dirty.clear()
                self._cn_dirty.clear()
                self.stats.active_slots = 0
                self._refresh_stats()
                return True
            P = self._decode_bucket_pages()
            # (the builder itself stays pure: warmup() calls it from
            # the server thread, which must not write the ledger)
            loop = self.stats.loop
            outer = loop.enter(
                STATE_BUILD,
                {"pages": P, "slots": len(active_idx)}
                if loop.capture else None)
            self._device_state = self._build_device_state(bucket=P)
            loop.resume(outer)
            self._state_bucket = P
            self._need_rebuild = False
            self._dirty_rows.clear()
            self._spec_dirty.clear()
            self._cn_dirty.clear()  # the full build carried the masks
        elif self._dirty_rows:
            self._apply_row_updates()
        if self._cn_dirty:
            # constrained slots whose FSM advanced since the last
            # dispatch: patch their bias rows (user bias + new mask)
            # before this dispatch samples under them
            self._apply_cn_row_updates()

        if self._inflight is not None:
            # Zombie-window guard: when every member slot reaches its
            # token limit within the window already in flight, another
            # dispatch would compute K junk steps against slots that are
            # all about to finish — junk that delays the next admission
            # by a full window (and burns K chip-steps per batch drain).
            # Drain instead; the loop admits or re-dispatches right
            # after. Slots admitted after the in-flight dispatch are not
            # advanced by it, so they block the guard (they need a
            # dispatch). Conservative under speculation (slots may
            # finish even sooner than +K; the guard then fires one
            # window later).
            K = self._inflight.k
            in_window = {i: req for i, req in self._inflight.members}
            if all(
                s is None
                or (in_window.get(i) is s.req
                    and (s.generated + K >= s.req.max_tokens
                         or s.pos + K >= min(s.limit, self.cfg.max_seq_len)))
                for i, s in enumerate(self._slots)
            ):
                self._drain_inflight()
                self._apply_frees()
                self.stats.active_slots = sum(
                    s is not None for s in self._slots)
                self._refresh_stats()
                return True

        # speculative dispatch width (and any rung-move patches) must
        # settle before the program choice below
        draft = self._choose_draft_len()
        if self._spec_dirty:
            self._apply_spec_row_updates()
        k = self._choose_window()
        members = tuple(
            (i, self._slots[i].req) for i in active_idx
        )
        draft_lens: tuple = ()
        if draft:
            draft_lens = tuple(
                (i, self._slots[i].ctrl.draft_len())
                for i in active_idx
                if self._slots[i].ctrl is not None
            )
        cn_epochs = tuple(
            (i, self._slots[i].cn_epoch,
             self._slots[i].cn.mask_row()) for i in active_idx
            if self._slots[i].cn is not None
        )
        frees, self._pending_frees = self._pending_frees, []
        lean = draft == 0 and self._lean_decode_ok()
        sorts = self._sample_sorts()
        decode_fn = self._decode_fn_for(k, lean, draft)
        if self.stats.loop.capture:
            # the same phase again, now that the window's facts are known
            self.stats.loop.resume(
                DECODE_DISPATCH,
                {"k": k, "slots": len(members), "draft": draft,
                 "pages": self._state_bucket})
        sampled, self._device_state, self.kv_cache, moe, kv_pages = (
            decode_fn(self.params, self.lora_params, self.kv_cache,
                      self._device_state))
        # start the device→host copy of the tokens (and of the two
        # page counts) now; it overlaps this window's on-device compute
        # and is resolved at drain time
        self._start_host_copy((sampled, kv_pages))
        # process the PREVIOUS window while this one runs on-device
        self._drain_inflight()
        self._inflight = _Window(sampled=sampled, members=members, k=k,
                                 frees=frees, draft=draft,
                                 draft_lens=draft_lens,
                                 cn_epochs=cn_epochs, moe=moe,
                                 kv_pages=kv_pages, sorts=sorts)
        for _i, _req in members:
            if _req.trace is not None:
                _req.trace.decode_window(k, lean, draft)
        self.stats.active_slots = sum(s is not None for s in self._slots)
        self._refresh_stats()
        return True

    @engine_thread_only
    def _emit_token(self, i: int, tok: int, lp=None) -> None:
        """Record one generated token for slot i; finish if stopping.
        ``lp`` = (chosen_logprob, [(top_id, top_logprob)]) when the
        engine runs with logprobs_topk > 0."""
        s = self._slots[i]
        assert s is not None
        req = s.req

        def _send(t: int, f: str | None) -> None:
            if req.emit_lp is not None:
                if lp is None or t < 0:
                    req.emit_lp(t, f, None, None)
                else:
                    req.emit_lp(t, f, lp[0], lp[1])
            else:
                req.emit(t, f)

        s.generated += 1
        if s.generated == 1:
            s.first_emit_at = time.monotonic()
            # engine-side TTFT: arrival → first sampled token available
            # (queue wait + prefill + first-emit residual). Batch
            # streams are EXCLUDED — the histogram feeds the SLO
            # burn-rate monitor and the gateway's predicted-TTFT
            # pricing, both of which must see only interactive latency
            # (offline work queuing for minutes is by design, not burn)
            if req.priority != "batch":
                self.phases.observe(
                    "ttft", 1e3 * (s.first_emit_at - req.enqueued_at),
                    req.trace.trace_id if req.trace is not None else "")
            if req.trace is not None:
                req.trace.first_token()
        finish: str | None = None
        send_tok = tok
        if tok in self.eos or tok in req.stop_token_ids:
            finish = "stop"
            send_tok = -1
        else:
            s.pos += 1  # where `tok` will be written by the next decode
            if s.generated >= req.max_tokens or s.pos >= self.cfg.max_seq_len:
                finish = "length"
        if finish is not None:
            # MeterRecord BEFORE the terminal emit: the consumer that
            # dequeues the finish item observes the record (engine
            # thread posts both; call_soon_threadsafe keeps FIFO order)
            self._meter_finish(s, finish)
        # counters BEFORE the emit too: a consumer woken by the terminal
        # item may read /state before this thread runs again
        self.stats.tokens_generated += 1
        if req.priority == "batch":
            self.stats.batch_tokens += 1
        _send(send_tok, finish)
        if finish is not None:
            if s.generated > 1 and s.first_emit_at:
                self.phases.observe(
                    "decode_per_token",
                    1e3 * (time.monotonic() - s.first_emit_at)
                    / (s.generated - 1),
                    req.trace.trace_id if req.trace is not None else "")
            if req.trace is not None:
                req.trace.engine_finish(finish)
            self._pending_frees.append(req.id)
            self._release_adapter_row(s.adapter_row)
            self._slots[i] = None
            self._dirty_rows.add(i)
            self._wake.set()  # maybe admit a queued request
        else:
            # the sampled token is the input of the next decode step
            s.pending_token = tok
            s.token_counts[tok] = s.token_counts.get(tok, 0) + 1
            s.gen_tokens.append(tok)

    def _pool_bytes(self) -> tuple[int, int]:
        """(bytes of the device cache, bytes sequences hold of it): the
        page pool and what is allocated of it, plus — for a family with
        per-slot state — the state pool and the taken slots' rows."""
        pool = self.cfg.num_pages * self.kv_page_bytes
        used = round(pool * self.allocator.occupancy)
        taken = sum(x is not None for x in self._slots)
        return (pool + self.stats.state_bytes_total,
                used + taken * self.stats.state_bytes_per_slot)

    @engine_thread_only
    def _refresh_stats(self) -> None:
        # ``queued`` is INTERACTIVE depth only — the picker's
        # predicted_ttft_ms and the controller's idle predicate price
        # it; offline backlog rides the batch_* pair below
        self.stats.queued, self.stats.queue_wait_ms = self.queue_depth()
        self.stats.batch_queued = (self._batch_q.qsize()
                                   + len(self._parked_batch))
        self.stats.batch_active = self._batch_active()
        if self.stats.prefill_tokens_padded:
            self.stats.prefill_padded_frac = round(
                1.0 - self.stats.prefill_tokens_real
                / self.stats.prefill_tokens_padded, 4)
        for key, value in self.compile_tracker.totals().items():
            setattr(self.stats, key, value)
        self.stats.kv_pages_free = self.allocator.free_pages
        self.stats.kv_occupancy = self.allocator.occupancy
        if self._stateful:
            # both pools: live pages, and the state of every slot that
            # is taken (a slot's state costs the same at any context).
            # The occupancy the picker reads is the share of the two
            # pools' bytes that sequences hold.
            pool, used = self._pool_bytes()
            self.stats.kv_occupancy = round(used / max(pool, 1), 4)
        # adapter residency + tenant fairness gauges (ISSUE 7)
        if self._adapter_store is not None:
            self.stats.adapter_loads = self._adapter_store.loads
            self.stats.adapter_evictions = self._adapter_store.evictions
            self.stats.adapter_resident = (
                self._adapter_store.resident_count)
        else:
            self.stats.adapter_resident = len(self.adapter_rows)
        self.stats.adapter_slots = sum(
            1 for s in self._slots
            if s is not None and s.adapter_row != self._base_row)
        # grammar-constrained decoding surface (ISSUE 9)
        self.stats.constrained_slots = sum(
            1 for s in self._slots if s is not None and s.cn is not None)
        self.stats.constraint_grammars = constrain.grammar_cache_size()
        # measured per-device memory (satellite): throttled — the
        # native memory_stats() call is cheap but pointless per tick
        now_m = time.monotonic()
        if now_m >= self._mem_next:
            self._mem_next = now_m + 0.5
            used, limit = device_memory_stats()
            self.stats.device_bytes_in_use = used
            self.stats.device_bytes_limit = limit
            self.stats.device_memory_frac = (
                round(used / limit, 4) if limit else 0.0)
            self.stats.kv_pool_bytes, self.stats.kv_bytes_in_use = (
                self._pool_bytes())
            # mesh serving (ISSUE 10): EVERY local device, not just
            # device 0 — per-device memory_stats, the device's real
            # share of the (head-sharded) KV pool, and its share of the
            # model weights, plus the worst-device memory fraction the
            # picker scores
            occ = self.allocator.occupancy
            kv_by_dev = _per_device_bytes(self.kv_cache)
            # only devices this ENGINE occupies (its param/KV shards):
            # a single-chip engine in a multi-device process reports
            # one device, not the process's whole population
            mine = set(self.param_bytes_by_device) | set(kv_by_dev)
            devs: list[dict] = []
            worst = 0.0
            for dev in device_memory_stats_all():
                did = dev["id"]
                if mine and did not in mine:
                    continue
                frac = (round(dev["bytes_in_use"] / dev["bytes_limit"], 4)
                        if dev["bytes_limit"] else 0.0)
                worst = max(worst, frac)
                devs.append({
                    **dev,
                    "memory_frac": frac,
                    "kv_pool_bytes": kv_by_dev.get(did, 0),
                    "kv_bytes_in_use": round(
                        kv_by_dev.get(did, 0) * occ),
                    "kv_occupancy": round(occ, 4),
                    "param_bytes":
                        self.param_bytes_by_device.get(did, 0),
                })
            self.device_stats = devs
            self.stats.device_count = max(1, len(devs))
            self.stats.device_memory_frac_worst = worst
        self.stats.ici_bytes_total = (
            self.ici_bytes_per_token * self.stats.tokens_generated)
        young = self.cfg.migration_young_tokens
        self.stats.migratable_slots = sum(
            1 for s in self._slots
            if s is not None and s.generated >= 1
            and (young <= 0 or s.generated <= young))
        tenants = self._tenant_slots()
        self.stats.tenants_active = len(tenants)
        self.stats.tenant_max_slots = max(tenants.values(), default=0)
        # MoE routing surface (ISSUE 18): scalars derived from the
        # per-expert / per-layer accumulators _fold_moe maintains. The
        # imbalance is hottest-expert / mean — the PR 10 worst-device
        # discipline (an ep-sharded replica steps at its hottest
        # expert's pace), priced by the gateway picker off /state.
        if self._moe:
            placed = float(self._moe_expert_tokens.sum())
            dropped = float(self._moe_layer_drops.sum())
            self.stats.moe_tokens_routed = int(placed)
            self.stats.moe_tokens_dropped = int(dropped)
            self.stats.moe_dropped_frac = round(
                dropped / (placed + dropped), 6) if placed + dropped \
                else 0.0
            mean = placed / max(self._moe_experts, 1)
            self.stats.moe_expert_imbalance = round(
                float(self._moe_expert_tokens.max()) / mean, 4) \
                if mean > 0 else 0.0
        self.stats.spec_accept_rate = (
            self.stats.spec_accepted / self.stats.spec_drafted
            if self.stats.spec_drafted else 0.0)
        if self.prefix_cache is not None:
            self.stats.prefix_cache_evictions = self.prefix_cache.evictions
            self.stats.prefix_pages_resident = (
                self.prefix_cache.resident_entries)
            self.stats.prefix_pages_pinned = (
                self.allocator.pinned_cached_pages)
            hm = (self.stats.prefix_cache_hits
                  + self.stats.prefix_cache_misses)
            self.stats.prefix_cache_hit_rate = (
                self.stats.prefix_cache_hits / hm if hm else 0.0)
        # KV memory hierarchy (ISSUE 11): host-tier occupancy/churn and
        # the resident+spilled chain digest the fleet index polls
        # (throttled — the digest walk is O(resident chains))
        if self.host_tier is not None:
            tier = self.host_tier
            self.stats.kv_spills = tier.spills
            self.stats.kv_revives = tier.revives
            self.stats.kv_spill_evictions = tier.evictions
            self.stats.kv_spilled_pages = tier.count
            self.stats.kv_spill_bytes = tier.bytes_used
            self.stats.kv_host_bytes = tier.max_bytes
        now_d = time.monotonic()
        if self.prefix_cache is not None and now_d >= self._kv_digest_next:
            self._kv_digest_next = now_d + 0.5
            self._refresh_kv_digest()


def continuation_request(blob: dict,
                         emit: Callable[[int, str | None], None]
                         = lambda t, f: None,
                         trace: Any = None) -> GenRequest:
    """Build the GenRequest that RESUMES a migrated session from an
    export blob (the wire half of migrate_export). The prompt is the
    full token history (original prompt + everything generated at the
    cut); import_state restores the sampling-key/penalty state so the
    resumed stream is byte-identical to a solo-served run. One builder
    shared by the /migrate/import endpoint and the migration tests —
    the wire format has exactly one consumer-side interpretation."""
    sp = blob.get("sampling") or {}
    sampling = SamplingParams(
        temperature=float(sp.get("temperature", 1.0)),
        top_p=float(sp.get("top_p", 1.0)),
        top_k=int(sp.get("top_k", 0)),
        seed=int(sp.get("seed", 0)),
        frequency_penalty=float(sp.get("frequency_penalty", 0.0)),
        presence_penalty=float(sp.get("presence_penalty", 0.0)),
        logit_bias=tuple((int(t), float(b))
                         for t, b in (sp.get("logit_bias") or ())),
    )
    tokens = [int(t) for t in blob["tokens"]]
    return GenRequest(
        prompt=tokens,
        max_tokens=int(blob["max_tokens"]),
        sampling=sampling,
        stop_token_ids=tuple(int(t) for t in
                             (blob.get("stop_token_ids") or ())),
        emit=emit,
        adapter=str(blob.get("adapter", "")),
        tenant=str(blob.get("tenant", "")),
        priority=str(blob.get("priority", "interactive")),
        import_state={
            "orig_prompt_len": int(blob.get("orig_prompt_len",
                                            len(tokens))),
            "generated": int(blob.get("generated", 0)),
            "key_seed": int(blob.get("key_seed", 0)),
            # the pending input token at the cut sat at position m-1 —
            # the resume's first sample must use its key
            "key_counter": len(tokens) - 1,
            # usage metering (ISSUE 20): the meter accumulated by the
            # exporting segment(s) — the resumed slot folds it into its
            # single terminal MeterRecord so a spliced stream meters once
            "meter_carry": blob.get("meter"),
        },
        trace=trace,
    )
