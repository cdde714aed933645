"""GenAI metrics with OTel semantic-convention names.

Reference: internal/metrics/genai.go:14-24 records
``gen_ai.client.token.usage``, ``gen_ai.server.request.duration``,
``gen_ai.server.time_to_first_token``, ``gen_ai.server.time_per_output_token``
with operation/provider/model/token-type attributes, exported via Prometheus
(+ optional OTLP). We register the same instruments on a prometheus_client
registry (dots become underscores per the Prometheus naming translation the
OTel exporter applies).
"""

from __future__ import annotations

import bisect
import time
from dataclasses import dataclass, field

from prometheus_client import CollectorRegistry, Counter, Histogram, generate_latest

from aigw_tpu.gateway.costs import TokenUsage

_LATENCY_BUCKETS = (
    0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
    10.0, 30.0, 60.0, 120.0,
)
_TOKEN_BUCKETS = (
    1, 4, 16, 64, 256, 1024, 4096, 16384, 65536, 262144, 1048576,
)


class GenAIMetrics:
    """Instrument set shared by the gateway and tpuserve."""

    def __init__(self, registry: CollectorRegistry | None = None):
        self.registry = registry or CollectorRegistry()
        labels = ["gen_ai_operation_name", "gen_ai_provider_name",
                  "gen_ai_request_model", "gen_ai_response_model"]
        self.token_usage = Histogram(
            "gen_ai_client_token_usage",
            "Number of input/output tokens used per request",
            labels + ["gen_ai_token_type"],
            registry=self.registry,
            buckets=_TOKEN_BUCKETS,
        )
        self.request_duration = Histogram(
            "gen_ai_server_request_duration_seconds",
            "End-to-end request duration",
            labels + ["error_type"],
            registry=self.registry,
            buckets=_LATENCY_BUCKETS,
        )
        self.time_to_first_token = Histogram(
            "gen_ai_server_time_to_first_token_seconds",
            "Time until the first streamed token",
            labels,
            registry=self.registry,
            buckets=_LATENCY_BUCKETS,
        )
        self.time_per_output_token = Histogram(
            "gen_ai_server_time_per_output_token_seconds",
            "Inter-token latency for streamed tokens",
            labels,
            registry=self.registry,
            buckets=_LATENCY_BUCKETS,
        )
        self.requests_total = Counter(
            "aigw_requests_total",
            "Requests by route/backend/status",
            ["route", "backend", "status"],
            registry=self.registry,
        )
        self.retries_total = Counter(
            "aigw_retries_total",
            "Upstream retry attempts",
            ["route", "backend"],
            registry=self.registry,
        )
        # SLO-aware admission control (ISSUE 8): requests shed with
        # 429 + Retry-After because every candidate replica's predicted
        # TTFT blew the configured SLO — load the gateway refused to
        # queue into collapse
        self.slo_sheds_total = Counter(
            "aigw_slo_sheds_total",
            "Requests shed because predicted TTFT exceeded the SLO on "
            "every candidate replica",
            ["route", "backend"],
            registry=self.registry,
        )
        # prefill/decode disaggregation: sessions the gateway moved from
        # a prefill-pressured replica to a decode-leaning one mid-stream
        self.migrations_total = Counter(
            "aigw_migrations_total",
            "Sessions migrated between replicas by the gateway",
            ["route", "backend"],
            registry=self.registry,
        )

    def export(self) -> bytes:
        return generate_latest(self.registry)


#: EngineStats attribute → Prometheus gauge name. One authoritative map
#: so tpuserve's /metrics, dashboards, and tests agree on the exported
#: serving-path surface. The /state twin of this contract is generated
#: in analysis/manifest.py (STATE_ONLY/METRICS_ONLY exemptions) and
#: enforced statically by the ``gauge-drift`` lint pass + the tier-1
#: drift smokes — adding an attr here without exporting it on /state
#: requires a METRICS_ONLY entry there. Including the adaptive decode window
#: (tpuserve_decode_window_steps: the K most recently dispatched, with
#: shrink/grow transition counters) and the phase breakdown
#: (prefill/transfer/emit milliseconds) behind TTFT regressions.
ENGINE_GAUGES: tuple[tuple[str, str], ...] = (
    ("active_slots", "tpuserve_active_slots"),
    ("queued", "tpuserve_queued_requests"),
    ("queue_wait_ms", "tpuserve_queue_wait_ms"),
    ("kv_pages_free", "tpuserve_kv_pages_free"),
    ("kv_occupancy", "tpuserve_kv_occupancy"),
    ("tokens_generated", "tpuserve_tokens_generated_total"),
    ("prefills", "tpuserve_prefills_total"),
    ("sp_prefills", "tpuserve_sp_prefills_total"),
    # long-context sp serving (sequence-sharded chunked prefill):
    # chunked-vs-monolithic routing volume and offset resumes on the
    # sp path (prefix-cache partial hits / migration continuations)
    ("sp_chunked_prefills", "tpuserve_sp_chunked_prefills_total"),
    ("sp_resume_prefills", "tpuserve_sp_resume_prefills_total"),
    ("sp_interactive_admits", "tpuserve_sp_interactive_admits_total"),
    ("chunked_prefill_steps", "tpuserve_chunked_prefill_steps_total"),
    ("decode_steps", "tpuserve_decode_steps_total"),
    ("sample_sort_steps", "tpuserve_sample_sort_steps_total"),
    ("decode_kv_pages_read", "tpuserve_decode_kv_pages_read_total"),
    ("decode_kv_pages_live", "tpuserve_decode_kv_pages_live_total"),
    ("decode_state_rows_read", "tpuserve_decode_state_rows_read_total"),
    ("decode_state_rows_live", "tpuserve_decode_state_rows_live_total"),
    # a group-limited router over a share of its experts: kept groups
    # that hold a held expert / groups kept, summed over the expert
    # layers; a latent family's prefill attention pairs (0 elsewhere)
    ("moe_groups_kept_hits", "tpuserve_moe_groups_kept_hits_total"),
    ("moe_group_slots", "tpuserve_moe_group_slots_total"),
    ("prefill_keys_attended", "tpuserve_prefill_keys_attended_total"),
    # a share with no shared expert: real tokens none of whose picks is
    # held here; sliding-window layers' decode steps: keys their
    # softmax saw / keys the rows' contexts hold (0 elsewhere)
    ("moe_unserved_tokens", "tpuserve_moe_unserved_tokens_total"),
    ("swa_keys_attended", "tpuserve_swa_keys_attended_total"),
    ("swa_keys_in_context", "tpuserve_swa_keys_in_context_total"),
    ("decode_window", "tpuserve_decode_window_steps"),
    ("window_shrinks", "tpuserve_decode_window_shrinks_total"),
    ("window_grows", "tpuserve_decode_window_grows_total"),
    # speculative decoding (ISSUE 4): draft/accept volume, the
    # cumulative acceptance rate, the adaptive ladder's current
    # dispatch width and transition counters, the prefix-cache
    # continuation draft source, and the pipeline-draining full-rebuild
    # counter the zero-rebuild criterion asserts on
    ("spec_accepted", "tpuserve_spec_accepted_total"),
    ("spec_drafted", "tpuserve_spec_drafted_tokens_total"),
    ("spec_accept_rate", "tpuserve_spec_accept_rate"),
    ("spec_draft_len", "tpuserve_spec_draft_len"),
    ("spec_rung_ups", "tpuserve_spec_rung_ups_total"),
    ("spec_rung_downs", "tpuserve_spec_rung_downs_total"),
    ("spec_lookahead_slots", "tpuserve_spec_lookahead_slots_total"),
    ("state_rebuilds", "tpuserve_state_rebuilds_total"),
    ("prefix_cache_hits", "tpuserve_prefix_cache_hits_total"),
    ("prefix_tokens_reused", "tpuserve_prefix_tokens_reused_total"),
    # a family whose prefix cache resumes from a snapshot of its
    # per-slot state (Engine.chunk_boundary; 0 elsewhere)
    ("state_snapshots_saved", "tpuserve_state_snapshots_saved_total"),
    ("state_snapshots_restored", "tpuserve_state_snapshots_restored_total"),
    ("state_snapshots_evicted", "tpuserve_state_snapshots_evicted_total"),
    ("state_snapshot_bytes_total", "tpuserve_state_snapshot_bytes"),
    ("prefix_tokens_unrestorable",
     "tpuserve_prefix_tokens_unrestorable_total"),
    # prefix-cache reuse surface (ISSUE 3): hit/miss/eviction counters,
    # the full-hit fast path (CoW'd final page + single-token resume),
    # and the residency/pinning gauges behind HBM capacity planning
    ("prefix_cache_misses", "tpuserve_prefix_cache_misses_total"),
    ("prefix_cache_evictions", "tpuserve_prefix_cache_evictions_total"),
    ("prefix_full_hits", "tpuserve_prefix_full_hits_total"),
    ("prefix_cow_copies", "tpuserve_prefix_cow_copies_total"),
    ("prefix_pages_resident", "tpuserve_prefix_pages_resident"),
    ("prefix_pages_pinned", "tpuserve_prefix_pages_pinned"),
    ("prefix_cache_hit_rate", "tpuserve_prefix_cache_hit_rate"),
    ("prefill_ms", "tpuserve_prefill_ms_total"),
    ("transfer_ms", "tpuserve_transfer_ms_total"),
    ("emit_ms", "tpuserve_emit_ms_total"),
    ("first_emit_ms", "tpuserve_first_emit_ms_total"),
    # prefill padding tax (ISSUE 6): real prompt tokens vs tokens the
    # padded program geometry processed — the per-replica observable
    # behind the ragged attention backend's padded_frac claim — plus
    # the warmup cost (collapsed compile surface = faster cold start)
    ("prefill_tokens_real", "tpuserve_prefill_tokens_real_total"),
    ("prefill_tokens_padded", "tpuserve_prefill_tokens_padded_total"),
    ("prefill_padded_frac", "tpuserve_prefill_padded_frac"),
    ("warmup_ms", "tpuserve_warmup_ms"),
    ("warm_programs", "tpuserve_warm_programs"),
    # XLA compile tracker (ISSUE 5, obs/xla_events.py): compiles seen
    # process-wide since the engine came up, and their total wall time —
    # a nonzero delta after warmup is a hot-path compile regression
    ("xla_compiles", "tpuserve_xla_compiles_total"),
    ("xla_compile_ms", "tpuserve_xla_compile_ms_total"),
    # persistent compile cache (utils/boot.py): a compile event above
    # is a LOAD when the cache served it — these tell the two apart
    ("xla_cache_hits", "tpuserve_xla_cache_hits_total"),
    ("xla_cache_misses", "tpuserve_xla_cache_misses_total"),
    # the load ledger (ISSUE 42, obs/xla_events.py): what getting
    # programs cost by stage, process-wide — Python tracing, lowering,
    # and of the backend span above the compile cache's read +
    # deserialize_and_load — and the LATE loads, the ones that came
    # after the server called itself ready, so a request waited for
    # them: tpuserve_xla_late_loads_total growing is the alert
    ("xla_trace_ms", "tpuserve_xla_trace_ms_total"),
    ("xla_lower_ms", "tpuserve_xla_lower_ms_total"),
    ("xla_retrieval_ms", "tpuserve_xla_retrieval_ms_total"),
    ("xla_late_loads", "tpuserve_xla_late_loads_total"),
    ("xla_late_ms", "tpuserve_xla_late_ms_total"),
    ("xla_late_trace_ms", "tpuserve_xla_late_trace_ms_total"),
    ("xla_late_lower_ms", "tpuserve_xla_late_lower_ms_total"),
    ("xla_late_retrieval_ms", "tpuserve_xla_late_retrieval_ms_total"),
    # the boot timeline (ISSUE 42, utils/boot.py): self time of each
    # phase from the process's start as the OS has it to the first
    # moment /health would answer ok, and their sum
    ("boot_import_ms", "tpuserve_boot_import_ms"),
    ("boot_backend_ms", "tpuserve_boot_backend_ms"),
    ("boot_weights_ms", "tpuserve_boot_weights_ms"),
    ("boot_weights_layout_ms", "tpuserve_boot_weights_layout_ms"),
    ("boot_engine_ms", "tpuserve_boot_engine_ms"),
    ("boot_warmup_ms", "tpuserve_boot_warmup_ms"),
    ("boot_listen_ms", "tpuserve_boot_listen_ms"),
    ("boot_ready_ms", "tpuserve_boot_ready_ms"),
    # adapter serving subsystem (ISSUE 7, tpuserve/adapters.py): hot
    # loads into the stacked LoRA rows, LRU evictions under row
    # pressure, resident adapters, and live slots decoding through a
    # non-base adapter row
    ("adapter_loads", "tpuserve_adapter_loads_total"),
    ("adapter_evictions", "tpuserve_adapter_evictions_total"),
    ("adapter_resident", "tpuserve_adapter_resident"),
    ("adapter_slots", "tpuserve_adapter_slots"),
    # prefill/decode disaggregation (ISSUE 8): sessions exported to /
    # imported from sibling replicas with the KV pages that traveled,
    # plus the live migration-eligibility gauge (prefill done, decode
    # young) the gateway's orchestrator polls
    ("migrations_out", "tpuserve_migrations_out_total"),
    ("migrations_in", "tpuserve_migrations_in_total"),
    ("migration_pages_out", "tpuserve_migration_pages_out_total"),
    ("migration_pages_in", "tpuserve_migration_pages_in_total"),
    ("migratable_slots", "tpuserve_migratable_slots"),
    # KV memory hierarchy (ISSUE 11, tpuserve/kvhost.py): host-spill-
    # tier churn (pages demoted on eviction / promoted back by prefix
    # hits / dropped by the host LRU budget), its live occupancy and
    # byte budget, and cross-replica /kv/pages fetch traffic in both
    # directions
    ("kv_spills", "tpuserve_kv_spills_total"),
    ("kv_revives", "tpuserve_kv_revives_total"),
    ("kv_spill_evictions", "tpuserve_kv_spill_evictions_total"),
    ("kv_spilled_pages", "tpuserve_kv_spilled_pages"),
    ("kv_spill_bytes", "tpuserve_kv_spill_bytes"),
    ("kv_host_bytes", "tpuserve_kv_host_bytes"),
    ("kv_fetches_out", "tpuserve_kv_fetches_out_total"),
    ("kv_fetches_in", "tpuserve_kv_fetches_in_total"),
    ("kv_fetch_pages_out", "tpuserve_kv_fetch_pages_out_total"),
    ("kv_fetch_pages_in", "tpuserve_kv_fetch_pages_in_total"),
    # multi-tenant fairness: distinct tenants holding decode slots, the
    # largest per-tenant in-flight count, and admissions the per-tenant
    # slot cap deferred (each deferral = one pass a request waited)
    ("tenants_active", "tpuserve_tenants_active"),
    ("tenant_max_slots", "tpuserve_tenant_max_slots"),
    ("tenant_deferrals", "tpuserve_tenant_deferrals_total"),
    # grammar-constrained decoding (ISSUE 9, tpuserve/constrain.py):
    # live constrained slots, requests admitted with a grammar, window
    # rollbacks at mask boundaries (the spec-rejection discipline),
    # device mask-row patches, and the compiled-grammar cache size
    ("constrained_slots", "tpuserve_constrained_slots"),
    ("constraint_requests", "tpuserve_constraint_requests_total"),
    ("constraint_rollbacks", "tpuserve_constraint_rollbacks_total"),
    ("constraint_mask_updates",
     "tpuserve_constraint_mask_updates_total"),
    ("constraint_grammars", "tpuserve_constraint_grammars"),
    # measured per-device memory (ISSUE 9 satellite): live jax
    # memory_stats() bytes (0 on backends without them) + the KV pool's
    # byte occupancy — the picker's first MEASURED memory signal
    ("device_bytes_in_use", "tpuserve_device_bytes_in_use"),
    ("device_bytes_limit", "tpuserve_device_bytes_limit"),
    ("device_memory_frac", "tpuserve_device_memory_frac"),
    ("kv_pool_bytes", "tpuserve_kv_pool_bytes"),
    ("kv_bytes_in_use", "tpuserve_kv_bytes_in_use"),
    # mesh serving (ISSUE 10): the engine's local device population,
    # the WORST per-device memory fraction (the picker's mesh memory
    # term — one hot shard stalls the whole tensor-parallel step), and
    # the analytical per-device ICI collective volume (bytes one
    # decoded token moves over the interconnect, and the running total)
    ("device_count", "tpuserve_device_count"),
    ("device_memory_frac_worst", "tpuserve_device_memory_frac_worst"),
    ("ici_bytes_per_token", "tpuserve_ici_bytes_per_token"),
    ("ici_bytes_total", "tpuserve_ici_bytes_total"),
    # quantized KV pages (ISSUE 13): bits per stored KV
    # element (32/16 native, 8/4 quantized) and the all-layer HBM
    # bytes one cached token costs including its per-page scale share.
    # The RESOLVED decode rung itself is a string — it rides /metrics
    # as the labeled info gauge tpuserve_decode_attn_impl{impl=...}
    # (rendered by the server, not this numeric map) and /state as
    # decode_attn_impl/decode_attn_reason.
    ("kv_quant_bits", "tpuserve_kv_quant_bits"),
    ("kv_bytes_per_token", "tpuserve_kv_bytes_per_token"),
    # MoE serving (ISSUE 18, expert-parallel families): tokens the
    # router PLACED into expert capacity slots vs tokens DROPPED at the
    # capacity limit (both count padding rows — truthful to device
    # compute), the drop fraction, and the hottest-expert load ratio
    # (max expert tokens / mean — 1.0 is perfectly balanced). The
    # imbalance gauge is the picker's MoE pricing signal: PR 10
    # worst-device discipline, a replica is as fast as its hottest
    # expert. Constant 0 on dense families.
    ("moe_tokens_routed", "tpuserve_moe_tokens_routed_total"),
    ("moe_tokens_dropped", "tpuserve_moe_tokens_dropped_total"),
    ("moe_dropped_frac", "tpuserve_moe_dropped_frac"),
    ("moe_expert_imbalance", "tpuserve_moe_expert_imbalance"),
    # an expert layer that holds one chip's share of its experts:
    # assignments routed anywhere / landed on a held expert, and held
    # experts hit summed over layers and decode steps (0 elsewhere)
    ("moe_local_assignments", "tpuserve_moe_local_assignments_total"),
    ("moe_total_assignments", "tpuserve_moe_total_assignments_total"),
    ("moe_held_hits_decode", "tpuserve_moe_held_hits_decode_total"),
    # the device cache's description (models/cache.py): layers with
    # pages, and the per-slot recurrent state of a hybrid family
    ("kv_layers", "tpuserve_kv_layers"),
    ("state_bytes_per_slot", "tpuserve_state_bytes_per_slot"),
    ("state_bytes_total", "tpuserve_state_bytes_total"),
    # priority-tiered serving (ISSUE 19): the offline /v1/batches
    # class. Queued = never-shed backlog + host-parked preempted
    # sessions; active = decode slots it holds (≤ the batch_slot_frac
    # ceiling); preemptions/resumed = the park→resume churn interactive
    # arrivals drive; tokens = the volume the idle slots soaked up
    # (what the tier earned).
    ("batch_queued", "tpuserve_batch_queued"),
    ("batch_active", "tpuserve_batch_active"),
    ("batch_preemptions", "tpuserve_batch_preemptions_total"),
    ("batch_resumed", "tpuserve_batch_resumed_total"),
    ("batch_tokens", "tpuserve_batch_tokens_total"),
    # engine-truth usage metering (ISSUE 20): cumulative MeterRecord
    # totals. Every terminal stream (stop/length/cancelled/error — and
    # a migrated continuation exactly once for the spliced whole) emits
    # one record; these counters only move inside the engine's
    # _meter_emit funnel, so the gateway ledger's per-tenant sums
    # reconcile against them token-for-token. The page·byte·second
    # pair is the TPU-native residency dimension: KV bytes × seconds
    # occupied in HBM and in the host spill/park tier.
    ("meter_records", "tpuserve_meter_records_total"),
    ("meter_prefill_tokens", "tpuserve_meter_prefill_tokens_total"),
    ("meter_prefill_padded_tokens",
     "tpuserve_meter_prefill_padded_tokens_total"),
    ("meter_prefix_reused_tokens",
     "tpuserve_meter_prefix_reused_tokens_total"),
    ("meter_decode_tokens", "tpuserve_meter_decode_tokens_total"),
    ("meter_spec_drafted", "tpuserve_meter_spec_drafted_total"),
    ("meter_spec_accepted", "tpuserve_meter_spec_accepted_total"),
    ("meter_hbm_page_byte_s", "tpuserve_meter_hbm_page_byte_s_total"),
    ("meter_host_page_byte_s",
     "tpuserve_meter_host_page_byte_s_total"),
)

#: per-device gauge surface (ISSUE 10): key in one entry of
#: ``Engine.device_stats`` → labeled Prometheus gauge name. One
#: authoritative map, same drift-check contract as ENGINE_GAUGES —
#: every key here must appear in the engine's per-device dicts and
#: every gauge must render on /metrics with a ``device`` label.
DEVICE_GAUGES: tuple[tuple[str, str], ...] = (
    ("bytes_in_use", "tpuserve_device_bytes_in_use_per_device"),
    ("bytes_limit", "tpuserve_device_bytes_limit_per_device"),
    ("memory_frac", "tpuserve_device_memory_frac_per_device"),
    ("kv_pool_bytes", "tpuserve_device_kv_pool_bytes"),
    ("kv_bytes_in_use", "tpuserve_device_kv_bytes_in_use"),
    ("kv_occupancy", "tpuserve_device_kv_occupancy"),
    ("param_bytes", "tpuserve_device_param_bytes"),
)


def render_device_gauges(devices: list) -> bytes:
    """Per-device stats dicts → labeled Prometheus gauges (appended to
    tpuserve's /metrics next to the scalar engine gauges)."""
    lines = []
    for _key, name in DEVICE_GAUGES:
        lines.append(f"# TYPE {name} gauge")
    for dev in devices:
        label = dev.get("id", 0)
        for key, name in DEVICE_GAUGES:
            lines.append(f'{name}{{device="{label}"}} {dev.get(key, 0)}')
    return ("\n".join(lines) + "\n").encode() if lines else b""


def render_moe_gauges(expert_load: list, layer_drops: list) -> bytes:
    """MoE per-expert / per-layer accumulators → labeled Prometheus
    gauges (appended to tpuserve's /metrics on MoE families only;
    dense families contribute zero bytes). The /state twins are the
    ``moe_expert_load`` / ``moe_layer_drops`` list fields — same
    ordering, expert index = gauge label."""
    if not expert_load and not layer_drops:
        return b""
    lines = ["# TYPE tpuserve_moe_expert_load gauge"]
    for e, n in enumerate(expert_load):
        lines.append(f'tpuserve_moe_expert_load{{expert="{e}"}} {n}')
    lines.append("# TYPE tpuserve_moe_layer_drops gauge")
    for layer, n in enumerate(layer_drops):
        lines.append(f'tpuserve_moe_layer_drops{{layer="{layer}"}} {n}')
    return ("\n".join(lines) + "\n").encode()


#: fleet rollup surface (ISSUE 12): key in ``FleetState.rollup()`` →
#: aggregate gauge name on the gateway's ``GET /fleet/metrics``. One
#: authoritative map, same drift-check contract as ENGINE_GAUGES —
#: every key here must appear in the rollup dict and every gauge must
#: render on the federation scrape next to the replica-labeled
#: ``tpuserve_*`` re-exports.
FLEET_GAUGES: tuple[tuple[str, str], ...] = (
    ("replicas_total", "aigw_fleet_replicas_total"),
    ("replicas_up", "aigw_fleet_replicas_up"),
    ("replicas_degraded", "aigw_fleet_replicas_degraded"),
    ("replicas_draining", "aigw_fleet_replicas_draining"),
    ("replicas_down", "aigw_fleet_replicas_down"),
    ("slots_total", "aigw_fleet_slots_total"),
    ("slots_free", "aigw_fleet_slots_free"),
    ("queued_total", "aigw_fleet_queued_total"),
    ("kv_occupancy_worst", "aigw_fleet_kv_occupancy_worst"),
    ("kv_occupancy_mean", "aigw_fleet_kv_occupancy_mean"),
    ("device_memory_frac_worst",
     "aigw_fleet_device_memory_frac_worst"),
    ("kv_spills_total", "aigw_fleet_kv_spills_total"),
    ("kv_revives_total", "aigw_fleet_kv_revives_total"),
    ("kv_fetch_pages_in_total", "aigw_fleet_kv_fetch_pages_in_total"),
    ("kv_fetch_pages_out_total",
     "aigw_fleet_kv_fetch_pages_out_total"),
    ("migrations_in_total", "aigw_fleet_migrations_in_total"),
    ("migrations_out_total", "aigw_fleet_migrations_out_total"),
    ("adapters_resident", "aigw_fleet_adapters_resident"),
    # live SLO burn-rate monitor (obs/slomon.py): latest closed
    # window's fleet goodput/burn (-1 = no closed window yet) and the
    # K-consecutive-windows sustained-overshoot flag ROADMAP item 2's
    # autoscaler consumes
    ("slo_goodput", "aigw_fleet_slo_goodput"),
    ("slo_burn_rate", "aigw_fleet_slo_burn_rate"),
    ("slo_overshoot_sustained", "aigw_fleet_slo_overshoot_sustained"),
)


def render_fleet_gauges(rollup: dict, backend: str = "") -> bytes:
    """FleetState rollup dict → aigw_fleet_* Prometheus gauges,
    labeled by backend pool when the gateway serves more than one."""
    sel = f'{{backend="{backend}"}}' if backend else ""
    lines = []
    for key, name in FLEET_GAUGES:
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name}{sel} {rollup.get(key, 0)}")
    return ("\n".join(lines) + "\n").encode()


#: usage-metering ledger surface (ISSUE 20): key in
#: ``UsageLedger.snapshot()`` → gauge name on the gateway's
#: ``GET /metrics``. Same drift-check contract as FLEET_GAUGES —
#: every key here must appear as a literal in the ledger's snapshot()
#: dict (gateway/usage.py) and every gauge must render on the scrape.
USAGE_GAUGES: tuple[tuple[str, str], ...] = (
    ("records_total", "aigw_usage_records_total"),
    ("prefill_tokens_total", "aigw_usage_prefill_tokens_total"),
    ("prefill_padded_tokens_total",
     "aigw_usage_prefill_padded_tokens_total"),
    ("prefix_reused_tokens_total",
     "aigw_usage_prefix_reused_tokens_total"),
    ("decode_tokens_total", "aigw_usage_decode_tokens_total"),
    ("spec_drafted_total", "aigw_usage_spec_drafted_total"),
    ("spec_accepted_total", "aigw_usage_spec_accepted_total"),
    ("hbm_page_byte_s_total", "aigw_usage_hbm_page_byte_s_total"),
    ("host_page_byte_s_total", "aigw_usage_host_page_byte_s_total"),
    ("cost_total", "aigw_usage_cost_total"),
    ("tenants", "aigw_usage_tenants"),
    ("windows_closed_total", "aigw_usage_windows_closed_total"),
    ("journal_lines_total", "aigw_usage_journal_lines_total"),
    ("reconcile_mismatches_total",
     "aigw_usage_reconcile_mismatches_total"),
    ("over_budget_tenants", "aigw_usage_over_budget_tenants"),
    ("burn_sustained_tenants", "aigw_usage_burn_sustained_tenants"),
)


def render_usage_gauges(snapshot: dict) -> bytes:
    """UsageLedger snapshot dict → aigw_usage_* Prometheus gauges
    (appended to the gateway's /metrics scrape)."""
    lines = []
    for key, name in USAGE_GAUGES:
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {snapshot.get(key, 0)}")
    return ("\n".join(lines) + "\n").encode()


#: fleet control plane surface (ISSUE 14): key in
#: ``FleetController.gauge_values()`` → gauge name on the gateway's
#: ``GET /fleet/metrics``. Same drift-check contract as FLEET_GAUGES —
#: every key here must appear in the controller's gauge dict and every
#: gauge must render on the federation scrape when a controller is
#: attached to the pool.
CONTROLLER_GAUGES: tuple[tuple[str, str], ...] = (
    ("scale_outs", "aigw_ctl_scale_outs_total"),
    ("scale_ins", "aigw_ctl_scale_ins_total"),
    ("drains", "aigw_ctl_drains_total"),
    ("retires", "aigw_ctl_retires_total"),
    ("failovers", "aigw_ctl_failovers_total"),
    ("launch_failures", "aigw_ctl_launch_failures_total"),
    ("launches_in_flight", "aigw_ctl_launches_in_flight"),
    ("drains_in_progress", "aigw_ctl_drains_in_progress"),
    ("replicas_min", "aigw_ctl_replicas_min"),
    ("replicas_max", "aigw_ctl_replicas_max"),
    ("replicas_live", "aigw_ctl_replicas_live"),
    ("idle_streak", "aigw_ctl_idle_streak"),
)


def render_controller_gauges(values: dict, backend: str = "") -> bytes:
    """FleetController gauge dict → aigw_ctl_* Prometheus gauges."""
    sel = f'{{backend="{backend}"}}' if backend else ""
    lines = []
    for key, name in CONTROLLER_GAUGES:
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name}{sel} {values.get(key, 0)}")
    return ("\n".join(lines) + "\n").encode()


#: the engine loop's phases (obs/flight.py LoopLedger; the index is the
#: phase id ``enter()`` takes). Every nanosecond of ``Engine._run``
#: belongs to exactly one: a phase entered inside another suspends the
#: outer one, so each is SELF time. By where the work happens —
#: reap: cancelled slots and the migration queue; admit: queue pops,
#: classification, prefix lookups, slot set-up (admit_wait: the
#: coalescing sleep alone); prefill_dispatch / prefill_block: host
#: building and dispatching a prefill call / host blocked on its
#: sampled token; state_build: the full device-state upload;
#: row_update: the per-row scatters (dirty rows, draft lengths, grammar
#: masks); decode_dispatch: the tick's bookkeeping and the window's
#: dispatch; window_fetch: host blocked fetching the in-flight window's
#: tokens (waiting for the device); emit: handing tokens to consumers;
#: idle: nothing to do (``_wake.wait``); other: the remainder.
LOOP_PHASES: tuple[str, ...] = (
    "reap", "admit", "admit_wait", "prefill_dispatch", "prefill_block",
    "state_build", "row_update", "decode_dispatch", "window_fetch",
    "emit", "idle", "other",
)

#: engine counters cut to a profiler capture: their deltas between the
#: first phase boundary after /debug/profile raised the capture flag
#: and the first after it lowered it accumulate under ``capture_<name>``
CAPTURE_COUNTERS: tuple[str, ...] = (
    "decode_steps", "tokens_generated", "prefill_tokens_real",
    "prefill_tokens_padded", "prefill_calls",
    "moe_local_assignments", "moe_total_assignments",
    "moe_held_hits_decode", "decode_kv_pages_live",
    "prefill_keys_attended", "decode_state_rows_live",
    "swa_keys_attended", "state_snapshots_saved",
    "state_snapshots_restored",
)

#: the loop ledger's flat surface: key of ``LoopLedger.flat()`` (spread
#: into /state as it is) → /metrics family. Generated from the two
#: tables above, rendered by ``render_engine_gauges`` after
#: ENGINE_GAUGES; all cumulative, none ever decreases.
LOOP_GAUGES: tuple[tuple[str, str], ...] = (
    ("loop_ns", "tpuserve_loop_ns_total"),
    ("loop_busy_ns", "tpuserve_loop_busy_ns_total"),
    *((f"loop_{p}_ns", f"tpuserve_loop_{p}_ns_total")
      for p in LOOP_PHASES),
    *((f"loop_{p}_n", f"tpuserve_loop_{p}_entries_total")
      for p in LOOP_PHASES),
    ("prefill_calls", "tpuserve_prefill_calls_total"),
    ("capture_ns", "tpuserve_capture_ns_total"),
    *((f"capture_{c}", f"tpuserve_capture_{c}_total")
      for c in CAPTURE_COUNTERS),
    *((f"capture_loop_{p}_ns", f"tpuserve_capture_loop_{p}_ns_total")
      for p in LOOP_PHASES),
)


def render_engine_gauges(stats: object) -> bytes:
    """EngineStats → Prometheus text exposition (appended to the
    prometheus_client registry output on tpuserve's /metrics): the
    ENGINE_GAUGES attrs, then the loop ledger's LOOP_GAUGES keys."""
    lines = []
    for attr, name in ENGINE_GAUGES:
        value = getattr(stats, attr, 0)
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {value}")
    loop = getattr(stats, "loop", None)
    if loop is not None:
        flat = loop.flat()
        for key, name in LOOP_GAUGES:
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {flat[key]}")
    return ("\n".join(lines) + "\n").encode()


#: serving-phase histogram surface (ISSUE 5): phase key → Prometheus
#: family name. The authoritative map — EnginePhases builds its
#: histograms from it, /metrics renders it, /state derives
#: phase_percentiles from it, and the tier-1 drift smoke asserts the two
#: sides agree — so a renamed phase can't silently drop a percentile.
#: Distinct from the ENGINE_GAUGES *_ms cumulative totals: these are
#: real per-observation distributions (p50/p95/p99 are readable).
ENGINE_HISTOGRAMS: tuple[tuple[str, str], ...] = (
    ("queue_wait", "tpuserve_queue_wait_hist_ms"),
    ("prefill", "tpuserve_prefill_hist_ms"),
    ("ttft", "tpuserve_ttft_hist_ms"),
    ("first_emit", "tpuserve_first_emit_hist_ms"),
    ("decode_per_token", "tpuserve_decode_per_token_hist_ms"),
    ("transfer", "tpuserve_transfer_hist_ms"),
)

#: histogram bucket upper bounds in milliseconds (+Inf implicit). Spans
#: sub-ms transfer fetches to multi-second queue waits.
PHASE_BUCKETS_MS: tuple[float, ...] = (
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 30000.0,
)


class PhaseHistogram:
    """Fixed-bucket latency histogram with per-bucket trace-id exemplars.

    Hand-rolled rather than prometheus_client because (a) the writer is
    the engine thread — observe() must be a couple of list/scalar ops,
    no label lookups or locks — and (b) classic prometheus_client text
    export drops exemplars; we render OpenMetrics-style exemplars on the
    bucket lines ourselves. int/float stores are GIL-atomic; readers
    (percentiles, render) tolerate a torn count by one observation.
    """

    __slots__ = ("name", "buckets", "counts", "total", "count",
                 "exemplars")

    def __init__(self, name: str,
                 buckets: tuple[float, ...] = PHASE_BUCKETS_MS):
        self.name = name
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)  # last = +Inf
        self.total = 0.0
        self.count = 0
        # bucket index → (trace_id, observed value) of the most recent
        # traced observation landing there
        self.exemplars: dict[int, tuple[str, float]] = {}

    def observe(self, ms: float, trace_id: str = "") -> None:
        i = bisect.bisect_left(self.buckets, ms)
        self.counts[i] += 1
        self.total += ms
        self.count += 1
        if trace_id:
            self.exemplars[i] = (trace_id, ms)

    def percentile(self, q: float) -> float:
        """q in (0, 1] → linear interpolation inside the target bucket.
        -1.0 when empty (distinguishable from a real 0ms)."""
        counts = list(self.counts)
        n = sum(counts)
        if n == 0:
            return -1.0
        target = q * n
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if cum >= target:
                hi = (self.buckets[i] if i < len(self.buckets)
                      else self.buckets[-1] * 2)
                lo = self.buckets[i - 1] if i > 0 else 0.0
                if c == 0:
                    return hi
                frac = (target - (cum - c)) / c
                return lo + (hi - lo) * frac
        return self.buckets[-1] * 2

    def percentiles(self) -> dict[str, float]:
        return {
            "p50": round(self.percentile(0.50), 3),
            "p95": round(self.percentile(0.95), 3),
            "p99": round(self.percentile(0.99), 3),
        }

    def cumulative(self) -> dict[str, int]:
        """Cumulative bucket counts ``{le: count}`` (including +Inf) —
        the JSON twin of the /metrics bucket lines, exported on /state
        (``ttft_hist_buckets``) so the gateway's burn-rate monitor
        (obs/slomon.py) consumes the histogram straight off the poll it
        already makes, no second scrape."""
        out: dict[str, int] = {}
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            le = (f"{self.buckets[i]:g}" if i < len(self.buckets)
                  else "+Inf")
            out[le] = cum
        return out

    def render(self) -> str:
        """Prometheus histogram exposition; bucket lines carry
        OpenMetrics-style ``# {trace_id="…"} v`` exemplars when a traced
        request landed in the bucket."""
        lines = [f"# TYPE {self.name} histogram"]
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            le = (f"{self.buckets[i]:g}" if i < len(self.buckets)
                  else "+Inf")
            line = f'{self.name}_bucket{{le="{le}"}} {cum}'
            ex = self.exemplars.get(i)
            if ex is not None:
                line += f' # {{trace_id="{ex[0]}"}} {ex[1]:g}'
            lines.append(line)
        lines.append(f"{self.name}_sum {self.total:g}")
        lines.append(f"{self.name}_count {cum}")
        return "\n".join(lines) + "\n"


class EnginePhases:
    """The engine's serving-phase histogram set (one PhaseHistogram per
    ENGINE_HISTOGRAMS entry). Owned by the Engine; rendered on /metrics
    and summarized as p50/p95/p99 on /state."""

    def __init__(self) -> None:
        self.hists: dict[str, PhaseHistogram] = {
            key: PhaseHistogram(name) for key, name in ENGINE_HISTOGRAMS
        }

    def observe(self, phase: str, ms: float, trace_id: str = "") -> None:
        h = self.hists.get(phase)
        if h is not None:
            h.observe(ms, trace_id)

    def percentiles(self) -> dict[str, dict[str, float]]:
        return {key: h.percentiles() for key, h in self.hists.items()}

    def render(self) -> bytes:
        return "".join(h.render() for h in self.hists.values()).encode()


class MCPMetrics:
    """MCP proxy instruments (reference internal/metrics/mcp_metrics.go:
    ``mcp.request.duration`` / ``mcp.method.count`` /
    ``mcp.initialization.duration`` / ``mcp.capabilities.negotiated`` /
    ``mcp.progress.notifications``, with method/backend/status/error
    attributes). Lives in the gateway's shared registry — scraped via
    GenAIMetrics.export on /metrics."""

    def __init__(self, registry: CollectorRegistry):
        self.registry = registry
        self.method_total = Counter(
            "mcp_method_total",
            "JSON-RPC methods handled by the MCP proxy",
            ["mcp_method_name", "mcp_backend", "status"],
            registry=self.registry,
        )
        self.request_duration = Histogram(
            "mcp_request_duration_seconds",
            "MCP request handling duration",
            ["mcp_method_name"],
            registry=self.registry,
            buckets=_LATENCY_BUCKETS,
        )
        self.initialization_duration = Histogram(
            "mcp_initialization_duration_seconds",
            "MCP session initialization duration (backend fan-out)",
            [],
            registry=self.registry,
            buckets=_LATENCY_BUCKETS,
        )
        self.capabilities_negotiated = Counter(
            "mcp_capabilities_negotiated_total",
            "Capabilities negotiated at initialize",
            ["capability_type", "capability_side"],
            registry=self.registry,
        )
        self.progress_notifications = Counter(
            "mcp_progress_notifications_total",
            "Progress notifications routed through the proxy",
            [],
            registry=self.registry,
        )
        self.errors_total = Counter(
            "mcp_errors_total",
            "MCP errors by method and type",
            ["mcp_method_name", "error_type"],
            registry=self.registry,
        )


@dataclass
class RequestMetrics:
    """Per-request lifecycle recorder (reference metrics.Metrics interface,
    metrics.go:97-127: StartRequest/SetModel/RecordTokenUsage/…)."""

    metrics: GenAIMetrics
    operation: str = "chat"
    provider: str = ""
    request_model: str = ""
    response_model: str = ""
    start: float = field(default_factory=time.monotonic)
    first_token_at: float = 0.0
    last_token_at: float = 0.0
    tokens_seen: int = 0
    final_usage: TokenUsage = field(default_factory=TokenUsage)
    error_type: str = ""
    # enrichment surfaced to the structured access log (reference: Envoy
    # dynamic-metadata pipeline)
    costs: dict[str, int] = field(default_factory=dict)
    attempts: int = 0
    # the serving replica's per-request id (tpuserve's x-aigw-request-id
    # response header) — joins gateway access-log lines against the
    # replica's /debug/requests/{id} flight-recorder timeline
    upstream_request_id: str = ""
    # the routing decision's audit-ring entry (ISSUE 12, mutable — the
    # ring owner keeps updating it): the access log extracts the
    # compact outcome fields so log lines join the decision ring the
    # same way they join spans and flight timelines
    decision: dict = field(default_factory=dict)

    def _labels(self) -> list[str]:
        return [
            self.operation,
            self.provider,
            self.request_model,
            self.response_model or self.request_model,
        ]

    def record_tokens_emitted(self, n: int) -> None:
        """Called per streamed chunk with content tokens (TTFT/ITL gauges,
        recorded only for streaming — reference processor_impl.go:563)."""
        if n <= 0:
            return
        now = time.monotonic()
        if self.first_token_at == 0.0:
            self.first_token_at = now
            self.metrics.time_to_first_token.labels(*self._labels()).observe(
                now - self.start
            )
        elif self.tokens_seen:
            itl = (now - self.last_token_at) / n
            self.metrics.time_per_output_token.labels(*self._labels()).observe(itl)
        self.last_token_at = now
        self.tokens_seen += n

    def finish(self, usage: TokenUsage, error_type: str = "") -> None:
        self.final_usage = usage
        self.error_type = error_type
        labels = self._labels()
        for token_type, n in (
            ("input", usage.input_tokens),
            ("output", usage.output_tokens),
            ("total", usage.total_tokens),
            ("cached_input", usage.cached_input_tokens),
        ):
            if n:
                self.metrics.token_usage.labels(*labels, token_type).observe(n)
        self.metrics.request_duration.labels(*labels, error_type).observe(
            time.monotonic() - self.start
        )
