"""`aigw-tpu` CLI — run the gateway standalone (reference cmd/aigw:
``aigw run`` embeds the whole system in one process, run.go:91-235).

Subcommands:
  run <config.yaml|bundle-dir|manifest-dir>  start the gateway data plane
  validate <config|manifest-dir>  parse + validate, print summary
  tpuserve <model-config>        start the TPU serving engine (tpuserve)

A manifest directory (CRD YAML files) runs under the reconciling control
plane: edits converge live and per-object Accepted conditions are written
to <dir>/aigw-status.json (config/controller.py).
"""

from __future__ import annotations

# first: where there is no /proc, the boot timeline starts at this import
import aigw_tpu.utils.boot  # noqa: F401, I001

import argparse
import asyncio
import logging
import os
import signal
import sys


def _build_version() -> str:
    """Package version, plus the git revision when running from THIS
    repo's checkout — the reference stamps the same via the Go linker
    (internal/version/version.go Current())."""
    try:
        from importlib.metadata import version as _pkg_version

        base = _pkg_version("aigw-tpu")
    except Exception:  # noqa: BLE001 — uninstalled checkout
        base = "0.1.0"
    try:
        import subprocess

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # only stamp when the repo containing the package IS this
        # project (a venv nested in some unrelated checkout must not
        # report that repo's revision as ours)
        top = subprocess.run(
            ["git", "rev-parse", "--show-toplevel"], cwd=root,
            capture_output=True, text=True, timeout=2,
        ).stdout.strip()
        if not top or not os.path.isdir(os.path.join(top, "aigw_tpu")):
            return base
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=root,
            capture_output=True, text=True, timeout=2,
        ).stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=root,
            capture_output=True, text=True, timeout=2,
        ).stdout.strip()
        if rev:
            return f"{base} ({rev}{'-dirty' if dirty else ''})"
    except Exception:  # noqa: BLE001 — no git / not a checkout
        pass
    return base


class _VersionAction(argparse.Action):
    """Lazy --version: the git stamp's subprocess calls must not tax
    every other CLI invocation's startup."""

    def __init__(self, option_strings, dest, **kwargs):
        super().__init__(option_strings, dest, nargs=0, **kwargs)

    def __call__(self, parser, namespace, values, option_string=None):
        print(f"aigw-tpu {_build_version()}")
        parser.exit(0)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="aigw-tpu")
    parser.add_argument(
        "--version", action=_VersionAction,
        help="print version (with git revision when run from a checkout; "
             "the reference's internal/version linker stamp)")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_run = sub.add_parser("run", help="run the gateway data plane")
    p_run.add_argument("config", nargs="?", default="",
                       help="config YAML, bundle dir, CRD manifest dir "
                            "(watched + reconciled with status conditions), "
                            "or kube:<kubeconfig>|kube:in-cluster to "
                            "list/watch the CRDs on a live cluster with "
                            "Accepted conditions patched onto object "
                            "status; omit to autoconfig from env: "
                            "OPENAI_API_KEY, ANTHROPIC_API_KEY, "
                            "AZURE_OPENAI_*, TPUSERVE_URL)")
    p_run.add_argument("--host", default="127.0.0.1")
    p_run.add_argument("--port", type=int, default=1975)
    p_run.add_argument("--watch-interval", type=float, default=5.0)
    p_run.add_argument("--log-level", default="info")
    p_run.add_argument(
        "--workers", type=int, default=1,
        help="worker processes sharing the port via SO_REUSEPORT "
             "(each runs the full data plane and watches the config; "
             "requires an explicit --port)")
    p_run.add_argument(
        "--reuse-port", action="store_true",
        help="bind with SO_REUSEPORT even with --workers 1, so a "
             "replacement gateway process can bind the same port and "
             "take over before this one drains — the rolling zero-"
             "downtime upgrade path (tests/test_upgrade_e2e.py)")
    p_run.add_argument(
        "--mcp-config", default="",
        help="Claude-Desktop-style mcpServers JSON file: http servers "
             "route through the MCP proxy; stdio servers (command/args) "
             "are spawned and bridged to Streamable HTTP automatically "
             "(the reference's aigw run --mcp-config)")
    p_run.add_argument(
        "--mcp-json", default="",
        help="same as --mcp-config but inline JSON")

    p_val = sub.add_parser("validate", help="validate a config file")
    p_val.add_argument("config")

    p_status = sub.add_parser(
        "status",
        help="print per-object Accepted conditions for a manifest dir "
             "(the reference surfaces these via `kubectl get`; here they "
             "live in <dir>/aigw-status.json, written by the reconciling "
             "gateway, or are computed fresh when no gateway has run)")
    p_status.add_argument("dir", help="CRD manifest directory")
    p_status.add_argument("--json", action="store_true",
                          help="machine-readable output")

    p_tr = sub.add_parser(
        "translate",
        help="compile a config and print the normalized runtime view "
             "(resolved translator pairs, auth kinds, quota rules) as JSON",
    )
    p_tr.add_argument("config")

    p_hc = sub.add_parser(
        "healthcheck",
        help="probe a gateway/tpuserve /health endpoint (exit 0 = healthy)")
    p_hc.add_argument("url", nargs="?", default="http://127.0.0.1:1975")
    p_hc.add_argument("--timeout", type=float, default=5.0)

    p_conv = sub.add_parser(
        "convert", help="import a local HF safetensors dir into an orbax "
                        "checkpoint usable by tpuserve")
    p_conv.add_argument("hf_dir")
    p_conv.add_argument("out_dir")

    p_core = sub.add_parser(
        "core-config",
        help="compile the native proxy core's config (native/aigw-core "
             "serves eligible routes in C++; the rest fall back to the "
             "Python gateway)")
    p_core.add_argument("config")
    p_core.add_argument("-o", "--out", default="aigw-core.json")
    p_core.add_argument("--listen-host", default="0.0.0.0")
    p_core.add_argument("--listen-port", type=int, default=1975)
    p_core.add_argument("--fallback-host", default="127.0.0.1")
    p_core.add_argument("--fallback-port", type=int, default=1976,
                        help="where the Python gateway listens (run it "
                             "with --port matching this)")
    p_core.add_argument("--access-log", default="",
                        help="JSON-lines access log for natively routed "
                             "requests (model/backend/status/duration/"
                             "token usage per line)")

    p_wh = sub.add_parser(
        "webhook",
        help="run the pod mutating webhook: injects the aigw gateway "
             "sidecar into Envoy Gateway pods (the reference's "
             "gateway_mutator role; K8s requires TLS — pass "
             "--tls-cert/--tls-key)")
    p_wh.add_argument("--host", default="0.0.0.0")
    p_wh.add_argument("--port", type=int, default=9443)
    p_wh.add_argument("--image", required=True,
                      help="sidecar image (must provide `python -m "
                           "aigw_tpu` as entrypoint)")
    p_wh.add_argument("--gateway-port", type=int, default=1975)
    p_wh.add_argument("--tls-cert", default="")
    p_wh.add_argument("--tls-key", default="")

    p_quota = sub.add_parser(
        "quota-service",
        help="run the shared quota service: gateways on other nodes "
             "point AIGW_QUOTA_URL here so one token budget is enforced "
             "with no shared filesystem (the reference's network "
             "ratelimit-service role)")
    p_quota.add_argument("--host", default="0.0.0.0")
    p_quota.add_argument("--port", type=int, default=1981)
    p_quota.add_argument("--dir", default="/tmp/aigw-quota",
                         help="counter storage (flock'd files; a shared "
                              "volume lets the service itself replicate)")

    p_serve = sub.add_parser("tpuserve", help="run the TPU serving engine")
    p_serve.add_argument("--model", required=True,
                         help="model name or path (see aigw_tpu.models)")
    p_serve.add_argument("--host", default="127.0.0.1")
    p_serve.add_argument("--port", type=int, default=8011)
    p_serve.add_argument("--max-batch-size", type=int, default=8)
    p_serve.add_argument("--max-seq-len", type=int, default=2048)
    p_serve.add_argument("--page-size", type=int, default=128)
    p_serve.add_argument("--hbm-pages", type=int, default=0,
                         help="KV pages to allocate (0 = auto)")
    p_serve.add_argument("--tp", type=int, default=1,
                         help="tensor-parallel degree (devices on the mesh)")
    p_serve.add_argument("--ep", type=int, default=1,
                         help="expert-parallel degree (MoE families; mesh "
                              "is dp=1 × tp × sp × ep)")
    p_serve.add_argument("--sp", type=int, default=1,
                         help="sequence-parallel degree: prompts >= "
                              "--sp-prefill-min-tokens prefill via ring "
                              "attention over the sp mesh axis")
    p_serve.add_argument("--sp-prefill-min-tokens", type=int, default=1024,
                         help="minimum prompt length routed through the "
                              "sequence-parallel prefill path")
    p_serve.add_argument("--quantize", default="",
                         choices=["", "int8", "int4"],
                         help="weight-only quantization: int8 (W8A16) "
                              "or int4 (W4A16, group-128 scales — "
                              "quarter the HBM weight traffic)")
    p_serve.add_argument("--prefill-chunk-tokens", type=int, default=256,
                         help="chunk prompts longer than this into "
                              "fixed-size prefill steps with decode "
                              "ticks interleaved (0 = off; default on "
                              "so long prompts never stall live "
                              "decodes)")
    p_serve.add_argument("--decode-steps-per-tick", type=int, default=8,
                         help="fused decode steps per host round-trip "
                              "(the adaptive window's MAX; it shrinks "
                              "to 1/4 of this under queue pressure)")
    p_serve.add_argument("--no-adaptive-window", action="store_true",
                         help="pin the decode window at "
                              "--decode-steps-per-tick instead of "
                              "adapting it to queue pressure")
    p_serve.add_argument("--warm-prefill-buckets", type=int, default=0,
                         help="pre-compile batched-prefill programs "
                              "for the N smallest prompt buckets at "
                              "startup (all group sizes) so a traffic "
                              "burst never pays an XLA compile")
    p_serve.add_argument("--warm-decode-buckets", type=int, default=0,
                         help="pre-compile the decode-window ladder "
                              "(and row-update scatters) at the N "
                              "smallest pow2 PAGE buckets so the "
                              "first admission at any covered length "
                              "never compiles a decode program on the "
                              "hot path (0 = only the quiesced bucket)")
    p_serve.add_argument("--prefill-bucket-rungs", type=int, default=2,
                         choices=[1, 2, 4],
                         help="prefill bucket rungs per octave: 1 = "
                              "power-of-two ladder, 2 adds a 1.5xS "
                              "rung, 4 adds 1.25x/1.5x/1.75x — "
                              "tighter rungs cut prompt-padding "
                              "compute (TTFT) at the cost of more "
                              "compiled prefill shapes")
    p_serve.add_argument("--logprobs", type=int, default=0,
                         help="enable per-token logprobs: max "
                              "top_logprobs servable per request "
                              "(0 = off; OpenAI caps requests at 20)")
    p_serve.add_argument("--spec-tokens", type=int, default=0,
                         help="speculative decoding: max draft tokens "
                              "verified per decode step (0 = off). "
                              "Drafts come from n-gram prompt lookup "
                              "plus prefix-cache continuations; an "
                              "adaptive per-slot ladder collapses to "
                              "plain decode when acceptance is poor, "
                              "so it is safe to leave on")
    p_serve.add_argument("--no-spec-adaptive", action="store_true",
                         help="pin the speculative draft length at "
                              "--spec-tokens instead of the adaptive "
                              "rung ladder (A/B + determinism knob)")
    p_serve.add_argument("--no-speculation", action="store_true",
                         help="force speculative decoding off "
                              "(overrides --spec-tokens)")
    p_serve.add_argument("--attention-backend", default="xla-bucketed",
                         choices=["xla-bucketed", "pallas-ragged"],
                         help="prefill attention backend: xla-bucketed "
                              "pads each prompt to a per-sequence "
                              "bucket rung; pallas-ragged packs a "
                              "mixed-length admission burst into ONE "
                              "ragged paged-attention program sized by "
                              "total tokens (padded to a token-budget "
                              "chunk), with prefix-cache resumes and "
                              "chunked continuations as start offsets. "
                              "Auto-falls back to XLA attention "
                              "off-TPU and to xla-bucketed on a mesh")
    p_serve.add_argument("--kv-cache-dtype", default="bfloat16",
                         choices=["bfloat16", "float32", "int8", "int4"],
                         help="KV page element dtype. int8/int4 store "
                              "quantized pages + per-page scale blocks "
                              "(~0.52x / ~0.27x the bf16 KV bytes at "
                              "head_dim 128 — more concurrent sessions "
                              "per chip), dequantized at the read")
    p_serve.add_argument("--ragged-chunk-tokens", type=int, default=256,
                         help="pallas-ragged padding granule: packed "
                              "totals pad to multiples of this (the "
                              "compiled-program ladder is its "
                              "multiples up to 8 chunks per call)")
    p_serve.add_argument("--no-prefix-cache", action="store_true",
                         help="disable automatic prompt prefix caching")
    p_serve.add_argument("--no-constrained-decoding", action="store_true",
                         help="disable grammar-constrained decoding "
                              "(response_format json modes + tool "
                              "calling); such requests then 400 with a "
                              "clear error instead of being enforced")
    p_serve.add_argument("--flight-entries", type=int, default=256,
                         help="flight-recorder ring size: per-request "
                              "lifecycle timelines kept in memory and "
                              "served at /debug/requests (slow-request "
                              "worst-N entries survive eviction)")
    p_serve.add_argument("--enable-profile-endpoint", action="store_true",
                         help="enable /debug/profile?seconds=N on-demand "
                              "jax.profiler captures (off by default: a "
                              "profiler on the data port is an "
                              "inspection/DoS surface)")
    p_serve.add_argument("--lora", action="append", default=[],
                         metavar="NAME=ORBAX_DIR",
                         help="register a LoRA adapter in the zoo "
                              "(repeatable); serve it via model "
                              "'<base>:<name>'")
    p_serve.add_argument("--lora-slots", type=int, default=0,
                         help="device rows for resident adapters; the "
                              "rest of the zoo hot-loads on demand with "
                              "refcounted LRU eviction (0 = one row per "
                              "registered adapter)")
    p_serve.add_argument("--tenant-slot-cap", type=int, default=0,
                         help="max in-flight decode slots one tenant "
                              "(x-aigw-tenant / adapter suffix) may hold "
                              "— the fairness guard against one "
                              "tenant's burst starving others (0 = off)")
    p_serve.add_argument("--migration-young-tokens", type=int,
                         default=64,
                         help="migration-eligibility window: a slot "
                              "counts as migratable on /state while its "
                              "generated tokens are at most this "
                              "(prefill done, decode young — the "
                              "gateway's disaggregation signal; 0 = "
                              "every decoding slot counts)")
    p_serve.add_argument("--drain-grace", type=float, default=30.0,
                         help="graceful-shutdown budget in seconds "
                              "(ISSUE 14): on SIGTERM/SIGINT the "
                              "server flips draining (new admissions "
                              "503 with Retry-After, /state reports "
                              "draining: true), waits up to this long "
                              "for live slots to finish or migrate "
                              "off, then exits 0; a second signal "
                              "skips the wait")
    p_serve.add_argument("--kv-host-bytes", type=int, default=0,
                         help="byte budget of the host-RAM KV spill "
                              "tier (ISSUE 11): cache-registered pages "
                              "evicted under pool pressure are copied "
                              "device->host and revived by later "
                              "prefix hits instead of recomputed; 0 "
                              "disables the tier")
    p_serve.add_argument("--weights", default="", choices=["", "random"],
                         help="'random' serves the model from seeded "
                              "random weights (chip bring-up, where no "
                              "checkpoint exists); default: the model "
                              "registry's source")
    p_serve.add_argument("--platform", default="",
                         help="JAX platform to serve on (e.g. cpu for "
                              "the fake-chip mode). Default: whatever "
                              "JAX_PLATFORMS names; when neither names "
                              "one a TPU is REQUIRED and boot fails "
                              "naming what JAX found instead")
    p_serve.add_argument("--log-level", default="info")

    args = parser.parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, getattr(args, "log_level", "info").upper(), 20),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )

    if args.cmd == "validate":
        from aigw_tpu.config.controller import Reconciler, is_manifest_dir
        from aigw_tpu.config.model import ConfigError, load_config

        def report_rejections(rec) -> int:
            bad = sorted(rec.not_accepted().items())
            for key, cond in bad:
                print(f"NOT ACCEPTED {key}: {cond['message']}",
                      file=sys.stderr)
            return len(bad)

        try:
            if args.config.startswith("kube:"):
                # one-shot cluster dry run: list the CRDs, reconcile,
                # print per-object rejections — no status writeback
                import tempfile

                from aigw_tpu.config.kube import (
                    KubeReconciler,
                    KubeSource,
                    parse_kube_target,
                )

                source = KubeSource(parse_kube_target(args.config))
                source.start()
                try:
                    if not source.wait_synced(30.0):
                        print("INVALID: API server never synced",
                              file=sys.stderr)
                        return 1
                    with tempfile.NamedTemporaryFile(
                            suffix=".json") as tf:
                        rec = KubeReconciler(source,
                                             status_path=tf.name,
                                             leader_election=False,
                                             dry_run=True)
                        cfg = rec.load()
                    if report_rejections(rec):
                        return 1
                finally:
                    source.stop()
            elif is_manifest_dir(args.config):
                # reconcile dry run: per-object conditions to stdout
                import tempfile

                with tempfile.NamedTemporaryFile(suffix=".json") as tf:
                    rec = Reconciler(args.config, status_path=tf.name)
                    cfg = rec.load()
                if report_rejections(rec):
                    return 1
            else:
                cfg = load_config(args.config)
        except ConfigError as e:
            print(f"INVALID: {e}", file=sys.stderr)
            return 1
        except (OSError, ValueError) as e:
            # bad kubeconfig / unreadable file: same INVALID contract as
            # every other validate failure, never a raw traceback
            print(f"INVALID: {e}", file=sys.stderr)
            return 1
        print(
            f"OK: {len(cfg.backends)} backends, {len(cfg.routes)} routes, "
            f"{len(cfg.models)} models, {len(cfg.llm_request_costs)} cost metrics"
        )
        return 0

    if args.cmd == "status":
        import json as _json
        import os as _os

        from aigw_tpu.config.controller import Reconciler, is_manifest_dir

        if not is_manifest_dir(args.dir):
            print(f"{args.dir}: not a CRD manifest directory",
                  file=sys.stderr)
            return 2
        # Always reconcile live (a dry run against a temp status path) so
        # the exit code reflects the manifests as they are NOW; the
        # running gateway's aigw-status.json is only preferred when its
        # per-object observedChecksums match the live view — a dead
        # gateway's stale file must not mask a broken (or fixed) edit.
        import tempfile

        with tempfile.NamedTemporaryFile(suffix=".json") as tf:
            rec = Reconciler(args.dir, status_path=tf.name)
            rec.load()
        conditions = rec.conditions()
        source = "live"
        status_file = _os.path.join(args.dir, "aigw-status.json")
        if _os.path.exists(status_file):
            try:
                with open(status_file, encoding="utf-8") as f:
                    file_conds = _json.load(f).get("objects", {})
            except (OSError, _json.JSONDecodeError):
                file_conds = None
            def _view(c: dict) -> dict:
                return {k: (v.get("status"), v.get("observedChecksum"))
                        for k, v in c.items()}
            if file_conds and _view(file_conds) == _view(conditions):
                conditions = file_conds
                source = "aigw-status.json"
            elif file_conds is not None:
                source = "live (aigw-status.json stale)"
        if args.json:
            print(_json.dumps({"source": source, "objects": conditions},
                              indent=1, sort_keys=True))
            return 0 if all(c.get("status") == "True"
                            for c in conditions.values()) else 1
        bad = 0
        for key in sorted(conditions):
            cond = conditions[key]
            accepted = cond.get("status") == "True"
            bad += not accepted
            mark = "Accepted" if accepted else "NOT ACCEPTED"
            line = f"{mark:13s} {key}"
            if not accepted:
                line += f"  [{cond.get('reason', '')}] {cond.get('message', '')}"
            print(line)
        print(f"-- {len(conditions)} objects, {bad} not accepted "
              f"(source: {source})")
        return 1 if bad else 0

    if args.cmd == "healthcheck":
        import json as _json
        import urllib.error
        import urllib.request

        try:
            with urllib.request.urlopen(
                args.url.rstrip("/") + "/health", timeout=args.timeout
            ) as resp:
                data = _json.loads(resp.read())
        except (urllib.error.URLError, OSError, ValueError) as e:
            print(f"UNHEALTHY: {e}", file=sys.stderr)
            return 1
        if data.get("status") != "ok":
            print(f"UNHEALTHY: {data}", file=sys.stderr)
            return 1
        print(_json.dumps(data))
        return 0

    if args.cmd == "core-config":
        from aigw_tpu.config.model import ConfigError, load_config
        from aigw_tpu.config.nativecore import (
            compile_core_config,
            write_core_config,
        )

        try:
            cfg = load_config(args.config)
        except ConfigError as e:
            print(f"INVALID: {e}", file=sys.stderr)
            return 1
        core, skipped = compile_core_config(
            cfg,
            listen_host=args.listen_host,
            listen_port=args.listen_port,
            fallback_host=args.fallback_host,
            fallback_port=args.fallback_port,
            access_log_path=args.access_log,
        )
        write_core_config(args.out, core)
        print(f"{args.out}: {len(core['rules'])} native rules, "
              f"fallback {args.fallback_host}:{args.fallback_port}")
        for s in skipped:
            print(f"  python-path: {s}")
        if cfg.llm_request_costs and args.access_log:
            # without the tailer, native requests' costs are silently
            # never computed — make the wiring requirement explicit
            print(f"  REMINDER: run the gateway with "
                  f"AIGW_CORE_ACCESS_LOG={args.access_log} so native "
                  f"requests get spans + post-hoc cost accounting")
        return 0

    if args.cmd == "translate":
        import json as _json

        from aigw_tpu.config.model import (
            APISchemaName,
            ConfigError,
            load_config,
        )
        from aigw_tpu.config.runtime import RuntimeConfig
        from aigw_tpu.translate import Endpoint, TranslationError, get_translator

        try:
            cfg = load_config(args.config)
            rc = RuntimeConfig.build(cfg)
        except ConfigError as e:
            print(f"INVALID: {e}", file=sys.stderr)
            return 1
        routes = []
        for route in cfg.routes:
            rules = []
            for rule in route.rules:
                backends = []
                for ref in rule.backends:
                    b = cfg.backend(ref.backend)
                    try:
                        # probe: is OpenAI-front chat translatable here?
                        get_translator(Endpoint.CHAT_COMPLETIONS,
                                       APISchemaName.OPENAI, b.schema.name)
                        chat_ok = True
                    except TranslationError:
                        chat_ok = False
                    backends.append({
                        "backend": ref.backend,
                        "weight": ref.weight,
                        "priority": ref.priority,
                        "schema": b.schema.name.value,
                        "auth": b.auth.kind.value,
                        "chat_translation": chat_ok,
                    })
                rules.append({
                    "models": list(rule.models),
                    "model_prefixes": list(rule.model_prefixes),
                    "backends": backends,
                })
            routes.append({"name": route.name, "rules": rules})
        print(_json.dumps({
            "version": cfg.version,
            "routes": routes,
            "models": [m.name for m in cfg.models],
            "costs": [c.to_dict() for c in cfg.llm_request_costs],
            "quotas": len(rc.rate_limiter.rules),
            "mcp_backends": len((cfg.mcp or {}).get("backends", [])),
        }, indent=2))
        return 0

    if args.cmd == "convert":
        from aigw_tpu.models.checkpoint import (
            import_hf_checkpoint,
            save_checkpoint,
        )

        params = import_hf_checkpoint(args.hf_dir)
        save_checkpoint(params, args.out_dir)
        print(f"converted {len(params)} tensors -> {args.out_dir}")
        return 0

    if args.cmd == "run":
        from aigw_tpu.config.model import ConfigError

        try:
            if getattr(args, "workers", 1) > 1:
                if getattr(args, "mcp_config", "") or \
                        getattr(args, "mcp_json", ""):
                    # each worker would spawn its OWN copy of every
                    # stdio server and SO_REUSEPORT would spray one MCP
                    # session across divergent children — run the stdio
                    # server once and point an http entry at it instead
                    print("config error: --mcp-config/--mcp-json is "
                          "incompatible with --workers > 1 (stateful "
                          "stdio servers would be spawned per worker); "
                          "bridge the server once and use an http url",
                          file=sys.stderr)
                    return 1
                return _run_gateway_workers(args)
            return asyncio.run(_run_gateway(
                args, reuse_port=getattr(args, "reuse_port", False)))
        except ConfigError as e:
            print(f"config error: {e}", file=sys.stderr)
            return 1
    if args.cmd == "webhook":
        import ssl as _ssl

        from aiohttp import web as _web

        from aigw_tpu.config.webhook import webhook_app

        logging.basicConfig(level=logging.INFO)
        app = webhook_app(args.image, port=args.gateway_port)
        if bool(args.tls_cert) != bool(args.tls_key):
            # half a TLS config must fail loudly — with failurePolicy
            # Ignore on the API-server side, a silently-plain-HTTP
            # webhook means pods are just never mutated
            print("webhook: --tls-cert and --tls-key must be provided "
                  "together", file=sys.stderr)
            return 1
        ssl_ctx = None
        if args.tls_cert and args.tls_key:
            ssl_ctx = _ssl.SSLContext(_ssl.PROTOCOL_TLS_SERVER)
            ssl_ctx.load_cert_chain(args.tls_cert, args.tls_key)
        print(f"webhook listening on "
              f"{'https' if ssl_ctx else 'http'}://{args.host}:{args.port}"
              f"/mutate (sidecar image {args.image})", flush=True)
        _web.run_app(app, host=args.host, port=args.port,
                     ssl_context=ssl_ctx, print=None)
        return 0

    if args.cmd == "quota-service":
        from aiohttp import web as _web

        from aigw_tpu.gateway.ratelimit import quota_service_app

        logging.basicConfig(level=logging.INFO)
        app = quota_service_app(args.dir)
        print(f"quota service listening on http://{args.host}:{args.port}"
              f" (dir={args.dir})", flush=True)
        _web.run_app(app, host=args.host, port=args.port, print=None)
        return 0

    if args.cmd == "tpuserve":
        from aigw_tpu.utils.boot import BootError, boot_jax

        try:
            boot_jax(args.platform)
        except BootError as e:
            print(f"tpuserve: {e}", file=sys.stderr)
            return 1
        return asyncio.run(_run_tpuserve(args))
    return 2


def _run_gateway_workers(args: argparse.Namespace) -> int:
    """Multi-worker mode: N processes share the port via SO_REUSEPORT,
    the kernel spreading accepted connections across them — the
    horizontal-scaling answer to the reference's multi-threaded Envoy
    core (CPython's GIL caps one process at one core). Each worker runs
    the complete data plane, including its own config watcher, so hot
    reloads converge within --watch-interval on every worker. Encrypted
    MCP sessions are worker-agnostic by construction; token-quota
    budgets and /v1/responses transcripts are shared through flock'd
    files (AIGW_QUOTA_DIR / AIGW_RESPONSES_DIR, exported below) so a
    configured budget stays ONE budget across workers and a
    previous_response_id resolves on whichever worker the follow-up
    lands on."""
    import multiprocessing
    import os
    import secrets

    if args.port == 0:
        print("--workers requires an explicit --port (SO_REUSEPORT "
              "workers must bind the same port)", file=sys.stderr)
        return 1
    # MCP session tokens are encrypted with mcp.session_seed; when it's
    # unconfigured each process would otherwise mint its own random seed
    # and tokens issued by one worker would 404 on the others. One
    # process-group seed (inherited through the spawn env) keeps
    # sessions valid on every worker.
    os.environ.setdefault("AIGW_MCP_SESSION_SEED", secrets.token_hex(32))
    # Cross-worker shared state (inherited through the spawn env): one
    # token-quota budget enforced across all workers, and response
    # transcripts reachable from whichever worker the follow-up
    # previous_response_id request lands on.
    if not (os.environ.get("AIGW_QUOTA_DIR")
            and os.environ.get("AIGW_RESPONSES_DIR")):
        import atexit
        import shutil
        import tempfile

        shared = tempfile.mkdtemp(prefix=f"aigw-shared-{args.port}-")
        atexit.register(shutil.rmtree, shared, ignore_errors=True)
        os.environ.setdefault("AIGW_QUOTA_DIR",
                              os.path.join(shared, "quota"))
        os.environ.setdefault("AIGW_RESPONSES_DIR",
                              os.path.join(shared, "responses"))
    ctx = multiprocessing.get_context("spawn")
    procs = [
        ctx.Process(target=_gateway_worker_main, args=(args,), daemon=True)
        for _ in range(args.workers - 1)
    ]
    for p in procs:
        p.start()
    try:
        return asyncio.run(_run_gateway(args, reuse_port=True))
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.join(timeout=5)


def _gateway_worker_main(args: argparse.Namespace) -> None:
    asyncio.run(_run_gateway(args, reuse_port=True))


async def _run_gateway(args: argparse.Namespace,
                       reuse_port: bool = False) -> int:
    from aigw_tpu.config.runtime import RuntimeConfig
    from aigw_tpu.config.watcher import ConfigWatcher
    from aigw_tpu.gateway.server import run_gateway

    holder = {}

    def on_reload(rc):
        server = holder.get("server")
        if server is not None:
            server.set_runtime(rc)

    # --mcp-config / --mcp-json: canonical mcpServers JSON; stdio
    # servers spawn + bridge to local Streamable HTTP first, then every
    # server (http + bridged) merges into the MCP proxy's backends —
    # re-applied on config reloads via the watcher transform
    bridges: list = []
    transform = None
    mcp_text = ""
    if getattr(args, "mcp_config", ""):
        with open(os.path.expanduser(args.mcp_config),
                  encoding="utf-8") as f:
            mcp_text = f.read()
    elif getattr(args, "mcp_json", ""):
        mcp_text = args.mcp_json
    if mcp_text:
        import dataclasses

        from aigw_tpu.mcp.stdio_bridge import (
            parse_mcp_servers,
            start_bridges,
        )

        try:
            http_backends, stdio_specs = parse_mcp_servers(mcp_text)
            bridged_backends, bridges = await start_bridges(stdio_specs)
        except ValueError as e:
            print(f"config error: {e}", file=sys.stderr)
            return 1
        mcp_backends = http_backends + bridged_backends
        print(f"mcp: {len(mcp_backends)} server(s): "
              f"{', '.join(b['name'] for b in mcp_backends)}"
              + (f" ({len(bridged_backends)} stdio-bridged)"
                 if bridged_backends else ""),
              flush=True)

        def transform(cfg):
            mcp = dict(cfg.mcp or {})
            existing = list(mcp.get("backends") or ())
            have = {b.get("name") for b in existing}
            mcp["backends"] = existing + [
                b for b in mcp_backends if b["name"] not in have]
            return dataclasses.replace(cfg, mcp=mcp)

    try:
        watcher = None
        if args.config:
            watcher = ConfigWatcher(args.config, on_reload,
                                    interval=args.watch_interval,
                                    transform=transform)
            runtime = watcher.load_initial()
        else:
            from aigw_tpu.config.autoconfig import autoconfig_from_env

            cfg = autoconfig_from_env()
            if transform is not None:
                cfg = transform(cfg)
            print(f"autoconfig: {len(cfg.backends)} backend(s): "
                  f"{', '.join(b.name for b in cfg.backends)}", flush=True)
            runtime = RuntimeConfig.build(cfg)
        server, runner = await run_gateway(runtime, host=args.host,
                                           port=args.port,
                                           reuse_port=reuse_port)
        holder["server"] = server
        if watcher is not None:
            server.conditions_fn = watcher.not_accepted
            await watcher.start()
        # native-core telemetry: when the C++ core's access log is
        # shared with us (AIGW_CORE_ACCESS_LOG), tail it into real OTel
        # spans and post-hoc CEL costs (obs/native_spans.py)
        tailer = None
        core_log = os.environ.get("AIGW_CORE_ACCESS_LOG", "")
        if core_log:
            from aigw_tpu.obs.native_spans import (
                NativeLogTailer,
                make_cost_fn,
            )

            tailer = NativeLogTailer(
                core_log, server.tracer,
                cost_fn=make_cost_fn(
                    lambda: getattr(holder.get("server"), "_runtime",
                                    None),
                    getattr(server, "_cost_sink", None)))
            tailer.start()
            print(f"native-core telemetry: tailing {core_log}",
                  flush=True)
        print(f"gateway listening on http://{args.host}:{args.port}",
              flush=True)
        await _wait_for_signal()
        # Graceful drain (Envoy's listener-drain role in the reference's
        # rolling upgrades): stop accepting first, then give connections
        # the kernel had already handed us a grace window to deliver and
        # finish their in-flight request before cleanup closes
        # everything.
        import os as _os

        for site in list(runner.sites):
            await site.stop()
        try:
            drain = float(_os.environ.get("AIGW_DRAIN_SECONDS", "1.0"))
        except ValueError:
            drain = 1.0
        if drain > 0:
            await asyncio.sleep(drain)
        if watcher is not None:
            await watcher.stop()
        if tailer is not None:
            await asyncio.to_thread(tailer.stop)
        await runner.cleanup()
        return 0
    finally:
        # terminate stdio MCP children on EVERY exit path — a config
        # error or failed bind must not orphan spawned servers
        for bridge in bridges:
            await bridge.stop()


async def _run_tpuserve(args: argparse.Namespace) -> int:
    from aigw_tpu.tpuserve.server import run_tpuserve

    lora_adapters = {}
    for spec_str in args.lora:
        name, _, path = spec_str.partition("=")
        if not name or not path:
            print(f"--lora expects NAME=ORBAX_DIR, got {spec_str!r}",
                  file=sys.stderr)
            return 1
        from aigw_tpu.models.checkpoint import restore_checkpoint

        lora_adapters[name] = restore_checkpoint(path)
    runner = await run_tpuserve(
        model=args.model,
        host=args.host,
        port=args.port,
        max_batch_size=args.max_batch_size,
        max_seq_len=args.max_seq_len,
        page_size=args.page_size,
        hbm_pages=args.hbm_pages,
        tp=args.tp,
        ep=args.ep,
        sp=args.sp,
        quantize=args.quantize,
        weights=args.weights,
        lora_adapters=lora_adapters or None,
        lora_slots=args.lora_slots,
        tenant_slot_cap=args.tenant_slot_cap,
        decode_steps_per_tick=args.decode_steps_per_tick,
        enable_prefix_cache=not args.no_prefix_cache,
        sp_prefill_min_tokens=args.sp_prefill_min_tokens,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        spec_tokens=0 if args.no_speculation else args.spec_tokens,
        spec_adaptive=not args.no_spec_adaptive,
        attention_backend=args.attention_backend,
        kv_cache_dtype=args.kv_cache_dtype,
        ragged_chunk_tokens=args.ragged_chunk_tokens,
        logprobs_topk=args.logprobs,
        adaptive_decode_window=not args.no_adaptive_window,
        warm_prefill_buckets=args.warm_prefill_buckets,
        warm_decode_buckets=args.warm_decode_buckets,
        prefill_bucket_rungs=args.prefill_bucket_rungs,
        flight_entries=args.flight_entries,
        enable_profile_endpoint=args.enable_profile_endpoint,
        migration_young_tokens=args.migration_young_tokens,
        constrained_decoding=not args.no_constrained_decoding,
        kv_host_bytes=args.kv_host_bytes,
    )
    print(f"tpuserve listening on http://{args.host}:{args.port}", flush=True)
    # graceful shutdown (ISSUE 14): the first SIGTERM/SIGINT drains —
    # 503 new admissions, wait out live slots — then exits 0; a second
    # signal skips the wait
    server = runner.app["tpuserve_server"]
    stop = asyncio.Event()
    server.install_signal_drain(stop, grace_s=args.drain_grace)
    await stop.wait()
    await runner.cleanup()
    return 0


async def _wait_for_signal() -> None:
    loop = asyncio.get_running_loop()
    stop = asyncio.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()


if __name__ == "__main__":
    sys.exit(main())
