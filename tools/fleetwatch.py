#!/usr/bin/env python3
"""fleetwatch — a tiny ``watch``-style view of the gateway's fleet.

Renders ``GET /fleet/state`` (the ISSUE 12 fleet observability plane)
as a per-replica table: health, slots, queue, worst KV / HBM pressure,
SLO burn rate, and telemetry staleness — the terminal companion for
benchmark runs and the MULTICHIP dryrun, where tailing N replica ``/state``
endpoints by hand stops scaling at N=2.

``--tenants`` switches to the usage-metering view (ISSUE 20): one row
per tenant rendered from ``GET /usage`` — tokens, measured decode
tok/s over the ledger span, KV residency, priced cost, and the budget
burn machine (burn rate + the K-consecutive-windows sustained flag).

Usage:
    python tools/fleetwatch.py http://127.0.0.1:1975 [--interval 2]
    python tools/fleetwatch.py http://127.0.0.1:1975 --once
    python tools/fleetwatch.py http://127.0.0.1:1975 --tenants --once

stdlib-only (urllib) on purpose: it must run anywhere the gateway runs,
including bare containers without aiohttp installed for the client.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.error
import urllib.request

_COLUMNS = ("REPLICA", "HEALTH", "SLOTS", "QUEUE", "BQUEUE", "BACT",
            "BPRE", "KV%", "HBM%", "BURN", "GOODPUT", "STALE(s)",
            "UPTIME(s)")


def fetch(url: str, timeout: float = 5.0) -> dict:
    with urllib.request.urlopen(url.rstrip("/") + "/fleet/state",
                                timeout=timeout) as resp:
        return json.loads(resp.read().decode("utf-8"))


def fetch_usage(url: str, timeout: float = 5.0) -> dict:
    with urllib.request.urlopen(url.rstrip("/") + "/usage",
                                timeout=timeout) as resp:
        return json.loads(resp.read().decode("utf-8"))


def _fmt(v, pct: bool = False) -> str:
    if v is None:
        return "-"
    if isinstance(v, (int, float)) and v < 0:
        return "-"  # -1 sentinels: no data yet
    if pct:
        return f"{100.0 * float(v):.0f}"
    if isinstance(v, float):
        return f"{v:.2f}"
    return str(v)


def render_table(snapshot: dict) -> str:
    """One /fleet/state payload → the table string (pure function —
    the tier-1 smoke drives it against a live gateway's snapshot)."""
    lines: list[str] = []
    widths = [22, 9, 7, 6, 6, 5, 5, 5, 5, 6, 8, 9, 10]

    def row(cells) -> str:
        return "  ".join(str(c).ljust(w)[:max(w, len(str(c)))]
                         for c, w in zip(cells, widths)).rstrip()

    for name, b in sorted((snapshot.get("backends") or {}).items()):
        lines.append(f"pool {name}")
        lines.append(row(_COLUMNS))
        for addr, r in sorted((b.get("replicas") or {}).items()):
            h = (r.get("health") or {}).get("state", "?")
            if (r.get("health") or {}).get("draining"):
                h = "draining"
            slo = r.get("slo") or {}
            lines.append(row((
                addr, h,
                f"{r.get('active_slots', 0)}/{r.get('max_slots', 0)}",
                r.get("queued", 0),
                # offline class footprint (ISSUE 19): queued+parked
                # batch work, batch-held slots, preemption churn
                r.get("batch_queued", 0),
                r.get("batch_active", 0),
                r.get("batch_preemptions", 0),
                _fmt(r.get("kv_occupancy"), pct=True),
                _fmt(r.get("device_memory_frac_worst"), pct=True),
                _fmt(slo.get("burn_rate")),
                _fmt(slo.get("goodput")),
                _fmt(r.get("staleness_s")),
                _fmt(round(float(r.get("uptime_s", 0.0)))),
            )))
        ru = b.get("rollup") or {}
        slo = b.get("slo") or {}
        lines.append(
            f"  up {ru.get('replicas_up', 0)}"
            f" degraded {ru.get('replicas_degraded', 0)}"
            f" draining {ru.get('replicas_draining', 0)}"
            f" down {ru.get('replicas_down', 0)}"
            f" | slots {ru.get('slots_free', 0)}/"
            f"{ru.get('slots_total', 0)} free"
            f" | worst kv {_fmt(ru.get('kv_occupancy_worst'), pct=True)}%"
            f" | fleet burn {_fmt(slo.get('burn_rate'))}"
            + (" ** SUSTAINED SLO OVERSHOOT **"
               if slo.get("sustained_overshoot") else ""))
        ctl = b.get("controller")
        if ctl:
            # fleet control plane (ISSUE 14): scaling decisions, drains
            # in progress, and the last lifecycle actions
            c = ctl.get("counters") or {}
            lines.append(
                f"  controller [{ctl.get('min_replicas', '?')}.."
                f"{ctl.get('max_replicas', '?')}]"
                f" live {len(ctl.get('replicas_live') or ())}"
                f" | out {c.get('scale_outs', 0)}"
                f" in {c.get('scale_ins', 0)}"
                f" drains {c.get('drains', 0)}"
                f" failovers {c.get('failovers', 0)}"
                f" launch-fail {c.get('launch_failures', 0)}"
                + (f" | launching {ctl.get('launches_in_flight')}"
                   if ctl.get("launches_in_flight") else "")
                + (f" | DRAINING {', '.join(ctl['drains_in_progress'])}"
                   if ctl.get("drains_in_progress") else ""))
            for ev in list(ctl.get("events") or ())[-3:]:
                lines.append(
                    "    "
                    + time.strftime("%H:%M:%S",
                                    time.localtime(ev.get("ts", 0)))
                    + f" {ev.get('action', '?')}"
                    + (f" {ev['replica']}" if ev.get("replica") else "")
                    + (f" ({ev['reason']})" if ev.get("reason") else ""))
        lines.append("")
    lines.append(
        f"decisions recorded: {snapshot.get('decisions_recorded', 0)}")
    return "\n".join(lines)


_TENANT_COLUMNS = ("TENANT", "REQS", "PREFILL", "REUSED", "DECODE",
                   "TOK/S", "HBM PB·S", "HOST PB·S", "COST", "BURN",
                   "BUDGET")


def render_tenants_table(payload: dict) -> str:
    """One ``GET /usage`` payload → the per-tenant table string (pure
    function — the tier-1 smoke drives it against a live gateway).

    TOK/S is measured decode throughput over each tenant's ledger span
    (first to last record); BURN is the budget burn machine's latest
    closed-window rate, flagged ``!OVER`` past 1.0 and ``!SUSTAINED``
    after K consecutive over-budget windows."""
    lines: list[str] = []
    widths = [16, 6, 9, 8, 8, 8, 10, 10, 8, 10, 8]

    def row(cells) -> str:
        return "  ".join(str(c).ljust(w)[:max(w, len(str(c)))]
                         for c, w in zip(cells, widths)).rstrip()

    lines.append(f"usage window {payload.get('window_s', 0)}s, "
                 f"{payload.get('retained_windows', 0)} closed "
                 "window(s) retained")
    lines.append(row(_TENANT_COLUMNS))
    for tenant, t in sorted((payload.get("tenants") or {}).items()):
        span = float(t.get("t1", 0.0)) - float(t.get("t0", 0.0))
        decode = int(t.get("decode_tokens", 0))
        tok_s = decode / span if span > 0 else -1.0
        budget = t.get("budget") or {}
        burn = budget.get("burn_rate", -1.0)
        flag = ("!SUSTAINED" if budget.get("sustained")
                else "!OVER" if budget.get("over_budget") else "")
        lines.append(row((
            tenant or "(anonymous)",
            t.get("records", 0),
            t.get("prefill_tokens", 0),
            t.get("prefix_reused_tokens", 0),
            decode,
            _fmt(round(tok_s, 2) if tok_s >= 0 else -1),
            _fmt(t.get("hbm_page_byte_s")),
            _fmt(t.get("host_page_byte_s")),
            t.get("cost", 0),
            (_fmt(burn) + flag) if flag else _fmt(burn),
            _fmt(budget.get("budget") or None),
        )))
    tot = payload.get("totals") or {}
    lines.append(
        f"  totals: {tot.get('records', 0)} reqs"
        f" | prefill {tot.get('prefill_tokens', 0)}"
        f" (+{tot.get('prefill_padded_tokens', 0)} padded geometry,"
        f" {tot.get('prefix_reused_tokens', 0)} cache-reused)"
        f" | decode {tot.get('decode_tokens', 0)}"
        f" | cost {tot.get('cost', 0)}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("url", help="gateway base url, e.g. "
                    "http://127.0.0.1:1975")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="refresh seconds (default 2)")
    ap.add_argument("--once", action="store_true",
                    help="print one snapshot and exit (scripts, tests)")
    ap.add_argument("--tenants", action="store_true",
                    help="per-tenant usage/cost/burn view from "
                    "GET /usage instead of the replica table")
    args = ap.parse_args(argv)
    while True:
        try:
            if args.tenants:
                out = render_tenants_table(fetch_usage(args.url))
            else:
                out = render_table(fetch(args.url))
        except (urllib.error.URLError, OSError, ValueError) as e:
            print(f"fleetwatch: {args.url}: {e}", file=sys.stderr)
            if args.once:
                return 1
            time.sleep(args.interval)
            continue
        if args.once:
            print(out)
            return 0
        # clear + home, watch-style
        sys.stdout.write("\x1b[2J\x1b[H")
        print(time.strftime("%H:%M:%S"), args.url)
        print(out, flush=True)
        time.sleep(args.interval)


if __name__ == "__main__":
    raise SystemExit(main())
