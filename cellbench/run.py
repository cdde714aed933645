#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``, as a new process::

    python cellbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts the cell's replica(s) through ``python -m aigw_tpu tpuserve``
(via ``serve_child.py``, which only registers the configuration file's
model) and ``python -m aigw_tpu run`` in front with the endpoint picker
on; sends the mix's lead-in (set-up); measures for ``--seconds``; stops
every child; prints the contract's JSON object as the LAST line of
stdout. Every request goes to the gateway's ``/v1/chat/completions``,
streaming. ``--seed`` drives the traffic only: the program seeds its own
weights (``PRNGKey(0)``, ``server.py:_load_params``).

This process never imports jax: a chip belongs to one process at a time
and the replica needs it. Without a TPU the replica refuses to boot
(``aigw_tpu/utils/boot.py``) and this exits non-zero, printing no result.

Everything that belongs to one cell is data found by name: the
configuration (``configs/``), the mix (``traffic/``), each metric's
definition (``e2e_metrics/``, ``layer_metrics/``) and its reader
(``readers/``). There is no branch here on a cell's, a configuration's
or a metric's name.
"""

from __future__ import annotations

_T_START = __import__("time").monotonic()  # set-up counts from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, CHECKOUT)

import aiohttp  # noqa: E402

from cellbench import stats  # noqa: E402
from cellbench.client import Driver, lateness_ms  # noqa: E402
from cellbench.stack import HarnessError, Stack  # noqa: E402
from cellbench.traffic import Schedule, tour_steps  # noqa: E402

#: seconds of profiler capture in a traced run, in mid-window
PROFILE_S = 4.0
#: the scrape that closes the counters' window may end this late, at
#: most; a later one voids the window's counters (no metric reads them)
SCRAPE_LATE_S = 1.0
#: seconds after the window in which requests in flight may finish
DRAIN_S = 90.0
#: what the gateway bills by must equal what the engine metered
LEDGER_KEYS = ("records", "prefill_tokens", "prefill_padded_tokens",
               "prefix_reused_tokens", "decode_tokens", "spec_drafted",
               "spec_accepted")


def log(msg: str) -> None:
    print(f"[cellbench +{time.monotonic() - _T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(CHECKOUT, *parts)) as f:
        return json.load(f)


def named(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise HarnessError(f"BENCHMARK.json has no {what} named {name!r}")


def cell_metrics(manifest: dict, kind: str, cell: str) -> list[dict]:
    """The manifest's metrics of ``kind`` that this cell reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(kind: str, name: str, ctx: dict) -> float | None:
    """A metric's value through its definition file and its reader. The
    file is the QUANTITY's: a metric named ``<quantity>.<variant>`` is
    the same quantity under a name of its own, which is how the manifest
    gives it another ``moves`` or another bound in other cells."""
    spec = load_json("cellbench", kind + "_metrics",
                     name.split(".", 1)[0] + ".json")
    reader = importlib.import_module("cellbench.readers." + spec["reader"])
    return reader.read(ctx, spec.get("args", {}))


async def get_json(http: aiohttp.ClientSession, url: str) -> dict:
    async with http.get(url) as r:
        return await r.json()


async def scrape(http: aiohttp.ClientSession, stack: Stack) -> dict:
    """Every replica's /state and /metrics and the gateway's /metrics,
    at (nearly) one instant."""
    async def get_text(url: str) -> str:
        async with http.get(url) as r:
            return await r.text()

    n = len(stack.replicas)
    got = await asyncio.gather(
        *(get_json(http, u + "/state") for u in stack.replicas),
        *(get_text(u + "/metrics") for u in stack.replicas),
        get_text(stack.gateway + "/metrics"))
    return {
        "states": got[:n],
        "state": stats.summed(got[:n]),
        "prom": stats.summed(
            [stats.parse_prometheus(t) for t in got[n:2 * n]]),
        "gateway": stats.parse_prometheus(got[-1]),
    }


async def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        await asyncio.sleep(d)


async def capture(http: aiohttp.ClientSession, replica: str) -> dict:
    """One profiler capture in the process that holds the chip, and
    what the engine did WHILE it traced, from two /state reads inside
    the capture: its counter rates (tracing slows the host, so the rates
    that go with the trace's shares are the traced ones) and the bytes
    of live keys and values. The call returns only when the server has
    written the trace out, which takes far longer than the capture."""
    async def rates() -> dict:
        await asyncio.sleep(0.3 * PROFILE_S)
        a, ta = await get_json(http, replica + "/state"), time.monotonic()
        await asyncio.sleep(0.5 * PROFILE_S)
        b, tb = await get_json(http, replica + "/state"), time.monotonic()
        out = {k + "_per_s": (b[k] - a[k]) / (tb - ta)
               for k in ("decode_steps", "prefill_tokens_real")}
        out["kv_bytes_in_use"] = 0.5 * (
            a["kv_bytes_in_use"] + b["kv_bytes_in_use"])
        return out

    async def profile() -> str:
        url = f"{replica}/debug/profile?seconds={PROFILE_S}"
        async with http.get(
                url, timeout=aiohttp.ClientTimeout(total=300)) as r:
            if r.status != 200:
                raise HarnessError(f"{url} -> {r.status}: {await r.text()}")
            return (await r.json())["profile_dir"]

    profile_dir, rate = await asyncio.gather(profile(), rates())
    return {"profile_dir": profile_dir, "rates": rate}


def reduce_trace(profile_dir: str, groups_file: str) -> dict:
    """The trace's reduction, by a child that may import jax (CPU)."""
    try:
        pbs = [os.path.join(d, f) for d, _, fs in os.walk(profile_dir)
               for f in fs if f.endswith(".xplane.pb")]
        if not pbs:
            raise HarnessError(f"no .xplane.pb under {profile_dir}")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "trace_reduce.py"),
             pbs[0], groups_file],
            env=env, capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            raise HarnessError(f"trace reduction failed:\n{out.stderr[-2000:]}")
        return json.loads(out.stdout.strip().splitlines()[-1])
    finally:
        shutil.rmtree(profile_dir, ignore_errors=True)


async def reconcile(http: aiohttp.ClientSession, stack: Stack) -> dict:
    """Gateway /usage totals against the replicas' meter_* counters,
    token for token; the ledger settles a moment after the last frame."""
    deadline = time.monotonic() + 15
    while True:
        usage = (await get_json(http, stack.gateway + "/usage"))["totals"]
        meters = stats.summed(
            [await get_json(http, u + "/state") for u in stack.replicas])
        mismatch = {k: [usage.get(k), meters.get("meter_" + k)]
                    for k in LEDGER_KEYS
                    if usage.get(k) != meters.get("meter_" + k)}
        if not mismatch or time.monotonic() > deadline:
            return mismatch
        await asyncio.sleep(0.5)


async def measure(stack: Stack, mix: dict, seed: int, seconds: float,
                  trace: bool) -> dict:
    timeout = aiohttp.ClientTimeout(total=60)
    async with aiohttp.ClientSession(timeout=timeout) as http, \
            Driver(stack.gateway, stack.model) as drv:
        # lead-in, part 1: the shape tour, one step at a time
        # a step's later requests are sent ``delay_s`` after its FIRST
        # request's first token, not after the step's start: in a fresh
        # checkout the first one's prefill compiles for a minute, and a
        # joiner sent meanwhile would be admitted WITH it (one state
        # build) instead of joining it (the row-update program)
        async def tour_send(sess, delay_s, first, leads):
            if not leads:
                await first.wait()
                await asyncio.sleep(delay_s)
            t = sess.turns[0]
            return await drv.send(
                [{"role": "user", "content": t.content}], t.max_tokens,
                time.monotonic(), "tour", first if leads else None)

        for i, step in enumerate(tour_steps(mix)):
            first = asyncio.Event()
            for res in await asyncio.gather(
                    *(tour_send(sess, d, first, j == 0)
                      for j, (sess, d) in enumerate(step))):
                if not res.ok:
                    raise HarnessError(
                        f"a request of tour step {i} ended {res.status}")
        log(f"tour of {len(drv.results)} requests done")
        # lead-in, part 2: the mix itself, straight into the window
        schedule = Schedule(mix, seed, seconds)
        await drv.start(schedule, time.monotonic() + 0.2)
        await sleep_until(drv.t0)
        snap0 = await scrape(http, stack)
        setup_s = drv.t0 - _T_START
        log(f"window open after {setup_s:.1f}s of set-up")
        # the counters' window ends at t1. A traced run closes it at 45 %
        # of the window, before the capture, which returns long after the
        # window (the server writes the trace out for over a minute):
        # counters are read over untraced time only, never over the
        # drain, and the write-out overlaps the rest of the window
        # instead of following it (a run has 360 s in all)
        t_read = drv.t0 + 0.45 * seconds if trace else drv.t1
        await sleep_until(t_read)
        snap1 = await scrape(http, stack)
        snap1_late_s = time.monotonic() - t_read
        captures = []
        if trace:
            captures = await asyncio.gather(
                *(capture(http, u) for u in stack.replicas))
        await sleep_until(drv.t1)
        cancelled = await drv.drain(DRAIN_S)
        snap2 = await scrape(http, stack)
        mismatch = await reconcile(http, stack)
        return {"driver": drv, "snap0": snap0, "snap1": snap1,
                "snap2": snap2, "setup_s": setup_s, "captures": captures,
                "snap1_late_s": snap1_late_s,
                "cancelled": cancelled, "ledger_mismatch": mismatch}


def run(args) -> int:
    manifest = load_json("BENCHMARK.json")
    cell = named(manifest["workloads"], args.workload, "workload")
    entry = named(manifest["configs"], cell["config"], "configuration")
    doc = load_json(entry["file"])
    cb = doc["cellbench"]
    mix = load_json("cellbench", "traffic", cell["traffic"] + ".json")
    trace = bool(args.trace)
    out_dir = os.path.join(
        CHECKOUT, "chiprun_out", "cellbench",
        f"{args.workload}.s{args.seed}.t{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    flags = [*cb["serve_flags"], *mix.get("serve_flags", []),
             *(["--enable-profile-endpoint"] if trace else [])]
    with Stack(os.path.join(CHECKOUT, entry["file"]), cb["name"], flags,
               cb["replicas"], out_dir, log) as stack:
        boot = stack.states()
        want = cb["expect"]["platform"]
        for st in boot:
            if st["platform"] != want:
                raise HarnessError(
                    f"replica runs on platform {st['platform']!r}, the "
                    f"configuration asks for {want!r}")
        if sum(st["process_device_count"] for st in boot) != cell["chips"]:
            raise HarnessError(
                f"replicas hold {[st['process_device_count'] for st in boot]}"
                f" devices, the cell asks for {cell['chips']}")
        log(f"{len(boot)} replica(s) on {boot[0]['device_kind']}, warmup "
            f"{boot[0]['warmup_ms'] / 1e3:.1f}s, "
            f"{boot[0]['warm_programs']} programs, cache misses "
            f"{boot[0]['xla_cache_misses']}")
        m = asyncio.run(measure(stack, mix, args.seed, args.seconds, trace))
    # every child is stopped; what follows is arithmetic
    groups_file = os.path.join(
        HERE, "module_groups", cb["module_groups"] + ".json")
    traces = [reduce_trace(c["profile_dir"], groups_file)
              for c in m["captures"]]
    drv = m["driver"]
    window = [r for r in drv.results if r.phase == "window"]
    if m["snap1_late_s"] > SCRAPE_LATE_S:
        # a stalled host, not a wrong output: the counters' window then
        # ends nobody knows when, so what reads it reports nothing
        log(f"counters read {m['snap1_late_s']:.1f}s late: voided")
        m["snap1"] = None
    ctx = {
        "config": doc, "mix": mix, "seconds": float(args.seconds),
        "t0": drv.t0, "t1": drv.t1, "setup_s": m["setup_s"],
        "results": drv.results, "window": window,
        "snap0": m["snap0"], "snap1": m["snap1"], "snap2": m["snap2"],
        "traces": traces, "rates": [c["rates"] for c in m["captures"]],
        "device_kind": boot[0]["device_kind"],
    }
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for spec in cell_metrics(manifest, kind, args.workload):
        value = read_metric(
            {"per_layer": "layer", "end_to_end": "e2e"}[kind],
            spec["name"], ctx)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}

    s0, s2 = m["snap0"]["state"], m["snap2"]["state"]
    want_bytes = cb["expect"]["param_bytes_total"]
    checks = {
        "every_stream_exact": all(r.ok for r in drv.results),
        "ledger_reconciles": not m["ledger_mismatch"],
        "no_compile_in_window": all(
            s2[k] == s0[k] for k in ("xla_compiles", "xla_cache_misses")),
        "param_bytes": all(
            abs(st["param_bytes_total"] - want_bytes) <= 0.02 * want_bytes
            for st in boot),
        "none_cancelled": m["cancelled"] == 0,
    }
    if not all(checks.values()):
        # say why on stderr too: the driver keeps its tail
        log("NOT CORRECT: " + json.dumps({
            "failed_checks": [k for k, v in checks.items() if not v],
            "not_ok": [[r.phase, r.status, r.expected, r.tokens]
                       for r in drv.results if not r.ok][:20],
            "ledger_mismatch": m["ledger_mismatch"],
            "compile_deltas": {k: s2[k] - s0[k] for k in (
                "xla_compiles", "xla_cache_misses", "xla_cache_hits")},
            "param_bytes_total": [st["param_bytes_total"] for st in boot],
            "cancelled": m["cancelled"]}))
    ok = [r for r in window if r.ok]
    late = lateness_ms(window) or [0.0]
    ttft = [1e3 * (r.first - r.due) for r in ok] or [0.0]
    tpot = [v for v in (stats.tpot_ms(r.first, r.last, r.tokens)
                        for r in ok) if v is not None] or [0.0]
    # the summary's counters: the late scrape if that is all there is
    snap1 = m["snap1"] or m["snap2"]
    summary = {
        "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds, "trace": args.trace,
        "checks": checks, "ledger_mismatch": m["ledger_mismatch"],
        "sent": {p: sum(1 for r in drv.results if r.phase == p)
                 for p in ("tour", "lead", "window", "after")},
        "failed_by_status": {
            s: sum(1 for r in window if r.status == s)
            for s in sorted({r.status for r in window} - {"ok"})},
        "generator_lateness_ms": {
            "p50": stats.percentile(late, 50), "max": max(late)},
        "ttft_ms_from_due": {
            "mean": sum(ttft) / len(ttft),
            **{q: stats.percentile(ttft, int(q[1:])) for q in ("p50", "p90")}},
        "tpot_ms": {
            "mean": sum(tpot) / len(tpot),
            **{q: stats.percentile(tpot, int(q[1:])) for q in ("p50", "p90")}},
        # what TTFT is made of, as exact means over the counters' window
        # (histogram deltas): no cell judges a TTFT, so these move no
        # judged metric and are no per-layer metrics of the manifest
        "ttft_parts_ms": {
            name: stats.hist_mean_delta(
                m["snap0"][src], snap1[src], hist)
            for name, src, hist in (
                ("queue_wait", "prom", "tpuserve_queue_wait_hist_ms"),
                ("replica_ttft", "prom", "tpuserve_ttft_hist_ms"),
                ("first_emit", "prom", "tpuserve_first_emit_hist_ms"),
                ("gateway_ttft_s", "gateway",
                 "gen_ai_server_time_to_first_token_seconds"))},
        "backlog_at_counters_end": {
            "queued": snap1["state"].get("queued"),
            "active_slots": snap1["state"].get("active_slots"),
            "kv_bytes_in_use": snap1["state"].get("kv_bytes_in_use"),
            "read_late_s": m["snap1_late_s"]},
        "compiles_in_window": s2["xla_compiles"] - s0["xla_compiles"],
        "boot": {k: boot[0][k] for k in (
            "warmup_ms", "warm_programs", "weights_init_ms",
            "weights_quantize_ms", "xla_cache_hits", "xla_cache_misses",
            "compile_cache_dir")},
        "metrics": {k: v["value"] for k, v in metrics.items()},
    }
    print(json.dumps(summary), flush=True)

    device = {
        "platform": boot[0]["platform"], "kind": boot[0]["device_kind"],
        "count": sum(st["process_device_count"] for st in boot),
        "memory_peak_bytes": max(
            (d.get("peak_bytes_in_use", 0)
             for st in m["snap2"]["states"] for d in st["devices"]),
            default=0),
    }
    line = {"correct": all(checks.values()), "attempted": len(window),
            "failed": sum(1 for r in window if not r.ok),
            "metrics": metrics, "device": device}
    on_device = [t for t in traces if t["devices"]]
    if on_device:  # a CPU's trace has no device plane: nothing to say
        n = len(on_device)
        device["busy_s"] = sum(t["busy_s"] for t in on_device) / n
        device["window_s"] = sum(t["window_s"] for t in on_device) / n
        line["breakdown"] = {"device_ops": on_device[0]["device_ops"],
                             "idle_gaps": on_device[0]["idle_gaps"]}
    print(json.dumps(line), flush=True)
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], required=True)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except (HarnessError, OSError, KeyError) as e:
        print(f"cellbench: no result: {e!r}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
