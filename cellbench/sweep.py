#!/usr/bin/env python3
"""The sweep that fixes an open-loop cell's rate, run once when the cell
is defined (a builder's tool, not part of a check)::

    python cellbench/sweep.py --workload <name> --rates 2,3,4,5 --seconds 20 --seed 1

Boots the cell's stack ONCE, then offers the cell's own mix at each rate
in turn for ``--seconds`` (its lead-in before each), and prints one JSON
line per rate: failures, TTFT in the first and second half of the
window, and the backlog at its end. The knee is the highest rate with
no failure and no backlog growing across the window — TTFT in the second
half no more than 1.5 times that of the first, and at most a quarter as
many requests queued at the end as the replicas have slots. Use windows as
long as the cell's: backlog episodes that a 50-second window shows, a
25-second one misses (PERF.md section 4). The cell then runs at 0.8 of
it: write that into the mix's ``rate_per_s`` and the sweep into PERF.md.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cellbench import run as cb_run, stats  # noqa: E402
from cellbench.stack import HarnessError, Stack  # noqa: E402


def half(window, t0, t1, which):
    mid = 0.5 * (t0 + t1)
    xs = [1e3 * (r.first - r.due) for r in window
          if r.ok and (r.due >= mid) == which]
    return stats.percentile(xs, 50) if xs else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    manifest = cb_run.load_json("BENCHMARK.json")
    cell = cb_run.named(manifest["workloads"], args.workload, "workload")
    entry = cb_run.named(manifest["configs"], cell["config"], "configuration")
    cb = cb_run.load_json(entry["file"])["cellbench"]
    mix = cb_run.load_json("cellbench", "traffic", cell["traffic"] + ".json")
    if mix["loop"] != "open":
        raise HarnessError("only an open-loop cell has a rate to sweep")
    out_dir = os.path.join(cb_run.CHECKOUT, "chiprun_out", "cellbench",
                           f"{args.workload}.sweep")
    flags = [*cb["serve_flags"], *mix.get("serve_flags", [])]
    slots = int(flags[flags.index("--max-batch-size") + 1]) * cb["replicas"]
    knee = None
    with Stack(os.path.join(cb_run.CHECKOUT, entry["file"]), cb["name"],
               flags, cb["replicas"], out_dir, cb_run.log) as stack:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            m = asyncio.run(cb_run.measure(
                stack, dict(mix, rate_per_s=rate), args.seed + i,
                args.seconds, False))
            mix = dict(mix, lead_in=dict(mix["lead_in"], tour=[]))
            drv = m["driver"]
            window = [r for r in drv.results if r.phase == "window"]
            ok = [r for r in window if r.ok]
            first = half(window, drv.t0, drv.t1, False)
            second = half(window, drv.t0, drv.t1, True)
            queued = m["snap1"]["state"].get("queued", 0)
            row = {
                "rate_per_s": rate, "sent": len(window),
                "failed": len(window) - len(ok),
                "ttft_p50_ms_first_half": first,
                "ttft_p50_ms_second_half": second,
                "ttft_p50_ms": stats.percentile(
                    [1e3 * (r.first - r.due) for r in ok], 50) if ok else None,
                "ttft_p90_ms": stats.percentile(
                    [1e3 * (r.first - r.due) for r in ok], 90) if ok else None,
                "queued_at_end": queued,
                "active_at_end": m["snap1"]["state"].get("active_slots"),
                "compiles": m["snap2"]["state"]["xla_compiles"]
                - m["snap0"]["state"]["xla_compiles"],
            }
            row["sustained"] = bool(
                ok and len(ok) == len(window) and first and second
                and second <= 1.5 * first and queued <= slots // 4)
            if row["sustained"]:
                knee = rate
            print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "knee_per_s": knee,
                      "rate_at_0.8": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
