#!/usr/bin/env python3
"""The driver's two-set test, replayed on a builder's runs of ONE tree:
``python3 cellbench/selfcheck.py <set A>.jsonl <set B>.jsonl``, each file the
stdout of ``--trace 0`` runs (summary line, then result line, as
``chip_runs.sh`` appends them; a run whose boot compiled is cold, left out).
Per cell and end-to-end metric, against ``BENCHMARK.json``'s bound:
R1: the medians differ by at most the bound, a share of A's median
    (``setup_s`` may be better by any amount);
R2: the mean of the two spreads is at most half the bound (``room`` is that
    mean over the bound: hand in under 0.33; not asked of ``setup_s``).
A spread is the distance between the quartiles of ``statistics.quantiles``
(under four runs the range), the run farthest from the median left out where
that narrows it. Exit code 1 if a line fails. Stdlib only."""

import json
import os
import statistics
import sys


def width(values):
    if len(values) < 4:
        return max(values) - min(values)
    q = statistics.quantiles(values, n=4)
    return q[2] - q[0]


def spread(values):
    """In the metric's own unit; the farthest run left out if narrower."""
    med = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - med))[:-1]
    return min(width(values), width(rest)) if len(rest) > 1 else width(values)


def r1(med_a, med_b, bound, may_improve=""):
    better = may_improve and (med_b < med_a) == (may_improve == "lower")
    return bool(better) or abs(med_b - med_a) <= bound * med_a


def r2(spread_a, spread_b, med_a, bound):
    return 0.5 * (spread_a + spread_b) <= 0.5 * bound * med_a


def read_set(path):
    """cell -> metric -> values, from summary + result line pairs."""
    out, cell = {}, None
    with open(path) as f:
        docs = [json.loads(x) if x.startswith("{") else {} for x in f]
    for d in docs:
        if "workload" in d:
            warm = d["trace"] == 0 and not d["boot"]["xla_cache_misses"]
            cell = d["workload"] if warm else None
        elif "correct" in d and cell:
            for k, v in d["metrics"].items():
                out.setdefault(cell, {}).setdefault(k, []).append(v["value"])
            cell = None
    return out


def main(argv):
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "BENCHMARK.json")) as f:
        e2e = {m["name"]: m for m in json.load(f)["end_to_end"]}
    a, b = read_set(argv[0]), read_set(argv[1])
    bad = 0
    for cell in sorted(set(a) & set(b)):
        for name in sorted(set(a[cell]) & set(b[cell]) & set(e2e)):
            va, vb, m = a[cell][name], b[cell][name], e2e[name]
            ma, mb = statistics.median(va), statistics.median(vb)
            sa, sb, setup = spread(va), spread(vb), name == "setup_s"
            ok1 = r1(ma, mb, m["bound"], m["better"] if setup else "")
            ok2 = setup or r2(sa, sb, ma, m["bound"])
            bad += not (ok1 and ok2)
            print(f"{cell} {name} bound {m['bound']} n {len(va)}+{len(vb)} "
                  f"medians {ma:.6g} {mb:.6g} ({(mb / ma - 1) * 100:+.2f} %) "
                  f"spreads {sa:.4g} {sb:.4g} ({sa / ma * 100:.2f} % "
                  f"{sb / mb * 100:.2f} %) R1 {'pass' if ok1 else 'FAIL'} "
                  + ("R2 n/a" if setup else f"R2 {'pass' if ok2 else 'FAIL'}"
                     f" room {0.5 * (sa + sb) / (m['bound'] * ma):.2f}"))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
