"""Arithmetic of the benchmark: percentiles, the per-request gap, and
deltas of Prometheus histograms. Pure functions, stdlib only."""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two closest ranks, as numpy's default does."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tpot_ms(first_s: float, last_s: float, tokens: int) -> float | None:
    """Time per output token of one request, first token excluded:
    (last token - first token) / (tokens - 1). The engine emits a decode
    window of K steps per host round trip, so raw gaps are 0 or K steps
    long; this is what a reader of the stream feels on average. None
    for a reply of one token."""
    if tokens < 2:
        return None
    return 1e3 * (last_s - first_s) / (tokens - 1)


def parse_prometheus(text: str) -> dict[str, float]:
    """Sample name -> value, summed over label sets (a histogram's
    ``_sum`` and ``_count`` over every model and route). ``_bucket`` and
    ``_created`` samples are dropped: nothing here reads them."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0]
        if name.endswith(("_bucket", "_created")):
            continue
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def hist_delta(before: dict[str, float], after: dict[str, float],
               name: str) -> tuple[float, float]:
    """(sum, count) a histogram gained between two scrapes."""
    ds = after.get(name + "_sum", 0.0) - before.get(name + "_sum", 0.0)
    dc = after.get(name + "_count", 0.0) - before.get(name + "_count", 0.0)
    return ds, dc


def hist_mean_delta(before: dict[str, float], after: dict[str, float],
                    name: str) -> float | None:
    """Exact mean of the observations made between two scrapes (the
    fixed buckets would make any percentile a bucket edge)."""
    ds, dc = hist_delta(before, after, name)
    return ds / dc if dc > 0 else None


def summed(dicts: list[dict[str, float]]) -> dict[str, float]:
    """Key-wise sum of several scrapes (one per replica)."""
    out: dict[str, float] = {}
    for d in dicts:
        for k, v in d.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[k] = out.get(k, 0.0) + v
    return out
