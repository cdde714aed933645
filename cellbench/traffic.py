"""The one traffic generator: a mix file of parameters + a seed -> the
sessions a run sends. Stdlib only; the program receives only what this
produces.

A mix (``cellbench/traffic/<name>.json``) says: ``loop`` (``open``: a
session starts when it is due, whatever the system does; ``closed``:
``clients`` callers each wait for their reply), the arrival process,
the length distributions, what requests share, and the lead-in.

**The seed reorders, it does not resize.** Every distribution is
sampled at fixed, evenly spaced quantiles (as many as the segment has
requests) and the seed only shuffles them, so every seed offers the
same multiset of prompt lengths, answer lengths and arrival gaps in
another order: runs differ by ordering noise, not by how much work the
draw happened to contain (the builder's contract asks for this).
Lengths are counted in tokens of the byte tokenizer the random-weight
models are served with: one character of content is one token; the chat
template adds some twenty more per message.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass, field
from statistics import NormalDist


@dataclass
class Turn:
    content: str
    max_tokens: int
    #: seconds between the previous turn's last token and this turn
    think_s: float = 0.0


@dataclass
class Session:
    #: seconds after its segment's start at which the first turn is due
    start_s: float
    system: str
    turns: list[Turn] = field(default_factory=list)


def _q(i: int, n: int) -> float:
    return (i + 0.5) / n


def _fixed(spec: dict, n: int) -> list[float]:
    return [float(spec["value"])] * n


def _uniform(spec: dict, n: int) -> list[float]:
    lo, hi = spec["min"], spec["max"]
    return [lo + (hi - lo) * _q(i, n) for i in range(n)]


def _lognormal(spec: dict, n: int) -> list[float]:
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    nd = NormalDist()
    return [min(max(math.exp(mu + sigma * nd.inv_cdf(_q(i, n))),
                    spec["min"]), spec["max"]) for i in range(n)]


def _lognormal_truncated(spec: dict, n: int) -> list[float]:
    """The lognormal between ``min`` and ``max`` only: quantiles of the
    TRUNCATED distribution, so no length piles up on a bound (clamping,
    as ``lognormal`` does, puts the whole cut-off tail on it)."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    nd = NormalDist()
    lo = nd.cdf((math.log(spec["min"]) - mu) / sigma)
    hi = nd.cdf((math.log(spec["max"]) - mu) / sigma)
    return [math.exp(mu + sigma * nd.inv_cdf(lo + (hi - lo) * _q(i, n)))
            for i in range(n)]


def _exponential(spec: dict, n: int) -> list[float]:
    return [-spec["mean"] * math.log(1.0 - _q(i, n)) for i in range(n)]


DISTS = {"fixed": _fixed, "uniform": _uniform, "lognormal": _lognormal,
         "lognormal_truncated": _lognormal_truncated,
         "exponential": _exponential}


def quantiles(spec: dict, n: int) -> list[float]:
    """``n`` evenly spaced quantiles of the distribution ``spec``."""
    return DISTS[spec["dist"]](spec, n)


def draw(spec: dict, n: int, rng: random.Random) -> list[float]:
    xs = quantiles(spec, n)
    rng.shuffle(xs)
    return xs


def draw_ints(spec: dict, n: int, rng: random.Random) -> list[int]:
    return [int(round(x)) for x in draw(spec, n, rng)]


def arrival_offsets(n: int, duration_s: float, zero_gap_share: float,
                    rng: random.Random) -> list[float]:
    """Due times of ``n`` arrivals over ``duration_s``: a Poisson
    process (exponential gaps at fixed quantiles) in which a share of
    the gaps is zero — a burst — and the rest are stretched so that the
    gaps sum to the duration exactly. The first arrival is due at 0."""
    n_zero = min(int(round(zero_gap_share * n)), n - 1)
    m = n - n_zero
    exp = [-math.log(1.0 - _q(i, m)) for i in range(m)]
    scale = duration_s / sum(exp)
    gaps = [g * scale for g in exp] + [0.0] * n_zero
    rng.shuffle(gaps)
    if gaps[-1] == 0.0:
        # the gap behind the last arrival is the one not used: keep it
        # non-zero, so that the last arrival is due inside the segment
        i = next(i for i, g in enumerate(gaps) if g > 0.0)
        gaps[i], gaps[-1] = gaps[-1], gaps[i]
    out, t = [], 0.0
    for g in gaps:
        out.append(t)
        t += g
    return out


class Text:
    """Seeded filler. Every piece starts with a tag no other piece has,
    so two prompts never share a page of the prefix cache unless the
    mix says they share."""

    def __init__(self, seed: int) -> None:
        rng = random.Random(seed ^ 0x5EED)
        self._base = "".join(
            rng.choice("bcdefghijklmnopqrstuvwxyz ") for _ in range(8192))
        self._rng = rng

    def piece(self, tag: str, n: int) -> str:
        off = self._rng.randrange(len(self._base))
        body = self._base[off:] + self._base[:off]
        while len(body) < n:
            body += body
        return (tag + " " + body)[:max(n, 1)]


class Schedule:
    """What one run sends: ``lead`` (unmeasured, brings the engine to
    steady state) and ``window`` segments of an open loop, or the
    endless request stream of a closed loop (``nth``)."""

    def __init__(self, mix: dict, seed: int, seconds: float) -> None:
        self.mix = mix
        self.seed = seed
        self.loop = mix["loop"]
        self.lead_s = float(mix["lead_in"]["traffic_seconds"])
        self.seconds = float(seconds)
        self._text = Text(seed)
        rng = random.Random(seed)
        if self.loop == "open":
            self.lead = self._segment("l", self.lead_s, rng)
            self.window = self._segment("w", self.seconds, rng)
        elif self.loop == "closed":
            self.clients = int(mix["clients"])
            pool = 8 * self.clients
            self._prompts = draw_ints(mix["prompt_tokens"], pool, rng)
            self._outputs = draw_ints(mix["output_tokens"], pool, rng)
        else:
            raise ValueError(f"unknown loop kind {self.loop!r}")

    # -- open loop -----------------------------------------------------
    def _segment(self, tag: str, duration_s: float,
                 rng: random.Random) -> list[Session]:
        mix = self.mix
        n = max(1, int(round(mix["rate_per_s"] * duration_s)))
        starts = arrival_offsets(
            n, duration_s, mix["arrivals"]["zero_gap_share"], rng)
        sharing = mix["sharing"]
        if sharing["kind"] == "none":
            prompts = draw_ints(mix["prompt_tokens"], n, rng)
            outputs = draw_ints(mix["output_tokens"], n, rng)
            return [Session(starts[i], "", [Turn(
                self._text.piece(f"{self.seed:x}{tag}{i:x}", prompts[i]),
                outputs[i])]) for i in range(n)]
        if sharing["kind"] == "sessions":
            return self._sessions(tag, n, starts, sharing, rng)
        raise ValueError(f"unknown sharing kind {sharing['kind']!r}")

    def _sessions(self, tag: str, n: int, starts: list[float],
                  sharing: dict, rng: random.Random) -> list[Session]:
        """Conversations: one of a few system prompts (shared across
        sessions), then turns that each resend the whole history."""
        mix = self.mix
        k = int(sharing["system_prompts"])
        sys_len = draw_ints(sharing["system_tokens"], k, rng)
        systems = [self._text.piece(f"sys{j}", sys_len[j]) for j in range(k)]
        n_turns = draw_ints(sharing["turns"], n, rng)
        total = sum(n_turns)
        prompts = draw_ints(mix["prompt_tokens"], total, rng)
        outputs = draw_ints(mix["output_tokens"], total, rng)
        thinks = draw(sharing["think_s"], total, rng)
        out, t = [], 0
        for i in range(n):
            turns = []
            for j in range(n_turns[i]):
                turns.append(Turn(
                    self._text.piece(f"{self.seed:x}{tag}{i:x}.{j}",
                                     prompts[t]),
                    outputs[t], thinks[t] if j else 0.0))
                t += 1
            out.append(Session(starts[i], systems[i % k], turns))
        return out

    # -- closed loop ---------------------------------------------------
    def nth(self, k: int) -> Session:
        """The k-th request of a closed loop, whichever client takes it."""
        i = k % len(self._prompts)
        return Session(0.0, "", [Turn(
            self._text.piece(f"{self.seed:x}c{k:x}", self._prompts[i]),
            self._outputs[i])])


def tour_steps(mix: dict) -> list[list[tuple[Session, float]]]:
    """The lead-in's shape tour: steps run one after another before any
    traffic; a step is one or more requests ``[content tokens, answer
    tokens, delay_s]`` (delay optional: a later request is sent that
    long after the step's first request's first token), so that
    every program shape the mix's lengths can reach has run (and, in a
    fresh checkout, compiled) before the window. A lone request reaches
    a decode-window pair and a prefill chunk/tail program at its page
    bucket; a second request that joins while the first still decodes
    reaches the row-update program of that bucket. Data, because which
    shapes exist follows from the mix's lengths and the configuration's
    buckets."""
    steps = []
    for i, step in enumerate(mix["lead_in"].get("tour", [])):
        steps.append([
            (Session(0.0, "", [Turn(("t%x.%x " % (i, j) + "x" * r[0])[:r[0]],
                                    r[1])]),
             float(r[2]) if len(r) > 2 else 0.0)
            for j, r in enumerate(step)])
    return steps
