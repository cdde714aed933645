"""The one traffic generator: the seed reorders and never resizes,
clipping holds, bursts have their share, arrivals fill the segment."""

import collections
import random

import pytest

from cellbench import traffic

MIX = {
    "loop": "open", "rate_per_s": 5.0,
    "arrivals": {"process": "poisson", "zero_gap_share": 0.25},
    "prompt_tokens": {"dist": "lognormal", "median": 400, "sigma": 0.8,
                      "min": 32, "max": 720},
    "output_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                      "min": 16, "max": 256},
    "sharing": {"kind": "none"},
    "lead_in": {"tour": [[[100, 10]], [[300, 20], [50, 5, 0.25]]],
                "traffic_seconds": 4},
}
SESSIONS = dict(MIX, rate_per_s=2.0, sharing={
    "kind": "sessions", "system_prompts": 3,
    "system_tokens": {"dist": "fixed", "value": 64},
    "turns": {"dist": "uniform", "min": 2, "max": 4},
    "think_s": {"dist": "exponential", "mean": 0.5}})
CLOSED = dict(MIX, loop="closed", clients=4)
BIG = 2 ** 31 + 977  # the driver's seeds do not fit 32 signed bits


def sizes(segment):
    return sorted((len(s.turns[0].content), s.turns[0].max_tokens)
                  for s in segment)


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_same_seed_same_schedule(seed):
    a = traffic.Schedule(MIX, seed, 20)
    b = traffic.Schedule(MIX, seed, 20)
    assert [(s.start_s, s.turns[0].content, s.turns[0].max_tokens)
            for s in a.window] == \
           [(s.start_s, s.turns[0].content, s.turns[0].max_tokens)
            for s in b.window]
    assert [s.start_s for s in a.lead] == [s.start_s for s in b.lead]


def test_seed_reorders_but_never_resizes():
    a = traffic.Schedule(MIX, 1, 20)
    b = traffic.Schedule(MIX, BIG, 20)
    assert len(a.window) == len(b.window) == 100
    pa = [len(s.turns[0].content) for s in a.window]
    pb = [len(s.turns[0].content) for s in b.window]
    assert pa != pb and sorted(pa) == sorted(pb)
    assert sorted(s.turns[0].max_tokens for s in a.window) == \
        sorted(s.turns[0].max_tokens for s in b.window)
    gaps = lambda seg: sorted(  # noqa: E731
        round(y.start_s - x.start_s, 9) for x, y in zip(seg, seg[1:]))
    ga, gb = gaps(a.window), gaps(b.window)
    # the one gap left out of the list (after the last arrival) may differ
    assert len(set(ga) ^ set(gb)) <= 2


@pytest.mark.parametrize("key,lo,hi", [("prompt_tokens", 32, 720),
                                       ("output_tokens", 16, 256)])
def test_lengths_are_clipped_and_spread(key, lo, hi):
    xs = traffic.draw_ints(MIX[key], 400, random.Random(3))
    assert lo <= min(xs) and max(xs) == hi
    assert min(traffic.draw_ints(dict(MIX[key], sigma=3.0), 400,
                                 random.Random(3))) == lo
    med = sorted(xs)[200]
    assert abs(med - MIX[key]["median"]) <= 0.03 * MIX[key]["median"]


@pytest.mark.parametrize("key,lo,hi", [("prompt_tokens", 32, 720),
                                       ("output_tokens", 16, 256)])
def test_truncated_lognormal_piles_nothing_on_a_bound(key, lo, hi):
    spec = dict(MIX[key], dist="lognormal_truncated")
    xs = traffic.draw_ints(spec, 400, random.Random(3))
    assert lo <= min(xs) and max(xs) <= hi
    assert collections.Counter(xs)[hi] <= 1     # clamping puts dozens there
    assert collections.Counter(
        traffic.draw_ints(MIX[key], 400, random.Random(3)))[hi] > 10
    # far inside its bounds it IS the lognormal
    wide = dict(spec, min=1, max=10 ** 6)
    assert traffic.quantiles(wide, 50) == pytest.approx(
        traffic.quantiles(dict(wide, dist="lognormal"), 50), rel=1e-3)


@pytest.mark.parametrize("name,digest", [
    ("prompt-heavy", "cdc7c1ddf8c26d54"),
    ("decode-closed", "97d0170ceaf9078e")])
def test_measured_mixes_send_what_they_sent_when_measured(name, digest):
    """The sets of PERF.md for these two cells were run before the
    truncated distribution existed: the generator must still produce
    their schedules to the byte."""
    import hashlib
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "..", "cellbench", "traffic",
                           name + ".json")) as f:
        s = traffic.Schedule(json.load(f), 2147483659, 50)
    h = hashlib.sha256()
    if s.loop == "open":
        for seg in (s.lead, s.window):
            for x in seg:
                h.update(repr((x.start_s, x.system, [
                    (t.content, t.max_tokens, t.think_s)
                    for t in x.turns])).encode())
    else:
        for k in range(300):
            h.update(repr([(t.content, t.max_tokens)
                           for t in s.nth(k).turns]).encode())
    assert h.hexdigest()[:16] == digest


@pytest.mark.parametrize("share", [0.0, 0.25, 0.6])
def test_burst_share_and_span(share):
    offs = traffic.arrival_offsets(200, 40.0, share, random.Random(5))
    assert offs[0] == 0.0 and offs == sorted(offs)
    gaps = [b - a for a, b in zip(offs, offs[1:])]
    zero = sum(1 for g in gaps if g == 0.0)
    assert abs(zero - share * 200) <= 1
    # gaps sum to the segment: the last arrival is one gap short of it
    assert 40.0 - max(gaps) - 1e-9 <= offs[-1] < 40.0


def test_rate_sets_the_count():
    s = traffic.Schedule(MIX, 1, 30)
    assert len(s.window) == 150 and len(s.lead) == 20
    assert all(0 <= x.start_s < 30 for x in s.window)


def test_unshared_prompts_share_no_page():
    s = traffic.Schedule(MIX, 9, 20)
    heads = [x.turns[0].content[:16] for x in s.window + s.lead]
    assert len(set(heads)) == len(heads)


@pytest.mark.parametrize("dist,spec,check", [
    ("fixed", {"value": 7}, lambda xs: set(xs) == {7.0}),
    ("uniform", {"min": 2, "max": 4}, lambda xs: 2 < min(xs) < max(xs) < 4),
    ("exponential", {"mean": 3.0},
     lambda xs: abs(sum(xs) / len(xs) - 3.0) < 0.1),
])
def test_distributions(dist, spec, check):
    assert check(traffic.quantiles(dict(spec, dist=dist), 200))


def test_unknown_kinds_are_errors():
    with pytest.raises(KeyError):
        traffic.quantiles({"dist": "zipf"}, 3)
    with pytest.raises(ValueError):
        traffic.Schedule(dict(MIX, loop="half-open"), 1, 5)
    with pytest.raises(ValueError):
        traffic.Schedule(dict(MIX, sharing={"kind": "all"}), 1, 5)


def test_sessions_share_system_prompts_and_grow():
    s = traffic.Schedule(SESSIONS, 11, 20)
    assert len(s.window) == 40
    systems = collections.Counter(x.system for x in s.window)
    assert len(systems) == 3 and all(len(k) == 64 for k in systems)
    assert all(2 <= len(x.turns) <= 4 for x in s.window)
    assert all(x.turns[0].think_s == 0.0 for x in s.window)
    assert all(t.think_s > 0 for x in s.window for t in x.turns[1:])


def test_closed_loop_stream_is_endless_and_seeded():
    a = traffic.Schedule(CLOSED, 2, 10)
    b = traffic.Schedule(CLOSED, 2, 10)
    assert a.clients == 4
    got = [a.nth(k) for k in range(70)]
    assert [len(x.turns[0].content) for x in got] == \
        [len(b.nth(k).turns[0].content) for k in range(70)]
    # past the pool it cycles the sizes under new tags
    assert len(got[0].turns[0].content) == len(got[32].turns[0].content)
    assert got[0].turns[0].content != got[32].turns[0].content


def test_tour_is_the_files_list():
    steps = traffic.tour_steps(MIX)
    assert [[(len(s.turns[0].content), s.turns[0].max_tokens, d)
             for s, d in step] for step in steps] == \
        [[(100, 10, 0.0)], [(300, 20, 0.0), (50, 5, 0.25)]]
    heads = [s.turns[0].content[:6] for step in steps for s, _ in step]
    assert len(set(heads)) == len(heads)   # no two share a prefix page
    assert traffic.tour_steps(dict(MIX, lead_in={"traffic_seconds": 1})) == []
