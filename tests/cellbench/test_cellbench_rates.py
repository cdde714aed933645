"""The committed cells' mixes as they are committed: an open loop's
window holds the requests its rate promises, a closed loop's pool holds
eight lengths a client, the seed reorders the same lengths and never
resizes them, a truncated lognormal puts nothing on a bound, and
``long-prompt``'s tour still lands every tail in the rung its
``lead_in.about`` names. Every (mix, seed) pair is a case of its own.

The mixes are the ones ``BENCHMARK.json``'s cells name, the window is
its ``run_seconds``, and what is asked of a mix is what the generator
gives ANY mix it accepts (``cellbench/traffic.py``: a count from the
rate or the clients, lengths at fixed quantiles, the seed shuffles),
read through the keys the mix declares: a cell a later PR adds gets the
same cases and cannot fail them by being different. What is particular
to ``long-prompt`` is read out of its own ``about`` texts, not written
here a second time."""

import hashlib
import json
import os
import re

import pytest
from test_cellbench_manifest import TEMPLATE, _shapes

from cellbench import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    M = json.load(_f)
#: the window every cell is judged over
WINDOW_S = M["run_seconds"]
#: the driver's seeds do not fit 32 signed bits
SEEDS = [0, 1, 7, 2147483659, 2 ** 31 + 977, 2 ** 31 + 50021]
LENGTHS = ("prompt_tokens", "output_tokens")


def _mixes(loop):
    out = {}
    for name in sorted({w["traffic"] for w in M["workloads"]}):
        with open(os.path.join(ROOT, "cellbench", "traffic",
                               name + ".json")) as fh:
            mix = json.load(fh)
        if mix.get("loop") == loop:
            out[name] = mix
    return out


OPEN, CLOSED = _mixes("open"), _mixes("closed")
MIXES = {**OPEN, **CLOSED}
TRUNCATED = [(name, key) for name, mix in MIXES.items() for key in LENGTHS
             if mix.get(key, {}).get("dist") == "lognormal_truncated"]


def _lengths(sessions):
    """(prompt lengths, answer lengths) of every turn sent."""
    turns = [t for s in sessions for t in s.turns]
    return ([len(t.content) for t in turns], [t.max_tokens for t in turns])


def _sent(s):
    """The sessions a schedule holds: an open loop's lead-in and window,
    a closed loop's pool once round."""
    if s.loop == "open":
        return s.lead + s.window
    return [s.nth(k) for k in range(8 * s.clients)]


def test_the_committed_mixes_are_found():
    assert {"chat-steady", "prompt-heavy"} <= set(OPEN)
    assert {"decode-closed", "long-prompt"} <= set(CLOSED)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(OPEN))
def test_a_window_holds_what_the_rate_promises(name, seed):
    mix = OPEN[name]
    s = traffic.Schedule(mix, seed, WINDOW_S)
    want = mix["rate_per_s"] * WINDOW_S
    assert abs(len(s.window) - want) <= 0.15 * want
    starts = [x.start_s for x in s.window]
    assert starts == sorted(starts) and starts[0] == 0.0
    assert starts[-1] < WINDOW_S
    # the lead-in is the same mix at the same rate
    lead = mix["rate_per_s"] * mix["lead_in"].get("traffic_seconds", 0)
    assert abs(len(s.lead) - lead) <= max(1, 0.15 * lead)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(CLOSED))
def test_a_pool_holds_eight_lengths_a_client(name, seed):
    mix = CLOSED[name]
    s = traffic.Schedule(mix, seed, WINDOW_S)
    pool = 8 * mix["clients"]
    prompts, outputs = _lengths(_sent(s))
    for key, got in zip(LENGTHS, (prompts, outputs)):
        want = sorted(int(round(x)) for x in traffic.quantiles(
            mix[key], pool))
        assert sorted(got) == want
    # past the pool the lengths come round again, under texts of their own
    again = s.nth(pool + 3).turns[0]
    first = s.nth(3).turns[0]
    assert (len(again.content), again.max_tokens) == (
        len(first.content), first.max_tokens)
    assert again.content != first.content


@pytest.mark.parametrize("seed", SEEDS[1:])
@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_seed_sends_the_same_lengths(name, seed):
    a = traffic.Schedule(MIXES[name], SEEDS[0], WINDOW_S)
    b = traffic.Schedule(MIXES[name], seed, WINDOW_S)
    if a.loop == "open":
        (pa, oa), (pb, ob) = _lengths(a.window), _lengths(b.window)
        assert len(a.lead) == len(b.lead) >= 1
    else:
        (pa, oa), (pb, ob) = _lengths(_sent(a)), _lengths(_sent(b))
    assert sorted(pa) == sorted(pb) and sorted(oa) == sorted(ob)
    if len(set(pa)) > 1:
        assert pa != pb


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name,key", TRUNCATED)
def test_no_length_lies_on_a_bound(name, key, seed):
    spec = MIXES[name][key]
    s = traffic.Schedule(MIXES[name], seed, WINDOW_S)
    xs = _lengths(_sent(s))[LENGTHS.index(key)]
    # as sampled: strictly between the bounds at any number of requests
    qs = traffic.quantiles(spec, len(xs))
    assert spec["min"] < min(qs) and max(qs) < spec["max"]
    # as sent (rounded to whole tokens)
    if spec["max"] - spec["min"] > len(xs):
        assert spec["min"] < min(xs) and max(xs) < spec["max"]
    else:
        assert spec["min"] <= min(xs) and max(xs) <= spec["max"]


# -- long-prompt: what its own ``about`` texts promise -------------------

LONG = CLOSED["long-prompt"]
TOUR_ABOUT = LONG["lead_in"]["about"]


def _ints(pattern, text):
    m = re.search(pattern, text)
    assert m, pattern
    return [int(x) for x in re.findall(r"\d+", m.group(1))]


#: "S in 64, 128, 256" and "content mod 256 of 10, 70, 150"
RUNGS = _ints(r"S in ((?:\d+, )+\d+)", TOUR_ABOUT)
CHUNK, *MODS = _ints(r"content mod (\d+ of (?:\d+, )+\d+)", TOUR_ABOUT)
#: content mod chunk -> the tail's rung
RUNG_OF = dict(zip(MODS, RUNGS))
#: "sits in page bucket 16 (up to 2048 tokens) or 32"
BUCKETS = _ints(r"page bucket (\d+) \(", TOUR_ABOUT) + _ints(
    r"page bucket \d+ \([^)]*\) or (\d+)", TOUR_ABOUT)

with open(os.path.join(ROOT, next(
        c["file"] for c in M["configs"] if c["name"] == next(
            w["config"] for w in M["workloads"]
            if w["traffic"] == "long-prompt")))) as _f:
    #: the geometry the cell serves with: its page size and slot length
    FLAGS = json.load(_f)["cellbench"]["serve_flags"] + LONG["serve_flags"]


def test_the_tour_about_names_template_lengths_and_rungs():
    assert _ints(r"template length from (\d+ to \d+)", TOUR_ABOUT) == [
        TEMPLATE[0], TEMPLATE[-1]]
    assert len(RUNGS) == len(MODS) == 3 and CHUNK == max(RUNGS)
    assert len(BUCKETS) == 2


@pytest.mark.parametrize("template", TEMPLATE)
def test_long_prompt_tour_lands_each_tail_in_its_rung(template):
    leads = set()
    for step in LONG["lead_in"]["tour"]:
        content, out = step[0][:2]
        bucket, tail = _shapes(content + template, out, FLAGS)
        assert tail == (RUNG_OF[content % CHUNK], bucket), (content, template)
        leads.add(tail)
        # a joiner brings no shape of its own: it is there for the
        # row-update program of the bucket its lead holds
        for content, out, _delay in step[1:]:
            assert _shapes(content + template, out, FLAGS)[0] <= bucket
    # every chunk/tail program [1,S] at both page buckets
    assert leads == {(S, b) for S in RUNGS for b in BUCKETS}


def test_long_prompt_mix_stays_inside_what_the_tour_warms():
    p, o = LONG["prompt_tokens"], LONG["output_tokens"]
    # "prompt + template + answer is 1086 to 4064 tokens"
    lo, hi = _ints(r"prompt \+ template \+ answer is (\d+ to \d+) tokens",
                   TOUR_ABOUT)
    assert p["min"] + TEMPLATE[0] + o["min"] == lo
    assert p["max"] + TEMPLATE[-1] + o["max"] == hi
    # so a request sits in one of the two page buckets the tour runs at
    # every template length, and every prompt is over one chunk (no
    # batched prefill program is used, "so none is warmed")
    for t in (TEMPLATE[0], TEMPLATE[-1]):
        for n, out in ((p["min"], o["min"]), (p["max"], o["max"])):
            assert _shapes(n + t, out, FLAGS)[0] in BUCKETS
    assert p["min"] > CHUNK


@pytest.mark.parametrize("seed", SEEDS)
def test_long_prompt_prompts_are_the_chunks_its_about_says(seed):
    """"Every prompt is 4 to 15 chunks of the engine's 256-token
    prefill chunk": every prompt runs the chunked form, none the
    batched one."""
    lo, hi, chunk = _ints(r"Every prompt is (\d+ to \d+ chunks of the "
                          r"engine's \d+)-token", LONG["about"])
    assert chunk == CHUNK
    s = traffic.Schedule(LONG, seed, WINDOW_S)
    chunks = [-(-n // chunk) for n in _lengths(_sent(s))[0]]
    assert lo <= min(chunks) and max(chunks) <= hi
    assert len(chunks) == 8 * LONG["clients"]


# -- long-prompt since PR 39: the parent's lengths, in a closed loop ------

#: the file the cell ran from PR 28 to PR 38, key by key (``about`` is
#: prose): an open loop at the rate PR 28 swept
PARENT = dict(
    {k: LONG[k] for k in ("name", *LENGTHS, "sharing", "serve_flags",
                          "lead_in")},
    loop="open", rate_per_s=0.8,
    arrivals={"process": "poisson", "zero_gap_share": 0.25})
#: seed -> (digest as committed, digest of the file PR 28 to PR 38 ran)
PINS = {
    7: ("80ae3dbe53a65c14", "922e346f5826fc55"),
    2147483659: ("100bc5283669b7c3", "dd647204365cd7e3"),
    2 ** 31 + 50021: ("f2151c43ae059a4d", "e9f0b267e200b7fb"),
}


def _digest(sessions):
    h = hashlib.sha256()
    for x in sessions:
        h.update(repr((x.start_s, x.system, [
            (t.content, t.max_tokens, t.think_s)
            for t in x.turns])).encode())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("seed", sorted(PINS))
def test_long_prompt_schedule_as_committed_is_pinned(seed):
    s = traffic.Schedule(LONG, seed, WINDOW_S)
    assert _digest(_sent(s)) == PINS[seed][0]


@pytest.mark.parametrize("seed", sorted(PINS))
def test_the_generator_still_sends_the_parents_schedule(seed):
    """``traffic.py`` is the parent's: given the file PR 28 to PR 38 ran
    it sends what they sent (these digests are that file's own)."""
    s = traffic.Schedule(PARENT, seed, WINDOW_S)
    assert _digest(s.lead + s.window) == PINS[seed][1]


def test_long_prompt_differs_from_the_parents_file_in_its_loop_alone():
    """What PR 28 measured, key by key: the lengths, the tour and the
    flags stay; the arrivals went with the open loop."""
    assert {k: v for k, v in LONG.items() if k not in ("about", "lead_in")
            } == {
        "name": "long-prompt", "loop": "closed", "clients": 16,
        "prompt_tokens": {"dist": "lognormal_truncated", "median": 2560,
                          "sigma": 0.4, "min": 1024, "max": 3840},
        "output_tokens": {"dist": "lognormal_truncated", "median": 96,
                          "sigma": 0.4, "min": 48, "max": 192},
        "sharing": {"kind": "none"}, "serve_flags": []}
    assert LONG["lead_in"]["traffic_seconds"] == 8
    assert [[r[:2] for r in step] for step in LONG["lead_in"]["tour"]] == [
        [[1290, 150], [1030, 48]], [[2570, 150], [1030, 48]],
        [[1350, 48]], [[1430, 48]], [[2630, 48]], [[2710, 48]]]


def test_long_prompts_clients_fit_its_slots_twice():
    """Nobody waits for a slot: the wait is for the prefill queue."""
    slots = int(FLAGS[FLAGS.index("--max-batch-size") + 1])
    assert 2 * LONG["clients"] <= slots


def test_long_prompts_why_names_the_clients_it_runs():
    cell = next(w for w in M["workloads"] if w["traffic"] == "long-prompt")
    assert f"{LONG['clients']} clients" in cell["why"]
    assert "closed loop" in cell["why"] and len(cell["why"]) <= 200


@pytest.mark.parametrize("name,counts,digest", [
    ("prompt-heavy", (14, 90), "cdc7c1ddf8c26d54"), ("chat-steady", (23, 145), "305b2a726a9726db")])
def test_the_open_mixes_are_the_parents_to_the_byte(name, counts, digest):
    """The free shuffle, as measured since PR 23: PR 39 leaves both
    files and the generator as they were."""
    s = traffic.Schedule(OPEN[name], 2147483659, WINDOW_S)
    assert (len(s.lead), len(s.window)) == counts
    assert _digest(s.lead + s.window) == digest
