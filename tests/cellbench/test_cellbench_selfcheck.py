"""``cellbench/selfcheck.py``, the driver's two-set test replayed, held
to the ledger's own cases: the refusals of PR 34, 37 and 38 come out as
the driver judged them, and what PR 39 hands in passes."""

import json
import statistics

import pytest

from cellbench import selfcheck


@pytest.mark.parametrize("med_a,med_b,bound,improve,want", [
    (65.2627, 73.255, 0.1, "lower", False),   # PR 34: mixtral setup_s
    (73.255, 65.2627, 0.1, "lower", True),    # setup_s may be better
    (73.255, 65.2627, 0.1, "", False),        # no other metric may
    (1207.96, 1207.6, 0.01, "", True),        # PR 38: decode-closed
    (1207.96, 1207.6, 0.02, "", True),
    (6.303, 6.289, 0.05, "", True),           # long-prompt's mean, PR 38
    (11.3609, 11.539, 0.1, "", True),         # PR 37: the medians stood
    (100.0, 110.0, 0.1, "", True),            # at the bound is inside it
    (100.0, 110.1, 0.1, "", False),
])
def test_r1(med_a, med_b, bound, improve, want):
    assert selfcheck.r1(med_a, med_b, bound, improve) is want


@pytest.mark.parametrize("spread_a,spread_b,median,bound,want", [
    (10.19, 8.66, 1207.96, 0.01, False),      # PR 38: under the bound,
    (10.19, 8.66, 1207.96, 0.02, True),       # over half of it; fits 2 %
    (3.07496, 3.64673, 11.3609, 0.1, False),  # PR 37: long-prompt's p90
    (2.81819, 2.16243, 16.0019, 0.1, False),  # PR 32
    (0.014, 0.014, 6.303, 0.05, True),
    (5.0, 5.0, 100.0, 0.1, True),             # exactly half the bound
    (5.0, 5.1, 100.0, 0.1, False),
])
def test_r2(spread_a, spread_b, median, bound, want):
    assert selfcheck.r2(spread_a, spread_b, median, bound) is want


@pytest.mark.parametrize("values,want", [
    ([10.0], 0.0),
    ([10.0, 10.4], pytest.approx(0.4)),
    ([10.0, 10.1, 10.2], pytest.approx(0.1)),     # either end may go
    ([10.0, 10.1, 14.0], pytest.approx(0.1)),     # one far run left out
    ([14.0, 10.1, 10.0], pytest.approx(0.1)),
])
def test_spread_of_a_few_runs_is_their_range_less_the_farthest(values, want):
    assert selfcheck.spread(values) == want


def test_spread_of_a_set_is_between_its_quartiles():
    values = [10.0, 10.1, 10.2, 10.3, 10.4, 10.5]
    q = statistics.quantiles(values, n=4)
    narrowed = statistics.quantiles(values[:-1], n=4)
    assert selfcheck.spread(values) == pytest.approx(
        min(q[2] - q[0], narrowed[2] - narrowed[0]))
    far = values[:-1] + [19.0]
    assert selfcheck.spread(far) == pytest.approx(narrowed[2] - narrowed[0])
    assert selfcheck.spread(far) < selfcheck.width(far)


def lines(cell, runs, misses=0, trace=0):
    out = []
    for metrics in runs:
        out.append(json.dumps({
            "workload": cell, "seed": 1, "trace": trace,
            "boot": {"xla_cache_misses": misses}}))
        out.append("rc=0 seed=1 trace=0")       # chip_runs.sh's own line
        out.append(json.dumps({
            "correct": True, "attempted": 9, "failed": 0, "device": {},
            "metrics": {k: {"value": v, "unit": "x"}
                        for k, v in metrics.items()}}))
    return "\n".join(out) + "\n"


def write_sets(tmp_path, a, b):
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    pa.write_text(a)
    pb.write_text(b)
    return [str(pa), str(pb)]


def test_read_set_pairs_lines_and_leaves_cold_and_traced_runs_out(tmp_path):
    cell = "qwen2-7b.decode-closed"
    text = (lines(cell, [{"tokens_per_s": 900.0, "setup_s": 840.0}], misses=27)
            + lines(cell, [{"decode_step_ms.closed": 12.0}], trace=1)
            + lines(cell, [{"tokens_per_s": 1208.0, "setup_s": 171.0},
                           {"tokens_per_s": 1209.0, "setup_s": 172.0}]))
    (tmp_path / "s.jsonl").write_text(text)
    assert selfcheck.read_set(str(tmp_path / "s.jsonl")) == {
        cell: {"tokens_per_s": [1208.0, 1209.0], "setup_s": [171.0, 172.0]}}


@pytest.mark.parametrize("cell,metric,a,b,rc,words", [
    ("mixtral-8x7b.prompt-heavy", "setup_s",
     [65.1, 65.2627, 65.4], [73.0, 73.255, 73.4], 1, "R1 FAIL R2 n/a"),
    ("mixtral-8x7b.prompt-heavy", "setup_s",
     [73.0, 73.255, 73.4], [65.1, 65.2627, 65.4], 0, "R1 pass R2 n/a"),
    ("qwen2-7b.decode-closed", "tokens_per_s",
     [1202.0, 1207.96, 1212.19], [1203.3, 1207.6, 1211.96], 0,
     "R1 pass R2 pass"),
    ("qwen2-7b.decode-closed", "tokens_per_s",
     [1190.0, 1207.96, 1226.0], [1189.0, 1207.6, 1226.0], 1,
     "R1 pass R2 FAIL"),
    ("qwen3-next-80b-a3b.long-prompt", "tpot_mean_ms",
     [6.28, 6.303, 6.31], [6.27, 6.289, 6.30], 0, "R1 pass R2 pass"),
])
def test_main_prints_a_line_a_metric_and_fails_on_a_fail(
        tmp_path, capsys, cell, metric, a, b, rc, words):
    """Against the committed bounds: PR 34's set-up fails R1 one way
    round only; PR 38's closed loop (10.19 and 8.66 wide) passes at the
    bound it has now; a set 36 tokens/s wide does not."""
    argv = write_sets(tmp_path, lines(cell, [{metric: v} for v in a]),
                      lines(cell, [{metric: v} for v in b]))
    assert selfcheck.main(argv) == rc
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1 and out[0].startswith(f"{cell} {metric} bound ")
    assert words in out[0] and "n 3+3" in out[0]
