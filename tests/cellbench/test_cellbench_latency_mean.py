"""The judged gap statistic of the open-loop cells since PR 39: the MEAN
over the window's completed requests of the per-request gap
(``readers/latency_mean.py``, ``e2e_metrics/tpot_mean_ms.json``), with
the p90 of the same quantity kept beside it as a per-layer reading
(``layer_metrics/gap_p90_ms.json``); and the manifest's bookkeeping that
goes with the swap. The sandboxed tiny cell that runs it end to end is
``test_cellbench_qwen3_next.py::test_tiny_cell_end_to_end``."""

import json
import os

import pytest

from cellbench import run, stats
from cellbench.client import Result
from cellbench.readers import latency_mean, latency_percentile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    M = json.load(_f)
OPEN = ["qwen2-7b.chat-steady", "mixtral-8x7b.prompt-heavy",
        "qwen3-next-80b-a3b.long-prompt"]
TPOT = {"what": "tpot"}


def res(first, last, tokens, status="ok", due=0.0, phase="window"):
    """A request whose ``tokens`` arrived evenly from first to last."""
    step = (last - first) / max(tokens - 1, 1)
    return Result(due=due, phase=phase, expected=tokens, status=status,
                  deltas=[(first + i * step, 1) for i in range(tokens)])


@pytest.mark.parametrize("window", [
    [],                                              # nothing sent
    [res(0.0, 1.0, 5, status="cut")],                # nothing completed
    [res(0.0, 1.0, 5, status="429"), res(1.0, 2.0, 3, status="short")],
    [res(4.0, 4.0, 1), res(5.0, 5.0, 1)],            # one-token replies only
], ids=["empty", "cut", "all-failed", "one-token"])
def test_nothing_to_read_says_nothing(window):
    assert latency_mean.read({"window": window}, TPOT) is None


@pytest.mark.parametrize("extra", [
    [], [res(4.0, 4.0, 1)], [res(1.0, 9.0, 3, status="cut")],
    [res(4.0, 4.0, 1), res(1.0, 9.0, 3, status="cut"),
     res(0.0, 5.0, 2, status="429")]],
    ids=["alone", "one-token", "failed", "both"])
def test_one_token_replies_and_failed_requests_are_left_out(extra):
    window = [res(1.0, 1.9, 10), res(2.0, 2.4, 5), *extra]
    assert latency_mean.read({"window": window}, TPOT) == \
        pytest.approx(100.0)


def test_the_mean_is_over_requests_not_over_tokens():
    window = [res(0.0, 0.9, 10), res(0.0, 3.0, 101)]    # 100 ms and 30 ms
    # over tokens (every gap weighing the same) it would be 3.9 s / 109 = 35.8
    assert latency_mean.read({"window": window}, TPOT) == pytest.approx(65.0)


def test_ttft_is_from_due_and_counts_one_token_replies():
    window = [res(1.0, 2.0, 4, due=0.5), res(3.0, 3.0, 1, due=1.0),
              res(9.0, 9.5, 3, status="cut")]
    assert latency_mean.read({"window": window}, {"what": "ttft"}) == \
        pytest.approx((500.0 + 2000.0) / 2)


@pytest.mark.parametrize("n", [1, 2, 7, 40, 145])
def test_it_is_the_summary_lines_mean(n):
    """``run.py`` prints ``tpot_ms.mean`` from the same list: the sum of
    ``stats.tpot_ms`` over the completed requests over their number."""
    window = [res(0.1 * i, 0.1 * i + 0.013 * (i % 5 + 1) * (7 + i % 3),
                  8 + i % 3) for i in range(n)]
    ok = [r for r in window if r.ok]
    tpot = [v for v in (stats.tpot_ms(r.first, r.last, r.tokens)
                        for r in ok) if v is not None]
    assert latency_mean.read({"window": window}, TPOT) == \
        sum(tpot) / len(tpot)


def test_the_mean_lies_under_the_p90_of_a_skewed_window():
    window = [res(0.0, 0.004 * 49, 50) for _ in range(36)] + \
        [res(0.0, 0.0167 * 49, 50) for _ in range(4)]   # long-prompt's shape
    mean = latency_mean.read({"window": window}, TPOT)
    p90 = latency_percentile.read({"window": window}, dict(TPOT, q=90))
    assert mean == pytest.approx(0.9 * 4.0 + 0.1 * 16.7)
    assert 4.0 < mean < p90 <= 16.7 + 1e-9


@pytest.mark.parametrize("kind,name,reader,args", [
    ("e2e", "tpot_mean_ms", latency_mean, {"what": "tpot"}),
    ("layer", "gap_p90_ms", latency_percentile, {"what": "tpot", "q": 90})])
def test_the_definition_files_reach_their_readers(kind, name, reader, args):
    with open(os.path.join(ROOT, "cellbench", kind + "_metrics",
                           name + ".json")) as fh:
        spec = json.load(fh)
    assert (spec["name"], spec["args"]) == (name, args)
    ctx = {"window": [res(1.0, 1.9, 10), res(2.0, 2.8, 5), res(0, 0, 1)]}
    assert run.read_metric(kind, name, ctx) == reader.read(ctx, args)
    assert run.read_metric(kind, name + ".variant", ctx) == \
        reader.read(ctx, args)


@pytest.mark.parametrize("cell", OPEN)
def test_an_open_cell_judges_the_mean_and_reads_the_p90(cell):
    e2e = {m["name"]: m for m in run.cell_metrics(M, "end_to_end", cell)}
    assert set(e2e) == {"tpot_mean_ms", "setup_s"}
    assert (e2e["tpot_mean_ms"]["unit"], e2e["tpot_mean_ms"]["better"],
            e2e["tpot_mean_ms"]["source"]) == ("ms", "lower", "host_clock")
    layer = {m["name"]: m for m in run.cell_metrics(M, "per_layer", cell)}
    p90 = layer["gap_p90_ms"]
    assert (p90["unit"], p90["better"], p90["source"], p90["layer"],
            p90["moves"], p90["workloads"]) == \
        ("ms", "lower", "host_clock", "scheduler", "tpot_mean_ms", OPEN)
    assert "bound" not in p90          # a reading, not a veto
    for m in layer.values():
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_no_entry_names_the_p90_that_was_judged(kind):
    for m in M[kind]:
        assert "tpot_p90_ms" not in (m["name"], m.get("moves"))
    assert not os.path.exists(os.path.join(
        ROOT, "cellbench", "e2e_metrics", "tpot_p90_ms.json"))


def test_the_bounds_the_cells_are_judged_at():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert set(e2e) == {"tpot_mean_ms", "tokens_per_s", "setup_s"}
    assert e2e["tpot_mean_ms"]["workloads"] == OPEN
    assert 0.01 <= e2e["tpot_mean_ms"]["bound"] <= 0.1
    # R2 puts 1 % out of reach of a cell that repeats to 0.7-0.85 %
    assert e2e["tokens_per_s"]["bound"] in (0.02, 0.03)
    assert e2e["tokens_per_s"]["workloads"] == ["qwen2-7b.decode-closed"]
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    assert M["run_seconds"] == 50
