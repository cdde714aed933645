"""Percentile and per-request gap arithmetic, and the histogram deltas
and counter readers against recorded /metrics text."""

import os
import types

import pytest

from cellbench import stats
from cellbench.readers import (counter_ratio, latency_percentile,
                               window_token_rate)

DATA = os.path.join(os.path.dirname(__file__), "data")


def scrape(name):
    with open(os.path.join(DATA, name)) as f:
        return stats.parse_prometheus(f.read())


@pytest.mark.parametrize("values,q,want", [
    ([5.0], 90, 5.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 102)), 90, 91.0),
    ([10, 0], 25, 2.5),
    ([3, 1, 2], 100, 3.0),
    ([3, 1, 2], 0, 1.0),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("first,last,tokens,want", [
    (1.0, 2.0, 11, 100.0),   # ten gaps in one second
    (0.0, 0.0, 8, 0.0),      # one decode window delivered at once
    (1.0, 1.5, 2, 500.0),
    (1.0, 1.0, 1, None),     # a one-token reply has no gap
])
def test_tpot(first, last, tokens, want):
    got = stats.tpot_ms(first, last, tokens)
    assert got is None if want is None else got == pytest.approx(want)


def test_prometheus_text_sums_label_sets_and_drops_buckets():
    gw = scrape("gateway_metrics_after.txt")
    name = "gen_ai_server_time_to_first_token_seconds"
    assert gw[name + "_sum"] == pytest.approx(0.84)
    assert gw[name + "_count"] == 4.0
    assert not any(k.endswith(("_bucket", "_created")) for k in gw)


@pytest.mark.parametrize("hist,mean", [
    ("tpuserve_queue_wait_hist_ms", 25.0),
    ("tpuserve_ttft_hist_ms", 200.0),
    ("tpuserve_never_observed_hist_ms", None),
])
def test_hist_mean_delta_is_exact(hist, mean):
    got = stats.hist_mean_delta(
        scrape("metrics_before.txt"), scrape("metrics_after.txt"), hist)
    assert got is None if mean is None else got == pytest.approx(mean)


def ctx(**kw):
    before, after = scrape("metrics_before.txt"), scrape("metrics_after.txt")
    base = {"snap0": {"prom": before, "gateway": {},
                      "state": {"a": 10.0, "b": 100.0}},
            "snap1": {"prom": after,
                      "gateway": scrape("gateway_metrics_after.txt"),
                      "state": {"a": 40.0, "b": 200.0}}}
    base.update(kw)
    return base


def test_gateway_and_replica_means_of_the_summary():
    # what run.py's summary prints as ``ttft_parts_ms``: the gateway's
    # 0.84 s over 4 = 210 ms; the replicas' 800 ms over 4 = 200 ms
    c = ctx()
    assert stats.hist_mean_delta(
        c["snap0"]["gateway"], c["snap1"]["gateway"],
        "gen_ai_server_time_to_first_token_seconds") == pytest.approx(0.21)
    assert stats.hist_mean_delta(
        c["snap0"]["prom"], c["snap1"]["prom"],
        "tpuserve_ttft_hist_ms") == pytest.approx(200.0)


@pytest.mark.parametrize("args,want", [
    ({"num": ["a"], "den": ["b"]}, 0.3),
    ({"num": ["a"], "den": ["b"], "scale": 100.0}, 30.0),
    ({"num": ["a"], "den": ["b"], "complement": True, "scale": 100.0}, 70.0),
    ({"num": ["a"], "den": ["a", "b"]}, 30.0 / 130.0),
])
def test_counter_ratio(args, want):
    assert counter_ratio.read(ctx(), args) == pytest.approx(want)


def test_counter_ratio_of_a_still_counter_is_nothing():
    c = ctx()
    c["snap1"]["state"] = dict(c["snap0"]["state"])
    assert counter_ratio.read(c, {"num": ["a"], "den": ["b"]}) is None


def test_counter_ratio_of_a_voided_window_is_nothing():
    # run.py sets snap1 to None where its scrape ended over 1 s late
    assert counter_ratio.read(ctx(snap1=None),
                              {"num": ["a"], "den": ["b"]}) is None


def result(due, deltas, status="ok"):
    r = types.SimpleNamespace(due=due, deltas=deltas, status=status)
    r.ok = status == "ok"
    r.first = deltas[0][0] if deltas else None
    r.last = deltas[-1][0] if deltas else None
    r.tokens = sum(k for _, k in deltas)
    return r


def test_latency_is_from_due_and_over_completed_requests_only():
    window = [result(10.0, [(10.1, 1), (10.3, 2)]),
              result(10.0, [(10.5, 1), (11.5, 10)]),
              result(10.0, [(19.0, 1)], status="cut")]
    c = {"window": window}
    assert latency_percentile.read(c, {"what": "ttft", "q": 50}) \
        == pytest.approx(300.0)
    assert latency_percentile.read(c, {"what": "ttft", "q": 100}) \
        == pytest.approx(500.0)
    # gaps: 0.2 s / 2 = 100 ms and 1.0 s / 10 = 100 ms
    assert latency_percentile.read(c, {"what": "tpot", "q": 90}) \
        == pytest.approx(100.0)
    assert latency_percentile.read({"window": []},
                                   {"what": "ttft", "q": 50}) is None


def test_token_rate_counts_tokens_inside_the_window_only():
    rs = [result(0, [(9.9, 5), (10.0, 8), (12.0, 8), (20.0, 8)]),
          result(0, [(15.0, 4)], status="cut")]
    c = {"results": rs, "t0": 10.0, "t1": 20.0, "seconds": 10.0}
    assert window_token_rate.read(c, {}) == pytest.approx(2.0)
    assert window_token_rate.read(dict(c, results=[]), {}) is None
