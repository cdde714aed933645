"""End-to-end dry runs on the CPU: one per loop kind, both families,
two replicas behind the gateway, a session mix that shares prefixes —
every cell of them ADDED AS FILES to a temporary copy of the benchmark
(a configuration, a mix, a per-layer metric, a reader, the manifest
entries), none of the committed files edited. Nothing measured here is
a speed: the runs prove the path and the output's shape."""

import json

import pytest

import cellbench_sandbox as sb

LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}

OPEN = {
    "name": "t-open", "loop": "open", "rate_per_s": 5.0,
    "arrivals": {"process": "poisson", "zero_gap_share": 0.25},
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 8, "max": 80},
    "output_tokens": {"dist": "uniform", "min": 4, "max": 20},
    "sharing": {"kind": "none"},
    "serve_flags": ["--warm-prefill-buckets", "2"],
    "lead_in": {"tour": [[[40, 40], [10, 8, 0.1]]], "traffic_seconds": 2},
}
CLOSED = dict(OPEN, name="t-closed", loop="closed", clients=6)
SESSIONS = dict(
    OPEN, name="t-sessions", rate_per_s=2.0,
    prompt_tokens={"dist": "uniform", "min": 10, "max": 30},
    output_tokens={"dist": "uniform", "min": 4, "max": 10},
    sharing={"kind": "sessions", "system_prompts": 2,
             "system_tokens": {"dist": "fixed", "value": 150},
             "turns": {"dist": "uniform", "min": 2, "max": 3},
             "think_s": {"dist": "exponential", "mean": 0.3}})

READER = '''"""Share of page-eligible prompts that resumed from cached pages."""


def read(ctx, args):
    s0, s1 = ctx["snap0"]["state"], ctx["snap1"]["state"]
    hits = s1["prefix_cache_hits"] - s0["prefix_cache_hits"]
    miss = s1["prefix_cache_misses"] - s0["prefix_cache_misses"]
    return 100.0 * hits / (hits + miss) if hits + miss else None
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    dst = sb.make_checkout(str(tmp_path_factory.mktemp("checkout")))
    sb.add_file(dst, "cellbench/configs/t-llama-x2.json",
                sb.tiny_config("t-llama-x2", "llama", replicas=2))
    sb.add_file(dst, "cellbench/configs/t-llama.json",
                sb.tiny_config("t-llama", "llama"))
    sb.add_file(dst, "cellbench/configs/t-moe.json",
                sb.tiny_config("t-moe", "mixtral"))
    for mix in (OPEN, CLOSED, SESSIONS):
        sb.add_file(dst, f"cellbench/traffic/{mix['name']}.json", mix)
    sb.add_file(dst, "cellbench/readers/prefix_hits.py", READER)
    sb.add_file(dst, "cellbench/layer_metrics/prefix_hit_share.json",
                {"name": "prefix_hit_share", "reader": "prefix_hits",
                 "args": {}})
    cells = {"t-llama-x2.sessions": ("t-llama-x2", "t-sessions", 2),
             "t-llama.open": ("t-llama", "t-open", 1),
             "t-moe.closed": ("t-moe", "t-closed", 1)}
    sb.add_entries(
        dst,
        configs=[{"name": c, "source": "tests",
                  "file": f"cellbench/configs/{c}.json", "reduced": [],
                  "why": "test"} for c in ("t-llama-x2", "t-llama", "t-moe")],
        workloads=[{"name": n, "config": c, "traffic": t, "chips": k,
                    "why": "test"} for n, (c, t, k) in cells.items()],
        end_to_end=[
            {"name": "ttft_p50_ms.t", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["t-llama.open", "t-llama-x2.sessions"]},
            {"name": "tokens_per_s.t", "unit": "tokens/s",
             "better": "higher", "bound": 0.1, "source": "host_clock",
             "workloads": ["t-moe.closed"]}],
        per_layer=[
            {"name": "prefix_hit_share", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "scheduler",
             "moves": "ttft_p50_ms.t",
             "workloads": ["t-llama-x2.sessions"]},
            {"name": "prefill_padded_frac.t", "unit": "%", "better": "lower",
             "source": "program_counter", "layer": "scheduler",
             "moves": "ttft_p50_ms.t",
             "workloads": ["t-llama-x2.sessions"]}])
    # a new quantity is a new file; ``tokens_per_s.t`` and
    # ``prefill_padded_frac.t`` are committed quantities under names of their
    # own (another ``moves``, other cells) and need none
    sb.add_file(dst, "cellbench/e2e_metrics/ttft_p50_ms.json",
                {"name": "ttft_p50_ms", "reader": "latency_percentile",
                 "args": {"what": "ttft", "q": 50}})
    return dst


def check_line(last, lines, chips):
    assert set(last) - {"breakdown"} == LAST_LINE_KEYS
    assert set(last["device"]) - {"busy_s", "window_s"} == {
        "platform", "kind", "count", "memory_peak_bytes"}
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == chips
    assert last["attempted"] > 0 and last["failed"] == 0
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    return json.loads(lines[-2])  # the summary, one line earlier


def test_open_loop_llama(checkout):
    rc, last, lines, err = sb.run_cell(checkout, "t-llama.open",
                                       2 ** 31 + 11, 3, 0)
    assert rc == 0, err[-3000:]
    summary = check_line(last, lines, 1)
    assert set(last["metrics"]) == {"ttft_p50_ms.t", "setup_s"}
    assert "busy_s" not in last["device"] and "breakdown" not in last
    assert last["attempted"] == 15
    assert summary["sent"]["tour"] == 2
    assert summary["checks"]["every_stream_exact"]
    assert summary["checks"]["ledger_reconciles"]
    assert summary["checks"]["param_bytes"]
    assert summary["checks"]["no_compile_in_window"], summary
    assert last["correct"] is True
    assert summary["generator_lateness_ms"]["max"] < 5000


def test_closed_loop_moe(checkout):
    rc, last, lines, err = sb.run_cell(checkout, "t-moe.closed", 5, 3, 0)
    assert rc == 0, err[-3000:]
    summary = check_line(last, lines, 1)
    assert set(last["metrics"]) == {"tokens_per_s.t", "setup_s"}
    assert summary["sent"]["lead"] >= 6 and summary["sent"]["after"] == 0
    assert summary["checks"]["every_stream_exact"]
    assert summary["checks"]["ledger_reconciles"]
    assert last["correct"] is True, summary


def test_sessions_on_two_replicas_traced(checkout):
    rc, last, lines, err = sb.run_cell(checkout, "t-llama-x2.sessions",
                                       123456789, 5, 1)
    assert rc == 0, err[-3000:]
    summary = check_line(last, lines, 2)
    # the metric and the reader that were added as files
    assert last["metrics"]["prefix_hit_share"]["value"] > 0
    assert "prefill_padded_frac.t" in last["metrics"]
    assert "warmup_s" in last["metrics"]          # a committed one, all cells
    assert "decode_step_ms.open" not in last["metrics"]  # not this cell's
    assert "ttft_p50_ms.t" not in last["metrics"]  # end-to-end: trace 0 only
    # the capture ran and was reduced, and a CPU's trace has no device
    # plane: no device time, no breakdown, no trace metric
    assert "busy_s" not in last["device"] and "breakdown" not in last
    assert "prefill_padded_frac.t" in last["metrics"]  # counters on time
    assert 0 <= summary["backlog_at_counters_end"]["read_late_s"] < 1.0
    assert summary["checks"]["every_stream_exact"]
    assert summary["checks"]["ledger_reconciles"], summary
