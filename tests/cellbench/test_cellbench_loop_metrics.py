"""The three per-layer metrics that read the engine's loop ledger
(``/state`` ``loop_*`` and ``capture_*``): their readers on made-up
snapshots, where they must say nothing, and a dry traced run on the CPU
that reports the two counter metrics."""

import json
import os

import pytest

import cellbench_sandbox as sb
from cellbench.readers import loop_counter_ratio, trace_per_captured

with open(os.path.join(sb.REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


def spec(name):
    with open(os.path.join(sb.REPO, "cellbench", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


HOST = spec("engine_host_ms_per_step")
WAIT = spec("engine_device_wait_share")
CAPTURED = spec("prefill_dev_ms_per_captured_ktok")

PHASES = ("reap", "admit", "admit_wait", "prefill_dispatch",
          "prefill_block", "state_build", "row_update", "decode_dispatch",
          "window_fetch", "emit", "idle", "other")


def state(ms_by_phase: dict, steps: int) -> dict:
    """A /state as the ledger exports it, from milliseconds a phase."""
    ns = {p: int(ms_by_phase.get(p, 0) * 1e6) for p in PHASES}
    out = {f"loop_{p}_ns": v for p, v in ns.items()}
    out["loop_ns"] = sum(ns.values())
    out["loop_busy_ns"] = out["loop_ns"] - ns["idle"]
    out["decode_steps"] = steps
    return out


def ctx_of(s0: dict, s1: dict | None) -> dict:
    return {"snap0": {"state": s0},
            "snap1": None if s1 is None else {"state": s1}}


ZERO = state({}, 0)
BUSY = state({"reap": 1, "admit": 9, "admit_wait": 50,
              "prefill_dispatch": 40, "prefill_block": 300,
              "state_build": 5, "row_update": 20, "decode_dispatch": 100,
              "window_fetch": 1500, "emit": 24, "idle": 700, "other": 1},
             100)


@pytest.mark.parametrize("reader_spec,want", [
    # working phases 1+9+40+5+20+100+24+1 = 200 ms over 100 steps
    (HOST, 2.0),
    # (1500 + 300) of the 2050 ms that were not idle
    (WAIT, 100.0 * 1800 / 2050),
])
def test_counter_metrics_by_hand(reader_spec, want):
    got = loop_counter_ratio.read(ctx_of(ZERO, BUSY), reader_spec["args"])
    assert got == pytest.approx(want)


@pytest.mark.parametrize("reader_spec", [HOST, WAIT],
                         ids=["host_ms_per_step", "device_wait_share"])
@pytest.mark.parametrize("case", ["late_scrape", "parent_program",
                                  "nothing_happened"])
def test_counter_metrics_say_nothing(reader_spec, case):
    ctx = {
        # the closing scrape came too late: the counters are voided
        "late_scrape": ctx_of(ZERO, None),
        # a commit from before the ledger serves no loop_* key
        "parent_program": ctx_of({"decode_steps": 0},
                                 {"decode_steps": 100}),
        # no decode step, no busy time: nothing to divide by
        "nothing_happened": ctx_of(ZERO, ZERO),
    }[case]
    assert loop_counter_ratio.read(ctx, reader_spec["args"]) is None


def test_host_ms_counts_every_working_phase_and_no_waiting_one():
    num = set(HOST["args"]["num"])
    waiting = {"window_fetch", "prefill_block", "idle", "admit_wait"}
    assert num == {f"loop_{p}_ns" for p in PHASES if p not in waiting}
    assert HOST["args"]["den"] == ["decode_steps"]
    assert set(WAIT["args"]["num"]) == {"loop_window_fetch_ns",
                                        "loop_prefill_block_ns"}


def trace(seconds: float, devices: int = 1) -> dict:
    return {"devices": devices, "window_s": 4.0,
            "groups": {"prefill": {"seconds": seconds, "runs": 12}}}


def cap_ctx(traces, before, after) -> dict:
    return {"traces": traces,
            "snap0": {"states": [{"capture_prefill_tokens_real": b}
                                 for b in before]},
            "snap2": {"states": [{"capture_prefill_tokens_real": a}
                                 for a in after]}}


@pytest.mark.parametrize("ctx,want", [
    # 0.3 s of prefill programs over 3000 tokens counted in the capture
    (cap_ctx([trace(0.3)], [1000], [4000]), 100.0),
    # two replicas: the mean of 100 and 50 ms/ktok
    (cap_ctx([trace(0.3), trace(0.1)], [0, 500], [3000, 2500]), 75.0),
    # a CPU's trace has no device plane
    (cap_ctx([trace(0.3, devices=0)], [0], [3000]), None),
    # nothing was counted inside the capture
    (cap_ctx([trace(0.3)], [3000], [3000]), None),
    # no capture at all (an untraced run has no traces)
    (cap_ctx([], [0], [3000]), None),
])
def test_trace_per_captured_by_hand(ctx, want):
    got = trace_per_captured.read(ctx, CAPTURED["args"])
    assert got == (None if want is None else pytest.approx(want))


def test_trace_per_captured_without_the_key_or_the_group():
    ctx = cap_ctx([trace(0.3)], [0], [3000])
    ctx["snap0"]["states"] = [{}]
    ctx["snap2"]["states"] = [{"decode_steps": 5}]  # a parent's /state
    assert trace_per_captured.read(ctx, CAPTURED["args"]) is None
    ctx = cap_ctx([{"devices": 1, "window_s": 4.0, "groups": {}}],
                  [0], [3000])
    assert trace_per_captured.read(ctx, CAPTURED["args"]) is None


@pytest.mark.parametrize("name,cells,moves", [
    ("engine_host_ms_per_step.open",
     ["qwen2-7b.chat-steady", "mixtral-8x7b.prompt-heavy"], "tpot_mean_ms"),
    ("engine_host_ms_per_step.closed", ["qwen2-7b.decode-closed"],
     "tokens_per_s"),
    ("engine_device_wait_share", ["qwen2-7b.decode-closed"],
     "tokens_per_s"),
    ("prefill_dev_ms_per_captured_ktok",
     ["qwen2-7b.chat-steady", "mixtral-8x7b.prompt-heavy"], "tpot_mean_ms"),
])
def test_manifest_entries(name, cells, moves):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == cells and entry["moves"] == moves
    # the cell reports the end-to-end metric the entry moves
    e2e = next(m for m in MANIFEST["end_to_end"] if m["name"] == moves)
    assert set(cells) <= set(e2e["workloads"])
    assert os.path.exists(os.path.join(
        sb.REPO, "cellbench", "layer_metrics",
        name.split(".", 1)[0] + ".json"))


MIX = {
    "name": "t-loop", "loop": "closed", "clients": 4,
    "arrivals": {"process": "poisson", "zero_gap_share": 0.25},
    "prompt_tokens": {"dist": "uniform", "min": 10, "max": 60},
    "output_tokens": {"dist": "uniform", "min": 8, "max": 24},
    "sharing": {"kind": "none"},
    "serve_flags": ["--warm-prefill-buckets", "2"],
    "lead_in": {"tour": [[[40, 40], [10, 8, 0.1]]], "traffic_seconds": 2},
}


def test_dry_traced_run_reports_the_counter_metrics(tmp_path):
    """A cell added as files (a tiny model on the CPU, the committed
    metric files under names of its own) and run with ``--trace 1``:
    the two counter metrics read the ledger the replica serves; the
    captured prefill figure needs a device plane and says nothing."""
    dst = sb.make_checkout(str(tmp_path))
    sb.add_file(dst, "cellbench/configs/t-llama.json",
                sb.tiny_config("t-llama", "llama"))
    sb.add_file(dst, "cellbench/traffic/t-loop.json", MIX)
    names = ("engine_host_ms_per_step.t", "engine_device_wait_share.t",
             "prefill_dev_ms_per_captured_ktok.t")
    sb.add_entries(
        dst,
        configs=[{"name": "t-llama", "source": "tests",
                  "file": "cellbench/configs/t-llama.json", "reduced": [],
                  "why": "test"}],
        workloads=[{"name": "t-llama.loop", "config": "t-llama",
                    "traffic": "t-loop", "chips": 1, "why": "test"}],
        end_to_end=[{"name": "tokens_per_s.t", "unit": "tokens/s",
                     "better": "higher", "bound": 0.1,
                     "source": "host_clock",
                     "workloads": ["t-llama.loop"]}],
        per_layer=[{"name": n, "unit": "ms", "better": "lower",
                    "source": "program_counter", "layer": "scheduler",
                    "moves": "tokens_per_s.t",
                    "workloads": ["t-llama.loop"]} for n in names])
    rc, last, lines, err = sb.run_cell(dst, "t-llama.loop", 2 ** 31 + 7,
                                       4, 1)
    assert rc == 0, err[-3000:]
    assert last["correct"] is True, lines[-2]
    host = last["metrics"]["engine_host_ms_per_step.t"]["value"]
    wait = last["metrics"]["engine_device_wait_share.t"]["value"]
    assert host > 0 and 0 < wait < 100
    assert "prefill_dev_ms_per_captured_ktok.t" not in last["metrics"]
