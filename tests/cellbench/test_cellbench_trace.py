"""The reduction from a trace to busy time, group time, top operations
and idle gaps; the operations-and-bytes functions; the peaks table."""

import json
import os

import pytest

from cellbench import roofline, trace_reduce
from cellbench.readers import roofline_decode, trace_time_per

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(__file__), "data")
with open(os.path.join(REPO, "cellbench", "module_groups",
                       "xla_default.json")) as _f:
    GROUPS = json.load(_f)


def config(name):
    with open(os.path.join(REPO, "cellbench", "configs", name + ".json")) as f:
        return json.load(f)


MS = 1e6  # ns


def tpu_planes():
    """A device plane as a TPU trace has it: 10 ms of wall time, a
    prefill of 2 ms, two decode scans of 1.5 ms, a row update."""
    mods = [(1 * MS, 3 * MS, "jit__prefill_suffix_step(11)"),
            (4 * MS, 5.5 * MS, "jit_scan_k(22)"),
            (5.6 * MS, 5.7 * MS, "jit__upd(33)"),
            (6 * MS, 7.5 * MS, "jit_scan_k(22)")]
    ops = [(1 * MS, 2 * MS, "fusion.1"), (1.5 * MS, 3 * MS, "fusion.2"),
           (4 * MS, 5.5 * MS, "while.3"), (4.1 * MS, 4.5 * MS, "fusion.1"),
           (5.6 * MS, 5.7 * MS, "scatter.4"),
           (6 * MS, 7.5 * MS, "while.3")]
    host = [(0.0, 10 * MS, "$python")]
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops},
            {"name": "Steps", "events": [(0.5 * MS, 9 * MS, "step")]}]},
    ]


@pytest.mark.parametrize("intervals,want", [
    ([], 0.0),
    ([(0, 1e9)], 1.0),
    ([(0, 1e9), (5e8, 2e9)], 2.0),           # overlap
    ([(0, 1e9), (2e9, 3e9)], 2.0),           # gap
    ([(2e9, 3e9), (0, 1e9), (1e8, 2e8)], 2.0),  # unsorted, nested
])
def test_union_of_intervals(intervals, want):
    assert trace_reduce.union_seconds(intervals) == pytest.approx(want)


def test_reduction_of_a_device_plane():
    r = trace_reduce.reduce_planes(tpu_planes(), GROUPS)
    assert r["devices"] == 1
    # first to last module or op on the device (1 .. 7.5 ms), not the
    # host's 10 ms
    assert r["window_s"] == pytest.approx(0.0065)
    # ops: [1,3] + [4,5.5] + [5.6,5.7] + [6,7.5] ms; the Steps line and
    # nested ops add nothing
    assert r["busy_s"] == pytest.approx(0.0051)
    assert r["groups"]["prefill"] == {"seconds": pytest.approx(0.002),
                                      "runs": 1}
    assert r["groups"]["decode"] == {"seconds": pytest.approx(0.003),
                                     "runs": 2}
    assert r["groups"]["other"]["runs"] == 1
    assert r["device_ops"][0] == ["while.3", pytest.approx(0.003)]
    gaps = dict(r["idle_gaps"])
    assert gaps["host not traced; device idle between "
                "jit__prefill_suffix_step and jit_scan_k"] \
        == pytest.approx(0.001)
    assert sum(gaps.values()) == pytest.approx(0.0075 - 0.001 - 0.0051)


@pytest.mark.parametrize("planes", [
    # a CPU trace: host planes only, whatever XLA wrote on them
    [{"name": "/host:CPU", "lines": [{"name": "x", "events": [
        (0, 2 * MS, "fusion"), (3 * MS, 4 * MS, "dot")]}]}],
    # a device plane with neither operations nor modules
    [{"name": "/device:TPU:0", "lines": [{"name": "Steps", "events": [
        (0, 2 * MS, "step")]}]}],
], ids=["host-only", "device-without-ops"])
def test_no_device_plane_reduces_to_nothing(planes):
    r = trace_reduce.reduce_planes(planes, GROUPS)
    assert r == {"window_s": 0.0, "busy_s": 0.0, "devices": 0, "groups": {},
                 "device_ops": [], "idle_gaps": []}
    # and no reader turns it into a device number
    ctx = {"traces": [r], "rates": [{"decode_steps_per_s": 100.0}]}
    assert trace_time_per.read(ctx, {"group": "decode",
                                     "rate": "decode_steps_per_s"}) is None


def test_empty_trace_reduces_to_nothing():
    r = trace_reduce.reduce_planes([{"name": "/host:CPU", "lines": []}],
                                   GROUPS)
    assert r["busy_s"] == 0.0 and r["groups"] == {}


@pytest.mark.parametrize("module,group", [
    ("jit__prefill_step", "prefill"), ("jit__prefill_suffix_step", "prefill"),
    ("jit__prefill_ragged_step", "prefill"), ("jit_scan_k", "decode"),
    ("jit__upd", "other"), ("jit_convert_element_type", "other")])
def test_module_groups(module, group):
    assert trace_reduce.group_of(module, GROUPS) == group
    assert trace_reduce.module_name(module + "(123)") == module


def test_time_per_unit_is_share_over_traced_rate():
    r = trace_reduce.reduce_planes(tpu_planes(), GROUPS)
    ctx = {"traces": [r], "rates": [{"decode_steps_per_s": 20.0}]}
    # decode holds 3 of the 6.5 traced ms; at 20 steps/s that is
    # 3 / 6.5 / 20 s a step
    assert trace_time_per.read(
        ctx, {"group": "decode", "rate": "decode_steps_per_s",
              "scale": 1e3}) == pytest.approx(1e3 * 3 / 6.5 / 20)
    ctx["rates"][0]["decode_steps_per_s"] = 0.0
    assert trace_time_per.read(
        ctx, {"group": "decode", "rate": "decode_steps_per_s"}) is None


def test_recorded_tpu_trace_reduces():
    """A small trace recorded on the v5e: ``tiny-random`` decoding six
    streams, 0.3 s captured through ``/debug/profile``, cut with
    TensorFlow's ``xplane_pb2`` to the device plane's first twelve decode
    scans and the profiler thread's line (290 KB). The plane and line
    names the reduction relies on are the ones a real TPU trace has."""
    path = os.path.join(DATA, "tiny_v5e.xplane.pb")
    r = trace_reduce.reduce_planes(trace_reduce.load_planes(path), GROUPS)
    assert r["devices"] == 1
    # twelve decode scans of tiny-random (my chip run, PR 23): 18.07 ms
    # of device time in 45.4 ms, the rest idle between the scans
    assert r["groups"] == {"decode": {"seconds": pytest.approx(0.018074499),
                                      "runs": 12}}
    assert r["busy_s"] == pytest.approx(0.018068919)
    assert r["window_s"] == pytest.approx(0.045392, abs=1e-5)
    assert r["device_ops"][0][0] == "%while.8 while"
    assert all(len(name) <= 120 for name, _ in r["device_ops"])
    assert r["idle_gaps"] == [[
        "host not traced; device idle between jit_scan_k and jit_scan_k",
        pytest.approx(r["window_s"] - r["busy_s"], rel=0.01)]]


# -- operations and bytes from shapes -------------------------------------

def test_qwen2_shapes():
    q = config("qwen2-7b-1chip")
    assert roofline.attn_matrix_params(q) == 29_360_128
    assert roofline.mlp_matrix_params(q) == 203_685_888
    assert roofline.kv_bytes_per_token(q) == 56 * 1024
    assert roofline.decode_weight_bytes(q, "int8") == 7_070_285_824
    assert roofline.decode_weight_bytes(q, "") == 2 * 7_070_285_824
    assert roofline.decode_step_bytes(q, "int8", 1e9) == 8_070_285_824


def test_mixtral_decode_streams_every_expert():
    m = config("mixtral-8x7b-1chip")
    assert roofline.mlp_matrix_params(m) == 176_160_768
    assert roofline.kv_bytes_per_token(m) == 8 * 4096
    # decode reads all 8 experts of each of the 8 layers
    assert roofline.decode_weight_bytes(m, "int8") == \
        8 * (41_943_040 + 8 * 176_160_768 + 32_768) + 4096 * 32000


def test_peaks_table():
    p = roofline.peaks_for("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["int8_ops_per_s"],
            p["hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9")


@pytest.mark.parametrize("least,took,want", [
    (1.0, 2.0, 50.0), (3.0, 2.0, 150.0), (1.0, 0.0, None)])
def test_share_is_never_clamped(least, took, want):
    assert roofline.share_pct(least, took) == want


def test_decode_roofline_reader():
    r = trace_reduce.reduce_planes(tpu_planes(), GROUPS)
    q = config("qwen2-7b-1chip")
    # the live keys and values are the ones read INSIDE the capture,
    # whatever the window's scrapes saw (here: a drained engine)
    drained = {"states": [{"kv_bytes_in_use": 0.0}]}
    ctx = {"config": q, "device_kind": "TPU v5 lite", "traces": [r],
           "rates": [{"decode_steps_per_s": 20.0,
                      "kv_bytes_in_use": 819e6}],
           "snap0": drained, "snap1": drained}
    least = (7_070_285_824 + 819e6) / 819e9   # seconds a step, at best
    assert roofline_decode.read(ctx, {}) == \
        pytest.approx(100.0 * least / (3 / 6.5 / 20))
