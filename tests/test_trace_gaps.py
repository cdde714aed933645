"""``tools/trace_gaps.py`` on a hand-made plane list: the largest device
idle gaps with the engine phase that overlaps each, device self seconds
per named scope, module runs and the windows dispatched in the capture.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import trace_gaps  # noqa: E402

MS = 1e6  # ns


def planes():
    """10 ms of one device: a prefill chunk, two decode scans with a row
    update between them, and the engine thread's spans beside it."""
    mods = [(1 * MS, 3 * MS, "jit__prefill_suffix_step(11)"),
            (4 * MS, 5.5 * MS, "jit_scan_k(22)"),
            (5.6 * MS, 5.7 * MS, "jit__upd(33)"),
            (7.7 * MS, 9.2 * MS, "jit_scan_k(22)")]
    ops = [
        (1 * MS, 3 * MS, "%fusion.1 = bf16[4] fusion(%p)",
         {"tf_op": "jit(_prefill_suffix_step)/jit(main)/layer/attn/dot"}),
        # the scan's while holds its body's operations
        (4 * MS, 5.5 * MS, "%while.3 = (s32[]) while(%t)", {}),
        (4.1 * MS, 4.5 * MS, "%fusion.7 = f32[4] fusion(%a)",
         {"tf_op": "jit(scan_k)/jit(main)/while/body/layer/mlp/mul"}),
        (4.5 * MS, 5.3 * MS, "%sort.16 = f32[4] sort(%l)",
         {"long_name": 'op_name="jit(scan_k)/while/body/sample/sort"'}),
        (5.6 * MS, 5.7 * MS, "%scatter.4 = s32[4] scatter(%s)", {}),
        (7.7 * MS, 9.2 * MS, "%while.3 = (s32[]) while(%t)", {}),
        (7.8 * MS, 9.0 * MS, "%gather.2 = bf16[4] gather(%kv)",
         {"tf_op": "jit(scan_k)/while/body/layer/kv_gather/gather"}),
    ]
    host = [
        (0.5 * MS, 3.1 * MS, "engine/prefill_block", {}),
        (3.1 * MS, 3.3 * MS, "engine/admit", {}),
        (3.3 * MS, 3.9 * MS, "engine/row_update", {"pages": 8, "rows": 1}),
        (3.9 * MS, 4.0 * MS, "engine/decode_dispatch",
         {"k": 2, "slots": 3, "draft": 0, "pages": 8}),
        (4.0 * MS, 5.6 * MS, "engine/window_fetch", {"k": 2, "slots": 3}),
        (5.6 * MS, 5.9 * MS, "engine/emit", {}),
        (5.9 * MS, 7.5 * MS, "engine/admit", {}),
        (6.0 * MS, 6.0 * MS, "request/admitted", {"rid": "chat-9"}),
        (7.5 * MS, 7.7 * MS, "engine/decode_dispatch",
         {"k": 8, "slots": 4, "draft": 0, "pages": 8}),
        (0.0, 10 * MS, "$python", {}),
    ]
    return [
        {"name": "/host:CPU", "lines": [{"name": "engine", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops},
            {"name": "Steps", "events": [(0.5 * MS, 9 * MS, "step", {})]}]},
    ]


def test_largest_gaps_name_the_host_phase():
    gaps = trace_gaps.largest_gaps(planes())
    assert [round(g["ms"], 3) for g in gaps] == [2.0, 1.0, 0.1]
    big = gaps[0]
    assert (big["before"], big["after"]) == ("jit__upd", "jit_scan_k")
    assert big["host_phase"] == "admit"
    assert [(h["phase"], round(h["overlap_ms"], 3)) for h in big["host"]] \
        == [("admit", 1.6), ("emit", 0.2), ("decode_dispatch", 0.2)]
    assert big["host"][2]["facts"] == [
        {"k": 8, "slots": 4, "draft": 0, "pages": 8}]
    assert big["requests"] == [{"mark": "admitted", "rid": "chat-9"}]
    # the second: after the prefill chunk the host updated a row
    assert gaps[1]["host_phase"] == "row_update"
    assert gaps[1]["host"][0]["facts"] == [{"pages": 8, "rows": 1}]
    assert gaps[2]["host_phase"] == "window_fetch"


def test_top_limits_the_list():
    assert len(trace_gaps.largest_gaps(planes(), top=1)) == 1


def test_scope_seconds_are_self_time():
    got = trace_gaps.scope_seconds(planes())
    assert got == {
        "layer/attn": pytest.approx(2e-3),
        "layer/kv_gather": pytest.approx(1.2e-3),
        "sample": pytest.approx(0.8e-3),
        # the two whiles less their children, and the scatter
        trace_gaps.UNSCOPED: pytest.approx(0.3e-3 + 0.3e-3 + 0.1e-3),
        "layer/mlp": pytest.approx(0.4e-3),
    }
    assert list(got)[0] == "layer/attn"  # most time first
    # a partition of the device's busy time
    assert sum(got.values()) == pytest.approx(5.1e-3)


@pytest.mark.parametrize("name,stats,want", [
    ("%fusion.1 = f32[] fusion()", {"tf_op": "jit(f)/layer/mlp/dot"},
     "layer/mlp"),
    ("%x = f32[] add()", {"n": 3, "name": "jit(f)/while/body/embed/take"},
     "embed"),
    ('%y = f32[] dot(), metadata={op_name="jit(f)/lm_head/dot_general"}', {},
     "lm_head"),
    ("%z = f32[] fusion()", {"tf_op": "jit(f)/layer/moe_experts/ecd"},
     "layer/moe_experts"),
    # a function called sample_rate is not the scope "sample"
    ("%w = f32[] add()", {"tf_op": "jit(f)/sample_rate/add"},
     trace_gaps.UNSCOPED),
    ("%while.8 = () while()", {}, trace_gaps.UNSCOPED),
])
def test_scope_of_an_operation(name, stats, want):
    assert trace_gaps.scope_of((0.0, 1.0, name, stats)) == want


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message from (number, int | bytes | str) fields."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            raw = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(raw)) + raw
    return out


def test_name_stacks_come_from_the_event_metadata():
    """A TPU trace keeps an operation's name stack (``tf_op``) on the
    event METADATA; the reader walks the XSpace message for it and
    skips the lines. Built here by hand: XSpace.planes=1; XPlane
    name=2, lines=3, event_metadata=4, stat_metadata=5; XEventMetadata
    name=2, stats=5; XStat metadata_id=1, str_value=5, ref_value=7."""
    def stat_meta(key, name):
        return (5, _msg((1, key), (2, _msg((1, key), (2, name)))))

    def event_meta(key, name, *stats):
        return (4, _msg((1, key), (2, _msg(
            (1, key), (2, name), *((5, s) for s in stats)))))

    device = _msg(
        (2, "/device:TPU:0"),
        (3, _msg((2, "XLA Ops"), (4, _msg((1, 7), (3, 5000))))),  # skipped
        stat_meta(1, "hlo_category"), stat_meta(2, "tf_op"),
        stat_meta(3, "jit(scan_k)/while/body/lm_head/dot_general:"),
        event_meta(7, "%sort.16 = f32[4] sort(%l)",
                   _msg((1, 1), (5, "sort")),
                   _msg((1, 2), (5, "jit(scan_k)/while/body/sample/sort:"))),
        event_meta(8, "%fusion.9 = f32[4] fusion(%x)", _msg((1, 2), (7, 3))),
        event_meta(9, "%copy.1 = f32[4] copy(%x)", _msg((1, 1), (5, "copy"))),
    )
    host = _msg((2, "/host:CPU"), stat_meta(2, "tf_op"),
                event_meta(1, "engine/admit", _msg((1, 2), (5, "x/embed/y"))))
    space = _msg((1, device), (1, host), (4, "some-hostname"))
    assert trace_gaps.op_name_stacks(space) == {"/device:TPU:0": {
        "%sort.16 = f32[4] sort(%l)": "jit(scan_k)/while/body/sample/sort:",
        "%fusion.9 = f32[4] fusion(%x)":
            "jit(scan_k)/while/body/lm_head/dot_general:",
    }}
    for name, want in (("%sort.16 = f32[4] sort(%l)", "sample"),
                       ("%fusion.9 = f32[4] fusion(%x)", "lm_head")):
        stack = trace_gaps.op_name_stacks(space)["/device:TPU:0"][name]
        assert trace_gaps.scope_of((0, 1, name, {"tf_op": stack})) == want


def test_modules_and_dispatched_windows():
    rep = trace_gaps.report(planes())
    assert rep["devices"] == 1
    assert rep["modules"]["jit_scan_k"] == {
        "runs": 2, "seconds": pytest.approx(3e-3)}
    assert rep["modules"]["jit__prefill_suffix_step"]["runs"] == 1
    assert rep["dispatched"] == {"windows": 2, "steps": 10}
    assert rep["phase_ms"]["admit"] == pytest.approx(1.8)
    assert rep["request_marks"] == 1
    text = trace_gaps.render(rep)
    assert "engine/admit" in text and "layer/kv_gather" in text
    json.dumps(rep)  # the --json form


def test_a_trace_without_the_ledger_names_no_phase():
    bare = [p for p in planes() if p["name"].startswith("/device:")]
    gaps = trace_gaps.largest_gaps(bare)
    assert len(gaps) == 3
    assert all(g["host"] == [] and g["host_phase"] is None for g in gaps)
    assert trace_gaps.report(
        [{"name": "/host:CPU", "lines": []}])["gaps"] == []


def test_reads_a_recorded_tpu_trace():
    """The v5e trace the benchmark's own tests keep (twelve decode scans
    of ``tiny-random``, taken before the ledger existed): it loads, its
    gaps lie between scans, and no phase is named."""
    path = os.path.join(REPO, "tests", "cellbench", "data",
                        "tiny_v5e.xplane.pb")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_gaps.py"),
         path, "--json"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["devices"] == 1
    assert rep["modules"]["jit_scan_k"]["runs"] == 12
    assert len(rep["gaps"]) == 10
    assert all(g["before"] == g["after"] == "jit_scan_k"
               and g["host_phase"] is None for g in rep["gaps"])
    assert rep["dispatched"] == {"windows": 0, "steps": 0}
