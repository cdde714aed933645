#!/usr/bin/env python3
"""trace_gaps — what the engine loop was doing while the device idled.

Reads one ``/debug/profile`` capture of a ``tpuserve`` replica (the
directory its reply names, or the ``.xplane.pb`` inside it) and prints

- the ten largest idle gaps of each device: the XLA modules on either
  side and the ``engine/<phase>`` spans of the loop ledger
  (``aigw_tpu/obs/flight.py``) that overlap the gap, longest overlap
  first, with the facts each span carries (window size ``k``, live
  ``slots``, prefill ``bucket``/``tokens``, ``pages`` of a row update)
  and the ``request/*`` marks that fall inside it;
- device seconds per named scope of the programs (``embed``,
  ``layer/attn``, ``layer/kv_walk``, ``layer/kv_gather``, ``layer/mlp``,
  ``layer/moe_route``, ``layer/moe_experts``, ``lm_head``, ``sample``),
  as SELF time: an operation that contains others (the decode scan's
  ``while``) is charged only what its children leave;
- runs and device seconds per XLA module, and the decode windows the
  loop dispatched inside the capture (count and the sum of ``k``).

    python tools/trace_gaps.py <profile_dir | trace.xplane.pb> [--json]

The phases are on the profiler's clock only while a capture runs, so a
trace from a build before the ledger names no phase (``host: []``).
Needs ``jax`` to parse the file (``JAX_PLATFORMS=cpu`` is enough);
everything after the load is plain arithmetic on plain data, which is
what ``tests/test_trace_gaps.py`` feeds it.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cellbench.trace_reduce import (  # noqa: E402
    MODULE_LINE,
    OPS_LINE,
    merged,
    module_name,
    op_name,
)

PHASE_PREFIX = "engine/"
MARK_PREFIX = "request/"
#: a scope is one component (or two, under ``layer/``) of an
#: operation's name stack
SCOPE = re.compile(
    r"(?:^|[/\"=])(embed|layer/[a-z_]+|lm_head|sample)(?=/)")
UNSCOPED = "(no scope)"


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            else:
                size = {1: 8, 5: 4}[wire]
            value = buf[i:i + size]
            i += size
        yield tag >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def op_name_stacks(data: bytes) -> dict[str, dict[str, str]]:
    """Device plane name -> {event name -> its ``tf_op`` stat}: the
    name stack XLA gives an operation ("jit(scan_k)/while/body/
    layer/attn/dot_general:"), which is where a ``named_scope`` shows.
    A TPU trace keeps it on the event METADATA, which
    ``jax.profiler.ProfileData`` does not hand out, so this walks the
    ``XSpace`` message itself — planes, their ``stat_metadata`` and
    ``event_metadata`` maps — and skips the lines."""
    out: dict[str, dict[str, str]] = {}
    for field, plane in _fields(memoryview(data)):
        if field != 1:  # XSpace.planes
            continue
        name, stat_names, metas = "", {}, []
        for f, value in _fields(plane):
            if f == 2:
                name = _text(value)
            elif f == 5:  # stat_metadata: map<int64, XStatMetadata>
                entry = dict(_fields(value))
                stat_names[entry.get(1, 0)] = _text(
                    dict(_fields(entry.get(2, b""))).get(2, b""))
            elif f == 4:  # event_metadata: map<int64, XEventMetadata>
                metas.append(dict(_fields(value)).get(2, b""))
        if not name.startswith("/device:"):
            continue
        stacks = out.setdefault(name, {})
        for meta in metas:
            event_name, tf_op = "", ""
            for f, value in _fields(meta):
                if f == 2:
                    event_name = _text(value)
                elif f == 5:  # XStat: metadata_id, str_value | ref_value
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        tf_op = (_text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if tf_op:
                stacks[event_name] = tf_op
    return out


def load_planes(path: str) -> list:
    """Every plane of the trace as plain data: ``{"name", "lines":
    [{"name", "events": [(start_ns, end_ns, name, {stat: value})]}]}``;
    a device operation's stats also hold its name stack (``tf_op``).
    """
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = [os.path.join(d, f) for d, _, fs in os.walk(path)
                 for f in fs if f.endswith(".xplane.pb")]
        if not found:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = sorted(found)[0]
    with open(path, "rb") as f:
        stacks = op_name_stacks(f.read())
    planes = []
    for plane in ProfileData.from_file(path).planes:
        named = stacks.get(plane.name, {})
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                stats = dict(ev.stats)
                if ev.name in named:
                    stats["tf_op"] = named[ev.name]
                events.append((float(ev.start_ns),
                               float(ev.start_ns) + float(ev.duration_ns),
                               ev.name, stats))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def host_spans(planes: list) -> tuple[list, list]:
    """(``engine/<phase>`` spans as (start, end, phase, facts), sorted;
    ``request/*`` marks as (t, name, rid)) from the host planes."""
    spans, marks = [], []
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                s, e, name = ev[0], ev[1], ev[2]
                stats = ev[3] if len(ev) > 3 else {}
                if name.startswith(PHASE_PREFIX):
                    spans.append((s, e, name[len(PHASE_PREFIX):], stats))
                elif name.startswith(MARK_PREFIX):
                    marks.append((s, name[len(MARK_PREFIX):],
                                  str(stats.get("rid", ""))))
    return sorted(spans, key=lambda x: x[0]), sorted(marks)


def device_lines(planes: list) -> list[dict]:
    """Per device plane its module and op events (3-tuples for the
    reduction's ``merged``; the ops keep their stats)."""
    out = []
    for plane in planes:
        if not plane["name"].startswith("/device:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if MODULE_LINE not in lines and OPS_LINE not in lines:
            continue
        out.append({
            "name": plane["name"],
            "modules": [(ev[0], ev[1], module_name(ev[2]))
                        for ev in lines.get(MODULE_LINE, [])],
            "ops": list(lines.get(OPS_LINE, [])),
        })
    return out


def largest_gaps(planes: list, top: int = 10) -> list[dict]:
    """The ``top`` largest idle gaps over the device planes, each with
    the host phases that overlap it."""
    spans, marks = host_spans(planes)
    gaps = []
    for dev in device_lines(planes):
        busy = merged(dev["modules"]
                      or [(ev[0], ev[1], op_name(ev[2]))
                          for ev in dev["ops"]])
        for a, b in zip(busy, busy[1:]):
            gaps.append((b[0] - a[1], a[1], b[0], dev["name"], a[3], b[2]))
    out = []
    for length, g0, g1, dev, before, after in sorted(
            gaps, key=lambda g: -g[0])[:top]:
        over: dict[str, dict] = {}
        for s, e, phase, facts in spans:
            if s >= g1:
                break
            ov = min(e, g1) - max(s, g0)
            if ov <= 0:
                continue
            o = over.setdefault(phase, {"phase": phase, "overlap_ms": 0.0,
                                        "facts": []})
            o["overlap_ms"] += ov / 1e6
            if facts:
                o["facts"].append(facts)
        host = sorted(over.values(), key=lambda o: -o["overlap_ms"])
        out.append({
            "device": dev, "ms": length / 1e6,
            "start_ms": g0 / 1e6, "before": before, "after": after,
            "host": host,
            "host_phase": host[0]["phase"] if host else None,
            "requests": [{"mark": n, "rid": rid}
                         for t, n, rid in marks if g0 <= t < g1],
        })
    return out


def scope_of(event: tuple) -> str:
    """The named scope an op event belongs to: the first scope found in
    its name or in any of its string stats (``load_planes`` puts the
    operation's name stack there as ``tf_op``), else ``UNSCOPED``."""
    stats = event[3] if len(event) > 3 else {}
    for text in (event[2], *(v for v in stats.values()
                             if isinstance(v, str))):
        found = SCOPE.search(text)
        if found:
            return found.group(1)
    return UNSCOPED


def scope_seconds(planes: list) -> dict[str, float]:
    """Device SELF seconds per named scope, averaged over devices: the
    ops line nests (a ``while`` holds its body's operations), so each
    event is charged its own span less its children's."""
    total: dict[str, float] = {}
    devs = device_lines(planes)
    for dev in devs:
        stack: list[list] = []  # [end, scope, self_ns]

        def close(upto: float) -> None:
            while stack and stack[-1][0] <= upto:
                _end, scope, self_ns = stack.pop()
                total[scope] = total.get(scope, 0.0) + self_ns / 1e9

        for ev in sorted(dev["ops"], key=lambda x: (x[0], -x[1])):
            close(ev[0])
            if stack:
                stack[-1][2] -= min(ev[1], stack[-1][0]) - ev[0]
            stack.append([ev[1], scope_of(ev), ev[1] - ev[0]])
        close(float("inf"))
    n = max(1, len(devs))
    return {k: v / n for k, v in sorted(total.items(), key=lambda kv: -kv[1])}


def module_runs(planes: list) -> dict[str, dict]:
    out: dict[str, dict] = {}
    for dev in device_lines(planes):
        for s, e, name in dev["modules"]:
            m = out.setdefault(name, {"runs": 0, "seconds": 0.0})
            m["runs"] += 1
            m["seconds"] += (e - s) / 1e9
    return out


def dispatched_windows(planes: list) -> dict[str, int]:
    """Decode windows the loop dispatched inside the capture: the
    ``engine/decode_dispatch`` spans that carry a ``k``."""
    ks = [int(facts["k"]) for _s, _e, phase, facts in host_spans(planes)[0]
          if phase == "decode_dispatch" and "k" in facts]
    return {"windows": len(ks), "steps": sum(ks)}


def report(planes: list) -> dict:
    spans, marks = host_spans(planes)
    phase_ms: dict[str, float] = {}
    for s, e, phase, _facts in spans:
        phase_ms[phase] = phase_ms.get(phase, 0.0) + (e - s) / 1e6
    return {
        "devices": len(device_lines(planes)),
        "gaps": largest_gaps(planes),
        "scope_seconds": scope_seconds(planes),
        "modules": module_runs(planes),
        "dispatched": dispatched_windows(planes),
        "phase_ms": phase_ms,
        "request_marks": len(marks),
    }


def render(rep: dict) -> str:
    lines = [f"{rep['devices']} device plane(s); "
             f"{rep['dispatched']['windows']} decode windows dispatched in "
             f"the capture ({rep['dispatched']['steps']} steps); "
             f"{rep['request_marks']} request marks"]
    lines.append("\nlargest device idle gaps:")
    for g in rep["gaps"]:
        lines.append(f"  {g['ms']:8.3f} ms at {g['start_ms']:.1f}  "
                     f"{g['before']} -> {g['after']}")
        for h in g["host"][:4]:
            facts = "; ".join(
                ",".join(f"{k}={v}" for k, v in f.items())
                for f in h["facts"][:3])
            lines.append(f"      {h['overlap_ms']:8.3f} ms  "
                         f"engine/{h['phase']}  {facts}")
        if not g["host"]:
            lines.append("      (no engine/<phase> span overlaps it)")
        for r in g["requests"][:4]:
            lines.append(f"      mark request/{r['mark']} rid={r['rid']}")
    lines.append("\ndevice self seconds per named scope:")
    busy = sum(rep["scope_seconds"].values()) or 1.0
    for scope, sec in rep["scope_seconds"].items():
        lines.append(f"  {sec:9.4f} s  {100 * sec / busy:5.1f} %  {scope}")
    lines.append("\nXLA modules:")
    for name, m in sorted(rep["modules"].items(),
                          key=lambda kv: -kv[1]["seconds"])[:12]:
        lines.append(f"  {m['seconds']:9.4f} s  {m['runs']:6d} runs  {name}")
    lines.append("\nhost ms per engine phase inside the capture:")
    for phase, ms in sorted(rep["phase_ms"].items(), key=lambda kv: -kv[1]):
        lines.append(f"  {ms:10.2f} ms  {phase}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("profile", help="profile directory or .xplane.pb")
    ap.add_argument("--json", action="store_true",
                    help="print the report as one JSON object")
    args = ap.parse_args(argv)
    rep = report(load_planes(args.profile))
    print(json.dumps(rep) if args.json else render(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
