"""Reduction of one profiler trace (``.xplane.pb``) to what the
per-layer readers use. Runs as a child under ``JAX_PLATFORMS=cpu``: it
needs ``jax.profiler.ProfileData`` and the parent never imports jax.

    python cellbench/trace_reduce.py <trace.xplane.pb> <module_groups.json>

prints one JSON object:

``window_s``     the traced span: first to last event on the device planes
                 (the host's python tracer also covers the seconds
                 ``start_trace`` and ``stop_trace`` themselves take, in
                 which nothing is captured; idle time before the first
                 and after the last device event of a capture is not
                 seen)
``busy_s``       seconds in which an operation ran on the device: the
                 union of the device-op intervals, averaged over devices
``groups``       per group of the module map: device ``seconds`` inside
                 its XLA modules and how many module ``runs``
``device_ops``   the ten operations with most device time
``devices``      device planes found (0: a CPU trace; every number is 0)
``idle_gaps``    the ten largest idle gaps, summed by the modules on
                 either side. The host's side of a gap is NOT in this
                 trace: the program writes no span on the profiler's
                 clock (left to the ``tracing`` issue), so a gap is
                 named by what the device ran before and after it.

A device plane is one named ``/device:...`` (a TPU's); its line
``XLA Modules`` carries one event per program run, ``XLA Ops`` one per
operation. A CPU trace has no device plane and reduces to nothing: the
harness then prints no device time and no trace metric.
"""

from __future__ import annotations

import json
import sys

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals (ns -> s)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def merged(intervals: list[tuple[float, float, str]]
           ) -> list[tuple[float, float, str, str]]:
    """Overlapping (start, end, name) intervals merged into busy spans
    (start, end, first name, last name)."""
    out: list[list] = []
    for s, e, name in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1], out[-1][3] = e, name
        else:
            out.append([s, e, name, name])
    return [tuple(x) for x in out]


def group_of(module: str, groups: dict) -> str:
    for prefix, group in groups["prefixes"].items():
        if module.startswith(prefix):
            return group
    return groups["default"]


def op_name(event_name: str) -> str:
    """A TPU trace names an operation by its whole HLO line
    (``%while.8 = (s32[], bf16[28,2,...``): keep the name before the
    ``=`` and the opcode behind the result type, not the kilobytes of
    shapes."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name[:120]
    depth, i = 0, 0
    for i, ch in enumerate(rest):  # skip the (possibly tuple) result type
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            break
    opcode = rest[i + 1:].split("(", 1)[0]
    return f"{head} {opcode}"[:120]


def module_name(event_name: str) -> str:
    """``jit_scan_k(1234567)`` -> ``jit_scan_k``."""
    return event_name.split("(", 1)[0]


def _device_views(planes: list) -> list[dict]:
    """One view per device plane: its module events and its op events,
    each (start_ns, end_ns, name)."""
    views = []
    for plane in planes:
        if not plane["name"].startswith("/device:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if OPS_LINE not in lines and MODULE_LINE not in lines:
            continue
        mods = [(s, e, module_name(n)) for s, e, n in
                lines.get(MODULE_LINE, [])]
        ops = [(s, e, op_name(n)) for s, e, n in lines.get(OPS_LINE, [])]
        views.append({"modules": mods, "ops": ops or mods})
    return views


def reduce_planes(planes: list, groups: dict) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(start_ns,
    end_ns, name)]}]}] — the trace as plain data, so that the
    arithmetic is testable without a trace file."""
    views = _device_views(planes)
    if not views:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": 0, "groups": {},
                "device_ops": [], "idle_gaps": []}
    out_groups: dict[str, dict] = {}
    op_time: dict[str, float] = {}
    gap_time: dict[str, float] = {}
    busy = 0.0
    for view in views:
        busy += union_seconds([(s, e) for s, e, _ in view["ops"]])
        for s, e, mod in view["modules"]:
            g = out_groups.setdefault(
                group_of(mod, groups), {"seconds": 0.0, "runs": 0})
            g["seconds"] += (e - s) / 1e9
            g["runs"] += 1
        for s, e, n in view["ops"]:
            op_time[n] = op_time.get(n, 0.0) + (e - s) / 1e9
        spans = merged(view["modules"] or view["ops"])
        for a, b in zip(spans, spans[1:]):
            name = f"host not traced; device idle between {a[3]} and {b[2]}"
            gap_time[name] = gap_time.get(name, 0.0) + (b[0] - a[1]) / 1e9
    n = len(views)
    for g in out_groups.values():
        g["seconds"] /= n

    def top(d: dict) -> list:
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    spans = [(s, e) for v in views for s, e, _ in v["ops"] + v["modules"]]
    return {
        "window_s": (max(e for _, e in spans)
                     - min(s for s, _ in spans)) / 1e9,
        "busy_s": busy / n,
        "devices": n,
        "groups": out_groups,
        "device_ops": top(op_time),
        "idle_gaps": top(gap_time),
    }


def load_planes(path: str) -> list:
    """The trace's device planes as plain data (the host planes say
    nothing the reduction reads)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        planes.append({"name": plane.name, "lines": [
            {"name": line.name, "events": [
                (float(ev.start_ns),
                 float(ev.start_ns) + float(ev.duration_ns), ev.name)
                for ev in line.events]}
            for line in plane.lines]})
    return planes


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        groups = json.load(f)
    print(json.dumps(reduce_planes(load_planes(argv[0]), groups)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
