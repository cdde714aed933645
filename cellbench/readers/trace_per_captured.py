"""Device time inside one group of XLA modules per unit of work COUNTED
OVER THE CAPTURE: per replica, the group's device seconds in the trace
over what the engine's ``capture_<counter>`` key gained from the
window's start to after the drain (``snap0`` -> ``snap2``; one capture
a run, so the difference is that capture's count), times ``scale``.
The engine cuts the count at its first phase boundary after the trace
started and at its first after the flag went down before the trace
stopped, so every counted prefill call or decode window lies inside
the trace; what the trace holds beyond the count is the call in flight
at each edge. Averaged over replicas. Nothing without a device plane (a
CPU run), with a zero count, or where the program has no such key."""


def read(ctx: dict, args: dict) -> float | None:
    key = "capture_" + args["counter"]
    values = []
    for trace, s0, s2 in zip(ctx["traces"], ctx["snap0"]["states"],
                             ctx["snap2"]["states"]):
        g = trace["groups"].get(args["group"])
        if not trace["devices"] or not g or key not in s2:
            continue
        count = s2[key] - s0.get(key, 0)
        if count > 0:
            values.append(g["seconds"] / count * args.get("scale", 1.0))
    return sum(values) / len(values) if values else None
