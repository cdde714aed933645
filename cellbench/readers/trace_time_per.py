"""Device time inside one group of XLA modules per unit of work, from
the profiler's trace: the group's share of the traced span over the
engine's counter rate WHILE it was traced (``rate``, per second), times
``scale``. Averaged over replicas. Only a trace with a device plane
counts: a CPU run has no device time to report."""


def shares(ctx: dict, group: str) -> list[tuple[float, dict]]:
    """(group's device seconds per traced second, traced rates) per
    replica that has both."""
    out = []
    for trace, rates in zip(ctx["traces"], ctx["rates"]):
        g = trace["groups"].get(group)
        if trace["devices"] and g and trace["window_s"] > 0:
            out.append((g["seconds"] / trace["window_s"], rates))
    return out


def read(ctx: dict, args: dict) -> float | None:
    values = [share / rates[args["rate"]] * args.get("scale", 1.0)
              for share, rates in shares(ctx, args["group"])
              if rates[args["rate"]] > 0]
    return sum(values) / len(values) if values else None
