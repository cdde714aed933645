"""Ratio of /state counter deltas over the window, summed over
replicas: sum(delta of ``num``) / sum(delta of ``den``), or with
``complement`` one minus that; times ``scale``. Nothing where the
scrape that closes the window came too late to say when it ended."""


def read(ctx: dict, args: dict) -> float | None:
    if ctx["snap1"] is None:
        return None
    s0, s1 = ctx["snap0"]["state"], ctx["snap1"]["state"]
    num = sum(s1[k] - s0[k] for k in args["num"])
    den = sum(s1[k] - s0[k] for k in args["den"])
    if den <= 0:
        return None
    ratio = num / den
    if args.get("complement"):
        ratio = 1.0 - ratio
    return args.get("scale", 1.0) * ratio
