"""A boot observable of /state (the slowest replica's), scaled."""


def read(ctx: dict, args: dict) -> float | None:
    values = [st[args["key"]] for st in ctx["snap2"]["states"]]
    return max(values) * args.get("scale", 1.0) if values else None
