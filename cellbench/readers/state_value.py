"""A /state value after the drain (the largest replica's), scaled;
nothing — instead of a ``KeyError`` — where the program serves no such
key (a commit from before it)."""


def read(ctx: dict, args: dict) -> float | None:
    values = [st[args["key"]] for st in ctx["snap2"]["states"]
              if args["key"] in st]
    return max(values) * args.get("scale", 1.0) if values else None
