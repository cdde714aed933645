"""Output tokens that reached the client inside the window, over the
window: every token of every stream, whichever request it belongs to."""


def read(ctx: dict, args: dict) -> float | None:
    t0, t1 = ctx["t0"], ctx["t1"]
    tokens = sum(k for r in ctx["results"] for t, k in r.deltas
                 if t0 <= t < t1)
    return tokens / ctx["seconds"] if tokens else None
