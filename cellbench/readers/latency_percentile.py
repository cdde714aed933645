"""A percentile over the window's completed requests, on the client's
clock at the gateway. ``what``: ``ttft`` (due -> first content token,
so queueing and a late generator both count) or ``tpot`` (per request,
(last token - first token) / (tokens - 1))."""

from cellbench import stats


def read(ctx: dict, args: dict) -> float | None:
    ok = [r for r in ctx["window"] if r.ok]
    if args["what"] == "ttft":
        values = [1e3 * (r.first - r.due) for r in ok]
    else:
        values = [v for v in (stats.tpot_ms(r.first, r.last, r.tokens)
                              for r in ok) if v is not None]
    return stats.percentile(values, args["q"]) if values else None
