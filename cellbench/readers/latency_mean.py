"""The mean over the window's completed requests of what
``latency_percentile`` takes a percentile of, on the client's clock at
the gateway. ``what``: ``ttft`` (due -> first content token) or ``tpot``
(per request, (last token - first token) / (tokens - 1); a reply of one
token has no gap and is left out). A mean over REQUESTS, not over
tokens: every request weighs the same, as in the percentile. It stands
on every request of the window, where a p90 stands on the slowest
tenth."""

from cellbench import stats


def read(ctx: dict, args: dict) -> float | None:
    ok = [r for r in ctx["window"] if r.ok]
    if args["what"] == "ttft":
        values = [1e3 * (r.first - r.due) for r in ok]
    else:
        values = [v for v in (stats.tpot_ms(r.first, r.last, r.tokens)
                              for r in ok) if v is not None]
    return sum(values) / len(values) if values else None
