"""The hybrid family's prefill programs' share of their roofline, in
percent: the least time the captured prefill calls could take — each
call the larger of its FLOP and its byte bound
(``cellbench/roofline_hybrid.py``) at the capture's mean PADDED tokens
a call (``capture_prefill_tokens_padded / capture_prefill_calls``: the
program runs the padding, and counting real tokens against a time that
includes it could read over 100) — over the prefill group's device time.
Nothing without a device plane or the counters."""

from cellbench import roofline, roofline_hybrid

NEEDS = ("capture_prefill_calls", "capture_prefill_tokens_padded")


def read(ctx: dict, args: dict) -> float | None:
    values = []
    for trace, s0, s2 in zip(ctx["traces"], ctx["snap0"]["states"],
                             ctx["snap2"]["states"]):
        g = trace["groups"].get("prefill")
        if not trace["devices"] or not g or any(k not in s2 for k in NEEDS):
            continue
        calls = s2["capture_prefill_calls"] - s0["capture_prefill_calls"]
        padded = (s2["capture_prefill_tokens_padded"]
                  - s0["capture_prefill_tokens_padded"])
        if calls <= 0 or padded <= 0:
            continue
        least = calls * roofline_hybrid.prefill_call_seconds(
            ctx["config"], round(padded / calls),
            roofline.peaks_for(ctx["device_kind"]))
        v = roofline.share_pct(least, g["seconds"])
        if v is not None:
            values.append(v)
    return sum(values) / len(values) if values else None
