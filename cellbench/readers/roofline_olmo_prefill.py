"""The Olmo-Hybrid family's prefill programs' share of their roofline,
in percent: the least time the captured prefill calls could take — each
call the larger of its FLOP and its byte bound
(``cellbench/roofline_olmo_hybrid.py``) at the capture's mean PADDED
tokens a call (``capture_prefill_tokens_padded / capture_prefill_calls``:
the program runs the padding), plus the captured snapshot saves and
restores at their logical bytes over the published bandwidth (the two
copy programs are named as prefill programs are, so the group's time
holds them) — over the prefill group's device time. Nothing without a
device plane or the counters."""

from cellbench import roofline, roofline_olmo_hybrid

NEEDS = ("capture_prefill_calls", "capture_prefill_tokens_padded",
         "capture_state_snapshots_saved", "capture_state_snapshots_restored")


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
        copies = sum(s2[k] - s0[k] for k in NEEDS[2:])
        if calls <= 0 or padded <= 0:
            continue
        peaks = roofline.peaks_for(ctx["device_kind"])
        least = calls * roofline_olmo_hybrid.prefill_call_seconds(
            ctx["config"], round(padded / calls), peaks) \
            + roofline_olmo_hybrid.snapshot_copy_seconds(
                ctx["config"], copies, peaks)
        v = roofline.share_pct(least, g["seconds"])
        if v is not None:
            values.append(v)
    return sum(values) / len(values) if values else None
