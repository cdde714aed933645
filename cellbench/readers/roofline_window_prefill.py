"""The window-and-global family's prefill programs' share of their
roofline, in percent: the least time the captured chunk and tail calls
could take (``cellbench/roofline_window.py``: each matrix once a call at
the capture's mean PADDED tokens a call — the program runs the padding
— and, over the REAL queries, the keys they attended to: the global
layers' from the engine's ``prefill_keys_attended``, the window layers'
``sliding_window`` a query at the most; the larger of the FLOP and the
byte bound) over the prefill group's device time. Nothing without a
device plane, the counters, or a configuration with window layers."""

from cellbench import roofline, roofline_window

NEEDS = ("capture_prefill_calls", "capture_prefill_tokens_padded",
         "capture_prefill_tokens_real", "capture_prefill_keys_attended")


def read(ctx: dict, args: dict) -> float | None:
    if "hybrid_layer_pattern" not in ctx["config"]:
        return None
    values = []
    for trace, s0, s2 in zip(ctx["traces"], ctx["snap0"]["states"],
                             ctx["snap2"]["states"]):
        g = trace["groups"].get("prefill")
        if not trace["devices"] or not g or any(k not in s2 for k in NEEDS):
            continue
        calls, padded, real, attended = (
            s2[k] - s0.get(k, 0) for k in NEEDS)
        if calls <= 0 or real <= 0:
            continue
        least = roofline_window.prefill_seconds(
            ctx["config"], calls, padded, real, attended,
            roofline.peaks_for(ctx["device_kind"]))
        v = roofline.share_pct(least, g["seconds"])
        if v is not None:
            values.append(v)
    return sum(values) / len(values) if values else None
