"""The window-and-global family's decode programs' share of their
roofline, in percent: the least time the captured decode steps could
take (``cellbench/roofline_window.py``: own matrices once a step, the
held experts hit, the live rows' global pages and their rings; the
larger of the byte and the FLOP bound) over the decode group's device
time. Everything is counted over the capture itself (the engine's
``capture_*`` keys): steps, live rows (``decode_state_rows_live``: the
rows whose rings a window layer's loop read, a layer), the held experts
hit (``moe_held_hits_decode``), the pages the live rows held
(``decode_kv_pages_live``; a page's tokens from the configuration's
``--page-size``) and the ring rows the window layers' softmaxes saw
(``swa_keys_attended``). Nothing where the program serves no such key (a
commit from before the counters, or another family's program) or the
trace has no device plane."""

from cellbench import roofline, roofline_window

NEEDS = ("capture_decode_steps", "capture_decode_state_rows_live",
         "capture_moe_held_hits_decode", "capture_decode_kv_pages_live",
         "capture_swa_keys_attended")


def read(ctx: dict, args: dict) -> float | None:
    flags = ctx["config"]["cellbench"]["serve_flags"]
    page = int(flags[flags.index("--page-size") + 1])
    values = []
    for trace, s0, s2 in zip(ctx["traces"], ctx["snap0"]["states"],
                             ctx["snap2"]["states"]):
        g = trace["groups"].get("decode")
        if not trace["devices"] or not g or any(k not in s2 for k in NEEDS):
            continue
        steps, rows, hits, pages, seen = (
            s2[k] - s0.get(k, 0) for k in NEEDS)
        if steps <= 0 or rows <= 0:
            continue
        least = roofline_window.decode_seconds(
            ctx["config"], steps, rows, pages, hits, seen, page,
            roofline.peaks_for(ctx["device_kind"]))
        v = roofline.share_pct(least, g["seconds"])
        if v is not None:
            values.append(v)
    return sum(values) / len(values) if values else None
