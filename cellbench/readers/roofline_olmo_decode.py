"""The Olmo-Hybrid family's decode programs' share of the HBM roofline,
in percent: the bytes a captured decode step had to move
(``cellbench/roofline_olmo_hybrid.py``: each layer's matrices once, the
live slots' DeltaNet state in and out at its LOGICAL size, the live
contexts' keys and values) over the published bandwidth, over the decode
group's device time a captured step. Everything is counted over the
capture itself (the engine's ``capture_*`` keys): steps and live rows a
step (``decode_state_rows_live / decode_steps``: the rows whose state
the step's loops updated, counted on the device). Nothing where the
program serves no such key or the trace has no device plane."""

from cellbench import roofline, roofline_olmo_hybrid

NEEDS = ("capture_decode_steps", "capture_decode_state_rows_live",
         "state_bytes_per_slot")


def read(ctx: dict, args: dict) -> float | None:
    doc = ctx["config"]
    values = []
    for trace, rates, s0, s2 in zip(
            ctx["traces"], ctx["rates"], ctx["snap0"]["states"],
            ctx["snap2"]["states"]):
        g = trace["groups"].get("decode")
        if not trace["devices"] or not g or any(k not in s2 for k in NEEDS):
            continue
        steps = s2["capture_decode_steps"] - s0["capture_decode_steps"]
        if steps <= 0:
            continue
        live = (s2["capture_decode_state_rows_live"]
                - s0["capture_decode_state_rows_live"]) / steps
        # /state's live bytes hold the live slots' state too: the pages
        kv_live = max(0.0, rates["kv_bytes_in_use"]
                      - live * s2["state_bytes_per_slot"])
        least = roofline_olmo_hybrid.decode_step_bytes(
            doc, live, kv_live) * steps / roofline.peaks_for(
            ctx["device_kind"])["hbm_bytes_per_s"]
        v = roofline.share_pct(least, g["seconds"])
        if v is not None:
            values.append(v)
    return sum(values) / len(values) if values else None
