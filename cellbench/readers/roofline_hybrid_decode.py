"""The hybrid family's decode programs' share of the HBM roofline, in
percent: the bytes a captured decode step had to move
(``cellbench/roofline_hybrid.py``: each layer's own matrices once, the
weights of the held experts its tokens were routed to, the live slots'
DeltaNet state in and out, the live contexts' keys and values) over the
published bandwidth, over the decode group's device time a captured
step. Everything is counted over the capture itself (the engine's
``capture_*`` keys): steps, live rows a step
(``tokens_generated / decode_steps``) and held experts hit a step and
layer (``moe_held_hits_decode``). Nothing where the program serves no
such key (a commit from before the counters) or the trace has no device
plane."""

from cellbench import roofline, roofline_hybrid

NEEDS = ("capture_decode_steps", "capture_tokens_generated",
         "capture_moe_held_hits_decode", "state_bytes_per_slot")


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
        live = (s2["capture_tokens_generated"]
                - s0["capture_tokens_generated"]) / steps
        hit = (s2["capture_moe_held_hits_decode"]
               - s0["capture_moe_held_hits_decode"]) / (
            steps * doc["num_hidden_layers"])
        # /state's live bytes hold the live slots' state too: the pages
        kv_live = max(0.0, rates["kv_bytes_in_use"]
                      - live * s2["state_bytes_per_slot"])
        least = roofline_hybrid.decode_step_bytes(
            doc, live, kv_live, hit) * steps / roofline.peaks_for(
            ctx["device_kind"])["hbm_bytes_per_s"]
        v = roofline.share_pct(least, g["seconds"])
        if v is not None:
            values.append(v)
    return sum(values) / len(values) if values else None
