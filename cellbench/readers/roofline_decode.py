"""The decode programs' share of the HBM roofline, in percent: the
bytes a step has to move (weights once + the live contexts' keys and
values as /state had them INSIDE the capture, ``cellbench/roofline.py``)
over the published bandwidth, over the device time a step took in the
trace. Decode at these batch sizes
is bandwidth-bound: the bound named here is bytes, not FLOPs."""

from cellbench import roofline
from cellbench.readers.trace_time_per import shares


def read(ctx: dict, args: dict) -> float | None:
    doc = ctx["config"]
    flags = doc["cellbench"]["serve_flags"]
    mode = flags[flags.index("--quantize") + 1] if "--quantize" in flags else ""
    got = shares(ctx, "decode")
    if not got:
        return None
    peak = roofline.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    values = []
    for share, rates in got:
        least = (roofline.decode_step_bytes(
            doc, mode, rates["kv_bytes_in_use"])
                 * rates["decode_steps_per_s"] / peak)
        v = roofline.share_pct(least, share)
        if v is not None:
            values.append(v)
    return sum(values) / len(values) if values else None
