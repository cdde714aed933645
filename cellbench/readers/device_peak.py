"""Peak bytes in use on the fullest device, in GB, as the backend
reports it (nothing on a CPU)."""


def read(ctx: dict, args: dict) -> float | None:
    peak = max((d.get("peak_bytes_in_use", 0)
                for st in ctx["snap2"]["states"] for d in st["devices"]),
               default=0)
    return peak / 1e9 if peak else None
