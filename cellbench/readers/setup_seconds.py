"""Process start -> lead-in done (the window opens): boot, weights,
warm-up, compilation in a fresh checkout, the shape tour and the
lead-in traffic."""


def read(ctx: dict, args: dict) -> float | None:
    return ctx["setup_s"]
