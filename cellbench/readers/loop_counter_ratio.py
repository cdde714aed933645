"""``counter_ratio`` over keys of the engine's loop ledger (/state
``loop_*``, ``capture_*``): the same ratio of counter deltas over the
counters' window, and nothing — instead of a ``KeyError`` — where the
program serves no such key (a commit from before the ledger)."""

from cellbench.readers import counter_ratio


def read(ctx: dict, args: dict) -> float | None:
    state = ctx["snap0"]["state"]
    if any(k not in state for k in (*args["num"], *args["den"])):
        return None
    return counter_ratio.read(ctx, args)
