"""``loop_counter_ratio`` of a counter that every layer adds to, a
layer: the ratio of counter deltas over the counters' window, over the
number of layers the CONFIGURATION has (``args["layers"]`` names its
key in the configuration file), times ``scale``. Nothing where the
program serves no such key or the file has no such count."""

from cellbench.readers import loop_counter_ratio


def read(ctx: dict, args: dict) -> float | None:
    layers = ctx["config"].get(args["layers"])
    ratio = loop_counter_ratio.read(ctx, args)
    if not layers or ratio is None:
        return None
    return ratio / layers
