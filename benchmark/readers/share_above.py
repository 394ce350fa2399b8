"""The share (%) of a series that lies above a multiple of its own
median: with two modes, the share in the upper one.
params: series, times_median."""

import statistics


def read(ctx, series, times_median):
    values = ctx["obs"].get(series) or []
    if not values:
        return None
    cut = statistics.median(values) * times_median
    return 100.0 * sum(1 for v in values if v > cut) / len(values)
