"""A percentile of a series of observations, by nearest rank (the
smallest sample with at least q% of the samples at or under it): a value
that was measured, never one interpolated between two modes.
params: series, q."""

import math


def read(ctx, series, q):
    values = sorted(ctx["obs"].get(series) or [])
    if not values:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(values)))
    return values[rank - 1]
