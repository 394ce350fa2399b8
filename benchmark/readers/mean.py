"""The mean of a series of observations. params: series."""


def read(ctx, series):
    values = ctx["obs"].get(series) or []
    return sum(values) / len(values) if values else None
