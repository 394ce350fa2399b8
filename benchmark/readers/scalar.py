"""One observation as it is. params: key, scale (default 1)."""


def read(ctx, key, scale=1.0):
    value = ctx["obs"].get(key)
    return None if value is None else value * scale
