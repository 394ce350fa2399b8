"""One observation over another: a rate, a time per step, a share.
params: num, den (observation names), scale (default 1)."""


def read(ctx, num, den, scale=1.0):
    obs = ctx["obs"]
    if num not in obs or not obs.get(den):
        return None
    return obs[num] / obs[den] * scale
