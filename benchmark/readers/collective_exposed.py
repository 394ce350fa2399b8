"""Share (%) of the traced window in which, on the lowest-numbered
device, a collective ran and no other operation did."""


def read(ctx):
    red = ctx.get("reduced")
    if not red or not red["window_s"] or not red["collective_s"]:
        return None
    return 100.0 * red["collective_exposed_s"] / red["window_s"]
