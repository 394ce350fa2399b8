"""The device's idle share (%) of the traced window: 1 - the union of
the device's operation intervals over the window, averaged over the
chips that ran anything. Only a traced run on the chip has it."""


def read(ctx):
    red = ctx.get("reduced")
    if not red or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
