"""Device: the share of the traced window in which no operation ran on
the device (1 minus the union of operation intervals over the window),
averaged over the chips.
"""


def read(ctx):
    red = ctx.red
    if not red.devices or red.window_s <= 0:
        return None
    return 1.0 - red.busy_s() / red.window_s
