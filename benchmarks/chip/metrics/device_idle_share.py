"""Share of the traced windows in which no operation ran on the device:
1 - (union of the device's operation intervals / traced window), averaged
over the chips the cell uses."""


def read(ctx):
    t = ctx.trace
    if not t.busy or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
