"""Share of the traced window in which no operation ran on the device: 100
times 1 - (union of device-operation intervals / window), averaged over the
chips used. Layer: device."""


def read(ctx):
    s = ctx.summary
    if s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
