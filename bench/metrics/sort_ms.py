"""Device time of XLA sort operations per completed frame, in ms. Layer:
frontend (the binning sorts of ``core/grouping.py``)."""


def read(ctx):
    if ctx.frames <= 0:
        return None
    s = ctx.summary
    return 1e3 * s.class_s["sort"] / s.devices / ctx.frames
