"""Device time of the Pallas kernels (``tpu_custom_call``: bitmask
generation and the fused rasterizer, read together) per completed frame,
in ms. Layer: kernels. Nothing is read where no kernel ran."""


def read(ctx):
    k = ctx.summary.class_s["kernel"]
    if ctx.frames <= 0 or k <= 0:
        return None
    return 1e3 * k / ctx.summary.devices / ctx.frames
