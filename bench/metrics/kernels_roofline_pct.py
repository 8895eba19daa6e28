"""The kernels' share of their roofline, in %: the least time the chip could
take for the rasterization work a frame needs (``bench/work.py``: alpha and
blend operations against the compute peak, feature and image bytes against
the memory peak, whichever bound is larger) over the kernels' device time
per frame. The work is float32 vector arithmetic measured against the
bf16 matrix peak, the only published compute peak, and the memory bound is
the larger of the two for these frames, so it reads small. Layer: kernels.
The work is counted by the reference on the frames the run compared;
nothing is read where no kernel ran or no frame was compared."""

from bench.work import least_time


def read(ctx):
    k = ctx.summary.class_s["kernel"]
    if ctx.work is None or ctx.frames <= 0 or k <= 0:
        return None
    per_frame = k / ctx.summary.devices / ctx.frames
    t, _bound = least_time(ctx.work["kernel_ops"], ctx.work["kernel_bytes"],
                           ctx.peak)
    return 100.0 * t / per_frame
