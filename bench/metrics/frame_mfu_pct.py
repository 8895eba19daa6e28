"""The whole frame's share of the chip's compute peak, in %: the operations a
frame needs (``bench/work.py``: projection, alpha and blend arithmetic)
times the traced window's frames per second, over the chips' peak. Layer:
whole frame. The work is counted by the reference on the frames the run
compared; nothing is read where none was."""


def read(ctx):
    if ctx.work is None or ctx.frames_per_s <= 0:
        return None
    peak = float(ctx.peak["ops_per_s"]) * ctx.chips
    return 100.0 * ctx.work["frame_ops"] * ctx.frames_per_s / peak
