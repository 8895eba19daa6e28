"""Device time per completed frame, in ms, of every operation that is
neither a sort nor a Pallas kernel: projection, identification, gathers,
packing, compaction and the rest of the XLA work. Layer: frontend and
backend XLA operations."""


def read(ctx):
    if ctx.frames <= 0:
        return None
    s = ctx.summary
    return 1e3 * s.class_s["other"] / s.devices / ctx.frames
