"""XLA compiles, or loads from the persistent compilation cache, while the
window's dispatches ran: the program's compile counter diffed across each
dispatch (``serving.dispatch_compiles``, ``bench/served_records.py``).
Layer: engine. Nothing is read where the program keeps no such record."""
from bench.served_records import split_dispatches


def read(ctx):
    split = split_dispatches("serving.dispatch_compiles", ctx.frames)
    if split is None:
        return None
    window, _setup = split
    return float(sum(window))
