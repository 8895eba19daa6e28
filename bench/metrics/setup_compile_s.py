"""Seconds the set-up's dispatches spent compiling the served program or
loading it from the persistent compilation cache, out of ``setup_s``: the
program's compile seconds diffed across each dispatch before the window
(``serving.dispatch_compile_s``, ``bench/served_records.py``). Layer:
engine. Nothing is read where the program keeps no such record."""
from bench.served_records import split_dispatches


def read(ctx):
    split = split_dispatches("serving.dispatch_compile_s", ctx.frames)
    if split is None or not split[1]:
        return None
    _window, setup = split
    return float(sum(setup))
