"""How unevenly a group-split backend's bands share the work: per request,
the largest band's valid group-table entries over the mean band's, from
the program's own record (``serving.band_skew``,
``bench/served_records.py``); the mean over the window's requests. 1.0
is an even split; the band count is all work on one chip. Layer: backend
split. Nothing is read where the program keeps no such record, as on one
chip, where no band split runs."""
import statistics

from bench.served_records import last_requests


def read(ctx):
    skews = last_requests("serving.band_skew", ctx.frames)
    if skews is None:
        return None
    return statistics.fmean(skews)
