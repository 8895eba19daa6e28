"""Mean time per window frame from the end of its dispatch's device work to
its image on the host (the copy out of device memory), in ms: the program's
own stamps ``device_done`` and ``fetched`` (``serving.fetch_s``,
``bench/served_records.py``). Layer: serving. Nothing is read where the
program keeps no such record."""
import statistics

from bench.served_records import last_requests


def read(ctx):
    fetches = last_requests("serving.fetch_s", ctx.frames)
    if fetches is None:
        return None
    return 1e3 * statistics.fmean(fetches)
