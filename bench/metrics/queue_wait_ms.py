"""Median wait of the window's requests in the server's queue, from
enqueue to the launch of their dispatch, in ms: the program's own stamps
(``serving.queue_wait_s``, ``bench/served_records.py``). Layer: serving.
Nothing is read where the program keeps no such record."""
import statistics

from bench.served_records import last_requests


def read(ctx):
    waits = last_requests("serving.queue_wait_s", ctx.frames)
    if waits is None:
        return None
    return 1e3 * statistics.median(waits)
