"""The traced window's own records, as the program keeps them.

``RenderServer`` publishes into the process's metrics registry, in dispatch
order (``serving/stats.py``): per request ``serving.queue_wait_s`` and
``serving.fetch_s``; per dispatch ``serving.batch_size``,
``serving.dispatch_compiles`` and ``serving.dispatch_compile_s``. A run
opens one server, warms it, and then serves the window; so the window's
frames are the last ``frames`` requests the server completed, and the
window's dispatches the last ones that served them. The readers in
``bench/metrics/`` take them from there. A program that keeps no such
record, or a reservoir that has begun to sample, gives nothing.
"""
from __future__ import annotations

from typing import List, Optional, Tuple


def _series(name: str) -> Optional[List[float]]:
    from repro.obs import get_registry

    get = getattr(get_registry(), "get", None)
    hist = get(name) if get is not None else None
    if hist is None or hist.sampled:
        return None
    return hist.values()


def last_requests(name: str, frames: int) -> Optional[List[float]]:
    """The per-request values of ``name`` for the last ``frames``
    requests."""
    values = _series(name)
    if values is None or frames <= 0 or len(values) < frames:
        return None
    return values[-frames:]


def split_dispatches(name: str, frames: int
                     ) -> Optional[Tuple[List[float], List[float]]]:
    """The per-dispatch values of ``name``: those of the dispatches that
    served the last ``frames`` requests, and those of every dispatch
    before them."""
    sizes, values = _series("serving.batch_size"), _series(name)
    if sizes is None or values is None or len(sizes) != len(values):
        return None
    served = k = 0
    while served < frames and k < len(sizes):
        k += 1
        served += int(sizes[-k])
    if frames <= 0 or served != frames:
        return None
    return values[len(values) - k:], values[:len(values) - k]
