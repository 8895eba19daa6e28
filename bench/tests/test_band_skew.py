"""The ``band_skew`` reader: the mean over the window's requests of the
program's ``serving.band_skew``, and nothing where the program keeps no
such record (one chip, or a program without a band split)."""
import types

import pytest

from bench import run
from bench.spec import load_cell


@pytest.fixture
def fresh_registry():
    from repro.obs import get_registry

    get_registry().reset()
    yield get_registry()
    get_registry().reset()


def _read(cell, frames):
    return cell.reader("band_skew").read(types.SimpleNamespace(frames=frames))


def test_band_skew_reads_the_window_requests(tiny_bench, fresh_registry):
    cell = load_cell("tiny.backlog", tiny_bench)
    fresh_registry.histogram("serving.band_skew").observe_many(
        [3.0, 1.25, 1.75])
    assert _read(cell, 2) == pytest.approx(1.5)
    assert _read(cell, 4) is None


def test_band_skew_gives_nothing_on_one_chip(tiny_bench, fresh_registry):
    cell = load_cell("tiny.backlog", tiny_bench)
    served = run.serve(cell, 2**31 + 78, 1.0)
    assert len(served.done) >= 1
    assert _read(cell, len(served.done)) is None
