"""The point-in-polygon kernel's tile-skip reader on the CPU: it reads
skipped tiles over all tiles from the program counter, and nothing where
the program has no such counter."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells  # noqa: E402

METRIC = "pip_tile_skip_pct.zones"
KEY = "geomesa_pip_tiles_total"


def _ctx(before: dict, after: dict):
    class Ctx:
        rows = 1 << 25
        trace = None

    ctx = Ctx()
    ctx.prom_before, ctx.prom_after = before, after
    return ctx


def _tiles(walked: float, skipped: float) -> dict:
    return {KEY + '{outcome="walked"}': walked,
            KEY + '{outcome="skipped"}': skipped}


@pytest.mark.parametrize("before,after,want", [
    # 1,000 of 1,200 tiles skipped over the window
    (_tiles(10.0, 50.0), _tiles(210.0, 1050.0), 100 * 1000 / 1200),
    # every tile walked: a full-span window
    (_tiles(0.0, 0.0), _tiles(256.0, 0.0), 0.0),
    # every tile skipped
    (_tiles(5.0, 5.0), _tiles(5.0, 261.0), 100.0),
])
def test_tile_skip_reader_reads_its_fixture(before, after, want):
    read = cells.load_reader(METRIC)
    assert read(_ctx(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    ({}, {}),
    # the counter is there but no count ran in the window
    (_tiles(10.0, 50.0), _tiles(10.0, 50.0)),
])
def test_tile_skip_reader_reads_nothing_without_it(before, after):
    """A program without the counter, or a window with no polygon count,
    reads None (never 0)."""
    assert cells.load_reader(METRIC)(_ctx(before, after)) is None
