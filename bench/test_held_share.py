"""Tests of the ``held_share`` reader on hand-set counter deltas.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_held_share.py
"""

from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

BATCHES = "serving_batches_total"
HELD = "serving_batches_held_total"


def _view(counters):
    return harness.RunView(harness.load_cell("set2_knn.poisson80"), counters,
                           None, {}, {})


@pytest.mark.parametrize("counters,want", [
    ({BATCHES: (1033.0, 0), HELD: (930.0, 0)}, 90.03),
    ({BATCHES: (615.0, 0), HELD: (615.0, 0)}, 100.0),
    ({BATCHES: (880.0, 0), HELD: (0.0, 0)}, 0.0),
])
def test_reads_held_over_batches(counters, want):
    read = harness.load_reader("held_share")
    assert read(_view(counters)) == pytest.approx(want, abs=0.01)


@pytest.mark.parametrize("counters", [
    {},
    {BATCHES: (1033.0, 0)},
    {BATCHES: (0.0, 0), HELD: (0.0, 0)},
])
def test_none_without_batches_or_the_counter(counters):
    assert harness.load_reader("held_share")(_view(counters)) is None
