"""Tests of the ``phase2_slot_fill`` reader on hand-set counter deltas.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_phase2_slot_fill.py
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

GATHERED = "serving_phase2_gathered_slots_total"
FILLED = "serving_phase2_filled_slots_total"


def _view(counters):
    return harness.RunView(harness.load_cell("set2_knn.saturate"), counters,
                           None, {}, {})


@pytest.mark.parametrize("counters,want", [
    ({GATHERED: (800.0, 0), FILLED: (704.0, 0)}, 88.0),
    ({GATHERED: (1000.0, 0), FILLED: (1000.0, 0)}, 100.0),
])
def test_reads_filled_over_gathered(counters, want):
    read = harness.load_reader("phase2_slot_fill")
    assert read(_view(counters)) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {},
    {"serving_batch_size": (128.0, 2)},
    {GATHERED: (0.0, 0), FILLED: (0.0, 0)},
])
def test_none_without_the_counters(counters):
    assert harness.load_reader("phase2_slot_fill")(_view(counters)) is None
