"""Tests of the ``phase1_col_fill`` reader on hand-set counter deltas.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_phase1_col_fill.py
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

COMPUTED = "serving_phase1_computed_cols_total"
WORDS = "serving_phase1_word_cols_total"


def _view(counters):
    return harness.RunView(harness.load_cell("set2_knn.saturate"), counters,
                           None, {}, {})


@pytest.mark.parametrize("counters,want", [
    ({COMPUTED: (2136.0, 0), WORDS: (1751.52, 0)}, 82.0),
    ({COMPUTED: (3072.0, 0), WORDS: (3072.0, 0)}, 100.0),
])
def test_reads_words_over_computed(counters, want):
    read = harness.load_reader("phase1_col_fill")
    assert read(_view(counters)) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {},
    {"serving_phase2_gathered_slots_total": (800.0, 0)},
    {COMPUTED: (0.0, 0), WORDS: (0.0, 0)},
])
def test_none_without_the_counters(counters):
    assert harness.load_reader("phase1_col_fill")(_view(counters)) is None
