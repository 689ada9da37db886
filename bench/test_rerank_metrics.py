"""Tests of the rerank readers (``rerank_device_ms``, ``sinkhorn_roofline``,
``sinkhorn_live_share``) and of ``bench/rerank_work.py`` on hand-set
counter deltas and a hand-set trace summary.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_rerank_metrics.py
"""

from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness, rerank_work  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

CELL = "set2_wmd.poisson80"
PEAKS = {"flops_per_s": 200e12, "bytes_per_s": 800e9}
#: One batch of 4 pairs: 300 real cells, 10 sweeps each, 60 real words;
#: the loop swept 3 levels × 10 × 4 pairs × 48².
BATCH = {"serving_batch_size": (4.0, 1),
         rerank_work.PAIRS: (4.0, 0), rerank_work.CELLS: (300.0, 0),
         rerank_work.WORDS: (60.0, 0), rerank_work.CELL_ITERS: (3000.0, 0),
         rerank_work.SWEPT: (276480.0, 0)}


def _trace(modules):
    return trace_lib.TraceSummary(window_s=10.0, busy_s=9.0, modules=modules,
                                  device_ops=[], idle_gaps=[])


def _view(counters, modules=None, peaks=PEAKS):
    trace = None if modules is None else _trace(modules)
    return harness.RunView(harness.load_cell(CELL), counters, trace, peaks,
                           {"m": 300})


def test_cell_reports_the_rerank_metrics():
    names = {m["name"] for m in harness.load_cell(CELL).per_layer}
    assert {"rerank_device_ms", "sinkhorn_roofline",
            "sinkhorn_live_share"} <= names


def test_work_counts_cost_cells_sweeps_and_embeddings():
    flops, nbytes = rerank_work.rerank_work(m=300, cells=300.0,
                                            cell_iters=3000.0, words=60.0)
    assert flops == 2 * 300 * 300 + 4 * 3000
    assert nbytes == 4 * 300 * 60


@pytest.mark.parametrize("modules,want", [
    ({"jit__segmented_rerank": (4, 0.1)}, 25.0),
    ({"jit__segmented_rerank": (1, 0.0275), "jit_step": (1, 0.058)}, 27.5),
])
def test_rerank_device_ms_reads_the_program(modules, want):
    read = harness.load_reader("rerank_device_ms")
    assert read(_view({}, modules)) == pytest.approx(want)


def test_roofline_is_least_time_over_device_time():
    # 192,000 operations at 200 TFLOP/s take 0.96 ns; 72,000 bytes at
    # 800 GB/s take 90 ns, which bounds it; the program took 90 µs.
    read = harness.load_reader("sinkhorn_roofline")
    got = read(_view(BATCH, {"jit__segmented_rerank": (2, 180e-6)}))
    assert got == pytest.approx(100.0 * 90e-9 / 90e-6)


def test_roofline_takes_the_work_per_batch():
    two = {k: (2 * s, 2 * n) for k, (s, n) in BATCH.items()}
    mods = {"jit__segmented_rerank": (2, 180e-6)}
    read = harness.load_reader("sinkhorn_roofline")
    assert read(_view(two, mods)) == pytest.approx(read(_view(BATCH, mods)))


def test_live_share_is_cell_iters_over_swept():
    read = harness.load_reader("sinkhorn_live_share")
    assert read(_view(BATCH)) == pytest.approx(100.0 * 3000 / 276480)


@pytest.mark.parametrize("metric", ["rerank_device_ms", "sinkhorn_roofline",
                                    "sinkhorn_live_share"])
@pytest.mark.parametrize("counters,modules,peaks", [
    ({}, None, PEAKS),                                   # untraced, no counter
    ({"serving_batch_size": (64.0, 2)}, {"jit_step": (2, 0.1)}, PEAKS),
    (BATCH, {"jit_step": (2, 0.1)}, {}),                 # no rerank program
])
def test_none_where_there_is_nothing_to_read(metric, counters, modules,
                                             peaks):
    """A program without the rerank's counters (the parent's), an
    untraced run, or a trace without the rerank program reads nothing."""
    read = harness.load_reader(metric)
    got = read(_view(counters, modules, peaks))
    if metric == "sinkhorn_live_share" and counters is BATCH:
        assert got is not None
    else:
        assert got is None
