"""The work of the WMD rerank, counted from the program's rerank counters.

The rerank's program returns, with each batch's answers, a few sums that
the serving core adds to its registry (``serving_rerank_*_total``,
``serving_sinkhorn_*_total``).  The work below is that of the solve itself,
the same whatever implements it:

* operations: 2·m per real cost cell (its word distance over m
  dimensions) and 4 per real cell and Sinkhorn iteration (the two
  log-sum-exp updates, each a subtract and an exponential-sum per cell);
* bytes: the word embeddings of both sides of every solved pair, read
  once, 4·m per real word.

Every operation counts against the chip's one FLOP/s peak in
``bench/peaks.json``; the exponentials run on the EUP, whose rate has no
peak there, so the share reads far below what the units allow.
"""

from __future__ import annotations

from bench import work

#: The rerank's compiled program, as the device trace names it.
MODULES = ("jit__segmented_rerank",)
PAIRS = "serving_rerank_pairs_total"
CELLS = "serving_rerank_cells_total"
WORDS = "serving_rerank_words_total"
CELL_ITERS = "serving_sinkhorn_cell_iters_total"
SWEPT = "serving_sinkhorn_swept_cells_total"


def rerank_work(*, m: int, cells: float, cell_iters: float,
                words: float) -> tuple[float, float]:
    """(operations, bytes) of solving pairs with ``cells`` real cost cells,
    ``cell_iters`` Σ iterations × real cells and ``words`` real words."""
    return 2.0 * m * cells + 4.0 * cell_iters, 4.0 * m * words


def device_ms(run) -> float | None:
    """Device ms of one execution of the rerank program in the traced
    window; None where it did not run."""
    n, t = run.module(*MODULES)
    return 1e3 * t / n if n else None


def roofline(run) -> float | None:
    """The rerank's least time per batch at the chip's peaks over its
    device time per execution (%).  The counters cover the whole window,
    so the work per batch is their change over the batches dispatched."""
    ms = device_ms(run)
    _q, batches = run.counter("serving_batch_size")
    cells, _ = run.counter(CELLS)
    if not ms or not batches or not cells or not run.peaks:
        return None
    flops, nbytes = rerank_work(
        m=run.work["m"], cells=cells / batches,
        cell_iters=run.counter(CELL_ITERS)[0] / batches,
        words=run.counter(WORDS)[0] / batches)
    least, _bound = work.roofline_s(flops, nbytes, run.peaks)
    return 100.0 * least / (ms / 1e3)


def live_share(run) -> float | None:
    """Share of the batched Sinkhorn loop's swept cells that did a live
    pair's work on a real cell (%)."""
    swept, _ = run.counter(SWEPT)
    return 100.0 * run.counter(CELL_ITERS)[0] / swept if swept else None
