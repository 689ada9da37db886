"""Share of the batched Sinkhorn loop's sweeps that did a live pair's work
(%): Δ of the program's ``serving_sinkhorn_cell_iters_total`` over Δ of its
``serving_sinkhorn_swept_cells_total`` over the window.  Padding slots,
padding pairs and pairs that stopped early sweep without work."""

from bench import rerank_work


def read(run):
    return rerank_work.live_share(run)
