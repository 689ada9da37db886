"""Cluster-routed index benchmark: recall@k vs speedup over the flat scan.

Measures the two claims of the routed-serving PR, persisted as
``BENCH_index.json``:

1. ``index_recall_sweep`` — one :class:`~repro.index.ClusterIndex` over an
   n >= 8192 corpus, sweeping ``top_p`` (probed cells per query) on random
   queries: recall@10 of the routed engine-level top-k against the flat
   symmetric :meth:`SegmentedEngine.topk` ground truth.  Routing replaces
   the O(n) scan with O(n/cells · p), so recall-vs-p is the
   accuracy/compute dial.

2. ``index_routed_vs_flat_serve`` — the compiled distributed serve step,
   routed vs flat, at the smallest swept ``top_p`` whose recall clears
   ``MIN_RECALL``.  The query batch is locality-correlated (drawn from one
   topic neighborhood): the routed step's compute is ∝ the number of
   DISTINCT cells the batch probes, so this is the regime the index is
   built for — batchers that group queries by tenant/topic, burst traffic,
   near-duplicate streams.  A batch of queries with unrelated routes
   degrades toward ``probe_cap`` probed cells (still bounded, never worse
   than ``probe_cap × rows_pad`` rows).  The ``speedup`` derived is the
   acceptance number: >= ``MIN_SPEEDUP``x wall-clock with recall@10 >=
   ``MIN_RECALL`` at n >= 8192.  ``INDEX_BENCH_SOFT=1`` downgrades the
   assertion to a report (loaded CI runners).

Recorded in EXPERIMENTS.md §Index.
"""

from __future__ import annotations

import os

import numpy as np

from benchmarks.common import BenchResult, cached_corpus, time_fn

N_DOCS = 8192       # acceptance floor: n >= 8192
N_CELLS = 32
VOCAB = 4096
EMB_DIM = 32
H_MAX = 12
K = 10
N_QUERIES = 64
TOP_P_SWEEP = (1, 2, 4, 8)
MIN_RECALL = 0.95   # acceptance: recall@10 at the chosen top_p
MIN_SPEEDUP = 4.0   # acceptance: routed vs flat serve wall-clock


def _docset(corpus, picks):
    from repro.data.docs import DocSet

    return DocSet(ids=corpus.docs.ids[picks],
                  weights=corpus.docs.weights[picks])


def _recall(approx_idx, exact_idx) -> float:
    a = np.asarray(approx_idx)
    b = np.asarray(exact_idx)
    return float(np.mean([len(set(a[i]) & set(b[i])) / b.shape[1]
                          for i in range(b.shape[0])]))


def run():
    from repro.core.lc_rwmd import SegmentedEngine
    from repro.distributed.lcrwmd_dist import build_serve_step
    from repro.index import ClusterIndex
    from repro.launch.mesh import make_host_mesh

    # A topic-clustered corpus — the structure IVF routing exploits.
    # n_classes == N_CELLS keeps the k-centers partition balanced, so
    # rows_pad (the padded per-cell scan extent) tracks n/cells.
    corpus = cached_corpus(n_docs=N_DOCS, vocab_size=VOCAB, emb_dim=EMB_DIM,
                           h_max=H_MAX, mean_h=8.0, n_classes=N_CELLS,
                           topic_noise=0.15, seed=5)
    eng = SegmentedEngine(corpus.docs, corpus.emb)
    mesh = make_host_mesh()
    rng = np.random.default_rng(3)

    # -- recall sweep: random queries, one exhaustive-capable index
    idx = ClusterIndex(eng, num_cells=N_CELLS, top_p=1, probe_cap=N_CELLS,
                       seed=0)
    picks = rng.choice(N_DOCS, N_QUERIES, replace=False)
    queries = _docset(corpus, picks)
    gt = np.asarray(eng.topk(queries, K).indices)
    recalls = {}
    for p in TOP_P_SWEEP:
        route = idx.route(queries, top_p=p, bound_slack=None)
        tk = idx.routed_topk(queries, K, route=route)
        recalls[p] = round(_recall(tk.indices, gt), 4)
    cell_sizes = np.bincount(idx.labels, minlength=N_CELLS)
    yield BenchResult(
        f"index_recall_sweep_n{N_DOCS}_c{N_CELLS}", 0.0,
        derived={**{f"recall@{K}_p{p}": r for p, r in recalls.items()},
                 "cells": N_CELLS, "rows_cap": idx.rows_cap,
                 "cell_min": int(cell_sizes.min()),
                 "cell_max": int(cell_sizes.max())})

    # -- serve-path speedup at the cheapest top_p that clears MIN_RECALL
    p_star = next((p for p in TOP_P_SWEEP if recalls[p] >= MIN_RECALL),
                  TOP_P_SWEEP[-1])
    # Locality-correlated batch: the N_QUERIES docs nearest one cell's WCD
    # centroid.  The batch's routed cells stay few, so the compiled step
    # scans a handful of cells instead of all N_DOCS rows.
    cen = np.asarray(idx._cen)
    cell = int(np.argmax(cell_sizes))
    members = np.nonzero(idx.labels == cell)[0]
    d_cen = np.linalg.norm(cen[members] - cen[members].mean(0), axis=1)
    l_picks = members[np.argsort(d_cen)[:N_QUERIES]]
    l_queries = _docset(corpus, l_picks)

    # Same seed over the same docs -> identical partition; a small
    # probe_cap keeps the compiled step's padded compute at a few slots
    # (+2 headroom over p_star for the batch's route union).
    idx_serve = ClusterIndex(eng, num_cells=N_CELLS, top_p=p_star,
                             probe_cap=p_star + 2, seed=0)
    flat_step = build_serve_step(mesh, engine=eng, k=K)
    routed_step = build_serve_step(mesh, engine=eng, index=idx_serve, k=K)
    t_flat = time_fn(flat_step, l_queries)
    t_routed = time_fn(routed_step, l_queries)
    recall = _recall(np.asarray(routed_step(l_queries).topk.indices),
                     np.asarray(flat_step(l_queries).topk.indices))

    speedup = t_flat / t_routed
    ok = speedup >= MIN_SPEEDUP and recall >= MIN_RECALL
    if not ok and not os.environ.get("INDEX_BENCH_SOFT"):
        raise AssertionError(
            f"routed serve speedup {speedup:.1f}x (need >= {MIN_SPEEDUP}x) "
            f"at recall@{K} {recall:.3f} (need >= {MIN_RECALL}) — "
            f"flat {t_flat / 1e3:.1f} ms vs routed {t_routed / 1e3:.1f} ms "
            f"at top_p={p_star}")
    yield BenchResult(
        f"index_routed_vs_flat_serve_n{N_DOCS}_p{p_star}", t_routed,
        derived={"flat_us": round(t_flat, 1), "speedup": round(speedup, 2),
                 "recall": round(recall, 4), "top_p": p_star,
                 "probe_cap": idx_serve.probe_cap,
                 "min_speedup": MIN_SPEEDUP, "min_recall": MIN_RECALL,
                 "ok": ok})
