"""Corpus-analytics workload benchmarks.

Three contracts, mirroring the kernels bench:

  1. **Tile-scheduler footprint** — asserted STRUCTURALLY on the traced
     block step: its largest f32 intermediate is tile-bounded, and the full
     (n, n) distance matrix appears nowhere; the brute-force all-pairs path
     is the (n, n) positive control.  The derived numbers record the memory
     model: peak tiled bytes (phase-1 Z cache (v_e, n) + one (tile, tile)
     block + (n, k) output) vs the (n, n) matrix.
  2. **Tiled vs brute timing** — XLA:CPU wall time of the tiled self top-k
     against brute-force symmetric LC-RWMD + top-k at the same shape.
  3. **Clustering quality** — k-medoids on a labeled centroid-degenerate
     corpus (make_bimodal_corpus) must beat the WCD-only baseline on
     ARI/purity; recorded as the acceptance flag ``beats_wcd``.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import BenchResult, intermediate_shapes, time_fn
from repro.core import SegmentedEngine, lc_rwmd_symmetric, topk_smallest
from repro.data.synth import CorpusSpec, make_bimodal_corpus, make_corpus
from repro.workloads import (
    SelfPairScheduler,
    adjusted_rand_index,
    corpus_self_topk,
    kmedoids,
    kmedoids_wcd_baseline,
    near_duplicate_graph,
    purity,
)


def _tiled_footprint_bench() -> list[BenchResult]:
    n, tile, k = 384, 64, 8
    c = make_corpus(CorpusSpec(
        n_docs=n, vocab_size=2048, emb_dim=48, h_max=16, mean_h=10.0,
        n_classes=4, seed=11))
    emb = jnp.asarray(c.emb)
    engine = SegmentedEngine(c.docs, emb)
    v_e = engine.emb_restricted.shape[0]
    h = c.docs.h_max

    # -- structural tiling contract on the traced step ---------------------
    sched = SelfPairScheduler(engine, tile=tile)
    idx = jnp.arange(tile, dtype=jnp.int32)
    z = engine.phase1_resident(idx)
    step_shapes = intermediate_shapes(sched._step_impl, z, z, idx, idx,
                                      sched._live)
    assert (n, n) not in step_shapes, "tiled step materialized (n, n)"
    assert (tile, tile) in step_shapes, "step should emit (tile, tile) blocks"
    biggest = max(int(np.prod(s)) for s in step_shapes if s)
    assert biggest <= max(tile * tile * h, v_e * tile), (
        f"step intermediate {biggest} exceeds the tile bound")
    # Positive control: the brute path really does materialize (n, n).
    brute_shapes = intermediate_shapes(
        lambda: lc_rwmd_symmetric(c.docs, c.docs, emb))
    assert (n, n) in brute_shapes, "positive control lost its (n, n)"

    # -- memory model ------------------------------------------------------
    bytes_full = 4 * n * n
    bytes_tiled = 4 * (v_e * n + tile * tile + n * k)  # Z cache+block+output
    bytes_block_peak = 4 * max(tile * tile * h, v_e * tile)

    # -- timing: tiled vs brute at the same shape --------------------------
    def tiled():
        return corpus_self_topk(engine, k, tile=tile)

    def brute():
        d = lc_rwmd_symmetric(c.docs, c.docs, emb)
        d = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, d)
        return topk_smallest(d, k)

    t_tiled = time_fn(lambda: tiled().dists, warmup=1, iters=3)
    t_brute = time_fn(lambda: brute().dists, warmup=1, iters=3)
    # Parity vs brute force: identical candidate SETS per row and distances
    # within the repo's f32 tolerance (adjacent ranks may swap inside ~2e-3
    # cancellation noise of the ‖a‖²+‖b‖²−2ab expansion; order-exactness at
    # small n is pinned by tests/test_workloads.py).
    tk_t, tk_b = tiled(), brute()
    set_match = float(np.mean([
        set(r1) == set(r2)
        for r1, r2 in zip(np.asarray(tk_t.indices), np.asarray(tk_b.indices))
    ]))
    dist_match = bool(np.allclose(np.asarray(tk_t.dists),
                                  np.asarray(tk_b.dists),
                                  rtol=1e-4, atol=1e-2))
    assert set_match == 1.0 and dist_match, (set_match, dist_match)
    return [
        BenchResult(f"workloads_self_topk_tiled_n{n}_t{tile}", t_tiled, derived={
            "n": n, "tile": tile, "k": k, "n_tile_pairs": 6 * 7 // 2,
            "topk_set_parity": set_match,
            "topk_dist_parity": dist_match,
            "bytes_full_matrix": bytes_full,
            "bytes_tiled_total": bytes_tiled,
            "bytes_block_peak": bytes_block_peak,
            "matrix_reduction_x": round(bytes_full / bytes_block_peak, 1),
            "note": "Z cache is O(v_e·n); block peak is the per-step HBM "
                    "high-water mark (see EXPERIMENTS §Workloads)"}),
        BenchResult(f"workloads_self_topk_brute_n{n}", t_brute, derived={
            "bytes_full_matrix": bytes_full,
            "vs_tiled": round(t_brute / t_tiled, 2),
            "note": "positive control: (n,n) symmetric LC-RWMD + top-k"}),
    ]


def _clustering_bench() -> list[BenchResult]:
    c = make_bimodal_corpus(CorpusSpec(
        n_docs=192, vocab_size=1024, emb_dim=32, h_max=24, mean_h=16.0,
        n_classes=4, topic_noise=0.1, emb_topic_scale=4.0,
        emb_word_scale=1.0, seed=5))
    engine = SegmentedEngine(c.docs, jnp.asarray(c.emb))

    t0 = time.perf_counter()
    rw = kmedoids(engine, 4, n_iters=8)
    t_rw = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    wc = kmedoids_wcd_baseline(engine, 4, n_iters=8)
    t_wc = (time.perf_counter() - t0) * 1e6

    ari_rw = adjusted_rand_index(rw.labels, c.labels)
    ari_wc = adjusted_rand_index(wc.labels, c.labels)
    pur_rw = purity(rw.labels, c.labels)
    pur_wc = purity(wc.labels, c.labels)
    assert ari_rw > ari_wc, (
        f"k-medoids (ARI {ari_rw:.3f}) must beat WCD (ARI {ari_wc:.3f})")
    return [
        BenchResult("workloads_kmedoids_rwmd_n192_c4", t_rw, derived={
            "ari": round(ari_rw, 3), "purity": round(pur_rw, 3),
            "iters": rw.n_iters, "beats_wcd": bool(ari_rw > ari_wc),
            "corpus": "bimodal (centroid-degenerate)",
        }),
        BenchResult("workloads_kmedoids_wcd_baseline_n192_c4", t_wc, derived={
            "ari": round(ari_wc, 3), "purity": round(pur_wc, 3),
            "iters": wc.n_iters,
            "note": "WCD is blind here by construction (doc centroids ~ 0); "
                    "paper Fig. 11's WCD<RWMD hierarchy, clustering edition",
        }),
    ]


def _neighbors_bench() -> BenchResult:
    c = make_corpus(CorpusSpec(
        n_docs=256, vocab_size=1024, emb_dim=48, h_max=16, mean_h=10.0,
        n_classes=4, seed=13))
    # Plant 8 duplicate pairs to give the threshold pass a known signal.
    ids = np.array(c.docs.ids)
    w = np.array(c.docs.weights)
    planted = [(i, 128 + i) for i in range(8)]
    for dst, src in planted:
        ids[dst] = ids[src]
        w[dst] = w[src]
    from repro.data.docs import DocSet

    docs = DocSet(ids=jnp.asarray(ids), weights=jnp.asarray(w))
    engine = SegmentedEngine(docs, jnp.asarray(c.emb))
    t0 = time.perf_counter()
    g = near_duplicate_graph(engine, 0.05, tile=64)
    t_us = (time.perf_counter() - t0) * 1e6
    found = sum(
        1 for a, b in planted
        if b in g.indices[g.indptr[a]:g.indptr[a + 1]])
    return BenchResult("workloads_near_dup_graph_n256", t_us, derived={
        "threshold": 0.05, "edges": g.n_edges,
        "planted_pairs": len(planted), "planted_found": found,
        "recall_planted": round(found / len(planted), 3),
    })


def run() -> list[BenchResult]:
    out = _tiled_footprint_bench()
    out += _clustering_bench()
    out.append(_neighbors_bench())
    return out
