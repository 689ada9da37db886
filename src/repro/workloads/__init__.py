"""Corpus-analytics workloads on top of the LC-RWMD serve engine.

The paper motivates LC-RWMD with three workloads — querying, clustering,
and classifying large document sets.  ``repro.core`` + ``repro.serving``
cover querying; this package covers the corpus-vs-corpus rest:

  * :mod:`corpus_distance` — tiled all-pairs scheduling (self and
    cross-set) with running top-k merges; the (n, n) matrix never
    materializes.
  * :mod:`clustering` — greedy k-centers seeding + k-medoids refinement
    with a WCD prefilter and optional Sinkhorn-WMD rerank.
  * :mod:`neighbors` — threshold / k-NN near-duplicate graphs and
    duplicate-group extraction from the same tile stream.

All entry points take a prebuilt :class:`~repro.core.lc_rwmd.SegmentedEngine`
(built once per corpus) and a ``tile`` knob that bounds every device
intermediate at (tile, tile) — the memory model is tabulated in
``docs/ARCHITECTURE.md`` and EXPERIMENTS.md §Workloads.
"""

from repro.workloads.clustering import (
    ClusterResult,
    adjusted_rand_index,
    kcenters,
    kmedoids,
    kmedoids_wcd_baseline,
    purity,
)
from repro.workloads.corpus_distance import (
    CorpusTopKResult,
    SelfPairScheduler,
    TileBlock,
    corpus_self_topk,
    corpus_self_topk_distributed,
    corpus_vs_corpus_topk,
)
from repro.workloads.neighbors import (
    DUPLICATE_SCORE_FLOOR,
    NeighborGraph,
    connected_components,
    duplicate_groups,
    ingest_dedup_mask,
    knn_graph,
    near_duplicate_graph,
)

__all__ = [
    "ClusterResult", "adjusted_rand_index", "kcenters", "kmedoids",
    "kmedoids_wcd_baseline", "purity",
    "CorpusTopKResult", "SelfPairScheduler", "TileBlock",
    "corpus_self_topk", "corpus_self_topk_distributed",
    "corpus_vs_corpus_topk",
    "DUPLICATE_SCORE_FLOOR", "NeighborGraph", "connected_components",
    "duplicate_groups", "ingest_dedup_mask", "knn_graph",
    "near_duplicate_graph",
]
