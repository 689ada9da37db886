"""Core algorithms: the paper's LC-RWMD plus every baseline it compares to."""

from repro.core.distances import dists, sq_dists
from repro.core.lc_rwmd import (
    EngineSegment,
    SegmentedEngine,
    SegmentTensors,
    lc_rwmd_one_sided,
    lc_rwmd_streaming,
    lc_rwmd_symmetric,
    phase1_z,
    phase1_z_from_t,
    phase2_spmm,
    restrict_vocab,
)
from repro.core.pipeline import (
    AdaptiveRefineBudget,
    PrunedWMDResult,
    knn_classify,
    pruned_wmd_topk,
)
from repro.core.rwmd import (
    rwmd_many_vs_many,
    rwmd_one_vs_many,
    rwmd_pair,
    rwmd_pairs_from_t,
)
from repro.core.topk import (
    StreamingTopK,
    TopK,
    crossshard_topk,
    distributed_topk,
    lex_smallest,
    merge_topk,
    topk_smallest,
    topk_smallest_cols,
)
from repro.core.wcd import (
    centroids,
    centroids_from_t,
    wcd_many_vs_many,
    wcd_one_vs_many,
)
from repro.core.wmd import (
    emd_exact_lp,
    sinkhorn_log,
    sinkhorn_log_batched,
    wmd_batched,
    wmd_batched_from_t,
    wmd_one_vs_many,
    wmd_pair,
)

__all__ = [
    "dists", "sq_dists",
    "EngineSegment", "SegmentTensors", "SegmentedEngine",
    "lc_rwmd_one_sided", "lc_rwmd_streaming",
    "lc_rwmd_symmetric", "phase1_z", "phase1_z_from_t", "phase2_spmm",
    "restrict_vocab",
    "AdaptiveRefineBudget", "PrunedWMDResult", "knn_classify",
    "pruned_wmd_topk",
    "rwmd_many_vs_many", "rwmd_one_vs_many", "rwmd_pair", "rwmd_pairs_from_t",
    "StreamingTopK", "TopK", "crossshard_topk", "distributed_topk",
    "lex_smallest", "merge_topk", "topk_smallest", "topk_smallest_cols",
    "centroids", "centroids_from_t", "wcd_many_vs_many", "wcd_one_vs_many",
    "emd_exact_lp", "sinkhorn_log", "sinkhorn_log_batched",
    "wmd_batched", "wmd_batched_from_t", "wmd_one_vs_many", "wmd_pair",
]
