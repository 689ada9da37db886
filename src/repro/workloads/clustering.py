"""Document clustering on LC-RWMD: greedy k-centers + k-medoids refinement.

The paper's clustering workload (Sec. I motivates LC-RWMD for "clustering
... large sets of documents") realized on top of the serve engine:

  * :func:`kcenters` — greedy farthest-first traversal (2-approximation of
    the k-centers objective); one B=1 engine block per center.
  * :func:`kmedoids` — PAM-style alternation driven by the engine's
    resident-tile entry points.  The assignment stage runs a **WCD-centroid
    prefilter** (cheap (n, k) centroid distances, reusing
    :mod:`repro.core.wcd`) to keep only ``prefilter`` candidate medoids per
    doc, then evaluates the symmetric RWMD bound ONLY on those pairs via
    :func:`repro.core.rwmd.rwmd_pairs_from_t` — O(n·c·h²·m) instead of the
    full block's O(n·k·h²·m) swapped-direction term.  Optionally the
    assignment is re-ranked by batched Sinkhorn-WMD
    (:func:`repro.core.wmd.wmd_batched_dispatch`) on the same candidate
    pairs.  The medoid-update stage shortlists members closest to the
    cluster's WCD centroid and picks the one minimizing the summed RWMD to
    all members — all clusters' shortlists batched into ONE
    (n, k·medoid_candidates) engine block with in-device per-cluster
    membership masking.

WCD is a heuristic prefilter here, not a bound on RWMD (WCD ≤ WMD holds,
WCD ≤ RWMD does not in general); ``prefilter=None`` disables it and scores
all k medoids exactly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topk as topk_lib
from repro.core.distances import dists
from repro.core.lc_rwmd import SegmentedEngine
from repro.core.rwmd import rwmd_pairs_from_t
from repro.core.wcd import centroids_from_t
from repro.core.wmd import wmd_batched_dispatch

Array = jax.Array


class ClusterResult(NamedTuple):
    labels: np.ndarray     # (n,) int32 cluster assignment
    medoids: np.ndarray    # (k,) int32 medoid doc ids
    objective: float       # sum of assigned distances (RWMD or WMD)
    n_iters: int           # k-medoids iterations executed


def kcenters(
    engine: SegmentedEngine, n_clusters: int, *, first: int | None = 0,
    seed: int | None = None,
) -> np.ndarray:
    """Greedy k-centers (farthest-first) seeding over the resident corpus.

    Returns (n_clusters,) int32 doc ids.  Each step adds the doc farthest
    (symmetric LC-RWMD) from the chosen set — the classic 2-approximation,
    and the standard k-medoids initializer.

    The traversal is deterministic given its starting doc: pass ``seed`` to
    derive ``first`` from an explicit PRNG (``first=None`` or ``seed``
    given), so index partitions rebuilt from the same corpus + seed land on
    identical centers — rebuild/compaction paths rely on this.
    """
    n = engine.resident.n_docs
    if not 1 <= n_clusters <= n:
        raise ValueError(f"need 1 <= n_clusters <= {n}, got {n_clusters}")
    if seed is not None or first is None:
        first = int(np.random.default_rng(0 if seed is None else seed)
                    .integers(0, n))
    centers = [int(first)]
    mind = np.full(n, np.inf, dtype=np.float32)
    for _ in range(n_clusters - 1):
        col = np.asarray(
            engine.symmetric_resident(jnp.array([centers[-1]], jnp.int32))
        )[:, 0]
        mind = np.minimum(mind, col)
        centers.append(int(np.argmax(mind)))
    return np.asarray(centers, dtype=np.int32)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _assign_prefiltered(
    cen: Array, t_r: Array, w_r: Array, medoids: Array,
    c: int, rerank_wmd: bool, sink_items: tuple = (),
):
    """WCD-prefilter → candidate-pair RWMD (→ optional Sinkhorn) assignment.

    cen (n, m) doc centroids, t_r (n, h, m) pre-gathered doc embeddings,
    w_r (n, h) weights, medoids (k,).  Returns (labels (n,), dist (n,)).
    """
    d_wcd = dists(cen, cen[medoids])                    # (n, k) cheap
    cand = topk_lib.topk_smallest(d_wcd, c).indices     # (n, c) medoid slots
    med_doc = medoids[cand]                             # (n, c) doc ids
    # One candidate slot at a time: t_r itself is the (n, h, m) left side of
    # every slot, so nothing is ever replicated c-fold.
    cols = []
    for j in range(c):
        sel = med_doc[:, j]
        if rerank_wmd:
            cols.append(wmd_batched_dispatch(
                t_r, w_r, t_r[sel], w_r[sel], **dict(sink_items)))
        else:
            cols.append(rwmd_pairs_from_t(t_r, w_r, t_r[sel], w_r[sel]))
    vals = jnp.stack(cols, axis=1)                      # (n, c)
    best = jnp.argmin(vals, axis=1)
    labels = jnp.take_along_axis(cand, best[:, None], axis=1)[:, 0]
    dist = jnp.take_along_axis(vals, best[:, None], axis=1)[:, 0]
    return labels.astype(jnp.int32), dist


@jax.jit
def _assign_full(d_block: Array):
    """(n, k) engine block → (labels, dist)."""
    return jnp.argmin(d_block, axis=1).astype(jnp.int32), jnp.min(d_block, axis=1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _medoid_cost_batched(block: Array, labels: Array, k: int, c: int):
    """Per-cluster candidate costs from ONE (n, k·c) engine block.

    ``block[:, j*c + u]`` is the distance of every doc to cluster j's u-th
    shortlisted candidate; membership is masked in-device per cluster, so
    the k per-cluster engine calls of the old medoid-update stage collapse
    into a single batched block.  Returns (k, c) summed member distances.
    """
    n = block.shape[0]
    member = labels[:, None] == jnp.arange(k, dtype=labels.dtype)[None, :]
    blk = block.reshape(n, k, c)
    return jnp.sum(jnp.where(member[:, :, None], blk, 0.0), axis=0)


def kmedoids(
    engine: SegmentedEngine,
    n_clusters: int,
    *,
    n_iters: int = 8,
    prefilter: int | None = None,
    rerank_wmd: bool = False,
    sinkhorn_kw: dict | None = None,
    medoid_candidates: int = 4,
    init: np.ndarray | None = None,
    seed: int | None = None,
) -> ClusterResult:
    """k-medoids over the engine's resident corpus (see module docstring).

    Returns a :class:`ClusterResult`: ``labels`` (n,) int32 cluster ids,
    ``medoids`` (n_clusters,) int32 doc ids, ``inertia`` float.  All device
    blocks are fixed-shape — (n, n_clusters) assignment blocks and ONE
    (n, n_clusters·medoid_candidates) medoid-update block per iteration —
    so ``n_clusters``/``prefilter``/``medoid_candidates`` are
    compile-relevant: keep them fixed across calls to reuse the engine's
    jit cache.

    ``prefilter``: number of WCD-nearest medoid candidates scored with RWMD
    per doc (None → all ``n_clusters`` scored via one engine block).  A
    speed knob for WCD-friendly corpora ONLY — on centroid-degenerate data
    the prefilter feeds the exact stage garbage (see EXPERIMENTS.md
    §Workloads).
    ``rerank_wmd``: score candidate pairs with batched Sinkhorn-WMD instead
    of the RWMD bound (requires ``prefilter``); ``sinkhorn_kw`` forwards
    solver knobs.
    ``medoid_candidates``: shortlist size for the medoid-update stage.
    ``seed``: explicit PRNG seed forwarded to the :func:`kcenters`
    initializer (ignored when ``init`` is given).  Every downstream stage
    is deterministic given the init, so a fixed seed makes the whole
    clustering reproducible across rebuilds of the same corpus.
    """
    n = engine.resident.n_docs
    if rerank_wmd and prefilter is None:
        prefilter = n_clusters  # WMD rerank rides the candidate-pair path
    if prefilter is not None:
        prefilter = max(1, min(prefilter, n_clusters))
    docs = engine.resident
    t_r = engine.emb_full[docs.ids]            # (n, h, m)
    cen = centroids_from_t(docs.weights, t_r)  # WCD centroids
    sink_items = tuple(sorted((sinkhorn_kw or {}).items()))

    medoids = np.asarray(
        kcenters(engine, n_clusters, seed=seed) if init is None else init,
        dtype=np.int32)
    labels = np.zeros(n, dtype=np.int32)
    obj = float("inf")
    it = 0
    for it in range(1, n_iters + 1):
        med_j = jnp.asarray(medoids)
        if prefilter is None:
            lab, dist = _assign_full(engine.symmetric_resident(med_j))
        else:
            lab, dist = _assign_prefiltered(
                cen, t_r, docs.weights, med_j, prefilter, rerank_wmd,
                sink_items)
        labels = np.asarray(lab)
        obj = float(np.sum(np.asarray(dist)))

        # Medoid update: per cluster, shortlist the members nearest the
        # cluster's WCD centroid, then pick the shortlisted member whose
        # summed RWMD to all members is smallest (exact over the shortlist).
        # ALL clusters' shortlists go through ONE (n, k·c_upd) engine block;
        # per-cluster membership is masked in-device (_medoid_cost_batched)
        # instead of issuing one engine call per cluster.
        new_medoids = medoids.copy()
        cen_np = np.asarray(cen)
        c_upd = medoid_candidates
        shortlists = np.repeat(medoids[:, None], c_upd, axis=1).astype(np.int32)
        valid_len = np.zeros(n_clusters, dtype=np.int64)
        for j in range(n_clusters):
            members = labels == j
            if not members.any():
                continue  # empty cluster keeps its medoid (valid_len 0)
            mean_c = cen_np[members].mean(axis=0)
            m_ids = np.nonzero(members)[0]
            d_c = np.linalg.norm(cen_np[m_ids] - mean_c, axis=1)
            short = m_ids[np.argsort(d_c)[:c_upd]]
            shortlists[j] = np.resize(short, c_upd)  # fixed engine shape
            valid_len[j] = len(short)
        block = engine.symmetric_resident(
            jnp.asarray(shortlists.reshape(-1), jnp.int32))  # (n, k·c_upd)
        costs = np.asarray(_medoid_cost_batched(
            block, jnp.asarray(labels), n_clusters, c_upd))   # (k, c_upd)
        for j in range(n_clusters):
            if valid_len[j]:
                best = int(np.argmin(costs[j, : valid_len[j]]))
                new_medoids[j] = shortlists[j, best]
        if np.array_equal(np.sort(new_medoids), np.sort(medoids)):
            medoids = new_medoids
            break
        medoids = new_medoids
    return ClusterResult(labels=labels, medoids=medoids, objective=obj,
                         n_iters=it)


def kmedoids_wcd_baseline(
    engine: SegmentedEngine, n_clusters: int, *, n_iters: int = 8,
) -> ClusterResult:
    """WCD-only k-medoids — the cheap baseline the bench compares against.

    Same alternation, but every distance is a centroid distance: no phase-1,
    no swapped direction, no transport.  Paper Fig. 11's point is that WCD
    is a poor WMD proxy; the workloads bench quantifies the clustering gap.
    """
    n = engine.resident.n_docs
    docs = engine.resident
    t_r = engine.emb_full[docs.ids]            # (n, h, m)
    cen = np.asarray(centroids_from_t(docs.weights, t_r))

    # Farthest-first on WCD for seeding (mirrors kcenters).
    medoids = [0]
    mind = np.full(n, np.inf, dtype=np.float32)
    for _ in range(n_clusters - 1):
        mind = np.minimum(
            mind, np.linalg.norm(cen - cen[medoids[-1]], axis=1))
        medoids.append(int(np.argmax(mind)))
    medoids = np.asarray(medoids, dtype=np.int32)

    labels = np.zeros(n, dtype=np.int32)
    obj = float("inf")
    it = 0
    for it in range(1, n_iters + 1):
        d = np.linalg.norm(cen[:, None, :] - cen[medoids][None], axis=2)
        labels = d.argmin(axis=1).astype(np.int32)
        obj = float(d.min(axis=1).sum())
        new_medoids = medoids.copy()
        for j in range(n_clusters):
            m_ids = np.nonzero(labels == j)[0]
            if not len(m_ids):
                continue
            intra = np.linalg.norm(
                cen[m_ids][:, None, :] - cen[m_ids][None], axis=2)
            new_medoids[j] = m_ids[int(intra.sum(axis=1).argmin())]
        if np.array_equal(np.sort(new_medoids), np.sort(medoids)):
            medoids = new_medoids
            break
        medoids = new_medoids
    return ClusterResult(labels=labels, medoids=medoids, objective=obj,
                         n_iters=it)


# ---------------------------------------------------------------------------
# Clustering quality metrics (host-side, label-permutation invariant)
# ---------------------------------------------------------------------------
def purity(pred: np.ndarray, true: np.ndarray) -> float:
    """Fraction of docs in their cluster's majority class."""
    pred = np.asarray(pred)
    true = np.asarray(true)
    total = 0
    for c in np.unique(pred):
        members = true[pred == c]
        total += np.bincount(members).max()
    return float(total / len(true))


def adjusted_rand_index(pred: np.ndarray, true: np.ndarray) -> float:
    """ARI from the pair-counting contingency table (no sklearn)."""
    pred = np.asarray(pred)
    true = np.asarray(true)
    n = len(true)
    cats_p, pred_i = np.unique(pred, return_inverse=True)
    cats_t, true_i = np.unique(true, return_inverse=True)
    table = np.zeros((len(cats_p), len(cats_t)), dtype=np.int64)
    np.add.at(table, (pred_i, true_i), 1)

    def comb2(x):
        return x * (x - 1) / 2.0

    sum_ij = comb2(table).sum()
    sum_a = comb2(table.sum(axis=1)).sum()
    sum_b = comb2(table.sum(axis=0)).sum()
    expected = sum_a * sum_b / comb2(n)
    max_index = 0.5 * (sum_a + sum_b)
    if max_index == expected:
        return 1.0
    return float((sum_ij - expected) / (max_index - expected))
