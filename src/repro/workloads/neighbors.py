"""Near-duplicate graphs from the tiled all-pairs stream.

Consumes the :class:`~repro.workloads.corpus_distance.SelfPairScheduler`
block stream: every symmetric (tile, tile) block is thresholded IN-DEVICE
into a fixed-size survivor list (flat position + distance, compacted with a
shape-static ``nonzero``), so the host only ever touches survivor-sized
arrays — the data-dependent edge count stays host-side while the device
program keeps the scheduler's fixed tile shapes.  Blocks whose survivor
count overflows the fixed capacity (near-duplicate blocks are sparse by
construction, so this is rare) fall back to a full host-side ``np.nonzero``
of that one block.

Graphs are undirected and stored with BOTH orientations (CSR rows are
complete neighbor lists).  ``threshold`` is in symmetric LC-RWMD units —
a LOWER bound on WMD, so a near-duplicate edge here is a superset of the
true WMD near-duplicates at the same threshold (no false dismissals).
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lc_rwmd import SegmentedEngine
from repro.workloads.corpus_distance import SelfPairScheduler, corpus_self_topk

#: Numeric noise floor of the symmetric LC-RWMD score for EXACT copies.
#: Phase-1 distances come from the matmul form ``||a||² + ||b||² − 2ab``
#: whose cancellation error survives the sqrt, so identical docs score
#: ~7e-4 — NOT 0.  Thresholds below this floor silently miss exact
#: duplicates; :func:`near_duplicate_graph` and :func:`ingest_dedup_mask`
#: clamp up to it (with a warning) instead of failing silently.
DUPLICATE_SCORE_FLOOR: float = 1e-2


def _floor_threshold(threshold: float, caller: str) -> float:
    """Validate/clamp a near-duplicate threshold against the noise floor."""
    if not threshold > 0.0:
        raise ValueError(
            f"{caller}: threshold must be > 0, got {threshold!r}")
    if threshold < DUPLICATE_SCORE_FLOOR:
        warnings.warn(
            f"{caller}: threshold {threshold:g} is below the symmetric "
            f"LC-RWMD numeric noise floor ({DUPLICATE_SCORE_FLOOR:g}); "
            f"exact duplicates score ~7e-4, not 0, so this threshold would "
            f"silently miss them.  Clamping to {DUPLICATE_SCORE_FLOOR:g}.",
            stacklevel=3)
        return DUPLICATE_SCORE_FLOOR
    return threshold


class NeighborGraph(NamedTuple):
    """CSR adjacency over corpus docs (undirected, both orientations)."""
    indptr: np.ndarray    # (n+1,) int64 row pointers
    indices: np.ndarray   # (nnz,) int32 neighbor doc ids
    data: np.ndarray      # (nnz,) f32 symmetric LC-RWMD distances
    n_docs: int

    @property
    def n_edges(self) -> int:
        """Undirected edge count (each stored twice in CSR)."""
        return len(self.indices) // 2

    def degree(self) -> np.ndarray:
        return np.diff(self.indptr)


def _edges_to_csr(rows, cols, vals, n: int) -> NeighborGraph:
    rows = np.concatenate(rows) if rows else np.empty(0, np.int64)
    cols = np.concatenate(cols) if cols else np.empty(0, np.int64)
    vals = np.concatenate(vals) if vals else np.empty(0, np.float32)
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return NeighborGraph(indptr=indptr, indices=cols.astype(np.int32),
                         data=vals.astype(np.float32), n_docs=n)


@functools.partial(jax.jit, static_argnums=(2,))
def _block_survivors(block: jax.Array, threshold: jax.Array, cap: int):
    """In-device threshold + compaction of one (R, C) block.

    Returns ``(count, flat_pos (cap,), dists (cap,))`` — a fixed-size
    overflow list: positions of the first ``cap`` survivors in flat
    row-major order (shape-static ``nonzero``) plus their distances.  The
    host reads ``count`` and slices; ``count > cap`` signals overflow.
    """
    flat = block.reshape(-1)
    mask = flat <= threshold  # +inf masks never pass
    count = jnp.sum(mask.astype(jnp.int32))
    (pos,) = jnp.nonzero(mask, size=cap, fill_value=0)
    return count, pos.astype(jnp.int32), flat[pos]


def near_duplicate_graph(
    engine: SegmentedEngine, threshold: float, *, tile: int = 64,
    block_edge_cap: int | None = None,
) -> NeighborGraph:
    """All doc pairs with symmetric LC-RWMD ≤ ``threshold``, as CSR.

    One pass over the s ≤ t tile pairs; mirrored blocks contribute both
    orientations from the same device block (the s == t diagonal block
    already holds both and its self-distance diagonal is pre-masked +inf,
    so identical docs link at distance 0 without self-loops).

    Each block is thresholded and compacted IN-DEVICE to a
    ``block_edge_cap``-sized survivor list (default ``4·tile``), so host
    transfers are survivor-sized, not (tile, tile)-sized; a block whose
    survivor count overflows the cap falls back to a host-side
    ``np.nonzero`` of that one block.
    """
    threshold = _floor_threshold(threshold, "near_duplicate_graph")
    n = engine.resident.n_docs
    sched = SelfPairScheduler(engine, tile=tile)
    cap = block_edge_cap or 4 * sched.tile
    thr = jnp.float32(threshold)
    rows, cols, vals = [], [], []
    for blk in sched.blocks():
        count, pos, d_dev = _block_survivors(blk.block, thr, cap)
        cnt = int(count)
        if cnt == 0:
            continue
        if cnt <= cap:
            flat = np.asarray(pos)[:cnt].astype(np.int64)
            r, c = flat // sched.tile, flat % sched.tile
            d = np.asarray(d_dev)[:cnt]
        else:  # overflow: full host pass for this one (dense) block
            b = np.asarray(blk.block)
            r, c = np.nonzero(b <= threshold)
            d = b[r, c]
        gi = np.asarray(blk.row_idx)[r].astype(np.int64)
        gj = np.asarray(blk.col_idx)[c].astype(np.int64)
        rows.append(gi)
        cols.append(gj)
        vals.append(d)
        if blk.mirrored:  # s < t: the (t, s) block is never visited
            rows.append(gj)
            cols.append(gi)
            vals.append(d)
    return _edges_to_csr(rows, cols, vals, n)


def knn_graph(
    engine: SegmentedEngine, k: int, *, tile: int = 64, mutual: bool = False
) -> NeighborGraph:
    """k-nearest-neighbor graph from the tiled top-k pass, symmetrized.

    ``mutual=False`` keeps an edge if EITHER endpoint ranks the other in its
    top-k (union symmetrization); ``mutual=True`` requires BOTH (the
    classic near-duplicate criterion — robust to hubness).
    """
    tk = corpus_self_topk(engine, k, tile=tile)
    idx = np.asarray(tk.indices)
    d = np.asarray(tk.dists)
    n = engine.resident.n_docs
    src = np.repeat(np.arange(n, dtype=np.int64), k)
    dst = idx.reshape(-1).astype(np.int64)
    w = d.reshape(-1).astype(np.float32)
    if mutual:
        directed = set(zip(src.tolist(), dst.tolist()))
        keep = np.fromiter(
            ((j, i) in directed for i, j in zip(src, dst)),
            dtype=bool, count=len(src))
        src, dst, w = src[keep], dst[keep], w[keep]
    # Union-symmetrize the surviving arcs, dropping duplicates.
    pair = {}
    for i, j, v in zip(src.tolist(), dst.tolist(), w.tolist()):
        pair[(i, j)] = v
        pair[(j, i)] = v
    if not pair:
        return _edges_to_csr([], [], [], n)
    rows = np.fromiter((p[0] for p in pair), np.int64, len(pair))
    cols = np.fromiter((p[1] for p in pair), np.int64, len(pair))
    vals = np.fromiter(pair.values(), np.float32, len(pair))
    return _edges_to_csr([rows], [cols], [vals], n)


def ingest_dedup_mask(
    engine, docs, threshold: float, *, intra_batch: bool = True,
) -> np.ndarray:
    """(B,) bool gate for ingest: True where a doc is NOT a near-duplicate.

    The serving layer's ingest path calls this before
    :meth:`~repro.core.lc_rwmd.SegmentedEngine.append`: each incoming doc is
    scored by symmetric LC-RWMD against the engine's live corpus (one engine
    call — tombstoned docs are +inf and can't block an ingest), and docs
    within ``threshold`` of an existing doc are dropped.  Because symmetric
    LC-RWMD lower-bounds WMD, every true WMD near-duplicate is caught (no
    false admits); some non-duplicates may be dropped, the usual trade of a
    lower-bound prefilter.

    ``intra_batch=True`` additionally de-dups WITHIN the batch (first
    occurrence wins), so a batch containing its own near-copies admits one.

    Pick ``threshold`` above the numeric noise floor: EXACT copies score
    ~1e-3 (not 0) because phase-1 distances come from the matmul-form
    ``||a||² + ||b||² − 2ab`` whose cancellation error survives the sqrt
    (:func:`repro.core.distances.sq_dists`);
    thresholds below :data:`DUPLICATE_SCORE_FLOOR` (1e-2) would silently
    admit exact copies, so they are clamped up to it with a warning.
    """
    threshold = _floor_threshold(threshold, "ingest_dedup_mask")
    b = docs.n_docs
    keep = np.ones(b, dtype=bool)
    if engine.n_live:
        d = np.asarray(engine.symmetric(docs))        # (n, B); dead rows +inf
        keep &= d.min(axis=0) > threshold
    if intra_batch and b > 1:
        from repro.core.lc_rwmd import lc_rwmd_symmetric

        dd = np.asarray(lc_rwmd_symmetric(docs, docs, engine.emb_full))
        for j in range(1, b):
            if keep[j] and bool((dd[:j, j][keep[:j]] <= threshold).any()):
                keep[j] = False
    return keep


def connected_components(graph: NeighborGraph) -> np.ndarray:
    """(n,) int32 component label per doc — near-duplicate groups."""
    n = graph.n_docs
    parent = np.arange(n)

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for i in range(n):
        for j in graph.indices[graph.indptr[i]:graph.indptr[i + 1]]:
            ri, rj = find(i), find(int(j))
            if ri != rj:
                parent[max(ri, rj)] = min(ri, rj)
    roots = np.fromiter((find(i) for i in range(n)), np.int64, n)
    _, labels = np.unique(roots, return_inverse=True)
    return labels.astype(np.int32)


def duplicate_groups(graph: NeighborGraph) -> list[np.ndarray]:
    """Connected components with ≥ 2 docs, largest first."""
    labels = connected_components(graph)
    groups = [np.nonzero(labels == c)[0]
              for c in np.unique(labels)]
    groups = [g for g in groups if len(g) >= 2]
    return sorted(groups, key=len, reverse=True)
