"""Top-k smallest-distance selection — local, streaming, and distributed.

The paper's output ``R`` is, per query, the k nearest resident docs.  In the
distributed setting the resident set is sharded over ``(pod, data)``; each
shard computes a local top-k (O(n/shards)) and the O(k)-sized candidates are
merged with one all_gather — "the associated communication cost is typically
marginal compared with the cost of computation" (paper Sec. V).

Every selection and merge in the repo goes through this module and shares
ONE tie-break contract: candidates are ordered by the lexicographic key
``(distance, global doc id)`` ascending.  ``jax.lax.top_k`` already orders
equal values by ascending index, so a :class:`StreamingTopK` reduction over
row blocks is *exactly* equal — values AND index sets, ties included — to a
materialized ``lax.top_k`` over the full distance matrix.  That equality is
what lets the serve path stream phase-2 blocks straight into a (B, k) carry
and never write the (n, B) RWMD matrix to HBM.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

Array = jax.Array

EMPTY_IDX = -1  # index sentinel of unfilled carry slots (dist = +inf)


class TopK(NamedTuple):
    dists: Array    # (..., k) ascending distances
    indices: Array  # (..., k) GLOBAL resident-doc indices


def lex_smallest(dists: Array, indices: Array, k: int) -> TopK:
    """k smallest (distance, index) pairs per row, lexicographic ascending.

    The single merge primitive behind every streaming/distributed top-k
    path: one two-key ``lax.sort`` over the trailing axis, then a slice.
    Equal distances order by ascending index — the same tie-break
    ``lax.top_k`` applies, so merge trees and flat selections agree exactly.
    """
    d, i = jax.lax.sort(
        (dists, indices.astype(jnp.int32)), dimension=-1, num_keys=2)
    return TopK(dists=d[..., :k], indices=i[..., :k])


class StreamingTopK:
    """Running top-k-smallest merge with a fixed-size (..., k) carry.

    Functional (jit/scan-friendly): ``init`` builds an empty carry of +inf
    distances and ``EMPTY_IDX`` ids, ``update`` folds a block of candidate
    (distance, global id) pairs in, and the carry itself is always a valid,
    ascending :class:`TopK`.  Folding the row blocks of an (n, B) distance
    matrix through ``update_cols`` yields bit-identical results to
    ``topk_smallest_cols`` of the materialized matrix (ties included) while
    the peak live intermediate is one (block, B) slab plus the (B, k) carry.

    Unfilled slots only surface when fewer than k finite candidates exist
    (e.g. every row masked to +inf); callers that mask rows should keep
    k ≤ the per-query count of unmasked rows.
    """

    def __init__(self, k: int):
        if k < 1:
            raise ValueError(f"k must be positive, got {k}")
        self.k = k

    def init(self, *batch_shape: int) -> TopK:
        """Empty carry of shape (*batch_shape, k)."""
        shape = (*batch_shape, self.k)
        return TopK(
            dists=jnp.full(shape, jnp.inf, jnp.float32),
            indices=jnp.full(shape, EMPTY_IDX, jnp.int32),
        )

    def update(self, carry: TopK, dists: Array, indices: Array) -> TopK:
        """Fold (..., c) candidate pairs into the (..., k) carry."""
        d = jnp.concatenate(
            [carry.dists, dists.astype(jnp.float32)], axis=-1)
        i = jnp.concatenate(
            [carry.indices, indices.astype(jnp.int32)], axis=-1)
        return lex_smallest(d, i, self.k)

    def update_cols(self, carry: TopK, d_block: Array, row_gids: Array) -> TopK:
        """Fold a resident-major (R, B) phase-2 block into a (B, k) carry.

        ``row_gids`` (R,) are the global resident-doc ids of the block rows;
        each query column receives the R candidates ``(d_block[:, j], gids)``.
        """
        r, b = d_block.shape
        with jax.named_scope("topk_fold"):
            idx = jnp.broadcast_to(
                row_gids[None, :].astype(jnp.int32), (b, r))
            return self.update(carry, d_block.T, idx)

    def update_rows(self, carry: TopK, block: Array, col_gids: Array) -> TopK:
        """Fold a (R, C) block row-wise into an (R, k) carry (per-row top-k
        over columns — the all-pairs scheduler orientation)."""
        r, c = block.shape
        idx = jnp.broadcast_to(col_gids[None, :].astype(jnp.int32), (r, c))
        return self.update(carry, block, idx)


def topk_smallest(d: Array, k: int) -> TopK:
    """Per-row k smallest entries of d (..., n) → TopK of (..., k)."""
    neg, idx = jax.lax.top_k(-d, k)
    return TopK(dists=-neg, indices=idx)


def topk_smallest_cols(d: Array, k: int) -> TopK:
    """Per-QUERY top-k over the resident axis of an (n_resident, B) matrix."""
    return topk_smallest(d.T, k)  # (B, k)


def topk_from_candidates(vals: Array, cand_indices: Array, k: int) -> TopK:
    """Top-k of per-candidate values, mapped back to global doc ids.

    vals (B, budget) distances for the candidates named by ``cand_indices``
    (B, budget); returns a TopK of (B, min(k, budget)) with global ids.
    """
    final = topk_smallest(vals, min(k, vals.shape[-1]))
    return TopK(
        final.dists,
        jnp.take_along_axis(cand_indices, final.indices, axis=-1),
    )


def merge_topk(parts: Sequence[TopK], k: int) -> TopK:
    """Merge several TopK candidate sets (same leading dims) into one."""
    d = jnp.concatenate([p.dists for p in parts], axis=-1)
    i = jnp.concatenate([p.indices for p in parts], axis=-1)
    return lex_smallest(d, i, k)


def crossshard_topk(local: TopK, k: int, *, axis_names: Sequence[str]) -> TopK:
    """Merge per-shard (B, k̃) TopK candidates into a replicated global TopK.

    The collective half of :func:`distributed_topk`, factored out so the
    streaming serve accumulator can feed it (B, k)-sized partials directly.
    ``local.indices`` must already be GLOBAL doc ids.  Communication: one
    all_gather of (B, k̃) pairs per axis.
    """
    with jax.named_scope("crossshard_topk"):
        d_all = local.dists
        i_all = local.indices
        for ax in axis_names:
            d_all = jax.lax.all_gather(d_all, ax, axis=-1, tiled=True)
            i_all = jax.lax.all_gather(i_all, ax, axis=-1, tiled=True)
        return lex_smallest(d_all, i_all, k)


def distributed_topk(
    local_d: Array, k: int, *, axis_names: Sequence[str], shard_offset: Array
) -> TopK:
    """Global top-k inside shard_map: local_d is this shard's (n_local, B).

    ``shard_offset`` is the global index of local row 0.  Result is replicated
    across ``axis_names``.  Communication: one all_gather of (B, k) pairs.
    """
    local = topk_smallest(local_d.T, min(k, local_d.shape[0]))  # (B, k̃)
    local = TopK(local.dists, local.indices + shard_offset)
    return crossshard_topk(local, k, axis_names=axis_names)
