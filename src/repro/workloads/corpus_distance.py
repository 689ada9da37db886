"""Tiled set-vs-set LC-RWMD: the corpus-analytics scheduler.

The paper motivates LC-RWMD with querying, *clustering*, and classifying
large document sets; the serve engine covers querying only.  This module
turns :class:`~repro.core.lc_rwmd.SegmentedEngine` into a corpus-vs-corpus
machine without ever materializing the (n, n) distance matrix in HBM.

Self all-pairs (the clustering / dedup substrate)
-------------------------------------------------
The corpus is cut into ``T = ⌈n/tile⌉`` query-side tiles.  Phase 1 runs
ONCE per tile against the engine's restricted vocabulary, fed by the
engine's pre-gathered resident targets (zero embedding-table gathers):
``Z_t = phase1(tile_t)`` of shape (v_e, tile).  The symmetric bound of an
(s, t) block pair is then two CHEAP phase-2 SpMMs::

    D_sym[rows_s, cols_t] = max(phase2(rows_s, Z_t), phase2(rows_t, Z_s)ᵀ)

so only UNORDERED pairs ``s ≤ t`` are visited (the transpose covers the
mirrored block — the symmetry skip halves phase-2 work), the diagonal of
``s == t`` blocks is masked to +inf (self-distance), and each block's
per-row top-k candidates are merged into a RUNNING (tile, k) state per row
tile — the (n, n) matrix never exists; peak intermediates are the
(v_e, n) phase-1 cache (column tiles, O(n·v_e) ≪ O(n²) for n ≫ v_e) and
(tile, tile) distance blocks.

Cross-set (corpus-vs-resident)
------------------------------
An external corpus streams through ``engine.symmetric`` in fixed-size query
tiles: per-query top-k blocks concatenate directly, and the optional
resident-side view keeps a running per-resident top-k merged across tiles.

Total complexity for the self case: O(n·v_e·h·m) phase 1 (linear, the
paper's contribution) + O(n²·h/2) phase 2 — versus O(n²·h²·m) for tiled
quadratic RWMD.
"""

from __future__ import annotations

import functools
import math
from typing import Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topk as topk_lib
from repro.core.lc_rwmd import SegmentedEngine
from repro.data.docs import DocSet

Array = jax.Array
_INF = jnp.float32(jnp.inf)


class TileBlock(NamedTuple):
    """One symmetric distance block from the self-pair scheduler."""
    s: int           # row-tile index
    t: int           # column-tile index (s <= t)
    row_idx: Array   # (tile,) global doc ids of the block rows
    col_idx: Array   # (tile,) global doc ids of the block columns
    block: Array     # (tile, tile) symmetric LC-RWMD; +inf at diagonal/padding
    mirrored: bool   # True when (col, row) is NOT visited separately (s < t)


def _tile_starts(n: int, tile: int) -> list[int]:
    if tile <= 0:
        raise ValueError(f"tile must be positive, got {tile}")
    return list(range(0, n, tile))


class SelfPairScheduler:
    """Pair-tiled symmetric all-pairs scan over an engine's resident corpus.

    Holds the per-tile phase-1 cache and the jitted block step; consumers
    (top-k, threshold graphs) iterate :meth:`blocks`.
    """

    def __init__(self, engine: SegmentedEngine, *, tile: int = 64):
        self.engine = engine
        self.n = engine.resident.n_docs
        self.tile = min(tile, self.n)
        self.starts = _tile_starts(self.n, self.tile)
        self._z: list[tuple] = []  # phase-1 cache: per tile, one (v_e, tile)
        #                              Z per segment
        # The engine carries tombstones: snapshot the live mask at
        # construction (the phase-1 cache is a same-version snapshot anyway)
        # and pass it as a TRACED argument so deletes elsewhere never force
        # a re-trace of the block step.  A dead doc's row AND column are
        # masked — it has no neighbors and is no one's neighbor.
        self._live = engine.live_mask_device()
        self._step = jax.jit(self._step_impl)

    def _tile_idx(self, lo: int) -> Array:
        # Global ids; the last tile runs past n and is masked downstream.
        return jnp.arange(lo, lo + self.tile, dtype=jnp.int32)

    def _step_impl(self, z_s: tuple, z_t: tuple, idx_s: Array, idx_t: Array,
                   live: Array):
        """max(D1[rows_s, cols_t], D1[rows_t, cols_s]ᵀ), masked."""
        b_st = self.engine._one_sided_rows_impl(idx_s, z_t)  # (tile, tile)
        b_ts = self.engine._one_sided_rows_impl(idx_t, z_s)  # (tile, tile)
        sym = jnp.maximum(b_st, b_ts.T)
        ri, ci = idx_s[:, None], idx_t[None, :]
        invalid = (ri == ci) | (ri >= self.n) | (ci >= self.n)
        lr = jnp.take(live, jnp.clip(idx_s, 0, self.n - 1))
        lc = jnp.take(live, jnp.clip(idx_t, 0, self.n - 1))
        invalid = invalid | (~lr[:, None]) | (~lc[None, :])
        return jnp.where(invalid, _INF, sym)

    def _z_tile(self, t: int) -> Array:
        while len(self._z) <= t:
            lo = self.starts[len(self._z)]
            self._z.append(self.engine.phase1_resident(self._tile_idx(lo)))
        return self._z[t]

    def blocks(self) -> Iterator[TileBlock]:
        """Yield every s ≤ t block; s > t is skipped (covered by transpose)."""
        for t, t_lo in enumerate(self.starts):
            z_t = self._z_tile(t)
            idx_t = self._tile_idx(t_lo)
            for s in range(t + 1):
                idx_s = self._tile_idx(self.starts[s])
                yield TileBlock(
                    s=s, t=t, row_idx=idx_s, col_idx=idx_t,
                    block=self._step(self._z[s], z_t, idx_s, idx_t,
                                     self._live),
                    mirrored=s < t,
                )


@functools.partial(jax.jit, static_argnums=(3,))
def _fold_block(carry: topk_lib.TopK, block: Array, col_gids: Array,
                k: int) -> topk_lib.TopK:
    """Fold one (R, C) block row-wise into the shared streaming carry."""
    return topk_lib.StreamingTopK(k).update_rows(carry, block, col_gids)


def corpus_self_topk(
    engine: SegmentedEngine, k: int, *, tile: int = 64
) -> topk_lib.TopK:
    """Per-document k nearest neighbours over the engine's own corpus.

    Exact symmetric LC-RWMD top-k (self excluded), computed by the pair-tiled
    scheduler — every block folds into the shared
    :class:`~repro.core.topk.StreamingTopK` carry per row tile, so the peak
    distance intermediate is one (tile, tile) block.

    Returns a TopK of (n, k): ascending distances, global doc ids.
    """
    n = engine.resident.n_docs
    n_eff = engine.n_live  # tombstones can't be neighbors
    if not 1 <= k <= n_eff - 1:
        raise ValueError(f"need 1 <= k <= n_live-1 = {n_eff - 1}, got {k}")
    sched = SelfPairScheduler(engine, tile=max(tile, k))
    stk = topk_lib.StreamingTopK(k)
    state = [stk.init(sched.tile) for _ in sched.starts]

    for blk in sched.blocks():
        state[blk.s] = _fold_block(state[blk.s], blk.block, blk.col_idx, k)
        if blk.mirrored:
            state[blk.t] = _fold_block(state[blk.t], blk.block.T,
                                       blk.row_idx, k)
    return topk_lib.TopK(
        dists=jnp.concatenate([st.dists for st in state])[:n],
        indices=jnp.concatenate([st.indices for st in state])[:n],
    )


def _pad_docset(ds: DocSet, rows: int) -> DocSet:
    pad = rows - ds.n_docs
    if pad <= 0:
        return ds
    return DocSet(
        ids=jnp.pad(ds.ids, ((0, pad), (0, 0))),
        weights=jnp.pad(ds.weights, ((0, pad), (0, 0))),
    )


class CorpusTopKResult(NamedTuple):
    query_topk: topk_lib.TopK              # (n_corpus, k) over resident docs
    resident_topk: topk_lib.TopK | None    # (n_resident, k) over corpus docs


def corpus_vs_corpus_topk(
    engine: SegmentedEngine,
    corpus: DocSet,
    k: int,
    *,
    tile: int = 64,
    resident_side: bool = False,
) -> CorpusTopKResult:
    """Per-corpus-doc top-k over the engine's resident set, streamed in tiles.

    Each fixed-size query tile produces one (n_resident, tile) symmetric
    block through the engine (shared query gather, pre-gathered resident
    tensors); per-query top-k rows concatenate directly.  With
    ``resident_side=True`` the same stream also maintains the transposed
    view — per-RESIDENT top-k over the corpus — as a running merge across
    tiles, so neither orientation ever materializes (n_resident, n_corpus).
    """
    n_q = corpus.n_docs
    n_r = engine.resident.n_docs
    k_q = min(k, n_r)       # per-query columns are resident docs
    k_res = min(k, n_q)     # per-resident columns are corpus docs
    tile = min(max(tile, k_res), n_q)
    padded = _pad_docset(corpus, math.ceil(n_q / tile) * tile)
    q_rows: list[topk_lib.TopK] = []
    running = topk_lib.StreamingTopK(k_res).init(n_r) if resident_side else None
    for lo in _tile_starts(n_q, tile):
        d = engine.symmetric(padded.slice_rows(lo, tile))  # (n_r, tile)
        col_gid = jnp.arange(lo, lo + tile, dtype=jnp.int32)
        # Padded query columns hold garbage (0·inf in phase 2); mask by index.
        d = jnp.where((col_gid >= n_q)[None, :], _INF, d)
        q_rows.append(topk_lib.topk_smallest_cols(d, k_q))
        if resident_side:
            running = _fold_block(running, d, col_gid, k_res)
    q_tk = topk_lib.TopK(
        dists=jnp.concatenate([p.dists for p in q_rows])[:n_q],
        indices=jnp.concatenate([p.indices for p in q_rows])[:n_q],
    )
    return CorpusTopKResult(query_topk=q_tk, resident_topk=running)


def corpus_self_topk_distributed(
    engine: SegmentedEngine,
    mesh,
    k: int,
    *,
    tile: int = 64,
    refine: bool = True,
    rerank_wmd: bool = False,
    wmd_kw: dict | None = None,
    bf16_matmul: bool = False,
) -> topk_lib.TopK:
    """Self-corpus kNN with tiles sharded over a TPU mesh.

    Streams resident tiles as query batches through the engine-backed
    distributed serve step (`distributed/lcrwmd_dist.build_serve_step`) with
    in-mesh self-exclusion: the resident rows stay sharded over the mesh
    batch axes, each tile costs one serve step, and the candidate cascade
    (one-sided top-k → symmetric refine → optional Sinkhorn rerank) matches
    serving semantics — returned distances are exact symmetric RWMD (or WMD)
    for the returned pairs.
    """
    from repro.distributed.lcrwmd_dist import build_serve_step

    n = engine.resident.n_docs
    tile = min(tile, n)
    serve = build_serve_step(
        mesh, k=k, engine=engine, refine=refine, bf16_matmul=bf16_matmul,
        rerank_wmd=rerank_wmd, wmd_kw=wmd_kw, self_exclude=True,
    )
    parts: list[topk_lib.TopK] = []
    for lo in _tile_starts(n, tile):
        idx = jnp.arange(lo, lo + tile, dtype=jnp.int32)
        res = serve(engine.resident_tile(idx), query_ids=idx)
        parts.append(res.topk)
    tk = topk_lib.TopK(
        dists=jnp.concatenate([p.dists for p in parts])[:n],
        indices=jnp.concatenate([p.indices for p in parts])[:n],
    )
    return tk
