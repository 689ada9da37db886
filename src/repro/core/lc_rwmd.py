"""Linear-Complexity RWMD (the paper's contribution, Sec. IV).

Decomposes RWMD against a *set* of documents into two linear phases:

  Phase 1:  For a batch of query docs, compute for every vocabulary word the
            distance to the closest word of each query:
            ``Z[w, j] = min_{q in doc_j} ||E[w] - E[q]||``          O(v·h·m)
  Phase 2:  SpMM of the resident ELL matrix with Z:
            ``D1[i, j] = sum_p W1[i,p] * Z[ids1[i,p], j]``          O(n·h)

The per-pair cost amortizes to O(hm) (vs O(h²m) quadratic RWMD).  The
symmetric (tighter) bound runs the same two phases with the sets swapped and
takes the elementwise max of ``D1`` and ``D2ᵀ`` (paper Sec. IV).

``use_kernel=True`` routes phase 1 (and optionally phase 2) through the
Pallas TPU kernels in :mod:`repro.kernels`; the default pure-jnp path is the
oracle the kernels are tested against.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distances import safe_sqrt, sq_dists
from repro.data.docs import DocSet

Array = jax.Array
_INF = jnp.float32(jnp.inf)


class SegmentTensors(NamedTuple):
    """Device tensors of one immutable engine segment (a jit-able pytree).

    Every :class:`EngineSegment` reduces to this record, and the
    module-level jitted segment kernels take it as a *traced* argument — so
    every segment with the same shapes shares ONE compiled trace (appending a
    delta segment of a previously seen shape never re-traces anything).

    Nothing here is (n_rows·h1, m): a path that needs the resident words'
    embeddings gathers ``emb_r[r_ids]`` for the rows it is working on, and
    the tier-2 WCD centroids are kept per row instead.
    """

    emb_r: Array     # (v_e, m) restricted embedding rows (phase-1 input)
    r_ids: Array     # (n_rows, h1) restricted int32 word ids (ELL)
    r_w: Array       # (n_rows, h1) f32 weights (0 at padding rows/slots)
    valid_r: Array   # (n_rows*h1,) bool slot validity
    cent: Array      # (n_rows, m) f32 WCD centroid Σ_h w·E[word] per row

    @property
    def nbytes(self) -> int:
        """Device bytes held by this segment's resident tensors."""
        return int(sum(x.size * x.dtype.itemsize for x in self))


def _word_rows(emb_r: Array, r_ids: Array) -> Array:
    """``emb_r[r_ids]``, (*r_ids.shape, m): the resident words' embeddings,
    at every valid slot the same row as the full table's.  A segment whose
    documents hold no word has an empty ``emb_r``; its rows are zeros."""
    if emb_r.shape[0] == 0:
        return jnp.zeros((*r_ids.shape, emb_r.shape[1]), emb_r.dtype)
    return emb_r[r_ids]


def _distinct_nbytes(arrays) -> int:
    """Bytes of ``arrays``, each distinct array object counted once."""
    seen = {id(x): x for x in arrays}
    return int(sum(x.size * x.dtype.itemsize for x in seen.values()))


def _pad_rows(x: Array, n_pad: int) -> Array:
    pad = n_pad - x.shape[0]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))


# ---------------------------------------------------------------------------
# Phase 1 — vocabulary-to-query minimum distances
# ---------------------------------------------------------------------------
def phase1_z(
    emb: Array,
    q_ids: Array,
    q_w: Array,
    *,
    bf16_matmul: bool = False,
    vocab_chunk: int | None = None,
) -> Array:
    """Z[w, j] = distance from vocab word w to the closest word of query j.

    Args:
      emb:   (v, m) embedding rows (the paper's E, already restricted to the
             resident vocabulary v_e where possible).
      q_ids: (B, h) int32 query word ids.
      q_w:   (B, h) f32 query weights (0 at padding).
      vocab_chunk: scan the vocab axis in chunks of this size to bound the
             (chunk, B, h) intermediate (the pure-jnp path materializes it;
             the Pallas kernel never does).

    Returns (v, B) f32.
    """
    t = emb[q_ids.reshape(-1)]  # (B*h, m)
    valid = (q_w > 0).reshape(-1)  # (B*h,)
    return phase1_z_from_t(
        emb, t, valid, q_ids.shape[0],
        bf16_matmul=bf16_matmul, vocab_chunk=vocab_chunk,
    )


def phase1_z_from_t(
    emb: Array,
    t: Array,       # (B*h, m) pre-gathered query word embeddings
    valid: Array,   # (B*h,) bool
    b: int,
    *,
    bf16_matmul: bool = False,
    vocab_chunk: int | None = None,
) -> Array:
    """phase1_z with the query-embedding gather hoisted out (engine shares it)."""
    v = emb.shape[0]
    h = t.shape[0] // b

    def chunk_z(e_chunk):
        c = sq_dists(e_chunk, t, bf16_matmul=bf16_matmul)  # (cv, B*h)
        c = jnp.where(valid[None, :], c, _INF)
        return safe_sqrt(jnp.min(c.reshape(-1, b, h), axis=2))  # (cv, B)

    if vocab_chunk is None or vocab_chunk >= v:
        return chunk_z(emb)
    # Non-divisible chunk sizes are handled by zero-padding the vocab axis;
    # the padded rows produce garbage Z rows that are sliced off below.
    pad = (-v) % vocab_chunk
    emb_p = jnp.pad(emb, ((0, pad), (0, 0))) if pad else emb
    _, z = jax.lax.scan(
        lambda _, e: (None, chunk_z(e)), None,
        emb_p.reshape(-1, vocab_chunk, emb_p.shape[1]),
    )
    return z.reshape(-1, b)[:v]


# ---------------------------------------------------------------------------
# Phase 2 — ELL SpMM against Z
# ---------------------------------------------------------------------------
def phase2_spmm(resident: DocSet, z: Array) -> Array:
    """D1[i, j] = Σ_p weights[i,p] · Z[ids[i,p], j].  Returns (n, B) f32.

    Pure-jnp path: a gather + einsum.  Padding slots have weight 0, so the
    gathered (possibly garbage) Z rows contribute nothing.
    """
    zg = z[resident.ids]  # (n, h, B)
    return jnp.einsum("nh,nhb->nb", resident.weights, zg)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------
def lc_rwmd_one_sided(
    resident: DocSet,
    queries: DocSet,
    emb: Array,
    *,
    bf16_matmul: bool = False,
    vocab_chunk: int | None = None,
    use_kernel: bool = False,
    interpret: bool = False,
) -> Array:
    """Cost of moving each resident doc INTO each query doc: (n, B) f32.

    (Each resident word ships its mass to the nearest query word.)
    """
    if use_kernel:
        from repro.kernels import ops as kops

        z = kops.lc_rwmd_phase1(
            emb, queries.ids, queries.weights,
            bf16_matmul=bf16_matmul, interpret=interpret,
        )
        return kops.spmm_ell(resident.ids, resident.weights, z, interpret=interpret)
    z = phase1_z(
        emb, queries.ids, queries.weights,
        bf16_matmul=bf16_matmul, vocab_chunk=vocab_chunk,
    )
    return phase2_spmm(resident, z)


def lc_rwmd_streaming(
    resident: DocSet,
    queries: DocSet,
    emb: Array,
    *,
    vocab_chunk: int = 512,
    fuse: str = "jnp",
    bf16_matmul: bool = False,
    block_n: int = 8,
    block_v: int = 256,
    interpret: bool = False,
) -> Array:
    """One-sided LC-RWMD with the fused phase-1→phase-2 streaming engine.

    Semantically identical to :func:`lc_rwmd_one_sided`, but Z is never
    materialized at full (v, B): the vocabulary is scanned in ``vocab_chunk``
    rows, each chunk's Z tile produced and immediately consumed into the
    running D accumulator (peak intermediate = (vocab_chunk, B)).

    ``fuse`` selects the backend: "jnp" (pure-jnp streaming scan, the CPU
    reference), "scan" (phase-1 kernel + blocked SpMM kernel per chunk), or
    "kernel" (single fused pallas_call per chunk; Z lives only in VMEM).
    """
    from repro.kernels import ops as kops

    return kops.lc_rwmd_fused(
        emb, queries.ids, queries.weights, resident.ids, resident.weights,
        vocab_chunk=vocab_chunk, fuse=fuse, block_n=block_n, block_v=block_v,
        bf16_matmul=bf16_matmul, interpret=interpret,
    )


def lc_rwmd_symmetric(
    set1: DocSet,
    set2: DocSet,
    emb: Array,
    *,
    bf16_matmul: bool = False,
    vocab_chunk: int | None = None,
    use_kernel: bool = False,
    interpret: bool = False,
) -> Array:
    """Tight symmetric LC-RWMD: D = max(D1, D2ᵀ), shape (n1, n2) f32."""
    kw = dict(
        bf16_matmul=bf16_matmul, vocab_chunk=vocab_chunk,
        use_kernel=use_kernel, interpret=interpret,
    )
    d1 = lc_rwmd_one_sided(set1, set2, emb, **kw)  # (n1, n2)
    d2 = lc_rwmd_one_sided(set2, set1, emb, **kw)  # (n2, n1)
    return jnp.maximum(d1, d2.T)


def restrict_vocab(resident: DocSet, emb: Array) -> tuple[DocSet, Array, Array]:
    """The paper's v_e optimization: drop vocab rows unused by the resident set.

    Returns (remapped resident DocSet, restricted emb (v_e, m), old→new map).
    Host-side preprocessing (jit-incompatible shapes).
    """
    ids = np.asarray(resident.ids)
    w = np.asarray(resident.weights)
    used = np.unique(ids[w > 0])
    old_to_new = np.full(emb.shape[0], -1, dtype=np.int32)
    old_to_new[used] = np.arange(len(used), dtype=np.int32)
    new_ids = np.where(w > 0, old_to_new[ids], 0)
    sub = DocSet(ids=jnp.asarray(new_ids), weights=resident.weights)
    return sub, jnp.asarray(np.asarray(emb)[used]), jnp.asarray(old_to_new)


# ---------------------------------------------------------------------------
# Segmented corpora — incremental ingest / delete without full rebuild
# ---------------------------------------------------------------------------
def _topk_stream_from_z(
    seg: SegmentTensors,
    z1: Array,          # (v_e, B) phase-1 output over seg.emb_r
    t_q: Array,         # (B*h2, m) pre-gathered query targets
    q_w: Array,         # (B, h2)
    row_valid: Array | None,   # (n_rows,) bool live mask, or None
    *,
    k: int,
    symmetric: bool,
    row_block: int,
    bf16_matmul: bool,
):
    """The streaming top-k fold over ONE segment's rows (post-phase-1).

    Every segment kernel runs this one fold: the same slab schedule and the
    same lexicographic (distance, doc id) tie order, so equal distances
    rank by ascending doc id in every segment and after
    :func:`repro.core.topk.merge_topk`.  ``row_valid=None`` and an all-True
    mask are exactly equal (a ``where`` with a true mask is the identity).
    """
    from repro.core.topk import StreamingTopK, TopK

    b, h2 = q_w.shape
    n, h1 = seg.r_ids.shape
    kk = min(k, n)
    if not symmetric:
        # The one-sided fold IS the shared phase-2 streaming reduction.
        from repro.kernels.ops import streaming_phase2_topk

        d, i = streaming_phase2_topk(
            seg.r_ids, seg.r_w, z1, kk, row_block=row_block,
            row_valid=row_valid)
        return TopK(d, i)

    r = min(row_block, n)
    nb = -(-n // r)
    n_pad = nb * r
    ids_b = _pad_rows(seg.r_ids, n_pad)
    w_b = _pad_rows(seg.r_w, n_pad)
    v_r_b = _pad_rows(seg.valid_r.reshape(n, h1), n_pad)
    live_b = (None if row_valid is None
              else _pad_rows(row_valid, n_pad).reshape(nb, r))
    xs = [ids_b.reshape(nb, r, h1), w_b.reshape(nb, r, h1),
          jnp.arange(nb, dtype=jnp.int32) * r,
          v_r_b.reshape(nb, r * h1), live_b]
    stk = StreamingTopK(kk)

    def body(carry, xs):
        ids_blk, w_blk, lo, vr_blk, live_blk = xs
        d1 = phase2_spmm(DocSet(ids=ids_blk, weights=w_blk), z1)
        # The block's resident word embeddings: at every valid slot the
        # same row of the full table; padding slots are masked below.
        tr_blk = _word_rows(seg.emb_r, ids_blk.reshape(-1))  # (R·h1, m)
        sq = sq_dists(t_q, tr_blk, bf16_matmul=bf16_matmul)
        sq = jnp.where(vr_blk[None, :], sq, _INF)
        z2 = safe_sqrt(jnp.min(sq.reshape(b * h2, r, h1), axis=2))
        d2 = jnp.einsum("bh,bhr->br", q_w, z2.reshape(b, h2, r))
        d_blk = jnp.maximum(d1.T, d2)                       # (B, R)
        row = lo + jnp.arange(r, dtype=jnp.int32)
        d_blk = jnp.where((row < n)[None, :], d_blk, _INF)
        if live_blk is not None:
            d_blk = jnp.where(live_blk[None, :], d_blk, _INF)
        idx = jnp.broadcast_to(row[None, :], (b, r))
        return stk.update(carry, d_blk, idx), None

    carry, _ = jax.lax.scan(body, stk.init(b), xs)
    return carry


@functools.partial(
    jax.jit,
    static_argnames=("k", "symmetric", "row_block", "bf16_matmul",
                     "vocab_chunk"),
)
def _segment_topk(
    seg: SegmentTensors, t_q: Array, q_w: Array, row_valid: Array,
    *, k: int, symmetric: bool, row_block: int, bf16_matmul: bool,
    vocab_chunk: int | None,
):
    """Streaming top-k of ONE segment: TopK (B, min(k, n_rows)), local ids.

    Module-level jit over a :class:`SegmentTensors` pytree: every segment of
    the same shape — across appends, corpora, and engines — shares one trace.
    """
    b = q_w.shape[0]
    z1 = phase1_z_from_t(
        seg.emb_r, t_q, (q_w > 0).reshape(-1), b,
        bf16_matmul=bf16_matmul, vocab_chunk=vocab_chunk,
    )
    return _topk_stream_from_z(
        seg, z1, t_q, q_w, row_valid,
        k=k, symmetric=symmetric, row_block=row_block,
        bf16_matmul=bf16_matmul,
    )


@functools.partial(
    jax.jit, static_argnames=("symmetric", "bf16_matmul", "vocab_chunk"),
)
def _segment_dense(
    seg: SegmentTensors, t_q: Array, q_w: Array, row_valid: Array,
    *, symmetric: bool, bf16_matmul: bool, vocab_chunk: int | None,
):
    """Materialized one-sided / symmetric distances of ONE segment: (n_rows, B).

    Tombstoned (and padding) rows come out +inf.
    """
    b, h2 = q_w.shape
    n, h1 = seg.r_ids.shape
    valid_q = (q_w > 0).reshape(-1)
    z1 = phase1_z_from_t(
        seg.emb_r, t_q, valid_q, b,
        bf16_matmul=bf16_matmul, vocab_chunk=vocab_chunk,
    )
    d = phase2_spmm(DocSet(ids=seg.r_ids, weights=seg.r_w), z1)
    if symmetric:
        t_r = _word_rows(seg.emb_r, seg.r_ids.reshape(-1))  # this call only
        sq = sq_dists(t_q, t_r, bf16_matmul=bf16_matmul)
        sq = jnp.where(seg.valid_r[None, :], sq, _INF)
        z2 = safe_sqrt(jnp.min(sq.reshape(b * h2, n, h1), axis=2))
        d2 = jnp.einsum("bh,bhn->bn", q_w, z2.reshape(b, h2, n))
        d = jnp.maximum(d, d2.T)
    return jnp.where(row_valid[:, None], d, _INF)


@functools.partial(
    jax.jit, static_argnames=("b", "bf16_matmul", "vocab_chunk"),
)
def _segment_phase1(
    emb_r: Array, t_q: Array, valid_q: Array,
    *, b: int, bf16_matmul: bool, vocab_chunk: int | None,
) -> Array:
    return phase1_z_from_t(
        emb_r, t_q, valid_q, b,
        bf16_matmul=bf16_matmul, vocab_chunk=vocab_chunk,
    )


@functools.partial(jax.jit, static_argnums=(0, 1))
def _segmented_rerank(
    k: int, sink_items: tuple, emb: Array, ids1: Array, w1: Array,
    t_q: Array, q_w: Array, cand_idx: Array, cand_valid: Array,
):
    """Sinkhorn re-rank of gathered candidates with a validity mask.

    ``ids1``/``w1`` (B·budget, h1) are the candidates' histograms, query
    major; their word embeddings are gathered from ``emb`` here, inside the
    scope ``rerank_cost`` with the cost.  Invalid candidates (empty top-k
    slots, tombstoned docs) and the candidates of padding queries solve as
    empty pairs and get +inf WMD, so they can never displace a live
    candidate.  Returns the (B, k) :class:`~repro.core.topk.TopK` and the
    batch's :func:`repro.core.wmd.sinkhorn_work` sums.
    """
    from repro.core import topk as topk_lib
    from repro.core.wmd import candidate_sinkhorn, sinkhorn_work

    with jax.named_scope("rerank"):
        with jax.named_scope("rerank_cost"):
            t1 = emb[ids1]                               # (B·budget, h1, m)
        solved = cand_valid & jnp.any(q_w > 0, axis=1)[:, None]
        w1 = jnp.where(solved.reshape(-1, 1), w1, 0.0)
        res = candidate_sinkhorn(t1, w1, t_q, q_w, **dict(sink_items))
        vals = jnp.where(cand_valid, res.cost.reshape(cand_valid.shape), _INF)
        work = sinkhorn_work(
            res, w1, jnp.repeat(q_w, cand_valid.shape[1], axis=0))
        return topk_lib.topk_from_candidates(vals, cand_idx, k), work


#: Rows per chunk of the centroid pass: at h1 = 128 and m = 300 a chunk's
#: gathered embeddings are ≈ 0.6 GB.
_CENT_ROWS = 4096


@functools.partial(jax.jit, static_argnames=("chunk",))
def _centroids(emb: Array, ids: Array, w: Array, *, chunk: int) -> Array:
    """WCD centroids (n_rows, m): Σ_h w · emb[ids], ``chunk`` rows at a
    time, so that the (n_rows·h1, m) gather never exists."""
    n, h1 = ids.shape
    nc = -(-n // chunk)
    ids = _pad_rows(ids, nc * chunk).reshape(nc, chunk, h1)
    w = _pad_rows(w, nc * chunk).reshape(nc, chunk, h1)
    with jax.named_scope("centroids"):
        c = jax.lax.map(lambda a: jnp.einsum(
            "nh,nhm->nm", a[1], _word_rows(emb, a[0])), (ids, w))
    return c.reshape(nc * chunk, -1)[:n]


class EngineSegment:
    """One immutable unit of a :class:`SegmentedEngine`.

    Owns a contiguous global doc-id range ``[offset, offset + n_real)`` and
    its precomputed state: the per-segment ``v_e`` vocab restriction (queries
    still gather from the FULL table, so out-of-resident-vocab query words
    stay exact), the remapped ELL resident matrix, and each row's WCD
    centroid (built in :data:`_CENT_ROWS`-row chunks).  Per document it
    holds O(h1 + m) device bytes, never an (h1, m) block of embeddings.
    Rows may be padded to ``n_pad`` (zero-weight, non-live) and the
    restricted vocab to a ``vocab_pad`` multiple so repeated delta shapes
    hit the same jit trace.
    """

    def __init__(
        self,
        docs: DocSet,
        emb_full: Array,
        *,
        offset: int,
        n_pad: int | None = None,
        vocab_pad: int | None = None,
    ):
        n_real = docs.n_docs
        if n_pad is not None and n_pad > n_real:
            docs = DocSet(
                ids=_pad_rows(docs.ids, n_pad),
                weights=_pad_rows(docs.weights, n_pad),
            )
        self.docs = docs
        self.offset = int(offset)
        self.n_real = int(n_real)
        sub, emb_r, old_to_new = restrict_vocab(docs, emb_full)
        if vocab_pad:
            pad = (-emb_r.shape[0]) % int(vocab_pad)
            if pad:
                emb_r = jnp.pad(emb_r, ((0, pad), (0, 0)))
        self.old_to_new = old_to_new
        self.tensors = SegmentTensors(
            emb_r=emb_r,
            r_ids=sub.ids,
            r_w=sub.weights,
            valid_r=(docs.weights > 0).reshape(-1),
            # From the full table: the same rows as emb_r[r_ids], and one
            # compiled pass for every segment of a shape, whatever its v_e.
            cent=_centroids(emb_full, docs.ids, docs.weights,
                            chunk=min(_CENT_ROWS, max(1, docs.n_docs))),
        )

    @property
    def n_rows(self) -> int:
        """Row count including trace-reuse padding (≥ ``n_real``)."""
        return self.docs.n_docs

    @property
    def nbytes(self) -> int:
        """Device bytes held by this segment (the eviction accounting
        unit): its tensors and its histograms, each array counted once."""
        return _distinct_nbytes(
            (*self.tensors, self.docs.ids, self.docs.weights))


class SegmentedEngine:
    """Serve-time LC-RWMD over a base + delta segment list.

    Built from a resident :class:`DocSet` and the embedding table (one base
    segment; ``resident=None`` starts empty), the engine hoists what does not
    depend on the query batch out of the query path: the ``v_e`` vocabulary
    restriction, the resident-side word-embedding gather the symmetric
    bound's swapped direction needs, and float32 casts.  Its query surface is
    ``one_sided`` / ``symmetric`` (materialized (n_docs, B)), the streaming
    ``topk`` / ``topk_streaming`` / ``symmetric_topk_streaming`` (resident
    rows fold into a (B, k) carry in ``row_block`` slabs, so the (n, B)
    matrix never forms), ``rerank_topk``, and the corpus-analytics tile
    entry points.  The distributed serve step
    (:func:`repro.distributed.lcrwmd_dist.build_serve_step`) places the same
    segments on a mesh.  Its corpus lifecycle:

      * :meth:`append` builds ONE small :class:`EngineSegment` over the new
        docs (its own v_e restriction + gathers) — cost O(delta), not
        O(corpus); returns the assigned global doc ids.
      * :meth:`delete` flips per-row tombstone bits.  The mask is a *traced*
        argument of every segment kernel, so deletes never recompile; dead
        docs are +inf in every distance path and can never appear in a top-k.
      * :meth:`compact` merges all segments into one base segment, re-running
        the vocab restriction with tombstoned rows zero-weighted (their words
        leave v_e).  Global doc ids are STABLE across compaction — dead rows
        keep their slots as empty histograms.

    Queries run phase-1/phase-2 per segment through module-level jitted
    kernels and fold per-segment (distance, global id) top-k candidates with
    :func:`repro.core.topk.merge_topk`.  Every segment runs the same fold
    (:func:`_topk_stream_from_z`) with the lexicographic (distance, doc id)
    tie order: equal distances rank by ascending global doc id.
    """

    def __init__(
        self,
        resident: DocSet | None,
        emb: Array,
        *,
        bf16_matmul: bool = False,
        vocab_chunk: int | None = None,
        row_block: int = 128,
        delta_pad: int | None = None,
        vocab_pad: int | None = None,
    ):
        self.emb_full = jnp.asarray(emb, dtype=jnp.float32)
        self.bf16_matmul = bf16_matmul
        self.vocab_chunk = vocab_chunk
        self.row_block = max(1, int(row_block))
        self.delta_pad = delta_pad
        self.vocab_pad = vocab_pad
        self.segments: list[EngineSegment] = []
        self._live: list[np.ndarray] = []
        self.version = 0          # bumped on every append/delete/compact
        self._resident_cache: DocSet | None = None
        self._resident_version = -1
        self._live_dev: tuple[Array, ...] | None = None
        self._global_live_dev: Array | None = None
        if resident is not None and resident.n_docs:
            self._append_segment(resident, n_pad=None, live=None)

    # -- lifecycle --------------------------------------------------------
    def _append_segment(self, docs: DocSet, *, n_pad, live) -> EngineSegment:
        seg = EngineSegment(
            docs, self.emb_full, offset=self.n_docs,
            n_pad=n_pad, vocab_pad=self.vocab_pad,
        )
        if live is None:
            live = np.zeros(seg.n_rows, dtype=bool)
            live[:seg.n_real] = True
        self.segments.append(seg)
        self._live.append(live)
        self._bump()
        return seg

    def _bump(self) -> None:
        self.version += 1
        self._resident_cache = None
        self._live_dev = None
        self._global_live_dev = None

    def append(self, docs: DocSet) -> np.ndarray:
        """Ingest ``docs`` as a new delta segment; returns their global ids."""
        if docs.n_docs == 0:
            return np.empty(0, dtype=np.int64)
        if self.segments:
            h = self.h_max
            if docs.h_max > h:
                raise ValueError(
                    f"appended docs have h_max={docs.h_max} > engine "
                    f"h_max={h}; re-pad the corpus or rebuild")
            if docs.h_max < h:
                pad = h - docs.h_max
                docs = DocSet(
                    ids=jnp.pad(docs.ids, ((0, 0), (0, pad))),
                    weights=jnp.pad(docs.weights, ((0, 0), (0, pad))),
                )
        n_pad = None
        if self.delta_pad and self.segments:
            n_pad = -(-docs.n_docs // int(self.delta_pad)) * int(self.delta_pad)
        lo = self.n_docs
        self._append_segment(docs, n_pad=n_pad, live=None)
        return np.arange(lo, lo + docs.n_docs, dtype=np.int64)

    def delete(self, doc_ids) -> int:
        """Tombstone global doc ids; returns how many were newly deleted."""
        n = self.n_docs
        removed = 0
        for g in np.atleast_1d(np.asarray(doc_ids, dtype=np.int64)):
            if g < 0 or g >= n:
                raise IndexError(f"doc id {int(g)} out of range [0, {n})")
            for seg, live in zip(self.segments, self._live):
                if seg.offset <= g < seg.offset + seg.n_real:
                    local = int(g - seg.offset)
                    removed += int(live[local])
                    live[local] = False
                    break
        if removed:
            self._bump()
        return removed

    def compact(self) -> None:
        """Merge every segment into one base segment (stable global ids).

        Re-runs the v_e vocab restriction over the merged corpus with
        tombstoned rows zero-weighted, so deleted docs' words leave the
        restricted vocabulary and delta fragmentation disappears; dead rows
        keep their (now empty) global id slots.
        """
        if not self.segments:
            return
        base = self.segments[0]
        if (len(self.segments) == 1 and base.n_rows == base.n_real
                and bool(self._live[0].all())):
            return   # already one dense, fully-live base segment
        res = self.resident
        live = self.live_mask()
        w = np.where(live[:, None], np.asarray(res.weights), 0.0)
        merged = DocSet(ids=jnp.asarray(np.asarray(res.ids)),
                        weights=jnp.asarray(w.astype(np.float32)))
        seg = EngineSegment(merged, self.emb_full, offset=0,
                            vocab_pad=self.vocab_pad)
        self.segments = [seg]
        self._live = [live.copy()]
        self._bump()

    # -- corpus views ------------------------------------------------------
    @property
    def n_docs(self) -> int:
        """Size of the global doc-id space (INCLUDING tombstoned docs)."""
        return sum(s.n_real for s in self.segments)

    @property
    def n_live(self) -> int:
        """Docs that are actually queryable (excludes tombstones)."""
        return int(sum(l[:s.n_real].sum()
                       for s, l in zip(self.segments, self._live)))

    @property
    def n_segments(self) -> int:
        return len(self.segments)

    @property
    def h_max(self) -> int:
        return self.segments[0].docs.h_max if self.segments else 0

    @property
    def nbytes(self) -> int:
        """Total device bytes of all segments (LRU eviction accounting)."""
        return sum(seg.nbytes for seg in self.segments)

    @property
    def emb_restricted(self) -> Array:
        """Base segment's restricted embedding (compat view for analytics)."""
        return self.segments[0].tensors.emb_r

    @property
    def resident(self) -> DocSet:
        """The merged corpus as one DocSet, global doc id == row (cached).

        Tombstoned docs keep their rows (their weights are untouched here;
        use :meth:`live_mask` to filter) so global ids stay stable.
        """
        if self._resident_cache is None or self._resident_version != self.version:
            segs = self.segments
            if len(segs) == 1 and segs[0].n_rows == segs[0].n_real:
                # One unpadded segment: its own device histograms, no copy.
                ids, w = segs[0].docs.ids, segs[0].docs.weights
            else:
                ids = np.concatenate(
                    [np.asarray(s.docs.ids)[:s.n_real] for s in segs])
                w = np.concatenate(
                    [np.asarray(s.docs.weights)[:s.n_real] for s in segs])
            self._resident_cache = DocSet(ids=jnp.asarray(ids),
                                          weights=jnp.asarray(w))
            self._resident_version = self.version
        return self._resident_cache

    def live_mask(self) -> np.ndarray:
        """(n_docs,) host bool mask: True where the doc is not tombstoned."""
        if not self.segments:
            return np.zeros(0, dtype=bool)
        return np.concatenate(
            [l[:s.n_real] for s, l in zip(self.segments, self._live)])

    def live_mask_device(self) -> Array:
        """(n_docs,) device live mask (cached per corpus version)."""
        if self._global_live_dev is None:
            self._global_live_dev = jnp.asarray(self.live_mask())
        return self._global_live_dev

    def _seg_live_device(self) -> tuple[Array, ...]:
        if self._live_dev is None:
            self._live_dev = tuple(jnp.asarray(l) for l in self._live)
        return self._live_dev

    # -- query surface -----------------------------------------------------
    def _gather_queries_flat(self, q_ids: Array) -> Array:
        return self.emb_full[jnp.asarray(q_ids).reshape(-1)]

    def gather_queries(self, q_ids: Array) -> Array:
        b, h = q_ids.shape
        with jax.named_scope("gather_queries"):
            return self._gather_queries_flat(q_ids).reshape(b, h, -1)

    def _fold_topk(self, queries: DocSet, k: int, symmetric: bool):
        from repro.core.topk import TopK, merge_topk

        t_q = self._gather_queries_flat(queries.ids)
        parts = []
        for seg, live in zip(self.segments, self._seg_live_device()):
            tk = _segment_topk(
                seg.tensors, t_q, queries.weights, live,
                k=min(k, seg.n_rows), symmetric=symmetric,
                row_block=max(1, min(self.row_block, seg.n_rows)),
                bf16_matmul=self.bf16_matmul, vocab_chunk=self.vocab_chunk,
            )
            idx = jnp.where(tk.indices >= 0, tk.indices + seg.offset,
                            tk.indices)
            parts.append(TopK(tk.dists, idx))
        kk = min(k, self.n_docs)
        if len(parts) == 1 and parts[0].dists.shape[-1] == kk:
            return parts[0]
        return merge_topk(parts, kk)

    def topk(self, queries: DocSet, k: int):
        """Top-k smallest symmetric LC-RWMD over all live docs: TopK (B, k)."""
        return self._fold_topk(queries, k, symmetric=True)

    def topk_streaming(self, queries: DocSet, k: int):
        """Top-k smallest one-sided LC-RWMD (D1), segment-folded."""
        return self._fold_topk(queries, k, symmetric=False)

    def symmetric_topk_streaming(self, queries: DocSet, k: int):
        """Top-k smallest symmetric bound, segment-folded."""
        return self._fold_topk(queries, k, symmetric=True)

    def _dense(self, queries: DocSet, *, symmetric: bool) -> Array:
        t_q = self._gather_queries_flat(queries.ids)
        outs = [
            _segment_dense(
                seg.tensors, t_q, queries.weights, live,
                symmetric=symmetric, bf16_matmul=self.bf16_matmul,
                vocab_chunk=self.vocab_chunk,
            )[:seg.n_real]
            for seg, live in zip(self.segments, self._seg_live_device())
        ]
        return jnp.concatenate(outs, axis=0)

    def one_sided(self, queries: DocSet) -> Array:
        """D1 (n_docs, B); tombstoned rows are +inf."""
        return self._dense(queries, symmetric=False)

    def symmetric(self, queries: DocSet) -> Array:
        """max(D1, D2ᵀ) (n_docs, B); tombstoned rows are +inf."""
        return self._dense(queries, symmetric=True)

    def rerank_topk(self, queries: DocSet, cand_indices: Array, k: int,
                    *, sinkhorn_kw: dict | None = None,
                    with_work: bool = False):
        """Batched Sinkhorn-WMD re-rank of global candidate doc ids.

        Args:
          queries: DocSet (B, h) — the batch the candidates were selected
            for.
          cand_indices: (B, budget) int32 global doc ids (e.g. a top-budget
            from :meth:`topk_streaming`); empty (-1) and tombstoned
            candidates are masked to +inf WMD.
          k: results per query (k ≤ budget).  JIT-STATIC.
          sinkhorn_kw: solver knobs (eps, eps_scaling, max_iters, …) for
            :func:`repro.core.wmd.candidate_sinkhorn`.  JIT-STATIC — hashed
            as a sorted items tuple, so pass plain scalars.

        Returns a :class:`~repro.core.topk.TopK` of (B, k): ascending WMD +
        global doc ids.  All B·budget pairs are solved in ONE batched
        log-domain Sinkhorn call.  The candidates' histograms are gathered
        eagerly at fixed (B, budget) shapes, so corpus churn (which changes
        ``n_docs``) never re-traces the jitted solve.  ``with_work`` also
        returns the solve's per-batch sums
        (:func:`repro.core.wmd.sinkhorn_work`, a device array).
        """
        items = tuple(sorted((sinkhorn_kw or {}).items()))
        res = self.resident
        n = self.n_docs
        cand = jnp.asarray(cand_indices)
        safe = jnp.clip(cand.reshape(-1), 0, n - 1)
        cand_valid = (cand >= 0) & jnp.take(
            self.live_mask_device(), jnp.clip(cand, 0, n - 1))
        tk, work = _segmented_rerank(
            k, items, self.emb_full, res.ids[safe], res.weights[safe],
            self.gather_queries(queries.ids), queries.weights, cand,
            cand_valid,
        )
        return (tk, work) if with_work else tk

    # -- corpus-analytics (query-tile) entry points ------------------------
    def resident_tile(self, idx: Array) -> DocSet:
        """Resident docs named by global ids ``idx`` as a query DocSet.

        Out-of-range AND tombstoned entries behave as empty histograms.
        """
        res = self.resident
        n = self.n_docs
        idx = jnp.asarray(idx, jnp.int32)
        safe = jnp.clip(idx, 0, n - 1)
        inb = ((idx >= 0) & (idx < n)
               & jnp.take(self.live_mask_device(), safe))
        return DocSet(
            ids=res.ids[safe],
            weights=jnp.where(inb[:, None], res.weights[safe], 0.0),
        )

    def symmetric_resident(self, idx: Array) -> Array:
        """Symmetric bound (n_docs, B) whose queries are resident docs ``idx``."""
        return self.symmetric(self.resident_tile(idx))

    def phase1_resident(self, idx: Array) -> tuple:
        """Per-segment phase-1 Z tiles for resident-doc queries ``idx``.

        Returns a TUPLE of (v_e_s, B) arrays — one per segment — which is the
        ``z`` handle :meth:`one_sided_rows` (and the pair scheduler) expects.
        """
        tile = self.resident_tile(idx)
        t_q = self._gather_queries_flat(tile.ids)
        valid = (tile.weights > 0).reshape(-1)
        return tuple(
            _segment_phase1(
                seg.tensors.emb_r, t_q, valid, b=tile.n_docs,
                bf16_matmul=self.bf16_matmul, vocab_chunk=self.vocab_chunk,
            )
            for seg in self.segments
        )

    def _one_sided_rows_impl(self, row_idx: Array, z: tuple) -> Array:
        total = None
        for seg, zz in zip(self.segments, z):
            local = row_idx - seg.offset
            owner = (local >= 0) & (local < seg.n_real)
            safe = jnp.clip(local, 0, seg.n_rows - 1)
            sub = DocSet(
                ids=seg.tensors.r_ids[safe],
                weights=jnp.where(owner[:, None],
                                  seg.tensors.r_w[safe], 0.0),
            )
            d = jnp.where(owner[:, None], phase2_spmm(sub, zz), 0.0)
            total = d if total is None else total + d
        return total

    def one_sided_rows(self, row_idx: Array, z: tuple) -> Array:
        """Phase-2 restricted to global rows ``row_idx``: (R, B).

        ``z`` is a :meth:`phase1_resident` tuple; each row's contribution
        comes from the one segment that owns it (others contribute 0).
        Tombstoned rows still produce values here — schedulers mask by the
        engine's :meth:`live_mask_device`.
        """
        return self._one_sided_rows_impl(jnp.asarray(row_idx, jnp.int32), z)
