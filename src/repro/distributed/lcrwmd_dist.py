"""Distributed LC-RWMD over a (pod, data, model) TPU mesh.

Sharding (the paper's "replicate the smaller set, distribute the larger",
Sec. V/VI, expressed as mesh axes):

  resident docs (ids, weights)  -> rows over (pod, data)    [the big set]
  embedding table E             -> rows (vocab) over model  [v_e x m]
  query batch                   -> replicated

Collective schedule per query batch (B queries, k results):
  1. query-embedding gather:  psum over model of masked local rows — O(B·h·m)
  2. phase 1 (fused kernel):  NO collective — Z stays vocab-sharded
  3. phase 2 partial SpMM:    psum over model — O(n_local·B)
  4. top-k merge:             all_gather over (pod, data) of (B, k) pairs

Total cross-pod traffic is only step 4's k-sized payload — "the associated
communication cost is typically marginal" (paper Sec. V) — which is what
makes the `pod` axis safe for DCN-speed links.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import numpy as np

from repro.compat import shard_map as compat_shard_map
from repro.obs import Observability
from repro.obs import sentinel as _sentinel
from repro.core.distances import dists, safe_sqrt, sq_dists
from repro.core.lc_rwmd import SegmentedEngine
from repro.core.topk import (
    StreamingTopK,
    TopK,
    crossshard_topk,
    distributed_topk,
    topk_smallest_cols,
)
from repro.data.docs import DocSet
from repro.launch.mesh import DATA_AXIS, MODEL_AXIS, POD_AXIS

Array = jax.Array
_INF = 3.4e38

# The serve step's named scopes (phase1, phase2, ...) live only in its ops'
# metadata, which a profile reads.  JAX's persistent compile cache keys
# leave metadata out by default, so a program loaded from the cache would
# carry the metadata of whatever build compiled it first: key on it too.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

# Module-level cache of compiled serve-step callables.  Historically every
# `build_serve_step` call created fresh `@jax.jit` objects, so each engine
# swap / adaptive-budget rebuild / tenant switch re-traced from scratch even
# when the mesh, shapes, and static config were identical.  Keying the step
# on (mesh, static config) — with ALL resident state passed as traced
# arguments (including the live-row mask) — lets same-shaped corpora share
# one trace: multi-tenant engine caches hit this instead of XLA.
_STEP_CACHE: dict = {}

#: Spans of serve callables built without an Observability bundle: on the
#: profiler's clock only (a disabled registry observes nothing).
_NO_OBS = Observability(metrics_enabled=False, tracing_enabled=False)

#: Count of engine-less `build_serve_step` calls (sentinel key suffix —
#: each such build mints fresh jit objects that cannot share traces).
_ENGINELESS_BUILDS = 0


def _mesh_key(mesh) -> tuple:
    return (
        tuple(mesh.axis_names),
        tuple(int(mesh.shape[a]) for a in mesh.axis_names),
        tuple((d.platform, int(d.id)) for d in mesh.devices.flat),
    )


def _slab_geometry(
    n_rows: int, n_batch_shards: int, row_block: int, psum_batch: int,
) -> tuple[int, int, int]:
    """(rb, g, row_mult): slab rows, slabs per collective, row pad multiple.

    ``g`` is the psum batching factor: the streaming scan evaluates ``g``
    consecutive ``rb``-row slabs per scan step and reduces them with ONE
    model-axis psum of the stacked (g·rb, B) partial — one collective (and
    one carry fold) per ``g`` slabs instead of per slab, at a peak-memory
    cost of (g·rb, B) instead of (rb, B).  Results are exactly equal: psum
    is elementwise and the streaming top-k fold is grouping-invariant.
    """
    rows_per_shard = max(1, -(-n_rows // n_batch_shards))
    rb = max(1, min(row_block, rows_per_shard))
    g = max(1, min(psum_batch, -(-rows_per_shard // rb)))
    return rb, g, n_batch_shards * rb * g


def _pad_rows_mult(x, mult: int, value=0):
    """Zero-pad the leading axis of ``x`` up to a multiple of ``mult``."""
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=value)


class ServeResult(NamedTuple):
    topk: TopK        # (B, k) replicated: global doc ids + distances
    d_local: Array | None  # (n_local, B) shard distances: engine-less step
    #                        only (the engine-backed steps stream, None)
    pruned_exact: Array | None = None  # (B,) bool, rerank_wmd engine path:
    #                        True → WMD top-k provably equals the full-corpus
    #                        WMD top-k (candidate RWMD bound beat the cutoff)
    tier: int = 0     # QualityTier the batch was served at (python int,
    #                        stamped outside jit; 0 = full configured cascade)
    rerank_work: Array | None = None  # (5,) f32 sums of the batch's WMD
    #                        rerank (core.wmd.SINKHORN_WORK); segmented and
    #                        routed steps with rerank_wmd only
    phase1_cols: Array | None = None  # (2,) int32: the columns phase 1
    #                        computed and the query words among them;
    #                        segmented step only


def _batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in (POD_AXIS, DATA_AXIS))


def _z_from_t(
    emb_local: Array, t_q: Array, q_valid: Array, *, bf16_matmul: bool = False
) -> Array:
    """Phase 1 against a local vocab shard: Z (v_local, B), distances."""
    v_l, m = emb_local.shape
    b, h, _ = t_q.shape
    with jax.named_scope("phase1"):
        sq = sq_dists(emb_local, t_q.reshape(b * h, m),
                      bf16_matmul=bf16_matmul)
        sq = jnp.where(q_valid.reshape(-1)[None, :] > 0, sq, _INF)
        return safe_sqrt(jnp.min(sq.reshape(v_l, b, h), axis=2))


def _gather_query_embeddings(
    q_ids: Array, emb_local: Array, v_local: int
) -> Array:
    """E[q_ids] with E row-sharded over `model`: mask-gather-psum. (B,h,m)."""
    mi = jax.lax.axis_index(MODEL_AXIS)
    lo = (mi * v_local).astype(jnp.int32)
    rel = q_ids - lo
    inb = (rel >= 0) & (rel < v_local)
    local = emb_local[jnp.clip(rel, 0, v_local - 1)]  # (B, h, m)
    local = jnp.where(inb[..., None], local, 0.0)
    return jax.lax.psum(local, MODEL_AXIS)


def _phase2_partial(
    r_ids: Array, r_w: Array, z_local: Array, v_local: int
) -> Array:
    """Masked local ELL-SpMM contribution; full D after psum over model."""
    with jax.named_scope("phase2"):
        mi = jax.lax.axis_index(MODEL_AXIS)
        lo = (mi * v_local).astype(jnp.int32)
        rel = r_ids - lo
        inb = (rel >= 0) & (rel < v_local)
        zg = z_local[jnp.clip(rel, 0, v_local - 1)]  # (n_l, h, B)
        w = r_w * inb.astype(r_w.dtype)
        return jnp.einsum("nh,nhb->nb", w, zg)


def _phase2_slot_sum(
    r_ids: Array, r_w: Array, z_local: Array, v_local: int
) -> Array:
    """:func:`_phase2_partial` summed over slots in slot order.

    A row's sum is then the same whatever number of trailing zero-weight
    slots the slab's width class gives it (each adds an exact 0), which a
    reduction that XLA associates by the slab's shape would not promise.
    """
    with jax.named_scope("phase2"):
        mi = jax.lax.axis_index(MODEL_AXIS)
        lo = (mi * v_local).astype(jnp.int32)
        rel = r_ids.T - lo                               # (h, n_l): slot-major
        inb = (rel >= 0) & (rel < v_local)
        zg = z_local[jnp.clip(rel, 0, v_local - 1)]      # (h, n_l, B)
        w = jax.lax.broadcast_in_dim(
            r_w.T * inb.astype(r_w.dtype), zg.shape, (0, 1))
        # lax ops, not jnp's: each jnp operator call traces a jaxpr of its own.
        acc = jax.lax.mul(w[0], zg[0])
        for j in range(1, zg.shape[0]):
            acc = jax.lax.add(acc, jax.lax.mul(w[j], zg[j]))
        return acc


def _slab_branch(r_ids, r_w, z_local, *, wd: int, v_span: int) -> Array:
    """One width class of a slab: phase 2 over its first ``wd`` slots."""
    return _phase2_slot_sum(r_ids[:, :wd], r_w[:, :wd], z_local, v_span)


def _width_classes(h1: int) -> tuple[int, ...]:
    """The slot widths a phase-2 slab may gather: multiples of 8, and h1."""
    return tuple(sorted({min(h1, w) for w in range(8, h1 + 8, 8)}))


#: Queries per phase-1 group of the segmented step: 16 queries of a width
#: class of 8 slots fill the MXU's 128 lanes.
_QUERY_GROUP = 16


def _query_groups(q_valid: Array) -> tuple[Array, Array, tuple[int, ...]]:
    """Length order of a query batch for phase 1 (traced).

    Returns ``order`` (the batch stable-sorted by filled length, longest
    first: the last slot with ``q_valid > 0``, plus 1, as
    :func:`_length_order`; padding queries last), ``cls`` (per group of
    :data:`_QUERY_GROUP` ordered queries: the index in ``widths`` of the
    narrowest width that holds its longest query) and ``widths`` (0, then
    :func:`_width_classes`).

    Every group's GEMM then has N = 16·w columns, a multiple of 128, and
    a query's distances do not depend on its batch-mates.  A batch that
    16 does not divide stays in its own order, in one group at the full
    width.
    """
    b, h = q_valid.shape
    widths = (0,) + _width_classes(h)
    if b % _QUERY_GROUP:
        return (jnp.arange(b), jnp.full((1,), len(widths) - 1, jnp.int32),
                widths)
    slot = jnp.arange(1, h + 1, dtype=jnp.int32)
    length = jnp.max(jnp.where(q_valid > 0, slot, 0), axis=1)
    order = jnp.argsort(-length, stable=True)
    longest = length[order][::_QUERY_GROUP]
    cls = jnp.sum(longest[:, None] > jnp.asarray(widths)[None, :], axis=1)
    return order, cls.astype(jnp.int32), widths


def _z_grouped(
    emb_local: Array, t_q: Array, q_valid: Array, cls: Array,
    widths: tuple[int, ...], *, bf16_matmul: bool = False
) -> Array:
    """Phase 1 over length-ordered query groups: Z (v_local, B).

    Group g (``len(cls)`` equal groups of the batch's queries) computes
    its distances over the first ``widths[cls[g]]`` slots only, in a
    ``lax.switch`` over the width classes, and writes its columns of Z in
    place.  A query's column is :func:`_z_from_t`'s: the slots a class
    leaves out are padding, which the min there skips too; a width-0 group
    holds only padding queries and writes their value.
    """
    v_l, m = emb_local.shape
    b, h, _ = t_q.shape
    n_groups = cls.shape[0]
    size = b // n_groups
    e2 = jnp.sum(emb_local * emb_local, axis=-1)   # once, not per group

    def branch(w):
        def run(t, valid):
            if w == 0:
                return jnp.full((v_l, size), safe_sqrt(jnp.float32(_INF)))
            sq = sq_dists(emb_local, t[:, :w].reshape(size * w, m),
                          bf16_matmul=bf16_matmul, a2=e2)
            sq = jnp.where(valid[:, :w].reshape(-1)[None, :] > 0, sq, _INF)
            return safe_sqrt(jnp.min(sq.reshape(v_l, size, w), axis=2))
        return run

    branches = [branch(w) for w in widths]

    def body(z, xs):
        t, valid, c, col = xs
        z_g = jax.lax.switch(c, branches, t, valid)
        return jax.lax.dynamic_update_slice(z, z_g, (0, col)), None

    z, _ = jax.lax.scan(
        body, jnp.zeros((v_l, b), jnp.float32),
        (t_q.reshape(n_groups, size, h, m), q_valid.reshape(n_groups, size, h),
         cls, jnp.arange(n_groups, dtype=jnp.int32) * size))
    return z


def _length_order(
    r_w: np.ndarray, n_shards: int, blk: int, widths: tuple[int, ...]
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Length-ordered placement of one segment's padded rows (host).

    Each data shard's contiguous row range is stable-sorted by filled
    length (the last nonzero weight's slot, plus 1).  Returns ``perm``
    (the source row of each placed row), ``row_map`` (int32, each placed
    row's shard-local source row) and ``cls`` (int32, per ``blk``-row
    slab: the index in ``widths`` of the narrowest width that holds its
    longest row).
    """
    n, h1 = r_w.shape
    nz = r_w != 0
    length = np.where(nz.any(axis=1), h1 - np.argmax(nz[:, ::-1], axis=1), 0)
    by_shard = length.reshape(n_shards, n // n_shards)
    order = np.argsort(by_shard, axis=1, kind="stable")
    perm = (order + (np.arange(n_shards) * (n // n_shards))[:, None]).ravel()
    slab_len = np.take_along_axis(by_shard, order, 1).reshape(-1, blk).max(1)
    cls = np.searchsorted(np.asarray(widths), slab_len)
    return perm, order.ravel().astype(np.int32), cls.astype(np.int32)


def build_serve_step(
    mesh: jax.sharding.Mesh,
    *,
    k: int,
    refine: bool = False,
    bf16_matmul: bool = True,
    phase1_full_mesh: bool = True,
    engine=None,
    rerank_wmd: bool = False,
    rerank_budget: int | None = None,
    wmd_kw: dict | None = None,
    self_exclude: bool = False,
    row_block: int = 128,
    psum_batch: int = 8,
    obs=None,
    index=None,
):
    """Returns the LC-RWMD serve callable for ``mesh``.

    Three step builders stand behind it, picked by what is passed:

      * no ``engine`` — the engine-less MATERIALIZED step,
        ``serve(resident, queries, emb) -> ServeResult`` (jit'd).  Phase 1
        runs over the whole table, every shard forms its (n_local, B)
        distance block (returned as ``ServeResult.d_local``) and
        :func:`~repro.core.topk.distributed_topk` merges the shards.  It is
        the reference the mesh steps are tested against and the step the
        dry-run cells lower from abstract shapes.
      * a :class:`repro.core.lc_rwmd.SegmentedEngine` — the SEGMENTED step
        (the served path), ``serve(queries) -> ServeResult``.  The shard
        kernel scans base + delta segments back-to-back: each segment
        phase-1s against its OWN restricted vocab (query words still gather
        from the FULL table, so out-of-resident-vocab words stay exact),
        streams phase-2 slabs with its tombstone mask and self-exclusion
        applied locally, and folds (distance, global id) candidates into
        one shared carry before the single cross-shard top-k collective.
        Segment tensors are placed on the mesh once and re-placed whenever
        ``engine.version`` changes, so ingest/delete/compact are admissible
        between batches: deletes only change the traced live-mask VALUES
        (no re-trace), and appends re-trace only for segment-shape
        signatures not yet seen (pad deltas via ``delta_pad``/``vocab_pad``
        to pin the shapes).
      * a SegmentedEngine and an ``index`` — the ROUTED step (below).

    Any other ``engine`` raises ``TypeError``.

    Shapes: ``queries`` (B, h) DocSet (keep B fixed — the compiled step is
    shape-specialized; the query servers pad to a fixed ``max_batch``) →
    ``ServeResult.topk`` (B, k) replicated TopK of global doc ids, and
    ``pruned_exact`` (B,) bool (rerank path only).  Everything passed HERE
    — ``k``, ``refine``, ``rerank_wmd``/``rerank_budget``/``wmd_kw``,
    ``row_block``/``psum_batch``, ``self_exclude``, ``bf16_matmul``,
    ``phase1_full_mesh`` — is baked into the compiled step; changing any of
    them means building a new serve step (the servers rebuild on adaptive-
    budget changes and count it in ``stats["budget_rebuilds"]``).

    The engine-backed steps STREAM candidate selection: resident rows are
    scanned in ``row_block`` slabs, each slab's psum'd distances fold into a
    :class:`~repro.core.topk.StreamingTopK` carry, and the cross-shard top-k
    collective consumes the (B, k)-sized per-shard partials — the
    (n_shard, B) distance block is never materialized and
    ``ServeResult.d_local`` is None.  Ties break as in the materialized
    top-k, by (distance, doc id).  ``psum_batch`` batches the per-slab
    model-axis psums: ``psum_batch`` consecutive ``row_block`` slabs are
    reduced with ONE collective of the stacked (psum_batch·row_block, B)
    partials per scan step (exactly equal results: psum is elementwise and
    the top-k fold is grouping-invariant).

    ``phase1_full_mesh`` (§Perf lcrwmd iteration 1 — beyond-paper): the
    paper's GPU mapping replicates phase 1 across the resident-data shards
    (every data row computes the same vocab-slice Z -> useful-FLOP ratio
    1/16 on a 16x16 mesh).  Instead, shard the vocabulary MODEL-major over
    the FULL mesh (each of the 256 devices scans v/256 rows), then all-gather
    Z along `data` — the gather is O(v/model * B) floats (~29 MB) against a
    16x phase-1 FLOP reduction.  ``False`` keeps the paper-faithful mapping
    (the recorded baseline).

    ``refine=True`` adds the symmetric-bound refinement: the swapped-direction
    RWMD term is evaluated with the fused pairwise kernel ONLY on the top-k
    candidates (k per query, not n), then the max-bound re-ranks them.  This
    recovers the paper's tighter max(D1, D2ᵀ) bound at serving time without
    the full second LC pass (which only pays off in all-pairs mode).

    ``rerank_wmd=True`` finishes the pruning cascade in the serve step: the
    LC-RWMD (optionally refined) top-``rerank_budget`` (default 2k) become
    candidates for ONE batched Sinkhorn-WMD call (``wmd_kw`` forwarded), and
    the final top-k is by WMD.  With an engine this routes through
    :meth:`repro.core.lc_rwmd.SegmentedEngine.rerank_topk`; without one,
    through the jnp batched solver.

    ``self_exclude=True`` (engine path only) is the corpus-analytics mode:
    the returned callable becomes ``serve(queries, query_ids)`` where
    ``query_ids`` (B,) are the queries' GLOBAL resident-doc ids, and each
    query's own resident row is masked to +inf inside the streaming
    accumulator before any candidate leaves the shard — tiles of the corpus
    can stream through the serve step as query batches without self-matches
    eating a candidate slot (see
    :func:`repro.workloads.corpus_distance.corpus_self_topk_distributed`).

    The ENGINE-path callable additionally accepts a keyword-only
    ``tier=`` (:class:`repro.core.pipeline.QualityTier`): the serving
    plane's degradation ladder.  Tier 0 is the full configured cascade;
    tier 1 serves the LC-RWMD candidates directly (the SAME compiled
    phase-1/2 step — shedding the refine/rerank stages never re-traces);
    tier 2 answers from a WCD centroid shortlist via a module-level
    ``(k, self_exclude)``-keyed jit cache.  ``ServeResult.tier`` records
    the tier a batch was served at.

    ``obs``: an optional :class:`repro.obs.Observability` bundle.  The
    segmented and routed callables open one serve span per host stage
    (``refresh``, ``gather_queries``, ``route``, ``step_launch``,
    ``refine_launch``, ``rerank_launch``: ``serve.<stage>`` on the
    profiler's clock, ``serving_stage_seconds{stage=...}`` in its
    registry); without one the spans reach the profiler only.  On the
    device, the compiled programs carry the named scopes ``phase1``,
    ``phase2``, ``topk_fold``, ``crossshard_topk``, ``refine`` and
    ``rerank`` (with ``rerank_cost`` and ``sinkhorn`` inside it) in their
    op metadata.  A batch that reranks returns its solve's sums in
    ``ServeResult.rerank_work``, and a batch of the segmented step its
    phase-1 column counts in ``ServeResult.phase1_cols``; the serving core
    adds both to its registry at collect.

    ``index``: a :class:`repro.index.ClusterIndex` over the ``engine``.
    The serve step then ROUTES each batch: the index's host
    routing stage picks the batch's probed cells (top-p by centroid
    distance, triangle-bound pruned), and the compiled step scans ONLY
    those cells through ``index.probe_cap`` jit-static probe slots —
    phase 1 runs per probed cell against that cell's restricted vocab and
    phase 2 streams only routed rows, so per-query work drops from O(n)
    to O(n/cells · p).  Batches with different probed-cell SETS reuse one
    trace (slots are sliced dynamically from the stacked cell tensors);
    only a cell-shape change (index rebuild/growth) compiles a new step.
    """
    batch_axes = _batch_axes(mesh)
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    n_model = mesh.shape[MODEL_AXIS]
    # With reranking on, the mesh top-k stage widens to the candidate budget
    # and the batched WMD stage narrows back down to k.  The budget can
    # never exceed the resident corpus (pipeline clamps its analogue the
    # same way); the engine path clamps here, the engine-less path clamps
    # at trace time when the resident shapes are known.
    kc = (rerank_budget or 2 * k) if rerank_wmd else k
    kc = max(kc, k)  # the rerank stage must keep at least k candidates
    if engine is not None:
        if not isinstance(engine, SegmentedEngine):
            raise TypeError(
                "build_serve_step takes a SegmentedEngine, got "
                f"{type(engine).__name__}")
        kc = min(kc, engine.n_docs)
        kw = dict(
            k=k, kc=kc, refine=refine, bf16_matmul=bf16_matmul,
            phase1_full_mesh=phase1_full_mesh, batch_axes=batch_axes,
            n_batch_shards=n_batch_shards, n_model=n_model,
            rerank_wmd=rerank_wmd, wmd_kw=wmd_kw, self_exclude=self_exclude,
            row_block=row_block, psum_batch=psum_batch, obs=obs,
        )
        if index is not None:
            return _build_routed_serve_step(mesh, engine, index, **kw)
        return _build_segmented_serve_step(mesh, engine, **kw)
    if index is not None:
        raise ValueError(
            "a ClusterIndex serve step needs a SegmentedEngine "
            "(the index's cells are engine segments)")
    if self_exclude:
        raise ValueError("self_exclude requires an engine-backed serve step")

    def kernel(r_ids, r_w, q_ids, q_w, emb_local):
        v_local = emb_local.shape[0]
        n_local = r_ids.shape[0]
        if phase1_full_mesh:
            # emb rows sharded (MODEL major, then batch axes): shard
            # (m, d0, d1...) owns rows [(m*D + d)*v_local, ...).
            didx = jnp.int32(0)
            for a in batch_axes:
                didx = didx * mesh.shape[a] + jax.lax.axis_index(a)
            mi = jax.lax.axis_index(MODEL_AXIS)
            lo = (mi * n_batch_shards + didx) * v_local
            # query embedding gather: mask + psum over the whole mesh
            rel = q_ids - lo
            inb = (rel >= 0) & (rel < v_local)
            t_q = emb_local[jnp.clip(rel, 0, v_local - 1)]
            t_q = jnp.where(inb[..., None], t_q, 0.0)
            for a in batch_axes:
                t_q = jax.lax.psum(t_q, a)
            t_q = jax.lax.psum(t_q, MODEL_AXIS)
            # phase 1 on this device's v/256 slice, then re-assemble the
            # model-axis slice by gathering along the batch axes.
            z_local = _z_from_t(emb_local, t_q, q_w, bf16_matmul=bf16_matmul)
            for a in reversed(batch_axes):
                z_local = jax.lax.all_gather(z_local, a, axis=0, tiled=True)
            # z_local now covers rows [mi*v/model, (mi+1)*v/model)
            partial = _phase2_partial(r_ids, r_w, z_local,
                                      v_local * n_batch_shards)
        else:
            t_q = _gather_query_embeddings(q_ids, emb_local, v_local)
            z_local = _z_from_t(emb_local, t_q, q_w, bf16_matmul=bf16_matmul)
            partial = _phase2_partial(r_ids, r_w, z_local, v_local)
        d_local = jax.lax.psum(partial, MODEL_AXIS)  # (n_l, B)

        # Global row offset of this shard: row-major over (pod, data).
        offset = jnp.int32(0)
        for a in batch_axes:
            offset = offset * mesh.shape[a] + jax.lax.axis_index(a)
        offset = offset * n_local

        tk = distributed_topk(
            d_local, min(kc, n_local * n_batch_shards),
            axis_names=batch_axes, shard_offset=offset)
        return (tk.dists, tk.indices), d_local

    rspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None)
    if phase1_full_mesh:
        espec = P((MODEL_AXIS,) + batch_axes, None)
    else:
        espec = P(MODEL_AXIS, None)
    qspec = P(None, None)

    shmapped = compat_shard_map(
        kernel,
        mesh=mesh,
        in_specs=(rspec, rspec, qspec, qspec, espec),
        out_specs=((P(None, None), P(None, None)), rspec),
    )

    @jax.jit
    def serve(resident: DocSet, queries: DocSet, emb: Array) -> ServeResult:
        (tk_d, tk_i), d_local = shmapped(
            resident.ids, resident.weights, queries.ids, queries.weights, emb
        )
        tk = TopK(tk_d, tk_i)
        if refine:
            tk = _symmetric_refine(resident, queries, emb, tk)
        if rerank_wmd:
            tk = _wmd_rerank(resident, queries, emb, tk, k, wmd_kw)
        return ServeResult(topk=tk, d_local=d_local)

    # Engine-less builds mint a FRESH jit object each call, so traces can
    # never be shared across builds — meter each under its own key (a
    # re-trace of a seen signature within one build is still the bug).
    global _ENGINELESS_BUILDS
    _ENGINELESS_BUILDS += 1
    return _sentinel.wrap(
        f"serve_step.engineless#{_ENGINELESS_BUILDS}", serve)


def _segmented_step(
    mesh, *, kc, rbs, gs, widths, self_exclude, bf16_matmul,
    phase1_full_mesh,
):
    """Compiled segmented shard step (one per segment-shape signature).

    The kernel scans every segment back-to-back INSIDE the shard: each
    segment phase-1s against its own restricted vocab shard, streams its
    phase-2 super-slabs with the traced live mask and per-segment
    self-exclusion applied locally, and folds (distance, GLOBAL id)
    candidates into one shared :class:`~repro.core.topk.StreamingTopK`
    carry — then ONE cross-shard top-k collective merges the per-shard
    partials, exactly like the monolithic step.  ``rbs``/``gs`` are the
    per-segment slab geometries (their length fixes the segment count);
    everything else — tensors, live masks, id offsets — is traced, so
    deletes and same-shape delta appends reuse the cached trace.

    Rows arrive length-ordered (:func:`_length_order`): each slab carries
    a traced class, an index into its segment's ``widths``, and gathers
    only that many ELL slots; each row's traced ``row_map`` entry names
    its shard-local source row, so global ids are unchanged.  Queries are
    length-ordered the same way inside the step (:func:`_query_groups`):
    phase 1 computes each group's columns of Z over its width class only
    (:func:`_z_grouped`), and the answers return in the batch's order.  One
    program serves every draw of lengths.

    The step returns the batch's top-k and a (2,) int32: the columns phase
    1 computed (Σ over groups of its queries × its width) and the query
    words among them.
    """
    key = ("seg", _mesh_key(mesh), kc, rbs, gs, widths, self_exclude,
           bf16_matmul, phase1_full_mesh)
    step = _STEP_CACHE.get(key)
    if step is not None:
        return step

    n_segments = len(rbs)
    batch_axes = _batch_axes(mesh)
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]

    def _z_and_span(t_q, q_valid, q_cls, q_widths, emb_local):
        v_local = emb_local.shape[0]
        with jax.named_scope("phase1"):
            z_local = _z_grouped(emb_local, t_q, q_valid, q_cls, q_widths,
                                 bf16_matmul=bf16_matmul)
        if phase1_full_mesh:
            for a in reversed(batch_axes):
                z_local = jax.lax.all_gather(z_local, a, axis=0, tiled=True)
            return z_local, v_local * n_batch_shards
        return z_local, v_local

    def _shard_offset(n_local):
        offset = jnp.int32(0)
        for a in batch_axes:
            offset = offset * mesh.shape[a] + jax.lax.axis_index(a)
        return offset * n_local

    def kernel(seg_rids, seg_rw, seg_live, seg_map, seg_cls, seg_offs, t_q,
               q_valid, q_gid, seg_embs):
        b = t_q.shape[0]
        total_local = sum(r.shape[0] for r in seg_rids)
        stk = StreamingTopK(min(kc, total_local))
        carry = stk.init(b)
        # Z's columns, the self-exclusion ids and the top-k rows follow the
        # queries' length order until the answers are put back.
        with jax.named_scope("phase1"):
            order, q_cls, q_widths = _query_groups(q_valid)
            t_q, q_valid, q_gid = t_q[order], q_valid[order], q_gid[order]
        for s in range(n_segments):
            rids, rw, live = seg_rids[s], seg_rw[s], seg_live[s]
            n_local, h1 = rids.shape
            z_local, v_span = _z_and_span(t_q, q_valid, q_cls, q_widths,
                                          seg_embs[s])
            # Rows of this shard's slice of segment s own the global ids
            # seg_offs[s] + shard_off + row_map — offsets are traced, so
            # compaction's offset rewrite reuses the cached trace too.
            row0 = seg_offs[s] + _shard_offset(n_local)
            blk = rbs[s] * gs[s]
            nb = n_local // blk
            ids_b = rids.reshape(nb, blk, h1)
            w_b = rw.reshape(nb, blk, h1)
            live_b = live.reshape(nb, blk)
            map_b = seg_map[s].reshape(nb, blk)
            branches = [functools.partial(_slab_branch, wd=wd, v_span=v_span)
                        for wd in widths[s]]

            def body(carry, xs, z_local=z_local, row0=row0,
                     branches=branches):
                ids_blk, w_blk, live_blk, map_blk, cls = xs
                partial = jax.lax.switch(cls, branches, ids_blk, w_blk,
                                         z_local)
                d_blk = jax.lax.psum(partial, MODEL_AXIS)    # (g·rb, B)
                row = row0 + map_blk                         # GLOBAL ids
                d_blk = jnp.where(live_blk[:, None], d_blk, _INF)
                if self_exclude:
                    d_blk = jnp.where(
                        row[:, None] == q_gid[None, :], _INF, d_blk)
                return stk.update_cols(carry, d_blk, row), None

            # phase2 scopes the slab loop (psum, masks); the carry fold
            # inside it is `topk_fold`.
            with jax.named_scope("phase2"):
                carry, _ = jax.lax.scan(
                    body, carry, (ids_b, w_b, live_b, map_b, seg_cls[s]))
        tk = crossshard_topk(carry, kc, axis_names=batch_axes)
        back = jnp.argsort(order)
        cols = jnp.stack([
            (b // q_cls.shape[0]) * jnp.asarray(q_widths)[q_cls].sum(),
            jnp.sum(q_valid > 0)]).astype(jnp.int32)
        return tk.dists[back], tk.indices[back], cols

    rspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None)
    lspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0])
    espec = (P((MODEL_AXIS,) + batch_axes, None) if phase1_full_mesh
             else P(MODEL_AXIS, None))
    seg = lambda spec: tuple(spec for _ in range(n_segments))  # noqa: E731
    shmapped = compat_shard_map(
        kernel, mesh=mesh,
        in_specs=(seg(rspec), seg(rspec), seg(lspec), seg(lspec), seg(lspec),
                  P(None), P(None, None, None), P(None, None), P(None),
                  seg(espec)),
        out_specs=(P(None, None), P(None, None), P(None)),
    )

    @jax.jit
    def step(seg_rids, seg_rw, seg_live, seg_map, seg_cls, seg_offs, t_q,
             q_valid, q_gid, seg_embs):
        tk_d, tk_i, cols = shmapped(seg_rids, seg_rw, seg_live, seg_map,
                                    seg_cls, seg_offs, t_q, q_valid, q_gid,
                                    seg_embs)
        return TopK(tk_d, tk_i), cols

    step = _sentinel.wrap(
        f"step_cache.seg[kc={kc},segs={n_segments}]", step)
    _STEP_CACHE[key] = step
    return step


def _build_segmented_serve_step(
    mesh, engine, *, k, kc, refine, bf16_matmul, phase1_full_mesh,
    batch_axes, n_batch_shards, n_model, rerank_wmd=False, wmd_kw=None,
    self_exclude=False, row_block=128, psum_batch=8, obs=None,
):
    """Serve step over a :class:`~repro.core.lc_rwmd.SegmentedEngine`.

    Per-segment resident tensors (ids, weights, live masks, restricted
    embedding shards) are placed on the mesh lazily and re-placed whenever
    ``engine.version`` changes, so the SAME callable keeps serving across
    ingest/delete/compact — no rebuild, and no re-trace unless the segment
    shape signature is new.  A segment's rows are placed length-ordered
    within each data shard (:func:`_length_order`) once; a delete only
    re-permutes its live mask.  Tier-2 centroids are refreshed on the same
    version check with tombstoned rows pushed to an unreachable distance.

    Each batch the step serves adds the ELL slots phase 2 gathers and the
    slots among them that hold a word to the registry counters
    ``serving_phase2_gathered_slots_total`` and
    ``serving_phase2_filled_slots_total``, and returns phase 1's column
    counts in ``ServeResult.phase1_cols``.
    """
    from jax.sharding import NamedSharding

    rspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None)
    lspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0])
    espec = (P((MODEL_AXIS,) + batch_axes, None) if phase1_full_mesh
             else P(MODEL_AXIS, None))
    emb_shards = n_model * (n_batch_shards if phase1_full_mesh else 1)
    state: dict = {"version": None}
    placed: dict = {}   # id(segment) -> (segment, its placement)
    obs = obs if obs is not None else _NO_OBS
    gathered_slots = obs.metrics.counter(
        "serving_phase2_gathered_slots_total",
        "ELL slots gathered by phase 2 of the segmented serve step")
    filled_slots = obs.metrics.counter(
        "serving_phase2_filled_slots_total",
        "ELL slots gathered by phase 2 that hold a word")

    def _place(seg) -> dict:
        rb_s, g_s, row_mult = _slab_geometry(
            seg.n_rows, n_batch_shards, row_block, psum_batch)
        r_ids = np.asarray(_pad_rows_mult(seg.tensors.r_ids, row_mult))
        r_w = np.asarray(_pad_rows_mult(seg.tensors.r_w, row_mult))
        widths = _width_classes(r_w.shape[1])
        perm, row_map, cls = _length_order(
            r_w, n_batch_shards, rb_s * g_s, widths)
        put = lambda x, spec: jax.device_put(  # noqa: E731
            x, NamedSharding(mesh, spec))
        return dict(
            rb=rb_s, g=g_s, widths=widths, perm=perm,
            rids=put(r_ids[perm], rspec), rw=put(r_w[perm], rspec),
            map=put(row_map, lspec), cls=put(cls, lspec),
            emb=put(_pad_rows_mult(seg.tensors.emb_r, emb_shards), espec),
            gathered=rb_s * g_s * int(np.take(widths, cls).sum()),
            filled=int(np.count_nonzero(r_w)))

    def _refresh():
        if state["version"] == engine.version:
            return
        if not engine.segments:
            raise ValueError("segmented serve step needs a non-empty engine")
        now = {}
        for seg in engine.segments:
            now[id(seg)] = placed.get(id(seg)) or (seg, _place(seg))
        placed.clear()
        placed.update(now)
        segs = [now[id(seg)][1] for seg in engine.segments]
        live = []
        for seg, lv, p in zip(engine.segments, engine._live, segs):
            lv_pad = np.zeros(p["perm"].size, dtype=bool)
            lv_pad[:seg.n_rows] = lv
            live.append(jax.device_put(lv_pad[p["perm"]],
                                       NamedSharding(mesh, lspec)))
        state["step"] = _segmented_step(
            mesh, kc=kc, rbs=tuple(p["rb"] for p in segs),
            gs=tuple(p["g"] for p in segs),
            widths=tuple(p["widths"] for p in segs),
            self_exclude=self_exclude, bf16_matmul=bf16_matmul,
            phase1_full_mesh=phase1_full_mesh)
        for f in ("rids", "rw", "map", "cls", "emb"):
            state[f] = tuple(p[f] for p in segs)
        state["live"] = tuple(live)
        state["offs"] = jnp.asarray([seg.offset for seg in engine.segments],
                                    dtype=jnp.int32)
        state["slots"] = (sum(p["gathered"] for p in segs),
                          sum(p["filled"] for p in segs))
        # Tier-2 WCD shortlist: per-segment centroids from the pre-gathered
        # resident embeddings; tombstoned rows sit at distance ~1e18 so the
        # shortlist can never surface them.
        cents = []
        for seg in engine.segments:
            n_rows, h1 = seg.docs.ids.shape
            c = jnp.einsum("nh,nhm->nm", seg.docs.weights,
                           seg.tensors.t_r.reshape(n_rows, h1, -1))
            cents.append(c[:seg.n_real])
        cent = jnp.concatenate(cents, axis=0)
        state["cent"] = jnp.where(
            engine.live_mask_device()[:, None], cent, 1e18)
        state["version"] = engine.version

    def serve(queries: DocSet, query_ids=None, *, tier: int = 0) -> ServeResult:
        """Tiered segmented serve (same ladder as the monolithic step)."""
        if self_exclude and query_ids is None:
            raise ValueError("self_exclude serve step needs query_ids (B,)")
        tier = int(tier)
        with obs.span("refresh"):
            _refresh()
        with obs.span("gather_queries"):
            t_q = engine.gather_queries(queries.ids)
            q_valid = (queries.weights > 0).astype(jnp.float32)
            q_gid = (jnp.asarray(query_ids, jnp.int32) if self_exclude
                     else jnp.full((queries.n_docs,), -1, jnp.int32))
        if tier >= 2:  # QualityTier.WCD
            tk = _wcd_topk_step(k, self_exclude, state["cent"], t_q,
                                queries.weights, q_gid)
            return ServeResult(topk=tk, d_local=None, pruned_exact=None,
                               tier=tier)
        with obs.span("step_launch"):
            tk, cols = state["step"](
                state["rids"], state["rw"], state["live"], state["map"],
                state["cls"], state["offs"], t_q, q_valid, q_gid,
                state["emb"])
        gathered_slots.inc(state["slots"][0])
        filled_slots.inc(state["slots"][1])
        if tier >= 1:  # QualityTier.LCRWMD: candidates ARE the answer
            return ServeResult(
                topk=TopK(tk.dists[:, :k], tk.indices[:, :k]),
                d_local=None, pruned_exact=None, tier=tier, phase1_cols=cols)
        with obs.span("refine_launch"):
            cand_max_rwmd = tk.dists[:, -1]
            if refine:
                tk = _symmetric_refine(
                    engine.resident, queries, engine.emb_full, tk)
        exact = work = None
        if rerank_wmd:
            with obs.span("rerank_launch"):
                tk, work = engine.rerank_topk(queries, tk.indices, k,
                                              sinkhorn_kw=wmd_kw,
                                              with_work=True)
                exact = cand_max_rwmd >= tk.dists[:, -1]
                if kc >= engine.n_live:  # candidates cover every live doc
                    exact = jnp.ones_like(exact)
        return ServeResult(topk=tk, d_local=None, pruned_exact=exact,
                           rerank_work=work, phase1_cols=cols)

    return serve


def _routed_step(
    mesh, *, kc, p_max, rb, g, n_cells, self_exclude, bf16_matmul,
    phase1_full_mesh,
):
    """Compiled cluster-routed shard step (one per cell-shape signature).

    Cell tensors arrive STACKED on a leading (replicated) cell axis —
    (n_cells, rows_pad, ...) with rows sharded over the batch axes — and
    the batch's probed cells arrive as ``p_max`` jit-STATIC probe slots:
    ``probed`` (p_max,) int32 cell ids (-1 pads) plus ``q_route``
    (B, p_max) per-query slot masks.  Each slot dynamic-slices its cell
    out of the stack, phase-1s against that cell's restricted vocab
    shard, and streams phase-2 slabs masked by live ∧ routed into ONE
    shared :class:`~repro.core.topk.StreamingTopK` carry keyed by the
    cell's per-row GLOBAL ids — then one cross-shard top-k merges shard
    partials, exactly like the segmented step.  Because slot→cell binding
    is a traced VALUE, batches probing different cell subsets reuse this
    trace; pad slots are fully masked (their sliced compute is dead
    work bounded by p_max, never a correctness hazard).

    Structurally, phase-2 contractions only ever see (slab, ...) operands
    from the p_max sliced cells — nothing in the jaxpr touches all
    n_cells · rows_pad rows at once (tests/test_index.py asserts this),
    which is the O(n) → O(n/cells · p) claim in compiled form.
    """
    key = ("routed", _mesh_key(mesh), kc, p_max, rb, g, n_cells,
           self_exclude, bf16_matmul, phase1_full_mesh)
    step = _STEP_CACHE.get(key)
    if step is not None:
        return step

    batch_axes = _batch_axes(mesh)
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]

    def _z_and_span(t_q, q_valid, emb_local):
        v_local = emb_local.shape[0]
        z_local = _z_from_t(emb_local, t_q, q_valid, bf16_matmul=bf16_matmul)
        if phase1_full_mesh:
            for a in reversed(batch_axes):
                z_local = jax.lax.all_gather(z_local, a, axis=0, tiled=True)
            return z_local, v_local * n_batch_shards
        return z_local, v_local

    def kernel(c_rids, c_rw, c_live, c_gids, probed, q_route, t_q, q_valid,
               q_gid, c_embs):
        b = t_q.shape[0]
        rows_local = c_rids.shape[1]
        h1 = c_rids.shape[2]
        stk = StreamingTopK(min(kc, p_max * rows_local))
        carry = stk.init(b)
        blk = rb * g
        nb = rows_local // blk
        for s in range(p_max):
            # Pad slots (probed = -1) clip to cell 0; their q_route column
            # is all-False, so every row they contribute is masked +inf.
            cid = jnp.clip(probed[s], 0, n_cells - 1)
            rids = jax.lax.dynamic_index_in_dim(c_rids, cid, 0, False)
            rw = jax.lax.dynamic_index_in_dim(c_rw, cid, 0, False)
            live = jax.lax.dynamic_index_in_dim(c_live, cid, 0, False)
            gids = jax.lax.dynamic_index_in_dim(c_gids, cid, 0, False)
            emb_c = jax.lax.dynamic_index_in_dim(c_embs, cid, 0, False)
            z_local, v_span = _z_and_span(t_q, q_valid, emb_c)
            ids_b = rids.reshape(nb, blk, h1)
            w_b = rw.reshape(nb, blk, h1)
            live_b = live.reshape(nb, blk)
            gid_b = gids.reshape(nb, blk)
            route_s = q_route[:, s]  # (B,) this slot's per-query mask

            def body(carry, xs, z_local=z_local, v_span=v_span,
                     route_s=route_s):
                ids_blk, w_blk, live_blk, gid_blk = xs
                partial = _phase2_partial(ids_blk, w_blk, z_local, v_span)
                d_blk = jax.lax.psum(partial, MODEL_AXIS)    # (g·rb, B)
                d_blk = jnp.where(
                    live_blk[:, None] & route_s[None, :], d_blk, _INF)
                if self_exclude:
                    d_blk = jnp.where(
                        gid_blk[:, None] == q_gid[None, :], _INF, d_blk)
                return stk.update_cols(carry, d_blk, gid_blk), None

            with jax.named_scope("phase2"):
                carry, _ = jax.lax.scan(
                    body, carry, (ids_b, w_b, live_b, gid_b))
        tk = crossshard_topk(carry, kc, axis_names=batch_axes)
        return tk.dists, tk.indices

    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    rspec = P(None, bspec, None)       # (cells, rows, h) — rows sharded
    lspec = P(None, bspec)             # (cells, rows)
    espec = (P(None, (MODEL_AXIS,) + batch_axes, None) if phase1_full_mesh
             else P(None, MODEL_AXIS, None))
    shmapped = compat_shard_map(
        kernel, mesh=mesh,
        in_specs=(rspec, rspec, lspec, lspec, P(None), P(None, None),
                  P(None, None, None), P(None, None), P(None), espec),
        out_specs=(P(None, None), P(None, None)),
    )

    @jax.jit
    def step(c_rids, c_rw, c_live, c_gids, probed, q_route, t_q, q_valid,
             q_gid, c_embs):
        tk_d, tk_i = shmapped(c_rids, c_rw, c_live, c_gids, probed,
                              q_route, t_q, q_valid, q_gid, c_embs)
        return TopK(tk_d, tk_i)

    step = _sentinel.wrap(
        f"step_cache.routed[kc={kc},p={p_max},cells={n_cells}]", step)
    _STEP_CACHE[key] = step
    return step


def _build_routed_serve_step(
    mesh, engine, index, *, k, kc, refine, bf16_matmul, phase1_full_mesh,
    batch_axes, n_batch_shards, n_model, rerank_wmd=False, wmd_kw=None,
    self_exclude=False, row_block=128, psum_batch=8, obs=None,
):
    """Serve step routed through a :class:`repro.index.ClusterIndex`.

    Host side per batch: ``index.route`` picks each query's top-p cells
    (triangle-bound pruned), the batch's probed-cell UNION is packed into
    ``index.probe_cap`` static slots (overflow drops the least-requested
    cells, counted in ``index_probe_overflow_total``), and the compiled
    step scans only those slots.  Device state — per-cell row tensors,
    global-id maps, live masks, restricted embedding shards — is stacked
    on a leading cell axis and re-placed whenever ``engine.version`` OR
    ``index.version`` moves, so ingest (``index.add``), deletes (no index
    call at all), and compaction (``index.rebuild``) are all admissible
    between batches; only a cell-SHAPE change re-traces.
    """
    from jax.sharding import NamedSharding

    p_max = index.probe_cap
    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    emb_shards = n_model * (n_batch_shards if phase1_full_mesh else 1)
    state: dict = {"key": None}
    obs = obs if obs is not None else _NO_OBS

    def _refresh():
        index._sync_live()  # raises if engine grew without index.add
        key = (engine.version, index.version)
        if state["key"] == key:
            return
        rows_cap = index.rows_cap
        rb, g, row_mult = _slab_geometry(
            rows_cap, n_batch_shards, row_block, psum_batch)
        live = engine.live_mask()
        rids, rw, lv, gids, embs = [], [], [], [], []
        for cell in index.cells:
            t = cell.segment.tensors
            rids.append(_pad_rows_mult(t.r_ids, row_mult))
            rw.append(_pad_rows_mult(t.r_w, row_mult))
            lv_np = np.zeros(rids[-1].shape[0], dtype=bool)
            if len(cell.members):
                lv_np[:len(cell.members)] = live[cell.members]
            lv.append(jnp.asarray(lv_np))
            gids.append(_pad_rows_mult(cell.gids_dev, row_mult, value=-1))
            embs.append(_pad_rows_mult(t.emb_r, emb_shards))
        rows_pad = int(rids[0].shape[0])
        if p_max * rows_pad < k:
            raise ValueError(
                f"probe_cap={p_max} × padded cell rows {rows_pad} cannot "
                f"yield k={k} candidates; raise probe_cap or num_cells")
        state["kc"] = min(kc, p_max * rows_pad)
        state["rids"] = jax.device_put(
            jnp.stack(rids), NamedSharding(mesh, P(None, bspec, None)))
        state["rw"] = jax.device_put(
            jnp.stack(rw), NamedSharding(mesh, P(None, bspec, None)))
        state["live"] = jax.device_put(
            jnp.stack(lv), NamedSharding(mesh, P(None, bspec)))
        state["gids"] = jax.device_put(
            jnp.stack(gids), NamedSharding(mesh, P(None, bspec)))
        state["embs"] = jax.device_put(
            jnp.stack(embs), NamedSharding(
                mesh, P(None, (MODEL_AXIS,) + batch_axes, None)
                if phase1_full_mesh else P(None, MODEL_AXIS, None)))
        step = _routed_step(
            mesh, kc=state["kc"], p_max=p_max, rb=rb, g=g,
            n_cells=index.num_cells, self_exclude=self_exclude,
            bf16_matmul=bf16_matmul, phase1_full_mesh=phase1_full_mesh)
        # A DIFFERENT compiled step (first build, or a cell-shape change
        # from index growth/rebuild) legitimately traces on its next call;
        # tell the armed sentinel so.  Same-shape refreshes (deletes, live
        # churn, value-only re-placement) keep the old step — no scope.
        state["fresh"] = step is not state.get("step")
        state["step"] = step
        # Tier-2 WCD shortlist over the ENGINE's flat resident order (the
        # degradation ladder bypasses routing entirely).
        cents = []
        for seg in engine.segments:
            n_rows, h1 = seg.docs.ids.shape
            c = jnp.einsum("nh,nhm->nm", seg.docs.weights,
                           seg.tensors.t_r.reshape(n_rows, h1, -1))
            cents.append(c[:seg.n_real])
        cent = jnp.concatenate(cents, axis=0)
        state["cent"] = jnp.where(
            engine.live_mask_device()[:, None], cent, 1e18)
        state["key"] = key

    def _pack_slots(route, b):
        """Probed-cell union → (probed (p_max,), q_route (B, p_max))."""
        probed = route.probed
        keep = route.keep
        if len(probed) > p_max:
            # Slot overflow: keep the cells the most queries asked for.
            req = np.zeros(index.num_cells, dtype=np.int64)
            np.add.at(req, route.cells[keep].reshape(-1), 1)
            order = np.argsort(-req[probed], kind="stable")
            dropped = probed[order[p_max:]]
            probed = np.sort(probed[order[:p_max]])
            keep = keep & ~np.isin(route.cells, dropped)
            if (index.obs is not None
                    and getattr(index.obs.metrics, "enabled", False)):
                index.obs.metrics.counter(
                    "index_probe_overflow_total",
                    "Probed cells dropped because a batch's routed-cell "
                    "union exceeded probe_cap slots.").inc(len(dropped))
        slots = np.full(p_max, -1, dtype=np.int32)
        slots[:len(probed)] = probed
        q_route = np.zeros((b, p_max), dtype=bool)
        for s, c in enumerate(probed):
            q_route[:, s] = ((route.cells == c) & keep).any(axis=1)
        return jnp.asarray(slots), jnp.asarray(q_route)

    def serve(queries: DocSet, query_ids=None, *, tier: int = 0) -> ServeResult:
        """Tiered routed serve (same ladder as the segmented step)."""
        if self_exclude and query_ids is None:
            raise ValueError("self_exclude serve step needs query_ids (B,)")
        tier = int(tier)
        with obs.span("refresh"):
            _refresh()
        with obs.span("gather_queries"):
            t_q = engine.gather_queries(queries.ids)
            q_valid = (queries.weights > 0).astype(jnp.float32)
            q_gid = (jnp.asarray(query_ids, jnp.int32) if self_exclude
                     else jnp.full((queries.n_docs,), -1, jnp.int32))
        if tier >= 2:  # QualityTier.WCD — no routing on the last rung
            tk = _wcd_topk_step(k, self_exclude, state["cent"], t_q,
                                queries.weights, q_gid)
            return ServeResult(topk=tk, d_local=None, pruned_exact=None,
                               tier=tier)
        with obs.span("route"):
            route = index.route(queries)
            slots, q_route = _pack_slots(route, queries.n_docs)
        step_args = (state["rids"], state["rw"], state["live"],
                     state["gids"], slots, q_route, t_q, q_valid, q_gid,
                     state["embs"])
        with obs.span("step_launch"):
            if state.pop("fresh", False):
                with _sentinel.expect("routed index cell-shape change"):
                    tk = state["step"](*step_args)
            else:
                tk = state["step"](*step_args)
        if tier >= 1:  # QualityTier.LCRWMD: candidates ARE the answer
            return ServeResult(
                topk=TopK(tk.dists[:, :k], tk.indices[:, :k]),
                d_local=None, pruned_exact=None, tier=tier)
        with obs.span("refine_launch"):
            cand_max_rwmd = tk.dists[:, -1]
            if refine:
                tk = _symmetric_refine(
                    engine.resident, queries, engine.emb_full, tk)
        exact = work = None
        if rerank_wmd:
            with obs.span("rerank_launch"):
                tk, work = engine.rerank_topk(queries, tk.indices, k,
                                              sinkhorn_kw=wmd_kw,
                                              with_work=True)
                # Exactness is RELATIVE TO THE ROUTED CELLS (the
                # pipeline's index-stage contract); promote to a
                # corpus-wide certificate only when routing provably
                # covered every live doc.
                exact = cand_max_rwmd >= tk.dists[:, -1]
                if (state["kc"] >= engine.n_live
                        and route.cells.shape[1] == index.num_cells
                        and bool(route.keep.all())):
                    exact = jnp.ones_like(exact)
        return ServeResult(topk=tk, d_local=None, pruned_exact=exact,
                           rerank_work=work)

    return serve


@jax.jit
def _symmetric_refine(
    resident: DocSet, queries: DocSet, emb: Array, tk: TopK
) -> TopK:
    """Tighten D1 candidates with the swapped-direction bound (paper's
    max(D1, D2ᵀ)) evaluated only on the (B, k) candidate pairs.

    jit'd at module level (DocSet/TopK are pytrees): the per-candidate
    ``rwmd_pair`` vmap is traced once per shape, not per serve call — the
    untraced version cost ~100 ms of host time PER FLUSH, which serialized
    the async pipeline's host stage (see EXPERIMENTS.md §Serving)."""
    from repro.core.rwmd import rwmd_pair

    def per_query(q_ids, q_w, cand_idx, cand_d):
        def one(i, d1):
            d_sym = rwmd_pair(
                resident.ids[i], resident.weights[i], q_ids, q_w, emb
            )
            return jnp.maximum(d1, d_sym)

        d = jax.vmap(one)(cand_idx, cand_d)
        order = jnp.argsort(d)
        return TopK(d[order], cand_idx[order])

    with jax.named_scope("refine"):
        return jax.vmap(per_query)(
            queries.ids, queries.weights, tk.indices, tk.dists)


# Module-level jit caches: the PR 5 fix made these trace once per shape —
# the sentinel keeps them honest.
_symmetric_refine = _sentinel.wrap(
    "lcrwmd_dist._symmetric_refine", _symmetric_refine)


def _wmd_rerank(
    resident: DocSet, queries: DocSet, emb: Array, tk: TopK, k: int,
    wmd_kw: dict | None,
) -> TopK:
    """Re-rank (B, budget) candidates by batched Sinkhorn-WMD; keep top-k.

    Engine-less serve path only (the engine-backed steps use
    :meth:`repro.core.lc_rwmd.SegmentedEngine.rerank_topk`).  Dispatches
    through a jit cache keyed on ``(k, wmd_kw)`` so the batched solve is
    traced once per shape."""
    return _wmd_rerank_jit(resident, queries, emb, tk, k,
                           tuple(sorted((wmd_kw or {}).items())))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _wcd_topk_step(
    k: int, self_exclude: bool, cent_r: Array, t_q: Array, q_w: Array,
    q_gid: Array,
) -> TopK:
    """Tier-2 degraded serve: top-k by Word Centroid Distance only.

    The cheapest rung of the cascade ladder (paper Sec. III): one (B, m)
    einsum + one (n, B) centroid-distance matrix — no phase 1/2, no mesh
    collectives (``cent_r`` is replicated; at n where WCD is the fallback
    the matrix is trivially small next to the shed stages).  Module-level
    jit keyed on ``(k, self_exclude)`` so every serve-step build — and every
    adaptive-budget rebuild — shares one trace.
    """
    c_q = jnp.einsum("bh,bhm->bm", q_w, t_q)
    d = dists(cent_r, c_q)  # (n, B)
    if self_exclude:
        row = jnp.arange(cent_r.shape[0], dtype=jnp.int32)
        d = jnp.where(row[:, None] == q_gid[None, :], _INF, d)
    return topk_smallest_cols(d, k)


_wcd_topk_step = _sentinel.wrap(
    "lcrwmd_dist._wcd_topk_step", _wcd_topk_step)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _wmd_rerank_jit(
    resident: DocSet, queries: DocSet, emb: Array, tk: TopK, k: int,
    kw_items: tuple,
) -> TopK:
    from repro.core.topk import topk_from_candidates
    from repro.core.wmd import wmd_candidate_values

    with jax.named_scope("rerank"):
        flat = tk.indices.reshape(-1)
        vals = wmd_candidate_values(
            emb[resident.ids[flat]], resident.weights[flat],
            emb[queries.ids], queries.weights,
            **dict(kw_items),
        )
        return topk_from_candidates(vals, tk.indices, k)


_wmd_rerank_jit = _sentinel.wrap(
    "lcrwmd_dist._wmd_rerank_jit", _wmd_rerank_jit)


def build_allpairs_d1(
    mesh: jax.sharding.Mesh, *, bf16_matmul: bool = True,
    phase1_full_mesh: bool = True,
):
    """All-pairs one-sided LC-RWMD: D1 (n1 sharded over batch axes, n2).

    The symmetric all-pairs bound runs this twice with sets swapped and takes
    max(D1, D2ᵀ) — exactly the paper's Sec. IV procedure.  n2 plays the role
    of a query batch and is replicated; callers chunk it.
    ``phase1_full_mesh`` applies the same beyond-paper vocab sharding as the
    serve path (§Perf Cell C): 16x less redundant phase-1 work.
    """
    batch_axes = _batch_axes(mesh)
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]

    def kernel(r_ids, r_w, q_ids, q_w, emb_local):
        v_local = emb_local.shape[0]
        if phase1_full_mesh:
            didx = jnp.int32(0)
            for a in batch_axes:
                didx = didx * mesh.shape[a] + jax.lax.axis_index(a)
            mi = jax.lax.axis_index(MODEL_AXIS)
            lo = (mi * n_batch_shards + didx) * v_local
            rel = q_ids - lo
            inb = (rel >= 0) & (rel < v_local)
            t_q = emb_local[jnp.clip(rel, 0, v_local - 1)]
            t_q = jnp.where(inb[..., None], t_q, 0.0)
            for a in batch_axes:
                t_q = jax.lax.psum(t_q, a)
            t_q = jax.lax.psum(t_q, MODEL_AXIS)
            z_local = _z_from_t(emb_local, t_q, q_w, bf16_matmul=bf16_matmul)
            for a in reversed(batch_axes):
                z_local = jax.lax.all_gather(z_local, a, axis=0, tiled=True)
            partial = _phase2_partial(r_ids, r_w, z_local,
                                      v_local * n_batch_shards)
        else:
            t_q = _gather_query_embeddings(q_ids, emb_local, v_local)
            z_local = _z_from_t(emb_local, t_q, q_w, bf16_matmul=bf16_matmul)
            partial = _phase2_partial(r_ids, r_w, z_local, v_local)
        return jax.lax.psum(partial, MODEL_AXIS)

    rspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None)
    espec = (P((MODEL_AXIS,) + batch_axes, None) if phase1_full_mesh
             else P(MODEL_AXIS, None))

    shmapped = compat_shard_map(
        kernel,
        mesh=mesh,
        in_specs=(rspec, rspec, P(None, None), P(None, None), espec),
        out_specs=rspec,
    )

    @jax.jit
    def d1(set1: DocSet, set2: DocSet, emb: Array) -> Array:
        return shmapped(set1.ids, set1.weights, set2.ids, set2.weights, emb)

    return d1
