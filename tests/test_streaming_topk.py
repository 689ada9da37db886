"""Streaming top-k subsystem: exact parity with materialized selection
(values AND index sets, ties included) across every layer — core merge,
kernel ops, engine entry points, pipeline stage 1, distributed serve — plus
the structural contract that no (n, B) intermediate exists on the streaming
paths, and the satellite behaviors (adaptive-budget decay, batched medoid
update, in-device near-dup thresholding)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import intermediate_shapes
from repro.core import topk as topk_lib
from repro.core.lc_rwmd import SegmentedEngine, _segment_dense, _segment_topk
from repro.core.pipeline import AdaptiveRefineBudget, pruned_wmd_topk
from repro.data.docs import DocSet


# ---------------------------------------------------------------------------
# StreamingTopK core: block folds == materialized top-k, ties included
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("block", [1, 7, 16, 64])
def test_streaming_equals_materialized_with_ties(block):
    """Integer-valued distances force many exact ties; the streaming fold
    must reproduce lax.top_k's (value, index)-lexicographic order bit-for-
    bit regardless of the block size it sees the rows in."""
    rng = np.random.default_rng(0)
    n, b, k = 64, 5, 9
    d = jnp.asarray(rng.integers(0, 6, (n, b)).astype(np.float32))
    want = topk_lib.topk_smallest_cols(d, k)

    stk = topk_lib.StreamingTopK(k)
    carry = stk.init(b)
    for lo in range(0, n, block):
        blk = d[lo: lo + block]
        carry = stk.update_cols(carry, blk, jnp.arange(lo, lo + blk.shape[0]))
    np.testing.assert_array_equal(np.asarray(carry.dists),
                                  np.asarray(want.dists))
    np.testing.assert_array_equal(np.asarray(carry.indices),
                                  np.asarray(want.indices))


def test_streaming_row_orientation_and_empty_slots():
    rng = np.random.default_rng(1)
    block = jnp.asarray(rng.integers(0, 4, (6, 10)).astype(np.float32))
    col_gids = jnp.arange(100, 110)
    stk = topk_lib.StreamingTopK(4)
    got = stk.update_rows(stk.init(6), block, col_gids)
    want = topk_lib.topk_smallest(block, 4)
    np.testing.assert_array_equal(np.asarray(got.dists), np.asarray(want.dists))
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(col_gids)[np.asarray(want.indices)])
    # Fewer candidates than k: the tail stays (+inf, EMPTY_IDX).
    small = stk.update(stk.init(2), jnp.ones((2, 2)), jnp.array([[5, 3], [3, 5]]))
    assert np.isinf(np.asarray(small.dists)[:, 2:]).all()
    np.testing.assert_array_equal(np.asarray(small.indices)[:, :2],
                                  [[3, 5], [3, 5]])  # tie -> ascending gid
    assert (np.asarray(small.indices)[:, 2:] == topk_lib.EMPTY_IDX).all()


def test_merge_topk_lexicographic_ties():
    """The shared merge primitive orders equal values by ascending id, so
    merge trees agree with flat selection no matter how parts are split."""
    a = topk_lib.TopK(jnp.array([[1.0, 2.0]]), jnp.array([[9, 4]]))
    b = topk_lib.TopK(jnp.array([[1.0, 2.0]]), jnp.array([[3, 8]]))
    m = topk_lib.merge_topk([a, b], 3)
    np.testing.assert_array_equal(np.asarray(m.dists), [[1.0, 1.0, 2.0]])
    np.testing.assert_array_equal(np.asarray(m.indices), [[3, 9, 4]])


# ---------------------------------------------------------------------------
# Kernel ops: fused streaming top-k (jnp scan + Pallas interpret)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fuse", ["jnp", "kernel"])
def test_ops_fused_topk_matches_materialized(small_corpus, fuse):
    from repro.kernels import ops

    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    q = ds[:5]
    d = ops.lc_rwmd_fused(emb, q.ids, q.weights, ds.ids, ds.weights,
                          fuse="jnp")
    want = topk_lib.topk_smallest_cols(d, 7)
    dd, ii = ops.lc_rwmd_fused_topk(
        emb, q.ids, q.weights, ds.ids, ds.weights, k=7, fuse=fuse,
        row_block=33, block_v=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(ii), np.asarray(want.indices))
    np.testing.assert_allclose(np.asarray(dd), np.asarray(want.dists),
                               rtol=1e-4, atol=1e-2)


def test_ops_fused_topk_no_nB_intermediate(small_corpus):
    """Structural: the streaming selection path contains NO (n, B) f32
    intermediate; the materialized lc_rwmd_fused positive control does
    produce the full (n, B) matrix."""
    import functools

    from repro.kernels import ops

    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    q = ds[:5]
    n, b = ds.n_docs, 5
    assert emb.shape[0] != n  # keep the (n, B) probe unambiguous
    streaming = functools.partial(ops.lc_rwmd_fused_topk, k=7, fuse="jnp",
                                  row_block=32)
    shapes = intermediate_shapes(
        streaming, emb, q.ids, q.weights, ds.ids, ds.weights)
    assert (n, b) not in shapes, "streaming top-k materialized (n, B)"
    mat = functools.partial(ops.lc_rwmd_fused, fuse="jnp")
    shapes_mat = intermediate_shapes(
        mat, emb, q.ids, q.weights, ds.ids, ds.weights)
    assert (n, b) in shapes_mat, "positive control lost its (n, B)"


# ---------------------------------------------------------------------------
# Engine entry points
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("row_block", [33, 128])
def test_engine_streaming_topk_parity(small_corpus, row_block):
    """Slabs of 33 rows (a ragged last slab) and one slab over all rows."""
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    q = ds[3:8]
    eng = SegmentedEngine(ds, emb, row_block=row_block)
    want_sym = topk_lib.topk_smallest_cols(eng.symmetric(q), 7)
    got_sym = eng.symmetric_topk_streaming(q, 7)
    np.testing.assert_array_equal(np.asarray(got_sym.indices),
                                  np.asarray(want_sym.indices))
    # Near-zero self-distances carry gram-expansion cancellation noise that
    # moves with matmul blocking; the documented floor is ~1e-2 absolute.
    np.testing.assert_allclose(np.asarray(got_sym.dists),
                               np.asarray(want_sym.dists),
                               rtol=1e-4, atol=1e-2)
    want_1s = topk_lib.topk_smallest_cols(eng.one_sided(q), 7)
    got_1s = eng.topk_streaming(q, 7)
    np.testing.assert_array_equal(np.asarray(got_1s.indices),
                                  np.asarray(want_1s.indices))
    np.testing.assert_allclose(np.asarray(got_1s.dists),
                               np.asarray(want_1s.dists),
                               rtol=1e-4, atol=1e-2)


def test_engine_topk_routes_through_streaming(small_corpus):
    """engine.topk is now an alias of the streaming symmetric path."""
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    q = ds[:4]
    eng = SegmentedEngine(ds, emb)
    a = eng.topk(q, 6)
    b = eng.symmetric_topk_streaming(q, 6)
    np.testing.assert_array_equal(np.asarray(a.indices), np.asarray(b.indices))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))


def test_engine_streaming_no_nB_intermediate(small_corpus):
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    q = ds[:5]
    n, b = ds.n_docs, 5
    eng = SegmentedEngine(ds, emb, row_block=32)
    assert eng.emb_restricted.shape[0] != n  # unambiguous (n, B) probe
    (seg,), (live,) = eng.segments, eng._seg_live_device()
    kw = dict(bf16_matmul=False, vocab_chunk=None)
    shapes = intermediate_shapes(
        lambda qi, qw: _segment_topk(
            seg.tensors, eng._gather_queries_flat(qi), qw, live, k=7,
            symmetric=True, row_block=32, **kw),
        q.ids, q.weights)
    assert (n, b) not in shapes, "engine streaming top-k materialized (n, B)"
    assert (b, n) not in shapes, "swapped direction materialized (B, n)"
    shapes_mat = intermediate_shapes(
        lambda qi, qw: _segment_dense(
            seg.tensors, eng._gather_queries_flat(qi), qw, live,
            symmetric=True, **kw),
        q.ids, q.weights)
    assert (n, b) in shapes_mat, "positive control lost its (n, B)"


# ---------------------------------------------------------------------------
# Pipeline stage 1
# ---------------------------------------------------------------------------
def test_pipeline_streaming_candidates_match_materialized(small_corpus):
    """Engine (streaming stage 1) and engine-less (materialized stage 1)
    cascades pick the SAME candidate sets and final top-k."""
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    resident, queries = ds[:32], ds[40:43]
    sink = dict(eps=0.05, eps_scaling=2, max_iters=100)
    base = pruned_wmd_topk(resident, queries, emb, k=4, refine_budget=8,
                           sinkhorn_kw=sink)
    eng = pruned_wmd_topk(resident, queries, emb, k=4, refine_budget=8,
                          sinkhorn_kw=sink,
                          engine=SegmentedEngine(resident, emb))
    np.testing.assert_array_equal(np.asarray(eng.rwmd_topk.indices),
                                  np.asarray(base.rwmd_topk.indices))
    np.testing.assert_array_equal(np.asarray(eng.topk.indices),
                                  np.asarray(base.topk.indices))
    np.testing.assert_allclose(np.asarray(eng.topk.dists),
                               np.asarray(base.topk.dists),
                               rtol=1e-4, atol=1e-2)
    np.testing.assert_array_equal(np.asarray(eng.pruned_exact),
                                  np.asarray(base.pruned_exact))


# ---------------------------------------------------------------------------
# Distributed serve step
# ---------------------------------------------------------------------------
def test_distributed_streaming_structural_and_self_exclude(small_corpus):
    """The segmented shard kernel holds no (n_shard, B) f32 before the
    cross-shard collective (the engine-less materialized step is the
    positive control), and in-accumulator self-exclusion gives the top-k of
    the one-sided distances with each query's own row at +inf."""
    from repro.distributed.lcrwmd_dist import build_serve_step
    from repro.launch.mesh import make_host_mesh

    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    n, b = ds.n_docs, 8
    mesh = make_host_mesh(data=1, model=1)
    eng = SegmentedEngine(ds, emb, row_block=32)
    assert eng.emb_restricted.shape[0] != n
    idx = jnp.arange(b, dtype=jnp.int32)
    tile = eng.resident_tile(idx)

    def build(psum_batch=8):
        return build_serve_step(mesh, k=5, engine=eng, bf16_matmul=False,
                                self_exclude=True, row_block=32,
                                psum_batch=psum_batch)

    stream = build()(tile, query_ids=idx)
    d = np.asarray(eng.one_sided(tile)).copy()          # (n, B)
    d[np.arange(b), np.arange(b)] = np.inf              # own rows out
    want = topk_lib.topk_smallest_cols(jnp.asarray(d), 5)
    np.testing.assert_array_equal(np.asarray(stream.topk.indices),
                                  np.asarray(want.indices))
    np.testing.assert_allclose(np.asarray(stream.topk.dists),
                               np.asarray(want.dists),
                               rtol=1e-5, atol=1e-5)
    for i in range(b):
        assert i not in np.asarray(stream.topk.indices)[i]

    # Structural contract, traced through shard_map into the mesh kernel:
    # the engine-less step forms (n_shard, B); the segmented step's
    # biggest doc-axis slab is the (psum_batch·row_block, B) super-slab —
    # bounded by the knobs, independent of n_shard.  psum_batch=2 here so
    # the super-slab (64, B) stays strictly below this small shard (96).
    materialized = build_serve_step(mesh, k=5, bf16_matmul=False)
    shapes_mat = intermediate_shapes(
        lambda qi, qw: materialized(ds, DocSet(qi, qw), emb).topk,
        tile.ids, tile.weights)
    super_slab = build(psum_batch=2)
    super_slab(tile, query_ids=idx)   # places the segment outside the trace
    shapes_stream = intermediate_shapes(
        lambda qi, qw, gid: super_slab(DocSet(qi, qw), query_ids=gid).topk,
        tile.ids, tile.weights, idx)
    assert (n, b) in shapes_mat, "positive control lost its (n_shard, B)"
    n_pad = -(-n // 64) * 64  # the step pads the doc axis to super-slabs
    assert eng.emb_restricted.shape[0] not in (n, n_pad)
    assert (n, b) not in shapes_stream and (n_pad, b) not in shapes_stream, (
        f"streaming serve materialized an (n_shard, B) block: {shapes_stream}")
    assert (64, b) in shapes_stream, "super-slab positive control lost"


# ---------------------------------------------------------------------------
# Adaptive budget decay + server wiring
# ---------------------------------------------------------------------------
def test_adaptive_budget_decays_after_streak():
    ab = AdaptiveRefineBudget(k=4, n_resident=256, init=64, decay_after=3)
    exact = np.ones(8, bool)
    assert ab.update(exact) == 64 and ab.exact_streak == 1
    assert ab.update(exact) == 64 and ab.exact_streak == 2
    assert ab.update(exact) == 32 and ab.exact_streak == 0  # halved
    # A failure burst re-grows, resets the streak, and floors future decay.
    fail = np.zeros(8, bool)
    assert ab.update(fail) == 64
    assert ab.exact_streak == 0 and ab.failed_budget == 32
    # The known-failed level is never re-probed: no oscillation.
    assert ab.update(exact) == 64
    assert ab.update(exact) == 64
    assert ab.update(exact) == 64 and ab.exact_streak == 0  # decay skipped
    ab.reset_decay_floor()  # e.g. corpus swap: probing allowed again
    assert ab.update(exact) == 64
    assert ab.update(exact) == 64
    assert ab.update(exact) == 32
    # Decay never drops below k.
    ab2 = AdaptiveRefineBudget(k=4, n_resident=256, init=5, decay_after=1)
    assert ab2.update(exact) == 4
    assert ab2.update(exact) == 4  # clamped at k, stays
    # Mixed-but-acceptable batches break the streak without growth.
    ab3 = AdaptiveRefineBudget(k=4, n_resident=256, init=64, decay_after=2,
                               target_failure_rate=0.5)
    mixed = np.array([True] * 7 + [False], bool)
    assert ab3.update(exact) == 64 and ab3.exact_streak == 1
    assert ab3.update(mixed) == 64 and ab3.exact_streak == 0
    assert ab3.update(exact) == 64 and ab3.exact_streak == 1


def test_adaptive_budget_legacy_grow_only():
    ab = AdaptiveRefineBudget(k=4, n_resident=64, init=16)  # no decay_after
    exact = np.ones(4, bool)
    for _ in range(10):
        assert ab.update(exact) == 16  # never decays


def test_query_server_adaptive_budget_wiring(small_corpus):
    from repro.launch.mesh import make_host_mesh
    from repro.serving.query_server import QueryServer, ServerConfig

    ds = small_corpus.docs
    n = ds.n_docs
    cfg = ServerConfig(k=4, max_batch=8, h_max=ds.h_max, rerank_wmd=True,
                       adaptive_budget=True, budget_decay_after=2,
                       wmd_kw=dict(eps=0.05, eps_scaling=2, max_iters=60))
    server = QueryServer(ds, small_corpus.emb, make_host_mesh(), cfg)
    assert server.budget is not None
    assert server.stats["budget_trajectory"] == [2 * cfg.k]
    ids = np.asarray(ds.ids)
    w = np.asarray(ds.weights)
    for round_ in range(6):
        for i in range(8):
            server.submit(ids[(8 * round_ + i) % n], w[(8 * round_ + i) % n])
        out = server.flush()
        assert len(out) == 8
    # Every observed budget respects the [k, n] clamp, and every rebuild
    # was recorded alongside its trajectory entry.
    traj = server.stats["budget_trajectory"]
    assert all(cfg.k <= bdg <= n for bdg in traj)
    assert server.stats["budget_rebuilds"] == len(traj) - 1
    assert server.budget.budget == traj[-1]


# ---------------------------------------------------------------------------
# Satellite coverage: batched medoid update, in-device near-dup threshold
# ---------------------------------------------------------------------------
def test_medoid_cost_batched_matches_per_cluster(small_corpus):
    from repro.workloads.clustering import _medoid_cost_batched

    rng = np.random.default_rng(3)
    n, k, c = 50, 4, 3
    block = jnp.asarray(rng.uniform(0, 5, (n, k * c)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    got = np.asarray(_medoid_cost_batched(block, labels, k, c))
    blk = np.asarray(block).reshape(n, k, c)
    lab = np.asarray(labels)
    for j in range(k):
        want = blk[lab == j, j, :].sum(axis=0)
        np.testing.assert_allclose(got[j], want, rtol=1e-5, atol=1e-5)


def test_near_duplicate_graph_overflow_fallback(small_corpus):
    """A tiny cap forces the overflow path; the graph must equal the
    generously-capped one (the in-device list is an optimization only)."""
    from repro.workloads import near_duplicate_graph

    eng = SegmentedEngine(small_corpus.docs, jnp.asarray(small_corpus.emb))
    thr = 6.0  # loose (typical distances ~5-8): plenty of edges per block
    big = near_duplicate_graph(eng, thr, tile=32)
    tiny = near_duplicate_graph(eng, thr, tile=32, block_edge_cap=2)
    np.testing.assert_array_equal(big.indptr, tiny.indptr)
    np.testing.assert_array_equal(big.indices, tiny.indices)
    np.testing.assert_allclose(big.data, tiny.data, rtol=1e-6)
    assert big.n_edges > 0
