"""Corpus-analytics subsystem: tiled all-pairs parity, structural tiling
contracts, clustering recovery, and near-duplicate graphs."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SegmentedEngine,
    rwmd_many_vs_many,
    rwmd_pair,
    topk_smallest,
)
from repro.data.docs import DocSet
from repro.data.synth import CorpusSpec, make_bimodal_corpus
from repro.workloads import (
    SelfPairScheduler,
    adjusted_rand_index,
    connected_components,
    corpus_self_topk,
    corpus_self_topk_distributed,
    corpus_vs_corpus_topk,
    duplicate_groups,
    kcenters,
    kmedoids,
    kmedoids_wcd_baseline,
    knn_graph,
    near_duplicate_graph,
    purity,
)


@pytest.fixture(scope="module")
def engine(small_corpus):
    return SegmentedEngine(small_corpus.docs, jnp.asarray(small_corpus.emb))


def _brute_self_topk(corpus, emb, k):
    n = corpus.docs.n_docs
    full = rwmd_many_vs_many(corpus.docs, corpus.docs, jnp.asarray(emb))
    full = full.at[jnp.arange(n), jnp.arange(n)].set(jnp.inf)
    return topk_smallest(full, k)


# ---------------------------------------------------------------------------
# Tiled all-pairs: parity vs brute-force quadratic RWMD
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tile", [16, 20, 96])  # divisible, ragged, single
def test_self_topk_matches_bruteforce(small_corpus, engine, tile):
    k = 5
    tk = corpus_self_topk(engine, k, tile=tile)
    want = _brute_self_topk(small_corpus, small_corpus.emb, k)
    np.testing.assert_array_equal(
        np.asarray(tk.indices), np.asarray(want.indices))
    np.testing.assert_allclose(
        np.asarray(tk.dists), np.asarray(want.dists), rtol=1e-4, atol=1e-2)


def test_self_topk_excludes_self(small_corpus, engine):
    idx = np.asarray(corpus_self_topk(engine, 4, tile=32).indices)
    for i in range(small_corpus.docs.n_docs):
        assert i not in idx[i]


def test_cross_corpus_topk_both_sides(small_corpus, engine):
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    queries = ds[60:83]  # 23 docs: ragged against tile=8
    res = corpus_vs_corpus_topk(engine, queries, 4, tile=8,
                                resident_side=True)
    full = rwmd_many_vs_many(ds, queries, emb)  # (n_res, n_q)
    want_q = topk_smallest(full.T, 4)
    np.testing.assert_array_equal(
        np.asarray(res.query_topk.indices), np.asarray(want_q.indices))
    np.testing.assert_allclose(
        np.asarray(res.query_topk.dists), np.asarray(want_q.dists),
        rtol=1e-4, atol=1e-2)
    want_r = topk_smallest(full, 4)
    np.testing.assert_array_equal(
        np.asarray(res.resident_topk.indices), np.asarray(want_r.indices))
    np.testing.assert_allclose(
        np.asarray(res.resident_topk.dists), np.asarray(want_r.dists),
        rtol=1e-4, atol=1e-2)


def test_self_scheduler_visits_only_upper_pairs(engine):
    """Symmetry skip: every unordered tile pair exactly once, s <= t."""
    sched = SelfPairScheduler(engine, tile=32)  # 96 docs -> 3 tiles
    seen = [(b.s, b.t, b.mirrored) for b in sched.blocks()]
    assert sorted((s, t) for s, t, _ in seen) == [
        (0, 0), (0, 1), (0, 2), (1, 1), (1, 2), (2, 2)]
    assert all(m == (s < t) for s, t, m in seen)


def test_step_is_tile_bounded(engine):
    """Structural tiling contract: the jitted block step's largest f32
    intermediate is (tile, tile)+ (v_e, tile) — never (n, n)."""
    from benchmarks.common import intermediate_shapes

    n = engine.resident.n_docs
    tile = 16
    sched = SelfPairScheduler(engine, tile=tile)
    idx = jnp.arange(tile, dtype=jnp.int32)
    z = engine.phase1_resident(idx)
    shapes = intermediate_shapes(sched._step_impl, z, z, idx, idx,
                                 sched._live)
    assert (n, n) not in shapes
    assert (tile, tile) in shapes
    v_e = engine.emb_restricted.shape[0]
    biggest = max(int(np.prod(s)) for s in shapes if s)
    # Phase-2's gather expands to (tile, h, tile); nothing approaches n².
    h = engine.resident.h_max
    assert biggest <= max(tile * tile * h, v_e * tile)


# ---------------------------------------------------------------------------
# Distributed tile serving
# ---------------------------------------------------------------------------
def test_self_topk_distributed_singleton_mesh(small_corpus, engine):
    from repro.launch.mesh import make_host_mesh

    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    n, k = ds.n_docs, 4
    tk = corpus_self_topk_distributed(
        engine, make_host_mesh(data=1, model=1), k, tile=40, refine=True)
    idx = np.asarray(tk.indices)
    d = np.asarray(tk.dists)
    assert idx.shape == (n, k)
    for i in range(n):
        assert i not in idx[i]  # in-mesh self-exclusion
        assert (np.diff(d[i]) >= -1e-6).all()  # ascending
    # Refined candidate distances are EXACT symmetric RWMD for those pairs.
    for i in range(0, n, 19):
        for j, dv in zip(idx[i], d[i]):
            ref = float(rwmd_pair(ds.ids[i], ds.weights[i],
                                  ds.ids[j], ds.weights[j], emb))
            assert abs(ref - dv) < 1e-2


# ---------------------------------------------------------------------------
# Clustering
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def bimodal():
    return make_bimodal_corpus(CorpusSpec(
        n_docs=128, vocab_size=512, emb_dim=32, h_max=24, mean_h=16.0,
        n_classes=4, topic_noise=0.1, emb_topic_scale=4.0,
        emb_word_scale=1.0, seed=5))


@pytest.fixture(scope="module")
def bimodal_engine(bimodal):
    return SegmentedEngine(bimodal.docs, jnp.asarray(bimodal.emb))


def test_kcenters_spreads_over_classes(bimodal, bimodal_engine):
    centers = kcenters(bimodal_engine, 4)
    assert len(set(centers.tolist())) == 4
    # Farthest-first on a 4-class corpus should touch >= 3 distinct classes.
    assert len(set(bimodal.labels[centers].tolist())) >= 3


def test_kmedoids_beats_wcd_on_centroid_degenerate_corpus(
        bimodal, bimodal_engine):
    """The acceptance property: word-level transport recovers the cluster
    structure that centroid distances cannot see at all."""
    rw = kmedoids(bimodal_engine, 4, n_iters=8)
    wc = kmedoids_wcd_baseline(bimodal_engine, 4, n_iters=8)
    ari_rw = adjusted_rand_index(rw.labels, bimodal.labels)
    ari_wc = adjusted_rand_index(wc.labels, bimodal.labels)
    assert ari_rw > ari_wc + 0.3, (ari_rw, ari_wc)
    assert ari_rw > 0.8, ari_rw
    assert purity(rw.labels, bimodal.labels) > 0.9


def test_kmedoids_prefilter_consistent_on_separable_corpus(small_corpus, engine):
    """Where WCD is informative (standard topic corpus), the prefiltered
    assignment must match the full assignment almost everywhere."""
    full = kmedoids(engine, 4, n_iters=4)
    pre = kmedoids(engine, 4, n_iters=4, prefilter=2,
                   init=full.medoids)
    agree = (full.labels == pre.labels).mean()
    assert agree > 0.9, agree


def test_kmedoids_sinkhorn_rerank_runs(small_corpus, engine):
    res = kmedoids(engine, 4, n_iters=2, prefilter=2, rerank_wmd=True,
                   sinkhorn_kw=dict(eps=0.05, eps_scaling=2, max_iters=60))
    assert res.labels.shape == (small_corpus.docs.n_docs,)
    assert np.isfinite(res.objective)
    assert len(np.unique(res.labels)) > 1


# ---------------------------------------------------------------------------
# Near-duplicate graphs
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dup_corpus(small_corpus):
    """small_corpus with docs 5≡50≡77 and 7≡90 made identical."""
    ids = np.array(small_corpus.docs.ids)
    w = np.array(small_corpus.docs.weights)
    for dst, src in ((5, 50), (77, 50), (7, 90)):
        ids[dst] = ids[src]
        w[dst] = w[src]
    return DocSet(ids=jnp.asarray(ids), weights=jnp.asarray(w))


def test_near_duplicate_graph_finds_planted_dups(small_corpus, dup_corpus):
    eng = SegmentedEngine(dup_corpus, jnp.asarray(small_corpus.emb))
    g = near_duplicate_graph(eng, 0.05, tile=40)
    groups = [sorted(gr.tolist()) for gr in duplicate_groups(g)]
    assert [5, 50, 77] in groups
    assert [7, 90] in groups
    # CSR is symmetric: every stored arc has its reverse.
    for i in range(g.n_docs):
        for j in g.indices[g.indptr[i]:g.indptr[i + 1]]:
            row_j = g.indices[g.indptr[j]:g.indptr[j + 1]]
            assert i in row_j
    # 5 docs merged into 2 groups -> exactly 3 fewer components than docs.
    assert len(np.unique(connected_components(g))) == g.n_docs - 3


def test_near_duplicate_graph_no_self_loops(small_corpus, dup_corpus):
    eng = SegmentedEngine(dup_corpus, jnp.asarray(small_corpus.emb))
    g = near_duplicate_graph(eng, 0.05, tile=64)
    for i in range(g.n_docs):
        assert i not in g.indices[g.indptr[i]:g.indptr[i + 1]]


def test_knn_graph_mutual_subset_of_union(small_corpus, engine):
    union = knn_graph(engine, 3, tile=32, mutual=False)
    mutual = knn_graph(engine, 3, tile=32, mutual=True)
    assert mutual.n_edges <= union.n_edges
    # Mutual edges are a subset of union edges.
    ue = set()
    for i in range(union.n_docs):
        for j in union.indices[union.indptr[i]:union.indptr[i + 1]]:
            ue.add((i, int(j)))
    for i in range(mutual.n_docs):
        for j in mutual.indices[mutual.indptr[i]:mutual.indptr[i + 1]]:
            assert (i, int(j)) in ue


def test_near_duplicate_threshold_floor_warns_and_clamps(small_corpus,
                                                         dup_corpus):
    """A threshold below the numeric noise floor is clamped up with a
    warning — and the planted exact copies are still caught."""
    from repro.workloads import DUPLICATE_SCORE_FLOOR

    eng = SegmentedEngine(dup_corpus, jnp.asarray(small_corpus.emb))
    with pytest.warns(UserWarning, match="noise floor"):
        g = near_duplicate_graph(eng, DUPLICATE_SCORE_FLOOR / 100, tile=40)
    groups = [sorted(gr.tolist()) for gr in duplicate_groups(g)]
    assert [5, 50, 77] in groups
    assert [7, 90] in groups


def test_kcenters_seed_reproducible(engine):
    a = kcenters(engine, 5, seed=42)
    b = kcenters(engine, 5, seed=42)
    np.testing.assert_array_equal(a, b)
    c = kcenters(engine, 5, seed=43)
    d = kcenters(engine, 5, first=None, seed=43)
    np.testing.assert_array_equal(c, d)   # seed wins over default first


def test_kmedoids_seed_reproducible(engine):
    a = kmedoids(engine, 4, seed=9, n_iters=3)
    b = kmedoids(engine, 4, seed=9, n_iters=3)
    np.testing.assert_array_equal(a.labels, b.labels)
    np.testing.assert_array_equal(a.medoids, b.medoids)
