"""Segmented-engine semantics: bit-parity with a monolithic rebuild,
tombstone exclusion in every query path, and stable global ids across
append/delete/compact."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.lc_rwmd import SegmentedEngine
from repro.data.docs import DocSet
from repro.data.synth import CorpusSpec, make_corpus

K = 8
BASE_N = 128


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CorpusSpec(
        n_docs=192, vocab_size=512, emb_dim=48, h_max=16, mean_h=8.0,
        n_classes=4, seed=3,
    ))


def _slice(docs: DocSet, lo: int, hi: int) -> DocSet:
    return DocSet(ids=docs.ids[lo:hi], weights=docs.weights[lo:hi])


def _dup_row(docs: DocSet, row: int) -> DocSet:
    """A one-doc DocSet that is an EXACT copy of ``docs[row]`` (tie maker)."""
    return DocSet(ids=docs.ids[row:row + 1], weights=docs.weights[row:row + 1])


def _concat(a: DocSet, b: DocSet) -> DocSet:
    return DocSet(ids=jnp.concatenate([a.ids, b.ids]),
                  weights=jnp.concatenate([a.weights, b.weights]))


@pytest.fixture(scope="module")
def grown(corpus):
    """Base + two deltas (the second contains an exact duplicate of a base
    doc, so top-k has genuine ties) and the equivalent monolithic corpus."""
    docs = corpus.docs
    base = _slice(docs, 0, BASE_N)
    d1 = _slice(docs, BASE_N, BASE_N + 32)
    d2 = _concat(_slice(docs, BASE_N + 32, BASE_N + 56), _dup_row(docs, 5))
    seg = SegmentedEngine(base, corpus.emb)
    gids1 = seg.append(d1)
    gids2 = seg.append(d2)
    np.testing.assert_array_equal(gids1, np.arange(BASE_N, BASE_N + 32))
    np.testing.assert_array_equal(gids2, np.arange(BASE_N + 32, BASE_N + 57))
    mono = SegmentedEngine(_concat(_concat(base, d1), d2), corpus.emb)
    assert seg.n_segments == 3 and mono.n_segments == 1
    assert seg.n_docs == mono.n_docs == BASE_N + 57
    return seg, mono


def _assert_topk_bit_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices))


@pytest.mark.parametrize("method", ["topk", "topk_streaming",
                                    "symmetric_topk_streaming"])
def test_topk_bit_equals_monolithic_rebuild(corpus, grown, method):
    """Segment-folded top-k is BIT-identical (dists AND tie order) to a
    from-scratch rebuild over the merged corpus, in every selection mode."""
    seg, mono = grown
    queries = _slice(corpus.docs, 4, 20)  # includes doc 5 = the duplicate
    _assert_topk_bit_equal(getattr(seg, method)(queries, K),
                           getattr(mono, method)(queries, K))


def test_serve_step_rerank_bit_parity(corpus, grown):
    """The distributed serve step (streaming + symmetric refine + WMD
    rerank) is bit-identical between the segmented engine and its
    monolithic rebuild."""
    from repro.distributed.lcrwmd_dist import build_serve_step
    from repro.launch.mesh import make_host_mesh

    seg, mono = grown
    mesh = make_host_mesh()
    kw = dict(k=K, refine=True, bf16_matmul=False, rerank_wmd=True,
              rerank_budget=2 * K)
    queries = _slice(corpus.docs, 0, 8)
    res_s = build_serve_step(mesh, engine=seg, **kw)(queries)
    res_m = build_serve_step(mesh, engine=mono, **kw)(queries)
    _assert_topk_bit_equal(res_s.topk, res_m.topk)
    np.testing.assert_array_equal(np.asarray(res_s.pruned_exact),
                                  np.asarray(res_m.pruned_exact))


def test_delete_excludes_engine_topk(corpus):
    docs = corpus.docs
    eng = SegmentedEngine(_slice(docs, 0, BASE_N), corpus.emb)
    eng.append(_slice(docs, BASE_N, BASE_N + 32))
    target = BASE_N + 3   # a delta doc; query is its exact copy
    queries = _slice(docs, target, target + 1)
    before = np.asarray(eng.topk(queries, K).indices)
    assert target in before[0]
    assert eng.delete([target]) == 1
    assert eng.n_live == BASE_N + 32 - 1
    after = eng.topk(queries, K)
    assert target not in np.asarray(after.indices)[0]
    assert np.isfinite(np.asarray(after.dists)).all()
    # Deleting again is a no-op (already tombstoned).
    assert eng.delete([target]) == 0


def test_delete_excludes_pipeline_self_topk(corpus):
    from repro.workloads.corpus_distance import corpus_self_topk

    eng = SegmentedEngine(_slice(corpus.docs, 0, 96), corpus.emb)
    dead = [7, 41]
    eng.delete(dead)
    tk = corpus_self_topk(eng, 4)
    idx = np.asarray(tk.indices)
    live = eng.live_mask()
    for g in dead:
        # A dead doc is no one's neighbor...
        assert not np.isin(g, idx[live]).any()
    # ...and has no neighbors of its own (its rows are +inf / padding).
    assert not np.isfinite(np.asarray(tk.dists)[dead]).any()


def test_delete_excludes_distributed_serve_without_rebuild(corpus):
    """Tombstones land in the SAME compiled serve step: the segmented step
    re-reads ``engine.version`` per call — no rebuild, no re-trace."""
    from repro.distributed.lcrwmd_dist import build_serve_step
    from repro.launch.mesh import make_host_mesh

    docs = corpus.docs
    eng = SegmentedEngine(_slice(docs, 0, BASE_N), corpus.emb)
    serve = build_serve_step(make_host_mesh(), k=K, engine=eng, refine=True,
                             bf16_matmul=False)
    target = 11
    queries = _slice(docs, target, target + 8)
    before = np.asarray(serve(queries).topk.indices)
    assert target in before[0]
    eng.delete([target])
    after = np.asarray(serve(queries).topk.indices)   # same callable
    assert target not in after
    assert before.shape == after.shape


def test_compact_preserves_answers(corpus):
    docs = corpus.docs
    eng = SegmentedEngine(_slice(docs, 0, BASE_N), corpus.emb)
    eng.append(_slice(docs, BASE_N, BASE_N + 32))
    eng.append(_slice(docs, BASE_N + 32, BASE_N + 48))
    eng.delete([2, BASE_N + 5])
    queries = _slice(docs, 30, 46)
    before = eng.topk(queries, K)
    n_docs, n_live = eng.n_docs, eng.n_live
    eng.compact()
    assert eng.n_segments == 1
    # Global ids and tombstones survive compaction exactly.
    assert (eng.n_docs, eng.n_live) == (n_docs, n_live)
    assert not eng.live_mask()[2] and not eng.live_mask()[BASE_N + 5]
    _assert_topk_bit_equal(before, eng.topk(queries, K))


def test_append_hmax_guard(corpus):
    docs = corpus.docs
    eng = SegmentedEngine(_slice(docs, 0, 64), corpus.emb)
    wide = DocSet(
        ids=jnp.pad(docs.ids[64:66], ((0, 0), (0, 4))),
        weights=jnp.pad(docs.weights[64:66], ((0, 0), (0, 4))),
    )
    with pytest.raises(ValueError, match="h_max"):
        eng.append(wide)
    # Narrower docs are padded up and accepted.
    narrow = DocSet(ids=docs.ids[64:66, :8], weights=docs.weights[64:66, :8])
    gids = eng.append(narrow)
    np.testing.assert_array_equal(gids, [64, 65])
    assert eng.h_max == docs.h_max


def test_delta_pad_rounds_segment_rows(corpus):
    docs = corpus.docs
    eng = SegmentedEngine(_slice(docs, 0, 64), corpus.emb, delta_pad=16)
    eng.append(_slice(docs, 64, 64 + 5))
    seg = eng.segments[-1]
    assert (seg.n_real, seg.n_rows) == (5, 16)   # padded rows are dead
    assert eng.n_docs == 69 and eng.n_live == 69
    # Padding rows never become answer candidates.
    tk = eng.topk(_slice(docs, 0, 4), K)
    assert np.asarray(tk.indices).max() < 69
