"""Query server integration: batching, recall, WMD re-rank, launcher CLIs."""

import pathlib
import subprocess
import sys
import os

import numpy as np
import pytest

from repro.data.synth import CorpusSpec, make_corpus
from repro.launch.mesh import make_host_mesh
from repro.serving.query_server import QueryServer, ServerConfig

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CorpusSpec(
        n_docs=256, vocab_size=1024, emb_dim=32, h_max=12, mean_h=8.0,
        n_classes=4, seed=11))


def _stream_from(corpus, n, rng):
    ids = np.asarray(corpus.docs.ids)
    w = np.asarray(corpus.docs.weights)
    picks = rng.integers(0, corpus.docs.n_docs, n)
    return [(ids[i], w[i]) for i in picks], picks


def test_server_self_recall(corpus):
    server = QueryServer(corpus.docs, corpus.emb, make_host_mesh(),
                         ServerConfig(k=5, max_batch=8, h_max=12))
    rng = np.random.default_rng(0)
    stream, picks = _stream_from(corpus, 24, rng)
    answers = list(server.serve_stream(stream))
    assert len(answers) == 24
    hits = [picks[i] in set(a[0].tolist()) for i, a in enumerate(answers)]
    assert np.mean(hits) == 1.0   # exact self-match must always be in top-k
    assert server.stats["queries"] == 24
    assert server.stats["batches"] >= 3  # max_batch=8 forced several batches


def test_server_wmd_rerank(corpus):
    server = QueryServer(
        corpus.docs, corpus.emb, make_host_mesh(),
        ServerConfig(k=4, max_batch=8, h_max=12, rerank_wmd=True,
                     wmd_kw=dict(eps=0.05, eps_scaling=2, max_iters=60)))
    rng = np.random.default_rng(1)
    stream, picks = _stream_from(corpus, 8, rng)
    answers = list(server.serve_stream(stream))
    assert len(answers) == 8
    assert server.stats["wmd_reranks"] == 8
    hits = [picks[i] in set(a[0].tolist()) for i, a in enumerate(answers)]
    assert np.mean(hits) >= 0.9


def test_server_overflow_chunked_single_shape(corpus):
    """> max_batch pending queries must flush as fixed max_batch-sized
    chunks — one compiled query shape, never a larger batch."""
    server = QueryServer(corpus.docs, corpus.emb, make_host_mesh(),
                         ServerConfig(k=5, max_batch=8, h_max=12))
    shapes = []
    inner = server._serve

    def spy(queries):
        shapes.append(tuple(queries.ids.shape))
        return inner(queries)

    server._serve = spy
    rng = np.random.default_rng(3)
    stream, picks = _stream_from(corpus, 21, rng)
    for q in stream:
        server.submit(*q)
    answers = server.flush()  # 21 pending > max_batch: 3 chunked serves
    assert len(answers) == 21
    assert server.stats["batches"] == 3
    assert shapes == [(8, 12)] * 3  # single compiled (max_batch, h) shape
    hits = [picks[i] in set(a[0].tolist()) for i, a in enumerate(answers)]
    assert np.mean(hits) == 1.0


def test_serve_stream_staleness_clock_starts_at_first_pending(corpus):
    """A long idle gap before a batch's first query must NOT count toward
    staleness: the timer starts when the first pending query arrives, so the
    post-gap batch still fills to max_batch instead of flushing size-1."""
    import time as _time

    server = QueryServer(corpus.docs, corpus.emb, make_host_mesh(),
                         ServerConfig(k=4, max_batch=4, h_max=12,
                                      max_wait_s=0.5))
    rng = np.random.default_rng(5)
    stream, _ = _stream_from(corpus, 8, rng)

    def gapped():
        for i, q in enumerate(stream):
            if i == 4:  # idle gap longer than max_wait_s before batch 2
                _time.sleep(1.2)
            yield q

    answers = list(server.serve_stream(gapped()))
    assert len(answers) == 8
    # Both batches fill to max_batch; the pre-fix behaviour flushed the
    # post-gap query alone (3 batches) because the gap consumed the budget.
    assert server.stats["batches"] == 2


def test_serve_stream_flushes_pending_on_input_error(corpus):
    """A producer that dies mid-stream must NOT lose accepted queries: the
    answers for everything queued before the failure are yielded, then the
    producer's exception propagates."""
    server = QueryServer(corpus.docs, corpus.emb, make_host_mesh(),
                         ServerConfig(k=5, max_batch=8, h_max=12,
                                      max_wait_s=10.0))
    rng = np.random.default_rng(13)
    stream, picks = _stream_from(corpus, 5, rng)

    def dying_producer():
        yield from stream  # 5 < max_batch: all still pending at the raise
        raise RuntimeError("ingest connection lost")

    got = []
    with pytest.raises(RuntimeError, match="ingest connection lost"):
        for answer in server.serve_stream(dying_producer()):
            got.append(answer)
    assert len(got) == 5
    assert server.stats["queries"] == 5
    hits = [picks[i] in set(a[0].tolist()) for i, a in enumerate(got)]
    assert np.mean(hits) == 1.0


def test_rerank_topk_matches_bruteforce_wmd(corpus):
    """Engine rerank over candidates == per-pair WMD re-sort of the same
    candidates (top-k parity of the serve-time rerank path)."""
    import jax
    import jax.numpy as jnp

    from repro.core.lc_rwmd import SegmentedEngine
    from repro.core.wmd import wmd_pair

    kw = dict(eps=0.05, eps_scaling=2, max_iters=100)
    ds, emb = corpus.docs, jnp.asarray(corpus.emb)
    engine = SegmentedEngine(ds, emb)
    queries = ds[10:14]
    k, budget = 4, 12
    cand = engine.topk(queries, budget).indices  # (B, budget)
    got = engine.rerank_topk(queries, cand, k, sinkhorn_kw=kw)

    def per_query(q_ids, q_w, idx):
        return jax.vmap(
            lambda i: wmd_pair(ds.ids[i], ds.weights[i], q_ids, q_w, emb, **kw)
        )(idx)

    wmd = jax.vmap(per_query)(queries.ids, queries.weights, cand)  # (B, budget)
    order = np.argsort(np.asarray(wmd), axis=1)[:, :k]
    want_idx = np.take_along_axis(np.asarray(cand), order, axis=1)
    want_d = np.take_along_axis(np.asarray(wmd), order, axis=1)
    # Near-zero self-match costs sit at the ε-regularization floor where the
    # two formulations differ by O(1e-3); rank order is what must agree.
    np.testing.assert_allclose(
        np.asarray(got.dists), want_d, rtol=1e-4, atol=5e-3)
    for row_got, row_want in zip(np.asarray(got.indices), want_idx):
        assert set(row_got.tolist()) == set(row_want.tolist())


@pytest.mark.slow
def test_launchers_cli():
    env = {**os.environ, "PYTHONPATH": str(REPO / "src")}
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "llama3.2-1b",
         "--steps", "4", "--batch", "2", "--seq", "16"],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "[train] done" in r.stdout
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--n-docs", "256",
         "--n-queries", "8", "--batch", "8"],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "self-recall" in r.stdout
