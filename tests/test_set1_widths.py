"""Set-1 widths: documents of about 100 words under h_max 128, and an engine
that keeps no (n·h1, m) copy of the resident words' embeddings.

The served path must answer a Set-1-shaped corpus as the benchmark's plain
reference does, with width classes above Set 2's 48 in both phases; the
symmetric segment paths that now gather ``emb_r[r_ids]`` per call must give
the bits the pre-gathered ``emb_full[docs.ids]`` gave; and a segment's
device bytes per document must stay O(h1 + m).
"""

from __future__ import annotations

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import corpus as corpus_lib  # noqa: E402
from bench.reference import Reference  # noqa: E402
from repro.core.distances import safe_sqrt, sq_dists  # noqa: E402
from repro.core.lc_rwmd import (  # noqa: E402
    EngineSegment, _centroids, _segment_dense,
    _topk_stream_from_z, phase1_z_from_t, phase2_spmm)
from repro.core.topk import StreamingTopK  # noqa: E402
from repro.data.docs import DocSet  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.serving import AsyncQueryServer, ServerConfig  # noqa: E402

H1 = 128
#: A Set-1-shaped corpus at a CPU size: h_max 128 and ≈ 100 words a
#: document, over a small vocabulary and narrow embeddings.
SPEC = dict(n_docs=2048, vocab_size=4096, emb_dim=32, h_max=H1, mean_h=100.0,
            n_classes=16, topic_noise=0.25, zipf_a=1.3, emb_topic_scale=4.0,
            emb_word_scale=1.0)
SET2_LIMITS = json.loads(
    (ROOT / "bench/configs/set2_knn.json").read_text())["check"]["limits"]
_INF = jnp.float32(jnp.inf)


@pytest.fixture(scope="module")
def set1_corpus():
    c = corpus_lib.make_corpus(SPEC, 2**33 + 19)
    emb = corpus_lib.make_embeddings(SPEC, c.model.word_topic, c.device_seed)
    qi, qw = corpus_lib.make_docs(
        SPEC, c.model, corpus_lib.doc_lengths(SPEC, 48, c.rng), c.rng)
    return c, emb, qi, qw


def test_set1_shaped_corpus_served_as_the_reference(set1_corpus):
    c, emb, qi, qw = set1_corpus
    n, k, mb = SPEC["n_docs"], 16, 16
    cfg = ServerConfig(k=k, max_batch=mb, max_wait_s=0.005, h_max=H1,
                       refine_symmetric=True, vocab_pad=512)
    docs = DocSet(ids=jnp.asarray(c.ids), weights=jnp.asarray(c.weights))
    with AsyncQueryServer(docs, emb, make_host_mesh(), cfg) as srv:
        nz = (qw > 0).sum(axis=1)
        futs = [srv.submit(qi[i, :nz[i]], qw[i, :nz[i]])
                for i in range(len(qi))]
        srv.drain()
        answers = [f.result() for f in futs]
        reg = srv.obs.metrics
        series = {name: {tuple(sorted(s["labels"].items())): s
                         for s in fam["series"]}
                  for name, fam in reg.snapshot().items()}
    ids = np.stack([np.asarray(a[0]) for a in answers])
    d = np.stack([np.asarray(a[1]) for a in answers])
    ref = Reference(c.ids, c.weights, emb, k=k, kc=k, mode="knn",
                    vocab_pad=512)
    nums = ref.judge(qi, qw, ids, d)
    for name, limit in SET2_LIMITS.items():
        assert nums[name] <= limit, (name, nums)

    def value(name, **labels):
        return series[name][tuple(sorted(labels.items()))]["value"]

    batches = series["serving_batch_size"][()]["count"]
    # Phase 1: one group of 16 a batch, so Σ 16·w over batches / 16 is the
    # mean width class; above 48 means a class Set 2 never reaches.
    assert value("serving_phase1_computed_cols_total") / (16 * batches) > 48
    # Phase 2: the mean slots a row gathers.
    assert value("serving_phase2_gathered_slots_total") / (batches * n) > 48
    # What the placed version holds: histograms and centroids exactly once.
    m = SPEC["emb_dim"]
    assert value("serving_resident_bytes", part="docs") == n * H1 * 8
    assert value("serving_resident_bytes", part="cent") == n * m * 4
    assert 0 < value("serving_resident_bytes", part="ell") <= n * H1 * 14
    assert value("serving_resident_bytes", part="emb") >= SPEC[
        "vocab_size"] * m * 4


# -- the symmetric segment paths without t_r -----------------------------------
def _old_segment_dense(seg, t_r, t_q, q_w, row_valid):
    """``_segment_dense(symmetric=True)`` as it read a pre-gathered
    ``t_r = emb_full[docs.ids]`` (n·h1, m)."""
    b, h2 = q_w.shape
    n, h1 = seg.r_ids.shape
    z1 = phase1_z_from_t(seg.emb_r, t_q, (q_w > 0).reshape(-1), b)
    d = phase2_spmm(DocSet(ids=seg.r_ids, weights=seg.r_w), z1)
    sq = sq_dists(t_q, t_r)
    sq = jnp.where(seg.valid_r[None, :], sq, _INF)
    z2 = safe_sqrt(jnp.min(sq.reshape(b * h2, n, h1), axis=2))
    d2 = jnp.einsum("bh,bhn->bn", q_w, z2.reshape(b, h2, n))
    d = jnp.maximum(d, d2.T)
    return jnp.where(row_valid[:, None], d, _INF)


def _old_topk_stream(seg, t_r, z1, t_q, q_w, row_valid, *, k, row_block):
    """``_topk_stream_from_z(symmetric=True)`` over row blocks of a
    pre-gathered ``t_r``."""
    b, h2 = q_w.shape
    n, h1 = seg.r_ids.shape
    m = t_r.shape[-1]
    r = min(row_block, n)
    nb = -(-n // r)
    pad = nb * r - n

    def rows(x):
        return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))

    xs = [rows(seg.r_ids).reshape(nb, r, h1), rows(seg.r_w).reshape(nb, r, h1),
          jnp.arange(nb, dtype=jnp.int32) * r,
          rows(t_r.reshape(n, h1, m)).reshape(nb, r * h1, m),
          rows(seg.valid_r.reshape(n, h1)).reshape(nb, r * h1),
          rows(row_valid).reshape(nb, r)]
    stk = StreamingTopK(min(k, n))

    def body(carry, xs):
        ids_blk, w_blk, lo, tr_blk, vr_blk, live_blk = xs
        d1 = phase2_spmm(DocSet(ids=ids_blk, weights=w_blk), z1)
        sq = jnp.where(vr_blk[None, :], sq_dists(t_q, tr_blk), _INF)
        z2 = safe_sqrt(jnp.min(sq.reshape(b * h2, r, h1), axis=2))
        d2 = jnp.einsum("bh,bhr->br", q_w, z2.reshape(b, h2, r))
        d_blk = jnp.maximum(d1.T, d2)
        row = lo + jnp.arange(r, dtype=jnp.int32)
        d_blk = jnp.where((row < n)[None, :], d_blk, _INF)
        d_blk = jnp.where(live_blk[None, :], d_blk, _INF)
        return stk.update(carry, d_blk,
                          jnp.broadcast_to(row[None, :], (b, r))), None

    carry, _ = jax.lax.scan(body, stk.init(b), xs)
    return carry


@pytest.fixture(scope="module")
def small_segment(set1_corpus):
    """A 200-row segment (not a multiple of the row block) of the Set-1
    corpus, rows padded to 208, with a tombstone."""
    c, emb, qi, qw = set1_corpus
    docs = DocSet(ids=jnp.asarray(c.ids[:200]),
                  weights=jnp.asarray(c.weights[:200]))
    seg = EngineSegment(docs, emb, offset=0, n_pad=208, vocab_pad=512)
    live = np.zeros(208, bool)
    live[:200] = True
    live[7] = False
    q = DocSet(ids=jnp.asarray(qi[:5]), weights=jnp.asarray(qw[:5]))
    return seg, emb, q, jnp.asarray(live)


@pytest.mark.parametrize("path", ("topk_stream", "segment_dense"))
def test_symmetric_paths_bit_equal_without_t_r(small_segment, path):
    seg, emb, q, live = small_segment
    t = seg.tensors
    t_r = emb[seg.docs.ids.reshape(-1)]
    t_q = emb[q.ids.reshape(-1)]
    if path == "segment_dense":
        new = _segment_dense(t, t_q, q.weights, live, symmetric=True,
                             bf16_matmul=False, vocab_chunk=None)
        old = jax.jit(_old_segment_dense)(t, t_r, t_q, q.weights, live)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
        return
    z1 = phase1_z_from_t(t.emb_r, t_q, (q.weights > 0).reshape(-1), 5)
    new = jax.jit(lambda z: _topk_stream_from_z(
        t, z, t_q, q.weights, live, k=9, symmetric=True, row_block=32,
        bf16_matmul=False))(z1)
    old = jax.jit(lambda z: _old_topk_stream(
        t, t_r, z, t_q, q.weights, live, k=9, row_block=32))(z1)
    np.testing.assert_array_equal(np.asarray(new.indices),
                                  np.asarray(old.indices))
    np.testing.assert_array_equal(np.asarray(new.dists), np.asarray(old.dists))


@pytest.mark.parametrize("chunk", (None, 48))
def test_centroids_match_the_full_gather(small_segment, chunk):
    """Each row's centroid equals Σ_h w·E[word] over the full table, in one
    chunk (the segment's own) or in chunks that do not divide the rows."""
    seg, emb, _q, _live = small_segment
    t = seg.tensors
    cent = (t.cent if chunk is None
            else _centroids(t.emb_r, t.r_ids, t.r_w, chunk=chunk))
    old = jnp.einsum("nh,nhm->nm", seg.docs.weights, emb[seg.docs.ids])
    assert cent.shape == old.shape == (208, SPEC["emb_dim"])
    np.testing.assert_allclose(np.asarray(cent), np.asarray(old),
                               rtol=2e-6, atol=2e-6)


def test_segment_bytes_per_document_at_set1_shape():
    """At h1 128 and m 300, a document adds at most ≈ 5 KB to a segment,
    and no tensor has a row per word slot of width m."""
    m, vocab = 300, 4096
    spec = dict(SPEC, vocab_size=vocab, emb_dim=m, mean_h=107.5)
    c = corpus_lib.make_corpus(dict(spec, n_docs=512), 5)
    emb = corpus_lib.make_embeddings(spec, c.model.word_topic, c.device_seed)
    segs = {}
    for n in (256, 512):
        docs = DocSet(ids=jnp.asarray(c.ids[:n]),
                      weights=jnp.asarray(c.weights[:n]))
        segs[n] = EngineSegment(docs, emb, offset=0, vocab_pad=vocab)
        for x in segs[n].tensors:
            assert x.shape[0] != n * H1 or x.ndim == 1, x.shape
    assert segs[256].tensors.emb_r.shape == segs[512].tensors.emb_r.shape
    per_doc = (segs[512].nbytes - segs[256].nbytes) / 256
    assert per_doc <= 5 * 1024, per_doc
