"""Fused streaming LC-RWMD + serve-time engine vs the two-phase oracles.

Covers the streaming contract (vocab scanned in chunks, Z never materialized
at (v, B)), all three fuse backends in interpret mode, and engine-vs-function
parity for the one-sided / symmetric / top-k entry points.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lc_rwmd import (
    SegmentedEngine,
    lc_rwmd_one_sided,
    lc_rwmd_streaming,
    lc_rwmd_symmetric,
    phase1_z,
)
from repro.core.pipeline import pruned_wmd_topk
from repro.core.topk import topk_smallest_cols
from repro.data.docs import DocSet


# ---------------------------------------------------------------------------
# Fused streaming vs two-phase oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fuse", ["jnp", "scan", "kernel"])
def test_streaming_matches_two_phase(small_corpus, fuse):
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    queries = ds[:5]
    want = lc_rwmd_one_sided(ds, queries, emb)
    got = lc_rwmd_streaming(
        ds, queries, emb, vocab_chunk=128, fuse=fuse, block_v=64,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("vocab_chunk", [64, 100, 512, 4096])
def test_streaming_chunk_invariance(small_corpus, vocab_chunk):
    """Any chunking (divisible or not, larger than v or not) is exact."""
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    queries = ds[:4]
    want = lc_rwmd_one_sided(ds, queries, emb)
    got = lc_rwmd_streaming(
        ds, queries, emb, vocab_chunk=vocab_chunk, fuse="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-2)


def test_phase1_z_non_divisible_chunk(small_corpus):
    """phase1_z pads (instead of raising) when vocab_chunk ∤ v."""
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    q = ds[:4]
    a = phase1_z(emb, q.ids, q.weights)
    b = phase1_z(emb, q.ids, q.weights, vocab_chunk=77)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-2)


def test_one_sided_kernel_path_threads_bf16(small_corpus):
    """use_kernel=True must actually honor bf16_matmul (regression: it was
    silently dropped) — bf16 results differ from fp32 but stay within the
    documented gram-expansion noise floor."""
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    q = ds[:4]
    f32 = lc_rwmd_one_sided(ds, q, emb, use_kernel=True, interpret=True)
    bf16 = lc_rwmd_one_sided(
        ds, q, emb, use_kernel=True, bf16_matmul=True, interpret=True)
    assert not np.allclose(np.asarray(f32), np.asarray(bf16), atol=1e-6)
    np.testing.assert_allclose(np.asarray(bf16), np.asarray(f32),
                               rtol=5e-2, atol=0.7)


# ---------------------------------------------------------------------------
# Engine vs function parity
# ---------------------------------------------------------------------------
def test_engine_one_sided_parity(small_corpus):
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    queries = ds[:6]
    eng = SegmentedEngine(ds, emb)
    want = lc_rwmd_one_sided(ds, queries, emb)
    np.testing.assert_allclose(np.asarray(eng.one_sided(queries)),
                               np.asarray(want), rtol=1e-4, atol=1e-2)


def test_engine_symmetric_parity(small_corpus):
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    queries = ds[:6]
    eng = SegmentedEngine(ds, emb)
    want = lc_rwmd_symmetric(ds, queries, emb)
    np.testing.assert_allclose(np.asarray(eng.symmetric(queries)),
                               np.asarray(want), rtol=1e-4, atol=1e-2)


def test_engine_symmetric_parity_chunked_and_kernel(small_corpus):
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    queries = ds[3:8]
    want = lc_rwmd_symmetric(ds, queries, emb)
    eng = SegmentedEngine(ds, emb, vocab_chunk=100)
    np.testing.assert_allclose(np.asarray(eng.symmetric(queries)),
                               np.asarray(want), rtol=1e-4, atol=1e-2)


def test_engine_handles_oov_query_words(small_corpus):
    """Query words OUTSIDE the resident vocabulary stay exact: the engine
    restricts the phase-1 vocab axis but gathers queries from the full
    table (plain restrict_vocab usage cannot serve such queries)."""
    ds_full = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    resident = ds_full[:20]   # restricted vocab = words of 20 docs only
    queries = ds_full[60:64]  # almost surely contains out-of-resident words
    eng = SegmentedEngine(resident, emb)
    want = lc_rwmd_symmetric(resident, queries, emb)
    np.testing.assert_allclose(np.asarray(eng.symmetric(queries)),
                               np.asarray(want), rtol=1e-4, atol=1e-2)


def test_engine_topk_parity(small_corpus):
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    queries = ds[:5]
    eng = SegmentedEngine(ds, emb)
    tk = eng.topk(queries, 7)
    want = topk_smallest_cols(lc_rwmd_symmetric(ds, queries, emb), 7)
    np.testing.assert_allclose(np.asarray(tk.dists), np.asarray(want.dists),
                               rtol=1e-4, atol=1e-2)


def test_pruned_wmd_topk_engine_parity(small_corpus):
    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    resident, queries = ds[:32], ds[40:43]
    sink = dict(eps=0.05, eps_scaling=2, max_iters=100)
    base = pruned_wmd_topk(resident, queries, emb, k=4, refine_budget=8,
                           sinkhorn_kw=sink)
    eng = pruned_wmd_topk(resident, queries, emb, k=4, refine_budget=8,
                          sinkhorn_kw=sink,
                          engine=SegmentedEngine(resident, emb))
    np.testing.assert_allclose(np.asarray(eng.topk.dists),
                               np.asarray(base.topk.dists),
                               rtol=1e-4, atol=1e-2)


def test_engine_serve_step_parity(small_corpus):
    """Engine-backed distributed serve == engine-less serve on the host mesh.

    The engine-less step materializes each shard's (n_local, B) block and
    returns it as d_local; the segmented step streams the same top-k from
    (B, k)-sized per-shard partials (d_local absent — the block never
    exists).
    """
    from repro.distributed.lcrwmd_dist import build_serve_step
    from repro.launch.mesh import make_host_mesh

    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    queries = ds[:5]
    mesh = make_host_mesh(data=1, model=1)
    eng = SegmentedEngine(ds, emb)
    base = build_serve_step(mesh, k=7, bf16_matmul=False)(ds, queries, emb)
    stream = build_serve_step(mesh, k=7, bf16_matmul=False,
                              engine=eng)(queries)
    assert stream.d_local is None
    np.testing.assert_array_equal(np.asarray(stream.topk.indices),
                                  np.asarray(base.topk.indices))
    np.testing.assert_allclose(np.asarray(stream.topk.dists),
                               np.asarray(base.topk.dists),
                               rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("engine", ["segment", "docset"])
def test_serve_step_rejects_other_engines(small_corpus, engine):
    """An engine-backed step takes a SegmentedEngine and nothing else."""
    from repro.core.lc_rwmd import EngineSegment
    from repro.distributed.lcrwmd_dist import build_serve_step
    from repro.launch.mesh import make_host_mesh

    ds = small_corpus.docs
    emb = jnp.asarray(small_corpus.emb)
    other = (EngineSegment(ds, emb, offset=0) if engine == "segment"
             else ds)
    with pytest.raises(TypeError, match="SegmentedEngine"):
        build_serve_step(make_host_mesh(data=1, model=1), k=7,
                         bf16_matmul=False, engine=other)
