"""Corpus-lifecycle benchmark: incremental ingest, churn serving, tenancy.

Measures the three claims of the segmented-engine PR, persisted as
``BENCH_lifecycle.json``:

1. ``ingest_delta_vs_rebuild`` — appending a small delta to a large corpus
   builds ONE delta-sized :class:`~repro.core.lc_rwmd.EngineSegment`
   (O(delta) vocab restriction + gathers) instead of re-running the full
   O(corpus) engine build.  The ``speedup`` derived is the acceptance
   number: >= 5x at base n >= 2048, delta <= 128 (measured ~1-2 orders of
   magnitude on XLA:CPU — the delta build does ~n_base/n_delta times less
   gather/sort work).  ``LIFECYCLE_BENCH_SOFT=1`` downgrades the assertion
   to a report (loaded CI runners).

2. ``serve_goodput_under_ingest`` — an :class:`AsyncQueryServer` keeps
   answering while deltas are ingested between batches (the manager lock
   serializes ingest against dispatch, never against the producer).  The
   ``goodput_ratio`` derived compares answered-queries/s with periodic
   ingests against an ingest-free run of the same stream.

3. ``tenant_cache`` — three tenant corpora share a
   :class:`~repro.serving.CorpusManager` whose byte budget holds only two:
   a skewed hot/hot/cold access pattern makes the cold tenant's checkout
   evict one hot tenant per round and readmit it from the host snapshot.
   Derived: hit/miss/eviction/readmission counts
   plus the measured hit vs readmission latency (the price of a cache
   miss = one compacted engine rebuild).

Recorded in EXPERIMENTS.md §Lifecycle.
"""

from __future__ import annotations

import os
import time

import numpy as np

import jax

from benchmarks.common import BenchResult, cached_corpus

BASE_N = 2048       # resident corpus size (acceptance floor: >= 2048)
DELTA_N = 128       # ingest delta size (acceptance ceiling: <= 128)
VOCAB = 4096
EMB_DIM = 64
H_MAX = 16
MIN_SPEEDUP = 5.0   # delta ingest vs full rebuild (acceptance criterion)
APPEND_REPS = 5
REBUILD_REPS = 3


def _block_engine(eng) -> None:
    """Block until every segment's device tensors are materialized."""
    for seg in eng.segments:
        jax.block_until_ready(seg.tensors.emb_r)
        jax.block_until_ready(seg.tensors.cent)


def _slice_docs(docs, lo: int, hi: int):
    from repro.data.docs import DocSet

    return DocSet(ids=docs.ids[lo:hi], weights=docs.weights[lo:hi])


def _ingest_vs_rebuild(corpus) -> BenchResult:
    from repro.core.lc_rwmd import SegmentedEngine

    base = _slice_docs(corpus.docs, 0, BASE_N)
    emb = corpus.emb

    # Full rebuild: what every ingest used to cost (O(n_base + delta)).
    rebuild_times = []
    for _ in range(REBUILD_REPS):
        t0 = time.perf_counter()
        eng = SegmentedEngine(_slice_docs(corpus.docs, 0, BASE_N + DELTA_N),
                              emb)
        _block_engine(eng)
        rebuild_times.append(time.perf_counter() - t0)
    t_rebuild = sorted(rebuild_times)[len(rebuild_times) // 2]

    # Delta ingest: one small segment build (O(delta)).  Each rep appends a
    # FRESH delta so no build work is amortized across reps; the engine
    # grows by a few deltas, which only makes the comparison conservative.
    eng = SegmentedEngine(base, emb)
    _block_engine(eng)
    append_times = []
    for r in range(APPEND_REPS):
        lo = BASE_N + (r * DELTA_N) % (corpus.docs.n_docs - BASE_N - DELTA_N)
        delta = _slice_docs(corpus.docs, lo, lo + DELTA_N)
        t0 = time.perf_counter()
        eng.append(delta)
        _block_engine(eng)
        append_times.append(time.perf_counter() - t0)
    t_append = sorted(append_times)[len(append_times) // 2]

    speedup = t_rebuild / t_append
    ok = speedup >= MIN_SPEEDUP
    if not ok and not os.environ.get("LIFECYCLE_BENCH_SOFT"):
        raise AssertionError(
            f"delta ingest speedup {speedup:.1f}x < {MIN_SPEEDUP}x "
            f"(rebuild {t_rebuild * 1e3:.1f} ms vs append "
            f"{t_append * 1e3:.1f} ms)")
    return BenchResult(
        f"lifecycle_ingest_n{BASE_N}_delta{DELTA_N}", t_append * 1e6,
        derived={"rebuild_us": round(t_rebuild * 1e6, 1),
                 "speedup": round(speedup, 1),
                 "min_speedup": MIN_SPEEDUP, "ok": ok})


def _goodput_under_ingest(corpus) -> BenchResult:
    from repro.launch.mesh import make_host_mesh
    from repro.serving import AsyncQueryServer, ServerConfig

    base = _slice_docs(corpus.docs, 0, 512)
    ids = np.asarray(corpus.docs.ids)
    w = np.asarray(corpus.docs.weights)
    rng = np.random.default_rng(0)
    picks = rng.integers(0, 512, 160)
    cfg = ServerConfig(k=8, max_batch=32, h_max=H_MAX, max_wait_s=0.002)
    mesh = make_host_mesh()

    def run(with_ingest: bool) -> float:
        server = AsyncQueryServer(base, corpus.emb, mesh, cfg)
        try:
            server.submit(ids[0], w[0]).result(60)
            t0 = time.perf_counter()
            futs = []
            for j, p in enumerate(picks):
                futs.append(server.submit(ids[p], w[p]))
                if with_ingest and j % 40 == 39:
                    lo = 512 + (j // 40) * 64
                    server.ingest(_slice_docs(corpus.docs, lo, lo + 64))
            server.drain()
            for f in futs:
                f.result(60)
            return len(futs) / (time.perf_counter() - t0)
        finally:
            server.close(timeout=30)

    # Warm-up pass: the segmented serve step is cached at module level
    # keyed by segment SHAPES (``_STEP_CACHE``), so running the full
    # ingest+query sequence once on a throwaway server pre-compiles every
    # segment-count shape the measured pass will touch.  The measured runs
    # then see steady-state goodput — per-batch serve + per-version tensor
    # re-placement — not one-off XLA compilation.
    run(with_ingest=True)
    q_plain = run(with_ingest=False)
    q_ingest = run(with_ingest=True)
    return BenchResult(
        "lifecycle_goodput_under_ingest", 1e6 / q_ingest,
        derived={"qps_plain": round(q_plain, 1),
                 "qps_under_ingest": round(q_ingest, 1),
                 "goodput_ratio": round(q_ingest / q_plain, 3)})


def _tenant_cache(corpus) -> BenchResult:
    from repro.serving import CorpusManager

    from repro.core.lc_rwmd import SegmentedEngine

    tenants = {}
    for t in range(3):
        lo = t * 512
        tenants[f"t{t}"] = _slice_docs(corpus.docs, lo, lo + 512)
    # Budget: two tenants fit, the third forces LRU eviction (sized from a
    # probe engine — admission enforces the budget, so it must be set first).
    one = SegmentedEngine(tenants["t0"], corpus.emb).nbytes
    mgr = CorpusManager(corpus.emb, cache_bytes=int(2.5 * one))
    for cid, docs in tenants.items():
        mgr.add_corpus(cid, docs)
    hit_t, readmit_t = [], []
    # Skewed access: t0/t1 are hot (mostly hits), t2 is the cold tenant
    # whose checkout evicts one of the hot pair each round.
    for _ in range(4):
        for cid in ("t0", "t1", "t0", "t1", "t2"):
            resident = mgr.is_resident(cid)
            t0 = time.perf_counter()
            st = mgr.checkout(cid)
            _block_engine(st.engine)
            dt = time.perf_counter() - t0
            (hit_t if resident else readmit_t).append(dt)
    s = mgr.snapshot()
    med = lambda xs: sorted(xs)[len(xs) // 2] if xs else 0.0
    return BenchResult(
        "lifecycle_tenant_cache_3x2", med(readmit_t) * 1e6,
        derived={"hit_us": round(med(hit_t) * 1e6, 1),
                 "readmit_us": round(med(readmit_t) * 1e6, 1),
                 "hits": s["hits"], "misses": s["misses"],
                 "evictions": s["evictions"],
                 "readmissions": s["readmissions"],
                 "resident_bytes": s["resident_bytes"],
                 "cache_bytes": s["cache_bytes"]})


def run():
    corpus = cached_corpus(n_docs=BASE_N + 8 * DELTA_N, vocab_size=VOCAB,
                           emb_dim=EMB_DIM, h_max=H_MAX, mean_h=10.0,
                           n_classes=8, seed=7)
    yield _ingest_vs_rebuild(corpus)
    yield _goodput_under_ingest(corpus)
    yield _tenant_cache(corpus)
