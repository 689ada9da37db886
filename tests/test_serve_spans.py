"""Serve spans and named scopes: the serve path's host stages on the
profiler's clock (``serve.<stage>`` annotations with the batch number),
one ``serving_stage_seconds`` observation per stage and batch, the named
scopes in the compiled serve step's op metadata, and the queue wait
observed for every query whether or not it carries a trace."""

import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.data.synth import CorpusSpec, make_corpus
from repro.distributed import lcrwmd_dist
from repro.launch.mesh import make_host_mesh
from repro.obs import SERVE_SPANS, Observability
from repro.serving import AsyncQueryServer, QueryServer, ServerConfig

#: The spans a batch meets on the default path (refine on, no rerank, no
#: index): every leaf span but those of the stages it does not run.
PATH_SPANS = tuple(s for s in SERVE_SPANS if s not in ("rerank_launch",
                                                       "route"))
N_QUERIES, MAX_BATCH = 24, 8


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CorpusSpec(
        n_docs=128, vocab_size=512, emb_dim=32, h_max=12, mean_h=8.0,
        n_classes=4, seed=31))


def _cfg(**kw):
    base = dict(k=4, max_batch=MAX_BATCH, h_max=12, max_wait_s=0.02)
    base.update(kw)
    return ServerConfig(**base)


def _stream(corpus, n, seed=0):
    rng = np.random.default_rng(seed)
    ids = np.asarray(corpus.docs.ids)
    w = np.asarray(corpus.docs.weights)
    return [(ids[i], w[i]) for i in rng.integers(0, corpus.docs.n_docs, n)]


def _serve_all(server, stream):
    futs = [server.submit(ids, w) for ids, w in stream]
    server.drain()
    return [f.result(timeout=60) for f in futs]


def _series(server, name):
    """{labels-as-tuple: count} of one registry family."""
    fam = server.metrics_snapshot()["metrics"].get(name, {"series": []})
    return {tuple(sorted(s["labels"].items())): s["count"]
            for s in fam["series"]}


@pytest.fixture(scope="module")
def traced(corpus, tmp_path_factory):
    """A warm AsyncQueryServer serving N_QUERIES under the profiler:
    (server, [(line key, name, start_ns, end_ns, stats)] of serve spans)."""
    from jax.profiler import ProfileData

    log_dir = str(tmp_path_factory.mktemp("profile"))
    with AsyncQueryServer(corpus.docs, corpus.emb, make_host_mesh(),
                          _cfg(tracing=False)) as server:
        _serve_all(server, _stream(corpus, 2 * MAX_BATCH, seed=1))  # warm
        with jax.profiler.trace(log_dir):
            _serve_all(server, _stream(corpus, N_QUERIES, seed=2))
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    spans = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.append(((p, i), ev.name, ev.start_ns, ev.end_ns,
                                  dict(ev.stats)))
    return server, spans


@pytest.mark.timeout(180)
@pytest.mark.parametrize("stage", PATH_SPANS)
def test_span_on_profiler_clock_with_batch(traced, stage):
    _server, spans = traced
    mine = [s for s in spans if s[1] == f"serve.{stage}"]
    assert mine, f"no serve.{stage} in the trace"
    assert all(isinstance(s[4].get("batch"), int) for s in mine)


@pytest.mark.timeout(180)
def test_worker_spans_do_not_overlap(traced):
    """Leaf spans only: an enclosing span would label every idle gap."""
    _server, spans = traced
    by_line: dict = {}
    for key, name, s, e, _stats in spans:
        by_line.setdefault(key, []).append((s, e, name))
    assert by_line
    for evs in by_line.values():
        evs.sort()
        for (s0, e0, n0), (s1, _e1, n1) in zip(evs, evs[1:]):
            assert s1 >= e0, f"{n1} opens inside {n0}"


@pytest.mark.timeout(180)
def test_batch_numbers_follow_dispatch_order(traced):
    _server, spans = traced
    steps = sorted((s, st["batch"]) for _k, n, s, _e, st in spans
                   if n == "serve.step_launch")
    seqs = [b for _s, b in steps]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


@pytest.mark.timeout(180)
@pytest.mark.parametrize("stage", PATH_SPANS)
def test_stage_count_equals_batches(traced, stage):
    server, _spans = traced
    batches = server.stats["batches"]
    assert batches >= (2 * MAX_BATCH + N_QUERIES) // MAX_BATCH
    if stage == "collect":   # an older series holds it alone
        counts = _series(server, "serving_device_collect_seconds")
        assert counts == {(): batches}
        assert (("stage", "collect"),) not in _series(
            server, "serving_stage_seconds")
    else:
        counts = _series(server, "serving_stage_seconds")
        assert counts[(("stage", stage),)] == batches


@pytest.mark.timeout(180)
def test_removed_series_stay_gone(traced):
    server, _spans = traced
    names = set(server.metrics_snapshot()["metrics"])
    assert "serve_step_host_seconds" not in names
    assert not any(n.startswith("serve_step_collectives_") for n in names)


@pytest.mark.timeout(180)
@pytest.mark.parametrize("front", ["sync", "async"])
def test_queue_wait_observed_without_tracing(corpus, front):
    """serving_queue_wait_seconds counts every dispatched query with the
    per-query tracer off."""
    stream = _stream(corpus, 20, seed=3)
    cfg = _cfg(tracing=False)
    if front == "async":
        with AsyncQueryServer(corpus.docs, corpus.emb, make_host_mesh(),
                              cfg) as server:
            answers = _serve_all(server, stream)
    else:
        server = QueryServer(corpus.docs, corpus.emb, make_host_mesh(), cfg)
        for ids, w in stream:
            server.submit(ids, w)
        answers = server.flush()
    assert all(a.trace is None for a in answers)
    assert server.obs.tracer.snapshot()["queries_traced"] == 0
    assert _series(server, "serving_queue_wait_seconds") == {
        (): server.stats["queries"]}
    assert server.stats["queries"] == len(stream)


@pytest.mark.timeout(120)
def test_span_widens_batch_timeline_stage():
    from repro.obs import BatchTrace

    obs = Observability()
    bt = BatchTrace(7)
    with obs.in_batch(7, bt):
        with obs.span("gather_queries"):
            pass
        with obs.span("step_launch"):
            pass
    t0, t1 = bt.spans["dispatch"]
    assert t0 <= t1
    hist = obs.metrics.snapshot()["serving_stage_seconds"]["series"]
    assert {s["labels"]["stage"]: s["count"] for s in hist} == {
        "gather_queries": 1, "step_launch": 1}
    with obs.span("wait", observe=False) as sp:
        pass
    assert sp.seconds >= 0.0
    assert "wait" not in {s["labels"]["stage"] for s in
                          obs.metrics.snapshot()["serving_stage_seconds"][
                              "series"]}


# -- named scopes in the compiled programs ------------------------------------
@pytest.fixture(scope="module")
def step_op_names(corpus, monkeypatch_module):
    """The op_name metadata of a segmented server's compiled serve step."""
    seen = {}
    build = lcrwmd_dist._segmented_step

    def recording(*a, **kw):
        step = build(*a, **kw)

        def call(*args):
            seen["args"] = args
            return step(*args)
        seen["step"] = step
        return call

    monkeypatch_module.setattr(lcrwmd_dist, "_segmented_step", recording)
    with AsyncQueryServer(corpus.docs, corpus.emb, make_host_mesh(),
                          _cfg()) as server:
        _serve_all(server, _stream(corpus, 4, seed=4))
    text = seen["step"].lower(*seen["args"]).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


@pytest.mark.timeout(240)
@pytest.mark.parametrize("scope", ["phase1", "phase2", "topk_fold",
                                   "crossshard_topk"])
def test_serve_step_carries_named_scope(step_op_names, scope):
    assert any(f"/{scope}/" in n for n in step_op_names), sorted(
        step_op_names)[:20]


@pytest.mark.timeout(240)
def test_topk_fold_nests_inside_phase2_loop(step_op_names):
    """The fold is its own scope inside the slab loop: the innermost
    scope of a path names the op's part."""
    folds = [n for n in step_op_names if "/topk_fold/" in n
             and n.startswith("jit(step)/")]
    assert folds and all("/phase2/" in n for n in folds)


@pytest.mark.timeout(240)
def test_symmetric_refine_carries_refine_scope(corpus):
    from repro.core.lc_rwmd import SegmentedEngine
    from repro.core.topk import TopK

    eng = SegmentedEngine(corpus.docs, corpus.emb)
    q = corpus.docs
    b = 4
    queries = type(q)(ids=q.ids[:b], weights=q.weights[:b])
    tk = TopK(jax.numpy.zeros((b, 3)), jax.numpy.tile(
        jax.numpy.arange(3, dtype=jax.numpy.int32), (b, 1)))
    text = lcrwmd_dist._symmetric_refine.lower(
        eng.resident, queries, eng.emb_full, tk).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    assert any("/refine/" in n for n in names), sorted(names)[:20]
