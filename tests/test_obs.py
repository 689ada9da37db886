"""Observability plane: metrics registry, traces, events, re-trace sentinel.

Covers the PR 8 acceptance surface: the sentinel must catch a
deliberately induced recompile and stay silent over a warm serving run;
per-query trace timelines must be complete and monotone through both
front-ends (including degraded-tier and quarantined queries); and
``metrics_snapshot()`` must stay consistent (and JSON-able) under
concurrent submit/collect.
"""

import functools
import json
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.data.synth import CorpusSpec, make_corpus
from repro.launch.mesh import make_host_mesh
from repro.obs import (
    STAGES,
    EventLog,
    MetricsRegistry,
    Observability,
    RetraceError,
    TierTransition,
    render_prometheus,
    sentinel,
)
from repro.serving import (
    Answer,
    AsyncQueryServer,
    FaultPlan,
    PoisonQuery,
    QueryServer,
    ServerConfig,
)


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CorpusSpec(
        n_docs=128, vocab_size=512, emb_dim=32, h_max=12, mean_h=8.0,
        n_classes=4, seed=29))


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


@pytest.fixture()
def clean_sentinel():
    """Isolate each sentinel test from process-wide state (the sentinel is
    a singleton because the jit caches it watches are)."""
    s = sentinel.get_sentinel()
    strict = s.strict
    sentinel.reset()
    s.strict = False
    yield s
    sentinel.reset()
    s.strict = strict


def _qs(corpus, n, seed=0):
    rng = np.random.default_rng(seed)
    ids = np.asarray(corpus.docs.ids)
    w = np.asarray(corpus.docs.weights)
    picks = rng.integers(0, corpus.docs.n_docs, n)
    return [(ids[i], w[i]) for i in picks], picks


def _cfg(**kw):
    base = dict(k=4, max_batch=8, h_max=12, max_wait_s=0.02)
    base.update(kw)
    return ServerConfig(**base)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = reg.gauge("g", "a gauge")
    g.set(7)
    g.inc(-2)
    assert g.value == 5.0
    h = reg.histogram("h_seconds", "a histogram")
    for v in (1e-5, 2e-5, 4e-5, 1.0):
        h.observe(v)
    assert h.total == 4
    assert h.sum == pytest.approx(1.00007)
    # Same (name, labels) returns the SAME child.
    assert reg.counter("c_total") is c


def test_histogram_percentiles_bounded_error():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "x")
    rng = np.random.default_rng(0)
    samples = rng.uniform(1e-3, 1e-1, 2000)
    for v in samples:
        h.observe(v)
    for p in (0.5, 0.95, 0.99):
        est = h.percentile(p)
        true = float(np.quantile(samples, p))
        # Factor-2 log buckets bound quantile error to one bucket width.
        assert true / 2 <= est <= true * 2


def test_disabled_registry_is_inert():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c", "x")
    h = reg.histogram("h", "x")
    c.inc(100)
    h.observe(1.0)
    assert c.value == 0.0 and h.total == 0


def test_metric_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("dual", "x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("dual", "x")


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", labels={"tier": "0"}).inc(3)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = render_prometheus(reg)
    assert '# TYPE req_total counter' in text
    assert 'req_total{tier="0"} 3' in text
    # Cumulative buckets incl. the +Inf overflow, plus _sum/_count.
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_count 2' in text


def test_snapshot_is_jsonable():
    obs = Observability()
    obs.metrics.histogram("h", "x").observe(0.5)
    obs.events.append(TierTransition(tier=1, reason="test"))
    json.dumps(obs.snapshot())   # must not raise


def test_snapshot_survives_a_series_registered_meanwhile(monkeypatch):
    """A series registered while the snapshot computes percentiles (outside
    its lock) neither breaks the snapshot nor enters it half-made."""
    reg = MetricsRegistry()
    for stage in ("a", "b"):
        reg.histogram("h", labels={"stage": stage}).observe(0.5)
    hist_cls = type(reg.histogram("h", labels={"stage": "a"}))
    percentile = hist_cls.percentile

    def registering(self, p):
        reg.histogram("h", labels={"stage": f"new{p}"}).observe(1.0)
        return percentile(self, p)

    monkeypatch.setattr(hist_cls, "percentile", registering)
    snap = reg.snapshot()
    assert [s["labels"] for s in snap["h"]["series"]] == [
        {"stage": "a"}, {"stage": "b"}]
    assert snap["h"]["series"][0]["p50"] == pytest.approx(
        percentile(reg.histogram("h", labels={"stage": "a"}), 0.5))


def test_event_ring_is_bounded():
    log = EventLog(maxlen=4)
    for i in range(10):
        log.append(TierTransition(tier=i, reason="r"))
    snap = log.snapshot()
    assert len(snap) == 4
    assert [e["tier"] for e in snap] == [6, 7, 8, 9]
    assert all(e["kind"] == "TierTransition" and e["t"] > 0 for e in snap)


# ---------------------------------------------------------------------------
# Re-trace sentinel
# ---------------------------------------------------------------------------

def test_sentinel_catches_induced_recompile(clean_sentinel):
    """Armed sentinel: a changed static shape IS a new trace — flagged."""
    f = sentinel.wrap("t.armed", jax.jit(lambda x: x * 2))
    f(jnp.ones(4))
    sentinel.arm()
    f(jnp.ones(4))                       # cached: silent
    assert not clean_sentinel.unexpected
    f(jnp.ones(8))                       # induced recompile
    bad = clean_sentinel.unexpected
    assert len(bad) == 1 and bad[0]["kind"] == "retrace-while-armed"
    with pytest.raises(RetraceError):
        sentinel.check()


def test_sentinel_flags_seen_signature_retrace(clean_sentinel):
    """Unarmed: the PR 5 bug class — same abstract signature, fresh trace
    every call (here: an identity-keyed static argument)."""

    class Opaque:
        def __repr__(self):
            return "Opaque()"

    @functools.partial(jax.jit, static_argnums=0)
    def f(o, x):
        return x + 1

    w = sentinel.wrap("t.seen", f)
    x = jnp.ones(3)
    w(Opaque(), x)                       # first trace of this signature: fine
    assert not clean_sentinel.unexpected
    w(Opaque(), x)                       # fresh static identity → re-trace
    bad = clean_sentinel.unexpected
    assert bad and bad[0]["kind"] == "retrace-of-seen-signature"


def test_sentinel_strict_raises_at_call_site(clean_sentinel):
    clean_sentinel.strict = True
    f = sentinel.wrap("t.strict", jax.jit(lambda x: x + 1))
    f(jnp.ones(2))
    sentinel.arm()
    with pytest.raises(RetraceError, match="t.strict"):
        f(jnp.ones(5))


def test_sentinel_expect_scope_allows_rebuild(clean_sentinel):
    f = sentinel.wrap("t.expect", jax.jit(lambda x: x - 1))
    f(jnp.ones(2))
    sentinel.arm()
    with sentinel.expect("deliberate rebuild"):
        f(jnp.ones(9))
    assert not clean_sentinel.unexpected
    sentinel.check()                     # no violations accumulated


@pytest.mark.timeout(120)
def test_sentinel_silent_across_warm_serving_run(corpus, mesh,
                                                 clean_sentinel):
    """Warm server + armed sentinel: three full-batch flushes must not
    trace anything new (the steady-state compile-free contract)."""
    server = QueryServer(corpus.docs, corpus.emb, mesh,
                         _cfg(max_wait_s=5.0))
    stream, _ = _qs(corpus, 8, seed=1)
    for ids, w in stream:
        server.submit(ids, w)
    server.flush()                       # compile warm-up
    sentinel.arm()
    for flush in range(3):
        for ids, w in stream:
            server.submit(ids, w)
        answers = server.flush()
        assert len(answers) == 8
    assert clean_sentinel.snapshot()["unexpected"] == []
    sentinel.check()


# ---------------------------------------------------------------------------
# Request traces
# ---------------------------------------------------------------------------

def _assert_timeline_ok(tr, expect_stages=STAGES):
    assert tr is not None and tr.done
    tl = tr.timeline()
    names = [n for n, _, _ in tl]
    assert set(names) == set(expect_stages)
    starts = [t0 for _, t0, _ in tl]
    assert starts == sorted(starts)
    assert all(t1 >= t0 for _, t0, t1 in tl)
    d = tr.to_dict()
    json.dumps(d)
    assert {s["stage"] for s in d["spans"]} == set(expect_stages)


@pytest.mark.timeout(120)
def test_sync_answers_carry_complete_trace(corpus, mesh):
    server = QueryServer(corpus.docs, corpus.emb, mesh, _cfg(max_wait_s=5.0))
    stream, _ = _qs(corpus, 8, seed=2)
    for ids, w in stream:
        server.submit(ids, w)
    answers = server.flush()
    for a in answers:
        _assert_timeline_ok(a.trace)
        assert a.trace.tier == a.tier


@pytest.mark.timeout(120)
def test_async_futures_carry_complete_trace(corpus, mesh):
    with AsyncQueryServer(corpus.docs, corpus.emb, mesh, _cfg()) as server:
        stream, _ = _qs(corpus, 12, seed=3)
        futs = [server.submit(ids, w) for ids, w in stream]
        server.drain()
        for f in futs:
            a = f.result(timeout=60)
            _assert_timeline_ok(f.trace)
            assert f.trace is a.trace
            assert f.trace.batch is not None
            # queue_wait opens at admission, before batch_formation.
            spans = dict((n, (t0, t1)) for n, t0, t1 in f.trace.timeline())
            assert spans["queue_wait"][0] <= spans["batch_formation"][0]


@pytest.mark.timeout(180)
def test_degraded_tier_stamped_in_trace(corpus, mesh):
    stream, _ = _qs(corpus, 48, seed=13)
    cfg = _cfg(max_batch=4, max_wait_s=0.001, degradation=True,
               shed_queue_depth=8, recover_after=2, queue_capacity=64)
    with AsyncQueryServer(corpus.docs, corpus.emb, mesh, cfg) as server:
        futs = [server.submit(ids, w) for ids, w in stream]
        server.drain()
        degraded = 0
        for f in futs:
            a = f.result(timeout=30)
            _assert_timeline_ok(f.trace)
            assert f.trace.tier == a.tier
            degraded += a.tier > 0
    assert degraded, "flood never engaged degradation"
    # Tier transitions landed in the event log too.
    kinds = [e["kind"] for e in server.obs.events.snapshot()]
    assert "TierTransition" in kinds


@pytest.mark.timeout(120)
def test_quarantined_query_error_carries_trace(corpus, mesh):
    ids = np.asarray(corpus.docs.ids)[:8].copy()
    w = np.asarray(corpus.docs.weights)[:8].copy()
    marker = 509
    ids[3, 0] = marker
    plan = FaultPlan(poison_word_id=marker)
    with AsyncQueryServer(corpus.docs, corpus.emb, mesh, _cfg(),
                          faults=plan) as server:
        futs = [server.submit(ids[i], w[i]) for i in range(8)]
        server.drain()
        with pytest.raises(PoisonQuery):
            futs[3].result(timeout=60)
    assert futs[3].trace is not None and futs[3].trace.done
    kinds = [e["kind"] for e in server.obs.events.snapshot()]
    assert "QueryQuarantined" in kinds
    healthy = [f.result(timeout=60) for i, f in enumerate(futs) if i != 3]
    for a in healthy:
        assert isinstance(a, Answer) and a.trace is not None


@pytest.mark.timeout(120)
def test_tracing_disabled_costs_nothing_visible(corpus, mesh):
    cfg = _cfg(observability=False, tracing=False)
    with AsyncQueryServer(corpus.docs, corpus.emb, mesh, cfg) as server:
        stream, _ = _qs(corpus, 8, seed=4)
        futs = [server.submit(ids, w) for ids, w in stream]
        server.drain()
        for f in futs:
            assert f.result(timeout=60).trace is None
            assert f.trace is None
    # Handles register at construction, but a disabled registry is inert:
    # nothing ever moves.
    snap = server.metrics_snapshot()["metrics"]
    for fam in snap.values():
        for series in fam["series"]:
            assert series.get("value", 0.0) == 0.0
            assert series.get("count", 0) == 0
    assert server.obs.tracer.snapshot()["queries_traced"] == 0


# ---------------------------------------------------------------------------
# EWMA seeding (satellite: rush-dispatch margin from real data)
# ---------------------------------------------------------------------------

@pytest.mark.timeout(120)
def test_ewma_seeds_from_first_batch_not_cold_default(corpus, mesh):
    with AsyncQueryServer(corpus.docs, corpus.emb, mesh,
                          _cfg(max_wait_s=0.25)) as server:
        core = server._core
        assert core.ewma_latency is None
        # Pre-seed the rush margin falls back to the config flush wait,
        # not a hardcoded cold constant.
        assert server._rush_margin() == pytest.approx(0.25)
        stream, _ = _qs(corpus, 8, seed=5)
        futs = [server.submit(ids, w) for ids, w in stream]
        server.drain()
        [f.result(timeout=60) for f in futs]
        ewma = core.ewma_latency
        assert ewma is not None and ewma > 0
        assert server._rush_margin() == pytest.approx(max(0.001, ewma))
        # Mirrored in stats and as a gauge.
        assert server.stats_snapshot()["ewma_latency_s"] == pytest.approx(ewma)
        snap = server.metrics_snapshot()["metrics"]
        assert (snap["serving_ewma_latency_seconds"]["series"][0]["value"]
                == pytest.approx(ewma))


# ---------------------------------------------------------------------------
# Snapshot consistency under concurrent submit/collect (satellite: torn reads)
# ---------------------------------------------------------------------------

@pytest.mark.timeout(180)
def test_snapshot_consistent_under_concurrent_load(corpus, mesh):
    stream, _ = _qs(corpus, 64, seed=6)
    stop = threading.Event()
    errs: list = []

    with AsyncQueryServer(corpus.docs, corpus.emb, mesh,
                          _cfg(max_wait_s=0.005)) as server:
        def prober():
            try:
                while not stop.is_set():
                    snap = server.metrics_snapshot()
                    json.dumps(snap)
                    s = snap["stats"]
                    h = server.health()
                    # A consistent snapshot can always account for every
                    # admitted query: answered + queued + in flight.
                    mb = server._core.cfg.max_batch
                    assert s["queries"] >= 0
                    assert (s["batches"] + h["in_flight"] + 1) * mb \
                        + h["queue_depth"] >= s["queries"]
            except Exception as e:  # surfaces in the main thread
                errs.append(e)

        t = threading.Thread(target=prober, daemon=True)
        t.start()
        futs = [server.submit(ids, w) for ids, w in stream]
        server.drain()
        answers = [f.result(timeout=60) for f in futs]
        stop.set()
        t.join(timeout=10)

    assert not errs, errs
    assert len(answers) == 64
    final = server.stats_snapshot()
    assert final["queries"] == 64
    # The snapshot is a copy: mutating it must not touch live stats.
    final["queries"] = -1
    assert server.stats_snapshot()["queries"] == 64
