"""Late batch formation in AsyncQueryServer: while a batch is in flight, a
partial batch is held until the batch in flight nears its predicted end
(less one host lead), and queries arriving meanwhile join it.

A fake serve step stands in for the device: it runs batches one after
another for ``PERIOD`` seconds each, its launch takes ``LEAD`` seconds of
host time inside the ``step_launch`` span, and its results turn ready at
their batch's end.  The core's ``trace`` hook, with a time on each event,
tells when each batch was dispatched and collected.
"""

import threading
import time

import numpy as np
import pytest

from repro.core.topk import TopK
from repro.data.synth import CorpusSpec, make_corpus
from repro.distributed.lcrwmd_dist import ServeResult
from repro.launch.mesh import make_host_mesh
from repro.serving import AsyncQueryServer, ServerConfig

PERIOD = 0.15    # device seconds a batch takes
LEAD = 0.03      # host seconds of the step launch
MAX_WAIT = 0.03  # max_wait_s: far shorter than a period
MAX_BATCH = 8


class _Ready:
    """A device array that turns ready at ``t``; readback blocks until then."""

    def __init__(self, value, t):
        self.value, self.t = value, t

    def is_ready(self):
        return time.perf_counter() >= self.t

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self.t - time.perf_counter()))
        return self.value if dtype is None else self.value.astype(dtype)


class FakeDevice:
    """A serial device: a batch starts when its launch returns or when the
    batch before it ends, whichever is later."""

    def __init__(self, obs, k):
        self.obs, self.k = obs, k
        self.period = PERIOD
        self.free_at = 0.0
        self.batches = []   # (real queries, launch return, start, end)

    def __call__(self, queries, tier=0):
        with self.obs.span("step_launch"):
            time.sleep(LEAD)
            now = time.perf_counter()
        start = max(now, self.free_at)
        self.free_at = end = start + self.period
        n_real = int((np.asarray(queries.weights) > 0).any(axis=1).sum())
        self.batches.append((n_real, now, start, end))
        b = queries.ids.shape[0]
        return ServeResult(
            topk=TopK(_Ready(np.zeros((b, self.k), np.float32), end),
                      _Ready(np.zeros((b, self.k), np.int32), end)),
            d_local=None)


class Timed(list):
    """The core's trace hook, with the time of each event."""

    def append(self, event):
        super().append((*event, time.perf_counter()))

    def at(self, kind, seq):
        return next(t for k, s, t in self if (k, s) == (kind, seq))


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CorpusSpec(
        n_docs=64, vocab_size=256, emb_dim=16, h_max=12, mean_h=6.0,
        n_classes=4, seed=23))


def _queries(corpus, n, seed=0):
    rng = np.random.default_rng(seed)
    ids = np.asarray(corpus.docs.ids)
    w = np.asarray(corpus.docs.weights)
    return [(ids[i], w[i]) for i in rng.integers(0, corpus.docs.n_docs, n)]


def _held(server):
    """(serving_batches_held_total, count and sum of stage=hold)."""
    m = server.metrics_snapshot()["metrics"]
    held = m.get("serving_batches_held_total", {"series": []})["series"]
    stage = [s for s in m.get("serving_stage_seconds", {"series": []})[
        "series"] if s["labels"] == {"stage": "hold"}]
    return (held[0]["value"] if held else 0.0,
            stage[0]["count"] if stage else 0,
            stage[0]["sum"] if stage else 0.0)


@pytest.fixture
def rig(corpus):
    """A server on the fake device, primed with six full batches queued
    back to back (enough for the clock's period and lead readings)."""
    server = AsyncQueryServer(
        corpus.docs, corpus.emb, make_host_mesh(),
        ServerConfig(k=4, max_batch=MAX_BATCH, h_max=12, max_wait_s=MAX_WAIT))
    dev = FakeDevice(server.obs, server.cfg.k)
    server._serve = dev
    trace = Timed()
    server._core.trace = trace
    for f in [server.submit(*q) for q in _queries(corpus, 6 * MAX_BATCH)]:
        f.result(timeout=30)
    assert len(dev.batches) == 6
    yield server, dev, trace, _queries(corpus, 32, seed=1)
    server.close(timeout=30)


def _in_flight_then_partial(server, dev, qs, n_partial=3):
    """A full batch X and ``n_partial`` more queries queue at once: X goes
    in flight, the rest stay queued behind it.  Returns X's batch number,
    its device end and the futures."""
    seq = server._core.next_seq
    futs = [server.submit(*q) for q in qs[:MAX_BATCH + n_partial]]
    time.sleep(LEAD + 0.01)           # X launched: on the device
    assert len(dev.batches) == 7
    return seq, dev.batches[-1][3], futs


@pytest.mark.timeout(120)
def test_partial_batch_formed_at_predicted_end_less_lead(rig):
    server, dev, trace, qs = rig
    x, end_x, futs = _in_flight_then_partial(server, dev, qs)
    for f in futs:
        f.result(timeout=30)
    t_form = trace.at("dispatch", x + 1)
    # Not at staleness (MAX_WAIT after the queries came, ≈ one period
    # before X's end): no earlier than X's end less the lead and a margin,
    # and before X is ready.
    assert end_x - 2 * LEAD <= t_form < end_x
    assert t_form < trace.at("collect", x)
    assert dev.batches[-1][0] == 3
    assert _held(server)[:2] == (1.0, 1)


@pytest.mark.timeout(120)
def test_arrivals_during_the_hold_join_the_batch(rig):
    server, dev, trace, qs = rig
    x, end_x, futs = _in_flight_then_partial(server, dev, qs)
    time.sleep(0.04)                  # inside the hold
    assert server.stats["batches"] == 7
    futs += [server.submit(*q) for q in qs[MAX_BATCH + 3:MAX_BATCH + 5]]
    for f in futs:
        f.result(timeout=30)
    assert [b[0] for b in dev.batches[-2:]] == [MAX_BATCH, 5]
    assert trace.at("dispatch", x + 1) >= end_x - 2 * LEAD


@pytest.mark.timeout(120)
@pytest.mark.parametrize("trigger", ["full", "flush", "close", "rush"])
def test_full_flush_close_or_rush_dispatch_at_once(rig, trigger):
    server, dev, trace, qs = rig
    x, end_x, futs = _in_flight_then_partial(server, dev, qs)
    time.sleep(0.02)                  # inside the hold
    t_trigger = time.perf_counter()
    if trigger == "full":
        futs += [server.submit(*q) for q in qs[MAX_BATCH + 3:2 * MAX_BATCH]]
    elif trigger == "flush":
        server.flush()
    elif trigger == "close":
        threading.Thread(target=server.close, daemon=True).start()
    else:   # a deadline inside the rush margin
        futs.append(server.submit(*qs[MAX_BATCH + 3],
                                  deadline=server._rush_margin()))
    for f in futs:
        f.exception(timeout=30)       # a rushed query may miss its deadline
    t_form = trace.at("dispatch", x + 1)
    # Held until the trigger, then formed long before the hold's end.
    assert 0 <= t_form - t_trigger < 0.5 * (end_x - LEAD - t_trigger)
    assert dev.batches[-1][0] == (MAX_BATCH if trigger == "full" else
                                  4 if trigger == "rush" else 3)


@pytest.mark.timeout(120)
def test_nothing_in_flight_follows_max_wait(rig):
    server, dev, trace, qs = rig
    seq = server._core.next_seq
    t_submit = time.perf_counter()
    futs = [server.submit(*q) for q in qs[:3]]
    for f in futs:
        f.result(timeout=30)
    t_form = trace.at("dispatch", seq)
    assert MAX_WAIT <= t_form - t_submit < MAX_WAIT + 0.5 * PERIOD
    assert _held(server) == (0.0, 0, 0.0)


@pytest.mark.timeout(120)
def test_ready_batch_in_flight_collected_at_once(rig):
    """A prediction that comes late (X ends far sooner than the periods
    seen) does not hold X's answers: X is collected when ready and the
    partial batch dispatches after it."""
    server, dev, trace, qs = rig
    dev.period = 0.03
    x, end_x, futs = _in_flight_then_partial(server, dev, qs)
    for f in futs:
        f.result(timeout=30)
    t_collect = trace.at("collect", x)
    assert t_collect - end_x < 0.5 * (PERIOD - dev.period)
    assert trace.at("dispatch", x + 1) > t_collect


@pytest.mark.timeout(120)
def test_held_counter_and_span_count_held_batches_only(rig):
    server, dev, trace, qs = rig
    assert _held(server) == (0.0, 0, 0.0)   # full batches are never held
    x, end_x, futs = _in_flight_then_partial(server, dev, qs)
    for f in futs:
        f.result(timeout=30)
    held, count, seconds = _held(server)
    assert (held, count) == (1.0, 1)
    # The hold began when the partial batch queued and ended at its
    # formation: most of X's period.
    assert 0.5 * PERIOD < seconds < PERIOD
    for f in [server.submit(*q) for q in qs[20:23]]:   # nothing in flight
        f.result(timeout=30)
    assert _held(server)[:2] == (1.0, 1)
    assert server.stats["batches"] == 9
