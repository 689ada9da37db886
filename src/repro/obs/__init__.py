"""`repro.obs` — dependency-free observability for the serving plane.

One :class:`Observability` bundle ties the three signal types together:

* ``obs.metrics`` — :class:`~repro.obs.metrics.MetricsRegistry`
  (counters / gauges / bucketed histograms, Prometheus-exportable).
* ``obs.tracer`` — :class:`~repro.obs.tracing.Tracer` minting per-query
  span timelines.
* ``obs.events`` — :class:`~repro.obs.events.EventLog` ring of typed
  state-change events.

Each server owns its own bundle by default (pass ``obs=`` through
``ServerConfig`` / ``CorpusManager`` to share one across components);
the re-trace sentinel is intentionally NOT per-bundle — it guards
process-wide jit caches, so it lives as a process-wide singleton in
:mod:`repro.obs.sentinel`.

``obs.span(stage)`` opens one leaf span of the serve path
(:class:`~repro.obs.tracing.Span`): a ``serve.<stage>`` annotation on the
profiler's clock, one observation of ``serving_stage_seconds{stage=…}``
and the stage's widening of the batch's :class:`BatchTrace`.

Also here: :func:`walk_eqns`, the repo's one jaxpr walker.
"""

from __future__ import annotations

import threading

from repro.obs import sentinel
from repro.obs.events import (
    BudgetRebuild, CorpusEvicted, CorpusReadmitted, Event, EventLog,
    IngestCrash, QueryQuarantined, TierTransition, WorkerRestart,
)
from repro.obs.metrics import (
    COUNT_BUCKETS, Counter, DEFAULT_BUCKETS, Gauge, Histogram,
    MetricsRegistry,
)
from repro.obs.metrics import render_prometheus as _render_metrics
from repro.obs.sentinel import RetraceError
from repro.obs.tracing import (
    SERVE_SPANS, SPAN_SERIES, STAGE_SERIES, BatchTrace, QueryTrace, Span,
    STAGES, Tracer,
)


class Observability:
    """Bundle of metrics + tracing + events with master switches.

    ``metrics_enabled`` / ``tracing_enabled`` gate each signal
    independently; a fully disabled bundle costs one attribute check per
    instrumentation site (the obs-overhead bench measures both states).
    """

    def __init__(self, *, metrics_enabled: bool = True,
                 tracing_enabled: bool = True, event_capacity: int = 1024):
        self.metrics = MetricsRegistry(enabled=metrics_enabled)
        self.tracer = Tracer(enabled=tracing_enabled)
        self.events = EventLog(maxlen=event_capacity)
        self._stage_hists: dict = {}
        # Per thread: the batch it serves, its spans' last end times.
        self._local = threading.local()

    # -- serve spans -------------------------------------------------------
    def stage_histogram(self, stage: str):
        """The registry histogram that serve span ``stage`` observes."""
        h = self._stage_hists.get(stage)
        if h is None:
            name = SPAN_SERIES.get(stage)
            h = (self.metrics.histogram(name) if name is not None else
                 self.metrics.histogram(
                     STAGE_SERIES, "host time of one serve-path stage "
                     "per batch", labels={"stage": stage}))
            self._stage_hists[stage] = h
        return h

    def span(self, stage: str, *, batch: int | None = None,
             trace: BatchTrace | None = None, observe: bool = True) -> Span:
        """Leaf span ``serve.<stage>`` (see :data:`SERVE_SPANS`).

        ``batch``/``trace`` default to those of the enclosing
        :meth:`in_batch` on this thread.  ``observe=False`` leaves the
        histogram to the caller (:meth:`observe_stage`), for a stage that
        sums several spans into one observation per batch."""
        if batch is None and trace is None:
            ctx = getattr(self._local, "batch", None)
            if ctx is not None:
                batch, trace = ctx
        hist = (self.stage_histogram(stage)
                if observe and self.metrics.enabled else None)
        return Span(stage, batch, hist, trace, self._ends())

    def _ends(self) -> dict:
        ends = getattr(self._local, "ends", None)
        if ends is None:
            ends = self._local.ends = {}
        return ends

    def span_end(self, stage: str) -> float | None:
        """``time.perf_counter()`` at which this thread's latest
        ``serve.<stage>`` span closed; None if it never opened one."""
        return self._ends().get(stage)

    def observe_stage(self, stage: str, seconds: float) -> None:
        if self.metrics.enabled:
            self.stage_histogram(stage).observe(seconds)

    def in_batch(self, batch: int | None, trace: BatchTrace | None = None):
        """Context in which this thread's spans default to ``batch`` and
        ``trace`` (the serve callable opens spans without knowing them)."""
        return _InBatch(self._local, (batch, trace))

    @property
    def enabled(self) -> bool:
        return self.metrics.enabled or self.tracer.enabled

    def snapshot(self) -> dict:
        """One JSON-able view: metrics + events + tracer counters +
        process-wide sentinel state."""
        return {
            "metrics": self.metrics.snapshot(),
            "events": self.events.snapshot(),
            "tracing": self.tracer.snapshot(),
            "sentinel": sentinel.snapshot(),
        }

    def render_prometheus(self) -> str:
        return _render_metrics(self.metrics)


class _InBatch:
    __slots__ = ("_local", "_ctx", "_prev")

    def __init__(self, local, ctx):
        self._local, self._ctx = local, ctx

    def __enter__(self) -> None:
        self._prev = getattr(self._local, "batch", None)
        self._local.batch = self._ctx

    def __exit__(self, *exc) -> bool:
        self._local.batch = self._prev
        return False


#: Module default bundle, for callers that don't thread their own.
_DEFAULT = Observability()


def get_default() -> Observability:
    return _DEFAULT


def render_prometheus(obs: Observability | MetricsRegistry | None = None) -> str:
    """Text exposition of a bundle, a bare registry, or the default."""
    if obs is None:
        obs = _DEFAULT
    reg = obs.metrics if isinstance(obs, Observability) else obs
    return _render_metrics(reg)


def walk_eqns(jaxpr, mult: int = 1):
    """Yield ``(eqn, mult)`` for every equation of ``jaxpr``, recursing into
    sub-jaxprs (jit, scan, cond, shard_map bodies).

    ``mult`` is the product of the enclosing ``scan`` lengths — how many
    times the equation runs per call.  The one jaxpr walker of the repo
    (the benchmarks' intermediate-shape probe reads the traced program
    through it).
    """
    import jax

    for eqn in jaxpr.eqns:
        yield eqn, mult
        inner = mult
        if eqn.primitive.name == "scan":
            length = eqn.params.get("length")
            if isinstance(length, int):
                inner = mult * length
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from walk_eqns(getattr(sub, "jaxpr", sub), inner)


__all__ = [
    "BatchTrace", "BudgetRebuild", "COUNT_BUCKETS",
    "CorpusEvicted", "CorpusReadmitted", "Counter", "DEFAULT_BUCKETS",
    "Event", "EventLog", "Gauge", "Histogram", "IngestCrash",
    "MetricsRegistry",
    "Observability", "QueryQuarantined", "QueryTrace", "RetraceError",
    "SERVE_SPANS", "STAGES", "STAGE_SERIES", "Span", "TierTransition",
    "Tracer", "WorkerRestart", "get_default", "render_prometheus",
    "sentinel", "walk_eqns",
]
