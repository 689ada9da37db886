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

Also here: :func:`jaxpr_collective_counts`, a build-time structural
probe that counts mesh collectives (psum / all_gather / …) in a traced
function — recorded once per serve-step build as gauges, so collective
regressions show up in a metrics diff instead of a profiler session — on
top of :func:`walk_eqns`, the repo's one jaxpr walker.
"""

from __future__ import annotations

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
    BatchTrace, QueryTrace, STAGES, Tracer, profiler_session,
)

#: Primitive names counted by :func:`jaxpr_collective_counts`.
#: ``psum2`` is the shard_map-era spelling of psum; both are folded into
#: the ``psum`` count.
COLLECTIVE_PRIMS: tuple[str, ...] = (
    "psum", "psum2", "all_gather", "all_reduce", "all_to_all", "ppermute",
    "reduce_scatter",
)
_PRIM_ALIASES = {"psum2": "psum"}


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
    times the equation runs per call.  The one jaxpr walker of the repo:
    collective counts here and the benchmarks' intermediate-shape probe
    both read the traced program through it.
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


def jaxpr_collective_counts(fn, *args, **kwargs) -> dict[str, int]:
    """Count collective primitives in ``fn``'s jaxpr for these args.

    Equations inside ``scan`` bodies are multiplied by the scan ``length``
    so the numbers reflect per-call collective *issues*, matching what a
    profiler would see (this is how the psum-batching win becomes a
    visible metric).  Returns only nonzero entries.
    """
    import jax

    counts: dict[str, int] = {}
    for eqn, mult in walk_eqns(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            name = _PRIM_ALIASES.get(name, name)
            counts[name] = counts.get(name, 0) + mult
    return counts


__all__ = [
    "BatchTrace", "BudgetRebuild", "COLLECTIVE_PRIMS", "COUNT_BUCKETS",
    "CorpusEvicted", "CorpusReadmitted", "Counter", "DEFAULT_BUCKETS",
    "Event", "EventLog", "Gauge", "Histogram", "IngestCrash",
    "MetricsRegistry",
    "Observability", "QueryQuarantined", "QueryTrace", "RetraceError",
    "STAGES", "TierTransition", "Tracer", "WorkerRestart",
    "get_default", "jaxpr_collective_counts", "profiler_session",
    "render_prometheus", "sentinel", "walk_eqns",
]
