"""Per-request span timelines through the serving pipeline.

A query admitted to either server carries a :class:`QueryTrace`; every
query that rides the same flush shares one :class:`BatchTrace`.  The
stage vocabulary is fixed (``STAGES``) so downstream tooling can rely on
names:

    admission → queue_wait → batch_formation → dispatch
              → device_compute → validation → delivery

Per-query stages (admission, queue_wait, delivery) live on the
QueryTrace; batch-level stages (batch_formation, dispatch,
device_compute, validation) live on the BatchTrace and are shared by
reference across batch-mates — recording them costs O(1) per batch, not
per query.

**Async-dispatch awareness** is the point of the split between
``dispatch`` and ``device_compute``: under JAX async dispatch the
dispatch call returns device futures immediately, so its span measures
*host* dispatch cost only.  ``device_compute`` opens when dispatch
returns and closes when collect's ``np.asarray`` readback completes —
i.e. at ``block_until_ready`` — which is the only host-observable proxy
for device wall time without a profiler.  With two batches in flight it
therefore includes queueing behind the previous batch; that is the
latency the *request* experienced, which is what a trace is for.

Traces attach to results: ``Answer.trace`` / ``ServeFuture.trace`` hold
the completed :class:`QueryTrace` (None when tracing is disabled).
``timeline()`` merges query- and batch-level spans sorted by start time;
``to_dict()`` is JSON-able for export.

**Serve spans** (:class:`Span`, opened by ``Observability.span``) are the
finer, per-batch leaf stages of the serve path (``SERVE_SPANS``).  Each
one is a ``jax.profiler.TraceAnnotation`` named ``serve.<stage>`` with the
batch number as an argument — so a profiler trace shows host work on the
same clock as the device planes — and one observation of the registry
histogram ``serving_stage_seconds{stage=<stage>}``; it widens the
timeline stage above that it belongs to on the batch's
:class:`BatchTrace`.  Spans on one thread never nest: a trace reader
labels a device-idle gap with the host event that overlaps it most, and
an enclosing span would win every gap.
"""

from __future__ import annotations

import threading
import time

#: Canonical stage names, in pipeline order.
STAGES: tuple[str, ...] = (
    "admission", "queue_wait", "batch_formation", "dispatch",
    "device_compute", "validation", "delivery",
)

_BATCH_STAGES = frozenset(
    {"batch_formation", "dispatch", "device_compute", "validation"})

#: The serve path's leaf spans, in the order a batch meets them, and the
#: timeline stage (``STAGES``) that each widens on its BatchTrace (None:
#: none; ``device_compute`` is recorded from two timestamps, since the
#: worker does other work while the device runs).
SERVE_SPANS: dict[str, str | None] = {
    "wait": None,                  # for fill, max_wait or a ready batch
    "prep": None,                  # vectorize or staging-ring collect
    "pad": "batch_formation",      # fixed-shape padding
    "refresh": "dispatch",         # resident re-placement; 0 in steady state
    "gather_queries": "dispatch",  # query-embedding gather launch
    "route": "dispatch",           # cluster-index routing (routed steps)
    "step_launch": "dispatch",     # serve-step (jit_step) launch
    "refine_launch": "dispatch",   # symmetric-refine launch
    "rerank_launch": "dispatch",   # Sinkhorn rerank launch
    "collect": None,               # readback: the host blocked on the device
    "validate": "validation",      # finiteness check, budget feedback
    "deliver": None,               # future resolution, callers' callbacks
}

#: The registry histogram a serve span observes, labelled ``stage``.
STAGE_SERIES = "serving_stage_seconds"

#: Spans whose time an older series already holds, alone.
SPAN_SERIES = {"collect": "serving_device_collect_seconds"}

_annotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class Span:
    """One leaf span of the serve path; a context manager.

    On the profiler's clock it is ``serve.<stage>`` (``batch=`` as its
    argument); on exit its length is ``seconds``, observed in ``hist``
    and widening the stage's timeline stage on ``trace``, where given.
    With no profiler session active the annotation costs about 1 µs.
    """

    __slots__ = ("stage", "_ann", "_hist", "_trace", "t0", "seconds")

    def __init__(self, stage: str, batch: int | None = None, hist=None,
                 trace: "BatchTrace | None" = None):
        ann = _trace_annotation()
        self.stage = stage
        self._ann = (ann("serve." + stage) if batch is None
                     else ann("serve." + stage, batch=batch))
        self._hist = hist
        self._trace = trace
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.seconds = t1 - self.t0
        if self._hist is not None:
            self._hist.observe(self.seconds)
        if self._trace is not None:
            stage = SERVE_SPANS.get(self.stage)
            if stage is not None:
                self._trace.widen(stage, self.t0, t1)
        return False


class _SpanHolder:
    """Mutable span store: name -> (t_start, t_end)."""

    __slots__ = ("spans",)

    def __init__(self):
        self.spans: dict[str, tuple[float, float]] = {}

    def span(self, stage: str, t0: float, t1: float) -> None:
        self.spans[stage] = (t0, t1)

    def widen(self, stage: str, t0: float, t1: float) -> None:
        """Stretch ``stage`` to cover [t0, t1] as well (open it if new)."""
        old = self.spans.get(stage)
        self.spans[stage] = ((t0, t1) if old is None
                             else (min(old[0], t0), max(old[1], t1)))


class BatchTrace(_SpanHolder):
    """Spans shared by every query in one dispatched flush."""

    __slots__ = ("seq", "tier")

    def __init__(self, seq: int):
        super().__init__()
        self.seq = seq
        self.tier = 0


class QueryTrace(_SpanHolder):
    """One query's journey; ``batch`` links the shared flush spans."""

    __slots__ = ("t_admit", "batch", "done")

    def __init__(self, t_admit: float | None = None):
        super().__init__()
        self.t_admit = time.perf_counter() if t_admit is None else t_admit
        self.batch: BatchTrace | None = None
        self.done = False
        self.span("admission", self.t_admit, self.t_admit)

    def joined_batch(self, batch: BatchTrace | None, t_dequeue: float | None = None
                     ) -> None:
        """Close queue_wait (admission → dequeue) and bind the batch."""
        self.batch = batch
        self.span("queue_wait",
                  self.t_admit,
                  time.perf_counter() if t_dequeue is None else t_dequeue)

    def finish(self) -> None:
        now = time.perf_counter()
        self.span("delivery", now, now)
        self.done = True

    @property
    def tier(self) -> int:
        return self.batch.tier if self.batch is not None else 0

    def timeline(self) -> list[tuple[str, float, float]]:
        """All spans (query-level + shared batch-level), sorted by start."""
        merged = dict(self.spans)
        if self.batch is not None:
            for k, v in self.batch.spans.items():
                merged[k] = v
        return sorted(((name, t0, t1) for name, (t0, t1) in merged.items()),
                      key=lambda s: (s[1], STAGES.index(s[0])
                                     if s[0] in STAGES else len(STAGES)))

    def to_dict(self) -> dict:
        return {
            "tier": self.tier,
            "batch_seq": self.batch.seq if self.batch is not None else None,
            "done": self.done,
            "spans": [
                {"stage": name, "start": t0, "end": t1,
                 "duration_s": t1 - t0,
                 "scope": "batch" if name in _BATCH_STAGES else "query"}
                for name, t0, t1 in self.timeline()
            ],
        }


class Tracer:
    """Factory for traces; a disabled tracer mints ``None`` everywhere,
    so instrumentation sites guard with ``if trace is not None`` and the
    disabled cost is one attribute check + one comparison per site."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._n_queries = 0
        self._n_batches = 0

    def admit(self, t_admit: float | None = None) -> QueryTrace | None:
        if not self.enabled:
            return None
        with self._lock:
            self._n_queries += 1
        return QueryTrace(t_admit)

    def batch(self, seq: int) -> BatchTrace | None:
        if not self.enabled:
            return None
        with self._lock:
            self._n_batches += 1
        return BatchTrace(seq)

    def snapshot(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled,
                    "queries_traced": self._n_queries,
                    "batches_traced": self._n_batches}


__all__ = ["BatchTrace", "QueryTrace", "SERVE_SPANS", "STAGES", "STAGE_SERIES",
           "Span", "Tracer"]
