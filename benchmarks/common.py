"""Shared benchmark utilities: timing, result records, corpus caching,
and the structural HBM-footprint probe used by the tiling assertions."""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class BenchResult:
    name: str
    us_per_call: float
    derived: dict[str, Any] = dataclasses.field(default_factory=dict)

    def csv(self) -> str:
        d = ";".join(f"{k}={v}" for k, v in self.derived.items())
        return f"{self.name},{self.us_per_call:.1f},{d}"


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-time per call in microseconds (blocks on jax arrays)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def intermediate_shapes(fn, *args) -> set[tuple[int, ...]]:
    """All f32 intermediate shapes in fn's jaxpr, recursing into sub-jaxprs
    (jit/scan bodies) — a structural HBM-footprint probe.  Shared by the
    kernel and workloads benches (and their tests): tiling contracts are
    asserted against the traced program, not against runtime telemetry."""
    from repro.obs import walk_eqns

    return {
        tuple(ov.aval.shape)
        for eqn, _ in walk_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
        for ov in eqn.outvars
        if getattr(getattr(ov, "aval", None), "dtype", None) == jnp.float32
    }


_CORPora: dict = {}


def cached_corpus(**kw):
    from repro.data.synth import CorpusSpec, make_corpus

    key = tuple(sorted(kw.items()))
    if key not in _CORPora:
        _CORPora[key] = make_corpus(CorpusSpec(**kw))
    return _CORPora[key]
