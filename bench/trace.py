"""Reduction of a profiler trace to device time, busy share and idle gaps.

A trace is read into plain data — ``{plane: {line: [(name, start_ns,
dur_ns), ...]}}`` — so that the reduction can be tested on a small trace
built by hand.  Device planes are named ``/device:TPU:<i>``; on each, the
``XLA Modules`` line holds one event per execution of a compiled program
(named after the jitted function, e.g. ``jit_step(42)``) and the
``XLA Ops`` line one event per operation.  Host planes hold one line per
thread.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

MODULES = "XLA Modules"
OPS = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
OWN_SPANS = "bench."      # the benchmark's own spans label no gap


def read_xspace(path: str) -> dict:
    """``{plane: {line: [(name, start_ns, dur_ns)]}}`` of one .xplane.pb."""
    from jax.profiler import ProfileData

    space = ProfileData.from_file(path)
    out: dict = {}
    for plane in space.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
    return out


def find_xspace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def device_planes(space: dict) -> list[str]:
    return sorted(p for p in space if p.startswith(DEVICE_PREFIX))


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _busy_line(lines: dict) -> list:
    return lines.get(OPS) or lines.get(MODULES) or []


def busy_intervals(space: dict, plane: str) -> list:
    return [(s, s + d) for _n, s, d in _busy_line(space.get(plane, {}))]


def module_name(event_name: str) -> str:
    """``jit_step(42)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def module_stats(space: dict, plane: str, lo: float, hi: float) -> dict:
    """``{module: (executions, device ns)}`` of programs that started in
    [lo, hi] on ``plane``."""
    out: dict = {}
    for name, s, d in space.get(plane, {}).get(MODULES, []):
        if lo <= s <= hi:
            key = module_name(name)
            n, t = out.get(key, (0, 0.0))
            out[key] = (n + 1, t + d)
    return out


def top_ops(space: dict, plane: str, lo: float, hi: float,
            limit: int = 10) -> list:
    """The ``limit`` device operations with the most time, [name, s]."""
    acc: dict = {}
    for name, s, d in space.get(plane, {}).get(OPS, []):
        if lo <= s <= hi:
            acc[name] = acc.get(name, 0.0) + d
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
    return [[n, t / 1e9] for n, t in top]


def idle_gaps(space: dict, plane: str, lo: float, hi: float,
              limit: int = 10) -> list:
    """The ``limit`` longest device-idle gaps in [lo, hi], each labelled with
    the host event that overlaps it most (``"host idle"`` where none does),
    as [label, s]."""
    busy = sorted((max(s, lo), min(e, hi))
                  for s, e in busy_intervals(space, plane) if e > lo and s < hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [(n, s, s + d) for p, lines in space.items()
            if not p.startswith(DEVICE_PREFIX)
            for ln, evs in lines.items() for n, s, d in evs
            if d > 0 and not n.startswith(OWN_SPANS)]
    out = []
    for gs, ge in gaps[:limit]:
        best, best_ov = "host idle", 0.0
        for n, s, e in host:
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = n, ov
        out.append([best, (ge - gs) / 1e9])
    return out


def span_ns(space: dict, name: str) -> tuple[float, float] | None:
    """(start, end) of the first host event called ``name``."""
    for p, lines in space.items():
        if p.startswith(DEVICE_PREFIX):
            continue
        for evs in lines.values():
            for n, s, d in evs:
                if n == name:
                    return s, s + d
    return None


@dataclasses.dataclass
class TraceSummary:
    """What the per-layer readers take from one traced window."""

    window_s: float
    busy_s: float                 # mean over the device planes
    modules: dict                 # module -> (executions, device s), plane 0
    device_ops: list              # [[name, s]] top operations, plane 0
    idle_gaps: list               # [[host label, s]] longest gaps, plane 0


def summarize(space: dict, window: tuple[float, float]) -> TraceSummary:
    lo, hi = window
    planes = device_planes(space)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy = [union_ns(busy_intervals(space, p), lo, hi) for p in planes]
    mods = {k: (n, t / 1e9)
            for k, (n, t) in module_stats(space, planes[0], lo, hi).items()}
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=sum(busy) / len(busy) / 1e9,
        modules=mods, device_ops=top_ops(space, planes[0], lo, hi),
        idle_gaps=idle_gaps(space, planes[0], lo, hi))
