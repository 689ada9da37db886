"""Open-loop traffic: seeded arrival schedules and the generator that offers them.

A traffic mix is a JSON file of ``phases``: each phase lasts ``seconds``
(null: the whole window) at ``rate_x_knee`` times the configuration's
``knee_qps``, and the phases repeat until the window is full.  Within a
phase the arrivals are a Poisson process.

Every seed gets the same work: the multiset of inter-arrival gaps of each
phase is drawn once from the mix's fixed ``base_seed`` and scaled so that
the phase's arrivals fill it exactly, and so are the query lengths; the
run's seed only permutes them and draws the words.  Runs with different
seeds then differ by the order of the same arrivals, not by how many there
are.

Latency is timed from each query's scheduled arrival (the arrival that an
open loop would have made, so a stalled submit counts against the server),
and the generator records how late it submitted each query.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    arrivals: np.ndarray   # (N,) seconds after the window opens, ascending
    lengths: np.ndarray    # (N,) query lengths in words, before dedup


def phase_rates(mix: dict, knee_qps: float, seconds: float):
    """[(start_s, duration_s, rate_qps)] covering [0, seconds)."""
    out, t = [], 0.0
    phases = mix["phases"]
    while t < seconds - 1e-9:
        for ph in phases:
            dur = seconds - t if ph["seconds"] is None else float(ph["seconds"])
            dur = min(dur, seconds - t)
            if dur <= 1e-9:
                break
            out.append((t, dur, float(ph["rate_x_knee"]) * knee_qps))
            t += dur
    return out


def make_schedule(mix: dict, knee_qps: float, seconds: float,
                  spec: dict, seed: int) -> Schedule:
    """Arrivals and query lengths of one run (see the module docstring)."""
    base = np.random.default_rng(int(mix["base_seed"]))
    perm = np.random.default_rng(seed)
    arrivals, lengths = [], []
    for start, dur, rate in phase_rates(mix, knee_qps, seconds):
        n = int(round(rate * dur))
        if n == 0:
            continue
        gaps = base.exponential(1.0, size=n + 1)
        gaps *= dur / gaps.sum()           # n arrivals strictly inside
        arr = start + np.cumsum(perm.permutation(gaps)[:n])
        arrivals.append(arr)
        ln = np.clip(base.poisson(float(spec["mean_h"]), size=n), 2,
                     int(spec["h_max"]))
        lengths.append(perm.permutation(ln))
    return Schedule(np.concatenate(arrivals), np.concatenate(lengths))


@dataclasses.dataclass
class OpenLoopResult:
    scheduled: np.ndarray      # (N,) scheduled offsets from t0
    submitted: np.ndarray      # (N,) bool: the query was handed to the server
    late_s: np.ndarray         # (N,) submit time minus scheduled time
    done_at: np.ndarray        # (N,) completion offset from t0 (nan: none)
    ids: np.ndarray            # (N, k) served document ids (-1: none)
    dists: np.ndarray          # (N, k) served distances (inf: none)
    errors: list               # (index, exception) of failed queries
    t0: float                  # perf_counter at the window's opening
    window_s: float


def drive(submit, ids: np.ndarray, weights: np.ndarray, schedule: Schedule,
          seconds: float, k: int, t0: float | None = None) -> OpenLoopResult:
    """Offer query ``i`` — row ``i`` of the padded ``ids``/``weights``, cut
    to its real words — at ``t0 + schedule.arrivals[i]`` through
    ``submit(ids, weights) -> future`` until the window closes.

    A submit that blocks (the server's queue is full) delays the queries
    behind it; those still unsent when the window closes are never sent.
    Each future's done callback stamps its completion time and copies its
    answer into the result's arrays, so no per-query object outlives its
    answer.
    """
    n = len(schedule.arrivals)
    nz = (weights > 0).sum(axis=1)
    sub = np.zeros(n, bool)
    late = np.full(n, np.nan)
    done = np.full(n, np.nan)
    out_ids = np.full((n, k), -1, np.int64)
    out_d = np.full((n, k), np.inf)
    errors: list = []
    t0 = time.perf_counter() if t0 is None else t0
    t_end = t0 + seconds
    clock = time.perf_counter

    def stamp(i):
        def cb(f):
            t = clock() - t0
            e = f.exception()
            if e is not None:
                errors.append((i, e))
            else:
                a_ids, a_d = f.result()
                out_ids[i, :len(a_ids)] = a_ids
                out_d[i, :len(a_d)] = a_d
            done[i] = t
        return cb

    for i in range(n):
        due = t0 + schedule.arrivals[i]
        now = clock()
        if now >= t_end:
            break
        if due > now:
            time.sleep(due - now)
        f = submit(ids[i, :nz[i]], weights[i, :nz[i]])
        late[i] = clock() - due
        sub[i] = True
        f.add_done_callback(stamp(i))
    return OpenLoopResult(schedule.arrivals, sub, late, done, out_ids, out_d,
                          errors, t0, seconds)


def wait_all(res: OpenLoopResult, timeout_s: float) -> int:
    """Wait up to ``timeout_s`` for every submitted query; returns how many
    never resolved."""
    deadline = time.perf_counter() + timeout_s
    while True:
        missing = int((res.submitted & np.isnan(res.done_at)).sum())
        if missing == 0 or time.perf_counter() >= deadline:
            return missing
        time.sleep(0.01)


def percentile(values, q: float) -> float:
    """Linear-interpolation percentile (numpy's default ``linear`` rule);
    infinite entries count as the slowest."""
    a = np.sort(np.asarray(values, np.float64))
    if a.size == 0:
        return math.nan
    pos = (a.size - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, a.size - 1)
    if not math.isfinite(a[hi]):
        return math.inf if pos > lo or not math.isfinite(a[lo]) else a[lo]
    return float(a[lo] + (a[hi] - a[lo]) * (pos - lo))


def latencies(res: OpenLoopResult) -> np.ndarray:
    """Seconds from scheduled arrival to answer for every query scheduled
    in the window; a query never sent, never answered or failed is +inf."""
    lat = res.done_at - res.scheduled
    bad = ~res.submitted | ~np.isfinite(lat)
    for i, _e in res.errors:
        bad[i] = True
    return np.where(bad, np.inf, lat)


def answered_in_window(res: OpenLoopResult) -> int:
    """Queries answered (without error) before the window closed."""
    failed = {i for i, _e in res.errors}
    ok = np.isfinite(res.done_at) & (res.done_at <= res.window_s)
    return int(sum(1 for i in np.flatnonzero(ok) if i not in failed))


def lateness_summary(res: OpenLoopResult) -> tuple[float, float]:
    """(median, max) seconds by which the generator submitted late."""
    late = res.late_s[res.submitted]
    if late.size == 0:
        return math.nan, math.nan
    return float(statistics.median(late.tolist())), float(late.max())
