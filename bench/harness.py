"""One benchmark run: a cell of ``BENCHMARK.json``, driven open loop through
the served path, measured, and checked against the plain reference.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is found by name: ``configs/<config>.json``,
``traffic/<mix>.json`` and ``metrics/<metric>.py`` beside this file.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import threading
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACING = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# -- the cell -----------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list        # metric entries of BENCHMARK.json for this cell
    per_layer: list
    root: pathlib.Path = ROOT


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its
    configuration and traffic mix read from their files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    return Cell(name, int(w["chips"]), config, mix,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)], root)


def load_reader(metric: str, root: pathlib.Path = ROOT):
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def corpus_spec(config: dict) -> dict:
    return {k: config[k] for k in (
        "n_docs", "vocab_size", "emb_dim", "h_max", "mean_h", "n_classes",
        "topic_noise", "zipf_a", "emb_topic_scale", "emb_word_scale")}


# -- the chip -----------------------------------------------------------------
def require_chips(chips: int):
    """The first device, if it is a TPU and there are ``chips`` of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[0]


def use_compile_cache() -> None:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else at a fixed directory of the checkout; every program is
    cached, however quickly it compiled."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts JAX's program builds by phase of the run: ``traces``,
    ``compiles`` (backend compiles, persistent-cache loads included) and
    ``cache_hits`` (the loads).  Register both listeners."""

    def __init__(self):
        self.phase = "setup"
        self.counts: dict = {}

    def _bump(self, what: str) -> None:
        key = (self.phase, what)
        self.counts[key] = self.counts.get(key, 0) + 1

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self._bump("compiles")
        elif event == TRACING:
            self._bump("traces")

    def event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self._bump("cache_hits")

    def register(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        jax.monitoring.register_event_listener(self.event)

    def get(self, phase: str, what: str) -> int:
        return self.counts.get((phase, what), 0)


# -- a run --------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    """Inputs and state that the window and the check share."""

    corpus: object            # bench.corpus.Corpus
    emb: object               # device (vocab, m) float32
    server: object            # repro.serving.AsyncQueryServer
    mesh: object              # the cell's mesh: one device per chip
    schedule: object          # bench.traffic.Schedule
    q_ids: np.ndarray         # (N, h_max) warm-up, then scheduled queries,
    q_w: np.ndarray           # padded with id 0 and weight 0


def build(cell: Cell, seed: int) -> Served:
    """Corpus, embeddings and server for ``seed`` (queries come from
    :func:`make_queries`)."""
    import jax.numpy as jnp

    from bench import corpus as corpus_lib
    from repro.data.docs import DocSet
    from repro.launch.mesh import make_host_mesh
    from repro.serving import AsyncQueryServer, ServerConfig

    cfg = cell.config
    spec = corpus_spec(cfg)
    corpus = corpus_lib.make_corpus(spec, seed)
    emb = corpus_lib.make_embeddings(spec, corpus.model.word_topic,
                                     corpus.device_seed)
    mesh = make_host_mesh(data=cell.chips)
    server = AsyncQueryServer(
        DocSet(ids=jnp.asarray(corpus.ids), weights=jnp.asarray(corpus.weights)),
        emb, mesh, ServerConfig(**cfg["server"]))
    return Served(corpus, emb, server, mesh, None, None, None)


def make_queries(served: Served, cell: Cell, seconds: float, seed: int,
                 n_warm: int) -> None:
    """The window's schedule and queries, after ``n_warm`` warm-up queries
    in ``served.q_ids`` and ``served.q_w``."""
    from bench import corpus as corpus_lib
    from bench import traffic

    cfg = cell.config
    spec = corpus_spec(cfg)
    sched = traffic.make_schedule(cell.mix, float(cfg["knee_qps"]), seconds,
                                  spec, seed)
    rng = served.corpus.rng
    warm_len = corpus_lib.doc_lengths(spec, n_warm, rng)
    ids, w = corpus_lib.make_docs(
        spec, served.corpus.model,
        np.concatenate([warm_len, sched.lengths]), rng)
    served.schedule = sched
    served.q_ids, served.q_w = ids, w


def warm_up(served: Served, n_warm: int) -> None:
    """Compile (or load from the cache) every program the window runs: full
    batches through both pipeline slots, then a partial batch."""
    srv = served.server
    mb = srv.cfg.max_batch
    nz = (served.q_w[:n_warm] > 0).sum(axis=1)
    for lo, hi in ((0, 2 * mb), (2 * mb, n_warm)):
        futs = [srv.submit(served.q_ids[i, :nz[i]], served.q_w[i, :nz[i]])
                for i in range(lo, hi)]
        srv.drain()
        for f in futs:
            f.result()


def series_totals(server) -> dict:
    """Every series of the program's metrics registry: (sum, count) of a
    histogram, (value, 0) of a counter or gauge; keyed by the metric's
    name, with ``{label=value,...}`` appended where it has labels."""
    out = {}
    for name, fam in server.obs.metrics.snapshot().items():
        for s in fam["series"]:
            key = name + ("{" + ",".join(f"{k}={v}" for k, v in sorted(
                s["labels"].items())) + "}" if s["labels"] else "")
            out[key] = ((float(s["sum"]), int(s["count"]))
                        if fam["kind"] == "histogram"
                        else (float(s["value"]), 0))
    return out


#: Seconds of the window that a ``--trace 1`` run traces, from its start:
#: enough steps for the per-layer readers, a trace small enough to read.
TRACE_S = 10.0


class GcWatch:
    """Counts the interpreter's full (generation 2) collections and their
    longest pause while it is open: host pauses that the tails feel."""

    def __init__(self):
        self.full, self.max_pause = 0, 0.0
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.full += 1
            self.max_pause = max(self.max_pause, time.perf_counter() - self._t)

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


@dataclasses.dataclass
class RunView:
    """What a per-layer reader may read: counter deltas over the window,
    the trace summary, and the cell's sizes."""

    cell: Cell
    counters: dict            # series -> change over the window
    trace: object             # bench.trace.TraceSummary | None
    peaks: dict
    work: dict                # v_e, nnz, mean query words, n_docs, ...

    def counter(self, name: str) -> tuple[float, int]:
        """(Δsum, Δcount) of a histogram of the program's registry over the
        window; (Δvalue, 0) of a counter or gauge (see :func:`series_totals`
        for the names)."""
        return self.counters.get(name, (0.0, 0))

    def module(self, *names: str) -> tuple[int, float]:
        """(executions, device s) of the named programs in the trace."""
        if self.trace is None:
            return 0, 0.0
        n, t = 0, 0.0
        for name in names:
            a, b = self.trace.modules.get(name, (0, 0.0))
            n, t = n + a, t + b
        return n, t


def check_sample(attempted: np.ndarray, size: int, seed: int) -> np.ndarray:
    """Indices (into the schedule) of the queries compared, drawn from the
    seed among those submitted."""
    rng = np.random.default_rng([seed, 7])
    idx = np.flatnonzero(attempted)
    return np.sort(rng.choice(idx, size=min(size, idx.size), replace=False))


def judge(served: Served, res, sample: np.ndarray, n_warm: int,
          reference) -> dict:
    """The compared numbers over ``sample``, and how many of its queries
    have no answer (``unanswered``: never resolved, or failed)."""
    failed = {i for i, _e in res.errors}
    missing = sum(1 for i in sample
                  if not np.isfinite(res.done_at[i]) or i in failed)
    qi = served.q_ids[n_warm + sample]
    qw = served.q_w[n_warm + sample]
    nums = reference.judge(qi, qw, res.ids[sample], res.dists[sample])
    nums["unanswered"] = float(missing)
    return nums


def make_reference(served: Served, cell: Cell):
    from bench.reference import Reference

    s = cell.config["server"]
    kc = 2 * s["k"] if s.get("rerank_wmd") else s["k"]
    return Reference(served.corpus.ids, served.corpus.weights, served.emb,
                     k=s["k"], kc=kc, vocab_pad=s["vocab_pad"],
                     mode="wmd" if s.get("rerank_wmd") else "knn",
                     sink=s.get("wmd_kw"))


def limits_hold(nums: dict, limits: dict) -> tuple[bool, dict]:
    checks = {name: {"value": nums[name], "limit": lim}
              for name, lim in limits.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, out=print, device=None) -> dict:
    """One measured run of ``cell``; returns the result line's object.

    ``device`` skips the look for a chip (the CPU rehearsals pass one)."""
    import jax

    from bench import corpus as corpus_lib
    from bench import traffic
    from bench import trace as trace_lib
    from bench import work as work_lib

    dev = require_chips(cell.chips) if device is None else device
    if device is None:
        use_compile_cache()
    counter = CompileCounter()
    counter.register()
    peaks = work_lib.peaks(dev.device_kind) if device is None else {}
    cfg = cell.config
    mb = cfg["server"]["max_batch"]
    n_warm = 2 * mb + mb // 2

    served = build(cell, seed)
    make_queries(served, cell, seconds, seed, n_warm)
    warm_up(served, n_warm)
    srv = served.server
    devices = list(served.mesh.devices.flat)
    resident = {str(d): (d.memory_stats() or {}).get("bytes_in_use")
                for d in devices}
    before = series_totals(srv)

    log_dir = ROOT / "results" / "bench" / f"{cell.name}.{seed}"
    # Set-up's objects are frozen out of the collector's reach, so that a
    # full collection in the window walks only what the window made.
    gc.collect()
    gc.freeze()
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    counter.phase = "window"
    setup_s = time.perf_counter() - t_start
    # The generator runs in a thread of its own, so that a submit blocked on
    # a full queue cannot stretch the window past ``seconds``.
    t0 = time.perf_counter()
    box: dict = {}
    gen = threading.Thread(target=lambda: box.update(res=traffic.drive(
        srv.submit, served.q_ids[n_warm:], served.q_w[n_warm:],
        served.schedule, seconds, cfg["server"]["k"], t0=t0)),
        name="bench-generator")
    gc_watch = GcWatch()
    with jax.profiler.TraceAnnotation("bench.window"):
        gen.start()
        time.sleep(max(0.0, t0 + min(seconds, TRACE_S if trace else seconds)
                       - time.perf_counter()))
    if trace:
        jax.profiler.stop_trace()
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    after = series_totals(srv)
    gc_watch.close()
    counter.phase = "drain"
    gen.join()
    res = box["res"]
    never = traffic.wait_all(res, timeout_s=60.0)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    v_e = corpus_lib.vocab_in_use(served.corpus.ids, served.corpus.weights)
    words = (served.q_w[n_warm:][res.submitted] > 0).sum()
    view_work = dict(
        v_e=v_e, m=int(cfg["emb_dim"]), n_docs=int(cfg["n_docs"]),
        h_max=int(cfg["h_max"]), max_batch=mb,
        nnz=int((served.corpus.weights > 0).sum()),
        words_per_query=float(words / max(1, res.submitted.sum())))
    counters = {n: (a[0] - before.get(n, (0.0, 0))[0],
                    a[1] - before.get(n, (0.0, 0))[1])
                for n, a in after.items()}
    summary = None
    if trace:
        space = trace_lib.read_xspace(trace_lib.find_xspace(str(log_dir)))
        window = trace_lib.span_ns(space, "bench.window")
        summary = trace_lib.summarize(space, window)
    view = RunView(cell, counters, summary, peaks, view_work)

    lat = traffic.latencies(res)
    late_med, late_max = traffic.lateness_summary(res)
    attempted = int(res.submitted.sum())
    failed = len(res.errors) + never
    metrics: dict = {}
    if not trace:
        e2e = {
            "setup_s": setup_s,
            "qps": traffic.answered_in_window(res) / seconds,
            "p50_ms": 1e3 * traffic.percentile(lat, 50),
            "p95_ms": 1e3 * traffic.percentile(lat, 95),
            "p99_ms": 1e3 * traffic.percentile(lat, 99),
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            value = load_reader(m["name"], cell.root)(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    out(f"[diag] cell={cell.name} seed={seed} v_e={v_e} "
        f"v_e_padded={-(-v_e // cfg['server']['vocab_pad']) * cfg['server']['vocab_pad']} "
        f"knee_qps={cfg['knee_qps']} scheduled={len(res.scheduled)} "
        f"submitted={attempted} failed={failed} "
        f"answered_in_window={traffic.answered_in_window(res)} "
        f"late_median_s={late_med} late_max_s={late_max} "
        f"gc_full={gc_watch.full} gc_max_pause_s={gc_watch.max_pause} "
        f"p95_ms={1e3 * traffic.percentile(lat, 95)} "
        f"p99_ms={1e3 * traffic.percentile(lat, 99)} "
        f"setup_compiles={counter.get('setup', 'compiles')} "
        f"setup_cache_hits={counter.get('setup', 'cache_hits')} "
        f"setup_traces={counter.get('setup', 'traces')} "
        f"window_compiles={counter.get('window', 'compiles')} "
        f"window_traces={counter.get('window', 'traces')} "
        f"setup_s={setup_s} peak_bytes={peak} bytes_in_use={resident} "
        f"batches={counters['serving_batch_size'][1]} "
        f"queries_dispatched={counters['serving_batch_size'][0]}")
    if summary is not None:
        out(f"[diag] trace window_s={summary.window_s} busy_s={summary.busy_s} "
            f"modules={json.dumps(summary.modules)}")

    # The check: the program's state is freed before the reference runs.
    sample = check_sample(res.submitted, int(cfg["check"]["sample"]), seed)
    gc.unfreeze()
    srv.close()
    served.server = srv = None
    gc.collect()
    counter.phase = "check"
    t_ref = time.perf_counter()
    reference = make_reference(served, cell)
    nums = judge(served, res, sample, n_warm, reference)
    recall = None
    n_rec = int(cfg["check"].get("recall_sample", 0))
    if n_rec and nums["unanswered"] == 0:
        from bench.reference import recall_at_k
        sub = sample[:n_rec]
        k = cfg["server"]["k"]
        recall = recall_at_k(served.emb, served.corpus.ids,
                             served.corpus.weights, served.q_ids[n_warm + sub],
                             served.q_w[n_warm + sub], res.ids[sub], k)
    ref_s = time.perf_counter() - t_ref
    correct, checks = limits_hold(nums, cfg["check"]["limits"])
    checks["unanswered"] = {"value": nums["unanswered"], "limit": 0}
    correct = correct and nums["unanswered"] == 0
    out(f"[diag] check sample={len(sample)} reference_s={ref_s} "
        f"numbers={json.dumps(nums)} "
        f"check_compiles={counter.get('check', 'compiles')} "
        f"check_cache_hits={counter.get('check', 'cache_hits')} "
        f"recall_at_k_vs_exact_symmetric={recall} (over {n_rec} queries)")

    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    import argparse

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    cell = load_cell(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0
