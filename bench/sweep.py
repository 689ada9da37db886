"""Knee sweep of one configuration on the chip, and the control's readings.

    python3 bench/sweep.py --config set2_knn --rates 600,900,1200 --seconds 6 --seed 3

``--n-docs`` serves a corpus of another size than the configuration's (to
find how many documents one chip holds: the last line gives the peak).

One set-up, then one open-loop Poisson window per offered rate, each
drained before the next.  A line per rate gives the answered rate in the
window, the backlog when it closed and the latency percentiles; the knee
is the highest rate the server sustains, read off the answered rate of the
overloaded windows.  ``--control`` then compares the last window's
answers, and the reference's own answers at ``HIGH`` and at bfloat16
inputs, against the float32 reference (the numbers that decide
``correct``).  ``--dump`` writes the structure of a traced window to
``results/bench/`` for reading by hand.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _dump_trace(log_dir: str, out: pathlib.Path) -> None:
    from bench import trace as trace_lib

    space = trace_lib.read_xspace(trace_lib.find_xspace(log_dir))
    doc = {}
    for plane, lines in space.items():
        doc[plane] = {}
        for line, evs in lines.items():
            tot: dict = {}
            for n, _s, d in evs:
                tot[n] = tot.get(n, 0.0) + d
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:25]
            doc[plane][line] = {"events": len(evs),
                                "top_ns": [[n, t] for n, t in top]}
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc, indent=1))


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--dump", action="store_true")
    ap.add_argument("--n-docs", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    import jax

    from bench import harness, traffic

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in spec["configs"]}[args.config]
    config = json.loads((ROOT / entry["file"]).read_text())
    if args.n_docs:
        config["n_docs"] = args.n_docs
    dev = harness.require_chips(1)
    harness.use_compile_cache()
    counter = harness.CompileCounter()
    counter.register()
    mb = config["server"]["max_batch"]
    n_warm = 2 * mb + mb // 2
    rates = [float(r) for r in args.rates.split(",")]
    cell = harness.Cell(args.config, 1, config, {}, [], [])
    served = harness.build(cell, args.seed)
    t_built = time.perf_counter()
    mix = {"base_seed": 5, "phases": [{"seconds": None, "rate_x_knee": 1.0}]}
    res = None
    for j, rate in enumerate(rates):
        cell.mix = mix
        cell.config = dict(config, knee_qps=rate)
        harness.make_queries(served, cell, args.seconds, args.seed + j, n_warm)
        if j == 0:
            harness.warm_up(served, n_warm)
            print(f"[setup] build_s={t_built - t_start:.2f} "
                  f"setup_s={time.perf_counter() - t_start:.2f} "
                  f"compiles={counter.get('setup', 'compiles')} "
                  f"peak_bytes={dev.memory_stats().get('peak_bytes_in_use')}",
                  flush=True)
        counter.phase = f"rate{j}"
        tracing = args.dump and j == len(rates) - 1
        log_dir = str(ROOT / "results" / "bench" / f"sweep.{args.config}")
        if tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            res = traffic.drive(served.server.submit, served.q_ids[n_warm:],
                                served.q_w[n_warm:], served.schedule,
                                args.seconds, config["server"]["k"], t0=t0)
            rest = t0 + args.seconds - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
        backlog = int(res.submitted.sum()) - int(
            (np.isfinite(res.done_at) & (res.done_at <= args.seconds)).sum())
        if tracing:
            jax.profiler.stop_trace()
        traffic.wait_all(res, 120.0)
        lat = traffic.latencies(res)
        answered = traffic.answered_in_window(res)
        late_med, late_max = traffic.lateness_summary(res)
        print(f"[rate] offered_qps={rate} answered_qps={answered / args.seconds:.1f} "
              f"scheduled={len(res.scheduled)} submitted={int(res.submitted.sum())} "
              f"backlog_at_close={backlog} "
              f"p50_ms={1e3 * traffic.percentile(lat, 50):.1f} "
              f"p90_ms={1e3 * traffic.percentile(lat, 90):.1f} "
              f"p99_ms={1e3 * traffic.percentile(lat, 99):.1f} "
              f"late_median_s={late_med:.5f} late_max_s={late_max:.4f} "
              f"compiles={counter.get(f'rate{j}', 'compiles')}", flush=True)
        if tracing:
            _dump_trace(log_dir, ROOT / "results" / "bench" / f"trace_{args.config}.json")
            from bench import trace as trace_lib
            space = trace_lib.read_xspace(trace_lib.find_xspace(log_dir))
            summ = trace_lib.summarize(space, trace_lib.span_ns(space, "bench.window"))
            print(f"[trace] window_s={summ.window_s} busy_s={summ.busy_s} "
                  f"modules={json.dumps(summ.modules)}", flush=True)
            print(f"[trace] ops={json.dumps(summ.device_ops)}", flush=True)
            print(f"[trace] gaps={json.dumps(summ.idle_gaps)}", flush=True)

    print(f"[peak] n_docs={config['n_docs']} "
          f"memory_stats={json.dumps(dev.memory_stats())}", flush=True)
    if args.control:
        sample = harness.check_sample(res.submitted,
                                      int(config["check"]["sample"]), args.seed)
        served.server.close()
        served.server = None
        import gc
        gc.collect()
        ref = harness.make_reference(served, cell)
        t = time.perf_counter()
        nums = harness.judge(served, res, sample, n_warm, ref)
        print(f"[check] program {json.dumps(nums)} "
              f"s={time.perf_counter() - t:.2f}", flush=True)
        qi = served.q_ids[n_warm + sample]
        qw = served.q_w[n_warm + sample]
        for passes in (3, 1):
            t = time.perf_counter()
            ids, d = ref.answers(qi, qw, passes=passes)
            nums = ref.judge(qi, qw, ids, d)
            print(f"[check] control passes={passes} {json.dumps(nums)} "
                  f"s={time.perf_counter() - t:.2f}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
