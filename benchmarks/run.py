"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per result and persists each
module's results as ``BENCH_<module>.json`` (``kernels_bench`` →
``BENCH_kernels.json``) so the perf trajectory accumulates across PRs.  Run:
    PYTHONPATH=src python -m benchmarks.run [--only fig12] [--out-dir .]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback


def _json_name(mod_name: str) -> str:
    stem = mod_name[: -len("_bench")] if mod_name.endswith("_bench") else mod_name
    return f"BENCH_{stem}.json"


def _persist(out_dir: pathlib.Path, mod_name: str, results) -> None:
    payload = [
        {"name": r.name, "us_per_call": r.us_per_call,
         "derived": {k: (v if isinstance(v, (int, float, str, bool)) else str(v))
                     for k, v in r.derived.items()}}
        for r in results
    ]
    (out_dir / _json_name(mod_name)).write_text(json.dumps(payload, indent=2))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on module names")
    ap.add_argument("--out-dir", default=".",
                    help="directory for the BENCH_*.json result files")
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)

    from repro.launch.serve import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        fig10_11_overlap,
        fig12_13_runtime,
        fig14_precision,
        index_bench,
        kernels_bench,
        lifecycle_bench,
        obs_overhead_bench,
        pruning_bench,
        robustness_bench,
        scaling_analysis,
        serving_bench,
        slo_bench,
        table3_complexity,
        workloads_bench,
    )

    modules = {
        "fig12_13_runtime": fig12_13_runtime,
        "fig10_11_overlap": fig10_11_overlap,
        "table3_complexity": table3_complexity,
        "fig14_precision": fig14_precision,
        "pruning_bench": pruning_bench,
        "kernels_bench": kernels_bench,
        "scaling_analysis": scaling_analysis,
        "serving_bench": serving_bench,
        "slo_bench": slo_bench,
        "index_bench": index_bench,
        "lifecycle_bench": lifecycle_bench,
        "obs_bench": obs_overhead_bench,
        "robustness_bench": robustness_bench,
        "workloads_bench": workloads_bench,
    }
    print("name,us_per_call,derived")
    failed = []
    for name, mod in modules.items():
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            results = list(mod.run())
            for r in results:
                print(r.csv(), flush=True)
            _persist(out_dir, name, results)
        except Exception:
            failed.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)
    print("# all benchmarks passed")


if __name__ == "__main__":
    main()
