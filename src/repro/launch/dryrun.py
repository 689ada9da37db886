import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

# NOTE: the lines above MUST run before any other import (jax locks the
# platform and device count at first init). 512 placeholder host devices
# exist ONLY here, never in tests/benchmarks.  The dry run compiles on the
# CPU even where an accelerator is attached: it never takes the chip.

import argparse
import json
import re
import sys
import time

import jax

from repro.launch.mesh import make_production_mesh
from repro.launch.cells import all_cells, build_cell
from repro.launch import hlo_cost

# TPU v5e hardware constants (per chip) for §Roofline.
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW_PER_LINK = 50e9   # ~50 GB/s per ICI link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape token like ``f32[128,1024]``."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in post-SPMD HLO.

    (Result bytes == operand bytes for all-reduce/all-to-all/permute; for
    all-gather the result is the gathered size — the amount that moves.)
    """
    out = {k: 0 for k in _COLL_OPS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^)=]*?\)?)\s*"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)(?:-start|-done)?\(", line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        if "-done(" in line:
            continue  # avoid double counting start/done pairs
        out[op] += _shape_bytes(shape_str)
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLL_OPS)
    return out


def run_cell(arch: str, shape: str, *, multi_pod: bool, out_path: str | None,
             skip_memory: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    t0 = time.time()
    cell = build_cell(arch, shape, mesh)
    t_build = time.time() - t0

    with mesh:
        t0 = time.time()
        lowered = jax.jit(
            cell.step_fn, donate_argnums=cell.donate_argnums
        ).lower(*cell.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = {}
    if not skip_memory:
        try:
            ma = compiled.memory_analysis()
            print(ma)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                if hasattr(ma, k):
                    mem[k] = int(getattr(ma, k))
        except Exception as ex:  # pragma: no cover - backend-dependent
            mem["error"] = str(ex)

    cost = compiled.cost_analysis() or {}
    cost = {k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float))}
    hlo = compiled.as_text()
    # Trip-count-aware analysis (XLA's cost_analysis counts while bodies
    # once; scan-based modules would under-report by the layer count).
    mine = hlo_cost.analyze(hlo)
    flops = mine["flops"]
    bytes_accessed = mine["hbm_bytes"]
    coll = dict(mine["collectives"])
    coll["total"] = mine["collective_bytes"]
    # Roofline terms (seconds) -- per §Roofline; all numbers PER-DEVICE.
    record = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "kind": cell.kind,
        "model_flops": cell.model_flops,
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll["total"],
        "collectives": coll,
        "xla_cost_analysis_flops": cost.get("flops", 0.0),
        "memory_analysis": mem,
        "compute_s": flops / PEAK_FLOPS_BF16,
        "memory_s": bytes_accessed / HBM_BW,
        "collective_s": coll["total"] / ICI_BW_PER_LINK,
        "timings": {"build": t_build, "lower": t_lower,
                    "compile": t_compile},
        "notes": cell.notes,
    }
    terms = {k: record[k] for k in ("compute_s", "memory_s", "collective_s")}
    record["dominant_term"] = max(terms, key=terms.get)
    record["useful_flops_ratio"] = (
        cell.model_flops / (flops * n_chips) if flops else None)

    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
    return record


def main() -> int:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", required=False)
    ap.add_argument("--shape", required=False)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for a, s in all_cells():
            print(f"{a}\t{s}")
        return 0

    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   out_path=args.out)
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("collectives", "memory_analysis")},
                     indent=2))
    print("memory:", json.dumps(rec["memory_analysis"]))
    print("collectives:", json.dumps(rec["collectives"]))
    print(f"DRYRUN OK {rec['arch']}/{rec['shape']} mesh={rec['mesh']} "
          f"dominant={rec['dominant_term']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
