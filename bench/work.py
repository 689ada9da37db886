"""The work a serve step must do, counted from the cell's sizes, and the
chip's peaks.

The counts never read the compiled program, so they stay the same whatever
implements the step:

* phase 1: 2·v_e·m·h_q operations per real query (h_q its real words);
* phase 2: 2·nnz(resident) operations per real query;
* bytes: the resident ELL histograms (int32 ids and float32 weights), the
  vocabulary-restricted embeddings (v_e, m) and Z (v_e, max_batch), each
  read or written once per batch.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of ``device_kind``; an unknown kind is an
    error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def step_work(*, v_e: int, m: int, nnz: int, n_docs: int, h_max: int,
              max_batch: int, queries: float, words: float) -> tuple[float, float]:
    """(operations, bytes) of one batch of ``queries`` real queries holding
    ``words`` real words between them."""
    flops = 2.0 * v_e * m * words + 2.0 * nnz * queries
    nbytes = 8.0 * n_docs * h_max + 4.0 * v_e * m + 4.0 * v_e * max_batch
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """Least time of the work at the peaks, and which bound sets it."""
    t_c = flops / float(pk["flops_per_s"])
    t_m = nbytes / float(pk["bytes_per_s"])
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
