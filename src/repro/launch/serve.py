"""Serving launcher for the paper's workload: LC-RWMD top-k query serving.

    PYTHONPATH=src python -m repro.launch.serve --n-docs 4096 --n-queries 64

Production (TPU fleet): ``--full`` builds the sharded serve step on the
production mesh — same code path the dry-run compiles.

:func:`serve_corpus` is the launcher's serving path — corpus, server,
stream — and ``chip_smoke.py`` at the repo root drives the same function.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import time
from typing import Callable, NamedTuple

import numpy as np

#: Fixed home of JAX's persistent compile cache when the environment names
#: none: the path is part of the cache key, so it must never move.
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn on JAX's persistent compile cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and nothing is set here; otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`.  Call before the first compile.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


class ServeRun(NamedTuple):
    corpus: object          # repro.data.synth.Corpus
    queries: list           # (ids, weights) histograms, in stream order
    answers: list           # one Answer (or ServingError) per query
    setup_s: float          # corpus build + server construction
    serve_s: float          # serve_stream wall time, first compile included
    device_bytes: dict      # device -> bytes_in_use with the server resident
    stats: dict             # the server's stats snapshot


def serve_corpus(spec, cfg, mesh,
                 make_queries: Callable[[object], list]) -> ServeRun:
    """Build the corpus of ``spec``, serve ``make_queries(corpus)`` through a
    :class:`~repro.serving.QueryServer` with ``cfg`` on ``mesh``, and
    return the answers.  The server is dropped before this returns, so its
    device state is free for whatever runs next."""
    from repro.data.synth import make_corpus
    from repro.serving.query_server import QueryServer

    t0 = time.perf_counter()
    corpus = make_corpus(spec)
    queries = make_queries(corpus)
    server = QueryServer(corpus.docs, corpus.emb, mesh, cfg)
    t1 = time.perf_counter()
    answers = list(server.serve_stream(queries))
    t2 = time.perf_counter()
    device_bytes = {str(d): (d.memory_stats() or {}).get("bytes_in_use")
                    for d in mesh.devices.flat}
    return ServeRun(corpus, queries, answers, t1 - t0, t2 - t1,
                    device_bytes, server.stats_snapshot())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-docs", type=int, default=4096)
    ap.add_argument("--n-queries", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--rerank-wmd", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    if args.full:
        from repro.launch.cells import build_cell
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        cell = build_cell("lcrwmd", "serve_set1_1m", mesh)
        print(f"[serve] production serve step built on {mesh.shape}; "
              "load the resident corpus on the fleet to start serving.")
        return 0

    from repro.data.synth import CorpusSpec
    from repro.launch.mesh import make_host_mesh
    from repro.serving.query_server import ServerConfig

    picks = np.random.default_rng(1).integers(0, args.n_docs, args.n_queries)

    def resident_copies(corpus):
        ids = np.asarray(corpus.docs.ids)
        w = np.asarray(corpus.docs.weights)
        return [(ids[i], w[i]) for i in picks]

    run = serve_corpus(
        CorpusSpec(n_docs=args.n_docs, vocab_size=8192, emb_dim=64,
                   h_max=32, mean_h=18.0, n_classes=8, seed=0),
        ServerConfig(k=args.k, max_batch=args.batch, h_max=32,
                     rerank_wmd=args.rerank_wmd),
        make_host_mesh(), resident_copies)
    answers, dt = run.answers, run.serve_s
    hit = np.mean([picks[i] in set(a[0].tolist())
                   for i, a in enumerate(answers)])
    print(f"[serve] {len(answers)} queries in {dt:.2f}s "
          f"({1e3 * dt / max(len(answers), 1):.1f} ms/q); "
          f"self-recall@{args.k}={hit:.3f}; stats={run.stats}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
