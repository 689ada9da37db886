"""Smoke run of the LC-RWMD serving path on a TPU at the paper's Set-2 widths.

    python chip_smoke.py                # one chip: the five phases below
    python chip_smoke.py --four-chips   # four chips: the sharded serve phase

One chip runs five phases and prints one line for each:

* serve — a seeded Set-2-width corpus served through the launcher's path
  (``repro.launch.serve.serve_corpus``: QueryServer → CorpusManager →
  SegmentedEngine → serve step), for exact copies of resident docs and for
  copies with 20% of their words dropped.
* cascade — the same corpus behind the pruned-WMD rerank.
* host plane — an AsyncQueryServer whose raw payloads vectorize in two
  spawned ingest processes; its answers must equal those of the direct
  (in-thread) submit path of the same server.
* kernels — the phase-1, blocked ELL SpMM and Sinkhorn Pallas kernels,
  compiled, against their jnp references.
* reference — the symmetric f32 RWMD of every served query against the
  whole corpus: recall@k and the error of the served distances.

``--four-chips`` runs only the serve phase, once on a 4-way ``data`` mesh and
once on one device in the same process, and compares the answers.

Exits non-zero, printing no result, unless JAX's first device is a TPU.
Otherwise the last line of standard output is one JSON object that names
the device, printed only when every check passed.  The phase lines are one
smoke run's readings, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import pathlib
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"

#: Set-2 widths of the paper's Table IV (h̄ 27.5, vocabulary 292,492,
#: m = 300).  n is cut from 2.8M to 65,536: each segment's resident word
#: embeddings (n·h·m·4 B ≈ 3.8 GB) and the phase-1 (v_e, B·h) intermediate
#: (≈ 3.5 GB at B = 64) must fit one chip's 16 GB.
SET2 = dict(n_docs=65_536, vocab_size=292_492, emb_dim=300, h_max=48,
            mean_h=27.5, n_classes=16, seed=0)
K = 16
MAX_BATCH = 64
RTOL_SERVED = 1e-3   # served distances vs the f32 reference
RTOL_KERNEL = 1e-4   # compiled kernels vs their jnp references
RTOL_MESH = 1e-5     # four chips vs one
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums the backend compile seconds JAX reports (a cache hit skips the
    backend compile, so a warm persistent cache shows up as fewer)."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.seconds += duration


def gram_floor(emb) -> float:
    """Absolute error floor of an f32 distance that contains exact matches.

    ‖a‖² + ‖b‖² − 2ab rounds at up to ~16·eps·max‖e‖² (the floor the
    kernel tests in tests/test_kernels.py already allow), and the square
    root turns that into this distance: a word two documents share comes
    out anywhere in [0, floor] instead of 0, in the served path and in the
    reference alike.  Weights sum to 1, so a document distance carries at
    most this much of that noise.
    """
    emb = np.asarray(emb, np.float32)
    sq = float(np.max(np.einsum("ij,ij->i", emb, emb)))
    return float(np.sqrt(16 * np.finfo(np.float32).eps * sq))


def errors(got, want, rtol: float, atol: float) -> tuple[float, float]:
    """(largest relative error where |want| > atol, largest excess of
    |got − want| over rtol·|want|); non-finite ``got`` counts as infinite."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    diff = np.where(np.isfinite(got), np.abs(got - want), np.inf)
    big = np.abs(want) > atol
    rel = diff[big] / np.abs(want[big])
    excess = diff - rtol * np.abs(want)
    return (float(rel.max()) if rel.size else 0.0,
            max(float(excess.max()), 0.0) if excess.size else 0.0)


def check_close(name: str, got, want, rtol: float, atol: float,
                failed: list) -> tuple[float, float]:
    """|got − want| <= rtol·|want| + atol everywhere (numpy's allclose)."""
    rel, excess = errors(got, want, rtol, atol)
    if not excess <= atol:
        failed.append(f"{name}: |got - want| exceeds {rtol:g}·|want| by "
                      f"{excess:.3g} (limit {atol:.3g}); largest relative "
                      f"error {rel:.3g}")
    return rel, excess


def resident_queries(corpus, n_exact: int, n_perturbed: int, seed: int):
    """``n_exact`` exact copies of resident docs, then ``n_perturbed`` copies
    of other resident docs with 20% of their words dropped (as
    examples/serve_queries.py makes them).  Returns (stream, source ids)."""
    rng = np.random.default_rng(seed)
    ids = np.asarray(corpus.docs.ids)
    w = np.asarray(corpus.docs.weights)
    src = rng.choice(len(ids), n_exact + n_perturbed, replace=False)
    stream = []
    for j, i in enumerate(src):
        wi = w[i].copy()
        if j >= n_exact:
            kept = np.where(rng.random(len(wi)) < 0.2, 0.0, wi)
            if kept.sum() > 0:
                wi = kept.astype(np.float32)
        stream.append((ids[i].copy(), wi))
    return stream, src


def _answer_checks(name, answers, src, n_exact, failed) -> dict:
    bad = [a for a in answers if isinstance(a, BaseException)]
    if bad:
        failed.append(f"{name}: {len(bad)} queries failed, first: {bad[0]!r}")
        return {}
    if not all(np.isfinite(a[1]).all() for a in answers):
        failed.append(f"{name}: non-finite served distances")
    self_at1 = np.mean([a[0][0] == s for a, s in
                        zip(answers[:n_exact], src[:n_exact])])
    if self_at1 != 1.0:
        failed.append(f"{name}: self-recall@1={self_at1:.3f} on exact copies")
    pert = [s in set(a[0].tolist()) for a, s in
            zip(answers[n_exact:], src[n_exact:])]
    return {"self_recall@1": float(self_at1),
            "perturbed_src_in_topk": float(np.mean(pert)) if pert else None}


def phase_serve(spec, mesh, *, n_exact: int, n_perturbed: int, k: int = K,
                max_batch: int = MAX_BATCH, rerank_wmd: bool = False):
    """Serve resident-copy queries through the launcher's serving path.

    Returns (metrics, failed checks, ServeRun, source doc per query)."""
    from repro.launch.serve import serve_corpus
    from repro.serving.query_server import ServerConfig

    src = []

    def make_queries(corpus):
        stream, s = resident_queries(corpus, n_exact, n_perturbed, seed=1)
        src.extend(s.tolist())
        return stream

    cfg = ServerConfig(k=k, max_batch=max_batch, h_max=spec.h_max,
                       rerank_wmd=rerank_wmd)
    run = serve_corpus(spec, cfg, mesh, make_queries)
    ids = np.asarray(run.corpus.docs.ids)
    w = np.asarray(run.corpus.docs.weights)
    failed: list = []
    metrics = {"n": spec.n_docs, "v_e": int(np.unique(ids[w > 0]).size),
               "queries": len(run.answers),
               "setup_s": round(run.setup_s, 3),
               "serve_s": round(run.serve_s, 3)}
    metrics.update(_answer_checks("cascade" if rerank_wmd else "serve",
                                  run.answers, src, n_exact, failed))
    if rerank_wmd:
        metrics["wmd_reranks"] = run.stats["wmd_reranks"]
        if run.stats["wmd_reranks"] != len(run.answers):
            failed.append("cascade: not every query went through the rerank")
    return metrics, failed, run, src


def phase_host_plane(corpus, mesh, *, n_payloads: int, workers: int,
                     k: int = K, max_batch: int = MAX_BATCH):
    """Raw payloads through spawned ingest workers == direct submits."""
    from benchmarks._slo_workload import BenchVectorizer
    from repro.serving import AsyncQueryServer, ServerConfig

    spec = corpus.spec
    vec = BenchVectorizer(vocab=spec.vocab_size, h_max=spec.h_max)
    cfg = ServerConfig(k=k, max_batch=max_batch, h_max=spec.h_max,
                       ingest_workers=workers)
    t0 = time.perf_counter()
    with AsyncQueryServer(corpus.docs, corpus.emb, mesh, cfg,
                          preprocess=vec) as server:
        pooled = [server.submit(p) for p in range(n_payloads)]
        server.drain()
        pooled = [f.result() for f in pooled]
        direct = [server.submit(*vec(p)) for p in range(n_payloads)]
        server.drain()
        direct = [f.result() for f in direct]
    failed = []
    same = sum(np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
               for a, b in zip(pooled, direct))
    if same != n_payloads:
        failed.append(f"host plane: {n_payloads - same} of {n_payloads} "
                      "pooled answers differ from the in-thread answers")
    return {"payloads": n_payloads, "workers": workers,
            "pooled_equal_in_thread": same,
            "wall_s": round(time.perf_counter() - t0, 3)}, failed


def phase_kernels(corpus, *, v: int, n: int, b: int, pairs: int,
                  interpret: bool):
    """The served path's Pallas kernels against their jnp references, at the
    corpus's widths (m, h) and a query batch of ``b``."""
    import jax
    import jax.numpy as jnp

    from repro.core.lc_rwmd import phase1_z_from_t, phase2_spmm
    from repro.core.wmd import wmd_batched_from_t
    from repro.data.docs import DocSet
    from repro.kernels import ops
    from repro.serving.query_server import ServerConfig

    emb_full = jnp.asarray(corpus.emb)
    ids = np.asarray(corpus.docs.ids)
    w = np.asarray(corpus.docs.weights)
    emb = emb_full[:v]
    h, m = ids.shape[1], emb.shape[1]
    t = emb_full[ids[:b]]                                  # (B, h, m)
    valid = jnp.asarray(w[:b] > 0)
    r_ids = jnp.asarray(ids[b:b + n] % v)
    r_w = jnp.asarray(w[b:b + n])
    t1, w1 = emb_full[ids[:pairs]], jnp.asarray(w[:pairs])
    t2 = emb_full[ids[pairs:2 * pairs]]
    w2 = jnp.asarray(w[pairs:2 * pairs])
    sink_kw = ServerConfig().wmd_kw

    with jax.default_matmul_precision("highest"):
        z_ref = jax.jit(phase1_z_from_t, static_argnums=3)(
            emb, t.reshape(b * h, m), valid.reshape(-1), b)
        d_ref = jax.jit(phase2_spmm)(DocSet(ids=r_ids, weights=r_w), z_ref)
        s_ref = jax.jit(functools.partial(wmd_batched_from_t, **sink_kw))(
            t1, w1, t2, w2)
    z = ops.lc_rwmd_phase1_pregathered(
        emb, t, valid.astype(jnp.float32), interpret=interpret)
    d = ops.spmm_ell(r_ids, r_w, z_ref, interpret=interpret)
    s = ops.sinkhorn_wmd(t1, w1, t2, w2, interpret=interpret, **sink_kw)

    failed: list = []
    floor = gram_floor(corpus.emb)
    p1 = check_close("phase-1 kernel", z, z_ref, RTOL_KERNEL, floor, failed)
    sp = check_close("spmm kernel", d, d_ref, RTOL_KERNEL, 1e-6, failed)
    sk = check_close("sinkhorn kernel", s, s_ref, RTOL_KERNEL, floor, failed)
    return {"v": v, "m": m, "h": h, "B": b, "n": n, "pairs": pairs,
            "phase1_rel_err": p1[0], "phase1_excess": p1[1],
            "spmm_rel_err": sp[0], "spmm_excess": sp[1],
            "sinkhorn_rel_err": sk[0], "sinkhorn_excess": sk[1],
            "gram_floor": floor}, failed


def phase_reference(run, *, k: int = K):
    """Symmetric f32 RWMD of every served query against the whole corpus."""
    import jax
    import jax.numpy as jnp

    from repro.core.rwmd import rwmd_many_vs_many
    from repro.serving.staging import pad_batch

    corpus = run.corpus
    queries = pad_batch(run.queries, len(run.queries), corpus.spec.h_max)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(functools.partial(rwmd_many_vs_many, query_chunk=1))(
            corpus.docs, queries, jnp.asarray(corpus.emb))
    ref = np.asarray(ref)                                  # (n, queries)
    dt = time.perf_counter() - t0
    served_ids = np.stack([a[0] for a in run.answers])
    served_d = np.stack([a[1] for a in run.answers])
    want = ref[served_ids, np.arange(len(run.answers))[:, None]]
    top = np.argsort(ref, axis=0, kind="stable")[:k].T    # (queries, k)
    recall = np.mean([len(set(s) & set(r)) / k
                      for s, r in zip(served_ids.tolist(), top.tolist())])
    failed: list = []
    floor = gram_floor(corpus.emb)
    rel, excess = check_close("served distances", served_d, want,
                              RTOL_SERVED, floor, failed)
    return {f"recall@{k}": float(recall), "dist_rel_err": rel,
            "dist_excess": excess, "gram_floor": floor,
            "reference_s": round(dt, 3)}, failed


def _phase(name: str, clock, failed: list, fn, *args, **kw):
    """Run one phase, print its line, and collect its failed checks."""
    import jax

    gc.collect()   # the previous phase's server leaves the device first
    c0 = clock.seconds
    out = fn(*args, **kw)
    failed += out[1]
    stats = jax.devices()[0].memory_stats() or {}
    fields = dict(out[0], compile_s=round(clock.seconds - c0, 3),
                  peak_bytes=stats.get("peak_bytes_in_use"))
    print(f"[{name}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)
    return out


def run_one_chip(clock) -> list:
    from repro.data.synth import CorpusSpec
    from repro.launch.mesh import make_host_mesh

    spec, mesh, failed = CorpusSpec(**SET2), make_host_mesh(), []
    run = _phase("serve", clock, failed, phase_serve, spec, mesh,
                 n_exact=128, n_perturbed=128)[2]
    _phase("cascade", clock, failed, phase_serve, spec, mesh, n_exact=32,
           n_perturbed=32, rerank_wmd=True)
    _phase("host_plane", clock, failed, phase_host_plane, run.corpus, mesh,
           n_payloads=128, workers=2)
    _phase("kernels", clock, failed, phase_kernels, run.corpus, v=32_768,
           n=8_192, b=MAX_BATCH, pairs=128, interpret=False)
    _phase("reference", clock, failed, phase_reference, run)
    return failed


def run_four_chips(clock) -> list:
    import jax

    from repro.data.synth import CorpusSpec
    from repro.launch.mesh import make_host_mesh

    if len(jax.devices()) < 4:
        return [f"--four-chips needs 4 devices, found {len(jax.devices())}"]
    spec, failed, runs = CorpusSpec(**SET2), [], {}
    for name, mesh in (("serve_1dev", make_host_mesh()),
                       ("serve_4dev", make_host_mesh(data=4))):
        runs[name] = _phase(name, clock, failed, phase_serve, spec, mesh,
                            n_exact=128, n_perturbed=128)[2]
        print(f"[{name}] bytes_in_use={runs[name].device_bytes}", flush=True)
    one, four = runs["serve_1dev"].answers, runs["serve_4dev"].answers
    ids_equal = sum(np.array_equal(a[0], b[0]) for a, b in zip(one, four))
    if ids_equal != len(one):
        failed.append(f"four chips: {len(one) - ids_equal} of {len(one)} "
                      "top-k id lists differ from one device")
    rel, excess = check_close(
        "four chips vs one", np.stack([a[1] for a in four]),
        np.stack([a[1] for a in one]), RTOL_MESH,
        gram_floor(runs["serve_1dev"].corpus.emb), failed)
    print(f"[mesh] topk_ids_equal={ids_equal}/{len(one)} dist_rel_err={rel} "
          f"dist_excess={excess}", flush=True)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a 4-way data mesh and compare with one "
                         "device (needs a four-chip host)")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r}, not a "
              "TPU; nothing was run", file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    from repro.launch.serve import use_compile_cache

    use_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    failed = run_four_chips(clock) if args.four_chips else run_one_chip(clock)
    if failed:
        print("chip_smoke FAILED:\n  " + "\n  ".join(failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
