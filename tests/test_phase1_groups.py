"""Length-ordered query groups with a width class in phase 1 of the
segmented serve step.

Phase 1 of the segmented step sorts a batch's queries by filled length,
cuts them into groups of 16 and computes each group's columns of Z over
the slots its longest query fills (rounded up to 8; 0 for a group of
padding queries).  Each query's answer must be the one the same batch gets
at the full width, with self-exclusion on or off; one compiled program
must serve every draw of lengths; and the two column counters must advance
by the host's counts each batch.

The two-device data mesh runs in its own interpreter:

    python tests/test_phase1_groups.py      # exits nonzero on a mismatch
"""

import os
import pathlib
import subprocess
import sys

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                               + os.environ.get("XLA_FLAGS", ""))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lc_rwmd import SegmentedEngine
from repro.data.docs import DocSet
from repro.distributed import lcrwmd_dist
from repro.distributed.lcrwmd_dist import build_serve_step
from repro.launch.mesh import make_host_mesh
from repro.obs import sentinel
from repro.serving.query_server import QueryServer, ServerConfig

REPO = pathlib.Path(__file__).resolve().parent.parent
H, VOCAB, DIM = 24, 512, 16
N_BASE, K = 300, 5
GROUP = 16


def lengths_of(case: str, seed: int) -> np.ndarray:
    """The filled lengths of one batch; 0 marks a padding query."""
    rng = np.random.default_rng(seed)
    if case == "skewed":     # a quarter full, a quarter of 2, the rest any
        lengths = rng.integers(2, H + 1, size=64)
        lengths[:16], lengths[16:32] = H, 2
        rng.shuffle(lengths)
        return lengths
    if case == "full":       # every group at the widest class
        return np.full(64, H)
    if case.startswith("real"):  # n real queries, then padding
        n = int(case[4:])
        return np.concatenate([rng.integers(2, H + 1, size=n),
                               np.zeros(64 - n, int)])
    if case == "b6":         # a batch 16 does not divide: at full width
        return rng.integers(2, H + 1, size=6)
    raise ValueError(case)


def docs_of(lengths: np.ndarray, seed: int) -> DocSet:
    """ELL rows of width H filled to ``lengths`` (0: all padding)."""
    rng = np.random.default_rng(seed)
    ids = np.zeros((lengths.size, H), np.int32)
    w = np.zeros((lengths.size, H), np.float32)
    for i, n in enumerate(lengths):
        if n:
            ids[i, :n] = rng.choice(VOCAB, size=n, replace=False)
            x = rng.random(n).astype(np.float32) + 0.1
            w[i, :n] = x / x.sum()
    return DocSet(ids=jnp.asarray(ids), weights=jnp.asarray(w))


def embeddings(seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).normal(
        size=(VOCAB, DIM)).astype(np.float32)


def _full_width_groups(q_valid):
    """The batch in its own order, in one group at the full width."""
    widths = (0,) + lcrwmd_dist._width_classes(H)
    return (jnp.arange(q_valid.shape[0]),
            jnp.full((1,), len(widths) - 1, jnp.int32), widths)


def _full_width_z(emb_local, t_q, q_valid, cls, widths, *, bf16_matmul):
    return lcrwmd_dist._z_from_t(emb_local, t_q, q_valid,
                                 bf16_matmul=bf16_matmul)


GROUPS = lcrwmd_dist._query_groups
GROUPED_Z = lcrwmd_dist._z_grouped


def serve_batch(mesh, case: str, self_exclude: bool, full_width: bool):
    """(ids, dists, n real) of one batch over a corpus that holds its real
    queries, served grouped or at the full width (phase 1 as
    :func:`lcrwmd_dist._z_from_t` computes it, in the batch's order)."""
    lengths = lengths_of(case, seed=5)
    queries = docs_of(lengths, seed=6)
    real = np.flatnonzero(lengths)
    base = docs_of(np.random.default_rng(7).integers(2, H + 1, N_BASE), 8)
    corpus = DocSet(ids=jnp.concatenate([base.ids, queries.ids[real]]),
                    weights=jnp.concatenate([base.weights,
                                             queries.weights[real]]))
    eng = SegmentedEngine(corpus, embeddings())
    gid = np.full(lengths.size, -1, np.int32)
    gid[real] = N_BASE + np.arange(real.size)
    saved = (lcrwmd_dist._STEP_CACHE, GROUPS, GROUPED_Z)
    if full_width:
        lcrwmd_dist._STEP_CACHE = {}
        lcrwmd_dist._query_groups = _full_width_groups
        lcrwmd_dist._z_grouped = _full_width_z
    try:
        serve = build_serve_step(mesh, k=K, engine=eng, bf16_matmul=False,
                                 self_exclude=self_exclude, row_block=16,
                                 psum_batch=2)
        res = serve(queries, gid) if self_exclude else serve(queries)
    finally:
        (lcrwmd_dist._STEP_CACHE, lcrwmd_dist._query_groups,
         lcrwmd_dist._z_grouped) = saved
    return np.asarray(res.topk.indices), np.asarray(res.topk.dists), gid


def check_equal_answers(mesh, case: str, self_exclude: bool) -> None:
    ids, dists, gid = serve_batch(mesh, case, self_exclude, False)
    ref_ids, ref_dists, _ = serve_batch(mesh, case, self_exclude, True)
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_allclose(dists, ref_dists, rtol=1e-6)
    real = gid >= 0
    assert np.isfinite(dists[real]).all()
    # Each real query is a resident doc at distance ≈ 0: among its answers
    # unless its own row is excluded.
    own = (ids[real] == gid[real, None]).any(axis=1)
    assert not own.any() if self_exclude else own.all()


CASES = [("skewed", False), ("skewed", True), ("full", False),
         ("real1", False), ("real17", True), ("real63", False),
         ("b6", True)]


# -- the grouping --------------------------------------------------------------
def host_groups(lengths: np.ndarray) -> tuple[np.ndarray, list[int], int,
                                              int]:
    """(order, width of each group, computed columns, query words) of one
    batch, counted here from its lengths."""
    b = lengths.size
    if b % GROUP:
        return np.arange(b), [H], b * H, int(lengths.sum())
    longest = np.sort(lengths)[::-1].reshape(-1, GROUP).max(axis=1)
    widths = [0 if n == 0 else min(H, -(-n // 8) * 8) for n in longest]
    return (np.argsort(-lengths, kind="stable"), widths, GROUP * sum(widths),
            int(lengths.sum()))


@pytest.mark.parametrize("case", ["skewed", "full", "real1", "real17",
                                  "real63", "b6"])
def test_query_groups_order_longest_first_and_round_up_to_8(case):
    lengths = lengths_of(case, seed=5)
    valid = np.asarray(docs_of(lengths, seed=6).weights) > 0
    valid[:, 0] &= lengths > 1          # a hole: the length is the last slot
    order, cls, widths = lcrwmd_dist._query_groups(
        jnp.asarray(valid, jnp.float32))
    assert widths == (0,) + lcrwmd_dist._width_classes(H)
    # Stable, longest first, padding last.
    want_order, want, _, _ = host_groups(lengths)
    np.testing.assert_array_equal(np.asarray(order), want_order)
    np.testing.assert_array_equal(np.take(widths, np.asarray(cls)), want)


# -- the answers ---------------------------------------------------------------
@pytest.mark.parametrize("case,self_exclude", CASES)
def test_grouped_phase1_keeps_answers_one_device(case, self_exclude):
    check_equal_answers(make_host_mesh(), case, self_exclude)


def test_answers_do_not_depend_on_batch_mates():
    """A query's group, width class and column move with its batch-mates;
    every group's GEMM has N = 16·w, so its answer stays the same."""
    mesh = make_host_mesh()
    ids, dists, gid = serve_batch(mesh, "skewed", True, False)
    # The same corpus, and the batch's last 20 queries in a batch of their
    # own: the full queries among them sit in other groups now.
    lengths = lengths_of("skewed", seed=5)
    queries = docs_of(lengths, seed=6)
    base = docs_of(np.random.default_rng(7).integers(2, H + 1, N_BASE), 8)
    eng = SegmentedEngine(
        DocSet(ids=jnp.concatenate([base.ids, queries.ids]),
               weights=jnp.concatenate([base.weights, queries.weights])),
        embeddings())
    pick = np.arange(44, 64)
    part = DocSet(ids=jnp.zeros_like(queries.ids).at[:20].set(
                      queries.ids[pick]),
                  weights=jnp.zeros_like(queries.weights).at[:20].set(
                      queries.weights[pick]))
    part_gid = np.full(64, -1, np.int32)
    part_gid[:20] = gid[pick]
    serve = build_serve_step(mesh, k=K, engine=eng, bf16_matmul=False,
                             self_exclude=True, row_block=16, psum_batch=2)
    res = serve(part, part_gid)
    np.testing.assert_array_equal(np.asarray(res.topk.indices)[:20],
                                  ids[pick])
    np.testing.assert_array_equal(np.asarray(res.topk.dists)[:20],
                                  dists[pick])


@pytest.mark.timeout(600)
def test_grouped_phase1_keeps_answers_two_device_data_mesh():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, __file__], capture_output=True,
                         text=True, env=env, timeout=540)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "phase-1 groups OK on 2 devices" in out.stdout


# -- one program, and the counters ---------------------------------------------
def test_one_program_and_the_counters_for_every_draw_of_lengths():
    k = 11                  # a step-cache key that no other test compiles
    key = f"step_cache.seg[kc={k},segs=1]"
    counts = sentinel.get_sentinel().counts
    base = docs_of(np.random.default_rng(7).integers(2, H + 1, N_BASE), 8)
    cfg = ServerConfig(k=k, max_batch=64, h_max=H, refine_symmetric=False,
                       max_wait_s=0.0)
    server = QueryServer(base, embeddings(), make_host_mesh(), cfg)
    names = ("serving_phase1_computed_cols_total",
             "serving_phase1_word_cols_total")

    def cols():
        snap = server.metrics_snapshot()["metrics"]
        return tuple(snap[n]["series"][0]["value"] if snap[n]["series"]
                     else 0.0 for n in names)

    before = counts.get(key, 0)
    seen = []
    for seed, case in enumerate(("skewed", "full", "real17", "real1")):
        lengths = lengths_of(case, seed=seed)
        real = lengths[lengths > 0]
        queries = docs_of(real, seed=seed + 100)
        start = cols()
        for i, n in enumerate(real):
            server.submit(np.asarray(queries.ids[i, :n]),
                          np.asarray(queries.weights[i, :n]))
        answers = server.flush()
        assert len(answers) == real.size
        end = cols()
        _, _, computed, words = host_groups(
            np.concatenate([real, np.zeros(64 - real.size, int)]))
        assert (end[0] - start[0], end[1] - start[1]) == (computed, words)
        seen.append(computed)
        assert counts.get(key, 0) == before + 1, case
    assert len(set(seen)) == len(seen)


def main() -> None:
    import jax

    assert len(jax.devices()) == 2, jax.devices()
    mesh = make_host_mesh(data=2, model=1)
    for case, self_exclude in CASES:
        check_equal_answers(mesh, case, self_exclude)
    print("phase-1 groups OK on 2 devices")


if __name__ == "__main__":
    main()
