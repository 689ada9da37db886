"""Length-ordered slabs with a width class in the segmented serve step.

Phase 2 of the segmented step sorts each data shard's rows by filled
length and gathers, per slab, only the ELL slots its longest row fills
(rounded up to 8).  The answers must be those of the same rows served at
the full width, in any order; one compiled program must serve every draw
of lengths and every delete; and the two slot counters must advance by
the host's counts each batch.

The two-device data mesh runs in its own interpreter:

    python tests/test_slab_widths.py        # exits nonzero on a mismatch
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
from repro.obs import Observability, sentinel

REPO = pathlib.Path(__file__).resolve().parent.parent
H1, VOCAB, DIM = 24, 512, 16
ROW_BLOCK, PSUM_BATCH = 16, 2          # 32-row slabs
K = 5


def skewed_docs(n: int, seed: int) -> DocSet:
    """n ELL rows of width H1: a quarter fill every slot, a quarter fill
    two, the rest a uniform length in [2, H1]; rows in random order."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(2, H1 + 1, size=n)
    lengths[: n // 4] = H1
    lengths[n // 4: n // 2] = 2
    rng.shuffle(lengths)
    ids = np.zeros((n, H1), np.int32)
    w = np.zeros((n, H1), np.float32)
    for i, length in enumerate(lengths):
        ids[i, :length] = rng.choice(VOCAB, size=length, replace=False)
        x = rng.random(length).astype(np.float32) + 0.1
        w[i, :length] = x / x.sum()
    return DocSet(ids=jnp.asarray(ids), weights=jnp.asarray(w))


def embeddings(seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).normal(
        size=(VOCAB, DIM)).astype(np.float32)


def _widest(r_w, n_shards, blk, widths):
    """The placement with every slab in the widest class."""
    perm, row_map, cls = ORDER(r_w, n_shards, blk, widths)
    return perm, row_map, np.full_like(cls, len(widths) - 1)


def _unordered(r_w, n_shards, blk, widths):
    """The rows in their own order, every slab at the full width."""
    n = r_w.shape[0]
    n_local = n // n_shards
    row_map = np.tile(np.arange(n_local, dtype=np.int32), n_shards)
    cls = np.full(n // blk, len(widths) - 1, np.int32)
    return np.arange(n), row_map, cls


ORDER = lcrwmd_dist._length_order
PLACEMENTS = {"widest": _widest, "unordered": _unordered}


def serve_two_segments(mesh, placement=None):
    """(ids, dists, gathered slots, filled slots) of one self-excluding
    batch over a base + delta engine with tombstones, placed by
    ``placement`` (default: the program's length order)."""
    docs = skewed_docs(300, seed=11)
    eng = SegmentedEngine(docs[:200], embeddings())
    eng.append(docs[200:])
    eng.delete([3, 40, 41, 217, 299])
    qids = np.array([0, 3, 17, 150, 210, 250], np.int32)
    queries = DocSet(ids=docs.ids[qids], weights=docs.weights[qids])
    obs = Observability()
    lcrwmd_dist._length_order = placement or ORDER
    try:
        serve = build_serve_step(
            mesh, k=K, engine=eng, bf16_matmul=False, self_exclude=True,
            row_block=ROW_BLOCK, psum_batch=PSUM_BATCH, obs=obs)
        res = serve(queries, qids)
    finally:
        lcrwmd_dist._length_order = ORDER
    snap = obs.metrics.snapshot()
    return (np.asarray(res.topk.indices), np.asarray(res.topk.dists),
            snap["serving_phase2_gathered_slots_total"]["series"][0]["value"],
            snap["serving_phase2_filled_slots_total"]["series"][0]["value"])


def check_equal_answers(mesh, name: str) -> None:
    ids, dists, gathered, filled = serve_two_segments(mesh)
    ref_ids, ref_dists, ref_gathered, ref_filled = serve_two_segments(
        mesh, PLACEMENTS[name])
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_allclose(dists, ref_dists, rtol=1e-6)
    assert np.isfinite(dists).all()
    # The tombstoned rows never answer; no query answers with itself.
    assert not np.isin(ids, [3, 40, 41, 217, 299]).any()
    assert not (ids == np.array([0, 3, 17, 150, 210, 250])[:, None]).any()
    assert filled == ref_filled
    assert ref_gathered % (ROW_BLOCK * PSUM_BATCH * H1) == 0
    assert filled < gathered < ref_gathered


# -- the placement -------------------------------------------------------------
@pytest.mark.parametrize("h1,want", [
    (48, (8, 16, 24, 32, 40, 48)), (12, (8, 12)), (5, (5,)), (8, (8,))])
def test_width_classes(h1, want):
    assert lcrwmd_dist._width_classes(h1) == want


def test_length_order_sorts_each_shard_and_rounds_up_to_8():
    lengths = np.array([3, 24, 0, 9, 17, 2, 8, 24])
    r_w = (np.arange(H1)[None, :] < lengths[:, None]).astype(np.float32)
    r_w[1, 5] = 0.0                       # a hole: the length is the last slot
    widths = lcrwmd_dist._width_classes(H1)
    perm, row_map, cls = lcrwmd_dist._length_order(r_w, 2, 2, widths)
    # Shard 0 holds rows 0-3 (lengths 3, 24, 0, 9); shard 1 rows 4-7.
    np.testing.assert_array_equal(perm, [2, 0, 3, 1, 5, 6, 4, 7])
    np.testing.assert_array_equal(row_map, [2, 0, 3, 1, 1, 2, 0, 3])
    # Slab maxima 3, 24, 8, 24 -> widths 8, 24, 8, 24.
    np.testing.assert_array_equal(np.take(widths, cls), [8, 24, 8, 24])
    assert row_map.dtype == np.int32 and cls.dtype == np.int32


def test_full_rows_take_the_widest_class():
    widths = lcrwmd_dist._width_classes(H1)
    perm, _map, cls = lcrwmd_dist._length_order(
        np.ones((64, H1), np.float32), 2, 8, widths)
    np.testing.assert_array_equal(perm, np.arange(64))
    assert (cls == len(widths) - 1).all()


# -- the answers ---------------------------------------------------------------
@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
def test_length_order_keeps_answers_one_device(placement):
    check_equal_answers(make_host_mesh(), placement)


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_length_order_keeps_answers_two_device_data_mesh():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, __file__], capture_output=True,
                         text=True, env=env, timeout=540)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "slab widths OK on 2 devices" in out.stdout


# -- one program ---------------------------------------------------------------
def host_slot_counts(docs: DocSet, blk: int) -> tuple[int, int]:
    """(gathered, filled) ELL slots of one batch over a one-segment,
    one-shard engine, counted here from the rows' lengths."""
    w = np.asarray(docs.weights)
    n = -(-w.shape[0] // blk) * blk
    length = np.zeros(n, int)
    length[:w.shape[0]] = [np.flatnonzero(r).max() + 1 if r.any() else 0
                           for r in w]
    slab_max = np.sort(length).reshape(-1, blk).max(axis=1)
    widths = np.minimum(H1, np.maximum(8, -(-slab_max // 8) * 8))
    return int(blk * widths.sum()), int(np.count_nonzero(w))


def test_one_program_for_every_draw_of_lengths_and_delete():
    mesh = make_host_mesh()
    kc = 13                 # a step-cache key that no other test compiles
    key = f"step_cache.seg[kc={kc},segs=1]"
    counts = sentinel.get_sentinel().counts
    obs = Observability()
    series = {}

    def slots():
        snap = obs.metrics.snapshot()
        return tuple(snap[name]["series"][0]["value"] for name in (
            "serving_phase2_gathered_slots_total",
            "serving_phase2_filled_slots_total"))

    queries = skewed_docs(8, seed=99)
    before = counts.get(key, 0)
    for seed in (1, 2):
        docs = skewed_docs(256, seed=seed)
        eng = SegmentedEngine(docs, embeddings(), vocab_pad=VOCAB)
        serve = build_serve_step(
            mesh, k=kc, engine=eng, bf16_matmul=False, obs=obs,
            row_block=ROW_BLOCK, psum_batch=PSUM_BATCH)
        for delete in (False, True):
            if delete:
                eng.delete([int(np.asarray(serve(queries).topk.indices)[0,
                                                                        0])])
            start = slots()
            ans = serve(queries)
            assert np.isfinite(np.asarray(ans.topk.dists)).all()
            end = slots()
            series[(seed, delete)] = (end[0] - start[0], end[1] - start[1])
            assert counts.get(key, 0) == before + 1, (seed, delete)
    # The two draws differ, and each batch adds the host's counts.
    for seed in (1, 2):
        want = host_slot_counts(skewed_docs(256, seed=seed),
                                ROW_BLOCK * PSUM_BATCH)
        assert series[(seed, False)] == want
        assert series[(seed, True)] == want
    assert series[(1, False)] != series[(2, False)]


def main() -> None:
    import jax

    assert len(jax.devices()) == 2, jax.devices()
    mesh = make_host_mesh(data=2, model=1)
    for name in sorted(PLACEMENTS):
        check_equal_answers(mesh, name)
    print("slab widths OK on 2 devices")


if __name__ == "__main__":
    main()
