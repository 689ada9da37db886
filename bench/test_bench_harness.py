"""Tests of the benchmark harness, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_bench_harness.py

They rehearse each cell's inner functions, check the trace reduction, the
work count and the corpus generator against hand-computed values, show that
the control and the planted faults come out not correct, that a run without
a chip prints no result, and that a cell is added by adding files only.
"""

from __future__ import annotations

import copy
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import corpus as corpus_lib  # noqa: E402
from bench import harness, traffic, work  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

CELLS = tuple(w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"])
#: The pruned-WMD cascade over the poisson80 cell's corpus and mix (no cell
#: runs it yet: the program's batched Sinkhorn is at fault, PERF.md, Open
#: questions), so that the reference's WMD mode stays tested.
WMD = "wmd"


def _cell(name: str) -> harness.Cell:
    if name != WMD:
        return harness.load_cell(name)
    cell = harness.load_cell("set2_knn.poisson80")
    cell.config["server"].update(
        rerank_wmd=True, wmd_kw={"eps": 0.02, "eps_scaling": 3,
                                 "max_iters": 200})
    return cell


def tiny(cell_name: str) -> harness.Cell:
    """The cell at a size the CPU runs in seconds; widths m, h̄ kept."""
    cell = _cell(cell_name)
    cfg = copy.deepcopy(cell.config)
    cfg.update(n_docs=512, vocab_size=4096, h_max=16, mean_h=8.0, n_classes=4)
    cfg["server"].update(k=4, max_batch=8, h_max=16, vocab_pad=512)
    cfg["knee_qps"] = 40
    cfg["check"]["sample"] = 16
    cell.config = cfg
    return cell


def cpu():
    import jax
    return jax.devices()[0]


# -- cells --------------------------------------------------------------------
@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal_is_correct(name):
    res = harness.run(tiny(name), 2**33 + 17, 1.5, False,
                      time.perf_counter(), out=lambda *_: None, device=cpu())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in _cell(name).end_to_end}
    assert set(res["metrics"]) == want
    assert list(res)[-1] == "checks"


#: Answers altered where they are produced, each with the number that must
#: catch it: ids shifted by one document; the best candidate repeated k
#: times with its own distance; the right answer in falling order.
FAULTS = {
    "shifted": ("cand_excess", lambda ids, d, n: ((ids + 1) % n, d)),
    "repeated": ("repeats", lambda ids, d, n: (ids[:1].repeat(len(ids)),
                                               d[:1].repeat(len(d)))),
    "reversed": ("unsorted", lambda ids, d, n: (ids[::-1], d[::-1])),
}


def _alter_answers(monkeypatch, fault):
    """Plant ``fault`` in the collected answers of every batch."""
    from repro.serving import query_server

    real = query_server._ServeCore.collect

    def collect(self, inflight):
        out = real(self, inflight)
        return [query_server.Answer(*fault(np.asarray(a[0]), np.asarray(a[1]),
                                           self.engine.n_docs))
                for a in out]

    monkeypatch.setattr(query_server._ServeCore, "collect", collect)


@pytest.mark.parametrize("name", ("set2_knn.poisson80", WMD))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_altered_answers_are_not_correct(name, fault, monkeypatch):
    number, alter = FAULTS[fault]
    _alter_answers(monkeypatch, alter)
    res = harness.run(tiny(name), 5, 1.0, False, time.perf_counter(),
                      out=lambda *_: None, device=cpu())
    assert not res["correct"]
    check = res["checks"][number]
    assert check["value"] > check["limit"], res["checks"]


@pytest.mark.parametrize("name", ("set2_knn.poisson80", WMD))
def test_control_is_not_correct(name):
    """The reference at one bfloat16 pass, in the program's place."""
    cell = tiny(name)
    served = harness.build(cell, 21)
    served.server.close()
    harness.make_queries(served, cell, 1.0, 21, 0)
    ref = harness.make_reference(served, cell)
    qi, qw = served.q_ids[:16], served.q_w[:16]
    ids, d = ref.answers(qi, qw, passes=1)
    nums = ref.judge(qi, qw, ids, d)
    ok, _ = harness.limits_hold(nums, cell.config["check"]["limits"])
    assert not ok, nums
    ids, d = ref.answers(qi, qw, passes=6)
    ok, _ = harness.limits_hold(ref.judge(qi, qw, ids, d),
                                cell.config["check"]["limits"])
    assert ok


def test_reference_sinkhorn_meets_exact_emd():
    """The reference's Sinkhorn at the cell's settings reaches the exact
    transport cost (scipy's LP) on small pairs it converges on."""
    from scipy.optimize import linprog

    from bench.reference import sinkhorn_cost

    rng = np.random.default_rng(0)
    p, h = 6, 5
    a = rng.random((p, h)).astype(np.float32)
    b = rng.random((p, h)).astype(np.float32)
    a /= a.sum(1, keepdims=True)
    b /= b.sum(1, keepdims=True)
    cost = (10 * rng.random((p, h, h))).astype(np.float32)
    got = np.asarray(sinkhorn_cost(a, b, cost, eps=0.02, eps_scaling=3,
                                   max_iters=2000))
    eq = np.zeros((2 * h, h * h))
    for i in range(h):
        eq[i, i * h:(i + 1) * h] = 1
        eq[h + i, i::h] = 1
    for j in range(p):
        lp = linprog(cost[j].ravel(), A_eq=eq[:-1],
                     b_eq=np.concatenate([a[j], b[j]])[:-1], method="highs")
        assert got[j] == pytest.approx(lp.fun, rel=1e-2)


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_mesh_follows_the_cell(monkeypatch):
    """The server's mesh has one device per chip the cell names."""
    cell = tiny(CELLS[0])
    served = harness.build(cell, 3)
    assert served.mesh.devices.size == 1
    served.server.close()
    cell.chips = 2
    with pytest.raises(ValueError):        # one CPU device: no 2-chip mesh
        harness.build(cell, 3)


def test_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a mix and a metric added as files make a new cell;
    the metric reads a series of the program's registry that no other
    reader reads."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    cfg = json.loads((ROOT / "bench/configs/set2_knn.json").read_text())
    cfg["n_docs"] = 1024
    (tmp_path / "bench/configs/small_knn.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/steady.json").write_text(json.dumps(
        {"arrival": "x", "base_seed": 1,
         "phases": [{"seconds": None, "rate_x_knee": 0.5}]}))
    (tmp_path / "bench/metrics/collect_ms.py").write_text(
        "def read(run):\n"
        "    s, n = run.counter('serving_device_collect_seconds')\n"
        "    return 1e3 * s / n if n else None\n")
    spec["configs"].append(dict(spec["configs"][0], name="small_knn",
                                file="bench/configs/small_knn.json"))
    spec["workloads"].append({"name": "small_knn.steady", "config": "small_knn",
                              "traffic": "steady", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "collect_ms", "unit": "ms",
                              "better": "lower", "source": "program_counter",
                              "layer": "host plane", "moves": "qps",
                              "workloads": ["small_knn.steady"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    assert all(after[p] == b for p, b in before.items())   # nothing edited

    cell = harness.load_cell("small_knn.steady", root=tmp_path)
    assert cell.config["n_docs"] == 1024
    assert cell.mix["phases"][0]["rate_x_knee"] == 0.5
    assert [m["name"] for m in cell.per_layer] == ["collect_ms"]

    # The reader finds its series among the registry's, read around a
    # served batch at a tiny size.
    small = tiny("set2_knn.poisson80")
    served = harness.build(small, 4)
    harness.make_queries(served, small, 0.5, 4, 20)
    before = harness.series_totals(served.server)
    harness.warm_up(served, 20)
    after = harness.series_totals(served.server)
    served.server.close()
    deltas = {n: (a[0] - before.get(n, (0.0, 0))[0],
                  a[1] - before.get(n, (0.0, 0))[1]) for n, a in after.items()}
    view = harness.RunView(cell, deltas, None, {}, {})
    got = harness.load_reader("collect_ms", tmp_path)(view)
    assert deltas["serving_device_collect_seconds"][1] > 0
    assert got is not None and got > 0


# -- generator ----------------------------------------------------------------
SPEC = dict(n_docs=4096, vocab_size=20000, emb_dim=8, h_max=48, mean_h=27.5,
            n_classes=16, topic_noise=0.25, zipf_a=1.3, emb_topic_scale=4.0,
            emb_word_scale=1.0)


def test_corpus_statistics():
    c = corpus_lib.make_corpus(SPEC, 3)
    nz = (c.weights > 0).sum(1)
    assert abs(nz.mean() - 27.5) < 0.5          # h̄ of the spec
    assert nz.max() <= 48 and nz.min() >= 1     # the h_max clip
    np.testing.assert_allclose(c.weights.sum(1), 1.0, rtol=1e-6)
    assert ((c.ids == 0) | (c.weights > 0)).all()
    # Every real word of a row is distinct.
    for r in range(50):
        real = c.ids[r][c.weights[r] > 0]
        assert len(set(real.tolist())) == len(real)
    # v_e: the topic words a corpus hits plus the uniform noise words.
    v_e = corpus_lib.vocab_in_use(c.ids, c.weights)
    assert 0.5 * SPEC["vocab_size"] < v_e < SPEC["vocab_size"]


def test_corpus_is_seeded():
    a = corpus_lib.make_corpus(SPEC, 2**40 + 3)
    b = corpus_lib.make_corpus(SPEC, 2**40 + 3)
    c = corpus_lib.make_corpus(SPEC, 2**40 + 4)
    assert (a.ids == b.ids).all() and a.device_seed == b.device_seed
    assert not (a.ids == c.ids).all()


def test_two_seeds_compile_the_same_shapes():
    """Under the config's vocab_pad, every seed's restricted vocabulary pads
    to one size, so the serve step's shapes do not depend on the seed."""
    cfg = json.loads((ROOT / "bench/configs/set2_knn.json").read_text())
    spec = harness.corpus_spec(cfg)
    pad = cfg["server"]["vocab_pad"]
    sizes = set()
    for seed in (1, 2**31 + 9):
        rng = np.random.default_rng(seed)
        tm = corpus_lib.topic_model(spec, rng)
        lengths = corpus_lib.doc_lengths(spec, spec["n_docs"], rng)
        ids, w = corpus_lib.make_docs(spec, tm, lengths, rng)
        v_e = corpus_lib.vocab_in_use(ids, w)
        sizes.add(-(-v_e // pad) * pad)
        assert ids.shape == (spec["n_docs"], spec["h_max"])
    assert len(sizes) == 1


def test_schedule_same_work_for_every_seed():
    mix = {"base_seed": 13, "phases": [{"seconds": 0.25, "rate_x_knee": 2.0},
                                       {"seconds": 0.75, "rate_x_knee": 1 / 3}]}
    a = traffic.make_schedule(mix, 400.0, 3.0, SPEC, 1)
    b = traffic.make_schedule(mix, 400.0, 3.0, SPEC, 2)
    assert len(a.arrivals) == len(b.arrivals) == 3 * (200 + 100)
    assert sorted(a.lengths) == sorted(b.lengths)
    assert not (a.arrivals == b.arrivals).all()
    assert (np.diff(a.arrivals) >= 0).all() and a.arrivals[-1] < 3.0
    on = ((a.arrivals % 1.0) < 0.25).sum()
    assert on == 3 * 200


def test_percentile_matches_numpy():
    x = np.random.default_rng(0).exponential(size=1001)
    for q in (50, 95, 99):
        assert traffic.percentile(x, q) == pytest.approx(np.percentile(x, q))
    assert traffic.percentile([1.0, np.inf], 99) == np.inf


# -- trace reduction, work, peaks ---------------------------------------------
def _space():
    dev = {"XLA Modules": [("jit_step(3)", 1000.0, 4000.0),
                           ("jit_step(3)", 7000.0, 2000.0),
                           ("jit__symmetric_refine(9)", 5000.0, 1000.0)],
           "XLA Ops": [("fusion.1", 1000.0, 3000.0),
                       ("convolution", 3500.0, 1500.0),
                       ("fusion.2", 5000.0, 1000.0),
                       ("fusion.1", 7000.0, 2000.0)]}
    host = {"main": [("bench.window", 0.0, 10000.0)],
            "worker": [("PjitFunction(step)", 6000.0, 900.0)]}
    return {"/device:TPU:0": dev, "/host:CPU": host}


def test_busy_union():
    assert trace_lib.union_ns([(0, 10), (5, 20), (30, 40)], 0, 100) == 30
    assert trace_lib.union_ns([(0, 10), (5, 20), (30, 40)], 8, 35) == 17
    assert trace_lib.union_ns([], 0, 10) == 0


def test_trace_summary():
    s = trace_lib.summarize(_space(), trace_lib.span_ns(_space(),
                                                        "bench.window"))
    assert s.window_s == pytest.approx(1e-5)
    # busy: [1000, 6000) and [7000, 9000)
    assert s.busy_s == pytest.approx(7e-6)
    assert s.modules["jit_step"] == (2, pytest.approx(6e-6))
    assert s.modules["jit__symmetric_refine"] == (1, pytest.approx(1e-6))
    assert s.device_ops[0] == ["fusion.1", pytest.approx(5e-6)]
    # gaps: [0, 1000), [6000, 7000), [9000, 10000); the middle one is the
    # worker's dispatch.
    assert [round(t * 1e9) for _n, t in s.idle_gaps] == [1000, 1000, 1000]
    assert sorted(n for n, _t in s.idle_gaps) == [
        "PjitFunction(step)", "host idle", "host idle"]


def test_metric_readers():
    s = trace_lib.summarize(_space(), (0.0, 10000.0))
    cell = harness.load_cell("set2_knn.saturate")
    view = harness.RunView(cell, {"serving_batch_size": (128.0, 2),
                                  "serving_queue_wait_seconds": (0.5, 100),
                                  "serving_dispatch_host_seconds": (0.01, 2)},
                           s, work.peaks("TPU v5 lite"),
                           dict(v_e=1000, m=300, nnz=5000, n_docs=100,
                                h_max=48, max_batch=64, words_per_query=27.5))
    read = lambda m: harness.load_reader(m)(view)  # noqa: E731
    assert read("queue_wait_ms") == pytest.approx(5.0)
    assert read("batch_fill") == pytest.approx(100.0)
    assert read("dispatch_host_ms") == pytest.approx(5.0)
    assert read("step_device_ms") == pytest.approx(3e-3)
    assert read("idle_share") == pytest.approx(30.0)
    flops = 2 * 1000 * 300 * 64 * 27.5 + 2 * 5000 * 64
    nbytes = 8 * 100 * 48 + 4 * 1000 * 300 + 4 * 1000 * 64
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("step_roofline") == pytest.approx(100 * least * 2 / 6e-6)


def test_work_and_peaks():
    f, b = work.step_work(v_e=10, m=3, nnz=7, n_docs=2, h_max=4,
                          max_batch=5, queries=2, words=6)
    assert f == 2 * 10 * 3 * 6 + 2 * 7 * 2
    assert b == 8 * 2 * 4 + 4 * 10 * 3 + 4 * 10 * 5
    pk = work.peaks("TPU v5 lite")
    assert work.roofline_s(197e12, 1.0, pk) == (pytest.approx(1.0), "compute")
    assert work.roofline_s(1.0, 819e9, pk)[1] == "memory"
    with pytest.raises(KeyError):
        work.peaks("cpu")
