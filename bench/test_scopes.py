"""Tests of the per-scope device time and the readers that use it, on
traces built by hand (XLA:CPU's op events carry no scope stat).

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_scopes.py
"""

from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness, scopes, work  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

CELL = "set2_knn.saturate"
PLANE = "/device:TPU:0"
WORK = dict(v_e=1000, m=300, nnz=5000, n_docs=100, h_max=48, max_batch=64,
            words_per_query=27.5)

# One serve step of 100 ns at [1000, 1100) and one at [2000, 2100): a loop
# (phase 2) encloses a gather (phase 2) and a fold (topk_fold); phase 1's
# GEMM and an unscoped copy come before it.  Self time per step: phase1
# 20, phase2 10 + 30, topk_fold 20, unscoped 5.
STEP_OPS = [
    ("%fusion.3", 0, 20, "jit(step)/phase1/dot_general:"),
    ("%copy.1", 20, 5, ""),
    ("%while.3", 25, 60, "jit(step)/phase2/while:"),
    ("%fusion.7", 30, 30, "jit(step)/phase2/while/body/closed_call/phase2/"
                          "gather:"),
    ("%sort.42", 62, 20, "jit(step)/phase2/while/body/closed_call/topk_fold/"
                         "sort:"),
    ("%sort.9", 88, 10, "jit(step)/crossshard_topk/sort:"),
]
REFINE_OPS = [("%fusion.1", 0, 40, "jit(_symmetric_refine)/refine/sub:")]


def _ops(scoped=True):
    out = []
    for t0 in (1000, 2000):
        out += [(n, t0 + s, d, path if scoped else "")
                for n, s, d, path in STEP_OPS]
    out += [(n, 1500 + s, d, path if scoped else "")
            for n, s, d, path in REFINE_OPS]
    return out


def _modules(steps=((1000, 100), (2000, 100))):
    return ([("jit_step(3)", s, d) for s, d in steps]
            + [("jit__symmetric_refine(9)", 1500, 40)])


def write_xspace(path: pathlib.Path, ops, modules, window=(0, 10000)):
    """A .xplane.pb holding a host plane with the benchmark's window span
    and one TPU plane with ``modules`` and ``ops`` (their scope path in
    the ``tf_op`` stat of each op's metadata)."""
    space = scopes._xspace_message()()
    host = space.planes.add(name=b"/host:CPU")
    host.event_metadata.add(key=1).value.name = b"bench.window"
    line = host.lines.add(name=b"python", timestamp_ns=0)
    line.events.add(metadata_id=1, offset_ps=window[0] * 1000,
                    duration_ps=(window[1] - window[0]) * 1000)
    dev = space.planes.add(name=PLANE.encode())
    dev.stat_metadata.add(key=1).value.name = scopes.SCOPE_STAT.encode()
    ids: dict = {}

    def meta(name, scope=""):
        if (name, scope) not in ids:
            key = len(ids) + 1
            ids[(name, scope)] = key
            entry = dev.event_metadata.add(key=key).value
            entry.name = name.encode()
            if scope:
                entry.stats.add(metadata_id=1, str_value=scope.encode())
        return ids[(name, scope)]

    for line_name, evs in (("XLA Modules", [(n, s, d, "") for n, s, d
                                            in modules]), ("XLA Ops", ops)):
        line = dev.lines.add(name=line_name.encode(), timestamp_ns=0)
        for name, s, d, scope in evs:
            line.events.add(metadata_id=meta(name, scope),
                            offset_ps=int(s * 1000), duration_ps=int(d * 1000))
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(space.SerializeToString())
    return path


def _view(root: pathlib.Path, seed: int = 5, counters=None):
    """A RunView over the trace written for ``seed`` under ``root``."""
    space = trace_lib.read_xspace(str(_trace_file(root, seed)))
    summary = trace_lib.summarize(space, trace_lib.span_ns(space,
                                                           "bench.window"))
    cell = harness.load_cell(CELL)
    cell.root = root
    return harness.RunView(cell, counters or {"serving_batch_size": (128.0, 2)},
                           summary, work.peaks("TPU v5 lite"), dict(WORK))


def _trace_file(root: pathlib.Path, seed: int = 5) -> pathlib.Path:
    return (root / "results" / "bench" / f"{CELL}.{seed}" / "plugins"
            / "profile" / "t" / "host.xplane.pb")


@pytest.fixture()
def recorded(tmp_path):
    write_xspace(_trace_file(tmp_path), _ops(), _modules())
    return _view(tmp_path)


# -- the reduction ------------------------------------------------------------
def test_self_time_takes_nested_ops_out_of_the_loop():
    evs = [(n, s, d) for n, s, d, _p in STEP_OPS]
    assert scopes.self_ns(evs) == [20, 5, 10, 30, 20, 10]


@pytest.mark.parametrize("path,scope", [
    ("jit(step)/phase1/dot_general:", "phase1"),
    ("jit(step)/phase2/while/body/closed_call/topk_fold/sort:", "topk_fold"),
    ("jit(step)/phase2/while/body/closed_call/phase2/gather:", "phase2"),
    ("jit(step)/crossshard_topk/all_gather", "crossshard_topk"),
    ("jit(step)/while:", None),
    ("", None),
])
def test_innermost_scope_names_the_op(path, scope):
    assert scopes.scope_of(path) == scope


def test_scope_self_time_counts_only_the_step():
    space = {PLANE: {"XLA Modules": _modules()}}
    got = scopes.scope_self_ns(_ops(), space, PLANE, 0, 10000)
    assert got == {"phase1": 40, "phase2": 80, "topk_fold": 40,
                   "crossshard_topk": 20, "unscoped": 10}
    # The refine's ops lie in no step; a step started outside the window
    # counts for nothing.
    assert scopes.scope_self_ns(_ops(), space, PLANE, 1500, 10000) == {
        "phase1": 20, "phase2": 40, "topk_fold": 20, "crossshard_topk": 10,
        "unscoped": 5}
    assert sum(got.values()) <= 200


def test_recorded_trace_reads_back(tmp_path):
    path = write_xspace(_trace_file(tmp_path), _ops(), _modules())
    ops = scopes.read_op_paths(str(path))[PLANE]
    assert [(n, s, d, p) for n, s, d, p in ops] == [
        (n, float(s), float(d), p) for n, s, d, p in _ops()]
    space = trace_lib.read_xspace(str(path))
    assert [e[:3] for e in space[PLANE]["XLA Ops"]] == [
        (n, float(s), float(d)) for n, s, d, _p in _ops()]


# -- the readers --------------------------------------------------------------
@pytest.mark.parametrize("metric,want", [
    ("phase1_device_ms", 20e-6),
    ("phase2_device_ms", 40e-6),
    ("topk_device_ms", 30e-6),
    ("refine_device_ms", 40e-6),
])
def test_device_readers(recorded, metric, want):
    assert harness.load_reader(metric)(recorded) == pytest.approx(want)


@pytest.mark.parametrize("phase", ["phase1", "phase2"])
def test_phase_rooflines(recorded, phase):
    per_batch = 64.0
    flops, nbytes = scopes.phase_work(
        **{k: WORK[k] for k in ("v_e", "m", "nnz", "n_docs", "h_max",
                                "max_batch")},
        queries=per_batch, words=per_batch * WORK["words_per_query"])[phase]
    least = max(flops / 197e12, nbytes / 819e9)
    t = {"phase1": 20e-9, "phase2": 40e-9}[phase]
    got = harness.load_reader(f"{phase}_roofline")(recorded)
    assert got == pytest.approx(100.0 * least / t)


def test_phase_work_splits_step_work():
    kw = dict(v_e=10, m=3, nnz=7, n_docs=2, h_max=4, max_batch=5,
              queries=2, words=6)
    f, b = work.step_work(**kw)
    parts = scopes.phase_work(**kw)
    assert parts["phase1"][0] + parts["phase2"][0] == f
    # Z is written by phase 1 and read by phase 2: counted once in each.
    assert parts["phase1"][1] + parts["phase2"][1] == b + 4 * 10 * 5


@pytest.mark.parametrize("metric", ["phase1_device_ms", "phase2_device_ms",
                                    "topk_device_ms", "phase1_roofline",
                                    "phase2_roofline"])
def test_device_readers_silent_without_scopes(tmp_path, metric):
    """A build of the program without named scopes reads nothing, and
    raises nothing."""
    write_xspace(_trace_file(tmp_path), _ops(scoped=False), _modules())
    assert harness.load_reader(metric)(_view(tmp_path)) is None


def test_step_scopes_takes_the_runs_own_file(tmp_path):
    write_xspace(_trace_file(tmp_path, seed=5), _ops(), _modules())
    view = _view(tmp_path, seed=5)
    # A newer trace of another run (another window's programs) is skipped.
    write_xspace(_trace_file(tmp_path, seed=6), _ops(),
                 _modules(steps=((1000, 100),)))
    assert scopes.step_scopes(view)["phase1"] == pytest.approx(40e-9)
    view.trace = None
    assert scopes.step_scopes(view) == {}


@pytest.mark.parametrize("metric,counters,want", [
    ("launch_host_ms", {"serving_stage_seconds{stage=gather_queries}":
                        (0.004, 2),
                        "serving_stage_seconds{stage=step_launch}": (0.001, 2),
                        "serving_stage_seconds{stage=refine_launch}":
                        (0.14, 2)}, 72.5),
    ("inflight_ms", {"serving_e2e_latency_seconds": (0.3, 2)}, 150.0),
    ("worker_wait_ms", {"serving_stage_seconds{stage=wait}": (0.006, 3)},
     2.0),
])
def test_counter_readers(recorded, metric, counters, want):
    recorded.counters = counters
    assert harness.load_reader(metric)(recorded) == pytest.approx(want)
    recorded.counters = {}
    assert harness.load_reader(metric)(recorded) is None
