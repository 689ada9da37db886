"""Device time of the serve step by named scope, and the work of each phase.

The program wraps the parts of its serve step in ``jax.named_scope``
(``phase1``, ``phase2``, ``topk_fold``, ``crossshard_topk``, ...).  On the
TPU the scope path reaches the profiler's trace in the ``tf_op`` stat of
each operation's event metadata (``jit(step)/phase2/while/body/closed_call/
topk_fold/sort:``), which ``jax.profiler.ProfileData`` does not expose, so
:func:`read_op_paths` reads the ``.xplane.pb`` itself.

A loop's body operations nest inside the loop's own event on the ``XLA
Ops`` line, so each operation counts its *self* time (:func:`self_ns`):
its duration less that of the operations nested in it.  The per-layer
readers find the run's trace through :func:`step_scopes`, which checks the
file against the harness's own summary of the window.
"""

from __future__ import annotations

import bisect
import pathlib

from bench import trace as trace_lib
from bench import work

#: The serve step's compiled program, whose time the scopes split.
STEP = "jit_step"
#: The program's named scopes, as ``jax.named_scope`` spells them.
SCOPES = ("phase1", "phase2", "topk_fold", "crossshard_topk",
          "gather_queries", "refine", "rerank")
#: The operation stat that holds the scope path on the TPU.
SCOPE_STAT = "tf_op"
#: Operations of the step in no scope.
UNSCOPED = "unscoped"


# -- reading the trace --------------------------------------------------------
def _xspace_message():
    """A message class for the profiler's XSpace (TSL's ``xplane.proto``)
    with the fields read here.  Maps are read as the repeated key/value
    entries they are on the wire, strings as bytes."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    f = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    i64, u64, f64, byt, msg = (f.TYPE_INT64, f.TYPE_UINT64, f.TYPE_DOUBLE,
                               f.TYPE_BYTES, f.TYPE_MESSAGE)
    schema = {
        "XSpace": [("planes", 1, msg, "XPlane")],
        "XPlane": [("name", 2, byt, None), ("lines", 3, msg, "XLine"),
                   ("event_metadata", 4, msg, "EventMetadataEntry"),
                   ("stat_metadata", 5, msg, "StatMetadataEntry")],
        "XLine": [("name", 2, byt, None), ("timestamp_ns", 3, i64, None),
                  ("events", 4, msg, "XEvent")],
        "XEvent": [("metadata_id", 1, i64, None), ("offset_ps", 2, i64, None),
                   ("duration_ps", 3, i64, None), ("stats", 4, msg, "XStat")],
        "XStat": [("metadata_id", 1, i64, None), ("double_value", 2, f64, None),
                  ("uint64_value", 3, u64, None), ("int64_value", 4, i64, None),
                  ("str_value", 5, byt, None), ("ref_value", 7, u64, None)],
        "XEventMetadata": [("id", 1, i64, None), ("name", 2, byt, None),
                           ("stats", 5, msg, "XStat")],
        "XStatMetadata": [("id", 1, i64, None), ("name", 2, byt, None)],
        "EventMetadataEntry": [("key", 1, i64, None),
                               ("value", 2, msg, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, i64, None),
                              ("value", 2, msg, "XStatMetadata")],
    }
    for name, fields in schema.items():
        m = fd.message_type.add(name=name)
        if name == "XStat":
            m.oneof_decl.add(name="value")
        for fname, num, typ, ref in fields:
            label = (f.LABEL_REPEATED if ref and fname != "value"
                     else f.LABEL_OPTIONAL)
            fld = m.field.add(name=fname, number=num, type=typ, label=label,
                              type_name=f".bench_xplane.{ref}" if ref else None)
            if name == "XStat" and fname.endswith("_value"):
                fld.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _text(b: bytes) -> str:
    return b.decode(errors="replace")


def _scope_stat(stats, stat_names: dict) -> str:
    """The text of the ``SCOPE_STAT`` among ``stats`` ("" where absent)."""
    for st in stats:
        if stat_names.get(st.metadata_id) != SCOPE_STAT:
            continue
        kind = st.WhichOneof("value")
        if kind == "str_value":
            return _text(st.str_value)
        if kind == "ref_value":
            return stat_names.get(st.ref_value, "")
    return ""


def read_op_paths(path: str) -> dict:
    """``{plane: [(name, start_ns, dur_ns, scope path)]}``: the ``XLA Ops``
    events of the device planes of one .xplane.pb, each with its operation's
    ``SCOPE_STAT`` ("" where it has none); times as
    :func:`bench.trace.read_xspace` gives them."""
    space = _xspace_message()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out: dict = {}
    for plane in space.planes:
        pname = _text(plane.name)
        if not pname.startswith(trace_lib.DEVICE_PREFIX):
            continue
        stat_names = {e.key: _text(e.value.name) for e in plane.stat_metadata}
        ops = {e.key: (_text(e.value.name),
                       _scope_stat(e.value.stats, stat_names))
               for e in plane.event_metadata}
        evs = out.setdefault(pname, [])
        for line in plane.lines:
            if _text(line.name) != trace_lib.OPS:
                continue
            t0 = float(line.timestamp_ns)
            for ev in line.events:
                name, scope = ops.get(ev.metadata_id, ("", ""))
                evs.append((name, t0 + ev.offset_ps / 1e3,
                            ev.duration_ps / 1e3,
                            scope or _scope_stat(ev.stats, stat_names)))
    return out


# -- reducing it --------------------------------------------------------------
def scope_of(path: str) -> str | None:
    """The innermost of ``SCOPES`` on a scope path (``tf_op`` ends in
    ``:<type>``), or None."""
    for part in reversed(path.rsplit(":", 1)[0].split("/")):
        if part in SCOPES:
            return part
    return None


def self_ns(events: list) -> list:
    """Self time of each event of one line, in order: its duration less
    that of the events nested inside it on the line.  ``events`` are
    ``(name, start_ns, dur_ns, ...)``."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    child = [0.0] * len(events)
    stack: list = []          # (end, index) of the open enclosing events
    for i in order:
        s, d = events[i][1], events[i][2]
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            end, parent = stack[-1]
            child[parent] += min(s + d, end) - s
        stack.append((s + d, i))
    return [ev[2] - c for ev, c in zip(events, child)]


def scope_self_ns(ops: list, space: dict, plane: str, lo: float, hi: float,
                  module: str = STEP) -> dict:
    """``{scope: device self ns}`` of the operations of ``module``'s
    executions that started in [lo, hi] on ``plane`` (those in no scope
    under ``UNSCOPED``).  ``space`` is :func:`bench.trace.read_xspace`'s,
    ``ops`` the plane's events from :func:`read_op_paths`."""
    runs = sorted((s, s + d) for n, s, d in space.get(plane, {}).get(
        trace_lib.MODULES, []) if trace_lib.module_name(n) == module
        and lo <= s <= hi)
    starts = [s for s, _e in runs]
    inside = []
    for ev in ops:
        j = bisect.bisect_right(starts, ev[1]) - 1
        if j >= 0 and ev[1] < runs[j][1]:
            inside.append(ev)
    out: dict = {}
    for ev, t in zip(inside, self_ns(inside)):
        key = scope_of(ev[3]) or UNSCOPED
        out[key] = out.get(key, 0.0) + t
    return out


_CACHE: dict = {}


def _scopes_of_file(path: pathlib.Path) -> tuple[dict, dict]:
    """(modules of the window as the harness summarizes them, ``{scope:
    device s}`` of the serve step) of one trace file, read once."""
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _CACHE:
        space = trace_lib.read_xspace(str(path))
        window = trace_lib.span_ns(space, "bench.window")
        plane = trace_lib.device_planes(space)[0]
        mods = {k: (n, t / 1e9) for k, (n, t) in trace_lib.module_stats(
            space, plane, *window).items()}
        ns = scope_self_ns(read_op_paths(str(path)).get(plane, []), space,
                           plane, *window)
        _CACHE[key] = (mods, {k: t / 1e9 for k, t in ns.items()})
    return _CACHE[key]


def step_scopes(run) -> dict:
    """``{scope: device self s}`` of the serve step in the traced window
    of ``run`` (a ``bench.harness.RunView``); {} where there is none.

    The harness keeps each traced run's profile under
    ``results/bench/<cell>.<seed>/``; the newest file whose window holds
    the same programs as the run's own summary is this run's."""
    if run.trace is None:
        return {}
    base = pathlib.Path(run.cell.root) / "results" / "bench"
    files = sorted(base.glob(f"{run.cell.name}.*/**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns, reverse=True)
    for path in files:
        mods, scopes = _scopes_of_file(path)
        if mods == run.trace.modules:
            return scopes
    return {}


def scope_ms(run, *names: str) -> float | None:
    """Device ms of the serve step's operations in the named scopes per
    execution of the step; None where the trace holds none of them."""
    scopes = step_scopes(run)
    n, _t = run.module(STEP)
    if not n or not any(name in scopes for name in names):
        return None
    return 1e3 * sum(scopes.get(name, 0.0) for name in names) / n


# -- work of each phase -------------------------------------------------------
def phase_work(*, v_e: int, m: int, nnz: int, n_docs: int, h_max: int,
               max_batch: int, queries: float, words: float) -> dict:
    """``{"phase1": (operations, bytes), "phase2": (operations, bytes)}`` of
    one batch: the counts of :func:`bench.work.step_work` split by phase,
    with Z counted in both (phase 1 writes it, phase 2 reads it)."""
    z = 4.0 * v_e * max_batch
    return {"phase1": (2.0 * v_e * m * words, 4.0 * v_e * m + z),
            "phase2": (2.0 * nnz * queries, 8.0 * n_docs * h_max + z)}


def phase_roofline(run, phase: str) -> float | None:
    """``phase``'s share of its roofline (%): the least time of its work at
    the chip's peaks over its device time per serve step."""
    ms = scope_ms(run, phase)
    queries, batches = run.counter("serving_batch_size")
    if not ms or not batches or not run.peaks:
        return None
    w = run.work
    per_batch = queries / batches
    flops, nbytes = phase_work(
        v_e=w["v_e"], m=w["m"], nnz=w["nnz"], n_docs=w["n_docs"],
        h_max=w["h_max"], max_batch=w["max_batch"], queries=per_batch,
        words=per_batch * w["words_per_query"])[phase]
    least, _bound = work.roofline_s(flops, nbytes, run.peaks)
    return 100.0 * least / (ms / 1e3)
