"""Cluster-routed index: cell partitioning, routing, and routed top-k.

Layering (see docs/ARCHITECTURE.md §Index layer):

  * **Partition** — k-centers seeds (or full k-medoids labels) from
    :mod:`repro.workloads.clustering`, with an explicit PRNG ``seed`` so a
    rebuild over the same corpus lands on identical cells (compaction
    re-partitions deterministically).
  * **Cells** — every cell is an :class:`~repro.core.lc_rwmd.EngineSegment`
    over its member docs (its own v_e vocab restriction + pre-gathered
    tensors).  All cells are padded to ONE uniform (rows_cap, v_cap) shape,
    so the module-level :func:`repro.core.lc_rwmd._segment_topk` kernel is
    traced ONCE and reused by every cell — probing different cell subsets
    batch-to-batch never re-traces (sentinel-clean).
  * **Routing** — one tiny jitted step computes query WCD centroids and
    top-``p`` nearest cell centroids, plus triangle-inequality bounds:
    for any member d of cell c, ``WMD(q, d) ≥ WCD(q, d) ≥ |q−μ_c| − r_c``
    (centroid distance obeys the triangle inequality; WCD lower-bounds
    WMD).  Cells whose lower bound exceeds ``bound_slack ×`` the best
    possible match of any routed cell are pruned before phase 1.
  * **Routed top-k** — per-cell streaming folds (local ids) are remapped
    through the cell's global-id table and merged with
    :func:`repro.core.topk.merge_topk` — the same lexicographic
    (distance, global id) order as the flat segmented scan, which is what
    makes exhaustive routing (``top_p = num_cells``) bit-identical to it.

Cells hold *scattered* global doc ids (unlike engine segments' contiguous
ranges), so each cell carries an explicit per-row global-id array; padded
rows carry id -1 and can never surface.  Tombstones stay the engine's
business: cell live masks are re-derived from ``engine.live_mask()``
whenever ``engine.version`` moves, so deletes made directly on the engine
are honored without touching the index.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distances import dists
from repro.core.lc_rwmd import _INF, EngineSegment, _segment_topk
from repro.core.topk import TopK, merge_topk, topk_smallest
from repro.obs import sentinel as _sentinel

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    """Serving-facing knobs for building/using a :class:`ClusterIndex`.

    Passed as ``ServerConfig(index=IndexConfig(...))`` — the corpus manager
    then builds one index per corpus and the serve step routes batches.

    ``num_cells``: cell count (the n/cells factor of the asymptotic).
    ``top_p``: cells probed per query (the recall/speedup knob).
    ``seed``: PRNG seed for the partition — fixed so compaction's
    re-partition is reproducible.
    ``bound_slack``: triangle-bound cell pruning slack (≥ 1.0 keeps every
    cell that could hold the single best match; larger is safer for top-k;
    None disables the bound stage).
    ``probe_cap``: max distinct cells one BATCH may probe in the compiled
    serve step (slots are jit-static).  Overflow drops the least-requested
    cells (counted in obs).  None → ``min(num_cells, max(8, 4·top_p))``.
    ``method``: ``"kcenters"`` (greedy seeds + one WCD assignment pass,
    cheap) or ``"kmedoids"`` (full alternation, tighter cells).
    """

    num_cells: int
    top_p: int = 1
    seed: int = 0
    bound_slack: float | None = None
    probe_cap: int | None = None
    method: str = "kcenters"

    def __post_init__(self):
        if self.num_cells < 1:
            raise ValueError(f"num_cells must be >= 1, got {self.num_cells}")
        if self.top_p < 1:
            raise ValueError(f"top_p must be >= 1, got {self.top_p}")
        if self.bound_slack is not None and self.bound_slack <= 0:
            raise ValueError(
                f"bound_slack must be positive or None, got {self.bound_slack}")
        if self.method not in ("kcenters", "kmedoids"):
            raise ValueError(f"unknown partition method {self.method!r}")


class RouteResult(NamedTuple):
    """Host-side routing decision for one query batch."""
    cells: np.ndarray        # (B, p) int32 routed cell ids (by distance)
    keep: np.ndarray         # (B, p) bool: slot survived bound + validity
    probed: np.ndarray       # (P,) int64 distinct cells any query kept
    n_bound_pruned: int      # (query, cell) slots killed by the bound stage
    n_docs_pruned: int       # live docs those pruned slots would have scanned


class _Cell(NamedTuple):
    """One cell's device-resident state (uniform shapes across cells)."""
    segment: EngineSegment   # offset=0; rows padded to rows_cap, v to v_cap
    members: np.ndarray      # (n_real,) int64 global doc ids, ASCENDING
    gids_dev: Array          # (rows_cap,) int32 global ids, -1 in pad rows


@functools.partial(jax.jit, static_argnames=("p",))
def _route_cells(mu: Array, radii: Array, alive: Array, t_q: Array,
                 q_w: Array, *, p: int):
    """Top-p cells by query-centroid → cell-centroid distance + bounds.

    Returns (d (B, p) routed distances ascending, cells (B, p) int32,
    lb (B, p) triangle lower bound on any member's WCD, ub_best (B,) upper
    bound on the best routed match's WCD).
    """
    b, h = q_w.shape
    c_q = jnp.einsum("bh,bhm->bm", q_w, t_q.reshape(b, h, -1))
    d = dists(c_q, mu)                                   # (B, C)
    d = jnp.where(alive[None, :], d, _INF)
    tk = topk_smallest(d, p)
    r = radii[tk.indices]                                # (B, p)
    lb = jnp.maximum(tk.dists - r, 0.0)
    ub_best = jnp.min(jnp.where(tk.dists < _INF, tk.dists + r, _INF), axis=1)
    return tk.dists, tk.indices, lb, ub_best


_route_cells = _sentinel.wrap("index._route_cells", _route_cells)


@jax.jit
def _remap_mask(tk_d: Array, tk_i: Array, gids: Array, qmask: Array) -> TopK:
    """Local cell top-k → global ids, with per-query routing mask applied."""
    safe = jnp.clip(tk_i, 0, gids.shape[0] - 1)
    g = jnp.where(tk_i >= 0, gids[safe], jnp.int32(-1))
    d = jnp.where(qmask[:, None] & (g >= 0), tk_d, _INF)
    return TopK(d, jnp.where(qmask[:, None], g, jnp.int32(-1)))


_remap_mask = _sentinel.wrap("index._remap_mask", _remap_mask)


def _doc_centroids(ids: np.ndarray, w: np.ndarray, emb: np.ndarray
                   ) -> np.ndarray:
    """(n, m) WCD centroids of ELL histograms, host-side."""
    return np.einsum("nh,nhm->nm", w, emb[ids]).astype(np.float32)


def _round_up(x: int, mult: int) -> int:
    return -(-max(int(x), 1) // mult) * mult


class ClusterIndex:
    """IVF-style cell index over a :class:`~repro.core.lc_rwmd.SegmentedEngine`.

    The engine stays the source of truth for docs, global ids, tombstones,
    and the Sinkhorn rerank; the index is an acceleration structure beside
    it (its per-cell tensors roughly double resident device bytes —
    ``nbytes`` reports them for the corpus manager's eviction accounting).

    Mutation surface mirrors the engine lifecycle:

      * :meth:`add` — assign freshly appended engine docs to their nearest
        cells and rebuild just those cells (O(cell), not O(corpus)); grows
        the uniform cell shape (→ full rebuild) only when a cell outruns
        its padding headroom.
      * deletes need no call — live masks re-derive from the engine.
      * :meth:`rebuild` — full re-partition with the SAME seed, for
        compaction (deterministic: identical corpus → identical cells).
    """

    def __init__(self, engine, *, num_cells: int, seed: int = 0,
                 top_p: int = 1, bound_slack: float | None = None,
                 probe_cap: int | None = None, method: str = "kcenters",
                 cell_pad: int = 32, obs=None):
        if not hasattr(engine, "segments"):
            raise TypeError(
                "ClusterIndex needs a SegmentedEngine (per-cell segments "
                "reuse its kernels)")
        if not 1 <= num_cells <= max(1, engine.n_docs):
            raise ValueError(
                f"need 1 <= num_cells <= {engine.n_docs}, got {num_cells}")
        self.engine = engine
        self.num_cells = int(num_cells)
        self.seed = int(seed)
        self.top_p = int(top_p)
        self.bound_slack = bound_slack
        self.method = method
        self.cell_pad = max(1, int(cell_pad))
        self.probe_cap = (int(probe_cap) if probe_cap is not None
                          else min(self.num_cells, max(8, 4 * self.top_p)))
        self.obs = obs
        self.version = 0            # bumped on add/rebuild (structure changes)
        self._live_sync = None      # (engine.version, index.version) synced
        self._live_dev: tuple[Array, ...] = ()
        self._alive: Array | None = None
        self.rebuild()

    # -- build / lifecycle -------------------------------------------------
    def _partition_labels(self) -> np.ndarray:
        """(n_docs,) int32 cell label per global doc id (deterministic)."""
        eng = self.engine
        if self.method == "kmedoids":
            from repro.workloads.clustering import kmedoids

            res = kmedoids(eng, self.num_cells, seed=self.seed)
            return np.asarray(res.labels, dtype=np.int32)
        from repro.workloads.clustering import kcenters

        centers = kcenters(eng, self.num_cells, seed=self.seed)
        # One WCD assignment pass: nearest center-doc centroid.  Routing
        # uses the same metric, so a query lands first on the cell its
        # nearest docs live in.
        d = np.linalg.norm(
            self._cen[:, None, :] - self._cen[centers][None], axis=2)
        return d.argmin(axis=1).astype(np.int32)

    def _build_cell(self, members: np.ndarray) -> _Cell:
        """Materialize one cell as a uniformly padded EngineSegment."""
        from repro.data.docs import DocSet

        res = self.engine.resident
        members = np.sort(np.asarray(members, dtype=np.int64))
        mem_j = jnp.asarray(members, dtype=jnp.int32)
        docs = DocSet(ids=res.ids[mem_j], weights=res.weights[mem_j]) \
            if len(members) else \
            DocSet(ids=jnp.zeros((1, res.ids.shape[1]), jnp.int32),
                   weights=jnp.zeros((1, res.ids.shape[1]), jnp.float32))
        seg = EngineSegment(docs, self.engine.emb_full, offset=0,
                            n_pad=self._rows_cap)
        pad = self._v_cap - seg.tensors.emb_r.shape[0]
        if pad < 0:
            raise AssertionError("cell v_e exceeded v_cap after sizing pass")
        if pad:
            seg.tensors = seg.tensors._replace(
                emb_r=jnp.pad(seg.tensors.emb_r, ((0, pad), (0, 0))))
        gids = np.full(self._rows_cap, -1, dtype=np.int64)
        gids[:len(members)] = members
        if not len(members):
            seg.n_real = 0  # the zero-weight placeholder row is not a doc
        return _Cell(segment=seg, members=members,
                     gids_dev=jnp.asarray(gids, dtype=jnp.int32))

    def _size_caps(self, sizes, v_es) -> None:
        """Uniform (rows_cap, v_cap) across cells, with growth headroom."""
        self._rows_cap = _round_up(max(sizes), self.cell_pad)
        self._v_cap = _round_up(max(max(v_es), 1), 8)

    @staticmethod
    def _cell_ve(ids: np.ndarray, w: np.ndarray) -> int:
        return len(np.unique(ids[w > 0])) if (w > 0).any() else 1

    def rebuild(self) -> None:
        """Full deterministic re-partition (same seed) — compaction's hook."""
        eng = self.engine
        res = eng.resident
        ids = np.asarray(res.ids)
        w = np.asarray(res.weights)
        self._cen = _doc_centroids(ids, w, np.asarray(eng.emb_full))
        self._labels = self._partition_labels()
        members = [np.nonzero(self._labels == j)[0]
                   for j in range(self.num_cells)]
        self._size_caps(
            [max(len(m), 1) for m in members],
            [self._cell_ve(ids[m], w[m]) if len(m) else 1 for m in members])
        self.cells = [self._build_cell(m) for m in members]
        self._n_docs_indexed = eng.n_docs
        self._refresh_centroids()
        self._bump()

    def _refresh_centroids(self) -> None:
        """Cell centroids = mean of live member doc centroids; radii cover
        every live member (the triangle bound's correctness invariant)."""
        live = self.engine.live_mask()
        mu = np.zeros((self.num_cells, self._cen.shape[1]), dtype=np.float32)
        radii = np.zeros(self.num_cells, dtype=np.float32)
        alive = np.zeros(self.num_cells, dtype=bool)
        for j, cell in enumerate(self.cells):
            m = cell.members[live[cell.members]] if len(cell.members) else \
                cell.members
            if not len(m):
                continue
            alive[j] = True
            mu[j] = self._cen[m].mean(axis=0)
            radii[j] = float(np.linalg.norm(
                self._cen[m] - mu[j], axis=1).max())
        self._mu = jnp.asarray(mu)
        self._radii = jnp.asarray(radii)
        self._alive_np = alive

    def _bump(self) -> None:
        self.version += 1
        self._live_sync = None

    def add(self, gids, docs) -> np.ndarray:
        """Assign freshly appended engine docs to their nearest cells.

        ``gids`` are the global ids :meth:`SegmentedEngine.append` returned
        for ``docs`` (monotonically increasing, so per-cell member lists
        stay ascending — the tie-order invariant).  Only the touched cells
        are rebuilt, unless one outgrows the uniform (rows_cap, v_cap)
        padding — then every cell re-pads to the new caps (rare; headroom
        comes from ``cell_pad`` rounding).  Returns the cell id per doc.
        """
        gids = np.asarray(gids, dtype=np.int64).reshape(-1)
        if not len(gids):
            return np.empty(0, dtype=np.int32)
        ids = np.asarray(docs.ids)
        w = np.asarray(docs.weights)
        # Pad to the engine's h_max (engine.append did the same internally).
        h = np.asarray(self.engine.resident.ids).shape[1]
        if ids.shape[1] < h:
            pad = h - ids.shape[1]
            ids = np.pad(ids, ((0, 0), (0, pad)))
            w = np.pad(w, ((0, 0), (0, pad)))
        cen_new = _doc_centroids(ids, w, np.asarray(self.engine.emb_full))
        mu = np.asarray(self._mu)
        d = np.linalg.norm(cen_new[:, None, :] - mu[None], axis=2)
        if self._alive_np.any():
            d[:, ~self._alive_np] = np.inf
        assign = d.argmin(axis=1).astype(np.int32)

        self._cen = np.concatenate([self._cen, cen_new], axis=0)
        self._labels = np.concatenate([self._labels, assign])
        touched = {}
        for g, c in zip(gids, assign):
            touched.setdefault(int(c), []).append(int(g))
        new_members = {
            c: np.concatenate([self.cells[c].members,
                               np.asarray(gs, dtype=np.int64)])
            for c, gs in touched.items()}
        res = self.engine.resident
        r_ids, r_w = np.asarray(res.ids), np.asarray(res.weights)
        need_rows = max(len(m) for m in new_members.values())
        need_v = max(self._cell_ve(r_ids[m], r_w[m])
                     for m in new_members.values())
        if need_rows > self._rows_cap or need_v > self._v_cap:
            # Grown past the uniform padding: re-pad EVERY cell so all
            # cells keep sharing one kernel trace.
            all_members = [new_members.get(j, self.cells[j].members)
                           for j in range(self.num_cells)]
            self._size_caps(
                [max(len(m), 1) for m in all_members],
                [self._cell_ve(r_ids[m], r_w[m]) if len(m) else 1
                 for m in all_members])
            self.cells = [self._build_cell(m) for m in all_members]
        else:
            for c, m in new_members.items():
                self.cells[c] = self._build_cell(m)
        self._n_docs_indexed = self.engine.n_docs
        self._refresh_centroids()
        self._bump()
        return assign

    # -- views -------------------------------------------------------------
    @property
    def rows_cap(self) -> int:
        """Uniform padded row count per cell (the compiled slab width)."""
        return self._rows_cap

    @property
    def labels(self) -> np.ndarray:
        """(n_docs,) int32 cell assignment per global doc id."""
        return self._labels

    @property
    def centroid_nbytes(self) -> int:
        """Device bytes of the routing tensors (centroids, radii, gid maps)."""
        n = self._mu.size * 4 + self._radii.size * 4
        n += sum(c.gids_dev.size * 4 for c in self.cells)
        return n

    @property
    def nbytes(self) -> int:
        """Device bytes the index pins: cell segments + routing tensors."""
        return (sum(c.segment.nbytes for c in self.cells)
                + self.centroid_nbytes)

    def _sync_live(self) -> None:
        """Re-derive per-cell live masks when engine or index moved."""
        key = (self.engine.version, self.version)
        if self._live_sync == key:
            return
        if self.engine.n_docs != self._n_docs_indexed:
            raise RuntimeError(
                f"engine has {self.engine.n_docs} docs but the index covers "
                f"{self._n_docs_indexed} — docs were appended directly to "
                "the engine; call index.add(gids, docs) or index.rebuild()")
        live = self.engine.live_mask()
        masks = []
        for cell in self.cells:
            m = np.zeros(self._rows_cap, dtype=bool)
            if len(cell.members):
                m[:len(cell.members)] = live[cell.members]
            masks.append(jnp.asarray(m))
        self._live_dev = tuple(masks)
        self._refresh_centroids()
        self._alive = jnp.asarray(self._alive_np)
        self._live_sync = key

    # -- routing + routed queries -------------------------------------------
    def route(self, queries, *, top_p: int | None = None,
              bound_slack: float | None | str = "cfg") -> RouteResult:
        """Route a query batch to cells; apply the triangle-bound stage.

        ``bound_slack="cfg"`` uses the index default; pass ``None`` to
        disable the bound for this call (exhaustive-parity paths do).
        """
        self._sync_live()
        slack = self.bound_slack if bound_slack == "cfg" else bound_slack
        p = min(int(top_p or self.top_p), self.num_cells)
        t_q = self.engine._gather_queries_flat(queries.ids)
        d, cells, lb, ub = _route_cells(
            self._mu, self._radii, self._alive, t_q, queries.weights, p=p)
        d_np = np.asarray(d)
        cells_np = np.asarray(cells, dtype=np.int32)
        keep = d_np < _INF / 2          # drop empty/dead-cell slots
        n_pruned = n_docs_pruned = 0
        if slack is not None:
            bound_ok = np.asarray(lb) <= float(slack) * np.asarray(ub)[:, None]
            pruned = keep & ~bound_ok
            n_pruned = int(pruned.sum())
            if n_pruned:
                live = self.engine.live_mask()
                cell_live = np.array(
                    [int(live[c.members].sum()) if len(c.members) else 0
                     for c in self.cells])
                n_docs_pruned = int(cell_live[cells_np[pruned]].sum())
            keep &= bound_ok
        probed = (np.unique(cells_np[keep]) if keep.any()
                  else np.empty(0, dtype=np.int64)).astype(np.int64)
        self._record_route_obs(len(probed), n_pruned)
        return RouteResult(cells=cells_np, keep=keep, probed=probed,
                           n_bound_pruned=n_pruned,
                           n_docs_pruned=n_docs_pruned)

    def _record_route_obs(self, n_probed: int, n_bound_pruned: int) -> None:
        obs = self.obs
        if obs is None or not getattr(obs.metrics, "enabled", False):
            return
        from repro.obs import COUNT_BUCKETS

        m = obs.metrics
        m.histogram("index_cells_probed",
                    "Distinct cells probed per routed batch.",
                    buckets=COUNT_BUCKETS).observe(n_probed)
        m.gauge("index_routed_fraction",
                "Fraction of resident cell rows the last routed batch "
                "scanned.").set(
            n_probed * self._rows_cap
            / max(1, self.num_cells * self._rows_cap))
        if n_bound_pruned:
            m.counter("index_bound_pruned_total",
                      "(query, cell) routing slots pruned by the "
                      "centroid/triangle bound stage.").inc(n_bound_pruned)

    def routed_topk(self, queries, k: int, *, top_p: int | None = None,
                    bound_slack: float | None | str = "cfg",
                    route: RouteResult | None = None) -> TopK:
        """Streaming symmetric top-k over routed cells only: TopK (B, k).

        With ``top_p = num_cells`` and the bound disabled this is
        bit-identical to ``engine.topk(queries, k)`` — same fold, same
        lexicographic tie order, global ids remapped per cell.
        """
        if route is None:
            route = self.route(queries, top_p=top_p, bound_slack=bound_slack)
        eng = self.engine
        t_q = eng._gather_queries_flat(queries.ids)
        b = queries.n_docs
        kk = min(k, self._rows_cap)
        parts = []
        for c in route.probed:
            cell = self.cells[int(c)]
            tk = _segment_topk(
                cell.segment.tensors, t_q, queries.weights,
                self._live_dev[int(c)],
                k=kk, symmetric=True,
                row_block=max(1, min(eng.row_block, self._rows_cap)),
                bf16_matmul=eng.bf16_matmul, vocab_chunk=eng.vocab_chunk,
            )
            qmask = ((route.cells == c) & route.keep).any(axis=1)
            parts.append(_remap_mask(
                tk.dists, tk.indices, cell.gids_dev, jnp.asarray(qmask)))
        k_out = min(k, max(eng.n_docs, 1))
        if not parts:   # nothing routed (e.g. all cells empty)
            return TopK(jnp.full((b, k_out), _INF),
                        jnp.full((b, k_out), -1, jnp.int32))
        merged = merge_topk(parts, min(k_out, kk * len(parts)))
        if merged.dists.shape[-1] < k_out:
            # Fewer routed rows than k: pad with empty slots (fixed width
            # is the serving contract).
            pad = k_out - merged.dists.shape[-1]
            merged = TopK(
                jnp.pad(merged.dists, ((0, 0), (0, pad)),
                        constant_values=_INF),
                jnp.pad(merged.indices, ((0, 0), (0, pad)),
                        constant_values=-1))
        return merged
