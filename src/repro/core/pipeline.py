"""The WMD pruning cascade (paper Sec. III, "Speeding-up WMD using RWMD").

Given a query, exact(-style) WMD against a huge resident set is made
tractable by:

  1. LC-RWMD against ALL resident docs (cheap lower bound, this paper),
  2. exact-k candidate selection: the top-k docs by RWMD get full WMD;
     the k-th WMD value becomes the cut-off L,
  3. every remaining doc with RWMD ≥ L is pruned (RWMD lower-bounds WMD,
     so it provably cannot enter the top-k),
  4. full WMD only on the survivors.

On TPU, data-dependent survivor counts are hostile to fixed shapes, so the
jit path uses a *fixed refinement budget*: WMD is evaluated on the
``refine_budget`` smallest-RWMD docs and survivors are masked, preserving
exactness whenever the number of true survivors ≤ budget (asserted via the
``pruned_exact`` flag in the result).  This is the standard static-shape
adaptation of the paper's dynamic pruning loop.
"""

from __future__ import annotations

import dataclasses
import enum
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topk as topk_lib
from repro.core.lc_rwmd import SegmentedEngine, lc_rwmd_symmetric
from repro.core.wmd import wmd_candidate_values
from repro.data.docs import DocSet

Array = jax.Array


class QualityTier(enum.IntEnum):
    """The serving plane's degradation ladder.

    The paper's pruning cascade (WCD → LC-RWMD → WMD) read TOP-DOWN is a
    quality/cost ladder: each stage is a cheaper approximation of the one
    above it, with a provable lower-bound relationship.  Under overload or
    repeated stage faults the serving plane sheds the most expensive stage
    first and keeps answering — bounded-quality results instead of errors:

      tier  stage served                        relative cost   bound quality
      ----  ----------------------------------  -------------   -------------
      0     full configured cascade             1x              exact-style
            (LC-RWMD [+refine] + Sinkhorn-WMD                   WMD ranking
            rerank, as built)
      1     LC-RWMD candidates served directly  ~1/5x – 1/50x   tight lower
            (rerank + symmetric refine shed)    (skips Sinkhorn) bound ranking
      2     WCD shortlist (centroid distances)  ~1/1000x        loose lower
                                                                bound (Fig. 11)

    Every delivered :class:`~repro.serving.query_server.Answer` is stamped
    with the tier it was served at; the controller steps back up when
    pressure clears.  Used by the tiered serve step
    (:func:`repro.distributed.lcrwmd_dist.build_serve_step` engine path) and
    the single-host :func:`cascade_topk` entry below.
    """

    FULL = 0
    LCRWMD = 1
    WCD = 2


def cascade_topk(
    engine: SegmentedEngine,
    queries: DocSet,
    k: int,
    *,
    tier: QualityTier | int = QualityTier.FULL,
    rerank_budget: int | None = None,
    sinkhorn_kw: dict | None = None,
) -> topk_lib.TopK:
    """Single-host tiered cascade entry: top-k at the requested quality tier.

    The non-mesh analogue of the tiered distributed serve step — each tier
    routes through the engine's already-jit'd methods, so tier switches
    never re-trace.  ``tier`` follows :class:`QualityTier`; ``k``,
    ``rerank_budget`` and ``sinkhorn_kw`` are jit-static.  Returns a
    (B, k) :class:`~repro.core.topk.TopK` (ascending, global doc ids).
    """
    tier = QualityTier(int(tier))
    if tier >= QualityTier.WCD:
        from repro.core.distances import dists
        from repro.core.wcd import centroids

        c_r = centroids(engine.resident, engine.emb_full)        # (n, m)
        c_q = centroids(queries, engine.emb_full)                # (B, m)
        d = dists(c_r, c_q)                                      # (n, B)
        # tombstoned docs never shortlist
        d = jnp.where(engine.live_mask_device()[:, None], d, jnp.inf)
        return topk_lib.topk_smallest_cols(d, k)
    if tier >= QualityTier.LCRWMD:
        return engine.topk_streaming(queries, k)
    budget = min(max(rerank_budget or 2 * k, k), engine.resident.n_docs)
    cand = engine.topk_streaming(queries, budget)
    return engine.rerank_topk(queries, cand.indices, k,
                              sinkhorn_kw=sinkhorn_kw)


class PrunedWMDResult(NamedTuple):
    topk: topk_lib.TopK     # (B, k) final WMD top-k (distances ascending)
    rwmd_topk: topk_lib.TopK  # (B, k) the RWMD-only top-k (for overlap metrics)
    n_refined: Array        # (B,) WMD evaluations actually spent per query
    pruned_exact: Array     # (B,) bool: True → result provably equals full WMD
    cutoff: Array           # (B,) the cut-off value L


def pruned_wmd_topk(
    resident: DocSet,
    queries: DocSet,
    emb: Array,
    *,
    k: int,
    refine_budget: int | None = None,
    sinkhorn_kw: dict | None = None,
    engine: SegmentedEngine | None = None,
    use_kernel: bool = False,
    interpret: bool = False,
    index=None,
    top_p: int | None = None,
) -> PrunedWMDResult:
    """Top-k WMD per query via the RWMD pruning cascade. jit-compatible.

    Shapes: ``resident`` (n, h1) / ``queries`` (B, h2) DocSets, ``emb``
    (v, m) → :class:`PrunedWMDResult` with ``topk``/``rwmd_topk`` (B, k)
    TopKs (ascending; global resident doc ids), ``n_refined``/``cutoff``
    (B,), and ``pruned_exact`` (B,) bool — True certifies the WMD top-k
    equals the full-corpus WMD top-k.  ``k`` and ``refine_budget`` select
    result/candidate widths, so treat them as jit-static (mark them static
    if you wrap this in ``jax.jit``); ``sinkhorn_kw`` must likewise be
    hashable-stable per compile.  ``refine_budget`` defaults to
    ``min(4·k, n)`` and is clamped to ``[k, n]`` — feed
    :class:`AdaptiveRefineBudget` with ``pruned_exact`` to tune it online.

    ``engine``: a prebuilt :class:`~repro.core.lc_rwmd.SegmentedEngine`
    over the SAME resident set and embeddings — stage 1 then reuses its
    restricted vocabulary and pre-gathered resident tensors instead of
    re-deriving them per call.

    The refine stage runs ALL ``(B, budget)`` candidate pairs as ONE batched
    log-domain Sinkhorn solve (:func:`repro.core.wmd.sinkhorn_log_batched`)
    instead of the historical per-candidate ``jax.lax.map`` — per-pair
    convergence masks keep exact pairwise semantics while the whole stage is
    GEMM-shaped.  ``use_kernel`` routes it through the fused Pallas kernel
    (cost tiles built in VMEM, see kernels/sinkhorn_wmd.py).

    ``index``: a :class:`repro.index.ClusterIndex` — inserts the
    centroid/triangle-bound stage BEFORE phase 1, making the full cascade
    WCD routing → centroid/triangle bound → LC-RWMD → Sinkhorn rerank:
    queries route to their ``top_p`` nearest cells (index default when
    None), the triangle bound drops routed cells that provably cannot hold
    a competitive match, and stage 1's streaming selection scans ONLY the
    surviving cells.  ``pruned_exact`` then certifies exactness *relative
    to the routed cells* — with ``top_p = index.num_cells`` and the bound
    disabled that is the full corpus again (bit-identical to the unrouted
    cascade, see tests/test_index.py).
    """
    sinkhorn_kw = sinkhorn_kw or {}
    n = resident.n_docs
    budget = refine_budget or min(4 * k, n)
    budget = min(max(budget, k), n)  # bootstrap needs k candidates

    # Stage 0 (optional): cell routing + centroid/triangle bound — whole
    # cells leave the cascade before any phase-1 work.  Stage 1: LC-RWMD
    # lower bounds + candidate selection.  With an engine, selection
    # happens INSIDE the streaming phase-2 pass (StreamingTopK carry) — the
    # (n, B) RWMD matrix never reaches HBM; the engine-less fallback keeps
    # the materialized reference path.  Both orders are identical, ties
    # included (shared lexicographic tie-break).
    if index is not None:
        route = index.route(queries, top_p=top_p)
        if route.n_docs_pruned and index.obs is not None \
                and index.obs.metrics.enabled:
            index.obs.metrics.counter(
                "cascade_bound_pruned_docs_total",
                "Docs excluded from phase 1 by the cascade's "
                "centroid/triangle bound stage.").inc(route.n_docs_pruned)
        cand = index.routed_topk(queries, budget, route=route)  # (B, budget)
    elif engine is not None:
        cand = engine.symmetric_topk_streaming(queries, budget)  # (B, budget)
    else:
        d_rwmd = lc_rwmd_symmetric(resident, queries, emb)  # (n, B)
        cand = topk_lib.topk_smallest_cols(d_rwmd, budget)  # (B, budget)

    # Stage 2+4 fused under a fixed budget: WMD on the `budget` best docs,
    # all (B, budget) pairs in one batched solve.  One top-k pass serves
    # both outputs: candidates sort ascending, so the RWMD-only top-k is the
    # first k columns of the candidate set.
    rwmd_topk = topk_lib.TopK(cand.dists[:, :k], cand.indices[:, :k])
    # The engine may hand back unfilled (-1) candidate slots when
    # fewer than `budget` live docs exist — clip the gather and re-inf the
    # values so dead slots never win; a no-op when every slot is filled.
    flat = jnp.clip(cand.indices, 0, n - 1).reshape(-1)  # (B*budget,)
    wmd_vals = wmd_candidate_values(
        emb[resident.ids[flat]], resident.weights[flat],
        emb[queries.ids], queries.weights,
        use_kernel=use_kernel,
        bf16_matmul=engine.bf16_matmul if engine is not None else False,
        interpret=interpret or None,
        **sinkhorn_kw,
    )  # (B, budget)
    wmd_vals = jnp.where(cand.indices >= 0, wmd_vals, jnp.inf)

    # Cut-off L = k-th smallest WMD among the first k candidates (the
    # paper's bootstrap); docs with RWMD >= L are provably outside top-k.
    cutoff = jnp.max(wmd_vals[:, :k], axis=1)           # (B,)
    needed = cand.dists < cutoff[:, None]  # docs whose bound does NOT prune
    # WMD spend: the k bootstrap docs are always evaluated; beyond them only
    # the unpruned candidates cost a solve (the bootstrap docs must not be
    # double-counted even when they also satisfy ``needed``).
    n_refined = k + jnp.sum(needed[:, k:], axis=1)
    # Exactness: every non-candidate doc had RWMD >= max candidate RWMD;
    # if the largest *candidate* RWMD >= cutoff, nothing outside the
    # budget can beat the cutoff either -> provably exact.  When the budget
    # covers the whole resident set there ARE no non-candidate docs, so the
    # result is unconditionally exact regardless of the cutoff test.
    exact = cand.dists[:, -1] >= cutoff
    if budget == n:
        # Routed cascades only get the unconditional certificate when the
        # routing provably covered every cell for every query.
        if index is None or (route.keep.all()
                             and route.cells.shape[1] == index.num_cells):
            exact = jnp.ones_like(exact)
    topk = topk_lib.topk_from_candidates(wmd_vals, cand.indices, k)
    return PrunedWMDResult(
        topk=topk, rwmd_topk=rwmd_topk, n_refined=n_refined,
        pruned_exact=exact, cutoff=cutoff,
    )


def knn_classify(
    topk: topk_lib.TopK, resident_labels: Array, n_classes: int,
    *, weights: str = "uniform", eps: float = 1e-6,
) -> Array:
    """kNN labels from a TopK result: (B,) int32.

    ``weights="uniform"`` is the plain majority vote; count ties resolve to
    the LOWEST class id (argmax convention) regardless of distance.
    ``weights="distance"`` weights each vote by ``1/(d + eps)`` from
    ``topk.dists`` — a class whose neighbors are nearer wins count ties, the
    standard distance-weighted kNN rule.
    """
    votes = resident_labels[topk.indices]  # (B, k)
    onehot = jax.nn.one_hot(votes, n_classes, dtype=jnp.float32)
    if weights == "uniform":
        w = jnp.ones_like(topk.dists, dtype=jnp.float32)
    elif weights == "distance":
        w = 1.0 / (topk.dists.astype(jnp.float32) + eps)
    else:
        raise ValueError(f"weights must be 'uniform' or 'distance', got {weights!r}")
    return jnp.argmax(
        jnp.sum(w[..., None] * onehot, axis=1), axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class AdaptiveRefineBudget:
    """Grow ``refine_budget`` geometrically from observed pruning failures.

    The cascade's ``pruned_exact`` flag (trustworthy since the PR 2 bugfix)
    reports per query whether the fixed budget provably covered every true
    survivor.  This helper replaces the static ``4·k`` default: feed each
    batch's flags to :meth:`update`; while the failure rate exceeds
    ``target_failure_rate``, the budget multiplies by ``growth`` (clamped to
    ``[k, n_resident]``).  Budgets converge after O(log_growth(n/k)) batches
    on a stationary corpus.

    ``decay_after`` adds the DOWN direction for drifting corpora: after that
    many CONSECUTIVE all-exact batches the budget halves (``decay`` factor,
    same [k, n_resident] clamp) and the streak resets, so a budget inflated
    by a hard traffic burst drifts back once the cascade is comfortably
    exact again.  Decay never probes below ``failed_budget`` — the largest
    budget ever observed to fail — so on stationary traffic each level is
    probed AT MOST once (one brief re-grow, then the budget is stable);
    without that floor the budget would oscillate forever, periodically
    serving a provably-inexact batch and rebuilding the serve step.  Call
    :meth:`reset_decay_floor` after a known corpus/traffic shift to allow
    re-probing.  ``decay_after=None`` (default) keeps the legacy grow-only
    behavior.
    """

    k: int
    n_resident: int
    init: int | None = None
    growth: float = 2.0
    target_failure_rate: float = 0.05
    decay_after: int | None = None
    decay: float = 0.5
    #: Optional ``repro.obs.Observability`` bundle; when set, each
    #: :meth:`update` records pruned-exact/inexact counters and the
    #: current budget gauge.  Excluded from repr/eq: it is plumbing, not
    #: controller state.
    obs: object = dataclasses.field(default=None, repr=False, compare=False)

    def __post_init__(self):
        if self.k < 1 or self.n_resident < 1:
            raise ValueError("k and n_resident must be positive")
        if self.growth <= 1.0:
            raise ValueError(f"growth must exceed 1, got {self.growth}")
        if not 0.0 < self.decay < 1.0:
            raise ValueError(f"decay must be in (0, 1), got {self.decay}")
        if self.decay_after is not None and self.decay_after < 1:
            raise ValueError(f"decay_after must be >= 1, got {self.decay_after}")
        start = 4 * self.k if self.init is None else self.init
        self.budget = self._clamp(start)
        self.exact_streak = 0   # consecutive all-exact batches observed
        self.failed_budget = 0  # largest budget observed to fail (decay floor)

    def _clamp(self, b: int) -> int:
        return max(self.k, min(int(b), self.n_resident))

    @property
    def saturated(self) -> bool:
        """True once the budget covers the whole resident set (always exact)."""
        return self.budget >= self.n_resident

    def reset_decay_floor(self) -> None:
        """Forget past failures (e.g. after a corpus swap) so decay may
        re-probe budgets that used to be insufficient."""
        self.failed_budget = 0

    def on_corpus_change(self, n_resident: int) -> None:
        """Re-anchor the controller after ingest/delete/compact or an engine
        swap: the failed-budget floor was measured against a DIFFERENT corpus,
        so inheriting it would pin another tenant's worst case onto this one.
        Updates the clamp range, re-clamps the current budget, resets the
        exactness streak, and forgets the stale floor."""
        if n_resident < 1:
            raise ValueError(f"n_resident must be positive, got {n_resident}")
        self.n_resident = int(n_resident)
        self.budget = self._clamp(self.budget)
        self.exact_streak = 0
        self.reset_decay_floor()

    def update(self, pruned_exact) -> int:
        """Observe one batch's ``pruned_exact`` flags; return the new budget."""
        flags = np.asarray(pruned_exact).astype(bool).reshape(-1)
        if not flags.size:
            return self.budget
        obs = self.obs
        if obs is not None and obs.metrics.enabled:
            n_exact = int(flags.sum())
            m = obs.metrics
            m.counter("cascade_pruned_exact_total",
                      "Queries whose rerank budget provably covered every "
                      "true survivor.").inc(n_exact)
            m.counter("cascade_pruned_inexact_total",
                      "Queries whose pruning was NOT certified exact "
                      "(drives budget growth).").inc(flags.size - n_exact)
        if (1.0 - flags.mean()) > self.target_failure_rate:
            self.failed_budget = max(self.failed_budget, self.budget)
            self.budget = self._clamp(math.ceil(self.budget * self.growth))
            self.exact_streak = 0
        elif flags.all():
            self.exact_streak += 1
            if (self.decay_after is not None
                    and self.exact_streak >= self.decay_after
                    and self.budget > self.k):
                target = self._clamp(math.floor(self.budget * self.decay))
                if target > self.failed_budget:  # never re-probe a known miss
                    self.budget = target
                self.exact_streak = 0
        else:
            self.exact_streak = 0
        if obs is not None and obs.metrics.enabled:
            obs.metrics.gauge(
                "cascade_refine_budget",
                "Current adaptive rerank budget (kc).").set(self.budget)
        return self.budget
