"""Word Mover's Distance — exact EMD semantics, TPU-idiomatic solver.

The paper computes WMD with FastEMD (network simplex) on CPUs, pruned by
RWMD.  Network simplex is sequential and branchy — no TPU analogue — so the
on-device solver here is **log-domain Sinkhorn with ε-scaling**
(Cuturi 2013), which is matrix-scaling (GEMV-shaped, MXU/VPU friendly) and
converges to the exact EMD value as ε→0.  ``emd_exact_lp`` (scipy linprog,
host-side) is retained as the test oracle; tests bound
|sinkhorn − LP| ≤ tol on random histograms (see tests/test_wmd.py).

All entry points take ELL-padded histograms: padding slots (weight 0) are
handled by assigning them +inf cost rows/columns *in log domain* (i.e. −inf
log-kernel), which zeroes their transport plan mass exactly.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.docs import DocSet

Array = jax.Array
_NEG_INF = -1e30


class SinkhornResult(NamedTuple):
    cost: Array       # ⟨P, C⟩ transport cost (the WMD estimate)
    n_iters: Array    # iterations executed (across all ε levels)
    marginal_err: Array  # final L1 violation of the row marginal
    loop_iters: Array | None = None  # (levels,) sweeps of the batched
    #                                 loop (batched solver only)


def _logsumexp(x: Array, axis: int) -> Array:
    m = jnp.max(x, axis=axis, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    return jnp.squeeze(m, axis) + jnp.log(
        jnp.sum(jnp.exp(x - m), axis=axis) + 1e-38
    )


def _eps_levels(eps: float, eps_scaling: int, eps_start: float) -> np.ndarray:
    """The ε ladder as float32 constants: geometric from ``eps_start``."""
    if eps_scaling <= 1:
        return np.array([eps], np.float32)
    return np.geomspace(eps_start, eps, eps_scaling).astype(np.float32)


def word_costs(t1: Array, t2: Array) -> Array:
    """(…, h1, h2) word distances ‖t1_i − t2_j‖ in direct form.

    ``t1`` (…, h1, m) and ``t2`` (…, h2, m) broadcast over their leading
    axes.  Unlike the Gram expansion of :func:`repro.core.distances.dists`,
    a word shared by both documents costs exactly 0 here; the expansion
    leaves it at ≈ sqrt(f32 rounding of ‖a‖²) (≈ 0.04 at Set-2 widths),
    which ε = 0.02 turns into a visible change of the plan.
    """
    d = t1[..., :, None, :] - t2[..., None, :, :]
    return jnp.sqrt(jnp.sum(d * d, axis=-1))


def sinkhorn_log(
    a: Array,
    b: Array,
    cost: Array,
    *,
    eps: float = 0.01,
    eps_scaling: int = 4,
    eps_start: float = 1.0,
    max_iters: int = 500,
    tol: float = 1e-5,
) -> SinkhornResult:
    """Log-domain Sinkhorn with ε-scaling. a:(h1,), b:(h2,), cost:(h1,h2).

    Zero-mass entries (padding) are excluded via −inf log-marginals.
    Returns the *unregularized* transport cost ⟨P, C⟩ under the final plan.
    """
    h1, h2 = cost.shape
    valid_a = a > 0
    valid_b = b > 0
    log_a = jnp.where(valid_a, jnp.log(jnp.maximum(a, 1e-38)), _NEG_INF)
    log_b = jnp.where(valid_b, jnp.log(jnp.maximum(b, 1e-38)), _NEG_INF)
    # Mask padding in the cost so exp(-C/eps) underflows to 0 there.
    big = jnp.where(valid_a[:, None] & valid_b[None, :], cost, jnp.inf)

    # ε-scaling schedule: geometric from eps_start down to eps.
    if eps_scaling <= 1:
        eps_levels = jnp.array([eps], dtype=jnp.float32)
    else:
        eps_levels = jnp.geomspace(eps_start, eps, eps_scaling).astype(jnp.float32)

    def run_level(carry, level_eps):
        f, g, it_total = carry

        def cond(state):
            f, g, it, err = state
            return jnp.logical_and(it < max_iters, err > tol)

        def body(state):
            f, g, it, _ = state
            # f-update: f = eps*(log_a - LSE_j((g - C)/eps))
            lk = (g[None, :] - big) / level_eps  # (h1, h2)
            f_new = level_eps * (log_a - _logsumexp(lk, axis=1))
            f_new = jnp.where(valid_a, f_new, _NEG_INF)
            lk2 = (f_new[:, None] - big) / level_eps
            g_new = level_eps * (log_b - _logsumexp(lk2, axis=0))
            g_new = jnp.where(valid_b, g_new, _NEG_INF)
            # Row-marginal violation under the updated potentials.
            log_p = (f_new[:, None] + g_new[None, :] - big) / level_eps
            row = jnp.sum(jnp.exp(log_p), axis=1)
            err = jnp.sum(jnp.abs(row - a))
            return f_new, g_new, it + 1, err

        f, g, it, err = jax.lax.while_loop(
            cond, body, (f, g, jnp.int32(0), jnp.float32(jnp.inf))
        )
        return (f, g, it_total + it), err

    f0 = jnp.zeros((h1,), jnp.float32)
    g0 = jnp.zeros((h2,), jnp.float32)
    (f, g, iters), errs = jax.lax.scan(run_level, (f0, g0, jnp.int32(0)), eps_levels)

    log_p = (f[:, None] + g[None, :] - big) / eps_levels[-1]
    p = jnp.exp(log_p)
    # Rescale rows to satisfy the row marginal exactly (rounding step of
    # Altschuler et al. 2017) so the reported cost is a valid feasible value.
    row = jnp.sum(p, axis=1)
    p = p * jnp.where(valid_a, a / jnp.maximum(row, 1e-38), 0.0)[:, None]
    cost_val = jnp.sum(jnp.where(jnp.isfinite(big), p * big, 0.0))
    return SinkhornResult(cost=cost_val, n_iters=iters, marginal_err=errs[-1])


def sinkhorn_log_batched(
    a: Array,
    b: Array,
    cost: Array,
    *,
    eps: float = 0.01,
    eps_scaling: int = 4,
    eps_start: float = 1.0,
    max_iters: int = 500,
    tol: float = 1e-5,
) -> SinkhornResult:
    """Batched log-domain Sinkhorn with ε-scaling over a leading pairs axis.

    a:(P,h1), b:(P,h2), cost:(P,h1,h2).  Zero-mass entries (padding) are
    excluded via −inf log-marginals.  All P problems share ONE
    ``while_loop`` per ε level with **per-pair convergence masks**: a pair
    whose row-marginal L1 violation drops to ``tol`` freezes its potentials
    (and its iteration counter) while the still-live pairs keep iterating,
    so each pair's result is that of a solve on its own.  A level ends when
    every pair has stopped or after ``max_iters`` sweeps; the final plan's
    rows are rescaled to ``a`` (the rounding step of Altschuler et al.
    2017) and the *unregularized* cost ⟨P, C⟩ is returned.

    Every sweep is the log-domain update f = ε(log a − LSE_j((g − C)/ε)),
    g = ε(log b − LSE_i((f − C)/ε)).  An exp-domain iteration (K v, Kᵀ u on
    a kernel refreshed every few sweeps) is not the same map here: at
    ε = 0.02 and word distances of 20–110, whole columns of exp(−C/ε)
    underflow between refreshes, and the clamped scalings then move the
    potentials by less than the log-domain step.  The row marginal of a
    sweep, exp(f/ε + LSE_j((g − C)/ε)), is the next sweep's f-update
    LSE, so each sweep reads the cost twice, not three times.

    Arrays are held pairs-minor — (h1, h2, P) — so the pairs axis fills the
    vector lanes and both reductions run across whole registers.

    Returns a :class:`SinkhornResult` of per-pair (P,) arrays, with
    ``loop_iters`` the (levels,) sweeps of the shared loop.
    """
    levels = _eps_levels(eps, eps_scaling, eps_start)
    a, b = a.T, b.T                                    # (h1, P), (h2, P)
    valid_a = a > 0
    valid_b = b > 0
    log_a = jnp.where(valid_a, jnp.log(jnp.maximum(a, 1e-38)), _NEG_INF)
    log_b = jnp.where(valid_b, jnp.log(jnp.maximum(b, 1e-38)), _NEG_INF)
    # Mask padding in the cost so exp(-C/eps) underflows to 0 there.
    big = jnp.where(valid_a[:, None, :] & valid_b[None, :, :],
                    jnp.transpose(cost, (1, 2, 0)), jnp.inf)  # (h1, h2, P)
    p = a.shape[1]

    f = jnp.zeros(a.shape, jnp.float32)
    g = jnp.zeros(b.shape, jnp.float32)
    iters = jnp.zeros((p,), jnp.int32)
    loop_iters, err = [], None
    for lev in levels:
        lev = jnp.float32(lev)

        def lse_f(g, lev=lev):       # LSE_j((g − C)/ε): (h1, P)
            return _logsumexp((g[None, :, :] - big) / lev, 1)

        def body(state, lev=lev, lse_f=lse_f):
            f, g, lf, it_pair, it, err = state
            live = err > tol  # (P,) pairs still iterating at this level
            f2 = jnp.where(valid_a, lev * (log_a - lf), _NEG_INF)
            g2 = jnp.where(valid_b, lev * (log_b - _logsumexp(
                (f2[:, None, :] - big) / lev, 0)), _NEG_INF)
            lf2 = lse_f(g2)
            # Row marginal under (f2, g2): exp(f2/ε + LSE_j((g2 − C)/ε)).
            row = jnp.where(valid_a, jnp.exp(f2 / lev + lf2), 0.0)
            err2 = jnp.sum(jnp.abs(row - a), axis=0)
            keep = live[None, :]
            return (jnp.where(keep, f2, f), jnp.where(keep, g2, g),
                    jnp.where(keep, lf2, lf), it_pair + live.astype(jnp.int32),
                    it + 1, jnp.where(live, err2, err))

        def cond(state):
            return jnp.logical_and(state[4] < max_iters,
                                   jnp.any(state[5] > tol))

        f, g, _, iters, it, err = jax.lax.while_loop(
            cond, body,
            (f, g, lse_f(g), iters, jnp.int32(0),
             jnp.full((p,), jnp.inf, jnp.float32)))
        loop_iters.append(it)

    log_p = (f[:, None, :] + g[None, :, :] - big) / jnp.float32(levels[-1])
    # Row-max stabilization: the per-row shift cancels in the row rescale
    # below, but keeps exp() finite when an unconverged pair's potentials
    # overshoot (exp(log_p) alone can overflow to inf -> inf/inf NaNs).
    mrow = jnp.max(log_p, axis=1, keepdims=True)
    mrow = jnp.where(jnp.isfinite(mrow), mrow, 0.0)
    plan = jnp.exp(log_p - mrow)
    row = jnp.sum(plan, axis=1)
    # Rescale rows to satisfy the row marginal exactly (rounding step of
    # Altschuler et al. 2017) so the reported cost is a valid feasible value.
    plan = plan * jnp.where(
        valid_a, a / jnp.maximum(row, 1e-30), 0.0)[:, None, :]
    cost_val = jnp.sum(
        jnp.where(jnp.isfinite(big), plan * big, 0.0), axis=(0, 1)
    )
    return SinkhornResult(cost=cost_val, n_iters=iters, marginal_err=err,
                          loop_iters=jnp.stack(loop_iters))


def wmd_batched_from_t(
    t1: Array, w1: Array, t2: Array, w2: Array, **sink_kw
) -> Array:
    """Batched WMD from pre-gathered word embeddings.

    t1:(P,h1,m), w1:(P,h1), t2:(P,h2,m), w2:(P,h2) — builds the (P,h1,h2)
    cost stack and solves all pairs in one batched Sinkhorn.  Returns (P,).
    """
    return sinkhorn_log_batched(w1, w2, word_costs(t1, t2), **sink_kw).cost


def wmd_batched(
    ids1: Array, w1: Array, ids2: Array, w2: Array, emb: Array, **sink_kw
) -> Array:
    """Batched WMD over P histogram pairs; ids*:(P,h), w*:(P,h). Returns (P,)."""
    return wmd_batched_from_t(emb[ids1], w1, emb[ids2], w2, **sink_kw)


# Solver kwargs: the jnp solver and the fused Pallas kernel take the same
# set, and anything else is rejected up front so a typo'd option cannot
# silently change behavior on one backend only.
_SINK_KEYS = frozenset({"eps", "eps_scaling", "eps_start", "max_iters", "tol"})

#: The per-batch sums :func:`sinkhorn_work` returns, in order.
SINKHORN_WORK = ("pairs", "cells", "words", "cell_iters", "swept_cells")


def _check_sink_kw(sink_kw: dict) -> None:
    unknown = set(sink_kw) - _SINK_KEYS
    if unknown:
        raise TypeError(f"unknown sinkhorn kwargs: {sorted(unknown)}")


def wmd_batched_dispatch(
    t1: Array, w1: Array, t2: Array, w2: Array,
    *,
    use_kernel: bool = False,
    bf16_matmul: bool = False,
    interpret: bool | None = None,
    **sink_kw,
) -> Array:
    """Backend dispatch for batched WMD from pre-gathered embeddings.

    The single place that maps a user ``sinkhorn_kw`` dict onto either the
    jnp batched solver or the fused Pallas kernel; ``bf16_matmul`` applies
    to the kernel's Gram-form cost tile only.
    """
    _check_sink_kw(sink_kw)
    if use_kernel:
        from repro.kernels import ops as kops

        return kops.sinkhorn_wmd(
            t1, w1, t2, w2, bf16_matmul=bf16_matmul, interpret=interpret,
            **sink_kw)
    return wmd_batched_from_t(t1, w1, t2, w2, **sink_kw)


def candidate_sinkhorn(
    t1_flat: Array, w1_flat: Array, t_q: Array, q_w: Array, **sink_kw
) -> SinkhornResult:
    """Batched Sinkhorn over B-major flattened candidate pairs.

    t1_flat/w1_flat: (B·budget, h1[, m]) candidate word embeddings+weights
    in query-major order (row ``q*budget + c`` is query q's c-th
    candidate); t_q/q_w: (B, h2, m)/(B, h2) query tensors.  Each query's
    embeddings are broadcast over its ``budget`` candidates inside the cost
    (scope ``rerank_cost``), never repeated into a (B·budget, h2, m) copy;
    the solve is scope ``sinkhorn``.
    """
    _check_sink_kw(sink_kw)
    b, h2 = q_w.shape
    budget = t1_flat.shape[0] // b
    with jax.named_scope("rerank_cost"):
        t1 = t1_flat.reshape(b, budget, *t1_flat.shape[1:])
        cost = word_costs(t1, t_q[:, None]).reshape(b * budget, -1, h2)
    with jax.named_scope("sinkhorn"):
        return sinkhorn_log_batched(
            w1_flat, jnp.repeat(q_w, budget, axis=0), cost, **sink_kw)


def sinkhorn_work(res: SinkhornResult, a: Array, b: Array) -> Array:
    """Per-batch sums of one batched solve, float32 (5,) in the order of
    :data:`SINKHORN_WORK`: pairs with a word on each side; their real cells
    Σ h1·h2; their real words Σ (h1 + h2); Σ over pairs of the pair's
    iterations (all levels) × its real cells; and the cells the shared loop
    swept, Σ over levels of its sweeps × P × the padded h1·h2."""
    n1 = jnp.sum(a > 0, axis=1).astype(jnp.float32)
    n2 = jnp.sum(b > 0, axis=1).astype(jnp.float32)
    real = (n1 > 0) & (n2 > 0)
    cells = jnp.where(real, n1 * n2, 0.0)
    swept = jnp.sum(res.loop_iters).astype(jnp.float32) * float(
        a.shape[0] * a.shape[1] * b.shape[1])
    return jnp.stack([
        jnp.sum(real.astype(jnp.float32)), jnp.sum(cells),
        jnp.sum(jnp.where(real, n1 + n2, 0.0)),
        jnp.sum(res.n_iters.astype(jnp.float32) * cells), swept])


def wmd_candidate_values(
    t1_flat: Array, w1_flat: Array, t_q: Array, q_w: Array,
    *,
    use_kernel: bool = False,
    bf16_matmul: bool = False,
    interpret: bool | None = None,
    **sink_kw,
) -> Array:
    """(B, budget) WMD values for B-major flattened candidate pairs
    (layout as in :func:`candidate_sinkhorn`).  Shared by every
    refine/rerank site so the pair expansion cannot drift; the fused
    kernel takes per-pair query tensors, so only its branch repeats them.
    """
    b = t_q.shape[0]
    budget = t1_flat.shape[0] // b
    if use_kernel:
        vals = wmd_batched_dispatch(
            t1_flat, w1_flat,
            jnp.repeat(t_q, budget, axis=0), jnp.repeat(q_w, budget, axis=0),
            use_kernel=True, bf16_matmul=bf16_matmul, interpret=interpret,
            **sink_kw)
    else:
        vals = candidate_sinkhorn(t1_flat, w1_flat, t_q, q_w, **sink_kw).cost
    return vals.reshape(b, budget)


def wmd_pair(
    ids1: Array, w1: Array, ids2: Array, w2: Array, emb: Array, **sink_kw
) -> Array:
    """WMD (Sinkhorn) between two padded histograms; returns scalar f32."""
    c = word_costs(emb[ids1], emb[ids2])
    return sinkhorn_log(w1, w2, c, **sink_kw).cost


def wmd_one_vs_many(
    resident: DocSet, q_ids: Array, q_w: Array, emb: Array, **sink_kw
) -> Array:
    """WMD of one query against every resident doc — vmapped Sinkhorn, (n,)."""
    def one(ids1, w1):
        return wmd_pair(ids1, w1, q_ids, q_w, emb, **sink_kw)

    return jax.vmap(one)(resident.ids, resident.weights)


# ---------------------------------------------------------------------------
# Host-side exact oracle (tests / tiny refinement only)
# ---------------------------------------------------------------------------
def emd_exact_lp(a, b, cost) -> float:
    """Exact EMD via scipy linprog (HiGHS). Host-side oracle, NOT jittable."""
    from scipy.optimize import linprog

    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    cost = np.asarray(cost, dtype=np.float64)
    ia = a > 0
    ib = b > 0
    a, b, cost = a[ia], b[ib], cost[np.ix_(ia, ib)]
    h1, h2 = cost.shape
    # Equality constraints: row sums = a, col sums = b.
    A_eq = np.zeros((h1 + h2, h1 * h2))
    for i in range(h1):
        A_eq[i, i * h2 : (i + 1) * h2] = 1.0
    for j in range(h2):
        A_eq[h1 + j, j::h2] = 1.0
    b_eq = np.concatenate([a, b])
    # Drop one redundant constraint (marginals both sum to the same mass).
    res = linprog(
        cost.reshape(-1), A_eq=A_eq[:-1], b_eq=b_eq[:-1],
        bounds=(0, None), method="highs",
    )
    if not res.success:  # pragma: no cover
        raise RuntimeError(f"LP failed: {res.message}")
    return float(res.fun)
