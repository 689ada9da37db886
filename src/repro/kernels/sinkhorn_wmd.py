"""Pallas TPU kernel: fused batched Sinkhorn-WMD (the refine/rerank stage).

The pruning cascade's most expensive stage is exact(-style) WMD on the
surviving candidates.  A naive batched implementation materializes the
``(P, h1, h2)`` cost stack in HBM and streams it back through every scaling
iteration — O(iters · P·h1·h2) HBM traffic for O(P·h1·h2·m) useful FLOPs.
Following the fused SDDMM-SpMM formulation of Tithi & Petrini (2021), this
kernel builds each pair-block's ``(h1, h2)`` cost tile **on the fly from the
gathered word embeddings** (an MXU batched dot — the SDDMM) and runs the
entire log-domain ε-scaled Sinkhorn iteration with the potentials ``f, g``
and the cost tile resident in VMEM; only the final ``(block_p,)`` transport
costs ever leave the core.  The ``(B, budget, h, h)`` cost tensor never
exists in HBM at any point.

Grid: ``(P // block_p,)`` — one block of candidate pairs per step.  Inside
a block the pairs are solved one after another, each with its own
while-loop per ε level, so one slow pair never holds back a neighbour's
iteration count, and every array in the loop is 2-D: the cost tile
(h1, h2), the potential f as an (h1, 1) column and g as a (1, h2) row.
Mosaic cannot relayout the 3-D broadcasts a block-wide formulation needs.

Blocks (all VMEM):
  t1  (block_p, h1, m)  index i -> (i, 0, 0)   candidate word embeddings
  w1  (block_p, h1, 1)  index i -> (i, 0, 0)   weights as columns
  t2  (block_p, h2, m)  index i -> (i, 0, 0)   query word embeddings
  w2  (block_p, 1, h2)  index i -> (i, 0, 0)   weights as rows
  b2  (block_p, 1, h2)  index i -> (i, 0, 0)   ‖t2‖² as rows
  out (block_p, 1, 128) index i -> (i, 0, 0)   ⟨P, C⟩ per pair, lane-broadcast

Alignment contract (enforced by ops.sinkhorn_wmd): m padded to lane width,
h1 to a multiple of 8, h2 to lane width, P to ``block_p``; padding word
slots and padding pairs carry weight 0 and are masked in log domain (−1e30
sentinels — kernels avoid true ±inf so the f32 arithmetic below never
produces inf−inf NaNs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30  # log-domain mask sentinel (finite: no inf-inf NaN hazard)


def eps_schedule(eps: float, eps_scaling: int, eps_start: float) -> tuple:
    """Geometric ε-scaling ladder as a static python tuple (compile-time)."""
    if eps_scaling <= 1:
        return (float(eps),)
    ratio = (eps / eps_start) ** (1.0 / (eps_scaling - 1))
    return tuple(float(eps_start * ratio**i) for i in range(eps_scaling))


def _lse(x, axis):
    """Masked-safe logsumexp over finite −1e30 sentinels (keeps dims)."""
    m = jnp.max(x, axis=axis, keepdims=True)
    return m + jnp.log(jnp.sum(jnp.exp(x - m), axis=axis, keepdims=True)
                       + 1e-38)


def _sinkhorn_kernel(
    t1_ref, w1_ref, t2_ref, w2_ref, b2_ref, out_ref,
    *, eps_levels: tuple, max_iters: int, tol: float, bf16_matmul: bool,
):
    def solve(pi, carry):
        t1 = t1_ref[pi]   # (h1, m)
        t2 = t2_ref[pi]   # (h2, m)
        w1 = w1_ref[pi]   # (h1, 1)
        w2 = w2_ref[pi]   # (1, h2)
        h1, h2 = w1.shape[0], w2.shape[1]

        # SDDMM-style on-the-fly cost tile: one (h1, m)x(m, h2) MXU dot,
        # built in VMEM and never written to HBM.
        if bf16_matmul:
            ab = jax.lax.dot_general(
                t1.astype(jnp.bfloat16), t2.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        else:
            ab = jax.lax.dot_general(
                t1, t2, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        a2 = jnp.sum(t1 * t1, axis=-1, keepdims=True)        # (h1, 1)
        cost = jnp.sqrt(jnp.maximum(a2 + b2_ref[pi] - 2.0 * ab, 0.0))

        valid_a = w1 > 0                                      # (h1, 1)
        valid_b = w2 > 0                                      # (1, h2)
        pair_mask = (valid_a.astype(jnp.float32)
                     * valid_b.astype(jnp.float32)) > 0       # (h1, h2)
        log_a = jnp.where(valid_a, jnp.log(jnp.maximum(w1, 1e-38)), _NEG_INF)
        log_b = jnp.where(valid_b, jnp.log(jnp.maximum(w2, 1e-38)), _NEG_INF)

        def run_level(level_eps, f, g):
            inv = 1.0 / level_eps

            def cond(state):
                _, _, it, err = state
                return jnp.logical_and(it < max_iters, err > tol)

            def body(state):
                f, g, it, _ = state
                lk = jnp.where(pair_mask, (g - cost) * inv, _NEG_INF)
                f = jnp.where(valid_a, level_eps * (log_a - _lse(lk, 1)),
                              _NEG_INF)
                lk2 = jnp.where(pair_mask, (f - cost) * inv, _NEG_INF)
                g = jnp.where(valid_b, level_eps * (log_b - _lse(lk2, 0)),
                              _NEG_INF)
                log_p = jnp.where(pair_mask, (f + g - cost) * inv, _NEG_INF)
                row = jnp.sum(jnp.exp(log_p), axis=1, keepdims=True)
                return f, g, it + 1, jnp.sum(jnp.abs(row - w1))

            f, g, _, _ = jax.lax.while_loop(
                cond, body, (f, g, jnp.int32(0), jnp.float32(3.4e38)))
            return f, g

        f = jnp.zeros((h1, 1), jnp.float32)
        g = jnp.zeros((1, h2), jnp.float32)
        for level_eps in eps_levels:  # static unroll: ε ladder is compile-time
            f, g = run_level(level_eps, f, g)

        inv = 1.0 / eps_levels[-1]
        log_p = jnp.where(pair_mask, (f + g - cost) * inv, _NEG_INF)
        # Row-max stabilization (cancels in the rescale below) so exp() stays
        # finite for unconverged rows; the division floor must be a NORMAL
        # f32 (1e-38 is subnormal and flushed to zero on XLA:CPU -> w1/0).
        mrow = jnp.max(log_p, axis=1, keepdims=True)
        mrow = jnp.where(mrow > -1e35, mrow, 0.0)
        plan = jnp.exp(log_p - mrow)
        row = jnp.sum(plan, axis=1, keepdims=True)
        # Feasibility rounding (Altschuler et al. 2017): rescale rows to hit
        # the row marginal exactly so the reported cost is a valid transport
        # value.
        plan = plan * jnp.where(valid_a, w1 / jnp.maximum(row, 1e-30), 0.0)
        cost_val = jnp.sum(jnp.where(pair_mask, plan * cost, 0.0))
        out_ref[pi] = jnp.full((1, out_ref.shape[-1]), cost_val, jnp.float32)
        return carry

    jax.lax.fori_loop(0, t1_ref.shape[0], solve, 0)


def sinkhorn_wmd_pallas(
    t1: jax.Array,   # (P, h1, m) f32
    w1: jax.Array,   # (P, h1) f32
    t2: jax.Array,   # (P, h2, m) f32
    w2: jax.Array,   # (P, h2) f32
    *,
    eps: float = 0.01,
    eps_scaling: int = 4,
    eps_start: float = 1.0,
    max_iters: int = 500,
    tol: float = 1e-5,
    block_p: int = 8,
    bf16_matmul: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Returns (P,) f32 fused batched Sinkhorn-WMD transport costs."""
    p, h1, m = t1.shape
    _, h2, _ = t2.shape
    if p % block_p != 0:
        raise ValueError(f"P={p} not a multiple of block_p={block_p}")
    out = pl.pallas_call(
        functools.partial(
            _sinkhorn_kernel,
            eps_levels=eps_schedule(eps, eps_scaling, eps_start),
            max_iters=max_iters, tol=tol, bf16_matmul=bf16_matmul,
        ),
        grid=(p // block_p,),
        in_specs=[
            pl.BlockSpec((block_p, h1, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_p, h1, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_p, h2, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_p, 1, h2), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_p, 1, h2), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_p, 1, 128), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, 1, 128), jnp.float32),
        interpret=interpret,
    )(t1, w1[:, :, None], t2, w2[:, None, :],
      jnp.sum(t2 * t2, axis=-1)[:, None, :])
    return out[:, 0, 0]
