"""Pairwise Euclidean-distance primitives (the paper's `∘` operator).

The paper's `A ∘ B` computes Euclidean distances between all row pairs of A
and B — "similar to a matrix multiplication ... but instead of dot products,
Euclidean distances" (Sec. III).  On TPU we expand
``‖a−b‖² = ‖a‖² + ‖b‖² − 2·a·b`` so the cubic-work middle term runs on the
MXU; mixed precision computes the GEMM in bf16 inputs with fp32 accumulation
and carries the norms in fp32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

_EPS = 0.0  # distances are clamped at 0; sqrt(0) grads are guarded below.


def sq_dists(a: Array, b: Array, *, bf16_matmul: bool = False) -> Array:
    """Squared Euclidean distances between rows of ``a`` (p,m) and ``b`` (q,m).

    Returns (p, q) float32.  ``bf16_matmul=True`` downcasts the GEMM inputs to
    bf16 (fp32 accumulation via ``preferred_element_type``) — the TPU
    adaptation of the paper's fp32 CUBLAS call.  The f32 GEMM asks for
    ``HIGHEST`` precision: a TPU's default f32 matmul rounds its inputs to
    bf16, and ‖a‖² + ‖b‖² − 2ab cancels that error into the distances.
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    a2 = jnp.sum(a * a, axis=-1)[:, None]
    b2 = jnp.sum(b * b, axis=-1)[None, :]
    if bf16_matmul:
        ab = jax.lax.dot_general(
            a.astype(jnp.bfloat16),
            b.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:
        ab = jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    return jnp.maximum(a2 + b2 - 2.0 * ab, 0.0)


def dists(a: Array, b: Array, **kw) -> Array:
    """Euclidean distances between rows of ``a`` and ``b``; safe sqrt."""
    return safe_sqrt(sq_dists(a, b, **kw))


def safe_sqrt(x: Array) -> Array:
    """sqrt with a zero-safe gradient (d/dx sqrt at 0 is inf otherwise)."""
    return jnp.sqrt(jnp.maximum(x, 1e-12)) * (x > 0)
