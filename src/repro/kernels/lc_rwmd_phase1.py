"""Pallas TPU kernel: fused LC-RWMD Phase 1 (distance + min-reduce).

Computes ``Z[w, j] = min_q ||E[w] - E[q_j]||`` for every vocabulary word w
and query doc j WITHOUT materializing the (v, B·h) distance matrix in HBM —
the GPU implementation in the paper (CUBLAS GEMM then Thrust row-min) writes
and re-reads that matrix; here each ``E_tile @ T_sᵀ`` product runs on the
MXU and the min-reduction happens in VMEM, so HBM traffic drops from
O(v·B·h) to O(v·m + B·h·m + v·B).

Grid: ``(v // block_v, h // block_h)``; the word-slot axis is innermost so
each (v-tile, all queries) output block accumulates a running min across
slot tiles.  Every step covers the whole query batch: one (block_v, B) MXU
product per word slot, reduced by an elementwise min — no reshape of the
lane axis, which Mosaic cannot relayout.

Block layout (all VMEM; every block's last two dims are (8, 128)-aligned or
the full array dims, as the TPU lowering requires):
  emb  (block_v, m)        index (i, p) -> (i, 0)
  t    (block_h, B, m)     index (i, p) -> (p, 0, 0)   word-slot-major
  t2   (block_h, B)        index (i, p) -> (p, 0)      ‖t‖², +_INF at padding
  out Z (block_v, B)       index (i, p) -> (i, 0)      [revisited over p]

Alignment contract (enforced by ops.lc_rwmd_phase1): m a multiple of 128,
block_v and block_h multiples of 8; padding word slots carry t2 = _INF and
padding vocab rows are sliced off by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INF = 3.4e38  # large finite sentinel (Python float: kernels cannot capture consts)


def _phase1_kernel(emb_ref, t_ref, t2_ref, z_ref, *, bf16_matmul: bool):
    p = pl.program_id(1)

    e = emb_ref[...]                                      # (bv, m) f32
    e2 = jnp.sum(e * e, axis=-1, keepdims=True)           # (bv, 1)
    t2 = t2_ref[...]                                      # (bh, B)
    if bf16_matmul:
        e = e.astype(jnp.bfloat16)
    tile_min = None
    for s in range(t_ref.shape[0]):                       # static word slots
        t = t_ref[s]                                      # (B, m)
        if bf16_matmul:
            et = jax.lax.dot_general(
                e, t.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            et = jax.lax.dot_general(
                e, t, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        sq = (e2 + t2[s:s + 1, :]) - 2.0 * et             # (bv, B)
        tile_min = sq if tile_min is None else jnp.minimum(tile_min, sq)
    tile_min = jnp.maximum(tile_min, 0.0)

    @pl.when(p == 0)
    def _init():
        z_ref[...] = tile_min

    @pl.when(p > 0)
    def _acc():
        z_ref[...] = jnp.minimum(z_ref[...], tile_min)


def lc_rwmd_phase1_pallas(
    emb: jax.Array,      # (v, m) f32, v % block_v == 0, m % 128 == 0
    t: jax.Array,        # (h, B, m) f32 word-slot-major, h % block_h == 0
    t2: jax.Array,       # (h, B) f32 ‖t‖², _INF at padding slots
    *,
    block_v: int = 512,
    block_h: int = 16,
    bf16_matmul: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call (pre-padded inputs). Returns SQUARED-min Z (v, B).

    The wrapper in ops.py applies sqrt + unpadding; keeping the kernel in
    squared space saves a transcendental per (v-tile, h-tile) visit.
    """
    v, m = emb.shape
    h, b, _ = t.shape
    grid = (v // block_v, h // block_h)

    return pl.pallas_call(
        functools.partial(_phase1_kernel, bf16_matmul=bf16_matmul),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_v, m), lambda i, p: (i, 0)),
            pl.BlockSpec((block_h, b, m), lambda i, p: (p, 0, 0)),
            pl.BlockSpec((block_h, b), lambda i, p: (p, 0)),
        ],
        out_specs=pl.BlockSpec((block_v, b), lambda i, p: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((v, b), jnp.float32),
        interpret=interpret,
    )(emb, t, t2)
