"""Pallas TPU kernels: LC-RWMD Phase 2 — ELL-format SpMM as a row gather.

Computes ``D[i, j] = Σ_p w[i, p] · Z[ids[i, p], j]`` (sparse resident matrix
times the dense Phase-1 output).  The paper uses CUSPARSE CSR SpMM; TPUs
have no sparse unit, so the ELL column ids ride in SMEM and steer DMAs of
exactly the Z rows each doc needs into VMEM — random-access gather.

Three formulations (see EXPERIMENTS.md §Perf for the HBM-traffic model):

``spmm_ell_pallas`` (blocked gather, the default):
  Grid ``(n // block_n,)`` over doc *tiles*.  Each step reads the tile's
  ELL ids from SMEM and issues all ``block_n·h`` (1, B) row DMAs out of Z
  in HBM before waiting on any, so the DMA engine overlaps the row fetches
  of a whole doc tile; the weighted sum then runs over the gathered rows
  in VMEM.

``spmm_ell_dense_pallas`` (one-hot MXU formulation):
  Grid ``(n // block_n, v // block_v)``.  Per step, the (block_n, h) id tile
  is expanded into a one-hot accumulator A[i, c] = Σ_p w[i,p]·[ids[i,p]=c]
  over the current vocab subtile, and ``A @ Z_tile`` runs on the MXU.  Dense
  compares cost n·h·v VPU ops total, so this only wins for small vocab
  chunks — exactly the fused-streaming regime (fused_stream.py reuses it).

``spmm_ell_naive_pallas`` (the seed kernel, kept as the recorded baseline):
  Grid ``(n, h)``, one doc × one ELL slot per step, one (1, B) row DMA each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# Blocked gather formulation (default)
# ---------------------------------------------------------------------------
def _spmm_blocked_kernel(ids_ref, w_ref, z_hbm, out_ref, rows, sem):
    # ids_ref: SMEM (block_n, h) int32 — ELL ids of the current doc tile
    # w_ref:   VMEM (block_n, h) f32 — weights of the current doc tile
    # z_hbm:   HBM (v, B) f32 — the whole Z; rows are DMA'd on demand
    # out_ref: VMEM (block_n, B) f32
    # rows:    VMEM (h, block_n, B) f32 scratch — the tile's gathered rows
    # sem:     one DMA semaphore shared by the tile's block_n·h row copies
    block_n, h = w_ref.shape

    def row_copy(p, j):
        return pltpu.make_async_copy(
            z_hbm.at[pl.ds(ids_ref[j, p], 1)], rows.at[p, pl.ds(j, 1)],
            sem.at[0])

    def start(p, carry):
        for j in range(block_n):
            row_copy(p, j).start()
        return carry

    def wait(p, carry):
        for j in range(block_n):
            row_copy(p, j).wait()
        return carry

    # Every row DMA of the tile is in flight before the first wait.
    jax.lax.fori_loop(0, h, start, 0)
    jax.lax.fori_loop(0, h, wait, 0)

    w = w_ref[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)

    def accumulate(p, acc):
        w_p = jnp.sum(jnp.where(slot == p, w, 0.0), axis=1, keepdims=True)
        return acc + w_p * rows[p]

    out_ref[...] = jax.lax.fori_loop(
        0, h, accumulate, jnp.zeros(out_ref.shape, jnp.float32))


def spmm_ell_pallas(
    ids: jax.Array,   # (n, h) int32 ELL column ids in [0, v) (0 at padding)
    w: jax.Array,     # (n, h) f32 weights (0 at padding)
    z: jax.Array,     # (v, B) f32 dense Phase-1 output
    *,
    block_n: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Blocked ELL SpMM: grid (n // block_n,), block_n·h row DMAs per step.

    Requires ``n % block_n == 0`` (ops.spmm_ell pads); padding docs carry
    weight 0 everywhere, so their gathered rows contribute nothing.  Each
    doc tile's ids ride in SMEM and steer explicit row DMAs out of Z in
    HBM: a (1, B) row block is not a legal BlockSpec tile on a (v, B)
    array, and scalar-prefetching all n·h ids would not fit SMEM at
    corpus scale.
    """
    n, h = ids.shape
    v, b = z.shape
    if n % block_n != 0:
        raise ValueError(f"n={n} not a multiple of block_n={block_n}")
    return pl.pallas_call(
        _spmm_blocked_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, h), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),                 # ids
            pl.BlockSpec((block_n, h), lambda i: (i, 0)),           # w
            pl.BlockSpec(memory_space=pl.ANY),                      # z
        ],
        out_specs=pl.BlockSpec((block_n, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        scratch_shapes=[pltpu.VMEM((h, block_n, b), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))],
        interpret=interpret,
    )(ids, w, z)


# ---------------------------------------------------------------------------
# Dense one-hot MXU formulation (small vocab chunks / fused streaming)
# ---------------------------------------------------------------------------
def _spmm_dense_kernel(ids_ref, w_ref, z_ref, out_ref, *, block_v: int):
    # ids_ref: VMEM (block_n, h) int32; w_ref: VMEM (block_n, h) f32
    # z_ref:   VMEM (block_v, B) f32 — current vocab subtile of Z
    # out_ref: VMEM (block_n, B) f32 — accumulated across vocab subtiles
    j = pl.program_id(1)
    ids = ids_ref[...]
    w = w_ref[...]
    bn, h = ids.shape
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (bn, h, block_v), 2)
    a = jnp.sum((ids[:, :, None] == cols).astype(jnp.float32) * w[:, :, None],
                axis=1)                                   # (block_n, block_v)
    contrib = jax.lax.dot_general(
        a, z_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == 0)
    def _init():
        out_ref[...] = contrib

    @pl.when(j > 0)
    def _acc():
        out_ref[...] += contrib


def spmm_ell_dense_pallas(
    ids: jax.Array,   # (n, h) int32
    w: jax.Array,     # (n, h) f32
    z: jax.Array,     # (v, B) f32
    *,
    block_n: int = 8,
    block_v: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """One-hot MXU SpMM: grid (n // block_n, v // block_v)."""
    n, h = ids.shape
    v, b = z.shape
    if n % block_n != 0 or v % block_v != 0:
        raise ValueError(
            f"n={n} / v={v} not multiples of block_n={block_n} / block_v={block_v}")
    grid = (n // block_n, v // block_v)
    return pl.pallas_call(
        functools.partial(_spmm_dense_kernel, block_v=block_v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, h), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, h), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, b), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, b), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        interpret=interpret,
    )(ids, w, z)


# ---------------------------------------------------------------------------
# Seed one-row-at-a-time kernel (recorded baseline for kernels_bench)
# ---------------------------------------------------------------------------
def _spmm_naive_kernel(ids_ref, w_ref, z_ref, out_ref):
    del ids_ref
    p = pl.program_id(1)
    w = w_ref[0, p]

    @pl.when(p == 0)
    def _init():
        out_ref[...] = w * z_ref[...]

    @pl.when(p > 0)
    def _acc():
        out_ref[...] += w * z_ref[...]


def spmm_ell_naive_pallas(
    ids: jax.Array, w: jax.Array, z: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """The seed (n, h) grid: one doc × one ELL slot × one (1, B) DMA per step."""
    n, h = ids.shape
    v, b = z.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, h),
        in_specs=[
            pl.BlockSpec((1, h), lambda i, p, ids: (i, 0)),
            pl.BlockSpec((1, b), lambda i, p, ids: (ids[i, p], 0)),
        ],
        out_specs=pl.BlockSpec((1, b), lambda i, p, ids: (i, 0)),
    )
    return pl.pallas_call(
        _spmm_naive_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        interpret=interpret,
    )(ids, w, z)
