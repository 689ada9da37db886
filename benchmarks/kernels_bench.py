"""Kernel-layer microbenchmarks.

CPU-container caveat: Pallas kernels execute in interpret mode here (Python
loop emulation — NOT representative of TPU time).  The numbers that matter
on this host are the pure-jnp reference path timings (XLA:CPU) and the
VMEM-footprint accounting of the BlockSpec tiling, which is hardware-
independent.  Real-TPU timing belongs to the roofline analysis (§Roofline).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BenchResult, intermediate_shapes, time_fn
from repro.kernels import ops, ref


def _vmem_bytes_phase1(block_v=512, block_h=16, m=384, b=64):
    # emb tile + t tile (bh, B, m) + t2 + out accumulator + (bv, B) min tile
    return 4 * (block_v * m + block_h * b * m + block_h * b
                + 2 * block_v * b)


def _vmem_bytes_fused(block_v=256, block_n=8, h=32, h1=32, m=384, b=8,
                      vocab_chunk=2048):
    # emb tile + t + valid + ids/w tiles + out tile + z cache (the chunk)
    # + the (block_n, h1, block_v) one-hot expansion temp
    b_pad = 128
    return 4 * (block_v * m + b * h * m + b * h + 2 * block_n * h1
                + block_n * b_pad + vocab_chunk * b_pad
                + block_n * h1 * block_v)


def run() -> list[BenchResult]:
    rng = np.random.default_rng(0)
    v, m, b, h = 8192, 128, 8, 32
    emb = jnp.asarray(rng.normal(size=(v, m)).astype(np.float32))
    q_ids = jnp.asarray(rng.integers(0, v, (b, h)).astype(np.int32))
    q_w = jnp.asarray(rng.uniform(0.1, 1, (b, h)).astype(np.float32))

    t_ref = time_fn(jax.jit(ref.lc_rwmd_phase1_ref), emb, q_ids, q_w)
    z = ref.lc_rwmd_phase1_ref(emb, q_ids, q_w)

    n = 4096
    ids = jnp.asarray(rng.integers(0, v, (n, h)).astype(np.int32))
    w = jnp.asarray(rng.uniform(0, 1, (n, h)).astype(np.float32))
    t_spmm = time_fn(jax.jit(ref.spmm_ell_ref), ids, w, z)

    # GNN fused gather-scale-scatter (jnp oracle path timing)
    n_nodes, n_edges, dg = 4096, 32768, 64
    srcg = jnp.asarray(rng.integers(0, n_nodes, n_edges).astype(np.int32))
    dstg = jnp.asarray(np.sort(rng.integers(0, n_nodes, n_edges)).astype(np.int32))
    featg = jnp.asarray(rng.normal(size=(n_nodes, dg)).astype(np.float32))
    radg = jnp.asarray(rng.uniform(0.1, 1, n_edges).astype(np.float32))
    t_seg = time_fn(jax.jit(ref.segment_spmm_ref, static_argnums=4),
                    srcg, dstg, featg, radg, n_nodes)

    # ---- seed two-phase vs fused streaming (pure-jnp paths, XLA:CPU) ------
    # The acceptance contract for the fused engine: same result, peak
    # intermediate (vocab_chunk, B) instead of (v, B), and no slower than
    # the seed two-phase path at the serve shape v=8192, n=4096, B=8.
    vocab_chunk = 2048
    r_ids, r_w = ids, w

    def two_phase(emb, q_ids, q_w, r_ids, r_w):
        zz = ref.lc_rwmd_phase1_ref(emb, q_ids, q_w)   # full Z (v, B) in HBM
        return ref.spmm_ell_ref(r_ids, r_w, zz)

    from repro.kernels.ops import lc_rwmd_fused

    fused = functools.partial(
        lc_rwmd_fused, vocab_chunk=vocab_chunk, fuse="jnp")
    t_two_phase = time_fn(jax.jit(two_phase), emb, q_ids, q_w, r_ids, r_w,
                          iters=9)
    t_fused = time_fn(fused, emb, q_ids, q_w, r_ids, r_w, iters=9)

    # Footprint assertion, checked STRUCTURALLY against the traced program:
    # the two-phase path must contain a full (v, B) Z intermediate (positive
    # control) and the fused streaming path must not — its Z tiles are
    # bounded at (vocab_chunk, B) inside the scan body.
    z_bytes_two_phase = 4 * v * b
    z_bytes_fused = 4 * vocab_chunk * b
    shapes_two_phase = intermediate_shapes(
        two_phase, emb, q_ids, q_w, r_ids, r_w)
    shapes_fused = intermediate_shapes(fused, emb, q_ids, q_w, r_ids, r_w)
    assert (v, b) in shapes_two_phase, "positive control: seed path has Z (v,B)"
    assert (v, b) not in shapes_fused, (
        "fused streaming materialized a full Z (v, B) intermediate")
    assert (vocab_chunk, b) in shapes_fused, (
        "fused streaming should produce (vocab_chunk, B) Z tiles")

    # ---- streaming top-k vs materialize-then-top_k (ISSUE 4 acceptance) --
    # Serve shape n=4096, B=32, k=16: candidate selection fused into the
    # phase-2 accumulator must (a) beat or match the materialized path's
    # wall time on XLA:CPU, (b) contain NO (n, B) f32 intermediate in its
    # traced program, and (c) agree with lax.top_k exactly (ties included).
    from repro.core.topk import topk_smallest_cols
    from repro.kernels.ops import lc_rwmd_fused_topk

    b_s, k_s = 32, 16
    q_ids32 = jnp.asarray(rng.integers(0, v, (b_s, h)).astype(np.int32))
    q_w32 = jnp.asarray(rng.uniform(0.1, 1, (b_s, h)).astype(np.float32))

    def materialized_topk(emb, q_ids, q_w, r_ids, r_w):
        d = two_phase(emb, q_ids, q_w, r_ids, r_w)   # (n, B) in HBM
        tk = topk_smallest_cols(d, k_s)
        return tk.dists, tk.indices

    streaming_topk = functools.partial(
        lc_rwmd_fused_topk, k=k_s, fuse="jnp", vocab_chunk=vocab_chunk,
        row_block=256)
    t_mat_topk = time_fn(jax.jit(materialized_topk),
                         emb, q_ids32, q_w32, r_ids, r_w, iters=9)
    t_stream_topk = time_fn(streaming_topk,
                            emb, q_ids32, q_w32, r_ids, r_w, iters=9)
    shapes_mat_tk = intermediate_shapes(
        materialized_topk, emb, q_ids32, q_w32, r_ids, r_w)
    shapes_stream_tk = intermediate_shapes(
        streaming_topk, emb, q_ids32, q_w32, r_ids, r_w)
    assert (n, b_s) in shapes_mat_tk, "positive control: (n, B) materialized"
    assert (n, b_s) not in shapes_stream_tk, (
        "streaming top-k materialized the (n, B) distance matrix")
    d_mat, i_mat = jax.jit(materialized_topk)(emb, q_ids32, q_w32, r_ids, r_w)
    d_st, i_st = streaming_topk(emb, q_ids32, q_w32, r_ids, r_w)
    assert bool(jnp.all(i_mat == i_st)), "streaming top-k index mismatch"
    assert float(jnp.max(jnp.abs(d_mat - d_st))) < 1e-2

    # Blocked vs naive SpMM: grid-step accounting (hardware-independent; the
    # acceptance floor is block_n >= 8) and interpret-mode step timing at a
    # small shape (the python-loop emulation makes the per-step cost visible;
    # absolute times are NOT TPU times).
    block_n = 8
    steps_naive = n * h
    steps_blocked = n // block_n
    ns, hs, vs, bs = 64, 8, 256, 8
    ids_s = jnp.asarray(rng.integers(0, vs, (ns, hs)).astype(np.int32))
    w_s = jnp.asarray(rng.uniform(0, 1, (ns, hs)).astype(np.float32))
    z_s = jnp.asarray(rng.normal(size=(vs, bs)).astype(np.float32))
    t_naive_i = time_fn(
        functools.partial(ops.spmm_ell, mode="naive", interpret=True),
        ids_s, w_s, z_s, warmup=1, iters=3)
    t_blocked_i = time_fn(
        functools.partial(ops.spmm_ell, mode="blocked", interpret=True),
        ids_s, w_s, z_s, warmup=1, iters=3)

    vmem = _vmem_bytes_phase1()
    vmem_fused = _vmem_bytes_fused(vocab_chunk=vocab_chunk)
    return [
        BenchResult("kernel_phase1_jnp_ref_v8192_b8_h32", t_ref, derived={
            "flops": 2 * v * b * h * m,
            "note": "XLA:CPU reference; Pallas kernel targets TPU"}),
        BenchResult("kernel_spmm_ell_jnp_ref_n4096", t_spmm, derived={
            "nnz": n * h}),
        BenchResult("kernel_two_phase_jnp_v8192_n4096_b8", t_two_phase, derived={
            "z_hbm_bytes": z_bytes_two_phase,
            "note": "seed pipeline: full Z (v, B) materialized between phases"}),
        BenchResult("kernel_fused_stream_jnp_v8192_n4096_b8", t_fused, derived={
            "z_peak_bytes": z_bytes_fused,
            "vocab_chunk": vocab_chunk,
            "z_reduction_x": z_bytes_two_phase / z_bytes_fused,
            "no_slower_than_two_phase": bool(t_fused <= 1.10 * t_two_phase),
            "vs_two_phase": t_fused / t_two_phase}),
        BenchResult("kernel_streaming_topk_v8192_n4096_b32_k16", t_stream_topk,
                    derived={
            "n": n, "B": b_s, "k": k_s,
            "us_materialized_topk": round(t_mat_topk, 1),
            "vs_materialized": round(t_stream_topk / t_mat_topk, 3),
            "d_hbm_bytes_materialized": 4 * n * b_s,
            "d_peak_bytes_streaming": 4 * k_s * b_s,
            "footprint_reduction_x": n // k_s,
            "no_nB_intermediate": bool((n, b_s) not in shapes_stream_tk),
            "exact_vs_lax_topk": True,
            "note": "selection fused into the phase-2 accumulator "
                    "(StreamingTopK scan); O(n*B) -> O(k*B) serve-path HBM"}),
        BenchResult("kernel_spmm_blocked_vs_naive_interp", t_blocked_i, derived={
            "t_naive_us": t_naive_i,
            "grid_steps_naive_n4096": steps_naive,
            "grid_steps_blocked_n4096": steps_blocked,
            "block_n": block_n,
            "step_reduction_x": steps_naive / steps_blocked,
            "note": "interpret-mode python-loop emulation at n=64; the grid "
                    "accounting is for the serve shape n=4096,h=32"}),
        BenchResult("kernel_segment_spmm_jnp_ref_e32768", t_seg, derived={
            "edges": n_edges,
            "note": "jnp oracle; fused Pallas kernel removes the ExD "
                    "message round-trip (see EXPERIMENTS §Roofline)"}),
        BenchResult("kernel_phase1_vmem_footprint", 0.0, derived={
            "bytes": vmem, "limit": 16 * 2**20,
            "fits_vmem": bool(vmem < 16 * 2**20),
            "blockspec": "bv=512,bh=16,B=64,m=384"}),
        BenchResult("kernel_fused_vmem_footprint", 0.0, derived={
            "bytes": vmem_fused, "limit": 16 * 2**20,
            "fits_vmem": bool(vmem_fused < 16 * 2**20),
            "blockspec": f"bv=256,bn=8,chunk={vocab_chunk},m=384"}),
    ]
