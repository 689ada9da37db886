"""jit'd public wrappers around the Pallas kernels.

Responsibilities: hardware-alignment padding (m, h → multiples of 128;
v, n → multiples of the v/n block), dtype policy, interpret-mode fallback on
CPU (the kernels target TPU; ``interpret=True`` executes the kernel body in
Python for validation, per the repo's CPU-container contract), and
un-padding of results.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_stream as _fs
from repro.kernels import lc_rwmd_phase1 as _p1
from repro.kernels import rwmd_pairwise as _rw
from repro.kernels import segment_spmm as _seg
from repro.kernels import sinkhorn_wmd as _sk
from repro.kernels import spmm_ell as _sp

Array = jax.Array

_INF = 3.4e38


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: Array, mult: int, axis: int, value=0) -> Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _phase1_padded(
    emb_f: Array,    # (v_pad, m_pad) f32, already block/lane aligned
    t: Array,        # (B, h, m_pad) f32 pre-gathered query word embeddings
    valid: Array,    # (B, h) f32 0/1
    v_out: int,
    *,
    block_v: int,
    block_h: int,
    bf16_matmul: bool,
    interpret: bool,
) -> Array:
    # Word-slot-major layout: the kernel takes one (B, m) slab per slot, so
    # the query batch rides whole in every block (no lane-axis reshape).
    bh = min(block_h, -(-t.shape[1] // 8) * 8)
    t_s = _pad_to(jnp.swapaxes(t, 0, 1), bh, axis=0)             # (h, B, m)
    t2 = jnp.where(valid.T > 0, jnp.sum(t * t, axis=-1).T, _INF)  # (h, B)
    t2 = _pad_to(t2, bh, axis=0, value=_INF)
    z_sq = _p1.lc_rwmd_phase1_pallas(
        emb_f, t_s, t2,
        block_v=block_v, block_h=bh,
        bf16_matmul=bf16_matmul, interpret=interpret,
    )
    return jnp.sqrt(jnp.maximum(z_sq[:v_out], 0.0))


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_h", "bf16_matmul", "interpret")
)
def lc_rwmd_phase1(
    emb: Array,      # (v, m) float
    q_ids: Array,    # (B, h) int32
    q_w: Array,      # (B, h) float (0 = padding)
    *,
    block_v: int = 512,
    block_h: int = 16,
    bf16_matmul: bool = False,
    interpret: bool | None = None,
) -> Array:
    """Z (v, B) f32 — min distance from every vocab word to each query doc."""
    if interpret is None:
        interpret = _on_cpu()
    v, m = emb.shape
    b, h = q_ids.shape

    emb_f = _pad_to(_pad_to(emb.astype(jnp.float32), 128, axis=1), block_v, axis=0)
    t = emb_f[q_ids.reshape(-1)].reshape(b, h, emb_f.shape[1])
    valid = (q_w > 0).astype(jnp.float32)
    return _phase1_padded(
        emb_f, t, valid, v, block_v=block_v, block_h=block_h,
        bf16_matmul=bf16_matmul, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_h", "bf16_matmul", "interpret")
)
def lc_rwmd_phase1_pregathered(
    emb: Array,      # (v, m) float — the vocab axis of Z
    t: Array,        # (B, h, m) float — PRE-GATHERED query word embeddings
    valid: Array,    # (B, h) float 0/1
    *,
    block_v: int = 512,
    block_h: int = 16,
    bf16_matmul: bool = False,
    interpret: bool | None = None,
) -> Array:
    """Phase 1 with the query gather hoisted out (LCRWMDEngine shares it)."""
    if interpret is None:
        interpret = _on_cpu()
    v = emb.shape[0]
    emb_f = _pad_to(_pad_to(emb.astype(jnp.float32), 128, axis=1), block_v, axis=0)
    t = _pad_to(t.astype(jnp.float32), emb_f.shape[1], axis=2)
    return _phase1_padded(
        emb_f, t, valid.astype(jnp.float32), v, block_v=block_v,
        block_h=block_h, bf16_matmul=bf16_matmul, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_v", "mode", "interpret")
)
def spmm_ell(
    ids: Array,   # (n, h) int32
    w: Array,     # (n, h) float
    z: Array,     # (v, B) float
    *,
    block_n: int = 8,
    block_v: int = 256,
    mode: str = "blocked",
    interpret: bool | None = None,
) -> Array:
    """D (n, B) f32 = ELL-sparse(ids, w) @ z.

    ``mode``: "blocked" (grid (n/block_n, h), block_n gathered-row DMAs per
    step), "dense" (one-hot MXU formulation for small vocab), or "naive"
    (the seed one-row-per-step grid, kept as the recorded baseline).
    """
    if interpret is None:
        interpret = _on_cpu()
    n, h = ids.shape
    z_p = _pad_to(z.astype(jnp.float32), 128, axis=1)
    w_f = w.astype(jnp.float32)
    if mode == "naive":
        out = _sp.spmm_ell_naive_pallas(ids, w_f, z_p, interpret=interpret)
        return out[:n, : z.shape[1]]
    # Pad the doc axis to the tile size; padding docs carry weight 0.
    ids_p = _pad_to(ids, block_n, axis=0)
    w_p = _pad_to(w_f, block_n, axis=0)
    if mode == "blocked":
        # Row DMAs are not bounds-checked on the device: clamp the ids.
        out = _sp.spmm_ell_pallas(
            jnp.clip(ids_p, 0, z.shape[0] - 1), w_p, z_p, block_n=block_n,
            interpret=interpret)
    elif mode == "dense":
        z_p = _pad_to(z_p, block_v, axis=0)
        out = _sp.spmm_ell_dense_pallas(
            ids_p, w_p, z_p, block_n=block_n, block_v=block_v,
            interpret=interpret)
    else:
        raise ValueError(f"unknown spmm mode {mode!r}")
    return out[:n, : z.shape[1]]


@functools.partial(
    jax.jit,
    static_argnames=("vocab_chunk", "fuse", "block_n", "block_v", "block_h",
                     "bf16_matmul", "interpret"),
)
def lc_rwmd_fused(
    emb: Array,      # (v, m) float
    q_ids: Array,    # (B, h) int32
    q_w: Array,      # (B, h) float (0 = padding)
    r_ids: Array,    # (n, h1) int32 resident ELL ids
    r_w: Array,      # (n, h1) float resident weights (0 = padding)
    *,
    vocab_chunk: int = 512,
    fuse: str = "scan",
    block_n: int = 8,
    block_v: int = 256,
    block_h: int = 16,
    bf16_matmul: bool = False,
    interpret: bool | None = None,
) -> Array:
    """Streaming phase-1→phase-2: D (n, B) f32 without a full Z (v, B).

    Scans the vocabulary in ``vocab_chunk``-sized chunks; each chunk's Z tile
    is produced, immediately consumed into the running D accumulator, and
    discarded, so the peak intermediate is (vocab_chunk, B) instead of the
    seed pipeline's (v, B).

    ``fuse``:
      "kernel" — one fused pallas_call per chunk (fused_stream.py): Z lives
                 only in a VMEM scratch cache, never in HBM.
      "scan"   — double-buffered composition of the phase-1 kernel and the
                 blocked SpMM kernel per chunk (Z bounded at (chunk, B) HBM).
      "jnp"    — pure-jnp streaming oracle (XLA:CPU reference + tests).
    """
    if interpret is None:
        interpret = _on_cpu()
    v, m = emb.shape
    b, h = q_ids.shape
    n, h1 = r_ids.shape

    # Chunk size aligned to the vocab subtile; vocab padded to chunk multiple.
    bv = min(block_v, vocab_chunk)
    vc = -(-vocab_chunk // bv) * bv
    emb_f = _pad_to(_pad_to(emb.astype(jnp.float32), 128, axis=1), vc, axis=0)
    n_chunks = emb_f.shape[0] // vc
    t = emb_f[q_ids.reshape(-1)].reshape(b, h, emb_f.shape[1])
    valid = (q_w > 0).astype(jnp.float32)

    r_ids_p = _pad_to(r_ids, block_n, axis=0)
    r_w_p = _pad_to(r_w.astype(jnp.float32), block_n, axis=0)

    emb_chunks = emb_f.reshape(n_chunks, vc, emb_f.shape[1])
    offsets = jnp.arange(n_chunks, dtype=jnp.int32) * vc

    def chunk_step(d_acc, xs):
        e_c, lo = xs
        rel = r_ids_p - lo
        inb = (rel >= 0) & (rel < vc)
        rel_c = jnp.clip(rel, 0, vc - 1).astype(jnp.int32)
        w_m = r_w_p * inb.astype(jnp.float32)
        if fuse == "kernel":
            d_c = _fs.fused_lc_rwmd_chunk_pallas(
                e_c, t, valid, rel_c, w_m,
                block_v=bv, block_n=block_n, bf16_matmul=bf16_matmul,
                interpret=interpret,
            )[:, :b]
        elif fuse == "scan":
            z = _phase1_padded(
                e_c, t, valid, vc, block_v=bv, block_h=block_h,
                bf16_matmul=bf16_matmul, interpret=interpret,
            )
            z_p = _pad_to(z, 128, axis=1)
            d_c = _sp.spmm_ell_pallas(
                rel_c, w_m, z_p, block_n=block_n, interpret=interpret,
            )[:, :b]
        elif fuse == "jnp":
            from repro.core.distances import sq_dists

            sq = sq_dists(e_c, t.reshape(b * h, -1), bf16_matmul=bf16_matmul)
            sq = jnp.where(valid.reshape(-1)[None, :] > 0, sq, _INF)
            z = jnp.sqrt(jnp.maximum(jnp.min(sq.reshape(vc, b, h), axis=2), 0.0))
            d_c = jnp.einsum("nh,nhb->nb", w_m, z[rel_c])
        else:
            raise ValueError(f"unknown fuse mode {fuse!r}")
        return d_acc + d_c, None

    d0 = jnp.zeros((r_ids_p.shape[0], b), jnp.float32)
    d, _ = jax.lax.scan(chunk_step, d0, (emb_chunks, offsets), unroll=2)
    return d[:n]


def streaming_phase2_topk(
    r_ids: Array,    # (n, h1) int32 resident ELL ids (into z's vocab axis)
    r_w: Array,      # (n, h1) float resident weights (0 = padding)
    z: Array,        # (v, B) f32 phase-1 output
    k: int,
    *,
    row_block: int = 128,
    q_gid: Array | None = None,  # (B,) global ids to self-exclude, or None
    row_valid: Array | None = None,  # (n,) bool row mask (tombstones), or None
) -> tuple[Array, Array]:
    """Phase-2 ELL SpMM streamed straight into a per-query top-k carry.

    The jnp/scan reduction behind every streaming top-k fallback: resident
    rows are scanned in ``row_block``-sized slabs, each slab's (R, B) partial
    distances folded into a :class:`~repro.core.topk.StreamingTopK` carry —
    the (n, B) matrix never materializes (peak live slab: (R, B)).  Returns
    ``(dists (B, k), indices (B, k))``, exactly equal (ties included) to
    ``lax.top_k`` over the materialized matrix.

    ``row_valid`` masks individual resident rows to +inf (the segmented
    engine's tombstones): a traced array argument, so flipping entries never
    re-compiles.  ``row_valid=None`` and an all-True mask are exactly equal.
    """
    from repro.core.topk import StreamingTopK

    n, h1 = r_ids.shape
    b = z.shape[1]
    kk = min(k, n)
    r = min(row_block, n)
    nb = -(-n // r)
    ids_b = _pad_to(r_ids, nb * r, axis=0).reshape(nb, r, h1)
    w_b = _pad_to(r_w.astype(jnp.float32), nb * r, axis=0).reshape(nb, r, h1)
    los = jnp.arange(nb, dtype=jnp.int32) * r
    if row_valid is not None:
        valid_b = _pad_to(row_valid, nb * r, axis=0).reshape(nb, r)
        xs = (ids_b, w_b, los, valid_b)
    else:
        xs = (ids_b, w_b, los, None)

    stk = StreamingTopK(kk)

    def body(carry, xs):
        ids_blk, w_blk, lo, valid_blk = xs
        zg = z[ids_blk]                              # (R, h1, B)
        d_blk = jnp.einsum("rh,rhb->rb", w_blk, zg)  # (R, B)
        row = lo + jnp.arange(r, dtype=jnp.int32)
        d_blk = jnp.where((row < n)[:, None], d_blk, jnp.inf)
        if valid_blk is not None:
            d_blk = jnp.where(valid_blk[:, None], d_blk, jnp.inf)
        if q_gid is not None:
            d_blk = jnp.where(row[:, None] == q_gid[None, :], jnp.inf, d_blk)
        return stk.update_cols(carry, d_blk, row), None

    carry, _ = jax.lax.scan(body, stk.init(b), xs)
    return carry.dists, carry.indices


@functools.partial(
    jax.jit,
    static_argnames=("k", "fuse", "row_block", "block_n", "block_v",
                     "block_h", "vocab_chunk", "bf16_matmul", "interpret"),
)
def lc_rwmd_fused_topk(
    emb: Array,      # (v, m) float
    q_ids: Array,    # (B, h) int32
    q_w: Array,      # (B, h) float (0 = padding)
    r_ids: Array,    # (n, h1) int32 resident ELL ids
    r_w: Array,      # (n, h1) float resident weights (0 = padding)
    *,
    k: int,
    fuse: str = "jnp",
    row_block: int = 128,
    block_n: int = 8,
    block_v: int = 256,
    block_h: int = 16,
    vocab_chunk: int = 512,
    bf16_matmul: bool = False,
    interpret: bool | None = None,
) -> tuple[Array, Array]:
    """Streaming one-sided LC-RWMD top-k: (B, k) dists + global doc ids.

    Candidate selection is fused into the phase-2 accumulator, so the (n, B)
    distance matrix never reaches HBM — the serve hot path's dominant
    round-trip (ROADMAP item 3).  Exactly equal (ties included) to
    ``lax.top_k`` over :func:`lc_rwmd_fused`'s output.

    Shapes: ``emb (v, m)``, ``q_ids``/``q_w (B, h)``, ``r_ids``/``r_w
    (n, h1)`` → ``(dists (B, k), doc_ids (B, k))``, distances ascending.

    JIT-STATIC kwargs (each distinct value compiles a new program): ``k``,
    ``fuse``, and every tiling knob — ``row_block`` (jnp slab rows),
    ``block_n``/``block_v``/``block_h`` (Pallas tile sizes), ``vocab_chunk``
    (phase-1 chunking), plus ``bf16_matmul``/``interpret``.  Only the array
    arguments may vary call-to-call without recompiling.

    ``fuse``:
      "kernel" — one fused pallas_call (fused_stream.fused_lc_rwmd_topk_pallas):
                 Z lives in a VMEM cache, per-tile distances in a VMEM
                 scratch, the sorted (k, B) carry in the revisited output
                 block.  HBM peak: O(k·B).  VMEM bounds v (use the engine's
                 restricted vocab).
      "jnp"    — phase-1 Z (v, B) in chunks, then the scan reduction of
                 :func:`streaming_phase2_topk`.  HBM peak: O(v·B) for Z
                 (v ≪ n at serving scale) — never O(n·B).
    """
    if interpret is None:
        interpret = _on_cpu()
    n = r_ids.shape[0]
    b = q_ids.shape[0]
    kk = min(k, n)

    if fuse == "kernel":
        bv = block_v
        emb_f = _pad_to(
            _pad_to(emb.astype(jnp.float32), 128, axis=1), bv, axis=0)
        t = emb_f[q_ids.reshape(-1)].reshape(b, q_ids.shape[1], -1)
        valid = (q_w > 0).astype(jnp.float32)
        ids_p = _pad_to(r_ids, block_n, axis=0)
        w_p = _pad_to(r_w.astype(jnp.float32), block_n, axis=0)
        vals, gids = _fs.fused_lc_rwmd_topk_pallas(
            emb_f, t, valid, ids_p, w_p, k=kk, n_real=n, block_v=bv,
            block_n=block_n, bf16_matmul=bf16_matmul, interpret=interpret)
        return vals[:kk, :b].T, gids[:kk, :b].T
    if fuse == "jnp":
        from repro.core.lc_rwmd import phase1_z

        z = phase1_z(emb, q_ids, q_w, bf16_matmul=bf16_matmul,
                     vocab_chunk=vocab_chunk)
        return streaming_phase2_topk(r_ids, r_w, z, kk, row_block=row_block)
    raise ValueError(f"unknown fuse mode {fuse!r}")


@functools.partial(
    jax.jit, static_argnames=("block_n", "bf16_matmul", "interpret")
)
def rwmd_pairwise(
    emb: Array,       # (v, m)
    r_ids: Array,     # (n, h1) resident ids
    r_w: Array,       # (n, h1)
    q_ids: Array,     # (B, h2) query ids
    q_w: Array,       # (B, h2)
    *,
    block_n: int = 8,
    bf16_matmul: bool = False,
    interpret: bool | None = None,
) -> Array:
    """Quadratic RWMD distance matrix (n, B) f32, fully fused per tile."""
    if interpret is None:
        interpret = _on_cpu()
    emb_f = _pad_to(emb.astype(jnp.float32), 128, axis=1)
    n, h1 = r_ids.shape
    b, h2 = q_ids.shape

    t1 = emb_f[r_ids.reshape(-1)].reshape(n, h1, emb_f.shape[1])
    t2 = emb_f[q_ids.reshape(-1)].reshape(b, h2, emb_f.shape[1])
    # Pad word axes to lane width so min-reductions stay aligned; padding
    # words get weight 0 (=> masked inside the kernel).
    t1 = _pad_to(t1, 128, axis=1)
    w1 = _pad_to(r_w.astype(jnp.float32), 128, axis=1)
    t2 = _pad_to(t2, 128, axis=1)
    w2 = _pad_to(q_w.astype(jnp.float32), 128, axis=1)
    # Pad doc axis to the doc-tile size.
    t1 = _pad_to(t1, block_n, axis=0)
    w1 = _pad_to(w1, block_n, axis=0)

    out = _rw.rwmd_pairwise_pallas(
        t1, w1, t2, w2,
        block_n=block_n, bf16_matmul=bf16_matmul, interpret=interpret,
    )
    return out[:n]


@functools.partial(
    jax.jit,
    static_argnames=("eps", "eps_scaling", "eps_start", "max_iters", "tol",
                     "block_p", "bf16_matmul", "interpret"),
)
def sinkhorn_wmd(
    t1: Array,    # (P, h1, m) candidate word embeddings (pre-gathered)
    w1: Array,    # (P, h1) weights (0 = padding)
    t2: Array,    # (P, h2, m) query word embeddings
    w2: Array,    # (P, h2)
    *,
    eps: float = 0.01,
    eps_scaling: int = 4,
    eps_start: float = 1.0,
    max_iters: int = 500,
    tol: float = 1e-5,
    block_p: int = 8,
    bf16_matmul: bool = False,
    interpret: bool | None = None,
) -> Array:
    """Fused batched Sinkhorn-WMD costs (P,) f32 — cost tiles built in VMEM.

    The (P, h1, h2) cost stack is never materialized in HBM: each pair
    block's tiles are produced from the gathered embeddings on the fly and
    consumed by the in-kernel ε-scaled scaling loop (per-pair convergence
    masks within the block).
    """
    if interpret is None:
        interpret = _on_cpu()
    p, h1, _ = t1.shape
    h2 = t2.shape[1]
    # Lane-align the embedding axis and the query word axis (the cost
    # tile's lanes); the candidate word axis is its sublanes.  Padding words
    # carry weight 0 (masked in log domain inside the kernel).  Padding
    # PAIRS (P axis) are all-zero-weight problems that converge on their
    # first iteration.
    t1 = _pad_to(t1.astype(jnp.float32), 128, axis=2)
    t2 = _pad_to(t2.astype(jnp.float32), 128, axis=2)
    t1 = _pad_to(t1, 8, axis=1)
    t2 = _pad_to(t2, 128, axis=1)
    w1 = _pad_to(w1.astype(jnp.float32), 8, axis=1)
    w2 = _pad_to(w2.astype(jnp.float32), 128, axis=1)
    t1 = _pad_to(t1, block_p, axis=0)
    t2 = _pad_to(t2, block_p, axis=0)
    w1 = _pad_to(w1, block_p, axis=0)
    w2 = _pad_to(w2, block_p, axis=0)
    out = _sk.sinkhorn_wmd_pallas(
        t1, w1, t2, w2,
        eps=eps, eps_scaling=eps_scaling, eps_start=eps_start,
        max_iters=max_iters, tol=tol, block_p=block_p,
        bf16_matmul=bf16_matmul, interpret=interpret,
    )
    return out[:p]


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(
    q: Array, k: Array, v: Array, *,
    causal: bool = True, block_q: int = 512, block_k: int = 512,
    interpret: bool | None = None,
) -> Array:
    """Fused causal GQA attention (flash). q (B,S,Hq,D); k/v (B,T,Hkv,D)."""
    if interpret is None:
        interpret = _on_cpu()
    b, s, hq, d = q.shape
    bq = min(block_q, s)
    bk = min(block_k, k.shape[1])
    # pad seq dims to block multiples; padded kv columns are masked by causal
    # position math only when causal; for non-causal, mask via -inf keys.
    assert s % bq == 0 and k.shape[1] % bk == 0, "pad seqs to block multiple"
    return _fa.flash_attention_pallas(
        q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_out", "interpret"))
def segment_spmm(
    src: Array,   # (E,) int32
    dst: Array,   # (E,) int32, sorted ascending (CSR edge order)
    feat: Array,  # (N, D) float
    rad: Array,   # (E,) float (0 at padding edges)
    n_out: int,
    *,
    interpret: bool | None = None,
) -> Array:
    """Fused GNN gather-scale-scatter: out[n] = sum_{dst=n} rad*feat[src].

    Zero-degree output rows are masked to 0 (unvisited blocks are undefined
    in the revisit-accumulate pattern). Feature dim padded to lane width.
    """
    if interpret is None:
        interpret = _on_cpu()
    d0 = feat.shape[1]
    feat_p = _pad_to(feat.astype(jnp.float32), 128, axis=1)
    meta = jnp.stack([src, dst]).astype(jnp.int32)
    out = _seg.segment_spmm_pallas(
        meta, feat_p, rad.astype(jnp.float32)[None, :], n_out,
        interpret=interpret)
    deg = jax.ops.segment_sum(jnp.ones_like(dst, jnp.float32), dst,
                              num_segments=n_out)
    return jnp.where(deg[:, None] > 0, out[:, :d0], 0.0)
