"""Plain float32 reference of the served answer, and the comparison that
decides ``correct``.

It imports nothing of the program.  The semantics it reproduces:

1. one-sided LC-RWMD of every resident document against the query,
   D1(doc) = Σ_i w_doc[i] · min_j ‖e(doc_i) − e(q_j)‖ over the query's words;
2. the ``kc`` documents of smallest D1 are the candidates (ties: lower id);
3. each candidate's final value is the symmetric RWMD bound max(D1, RWMD)
   (``knn``), or its Sinkhorn WMD (``wmd``: log-domain Sinkhorn with
   ε-scaling, per-pair stopping on the row-marginal error, and the final
   row rounding);
4. the answer is the ``k`` candidates of smallest final value.

Phase 1's word distances use the Gram expansion ‖a‖² + ‖b‖² − 2ab at
``HIGHEST`` matmul precision (the only affordable form over the whole
vocabulary); the candidates' word distances use the direct form
sqrt(Σ (a − b)²).  ``passes`` lowers the precision of every matrix product
(3: ``HIGH``, three bfloat16 passes on a TPU; 1: bfloat16 inputs) and
computes every word distance through the expansion: that is the control.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

_BIG = -1e30   # log-domain stand-in for log(0)


def matmul_t(a, b, passes: int):
    """a (p, m) · bᵀ (m, q) with a float32 result: at ``HIGHEST`` (6 bf16
    passes on a TPU), ``HIGH`` (3 passes) or from bfloat16 inputs (1)."""
    dims = (((1,), (1,)), ((), ()))
    if passes == 1:
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        precision = jax.lax.Precision.DEFAULT
    elif passes == 3:
        precision = jax.lax.Precision.HIGH
    else:
        precision = jax.lax.Precision.HIGHEST
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def gram_dists(a, b, passes: int):
    """Euclidean distances (p, q) between rows of a and b, expansion form."""
    a2 = jnp.sum(a * a, axis=-1)[:, None]
    b2 = jnp.sum(b * b, axis=-1)[None, :]
    return jnp.sqrt(jnp.maximum(a2 + b2 - 2.0 * matmul_t(a, b, passes), 0.0))


def pair_dists(t1, t2, passes: int):
    """(h1, h2) word distances of one document pair."""
    if passes >= 6:
        d = t1[:, None, :] - t2[None, :, :]
        return jnp.sqrt(jnp.sum(d * d, axis=-1))
    return gram_dists(t1, t2, passes)


@functools.partial(jax.jit, static_argnames=("passes", "chunk"))
def one_sided(emb_u, r_idx, r_w, t_q, q_w, *, passes: int, chunk: int):
    """D1 (Q, n): phase 1 over the corpus vocabulary ``emb_u`` (v_e, m),
    phase 2 over the remapped resident histograms, ``chunk`` queries at a
    time.  t_q (Q, h, m), q_w (Q, h)."""
    nq, h, m = t_q.shape

    def block(args):
        t, w = args                                   # (c, h, m), (c, h)
        d = gram_dists(emb_u, t.reshape(-1, m), passes)   # (v_e, c·h)
        d = jnp.where((w > 0).reshape(1, -1), d, jnp.inf)
        z = jnp.min(d.reshape(d.shape[0], chunk, h), axis=2)  # (v_e, c)
        zg = z[r_idx]                                  # (n, h_r, c)
        return jnp.sum(r_w[:, :, None] * zg, axis=1).T  # (c, n)

    out = jax.lax.map(block, (t_q.reshape(nq // chunk, chunk, h, m),
                              q_w.reshape(nq // chunk, chunk, h)))
    return out.reshape(nq, -1)


def _rwmd_pair(t1, w1, t2, w2, passes):
    c = pair_dists(t1, t2, passes)
    v1, v2 = w1 > 0, w2 > 0
    d12 = jnp.sum(jnp.where(v1, w1 * jnp.min(
        jnp.where(v2[None, :], c, jnp.inf), axis=1), 0.0))
    d21 = jnp.sum(jnp.where(v2, w2 * jnp.min(
        jnp.where(v1[:, None], c, jnp.inf), axis=0), 0.0))
    return jnp.maximum(d12, d21)


def _lse(x, axis):
    m = jnp.max(x, axis=axis, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    return jnp.squeeze(m, axis) + jnp.log(
        jnp.sum(jnp.exp(x - m), axis=axis) + 1e-38)


def sinkhorn_cost(a, b, cost, *, eps, eps_scaling, max_iters, tol=1e-5,
                  eps_start=1.0):
    """Transport cost ⟨P, C⟩ of P pairs: a (P, h1), b (P, h2), cost
    (P, h1, h2).  Log-domain iterations per ε level until a pair's
    row-marginal L1 error is at most ``tol`` (it then stops) or the level
    has run ``max_iters``; the final plan's rows are rescaled to ``a``."""
    va, vb = a > 0, b > 0
    big = jnp.where(va[:, :, None] & vb[:, None, :], cost, jnp.inf)
    log_a = jnp.where(va, jnp.log(jnp.maximum(a, 1e-38)), _BIG)
    log_b = jnp.where(vb, jnp.log(jnp.maximum(b, 1e-38)), _BIG)
    levels = (np.geomspace(eps_start, eps, eps_scaling) if eps_scaling > 1
              else np.array([eps])).astype(np.float32)
    f = jnp.zeros(a.shape, jnp.float32)
    g = jnp.zeros(b.shape, jnp.float32)
    for lev in levels:
        lev = jnp.float32(lev)

        def body(state, lev=lev):
            f, g, it, err = state
            live = err > tol
            f2 = jnp.where(va, lev * (log_a - _lse(
                (g[:, None, :] - big) / lev, 2)), _BIG)
            g2 = jnp.where(vb, lev * (log_b - _lse(
                (f2[:, :, None] - big) / lev, 1)), _BIG)
            row = jnp.sum(jnp.exp(
                (f2[:, :, None] + g2[:, None, :] - big) / lev), axis=2)
            err2 = jnp.sum(jnp.abs(row - a), axis=1)
            return (jnp.where(live[:, None], f2, f),
                    jnp.where(live[:, None], g2, g), it + 1,
                    jnp.where(live, err2, err))

        def cond(state):
            return (state[2] < max_iters) & jnp.any(state[3] > tol)

        f, g, _, _ = jax.lax.while_loop(
            cond, body, (f, g, jnp.int32(0),
                         jnp.full(a.shape[:1], jnp.inf, jnp.float32)))
    log_p = (f[:, :, None] + g[:, None, :] - big) / jnp.float32(levels[-1])
    mrow = jnp.max(log_p, axis=2, keepdims=True)
    mrow = jnp.where(jnp.isfinite(mrow), mrow, 0.0)
    plan = jnp.exp(log_p - mrow)
    plan = plan * jnp.where(
        va, a / jnp.maximum(jnp.sum(plan, axis=2), 1e-30), 0.0)[:, :, None]
    return jnp.sum(jnp.where(jnp.isfinite(big), plan * big, 0.0), axis=(1, 2))


@functools.partial(jax.jit, static_argnames=("passes", "mode", "sink"))
def final_values(emb, r_ids, r_w, q_ids, q_w, cand, d1_cand, *, passes: int,
                 mode: str, sink: tuple):
    """Final values (Q, c) of candidate documents ``cand`` (Q, c)."""
    t_q = emb[q_ids]                                   # (Q, h, m)

    def per_query(args):
        tq, wq, cd, d1 = args
        t_r = emb[r_ids[cd]]                           # (c, h_r, m)
        w_r = r_w[cd]
        if mode == "knn":
            sym = jax.vmap(lambda t, w: _rwmd_pair(t, w, tq, wq, passes))(
                t_r, w_r)
            return jnp.maximum(d1, sym)
        cost = jax.vmap(lambda t: pair_dists(t, tq, passes))(t_r)
        return cost, w_r

    if mode == "knn":
        return jax.lax.map(per_query, (t_q, q_w, cand, d1_cand))
    cost, w_r = jax.lax.map(per_query, (t_q, q_w, cand, d1_cand))
    nq, c = cand.shape
    kw = dict(sink)
    vals = sinkhorn_cost(
        w_r.reshape(nq * c, -1), jnp.repeat(q_w, c, axis=0),
        cost.reshape(nq * c, *cost.shape[2:]), **kw)
    return vals.reshape(nq, c)


class Reference:
    """The reference over one corpus: host histograms and the device
    embedding table, both made by the benchmark from the seed."""

    def __init__(self, ids: np.ndarray, weights: np.ndarray, emb, *,
                 k: int, kc: int, mode: str, sink: dict | None = None,
                 chunk: int = 8, vocab_pad: int = 8192):
        used = np.unique(ids[weights > 0])
        # Pad v_e (with repeats of the first word, which no document
        # indexes there) so every seed runs the same compiled programs.
        used_pad = np.pad(used, (0, (-used.size) % vocab_pad), mode="edge")
        self.r_idx = jnp.asarray(np.where(
            weights > 0, np.searchsorted(used, ids), 0).astype(np.int32))
        self.r_ids = jnp.asarray(ids)
        self.r_w = jnp.asarray(weights)
        self.emb = emb
        self.emb_u = emb[jnp.asarray(used_pad)]
        self.k, self.kc, self.mode, self.chunk = k, kc, mode, chunk
        self.sink = tuple(sorted((sink or {}).items()))

    def _d1(self, q_ids, q_w, passes):
        nq = q_ids.shape[0]
        pad = (-nq) % self.chunk
        qi = jnp.pad(jnp.asarray(q_ids), ((0, pad), (0, 0)))
        qw = jnp.pad(jnp.asarray(q_w), ((0, pad), (0, 0)))
        d1 = one_sided(self.emb_u, self.r_idx, self.r_w, self.emb[qi], qw,
                       passes=passes, chunk=self.chunk)
        return d1[:nq]

    def _final(self, q_ids, q_w, cand, d1_cand, passes):
        return final_values(self.emb, self.r_ids, self.r_w,
                            jnp.asarray(q_ids), jnp.asarray(q_w),
                            jnp.asarray(cand), jnp.asarray(d1_cand),
                            passes=passes, mode=self.mode, sink=self.sink)

    def answers(self, q_ids, q_w, passes: int = 6):
        """The reference's own answers (ids, values), each (Q, k)."""
        d1 = self._d1(q_ids, q_w, passes)
        neg, cand = jax.lax.top_k(-d1, self.kc)
        vals = self._final(q_ids, q_w, cand, -neg, passes)
        order = jnp.argsort(vals, axis=1, stable=True)[:, :self.k]
        return (np.asarray(jnp.take_along_axis(cand, order, 1)),
                np.asarray(jnp.take_along_axis(vals, order, 1)))

    def judge(self, q_ids, q_w, served_ids, served_d) -> dict:
        """The numbers compared, over Q queries and their served answers
        (ids and distances, each (Q, k)):

        * ``dist_err``: largest |served distance − reference value of the
          served document| / max(reference value, 1);
        * ``cand_excess``: largest share by which a served document's D1
          exceeds the kc-th smallest D1 (every answer must be a candidate);
        * ``repeats``: answers that name a document twice;
        * ``unsorted``: answers whose distances fall somewhere along them.

        With ``repeats`` 0 and ``cand_excess`` within rounding, the k served
        documents are k distinct candidates: with kc = k, the reference's
        candidate set up to ties.
        """
        served_ids = np.asarray(served_ids, np.int64)
        served_d = np.asarray(served_d, np.float64)
        n = self.r_ids.shape[0]
        bad = (served_ids < 0) | (served_ids >= n)
        safe = jnp.asarray(np.where(bad, 0, served_ids).astype(np.int32))
        d1 = self._d1(q_ids, q_w, 6)
        kth_d1 = np.asarray(-jax.lax.top_k(-d1, self.kc)[0][:, -1], np.float64)
        d1_served = jnp.take_along_axis(d1, safe, 1)
        ref = np.asarray(self._final(q_ids, q_w, safe, d1_served, 6),
                         np.float64)
        d1_served = np.asarray(d1_served, np.float64)
        with np.errstate(invalid="ignore"):
            err = np.abs(served_d - ref) / np.maximum(ref, 1.0)
            err = np.where(np.isfinite(err) & ~bad, err, np.inf)
            cand_x = np.maximum(d1_served - kth_d1[:, None], 0.0) / kth_d1[:, None]
            cand_x = np.where(bad, np.inf, cand_x)
        srt = np.sort(served_ids, axis=1)
        repeats = (srt[:, 1:] == srt[:, :-1]).any(axis=1).sum()
        unsorted = (np.diff(served_d, axis=1) < 0).any(axis=1).sum()
        return {"dist_err": float(np.max(err)),
                "cand_excess": float(np.max(cand_x)),
                "repeats": float(repeats), "unsorted": float(unsorted)}


@functools.partial(jax.jit, static_argnames=("chunk",))
def symmetric_all(emb, r_ids, r_w, q_ids, q_w, *, chunk: int = 1024):
    """Symmetric RWMD (Q, n) of Q queries against every resident document
    (the exact answer that LC-RWMD's candidate stage approximates)."""
    n, h = r_ids.shape
    nq, hq = q_ids.shape
    m = emb.shape[1]
    t_q = emb[q_ids].reshape(-1, m)
    vq = q_w > 0

    def block(args):
        ids, w = args                                  # (c, h)
        d = gram_dists(emb[ids.reshape(-1)], t_q, 6).reshape(
            chunk, h, nq, hq)
        vr = w > 0
        d12 = jnp.sum(jnp.where(vr[:, :, None], w[:, :, None] * jnp.min(
            jnp.where(vq[None, None], d, jnp.inf), axis=3), 0.0), axis=1)
        d21 = jnp.sum(jnp.where(vq[None], q_w[None] * jnp.min(
            jnp.where(vr[:, :, None, None], d, jnp.inf), axis=1), 0.0),
            axis=2)
        return jnp.maximum(d12, d21).T                 # (Q, c)

    out = jax.lax.map(block, (r_ids.reshape(n // chunk, chunk, h),
                              r_w.reshape(n // chunk, chunk, h)))
    return jnp.moveaxis(out, 0, 1).reshape(nq, n)


def recall_at_k(emb, ids, weights, q_ids, q_w, served_ids, k: int,
                chunk: int = 1024) -> float:
    """Share of the exact symmetric-RWMD top k found in the served top k."""
    chunk = int(np.gcd(chunk, ids.shape[0]))
    sym = symmetric_all(emb, jnp.asarray(ids), jnp.asarray(weights),
                        jnp.asarray(q_ids), jnp.asarray(q_w), chunk=chunk)
    top = np.asarray(jax.lax.top_k(-sym, k)[1])
    hits = [len(set(a.tolist()) & set(b.tolist())) / k
            for a, b in zip(np.asarray(served_ids), top)]
    return float(np.mean(hits))
