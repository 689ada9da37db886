"""Word Centroid Distance (paper Sec. III) — the cheap, loose lower bound.

Centroid of a histogram = weighted average of its word embeddings
(``X[i] @ E`` in the paper's notation); WCD between two docs is the Euclidean
distance between centroids.  O(nhm) to build all centroids, O(n²m) for all
pairs — fast but a poor WMD approximation (paper Fig. 11), used as the first
stage of the pruning cascade.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.distances import dists
from repro.data.docs import DocSet

Array = jax.Array


def centroids(ds: DocSet, emb: Array) -> Array:
    """(n, m) f32 weighted-average embeddings (weights are L1-normalized)."""
    return centroids_from_t(ds.weights, emb[ds.ids])


def centroids_from_t(weights: Array, t: Array) -> Array:
    """Centroids from PRE-GATHERED word embeddings t (n, h, m), w (n, h).

    The engine-friendly variant: callers that already hold the resident
    word embeddings (``emb_full[ids]``) skip a second ``emb[ids]`` gather
    (used by the k-medoids WCD prefilter in repro.workloads.clustering).
    """
    return jnp.einsum("nh,nhm->nm", weights, t)


def wcd_many_vs_many(set1: DocSet, set2: DocSet, emb: Array) -> Array:
    """(n1, n2) f32 centroid distances."""
    return dists(centroids(set1, emb), centroids(set2, emb))


def wcd_one_vs_many(resident: DocSet, q_ids: Array, q_w: Array, emb: Array) -> Array:
    c1 = centroids(resident, emb)  # (n, m)
    c2 = jnp.einsum("h,hm->m", q_w, emb[q_ids])  # (m,)
    return dists(c1, c2[None, :])[:, 0]
