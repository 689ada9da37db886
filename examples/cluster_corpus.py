"""Corpus analytics end-to-end: cluster a corpus and extract near-duplicates.

The paper's clustering workload (Sec. I) on the centroid-degenerate
synthetic corpus — the regime where WCD is structurally blind but
word-level transport is not:

  1. greedy k-centers seeding + k-medoids refinement over LC-RWMD,
  2. the WCD-only baseline for contrast (paper Fig. 11, clustering edition),
  3. a near-duplicate graph from the same tiled all-pairs scheduler.

    PYTHONPATH=src python examples/cluster_corpus.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import SegmentedEngine
from repro.data.docs import DocSet
from repro.data.synth import CorpusSpec, make_bimodal_corpus
from repro.workloads import (
    adjusted_rand_index,
    corpus_self_topk,
    duplicate_groups,
    kcenters,
    kmedoids,
    kmedoids_wcd_baseline,
    near_duplicate_graph,
    purity,
)


def main():
    corpus = make_bimodal_corpus(CorpusSpec(
        n_docs=256, vocab_size=1024, emb_dim=32, h_max=24, mean_h=16.0,
        n_classes=4, topic_noise=0.1, seed=17))
    # Plant a few exact duplicates for the dedup pass to find.
    ids = np.array(corpus.docs.ids)
    w = np.array(corpus.docs.weights)
    for dst, src in ((3, 200), (4, 200), (9, 150)):
        ids[dst] = ids[src]
        w[dst] = w[src]
    docs = DocSet(ids=jnp.asarray(ids), weights=jnp.asarray(w))
    engine = SegmentedEngine(docs, jnp.asarray(corpus.emb))

    seeds = kcenters(engine, 4)
    print(f"k-centers seeds: {seeds.tolist()} "
          f"(classes {corpus.labels[seeds].tolist()})")

    res = kmedoids(engine, 4, n_iters=8, init=seeds)
    base = kmedoids_wcd_baseline(engine, 4, n_iters=8)
    print("clustering vs true topics (4 classes, chance ARI = 0):")
    print(f"  LC-RWMD k-medoids  ARI {adjusted_rand_index(res.labels, corpus.labels):.3f}"
          f"  purity {purity(res.labels, corpus.labels):.3f}"
          f"  ({res.n_iters} iters)")
    print(f"  WCD baseline       ARI {adjusted_rand_index(base.labels, corpus.labels):.3f}"
          f"  purity {purity(base.labels, corpus.labels):.3f}"
          f"  (centroid-degenerate corpus: WCD is blind by construction)")

    g = near_duplicate_graph(engine, 0.05, tile=64)
    groups = [sorted(gr.tolist()) for gr in duplicate_groups(g)]
    print(f"near-duplicate graph: {g.n_edges} edges at threshold 0.05; "
          f"groups: {groups}")

    tk = corpus_self_topk(engine, 5, tile=64)
    same = np.mean(corpus.labels[np.asarray(tk.indices)]
                   == corpus.labels[:, None])
    print(f"5-NN label agreement across the corpus: {same:.3f}")


if __name__ == "__main__":
    main()
