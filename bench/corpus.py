"""Seeded corpus, query and embedding generation for the benchmark cells.

A vectorized copy of the topic-Zipf model of ``repro.data.synth.make_corpus``
(the same semantics, without its Python loop over documents), kept here so
that the yardstick does not move when the program's generator does:

* each of ``n_classes`` topics owns a shuffled slice of the vocabulary;
* a document of length h draws round(h·(1 − topic_noise)) Zipf(zipf_a) ranks
  inside its topic's slice and fills up to h distinct words with words drawn
  uniformly over the whole vocabulary;
* repeated words become counts, the ``h_max`` heaviest words are kept, and
  the weights are L1-normalized;
* word embeddings are a topic centroid (scale ``emb_topic_scale``) plus
  per-word jitter (scale ``emb_word_scale``), made on the device in one
  jitted call.

Queries are fresh documents of the same model, so they share the
vocabulary and the embeddings of the corpus they are sent to.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class TopicModel:
    """The vocabulary side of one seeded corpus."""

    word_topic: np.ndarray    # (v,) int32 topic of each word
    topic_words: np.ndarray   # (v,) int32 word ids grouped by topic
    starts: np.ndarray        # (n_classes,) first slot of each topic
    sizes: np.ndarray         # (n_classes,) words per topic


def topic_model(spec: dict, rng: np.random.Generator) -> TopicModel:
    v, c = int(spec["vocab_size"]), int(spec["n_classes"])
    word_topic = rng.integers(0, c, size=v).astype(np.int32)
    perm = rng.permutation(v).astype(np.int32)
    grouped = perm[np.argsort(word_topic[perm], kind="stable")]
    sizes = np.bincount(word_topic, minlength=c).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)
    return TopicModel(word_topic, grouped, starts, sizes)


def doc_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Poisson(mean_h) lengths clipped to [2, h_max]."""
    return np.clip(rng.poisson(float(spec["mean_h"]), size=n), 2,
                   int(spec["h_max"])).astype(np.int64)


def _first_k_mask(ok: np.ndarray, k: np.ndarray) -> np.ndarray:
    """Per row, keep the first ``k[i]`` True entries of ``ok``."""
    return ok & (np.cumsum(ok, axis=1) <= k[:, None])


def make_docs(spec: dict, tm: TopicModel, lengths: np.ndarray,
              rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """Histograms of ``len(lengths)`` documents: (ids, weights), each
    (n, h_max); padding slots hold id 0 and weight 0, real words come first
    in order of falling count, and every row's weights sum to 1."""
    n = len(lengths)
    v, h_max = int(spec["vocab_size"]), int(spec["h_max"])
    noise = float(spec["topic_noise"])
    labels = rng.integers(0, len(tm.sizes), size=n)
    n_topic = np.maximum(1, np.round(lengths * (1.0 - noise))).astype(np.int64)

    # Zipf ranks inside the document's topic; ranks past the topic's size
    # are rejected and the first n_topic accepted ones are kept.
    draws = rng.zipf(float(spec["zipf_a"]), size=(n, 4 * int(n_topic.max()))) - 1
    ok = draws < tm.sizes[labels][:, None]
    take = _first_k_mask(ok, n_topic)
    none = ~take.any(axis=1)                  # no rank accepted: the topic's
    take[none, 0] = True                      # first word stands in
    draws[none, 0] = 0
    slot = tm.starts[labels][:, None] + np.where(take, draws, 0)
    chosen = np.where(take, tm.topic_words[np.minimum(slot, v - 1)], v)

    # Uniform noise words fill each document up to its length in distinct
    # topic words (collisions with topic words become counts).
    srt = np.sort(chosen, axis=1)
    distinct = ((srt != v) & np.concatenate(
        [np.ones((n, 1), bool), srt[:, 1:] != srt[:, :-1]], axis=1)).sum(1)
    n_noise = np.maximum(lengths - distinct, 0)
    noise_w = rng.integers(0, v, size=(n, int(lengths.max())))
    noise_w = np.where(_first_k_mask(np.ones_like(noise_w, bool), n_noise),
                       noise_w, v)

    words = np.sort(np.concatenate([chosen, noise_w], axis=1), axis=1)
    first = (words != v) & np.concatenate(
        [np.ones((n, 1), bool), words[:, 1:] != words[:, :-1]], axis=1)
    group = np.cumsum(first, axis=1) - 1                 # run index per slot
    width = words.shape[1]
    counts = np.zeros((n, width), np.int64)
    rows = np.broadcast_to(np.arange(n)[:, None], words.shape)
    valid = words != v
    np.add.at(counts, (rows[valid], group[valid]), 1)
    uniq = np.full((n, width), v, np.int64)
    uniq[rows[first], group[first]] = words[first]
    # Heaviest first, ties by word id; empty runs sort last.
    key = np.where(counts > 0, (width + 1 - counts) * (v + 1) + uniq,
                   np.iinfo(np.int64).max)
    order = np.argsort(key, axis=1, kind="stable")[:, :h_max]
    top_c = np.take_along_axis(counts, order, 1).astype(np.float32)
    top_w = np.take_along_axis(uniq, order, 1)
    ids = np.where(top_c > 0, top_w, 0).astype(np.int32)
    weights = top_c / top_c.sum(axis=1, keepdims=True)
    return ids, weights.astype(np.float32)


def make_embeddings(spec: dict, word_topic: np.ndarray, device_seed: int):
    """(vocab_size, emb_dim) float32 embeddings, made on the device."""
    import jax
    import jax.numpy as jnp

    c, m = int(spec["n_classes"]), int(spec["emb_dim"])
    topic_scale = float(spec["emb_topic_scale"])
    word_scale = float(spec["emb_word_scale"])

    @jax.jit
    def build(key, topic):
        k1, k2 = jax.random.split(key)
        cent = topic_scale * jax.random.normal(k1, (c, m), jnp.float32)
        jitter = word_scale * jax.random.normal(
            k2, (topic.shape[0], m), jnp.float32)
        return cent[topic] + jitter

    return build(jax.random.key(device_seed), jnp.asarray(word_topic))


@dataclasses.dataclass(frozen=True)
class Corpus:
    ids: np.ndarray        # (n, h_max) int32
    weights: np.ndarray    # (n, h_max) float32, rows sum to 1
    model: TopicModel
    rng: np.random.Generator   # continues the seeded stream for queries
    device_seed: int


def make_corpus(spec: dict, seed: int) -> Corpus:
    """The resident corpus of ``spec`` for ``seed`` (host arrays only; the
    embeddings come from :func:`make_embeddings` with ``device_seed``)."""
    rng = np.random.default_rng(seed)
    tm = topic_model(spec, rng)
    lengths = doc_lengths(spec, int(spec["n_docs"]), rng)
    ids, weights = make_docs(spec, tm, lengths, rng)
    device_seed = int(rng.integers(0, 2**31 - 1))
    return Corpus(ids, weights, tm, rng, device_seed)


def vocab_in_use(ids: np.ndarray, weights: np.ndarray) -> int:
    """v_e: distinct words that carry weight in the corpus."""
    return int(np.unique(ids[weights > 0]).size)
