"""Cluster-routed serving index: exhaustive-routing bit-parity with the
flat segmented scan (engine, pipeline, and distributed serve), structural
proof that non-routed cells contribute zero phase-2 FLOPs, deterministic
partitions, and the ingest/delete/compact lifecycle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.lc_rwmd import SegmentedEngine
from repro.data.docs import DocSet
from repro.data.synth import CorpusSpec, make_corpus
from repro.index import ClusterIndex, IndexConfig
from repro.launch.mesh import make_host_mesh

K = 8
N_CELLS = 6


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CorpusSpec(
        n_docs=192, vocab_size=512, emb_dim=48, h_max=16, mean_h=8.0,
        n_classes=4, seed=3))


def _slice(docs: DocSet, lo: int, hi: int) -> DocSet:
    return DocSet(ids=docs.ids[lo:hi], weights=docs.weights[lo:hi])


def _concat(a: DocSet, b: DocSet) -> DocSet:
    return DocSet(ids=jnp.concatenate([a.ids, b.ids]),
                  weights=jnp.concatenate([a.weights, b.weights]))


@pytest.fixture(scope="module")
def engine(corpus):
    """160 base docs + an exact duplicate of doc 5 (a genuine tie)."""
    docs = corpus.docs
    base = _concat(_slice(docs, 0, 160), _slice(docs, 5, 6))
    return SegmentedEngine(base, corpus.emb)


@pytest.fixture(scope="module")
def index(engine):
    return ClusterIndex(engine, num_cells=N_CELLS, top_p=N_CELLS,
                        probe_cap=N_CELLS, seed=0)


@pytest.fixture(scope="module")
def queries(corpus):
    return _slice(corpus.docs, 4, 20)   # includes doc 5 = the tie maker


def _assert_topk_bit_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices))


# ---------------------------------------------------------------------------
# Exhaustive-routing bit-parity: engine, pipeline, distributed serve
# ---------------------------------------------------------------------------

def test_exhaustive_routing_bit_parity_engine(engine, index, queries):
    """top_p = num_cells + bound off == flat segmented scan, bit-exact —
    distances AND indices, ties included."""
    _assert_topk_bit_equal(
        index.routed_topk(queries, K, top_p=N_CELLS, bound_slack=None),
        engine.topk(queries, K))


def test_exhaustive_routing_bit_parity_pipeline(corpus, engine, index,
                                                queries):
    """The full cascade (bound stage + routing + rerank) with exhaustive
    routing equals the unrouted cascade bit-exactly."""
    from repro.core.pipeline import pruned_wmd_topk

    kw = dict(k=K, refine_budget=2 * K,
              sinkhorn_kw=dict(eps=0.05, eps_scaling=2, max_iters=60),
              engine=engine)
    flat = pruned_wmd_topk(engine.resident, queries, corpus.emb, **kw)
    routed = pruned_wmd_topk(engine.resident, queries, corpus.emb,
                             index=index, top_p=N_CELLS, **kw)
    _assert_topk_bit_equal(flat.topk, routed.topk)
    _assert_topk_bit_equal(flat.rwmd_topk, routed.rwmd_topk)
    np.testing.assert_array_equal(np.asarray(flat.pruned_exact),
                                  np.asarray(routed.pruned_exact))


def test_exhaustive_routing_bit_parity_distributed_serve(engine, index,
                                                         queries):
    """The compiled routed serve step (refine + WMD rerank) matches the
    flat segmented serve step bit-exactly under exhaustive routing."""
    from repro.distributed.lcrwmd_dist import build_serve_step

    mesh = make_host_mesh()
    kw = dict(k=K, refine=True, bf16_matmul=False, rerank_wmd=True,
              rerank_budget=2 * K)
    r_flat = build_serve_step(mesh, engine=engine, **kw)(queries)
    r_routed = build_serve_step(mesh, engine=engine, index=index,
                                **kw)(queries)
    _assert_topk_bit_equal(r_flat.topk, r_routed.topk)
    np.testing.assert_array_equal(np.asarray(r_flat.pruned_exact),
                                  np.asarray(r_routed.pruned_exact))


def test_partial_routing_high_self_recall(corpus, engine, queries):
    """Self-queries land in their own doc's cell: top_p=2 of 6 keeps the
    exact match in the top-k and overall recall stays high."""
    from repro.distributed.lcrwmd_dist import build_serve_step

    idx = ClusterIndex(engine, num_cells=N_CELLS, top_p=2,
                       probe_cap=N_CELLS, seed=0)
    mesh = make_host_mesh()
    kw = dict(k=K, refine=False, bf16_matmul=False)
    flat = np.asarray(build_serve_step(mesh, engine=engine, **kw)
                      (queries).topk.indices)
    routed = np.asarray(build_serve_step(mesh, engine=engine, index=idx,
                                         **kw)(queries).topk.indices)
    recall = np.mean([len(set(routed[i]) & set(flat[i])) / K
                      for i in range(len(flat))])
    assert recall >= 0.8
    for i, g in enumerate(range(4, 20)):   # query i IS resident doc g
        assert g in routed[i]


# ---------------------------------------------------------------------------
# Structural: non-routed cells contribute zero phase-2 FLOPs
# ---------------------------------------------------------------------------

def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _all_eqns(sub)


def _sub_jaxprs(v):
    if hasattr(v, "eqns"):            # raw Jaxpr
        return [v]
    if hasattr(v, "jaxpr"):           # ClosedJaxpr
        return [v.jaxpr]
    if isinstance(v, (tuple, list)):
        return [j for item in v for j in _sub_jaxprs(item)]
    return []


def _routed_step_jaxpr(p_max, n_cells=4, rows=16, h1=5, v_cap=8, m=7, b=3):
    from repro.distributed.lcrwmd_dist import _routed_step

    mesh = make_host_mesh()
    step = _routed_step(mesh, kc=K, p_max=p_max, rb=8, g=1,
                        n_cells=n_cells, self_exclude=False,
                        bf16_matmul=False, phase1_full_mesh=True)
    args = (jnp.zeros((n_cells, rows, h1), jnp.int32),
            jnp.zeros((n_cells, rows, h1), jnp.float32),
            jnp.zeros((n_cells, rows), bool),
            jnp.zeros((n_cells, rows), jnp.int32),
            jnp.zeros((p_max,), jnp.int32),
            jnp.zeros((b, p_max), bool),
            jnp.zeros((b, h1, m), jnp.float32),
            jnp.zeros((b, h1), jnp.float32),
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((n_cells, v_cap, m), jnp.float32))
    return jax.make_jaxpr(getattr(step, "__wrapped__", step))(*args)


@pytest.mark.parametrize("p_max", [2, 4])
def test_routed_step_flops_scale_with_probed_cells_only(p_max):
    """Structural jaxpr assertion: the compiled routed step's phase-2 work
    is ∝ p_max probe slots — one streaming scan per SLOT, and no matmul
    operand anywhere in the program touches all n_cells · rows rows at
    once (a flat scan would)."""
    n_cells, rows = 4, 16
    jaxpr = _routed_step_jaxpr(p_max, n_cells=n_cells, rows=rows)
    eqns = list(_all_eqns(jaxpr.jaxpr))
    scans = [e for e in eqns if e.primitive.name == "scan"]
    assert len(scans) == p_max        # one phase-2 stream per probe slot
    flat_rows = n_cells * rows        # 64: the would-be flat-scan extent
    for e in eqns:
        if e.primitive.name == "dot_general":
            for var in e.invars:
                assert flat_rows not in getattr(var.aval, "shape", ()), (
                    f"dot_general touches all {flat_rows} stacked rows — "
                    "non-routed cells are leaking phase-2 FLOPs")


# ---------------------------------------------------------------------------
# Deterministic partitions (seeded k-centers / k-medoids end-to-end)
# ---------------------------------------------------------------------------

def test_partition_deterministic_across_rebuilds(engine, index):
    before = index.labels.copy()
    index.rebuild()
    np.testing.assert_array_equal(index.labels, before)
    twin = ClusterIndex(engine, num_cells=N_CELLS, seed=0)
    np.testing.assert_array_equal(twin.labels, before)


def test_partition_seed_flows_to_clustering(engine):
    """Different seeds may pick different partitions; the same seed always
    reproduces — including through the kmedoids path."""
    a = ClusterIndex(engine, num_cells=4, seed=7, method="kmedoids")
    b = ClusterIndex(engine, num_cells=4, seed=7, method="kmedoids")
    np.testing.assert_array_equal(a.labels, b.labels)


# ---------------------------------------------------------------------------
# Lifecycle: ingest, delete, compaction, misuse
# ---------------------------------------------------------------------------

def test_ingest_add_keeps_parity(corpus):
    docs = corpus.docs
    eng = SegmentedEngine(_slice(docs, 0, 128), corpus.emb)
    idx = ClusterIndex(eng, num_cells=4, top_p=4, probe_cap=4, seed=0)
    delta = _slice(docs, 128, 150)
    gids = eng.append(delta)
    assign = idx.add(gids, delta)
    assert assign.shape == (22,)
    queries = _slice(docs, 130, 138)
    _assert_topk_bit_equal(
        idx.routed_topk(queries, K, top_p=4, bound_slack=None),
        eng.topk(queries, K))


def test_delete_honored_without_index_call(corpus):
    docs = corpus.docs
    eng = SegmentedEngine(_slice(docs, 0, 128), corpus.emb)
    idx = ClusterIndex(eng, num_cells=4, top_p=4, probe_cap=4, seed=0)
    target = 17
    queries = _slice(docs, target, target + 1)
    assert target in np.asarray(
        idx.routed_topk(queries, K).indices)[0]
    eng.delete([target])    # no index.add / rebuild
    tk = idx.routed_topk(queries, K)
    assert target not in np.asarray(tk.indices)[0]


def test_unindexed_engine_append_raises(corpus):
    docs = corpus.docs
    eng = SegmentedEngine(_slice(docs, 0, 128), corpus.emb)
    idx = ClusterIndex(eng, num_cells=4, seed=0)
    eng.append(_slice(docs, 128, 132))   # bypasses the index
    with pytest.raises(RuntimeError, match="appended directly"):
        idx.route(_slice(docs, 0, 4))


def test_bound_stage_prunes_and_counts(corpus):
    """With a tight slack on a class-separable corpus the triangle bound
    prunes routed slots; the exact self-match always survives (its own
    cell has lb = 0 ≤ slack · ub_best)."""
    docs = corpus.docs
    eng = SegmentedEngine(_slice(docs, 0, 160), corpus.emb)
    idx = ClusterIndex(eng, num_cells=8, top_p=8, probe_cap=8, seed=0,
                       bound_slack=1.0)
    queries = _slice(docs, 10, 26)
    route = idx.route(queries)
    assert route.n_bound_pruned > 0
    assert route.n_docs_pruned > 0
    tk = idx.routed_topk(queries, K, route=route)
    idxs = np.asarray(tk.indices)
    for i, g in enumerate(range(10, 26)):
        assert g in idxs[i]


# ---------------------------------------------------------------------------
# Serving integration: ServerConfig(index=...) lifecycle
# ---------------------------------------------------------------------------

def test_server_routed_lifecycle(corpus):
    from repro.serving.query_server import QueryServer, ServerConfig

    ids = np.asarray(corpus.docs.ids)
    w = np.asarray(corpus.docs.weights)
    server = QueryServer(
        corpus.docs, corpus.emb, make_host_mesh(),
        ServerConfig(k=5, max_batch=8, h_max=16,
                     index=IndexConfig(num_cells=6, top_p=3, probe_cap=6)))
    picks = np.random.default_rng(0).integers(0, 192, 16)
    answers = list(server.serve_stream([(ids[i], w[i]) for i in picks]))
    hits = [picks[i] in set(a[0].tolist()) for i, a in enumerate(answers)]
    assert np.mean(hits) == 1.0
    # ingest routes new docs to their nearest cells through the manager
    delta = DocSet(ids=corpus.docs.ids[:4], weights=corpus.docs.weights[:4])
    gids, keep = server.ingest(delta)
    assert keep.all()
    st = server._core._active
    assert st.index is not None
    assert st.index.labels.shape[0] == st.engine.n_docs
    # the index's device tensors count toward eviction accounting
    assert st.nbytes > st.engine.nbytes
    # compaction re-partitions deterministically and serving continues
    server.delete_docs([int(gids[0])])
    server.compact()
    a = list(server.serve_stream([(ids[7], w[7])]))
    assert 7 in set(a[0][0].tolist())


def test_index_config_validation():
    with pytest.raises(ValueError):
        IndexConfig(num_cells=0)
    with pytest.raises(ValueError):
        IndexConfig(num_cells=4, top_p=0)
    with pytest.raises(ValueError):
        IndexConfig(num_cells=4, bound_slack=-1.0)
    with pytest.raises(ValueError):
        IndexConfig(num_cells=4, method="voronoi")
