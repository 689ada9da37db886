"""chip_smoke.py rehearsed on the CPU at a tiny size (kernels in interpret
mode): every phase runs and passes its own checks, and ``main`` refuses to
run anywhere but on a TPU."""

import chip_smoke
import pytest

from repro.data.synth import CorpusSpec
from repro.launch.mesh import make_host_mesh

TINY = CorpusSpec(n_docs=192, vocab_size=1024, emb_dim=32, h_max=16,
                  mean_h=8.0, n_classes=4, seed=0)
SMALL = dict(k=4, max_batch=8)


@pytest.fixture(scope="module")
def served():
    return chip_smoke.phase_serve(TINY, make_host_mesh(), n_exact=8,
                                  n_perturbed=8, **SMALL)


def test_phase_serve(served):
    metrics, failed, run, src = served
    assert failed == []
    assert metrics["queries"] == 16 and metrics["self_recall@1"] == 1.0
    assert len(src) == 16 and len(run.answers) == 16


def test_phase_cascade():
    metrics, failed, _, _ = chip_smoke.phase_serve(
        TINY, make_host_mesh(), n_exact=4, n_perturbed=4, rerank_wmd=True,
        **SMALL)
    assert failed == []
    assert metrics["wmd_reranks"] == 8


@pytest.mark.timeout(300)
def test_phase_host_plane(served):
    metrics, failed = chip_smoke.phase_host_plane(
        served[2].corpus, make_host_mesh(), n_payloads=12, workers=2,
        **SMALL)
    assert failed == []
    assert metrics["pooled_equal_in_thread"] == 12


def test_phase_kernels(served):
    metrics, failed = chip_smoke.phase_kernels(
        served[2].corpus, v=256, n=32, b=8, pairs=8, interpret=True)
    assert failed == []
    assert metrics["h"] == TINY.h_max and metrics["m"] == TINY.emb_dim


def test_phase_reference(served):
    metrics, failed = chip_smoke.phase_reference(served[2], k=4)
    assert failed == []
    assert 0.0 < metrics["recall@4"] <= 1.0


def test_errors_are_allclose_with_a_floor():
    rel, excess = chip_smoke.errors([10.1, 0.05, float("nan")],
                                    [10.0, 0.0, 5.0], rtol=1e-3, atol=0.1)
    assert rel == float("inf") and excess == float("inf")
    rel, excess = chip_smoke.errors([10.1, 0.05], [10.0, 0.0],
                                    rtol=1e-3, atol=0.1)
    assert rel == pytest.approx(0.01) and excess == pytest.approx(0.09)
    failed = []
    chip_smoke.check_close("x", [10.1, 0.05], [10.0, 0.0], 1e-3, 0.1, failed)
    assert failed == []
    chip_smoke.check_close("x", [10.2, 0.05], [10.0, 0.0], 1e-3, 0.1, failed)
    assert len(failed) == 1


def test_main_refuses_the_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "'cpu'" in out.err
