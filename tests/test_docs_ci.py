"""Docs are load-bearing: link checker + quickstart extraction (the CI docs
job executes the quickstart itself; the slow marker covers it here)."""

import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import check_docs  # noqa: E402


def test_repo_docs_links_are_valid():
    assert check_docs.check_links() == []


def test_link_checker_catches_breakage(tmp_path, monkeypatch):
    bad = tmp_path / "BAD.md"
    bad.write_text("see [missing](no/such/file.md) and "
                   "[anchor](#nonexistent-heading)\n\n# Real Heading\n")
    monkeypatch.setattr(check_docs, "REPO", tmp_path)
    errors = check_docs.check_links(("BAD.md",))
    assert len(errors) == 2
    assert any("no/such/file.md" in e for e in errors)
    assert any("nonexistent-heading" in e for e in errors)


def test_github_anchor_slugging():
    assert check_docs.github_anchor(
        "## §Serving — async double-buffered pipeline (`serving/query_server.py`)"
    ) == "serving--async-double-buffered-pipeline-servingquery_serverpy"


def test_quickstart_extraction():
    code = check_docs.extract_quickstart()
    assert "SegmentedEngine" in code
    assert "rerank_topk" in code
    compile(code, "<readme-quickstart>", "exec")  # must at least parse


@pytest.mark.slow
def test_quickstart_executes():
    r = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_docs.py"),
         "--quickstart"],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
