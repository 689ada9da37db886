"""The control of a configuration on the chip: the reference at lower
precision, put in the program's place, judged like a run.

    python3 bench/control.py --config set2_knn --seeds 11,12,13

For each seed: the cell's corpus and embeddings, ``check.sample`` fresh
queries from the corpus's topic model, then the reference's own answers at
3 bfloat16 passes (``high``) and at one pass (bfloat16), each judged
against the float32 reference with the numbers that decide ``correct``.
One line per seed and precision; no program state is built.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    from bench import corpus as corpus_lib
    from bench import harness
    from bench.reference import Reference

    spec_all = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in spec_all["configs"]}[args.config]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    harness.require_chips(1)
    harness.use_compile_cache()
    spec = harness.corpus_spec(cfg)
    s = cfg["server"]
    kc = 2 * s["k"] if s.get("rerank_wmd") else s["k"]
    for seed in (int(x) for x in args.seeds.split(",")):
        corpus = corpus_lib.make_corpus(spec, seed)
        emb = corpus_lib.make_embeddings(spec, corpus.model.word_topic,
                                         corpus.device_seed)
        n = int(cfg["check"]["sample"])
        qi, qw = corpus_lib.make_docs(
            spec, corpus.model, corpus_lib.doc_lengths(spec, n, corpus.rng),
            corpus.rng)
        ref = Reference(corpus.ids, corpus.weights, emb, k=s["k"], kc=kc,
                        vocab_pad=s["vocab_pad"],
                        mode="wmd" if s.get("rerank_wmd") else "knn",
                        sink=s.get("wmd_kw"))
        for passes in (6, 3, 1):
            t = time.perf_counter()
            ids, d = ref.answers(qi, qw, passes=passes)
            nums = ref.judge(qi, qw, ids, d)
            ok, _ = harness.limits_hold(nums, cfg["check"]["limits"])
            print(f"[control] config={args.config} seed={seed} "
                  f"passes={passes} correct={ok} {json.dumps(nums)} "
                  f"s={time.perf_counter() - t:.2f}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
