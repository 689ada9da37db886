"""Device time of candidate selection in one execution of the serve step —
the streaming top-k fold of every slab and the cross-shard top-k (ms): the
self time of the step's operations in the named scopes ``topk_fold`` and
``crossshard_topk`` (bench/scopes.py), from the traced window."""

from bench import scopes


def read(run):
    return scopes.scope_ms(run, "topk_fold", "crossshard_topk")
