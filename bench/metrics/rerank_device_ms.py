"""Device time of one execution of the WMD rerank of a batch's candidates —
gathering their embeddings, the direct-form cost and the batched
log-domain Sinkhorn (ms), from the traced window (bench/rerank_work.py)."""

from bench import rerank_work


def read(run):
    return rerank_work.device_ms(run)
