"""The WMD rerank's share of its roofline (%): the least time of a batch's
rerank work at the chip's peaks (bench/rerank_work.py: 2·m operations per
real cost cell, 4 per real cell and Sinkhorn iteration; both sides'
embeddings read once), from the program's rerank counters, over
``rerank_device_ms``."""

from bench import rerank_work


def read(run):
    return rerank_work.roofline(run)
