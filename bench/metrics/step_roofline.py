"""The serve step's share of its roofline (%): the least time of the
step's work at the chip's peaks (bench/work.py: phase 1, phase 2 and the
bytes of the resident ELL, the restricted embeddings and Z) over the
step's device time in the traced window.  Batches hold the mean number of
real queries the window dispatched."""

from bench import work

MODULES = ("jit_step",)


def read(run):
    n, t = run.module(*MODULES)
    queries, batches = run.counter("serving_batch_size")
    if not n or not batches or not run.peaks:
        return None
    w = run.work
    per_batch = queries / batches
    flops, nbytes = work.step_work(
        v_e=w["v_e"], m=w["m"], nnz=w["nnz"], n_docs=w["n_docs"],
        h_max=w["h_max"], max_batch=w["max_batch"], queries=per_batch,
        words=per_batch * w["words_per_query"])
    least, _bound = work.roofline_s(flops, nbytes, run.peaks)
    return 100.0 * least * n / t
