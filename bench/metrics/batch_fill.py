"""Mean share of a dispatched batch's slots that hold real queries (%):
Δsum / Δcount of the program's ``serving_batch_size`` over the window,
divided by ``max_batch``."""


def read(run):
    s, n = run.counter("serving_batch_size")
    mb = run.cell.config["server"]["max_batch"]
    return 100.0 * s / n / mb if n else None
