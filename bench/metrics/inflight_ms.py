"""Mean time of a batch from dispatch entry to its answers validated (ms):
Δsum / Δcount of the program's ``serving_e2e_latency_seconds`` over the
window."""


def read(run):
    s, n = run.counter("serving_e2e_latency_seconds")
    return 1e3 * s / n if n else None
