"""Mean host time of one batch dispatch — pad, query gather and serve-step
launch (ms): Δsum / Δcount of the program's
``serving_dispatch_host_seconds`` over the window."""


def read(run):
    s, n = run.counter("serving_dispatch_host_seconds")
    return 1e3 * s / n if n else None
