"""Mean admission-to-dequeue wait of a query in the server's pending queue
(ms): Δsum / Δcount of the program's ``serving_queue_wait_seconds`` over
the window."""


def read(run):
    s, n = run.counter("serving_queue_wait_seconds")
    return 1e3 * s / n if n else None
