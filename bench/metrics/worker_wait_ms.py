"""Mean time the server's worker waits before each batch — for fill,
``max_wait_s`` or a ready batch in flight (ms): Δsum / Δcount of the
program's ``serving_stage_seconds`` for the span ``serve.wait``."""


def read(run):
    s, n = run.counter("serving_stage_seconds{stage=wait}")
    return 1e3 * s / n if n else None
