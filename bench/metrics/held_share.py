"""Share of the dispatched batches that the server's worker formed after a
hold for the batch in flight (%): Δ of the program's
``serving_batches_held_total`` over Δ of its ``serving_batches_total``
over the window.  None where the program has no such counter."""

HELD = "serving_batches_held_total"


def read(run):
    if HELD not in run.counters:
        return None
    batches, _ = run.counter("serving_batches_total")
    held, _ = run.counter(HELD)
    return 100.0 * held / batches if batches else None
