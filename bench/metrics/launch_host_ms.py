"""Host time of one batch's launches — the query-embedding gather, the
serve step and the refine (ms): the sum of the mean of the program's
``serving_stage_seconds`` for the spans ``serve.gather_queries``,
``serve.step_launch`` and ``serve.refine_launch`` over the window."""

STAGES = ("gather_queries", "step_launch", "refine_launch")


def read(run):
    total = 0.0
    for stage in STAGES:
        s, n = run.counter(f"serving_stage_seconds{{stage={stage}}}")
        if not n:
            return None
        total += s / n
    return 1e3 * total
