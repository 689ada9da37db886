"""Device time of one execution of the symmetric-RWMD refine of a batch's
candidates (ms), from the traced window."""

#: The refine's compiled program, as the device trace names it.
MODULES = ("jit__symmetric_refine",)


def read(run):
    n, t = run.module(*MODULES)
    return 1e3 * t / n if n else None
