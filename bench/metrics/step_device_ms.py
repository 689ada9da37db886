"""Device time of one execution of the serve step — phase 1, phase 2 and
the streaming and cross-shard top-k, one compiled program (ms), from the
traced window."""

#: The serve step's compiled program, as the device trace names it.
MODULES = ("jit_step",)


def read(run):
    n, t = run.module(*MODULES)
    return 1e3 * t / n if n else None
