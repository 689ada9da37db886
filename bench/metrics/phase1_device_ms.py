"""Device time of phase 1 in one execution of the serve step — the
(v_e, B·h) distance GEMM and its min over h (ms): the self time of the
step's operations in the named scope ``phase1`` (bench/scopes.py), from the
traced window."""

from bench import scopes


def read(run):
    return scopes.scope_ms(run, "phase1")
