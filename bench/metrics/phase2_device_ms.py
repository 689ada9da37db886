"""Device time of phase 2 in one execution of the serve step — the ELL
gather-SpMM of every resident slab, its psum and masks, and the slab loop
(ms): the self time of the step's operations in the named scope ``phase2``
(bench/scopes.py), from the traced window."""

from bench import scopes


def read(run):
    return scopes.scope_ms(run, "phase2")
