"""Phase 2's share of its roofline (%): the least time of phase 2's work
at the chip's peaks (bench/scopes.py ``phase_work``: 2·nnz operations per
real query; the resident ELL and Z read) over ``phase2_device_ms``."""

from bench import scopes


def read(run):
    return scopes.phase_roofline(run, "phase2")
