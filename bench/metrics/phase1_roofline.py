"""Phase 1's share of its roofline (%): the least time of phase 1's work
at the chip's peaks (bench/scopes.py ``phase_work``: 2·v_e·m operations
per real query word; the restricted embeddings read and Z written) over
``phase1_device_ms``."""

from bench import scopes


def read(run):
    return scopes.phase_roofline(run, "phase1")
