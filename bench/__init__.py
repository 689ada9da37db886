"""On-chip benchmark of the LC-RWMD search service (see PERF.md)."""
