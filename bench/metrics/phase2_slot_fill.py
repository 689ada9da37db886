"""Share of the ELL slots that phase 2 gathered which hold a word (%):
Δ of the program's ``serving_phase2_filled_slots_total`` over Δ of its
``serving_phase2_gathered_slots_total`` over the window."""


def read(run):
    gathered, _ = run.counter("serving_phase2_gathered_slots_total")
    filled, _ = run.counter("serving_phase2_filled_slots_total")
    return 100.0 * filled / gathered if gathered else None
