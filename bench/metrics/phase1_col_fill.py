"""Share of the query columns that phase 1 computed which hold a word (%):
Δ of the program's ``serving_phase1_word_cols_total`` over Δ of its
``serving_phase1_computed_cols_total`` over the window."""


def read(run):
    computed, _ = run.counter("serving_phase1_computed_cols_total")
    words, _ = run.counter("serving_phase1_word_cols_total")
    return 100.0 * words / computed if computed else None
