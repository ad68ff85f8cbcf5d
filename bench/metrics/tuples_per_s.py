"""Tuples consumed by the statements of the window (TRAIN: every epoch over
the table; PREDICT and aggregates: every scanned row), over the window
(host clock). The window closes at the end of the first whole cycle of the
mix's statements after ``--seconds``, so it holds whole statements, each
statement of the mix equally often, and all of their time."""


def read(run):
    return run.counters["tuples"] / run.window_s
