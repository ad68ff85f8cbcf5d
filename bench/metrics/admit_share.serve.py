"""Share of the window the server spent admitting requests into slots: the
sum of ``BatchedServer.last_admit_s`` over the window's steps (the program's
own host-clock span of ``_admit``), over the window."""


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * run.counters["admit_s"] / run.window_s
