"""Share of the window the statements spent blocked on the buffer pool's
page fetch: the sum of the program's ``exposed_io_s`` (host clock around
``PrefetchHandle.result()``) over the window's statements, over the window."""


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * run.counters["exposed_io_s"] / run.window_s
