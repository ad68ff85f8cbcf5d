"""Output tokens emitted during the window, over the window (host clock)."""


def read(run):
    return run.counters["tokens"] / run.window_s
