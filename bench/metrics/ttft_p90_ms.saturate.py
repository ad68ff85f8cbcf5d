"""90th percentile time to first token in a cell offered more than the
server sustains: the same arithmetic as ``ttft_p90_ms`` (every request due
in the window, from its due time). Above the knee the queue grows all
through the window, so the tail swings with the smallest change and is
recorded, not judged (host clock)."""
from bench import latency


def read(run):
    return latency.p90(latency.ttft_ms(run))
