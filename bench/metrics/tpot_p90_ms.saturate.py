"""90th percentile time per output token in a cell offered more than the
server sustains: the same arithmetic as ``tpot_p90_ms`` (requests finished
in the window). Recorded above the knee, not judged (host clock)."""
from bench import latency


def read(run):
    return latency.p90(latency.tpot_ms(run))
