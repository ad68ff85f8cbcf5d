"""90th percentile time to first token over every request due in the
window, from its due time (open loop); a request without a first token by
the window's end counts with its age then (host clock)."""
from bench import latency


def read(run):
    v = latency.ttft_ms(run)
    run.note(f"ttft_p90_ms over {len(v)} requests")
    return latency.p90(v)
