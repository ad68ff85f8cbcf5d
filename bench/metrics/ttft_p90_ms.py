"""90th percentile time to first token over every request due in the
window, from its due time (open loop). Where the mix gives
``first_token_wait_s``, each is followed past the window's close to its
first token; one still without it when that wait ends, or at the close
where the mix gives none, counts with its age then (host clock)."""
from bench import latency


def read(run):
    v = latency.ttft_ms(run)
    run.note(f"ttft_p90_ms over {len(v)} requests")
    return latency.p90(v)
