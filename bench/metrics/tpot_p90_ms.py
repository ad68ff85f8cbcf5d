"""90th percentile, over the requests finished in the window, of (last
token time - first token time) / (output tokens - 1): the time a request
waits for each token after its first (host clock). Chunked stepping emits
up to a chunk of tokens a step at once, so a per-token gap would measure
burst spacing; a per-request time per token does not."""
from bench import latency


def read(run):
    v = latency.tpot_ms(run)
    run.note(f"tpot_p90_ms over {len(v)} requests")
    return latency.p90(v)
