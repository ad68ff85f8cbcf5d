"""Shared arithmetic of the serving latency metrics (host clock)."""
from statistics import quantiles


def p90(values):
    """90th percentile (``statistics.quantiles``, exclusive method)."""
    if len(values) < 2:
        return values[0] if values else None
    return quantiles(values, n=10)[8]


def ttft_ms(run):
    """Each request due in the window: its due time to its first token, or,
    when none came, to the time first tokens were awaited until: the
    window's end, or past it where the mix waits for them
    (``first_token_wait_s``)."""
    until = getattr(run, "state", {}).get("ttft_until", run.window_t1)
    out = []
    for r in run.records:
        if run.window_t0 <= r["due"] < run.window_t1:
            end = r["first"] if r["first"] is not None else until
            out.append(1e3 * (min(end, until) - r["due"]))
    return out


def tpot_ms(run):
    """Each request finished in the window with two tokens or more: the time
    from its first to its last token over its tokens after the first."""
    out = []
    for r in run.records:
        if (r["done"] is not None and run.window_t0 <= r["done"] <= run.window_t1
                and "error" not in r and r["n_seen"] >= 2):
            out.append(1e3 * (r["last"] - r["first"]) / (r["n_seen"] - 1))
    return out
