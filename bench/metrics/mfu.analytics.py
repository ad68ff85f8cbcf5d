"""The whole chunk work's share of the chip's peak over the window: the
least time the window's statements need at the v5e's peaks, over the window
(host clock). Least time is the larger of the GLM FLOPs over the bf16 peak
and the pages scanned, read once from HBM, over HBM bandwidth (the decoded
tuples stay in VMEM; see strider_decode.roofline); both counted by the
configuration's reference. For these GLMs the byte bound binds."""


def read(run):
    if run.window_s <= 0:
        return None
    ok = [r for r in run.records if "error" not in r]
    flops = sum(run.ref.glm_flops(run.config, r["verb"], r["tuples"]) for r in ok)
    nbytes = run.ref.page_bytes(run.config, sum(r["tuples"] for r in ok))
    least = max(flops / run.peaks["flops_bf16"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / run.window_s
