"""Share of its roofline the paged-attention kernel reached in the traced
span: for every useful pass in the span (see mfu.serve) and every layer,
the FLOPs and bytes the absorbed-latent attention of one token at its
position needs (``attention_counts`` of the configuration's reference:
the latent and rope rows it attends, read once in bfloat16, its query and
output), counted from positions and not from the kernel's block walk; the
larger of the FLOP and byte bounds over the kernel's device time."""
import numpy as np

KERNEL = "paged_attention"


def counts(run) -> tuple[float, float]:
    st = run.state
    p0, p1 = st.get("trace_passes_0"), st.get("trace_passes_1")
    if p0 is None or p1 is None:
        return 0.0, 0.0
    layers = run.config["num_hidden_layers"]
    flops = nbytes = 0.0
    for r in run.records:
        a, b = p0.get(id(r), 0), p1.get(id(r), 0)
        if b > a:
            f, m = run.ref.attention_counts(run.config, np.arange(a, b))
            flops += layers * float(np.sum(f))
            nbytes += layers * float(np.sum(m))
    return flops, nbytes


def read(run):
    t = run.trace_red
    if t is None or not t["kernel_s"].get(KERNEL):
        return None
    flops, nbytes = counts(run)
    least = max(flops / run.peaks["flops_bf16"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t["kernel_s"][KERNEL]
