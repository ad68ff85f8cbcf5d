"""Share of its roofline the strider decode kernel reached in the traced
window: least time / kernel device time. The least time reads, at HBM
bandwidth, the pages each statement scans, once (``page_bytes`` of the
configuration's reference). Only the pages are counted: they arrive from
the host in HBM, while the compiler keeps the kernel's decoded tuples in
VMEM (``S(1)`` in the trace), so writing them bounds nothing. The count
comes from the statements, not from the kernel's implementation: its
slot-major output and the transpose after it are not counted."""

KERNEL = "strider_decode"


def read(run):
    t = run.trace_red
    if t is None or not t["kernel_s"].get(KERNEL):
        return None
    tuples = sum(r["tuples"] for r in run.records if "error" not in r)
    least = run.ref.page_bytes(run.config, tuples) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t["kernel_s"][KERNEL]
