"""The served work's share of the chip's bf16 peak over the window: the
model FLOPs of every useful forward pass the window's steps made (prompt
positions fed, and each served token's position; never idle slots,
padding, or positions past a request's last token), from the
configuration's shapes with attention over each pass's actual positions
(``flops_per_pass`` of the configuration's reference), over the window
(host clock) and the peak."""
import numpy as np


def window_flops(run) -> float:
    p0, p1 = run.state["passes_0"], run.state["passes_1"]
    total = 0.0
    for r in run.records:
        a, b = p0.get(id(r), 0), p1.get(id(r), 0)
        if b > a:
            total += float(np.sum(run.ref.flops_per_pass(run.config,
                                                          np.arange(a, b))))
    return total


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * window_flops(run) / run.window_s / run.peaks["flops_bf16"]
