"""Profiler trace -> device busy and idle time, per-kernel device time, and the
longest idle gaps labelled by the benchmark's host span open at the time.

``WindowTracer`` records one traced span of a run's window with the JAX
profiler; ``load_xplane`` turns the profiler's ``.xplane.pb`` into a plain
dict of events; ``reduce`` computes the numbers from that dict alone, so a
small recorded trace in the same form checks the reduction without a chip.

Plain form: ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, dur_ns, op_path], ...]}]}]}``. On a TPU a device op's
name is its HLO instruction (``%strider_decode.1 = f32[...] custom-call(...)``)
and a Pallas kernel's instruction carries the kernel's name; ``op_path`` is
the op's framework name (the ``tf_op`` / ``long_name`` stat) where the trace
has one.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import shutil

WINDOW_SPAN = "bench.window"
# host spans the benchmark opens around its calls into the program
HOST_SPANS = ("Session.sql", "BatchedServer.submit", "BatchedServer.step",
              WINDOW_SPAN)
OP_LINES = ("XLA Ops",)
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OP_STATS = ("tf_op", "long_name", "hlo_op")


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = []
            for e in line.events:
                op_path = ""
                if _DEVICE_PLANE.match(plane.name):
                    stats = dict(e.stats)
                    op_path = " ".join(str(stats[k]) for k in _OP_STATS
                                       if k in stats)
                evs.append([e.name, int(e.start_ns), int(e.duration_ns),
                            op_path])
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_name(name: str) -> str:
    """``%glm_grad.6 = f32[1,2048] custom-call(...)`` -> ``glm_grad``,
    ``fusion.12`` -> ``fusion``: the instruction's own name without its
    number, never the names of its operands."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head) or head


def _self_ns(ivs):
    """Each op's own time: its span less the ops nested in it (a ``while``
    holds its body's ops on the same line). Sorts ``ivs`` in place."""
    ivs.sort(key=lambda t: (t[0], -t[1]))
    own = [e - s for s, e, _, _ in ivs]
    stack = []
    for i, (s, e, _, _) in enumerate(ivs):
        while stack and ivs[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ivs[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [max(0, o) for o in own]


def _gap_labels(events, gaps):
    """For each idle gap (by its midpoint), what the host thread that runs
    the benchmark was doing: the innermost benchmark span open then, and
    inside it the innermost other event of that thread, as ``span/event``.
    ``events`` are ``(start, end, name)`` of that thread, nested as one
    thread's events are."""
    events = sorted(events)
    out = collections.Counter()
    stack, i = [], 0
    for mid, g0, g1 in sorted(((g0 + g1) // 2, g0, g1) for g0, g1 in gaps):
        while i < len(events) and events[i][0] <= mid:
            while stack and stack[-1][1] <= events[i][0]:
                stack.pop()
            stack.append(events[i])
            i += 1
        open_ = [ev for ev in stack if ev[1] > mid]
        spans = [ev for ev in open_ if ev[2] in HOST_SPANS]
        if not spans:
            label = "no benchmark span"
        else:
            label = spans[-1][2]
            if open_[-1] is not spans[-1]:
                label += "/" + open_[-1][2]
        out[label] += g1 - g0
    return out


def reduce(trace: dict, kernels=(), top: int = 10) -> dict:
    """Busy/idle over the ``bench.window`` span, per-kernel device seconds
    (ops whose own name, or op path, is the kernel's name), the device ops
    that took most time (their own time, nested ops taken out), and idle time by what the benchmark's host thread
    was doing in each gap (see ``_gap_labels``). Seconds are per chip
    (averaged over the devices)."""
    host_lines = [ln for p in trace["planes"]
                  if not _DEVICE_PLANE.match(p["name"]) for ln in p["lines"]]
    windows = [(s, s + d) for ln in host_lines for n, s, d, _ in ln["events"]
               if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    thread = [(s, s + d, n) for ln in host_lines
              if any(ev[0] in HOST_SPANS for ev in ln["events"])
              for n, s, d, _ in ln["events"] if n != WINDOW_SPAN]

    devices = [p for p in trace["planes"] if _DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("trace has no TPU device plane")
    busy_ns = 0
    kernel_ns = collections.Counter()
    op_ns = collections.Counter()
    gaps = []
    for dev in devices:
        ops = [ev for ln in dev["lines"] if ln["name"] in OP_LINES
               for ev in ln["events"]]
        ivs = []
        for name, s, d, op_path in ops:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 > s0:
                ivs.append((s0, e0, name, op_path))
        for (s0, e0, name, op_path), own in zip(ivs, _self_ns(ivs)):
            op = op_name(name)
            op_ns[op] += own
            for k in kernels:
                if op == k or k in op_path.split("/"):
                    kernel_ns[k] += e0 - s0
        ivs = [(s0, e0) for s0, e0, _, _ in ivs]
        merged = _union(ivs)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g0, g1))
    n_dev = len(devices)
    idle = _gap_labels(thread, gaps)

    window_s = (w1 - w0) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_ns / n_dev / 1e9,
        "devices": n_dev,
        "kernel_s": {k: kernel_ns[k] / n_dev / 1e9 for k in kernels},
        "breakdown": {
            "device_ops": [[k, v / n_dev / 1e9]
                           for k, v in op_ns.most_common(top)],
            "idle_gaps": [[k, v / n_dev / 1e9]
                          for k, v in idle.most_common(top)],
        },
    }


class WindowTracer:
    """Traces one span of a run's window into ``bench/out/trace/<cell>``.
    Python function tracing is off: only the profiler's own host events, the
    benchmark's spans and the device's ops are recorded."""

    def __init__(self, run, kernels=()):
        self.run = run
        self.kernels = tuple(kernels)
        self.dir = run.out_dir("trace", run.cell["name"])
        shutil.rmtree(self.dir, ignore_errors=True)
        self._ann = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()

    def stop(self):
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no profiler trace under {self.dir}")
        red = reduce(load_xplane(paths[0]), self.kernels)
        shutil.rmtree(self.dir, ignore_errors=True)
        return red
