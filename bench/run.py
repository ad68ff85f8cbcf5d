#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` (see ``bench/harness.py``). Set-up (loading, compiling or
loading compiled programs, weights, the ramp of the load) runs from process
start to the window; the window measures for ``--seconds``; the outputs of
the window are then checked against the configuration's plain reference.
``--trace 1`` records the window with the profiler and reports the cell's
per-layer metrics instead of its end-to-end ones.

Exits non-zero, printing no result, unless JAX's devices are TPUs, as many
as the cell asks for. The last line of standard output is the result object;
the numbers the check compared, each with its limit, are the last lines of
standard error.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the checkout root, not this directory, goes on the path: bench/trace.py
# would otherwise shadow the standard library's ``trace``
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    harness.ensure_program_on_path()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX's first device is {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {cell['name']} needs {cell['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    result = harness.run_cell(ROOT, bench, cell, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS,
                              devices[0].device_kind)
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
