#!/usr/bin/env python3
"""Find a serving cell's knee once, by a sweep of offered rates in one
process: for each rate, the cell's mix at that rate (its ramp, then a window
of ``--seconds``), and the queue of waiting requests at the window's start
and end. The knee is the highest rate whose queue does not grow over the
window. The benchmark's own runs never run this.

    python3 bench/sweep.py --workload <serving cell> --rates 2,4,6 --seed 1
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="requests/s, comma-separated")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    from bench import harness, latency

    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    harness.ensure_program_on_path()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("sweep: no TPU; nothing was run", file=sys.stderr)
        return 2
    for rate in (float(r) for r in args.rates.split(",")):
        run = harness.make_run(ROOT, cell, args.seed, args.seconds, False,
                               T_PROCESS, dev.device_kind)
        run.traffic = dict(run.traffic, rate_per_s=rate)
        run.system.setup(run)
        run.window_t0 = time.perf_counter()
        run.system.window(run, None)
        c = run.counters
        print(json.dumps({
            "rate_per_s": rate,
            "queued": [c["queued_0"], c["queued_1"]],
            "running": [c["running_0"], c["running_1"]],
            "out_tok_per_s": c["tokens"] / run.window_s,
            "ttft_p90_ms": latency.p90(latency.ttft_ms(run)),
            "tpot_p90_ms": latency.p90(latency.tpot_ms(run)),
            "due": c["due"], "steps": c["steps"],
            "step_s": run.window_s / max(1, c["steps"]),
        }), flush=True)
        run.system.release(run)
        run.state.clear()
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
