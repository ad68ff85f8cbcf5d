#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers on many seeds and
the control's, in one process. The benchmark's own runs never run this.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds S]

Analytics cells: set-up once, then for each seed every statement of the mix
once, compared with the reference at float32 (``highest``); and the
control, the reference at three bfloat16 passes (``bf16x3``, the TPU's
HIGH), compared in the program's place. Serving cells:
for each seed new weights, a new server, the mix's ramp and a window of
``--seconds`` at the cell's own load, then the served tokens' gap and the
control's gap (the reference with float8 weights). One JSON line per seed,
with the verdict of the cell's own comparison on the program's readings and
on the control's.
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


def verdict(run, readings: dict) -> bool:
    """Whether readings pass the cell's check: each through ``run.compare``
    with the system's limits, as a run's check puts them."""
    run.compared.clear()
    for name, v in readings.items():
        run.compare(name, v, run.system.LIMITS[name])
    return run.correct


def _row(run, seed, program: dict, control: dict, **extra) -> dict:
    return dict({"seed": seed, "program": program, "control": control,
                 "program_correct": verdict(run, program),
                 "control_correct": verdict(run, control)}, **extra)


def analytics(run, seeds):
    from bench import traffic

    drv = run.system
    drv.setup(run)
    fill = run.state["fill"]
    texts = [t.format(**fill) for t in run.traffic["statements"]]
    for seed in seeds:
        run.seed = seed
        run.records.clear()
        for k, text in enumerate(texts):
            drv.run_statement(run, k, text, traffic.statement_seed(seed, k))
        yield _row(run, seed, drv.readings(run),
                   drv.readings(run, control="bf16x3"))


def serving(run, seeds):
    drv = run.system
    for seed in seeds:
        run.seed = seed
        run.records.clear()
        run.state.clear()
        drv.setup(run)
        run.window_t0 = time.perf_counter()
        drv.window(run, None)
        drv.release(run)
        gc.collect()
        got = drv.readings(run, control=True)
        yield _row(run, seed, {"served_gap": got["served_gap"]},
                   {"served_gap": got["control_gap"]},
                   served_tokens=got["served_tokens"],
                   tokens_per_s=run.counters["tokens"] / run.window_s)
        run.state.clear()
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from bench import harness

    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    harness.ensure_program_on_path()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("calibrate: no TPU; nothing was run", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    run = harness.make_run(ROOT, cell, seeds[0], args.seconds, False,
                           T_PROCESS, dev.device_kind)
    kind = analytics if run.config["system"] == "analytics" else serving
    for row in kind(run, seeds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
