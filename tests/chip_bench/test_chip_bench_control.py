"""The control of each kind of cell: the plain reference put in the program's
place at the precision below the configuration's (float32 at three bfloat16
passes for the analytics cells, float8 weights for the served model) reads
well above what the program reads, on three seeds, and comes out not correct
under the cell's own limits, through the same readings and comparison
``bench/calibrate.py`` takes on the chip at the cells' own sizes."""
import time

import pytest

from benchfix import REPO  # noqa: F401  (puts the checkout on the path)

CASES = {
    "tiny_logistic.train": "train_w_rel",
    "tiny_logistic.scan": "pred_abs",
    "tiny_mla.chat": "served_gap",
    "tiny_mla.saturate": "served_gap",
}


@pytest.mark.parametrize("workload", sorted(CASES))
def test_control_reads_well_above_the_program(tiny_root, monkeypatch, workload):
    from bench import calibrate, harness
    from repro.launch import common

    monkeypatch.setattr(common, "enable_compile_cache", lambda: "")
    bench = harness.load_benchmark(tiny_root)
    cell = harness.find_cell(bench, workload)
    run = harness.make_run(tiny_root, cell, 11, 1.0, False, time.perf_counter(),
                           "cpu")
    kind = (calibrate.analytics if run.config["system"] == "analytics"
            else calibrate.serving)
    rows = list(kind(run, [11, 12, 13]))
    number = CASES[workload]
    program = max(r["program"][number] for r in rows)
    control = min(r["control"][number] for r in rows)
    assert control > 0 and control >= 3 * program, rows
    assert all(r["program_correct"] for r in rows), rows
    assert not any(r["control_correct"] for r in rows), rows
