"""The benchmark's runs end to end on the CPU at fixture sizes: each kind of
cell comes out correct with the contract's result object, and the command
refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from benchfix import REPO, run_tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


@pytest.mark.parametrize("workload", ["tiny_logistic.train",
                                      "tiny_logistic.scan", "tiny_mla.chat",
                                      "tiny_mla.saturate"])
def test_fixture_cell_runs_correct_with_the_result_schema(tiny_root, workload,
                                                          monkeypatch):
    from bench import harness

    res = run_tiny(tiny_root, workload, monkeypatch)
    assert list(res) == KEYS  # "compared" comes last
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    bench = harness.load_benchmark(tiny_root)
    want = harness.cell_metrics(bench, harness.find_cell(bench, workload), False)
    assert set(res["metrics"]) == {m["name"] for m in want}
    for m in want:
        got = res["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    for c in res["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.loads(json.dumps(res))  # one JSON line


@pytest.mark.parametrize("workload,waits", [("tiny_mla.chat", True),
                                            ("tiny_mla.saturate", False)])
def test_chat_window_requests_are_followed_to_their_first_token(
        tiny_root, workload, waits, monkeypatch):
    """A mix with ``first_token_wait_s`` steps the server past the window's
    close until every request due in the window has its first token, and
    counts no token of those steps in the window's; one without the key
    stops at the close."""
    from bench import harness

    box = {}
    make_run = harness.make_run

    def keep(*a, **k):
        box["run"] = make_run(*a, **k)
        return box["run"]

    monkeypatch.setattr(harness, "make_run", keep)
    res = run_tiny(tiny_root, workload, monkeypatch)
    run = box["run"]
    due = [r for r in run.records
           if run.window_t0 <= r["due"] < run.window_t1]
    assert res["attempted"] == len(due) >= 1
    after = run.state["ttft_until"] - run.window_t1
    if waits:
        assert all(r["first"] is not None for r in due)
        assert 0 <= after < 10.0
    else:
        assert after == 0
    window_steps = [t for t, _, _ in run.state["steps"]
                    if run.window_t0 < t <= run.window_t1]
    assert run.counters["steps"] == len(window_steps)


def test_command_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sn_logistic.train",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_command_refuses_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's files
    cannot run a cell."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("data", "out", "dev"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "minicpm3-4b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())
