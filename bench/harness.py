"""The benchmark harness: one run of one cell, driven by the files that
``BENCHMARK.json`` names.

Everything that belongs to one configuration, one traffic mix or one metric
lives in a file of its own and is found by its name:

  bench/configs/<config>.json   the deployment's sizes and guarantees; its
                                ``system`` key names the code that drives it,
                                its ``reference`` key the plain reference
                                module beside it (bench/configs/<ref>.py)
  bench/traffic/<mix>.json      the mix's parameters, read by bench/traffic.py
  bench/systems/<system>.py     set-up, window and check for one kind of
                                system (the analytics SQL path, the LM server)
  bench/metrics/<metric>.py     ``read(run) -> float | None`` for one metric
  bench/peaks.json              published peaks keyed by ``device_kind``

A system module exposes ``setup(run)``, ``window(run)`` and ``check(run)``.
``run`` is the :class:`Run` record below: the system modules fill it (timeline,
counters, per-request or per-statement records) and the metric readers read
it. The harness owns the clock of the window, the profiler trace and the
result line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CODE_ROOT = os.path.dirname(BENCH_DIR)

CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def code_path(*parts) -> str:
    return os.path.join(CODE_ROOT, *parts)


def ensure_program_on_path() -> None:
    """The system under test is the checkout's ``src/`` package."""
    src = code_path("src")
    if src not in sys.path:
        sys.path.insert(0, src)


# -- discovery by name --------------------------------------------------------
def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(root: str, *parts) -> dict:
    with open(os.path.join(root, "bench", *parts)) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold '.' and '-')."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    mod_name = "bench_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    names = ", ".join(c["name"] for c in bench["workloads"])
    raise KeyError(f"unknown workload {workload!r} (cells: {names})")


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list[dict]:
    """The metrics this cell reports: its end-to-end metrics untraced, its
    per-layer metrics traced. A metric without ``workloads`` belongs to every
    cell that reports the end-to-end metric it moves."""
    name = cell["name"]

    def has(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if has(m)]
    if not trace:
        return e2e
    e2e_names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_names)]


def load_peaks(root: str, device_kind: str) -> dict:
    table = load_json(root, "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return table["devices"][device_kind]


class CompileCounter:
    """Programs compiled (persistent-cache misses) and programs loaded from
    the cache since the counter was made."""

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == CACHE_REQUEST:
            self.requests += 1
        elif event == CACHE_HIT:
            self.hits += 1

    @property
    def compiled(self) -> int:
        return self.requests - self.hits


# -- the run record -------------------------------------------------------------
@dataclasses.dataclass
class Run:
    root: str
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float  # perf_counter at process start
    peaks: dict
    ref: object = None  # the configuration's plain reference module
    system: object = None
    setup_s: float = 0.0
    window_t0: float = 0.0
    window_t1: float = 0.0
    counters: dict = dataclasses.field(default_factory=dict)
    records: list = dataclasses.field(default_factory=list)
    trace_red: dict | None = None
    compared: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    state: dict = dataclasses.field(default_factory=dict)  # system-private
    compiles: CompileCounter | None = None

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0

    def out_dir(self, *parts) -> str:
        d = os.path.join(self.root, "bench", "out", *parts)
        os.makedirs(d, exist_ok=True)
        return d

    def data_dir(self, *parts) -> str:
        d = os.path.join(self.root, "bench", "data", *parts)
        os.makedirs(d, exist_ok=True)
        return d

    def note(self, msg: str) -> None:
        """An earlier line of output (never the result line)."""
        print(msg, flush=True)

    def compare(self, name: str, value: float, limit: float) -> None:
        """Record one number compared with its limit (``value <= limit``
        passes)."""
        self.compared[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(
            c["value"] <= c["limit"] for c in self.compared.values())


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def make_run(root: str, cell: dict, seed: int, seconds: float, trace: bool,
             t_process: float, device_kind: str) -> Run:
    """The run record of one cell with its configuration, mix, reference and
    system module loaded by name, and the compile cache on."""
    ensure_program_on_path()
    import jax

    config = load_json(root, "configs", cell["config"] + ".json")
    traffic = load_json(root, "traffic", cell["traffic"] + ".json")
    run = Run(root=root, cell=cell, config=config, traffic=traffic,
              seed=int(seed), seconds=float(seconds), trace=bool(trace),
              t_process=t_process, peaks=load_peaks(root, device_kind))
    run.ref = load_module(root, "configs", config["reference"])
    run.system = load_module(root, "systems", config["system"])
    run.compiles = CompileCounter()
    # persistent compile cache at the checkout's fixed path: every program
    # this cell runs is found there after the cell's first run
    from repro.launch import common

    common.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return run


def run_cell(root: str, bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, t_process: float, device_kind: str) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    run = make_run(root, cell, seed, seconds, trace, t_process, device_kind)
    metrics = cell_metrics(bench, cell, trace)
    readers = {m["name"]: load_module(root, "metrics", m["name"])
               for m in metrics}
    run.system.setup(run)
    from bench import trace as trace_mod

    kernels = tuple(getattr(mod, "KERNEL") for mod in readers.values()
                    if hasattr(mod, "KERNEL"))
    tracer = trace_mod.WindowTracer(run, kernels) if trace else None
    compiled0, hits0 = run.compiles.compiled, run.compiles.hits
    run.window_t0 = time.perf_counter()
    run.setup_s = run.window_t0 - t_process
    run.system.window(run, tracer)
    compiled = run.compiles.compiled - compiled0
    run.counters["compiles_in_window"] = compiled
    run.note(f"window {run.window_s:.3f} s; in the window {compiled} programs "
             f"compiled, {run.compiles.hits - hits0} loaded from the compile "
             f"cache")
    if tracer is not None:
        run.trace_red = tracer.reduce()
    device = device_info(cell["chips"])
    if trace and run.trace_red is not None:
        device["busy_s"] = run.trace_red["busy_s"]
        device["window_s"] = run.trace_red["window_s"]

    run.system.release(run)  # the program's state is freed before the check
    gc.collect()
    run.system.check(run)

    values = {}
    for m in metrics:
        v = readers[m["name"]].read(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {
        "correct": run.correct,
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": values,
        "device": device,
    }
    if trace and run.trace_red is not None:
        result["breakdown"] = run.trace_red["breakdown"]
    result["compared"] = run.compared
    return result
