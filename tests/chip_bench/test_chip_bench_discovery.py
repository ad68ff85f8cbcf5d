"""BENCHMARK.json keeps to its contract, and every piece it names is found by
name: configurations, traffic mixes, system modules, references and metric
readers. A fixture shows that a configuration, a traffic mix and a metric
are added with new files and entries alone."""
import json
import os
import re

import pytest

from benchfix import REPO, run_tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_and_entries_keep_to_the_contract():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(b["paths"]) <= 16 and len(b["command"]) <= 32
    for p in b["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    assert b["command"][1] in ("bench/run.py",)
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = set()
    for section, keys in ENTRY_KEYS.items():
        for e in b[section]:
            extra = set(e) - keys - ({"workloads"} if section in (
                "end_to_end", "per_layer") else set())
            assert keys <= set(e) and not extra, (section, e["name"])
            assert NAME.match(e["name"]) and e["name"] not in names
            names.add(e["name"])
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    assert 1 <= len(b["configs"]) <= 24 and 1 <= len(b["workloads"]) <= 24
    assert 1 <= len(b["end_to_end"]) <= 16 and 1 <= len(b["per_layer"]) <= 128
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    four = sum(c["chips"] == 4 for c in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    from bench import harness

    b = bench()
    layers = {}
    for cell in b["workloads"]:
        assert cell["chips"] in (1, 4)
        e2e = {m["name"] for m in harness.cell_metrics(b, cell, False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per = harness.cell_metrics(b, cell, True)
        assert per, cell["name"]
        for m in per:
            assert m["moves"] in e2e, (cell["name"], m["name"])
    for m in b["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    used = {c["config"] for c in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    pairs = [(c["config"], c["traffic"]) for c in b["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", [c["name"] for c in bench()["workloads"]])
def test_each_cell_finds_its_pieces_by_name(cell):
    from bench import harness

    b = bench()
    c = harness.find_cell(b, cell)
    conf = [x for x in b["configs"] if x["name"] == c["config"]][0]
    assert conf["file"] == f"bench/configs/{conf['name']}.json"
    cfg = harness.load_json(REPO, "configs", c["config"] + ".json")
    assert cfg["name"] == c["config"] and cfg["source"] == conf["source"]
    assert set(conf["reduced"]) <= set(cfg.get("reduced", conf["reduced"]))
    for k in conf["reduced"]:
        assert not re.search(r"(_dim|_rank|_size|hidden|intermediate|heads)",
                             k)  # never a width
    mix = harness.load_json(REPO, "traffic", c["traffic"] + ".json")
    assert mix["kind"] in ("statements", "open_loop")
    drv = harness.load_module(REPO, "systems", cfg["system"])
    for fn in ("setup", "window", "release", "check", "readings"):
        assert callable(getattr(drv, fn))
    harness.load_module(REPO, "configs", cfg["reference"])
    for trace in (False, True):
        for m in harness.cell_metrics(b, c, trace):
            assert callable(harness.load_module(REPO, "metrics", m["name"]).read)
    assert "TPU v5 lite" in harness.load_json(REPO, "peaks.json")["devices"]


def test_an_unknown_device_kind_is_an_error():
    from bench import harness

    with pytest.raises(KeyError):
        harness.load_peaks(REPO, "TPU v0 imaginary")


def test_new_config_mix_and_metric_need_only_files_and_entries(tiny_root,
                                                               monkeypatch):
    """The fixture root adds the configuration ``tiny_logistic``, a mix, and
    here an end-to-end metric, each as a new file plus an entry: the harness
    runs it with no code changed."""
    root = tiny_root
    with open(os.path.join(root, "bench/metrics/statements_per_s.py"), "w") as f:
        f.write("def read(run):\n"
                "    return len(run.records) / run.window_s\n")
    with open(os.path.join(root, "bench/traffic/train_twice.json"), "w") as f:
        json.dump({"kind": "statements",
                   "statements": ["SELECT * FROM dana.{udf}('{table}');"] * 2},
                  f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["workloads"].append({"name": "tiny_logistic.train_twice",
                           "config": "tiny_logistic", "traffic": "train_twice",
                           "chips": 1, "why": "fixture"})
    b["end_to_end"].append({"name": "statements_per_s", "unit": "1/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["tiny_logistic.train_twice"]})
    for m in b["end_to_end"]:
        if m["name"] == "tuples_per_s":
            m["workloads"].append("tiny_logistic.train_twice")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    res = run_tiny(root, "tiny_logistic.train_twice", monkeypatch)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"statements_per_s", "tuples_per_s",
                                   "setup_s"}
