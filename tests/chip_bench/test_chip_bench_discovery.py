"""BENCHMARK.json keeps to its contract, and every piece it names is found by
name: configurations, traffic mixes, system modules, references and metric
readers. A fixture shows that a configuration, a traffic mix and a metric
are added with new files and entries alone.

A configuration may be cut to one chip's share of a stated deployment
(depth, and the experts, heads and vocabulary one chip holds), never in a
width; ``cut_faults`` is that rule over the configuration file."""
import json
import os
import re

import pytest

from benchfix import REPO, TINY_MOE, moe_with, run_tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


# sizes that are never cut: widths of the hidden state, the feed-forward
# and expert layers, heads, latents, windows, states and convolutions, and
# the experts each token is routed to
WIDTH_KEYS = {
    "hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "sliding_window", "num_experts_per_tok", "state_size",
    "ssm_state_size", "d_state", "conv_kernel", "d_conv", "expand",
    "mamba_expand", "head_size",
}
LAYER_KEYS = {"num_hidden_layers", "num_layers", "n_layers"}
EXPERT_KEYS = {"n_routed_experts", "num_experts", "num_local_experts"}
HEAD_KEYS = {"num_attention_heads", "num_key_value_heads"}
CHIPS = re.compile(r"\b\d[\d,]*[ -]chips?\b")


def is_width(key: str) -> bool:
    return key in WIDTH_KEYS or key.endswith(("_dim", "_rank"))


def cut_faults(cfg: dict) -> list[str]:
    """What keeps a configuration file's cuts from standing: each key in
    ``reduced`` is no width, states its published value under
    ``published`` and is smaller than it, and keeps the floors of a chip's
    share (an eighth of the vocabulary; 8 routed experts held, or all; the
    leading dense layers, held once, plus 4; heads that divide the published
    count); the file's ``deployment`` says over how many chips a layer is
    divided; and every key that differs from its published value is in
    ``reduced``."""
    reduced = cfg.get("reduced", [])
    published = cfg.get("published", {})
    faults = []
    if reduced and not CHIPS.search(cfg.get("deployment", "")):
        faults.append("deployment does not say over how many chips a layer "
                      "is divided")
    dense = cfg.get("first_k_dense_replace", 0)
    for k in reduced:
        if is_width(k):
            faults.append(f"{k}: a width is never cut")
            continue
        if k not in published or k not in cfg:
            faults.append(f"{k}: no published value, or not in the file")
            continue
        v, p = cfg[k], published[k]
        if not v < p:
            faults.append(f"{k}: {v} is no cut of the published {p}")
        elif k == "vocab_size" and 8 * v < p:
            faults.append(f"{k}: {v} is under an eighth of {p}")
        elif k in EXPERT_KEYS and v < 8:
            faults.append(f"{k}: {v} experts held, under 8 and not all {p}")
        elif k in LAYER_KEYS and v < min(p, dense + 4):
            faults.append(f"{k}: {v} layers, under {dense} dense + 4")
        elif k == "first_k_dense_replace" and v < 1:
            faults.append(f"{k}: the leading dense layers are held once")
        elif k in HEAD_KEYS and p % v:
            faults.append(f"{k}: {v} does not divide the published {p}")
    for k, p in published.items():
        if cfg.get(k) != p and k not in reduced:
            faults.append(f"{k}: differs from the published {p}, not in "
                          "reduced")
    return faults


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
    assert set(conf["reduced"]) == set(cfg.get("reduced", []))
    assert len(conf["reduced"]) <= 16
    assert all(NAME.match(k) for k in conf["reduced"])
    assert not cut_faults(cfg), cut_faults(cfg)
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


def test_a_chip_share_cut_with_published_values_and_floors_is_accepted(
        tiny_root):
    """Depth, vocabulary and experts cut to one chip's share, each with its
    published value, in a file found by name like any configuration."""
    from bench import harness

    cfg = harness.load_json(tiny_root, "configs", "tiny_moe.json")
    assert cfg == TINY_MOE
    assert not cut_faults(cfg), cut_faults(cfg)


REFUSED = {
    # a width, even with its published value
    "hidden_size": moe_with(
        hidden_size=32, reduced=TINY_MOE["reduced"] + ["hidden_size"],
        published=dict(TINY_MOE["published"], hidden_size=64)),
    "experts_per_token": moe_with(
        num_experts_per_tok=1,
        reduced=TINY_MOE["reduced"] + ["num_experts_per_tok"],
        published=dict(TINY_MOE["published"], num_experts_per_tok=2)),
    # a share with no published value
    "no_published": moe_with(published={"num_hidden_layers": 8,
                                        "n_routed_experts": 64}),
    # a key changed from its published value but not listed as cut
    "unlisted": moe_with(reduced=["num_hidden_layers", "n_routed_experts"]),
    # no deployment that says over how many chips a layer is divided
    "no_deployment": moe_with(deployment="one chip's share"),
    # under a floor
    "vocab_floor": moe_with(vocab_size=256),
    "expert_floor": moe_with(n_routed_experts=4),
    "depth_floor": moe_with(num_hidden_layers=4),
    "dense_floor": moe_with(
        first_k_dense_replace=0,
        reduced=TINY_MOE["reduced"] + ["first_k_dense_replace"],
        published=dict(TINY_MOE["published"], first_k_dense_replace=1)),
    "heads_divide": moe_with(
        num_attention_heads=3,
        reduced=TINY_MOE["reduced"] + ["num_attention_heads"],
        published=dict(TINY_MOE["published"], num_attention_heads=4)),
    # a "cut" that is not smaller than the published value
    "not_a_cut": moe_with(published=dict(TINY_MOE["published"],
                                         num_hidden_layers=5)),
}


# the fault each case has to be refused for
REASON = {"hidden_size": "hidden_size: a width",
          "experts_per_token": "num_experts_per_tok: a width",
          "no_published": "vocab_size: no published value",
          "unlisted": "vocab_size: differs", "no_deployment": "deployment",
          "vocab_floor": "vocab_size: 256", "expert_floor": "n_routed_experts",
          "depth_floor": "num_hidden_layers: 4",
          "dense_floor": "first_k_dense_replace",
          "heads_divide": "num_attention_heads", "not_a_cut": "no cut"}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_cut_of_a_width_or_without_evidence_or_under_a_floor_is_refused(
        case):
    faults = cut_faults(REFUSED[case])
    assert any(REASON[case] in f for f in faults), faults
