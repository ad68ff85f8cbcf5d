"""Helpers for the benchmark's tests: a checkout-shaped directory holding
``BENCHMARK.json`` and a copy of ``bench/``, with small fixture cells that
run on the CPU (a 512-feature table, a 2-layer latent-attention model), and
a configuration cut to one chip's share of a mixture of experts."""
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_TABLE = {
    "name": "tiny_logistic", "source": "fixture", "system": "analytics",
    "reference": "sn_logistic", "table": "tiny", "n_features": 512,
    "n_tuples": 6000, "page_bytes": 16384, "tuples_per_page": 7,
    "quantized": False, "device_resident_pages": 512,
    "udf": {"name": "logit", "function": "logistic_regression", "lr": 0.5,
            "merge_coef": 512, "epochs": 2},
    "data_seed": 3, "model_seed": 0,
}
TINY_LM = {
    "name": "tiny_mla", "source": "fixture", "system": "serving",
    "reference": "minicpm3-4b", "program_config": "minicpm3-4b",
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "vocab_size": 503, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16",
    "serving": {"slots": 4, "max_seq": 96, "kv": "paged", "block_size": 8,
                "kv_blocks": 40,
                "attn_impl": "pallas", "prefill_chunk": 4,
                "step_mode": "chunked"},
}
# one chip's share of a mixture-of-experts deployment, cut as a real one is
# (depth, the experts held, the vocabulary), with its published sizes: read
# by the discovery rule and by the serving system's model configuration,
# never run (no plain reference of a mixture of experts is kept yet)
TINY_MOE = {
    "name": "tiny_moe", "source": "fixture", "system": "serving",
    "program_config": "deepseek-v3-671b",
    "deployment": "fixture: each layer divided over 8 chips by its experts; "
                  "this chip holds 8 of the 64 routed experts, an eighth of "
                  "the vocabulary and 5 of the 8 layers, the rest lying on "
                  "further chips",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "vocab_size": 512, "max_position_embeddings": 4096,
    "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "program_fields": {"n_routed_experts": "n_experts"},
    "not_applied": {"max_position_embeddings": "positions are the server's "
                    "max_seq"},
    "reduced": ["num_hidden_layers", "vocab_size", "n_routed_experts"],
    "published": {"num_hidden_layers": 8, "vocab_size": 4096,
                  "n_routed_experts": 64},
}
TINY_CHAT = {
    "kind": "open_loop", "rate_per_s": 8.0, "ramp_s": 0.5, "trace_s": 1.0,
    "first_token_wait_s": 10.0,
    "prompt": {"median": 8, "sigma": 0.5, "min": 2, "max": 24},
    "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 24},
}


def moe_with(**changes) -> dict:
    """A copy of ``TINY_MOE`` with some keys given other values."""
    return dict(json.loads(json.dumps(TINY_MOE)), **changes)


# more requests than the fixture server's 4 slots keep up with
TINY_SATURATE = dict({k: v for k, v in TINY_CHAT.items()
                      if k != "first_token_wait_s"}, rate_per_s=24.0)


def tiny_benchmark(real: dict) -> dict:
    """The real benchmark's metrics, with fixture cells on fixture configs."""
    cells = [
        {"name": "tiny_logistic.train", "config": "tiny_logistic",
         "traffic": "train", "chips": 1, "why": "fixture"},
        {"name": "tiny_logistic.scan", "config": "tiny_logistic",
         "traffic": "scan", "chips": 1, "why": "fixture"},
        {"name": "tiny_mla.chat", "config": "tiny_mla",
         "traffic": "tiny_chat", "chips": 1, "why": "fixture"},
        {"name": "tiny_mla.saturate", "config": "tiny_mla",
         "traffic": "tiny_saturate", "chips": 1, "why": "fixture"},
    ]
    rename = {"sn_logistic.train": "tiny_logistic.train",
              "sn_logistic.scan": "tiny_logistic.scan",
              "minicpm3-4b.chat": "tiny_mla.chat",
              "minicpm3-4b.saturate": "tiny_mla.saturate"}
    out = json.loads(json.dumps(real))
    out["workloads"] = cells
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"] if w in rename]
    return out


def make_tiny_root(tmp_path):
    """A directory the harness can run from: BENCHMARK.json with the fixture
    cells, bench/ copied, the fixture configs and mix added, and the CPU's
    device kind in the peaks table."""
    import pathlib

    root = pathlib.Path(tmp_path) / "checkout"
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("data", "out", "dev",
                                                  "__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    (root / "BENCHMARK.json").write_text(json.dumps(tiny_benchmark(real)))
    (root / "bench/configs/tiny_logistic.json").write_text(json.dumps(TINY_TABLE))
    (root / "bench/configs/tiny_mla.json").write_text(json.dumps(TINY_LM))
    (root / "bench/configs/tiny_moe.json").write_text(json.dumps(TINY_MOE))
    (root / "bench/traffic/tiny_chat.json").write_text(json.dumps(TINY_CHAT))
    (root / "bench/traffic/tiny_saturate.json").write_text(
        json.dumps(TINY_SATURATE))
    peaks = json.loads((root / "bench/peaks.json").read_text())
    peaks["devices"]["cpu"] = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11,
                               "hbm_bytes": 1e10}
    (root / "bench/peaks.json").write_text(json.dumps(peaks))
    return str(root)


def run_tiny(root: str, workload: str, monkeypatch, seed: int = 7,
             seconds: float = 1.0) -> dict:
    """One untraced run of a fixture cell on the CPU, past the run's look for
    a chip, with JAX's persistent compile cache left off."""
    import time

    from bench import harness
    from repro.launch import common

    monkeypatch.setattr(common, "enable_compile_cache", lambda: "")

    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, workload)
    return harness.run_cell(root, bench, cell, seed, seconds, False,
                            time.perf_counter(), "cpu")
