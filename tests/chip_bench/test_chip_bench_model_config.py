"""Every size a serving configuration states reaches the program's model
configuration, or set-up names the size it would leave out; the configuration
built for minicpm3-4b is field for field what the fixed set of keys built
before files could map or excuse their own."""
import dataclasses
import json
import os

import pytest

from benchfix import REPO, TINY_LM, TINY_MOE, moe_with, run_tiny

# the earlier rule: exactly these keys, every one required
FIXED_WIDTHS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def serving():
    from bench import harness

    harness.ensure_program_on_path()
    return harness.load_module(REPO, "systems", "serving")


def test_minicpm3_4b_builds_the_same_model_config_as_the_fixed_keys():
    from repro.configs import get_config

    with open(os.path.join(REPO, "bench/configs/minicpm3-4b.json")) as f:
        cfg = json.load(f)
    fixed = dataclasses.replace(
        get_config(cfg["program_config"]), param_dtype=cfg["torch_dtype"],
        **{field: cfg[key] for key, field in FIXED_WIDTHS.items()})
    got = serving()._model_config(cfg)
    assert dataclasses.asdict(got) == dataclasses.asdict(fixed)
    assert got.n_experts == 0 and got.family == "dense"


def test_a_chip_share_of_experts_reaches_the_program_field_by_field():
    mcfg = serving()._model_config(TINY_MOE)
    want = {
        "d_model": 64, "d_ff": 96, "moe_d_ff": 32, "n_layers": 5,
        "first_dense_layers": 1, "n_heads": 4, "n_kv_heads": 4,
        "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "n_experts": 8,
        "experts_per_token": 2, "n_shared_experts": 1, "vocab_size": 512,
        "norm_eps": 1e-06, "rope_theta": 10000.0, "tie_embeddings": False,
        "param_dtype": "bfloat16", "family": "moe", "attn_kind": "mla",
    }
    assert {k: getattr(mcfg, k) for k in want} == want
    fields = {**serving().WIDTHS, **TINY_MOE["program_fields"]}
    for key, v in TINY_MOE.items():
        if isinstance(v, (int, float)) and key not in TINY_MOE["not_applied"]:
            assert getattr(mcfg, fields[key]) == v, key


@pytest.mark.parametrize("cfg,named", [
    (moe_with(n_group=8), "n_group"),  # stated, neither applied nor declared
    (moe_with(not_applied={}), "max_position_embeddings"),
    (moe_with(rope_scaling={"type": "yarn", "factor": 40}), "rope_scaling"),
    (moe_with(program_fields={}), "n_routed_experts"),
    (moe_with(program_fields={"n_routed_experts": "routed"}), "routed"),
    (moe_with(program_fields={"n_routed_experts": "n_experts",
                           "topk_group": "n_experts"}), "topk_group"),
])
def test_a_size_that_would_not_reach_the_program_is_named(cfg, named):
    with pytest.raises(ValueError, match=named):
        serving()._model_config(cfg)


def test_setup_fails_on_a_stated_size_it_would_ignore(tiny_root, monkeypatch):
    """The fixture chat cell with one more size in its file, which no field
    takes and no reason excuses: set-up stops before any weight is drawn."""
    path = os.path.join(tiny_root, "bench/configs/tiny_mla.json")
    with open(path, "w") as f:
        json.dump(dict(TINY_LM, max_position_embeddings=32768), f)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        run_tiny(tiny_root, "tiny_mla.chat", monkeypatch)
