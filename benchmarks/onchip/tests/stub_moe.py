"""A stub reference for a configuration of another block kind: a
DeepSeekMoE-style decoder (a leading dense layer, then routed and shared
experts), as much as the harness asks of a reference to check the
program's configuration.  ``tests/test_harness.py`` maps it onto the
program's registered reduced ``deepseek_moe_16b`` with no change to the
harness: a later MoE reference brings only new files."""
from __future__ import annotations

from typing import Dict

# a configuration file in the published config.json's keys (DeepSeekMoE),
# at the registered reduced sizes
CONFIG = {
    "name": "deepseek-moe-16b-stub", "arch": "deepseek_moe_16b",
    "reference": "stub_moe", "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 32, "vocab_size": 512,
    "num_hidden_layers": 3, "intermediate_size": 256,
    "moe_intermediate_size": 64, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "reduced": {},
}

KEYS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
        "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
        "vocab_size": "vocab", "num_hidden_layers": "n_layers",
        "intermediate_size": "moe.dense_d_ff",
        "moe_intermediate_size": "moe.expert_d_ff",
        "n_routed_experts": "moe.n_experts",
        "num_experts_per_tok": "moe.top_k",
        "n_shared_experts": "moe.n_shared",
        "first_k_dense_replace": "moe.first_dense_layers"}


def sizes(config: Dict) -> Dict:
    return {name: config[key] for key, name in KEYS.items()}


def program_fields(s: Dict) -> Dict:
    """The sizes, the routed experts' width as the program's ``d_ff``, and
    the leading dense layers then expert layers."""
    dense = s["moe.first_dense_layers"]
    return dict(s, d_ff=s["moe.expert_d_ff"],
                resolved_pattern=("dense_first",) * dense
                + ("moe",) * (s["n_layers"] - dense))
