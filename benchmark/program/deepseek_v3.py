"""The program's side of the family ``deepseek_v3``: the one file of the
benchmark that names the package's model schema for it. A configuration file
that says ``"family": "deepseek_v3"`` gets this module as its program
(``harness.attach_family``). It serves only: ``model_config`` and
``serve_config`` (``FAMILY_CONTRACT`` in ``benchmark/harness.py``).
"""

from __future__ import annotations

from benchmark import scopes
# At the top, not in the functions: a program that has no such schema (a
# commit before the family was served) fails as the cell is loaded, before
# any weight is made, and `benchmark.run` exits 1 at once.
from gpt_2_distributed_tpu.config import DeepseekV3Config, ServeConfig

# This family's device-trace readers find their operations by the program's
# named scopes, which only the trace file itself carries: keep it.
scopes.keep_trace()

KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "n_shared_experts", "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim", "n_group", "topk_group",
    "num_experts_per_tok", "first_k_dense_replace", "norm_topk_prob", "rms_norm_eps",
    "rope_theta", "rope_interleave", "max_position_embeddings", "tie_word_embeddings",
)


def model_config(config_file: dict):
    """The package's ``DeepseekV3Config`` with every size taken from the file:
    the published keys, the depth as run from ``first_layer`` on, the router
    at its published width and the range of experts held."""
    return DeepseekV3Config(
        n_routed_experts=config_file.get(
            "published_n_routed_experts", config_file["n_routed_experts"]),
        experts_held=tuple(config_file.get(
            "experts_held", (0, config_file["n_routed_experts"]))),
        first_layer=config_file.get("first_layer", 0),
        initializer_range=config_file["assumed"]["initializer_range"],
        **{k: config_file[k] for k in KEYS})


def serve_config(config_file: dict, mix: dict):
    """The engine's ``ServeConfig``. Pool and block tables are sized by the
    traffic: every slot can hold a request of the mix's longest total (and
    block 0 is the null block), and no request may be longer."""
    s = config_file["serve"]
    blocks = s["max_batch"] * (-(-int(mix["max_total"]) // s["block_size"])) + 1
    return ServeConfig(
        max_batch=s["max_batch"], block_size=s["block_size"], num_blocks=blocks,
        prefill_chunk=s["prefill_chunk"], prefix_cache=s["prefix_cache"],
        admission=s["admission"], max_seq_len=int(mix["max_total"]))
