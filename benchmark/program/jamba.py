"""The program's side of the family ``jamba``: the one file of the benchmark
that names the package's model schema for it. A configuration file that says
``"family": "jamba"`` gets this module as its program
(``harness.attach_family``). It serves only: ``model_config`` and
``serve_config`` (``FAMILY_CONTRACT`` in ``benchmark/harness.py``).
"""

from __future__ import annotations

from benchmark import scopes
# At the top, not in the functions: a program that has no such schema (a
# commit before the family was served) fails as the cell is loaded, before
# any weight is made, and `benchmark.run` exits 1 at once.
from gpt_2_distributed_tpu.config import JambaConfig, ServeConfig

# This family's device-trace readers find their operations by the program's
# named scopes, which only the trace file itself carries: keep it.
scopes.keep_trace()

KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "attn_layer_period", "attn_layer_offset", "num_experts", "num_attention_heads",
    "num_key_value_heads", "mamba_d_state", "mamba_d_conv", "mamba_expand",
    "mamba_dt_rank", "rms_norm_eps", "max_position_embeddings", "tie_word_embeddings",
)


def model_config(config_file: dict):
    """The package's ``JambaConfig`` with every size taken from the file: the
    published keys, and what the file's ``assumed`` sets."""
    assumed = config_file["assumed"]
    low, high = assumed["time_step_range"]
    return JambaConfig(
        head_dim=assumed["head_dim"], initializer_range=assumed["initializer_range"],
        time_step_min=low, time_step_max=high,
        **{k: config_file[k] for k in KEYS})


def serve_config(config_file: dict, mix: dict):
    """The engine's ``ServeConfig``. Pool and block tables are sized by the
    traffic, not by the 262,144 positions the model is published for: every
    slot can hold a request of the mix's longest total (and block 0 is the
    null block), and no request may be longer."""
    s = config_file["serve"]
    blocks = s["max_batch"] * (-(-int(mix["max_total"]) // s["block_size"])) + 1
    return ServeConfig(
        max_batch=s["max_batch"], block_size=s["block_size"], num_blocks=blocks,
        prefill_chunk=s["prefill_chunk"], prefix_cache=s["prefix_cache"],
        admission=s["admission"], max_seq_len=int(mix["max_total"]))
