"""The program's side of the family ``minicpm_sala``: the one file of the
benchmark that names the package's model schema for it. A configuration file
that says ``"family": "minicpm_sala"`` gets this module as its program
(``harness.attach_family``). It serves only: ``model_config`` and
``serve_config`` (``FAMILY_CONTRACT`` in ``benchmark/harness.py``).
"""

from __future__ import annotations

from benchmark import scopes
# At the top, not in the functions: a program that has no such schema (a
# commit before the family was served) fails as the cell is loaded, before
# any weight is made, and `benchmark.run` exits 1 at once.
from gpt_2_distributed_tpu.config import SalaConfig, ServeConfig, SparseAttentionConfig

# This family's device-trace readers find their operations by the program's
# named scopes, which only the trace file itself carries: keep it.
scopes.keep_trace()


def model_config(config_file: dict):
    """The package's ``SalaConfig`` with every size taken from the file: the
    published keys, the layers as run (``mixer_types``), and the sparse sizes
    out of ``assumed``."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "lightning_nh", "lightning_head_dim", "max_position_embeddings",
            "rms_norm_eps", "rope_theta", "scale_emb", "scale_depth",
            "dim_model_base")
    return SalaConfig(
        # the schema's depth is the published one (the constant of the branch
        # scale); the depth run is the length of `mixer_types`
        num_hidden_layers=config_file.get(
            "published_num_hidden_layers", config_file["num_hidden_layers"]),
        mixer_types=tuple(config_file["mixer_types"]),
        initializer_range=config_file["assumed"]["initializer_range"],
        sparse=SparseAttentionConfig(**config_file["assumed"]["sparse"]),
        **{k: config_file[k] for k in keys})


def serve_config(config_file: dict, mix: dict):
    """The engine's ``ServeConfig``. Pool and block tables are sized by the
    traffic, not by the 524,288 positions the model is published for: every
    slot can hold a request of the mix's longest total (and block 0 is the
    null block), and no request may be longer."""
    s = config_file["serve"]
    blocks = s["max_batch"] * (-(-int(mix["max_total"]) // s["block_size"])) + 1
    return ServeConfig(
        max_batch=s["max_batch"], block_size=s["block_size"], num_blocks=blocks,
        prefill_chunk=s["prefill_chunk"], prefix_cache=s["prefix_cache"],
        admission=s["admission"], max_seq_len=int(mix["max_total"]))
