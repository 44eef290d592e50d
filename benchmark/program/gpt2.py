"""The program's side of the family ``gpt2``: the one file of the benchmark
that names the package's model schema for it. A configuration file that says
``"family": "gpt2"`` gets this module as its program (``harness.attach_family``).

What every family's program module exports, under these names
(``FAMILY_CONTRACT`` in ``benchmark/harness.py``): ``model_config`` and
``serve_config`` for a serving cell; ``trainer_flags`` and
``train_model_config`` for a training cell.
"""

from __future__ import annotations


def _preset(config_file: dict):
    from gpt_2_distributed_tpu.config import MODEL_PRESETS

    return MODEL_PRESETS[config_file["program"]["preset"]]


def model_config(config_file: dict):
    """The package's model configuration that a serving cell's engine is
    built with: the preset, with every size taken from the file."""
    return _preset(config_file).replace(
        n_layer=config_file["n_layer"], n_embd=config_file["n_embd"],
        n_head=config_file["n_head"], vocab_size=config_file["vocab_size"],
        n_positions=config_file["n_positions"])


def serve_config(config_file: dict, mix: dict):
    """The engine's ``ServeConfig``. The pool is sized by the traffic, not by
    the position table: the program's own worst-case rule - every slot can
    hold a request of the mix's longest total, and block 0 is the null
    block."""
    from gpt_2_distributed_tpu.config import ServeConfig

    s = config_file["serve"]
    blocks = s["max_batch"] * (-(-int(mix["max_total"]) // s["block_size"])) + 1
    return ServeConfig(
        max_batch=s["max_batch"], block_size=s["block_size"], num_blocks=blocks,
        prefill_chunk=s["prefill_chunk"], prefix_cache=s["prefix_cache"],
        admission=s["admission"], attn_impl=s.get("attn_impl", "auto"))


def trainer_flags(config_file: dict, mix: dict, data_dir: str, seed: int) -> list[str]:
    """The trainer's command line for a training cell of this configuration
    under this mix (parsed by ``train.build_parser``)."""
    train = config_file["train"]
    argv = [
        "--data_dir", data_dir, "--model", config_file["program"]["preset"],
        "--n_layer", str(config_file["n_layer"]),
        "--n_embd", str(config_file["n_embd"]),
        "--n_head", str(config_file["n_head"]),
        "--vocab_size", str(config_file["vocab_size"]),
        "--seq_len", str(mix["seq_len"]), "--batch", str(train["micro_batch"]),
        "--grad_accum_steps", str(train["grad_accum"]), "--seed", str(seed),
    ]
    for flag, value in train["flags"].items():
        argv += [f"--{flag}", str(value)]
    return argv


def train_model_config(args):
    """``train.main``'s flags-to-config lines (it has them inline; a test
    pins both to ``train.main`` step by step)."""
    from gpt_2_distributed_tpu.config import MODEL_PRESETS

    overrides = {
        k: getattr(args, k)
        for k in ("n_layer", "n_embd", "n_head", "vocab_size")
        if getattr(args, k) is not None
    }
    if args.scan_layers == "auto":
        scan_layers = args.model not in ("124M", "345M")
    else:
        scan_layers = args.scan_layers == "on"
    config = MODEL_PRESETS[args.model].replace(
        n_positions=args.seq_len, remat=args.remat, scan_layers=scan_layers,
        loss_impl=args.loss_impl, **overrides)
    if args.attention_impl:
        config = config.replace(attention_impl=args.attention_impl)
    if args.dropout is not None:
        config = config.replace(embd_dropout=args.dropout,
                                attn_dropout=args.dropout,
                                resid_dropout=args.dropout)
    return config
