"""Tiny cells for the CPU tests: the harness's own functions, fed a
configuration and mixes small enough for the sandbox. Not a benchmark."""

import copy
import os

from benchmark import harness

TINY_CONFIG = {
    "name": "tiny", "family": "gpt2", "source": "tests",
    "vocab_size": 257, "n_positions": 128, "n_embd": 32, "n_layer": 2,
    "n_head": 2, "layer_norm_epsilon": 1e-05, "initializer_range": 0.02,
    "program": {"preset": "124M"},
    "train": {
        "micro_batch": 2, "grad_accum": 2,
        "flags": {"dropout": 0.0, "step_guard": "on", "scan_layers": "auto",
                  "attention_impl": "flash", "loss_impl": "blocked",
                  "device_prefetch": "on", "workers": 1},
    },
    "serve": {"max_batch": 4, "block_size": 8, "prefill_chunk": 16,
              "prefix_cache": False, "admission": "reserve", "temperature": 0,
              "attn_impl": "pallas"},
    "reference": {"rows_per_block": 2},
}

TINY_MIXES = {
    "train": {"kind": "train", "seq_len": 128, "shards": 2,
              "tokens_per_shard": 4096, "reference_steps": 3},
    "backlog": {
        "kind": "backlog", "base_seed": 7, "pool": 16,
        "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 40},
        "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2, "max": 12},
        "max_total": 128, "min_queue_slots": 1.0, "check_tokens": 30,
    },
}

END_TO_END = {
    "train": ["train_tok_s_per_chip", "setup_s"],
    "backlog": ["serve_tok_s", "setup_s"],
}

# Loose on purpose: these tests pin control flow, not the chip's limits.
LIMITS = {
    "train": {"data_rows_wrong": {"limit": 0}, **{k: {"limit": 0.05} for k in (
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap", "moved_norm_gap")},
        # bf16 program on the CPU reads 0.014-0.019, the fp8 control 0.08-0.14
        "grad_diff": {"limit": 0.04}},
    "backlog": {"token_logit_gap": {"limit": 0.05}},
}


def _merged(into: dict, changes: dict) -> dict:
    for key, value in changes.items():
        if isinstance(value, dict) and isinstance(into.get(key), dict):
            _merged(into[key], value)
        else:
            into[key] = value
    return into


def tiny_cell(kind: str, **config_changes) -> dict:
    """A cell as ``harness.load_cell`` gives one, the family's two modules
    and the sizes on it. ``config_changes`` are merged into the tiny
    configuration first (group by group): the sizes are read from it once,
    here, as ``load_cell`` reads them."""
    # its own work directory (shards, traces) in each test process
    return harness.attach_family({
        "name": f"tiny-{kind}-{os.getpid()}", "config": "tiny", "traffic": kind, "chips": 1,
        "config_file": _merged(copy.deepcopy(TINY_CONFIG), config_changes),
        "mix": copy.deepcopy(TINY_MIXES[kind]),
        "limits": copy.deepcopy(LIMITS[kind]),
        "end_to_end": [{"name": n, "unit": "x"} for n in END_TO_END[kind]],
        "per_layer": [],
    })


CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
