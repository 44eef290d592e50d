"""Model-FLOPs accounting and MFU (model FLOPs utilization).

The reference has no performance instrumentation beyond wall-clock tokens/sec
(``/root/reference/stats_tracker.py:209-234``); BASELINE.md defines this
framework's north-star metric as MFU, so FLOPs accounting is first-party here.

Convention: the standard decoder-only training cost
``6 * N * T + 12 * L * H * D * T^2`` FLOPs per sequence (matmul fwd + 2x bwd,
attention scores/values counted explicitly), i.e. per token:

    flops/token = 6 * N_matmul + 12 * L * C * T

where ``N_matmul`` counts parameters that participate in matmuls (all weights
+ the tied lm_head's second use; embedding *lookups* are gathers, not FLOPs,
but the tied head's ``[C, V]`` projection is a real matmul and is included).
"""

from __future__ import annotations

import jax

from gpt_2_distributed_tpu.config import GPT2Config


def flops_per_token(config: GPT2Config, seq_len: int) -> float:
    """Training FLOPs per token (fwd + bwd) for one model replica."""
    c, l, v = config.n_embd, config.n_layer, config.vocab_size
    # Matmul params per block: qkv (3C^2) + attn proj (C^2) + mlp (8C^2).
    matmul_params = l * 12 * c * c
    # wpe is an add, wte lookup is a gather; the tied lm_head projection C->V
    # is a matmul over the full vocab.
    matmul_params += c * v
    # 6 FLOPs per matmul-param per token (2 fwd + 4 bwd), plus the attention
    # score/value matmuls: 2 * (2 * C * T) fwd -> *3 for bwd = 12 * C * T
    # per layer per token.
    return 6.0 * matmul_params + 12.0 * l * c * seq_len


# Peak dense bf16 FLOP/s per *chip* (not per core), from published TPU specs.
# device_kind strings as reported by jax.devices()[0].device_kind.
_TPU_PEAK_FLOPS: dict[str, float] = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v4 lite": 137e12,  # v4i
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # Trillium / v6e
    "TPU v6e": 918e12,
    "TPU7x": 4614e12,
}


def device_peak_flops(device=None) -> float | None:
    """Peak bf16 FLOP/s of one device; None off the TPU (a CPU has no peak
    this framework measures against).

    The kind is matched exactly. A TPU kind the table does not list raises:
    a prefix match would hand "TPU v5x" the figure of "TPU v5", and every
    MFU computed from it would be wrong without a word."""
    if device is None:
        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = device.device_kind
    if kind not in _TPU_PEAK_FLOPS:
        raise ValueError(
            f"no peak FLOP/s on record for TPU device kind {kind!r}; MFU "
            f"cannot be computed against a guessed peak — add the kind to "
            f"utils/flops.py::_TPU_PEAK_FLOPS with its published figure "
            f"(listed: {', '.join(sorted(_TPU_PEAK_FLOPS))})"
        )
    return _TPU_PEAK_FLOPS[kind]


def mfu(
    tokens_per_sec_per_chip: float,
    config: GPT2Config,
    seq_len: int,
    peak_flops: float | None = None,
) -> float | None:
    """Model FLOPs utilization in [0, 1], or None when peak is unknown."""
    if peak_flops is None:
        peak_flops = device_peak_flops()
    if peak_flops is None or peak_flops <= 0:
        return None
    return tokens_per_sec_per_chip * flops_per_token(config, seq_len) / peak_flops
