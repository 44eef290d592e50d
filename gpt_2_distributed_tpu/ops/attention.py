"""Causal multi-head self-attention math.

Behavioral parity with the reference's ``CausalMultiHeadSelfAttention``
(``/root/reference/model.py:80-159``): scaled dot-product over split heads,
causal positions masked to **-1e4** before the softmax (not -inf — the
reference masked-fills with -1e4 and after softmax the difference is below
bf16 resolution, but we keep the exact constant for loss-curve parity),
dropout on the attention probabilities.

TPU-first shape: no precomputed ``n_positions x n_positions`` mask buffer (the
reference materializes one as a module buffer, ``model.py:105-108``); the mask
is an iota comparison fused by XLA into the softmax, costing zero HBM. Scores
are accumulated in fp32 via ``preferred_element_type`` so the bf16 MXU matmul
keeps fp32 softmax inputs — the same numerics torch autocast produces (bf16
matmul, fp32 softmax).

This dense O(T^2) formulation is the parity baseline; `flash` (a Pallas
fused kernel) is selected by the caller when profiling demands it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MASK_VALUE = -1e4  # reference masks scores to -1e4, /root/reference/model.py:144


def causal_attention(
    q: jnp.ndarray,  # [B, H, T, D]
    k: jnp.ndarray,  # [B, H, T, D]
    v: jnp.ndarray,  # [B, H, T, D]
    *,
    dropout_rate: float = 0.0,
    rng: jax.Array | None = None,
    deterministic: bool = True,
) -> jnp.ndarray:
    """Dense causal attention. Returns [B, H, T, D] in q's dtype."""
    _, _, t, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, dtype=jnp.float32))
    # bf16 inputs, fp32 accumulation: the MXU computes bf16 x bf16 -> fp32.
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    qpos = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    causal = kpos <= qpos
    scores = jnp.where(causal, scores, jnp.asarray(MASK_VALUE, dtype=scores.dtype))
    probs = jax.nn.softmax(scores, axis=-1)
    if not deterministic and dropout_rate > 0.0:
        if rng is None:
            raise ValueError("attention dropout requires an rng key")
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), jnp.zeros_like(probs))
    probs = probs.astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def causal_attention_bthd(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    **kwargs,
) -> jnp.ndarray:
    """Dense causal attention over the model's native [B, T, H, D] layout.

    The transposes here are the head-major round trip the flash kernel
    avoids entirely (its BlockSpecs index the head dim in place); the dense
    parity path keeps them, and XLA typically folds them into the adjacent
    matmuls."""
    out = causal_attention(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        **kwargs,
    )
    return out.transpose(0, 2, 1, 3)


def causal_grouped_attention(q, k, v):
    """One sequence's causal grouped-query attention, dense: q [T, H, d] over
    k and v [T, KV, d], softmax at 1/sqrt(d) in float32; [T, H * d]. What the
    layer-pattern families' plain forwards (``models/nemotron_h.py``,
    ``models/jamba.py``) attend with; their serving programs go through the
    paged pools."""
    t, heads, d = q.shape
    kv = k.shape[1]
    qg = q.reshape(t, kv, heads // kv, d)
    s = jnp.einsum("tkgd,skd->kgts", qg, k,
                   preferred_element_type=jnp.float32) / jnp.sqrt(jnp.float32(d))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, axis=-1).astype(v.dtype), v)
    return o.reshape(t, heads * d)


def _ring_mesh():
    """The active mesh when its 'sp' axis is >1 (ring attention applies)."""
    from gpt_2_distributed_tpu.parallel.mesh import SP_AXIS, active_mesh

    m = active_mesh()
    if m is not None and SP_AXIS in m.axis_names and m.shape[SP_AXIS] > 1:
        return m
    return None


def select_attention_impl(impl: str, seq_len: int):
    """Resolve an attention implementation name to a callable taking
    ``[B, T, H, D]`` q/k/v (the model's native layout — no head transpose on
    the hot path). Called at trace time (static shapes).

    ``ring`` shards the sequence over the active mesh's 'sp' axis
    (``ops/ring_attention.py``); with no active mesh or sp=1 it falls through
    to the auto policy (a 1-rank ring is just local attention). ``auto``
    prefers ring when sp>1 — an sp mesh whose attention ignored the axis
    would silently replicate the sequence on every rank."""
    import functools

    from gpt_2_distributed_tpu.ops.flash_attention import (
        flash_attention_bthd,
        pick_block_q,
    )
    from gpt_2_distributed_tpu.ops.spmd import record_resolved_impl

    if impl not in ("dense", "flash", "ring", "auto"):
        raise ValueError(
            f"unknown attention_impl {impl!r}; expected dense|flash|ring|auto"
        )
    if impl in ("ring", "auto"):
        mesh = _ring_mesh()
        if mesh is not None:
            from gpt_2_distributed_tpu.ops.ring_attention import (
                ring_attention_bthd,
            )

            record_resolved_impl("attention", "ring")
            return functools.partial(ring_attention_bthd, mesh=mesh)
        flash_ok = (
            pick_block_q(seq_len) is not None
            and jax.devices()[0].platform == "tpu"
        )
        impl = "flash" if flash_ok else "dense"
    if impl == "flash":
        return flash_attention_bthd  # reports how its kernel runs itself
    record_resolved_impl("attention", "dense (xla)")
    return causal_attention_bthd
