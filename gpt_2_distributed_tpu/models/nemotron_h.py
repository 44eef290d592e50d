"""Nemotron-H: a stack whose layers are ONE mixer each, of three kinds
(``NemotronHConfig``).

``h = E[ids]``; for each character of ``hybrid_override_pattern``, ``h = h +
Mixer(RMSNorm(h))``; ``logits = W_lm RMSNorm(h)``. No positions of any kind,
no biases but the convolution's, an untied head.

* ``M`` - a Mamba-2 state-space mixer: one in-projection to a gate ``z``, the
  convolved ``x | B | C`` and a step ``dt`` a head; a causal depthwise
  convolution and SiLU; the recurrence of ``ops/ssd.py`` (64 heads of 64
  over a state of 128, ``B`` and ``C`` shared by groups of heads); ``D x``
  added; ``RMSNorm`` over groups of the gated result; an out-projection.
* ``*`` - grouped-query softmax attention, 16 query heads to a KV head.
* ``E`` - sparse experts (``ops/moe.py``): a float32 router over ALL the
  published experts, ``relu^2`` experts of which this holder computes its
  own (``config.experts_held``) and leaves out the rest, beside a shared
  expert that every holder computes whole.

Parameters are ONE DICT A LAYER, in stack order (``params["layers"][i]``), as
``models/minicpm_sala.py`` keeps them. Matrices and embeddings are stored in
bfloat16 and used as stored - no program casts or re-lays a weight; the
routed experts are ``[held, F, C]`` twice over, the layout the grouped matmul
reads as it lies, either way round. Vectors (norms, ``A_log``, ``D``, ``dt_bias``,
the convolution's kernel and bias, the router's bias) are float32.

Here: the parameters, the pieces every program of the family shares, and the
plain dense forward over whole sequences that the tests use. The serving step
programs are in ``serving/nemotron_programs.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from gpt_2_distributed_tpu.config import (
    ATTENTION_LAYER,
    EXPERT_LAYER,
    MAMBA_LAYER,
    NemotronHConfig,
)
from gpt_2_distributed_tpu.models.minicpm_sala import rms_norm
from gpt_2_distributed_tpu.ops import moe, ssd
from gpt_2_distributed_tpu.ops.attention import causal_grouped_attention


# --- parameters -------------------------------------------------------------


def _init_layer(config: NemotronHConfig, kind: str, key, dtype):
    c = config.hidden_size
    ks = jax.random.split(key, 8)

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * config.initializer_range).astype(dtype)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    if kind == MAMBA_LAYER:
        h, d, conv = config.mamba_num_heads, config.d_inner, config.conv_dim
        half = 1.0 / math.sqrt(config.conv_kernel)
        lo, hi = math.log(config.time_step_min), math.log(config.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(ks[4], (h,)) * (hi - lo) + lo),
                         config.time_step_floor)
        return {
            "norm": ones(c),
            "in_proj": normal(ks[0], (c, d + conv + h)),
            "conv_w": jax.random.uniform(
                ks[1], (config.conv_kernel, conv), jnp.float32, -half, half),
            "conv_b": jax.random.uniform(ks[2], (conv,), jnp.float32, -half, half),
            "A_log": jnp.log(jax.random.uniform(ks[3], (h,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
            "D": ones(h),
            "gate_norm": ones(d),
            "out_proj": normal(ks[5], (d, c)),
        }
    if kind == ATTENTION_LAYER:
        a = config.num_attention_heads * config.head_dim
        kv = config.num_key_value_heads * config.head_dim
        return {
            "norm": ones(c),
            "wq": normal(ks[0], (c, a)), "wk": normal(ks[1], (c, kv)),
            "wv": normal(ks[2], (c, kv)), "wo": normal(ks[3], (a, c)),
        }
    f, fs = config.moe_intermediate_size, config.moe_shared_expert_intermediate_size
    held = jnp.arange(*config.experts_held)

    def experts(k):       # expert e's matrix comes of e, whoever holds it
        return jax.vmap(lambda e: normal(jax.random.fold_in(k, e), (f, c)))(held)

    return {
        "norm": ones(c),
        "router": normal(ks[0], (c, config.n_routed_experts)),
        "router_bias": jnp.zeros((config.n_routed_experts,), jnp.float32),
        "w_up": experts(ks[1]), "w_down": experts(ks[2]),
        "shared_up": normal(ks[3], (c, fs)), "shared_down": normal(ks[4], (fs, c)),
    }


@functools.partial(jax.jit, static_argnums=(0, 2))
def init_params(config: NemotronHConfig, key: jax.Array, dtype=jnp.bfloat16):
    """``{"embed", "lm_head", "norm_f", "layers": [one dict a layer]}``, made
    on the device in one jitted call: N(0, ``initializer_range``) for every
    matrix and embedding, norms and ``D`` at 1, the router's bias at 0, the
    convolution uniform in +-1/sqrt(kernel), ``A_log = log(uniform[1, 16])``,
    ``dt_bias`` the inverse softplus of log-uniform[``time_step_min``,
    ``time_step_max``] floored at ``time_step_floor``."""
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    shape = (config.vocab_size, config.hidden_size)
    scale = config.initializer_range
    return {
        "embed": (jax.random.normal(k_embed, shape, jnp.float32) * scale).astype(dtype),
        "lm_head": (jax.random.normal(k_head, shape, jnp.float32) * scale).astype(dtype),
        "norm_f": jnp.ones((config.hidden_size,), jnp.float32),
        "layers": [
            _init_layer(config, kind, jax.random.fold_in(key, i + 1), dtype)
            for i, kind in enumerate(config.hybrid_override_pattern)
        ],
    }


# --- the pieces every program shares ---------------------------------------


def embed(params, ids):
    """The residual stream's start, float32: it is carried in float32 from
    here to the head, and each mixer reads it through its norm."""
    return params["embed"].at[ids].get(mode="clip").astype(jnp.float32)


def normed_input(config: NemotronHConfig, params, lp, h):
    """``RMSNorm(h)`` as the mixer's matmuls take it."""
    return rms_norm(h, lp["norm"], config.layer_norm_epsilon, params["embed"].dtype)


def ssm_in(config: NemotronHConfig, lp, x):
    """The Mamba-2 in-projection of ``x`` [..., C]: the gate ``z`` [...,
    d_inner], what the convolution runs over ``xBC`` [..., conv_dim], and the
    raw step ``dt`` [..., H]."""
    with jax.named_scope("nemotron/ssm_proj"):
        zxbcdt = x @ lp["in_proj"]
    d, conv = config.d_inner, config.conv_dim
    return zxbcdt[..., :d], zxbcdt[..., d:d + conv], zxbcdt[..., d + conv:]


def ssm_inputs(config: NemotronHConfig, lp, conv_out, dt_raw, live, dtype):
    """From the convolution's float32 output [..., conv_dim] and the raw step:
    ``x`` [..., H, P], ``B`` and ``C`` [..., G, N] in ``dtype``, the step
    ``dt = softplus(dt_raw + dt_bias)`` float32 - 0 wherever ``live`` [...]
    is False, which leaves the state as it was - and ``A`` [H]."""
    xbc = jax.nn.silu(conv_out).astype(dtype)
    lead = xbc.shape[:-1]
    d, gn = config.d_inner, config.n_groups * config.ssm_state_size
    x = xbc[..., :d].reshape(*lead, config.mamba_num_heads, config.mamba_head_dim)
    b = xbc[..., d:d + gn].reshape(*lead, config.n_groups, config.ssm_state_size)
    c = xbc[..., d + gn:].reshape(*lead, config.n_groups, config.ssm_state_size)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"])
    dt = jnp.where(live[..., None], dt, 0.0)
    return x, b, c, dt, -jnp.exp(lp["A_log"])


def ssm_gate(config: NemotronHConfig, lp, y, x, z):
    """``RMSNormGrouped((y + D x) * silu(z))``: the scan's float32 ``y`` [...,
    H, P] with the skip added, gated, normed over each of the ``n_groups``
    groups of the joined heads; [..., d_inner] in ``z``'s dtype."""
    lead = y.shape[:-2]
    y = y + lp["D"][:, None] * x.astype(jnp.float32)
    y = y.reshape(*lead, config.d_inner) * jax.nn.silu(z.astype(jnp.float32))
    g = y.reshape(*lead, config.n_groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + config.layer_norm_epsilon)
    return (g.reshape(*lead, config.d_inner) * lp["gate_norm"]).astype(z.dtype)


def ssm_out(lp, y):
    with jax.named_scope("nemotron/ssm_proj"):
        return y @ lp["out_proj"]


def attention_qkv(config: NemotronHConfig, lp, x):
    """``x`` [..., C] -> q [..., H, d], k and v [..., KV, d]: no norm, no
    rotary - the family has no positions."""
    lead = x.shape[:-1]
    d = config.head_dim
    q = (x @ lp["wq"]).reshape(*lead, config.num_attention_heads, d)
    k = (x @ lp["wk"]).reshape(*lead, config.num_key_value_heads, d)
    v = (x @ lp["wv"]).reshape(*lead, config.num_key_value_heads, d)
    return q, k, v


def expert_mixer(config: NemotronHConfig, params, lp, h, valid,
                 router_dtype=jnp.float32):
    """The ``E`` layer over ``h`` [T, C] float32: (out [T, C] float32, sizes
    [held] int32 - the rows each held expert was given; rows that are not
    ``valid`` are given to none). The router reads the float32 norm of the
    float32 stream and scores in float32; ``router_dtype`` is the tests' (a
    bfloat16 router is what the comparison with the reference must catch)."""
    xf = rms_norm(h, lp["norm"], config.layer_norm_epsilon, jnp.float32)
    x = xf.astype(params["embed"].dtype)
    with jax.named_scope("nemotron/moe_route"):
        chosen, weights = moe.route(
            xf, lp["router"], lp["router_bias"], config.num_experts_per_tok,
            config.routed_scaling_factor, config.norm_topk_prob, router_dtype)
    with jax.named_scope("nemotron/moe_experts"):
        y, sizes = moe.expert_layer(x, chosen, weights, valid, lp["w_up"],
                                    lp["w_down"], config.experts_held[0])
    with jax.named_scope("nemotron/moe_shared"):
        up = jnp.dot(x, lp["shared_up"], preferred_element_type=jnp.float32)
        shared = jnp.dot(moe.relu2(up).astype(x.dtype), lp["shared_down"],
                         preferred_element_type=jnp.float32)
    return y + shared, sizes


def logits_of(config: NemotronHConfig, params, h):
    """Final hidden states [..., C] -> float32 logits [..., V]."""
    y = rms_norm(h, params["norm_f"], config.layer_norm_epsilon, params["lm_head"].dtype)
    return jnp.einsum("...c,vc->...v", y, params["lm_head"],
                      preferred_element_type=jnp.float32)


# --- the plain dense forward -------------------------------------------------


def forward(params, config: NemotronHConfig, ids, router_dtype=jnp.float32):
    """[B, T] token ids -> [B, T, V] float32 logits, every position, with
    nothing cached: each mixer over the whole sequence at once, a row at a
    time."""
    dtype = params["embed"].dtype

    def one(row):
        t = row.shape[0]
        live = jnp.ones((t,), bool)
        h = embed(params, row)
        sub = next(c for c in (config.chunk_size, 64, 16, 8, 4, 2, 1) if t % c == 0)
        for kind, lp in zip(config.hybrid_override_pattern, params["layers"]):
            if kind == EXPERT_LAYER:
                out, _ = expert_mixer(config, params, lp, h, live, router_dtype)
                h = h + out
                continue
            x = normed_input(config, params, lp, h)
            if kind == MAMBA_LAYER:
                z, xbc, dt_raw = ssm_in(config, lp, x)
                zeros = jnp.zeros((config.conv_kernel - 1, config.conv_dim), dtype)
                conv, _ = ssd.conv_chunk(xbc, zeros, lp["conv_w"], lp["conv_b"], t)
                xs, b, c, dt, a = ssm_inputs(config, lp, conv, dt_raw, live, dtype)
                y, _ = ssd.chunked(xs, b, c, dt, a, jnp.zeros(
                    (config.mamba_num_heads, config.mamba_head_dim,
                     config.ssm_state_size), jnp.float32), sub)
                out = ssm_out(lp, ssm_gate(config, lp, y, xs, z))
            else:
                out = causal_grouped_attention(*attention_qkv(config, lp, x)) @ lp["wo"]
            h = h + out.astype(jnp.float32)
        return logits_of(config, params, h)

    return jnp.stack([one(row) for row in ids])
