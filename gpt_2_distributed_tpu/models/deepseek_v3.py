"""DeepSeek-V3's block (``DeepseekV3Config``): multi-head latent attention
(MLA) and, after the leading dense layers, sparse SwiGLU experts beside a
shared one.

``h = E[ids]``; per layer ``h = h + MLA(RMSNorm(h))``, then ``h = h +
FFN(RMSNorm(h))``; ``logits = W_lm RMSNorm(h)``, the head untied.

* MLA (``H`` heads; ``r`` the latent rank, ``dn`` / ``dr`` the unrotated and
  rotated widths of a query-key head, ``dv`` a value head's): ``[q_nope |
  q_pe] = x W_q`` a head; ``[c | k_pe] = x W_kva``, ``k_pe`` ONE rotary key
  that every head shares; ``ĉ = RMSNorm(c)``; ``[k_nope | v] = ĉ W_kvb`` a
  head; RoPE on ``q_pe`` and ``k_pe``, the pairs ``(2i, 2i + 1)`` rotated by
  ``pos * theta^(-2i / dr)``; ``s = (q_nope . k_nope + q_pe . k_pe) /
  sqrt(dn + dr)``, causal softmax, ``o = sum p v``; ``out = concat(o) W_o``.
* What the serving programs cache is the row ``[ĉ ; RoPE(k_pe)]``, ``r + dr``
  values a position and layer for all the heads. A prefill chunk decompresses
  the keys and values of the rows it attends tile by tile (the expanded form,
  ``paged_mla_prefill``); a decode step moves the up-projections to the
  query's side instead (the absorbed form: ``q̃ = [W_UK^T q_nope ; q_pe]``
  scores the rows themselves, ``u = sum p ĉ`` and ``o = W_UV u``;
  ``paged_mla_attention``). The two are the same mathematics.
* FFN: ``W_down (silu(W_gate y) * W_up y)`` in a dense layer; in an expert
  layer a float32 sigmoid router over ALL the published experts (top-k of
  score + bias, normalised over the chosen, scaled), SwiGLU experts of which
  this holder computes its own (``config.experts_held``, ``ops/moe.py``) and
  leaves out the rest, and the shared experts as one SwiGLU of
  ``n_shared_experts`` times the expert width, which every holder computes.

Parameters: ``params["dense"]`` is one dict a leading dense layer;
``params["moe"]`` holds the expert layers' dicts STACKED on a leading axis
(the step programs scan over them: one traced layer, however deep the stack).
Matrices and embeddings are stored in bfloat16 and used as stored; gate and
up are one matrix ``[C, 2F]`` (the gate's columns first), a routed expert's
``[2F, C]`` and its down-projection ``[F, C]``, the layout the grouped matmul
reads as it lies. Norms and the router's bias are float32.

Here: the parameters, the pieces every program of the family shares, and the
plain (expanded, uncached) forward over whole sequences that the tests use.
The serving step programs are in ``serving/deepseek_v3_programs.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from gpt_2_distributed_tpu.config import DENSE_LAYER, DeepseekV3Config
from gpt_2_distributed_tpu.models.minicpm_sala import rms_norm
from gpt_2_distributed_tpu.ops import moe

SCOPE = "deepseek_v3/"


# --- parameters -------------------------------------------------------------


def _init_layer(config: DeepseekV3Config, kind: str, key, dtype):
    c, h, r = config.hidden_size, config.num_attention_heads, config.kv_lora_rank
    ks = jax.random.split(key, 10)

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * config.initializer_range).astype(dtype)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    layer = {
        "attn_norm": ones(c),
        "wq": normal(ks[0], (c, h * config.qk_head_dim)),
        "wkv_a": normal(ks[1], (c, config.latent_dim)),
        "kv_norm": ones(r),
        "wkv_b": normal(ks[2], (r, h * (config.qk_nope_head_dim + config.v_head_dim))),
        "wo": normal(ks[3], (h * config.v_head_dim, c)),
        "mlp_norm": ones(c),
    }
    if kind == DENSE_LAYER:
        f = config.intermediate_size
        layer.update(gate_up=normal(ks[4], (c, 2 * f)), down=normal(ks[5], (f, c)))
        return layer
    f = config.moe_intermediate_size
    fs = config.n_shared_experts * f
    held = jnp.arange(*config.experts_held)

    def experts(k, shape):       # expert e's matrix comes of e, whoever holds it
        return jax.vmap(lambda e: normal(jax.random.fold_in(k, e), shape))(held)

    layer.update(
        router=normal(ks[4], (c, config.n_routed_experts)),
        router_bias=jnp.zeros((config.n_routed_experts,), jnp.float32),
        w_gate_up=experts(ks[5], (2 * f, c)), w_down=experts(ks[6], (f, c)),
        shared_gate_up=normal(ks[7], (c, 2 * fs)), shared_down=normal(ks[8], (fs, c)))
    return layer


@functools.partial(jax.jit, static_argnums=(0, 2))
def init_params(config: DeepseekV3Config, key: jax.Array, dtype=jnp.bfloat16):
    """``{"embed", "lm_head", "norm_f", "dense": [a dict a dense layer],
    "moe": the expert layers' dicts stacked}`` (``moe`` only where the stack
    holds expert layers), made on the device in one jitted call: N(0,
    ``initializer_range``) for every matrix and embedding, norms at 1, the
    router's bias at 0. Layer ``i`` of the stack comes of ``fold_in(key, i +
    1)``."""
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    shape = (config.vocab_size, config.hidden_size)
    scale = config.initializer_range
    layers = [_init_layer(config, kind, jax.random.fold_in(key, i + 1), dtype)
              for i, kind in enumerate(config.layer_kinds)]
    params = {
        "embed": (jax.random.normal(k_embed, shape, jnp.float32) * scale).astype(dtype),
        "lm_head": (jax.random.normal(k_head, shape, jnp.float32) * scale).astype(dtype),
        "norm_f": jnp.ones((config.hidden_size,), jnp.float32),
        "dense": [lp for lp, kind in zip(layers, config.layer_kinds)
                  if kind == DENSE_LAYER],
    }
    moe_layers = [lp for lp, kind in zip(layers, config.layer_kinds)
                  if kind != DENSE_LAYER]
    if moe_layers:
        params["moe"] = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *moe_layers)
    return params


def layers_in_order(config: DeepseekV3Config, params):
    """``(kind, layer dict)`` of every layer of the stack, in order (an
    expert layer's slice of the stacked tree)."""
    n_dense = len(params["dense"])
    for i, kind in enumerate(config.layer_kinds):
        if kind == DENSE_LAYER:
            yield kind, params["dense"][i]
        else:
            yield kind, jax.tree_util.tree_map(lambda a: a[i - n_dense], params["moe"])


# --- the pieces every program shares ---------------------------------------


def embed(params, ids):
    """The residual stream's start, float32: it is carried in float32 from
    here to the head."""
    return params["embed"].at[ids].get(mode="clip").astype(jnp.float32)


def rope(x, pos, theta: float):
    """RoPE of ``x`` [..., d] at ``pos`` (broadcast against ``x``'s leading
    axes), the pairs ``(2i, 2i + 1)`` rotated in place by ``pos *
    theta^(-2i / d)``, in float32."""
    d = x.shape[-1]
    angle = pos[..., None].astype(jnp.float32) * (
        theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def mla_project(config: DeepseekV3Config, params, lp, h, pos):
    """From the stream ``h`` [..., C] at positions ``pos`` [...]: the queries
    ``q_nope`` [..., H, dn] and ``q_pe`` [..., H, dr] (rotated), in the
    matmuls' dtype, and the latent row ``[RMSNorm(c) ; RoPE(k_pe)]`` [..., r
    + dr] float32 that the cache keeps."""
    dtype = params["embed"].dtype
    with jax.named_scope(SCOPE + "mla_project"):
        x = rms_norm(h, lp["attn_norm"], config.rms_norm_eps, dtype)
        q = (x @ lp["wq"]).reshape(*x.shape[:-1], config.num_attention_heads,
                                   config.qk_head_dim)
        kv_a = jnp.dot(x, lp["wkv_a"], preferred_element_type=jnp.float32)
        r, dn = config.kv_lora_rank, config.qk_nope_head_dim
        latent = rms_norm(kv_a[..., :r], lp["kv_norm"], config.rms_norm_eps)
        k_pe = rope(kv_a[..., r:], pos, config.rope_theta)
        q_pe = rope(q[..., dn:], pos[..., None], config.rope_theta).astype(dtype)
    return q[..., :dn], q_pe, jnp.concatenate([latent, k_pe], axis=-1)


def up_projections(config: DeepseekV3Config, lp):
    """``W_kvb`` as the latent's up-projections to keys ``W_UK`` [r, H, dn]
    and to values ``W_UV`` [r, H, dv]."""
    dn = config.qk_nope_head_dim
    w = lp["wkv_b"].reshape(config.kv_lora_rank, config.num_attention_heads, -1)
    return w[..., :dn], w[..., dn:]


def absorbed_query(config: DeepseekV3Config, lp, q_nope, q_pe):
    """The absorbed form's query ``[W_UK^T q_nope ; q_pe]`` [..., H, r + dr],
    which scores a cached latent row as it lies."""
    w_uk, _ = up_projections(config, lp)
    with jax.named_scope(SCOPE + "mla_project"):
        q_lat = jnp.einsum("...hd,rhd->...hr", q_nope, w_uk,
                           preferred_element_type=jnp.float32)
        return jnp.concatenate([q_lat.astype(q_pe.dtype), q_pe], axis=-1)


def mla_out(config: DeepseekV3Config, lp, o, absorbed: bool = False):
    """``concat_h(o_h) W_o`` [..., C] float32 from ``o`` [..., H, dv]; or,
    ``absorbed``, from ``u`` [..., H, r]: ``o_h = W_UV,h u_h`` first."""
    with jax.named_scope(SCOPE + "mla_out"):
        if absorbed:
            _, w_uv = up_projections(config, lp)
            o = jnp.einsum("...hr,rhv->...hv", o, w_uv,
                           preferred_element_type=jnp.float32).astype(lp["wo"].dtype)
        return jnp.dot(o.reshape(*o.shape[:-2], -1), lp["wo"],
                       preferred_element_type=jnp.float32)


def swiglu_mlp(x, gate_up, down):
    """``down(silu(x W_gate) * x W_up)`` float32, gate and up one matrix."""
    act = moe.swiglu(jnp.dot(x, gate_up, preferred_element_type=jnp.float32))
    return jnp.dot(act.astype(x.dtype), down, preferred_element_type=jnp.float32)


def feed_forward(config: DeepseekV3Config, params, lp, kind: str, h, valid,
                 router_dtype=jnp.float32):
    """The layer's FFN over ``h`` [T, C] float32: (out [T, C] float32, sizes
    [held] int32 - the rows each held expert was given, None in a dense
    layer; rows that are not ``valid`` are given to none). The router reads
    the float32 norm of the float32 stream and scores in float32;
    ``router_dtype`` is the tests'."""
    xf = rms_norm(h, lp["mlp_norm"], config.rms_norm_eps, jnp.float32)
    x = xf.astype(params["embed"].dtype)
    if kind == DENSE_LAYER:
        with jax.named_scope(SCOPE + "mlp"):
            return swiglu_mlp(x, lp["gate_up"], lp["down"]), None
    with jax.named_scope(SCOPE + "moe_route"):
        chosen, weights = moe.route(
            xf, lp["router"], lp["router_bias"], config.num_experts_per_tok,
            config.routed_scaling_factor, config.norm_topk_prob, router_dtype)
    with jax.named_scope(SCOPE + "moe_experts"):
        y, sizes = moe.expert_layer(x, chosen, weights, valid, lp["w_gate_up"],
                                    lp["w_down"], config.experts_held[0], gated=True)
    with jax.named_scope(SCOPE + "moe_shared"):
        shared = swiglu_mlp(x, lp["shared_gate_up"], lp["shared_down"])
    return y + shared, sizes


def logits_of(config: DeepseekV3Config, params, h):
    """Final hidden states [..., C] -> float32 logits [..., V]."""
    y = rms_norm(h, params["norm_f"], config.rms_norm_eps, params["lm_head"].dtype)
    return jnp.einsum("...c,vc->...v", y, params["lm_head"],
                      preferred_element_type=jnp.float32)


# --- the plain expanded forward ----------------------------------------------


def expanded_attention(config: DeepseekV3Config, lp, q_nope, q_pe, row):
    """The published form over one whole sequence, nothing cached: every
    head's key and value decompressed from the latent rows ``row`` [T, r +
    dr], causal softmax in float32. Returns o [T, H, dv]."""
    r = config.kv_lora_rank
    w_uk, w_uv = up_projections(config, lp)
    dtype = q_nope.dtype
    latent, k_pe = row[:, :r].astype(dtype), row[:, r:].astype(dtype)
    k_nope = jnp.einsum("sr,rhd->shd", latent, w_uk,
                        preferred_element_type=jnp.float32).astype(dtype)
    v = jnp.einsum("sr,rhd->shd", latent, w_uv,
                   preferred_element_type=jnp.float32).astype(dtype)
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope, preferred_element_type=jnp.float32)
         + jnp.einsum("thd,sd->hts", q_pe, k_pe, preferred_element_type=jnp.float32))
    s = s / jnp.sqrt(jnp.float32(config.qk_head_dim))
    t = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1).astype(dtype), v)


def forward(params, config: DeepseekV3Config, ids, router_dtype=jnp.float32):
    """[B, T] token ids -> [B, T, V] float32 logits, every position, with
    nothing cached: each layer over the whole sequence at once, a row at a
    time."""
    def one(row):
        t = row.shape[0]
        pos = jnp.arange(t)
        h = embed(params, row)
        for kind, lp in layers_in_order(config, params):
            q_nope, q_pe, latent = mla_project(config, params, lp, h, pos)
            o = expanded_attention(config, lp, q_nope, q_pe, latent)
            h = h + mla_out(config, lp, o)
            out, _ = feed_forward(config, params, lp, kind, h, jnp.ones((t,), bool),
                                  router_dtype)
            h = h + out
        return logits_of(config, params, h)

    return jnp.stack([one(row) for row in ids])
