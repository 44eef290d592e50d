"""MiniCPM-SALA: a stack whose layers are of two kinds (``SalaConfig``).

``minicpm4`` layers are grouped-query softmax attention (2 KV heads under 32
query heads, no rotary) that attends a SELECTED set of 64-key blocks
(``ops/sparse_select.py``); ``lightning-attn`` layers are linear attention
with a per-head decay (``ops/linear_attention.py``), rotary positions, and a
norm over the joined heads. Both end in a sigmoid output gate. Around them:
RMSNorm, SwiGLU, no biases, an untied head, and the family's three scalings
(``scale_emb`` on the embedding, ``scale_depth / sqrt(published depth)`` on
every residual branch, ``hidden_size / dim_model_base`` under the logits).

Parameters are ONE DICT A LAYER, in stack order (``params["layers"][i]``):
layers of two kinds do not stack into one array, and a program that walks
the list never slices a stacked weight. Matrices and embeddings are stored
in bfloat16 and used as stored - no program casts a weight per step; norm
weights are float32.

Here: the parameters, the pieces every program of the family shares
(projections, norms, rotary, MLP), and the plain dense forward over whole
sequences that the tests and ``sample.py`` use. The serving step programs
(chunked prefill and decode through the paged pools and the per-slot state)
are in ``serving/sala_programs.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from gpt_2_distributed_tpu.config import LIGHTNING_MIXER, SalaConfig
from gpt_2_distributed_tpu.ops import linear_attention, sparse_select


# --- parameters -------------------------------------------------------------


def _layer_dims(config: SalaConfig, kind: str) -> tuple[int, int, int]:
    """(query heads, KV heads, head width) of a layer of ``kind``."""
    if kind == LIGHTNING_MIXER:
        return config.lightning_nh, config.lightning_nh, config.lightning_head_dim
    return config.num_attention_heads, config.num_key_value_heads, config.head_dim


def _init_layer(config: SalaConfig, kind: str, key, dtype):
    c, f = config.hidden_size, config.intermediate_size
    heads, kv_heads, d = _layer_dims(config, kind)
    ks = jax.random.split(key, 8)

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * config.initializer_range).astype(dtype)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    layer = {
        "ln1": ones(c), "ln2": ones(c),
        "wq": normal(ks[0], (c, heads * d)), "wk": normal(ks[1], (c, kv_heads * d)),
        "wv": normal(ks[2], (c, kv_heads * d)), "wg": normal(ks[3], (c, heads * d)),
        "wo": normal(ks[4], (heads * d, c)),
        "q_norm": ones(d), "k_norm": ones(d),
        "mlp_gate": normal(ks[5], (c, f)), "mlp_up": normal(ks[6], (c, f)),
        "mlp_down": normal(ks[7], (f, c)),
    }
    if kind == LIGHTNING_MIXER:
        layer["o_norm"] = ones(heads * d)
    return layer


@functools.partial(jax.jit, static_argnums=(0, 2))
def init_params(config: SalaConfig, key: jax.Array, dtype=jnp.bfloat16):
    """N(0, ``initializer_range``) for every matrix and embedding, norms at
    1: ``{"embed", "lm_head", "norm_f", "layers": [one dict a layer]}``, made
    on the device in one jitted call."""
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    shape = (config.vocab_size, config.hidden_size)
    scale = config.initializer_range
    return {
        "embed": (jax.random.normal(k_embed, shape, jnp.float32) * scale).astype(dtype),
        "lm_head": (jax.random.normal(k_head, shape, jnp.float32) * scale).astype(dtype),
        "norm_f": jnp.ones((config.hidden_size,), jnp.float32),
        "layers": [
            _init_layer(config, kind, jax.random.fold_in(key, i + 1), dtype)
            for i, kind in enumerate(config.mixer_types)
        ],
    }


# --- the pieces every program shares ---------------------------------------


def rms_norm(x, weight, eps: float, dtype=None):
    """RMSNorm in float32 over the last axis; the result in ``dtype``
    (``x``'s own unless given)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * weight).astype(dtype or x.dtype)


def rotary(x, positions, theta: float):
    """Rotary embedding (rotate-half) of ``x`` [..., T, H, d] at
    ``positions`` [..., T], computed in float32."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    return (xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(x.dtype)


def embed(config: SalaConfig, params, ids):
    """The residual stream's start, float32: it is carried in float32 from
    here to the head, and each branch reads it through a norm that hands the
    matmuls the weights' dtype."""
    return params["embed"].at[ids].get(mode="clip").astype(jnp.float32) * config.scale_emb


def normed_input(config: SalaConfig, params, lp, h):
    """``RMSNorm(h)`` as the mixer's matmuls take it."""
    return rms_norm(h, lp["ln1"], config.rms_norm_eps, params["embed"].dtype)


def branch_scale(config: SalaConfig) -> float:
    return config.scale_depth / math.sqrt(config.num_hidden_layers)


def qkv(config: SalaConfig, kind: str, lp, x, positions):
    """The layer's normed input ``x`` [..., T, C] -> q [..., T, H, d], k and v
    [..., T, KV, d], q and k RMS-normed per head, and in a lightning layer
    turned to their ``positions``."""
    heads, kv_heads, d = _layer_dims(config, kind)
    lead = x.shape[:-1]
    q = rms_norm((x @ lp["wq"]).reshape(*lead, heads, d), lp["q_norm"], config.rms_norm_eps)
    k = rms_norm((x @ lp["wk"]).reshape(*lead, kv_heads, d), lp["k_norm"], config.rms_norm_eps)
    v = (x @ lp["wv"]).reshape(*lead, kv_heads, d)
    if kind == LIGHTNING_MIXER:
        q = rotary(q, positions, config.rope_theta)
        k = rotary(k, positions, config.rope_theta)
    return q, k, v


def mixer_out(config: SalaConfig, kind: str, lp, x, o):
    """The mixer's heads ``o`` [..., T, H, d] (float32 from a lightning
    layer, unscaled) through the output norm (lightning), the gate and the
    out-projection."""
    lead = o.shape[:-2]
    if kind == LIGHTNING_MIXER:
        o = o / math.sqrt(config.lightning_head_dim)
        o = rms_norm(o.reshape(*lead, -1), lp["o_norm"], config.rms_norm_eps)
    o = o.reshape(*lead, -1).astype(x.dtype)
    return (o * jax.nn.sigmoid(x @ lp["wg"])) @ lp["wo"]


def mlp(config: SalaConfig, lp, h):
    y = rms_norm(h, lp["ln2"], config.rms_norm_eps, lp["mlp_gate"].dtype)
    return (jax.nn.silu(y @ lp["mlp_gate"]) * (y @ lp["mlp_up"])) @ lp["mlp_down"]


def add_branch(config: SalaConfig, h, out):
    """``h + scale_depth / sqrt(published depth) * out`` in float32."""
    return h + branch_scale(config) * out.astype(jnp.float32)


def logits_of(config: SalaConfig, params, h):
    """Final hidden states [..., C] -> float32 logits [..., V]."""
    y = rms_norm(h, params["norm_f"], config.rms_norm_eps, params["lm_head"].dtype)
    out = jnp.einsum("...c,vc->...v", y, params["lm_head"],
                     preferred_element_type=jnp.float32)
    return out / (config.hidden_size / config.dim_model_base)


# --- the plain dense forward -------------------------------------------------


def _dense_sparse_attention(config: SalaConfig, q, k, v, query_block: int = 512):
    """One sequence's sparse layer with every key in hand: [T, H, d] queries
    over [T, KV, d], the selected set as a mask on a dense softmax, queries
    ``query_block`` at a time."""
    sp = config.sparse
    t, heads, d = q.shape
    kv_heads = k.shape[1]
    n_blocks = -(-t // sp.block)
    pad = n_blocks * sp.block - t
    kc = sparse_select.window_means(jnp.pad(k, ((0, pad), (0, 0), (0, 0))), sp)
    key_block = jnp.arange(t) // sp.block
    outs = []
    for t0 in range(0, t, query_block):
        qb = q[t0:t0 + query_block].reshape(-1, kv_heads, heads // kv_heads, d)
        pos = jnp.arange(t0, t0 + qb.shape[0])
        keep = sparse_select.select_blocks(
            sparse_select.block_scores(qb, kc, pos, sp), pos, sp)       # [KV, Tq, B]
        keep = keep[:, :, key_block] & (jnp.arange(t)[None] <= pos[:, None])[None]
        s = jnp.einsum("tkgd,skd->kgts", qb, k,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", p.astype(v.dtype), v)
        outs.append(o.reshape(-1, heads, d))
    return jnp.concatenate(outs, axis=0)


def forward(params, config: SalaConfig, ids):
    """[B, T] token ids -> [B, T, V] float32 logits, every position, with
    nothing cached: the mixers over the whole sequence at once."""
    slopes = linear_attention.decay_slopes(config.lightning_nh)

    def one(row):
        t = row.shape[0]
        positions = jnp.arange(t)
        h = embed(config, params, row)
        sub = next(c for c in (256, 64, 16, 4, 2, 1) if t % c == 0)
        for kind, lp in zip(config.mixer_types, params["layers"]):
            x = normed_input(config, params, lp, h)
            q, k, v = qkv(config, kind, lp, x, positions)
            if kind == LIGHTNING_MIXER:
                d = config.lightning_head_dim
                o, _ = linear_attention.chunked(
                    q, k, v, jnp.ones((t,), bool),
                    jnp.zeros((config.lightning_nh, d, d), jnp.float32), slopes, sub)
            else:
                o = _dense_sparse_attention(config, q, k, v)
            h = add_branch(config, h, mixer_out(config, kind, lp, x, o))
            h = add_branch(config, h, mlp(config, lp, h))
        return logits_of(config, params, h)

    return jax.vmap(one)(ids)
