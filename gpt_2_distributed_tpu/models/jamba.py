"""Jamba: a stack whose every layer is a mixer and a gated MLP
(``JambaConfig``).

``h = E[ids]``; for each layer ``h = h + Mixer(RMSNorm(h))`` then ``h = h +
W_down(silu(W_gate x) * (W_up x))`` with ``x = RMSNorm(h)``; ``logits =
RMSNorm_f(h) E^T`` - the head is the embedding. No positions of any kind, no
biases but the convolution's and ``dt_proj``'s.

* ``M`` - a Mamba-1 mixer: ``[u | z] = W_in x``; a causal depthwise
  convolution over ``u`` and SiLU; ``[r | B | C] = W_x u``, each through an
  RMSNorm of its own (the family's three inner norms); a step for every
  channel from the low-rank ``r``, ``dt = softplus(W_dt r + b_dt)``; the
  recurrence of ``ops/selective_scan.py`` over a state of ``[N, D]``; ``D u``
  added, gated by ``silu(z)``; an out-projection.
* ``*`` - grouped-query softmax attention: 20 query heads over ONE KV head at
  the published sizes.

Parameters are ONE DICT A LAYER, in stack order (``params["layers"][i]``), as
``models/nemotron_h.py`` keeps them. Matrices and the embedding are stored in
bfloat16 and used as stored - no program casts or re-lays a weight. Vectors
(norms, ``A_log``, ``D``, ``dt_bias``, the convolution's kernel and bias) are
float32. ``A_log`` is ``[D, N]`` as published; the scan takes ``A`` the other
way round (channels last), a transpose of 82 k numbers a layer and dispatch.

Here: the parameters, the pieces every program of the family shares, and the
plain dense forward over whole sequences that the tests use. The serving step
programs are in ``serving/jamba_programs.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from gpt_2_distributed_tpu.config import MAMBA_LAYER, JambaConfig
from gpt_2_distributed_tpu.models.minicpm_sala import rms_norm
from gpt_2_distributed_tpu.ops import selective_scan, ssd
from gpt_2_distributed_tpu.ops.attention import causal_grouped_attention


# --- parameters -------------------------------------------------------------


def _init_layer(config: JambaConfig, kind: str, key, dtype):
    c, f = config.hidden_size, config.intermediate_size
    ks = jax.random.split(key, 12)

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * config.initializer_range).astype(dtype)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    mlp = {
        "ff_norm": ones(c),
        "w_gate": normal(ks[0], (c, f)), "w_up": normal(ks[1], (c, f)),
        "w_down": normal(ks[2], (f, c)),
    }
    if kind != MAMBA_LAYER:
        a = config.num_attention_heads * config.head_dim
        kv = config.num_key_value_heads * config.head_dim
        return {
            "norm": ones(c),
            "wq": normal(ks[3], (c, a)), "wk": normal(ks[4], (c, kv)),
            "wv": normal(ks[5], (c, kv)), "wo": normal(ks[6], (a, c)),
            **mlp,
        }
    d, n, r, k = config.d_inner, config.mamba_d_state, config.mamba_dt_rank, config.mamba_d_conv
    half = 1.0 / math.sqrt(k)
    lo, hi = math.log(config.time_step_min), math.log(config.time_step_max)
    dt = jnp.exp(jax.random.uniform(ks[9], (d,)) * (hi - lo) + lo)
    return {
        "norm": ones(c),
        "in_proj": normal(ks[3], (c, 2 * d)),
        "conv_w": jax.random.uniform(ks[4], (k, d), jnp.float32, -half, half),
        "conv_b": jax.random.uniform(ks[5], (d,), jnp.float32, -half, half),
        "x_proj": normal(ks[6], (d, r + 2 * n)),
        "dt_norm": ones(r), "b_norm": ones(n), "c_norm": ones(n),
        "dt_proj": normal(ks[7], (r, d)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),           # softplus^-1(dt)
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (d, n)),
        "D": ones(d),
        "out_proj": normal(ks[8], (d, c)),
        **mlp,
    }


@functools.partial(jax.jit, static_argnums=(0, 2))
def init_params(config: JambaConfig, key: jax.Array, dtype=jnp.bfloat16):
    """``{"embed", "norm_f", "layers": [one dict a layer]}``, made on the
    device in one jitted call: N(0, ``initializer_range``) for every matrix
    and the embedding, norms and ``D`` at 1, the convolution uniform in
    +-1/sqrt(kernel), ``A_log = log(1..N)`` in every channel, ``dt_bias`` the
    inverse softplus of log-uniform[``time_step_min``, ``time_step_max``]."""
    shape = (config.vocab_size, config.hidden_size)
    embed = jax.random.normal(jax.random.fold_in(key, 0), shape, jnp.float32)
    return {
        "embed": (embed * config.initializer_range).astype(dtype),
        "norm_f": jnp.ones((config.hidden_size,), jnp.float32),
        "layers": [
            _init_layer(config, kind, jax.random.fold_in(key, i + 1), dtype)
            for i, kind in enumerate(config.layer_kinds)
        ],
    }


# --- the pieces every program shares ---------------------------------------


def embed(params, ids):
    """The residual stream's start, float32: it is carried in float32 from
    here to the head, and each mixer and MLP reads it through its norm."""
    return params["embed"].at[ids].get(mode="clip").astype(jnp.float32)


def normed_input(config: JambaConfig, params, lp, h):
    """``RMSNorm(h)`` as the mixer's matmuls take it."""
    return rms_norm(h, lp["norm"], config.rms_norm_eps, params["embed"].dtype)


# The device trace's names (``benchmark/scopes.py`` reads them): the four
# projections of a Mamba mixer, and everything else of it - convolution, inner
# norms, ``dt``, the scan or the one-token update, the skip and the gate.
PROJ_SCOPE, SCAN_SCOPE = "jamba/mamba_proj", "jamba/sscan"


def mamba_in(config: JambaConfig, lp, x):
    """The in-projection of ``x`` [..., C]: what the convolution runs over
    ``u`` [..., D] and the gate ``z`` [..., D]."""
    with jax.named_scope(PROJ_SCOPE):
        uz = x @ lp["in_proj"]
    return uz[..., :config.d_inner], uz[..., config.d_inner:]


def scan_inputs(config: JambaConfig, lp, conv_out, live, inner_norms: bool = True):
    """From the convolution's float32 output [..., D]: the scan's input ``u =
    silu(conv)`` float32, ``B`` and ``C`` [..., N] float32, the step ``dt =
    softplus(W_dt r + b_dt)`` [..., D] float32 - 0 wherever ``live`` [...] is
    False, which leaves the state as it was - and ``A`` [N, D]. ``r``, ``B``
    and ``C`` are each normed in float32 before their use; ``inner_norms`` is
    the tests' (a mixer without them is what the comparison with the
    reference must catch). Call it outside any scope: it names its own."""
    r, n = config.mamba_dt_rank, config.mamba_d_state
    with jax.named_scope(SCAN_SCOPE):
        u = jax.nn.silu(conv_out)
    with jax.named_scope(PROJ_SCOPE):
        rbc = jnp.dot(u.astype(lp["x_proj"].dtype), lp["x_proj"],
                      preferred_element_type=jnp.float32)
    with jax.named_scope(SCAN_SCOPE):
        low, b, c = rbc[..., :r], rbc[..., r:r + n], rbc[..., r + n:]
        if inner_norms:
            eps = config.rms_norm_eps
            low, b, c = (rms_norm(low, lp["dt_norm"], eps),
                         rms_norm(b, lp["b_norm"], eps), rms_norm(c, lp["c_norm"], eps))
    with jax.named_scope(PROJ_SCOPE):
        dt = jnp.dot(low.astype(lp["dt_proj"].dtype), lp["dt_proj"],
                     preferred_element_type=jnp.float32)
    with jax.named_scope(SCAN_SCOPE):
        dt = jnp.where(live[..., None], jax.nn.softplus(dt + lp["dt_bias"]), 0.0)
        return u, b, c, dt, -jnp.exp(lp["A_log"]).T


def mamba_gate(lp, y, u, z):
    """``(y + D u) * silu(z)`` in float32, in ``z``'s dtype."""
    return ((y + lp["D"] * u) * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)


def mamba_out(lp, y):
    with jax.named_scope(PROJ_SCOPE):
        return y @ lp["out_proj"]


def attention_qkv(config: JambaConfig, lp, x):
    """``x`` [..., C] -> q [..., H, d], k and v [..., KV, d]: no norm, no
    rotary - the family has no positions."""
    lead = x.shape[:-1]
    d = config.head_dim
    q = (x @ lp["wq"]).reshape(*lead, config.num_attention_heads, d)
    k = (x @ lp["wk"]).reshape(*lead, config.num_key_value_heads, d)
    v = (x @ lp["wv"]).reshape(*lead, config.num_key_value_heads, d)
    return q, k, v


def mlp(config: JambaConfig, params, lp, h):
    """The layer's second half over the float32 stream ``h`` [..., C]:
    ``W_down(silu(W_gate x) * (W_up x))`` of ``x = RMSNorm(h)``, float32."""
    x = rms_norm(h, lp["ff_norm"], config.rms_norm_eps, params["embed"].dtype)
    with jax.named_scope("jamba/mlp"):
        gate = jnp.dot(x, lp["w_gate"], preferred_element_type=jnp.float32)
        up = jnp.dot(x, lp["w_up"], preferred_element_type=jnp.float32)
        return jnp.dot((jax.nn.silu(gate) * up).astype(x.dtype), lp["w_down"],
                       preferred_element_type=jnp.float32)


def logits_of(config: JambaConfig, params, h):
    """Final hidden states [..., C] -> float32 logits [..., V] over the tied
    embedding."""
    y = rms_norm(h, params["norm_f"], config.rms_norm_eps, params["embed"].dtype)
    return jnp.einsum("...c,vc->...v", y, params["embed"],
                      preferred_element_type=jnp.float32)


# --- the plain dense forward -------------------------------------------------


def forward(params, config: JambaConfig, ids, inner_norms: bool = True,
            state_dtype=jnp.float32):
    """[B, T] token ids -> [B, T, V] float32 logits, every position, with
    nothing cached: each mixer over the whole sequence at once, a row at a
    time. ``inner_norms`` and ``state_dtype`` are the tests': what the
    comparison with the reference must tell from the model as published."""
    dtype = params["embed"].dtype

    def one(row):
        t = row.shape[0]
        live = jnp.ones((t,), bool)
        h = embed(params, row)
        for kind, lp in zip(config.layer_kinds, params["layers"]):
            x = normed_input(config, params, lp, h)
            if kind == MAMBA_LAYER:
                u_raw, z = mamba_in(config, lp, x)
                zeros = jnp.zeros((config.mamba_d_conv - 1, config.d_inner), dtype)
                conv, _ = ssd.conv_chunk(u_raw, zeros, lp["conv_w"], lp["conv_b"], t)
                u, b, c, dt, a = scan_inputs(config, lp, conv, live, inner_norms)
                y, _ = selective_scan.chunked(
                    u, dt, a, b, c, jnp.zeros(a.shape, state_dtype))
                out = mamba_out(lp, mamba_gate(lp, y.astype(jnp.float32), u, z))
            else:
                out = causal_grouped_attention(*attention_qkv(config, lp, x)) @ lp["wo"]
            h = h + out.astype(jnp.float32)
            h = h + mlp(config, params, lp, h)
        return logits_of(config, params, h)

    return jnp.stack([one(row) for row in ids])
