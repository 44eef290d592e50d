"""The plain reference: GPT-2 in straightforward ``jax.numpy`` and float32.

Follows the published description (Radford et al. 2019; the Hugging Face
``config.json`` keys): learned token and position embeddings, pre-LayerNorm
blocks of causal multi-head attention and a 4x tanh-GELU MLP, a final
LayerNorm, the output head tied to the token embedding, token-mean cross
entropy; AdamW with decoupled weight decay on every parameter.

Nothing here imports the program under test. The parameter tree's names and
stacked ``[n_layer, ...]`` layout are the program's input format, so the same
tree feeds both; the values come from ``make_weights`` (below), which the
benchmark calls once for the program and again, after the program's state is
freed, for the reference.

``matmul`` is the hook the control uses: ``control_matmul`` (``fp8_matmul``)
computes every weight matmul (and the head) on 8-bit floating-point
operands, the nearest precision below the bfloat16 the configurations state.
(Per-row-scaled int8, the other 8-bit choice, turned out as exact as the
program's bfloat16 and cannot serve as a control: PERF.md, Findings, PR 24.)

This is the family ``gpt2``: a configuration file that says ``"family":
"gpt2"`` gets this module as its reference (``harness.attach_family``). What
every family's reference exports, under these names (``FAMILY_CONTRACT`` in
``benchmark/harness.py``): ``sizes_of``, ``make_weights``, ``control_matmul``
and ``attention_shapes``; for serving ``serving_reference`` and
``forward_flops_per_token``; for training ``train_steps``, ``leaf_norms``,
``train_flops_per_token`` and ``ADAM_B1``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8

SIZE_KEYS = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head")


def sizes_of(config: dict) -> dict:
    """The model's sizes out of a configuration file's object."""
    sizes = {k: int(config[k]) for k in SIZE_KEYS}
    sizes["layer_norm_epsilon"] = float(config.get("layer_norm_epsilon", 1e-5))
    sizes["initializer_range"] = float(config.get("initializer_range", 0.02))
    return sizes


def _frozen(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


# --- operations from shapes: what the algorithm needs, never what an
# implementation happens to do; recomputed operations do not count ----------


def matmul_params(sizes: dict) -> int:
    """Parameters that take part in matmuls: per block qkv (3C^2), attention
    projection (C^2) and MLP (8C^2), plus the tied head's [C, V] projection.
    Embedding lookups are gathers, not operations."""
    c, l, v = sizes["n_embd"], sizes["n_layer"], sizes["vocab_size"]
    return l * 12 * c * c + c * v


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    """Forward and backward: 6 per matmul parameter, and the attention
    score and value matmuls (2 * 2*C*T forward, twice that backward) in
    each layer, counted over the full square as the usual convention does."""
    c, l = sizes["n_embd"], sizes["n_layer"]
    return 6.0 * matmul_params(sizes) + 12.0 * l * c * seq_len


def forward_flops_per_token(sizes: dict, context: float) -> float:
    """One forward pass of one token that attends over ``context`` keys."""
    c, l = sizes["n_embd"], sizes["n_layer"]
    return 2.0 * matmul_params(sizes) + 4.0 * l * c * context


def attention_shapes(sizes: dict) -> dict:
    """What a kernel's roofline needs of the model: the layers that hold a
    KV cache, the query heads, the KV heads and the head width. Every layer
    of GPT-2 is full multi-head attention."""
    heads = sizes["n_head"]
    return {"kv_layers": sizes["n_layer"], "heads": heads, "kv_heads": heads,
            "head_dim": sizes["n_embd"] // heads}


@functools.partial(jax.jit, static_argnums=(0,))
def _make_weights(frozen_sizes: tuple, key):
    s = dict(frozen_sizes)
    c, l, v, p, h = (s["n_embd"], s["n_layer"], s["vocab_size"],
                     s["n_positions"], s["n_head"])
    std = s["initializer_range"]
    k_wte, k_wpe, k_qkv, k_aproj, k_fc, k_mproj = jax.random.split(key, 6)

    def normal(k, shape):
        # The barrier keeps XLA from folding the scale into the sampler's
        # own constants, which would move some weights by one ulp from
        # what a plain ``normal(...) * std`` gives.
        return jax.lax.optimization_barrier(
            jax.random.normal(k, shape, dtype=jnp.float32)) * std

    zeros = lambda shape: jnp.zeros(shape, jnp.float32)
    ones = lambda shape: jnp.ones(shape, jnp.float32)
    return {
        "wte": normal(k_wte, (v, c)),
        "wpe": normal(k_wpe, (p, c)),
        "block": {
            "ln1_scale": ones((l, c)),
            "ln1_bias": zeros((l, c)),
            "attn_qkv_w": normal(k_qkv, (l, c, 3, h, c // h)),
            "attn_qkv_b": zeros((l, 3, h, c // h)),
            "attn_proj_w": normal(k_aproj, (l, c, c)),
            "attn_proj_b": zeros((l, c)),
            "ln2_scale": ones((l, c)),
            "ln2_bias": zeros((l, c)),
            "mlp_fc_w": normal(k_fc, (l, c, 4 * c)),
            "mlp_fc_b": zeros((l, 4 * c)),
            "mlp_proj_w": normal(k_mproj, (l, 4 * c, c)),
            "mlp_proj_b": zeros((l, c)),
        },
        "ln_f_scale": ones((c,)),
        "ln_f_bias": zeros((c,)),
    }


def make_weights(sizes: dict, seed: int):
    """Float32 weights on the default device, in one jitted call from the
    seed: N(0, initializer_range) for every matrix and embedding, zero
    biases, LayerNorm at (1, 0) - GPT-2's published initialisation."""
    return _make_weights(_frozen(sizes), jax.random.PRNGKey(seed))


# --- the forward pass --------------------------------------------------------


def plain_matmul(x, w):
    return x @ w


def _fp8(a, axis, dtype):
    """Scale each row along ``axis`` to the type's range and round-trip
    through it."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    return (a / scale).astype(dtype).astype(a.dtype) * scale


@jax.custom_vjp
def fp8_matmul(x, w):
    """The control's matmul, as an fp8 training recipe computes it: operands
    in ``float8_e4m3fn`` forward (3 bits of mantissa), the incoming gradient
    in ``float8_e5m2`` backward (2 bits), each scaled per row; products
    accumulated exactly."""
    return _fp8(x, -1, jnp.float8_e4m3fn) @ _fp8(w, 0, jnp.float8_e4m3fn)


def _fp8_matmul_fwd(x, w):
    return fp8_matmul(x, w), (x, w)


def _fp8_matmul_bwd(saved, dy):
    x, w = saved
    dyq = _fp8(dy, -1, jnp.float8_e5m2)
    dx = dyq @ _fp8(w, 0, jnp.float8_e4m3fn).T
    x2 = _fp8(x, -1, jnp.float8_e4m3fn).reshape(-1, x.shape[-1])
    dw = x2.T @ dyq.reshape(-1, dy.shape[-1])
    return dx, dw


fp8_matmul.defvjp(_fp8_matmul_fwd, _fp8_matmul_bwd)

control_matmul = fp8_matmul   # the nearest precision below the stated bfloat16


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden(w, sizes: dict, idx, matmul=plain_matmul):
    """[B, T] token ids -> [B, T, C] final hidden states (after ln_f)."""
    b, t = idx.shape
    c, h = sizes["n_embd"], sizes["n_head"]
    d = c // h
    eps = sizes["layer_norm_epsilon"]
    x = w["wte"][idx] + w["wpe"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, bp):
        y = _layer_norm(x, bp["ln1_scale"], bp["ln1_bias"], eps)
        qkv = matmul(y, bp["attn_qkv_w"].reshape(c, 3 * c)) \
            + bp["attn_qkv_b"].reshape(3 * c)
        q, k, v = (a.reshape(b, t, h, d) for a in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + matmul(o.reshape(b, t, c), bp["attn_proj_w"]) + bp["attn_proj_b"]
        y = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"], eps)
        y = _gelu_tanh(matmul(y, bp["mlp_fc_w"]) + bp["mlp_fc_b"])
        x = x + matmul(y, bp["mlp_proj_w"]) + bp["mlp_proj_b"]
        return x, None

    x, _ = jax.lax.scan(block, x, w["block"])
    return _layer_norm(x, w["ln_f_scale"], w["ln_f_bias"], eps)


def logits_of(w, sizes: dict, idx, matmul=plain_matmul):
    """[B, T] token ids -> [B, T, V] float32 logits of the tied head."""
    return matmul(hidden(w, sizes, idx, matmul), w["wte"].T)


def _summed_nll(w, sizes, idx, labels, matmul):
    logp = jax.nn.log_softmax(logits_of(w, sizes, idx, matmul), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _nll_and_grad(frozen_sizes, w, idx, labels, matmul):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_summed_nll)(
            w, dict(frozen_sizes), idx, labels, matmul)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits_jit(frozen_sizes, w, idx, matmul):
    with jax.default_matmul_precision("highest"):
        return logits_of(w, dict(frozen_sizes), idx, matmul)


def logits(w, sizes: dict, idx, matmul=plain_matmul):
    return _logits_jit(_frozen(sizes), w, jnp.asarray(idx, jnp.int32), matmul)


def serving_reference(sizes: dict, seed: int):
    """The serving check's reference: ``logits(ids, matmul=plain_matmul)``
    over [B, T] token ids, with the seed's weights. This family holds one
    whole weight tree, made once here and kept for as long as the returned
    function lives; a family too large for that makes them layer by layer
    inside the call."""
    w = make_weights(sizes, seed)
    return functools.partial(logits, w, sizes)


# --- training: loss, gradient, AdamW ----------------------------------------


def loss_and_grad(w, sizes: dict, x, y, rows_per_block: int,
                  matmul=plain_matmul):
    """Token-mean loss and its gradient over ``x, y`` of shape [rows, T],
    computed ``rows_per_block`` rows at a time so that it fits."""
    rows, t = x.shape
    total, grads = 0.0, None
    for r in range(0, rows, rows_per_block):
        nll, g = _nll_and_grad(
            _frozen(sizes), w, jnp.asarray(x[r:r + rows_per_block]),
            jnp.asarray(y[r:r + rows_per_block]), matmul)
        total = total + nll
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    n = rows * t
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)


@jax.jit
def _adamw(w, grads, mu, nu, count, lr, weight_decay):
    count = count + 1
    mu = jax.tree_util.tree_map(
        lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree_util.tree_map(
        lambda n, g: ADAM_B2 * n + (1 - ADAM_B2) * g * g, nu, grads)
    c1 = 1 - ADAM_B1 ** count
    c2 = 1 - ADAM_B2 ** count

    def leaf(p, m, n):
        step = (m / c1) / (jnp.sqrt(n / c2) + ADAM_EPS) + weight_decay * p
        return p - lr * step

    return jax.tree_util.tree_map(leaf, w, mu, nu), mu, nu, count


def train_steps(sizes: dict, seed: int, batches, *, lr: float,
                weight_decay: float, rows_per_block: int,
                matmul=plain_matmul, weights=None):
    """Follow optimizer steps from the seed's weights over ``batches`` (a
    list of ``(x, y)`` int arrays of shape [rows, T], one pair a step).

    Returns ``losses`` (one per step), ``first_grad`` (the first step's
    gradient tree) and ``moved`` (final weights minus the seed's)."""
    w0 = make_weights(sizes, seed) if weights is None else weights
    w = w0
    zeros = jax.tree_util.tree_map(jnp.zeros_like, w)
    mu, nu, count = zeros, zeros, jnp.zeros((), jnp.float32)
    losses, first_grad = [], None
    for x, y in batches:
        loss, grads = loss_and_grad(w, sizes, x, y, rows_per_block, matmul)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = grads
        w, mu, nu, count = _adamw(w, grads, mu, nu, count, lr, weight_decay)
    moved = jax.tree_util.tree_map(jnp.subtract, w, w0)
    return losses, first_grad, moved


# --- views shared by both sides of a comparison ------------------------------


@jax.jit
def _leaf_norms(tree):
    def norm(a, axes):
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)), axis=axes))

    out = {}
    for name, a in tree["block"].items():
        if name.startswith("attn_qkv"):
            # q, k and v are leaves of their own: under softmax the key's
            # bias has no gradient, and must not hide behind q's and v's.
            axis3 = 2 if name.endswith("_w") else 1
            axes = tuple(i for i in range(1, a.ndim) if i != axis3)
            out[name] = norm(a, axes)                       # [L, 3]
        else:
            out[name] = norm(a, tuple(range(1, a.ndim)))    # [L]
    for name in ("wte", "wpe", "ln_f_scale", "ln_f_bias"):
        out[name] = norm(tree[name], None)
    return out


def leaf_norms(tree) -> dict[str, float]:
    """L2 norm of every leaf of a parameter-shaped tree, one layer and one
    of q/k/v at a time: ``block.3.attn_qkv_b.k`` -> norm."""
    flat = {}
    for name, a in jax.device_get(_leaf_norms(tree)).items():
        a = np.asarray(a)
        if a.ndim == 0:
            flat[name] = float(a)
        elif a.ndim == 1:
            for i, v in enumerate(a):
                flat[f"block.{i}.{name}"] = float(v)
        else:
            for i, row in enumerate(a):
                for part, v in zip("qkv", row):
                    flat[f"block.{i}.{name}.{part}"] = float(v)
    return flat
