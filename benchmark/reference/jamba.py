"""The plain reference of the family ``jamba``: Jamba in straightforward
``jax.numpy`` and float32, straight from the equations.

A configuration file that says ``"family": "jamba"`` gets this module as its
reference (``harness.attach_family``). Nothing here imports the program under
test. The parameter tree's names are the program's input format (one dict a
layer, in stack order), so the same tree feeds both; the values come from
``make_weights``.

**The equations** (``C`` hidden; every Linear without bias but ``dt_proj``'s;
RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``):

* Stack: ``h = E[ids]`` (no scaling, no positions of any kind); for each layer
  ``h = h + Mixer(RMSNorm(h))`` then ``h = h + W_down(silu(W_gate x) * (W_up
  x))``, ``x = RMSNorm_ff(h)``; ``logits = RMSNorm_f(h) E^T``, the head tied.
  Layer ``i`` is an attention layer where ``i % attn_layer_period ==
  attn_layer_offset``, a Mamba layer otherwise.
* Mamba-1 mixer (``D = mamba_expand C`` channels, state ``N``, ``dt_rank``
  ``R``, kernel ``K``): ``[u | z] = W_in x``; ``u = silu(conv1d_causal_
  depthwise(u) + b)``; ``[r | B | C] = W_x u``; ``r, B, C = RMSNorm(r),
  RMSNorm(B), RMSNorm(C)`` (the family's three inner norms); ``dt =
  softplus(W_dt r + b_dt)``; ``A = -exp(A_log)`` ``[D, N]``; per channel ``d``
  and index ``n``: ``S_t = exp(dt_t[d] A[d, n]) S_{t-1} + dt_t[d] u_t[d]
  B_t[n]``, ``y_t[d] = sum_n S_t[d, n] C_t[n] + D[d] u_t[d]``; ``out =
  W_out(y * silu(z))``. The recurrence is a plain scan over tokens.
* Attention mixer: ``q = W_q x`` (``num_attention_heads`` heads), ``k, v = W_k
  x, W_v x`` (``num_key_value_heads`` heads), causal softmax at ``1 /
  sqrt(head_dim)``, ``out = W_o o``. No rotary, no window.

**Assumed** (each is written into the configuration file's ``assumed``):
``head_dim = hidden_size / num_attention_heads``; the layer order above (the
family's convention for ``attn_layer_period`` / ``attn_layer_offset``); the
dense feed-forward in every layer (``num_experts`` 1); no positions; N(0, 0.02)
matrices and embedding; norms and ``D`` at 1; the convolution's kernel and bias
uniform in +-1/sqrt(K); ``A_log = log(1..N)`` in every channel; ``dt_bias`` the
inverse softplus of log-uniform[0.001, 0.1]; a float32 state.

Weights are made layer by layer from the seed and raised to float32 where they
are used, attention walks its queries in blocks and the MLP its rows, and the
logits are handed back as a host array filled block by block.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MAMBA, ATTENTION = "M", "*"
QUERY_BLOCK = 256          # queries walked at a time in an attention layer
TOKEN_BLOCK = 2048         # rows of the MLP and of the head at a time

INT_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "attn_layer_period", "attn_layer_offset", "num_attention_heads",
    "num_key_value_heads", "mamba_d_state", "mamba_d_conv", "mamba_expand",
    "mamba_dt_rank", "max_position_embeddings",
)


def sizes_of(config: dict) -> dict:
    """The model's sizes out of a configuration file's object: the published
    keys, the layer kinds they give, and what the file's ``assumed`` sets."""
    sizes = {k: int(config[k]) for k in INT_KEYS}
    sizes["rms_norm_eps"] = float(config["rms_norm_eps"])
    if int(config["num_experts"]) != 1 or not config["tie_word_embeddings"]:
        raise ValueError("this reference is of the dense, tied model: "
                         "num_experts 1, tie_word_embeddings true")
    assumed = config["assumed"]
    sizes["head_dim"] = int(assumed["head_dim"])
    sizes["init_std"] = float(assumed["initializer_range"])
    sizes["time_step_min"], sizes["time_step_max"] = (
        float(t) for t in assumed["time_step_range"])
    sizes["pattern"] = "".join(
        ATTENTION if i % sizes["attn_layer_period"] == sizes["attn_layer_offset"]
        else MAMBA for i in range(sizes["num_hidden_layers"]))
    return sizes


def _frozen(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def d_inner(sizes: dict) -> int:
    return sizes["mamba_expand"] * sizes["hidden_size"]


def count(sizes: dict, kind: str) -> int:
    return sizes["pattern"].count(kind)


# --- operations and bytes from shapes: what the equations ask, never what an
# implementation spends -----------------------------------------------------


def mixer_matmul_params(sizes: dict, kind: str) -> int:
    """Parameters of one mixer that a token's forward pass multiplies by."""
    c = sizes["hidden_size"]
    if kind == MAMBA:
        d, n, r = d_inner(sizes), sizes["mamba_d_state"], sizes["mamba_dt_rank"]
        return c * 2 * d + d * (r + 2 * n) + r * d + d * c
    a = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    return 2 * c * a + 2 * c * kv


def mlp_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def layer_params(sizes: dict, kind: str) -> int:
    """Parameters of one layer, vectors included: the mixer, the gated MLP and
    the two norms."""
    n = mixer_matmul_params(sizes, kind) + mlp_params(sizes) + 2 * sizes["hidden_size"]
    if kind == MAMBA:
        d = d_inner(sizes)
        n += (d * (sizes["mamba_d_conv"] + 1) + sizes["mamba_dt_rank"]
              + 2 * sizes["mamba_d_state"] + d + d * sizes["mamba_d_state"] + d)
    return n


def num_params(sizes: dict) -> int:
    """Every parameter held; the tied embedding counted once."""
    return (sum(layer_params(sizes, k) for k in sizes["pattern"])
            + sizes["hidden_size"] + sizes["vocab_size"] * sizes["hidden_size"])


def forward_flops_per_token(sizes: dict, context: float) -> float:
    """One forward pass of one token that sees ``context`` keys: two per
    matmul parameter (the mixers, the MLPs, the head over the vocabulary); in
    a Mamba layer the convolution (2 K D) and the state's update and read
    (``dt A`` and its ``exp``, the decay times the state, ``dt u B`` and its
    sum, ``S C`` and its sum: 7 D N); in an attention layer the two products
    over the keys seen (4 heads d context)."""
    matmuls = sum(mixer_matmul_params(sizes, k) + mlp_params(sizes)
                  for k in sizes["pattern"]) \
        + sizes["hidden_size"] * sizes["vocab_size"]
    ssm = 2.0 * sizes["mamba_d_conv"] * d_inner(sizes) + scan_ops_per_token(sizes)
    attend = 4.0 * sizes["num_attention_heads"] * sizes["head_dim"] * context
    return 2.0 * matmuls + count(sizes, MAMBA) * ssm + count(sizes, ATTENTION) * attend


def prefill_flops_per_token(sizes: dict, context: float) -> float:
    """A prompt token's forward pass: the head is asked of the last alone."""
    return forward_flops_per_token(sizes, context) \
        - 2.0 * sizes["hidden_size"] * sizes["vocab_size"]


def attention_shapes(sizes: dict) -> dict:
    """What a kernel's roofline needs of the model: only the attention layers
    hold a KV cache; their query heads share ``num_key_value_heads``."""
    return {"kv_layers": count(sizes, ATTENTION),
            "heads": sizes["num_attention_heads"],
            "kv_heads": sizes["num_key_value_heads"],
            "head_dim": sizes["head_dim"]}


def scan_ops_per_token(sizes: dict) -> float:
    """Operations of one token's state update and read in one Mamba layer, one
    for each ``(d, n)``: ``dt A``, its ``exp``, the decay times the state,
    ``(dt u) B``, their sum, ``S C`` and its sum over ``n``."""
    return 7.0 * d_inner(sizes) * sizes["mamba_d_state"]


def selective_scan_work(sizes: dict, tokens: float, chunk: int = 1024,
                        bytes_per_el: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the selective scan over ``tokens`` (real tokens
    times Mamba layers, summed over chunk dispatches of ``chunk`` tokens): the
    state updates' operations, and the least bytes any form must move - a
    token's ``u`` and ``z`` read and its ``y`` written once in the compute
    dtype, its float32 ``dt``, ``B`` and ``C`` read, and a chunk's float32
    state read and written once. The operations run on the vector units,
    where ``flops_per_s_bf16`` is no peak: what this bounds is the memory
    time, which no form can beat."""
    d, n = d_inner(sizes), sizes["mamba_d_state"]
    per_token = 3 * d * bytes_per_el + d * 4 + 2 * n * 4
    per_chunk = 2 * d * n * 4
    return (scan_ops_per_token(sizes) * tokens,
            tokens * per_token + tokens / chunk * per_chunk)


# --- weights -----------------------------------------------------------------


def _layer_tree(sizes: dict, kind: str, key):
    c, f, std = sizes["hidden_size"], sizes["intermediate_size"], sizes["init_std"]
    ks = jax.random.split(key, 12)

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    mlp = {
        "ff_norm": ones(c),
        "w_gate": normal(ks[0], (c, f)), "w_up": normal(ks[1], (c, f)),
        "w_down": normal(ks[2], (f, c)),
    }
    if kind == ATTENTION:
        a = sizes["num_attention_heads"] * sizes["head_dim"]
        kv = sizes["num_key_value_heads"] * sizes["head_dim"]
        return {
            "norm": ones(c),
            "wq": normal(ks[3], (c, a)), "wk": normal(ks[4], (c, kv)),
            "wv": normal(ks[5], (c, kv)), "wo": normal(ks[6], (a, c)),
            **mlp,
        }
    d, n, r, k = (d_inner(sizes), sizes["mamba_d_state"], sizes["mamba_dt_rank"],
                  sizes["mamba_d_conv"])
    half = 1.0 / math.sqrt(k)
    lo, hi = math.log(sizes["time_step_min"]), math.log(sizes["time_step_max"])
    dt = jnp.exp(jax.random.uniform(ks[9], (d,)) * (hi - lo) + lo)
    return {
        "norm": ones(c),
        "in_proj": normal(ks[3], (c, 2 * d)),
        "conv_w": jax.random.uniform(ks[4], (k, d), jnp.float32, -half, half),
        "conv_b": jax.random.uniform(ks[5], (d,), jnp.float32, -half, half),
        "x_proj": normal(ks[6], (d, r + 2 * n)),
        "dt_norm": ones(r), "b_norm": ones(n), "c_norm": ones(n),
        "dt_proj": normal(ks[7], (r, d)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (d, n)),
        "D": ones(d),
        "out_proj": normal(ks[8], (d, c)),
        **mlp,
    }


def _layer_key(key, i: int):
    return jax.random.fold_in(key, i + 1)


def _embed_tree(sizes: dict, key):
    shape = (sizes["vocab_size"], sizes["hidden_size"])
    embed = jax.random.normal(jax.random.fold_in(key, 0), shape, jnp.float32)
    return (embed * sizes["init_std"]).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(0,))
def _make_weights(frozen_sizes: tuple, key):
    s = dict(frozen_sizes)
    return {
        "embed": _embed_tree(s, key),
        "norm_f": jnp.ones((s["hidden_size"],), jnp.float32),
        "layers": [_layer_tree(s, kind, _layer_key(key, i))
                   for i, kind in enumerate(s["pattern"])],
    }


def make_weights(sizes: dict, seed: int):
    """What the program is handed: bfloat16 matrices and embedding, float32
    vectors, on the default device, in one jitted call from the seed."""
    return _make_weights(_frozen(sizes), jax.random.PRNGKey(seed))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _one_layer(frozen_sizes: tuple, kind: str, key):
    return _layer_tree(dict(frozen_sizes), kind, key)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_only(frozen_sizes: tuple, key):
    return _embed_tree(dict(frozen_sizes), key)


# --- the pieces of the forward pass ------------------------------------------


def plain_matmul(x, w):
    return x @ w


def _fp8(a, axis):
    """Scale each row along ``axis`` to float8_e4m3fn's range and round-trip
    through it."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype) * scale


def fp8_matmul(x, w):
    """The control's matmul: operands in ``float8_e4m3fn`` (3 bits of
    mantissa), each scaled per row; products accumulated exactly."""
    return _fp8(x, -1) @ _fp8(w, 0)


control_matmul = fp8_matmul   # the nearest precision below the stated bfloat16


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def causal_conv(u, weight, bias):
    """Depthwise causal convolution over [T, D]: ``out_t = sum_k w[k] *
    in_{t - (K - 1) + k} + b``, the inputs before the sequence zero."""
    k, t = weight.shape[0], u.shape[0]
    padded = jnp.pad(u, ((k - 1, 0), (0, 0)))
    return sum(padded[i:i + t] * weight[i] for i in range(k)) + bias


def selective_scan(u, dt, a, b, c):
    """``S_t = exp(dt_t[:, None] A) S_{t-1} + (dt_t u_t)[:, None] B_t[None,
    :]``, ``y_t = S_t C_t`` over u and dt [T, D], a [D, N], b and c [T, N],
    one token at a time from a zero state."""

    def step(state, xs):
        u_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t[:, None] * a) * state + (dt_t * u_t)[:, None] * b_t[None, :]
        return state, jnp.sum(state * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32), (u, dt, b, c))
    return y


def mamba_mixer(w, sizes: dict, x, matmul=plain_matmul):
    d, n, r = d_inner(sizes), sizes["mamba_d_state"], sizes["mamba_dt_rank"]
    eps = sizes["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)
    uz = matmul(x, f32(w["in_proj"]))
    u, z = uz[:, :d], uz[:, d:]
    u = jax.nn.silu(causal_conv(u, w["conv_w"], w["conv_b"]))
    rbc = matmul(u, f32(w["x_proj"]))
    low = rms_norm(rbc[:, :r], w["dt_norm"], eps)
    b = rms_norm(rbc[:, r:r + n], w["b_norm"], eps)
    c = rms_norm(rbc[:, r + n:], w["c_norm"], eps)
    dt = jax.nn.softplus(matmul(low, f32(w["dt_proj"])) + w["dt_bias"])
    y = selective_scan(u, dt, -jnp.exp(w["A_log"]), b, c) + w["D"] * u
    return matmul(y * jax.nn.silu(z), f32(w["out_proj"]))


def attention_mixer(w, sizes: dict, x, matmul=plain_matmul):
    t = x.shape[0]
    heads, kv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                    sizes["head_dim"])
    f32 = lambda a: a.astype(jnp.float32)
    q = matmul(x, f32(w["wq"])).reshape(t, kv, heads // kv, d)
    k = matmul(x, f32(w["wk"])).reshape(t, kv, d)
    v = matmul(x, f32(w["wv"])).reshape(t, kv, d)
    qb = min(QUERY_BLOCK, t)
    n_q = -(-t // qb)
    qp = jnp.pad(q, ((0, n_q * qb - t), (0, 0), (0, 0), (0, 0))).reshape(
        n_q, qb, kv, heads // kv, d)
    pos = jnp.arange(n_q * qb).reshape(n_q, qb)

    def one(args):
        q_blk, p_blk = args
        s = jnp.einsum("tkgd,skd->kgts", q_blk, k) / math.sqrt(d)
        s = jnp.where((jnp.arange(t)[None] <= p_blk[:, None])[None, None], s, -jnp.inf)
        return jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(one, (qp, pos)).reshape(n_q * qb, heads * d)[:t]
    return matmul(o, f32(w["wo"]))


def gated_mlp(w, sizes: dict, h, matmul=plain_matmul):
    """``W_down(silu(W_gate x) * (W_up x))`` of ``x = RMSNorm_ff(h)``, the
    rows walked ``TOKEN_BLOCK`` at a time."""
    f32 = lambda a: a.astype(jnp.float32)
    gate, up, down = f32(w["w_gate"]), f32(w["w_up"]), f32(w["w_down"])
    t = h.shape[0]
    tb = min(TOKEN_BLOCK, t)
    n_b = -(-t // tb)
    hp = jnp.pad(h, ((0, n_b * tb - t), (0, 0))).reshape(n_b, tb, -1)

    def one(rows):
        x = rms_norm(rows, w["ff_norm"], sizes["rms_norm_eps"])
        return matmul(jax.nn.silu(matmul(x, gate)) * matmul(x, up), down)

    return jax.lax.map(one, hp).reshape(n_b * tb, -1)[:t]


def layer(w, sizes: dict, kind: str, h, matmul=plain_matmul):
    """One layer over one sequence's hidden states [T, C]."""
    x = rms_norm(h, w["norm"], sizes["rms_norm_eps"])
    mixer = mamba_mixer if kind == MAMBA else attention_mixer
    h = h + mixer(w, sizes, x, matmul)
    return h + gated_mlp(w, sizes, h, matmul)


@functools.partial(jax.jit, static_argnums=(0, 1, 4), donate_argnums=(3,))
def _layer_jit(frozen_sizes, kind, w, h, matmul):
    with jax.default_matmul_precision("highest"):
        return layer(w, dict(frozen_sizes), kind, h, matmul)


@jax.jit
def _embed_jit(embed, ids):
    return embed[ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head_jit(frozen_sizes, norm_f, embed, h, matmul):
    s = dict(frozen_sizes)
    with jax.default_matmul_precision("highest"):
        y = rms_norm(h, norm_f, s["rms_norm_eps"])
        return matmul(y, embed.astype(jnp.float32).T)


def logits_with(weights, sizes: dict, ids, matmul=plain_matmul) -> np.ndarray:
    """[B, T] token ids -> [B, T, V] float32 logits on the host, from a
    whole weight tree as ``make_weights`` gives it: the form the CPU tests
    use."""
    return _logits(sizes, ids, matmul, weights["embed"],
                   lambda i, kind: weights["layers"][i])


def _logits(sizes, ids, matmul, embed, layer_weights) -> np.ndarray:
    ids = np.asarray(ids, np.int32)
    frozen = _frozen(sizes)
    norm_f = jnp.ones((sizes["hidden_size"],), jnp.float32)
    out = np.empty(ids.shape + (sizes["vocab_size"],), np.float32)
    for b in range(ids.shape[0]):
        h = _embed_jit(embed, jnp.asarray(ids[b]))
        for i, kind in enumerate(sizes["pattern"]):
            h = _layer_jit(frozen, kind, layer_weights(i, kind), h, matmul)
        for t0 in range(0, ids.shape[1], TOKEN_BLOCK):
            out[b, t0:t0 + TOKEN_BLOCK] = np.asarray(_head_jit(
                frozen, norm_f, embed, h[t0:t0 + TOKEN_BLOCK], matmul))
    return out


def serving_reference(sizes: dict, seed: int):
    """The serving check's reference: ``logits(ids, matmul=plain_matmul)``
    over [B, T] token ids with the seed's weights - the bfloat16 values that
    ``make_weights`` hands the program, raised to float32 where they are
    used. Only the embedding is held; each layer's tree is made from the seed
    when the walk reaches it. Every position is the float32 forward's."""
    key = jax.random.PRNGKey(seed)
    frozen = _frozen(sizes)
    embed = _embed_only(frozen, key)

    def layer_weights(i, kind):
        return _one_layer(frozen, kind, _layer_key(key, i))

    def logits(ids, matmul=plain_matmul):
        return _logits(sizes, ids, matmul, embed, layer_weights)

    return logits
