"""The plain reference of the family ``minicpm_sala``: MiniCPM-SALA in
straightforward ``jax.numpy`` and float32, straight from the equations.

A configuration file that says ``"family": "minicpm_sala"`` gets this module
as its reference (``harness.attach_family``). Nothing here imports the
program under test. The parameter tree's names are the program's input
format (one dict a layer, in the order of ``mixer_types``), so the same tree
feeds both; the values come from ``make_weights``.

**The equations** (``C`` hidden, ``F`` intermediate, ``L_pub`` the PUBLISHED
depth - a configuration file cut in depth states it as
``published_num_hidden_layers`` beside the ``num_hidden_layers`` it runs):

* Stack: ``h = scale_emb * E[ids]``; for each layer ``h += s * Mixer(RMSNorm(h))``,
  ``h += s * MLP(RMSNorm(h))`` with ``s = scale_depth / sqrt(L_pub)`` and
  ``MLP(x) = W_down(silu(W_gate x) * (W_up x))``;
  ``logits = W_lm RMSNorm(h) / (C / dim_model_base)``. No biases, untied head.
* ``lightning-attn``: ``q, k, v = W_q x, W_k x, W_v x`` as ``lightning_nh``
  heads of ``lightning_head_dim``; RMSNorm with a learned weight over each
  head of q and of k; rotary embedding on q and k; per head
  ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(d)``;
  ``o = RMSNorm(o)`` over the joined heads; ``o = o * sigmoid(W_g x)``;
  ``y = W_o o``. The recurrence is a plain scan over tokens.
* ``minicpm4`` (InfLLM-V2): ``q = W_q x`` (``num_attention_heads`` heads),
  ``k, v = W_k x, W_v x`` (``num_key_value_heads`` heads, ``G`` query heads
  to one); RMSNorm over each head of q and of k; no rotary. Selection, per
  query token ``t`` and KV head: compressed keys ``Kc_j = mean(k[stride*j :
  stride*j + window])`` for every window whose last token is at or before
  ``t``; ``p = softmax_j(q_t . Kc_j / sqrt(d))`` per query head, summed over
  the group's heads; the score of block ``b`` is the maximum of ``p_j`` over
  the windows that overlap it; ``I_t`` holds the first ``init_blocks``
  blocks, the ``local_window / block`` blocks that end with ``t``'s own, and
  the ``topk`` highest-scoring of the rest. Attention: softmax over the
  tokens ``s <= t`` of the blocks in ``I_t``. At ``t < dense_below`` every
  visible token is attended. ``o = o * sigmoid(W_g x)``; ``y = W_o o``.

**Assumed** (no key of the published config gives them; each is written into
the configuration file's ``assumed``): the decay ``lambda_h = exp(-2^(-8 (h +
1) / H))``, the same in every layer (Lightning Attention's published
convention); the scope of the norms (q/k norm weight of ``head_dim`` shared by
the heads; output norm over the joined heads); the sparse sizes (window 32,
stride 16, block 64, 1 initial block, local window 2048, top-k 64, dense below
8192); "the blocks of the last 2048 tokens" read as the 32 blocks ending with
the query's own; N(0, 0.02) for every matrix and embedding, norms at 1.

Sequences are walked so that nothing of ``[T, T]`` or ``[T, T / stride]``
per head exists whole (queries in blocks), weights are made layer by layer
from the seed (16 layers of float32 do not fit beside the activations), and
the logits are handed back as a host array filled block by block.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
INIT_STD = 0.02            # assumed: the published config carries no range
QUERY_BLOCK = 128          # queries walked at a time in a sparse layer
TOKEN_BLOCK = 2048         # rows of the MLP and of the head at a time

SIZE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "lightning_nh", "lightning_nkv", "lightning_head_dim",
    "max_position_embeddings", "dim_model_base",
)
SPARSE_KEYS = ("window", "stride", "block", "init_blocks", "local_window",
               "topk", "dense_below")


def sizes_of(config: dict) -> dict:
    """The model's sizes out of a configuration file's object: the
    published keys, the layer kinds as run (``mixer_types``), and the sparse
    sizes out of ``assumed``."""
    sizes = {k: int(config[k]) for k in SIZE_KEYS}
    for k in ("rms_norm_eps", "rope_theta", "scale_emb", "scale_depth"):
        sizes[k] = float(config[k])
    sizes["mixer_types"] = tuple(config["mixer_types"])
    if len(sizes["mixer_types"]) != sizes["num_hidden_layers"]:
        raise ValueError("mixer_types names another number of layers than num_hidden_layers")
    sizes["published_num_hidden_layers"] = int(
        config.get("published_num_hidden_layers", config["num_hidden_layers"]))
    sizes["sparse"] = tuple(int(config["assumed"]["sparse"][k]) for k in SPARSE_KEYS)
    if sizes["lightning_nkv"] != sizes["lightning_nh"]:
        raise ValueError("lightning_nkv != lightning_nh is not written down here")
    return sizes


def _frozen(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def sparse_sizes(sizes: dict) -> dict:
    return dict(zip(SPARSE_KEYS, sizes["sparse"]))


# --- operations from shapes: what the equations ask, never what an
# implementation spends -----------------------------------------------------


def layer_matmul_params(sizes: dict, kind: str) -> int:
    c, f = sizes["hidden_size"], sizes["intermediate_size"]
    if kind == LIGHTNING:
        a = sizes["lightning_nh"] * sizes["lightning_head_dim"]
        return 4 * c * a + a * c + 3 * c * f                 # q k v g, o, mlp
    a = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    return 2 * c * a + 2 * c * kv + a * c + 3 * c * f        # q g, k v, o, mlp


def matmul_params(sizes: dict) -> int:
    """Parameters that take part in matmuls: every layer's projections and
    MLP and the untied head. The embedding lookup is a gather."""
    return sum(layer_matmul_params(sizes, k) for k in sizes["mixer_types"]) \
        + sizes["hidden_size"] * sizes["vocab_size"]


def selected_blocks(sizes: dict, context: float) -> float:
    """Blocks of ``I_t`` for a token that sees ``context`` keys."""
    sp = sparse_sizes(sizes)
    visible = math.ceil(context / sp["block"])
    if context <= sp["dense_below"]:
        return visible
    return min(visible, sp["init_blocks"] + sp["local_window"] // sp["block"]
               + sp["topk"])


def forward_flops_per_token(sizes: dict, context: float) -> float:
    """One forward pass of one token that sees ``context`` keys: two per
    matmul parameter; in a lightning layer the state update and its read
    (``k^T v`` and ``q S``: 4 H d^2); in a sparse layer the compressed
    scores over the windows seen (2 heads d context/stride) and the two
    products over the keys of the selected blocks (4 heads d keys)."""
    sp = sparse_sizes(sizes)
    n_lin = sum(k == LIGHTNING for k in sizes["mixer_types"])
    n_sparse = len(sizes["mixer_types"]) - n_lin
    hl, dl = sizes["lightning_nh"], sizes["lightning_head_dim"]
    h, d = sizes["num_attention_heads"], sizes["head_dim"]
    keys = min(context, selected_blocks(sizes, context) * sp["block"])
    select = 2.0 * h * d * context / sp["stride"] if context > sp["dense_below"] else 0.0
    return (2.0 * matmul_params(sizes) + n_lin * 4.0 * hl * dl * dl
            + n_sparse * (select + 4.0 * h * d * keys))


def prefill_flops_per_token(sizes: dict, context: float) -> float:
    """A prompt token's forward pass: the head is asked of the last alone."""
    return forward_flops_per_token(sizes, context) \
        - 2.0 * sizes["hidden_size"] * sizes["vocab_size"]


def attention_shapes(sizes: dict) -> dict:
    """What a kernel's roofline needs of the model: only the sparse layers
    hold a KV cache; their query heads share ``num_key_value_heads``."""
    return {"kv_layers": sum(k == SPARSE for k in sizes["mixer_types"]),
            "heads": sizes["num_attention_heads"],
            "kv_heads": sizes["num_key_value_heads"],
            "head_dim": sizes["head_dim"]}


# --- weights -----------------------------------------------------------------


def _normal(key, shape):
    return (jax.random.normal(key, shape, jnp.float32) * INIT_STD).astype(jnp.bfloat16)


def _layer_tree(sizes: dict, kind: str, key):
    c, f = sizes["hidden_size"], sizes["intermediate_size"]
    ks = jax.random.split(key, 8)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    if kind == LIGHTNING:
        d = sizes["lightning_head_dim"]
        a = kv = sizes["lightning_nh"] * d
    else:
        d = sizes["head_dim"]
        a, kv = sizes["num_attention_heads"] * d, sizes["num_key_value_heads"] * d
    tree = {
        "ln1": ones(c), "ln2": ones(c),
        "wq": _normal(ks[0], (c, a)), "wk": _normal(ks[1], (c, kv)),
        "wv": _normal(ks[2], (c, kv)), "wg": _normal(ks[3], (c, a)),
        "wo": _normal(ks[4], (a, c)),
        "q_norm": ones(d), "k_norm": ones(d),
        "mlp_gate": _normal(ks[5], (c, f)), "mlp_up": _normal(ks[6], (c, f)),
        "mlp_down": _normal(ks[7], (f, c)),
    }
    if kind == LIGHTNING:
        tree["o_norm"] = ones(a)
    return tree


def _layer_key(key, i: int):
    return jax.random.fold_in(key, i + 1)


@functools.partial(jax.jit, static_argnums=(0,))
def _make_weights(frozen_sizes: tuple, key):
    s = dict(frozen_sizes)
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    v, c = s["vocab_size"], s["hidden_size"]
    return {
        "embed": _normal(k_embed, (v, c)),
        "lm_head": _normal(k_head, (v, c)),
        "norm_f": jnp.ones((c,), jnp.float32),
        "layers": [_layer_tree(s, kind, _layer_key(key, i))
                   for i, kind in enumerate(s["mixer_types"])],
    }


def make_weights(sizes: dict, seed: int):
    """What the program is handed: bfloat16 matrices and embeddings, N(0,
    0.02), float32 norm weights at 1, on the default device, in one jitted
    call from the seed."""
    return _make_weights(_frozen(sizes), jax.random.PRNGKey(seed))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _one_layer(frozen_sizes: tuple, kind: str, key):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), _layer_tree(dict(frozen_sizes), kind, key))


@functools.partial(jax.jit, static_argnums=(0,))
def _ends(frozen_sizes: tuple, key):
    s = dict(frozen_sizes)
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    shape = (s["vocab_size"], s["hidden_size"])
    return (_normal(k_embed, shape).astype(jnp.float32),
            _normal(k_head, shape).astype(jnp.float32))


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


def rotary(x, positions, theta):
    """``x`` [T, H, d] at ``positions`` [T]: the rotate-half convention."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None]    # [T, d/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def lightning_decay(heads: int):
    """``lambda_h = exp(-2^(-8 (h + 1) / H))`` (assumed)."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return jnp.exp(-(2.0 ** (-8.0 * h / heads)))


def lightning_recurrence(q, k, v, decay):
    """``S_t = lambda S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t`` over [T, H, d],
    one token at a time."""
    h, d = q.shape[1:]

    def step(state, qkv):
        q_t, k_t, v_t = qkv
        state = decay[:, None, None] * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hd,hde->he", q_t, state)

    _, o = jax.lax.scan(step, jnp.zeros((h, d, d), jnp.float32), (q, k, v))
    return o


def compressed_keys(k, sp: dict):
    """``Kc_j = mean(k[stride j : stride j + window])`` over [T, KV, d], for
    every ``j`` whose window starts inside the sequence (one that runs past
    its end is never looked at: it ends after every token)."""
    t = k.shape[0]
    stride, per = sp["stride"], sp["window"] // sp["stride"]
    n = -(-t // stride)
    seg = jnp.pad(k, ((0, (n + per) * stride - t), (0, 0), (0, 0)))
    seg = seg.reshape(n + per, stride, *k.shape[1:]).mean(axis=1)     # [n + per, KV, d]
    return sum(seg[i:n + i] for i in range(per)) / per                # [n, KV, d]


def windows_of_blocks(n_blocks: int, n_windows: int, sp: dict):
    """[n_blocks, W] indices of the windows that overlap each block, and
    which of them exist."""
    lo = (jnp.arange(n_blocks) * sp["block"] - sp["window"]) // sp["stride"] + 1
    idx = lo[:, None] + jnp.arange((sp["block"] + sp["window"]) // sp["stride"] - 1)[None]
    return jnp.clip(idx, 0, n_windows - 1), (idx >= 0) & (idx < n_windows)


def selected_set(q, kc, positions, n_blocks: int, sp: dict):
    """``I_t`` as a mask [KV, Tq, n_blocks] for the queries ``q`` [Tq, H, d]
    at ``positions`` [Tq], from the compressed keys ``kc`` [n_windows, KV, d]."""
    tq, h, d = q.shape
    n_windows, kv = kc.shape[:2]
    g = h // kv
    stride, window, block = sp["stride"], sp["window"], sp["block"]
    scores = jnp.einsum("tkgd,jkd->kgtj", q.reshape(tq, kv, g, d), kc) / math.sqrt(d)
    ends = jnp.arange(n_windows) * stride + window - 1               # last token
    seen = ends[None] <= positions[:, None]                          # [Tq, J]
    p = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
    p = jnp.where(seen[None, None], p, 0.0).sum(axis=1)              # [KV, Tq, J]
    idx, exists = windows_of_blocks(n_blocks, n_windows, sp)
    score = jnp.where((exists[None] & seen[:, idx])[None],
                      p[:, :, idx], -jnp.inf).max(axis=-1)           # [KV, Tq, B]
    b = jnp.arange(n_blocks)[None]
    own = (positions // block)[:, None]
    visible = b <= own
    forced = visible & ((b < sp["init_blocks"]) | (b > own - sp["local_window"] // block))
    rest = jnp.where((visible & ~forced)[None], score, -jnp.inf)
    k_top = min(sp["topk"], n_blocks)
    top_score, top_idx = jax.lax.top_k(rest, k_top)
    picked = jnp.zeros(rest.shape, bool).at[
        jnp.arange(kv)[:, None, None], jnp.arange(tq)[None, :, None], top_idx
    ].max(top_score > -jnp.inf)
    dense = (positions < sp["dense_below"])[:, None]
    return jnp.where(dense[None], visible[None], forced[None] | picked)


def sparse_attention(q, k, v, sp: dict):
    """The InfLLM-V2 layer's attention over one sequence: [T, H, d] queries
    against [T, KV, d], queries in blocks of ``QUERY_BLOCK``."""
    t, h, d = q.shape
    kv = k.shape[1]
    g = h // kv
    block = sp["block"]
    n_blocks = -(-t // block)
    kc = compressed_keys(k, sp)
    qb = min(QUERY_BLOCK, t)
    n_q = -(-t // qb)
    pad = n_q * qb - t
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(n_q, qb, h, d)
    pos = jnp.arange(n_q * qb).reshape(n_q, qb)
    key_block = jnp.arange(t) // block

    def one(args):
        q_blk, p_blk = args
        keep = selected_set(q_blk, kc, p_blk, n_blocks, sp)          # [KV, qb, B]
        keep = keep[:, :, key_block] & (jnp.arange(t)[None] <= p_blk[:, None])[None]
        s = jnp.einsum("tkgd,skd->kgts", q_blk.reshape(qb, kv, g, d), k) / math.sqrt(d)
        s = jnp.where(keep[:, None], s, -jnp.inf)
        o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(qb, h, d)

    return jax.lax.map(one, (qp, pos)).reshape(n_q * qb, h, d)[:t]


def _by_rows(fn, x, rows: int):
    """``fn`` over ``x`` [T, ...] in blocks of ``rows``, so that what it makes
    is ``rows`` wide at a time."""
    t = x.shape[0]
    if t <= rows:
        return fn(x)
    n = -(-t // rows)
    xp = jnp.pad(x, ((0, n * rows - t),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, xp.reshape(n, rows, *x.shape[1:]))
    return out.reshape(n * rows, *out.shape[2:])[:t]


def layer(w, sizes: dict, kind: str, h, matmul=plain_matmul):
    """One layer over one sequence's hidden states [T, C]."""
    eps = sizes["rms_norm_eps"]
    s = sizes["scale_depth"] / math.sqrt(sizes["published_num_hidden_layers"])
    t = h.shape[0]
    x = rms_norm(h, w["ln1"], eps)
    if kind == LIGHTNING:
        heads, d = sizes["lightning_nh"], sizes["lightning_head_dim"]
        kv = heads
    else:
        heads, kv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                        sizes["head_dim"])
    q = rms_norm(matmul(x, w["wq"]).reshape(t, heads, d), w["q_norm"], eps)
    k = rms_norm(matmul(x, w["wk"]).reshape(t, kv, d), w["k_norm"], eps)
    v = matmul(x, w["wv"]).reshape(t, kv, d)
    if kind == LIGHTNING:
        positions = jnp.arange(t)
        q = rotary(q, positions, sizes["rope_theta"])
        k = rotary(k, positions, sizes["rope_theta"])
        o = lightning_recurrence(q, k, v, lightning_decay(heads)) / math.sqrt(d)
        o = rms_norm(o.reshape(t, heads * d), w["o_norm"], eps)
    else:
        o = sparse_attention(q, k, v, sparse_sizes(sizes)).reshape(t, heads * d)
    o = o * jax.nn.sigmoid(matmul(x, w["wg"]))
    h = h + s * matmul(o, w["wo"])

    def mlp(rows):
        y = rms_norm(rows, w["ln2"], eps)
        return matmul(jax.nn.silu(matmul(y, w["mlp_gate"])) * matmul(y, w["mlp_up"]),
                      w["mlp_down"])

    return h + s * _by_rows(mlp, h, TOKEN_BLOCK)


@functools.partial(jax.jit, static_argnums=(0, 1, 4), donate_argnums=(3,))
def _layer_jit(frozen_sizes, kind, w, h, matmul):
    with jax.default_matmul_precision("highest"):
        return layer(w, dict(frozen_sizes), kind, h, matmul)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_jit(frozen_sizes, embed, ids):
    return dict(frozen_sizes)["scale_emb"] * embed[ids]


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head_jit(frozen_sizes, norm_f, lm_head, h, matmul):
    s = dict(frozen_sizes)
    with jax.default_matmul_precision("highest"):
        y = rms_norm(h, norm_f, s["rms_norm_eps"])
        return matmul(y, lm_head.T) / (s["hidden_size"] / s["dim_model_base"])


def hidden_of(layer_weights, sizes: dict, h, matmul=plain_matmul):
    """``h`` [T, C] through every layer; ``layer_weights(i, kind)`` hands
    over layer ``i``'s float32 tree when its turn comes."""
    frozen = _frozen(sizes)
    for i, kind in enumerate(sizes["mixer_types"]):
        h = _layer_jit(frozen, kind, layer_weights(i, kind), h, matmul)
    return h


def logits_with(weights, sizes: dict, ids, matmul=plain_matmul) -> np.ndarray:
    """[B, T] token ids -> [B, T, V] float32 logits on the host, from a
    whole weight tree as ``make_weights`` gives it (raised to float32 a
    layer at a time): the form the CPU tests use."""
    up = lambda tree: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)
    return _logits(
        sizes, ids, matmul, up(weights["embed"]), up(weights["lm_head"]),
        lambda i, kind: up(weights["layers"][i]))


def _logits(sizes, ids, matmul, embed, lm_head, layer_weights) -> np.ndarray:
    ids = np.asarray(ids, np.int32)
    frozen = _frozen(sizes)
    norm_f = jnp.ones((sizes["hidden_size"],), jnp.float32)
    out = np.empty(ids.shape + (sizes["vocab_size"],), np.float32)
    for b in range(ids.shape[0]):
        h = _embed_jit(frozen, embed, jnp.asarray(ids[b]))
        h = hidden_of(layer_weights, sizes, h, matmul)
        for t0 in range(0, ids.shape[1], TOKEN_BLOCK):
            out[b, t0:t0 + TOKEN_BLOCK] = np.asarray(_head_jit(
                frozen, norm_f, lm_head, h[t0:t0 + TOKEN_BLOCK], matmul))
    return out


def serving_reference(sizes: dict, seed: int):
    """The serving check's reference: ``logits(ids, matmul=plain_matmul)``
    over [B, T] token ids with the seed's weights - the bfloat16 values that
    ``make_weights`` hands the program, raised to float32. Only the two
    ends are held; each layer's tree is made from the seed when the walk
    reaches it."""
    key = jax.random.PRNGKey(seed)
    frozen = _frozen(sizes)
    embed, lm_head = _ends(frozen, key)

    def layer_weights(i, kind):
        return _one_layer(frozen, kind, _layer_key(key, i))

    def logits(ids, matmul=plain_matmul):
        return _logits(sizes, ids, matmul, embed, lm_head, layer_weights)

    return logits
