"""The plain reference of the family ``deepseek_v3``: DeepSeek-V3's block in
straightforward ``jax.numpy`` and float32, straight from the equations, in
the published (expanded) form only: no absorption, no cache, no kernels.

A configuration file that says ``"family": "deepseek_v3"`` gets this module as
its reference (``harness.attach_family``). Nothing here imports the program
under test. The parameter tree's names are the program's input format (one
dict a leading dense layer under ``dense``, the expert layers' dicts stacked
under ``moe``), so the same tree feeds both; the values come from
``make_weights``.

**The equations** (``C`` hidden, ``H`` heads, ``r`` = ``kv_lora_rank``, ``dn``
/ ``dr`` / ``dv`` = ``qk_nope_head_dim`` / ``qk_rope_head_dim`` /
``v_head_dim``; every Linear without bias; RMSNorm ``x * rsqrt(mean(x^2) +
eps) * w``):

* Stack: ``h = E[ids]``; per layer ``h = h + MLA(RMSNorm(h))``, ``h = h +
  FFN(RMSNorm(h))``; ``logits = W_lm RMSNorm_f(h)``, the head untied.
* MLA (``q_lora_rank`` null: a plain query projection): ``[q_nope_h |
  q_pe_h] = x W_q`` (``dn + dr`` a head); ``[c | k_pe] = x W_kva`` (``r +
  dr``; ``k_pe`` one key that every head shares); ``c = RMSNorm_kv(c)``;
  ``[k_nope_h | v_h] = c W_kvb`` (``dn + dv`` a head); RoPE at
  ``rope_theta`` on ``q_pe`` and ``k_pe`` as ``rope_interleave`` publishes
  it - each vector's pairs ``(2i, 2i + 1)`` taken apart into evens then odds,
  then rotated half against half by ``pos * theta^(-2i / dr)``; ``s_h(t, u)
  = (q_nope_h . k_nope_h + q_pe_h . k_pe) / sqrt(dn + dr)``, causal softmax,
  ``o_h = sum p v_h``; ``out = concat_h(o_h) W_o``.
* FFN of the first ``first_k_dense_replace`` layers: ``W_down(silu(W_gate y)
  * W_up y)`` at ``intermediate_size``. After them: ``s = sigmoid(y W_r)``
  over ALL the published experts; the ``num_experts_per_tok`` with the largest
  ``s + e_score_correction_bias`` are chosen (``noaux_tc``; ``n_group`` 1
  makes grouped routing a no-op); ``w_i = s_i / (sum of the chosen s +
  1e-20)`` times ``routed_scaling_factor``; expert ``i`` a SwiGLU at
  ``moe_intermediate_size``; ``out = sum_i w_i Expert_i(y) + Shared(y)``,
  ``Shared`` the ``n_shared_experts`` shared experts as ONE SwiGLU of
  ``n_shared_experts * moe_intermediate_size`` (DeepSeek-V3's
  ``shared_experts``). **The share**: of the chosen experts only those in
  ``experts_held`` are computed - a plain loop over them - and what the
  others would add is left out; the ``w_i`` are normalised over all the
  chosen, held or not.

**Assumed** (each is written into the configuration file's ``assumed``):
N(0, ``initializer_range`` 0.02) matrices and embeddings; norms at 1;
``e_score_correction_bias`` 0 (a trained model's bias is no part of its
config). Gate and up are stored as one matrix (the gate's columns first) and
the routed experts as ``[held, 2F, C]`` and ``[held, F, C]``: the program's
input format, not the published checkpoint's; the mathematics is the same.

Weights are made layer by layer from the seed and raised to float32 where they
are used, attention walks its queries in blocks, and the logits are handed
back as a host array filled block by block.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

DENSE, EXPERTS = "D", "E"
QUERY_BLOCK = 256          # queries walked at a time in an attention layer
TOKEN_BLOCK = 2048         # rows of the head at a time

INT_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "kv_lora_rank", "qk_rope_head_dim",
    "qk_nope_head_dim", "v_head_dim", "num_experts_per_tok", "first_k_dense_replace",
    "n_shared_experts", "max_position_embeddings",
)
FLOAT_KEYS = ("routed_scaling_factor", "rms_norm_eps", "rope_theta")


def sizes_of(config: dict) -> dict:
    """The model's sizes out of a configuration file's object: the published
    keys, the depth as run from the published layer ``first_layer`` on, the
    router's width (``published_n_routed_experts`` where the file's
    ``n_routed_experts`` counts a share) and the range of experts held."""
    sizes = {k: int(config[k]) for k in INT_KEYS}
    sizes.update({k: float(config[k]) for k in FLOAT_KEYS})
    if config.get("q_lora_rank") is not None or not config.get("rope_interleave", True):
        raise ValueError("only q_lora_rank null with interleaved RoPE is written")
    sizes["norm_topk_prob"] = bool(config["norm_topk_prob"])
    first = int(config.get("first_layer", 0))
    sizes["pattern"] = "".join(
        DENSE if i < sizes["first_k_dense_replace"] else EXPERTS
        for i in range(first, first + sizes["num_hidden_layers"]))
    sizes["router_width"] = int(config.get(
        "published_n_routed_experts", config["n_routed_experts"]))
    sizes["experts_held"] = tuple(
        int(e) for e in config.get("experts_held", (0, config["n_routed_experts"])))
    lo, hi = sizes["experts_held"]
    if hi - lo != int(config["n_routed_experts"]) or hi > sizes["router_width"]:
        raise ValueError("experts_held is not n_routed_experts of the router's width")
    sizes["init_std"] = float(config["assumed"]["initializer_range"])
    return sizes


def _frozen(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def held(sizes: dict) -> int:
    lo, hi = sizes["experts_held"]
    return hi - lo


def qk_head_dim(sizes: dict) -> int:
    return sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]


def latent_dim(sizes: dict) -> int:
    """Values of a latent row: the latent and the shared rotary key."""
    return sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]


# --- operations and bytes from shapes: what the equations ask, never what an
# implementation spends -----------------------------------------------------


def attention_matmul_params(sizes: dict) -> int:
    """Parameters of a layer's four attention projections: the query, the
    latent and rotary key, the latent's up-projection to keys and values
    (each key and value made once, as the published form caches them), and
    the output."""
    c, h, r = sizes["hidden_size"], sizes["num_attention_heads"], sizes["kv_lora_rank"]
    return (c * h * qk_head_dim(sizes) + c * latent_dim(sizes)
            + r * h * (sizes["qk_nope_head_dim"] + sizes["v_head_dim"])
            + h * sizes["v_head_dim"] * c)


def ffn_matmul_params(sizes: dict, kind: str) -> float:
    """Parameters of a layer's feed-forward that a token multiplies by. An
    expert layer: the router, the shared experts, and the EXPECTATION of the
    routed experts this holder is asked - ``num_experts_per_tok`` times the
    share it holds (6 x 16 / 128 = 0.75), what uniform routing sends here."""
    c = sizes["hidden_size"]
    if kind == DENSE:
        return 3 * c * sizes["intermediate_size"]
    f = sizes["moe_intermediate_size"]
    asked = sizes["num_experts_per_tok"] * held(sizes) / sizes["router_width"]
    return (c * sizes["router_width"] + 3 * c * sizes["n_shared_experts"] * f
            + asked * 3 * c * f)


def layer_params(sizes: dict, kind: str) -> int:
    """Parameters of one layer as this holder keeps them, vectors included."""
    c = sizes["hidden_size"]
    n = 2 * c + sizes["kv_lora_rank"] + attention_matmul_params(sizes)
    if kind == DENSE:
        return n + 3 * c * sizes["intermediate_size"]
    f = sizes["moe_intermediate_size"]
    return (n + c * sizes["router_width"] + sizes["router_width"]
            + held(sizes) * 3 * c * f + 3 * c * sizes["n_shared_experts"] * f)


def num_params(sizes: dict) -> int:
    return (sum(layer_params(sizes, k) for k in sizes["pattern"])
            + sizes["hidden_size"] + 2 * sizes["vocab_size"] * sizes["hidden_size"])


def forward_flops_per_token(sizes: dict, context: float) -> float:
    """One forward pass of one token that sees ``context`` keys, as this
    holder is asked it: two per matmul parameter (the attention projections,
    the feed-forwards with the expected share of routed experts, the head)
    and, in every layer, the two products over the keys seen in the published
    form (``2 H context (dn + dr + dv)``)."""
    matmuls = sum(attention_matmul_params(sizes) + ffn_matmul_params(sizes, k)
                  for k in sizes["pattern"]) + sizes["hidden_size"] * sizes["vocab_size"]
    attend = 2.0 * sizes["num_attention_heads"] * context * (
        qk_head_dim(sizes) + sizes["v_head_dim"])
    return 2.0 * matmuls + len(sizes["pattern"]) * attend


def prefill_flops_per_token(sizes: dict, context: float) -> float:
    """A prompt token's forward pass: the head is asked of the last alone."""
    return forward_flops_per_token(sizes, context) \
        - 2.0 * sizes["hidden_size"] * sizes["vocab_size"]


def attention_shapes(sizes: dict) -> dict:
    """What a kernel's roofline needs of the model: every layer holds a
    cache, ONE latent row a position (``kv_heads`` 1 of ``r + dr``) that all
    the query heads read."""
    return {"kv_layers": len(sizes["pattern"]), "heads": sizes["num_attention_heads"],
            "kv_heads": 1, "head_dim": latent_dim(sizes)}


def mla_decode_work(sizes: dict, keys: float, rows: float,
                    bytes_per_el: int = 2) -> tuple[float, float]:
    """(flops, bytes) of the decode steps' latent attention in the absorbed
    form over ``keys`` (the sum over live rows of the keys each attends in
    one layer) and ``rows`` live rows, summed over dispatches, in every
    layer: each key's latent row read once for all the heads; a row's
    absorbed query (``r + dr`` a head) read and its ``u`` (``r`` a head)
    written; every head scores each key over ``r + dr`` and takes ``r`` of
    its value. Whatever implements it, the count is this."""
    layers, heads = len(sizes["pattern"]), sizes["num_attention_heads"]
    width = latent_dim(sizes) + sizes["kv_lora_rank"]          # 576 + 512
    moved = keys * layers * latent_dim(sizes) + rows * layers * heads * width
    return keys * layers * heads * 2.0 * width, moved * bytes_per_el


# --- weights -----------------------------------------------------------------


def _layer_tree(sizes: dict, kind: str, key):
    c, h, r = sizes["hidden_size"], sizes["num_attention_heads"], sizes["kv_lora_rank"]
    std = sizes["init_std"]
    ks = jax.random.split(key, 10)

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    tree = {
        "attn_norm": ones(c),
        "wq": normal(ks[0], (c, h * qk_head_dim(sizes))),
        "wkv_a": normal(ks[1], (c, latent_dim(sizes))),
        "kv_norm": ones(r),
        "wkv_b": normal(ks[2], (r, h * (sizes["qk_nope_head_dim"] + sizes["v_head_dim"]))),
        "wo": normal(ks[3], (h * sizes["v_head_dim"], c)),
        "mlp_norm": ones(c),
    }
    if kind == DENSE:
        f = sizes["intermediate_size"]
        tree.update(gate_up=normal(ks[4], (c, 2 * f)), down=normal(ks[5], (f, c)))
        return tree
    f = sizes["moe_intermediate_size"]
    fs = sizes["n_shared_experts"] * f
    ids = jnp.arange(*sizes["experts_held"])

    def experts(k, shape):       # expert e's matrix comes of e, whoever holds it
        return jax.vmap(lambda e: normal(jax.random.fold_in(k, e), shape))(ids)

    tree.update(
        router=normal(ks[4], (c, sizes["router_width"])),
        router_bias=jnp.zeros((sizes["router_width"],), jnp.float32),
        w_gate_up=experts(ks[5], (2 * f, c)), w_down=experts(ks[6], (f, c)),
        shared_gate_up=normal(ks[7], (c, 2 * fs)), shared_down=normal(ks[8], (fs, c)))
    return tree


def _layer_key(key, i: int):
    return jax.random.fold_in(key, i + 1)


def _ends_tree(sizes: dict, key):
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    shape = (sizes["vocab_size"], sizes["hidden_size"])
    scale = sizes["init_std"]
    make = lambda k: (jax.random.normal(k, shape, jnp.float32) * scale).astype(jnp.bfloat16)
    return make(k_embed), make(k_head)


@functools.partial(jax.jit, static_argnums=(0,))
def _make_weights(frozen_sizes: tuple, key):
    s = dict(frozen_sizes)
    embed, lm_head = _ends_tree(s, key)
    layers = [_layer_tree(s, kind, _layer_key(key, i)) for i, kind in enumerate(s["pattern"])]
    tree = {"embed": embed, "lm_head": lm_head,
            "norm_f": jnp.ones((s["hidden_size"],), jnp.float32),
            "dense": [t for t, kind in zip(layers, s["pattern"]) if kind == DENSE]}
    moe = [t for t, kind in zip(layers, s["pattern"]) if kind == EXPERTS]
    if moe:
        tree["moe"] = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *moe)
    return tree


def make_weights(sizes: dict, seed: int):
    """What the program is handed: bfloat16 matrices and embeddings, float32
    vectors, the routed experts of ``experts_held`` alone, the expert layers
    stacked, on the default device, in one jitted call from the seed."""
    return _make_weights(_frozen(sizes), jax.random.PRNGKey(seed))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _one_layer(frozen_sizes: tuple, kind: str, key):
    return _layer_tree(dict(frozen_sizes), kind, key)


@functools.partial(jax.jit, static_argnums=(0,))
def _ends(frozen_sizes: tuple, key):
    return _ends_tree(dict(frozen_sizes), key)


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


def rope(x, pos, theta):
    """The published interleaved RoPE of ``x`` [..., d] at ``pos``
    (broadcast against the leading axes): the pairs taken apart into evens
    then odds, then ``x cos + rotate_half(x) sin`` with the frequencies
    ``theta^(-2i / d)`` twice over."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = pos[..., None].astype(jnp.float32) * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, gate_up, down, matmul=plain_matmul):
    """``W_down(silu(W_gate x) * W_up x)`` with gate and up one matrix
    [C, 2F] (the gate's columns first) and ``down`` [F, C], float32."""
    f = down.shape[0]
    both = matmul(x, gate_up.astype(jnp.float32))
    return matmul(silu(both[:, :f]) * both[:, f:], down.astype(jnp.float32))


def mla(w, sizes: dict, x, matmul=plain_matmul):
    """Latent attention over one whole sequence [T, C], expanded: every
    head's key and value made from the normed latent, causal softmax at
    ``1 / sqrt(dn + dr)``, queries walked in blocks."""
    t = x.shape[0]
    h, r = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    dn, dv = sizes["qk_nope_head_dim"], sizes["v_head_dim"]
    f32 = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(t)
    q = matmul(x, f32(w["wq"])).reshape(t, h, qk_head_dim(sizes))
    kv_a = matmul(x, f32(w["wkv_a"]))
    c = rms_norm(kv_a[:, :r], w["kv_norm"], sizes["rms_norm_eps"])
    k_pe = rope(kv_a[:, r:], pos, sizes["rope_theta"])
    q = jnp.concatenate(
        [q[..., :dn], rope(q[..., dn:], pos[:, None], sizes["rope_theta"])], axis=-1)
    kv = matmul(c, f32(w["wkv_b"])).reshape(t, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, None], (t, h, k_pe.shape[-1]))], axis=-1)
    v = kv[..., dn:]
    qb = min(QUERY_BLOCK, t)
    n_q = -(-t // qb)
    qp = jnp.pad(q, ((0, n_q * qb - t), (0, 0), (0, 0))).reshape(n_q, qb, h, -1)
    at = jnp.arange(n_q * qb).reshape(n_q, qb)

    def one(args):
        q_blk, p_blk = args
        s = jnp.einsum("thd,shd->hts", q_blk, k) / math.sqrt(qk_head_dim(sizes))
        s = jnp.where((pos[None] <= p_blk[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(one, (qp, at)).reshape(n_q * qb, h * dv)[:t]
    return matmul(o, f32(w["wo"]))


def router_choice(w, sizes: dict, x, matmul=plain_matmul):
    """(chosen [T, k], weights [T, k]) over ALL the experts."""
    k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(matmul(x, w["router"].astype(jnp.float32)))
    _, chosen = jax.lax.top_k(s + w["router_bias"], k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen, picked * sizes["routed_scaling_factor"]


def routed_part(w, sizes: dict, x, matmul=plain_matmul):
    """``sum_i w_i Expert_i(x)`` over the chosen experts that are held: a
    plain loop over the held experts, each over every token, weighted 0 where
    it was not chosen."""
    chosen, weights = router_choice(w, sizes, x, matmul)
    first = sizes["experts_held"][0]

    def one(total, expert):
        index, gate_up, down = expert
        w_e = jnp.where(chosen == first + index, weights, 0.0).sum(axis=-1)
        return total + w_e[:, None] * swiglu(x, gate_up.T, down, matmul), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(held(sizes)), w["w_gate_up"], w["w_down"]))
    return total


def layer(w, sizes: dict, kind: str, h, matmul=plain_matmul):
    """One layer over one sequence's hidden states [T, C]."""
    eps = sizes["rms_norm_eps"]
    h = h + mla(w, sizes, rms_norm(h, w["attn_norm"], eps), matmul)
    y = rms_norm(h, w["mlp_norm"], eps)
    if kind == DENSE:
        return h + swiglu(y, w["gate_up"], w["down"], matmul)
    return (h + routed_part(w, sizes, y, matmul)
            + swiglu(y, w["shared_gate_up"], w["shared_down"], matmul))


@functools.partial(jax.jit, static_argnums=(0, 1, 4), donate_argnums=(3,))
def _layer_jit(frozen_sizes, kind, w, h, matmul):
    with jax.default_matmul_precision("highest"):
        return layer(w, dict(frozen_sizes), kind, h, matmul)


@jax.jit
def _embed_jit(embed, ids):
    return embed[ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head_jit(frozen_sizes, norm_f, lm_head, h, matmul):
    s = dict(frozen_sizes)
    with jax.default_matmul_precision("highest"):
        y = rms_norm(h, norm_f, s["rms_norm_eps"])
        return matmul(y, lm_head.astype(jnp.float32).T)


def stacked_layer(weights, sizes: dict, i: int):
    """Layer ``i``'s dict out of a whole tree as ``make_weights`` gives it."""
    n_dense = len(weights["dense"])
    if i < n_dense:
        return weights["dense"][i]
    return jax.tree_util.tree_map(lambda a: a[i - n_dense], weights["moe"])


def logits_with(weights, sizes: dict, ids, matmul=plain_matmul) -> np.ndarray:
    """[B, T] token ids -> [B, T, V] float32 logits on the host, from a
    whole weight tree as ``make_weights`` gives it: the form the CPU tests
    use."""
    return _logits(sizes, ids, matmul, weights["embed"], weights["lm_head"],
                   lambda i, kind: stacked_layer(weights, sizes, i))


def _logits(sizes, ids, matmul, embed, lm_head, layer_weights) -> np.ndarray:
    ids = np.asarray(ids, np.int32)
    out = np.empty(ids.shape + (sizes["vocab_size"],), np.float32)
    for b in range(ids.shape[0]):
        _fill_row(sizes, ids[b], matmul, embed, lm_head, layer_weights, out[b])
    return out


def _fill_row(sizes, ids, matmul, embed, lm_head, layer_weights, out) -> None:
    """``out[:len(ids)]`` = the logits of the sequence ``ids`` [T]."""
    frozen = _frozen(sizes)
    norm_f = jnp.ones((sizes["hidden_size"],), jnp.float32)
    h = _embed_jit(embed, jnp.asarray(ids))
    for i, kind in enumerate(sizes["pattern"]):
        h = _layer_jit(frozen, kind, layer_weights(i, kind), h, matmul)
    for t0 in range(0, len(ids), TOKEN_BLOCK):
        out[t0:t0 + TOKEN_BLOCK] = np.asarray(_head_jit(
            frozen, norm_f, lm_head, h[t0:t0 + TOKEN_BLOCK], matmul))


PAD_MARGIN = 64            # positions kept past a padded sequence's last non-zero id
SPAN_ROUND = 4096          # the widths the forward is compiled at


def live_length(ids: np.ndarray) -> int:
    """Positions of a zero-padded sequence [T] that the serving check reads:
    up to its last non-zero id and a margin of ``PAD_MARGIN`` after it, rounded
    up to ``SPAN_ROUND`` (a few compiled widths however long the sequences),
    at most T. Attention is causal, so these positions' logits are those of
    the forward over all T."""
    nonzero = np.flatnonzero(ids)
    n = (nonzero[-1] + 1 if len(nonzero) else 0) + PAD_MARGIN
    return min(len(ids), -(-n // SPAN_ROUND) * SPAN_ROUND)


def serving_reference(sizes: dict, seed: int):
    """The serving check's reference: ``logits(ids, matmul=plain_matmul)``
    over [B, T] token ids with the seed's weights - the bfloat16 values that
    ``make_weights`` hands the program, raised to float32 where they are
    used. Only the two ends are held; each layer's tree is made from the seed
    when the walk reaches it. Every position is the float32 forward's, with
    the reference's own choice of experts.

    The serving check pads each sequence with zeros to the mix's longest
    total and reads only the positions of the sequence itself, so the
    forward runs over ``live_length`` positions and the rows past them read
    0. The array handed back is held here, one a ``matmul``, and refilled by
    the next call with that ``matmul``: [1, 32768] ids over 128,256 logits
    would be 16.8 GB a call, and the check holds the last call's while it
    makes the next."""
    key = jax.random.PRNGKey(seed)
    frozen = _frozen(sizes)
    embed, lm_head = _ends(frozen, key)
    held_out: dict = {}

    def layer_weights(i, kind):
        return _one_layer(frozen, kind, _layer_key(key, i))

    def logits(ids, matmul=plain_matmul):
        ids = np.asarray(ids, np.int32)
        out, filled = held_out.get(matmul, (None, None))
        if out is None or out.shape[:2] != ids.shape:
            out = np.zeros(ids.shape + (sizes["vocab_size"],), np.float32)
            filled = np.zeros(ids.shape[0], np.int64)
            held_out[matmul] = (out, filled)
        for b in range(ids.shape[0]):
            n = live_length(ids[b])
            _fill_row(sizes, ids[b, :n], matmul, embed, lm_head, layer_weights, out[b])
            out[b, n:filled[b]] = 0.0
            filled[b] = n
        return out

    return logits
