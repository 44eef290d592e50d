"""GPT-2 as pure functions over a parameter pytree.

Capability parity with the reference's ``model.py`` (pre-LN GPT-2, learned
positional embeddings, fused qkv, exact-OpenAI tanh GELU, tied lm_head,
N(0, 0.02) seeded init, flat cross-entropy with ignore_index=-100), expressed
TPU-first:

* **Params are a pytree**, not module state — the same ``forward`` is jitted
  under any `jax.sharding` configuration; DDP vs FSDP is purely a change of
  `NamedSharding` on this tree, not a different wrapper class.
* **Per-layer parameters are stacked on a leading [n_layer, ...] axis** and the
  block stack runs as one ``lax.scan`` — HLO size is constant in depth, so the
  1.5B (48-layer) config compiles as fast as 124M, and `jax.checkpoint` on the
  scan body gives FSDP-style per-block rematerialization for free.
* **Mixed precision** follows torch autocast semantics the reference trains
  under (``/root/reference/train_gpt2_distributed.py:404``): params stay fp32;
  matmuls run in ``compute_dtype`` (bf16); LayerNorm, softmax and the
  cross-entropy run in fp32.

Reference compute graph being reproduced (``/root/reference/model.py``):
  wte[idx] + wpe[:T] -> embd dropout                    (:295-304)
  12 x [ x += attn(ln1(x)); x += mlp(ln2(x)) ]          (:215-218, 307-308)
      attn: fused qkv (:95,116), split heads (:124-129), qk^T/sqrt(d) (:137),
            mask -1e4 (:144), softmax+drop (:145-146), @v, out proj+drop (:151-158)
      mlp: fc1(C->4C) -> tanh-GELU -> drop -> fc2(4C->C) -> drop (:186-192;
            note the post-activation dropout at :188 — preserved here)
  ln_f (:311) -> logits = lm_head(x), lm_head tied to wte (:326-333,351)
  loss = flat CE(logits, labels, ignore_index=-100) (:353-359) — labels are
  already next-tokens (the dataloader shifts, dataloader.py:131-132), so no
  logit/label shift here either.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from gpt_2_distributed_tpu.config import GPT2Config
from gpt_2_distributed_tpu.ops.activations import gelu_tanh
from gpt_2_distributed_tpu.ops.attention import causal_attention, select_attention_impl
from gpt_2_distributed_tpu.ops.fused_layer import (
    fused_bias_gelu_dropout,
    fused_ln_residual_dropout,
    fused_residual_dropout,
)
from gpt_2_distributed_tpu.ops.fused_matmul import (
    SALT_MM_ATTN_PROJ,
    SALT_MM_MLP_PROJ,
    matmul_bias,
    matmul_bias_gelu_dropout,
    matmul_bias_residual_dropout,
)
from gpt_2_distributed_tpu.ops.layers import dropout, layer_norm
from gpt_2_distributed_tpu.ops.losses import blocked_cross_entropy

Params = dict[str, Any]


def _tp_active() -> bool:
    """True when the ambient mesh tensor-parallel axis is >1 (trace time).

    Reads the framework's activate_mesh registry (a bare ``with mesh:`` is
    invisible to it — parallel/mesh.py). Failure mode is graceful: a tp>1
    caller outside activate_mesh takes the flat-matmul branch, which is
    CORRECT but slow (GSPMD all-gathers the head-sharded qkv weight per
    layer) — the same degraded-not-wrong contract as the flash kernel's
    mesh discovery."""
    from gpt_2_distributed_tpu.parallel.mesh import TP_AXIS, active_mesh

    m = active_mesh()
    return m is not None and TP_AXIS in m.axis_names and m.shape[TP_AXIS] > 1

IGNORE_INDEX = -100  # reference CE ignore_index, /root/reference/model.py:357-359
INIT_SEED = 42  # reference's dedicated init generator seed, /root/reference/model.py:250-252


def init_params(
    config: GPT2Config, seed: int = INIT_SEED, dtype: jnp.dtype = jnp.float32
) -> Params:
    """Seeded init matching the reference's distribution exactly
    (``/root/reference/model.py:250-268``): N(0, initializer_range) for every
    Linear and Embedding weight, zero biases, LayerNorm at (1, 0). The lm_head
    is tied to ``wte`` (``model.py:326-333``) so it has no parameters here.

    Per-layer params are stacked: each leaf under ``params["block"]`` has a
    leading ``n_layer`` axis.
    """
    c, l, v, p = config.n_embd, config.n_layer, config.vocab_size, config.n_positions
    h = config.n_head
    std = config.initializer_range
    key = jax.random.PRNGKey(seed)
    k_wte, k_wpe, k_attn, k_attn_proj, k_fc1, k_fc2 = jax.random.split(key, 6)

    def normal(k, shape):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * std).astype(dtype)

    zeros = lambda shape: jnp.zeros(shape, dtype=dtype)
    ones = lambda shape: jnp.ones(shape, dtype=dtype)

    return {
        "wte": normal(k_wte, (v, c)),
        "wpe": normal(k_wpe, (p, c)),
        "block": {
            "ln1_scale": ones((l, c)),
            "ln1_bias": zeros((l, c)),
            # Fused qkv stored head-explicit [L, C, 3, H, D] rather than the
            # reference's [C, 3C] q|k|v concatenation (model.py:95): the same
            # matmul (the flat layouts are bit-identical under reshape — 3C
            # factors as (3, H, D) row-major), but the head dim is a real
            # tensor axis, so tensor parallelism can column-shard it — with
            # [C, 3C], tp slices of the fused dim would mix q/k/v columns,
            # which is why round 2 left qkv replicated (25% of block flops).
            "attn_qkv_w": normal(k_attn, (l, c, 3, h, c // h)),
            "attn_qkv_b": zeros((l, 3, h, c // h)),
            "attn_proj_w": normal(k_attn_proj, (l, c, c)),
            "attn_proj_b": zeros((l, c)),
            "ln2_scale": ones((l, c)),
            "ln2_bias": zeros((l, c)),
            "mlp_fc_w": normal(k_fc1, (l, c, 4 * c)),
            "mlp_fc_b": zeros((l, 4 * c)),
            "mlp_proj_w": normal(k_fc2, (l, 4 * c, c)),
            "mlp_proj_b": zeros((l, c)),
        },
        "ln_f_scale": ones((c,)),
        "ln_f_bias": zeros((c,)),
    }


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# The block leaves every forward casts whole, `bp[name].astype(cdt)`, at
# their one use. The LayerNorm leaves are read as float32
# (`ops/layers.layer_norm`) and are not among them.
_MATMUL_LEAVES = (
    "attn_qkv_w", "attn_qkv_b", "attn_proj_w", "attn_proj_b",
    "mlp_fc_w", "mlp_fc_b", "mlp_proj_w", "mlp_proj_b",
)


@functools.partial(jax.jit, static_argnames="dtype")
def _cast_weights(tree, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)


def serving_weights(params: Params, dtype) -> Params:
    """``params`` as a server holds them: the embeddings and every matmul
    leaf of the blocks in ``dtype``, cast once; the LayerNorm leaves as given.

    Weights do not change between a server's steps, and the forwards take
    ``leaf.astype(compute_dtype)`` at every use: on a float32 tree XLA hoists
    those casts out of the layer scan and runs them whole on every call
    (1.5B: 6.2 GB read and 3.1 GB written a decode step). On this tree they
    are no-ops, and the values are the same, ``bf16(w)`` either way.

    Same structure and shapes. A leaf already in ``dtype`` is the caller's
    own array; the rest come from one jitted call that donates nothing, so
    the tree passed in stays whole and alive with its caller.
    """
    dtype = jnp.dtype(dtype)
    block = params["block"]
    top = {k: params[k] for k in ("wte", "wpe") if params[k].dtype != dtype}
    inner = {k: block[k] for k in _MATMUL_LEAVES if block[k].dtype != dtype}
    if not top and not inner:
        return params
    top, inner = _cast_weights((top, inner), dtype)
    return {**params, **top, "block": {**block, **inner}}


def qkv_proj(
    config: GPT2Config,
    y: jnp.ndarray,  # [B, T, C] post-ln1, compute dtype
    bp: dict[str, jnp.ndarray],  # one layer's params
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused qkv projection -> (q, k, v), each [B, T, H, D].

    q/k/v stay in [B, T, H, D] — the flash kernel transposes at its own
    boundary where XLA can fold the permute into the reshape (the
    reference's permute at model.py:124-129 is a layout copy on GPU).
    The weight is STORED head-explicit [C, 3, H, D] so tensor parallelism
    can shard the head axis (see init_params). Compute-side there are two
    equivalent contractions:
     * tp inactive: flatten the weight to [C, 3C] and run one plain matmul
       (measured ~6% faster whole-step on v5e than the head-explicit
       einsum — XLA picks a better layout for the flat form);
     * tp active: the flatten would merge the sharded H axis into an
       unshardable merged dim (full re-gather), so contract head-explicit
       and let GSPMD keep q/k/v head-sharded end to end.

    Shared by the training forward and the KV-cache decode path
    (``models/decode.py``), which calls it with T=1 token rows.
    """
    cdt = y.dtype
    b_, t_, c = y.shape
    h_, d_ = config.n_head, config.head_dim
    if _tp_active():
        qkv = jnp.einsum(
            "btc,cshd->btshd", y, bp["attn_qkv_w"].astype(cdt)
        ) + bp["attn_qkv_b"].astype(cdt)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    w2 = bp["attn_qkv_w"].astype(cdt).reshape(c, 3 * c)
    b2 = bp["attn_qkv_b"].astype(cdt).reshape(3 * c)
    if config.fused_matmul == "all":
        # v2 tiled kernel with fp32 accumulation (ops/fused_matmul.py); the
        # tp-active branch above stays head-explicit so GSPMD can shard H.
        # Decode's T=1 rows fall back inside the op on real TPUs.
        qkv = matmul_bias(y, w2, b2)
    else:
        qkv = y @ w2 + b2
    q, k, v = jnp.split(qkv, 3, axis=-1)
    return (
        q.reshape(b_, t_, h_, d_),
        k.reshape(b_, t_, h_, d_),
        v.reshape(b_, t_, h_, d_),
    )


def gather_attn_heads(o: jnp.ndarray, data_rows: bool = False) -> jnp.ndarray:
    """All-gather the head axis of an attention output before the
    out-projection when serving tensor parallelism is active; no-op
    otherwise (single device, tp=1, or outside ``activate_mesh``).

    The out-projection contracts over C = H*D. With H tp-sharded (the
    serving mesh — ``parallel.sharding.serve_param_pspecs``) GSPMD would
    compute per-shard partial products and psum them, re-associating the
    accumulation and breaking the serving engine's bit-exactness contract.
    Pinning ``o`` head-replicated first makes the shard boundary pure data
    movement: the gather moves bits, and the contraction then runs the
    single-device program on every device. ``data_rows`` keeps the leading
    batch axis sharded over 'data' (the decode step's row placement) so the
    gather is tp-only.
    """
    if not _tp_active():
        return o
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gpt_2_distributed_tpu.parallel.mesh import DATA_AXIS, active_mesh

    lead = DATA_AXIS if data_rows else None
    spec = P(lead, *([None] * (o.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        o, NamedSharding(active_mesh(), spec)
    )


def _attn_sublayer(
    config: GPT2Config,
    x: jnp.ndarray,  # [B, T, C] in compute dtype
    bp: dict[str, jnp.ndarray],
    rng: jax.Array | None,
    deterministic: bool,
) -> jnp.ndarray:
    """x + dropout(proj(attn(ln1(x)))).

    NOTE: ``models/decode.py::prefill`` mirrors this sublayer inline (it
    must capture each layer's K/V projection, which this function discards).
    A change to the sublayer structure here — a new op, a moved dropout
    site — must be replicated there; the teacher-forcing logit-parity test
    in tests/test_decode.py is the guard that catches a desync.
    """
    b, t, c = x.shape
    cdt = x.dtype
    if rng is not None:
        r_attn, r_aresid = jax.random.split(rng)
    else:
        r_attn = r_aresid = None

    y = layer_norm(x, bp["ln1_scale"], bp["ln1_bias"], config.layer_norm_eps)
    q, k, v = qkv_proj(config, y, bp)
    attn_fn = select_attention_impl(config.attention_impl, t)
    o = attn_fn(
        q, k, v,
        dropout_rate=config.attn_dropout, rng=r_attn, deterministic=deterministic,
    )
    o = o.reshape(b, t, c)
    if _mm_proj_fused(config):
        return matmul_bias_residual_dropout(
            o, bp["attn_proj_w"].astype(cdt), bp["attn_proj_b"].astype(cdt), x,
            rate=config.resid_dropout, rng=r_aresid, deterministic=deterministic,
            salt=SALT_MM_ATTN_PROJ,
        )
    o = o @ bp["attn_proj_w"].astype(cdt) + bp["attn_proj_b"].astype(cdt)
    o = dropout(o, config.resid_dropout, r_aresid, deterministic)
    return x + o


def _gelu_fused(config: GPT2Config) -> bool:
    return config.fused_layers in ("gelu", "all")


def _ln_fused(config: GPT2Config) -> bool:
    return config.fused_layers in ("ln", "all")


def _mm_fc_fused(config: GPT2Config) -> bool:
    return config.fused_matmul in ("mlp", "all")


def _mm_proj_fused(config: GPT2Config) -> bool:
    return config.fused_matmul in ("proj", "all")


def _mlp_core(
    config: GPT2Config,
    y: jnp.ndarray,  # [B, T, C] post-ln2, compute dtype
    bp: dict[str, jnp.ndarray],
    rng: jax.Array | None,
    deterministic: bool,
) -> jnp.ndarray:
    """fc matmul -> bias -> tanh-GELU -> activation dropout ([B, T, 4C]).

    With ``fused_layers`` in ("gelu", "all") the bias add, GELU and dropout
    run as one Pallas epilogue kernel over the matmul output — the [*, 4C]
    tensor is the largest between-matmul bandwidth pass in the block
    (ops/fused_layer.py); otherwise the unfused reference composition."""
    cdt = y.dtype
    if _mm_fc_fused(config):
        # v2: the fc matmul AND its epilogue in one kernel — supersedes the
        # v1 epilogue-only fusion below when both flags cover this leg.
        return matmul_bias_gelu_dropout(
            y, bp["mlp_fc_w"].astype(cdt), bp["mlp_fc_b"].astype(cdt),
            rate=config.resid_dropout, rng=rng, deterministic=deterministic,
        )
    if _gelu_fused(config):
        h = y @ bp["mlp_fc_w"].astype(cdt)
        return fused_bias_gelu_dropout(
            h, bp["mlp_fc_b"].astype(cdt),
            rate=config.resid_dropout, rng=rng, deterministic=deterministic,
        )
    y = y @ bp["mlp_fc_w"].astype(cdt) + bp["mlp_fc_b"].astype(cdt)
    y = gelu_tanh(y)
    return dropout(y, config.resid_dropout, rng, deterministic)


def _mlp_sublayer(
    config: GPT2Config,
    x: jnp.ndarray,  # [B, T, C] in compute dtype
    bp: dict[str, jnp.ndarray],
    rng: jax.Array | None,
    deterministic: bool,
) -> jnp.ndarray:
    """x + mlp(ln2(x)) — dropout after the activation AND after the
    projection, matching the reference's extra site at model.py:188."""
    cdt = x.dtype
    if rng is not None:
        r_mact, r_mresid = jax.random.split(rng)
    else:
        r_mact = r_mresid = None
    y = layer_norm(x, bp["ln2_scale"], bp["ln2_bias"], config.layer_norm_eps)
    y = _mlp_core(config, y, bp, r_mact, deterministic)
    if _mm_proj_fused(config):
        return matmul_bias_residual_dropout(
            y, bp["mlp_proj_w"].astype(cdt), bp["mlp_proj_b"].astype(cdt), x,
            rate=config.resid_dropout, rng=r_mresid, deterministic=deterministic,
            salt=SALT_MM_MLP_PROJ,
        )
    y = y @ bp["mlp_proj_w"].astype(cdt) + bp["mlp_proj_b"].astype(cdt)
    y = dropout(y, config.resid_dropout, r_mresid, deterministic)
    return x + y


def _attn_half_fused(
    config: GPT2Config,
    x: jnp.ndarray,  # [B, T, C] in compute dtype
    bp: dict[str, jnp.ndarray],
    rng: jax.Array | None,
    deterministic: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Attention sublayer ending in the fused LN+residual+dropout junction.

    Returns ``(r, y2)``: the post-attention residual stream ``r = x +
    dropout(proj(attn(ln1(x))))`` and ``y2 = ln2(r)``, the MLP's input —
    computed in one kernel pass (ops/fused_layer.py) instead of three
    bandwidth passes. The attention body is identical to ``_attn_sublayer``
    (which stays the decode-mirror reference; models/decode.py note there)."""
    b, t, c = x.shape
    cdt = x.dtype
    if rng is not None:
        r_attn, r_aresid = jax.random.split(rng)
    else:
        r_attn = r_aresid = None

    y = layer_norm(x, bp["ln1_scale"], bp["ln1_bias"], config.layer_norm_eps)
    q, k, v = qkv_proj(config, y, bp)
    attn_fn = select_attention_impl(config.attention_impl, t)
    o = attn_fn(
        q, k, v,
        dropout_rate=config.attn_dropout, rng=r_attn, deterministic=deterministic,
    )
    o = o.reshape(b, t, c)
    if _mm_proj_fused(config):
        # fused_matmul takes the proj leg: the v2 kernel already folds the
        # dropout and residual add into the matmul write-back, leaving the
        # v1 junction kernel nothing but the LN — run that unfused (a lone
        # LN is a single bandwidth pass XLA handles fine).
        r = matmul_bias_residual_dropout(
            o, bp["attn_proj_w"].astype(cdt), bp["attn_proj_b"].astype(cdt), x,
            rate=config.resid_dropout, rng=r_aresid, deterministic=deterministic,
            salt=SALT_MM_ATTN_PROJ,
        )
        return r, layer_norm(
            r, bp["ln2_scale"], bp["ln2_bias"], config.layer_norm_eps
        )
    o = o @ bp["attn_proj_w"].astype(cdt) + bp["attn_proj_b"].astype(cdt)
    return fused_ln_residual_dropout(
        x, o, bp["ln2_scale"], bp["ln2_bias"],
        eps=config.layer_norm_eps, rate=config.resid_dropout,
        rng=r_aresid, deterministic=deterministic,
    )


def _mlp_half_fused(
    config: GPT2Config,
    x: jnp.ndarray,   # [B, T, C] post-attention residual stream
    y2: jnp.ndarray,  # [B, T, C] ln2(x), produced by _attn_half_fused
    bp: dict[str, jnp.ndarray],
    rng: jax.Array | None,
    deterministic: bool,
) -> jnp.ndarray:
    """MLP sublayer consuming the pre-normalized ``y2`` and closing the block
    with the fused residual+dropout kernel. The block-final LN is NOT fused
    here — it belongs to the next block across the scan boundary."""
    cdt = x.dtype
    if rng is not None:
        r_mact, r_mresid = jax.random.split(rng)
    else:
        r_mact = r_mresid = None
    y = _mlp_core(config, y2, bp, r_mact, deterministic)
    if _mm_proj_fused(config):
        # fused_matmul takes the proj leg (matmul + bias + dropout +
        # block-closing residual in one kernel) — subsumes the v1
        # residual+dropout kernel below.
        return matmul_bias_residual_dropout(
            y, bp["mlp_proj_w"].astype(cdt), bp["mlp_proj_b"].astype(cdt), x,
            rate=config.resid_dropout, rng=r_mresid, deterministic=deterministic,
            salt=SALT_MM_MLP_PROJ,
        )
    y = y @ bp["mlp_proj_w"].astype(cdt) + bp["mlp_proj_b"].astype(cdt)
    return fused_residual_dropout(
        x, y, rate=config.resid_dropout, rng=r_mresid, deterministic=deterministic,
    )


def _block(
    config: GPT2Config,
    x: jnp.ndarray,  # [B, T, C] in compute dtype
    bp: dict[str, jnp.ndarray],  # one layer's params (no leading L axis)
    rng: jax.Array | None,
    deterministic: bool,
) -> jnp.ndarray:
    """One pre-LN transformer block: x + attn(ln1(x)); x + mlp(ln2(x))."""
    if rng is not None:
        r_attn, r_mlp = jax.random.split(rng)
    else:
        r_attn = r_mlp = None
    if _ln_fused(config):
        # Fused-junction layout: the attention half ends in the fused
        # LN+residual+dropout kernel and hands (r, ln2(r)) straight to the
        # MLP half, which closes the block with the fused residual kernel.
        # The remat split mirrors the unfused dispatch below — each half is
        # a checkpointable unit with the same save/replay trade-offs.
        attn_half = _attn_half_fused
        mlp_half = _mlp_half_fused
        if config.remat == "mlp":
            mlp_half = jax.checkpoint(_mlp_half_fused, static_argnums=(0, 5))
        elif config.remat == "attn":
            attn_half = jax.checkpoint(_attn_half_fused, static_argnums=(0, 4))
        elif config.remat == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            attn_half = jax.checkpoint(
                _attn_half_fused, policy=policy, static_argnums=(0, 4)
            )
            mlp_half = jax.checkpoint(
                _mlp_half_fused, policy=policy, static_argnums=(0, 5)
            )
        x, y2 = attn_half(config, x, bp, r_attn, deterministic)
        return mlp_half(config, x, y2, bp, r_mlp, deterministic)
    attn = _attn_sublayer
    mlp = _mlp_sublayer
    if config.remat == "mlp":
        # Sublayer remat: save the attention sublayer (its flash-kernel
        # forward is expensive to replay and its residuals are small), replay
        # only the MLP — whose 4C-wide activations dominate saved-activation
        # memory. Cuts the remat recompute from a full extra forward to the
        # MLP half, and the attention kernel runs once, not twice.
        mlp = jax.checkpoint(_mlp_sublayer, static_argnums=(0, 4))
    elif config.remat == "attn":
        # The mirror of "mlp": replay the attention sublayer, save the MLP's
        # activations. The memory-vs-recompute profile single-chip 774M
        # wants: attention's per-head internals ([B,H,T,D] stacks — 2x-padded
        # at D=64 tiling) are what blow 16G HBM, while its replay is only
        # ~10-15% of layer flops; the MLP's 4C tensors fit once the
        # attention stacks are gone and its replay (the expensive half)
        # never runs.
        attn = jax.checkpoint(_attn_sublayer, static_argnums=(0, 4))
    elif config.remat == "dots":
        # Policy remat: save matmul (dot) outputs, recompute only elementwise
        # ops (LN, GELU, dropout, residuals) in backward. Measured SLOWER
        # than both no-remat and "mlp" for 124M on v5e (41% vs 49% MFU at
        # b8a8); kept as an option for configs where matmul replays are the
        # binding cost, not a recommended default.
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        attn = jax.checkpoint(_attn_sublayer, policy=policy, static_argnums=(0, 4))
        mlp = jax.checkpoint(_mlp_sublayer, policy=policy, static_argnums=(0, 4))
    x = attn(config, x, bp, r_attn, deterministic)
    return mlp(config, x, bp, r_mlp, deterministic)


def hidden_states(
    params: Params,
    config: GPT2Config,
    idx: jnp.ndarray,  # [B, T] int token ids
    *,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    compute_dtype: jnp.dtype = jnp.bfloat16,
) -> jnp.ndarray:
    """Backbone forward: embeddings -> block stack -> final LayerNorm.

    Returns the [B, T, C] final hidden states in ``compute_dtype`` — the
    input to the tied lm_head. Exposed separately so callers that need
    logits for only a few positions (autoregressive decode,
    ``models/generate.py``) can slice before the [*, vocab] contraction
    instead of materializing full-vocab logits for every position.
    """
    b, t = idx.shape
    if t > config.n_positions:
        raise ValueError(
            f"sequence length {t} exceeds n_positions {config.n_positions}"
        )
    if not deterministic and rng is None:
        raise ValueError("training-mode forward (deterministic=False) needs rng")

    if rng is not None:
        r_embd, r_blocks = jax.random.split(rng)
    else:
        r_embd = r_blocks = None

    # Clip-mode gather: out-of-range token ids clamp (TPU hardware gather
    # semantics) instead of JAX's default NaN-fill — a stray corrupt token
    # degrades to a wrong embedding rather than silently NaN-ing the step.
    tok_embd = params["wte"].astype(compute_dtype).at[idx].get(mode="clip")
    x = tok_embd + params["wpe"].astype(compute_dtype)[:t]
    x = dropout(x, config.embd_dropout, r_embd, deterministic)

    block_params = params["block"]
    if config.scan_layers:
        layer_rngs = (
            jax.random.split(r_blocks, config.n_layer)
            if r_blocks is not None
            else jnp.zeros((config.n_layer, 2), dtype=jnp.uint32)
        )

        def body(carry, layer):
            bp, lr = layer
            out = _block(config, carry, bp, lr if r_blocks is not None else None,
                         deterministic)
            return out, None

        if config.remat and config.remat not in ("mlp", "attn", "dots"):
            # Full-block remat ("block"/True); the "mlp" and "dots" policies
            # are applied inside _block itself.
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, (block_params, layer_rngs))
    else:
        full_remat = config.remat and config.remat not in ("mlp", "attn", "dots")
        for i in range(config.n_layer):
            bp = jax.tree_util.tree_map(lambda a: a[i], block_params)
            lr = jax.random.fold_in(r_blocks, i) if r_blocks is not None else None
            blk = jax.checkpoint(_block, static_argnums=(0, 4)) if full_remat else _block
            x = blk(config, x, bp, lr, deterministic)

    return layer_norm(
        x, params["ln_f_scale"], params["ln_f_bias"], config.layer_norm_eps
    )


def forward(
    params: Params,
    config: GPT2Config,
    idx: jnp.ndarray,  # [B, T] int token ids
    labels: jnp.ndarray | None = None,  # [B, T] next-token ids, -100 = ignore
    *,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    return_logits: bool = False,
) -> tuple[jnp.ndarray | None, jnp.ndarray | None]:
    """Forward pass. Returns ``(logits [B,T,V] fp32 | None, loss fp32 | None)``.

    When ``labels`` are given and ``return_logits`` is False (the training
    path), the loss comes from the blocked cross-entropy — full ``[B,T,V]``
    logits are never materialized (``ops/losses.py``), and ``None`` is
    returned in their place. Inference (``labels=None``) always returns
    logits.

    Sequence-length guard matches the reference's hard error beyond
    n_positions (``/root/reference/model.py:291-292``) — here it is a trace-time
    (static-shape) check, which is the XLA-native place for it.
    """
    x = hidden_states(
        params, config, idx,
        rng=rng, deterministic=deterministic, compute_dtype=compute_dtype,
    )

    wte = params["wte"].astype(compute_dtype)
    if labels is not None and not return_logits and config.loss_impl == "blocked":
        # Training path: blocked CE over the tied head — no [B,T,V] logits.
        loss = blocked_cross_entropy(
            x.reshape(-1, config.n_embd), wte, labels.reshape(-1),
            config.loss_block_rows,
        )
        return None, loss

    # Tied lm_head: logits = x @ wte^T, fp32 accumulation out of the bf16 matmul.
    logits = jnp.einsum(
        "btc,vc->btv", x, wte, preferred_element_type=jnp.float32,
    )
    loss = None
    if labels is not None:
        loss = cross_entropy(logits, labels)
    if labels is not None and not return_logits:
        # Training path with loss_impl="dense": logits are a backward-pass
        # residual, not an output — dropping them here lets jit DCE the
        # [B, T, V] fp32 tensor from the step's outputs.
        return None, loss
    return logits, loss


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Flat token-mean cross-entropy with ignore_index=-100, fp32 — the
    reference's loss exactly (``/root/reference/model.py:353-359``)."""
    logits = logits.astype(jnp.float32)
    valid = labels != IGNORE_INDEX
    safe_labels = jnp.where(valid, labels, 0)
    logprobs = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(
        logprobs, safe_labels[..., None], axis=-1, mode="clip"
    )[..., 0]
    ll = jnp.where(valid, ll, 0.0)
    count = jnp.maximum(valid.sum(), 1)
    return -(ll.sum() / count)
