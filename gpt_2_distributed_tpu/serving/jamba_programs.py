"""The serving step programs of the family ``JambaConfig`` (Jamba): chunked
prefill and the decode step through THREE kinds of cache.

* The attention layers' K/V live in the paged pools exactly as GPT-2's do
  (``paged_cache.init_pools`` over ``config.kv_pool_view``: ``[kv_layers, N,
  KV, bs, D]``, written by ``write_chunk`` / ``write_rows``) and are attended
  through the grouped-query forms of ``ops/paged_attention.py`` at ONE KV
  head - a chunk's queries walk the row's table in tiles, a decode row
  gathers its table.
* ``state["ssm"]`` is ``[mamba_layers, max_batch, N, D]`` float32, one
  selective-scan state a slot and Mamba layer, the channels last (a last
  dimension of N = 16 would be padded to 128 lanes); it does not grow with
  the sequence.
* ``state["conv"]`` is ``[mamba_layers, max_batch, (K - 1) * D]``: the K - 1
  inputs of the causal convolution that came before the slot's next token,
  end to end (flat, as ``serving/nemotron_programs.py`` keeps its own).

A chunk that starts at position 0 starts from zero states, whatever the slot
held - so a slot that is reused, or a preempted request that prefills again,
needs no reset dispatch. A padded tail leaves both states as the last real
token left them (``dt = 0`` there; the convolution's tail is cut at the true
end), and a decode step leaves an idle row's alone.

Both programs walk ``params["layers"]`` in Python and run each part under a
``jax.named_scope`` - ``jamba/mamba_proj`` (the four projections),
``jamba/sscan`` (convolution, inner norms, ``dt``, the scan - a Pallas kernel
on a TPU - or the one-token update, the skip and the gate), ``jamba/attend``
(the K/V write and the attention over the pools), ``jamba/mlp`` - so that a
device trace names its operations. Nothing follows the sampled tokens: the family's counters are the
host's (``families._jamba_rows``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from gpt_2_distributed_tpu.config import MAMBA_LAYER, JambaConfig, ServeConfig
from gpt_2_distributed_tpu.models import jamba as jb
from gpt_2_distributed_tpu.models.generate import sample_rows
from gpt_2_distributed_tpu.ops import selective_scan, ssd
from gpt_2_distributed_tpu.ops.paged_attention import (
    paged_masked_attention,
    paged_sparse_attention,
)
from gpt_2_distributed_tpu.serving.paged_cache import (
    as_blocks,
    write_chunk,
    write_rows,
)


def init_state(config: JambaConfig, serve: ServeConfig, dtype) -> dict:
    """The family's cache beside the K/V pools, zeros."""
    layers = len(config.layers_of(MAMBA_LAYER))
    return {
        "ssm": jnp.zeros(
            (layers, serve.max_batch, config.mamba_d_state, config.d_inner), jnp.float32),
        "conv": jnp.zeros(
            (layers, serve.max_batch, (config.mamba_d_conv - 1) * config.d_inner), dtype),
    }


def chunk_prefill_impl(
    params,
    k_pool: jnp.ndarray,       # as stored (`paged_cache.pool_shape`) — donated
    v_pool: jnp.ndarray,
    state: dict,               # {"ssm", "conv"} — donated
    bt: jnp.ndarray,           # [1, M] int32 the request's block-table row
    chunk: jnp.ndarray,        # [1, C] int32 tokens, right-padded
    start: jnp.ndarray,        # [1] int32 position of chunk[0, 0]
    clen: jnp.ndarray,         # [1] int32 real tokens
    keys: jnp.ndarray,         # [1, 2] uint32 the request's PRNG chain
    slots: jnp.ndarray,        # [1] int32 the request's slot: whose state this is
    *,
    config: JambaConfig,
    temperature: float,
    top_k: int | None,
):
    """One request's prefill chunk: K/V of the attention layers into its pool
    blocks, the scan and convolution states carried on from the slot's (from
    zero where the chunk starts the request). Returns (the token sampled after
    the last real position [1], advanced keys, pools, state)."""
    c = chunk.shape[1]
    stored = k_pool.shape
    k_pool, v_pool = as_blocks(k_pool), as_blocks(v_pool)
    ssm, conv = state["ssm"], state["conv"]
    table, slot = bt[0], slots[0]
    start0 = jnp.asarray(start, jnp.int32)[0]
    n_real = jnp.asarray(clen, jnp.int32)[0]
    pos = start0 + jnp.arange(c, dtype=jnp.int32)
    valid = jnp.arange(c) < n_real
    fresh = start0 == 0
    dtype = params["embed"].dtype
    see_all = jnp.ones((config.num_key_value_heads, c, table.shape[0]), bool)

    h = jb.embed(params, chunk[0])                                  # [C, hidden] f32
    kv_i = ssm_i = 0
    for kind, lp in zip(config.layer_kinds, params["layers"]):
        x = jb.normed_input(config, params, lp, h)
        if kind == MAMBA_LAYER:
            u_raw, z = jb.mamba_in(config, lp, x)
            with jax.named_scope(jb.SCAN_SCOPE):
                tail = jnp.where(fresh, 0, conv[ssm_i, slot]).astype(dtype)
                tail = tail.reshape(config.mamba_d_conv - 1, config.d_inner)
                conv_out, tail = ssd.conv_chunk(
                    u_raw, tail, lp["conv_w"], lp["conv_b"], n_real)
            u, b, cc, dt, a = jb.scan_inputs(config, lp, conv_out, valid)
            with jax.named_scope(jb.SCAN_SCOPE):
                s_in = jnp.where(fresh, 0.0, ssm[ssm_i, slot])
                y, s_out = selective_scan.chunked(u, dt, a, b, cc, s_in)
                ssm = jax.lax.dynamic_update_slice(
                    ssm, s_out[None, None], (ssm_i, slot, 0, 0))
                conv = jax.lax.dynamic_update_slice(
                    conv, tail.reshape(1, 1, -1).astype(conv.dtype), (ssm_i, slot, 0))
                gated = jb.mamba_gate(lp, y, u, z)
            out = jb.mamba_out(lp, gated)
            ssm_i += 1
        else:
            q, k, v = jb.attention_qkv(config, lp, x)
            with jax.named_scope("jamba/attend"):
                k_pool, v_pool = write_chunk(
                    k_pool, v_pool, kv_i, bt, start, valid[None],
                    k[None].astype(k_pool.dtype), v[None].astype(v_pool.dtype))
                qg = q.reshape(c, k.shape[1], -1, q.shape[-1])       # [C, KV, G, d]
                o = paged_masked_attention(qg, k_pool, v_pool, table, pos, see_all, kv_i)
            out = o.reshape(c, -1) @ lp["wo"]
            kv_i += 1
        h = h + out.astype(jnp.float32)
        h = h + jb.mlp(config, params, lp, h)

    h_last = jax.lax.dynamic_index_in_dim(h, jnp.maximum(n_real - 1, 0), keepdims=True)
    first, keys = sample_rows(
        jb.logits_of(config, params, h_last), keys, temperature, top_k)
    return (first, keys, k_pool.reshape(stored), v_pool.reshape(stored),
            {"ssm": ssm, "conv": conv})


def decode_step_impl(
    params,
    k_pool: jnp.ndarray,       # as stored — donated
    v_pool: jnp.ndarray,
    state: dict,               # {"ssm", "conv"} — donated
    block_table: jnp.ndarray,  # [B, M] int32
    tokens: jnp.ndarray,       # [B] int32 the token to process, at `pos`
    pos: jnp.ndarray,          # [B] int32
    active: jnp.ndarray,       # [B] bool
    keys: jnp.ndarray,         # [B, 2] uint32 per-slot PRNG chains
    *,
    config: JambaConfig,
    temperature: float,
    top_k: int | None,
):
    """One decode step for every slot: row ``b`` is slot ``b``. An idle row
    (or one still prefilling) writes to the null block, attends nothing,
    keeps both its states, and its token is discarded by the host. Returns
    (next tokens [B], advanced keys, pools, state)."""
    bsz = block_table.shape[0]
    stored = k_pool.shape
    k_pool, v_pool = as_blocks(k_pool), as_blocks(v_pool)
    ssm, conv = state["ssm"], state["conv"]
    bs = k_pool.shape[-2]
    pos = jnp.asarray(pos, jnp.int32)
    blk = jnp.where(active, block_table[jnp.arange(bsz), pos // bs], 0)
    off = pos % bs
    kv_heads, width = config.num_key_value_heads, block_table.shape[1]
    # a row attends its whole table as far as it has got
    physical = jnp.broadcast_to(block_table[:, None], (bsz, kv_heads, width))
    logical = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32), physical.shape)
    count = jnp.broadcast_to(
        jnp.where(active, pos // bs + 1, 0)[:, None], (bsz, kv_heads))

    h = jb.embed(params, tokens)                                    # [B, hidden] f32
    kv_i = ssm_i = 0
    for kind, lp in zip(config.layer_kinds, params["layers"]):
        x = jb.normed_input(config, params, lp, h)
        if kind == MAMBA_LAYER:
            u_raw, z = jb.mamba_in(config, lp, x)
            with jax.named_scope(jb.SCAN_SCOPE):
                conv_out, tail = ssd.conv_step(
                    u_raw, conv[ssm_i], lp["conv_w"], lp["conv_b"], active)
            u, b, cc, dt, a = jb.scan_inputs(config, lp, conv_out, active)
            with jax.named_scope(jb.SCAN_SCOPE):
                y, s_new = selective_scan.step(u, dt, a, b, cc, ssm[ssm_i])
                ssm = ssm.at[ssm_i].set(s_new)
                conv = conv.at[ssm_i].set(tail)
                gated = jb.mamba_gate(lp, y, u, z)
            out = jb.mamba_out(lp, gated)
            ssm_i += 1
        else:
            q, k, v = jb.attention_qkv(config, lp, x)                # [B, H, d]
            with jax.named_scope("jamba/attend"):
                k_pool, v_pool = write_rows(
                    k_pool, v_pool, kv_i, blk, off,
                    k.astype(k_pool.dtype), v.astype(v_pool.dtype))
                qg = q.reshape(bsz, kv_heads, -1, q.shape[-1])       # [B, KV, G, d]
                o = paged_sparse_attention(
                    qg, k_pool, v_pool, physical, logical, count, pos, kv_i)
            out = o.reshape(bsz, -1) @ lp["wo"]
            kv_i += 1
        h = h + out.astype(jnp.float32)
        h = h + jb.mlp(config, params, lp, h)

    next_tokens, keys = sample_rows(
        jb.logits_of(config, params, h), keys, temperature, top_k)
    return (next_tokens, keys, k_pool.reshape(stored), v_pool.reshape(stored),
            {"ssm": ssm, "conv": conv})
