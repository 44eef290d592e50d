"""The serving step programs of the family ``DeepseekV3Config``: chunked
prefill and the decode step through ONE latent pool.

* Every layer's cache is its latent rows ``[RMSNorm(c) ; RoPE(k_pe)]`` (``r
  + dr`` values a position, shared by all the heads, zero-padded to
  ``config.latent_lanes``) in the pool that ``paged_cache.init_pools`` makes
  of ``config.kv_pool_view``: ``[layers, N, 1, bs, latent_lanes]``, written
  by ``write_chunk`` / ``write_rows`` with no V pool. The second pool the engine hands the programs is empty and goes
  back as it came; the family keeps no state beside the pool.
* A chunk attends in the expanded form: its queries walk the row's table
  tile by tile, each tile's rows decompressed to keys and values
  (``paged_mla_prefill``). A decode step attends in the absorbed form: the
  up-projections move to the query's side and the rows are scored as they
  lie (``paged_mla_attention``; on a TPU the kernel ``paged_mla_decode``).

The leading dense layers are walked in Python, the expert layers - all
alike - in one ``lax.scan`` over their stacked weights, the pool carried
through it with the layer's index traced: one traced expert layer, whatever
the depth. Each part runs under a ``jax.named_scope`` - ``deepseek_v3/
mla_project`` (the norm, the query and latent projections, RoPE, the
absorbed query), ``deepseek_v3/mla_prefill`` and ``deepseek_v3/mla_decode``
(the row written into the pool and the attention over it),
``deepseek_v3/mla_out`` (``W_UV`` where absorbed, ``W_o``),
``deepseek_v3/mlp``, ``deepseek_v3/moe_route``, ``deepseek_v3/moe_experts``,
``deepseek_v3/moe_shared`` - so that a device trace names its operations.
After the sampled tokens both return ``COUNTERS``, summed over the expert
layers, in the same int32 array: they reach the host in the token read-back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from gpt_2_distributed_tpu.config import (
    DENSE_LAYER,
    EXPERT_LAYER,
    DeepseekV3Config,
    ServeConfig,
)
from gpt_2_distributed_tpu.models import deepseek_v3 as ds
from gpt_2_distributed_tpu.models.generate import sample_rows
from gpt_2_distributed_tpu.ops.paged_attention import paged_mla_attention, paged_mla_prefill
from gpt_2_distributed_tpu.serving.paged_cache import as_blocks, write_chunk, write_rows

# What follows the tokens in both programs' first output: token-expert pairs
# that fell to held experts, and held experts that got a row.
COUNTERS = ("moe_rows", "moe_experts_touched")


def init_state(config: DeepseekV3Config, serve: ServeConfig, dtype) -> dict:
    """The family keeps nothing beside its latent pool."""
    return {}


def _layers(config: DeepseekV3Config, params, h, pool, attend, valid):
    """Every layer over ``h`` [T, C] float32: ``attend(lp, layer, h, pool)``
    gives (the attention's out [T, C] float32, the pool). Returns (h, pool,
    sizes [expert layers, held] int32)."""
    n_dense = len(params["dense"])
    for i, lp in enumerate(params["dense"]):
        out, pool = attend(lp, i, h, pool)
        h = h + out
        h = h + ds.feed_forward(config, params, lp, DENSE_LAYER, h, valid)[0]
    if "moe" not in params:
        return h, pool, jnp.zeros((0, config.n_held), jnp.int32)

    def layer(carry, xs):
        h, pool = carry
        lp, i = xs
        out, pool = attend(lp, i, h, pool)
        h = h + out
        out, sizes = ds.feed_forward(config, params, lp, EXPERT_LAYER, h, valid)
        return (h + out, pool), sizes

    n_moe = config.num_hidden_layers - n_dense
    (h, pool), sizes = jax.lax.scan(
        layer, (h, pool),
        (params["moe"], jnp.arange(n_dense, n_dense + n_moe, dtype=jnp.int32)))
    return h, pool, sizes


def _as_stored(config: DeepseekV3Config, x, dtype):
    """A latent row (or an absorbed query) [..., r + dr] as the pool holds
    it: zero-padded to ``config.latent_lanes``, in ``dtype``."""
    pad = config.latent_lanes - x.shape[-1]
    return jnp.pad(x.astype(dtype), [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _with_counters(tokens, sizes):
    """``tokens`` [R] int32 with ``COUNTERS`` after them."""
    counted = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return jnp.concatenate([tokens, counted])


def chunk_prefill_impl(
    params,
    k_pool: jnp.ndarray,       # the latent pool as stored (`paged_cache.pool_shape`) — donated
    v_pool: jnp.ndarray,       # empty — donated, handed back
    state: dict,               # {} — donated
    bt: jnp.ndarray,           # [1, M] int32 the request's block-table row
    chunk: jnp.ndarray,        # [1, C] int32 tokens, right-padded
    start: jnp.ndarray,        # [1] int32 position of chunk[0, 0]
    clen: jnp.ndarray,         # [1] int32 real tokens
    keys: jnp.ndarray,         # [1, 2] uint32 the request's PRNG chain
    slots: jnp.ndarray,        # [1] int32 the request's slot
    *,
    config: DeepseekV3Config,
    temperature: float,
    top_k: int | None,
):
    """One request's prefill chunk: its latent rows into its pool blocks, its
    queries over the row's table in the expanded form. Returns (the token
    sampled after the last real position and ``COUNTERS`` [1 + 2], advanced
    keys, pools, state)."""
    c = chunk.shape[1]
    stored = k_pool.shape
    pool = as_blocks(k_pool)
    table = bt[0]
    n_real = jnp.asarray(clen, jnp.int32)[0]
    pos = jnp.asarray(start, jnp.int32)[0] + jnp.arange(c, dtype=jnp.int32)
    valid = jnp.arange(c) < n_real

    def attend(lp, layer, h, pool):
        q_nope, q_pe, row = ds.mla_project(config, params, lp, h, pos)
        row = _as_stored(config, row, pool.dtype)
        w_uk, w_uv = ds.up_projections(config, lp)
        with jax.named_scope(ds.SCOPE + "mla_prefill"):
            pool, _ = write_chunk(pool, None, layer, bt, start, valid[None],
                                  row[None, :, None], None)
            o = paged_mla_prefill(q_nope, q_pe, pool, table, pos, w_uk, w_uv, layer)
        return ds.mla_out(config, lp, o), pool

    h, pool, sizes = _layers(config, params, ds.embed(params, chunk[0]), pool,
                             attend, valid)
    h_last = jax.lax.dynamic_index_in_dim(h, jnp.maximum(n_real - 1, 0), keepdims=True)
    first, keys = sample_rows(
        ds.logits_of(config, params, h_last), keys, temperature, top_k)
    return _with_counters(first, sizes), keys, pool.reshape(stored), v_pool, state


def decode_step_impl(
    params,
    k_pool: jnp.ndarray,       # the latent pool as stored — donated
    v_pool: jnp.ndarray,       # empty — donated, handed back
    state: dict,               # {} — donated
    block_table: jnp.ndarray,  # [B, M] int32
    tokens: jnp.ndarray,       # [B] int32 the token to process, at `pos`
    pos: jnp.ndarray,          # [B] int32
    active: jnp.ndarray,       # [B] bool
    keys: jnp.ndarray,         # [B, 2] uint32 per-slot PRNG chains
    *,
    config: DeepseekV3Config,
    temperature: float,
    top_k: int | None,
):
    """One decode step for every slot: row ``b`` is slot ``b``. An idle row
    (or one still prefilling) writes to the null block, attends nothing, is
    given to no expert, and its token is discarded by the host. Returns (next
    tokens and ``COUNTERS`` [B + 2], advanced keys, pools, state)."""
    bsz, width = block_table.shape
    stored = k_pool.shape
    pool = as_blocks(k_pool)
    bs = pool.shape[-2]
    pos = jnp.asarray(pos, jnp.int32)
    blk = jnp.where(active, block_table[jnp.arange(bsz), pos // bs], 0)
    off = pos % bs
    # a row attends its whole table as far as it has got
    blocks = block_table[:, None]
    logical = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32), blocks.shape)
    count = jnp.where(active, pos // bs + 1, 0)[:, None]

    def attend(lp, layer, h, pool):
        q_nope, q_pe, row = ds.mla_project(config, params, lp, h, pos)
        q = _as_stored(config, ds.absorbed_query(config, lp, q_nope, q_pe), q_pe.dtype)
        with jax.named_scope(ds.SCOPE + "mla_decode"):
            pool, _ = write_rows(pool, None, layer, blk, off,
                                 _as_stored(config, row, pool.dtype)[:, None], None)
            u = paged_mla_attention(
                q[:, None], pool, blocks, logical, count, pos, layer,
                value_lanes=config.kv_lora_rank, scale_dim=config.qk_head_dim)
        return ds.mla_out(config, lp, u[:, 0], absorbed=True), pool

    h, pool, sizes = _layers(config, params, ds.embed(params, tokens), pool,
                             attend, active)
    next_tokens, keys = sample_rows(
        ds.logits_of(config, params, h), keys, temperature, top_k)
    return _with_counters(next_tokens, sizes), keys, pool.reshape(stored), v_pool, state
