"""The serving step programs of the family ``SalaConfig`` (MiniCPM-SALA):
chunked prefill and the decode step through TWO kinds of cache.

* The sparse layers' K/V live in the paged pools exactly as GPT-2's do
  (``paged_cache.init_pools`` over ``config.kv_pool_view``: ``[kv_layers, N,
  KV, bs, D]``, written by ``write_chunk`` / ``write_rows``), one pool block
  being one selection block. Beside them ``state["kc"]`` holds the selector's
  compressed keys, paged by the same tables: the windows that START in block
  ``b`` are the ``block / stride`` slots of block ``b``, written when their
  last token arrives (a window spans two blocks).
* The linear layers keep no K/V: ``state["lin"]`` is ``[linear_layers,
  max_batch, H, d, d]`` float32, one state a slot and layer. A chunk that
  starts at position 0 starts from a zero state, whatever the slot held - so
  a slot that is reused, or a preempted request that prefills again, needs no
  reset dispatch - and a decode step leaves an idle row's state alone.

Both programs walk ``params["layers"]`` in Python: sixteen layers of two
kinds unroll into one program (no stacked weight is ever sliced). Each mixer
runs under a ``jax.named_scope`` (``sala/select``, ``sala/sparse_attend``,
``sala/lightning``) so that a device trace names its operations.

A prefill chunk is ONE request's (``prefill_batch == 1``): its queries walk
the row's table in tiles under the selection mask
(``paged_masked_attention``); decode rows gather exactly their selected
blocks (``paged_sparse_attention``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from gpt_2_distributed_tpu.config import LIGHTNING_MIXER, SalaConfig, ServeConfig
from gpt_2_distributed_tpu.models import minicpm_sala as sala
from gpt_2_distributed_tpu.models.generate import sample_token
from gpt_2_distributed_tpu.ops import linear_attention, sparse_select
from gpt_2_distributed_tpu.ops.paged_attention import (
    paged_masked_attention,
    paged_sparse_attention,
)
from gpt_2_distributed_tpu.serving.paged_cache import (
    as_blocks,
    write_chunk,
    write_rows,
)

SELECT_ROWS = 512   # queries of a chunk selected and attended at a time


def init_state(config: SalaConfig, serve: ServeConfig, dtype) -> dict:
    """The family's cache beside the K/V pools, zeros."""
    sp = config.sparse
    d = config.lightning_head_dim
    return {
        "kc": jnp.zeros(
            (len(config.sparse_layers), serve.num_blocks,
             config.num_key_value_heads, sp.block // sp.stride, config.head_dim),
            dtype),
        "lin": jnp.zeros(
            (len(config.lightning_layers), serve.max_batch, config.lightning_nh, d, d),
            jnp.float32),
    }


def _row_windows(kc_pool, layer: int, table):
    """One row's compressed keys in window order, [M * wpb, KV, d]."""
    rows = kc_pool[layer, table]                              # [M, KV, wpb, d]
    m, kv, wpb, d = rows.shape
    return rows.transpose(0, 2, 1, 3).reshape(m * wpb, kv, d)


def _sample_rows(logits, keys, temperature, top_k):
    def row_sample(logits_row, key):
        key, sub = jax.random.split(key)
        return sample_token(logits_row[None], sub, temperature, top_k)[0], key

    tokens, keys = jax.vmap(row_sample)(logits, keys)
    return tokens.astype(jnp.int32), keys


def _refresh_chunk_windows(config, kc_pool, k_pool, layer, table, start, chunk_len):
    """Recompute, from the keys the pool now holds, every window that starts
    in the chunk's blocks or in the block before them, and put them in the
    compressed-key pool. The windows that run past the chunk hold a partial
    mean until the next chunk (or the decode step that completes them)
    rewrites them; no query looks at a window before its last token."""
    sp = config.sparse
    bs, wpb = sp.block, sp.block // sp.stride
    m = table.shape[0]
    n = chunk_len // bs + 1
    slots = start // bs - 1 + jnp.arange(n)                   # [n] table slots
    inside = (slots >= 0) & (slots < m)
    blocks = jnp.where(inside, table[jnp.clip(slots, 0, m - 1)], 0)
    keys = k_pool[layer, blocks]                              # [n, KV, bs, d]
    kv, d = keys.shape[1], keys.shape[3]
    keys = keys.transpose(0, 2, 1, 3).reshape(n * bs, kv, d)
    means = sparse_select.window_means(keys, sp)              # [n * wpb, KV, d]
    means = means.reshape(n, wpb, kv, d).transpose(0, 2, 1, 3).astype(kc_pool.dtype)
    return kc_pool.at[layer, blocks].set(means)


def _refresh_row_windows(config, kc_pool, k_pool, layer, block_table, pos, active):
    """The decode step's share: the window whose last token is ``pos`` (there
    is one when ``pos + 1`` is a multiple of the stride), a row at a time. Its
    keys lie in at most two blocks, which are gathered WHOLE and averaged
    under a mask: a gather of single positions across the KV heads makes the
    compiler re-lay out the entire pool for it, once a layer."""
    sp = config.sparse
    bs, wpb = sp.block, sp.block // sp.stride
    b = pos.shape[0]
    j = (pos + 1 - sp.window) // sp.stride
    done = active & ((pos + 1) % sp.stride == 0) & (pos + 1 >= sp.window)
    j = jnp.where(done, j, 0)
    first = j * sp.stride                                                  # [B]
    n_span = -(-sp.window // bs) + 1
    slots = (first // bs)[:, None] + jnp.arange(n_span)[None]              # [B, S]
    slots = jnp.minimum(slots, block_table.shape[1] - 1)
    keys = k_pool[layer, jnp.take_along_axis(block_table, slots, axis=1)]  # [B, S, KV, bs, d]
    at = slots[:, :, None] * bs + jnp.arange(bs)[None, None]               # [B, S, bs]
    inside = (at >= first[:, None, None]) & (at < (first + sp.window)[:, None, None])
    # a clipped slot repeats the last block: count each position once
    inside = inside & (jnp.arange(n_span)[None, :, None] == (at // bs - (first // bs)[:, None, None]))
    mean = jnp.einsum("bsktd,bst->bkd", keys.astype(jnp.float32),
                      inside.astype(jnp.float32) / sp.window,
                      precision=jax.lax.Precision.HIGHEST).astype(kc_pool.dtype)
    home = jnp.where(done, block_table[jnp.arange(b), j // wpb], 0)
    return kc_pool.at[layer, home, :, j % wpb].set(mean)


def chunk_prefill_impl(
    params,
    k_pool: jnp.ndarray,       # as stored (`paged_cache.pool_shape`) — donated
    v_pool: jnp.ndarray,
    state: dict,               # {"kc", "lin"} — donated
    bt: jnp.ndarray,           # [1, M] int32 the request's block-table row
    chunk: jnp.ndarray,        # [1, C] int32 tokens, right-padded
    start: jnp.ndarray,        # [1] int32 position of chunk[0, 0], a multiple of C
    clen: jnp.ndarray,         # [1] int32 real tokens
    keys: jnp.ndarray,         # [1, 2] uint32 the request's PRNG chain
    slots: jnp.ndarray,        # [1] int32 the request's slot: whose state this is
    *,
    config: SalaConfig,
    temperature: float,
    top_k: int | None,
):
    """One request's prefill chunk: K/V of the sparse layers into its pool
    blocks, the compressed keys of the windows it completes beside them, the
    linear layers' state carried on from the slot's (from zero where the
    chunk starts the request). Returns (the token sampled after the last real
    position [1], advanced keys, pools, state)."""
    c = chunk.shape[1]
    stored = k_pool.shape
    k_pool, v_pool = as_blocks(k_pool), as_blocks(v_pool)
    kc_pool, lin = state["kc"], state["lin"]
    table, slot = bt[0], slots[0]
    start0 = jnp.asarray(start, jnp.int32)[0]
    n_real = jnp.asarray(clen, jnp.int32)[0]
    pos = start0 + jnp.arange(c, dtype=jnp.int32)
    valid = jnp.arange(c) < n_real
    slopes = linear_attention.decay_slopes(config.lightning_nh)
    rows = min(SELECT_ROWS, c)

    h = sala.embed(config, params, chunk[0])                         # [C, hidden] f32
    kv_i = lin_i = 0
    for kind, lp in zip(config.mixer_types, params["layers"]):
        x = sala.normed_input(config, params, lp, h)
        q, k, v = sala.qkv(config, kind, lp, x, pos)
        if kind == LIGHTNING_MIXER:
            with jax.named_scope("sala/lightning"):
                s_in = jnp.where(start0 == 0, 0.0, lin[lin_i, slot])
                o, s_out = linear_attention.chunked(q, k, v, valid, s_in, slopes)
                lin = jax.lax.dynamic_update_slice(
                    lin, s_out[None, None], (lin_i, slot, 0, 0, 0))
            lin_i += 1
        else:
            k_pool, v_pool = write_chunk(
                k_pool, v_pool, kv_i, bt, start, valid[None],
                k[None].astype(k_pool.dtype), v[None].astype(v_pool.dtype))
            kv_heads = k.shape[1]
            qg = q.reshape(c, kv_heads, -1, q.shape[-1])             # [C, KV, G, d]
            with jax.named_scope("sala/select"):
                kc_pool = _refresh_chunk_windows(
                    config, kc_pool, k_pool, kv_i, table, start0, c)
                windows = _row_windows(kc_pool, kv_i, table)

            def attend(args, layer=kv_i, windows=windows, kp=k_pool, vp=v_pool):
                q_rows, p_rows = args
                with jax.named_scope("sala/select"):
                    keep = sparse_select.select_blocks(
                        sparse_select.block_scores(q_rows, windows, p_rows, config.sparse),
                        p_rows, config.sparse)
                with jax.named_scope("sala/sparse_attend"):
                    return paged_masked_attention(
                        q_rows, kp, vp, table, p_rows, keep, layer)

            o = jax.lax.map(attend, (
                qg.reshape(c // rows, rows, *qg.shape[1:]), pos.reshape(c // rows, rows)))
            o = o.reshape(c, *q.shape[1:])
            kv_i += 1
        h = sala.add_branch(config, h, sala.mixer_out(config, kind, lp, x, o))
        h = sala.add_branch(config, h, sala.mlp(config, lp, h))

    h_last = jax.lax.dynamic_index_in_dim(h, jnp.maximum(n_real - 1, 0), keepdims=True)
    first, keys = _sample_rows(
        sala.logits_of(config, params, h_last), keys, temperature, top_k)
    return (first, keys, k_pool.reshape(stored), v_pool.reshape(stored),
            {"kc": kc_pool, "lin": lin})


def decode_step_impl(
    params,
    k_pool: jnp.ndarray,       # as stored — donated
    v_pool: jnp.ndarray,
    state: dict,               # {"kc", "lin"} — donated
    block_table: jnp.ndarray,  # [B, M] int32
    tokens: jnp.ndarray,       # [B] int32 the token to process, at `pos`
    pos: jnp.ndarray,          # [B] int32
    active: jnp.ndarray,       # [B] bool
    keys: jnp.ndarray,         # [B, 2] uint32 per-slot PRNG chains
    *,
    config: SalaConfig,
    temperature: float,
    top_k: int | None,
):
    """One decode step for every slot: row ``b`` is slot ``b``. An idle row
    (or one still prefilling) writes to the null block, selects nothing,
    keeps its linear state, and its token is discarded by the host."""
    sp = config.sparse
    bsz = tokens.shape[0]
    stored = k_pool.shape
    k_pool, v_pool = as_blocks(k_pool), as_blocks(v_pool)
    kc_pool, lin = state["kc"], state["lin"]
    bs = k_pool.shape[-2]
    pos = jnp.asarray(pos, jnp.int32)
    blk = jnp.where(active, block_table[jnp.arange(bsz), pos // bs], 0)
    off = pos % bs
    slopes = linear_attention.decay_slopes(config.lightning_nh)
    width = min(sp.list_width, block_table.shape[1])

    h = sala.embed(config, params, tokens)                           # [B, hidden] f32
    kv_i = lin_i = 0
    for kind, lp in zip(config.mixer_types, params["layers"]):
        x = sala.normed_input(config, params, lp, h)
        q, k, v = sala.qkv(config, kind, lp, x[:, None], pos[:, None])
        q, k, v = q[:, 0], k[:, 0], v[:, 0]                          # [B, H, d]
        if kind == LIGHTNING_MIXER:
            with jax.named_scope("sala/lightning"):
                o, s_new = linear_attention.step(q, k, v, active, lin[lin_i], slopes)
                lin = lin.at[lin_i].set(s_new)
            lin_i += 1
        else:
            k_pool, v_pool = write_rows(
                k_pool, v_pool, kv_i, blk, off,
                k.astype(k_pool.dtype), v.astype(v_pool.dtype))
            kv_heads = k.shape[1]
            qg = q.reshape(bsz, kv_heads, -1, q.shape[-1])           # [B, KV, G, d]
            with jax.named_scope("sala/select"):
                kc_pool = _refresh_row_windows(
                    config, kc_pool, k_pool, kv_i, block_table, pos, active)

                def select(q_row, table, p, layer=kv_i, kcp=kc_pool):
                    keep = sparse_select.select_blocks(
                        sparse_select.block_scores(
                            q_row[None], _row_windows(kcp, layer, table), p[None], sp),
                        p[None], sp)[:, 0]                           # [KV, M]
                    return sparse_select.mask_to_list(keep, width)

                logical, count = jax.vmap(select)(qg, block_table, pos)
                count = jnp.where(active[:, None], count, 0)
                physical = jnp.take_along_axis(block_table[:, None], logical, axis=2)
            with jax.named_scope("sala/sparse_attend"):
                o = paged_sparse_attention(
                    qg, k_pool, v_pool, physical, logical, count, pos, kv_i)
            o = o.reshape(bsz, *q.shape[1:])
            kv_i += 1
        h = sala.add_branch(config, h, sala.mixer_out(config, kind, lp, x, o))
        h = sala.add_branch(config, h, sala.mlp(config, lp, h))

    next_tokens, keys = _sample_rows(
        sala.logits_of(config, params, h), keys, temperature, top_k)
    return (next_tokens, keys, k_pool.reshape(stored), v_pool.reshape(stored),
            {"kc": kc_pool, "lin": lin})
