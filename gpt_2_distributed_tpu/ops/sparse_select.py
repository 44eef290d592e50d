"""Block selection for learned sparse attention (InfLLM-V2).

A query does not attend its whole context: it scores the context's
*compressed keys* (means of ``window`` keys, one every ``stride``), turns the
scores into one score a block of ``block`` keys, and attends the first
``init_blocks`` blocks, the ``local_blocks`` that end with its own, and the
``topk`` best-scoring of the rest - every block while its context is shorter
than ``dense_below``. One set serves all the query heads of a KV group.

The functions take one row's compressed keys in window order, ``[J, KV, d]``:
the serving programs gather them through the row's block table from a pool
shaped like the K/V pools (``serving/paged_cache.py``), where the windows
that START in block ``b`` are slots ``0 .. block/stride - 1`` of block ``b``.
Scores are float32 whatever the compute dtype: rounding moves blocks in and
out of the top-k.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from gpt_2_distributed_tpu.config import SparseAttentionConfig


def window_means(keys: jnp.ndarray, sp: SparseAttentionConfig) -> jnp.ndarray:
    """``keys`` [T, KV, d], T a multiple of ``stride`` -> [T / stride, KV, d]:
    window ``j`` is the mean of ``keys[stride j : stride j + window]``, a
    window that runs past the end taking zeros there (it is rewritten when
    its tokens arrive, and never looked at before)."""
    t = keys.shape[0]
    per = sp.window // sp.stride
    seg = keys.astype(jnp.float32).reshape(t // sp.stride, sp.stride, *keys.shape[1:])
    seg = jnp.pad(seg.mean(axis=1), ((0, per - 1), (0, 0), (0, 0)))
    n = t // sp.stride
    return sum(seg[i:n + i] for i in range(per)) / per


def block_scores(
    q: jnp.ndarray,            # [Tq, KV, G, d]
    kc: jnp.ndarray,           # [J, KV, d] compressed keys, window order
    pos: jnp.ndarray,          # [Tq] int32 query positions
    sp: SparseAttentionConfig,
) -> jnp.ndarray:
    """[KV, Tq, J * stride / block] float32: per block the largest
    probability, summed over the group's heads, of a window that overlaps it
    and has ended at or before the query (``-inf`` where none has)."""
    j, _, d = kc.shape
    scores = jnp.einsum(
        "tkgd,jkd->kgtj", q.astype(jnp.float32), kc.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST) / math.sqrt(d)
    ends = jnp.arange(j) * sp.stride + sp.window - 1
    seen = ends[None] <= pos[:, None]                                  # [Tq, J]
    p = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
    p = jnp.where(seen[None], p.sum(axis=1), -jnp.inf)                 # [KV, Tq, J]
    # Block b's windows are lo .. lo + n_over - 1 with lo = b * wpb - (per - 1):
    # pad in front, cut into blocks of wpb, and take the per - 1 windows
    # before each block from the block before.
    wpb, per = sp.block // sp.stride, sp.window // sp.stride
    own = p.reshape(*p.shape[:2], j // wpb, wpb)
    best = own.max(axis=-1)
    if per > 1:
        tail = own[..., wpb - (per - 1):].max(axis=-1)                 # [KV, Tq, B]
        before = jnp.pad(tail, ((0, 0), (0, 0), (1, 0)),
                         constant_values=-jnp.inf)[..., :-1]
        best = jnp.maximum(best, before)
    return best


def top_k_mask(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """The ``k`` largest of ``x`` [..., B] along its last axis as a mask, the
    earlier one first among equals - what ``lax.top_k``'s indices mark. Equal
    scores are common here (a window that overlaps two blocks gives both its
    probability), so the tie rule is part of the result.

    No sort: ``lax.top_k`` lowers to a full sort of every row on the chip (2 ms
    for a [2, 512, 512] selection, a sixth of a prefill chunk's device time).
    Instead the k-th largest value is built bit by bit - floats in an integer
    order that keeps theirs, 32 counts of "how many are at least this" - and
    ties at it are taken in index order by a running count."""
    if k >= x.shape[-1]:
        return jnp.ones(x.shape, bool)
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)

    def grow(i, kth):
        trial = kth | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = (key >= trial[..., None]).sum(axis=-1) >= k
        return jnp.where(enough, trial, kth)

    kth = jax.lax.fori_loop(0, 32, grow, jnp.zeros(x.shape[:-1], jnp.uint32))[..., None]
    above = key > kth
    ties = key == kth
    room = k - above.sum(axis=-1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=-1) <= room))


def select_blocks(
    score: jnp.ndarray,        # [KV, Tq, B] from `block_scores`
    pos: jnp.ndarray,          # [Tq] int32
    sp: SparseAttentionConfig,
) -> jnp.ndarray:
    """The selected set as a mask [KV, Tq, B]."""
    n_blocks = score.shape[-1]
    b = jnp.arange(n_blocks)[None]
    own = (pos // sp.block)[:, None]
    visible = b <= own                                                 # [Tq, B]
    forced = visible & ((b < sp.init_blocks) | (b > own - sp.local_blocks))
    rest = jnp.where((visible & ~forced)[None], score, -jnp.inf)
    picked = top_k_mask(rest, sp.topk) & (rest > -jnp.inf)
    dense = (pos < sp.dense_below)[:, None]
    return jnp.where(dense[None], visible[None], forced[None] | picked)


def mask_to_list(mask: jnp.ndarray, width: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``mask`` [..., B] -> (the selected blocks in ascending order, padded
    with block 0, [..., width] int32; how many there are, [...])."""
    n_blocks = mask.shape[-1]
    rank = jnp.where(mask, n_blocks - jnp.arange(n_blocks), 0)
    top, _ = jax.lax.top_k(rank, min(width, n_blocks))
    count = jnp.minimum(mask.sum(axis=-1), width).astype(jnp.int32)
    blocks = jnp.where(top > 0, n_blocks - top, 0).astype(jnp.int32)
    if blocks.shape[-1] < width:
        blocks = jnp.pad(blocks, [(0, 0)] * (blocks.ndim - 1)
                         + [(0, width - blocks.shape[-1])])
    return blocks, count
