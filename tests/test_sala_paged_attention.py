"""Grouped-query attention over a selected list of pool blocks
(``paged_sparse_attention``, ``paged_masked_attention``) against the paged
attention the engine already had, which this PR leaves as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.ops.attention import MASK_VALUE
from gpt_2_distributed_tpu.ops.paged_attention import (
    paged_attention,
    paged_masked_attention,
    paged_prefill_attention,
    paged_sparse_attention,
)

L, N, BS, D, M = 2, 12, 4, 8, 5


def pools(heads, seed=0):
    rng = np.random.default_rng(seed)
    shape = (L, N, heads, BS, D)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


TABLE = jnp.asarray([[3, 7, 1, 9, 0], [5, 2, 0, 0, 0], [0, 0, 0, 0, 0]], jnp.int32)
LENGTHS = jnp.asarray([14, 6, 0], jnp.int32)      # the third row is an idle slot


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_attention_at_one_kv_head_a_query_head_is_what_it_was(impl):
    """Bit for bit the masked float32 softmax over the table's contiguous
    view that ``decode.decode_step`` runs (the XLA path; the kernel differs
    from it by the online softmax's ulps, as before)."""
    heads = 3
    kp, vp = pools(heads)
    q = jnp.asarray(np.random.default_rng(1).normal(size=(3, heads, D)), jnp.float32)
    got = paged_attention(q, kp, vp, TABLE, LENGTHS, 1, impl=impl)
    view = lambda pool: pool[1, TABLE].transpose(0, 2, 1, 3, 4).reshape(3, heads, M * BS, D)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, None], view(kp),
                        preferred_element_type=jnp.float32) * (1.0 / jnp.sqrt(jnp.float32(D)))
    mask = jnp.arange(M * BS)[None, None, None] < LENGTHS[:, None, None, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, MASK_VALUE), axis=-1)
    probs = jnp.where(LENGTHS[:, None, None, None] > 0, probs, 0.0)
    want = jnp.einsum("bhqk,bhkd->bhqd", probs, view(vp))[:, :, 0]
    if impl == "xla":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, atol=1e-5)
    assert not np.asarray(got[2]).any()


def _listed(table, lengths, kv):
    """Every block of each row's context as its list: selection off."""
    n = -(-lengths // BS)
    logical = jnp.broadcast_to(jnp.arange(M)[None, None], (table.shape[0], kv, M))
    return (jnp.broadcast_to(table[:, None], logical.shape), logical,
            jnp.broadcast_to(n[:, None], logical.shape[:2]))


def test_sparse_attention_over_the_whole_list_is_paged_attention():
    heads = 3
    kp, vp = pools(heads)
    q = jnp.asarray(np.random.default_rng(2).normal(size=(3, heads, D)), jnp.float32)
    blocks, logical, count = _listed(TABLE, LENGTHS, heads)
    got = paged_sparse_attention(
        q[:, :, None], kp, vp, blocks, logical, count, LENGTHS - 1, 1)[:, :, 0]
    np.testing.assert_allclose(
        got, paged_attention(q, kp, vp, TABLE, LENGTHS, 1, impl="xla"), atol=1e-6)
    assert not np.asarray(got[2]).any()


def test_grouped_queries_share_a_kv_head_and_a_list_skips_blocks():
    kv, g = 2, 3
    kp, vp = pools(kv, seed=3)
    q = jnp.asarray(np.random.default_rng(4).normal(size=(2, kv, g, D)), jnp.float32)
    pos = jnp.asarray([13, 5], jnp.int32)
    # row 0 attends its blocks 0, 2 and 3 (of 4), row 1 both of its two
    logical = jnp.asarray([[[0, 2, 3, 0, 0]] * kv, [[0, 1, 0, 0, 0]] * kv], jnp.int32)
    count = jnp.asarray([[3] * kv, [2] * kv], jnp.int32)
    blocks = jnp.take_along_axis(TABLE[:2, None], logical, axis=2)
    got = paged_sparse_attention(q, kp, vp, blocks, logical, count, pos, 0)
    for b in range(2):
        for h in range(kv):
            keys = np.concatenate([
                np.asarray(kp[0, int(TABLE[b, j]), h]) for j in logical[b, h, :count[b, h]]])
            vals = np.concatenate([
                np.asarray(vp[0, int(TABLE[b, j]), h]) for j in logical[b, h, :count[b, h]]])
            at = np.concatenate([np.arange(BS) + int(j) * BS for j in logical[b, h, :count[b, h]]])
            keep = at <= int(pos[b])
            s = np.asarray(q[b, h]) @ keys[keep].T / np.sqrt(D)
            p = np.exp(s - s.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ vals[keep]
            np.testing.assert_allclose(got[b, h], want, atol=1e-5)


@pytest.mark.parametrize("tile_blocks", [1, 2, 8])
def test_masked_chunk_attention_is_chunked_prefill_attention_when_all_is_kept(tile_blocks):
    heads, t, start = 2, 6, 7
    kp, vp = pools(heads, seed=5)
    q = jnp.asarray(np.random.default_rng(6).normal(size=(t, heads, D)), jnp.float32)
    pos = start + jnp.arange(t)
    want = paged_prefill_attention(q[None], kp, vp, TABLE[:1], jnp.asarray([start]), 1)[0]
    keep = jnp.ones((heads, t, M), bool)
    got = paged_masked_attention(
        q[:, :, None], kp, vp, TABLE[0], pos, keep, 1, tile_blocks=tile_blocks)[:, :, 0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    # with block 1 of the sequence dropped for the later queries only
    keep = keep.at[:, 3:, 1].set(False)
    got = paged_masked_attention(
        q[:, :, None], kp, vp, TABLE[0], pos, keep, 1, tile_blocks=tile_blocks)[:, :, 0]
    np.testing.assert_allclose(got[:3], want[:3], atol=1e-5)
    assert np.abs(np.asarray(got[3:] - want[3:])).max() > 1e-3
