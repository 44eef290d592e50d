"""The two latent-attention kernels, interpreted here, against the XLA forms
they stand in for on a TPU, at the served cell's widths (one latent row of
512 + 64 values padded to 640 lanes): the absorbed decode kernel
(``paged_mla_decode``) against ``paged_mla_attention``'s gather - 32 query
heads, whole tables and a list that stops inside a group, in scrambled pool
blocks - and the expanded prefill kernel (``mla_prefill_attention``) against
``paged_mla_prefill``'s walk in tiles - a chunk at the sequence's start, one
whose first tile is whole, and one that reaches the table's end."""

import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.ops.paged_attention import (
    mla_prefill_attention,
    paged_decode_grid,
    paged_mla_attention,
    paged_mla_decode,
    paged_mla_prefill,
)

BS, RANK, LANES, HEADS, W, LAYERS = 64, 512, 640, 32, 20, 2


def _case(dtype, seed=0):
    """Four rows: a whole table with the query inside its last block; a list
    that stops inside a group, the slots past ``count`` holding blocks that
    would be visible if read; an idle row; a row a few blocks in."""
    rng = np.random.default_rng(seed)
    b = 4
    n = 1 + b * W
    pool = rng.normal(size=(LAYERS, n, 1, BS, LANES))
    pool[..., 576:] = 0.0                                       # the rows' padding
    q = rng.normal(size=(b, 1, HEADS, LANES)) * 0.05
    q[..., 576:] = 0.0
    table = (1 + rng.permutation(b * W)).reshape(b, W)         # scrambled blocks
    logical = np.broadcast_to(np.arange(W), (b, 1, W)).copy()
    logical[1, 0, 11:] = rng.integers(0, 11, W - 11)           # stale, yet visible
    blocks = np.take_along_axis(table[:, None], logical, axis=2)
    pos = np.asarray([W * BS - 23, 10 * BS + 17, 0, 5 * BS + 1])
    count = np.asarray([W, 11, 0, 6])[:, None]
    as_i32 = lambda a: jnp.asarray(a, jnp.int32)
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype), as_i32(blocks),
            as_i32(logical), as_i32(count), as_i32(pos))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_kernel_is_the_xla_form(dtype):
    args = _case(dtype)
    per_group = paged_decode_grid(4, 1, W, BS, LANES, jnp.dtype(dtype).itemsize, 1)[1]
    assert per_group < 11 < W      # several groups a row, the last one partial
    got = paged_mla_decode(*args, jnp.int32(1), value_lanes=RANK, scale_dim=192,
                           interpret=True)
    want = paged_mla_attention(*args, 1, value_lanes=RANK, scale_dim=192)
    assert got.shape == want.shape == (4, 1, HEADS, RANK) and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert not got[2].any()                                    # the idle row: exact zeros
    if dtype == jnp.float32:       # sums in another order
        np.testing.assert_allclose(got, want, atol=2e-6)
    else:                          # the output's rounding, an ulp or two at most
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("start", [0, 700, 24 * BS - 256])
def test_prefill_kernel_is_the_xla_walk(dtype, start):
    rng = np.random.default_rng(start)
    heads, t, m = 2, 256, 24
    as_dtype = lambda a: jnp.asarray(a, dtype)
    q_nope = as_dtype(rng.normal(size=(t, heads, 128)) * 0.5)
    q_pe = as_dtype(rng.normal(size=(t, heads, 64)) * 0.5)
    w_uk = as_dtype(rng.normal(size=(RANK, heads, 128)) * 0.05)
    w_uv = as_dtype(rng.normal(size=(RANK, heads, 128)) * 0.05)
    pool = rng.normal(size=(LAYERS, 1 + m, 1, BS, LANES))
    pool[..., 576:] = 0.0                                       # the rows' padding
    pool = as_dtype(pool)
    table = jnp.asarray(1 + rng.permutation(m), jnp.int32)     # scrambled blocks
    pos = start + jnp.arange(t, dtype=jnp.int32)
    want = paged_mla_prefill(q_nope, q_pe, pool, table, pos, w_uk, w_uv, 1)
    latent = pool[1, table, 0].reshape(m * BS, LANES)
    got = mla_prefill_attention(q_nope, q_pe, latent, start, w_uk, w_uv, interpret=True)
    assert got.shape == want.shape == (t, heads, 128) and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:       # sums in another order, base 2 for base e
        np.testing.assert_allclose(got, want, atol=2e-6)
    else:                          # keys, values and probabilities rounded as the
        np.testing.assert_allclose(got, want, atol=1e-2, rtol=2e-2)   # walk rounds them


def test_prefill_kernel_rounds_no_more_than_the_xla_walk():
    """In bfloat16 against the same attention in float64, the kernel's error
    is the walk's: the score's scale goes into the decompressed keys before
    their one rounding, not into the queries as a second one (which read 30 %
    above the walk). Scores spread as wide as a trained model's (std ~6)."""
    rng = np.random.default_rng(3)
    heads, t, m = 2, 256, 16
    start = m * BS - t
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    q_nope, q_pe = bf(rng.normal(size=(t, heads, 128)) * 3), bf(rng.normal(size=(t, heads, 64)) * 3)
    w_uk, w_uv = bf(rng.normal(size=(RANK, heads, 128)) * 0.1), bf(rng.normal(size=(RANK, heads, 128)) * 0.05)
    pool = rng.normal(size=(1, 1 + m, 1, BS, LANES))
    pool[..., 576:] = 0.0
    pool = bf(pool)
    table = jnp.asarray(1 + rng.permutation(m), jnp.int32)
    pos = start + jnp.arange(t, dtype=jnp.int32)
    f64 = lambda a: np.asarray(a, np.float64)
    latent = f64(pool[0, table, 0].reshape(m * BS, LANES))
    c, k_pe = latent[:, :RANK], latent[:, RANK:576]
    k_nope, v = np.einsum("sr,rhd->hsd", c, f64(w_uk)), np.einsum("sr,rhd->hsd", c, f64(w_uv))
    s = (np.einsum("thd,hsd->hts", f64(q_nope), k_nope)
         + np.einsum("thd,sd->hts", f64(q_pe), k_pe)) / np.sqrt(192)
    s = np.where(np.arange(m * BS) <= f64(pos)[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    exact = np.einsum("hts,hsd->thd", p / p.sum(-1, keepdims=True), v)
    rms = lambda got: np.sqrt(((f64(got) - exact) ** 2).mean())
    walk = rms(paged_mla_prefill(q_nope, q_pe, pool, table, pos, w_uk, w_uv, 0))
    kernel = rms(mla_prefill_attention(q_nope, q_pe, pool[0, table, 0].reshape(m * BS, LANES),
                                       start, w_uk, w_uv, interpret=True))
    assert kernel < 1.05 * walk        # 1.011x here; the queries scaled read 1.30x
