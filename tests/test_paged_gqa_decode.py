"""The grouped-query paged decode kernel (``paged_gqa_decode``, interpreted
here) against ``paged_sparse_attention``'s XLA form, which it stands in for
on a TPU, at the three state families' head shapes: whole tables (Nemotron-H,
Jamba) and selected lists (MiniCPM-SALA), in scrambled pool blocks."""

import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.ops.paged_attention import (
    paged_decode_grid,
    paged_gqa_decode,
    paged_sparse_attention,
)

BS, D, W, LAYERS = 64, 128, 40, 2
HEADS = {"nemotron-16/2": (2, 16), "jamba-20/1": (1, 20), "sala-16/2": (2, 16)}


def _case(kv, g, dtype, seed=0):
    """Four rows: a list as wide as the table with the query inside its last
    block; a list that stops inside a group, the slots past ``count`` holding
    stale blocks that would be visible if read; an idle row; a selected list
    in no order, the query's own block among it."""
    rng = np.random.default_rng(seed)
    b = 4
    n = 1 + b * W
    shape = (LAYERS, n, kv, BS, D)
    k_pool = jnp.asarray(rng.normal(size=shape), dtype)
    v_pool = jnp.asarray(rng.normal(size=shape), dtype)
    q = jnp.asarray(rng.normal(size=(b, kv, g, D)), dtype)
    table = (1 + rng.permutation(b * W)).reshape(b, W)         # scrambled blocks
    logical = np.broadcast_to(np.arange(W), (b, kv, W)).copy()
    pos = np.asarray([W * BS - 23, 20 * BS + 17, 0, 30 * BS + 1])
    count = np.asarray([W, 21, 0, 11])[:, None].repeat(kv, 1)
    for h in range(kv):
        picked = rng.permutation(30)[:10]
        logical[3, h, :11] = rng.permutation(np.append(picked, 30))
        logical[1, h, 21:] = rng.integers(0, 21, W - 21)       # stale, yet visible
    blocks = np.take_along_axis(table[:, None], logical, axis=2)
    as_i32 = lambda a: jnp.asarray(a, jnp.int32)
    return (q, k_pool, v_pool, as_i32(blocks), as_i32(logical), as_i32(count),
            as_i32(pos))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", HEADS.values(), ids=HEADS)
def test_kernel_is_the_xla_form(heads, dtype):
    kv, g = heads
    args = _case(kv, g, dtype)
    per_group = paged_decode_grid(4, 1, W, BS, D, jnp.dtype(dtype).itemsize)[1]
    assert per_group < 21 < W      # several groups a row, the last one partial
    got = paged_gqa_decode(*args, jnp.int32(1), interpret=True)
    want = paged_sparse_attention(*args, 1)     # the XLA form, off the chip
    assert got.shape == want.shape == (4, kv, g, D) and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert not got[2].any()                                    # the idle row: exact zeros
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=2e-5)
    else:                          # the output's rounding, an ulp or two at most
        np.testing.assert_allclose(got, want, atol=2e-2, rtol=1e-2)
