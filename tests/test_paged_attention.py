"""Paged decode attention (ops/paged_attention.py): both impls against a
dense reference, and the exactness property the serving engine's
bit-parity contract stands on (extra masked pool columns are invisible to
the softmax).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.ops.attention import MASK_VALUE
from gpt_2_distributed_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_pallas,
    paged_decode_grid,
    paged_attention_xla,
    paged_prefill_attention,
)
from gpt_2_distributed_tpu.serving.paged_cache import write_chunk, write_rows


def _dense_view(pool, table):
    """The contiguous per-sequence view ``[B, H, M*bs, D]`` a table encodes."""
    c = np.asarray(pool, np.float32)[np.asarray(table)]    # [B, M, H, bs, D]
    b, m, h, bs, d = c.shape
    return c.transpose(0, 2, 1, 3, 4).reshape(b, h, m * bs, d)


def _paged_case(rng, b=3, h=2, d=8, bs=4, m=4, n_blocks=32, scramble=True):
    """Random q + pools + a block table; returns the dense per-sequence
    K/V views the pools encode, for reference computation."""
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(n_blocks, h, bs, d)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(n_blocks, h, bs, d)), jnp.float32)
    # Distinct non-null blocks per sequence, scrambled across the pool.
    perm = rng.permutation(np.arange(1, n_blocks))[: b * m]
    if not scramble:
        perm = np.sort(perm)
    table = jnp.asarray(perm.reshape(b, m), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, m * bs + 1, b), jnp.int32)
    return (q, k_pool, v_pool, table, lengths,
            _dense_view(k_pool, table), _dense_view(v_pool, table))


def _dense_reference(q, kc, vc, lengths):
    """fp64 numpy softmax attention over each sequence's valid prefix."""
    b, h, d = q.shape
    out = np.zeros((b, h, d))
    qn = np.asarray(q, np.float64)
    for i in range(b):
        ln = int(lengths[i])
        if ln == 0:
            continue
        s = np.einsum("hd,hkd->hk", qn[i], kc[i, :, :ln].astype(np.float64))
        s /= np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("hk,hkd->hd", p, vc[i, :, :ln].astype(np.float64))
    return out


def test_xla_matches_dense_reference(rng_np):
    q, kp, vp, table, lengths, kc, vc = _paged_case(rng_np)
    got = paged_attention_xla(q, kp, vp, table, lengths)
    want = _dense_reference(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def _edge_case(rng, h, bs, dtype, m=7, k=3, d=64):
    """One batch of the lengths a block table can hold awkwardly - 1,
    ``k*bs - 1``, ``k*bs``, ``k*bs + 1``, the full table - beside an idle
    row, at a head count and block size the presets run."""
    lengths = [1, k * bs - 1, k * bs, k * bs + 1, m * bs, 0]
    b, n_blocks = len(lengths), 1 + len(lengths) * m
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    k_pool = jnp.asarray(rng.normal(size=(n_blocks, h, bs, d)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(n_blocks, h, bs, d)), dtype)
    table = jnp.asarray(
        rng.permutation(np.arange(1, n_blocks)).reshape(b, m), jnp.int32)
    return (q, k_pool, v_pool, table, jnp.asarray(lengths, jnp.int32),
            _dense_view(k_pool, table), _dense_view(v_pool, table))


KERNEL_CASES = {
    # heads, block_size, dtype, whether the chosen P leaves the table's width
    # (7) a remainder: the last step's spare slots hold the clamp
    "random-small": None,
    "H12-bs16": (12, 16, jnp.float32, True),
    "H16-bs16": (16, 16, jnp.float32, True),
    "H20-bs16": (20, 16, jnp.float32, False),
    "H25-bs16": (25, 16, jnp.float32, False),
    "H12-bs32": (12, 32, jnp.float32, False),
    "H25-bs32": (25, 32, jnp.float32, False),
    "H12-bs16-bf16": (12, 16, jnp.bfloat16, True),
    "H25-bs16-bf16": (25, 16, jnp.bfloat16, True),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_pallas_matches_dense_reference(rng_np, case):
    if KERNEL_CASES[case] is None:
        q, kp, vp, table, lengths, kc, vc = _paged_case(rng_np)
        tol = dict(rtol=1e-4, atol=1e-5)
    else:
        h, bs, dtype, remainder = KERNEL_CASES[case]
        q, kp, vp, table, lengths, kc, vc = _edge_case(rng_np, h, bs, dtype)
        m = table.shape[1]
        (rows, steps), per_step = paged_decode_grid(
            q.shape[0], h, m, bs, q.shape[2], kp.dtype.itemsize)
        assert rows == q.shape[0] and steps == -(-m // per_step)
        assert bool(m % per_step) == remainder, per_step
        # bf16: q, the probabilities and the output are rounded to it
        tol = (dict(rtol=1e-4, atol=1e-5) if dtype == jnp.float32
               else dict(rtol=3e-2, atol=3e-2))
    got = paged_attention_pallas(q, kp, vp, table, lengths)  # interpret=CPU
    want = _dense_reference(np.asarray(q, np.float32), kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, **tol)
    xla = paged_attention_xla(q, kp, vp, table, lengths)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(xla, np.float32), **tol)
    idle = np.asarray(lengths) == 0
    np.testing.assert_array_equal(np.asarray(got, np.float32)[idle], 0.0)


@pytest.mark.parametrize("layer", [0, 2], ids=["one-layer-pool", "last-layer"])
def test_table_tail_is_never_read(rng_np, layer):
    """The kernel stops at each row's last live block: table entries past
    it - every entry of an idle row - may point at blocks full of NaN, and
    at the pool's very last index, and the output is bitwise what a table
    parked on the null block gives. (The XLA gather does read them: 0 x NaN.)"""
    h, bs, d, m, n = 12, 16, 64, 7, 40
    lengths = [0, 1, 2 * bs, 3 * bs + 1, m * bs - 1]
    b = len(lengths)
    shape = (n, h, bs, d) if layer == 0 else (layer + 1, n, h, bs, d)
    q = jnp.asarray(rng_np.normal(size=(b, h, d)), jnp.float32)
    kp = rng_np.normal(size=shape).astype(np.float32)
    vp = rng_np.normal(size=shape).astype(np.float32)
    poisoned = [n - 3, n - 2, n - 1]          # n - 1: the pool's last index
    kp[..., poisoned, :, :, :] = np.nan
    vp[..., poisoned, :, :, :] = np.nan
    parked = np.zeros((b, m), np.int32)
    pointed = np.zeros((b, m), np.int32)
    free = iter(rng_np.permutation(np.arange(1, n - 3)))
    for i, ln in enumerate(lengths):
        live = -(-ln // bs)
        parked[i, :live] = pointed[i, :live] = [next(free) for _ in range(live)]
        pointed[i, live:] = [poisoned[(i + j) % 3] for j in range(m - live)]
    assert paged_decode_grid(b, h, m, bs, d, 4)[1] == 2      # 7 slots, 4 steps
    out = [paged_attention_pallas(q, jnp.asarray(kp), jnp.asarray(vp),
                                  jnp.asarray(t), jnp.asarray(lengths, jnp.int32),
                                  jnp.int32(layer))
           for t in (parked, pointed)]
    assert np.isfinite(np.asarray(out[0])).all()
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(out[0]))
    np.testing.assert_array_equal(np.asarray(out[1][0]), 0.0)    # the idle row


def test_idle_slot_outputs_exact_zeros(rng_np):
    q, kp, vp, table, lengths, _, _ = _paged_case(rng_np)
    lengths = lengths.at[1].set(0)   # idle slot mid-batch
    for impl in ("xla", "pallas"):
        out = paged_attention(q, kp, vp, table, lengths, impl=impl)
        np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
        assert np.abs(np.asarray(out[0])).max() > 0  # neighbors unaffected


def test_block_placement_is_invisible(rng_np):
    """The same logical K/V through a scrambled vs a sorted block table must
    give IDENTICAL outputs — the table is pure indirection, and both impls
    visit blocks in table order regardless of where they live in the pool."""
    q, kp, vp, table_s, lengths, kc, vc = _paged_case(rng_np, scramble=True)
    b, h, d = q.shape
    m, bs = table_s.shape[1], kp.shape[2]
    # Rebuild pools with the SAME per-sequence K/V laid out contiguously.
    kp2 = np.zeros_like(np.asarray(kp))
    vp2 = np.zeros_like(np.asarray(vp))
    table_c = np.arange(1, 1 + b * m, dtype=np.int32).reshape(b, m)
    kb = kc.reshape(b, h, m, bs, d).transpose(0, 2, 1, 3, 4)  # [B,M,H,bs,D]
    vb = vc.reshape(b, h, m, bs, d).transpose(0, 2, 1, 3, 4)
    kp2[table_c] = kb
    vp2[table_c] = vb
    for impl in ("xla", "pallas"):
        a = paged_attention(q, kp, vp, table_s, lengths, impl=impl)
        c = paged_attention(q, jnp.asarray(kp2), jnp.asarray(vp2),
                            jnp.asarray(table_c), lengths, impl=impl)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c)), impl


def test_masked_tail_content_is_bitwise_invisible(rng_np):
    """The serving engine's exactness contract in miniature: whatever lives
    in positions past a sequence's length — stale K/V from an evicted
    request, huge values, zeros — must be BITWISE invisible to the output.
    Masked lanes score MASK_VALUE, underflow to exact zero after the
    max-subtract, and contribute exact-zero terms to both softmax sums, so
    swapping the tail content cannot flip a single bit."""
    q, kp, vp, table, lengths, kc, vc = _paged_case(rng_np)
    lengths = jnp.minimum(lengths, lengths - 2).clip(1)  # guarantee a tail
    base = {impl: paged_attention(q, kp, vp, table, lengths, impl=impl)
            for impl in ("xla", "pallas")}

    bs = kp.shape[2]
    kn, vn = np.array(kp), np.array(vp)
    for i in range(q.shape[0]):
        ln = int(lengths[i])
        for j, blk in enumerate(np.asarray(table[i])):
            lo = max(0, ln - j * bs)   # first masked offset in this block
            if lo < bs:
                kn[blk, :, lo:] = 1e6  # scribble on every masked position
                vn[blk, :, lo:] = -1e6
    for impl in ("xla", "pallas"):
        got = paged_attention(q, jnp.asarray(kn), jnp.asarray(vn),
                              table, lengths, impl=impl)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(base[impl])
        ), impl
    assert MASK_VALUE < -1e3  # the mask must dominate the scribbled scores


def _prefill_case(rng, b=2, t=5, h=2, d=8, bs=4, m=6, n_blocks=32):
    """Chunk queries at arbitrary absolute starts over fully-built tables,
    plus the dense per-sequence K/V views for reference computation."""
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(n_blocks, h, bs, d)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(n_blocks, h, bs, d)), jnp.float32)
    perm = rng.permutation(np.arange(1, n_blocks))[: b * m]
    table = jnp.asarray(perm.reshape(b, m), jnp.int32)
    start = jnp.asarray(rng.integers(0, m * bs - t + 1, b), jnp.int32)
    return (q, k_pool, v_pool, table, start,
            _dense_view(k_pool, table), _dense_view(v_pool, table))


def _prefill_dense_reference(q, kc, vc, start):
    """fp64 causal softmax: query t of sequence b attends to positions
    <= start[b] + t of the table's contiguous view."""
    b, t, h, d = q.shape
    out = np.zeros((b, t, h, d))
    for i in range(b):
        for tt in range(t):
            ln = int(start[i]) + tt + 1
            s = np.einsum(
                "hd,hkd->hk", np.asarray(q[i, tt], np.float64),
                kc[i, :, :ln].astype(np.float64),
            ) / np.sqrt(d)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[i, tt] = np.einsum(
                "hk,hkd->hd", p, vc[i, :, :ln].astype(np.float64)
            )
    return out


def test_prefill_matches_dense_reference(rng_np):
    q, kp, vp, table, start, kc, vc = _prefill_case(rng_np)
    got = paged_prefill_attention(q, kp, vp, table, start)
    want = _prefill_dense_reference(q, kc, vc, start)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def test_prefill_future_positions_are_bitwise_invisible(rng_np):
    """Chunked prefill attends over a PARTIALLY-built table: everything
    past the chunk's causal frontier is stale garbage by construction, and
    must be bitwise invisible to every query row."""
    q, kp, vp, table, start, _, _ = _prefill_case(rng_np)
    base = paged_prefill_attention(q, kp, vp, table, start)
    t, bs = q.shape[1], kp.shape[2]
    kn, vn = np.array(kp), np.array(vp)
    for i in range(q.shape[0]):
        frontier = int(start[i]) + t - 1     # last attendable position
        for j, blk in enumerate(np.asarray(table[i])):
            lo = max(0, frontier + 1 - j * bs)
            if lo < bs:
                kn[blk, :, lo:] = 1e6
                vn[blk, :, lo:] = -1e6
    got = paged_prefill_attention(
        q, jnp.asarray(kn), jnp.asarray(vn), table, start
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
@pytest.mark.parametrize("heads", [25, 12, 16, 20], ids=["H25", "H12", "H16", "H20"])
def test_layer_of_the_whole_pool_matches_its_slice(rng_np, heads, layer):
    """Every op takes the whole ``[L, N, H, bs, D]`` pool and a layer: the
    kernel (interpret mode; the layer folded into its block table) and the
    one-gather XLA paths give what the XLA path gives on ``pool[layer]``,
    for ragged lengths with an idle row and a full table."""
    l, n, bs, d, m = 5, 9, 16, 64, 2
    q = jnp.asarray(rng_np.normal(size=(4, heads, d)), jnp.float32)
    kp = jnp.asarray(rng_np.normal(size=(l, n, heads, bs, d)), jnp.float32)
    vp = jnp.asarray(rng_np.normal(size=(l, n, heads, bs, d)), jnp.float32)
    table = jnp.asarray(
        rng_np.permutation(np.arange(1, n)).reshape(4, m), jnp.int32)
    lengths = jnp.asarray([0, m * bs, 17, 1], jnp.int32)
    want = paged_attention_xla(q, kp[layer], vp[layer], table, lengths)
    got = paged_attention_xla(q, kp, vp, table, lengths, jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got = paged_attention_pallas(q, kp, vp, table, lengths, jnp.int32(layer))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[0]), 0.0)      # the idle row
    qc = jnp.asarray(rng_np.normal(size=(4, 3, heads, d)), jnp.float32)
    start = jnp.asarray([0, m * bs - 3, 14, 5], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(paged_prefill_attention(
            qc, kp, vp, table, start, jnp.int32(layer))),
        np.asarray(paged_prefill_attention(
            qc, kp[layer], vp[layer], table, start)))


def _scattered(pool, layer, blk, off, new):
    """The write as the step programs made it before PR 26: one scatter,
    rows whose block is out of range dropped."""
    return pool.at[layer, blk, :, off].set(new.astype(pool.dtype), mode="drop")


WRITES = {
    # rows: (start, clen) of a C-wide chunk; bs = 4, M = 4 (16 positions)
    "mid-block to mid-block, a padding row, a short row":
        (6, [(2, 5), (0, 0), (5, 2)]),
    "aligned full blocks, a one-token row, a row to the table's end":
        (8, [(4, 8), (7, 1), (8, 8)]),
    "a window that straddles the table's end (the verify pass masks it)":
        (5, [(13, 3), (0, 5), (11, 5)]),
}


@pytest.mark.parametrize("case", WRITES)
def test_chunk_write_leaves_the_pool_a_scatter_would(rng_np, case):
    """``write_chunk`` (whole blocks read, merged and put back) against the
    position-granular scatter it replaces: bit-equal pools, in every layer -
    padding rows dropped, the rest of each partial block untouched."""
    c, rows = WRITES[case]
    l, n, h, bs, d, m = 3, 16, 2, 4, 8, 4
    r = len(rows)
    kp = jnp.asarray(rng_np.normal(size=(l, n, h, bs, d)), jnp.bfloat16)
    vp = jnp.asarray(rng_np.normal(size=(l, n, h, bs, d)), jnp.bfloat16)
    k = jnp.asarray(rng_np.normal(size=(r, c, h, d)), jnp.float32)
    v = jnp.asarray(rng_np.normal(size=(r, c, h, d)), jnp.float32)
    bt = jnp.asarray(
        rng_np.permutation(np.arange(1, n))[: r * m].reshape(r, m), jnp.int32)
    start = jnp.asarray([s for s, _ in rows], jnp.int32)
    clen = jnp.asarray([n_ for _, n_ in rows], jnp.int32)
    pos = start[:, None] + jnp.arange(c)[None]
    valid = (jnp.arange(c)[None] < clen[:, None]) & (pos < m * bs)
    blk = jnp.take_along_axis(bt, jnp.minimum(pos // bs, m - 1), axis=1)
    blk = jnp.where(valid, blk, n)
    layer = 1
    got_k, got_v = jax.jit(write_chunk)(
        kp, vp, jnp.int32(layer), bt, start, valid, k, v)
    np.testing.assert_array_equal(
        np.asarray(got_k, np.float32),
        np.asarray(_scattered(kp, layer, blk, pos % bs, k), np.float32))
    np.testing.assert_array_equal(
        np.asarray(got_v, np.float32),
        np.asarray(_scattered(vp, layer, blk, pos % bs, v), np.float32))


def test_row_write_leaves_the_pool_a_scatter_would(rng_np):
    """``write_rows``, the decode step's write: active rows each to their
    own block, two rows into one block, idle rows onto the null block at
    their own offsets - bit-equal with the scatter it replaces."""
    l, n, h, bs, d = 3, 8, 2, 4, 8
    kp = jnp.asarray(rng_np.normal(size=(l, n, h, bs, d)), jnp.bfloat16)
    vp = jnp.asarray(rng_np.normal(size=(l, n, h, bs, d)), jnp.bfloat16)
    k = jnp.asarray(rng_np.normal(size=(6, h, d)), jnp.bfloat16)
    v = jnp.asarray(rng_np.normal(size=(6, h, d)), jnp.bfloat16)
    blk = jnp.asarray([5, 0, 2, 2, 0, 7], jnp.int32)   # rows 1 and 4 idle
    off = jnp.asarray([3, 1, 0, 2, 2, 1], jnp.int32)
    layer = 2
    got_k, got_v = jax.jit(write_rows)(kp, vp, jnp.int32(layer), blk, off, k, v)
    np.testing.assert_array_equal(
        np.asarray(got_k, np.float32),
        np.asarray(_scattered(kp, layer, blk, off, k), np.float32))
    np.testing.assert_array_equal(
        np.asarray(got_v, np.float32),
        np.asarray(_scattered(vp, layer, blk, off, v), np.float32))


def test_rejects_bad_impl_and_shapes(rng_np):
    q, kp, vp, table, lengths, _, _ = _paged_case(rng_np)
    with pytest.raises(ValueError, match="impl="):
        paged_attention(q, kp, vp, table, lengths, impl="dense")
    with pytest.raises(ValueError, match=r"q must be \[B, H, D\]"):
        paged_attention(q[:, :, None], kp, vp, table, lengths)
    with pytest.raises(ValueError, match="matching"):
        paged_attention(q, kp, vp[:-1], table, lengths)
    with pytest.raises(ValueError, match="matching"):      # a stored 6-D pool
        paged_attention(q, kp[None, None], vp[None, None], table, lengths)


@pytest.mark.parametrize("impl,line", [
    ("auto", "[kernels] paged_attention: xla (gather)"),      # the CPU's choice
    ("xla", "[kernels] paged_attention: xla (gather)"),
    ("pallas", "[kernels] paged_attention: pallas (interpret)"),
])
def test_resolved_impl_is_said_once(rng_np, monkeypatch, capsys, impl, line):
    """Which way the dispatch went is printed once per process: a chip run
    that landed on the gather or the interpreter must not look healthy."""
    from gpt_2_distributed_tpu.ops import spmd

    monkeypatch.setattr(spmd, "_RESOLVED_IMPLS", set())
    q, kp, vp, bt, ln, _, _ = _paged_case(rng_np)
    for _ in range(2):
        paged_attention(q, kp, vp, bt, ln, impl=impl)
    assert capsys.readouterr().err.splitlines() == [line]
