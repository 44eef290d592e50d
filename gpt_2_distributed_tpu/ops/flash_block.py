"""Rectangular block flash attention with global coordinates — the Pallas
core that lets ring attention run flash-class math (round-3 VERDICT item 4:
``ops/ring_attention.py`` previously combined blocks via XLA einsums at
dense-rate exactly where long context makes attention dominant).

``flash_block(q, k, v, row_off, col_off, ...) -> (o, lse)`` computes causal
attention of a local query block ``[B, H, Tq, D]`` against one key/value
block ``[B, H, Tc, D]`` whose GLOBAL column origin is ``col_off`` (query rows
start at ``row_off``): position (r, c) attends iff
``col_off + c <= row_off + r``. Outputs are the block-local softmax output
(normalized over this block's columns only) plus the base-2 log-sum-exp per
row — exactly what a blockwise/ring combine needs:

    o_total = sum_r exp2(lse_r - m) * o_r / sum_r exp2(lse_r - m)

The pair (o, lse) is differentiable as a custom VJP that accepts BOTH
cotangents (do, dlse). The dlse flow folds into the existing flash-backward
delta term: with P = exp(s - LSE), dL/ds = P (dp - <dp, P>_row + dLSE_nat)
and <dp, P>_row = rowsum(do * o), so the backward kernel runs unchanged with
``delta_eff = rowsum(do * o) - dlse * log2(e)`` (the log2e converts the
base-2 lse cotangent to natural units). See ``attn_bwd`` below.

Why a separate module from ``flash_attention.py``: that kernel is the
self-attention fast path (square T, block self-indexing, shard_map wrapper,
benchmarked on the headline configs) — this one is device-LOCAL (callers sit
inside ring attention's shard_map already), rectangular, offset-addressed,
and exposes lse as a public differentiable output. They share the grid
layout, the exp2 folding and the dropout stream helpers.

Dropout matches the XLA ring path bit-for-bit: bits are the shared
``spmd.dropout_hash_bits`` of GLOBAL (batch, head, row, col) coordinates
(``b_off``/``h_off`` give the shard's batch/head origin), so the mask is
invariant to the ring schedule, the sp degree, and the block sizes — the
same contract ``ring_attention._dropout_bits_4d`` pins.

Fully-masked blocks (a ring step where the whole K/V block is in this
query's future, src > idx) are handled degenerately but exactly: every
score row is masked, l stays 0, and the kernel returns o = 0 with
lse = NEG_INF — the combine weight exp2(NEG_INF - m) underflows to 0. The
masked branches force ``p = where(mask, ., 0)`` explicitly because with
m == NEG_INF the difference (s - m) is 0, and exp2(0) would leak 1s (the
same guard the XLA ring documents).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gpt_2_distributed_tpu.ops.flash_attention import (
    LOG2E,
    NEG_INF,
    _causal_gates,
    _dropout_bits,
    pick_block_q,
)
from gpt_2_distributed_tpu.ops.spmd import pallas_mode, record_resolved_impl

# Same rationale as flash_attention: (b, h, qi) parallel in fwd; the bwd's
# revisited dk/dv accumulators need qi "arbitrary".
_FWD_DIMS = ("parallel", "parallel", "parallel", "arbitrary")
_BWD_DIMS = ("parallel", "parallel", "arbitrary", "arbitrary")


def _fwd_kernel(
    scalars_ref,  # [5] int32: seed, row_off, col_off, b_off, h_off
    q_ref,        # [1, 1, bq, D]
    k_ref,        # [1, 1, bk, D]
    v_ref,        # [1, 1, bk, D]
    o_ref,        # [1, 1, bq, D]
    lse_ref,      # [1, 1, bq, 1] f32, base-2; NEG_INF on fully-masked rows
    m_scr,        # VMEM [bq, 1] f32
    l_scr,        # VMEM [bq, 1] f32
    acc_scr,      # VMEM [bq, D] f32
    *,
    block_q: int,
    block_k: int,
    dropout_rate: float,
):
    b, h, qi, j = (pl.program_id(0), pl.program_id(1),
                   pl.program_id(2), pl.program_id(3))
    bq, bk = block_q, block_k
    d = q_ref.shape[3]
    scale = LOG2E / (d ** 0.5)
    seed = scalars_ref[0]
    row_off = scalars_ref[1]
    col_off = scalars_ref[2]

    # Global origins of this (qi, j) tile; gates shared with the
    # self-attention kernels (traced offsets vary per ring step under scan).
    r0 = row_off + qi * bq
    c0 = col_off + j * bk
    needed, fully_unmasked, is_last = _causal_gates(
        qi, j, bq, bk, row_off, col_off)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute(masked: bool):
        q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(q_ref.dtype)
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk] f32, base-2 logits
        if masked:
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = c0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = col <= row
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        if masked:
            # Rows with no unmasked lane keep m_new == NEG_INF; exp2(s-m)
            # would be exp2(0) = 1 there — force masked lanes to 0.
            p = jnp.where(mask, jnp.exp2(s - m_new), 0.0)
        else:
            p = jnp.exp2(s - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_rate > 0.0:
            bits = _dropout_bits(
                seed, scalars_ref[3] + b, scalars_ref[4] + h, r0, c0, s.shape
            )
            threshold = jnp.uint32(int(dropout_rate * (2**32)))
            p = jnp.where(bits >= threshold, p / (1.0 - dropout_rate), 0.0)
        v = v_ref[0, 0]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    pl.when(needed & fully_unmasked)(lambda: _compute(masked=False))
    pl.when(needed & jnp.logical_not(fully_unmasked))(
        lambda: _compute(masked=True))

    @pl.when(is_last)
    def _finalize():
        l = l_scr[...]
        has = l > 0.0
        lse_ref[0, 0] = jnp.where(
            has, m_scr[...] + jnp.log2(jnp.maximum(l, 1e-37)), NEG_INF
        )
        o_ref[0, 0] = jnp.where(
            has, acc_scr[...] / jnp.maximum(l, 1e-37), 0.0
        ).astype(o_ref.dtype)


def _bwd_kernel(
    scalars_ref,   # [5] int32: seed, row_off, col_off, b_off, h_off
    q_ref,         # [1, 1, bq, D]
    k_ref,         # [1, 1, bk, D]
    v_ref,         # [1, 1, bk, D]
    do_ref,        # [1, 1, bq, D]
    lse_ref,       # [1, 1, bq, 1] f32 base-2 (NEG_INF rows contribute 0)
    delta_ref,     # [1, 1, bq, 1] f32: rowsum(do*o) - dlse*LOG2E
    dq_ref,        # [1, 1, bq, D]
    dk_ref,        # [1, 1, Tc, D] f32 accumulated per (b, h)
    dv_ref,        # [1, 1, Tc, D] f32
    dq_scr,        # VMEM [bq, D] f32
    *,
    block_q: int,
    block_k: int,
    dropout_rate: float,
):
    b, h, qi, j = (pl.program_id(0), pl.program_id(1),
                   pl.program_id(2), pl.program_id(3))
    bq, bk = block_q, block_k
    d = q_ref.shape[3]
    scale = LOG2E / (d ** 0.5)
    kp = 1.0 - dropout_rate
    seed = scalars_ref[0]
    row_off = scalars_ref[1]
    col_off = scalars_ref[2]
    r0 = row_off + qi * bq
    c0 = col_off + j * bk
    needed, fully_unmasked, is_last = _causal_gates(
        qi, j, bq, bk, row_off, col_off)

    @pl.when((qi == 0) & (j == 0))
    def _init_kv():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(j == 0)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute(masked: bool):
        q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(q_ref.dtype)
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if masked:
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = c0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = col <= row
            # Explicit select (not just s = NEG_INF): rows whose lse is
            # NEG_INF would otherwise compute exp2(NEG_INF - NEG_INF) = 1.
            p = jnp.where(mask, jnp.exp2(s - lse), 0.0)
        else:
            p = jnp.exp2(s - lse)
        dpd = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if dropout_rate > 0.0:
            bits = _dropout_bits(
                seed, scalars_ref[3] + b, scalars_ref[4] + h, r0, c0, s.shape
            )
            keep = bits >= jnp.uint32(int(dropout_rate * (2**32)))
            pd = jnp.where(keep, p / kp, 0.0)
            dp = jnp.where(keep, dpd / kp, 0.0)
        else:
            pd = p
            dp = dpd

        ds = (p * (dp - delta)).astype(q.dtype)  # natural-domain ds
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale / LOG2E)
        dk_ref[0, 0, pl.ds(j * bk, bk), :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (1.0 / LOG2E)
        dv_ref[0, 0, pl.ds(j * bk, bk), :] += jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    pl.when(needed & fully_unmasked)(lambda: _compute(masked=False))
    pl.when(needed & jnp.logical_not(fully_unmasked))(
        lambda: _compute(masked=True))

    @pl.when(is_last)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


@functools.lru_cache(maxsize=None)
def _build(dropout_rate: float, block_q: int, block_k: int, interpret: bool):
    """Custom-VJP (o, lse) block attention for one config. Device-local —
    callers are already inside ring attention's shard_map."""

    def _raw_fwd(scalars, q, k, v):
        batch, heads, tq, d = q.shape
        tc = k.shape[2]
        nq, nk = tq // block_q, tc // block_k
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, heads, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, i, j, *_: (b, h, j, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, i, j, *_: (b, h, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, i, j, *_: (b, h, i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        )
        return pl.pallas_call(
            functools.partial(
                _fwd_kernel, block_q=block_q, block_k=block_k,
                dropout_rate=dropout_rate,
            ),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct((batch, heads, tq, 1), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(dimension_semantics=_FWD_DIMS),
            interpret=interpret,
        )(scalars, q, k, v)

    def _raw_bwd(scalars, q, k, v, do, lse, delta_eff):
        batch, heads, tq, d = q.shape
        tc = k.shape[2]
        nq, nk = tq // block_q, tc // block_k
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, heads, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, i, j, *_: (b, h, j, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, i, j, *_: (b, h, j, 0)),
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, i, j, *_: (b, h, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, tc, d),
                             lambda b, h, i, j, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, tc, d),
                             lambda b, h, i, j, *_: (b, h, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        )
        return pl.pallas_call(
            functools.partial(
                _bwd_kernel, block_q=block_q, block_k=block_k,
                dropout_rate=dropout_rate,
            ),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, jnp.float32),
                jax.ShapeDtypeStruct(v.shape, jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=_BWD_DIMS,
                vmem_limit_bytes=64 * 1024 * 1024,
            ),
            interpret=interpret,
        )(scalars, q, k, v, do, lse, delta_eff)

    @jax.custom_vjp
    def attn(q, k, v, scalars):
        return _raw_fwd(scalars, q, k, v)

    def attn_fwd(q, k, v, scalars):
        o, lse = _raw_fwd(scalars, q, k, v)
        return (o, lse), (q, k, v, scalars, o, lse)

    def attn_bwd(res, cts):
        q, k, v, scalars, o, lse = res
        do, dlse = cts
        do = do.astype(q.dtype)
        # dL/ds = P (dp - rowsum(dp P) + dLSE_nat); rowsum(dp P) = rowsum
        # (do o) and dLSE_nat = dlse * log2e folds in with opposite sign, so
        # one effective delta feeds the unchanged kernel contraction.
        delta_eff = (
            jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
            - dlse * LOG2E
        )
        dq, dk, dv = _raw_bwd(scalars, q, k, v, do, lse, delta_eff)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype), None

    attn.defvjp(attn_fwd, attn_bwd)
    return attn


def flash_block(
    q: jnp.ndarray,  # [B, H, Tq, D] (head-major; device-local)
    k: jnp.ndarray,  # [B, H, Tc, D]
    v: jnp.ndarray,
    row_off,         # int32 scalar: global row origin of q
    col_off,         # int32 scalar: global col origin of k/v
    *,
    seed=None,           # [1] int32 dropout seed (global, unmixed)
    b_off=0,             # int32 scalar: global batch origin of this shard
    h_off=0,             # int32 scalar: global head origin
    dropout_rate: float = 0.0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(o, lse) of one causal attention block at global coordinates.

    Returns None-compatible failure by raising ValueError when no viable
    block size divides Tq/Tc (callers fall back to the XLA path).
    """
    tq, tc = q.shape[2], k.shape[2]
    bq = pick_block_q(tq, block_q if block_q is not None else min(tq, 1024))
    bk = pick_block_q(tc, block_k if block_k is not None else min(tc, 1024))
    if bq is None or bk is None:
        raise ValueError(
            f"flash_block needs Tq/Tc divisible by a viable block size "
            f"(1024/512/256/128), got Tq={tq} Tc={tc}"
        )
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    record_resolved_impl("flash_block", f"pallas ({pallas_mode(interpret)})")
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    scalars = jnp.concatenate([
        seed.astype(jnp.int32).reshape(1),
        jnp.asarray(row_off, jnp.int32).reshape(1),
        jnp.asarray(col_off, jnp.int32).reshape(1),
        jnp.asarray(b_off, jnp.int32).reshape(1),
        jnp.asarray(h_off, jnp.int32).reshape(1),
    ])
    attn = _build(float(dropout_rate), bq, bk, interpret)
    return attn(q, k, v, scalars)
