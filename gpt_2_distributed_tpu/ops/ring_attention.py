"""Ring attention: causal attention with the sequence sharded over 'sp'.

Sequence/context parallelism is absent from the reference in every form
(SURVEY.md §5.7: max context 1024, dense O(T^2) scores, no ring/blockwise/
Ulysses) — this module is the beyond-parity capability that makes long
contexts a mesh shape instead of a memory wall. Design (the standard ring
schedule, cf. PAPERS.md ring-attention entry):

* Each of the ``sp`` devices along the ring holds one contiguous sequence
  block of Q, K and V: ``[B, T/sp, H, D]`` each. Q never moves.
* ``sp`` ring steps: at step r the device combines its Q block with the K/V
  block it currently holds (originally from rank ``(idx - r) % sp``) via the
  online-softmax flash recurrence (running max ``m``, normalizer ``l``,
  unnormalized accumulator ``acc``), then passes K/V to the next rank with
  ``lax.ppermute`` — a neighbor exchange that rides ICI, never DCN-wide
  collectives. XLA overlaps the permute with the block's matmuls.
* Causality works on GLOBAL coordinates: query row ``idx*Tl + i`` attends to
  key col ``src*Tl + j`` iff col <= row. One formula covers all three block
  cases (src < idx: full, src == idx: triangular, src > idx: skip — fully
  masked blocks contribute nothing and cost one gated matmul).

Per-device memory is O(T/sp · T/sp) for one score block — long sequences
scale by adding ring ranks. Per-block math has two paths (round 4): the
default runs the Pallas ``flash_block`` kernel per ring step (VMEM-resident
score stripes, exp2 softmax — flash-class throughput) and recombines steps
at BLOCK granularity from the kernel's (o, lse) outputs
(``_ring_local_flash``); shapes too small for the kernel's 128-lane tiling
fall back to XLA einsums with the blockwise KV sub-schedule below — both
paths share one dropout stream and match the dense numerics.

Differentiation is plain autodiff: the whole ring (scan + ppermute) is
reverse-differentiable, with dropout applied through the same
counter-based-hash bits the flash kernel uses (global coordinates, so the
mask is independent of the ring schedule and the sp degree).

Numerics vs. the dense parity path: identical to the flash kernel's contract
(``ops/flash_attention.py`` module docstring) — masked lanes excluded via
-1e30 before the row max instead of the reference's -1e4 additive mask; the
difference is below bf16 resolution after softmax.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from gpt_2_distributed_tpu.ops.spmd import (
    BATCH_AXIS_NAMES,
    HEAD_AXIS_NAMES,
    dividing_axes,
    dropout_hash_bits,
    record_resolved_impl,
)

NEG_INF = -1e30  # same fill as the flash kernel (fp32 row-max stability)
# KV sub-block size within one ring step (see _ring_local): bounds the live
# score block to [b, h, tl, KV_BLOCK]. Module-level so tests can shrink it
# to exercise multi-sub-block schedules at small shapes.
KV_BLOCK = 1024


def _dropout_bits_4d(seed, b_off, h_off, row_off, col_off, shape):
    """Counter-based uint32 bits for a [b, h, rows, cols] block: 4-D iotas
    over the shared ``spmd.dropout_hash_bits`` stream, offset by the shard's
    GLOBAL (batch, head, row, col) origin — every position hashes its
    absolute coordinates, so the mask is invariant to sp/batch/head sharding.
    """
    u = functools.partial(jnp.asarray, dtype=jnp.uint32)

    def iota(axis):
        return jax.lax.broadcasted_iota(jnp.uint32, shape, axis)

    b = u(b_off) + iota(0)
    h = u(h_off) + iota(1)
    row = u(row_off) + iota(2)
    col = u(col_off) + iota(3)
    return dropout_hash_bits(seed, b, h, row, col)


def _shard_offset(axes, local_dim):
    """Global element origin of this shard along sharded mesh axes — feeds
    the dropout hash's absolute coordinates; shared by both ring paths so
    they cannot drift off the one-stream contract."""
    off = jnp.uint32(0)
    for a in axes:
        off = off * jnp.uint32(jax.lax.axis_size(a)) + jax.lax.axis_index(
            a).astype(jnp.uint32)
    return off * jnp.uint32(local_dim)


def _ring_local(
    q,  # [b, tl, h, d] local Q block (model-native layout)
    k,  # [b, tl, h, d]
    v,  # [b, tl, h, d]
    seed,  # [1] int32
    *,
    axis: str,
    sp: int,
    b_shard_axes: tuple[str, ...],
    h_shard_axes: tuple[str, ...],
    dropout_rate: float,
    use_flash: bool = False,
):
    """Device-local ring schedule; runs inside shard_map with axis ``axis``."""
    if use_flash:
        return _ring_local_flash(
            q, k, v, seed, axis=axis, sp=sp,
            b_shard_axes=b_shard_axes, h_shard_axes=h_shard_axes,
            dropout_rate=dropout_rate,
        )

    b, tl, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    # Global origins of this shard's batch/head dims, for the dropout hash.
    b_off = _shard_offset(b_shard_axes, b)
    h_off = _shard_offset(h_shard_axes, h)
    kp = 1.0 - dropout_rate

    # Blockwise attention inside the ring: per-device sequence blocks can
    # grow without the forward transient growing quadratically. tl <=
    # KV_BLOCK (or an indivisible tl) collapses to a single sub-step.
    kv_block = min(tl, KV_BLOCK)
    n_sub = tl // kv_block if tl % kv_block == 0 else 1
    if n_sub == 1:
        kv_block = tl

    @jax.checkpoint
    def combine(k_c, v_c, m, l, acc, src):
        """One online-softmax update of (m, l, acc) against the K/V block
        originally owned by rank ``src``, scanned over KV sub-blocks.

        Rematerialized at BOTH levels: the outer jax.checkpoint keeps the
        scan-over-ring-steps from saving per-step residuals (O(T^2/sp)
        without it), and the inner jax.checkpoint on ``sub`` keeps the
        sub-block scan's VJP from stacking per-sub-block score residuals
        back to O(tl^2) during the replay (scan VJPs save their bodies'
        residuals across iterations — verified on the grad jaxpr). Net:
        backward replays one sub-block at a time, O(tl x kv_block) live, at
        ~1/3 extra attention flops — the blockwise-attention tradeoff."""

        @jax.checkpoint
        def sub(carry, args):
            m, l, acc = carry
            k_b, v_b, sub_i = args                 # [b, kv_block, h, d]
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k_b, preferred_element_type=jnp.float32
            ) * scale                              # [b, h, tl, kv_block]
            col0 = src * tl + sub_i * kv_block
            col_g = col0 + jax.lax.broadcasted_iota(
                jnp.int32, (tl, kv_block), 1)
            row_b = idx * tl + jax.lax.broadcasted_iota(
                jnp.int32, (tl, kv_block), 0)
            mask = col_g <= row_b                  # global causal
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            # Masked lanes forced to 0 (not exp(NEG_INF - m)): rows with no
            # unmasked lane yet have m_new == NEG_INF, exp(0) would leak 1s.
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if dropout_rate > 0.0:
                bits = _dropout_bits_4d(
                    seed[0], b_off, h_off, idx * tl, col0, p.shape
                )
                threshold = jnp.uint32(int(dropout_rate * (2**32)))
                # Torch semantics via the flash kernel's identity: drop +
                # rescale the unnormalized exponentials, divide by the
                # UNdropped row sum.
                p = jnp.where(bits >= threshold, p / kp, 0.0)
            alpha_bthd = alpha.transpose(0, 2, 1, 3)  # [b, tl, h, 1]
            acc = acc * alpha_bthd + jnp.einsum(
                "bhqk,bkhd->bqhd", p.astype(v_b.dtype), v_b,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l, acc), None

        k_sub = k_c.reshape(b, n_sub, kv_block, h, d).transpose(1, 0, 2, 3, 4)
        v_sub = v_c.reshape(b, n_sub, kv_block, h, d).transpose(1, 0, 2, 3, 4)
        (m, l, acc), _ = jax.lax.scan(
            sub, (m, l, acc), (k_sub, v_sub, jnp.arange(n_sub))
        )
        return m, l, acc

    def body(carry, r):
        # Rotate at the TOP: step r receives the block from r hops back, and
        # the final iteration's blocks are actually consumed — sp-1 permutes
        # total, not sp (the sp-th would just return K/V to their origins).
        k_c, v_c, m, l, acc = carry
        k_c = jax.lax.ppermute(k_c, axis, perm)
        v_c = jax.lax.ppermute(v_c, axis, perm)
        m, l, acc = combine(k_c, v_c, m, l, acc, (idx - r) % sp)
        return (k_c, v_c, m, l, acc), None

    m0 = jnp.full((b, h, tl, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tl, 1), jnp.float32)
    acc0 = jnp.zeros((b, tl, h, d), jnp.float32)
    m1, l1, acc1 = combine(k, v, m0, l0, acc0, idx)   # own (diagonal) block
    (_, _, _, l, acc), _ = jax.lax.scan(
        body, (k, v, m1, l1, acc1), jnp.arange(1, sp)
    )
    # Every row's diagonal element is always unmasked, so l > 0 everywhere.
    return (acc / l.transpose(0, 2, 1, 3)).astype(q.dtype)


def _ring_local_flash(
    q,  # [b, tl, h, d] local Q block (model-native layout)
    k,
    v,
    seed,  # [1] int32
    *,
    axis: str,
    sp: int,
    b_shard_axes: tuple[str, ...],
    h_shard_axes: tuple[str, ...],
    dropout_rate: float,
):
    """Flash-class ring schedule (round-3 VERDICT item 4): each ring step
    runs the Pallas ``flash_block`` kernel on (q_local, K/V block) at global
    coordinates and the steps recombine at BLOCK granularity via their lse
    outputs — O(tl) XLA work per step instead of the O(tl x kv_block) einsum
    softmax of the fallback path, with all O(tl^2) score math fused in VMEM.

    Differentiation stays plain autodiff: flash_block's custom VJP accepts
    (do, dlse) cotangents, and the lse-weighted combine is ordinary XLA, so
    the scan + ppermute reverse-differentiates as before. The dropout stream
    is bit-identical to the XLA path (global-coordinate hash, same seed, no
    shard mixing), so masks remain invariant to the sp degree AND to which
    path computed them.
    """
    from gpt_2_distributed_tpu.ops.flash_block import flash_block

    b, tl, h, d = q.shape
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    b_off = _shard_offset(b_shard_axes, b).astype(jnp.int32)
    h_off = _shard_offset(h_shard_axes, h).astype(jnp.int32)

    # Head-major layout for the kernel; one transpose at each boundary (XLA
    # folds them into the surrounding reshapes).
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)

    def fb(k_blk, v_blk, src):
        return flash_block(
            qh, k_blk, v_blk, idx * tl, src * tl,
            seed=seed, b_off=b_off, h_off=h_off,
            dropout_rate=dropout_rate,
        )

    # Own (diagonal) block first — every row's diagonal is unmasked, so lse0
    # is finite everywhere and the combine never divides by zero.
    o0, lse0 = fb(kh, vh, idx)
    acc0 = o0.astype(jnp.float32)
    l0 = jnp.ones_like(lse0)

    def body(carry, r):
        k_c, v_c, m, l, acc = carry
        k_c = jax.lax.ppermute(k_c, axis, perm)
        v_c = jax.lax.ppermute(v_c, axis, perm)
        o_r, lse_r = fb(k_c, v_c, (idx - r) % sp)
        # Block-granularity online-softmax combine: weights exp2(lse - m);
        # fully-masked blocks return lse = NEG_INF -> weight underflows to 0.
        m_new = jnp.maximum(m, lse_r)
        w_old = jnp.exp2(m - m_new)
        w_new = jnp.exp2(lse_r - m_new)
        l = l * w_old + w_new
        acc = acc * w_old + o_r.astype(jnp.float32) * w_new
        return (k_c, v_c, m_new, l, acc), None

    (_, _, _, l, acc), _ = jax.lax.scan(
        body, (kh, vh, lse0, l0, acc0), jnp.arange(1, sp)
    )
    return (acc / l).astype(q.dtype).transpose(0, 2, 1, 3)


def ring_attention_bthd(
    q: jnp.ndarray,  # [B, T, H, D] (model-native layout)
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mesh,
    axis: str = "sp",
    dropout_rate: float = 0.0,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    use_flash: bool | None = None,
) -> jnp.ndarray:
    """Causal ring attention over mesh axis ``axis``; drop-in for
    ``causal_attention_bthd`` when the sequence dim is sharded.

    ``T`` must divide by the axis size. Batch/head dims are additionally
    split over whatever data-like/tensor-like mesh axes divide them (same
    policy as the flash kernel's shard_map wrapper).

    ``use_flash`` selects the Pallas flash_block ring (``_ring_local_flash``)
    vs the XLA einsum ring; None = auto (flash whenever the per-device block
    T/sp divides a viable kernel block size — tiny test shapes fall back).
    """
    B, T, H, D = q.shape
    sp = mesh.shape[axis]
    if T % sp != 0:
        raise ValueError(
            f"ring attention needs seq_len divisible by the '{axis}' axis: "
            f"T={T}, {axis}={sp}"
        )
    if use_flash is None:
        from gpt_2_distributed_tpu.ops.flash_attention import pick_block_q

        # Platform-gated like attention.py's flash auto-select: in interpret
        # mode (CPU) the Pallas path is orders of magnitude slower than the
        # XLA einsum ring, so auto only picks it on real TPU; tests force it
        # with use_flash=True.
        # One pick_block_q(T // sp) check covers BOTH kernel operands only
        # because the ring passes full tl-sized K/V blocks, so Tq == Tc == tl
        # (flash_block also needs Tc to divide a viable block). If the ring
        # ever passes differently-sized K/V chunks, gate on both lengths.
        use_flash = (
            jax.devices()[0].platform == "tpu"
            and pick_block_q(T // sp) is not None
        )
    if not use_flash:
        record_resolved_impl("ring blocks", "xla (einsum)")  # else flash_block says
    rate = float(dropout_rate) if (not deterministic and rng is not None) else 0.0
    if rate > 0.0:
        seed = jax.random.randint(rng, (1,), 0, jnp.iinfo(jnp.int32).max, jnp.int32)
    else:
        seed = jnp.zeros((1,), jnp.int32)

    b_axes = dividing_axes(mesh, BATCH_AXIS_NAMES, B)
    h_axes = dividing_axes(mesh, HEAD_AXIS_NAMES, H)
    spec = P(b_axes or None, axis, h_axes or None, None)

    local = functools.partial(
        _ring_local,
        axis=axis,
        sp=sp,
        b_shard_axes=b_axes,
        h_shard_axes=h_axes,
        dropout_rate=rate,
        use_flash=use_flash,
    )
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec, P(None)),
        out_specs=spec, check_vma=False,
    )(q, k, v, seed)
