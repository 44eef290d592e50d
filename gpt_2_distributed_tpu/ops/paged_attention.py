"""Paged decode attention: one query row per sequence against a block pool.

The serving subsystem (``gpt_2_distributed_tpu/serving/``) keeps every
in-flight sequence's K/V in fixed-size blocks carved out of ONE preallocated
device buffer (``[L, num_blocks, H, block_size, D]``, all layers in one
array), addressed through a per-sequence block table — so sequences of
wildly different lengths share the buffer with no per-shape recompiles and
no per-request contiguous allocation. This module is the attention op over
that layout:

    o[b] = softmax(q[b] · K[b]^T / sqrt(D)) · V[b]

where K[b]/V[b] are the first ``lengths[b]`` positions of sequence ``b``,
scattered across pool blocks ``block_table[b, :]`` of layer ``layer``.

Every op here takes the WHOLE pool and a layer index, never a layer's
slice: the step programs carry the pool through their layer loop in the one
device layout the kernel reads (row-major, ``paged_cache.pool_shape``), and
a slice would be a copy of ``N·H·bs·D`` elements per layer. A 4-D
``[N, H, bs, D]`` pool is the one-layer case (``L = 1``, layer 0) of the
same functions.

Two implementations, one contract:

* ``impl="xla"`` — gather the table's blocks into a contiguous
  ``[B, H, S, D]`` view and run exactly the masked fp32 softmax the
  contiguous-cache decode path runs (``models/decode.py::decode_step`` —
  same einsums, same ``MASK_VALUE`` fill, same dtype round-trips), so the
  paged path is testable bit-for-bit against the exactness reference.
  The gather materializes the per-sequence K/V (HBM traffic ~2·B·S·H·D),
  which is what the Pallas kernel exists to avoid.
* ``impl="pallas"`` — a scalar-prefetch kernel with the exp2-folded online
  softmax of ``ops/flash_block.py`` (m/l/acc VMEM scratch carried over the
  grid's block axis). The grid is ``(B, ceil(M / P))``: one step takes ``P``
  blocks of one row with EVERY head — block ``n``'s ``[H, bs, D]`` is one
  contiguous run of the row-major pool, so one DMA brings it — and the
  pool is handed to the kernel ``P`` times, each operand's ``index_map``
  reading its block from the prefetched table (offset to the layer's run
  of blocks): no gathered copy and no layer slice ever exists. Table slots
  past a row's last live block are clamped onto that block before the
  call; a step whose block index repeats fetches nothing, and the
  arithmetic is skipped too, so the table's tail costs neither.
  ``paged_decode_grid`` gives the grid and ``P`` from the shapes. A block's
  two products run on the VPU (a one-row product wastes the MXU): bf16
  products summed in fp32, what the MXU gives but for the order. Decode is
  forward-only, so unlike flash_block there is no VJP; numerics differ from
  the XLA path by online-softmax ulps (same contract as flash vs dense
  attention).

Per-sequence lengths do the masking: position ``s`` of sequence ``b`` is
attendable iff ``s < lengths[b]``. ``lengths[b] == 0`` marks an idle slot
(o = 0) — pool blocks behind the table row are never read into the result.
Block-table entries past a sequence's last block must point at a valid pool
index (the serving layer parks them on the reserved null block 0): the XLA
gather fetches them and masks them, the kernel does not read them at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gpt_2_distributed_tpu.ops.attention import MASK_VALUE
from gpt_2_distributed_tpu.ops.flash_attention import LOG2E, NEG_INF
from gpt_2_distributed_tpu.ops.spmd import pallas_mode, record_resolved_impl

_DIMS = ("parallel", "arbitrary")  # j carries the m/l/acc scratch

# What the K and V tiles of one grid step may hold of VMEM, double-buffered
# as the pipeline keeps them (a v5e kernel has 16 MiB by default). More buys
# nothing: the scalar core's work is per operand and step, so per table slot
# whatever P is, and a wider step only fetches more of the clamp's repeats
# (my chip runs, PR 29: a decode step's 48 calls at B 4, H 25, M 64 and
# rows of 70-480 keys take 2.66 / 2.47 / 2.53 / 2.54 / 2.66 / 2.74 ms at
# P 1 / 2 / 3 / 4 / 5 / 8; at full rows 6.85 / 5.90 / 5.86 / 5.81 / 5.90 /
# 5.93).
_VMEM_BUDGET = 2**20


def _contiguous_view(pool: jnp.ndarray, layer, block_table: jnp.ndarray):
    """One gather of ``pool[layer, block_table]``, ``[B, M, H, bs, D]``, as
    the contiguous per-sequence view ``[B, H, M*bs, D]``."""
    if pool.ndim == 4:       # one layer's [N, H, bs, D]: the L = 1 case
        pool = pool[None]
    b, m = block_table.shape
    _, _, h, bs, d = pool.shape
    blocks = pool[layer, block_table]
    return blocks.transpose(0, 2, 1, 3, 4).reshape(b, h, m * bs, d)


def paged_attention_xla(
    q: jnp.ndarray,            # [B, H, D] compute dtype
    k_pool: jnp.ndarray,       # [L, N, H, bs, D] (or [N, H, bs, D], layer 0)
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, M] int32 pool indices
    lengths: jnp.ndarray,      # [B] int32 attendable positions (0 = idle)
    layer=0,                   # scalar int32 (traced in the layer loop)
) -> jnp.ndarray:
    """Gather-based reference path. Mirrors ``decode.decode_step``'s
    attention bit-for-bit on the attendable prefix: identical einsum forms,
    fp32 scores, ``MASK_VALUE`` fill (which underflows to exactly 0 after
    the softmax max-subtract), probs cast back to the compute dtype."""
    b, _, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    kc = _contiguous_view(k_pool, layer, block_table)  # [B, H, M*bs, D]
    vc = _contiguous_view(v_pool, layer, block_table)
    s = kc.shape[2]

    qh = q[:, :, None]                               # [B, H, 1, D]
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", qh, kc, preferred_element_type=jnp.float32
    ) * scale                                        # [B, H, 1, M*bs] fp32
    kpos = jax.lax.broadcasted_iota(jnp.int32, (b, 1, 1, s), 3)
    mask = kpos < lengths[:, None, None, None]
    scores = jnp.where(mask, scores, MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    # Idle slots (lengths == 0) softmax over an all-MASK_VALUE row to a
    # uniform distribution; zero them explicitly so o is exactly 0.
    probs = jnp.where(lengths[:, None, None, None] > 0, probs, 0.0)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, vc)
    return o[:, :, 0]                                # [B, H, D]


def paged_prefill_attention(
    q: jnp.ndarray,            # [B, T, H, D] chunk queries, compute dtype
    k_pool: jnp.ndarray,       # [L, N, H, bs, D] (or [N, H, bs, D], layer 0)
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, M] int32 pool indices
    start: jnp.ndarray,        # [B] int32 absolute position of q[:, 0]
    layer=0,                   # scalar int32 (traced in the layer loop)
) -> jnp.ndarray:
    """Chunked-prefill attention over a partially-built block table.

    Query ``t`` of sequence ``b`` sits at absolute position
    ``start[b] + t`` and attends causally over the table's contiguous
    view — all earlier positions (prior chunks and prefix-cache hits
    already scattered into pool blocks) plus the current chunk's own
    K/V, which the caller must have scattered before this call.

    Mirrors the dense prefill path (``ops/attention.py::
    causal_attention_bthd``) op-for-op on the attendable region —
    identical einsum forms, fp32 scores with the scale applied after,
    ``MASK_VALUE`` fill, fp32 softmax, probs cast back — so on the
    dense-prefill path (CPU "auto"/"xla") chunked prefill is bit-identical
    to whole-prompt prefill for any chunk split. Positions past the causal
    frontier read whatever the pool holds (stale blocks, later rows of a
    partially-filled tail block): MASK_VALUE's post-max-subtract underflow
    zeroes them exactly — the same masked-width invariance
    ``paged_attention_xla`` already relies on.

    XLA gather only: prefill is compute-bound (the O(T·S) score matmul
    dominates the gathered-copy traffic), so the Pallas scalar-prefetch
    treatment that pays off for single-row decode is left to the on-chip
    campaign.
    """
    b, t, _, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    kc = _contiguous_view(k_pool, layer, block_table)  # [B, H, M*bs, D]
    vc = _contiguous_view(v_pool, layer, block_table)
    s = kc.shape[2]

    qh = q.transpose(0, 2, 1, 3)                     # [B, H, T, D]
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", qh, kc, preferred_element_type=jnp.float32
    ) * scale                                        # [B, H, T, M*bs] fp32
    qpos = start[:, None, None, None] + jax.lax.broadcasted_iota(
        jnp.int32, (b, 1, t, 1), 2
    )
    kpos = jax.lax.broadcasted_iota(jnp.int32, (b, 1, 1, s), 3)
    scores = jnp.where(kpos <= qpos, scores, MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, vc)     # [B, H, T, D]
    return o.transpose(0, 2, 1, 3)                   # [B, T, H, D]


def spec_verify_attention(
    q: jnp.ndarray,            # [B, T, H, D] verify-window queries
    k_pool: jnp.ndarray,       # [L, N, H, bs, D] (or [N, H, bs, D], layer 0)
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, M] int32 pool indices
    start: jnp.ndarray,        # [B] int32 absolute position of q[:, 0]
    layer=0,                   # scalar int32 (traced in the layer loop)
) -> jnp.ndarray:
    """Speculative-decoding verify pass: the target model re-scores a
    draft run of T tokens (the committed decode input plus the drafted
    continuation) in ONE call.

    This is *exactly* a T-token chunked prefill over the request's
    partially-built block table — query ``t`` sits at ``start[b] + t``,
    attends causally over the table, and the caller has already
    scattered the window's own K/V — so it delegates to
    :func:`paged_prefill_attention` unchanged. The alias exists so the
    verify pass has a named entry here (profiling, future Pallas
    treatment) and so the bit-exactness argument is explicit: verify
    shares every op with chunked prefill, which is already pinned
    bit-identical to the dense path, so a greedy verify re-derives the
    exact logits sequential decode would have produced at each drafted
    position.
    """
    return paged_prefill_attention(
        q, k_pool, v_pool, block_table, start, layer
    )


def paged_decode_grid(
    b: int, h: int, m: int, bs: int, d: int, itemsize: int = 2, pools: int = 2
) -> tuple[tuple[int, int], int]:
    """The kernel's grid ``(B, ceil(M / P))`` and ``P``, the blocks one step
    takes, from the shapes alone: as many ``[H, bs, D]`` tiles as
    ``_VMEM_BUDGET`` holds for K and V (``pools``: 1 for a latent pool
    alone), double-buffered — at least one, at most the table's width. A
    tile is counted as VMEM lays it out (``bs`` to the dtype's sublane tile,
    ``D`` to 128 lanes)."""
    sublanes = 8 * 4 // itemsize
    tile = h * -(-bs // sublanes) * sublanes * -(-d // 128) * 128 * itemsize
    p = max(1, min(m, _VMEM_BUDGET // (2 * pools * tile)))
    return (b, -(-m // p)), p


def _paged_fwd_kernel(
    bt_ref,       # scalar prefetch: [B, steps * P] int32 clamped block table
    len_ref,      # scalar prefetch: [B] int32 lengths
    q_ref,        # [1, H, 1, D]
    *refs,        # P K tiles and P V tiles [1, H, bs, D], each the pool block
                  # its index_map selected; o [1, H, 1, D]; then VMEM scratch
                  # m [H, 1, 1] f32, l [H, 1, 1] f32, acc [H, 1, D] f32
    block_size: int,
    per_step: int,
    table_width: int,
):
    k_refs, v_refs = refs[:per_step], refs[per_step:2 * per_step]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * per_step:]
    b, j = pl.program_id(0), pl.program_id(1)
    d = q_ref.shape[3]
    scale = LOG2E / (d ** 0.5)
    # Slots past the table's width (P need not divide it) hold a clamped
    # block: never attend them, whatever the length says.
    length = jnp.minimum(len_ref[b], table_width * block_size)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(k_ref, v_ref, base):
        """One block, every head; all layouts keep their dims, so scores
        stay [H, bs, 1]: keys on sublanes as K and V hold them."""
        q = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)
        s = jnp.sum(
            k_ref[0].astype(jnp.float32) * q.astype(jnp.float32),
            axis=-1, keepdims=True,
        )  # [H, bs, 1] f32, base-2 logits
        row = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = row < length
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        # Masked lanes must be forced to 0: on a row where every lane is
        # masked m_new stays NEG_INF and exp2(s - m_new) would leak 1s
        # (the same guard flash_block documents).
        p = jnp.where(valid, jnp.exp2(s - m_new), 0.0)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]
        acc_scr[...] = acc_scr[...] * alpha + jnp.sum(
            p.astype(v.dtype).astype(jnp.float32) * v.astype(jnp.float32),
            axis=1, keepdims=True,
        )

    # Blocks wholly past the sequence contribute nothing and were not
    # fetched (their slot repeats the last live block): skip the math.
    for i in range(per_step):
        base = (j * per_step + i) * block_size
        pl.when(base < length)(
            functools.partial(attend, k_refs[i], v_refs[i], base)
        )

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_scr[...]
        has = l > 0.0
        o_ref[0] = jnp.where(
            has, acc_scr[...] / jnp.maximum(l, 1e-37), 0.0
        ).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jnp.ndarray,            # [B, H, D]
    k_pool: jnp.ndarray,       # [L, N, H, bs, D] (or [N, H, bs, D], layer 0)
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, M] int32
    lengths: jnp.ndarray,      # [B] int32
    layer=0,                   # scalar int32 (traced in the layer loop)
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Scalar-prefetch paged attention: K/V blocks stream from their pool
    slots via the table-indexed ``index_map`` — neither a layer's slice of
    the pool nor the gathered contiguous [B, H, S, D] view ever
    materializes. The call reads the pool row-major, the layout it is
    stored in (``paged_cache.pool_shape``).

    The layer is folded into the table, not into the kernel: the pool is
    viewed as ``[L*N, H, bs, D]`` (merging the two major axes of a
    row-major array moves nothing) and block ``n`` of layer ``l`` is row
    ``l*N + n`` of it. So is the clamp that stops a row at its last live
    block: the table the kernel gets is ``steps * P`` wide and slot ``s``
    of row ``b`` holds ``block_table[b, min(s, last live slot)]`` (slot 0
    for an idle row), made here by one small gather. The scalar core pays
    for every term of an index map, once per operand and step: with the
    division and the minimum inside the map the same 48 calls took 3.03 ms
    for 2.69 at P = 4 (my chip runs, PR 29)."""
    b, h, d = q.shape
    n, _, bs, _ = k_pool.shape[-4:]
    m = block_table.shape[1]
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    record_resolved_impl(
        "paged_attention", f"pallas ({pallas_mode(interpret)})"
    )
    grid, per_step = paged_decode_grid(b, h, m, bs, d, k_pool.dtype.itemsize)

    lengths = lengths.astype(jnp.int32)
    last = jnp.clip((lengths - 1) // bs, 0, m - 1)
    slots = jnp.minimum(
        jnp.arange(grid[1] * per_step, dtype=jnp.int32), last[:, None]
    )
    table = jnp.take_along_axis(block_table.astype(jnp.int32), slots, axis=1)

    row = pl.BlockSpec((1, h, 1, d), lambda b_, j, bt, ln: (b_, 0, 0, 0))

    def block_spec(i):
        # The paging trick: the pool's block axis is indexed by the
        # PREFETCHED table, not the grid — block j*P + i of sequence b
        # lives wherever the allocator put it, with all its heads.
        return pl.BlockSpec(
            (1, h, bs, d),
            lambda b_, j, bt, ln: (bt[b_, j * per_step + i], 0, 0, 0),
        )

    blocks = [block_spec(i) for i in range(per_step)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[row, *blocks, *blocks],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((h, 1, 1), jnp.float32),
            pltpu.VMEM((h, 1, 1), jnp.float32),
            pltpu.VMEM((h, 1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_fwd_kernel, block_size=bs, per_step=per_step, table_width=m
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=_DIMS),
        interpret=interpret,
    )(
        table + jnp.asarray(layer, jnp.int32) * n,
        lengths,
        q[:, :, None],               # [B, H, 1, D]
        *[k_pool.reshape(-1, h, bs, d)] * per_step,
        *[v_pool.reshape(-1, h, bs, d)] * per_step,
    )
    return out[:, :, 0]


def _serving_mesh_active() -> bool:
    """True when tracing under a multi-device serving mesh (data or tp > 1)
    activated via ``parallel.mesh.activate_mesh``."""
    from gpt_2_distributed_tpu.parallel.mesh import (
        DATA_AXIS,
        TP_AXIS,
        active_mesh,
    )

    m = active_mesh()
    if m is None:
        return False
    return any(
        ax in m.axis_names and m.shape[ax] > 1 for ax in (DATA_AXIS, TP_AXIS)
    )


def paged_attention(
    q: jnp.ndarray,            # [B, H, D]
    k_pool: jnp.ndarray,       # [L, N, H, bs, D] (or [N, H, bs, D], layer 0)
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, M] int32
    lengths: jnp.ndarray,      # [B] int32
    layer=0,                   # scalar int32 (traced in the layer loop)
    *,
    impl: str = "auto",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Dispatch: "auto" = Pallas on TPU (no gather traffic), XLA elsewhere
    (bit-exact vs the contiguous decode path — the serving tests' mode)."""
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"paged_attention impl={impl!r}: expected 'auto', 'xla' or 'pallas'"
        )
    if q.ndim != 3:
        raise ValueError(f"q must be [B, H, D], got shape {q.shape}")
    if k_pool.ndim not in (4, 5) or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"k_pool/v_pool must be matching [L, N, H, bs, D] (or one "
            f"layer's [N, H, bs, D]), got {k_pool.shape} / {v_pool.shape}"
        )
    if impl == "auto":
        impl = "pallas" if jax.devices()[0].platform == "tpu" else "xla"
        if impl == "pallas" and _serving_mesh_active():
            # A sharded engine traces this under its data×tp mesh; the
            # Pallas kernel can't consume GSPMD-sharded pools/tables, so
            # "auto" degrades to the XLA gather (correct on any mesh).
            # Forced "pallas" still goes through and fails loudly.
            impl = "xla"
    if impl == "pallas":
        return paged_attention_pallas(
            q, k_pool, v_pool, block_table, lengths, layer,
            interpret=interpret,
        )
    record_resolved_impl("paged_attention", "xla (gather)")
    return paged_attention_xla(
        q, k_pool, v_pool, block_table, lengths, layer
    )


# --- grouped queries over a SELECTED list of blocks ---------------------------
#
# A row of the state families' decode step attends over a list of its blocks
# (the whole table as far as the row has got, or a sparse layer's selection),
# and ``G`` query heads share each KV head. Decode has two forms, one contract:
# on a TPU the kernel ``paged_gqa_decode`` reads each (row, KV head)'s listed
# tiles straight from the pools and nothing past ``count``; elsewhere
# ``paged_sparse_attention`` gathers the listed blocks in XLA - the tests'
# reference and the path off the chip. A prefill chunk, whose thousands of
# queries each list other blocks and together list nearly all, walks the row's
# table in tiles under a mask in XLA (``paged_masked_attention``), which keeps
# the products on the MXU. ``paged_attention`` above is untouched by any of
# them. A latent pool (one row a position from which every head's key and
# value are made, no V pool) is read two ways: a decode row scores the rows as
# they lie with absorbed queries - the kernel's walk over the one pool,
# ``paged_mla_decode`` - and a chunk decompresses each tile's rows to keys and
# values and attends them (``paged_mla_prefill``: on a TPU the kernel
# ``mla_prefill_attention``, the scores kept in VMEM; elsewhere in XLA).

_MASKED = -1e30   # finite, so a row with nothing selected in a tile stays NaN-free


def _gqa_decode_kernel(
    layer_ref,    # scalar prefetch: [1] int32
    blocks_ref,   # scalar prefetch: [B * KV * W] int32 pool blocks, as listed
    logical_ref,  # scalar prefetch: [B * KV * W] int32 their index in the sequence
    count_ref,    # scalar prefetch: [B * KV] int32 entries in use, at most W
    pos_ref,      # scalar prefetch: [B] int32 query positions
    q_ref,        # [1, 1, G, D]
    *refs,
    per_group: int,
    n_blocks: int,
    value_lanes: int | None = None,
    scale_dim: int | None = None,
):
    """One (row, KV head) a grid step, its list walked ``P`` tiles at a time.
    The grid runs in order, and the last group of a step starts the copies of
    the next step's first, so the copies run ahead across rows too.

    ``refs`` are, for a K and a V pool: ``k_hbm``, ``v_hbm`` ([L * N, KV,
    bs, D], the pools left where they are), ``o_ref`` [1, 1, G, D], ``k_buf``,
    ``v_buf`` (VMEM [2, P, bs, D]: two groups of P tiles, one being read while
    the other fills), ``sem`` (DMA semaphores [2, 2]: (K, V) x buffer) and
    ``ring`` (SMEM [2] int32: the buffer the step's first group goes to, and
    whether the step before already started it). For a latent pool alone
    (``value_lanes``: ``paged_mla_decode``) there is no V pool and no V
    buffer, ``sem`` is [1, 2], and a tile's first ``value_lanes`` lanes are
    its value; ``o_ref`` is [1, 1, G, value_lanes]. The scores are scaled by
    ``1 / sqrt(scale_dim)`` (D unless given)."""
    if value_lanes is None:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, ring = refs
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    else:
        k_hbm, o_ref, k_buf, sem, ring = refs
        pools = ((k_hbm, k_buf),)
        v_buf = k_buf
    b, h = pl.program_id(0), pl.program_id(1)
    kv = pl.num_programs(1)
    steps = pl.num_programs(0) * kv
    row = b * kv + h
    width = blocks_ref.shape[0] // steps
    bs, d = k_buf.shape[2], k_buf.shape[3]
    span = per_group * bs

    def copies(at, g, buf, start):
        """Start, or wait for, the copies of group ``g`` of step ``at``: the
        K and V tiles of its blocks in use, into buffer ``buf``."""
        def one(i, carry):
            blk = blocks_ref[at * width + g * per_group + i] + layer_ref[0] * n_blocks
            for j, (pool, into) in enumerate(pools):
                copy = pltpu.make_async_copy(
                    pool.at[blk, at % kv], into.at[buf, i], sem.at[j, buf])
                if start:
                    copy.start()
                else:
                    copy.wait()
            return carry

        n = jnp.minimum(per_group, count_ref[at] - g * per_group)
        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(row == 0)
    def _reset():
        ring[0] = 0
        ring[1] = 0

    n = count_ref[row]
    groups = (n + per_group - 1) // per_group
    after = jnp.minimum(row + 1, steps - 1)
    next_n = jnp.where(row + 1 < steps, count_ref[after], 0)

    @pl.when((ring[1] == 0) & (groups > 0))
    def _first():
        copies(row, 0, ring[0], True)

    q = q_ref[0, 0]                                          # [G, D]
    pos = pos_ref[b]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
    tile_of, key_of = lane // bs, lane % bs

    def group(g, carry):
        top, total, acc, buf = carry
        other = 1 - buf
        more = g + 1 < groups

        @pl.when(more | (next_n > 0))
        def _ahead():     # this step's next group, or the next step's first
            copies(jnp.where(more, row, after), jnp.where(more, g + 1, 0), other, True)

        copies(row, g, buf, False)
        used = n - g * per_group      # tiles of this group in use, 1..P

        def clear(i, c):
            # an unfilled tile holds whatever VMEM held: its weight is 0, and
            # 0 * NaN is not
            v_buf[buf, i] = jnp.zeros((bs, d), v_buf.dtype)
            return c

        jax.lax.fori_loop(used, per_group, clear, 0)

        def last_key(i, edge):
            """Per lane, the last key offset its tile may attend (-1: none)."""
            at = row * width + jnp.minimum(g * per_group + i, width - 1)
            mine = jnp.where(i < used, pos - logical_ref[at] * bs, -1)
            return jnp.where(tile_of == i, mine, edge)

        edge = jax.lax.fori_loop(
            0, per_group, last_key, jnp.full((1, span), -1, jnp.int32))
        keep = key_of <= edge                                # [1, P*bs]
        k = k_buf[buf].reshape(span, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) / ((scale_dim or d) ** 0.5)                        # [G, P*bs] f32
        s = jnp.where(keep, s, _MASKED)
        new_top = jnp.maximum(top, s.max(axis=1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - new_top), 0.0)
        alpha = jnp.exp(top - new_top)
        v = v_buf[buf].reshape(span, d)
        if value_lanes is not None:
            v = v[:, :value_lanes]
        acc = acc * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return new_top, total * alpha + p.sum(axis=1, keepdims=True), acc, other

    g_heads = q.shape[0]
    top, total, acc, buf = jax.lax.fori_loop(0, groups, group, (
        jnp.full((g_heads, 1), _MASKED, jnp.float32),
        jnp.zeros((g_heads, 1), jnp.float32),
        jnp.zeros((g_heads, value_lanes or d), jnp.float32),
        ring[0],
    ))
    ring[0] = buf
    ring[1] = ((groups > 0) & (next_n > 0)).astype(jnp.int32)
    o_ref[0, 0] = jnp.where(
        total > 0.0, acc / jnp.maximum(total, 1e-37), 0.0).astype(o_ref.dtype)


def _listed_decode_call(q, pools, blocks, logical, count, pos, layer, *,
                        name: str, value_lanes: int | None, scale_dim: int | None,
                        interpret: bool):
    """``_gqa_decode_kernel`` over ``pools`` (K and V, or a latent pool
    alone): grid ``(B, KV)``, the lists prefetched as scalars."""
    b, kv, g, d = q.shape
    n, _, bs, _ = pools[0].shape[-4:]
    w = blocks.shape[-1]
    per_group = paged_decode_grid(
        b, 1, w, bs, d, pools[0].dtype.itemsize, len(pools))[1]
    tiles = pltpu.VMEM((2, per_group, bs, d), pools[0].dtype)
    dv = value_lanes or d
    head = pl.BlockSpec((1, 1, g, d), lambda i, j, *_: (i, j, 0, 0))
    out = pl.BlockSpec((1, 1, g, dv), lambda i, j, *_: (i, j, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_gqa_decode_kernel, per_group=per_group, n_blocks=n,
                          value_lanes=value_lanes, scale_dim=scale_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, kv),
            in_specs=[head] + [pool] * len(pools),
            out_specs=out,
            scratch_shapes=[
                *[tiles] * len(pools),
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dv), q.dtype),
        # the steps run in order: each starts the next one's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        blocks.reshape(-1).astype(jnp.int32),
        logical.reshape(-1).astype(jnp.int32),
        jnp.minimum(count, w).reshape(-1).astype(jnp.int32),
        pos.astype(jnp.int32),
        q,
        *(p.reshape(-1, kv, bs, d) for p in pools),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gqa_decode(
    q: jnp.ndarray,            # [B, KV, G, D] one query position per row
    k_pool: jnp.ndarray,       # [L, N, KV, bs, D]
    v_pool: jnp.ndarray,
    blocks: jnp.ndarray,       # [B, KV, W] int32 pool blocks, as listed
    logical: jnp.ndarray,      # [B, KV, W] int32 their index in the sequence
    count: jnp.ndarray,        # [B, KV] int32 entries of the list in use
    pos: jnp.ndarray,          # [B] int32 query position (keys <= pos)
    layer: jnp.ndarray,        # int32 scalar, traced
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """The decode kernel behind ``paged_sparse_attention`` on a TPU. Grid
    ``(B, KV)``; a step reads its (row, KV head)'s ``[bs, D]`` tile of each
    listed block in use, ``P`` tiles a group (``paged_decode_grid``'s ``P``
    at one head: as many as ``_VMEM_BUDGET`` holds double-buffered), and
    attends all ``G`` query heads against a group in one product, with the
    softmax online in float32. The pools stay in HBM (``memory_space=ANY``)
    and the list is prefetched as scalars, so what the kernel's lowering
    holds does not grow with ``W``. It is a ``jax.jit`` of its own with the
    layer traced: the 2-4 attention layers of a decode program call one
    lowered function (one ``tpu_custom_call`` in the program's text)."""
    return _listed_decode_call(
        q, (k_pool, v_pool), blocks, logical, count, pos, layer,
        name="paged_gqa_decode", value_lanes=None, scale_dim=None,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("value_lanes", "scale_dim", "interpret"))
def paged_mla_decode(
    q: jnp.ndarray,            # [B, 1, H, D] absorbed queries: [W_UK^T q_nope ; q_pe ; 0]
    pool: jnp.ndarray,         # [L, N, 1, bs, D] latent rows: [c ; k_pe ; 0]
    blocks: jnp.ndarray,       # [B, 1, W] int32 pool blocks, as listed
    logical: jnp.ndarray,      # [B, 1, W] int32 their index in the sequence
    count: jnp.ndarray,        # [B, 1] int32 entries of the list in use
    pos: jnp.ndarray,          # [B] int32 query position (keys <= pos)
    layer: jnp.ndarray,        # int32 scalar, traced
    *,
    value_lanes: int,
    scale_dim: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Absorbed latent attention, the kernel behind ``paged_mla_attention``
    on a TPU: ``paged_gqa_decode``'s walk over ONE pool whose row is both
    key and value. A grid step reads a row's ``[bs, R]`` tiles once, scores
    all ``H`` heads against them in one product at ``1 / sqrt(scale_dim)``,
    and accumulates ``u = sum p c`` from each tile's first ``value_lanes``
    lanes. Returns u [B, 1, H, value_lanes]. A ``jax.jit`` of its own with
    the layer traced: lowered once a program, however many layers call it."""
    return _listed_decode_call(
        q, (pool,), blocks, logical, count, pos, layer, name="paged_mla_decode",
        value_lanes=value_lanes, scale_dim=scale_dim, interpret=interpret)


def paged_sparse_attention(
    q: jnp.ndarray,            # [B, KV, G, D] one query position per row
    k_pool: jnp.ndarray,       # [L, N, KV, bs, D]
    v_pool: jnp.ndarray,
    blocks: jnp.ndarray,       # [B, KV, W] int32 pool blocks, as listed
    logical: jnp.ndarray,      # [B, KV, W] int32 their index in the sequence
    count: jnp.ndarray,        # [B, KV] int32 entries of the list in use
    pos: jnp.ndarray,          # [B] int32 query position (keys <= pos)
    layer=0,
) -> jnp.ndarray:
    """``o[b, kv, g] = softmax(q . K^T / sqrt(D)) V`` over the keys at or
    before ``pos[b]`` of the ``count[b, kv]`` listed blocks. A row with
    ``count == 0`` (an idle slot) gives 0. Returns [B, KV, G, D]. On a TPU
    the kernel ``paged_gqa_decode``; elsewhere one gather of the listed
    blocks, fp32 scores and softmax, probabilities cast back to the compute
    dtype."""
    if jax.devices()[0].platform == "tpu":
        record_resolved_impl("paged_sparse_attention", "pallas (mosaic)")
        return paged_gqa_decode(
            q, k_pool, v_pool, blocks, logical, count, pos,
            jnp.asarray(layer, jnp.int32))
    record_resolved_impl("paged_sparse_attention", "xla (gather)")
    b, kv, g, d = q.shape
    bs = k_pool.shape[-2]
    w = blocks.shape[-1]
    head = jnp.arange(kv)[None, :, None]
    kc = k_pool[layer, blocks, head]                       # [B, KV, W, bs, D]
    vc = v_pool[layer, blocks, head]
    scores = jnp.einsum("bkgd,bkwsd->bkgws", q, kc,
                        preferred_element_type=jnp.float32) / jnp.sqrt(float(d))
    key_pos = logical[..., None] * bs + jnp.arange(bs)     # [B, KV, W, bs]
    keep = (jnp.arange(w)[None, None, :, None] < count[..., None, None]) \
        & (key_pos <= pos[:, None, None, None])
    scores = jnp.where(keep[:, :, None], scores, _MASKED).reshape(b, kv, g, w * bs)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(count[:, :, None, None] > 0, probs, 0.0).astype(q.dtype)
    return jnp.einsum("bkgs,bksd->bkgd", probs, vc.reshape(b, kv, w * bs, d))


def paged_mla_attention(
    q: jnp.ndarray,            # [B, 1, H, D] absorbed queries: [W_UK^T q_nope ; q_pe ; 0]
    pool: jnp.ndarray,         # [L, N, 1, bs, D] latent rows: [c ; k_pe ; 0]
    blocks: jnp.ndarray,       # [B, 1, W] int32 pool blocks, as listed
    logical: jnp.ndarray,      # [B, 1, W] int32 their index in the sequence
    count: jnp.ndarray,        # [B, 1] int32 entries of the list in use
    pos: jnp.ndarray,          # [B] int32 query position (keys <= pos)
    layer=0,
    *,
    value_lanes: int,
    scale_dim: int,
) -> jnp.ndarray:
    """Absorbed latent attention of one query a row: ``u[b, h] = sum_s
    softmax_s(q[b, h] . row_s / sqrt(scale_dim)) c_s`` over the keys at or
    before ``pos[b]`` of the ``count[b]`` listed blocks, ``c_s`` a row's
    first ``value_lanes`` lanes. A row with ``count == 0`` gives 0. Returns
    [B, 1, H, value_lanes]. On a TPU the kernel ``paged_mla_decode``;
    elsewhere one gather of the listed blocks, float32 scores and softmax,
    probabilities cast back to the compute dtype."""
    if jax.devices()[0].platform == "tpu":
        record_resolved_impl("paged_mla_decode", "pallas (mosaic)")
        return paged_mla_decode(
            q, pool, blocks, logical, count, pos, jnp.asarray(layer, jnp.int32),
            value_lanes=value_lanes, scale_dim=scale_dim)
    record_resolved_impl("paged_mla_decode", "xla (gather)")
    b, _, h, r = q.shape
    bs = pool.shape[-2]
    w = blocks.shape[-1]
    rows = pool[layer, blocks[:, 0], 0].reshape(b, w * bs, r)   # [B, W*bs, R]
    scores = jnp.einsum("bhr,bsr->bhs", q[:, 0], rows,
                        preferred_element_type=jnp.float32) / jnp.sqrt(float(scale_dim))
    key_pos = (logical[:, 0, :, None] * bs + jnp.arange(bs)).reshape(b, w * bs)
    keep = (jnp.arange(w)[None, :, None] < count[:, :, None]).repeat(bs, axis=2)
    keep = keep.reshape(b, w * bs) & (key_pos <= pos[:, None])
    probs = jax.nn.softmax(jnp.where(keep[:, None], scores, _MASKED), axis=-1)
    probs = jnp.where(count[:, :, None] > 0, probs, 0.0).astype(q.dtype)
    return jnp.einsum("bhs,bsr->bhr", probs, rows[..., :value_lanes])[:, None]


# Keys a grid step of ``mla_prefill_attention`` decompresses and attends: a
# [T, 512] float32 score tile of a 2,048-query chunk is 4 MB of VMEM.
_PREFILL_KEYS = 512
_PE_LANES = 128       # the rotary part of a query and of a row, padded to one lane tile


def _mla_prefill_kernel(
    start_ref,    # scalar prefetch: [1] int32 position of the chunk's first query
    tiles_ref,    # scalar prefetch: [1] int32 key tiles that hold a visible key
    q_nope_ref,   # [1, T, dn] one head's queries
    q_pe_ref,     # [1, T, 128] their rotary part, zero-padded
    lat_ref,      # [tk, lanes] the row's latent rows of this key tile
    w_uk_ref,     # [1, R, dn] the head's up-projection to keys
    w_uv_ref,     # [1, R, dv] and to values
    o_ref,        # [1, T, dv]
    top,          # VMEM [T, 1] f32 running max (base 2)
    total,        # VMEM [T, 1] f32 running sum
    acc,          # VMEM [T, dv] f32
    *,
    rank: int,
    scale: float,
):
    """One (head, key tile) a grid step: the tile's latent rows decompressed
    to the head's keys and values in VMEM, then attended by the chunk's
    queries with the softmax online in float32 (base 2, ``scale`` folded into
    the decompressed keys before their one rounding, and into the rotary
    queries). A tile past the last visible key is neither fetched (its
    block index repeats the last one's) nor computed; only tiles that reach
    past the first query's position pay the causal mask."""
    j = pl.program_id(1)
    start, n = start_ref[0], tiles_ref[0]
    tk = lat_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        top[...] = jnp.full_like(top, _MASKED)
        total[...] = jnp.zeros_like(total)
        acc[...] = jnp.zeros_like(acc)

    def attend(masked: bool):
        lat = lat_ref[...]
        c = lat[:, :rank]
        k_nope = (jnp.dot(c, w_uk_ref[0], preferred_element_type=jnp.float32)
                  * scale).astype(c.dtype)
        v = jnp.dot(c, w_uv_ref[0], preferred_element_type=jnp.float32).astype(c.dtype)
        q_nope = q_nope_ref[0]
        q_pe = (q_pe_ref[0].astype(jnp.float32) * scale).astype(c.dtype)
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(q_nope, k_nope, nt, preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q_pe, lat[:, rank:rank + _PE_LANES], nt,
                                   preferred_element_type=jnp.float32))   # [T, tk]
        if masked:
            row = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = j * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = col <= row
            s = jnp.where(keep, s, _MASKED)
        new_top = jnp.maximum(top[...], s.max(axis=1, keepdims=True))
        p = jnp.exp2(s - new_top)
        if masked:
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp2(top[...] - new_top)
        total[...] = total[...] * alpha + p.sum(axis=1, keepdims=True)
        acc[...] = acc[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        top[...] = new_top

    live = j < n
    unmasked = (j + 1) * tk <= start + 1
    pl.when(live & unmasked)(lambda: attend(False))
    pl.when(live & jnp.logical_not(unmasked))(lambda: attend(True))

    @pl.when(j == n - 1)
    def _out():
        o_ref[0] = (acc[...] / total[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mla_prefill_attention(
    q_nope: jnp.ndarray,       # [T, H, dn] a chunk's queries, at start .. start + T - 1
    q_pe: jnp.ndarray,         # [T, H, dr] their rotary part, rotated
    latent: jnp.ndarray,       # [S, lanes] the row's latent rows [c ; k_pe ; 0] in order
    start: jnp.ndarray,        # int32 scalar, the first query's position
    w_uk: jnp.ndarray,         # [R, H, dn]
    w_uv: jnp.ndarray,         # [R, H, dv]
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Expanded latent attention of a chunk's consecutive queries, the
    kernel behind ``paged_mla_prefill`` on a TPU. Grid ``(H, S / 512)``: a
    step decompresses 512 latent rows to the head's keys ``[c W_UK ; k_pe]``
    and values ``c W_UV`` and attends all ``T`` queries against them, so
    what a step holds follows the tile and no score leaves VMEM. Returns
    [T, H, dv]. Needs ``R`` a multiple of 128 and ``lanes >= R + 128``."""
    t, h, dn = q_nope.shape
    dr = q_pe.shape[-1]
    s_len, lanes = latent.shape
    r, dv = w_uk.shape[0], w_uv.shape[-1]
    tk = _PREFILL_KEYS
    start = jnp.asarray(start, jnp.int32)
    tiles = jnp.minimum((start + t + tk - 1) // tk, s_len // tk)
    head = lambda d: pl.BlockSpec((1, t, d), lambda i, j, *_: (i, 0, 0))
    weight = lambda d: pl.BlockSpec((1, r, d), lambda i, j, *_: (i, 0, 0))
    rows = pl.BlockSpec((tk, lanes), lambda i, j, _, n: (jnp.minimum(j, n[0] - 1), 0))
    q_pe = jnp.pad(q_pe, ((0, 0), (0, 0), (0, _PE_LANES - dr)))
    o = pl.pallas_call(
        functools.partial(_mla_prefill_kernel, rank=r, scale=LOG2E / (dn + dr) ** 0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, s_len // tk),
            in_specs=[head(dn), head(_PE_LANES), rows, weight(dn), weight(dv)],
            out_specs=head(dv),
            scratch_shapes=[
                pltpu.VMEM((t, 1), jnp.float32),
                pltpu.VMEM((t, 1), jnp.float32),
                pltpu.VMEM((t, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((h, t, dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="mla_prefill",
    )(start.reshape(1), tiles.reshape(1), q_nope.transpose(1, 0, 2),
      q_pe.transpose(1, 0, 2), latent, w_uk.transpose(1, 0, 2), w_uv.transpose(1, 0, 2))
    return o.transpose(1, 0, 2)


def paged_mla_prefill(
    q_nope: jnp.ndarray,       # [T, H, dn] queries of ONE row's chunk
    q_pe: jnp.ndarray,         # [T, H, dr] their rotary part, rotated
    pool: jnp.ndarray,         # [L, N, 1, bs, >= R + dr] latent rows: [c ; k_pe ; pad]
    table: jnp.ndarray,        # [M] int32 the row's block table
    pos: jnp.ndarray,          # [T] int32 query positions, ascending
    w_uk: jnp.ndarray,         # [R, H, dn] the latent's up-projection to keys
    w_uv: jnp.ndarray,         # [R, H, dv] and to values
    layer=0,
    tile_blocks: int = 8,
) -> jnp.ndarray:
    """Expanded latent attention of a chunk's queries over the row's table,
    ``tile_blocks`` blocks at a time with a running softmax: each tile's
    latent rows are decompressed to every head's key ``[c W_UK ; k_pe]`` and
    value ``c W_UV``, and query ``t`` attends the keys at or before
    ``pos[t]`` at ``1 / sqrt(dn + dr)``. What a tile holds follows the tile,
    not the context, and the walk stops at the tile that holds ``pos[-1]``.
    Returns [T, H, dv]. On a TPU, where the shapes allow it (``R`` a
    multiple of 128, the rows at least ``R + 128`` lanes, the row's table
    ``S`` = ``M * bs`` keys a multiple of 512) and ``pos`` are consecutive,
    the kernel ``mla_prefill_attention`` over the row's rows gathered in
    order; elsewhere this walk in XLA."""
    t, h, dn = q_nope.shape
    r, dr = w_uk.shape[0], q_pe.shape[-1]
    dv = w_uv.shape[-1]
    bs = pool.shape[-2]
    m = table.shape[0]
    if (jax.devices()[0].platform == "tpu" and r % 128 == 0 and dr <= _PE_LANES
            and pool.shape[-1] >= r + _PE_LANES and (m * bs) % _PREFILL_KEYS == 0):
        record_resolved_impl("paged_mla_prefill", "pallas (mosaic)")
        latent = pool[layer, table, 0].reshape(m * bs, pool.shape[-1])
        return mla_prefill_attention(q_nope, q_pe, latent, pos[0], w_uk, w_uv)
    record_resolved_impl("paged_mla_prefill", "xla (tiles)")
    tile_blocks = min(tile_blocks, m)
    span = tile_blocks * bs
    n_tiles = -(-m // tile_blocks)
    table = jnp.pad(table, (0, n_tiles * tile_blocks - m))
    scale = 1.0 / jnp.sqrt(jnp.asarray(dn + dr, jnp.float32))
    dtype = q_nope.dtype

    def tile(i, carry):
        top, total, acc = carry                            # [H, T], [H, T], [H, T, dv]
        blocks = jax.lax.dynamic_slice_in_dim(table, i * tile_blocks, tile_blocks)
        rows = pool[layer, blocks, 0].reshape(span, -1)    # [span, R + dr + pad]
        latent, k_pe = rows[:, :r], rows[:, r:r + dr]
        k_nope = jnp.einsum("sr,rhd->hsd", latent, w_uk,
                            preferred_element_type=jnp.float32).astype(dtype)
        v = jnp.einsum("sr,rhd->hsd", latent, w_uv,
                       preferred_element_type=jnp.float32).astype(dtype)
        s = (jnp.einsum("thd,hsd->hts", q_nope, k_nope, preferred_element_type=jnp.float32)
             + jnp.einsum("thd,sd->hts", q_pe, k_pe.astype(dtype),
                          preferred_element_type=jnp.float32)) * scale
        ok = (i * span + jnp.arange(span))[None] <= pos[:, None]   # [T, span]
        s = jnp.where(ok[None], s, _MASKED)
        new_top = jnp.maximum(top, s.max(axis=-1))
        p = jnp.where(ok[None], jnp.exp(s - new_top[..., None]), 0.0)
        alpha = jnp.exp(top - new_top)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hts,hsd->htd", p.astype(dtype), v, preferred_element_type=jnp.float32)
        return new_top, total * alpha + p.sum(axis=-1), acc

    init = (jnp.full((h, t), _MASKED, jnp.float32),
            jnp.zeros((h, t), jnp.float32),
            jnp.zeros((h, t, dv), jnp.float32))
    _, total, acc = jax.lax.fori_loop(
        0, jnp.minimum(pos[-1] // span + 1, n_tiles), tile, init)
    o = acc / jnp.maximum(total, 1e-37)[..., None]
    return o.transpose(1, 0, 2).astype(dtype)


def paged_masked_attention(
    q: jnp.ndarray,            # [T, KV, G, D] queries of ONE row's chunk
    k_pool: jnp.ndarray,       # [L, N, KV, bs, D]
    v_pool: jnp.ndarray,
    table: jnp.ndarray,        # [M] int32 the row's block table
    pos: jnp.ndarray,          # [T] int32 query positions, ascending
    keep: jnp.ndarray,         # [KV, T, M] bool selected blocks per query
    layer=0,
    tile_blocks: int = 8,
) -> jnp.ndarray:
    """Attention of a chunk's queries over the row's table, ``tile_blocks``
    blocks at a time with a running softmax: query ``t`` attends the keys at
    or before ``pos[t]`` of the blocks ``keep[:, t]`` marks. The walk stops at
    the tile that holds ``pos[-1]``: its cost follows the context, not the
    table's width. Returns [T, KV, G, D]."""
    t, kv, g, d = q.shape
    bs = k_pool.shape[-2]
    m = table.shape[0]
    tile_blocks = min(tile_blocks, m)
    span = tile_blocks * bs
    n_tiles = -(-m // tile_blocks)
    table = jnp.pad(table, (0, n_tiles * tile_blocks - m))
    keep = jnp.pad(keep, ((0, 0), (0, 0), (0, n_tiles * tile_blocks - m)))
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    head = jnp.arange(kv)[None, :]

    def tile(i, carry):
        top, total, acc = carry                            # [KV, G, T], ..., [KV, G, T, D]
        blocks = jax.lax.dynamic_slice_in_dim(table, i * tile_blocks, tile_blocks)
        kc = k_pool[layer, blocks[:, None], head]          # [tb, KV, bs, D]
        vc = v_pool[layer, blocks[:, None], head]
        kc = kc.transpose(1, 0, 2, 3).reshape(kv, span, d)
        vc = vc.transpose(1, 0, 2, 3).reshape(kv, span, d)
        s = jnp.einsum("tkgd,ksd->kgts", q, kc,
                       preferred_element_type=jnp.float32) * scale
        key_pos = i * span + jnp.arange(span)
        ok = jax.lax.dynamic_slice_in_dim(keep, i * tile_blocks, tile_blocks, axis=2)
        ok = jnp.repeat(ok, bs, axis=2) & (key_pos[None, None] <= pos[None, :, None])
        s = jnp.where(ok[:, None], s, _MASKED)
        new_top = jnp.maximum(top, s.max(axis=-1))
        p = jnp.where(ok[:, None], jnp.exp(s - new_top[..., None]), 0.0)
        alpha = jnp.exp(top - new_top)
        acc = acc * alpha[..., None] + jnp.einsum(
            "kgts,ksd->kgtd", p.astype(q.dtype), vc,
            preferred_element_type=jnp.float32)
        return new_top, total * alpha + p.sum(axis=-1), acc

    init = (jnp.full((kv, g, t), _MASKED, jnp.float32),
            jnp.zeros((kv, g, t), jnp.float32),
            jnp.zeros((kv, g, t, d), jnp.float32))
    _, total, acc = jax.lax.fori_loop(
        0, jnp.minimum(pos[-1] // span + 1, n_tiles), tile, init)
    o = acc / jnp.maximum(total, 1e-37)[..., None]
    return o.transpose(2, 0, 1, 3).astype(q.dtype)
