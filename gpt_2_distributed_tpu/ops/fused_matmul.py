"""Fused matmul+epilogue Pallas kernels (v2): the matmul and its epilogue in
one pass over the accumulator tile.

PERF_ANALYSIS §9 relocated the remaining ~15 MFU points from "slow matmul
shapes" (disproved — every shape sustains 95-99% of nameplate in isolation)
to the junctions *between* matmuls, where XLA materializes 8k×768-class
activations to HBM at every custom_vjp/remat boundary. The v1 kernels
(ops/fused_layer.py) collapsed the elementwise chains but still hand the
matmul its inputs and outputs through HBM; these v2 kernels fuse the matmul
itself, applying the epilogue to the fp32 accumulator tile *before* it is
written back — the epilogue costs zero extra HBM traffic instead of a full
read+write of the activation. Three fusions cover the block's matmul legs:

* ``matmul_bias_gelu_dropout`` — the MLP fc leg: ``dropout(gelu(x@W + b))``.
  The [*, 4C] GELU input never round-trips; the forward additionally writes
  ``u = x@W + b`` as a backward residual (one extra write, vs. the unfused
  path's write-u + read-u + write-y).
* ``matmul_bias_residual_dropout`` — the attn-proj and MLP-proj legs:
  ``resid + dropout(x@W + b)``, folding the residual add that is otherwise a
  separate bandwidth pass. No extra residual tensor is saved: the dropout
  mask regenerates from (seed, coordinates) alone.
* ``matmul_bias`` — the qkv leg: ``x@W + b`` with fp32 accumulation.

Kernels run a 128×128-class MXU-aligned tiled grid with the contraction dim
innermost and an fp32 VMEM scratch accumulator (bf16 I/O, fp32 accumulate —
`preferred_element_type` on the MXU dot). Each op is a ``jax.custom_vjp``
whose backward runs dgrad (dx = dy@Wᵀ) and wgrad (dW = xᵀ@dy, db = Σdy)
through the same tiled-kernel family, *recomputing* the GELU derivative and
the dropout mask in-kernel: masks hash absolute output coordinates through
``ops.spmd.dropout_hash_bits`` with per-site salts (4/5/6 — disjoint from
fused_layer's 1/2/3), so they are tiling-invariant and reconstructable
outside the kernel (``fused_layer.epilogue_dropout_mask``) for parity tests.

Numerics: accumulation, bias add, GELU, dropout scaling and the residual add
all run in fp32 inside the kernel with a single cast on write-back. fp32
inputs agree with the unfused composition to matmul-reassociation round-off
(~1e-7 relative); bf16 tracks (the fused path is the *more* accurate one).

SPMD mirrors fused_layer: under an active data/fsdp mesh the entry points
shard_map over the batch-like axes (rows are embarrassingly parallel; each
shard mixes its linear index into the dropout seed); weights ride in
replicated (`P(None)`) — the same per-layer all-gather FSDP performs for any
matmul, and shard_map's transpose psums the weight cotangents back. Meshes
that shard 'sp' or a tensor axis, and shapes that won't tile (the 1.5B
C=1600 preset, 1600 % 128 != 0; decode's T=1 rows on real TPUs), fall back
to the unfused XLA composition — degraded-not-wrong, and no longer silent:
every fallback records through ``ops.spmd.record_fused_fallback`` (warn-once
+ the `fused_fallback` metric).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from gpt_2_distributed_tpu.ops.activations import gelu_tanh
from gpt_2_distributed_tpu.ops.fused_layer import (
    _gelu_core,
    _GELU_A,
    _GELU_C0,
    _mesh_axes,
    _resolve,
    _shard_seed,
    _threshold,
    _tile_bits,
)
from gpt_2_distributed_tpu.ops.layers import dropout as unfused_dropout
from gpt_2_distributed_tpu.ops.spmd import record_fused_fallback

# Per-site dropout-stream salts (hash head coordinate). fused_layer owns
# 1/2/3; flash attention hashes real head indices under a different seed.
SALT_MM_GELU = 4       # MLP fc leg activation dropout
SALT_MM_ATTN_PROJ = 5  # attention proj-leg residual dropout
SALT_MM_MLP_PROJ = 6   # MLP proj-leg residual dropout


# ---------------------------------------------------------------------------
# Tile planning
# ---------------------------------------------------------------------------

def _pick_dim(dim: int, cands: tuple[int, ...], interpret: bool) -> int | None:
    if interpret:
        cands = cands + (64, 32, 16, 8, 4, 2, 1)
    for b in cands:
        if b <= dim and dim % b == 0:
            return b
    return None


def plan_tiles(n: int, k: int, m: int, interpret: bool) -> tuple[int, int, int] | None:
    """(bm, bk, bn) row/contraction/column block sizes for an [n,k]@[k,m]
    matmul — one plan serves the forward and both backward kernels (their
    grids permute the same three block sizes). None = the shape can't tile;
    callers fall back to the unfused path.

    On real TPUs both matrix-lane dims (k for x, m for w and the output)
    must be multiples of 128 (Mosaic tiling) — the 1.5B preset's C=1600
    fails this and falls back. Rows need only divide by a sublane-friendly
    block. Interpret mode has no hardware constraints, so CPU tests can run
    tiny shapes and exercise multi-step grids."""
    if not interpret and (k % 128 != 0 or m % 128 != 0):
        return None
    bm = _pick_dim(n, (256, 128, 64, 32, 16, 8), interpret)
    bk = _pick_dim(k, (512, 256, 128), interpret)
    bn = _pick_dim(m, (256, 128), interpret)
    if bm is None or bk is None or bn is None:
        return None
    # Worst case 256*512 + 512*256 + 2*256*256 fp32 elements ≈ 1.5 MB VMEM
    # per operand set — comfortably inside fused_layer._MAX_BLOCK_ELEMS-class
    # budgets, so no dynamic shrinking is needed.
    return bm, bk, bn


def _gelu_grad(u):
    """d/du of the tanh-GELU, fp32 — matches fused_layer's backward exactly."""
    _, t = _gelu_core(u)
    return 0.5 * (1.0 + t) + 0.5 * u * (1.0 - t * t) * _GELU_C0 * (
        1.0 + 3.0 * _GELU_A * u * u
    )


def _mask_scale(x, seed, salt: int, rate: float, row_off, col_off):
    """Apply the salted keep-mask (absolute coordinates) with 1/(1-p) scaling
    to an fp32 tile. Identity at rate 0."""
    if rate <= 0.0:
        return x
    bits = _tile_bits(seed, salt, row_off, col_off, x.shape)
    return jnp.where(bits >= _threshold(rate), x / (1.0 - rate), 0.0)


# ---------------------------------------------------------------------------
# Forward kernels: grid (n/bm, m/bn, k/bk), contraction innermost, fp32
# accumulator in VMEM scratch, epilogue on the last contraction step.
# ---------------------------------------------------------------------------

def _acc_step(x_ref, w_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _mm_bias_fwd_kernel(seed_ref, x_ref, w_ref, b_ref, y_ref, acc_ref):
    _acc_step(x_ref, w_ref, acc_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        y_ref[...] = (acc_ref[...] + b_ref[...].astype(jnp.float32)).astype(
            y_ref.dtype
        )


def _mm_gelu_fwd_kernel(
    seed_ref, x_ref, w_ref, b_ref, y_ref, u_ref, acc_ref, *,
    bm: int, bn: int, rate: float, salt: int,
):
    _acc_step(x_ref, w_ref, acc_ref)
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        u = acc_ref[...] + b_ref[...].astype(jnp.float32)
        u_ref[...] = u.astype(u_ref.dtype)  # backward residual (one write)
        g, _ = _gelu_core(u)
        g = _mask_scale(g, seed_ref[0], salt, rate, i * bm, j * bn)
        y_ref[...] = g.astype(y_ref.dtype)


def _mm_resid_fwd_kernel(
    seed_ref, x_ref, w_ref, b_ref, r_ref, y_ref, acc_ref, *,
    bm: int, bn: int, rate: float, salt: int,
):
    _acc_step(x_ref, w_ref, acc_ref)
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        u = acc_ref[...] + b_ref[...].astype(jnp.float32)
        u = _mask_scale(u, seed_ref[0], salt, rate, i * bm, j * bn)
        y_ref[...] = (r_ref[...].astype(jnp.float32) + u).astype(y_ref.dtype)


# ---------------------------------------------------------------------------
# dgrad kernels: dx[n,k] = du[n,m] @ w[k,m]ᵀ; grid (n/bm, k/bk, m/bn) with the
# m-contraction innermost. du (the epilogue-transformed dy) is recomputed
# per tile from dy (+ u for the GELU derivative) — elementwise, cheap next to
# the MXU dot, and it keeps du out of HBM entirely.
# ---------------------------------------------------------------------------

def _dgrad_tile(g_ref, seed_ref, rate, salt, row_off, col_off, u_ref=None):
    du = _mask_scale(
        g_ref[...].astype(jnp.float32), seed_ref[0], salt, rate, row_off, col_off
    )
    if u_ref is not None:
        du = du * _gelu_grad(u_ref[...].astype(jnp.float32))
    return du.astype(g_ref.dtype)


def _mm_dgrad_kernel(
    seed_ref, g_ref, w_ref, dx_ref, acc_ref, *,
    bm: int, bn: int, rate: float, salt: int,
):
    i, q = pl.program_id(0), pl.program_id(2)

    @pl.when(q == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    du = _dgrad_tile(g_ref, seed_ref, rate, salt, i * bm, q * bn)
    acc_ref[...] += jax.lax.dot_general(
        du, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(q == pl.num_programs(2) - 1)
    def _write():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _mm_dgrad_gelu_kernel(
    seed_ref, g_ref, u_ref, w_ref, dx_ref, acc_ref, *,
    bm: int, bn: int, rate: float, salt: int,
):
    i, q = pl.program_id(0), pl.program_id(2)

    @pl.when(q == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    du = _dgrad_tile(g_ref, seed_ref, rate, salt, i * bm, q * bn, u_ref)
    acc_ref[...] += jax.lax.dot_general(
        du, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(q == pl.num_programs(2) - 1)
    def _write():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


# ---------------------------------------------------------------------------
# wgrad kernels: dw[k,m] = x[n,k]ᵀ @ du[n,m], db[m] = Σ_n du; grid
# (m/bn, k/bk, n/bm) — the m-axis OUTERMOST so the revisited db block (0, j)
# is visited consecutively within each j stripe (Mosaic revisited-output
# constraint), with the n-contraction innermost under the dw scratch
# accumulator. du is recomputed per (k-tile, n-tile) visit; db accumulates
# only on the first k-tile (i == 0) so each n-tile contributes once.
# ---------------------------------------------------------------------------

def _wgrad_body(seed_ref, x_ref, g_ref, u_ref, dw_ref, db_ref, acc_ref, *,
                bm: int, bn: int, rate: float, salt: int):
    j, i, q = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(q == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((q == 0) & (i == 0))
    def _init_db():
        db_ref[...] = jnp.zeros_like(db_ref)

    du = _dgrad_tile(g_ref, seed_ref, rate, salt, q * bm, j * bn, u_ref)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], du, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == 0)
    def _accum_db():
        db_ref[...] += jnp.sum(du.astype(jnp.float32), axis=0, keepdims=True)

    @pl.when(q == pl.num_programs(2) - 1)
    def _write():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _mm_wgrad_plain_kernel(seed_ref, x_ref, g_ref, dw_ref, db_ref, acc_ref,
                           **kw):
    _wgrad_body(seed_ref, x_ref, g_ref, None, dw_ref, db_ref, acc_ref, **kw)


def _mm_wgrad_gelu_kernel(seed_ref, x_ref, g_ref, u_ref, dw_ref, db_ref,
                          acc_ref, **kw):
    _wgrad_body(seed_ref, x_ref, g_ref, u_ref, dw_ref, db_ref, acc_ref, **kw)


# ---------------------------------------------------------------------------
# Builders: one custom_vjp per (kind, rate, tile plan, salt, interpret).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _build_matmul(kind: str, rate: float, bm: int, bk: int, bn: int,
                  salt: int, interpret: bool):
    """custom-VJP fused matmul+epilogue over 2-D operands.

    kind: "bias"  -> fused(x, w, b, seed) = x@w + b
          "gelu"  -> fused(x, w, b, seed) = dropout(gelu(x@w + b))
          "resid" -> fused(x, w, b, r, seed) = r + dropout(x@w + b)
    """
    assert kind in ("bias", "gelu", "resid"), kind
    kw = dict(bm=bm, bn=bn, rate=rate, salt=salt)

    def _x_spec():
        return pl.BlockSpec((bm, bk), lambda i, j, kk, *_: (i, kk))

    def _w_spec():
        return pl.BlockSpec((bk, bn), lambda i, j, kk, *_: (kk, j))

    def _b_spec():
        return pl.BlockSpec((1, bn), lambda i, j, kk, *_: (0, j))

    def _y_spec():
        return pl.BlockSpec((bm, bn), lambda i, j, kk, *_: (i, j))

    def _fwd_call(seed, x, w, b, r=None):
        n, k = x.shape
        m = w.shape[1]
        grid = (n // bm, m // bn, k // bk)
        in_specs = [_x_spec(), _w_spec(), _b_spec()]
        operands = [x, w, b.reshape(1, m)]
        if kind == "bias":
            kernel = _mm_bias_fwd_kernel
            out_specs, out_shape = _y_spec(), jax.ShapeDtypeStruct((n, m), x.dtype)
        elif kind == "gelu":
            kernel = functools.partial(_mm_gelu_fwd_kernel, **kw)
            out_specs = [_y_spec(), _y_spec()]
            out_shape = [jax.ShapeDtypeStruct((n, m), x.dtype)] * 2
        else:
            kernel = functools.partial(_mm_resid_fwd_kernel, **kw)
            in_specs.append(_y_spec())
            operands.append(r)
            out_specs, out_shape = _y_spec(), jax.ShapeDtypeStruct((n, m), x.dtype)
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            ),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(seed, *operands)

    def _dgrad_call(seed, g, w, u=None):
        n, m = g.shape
        k = w.shape[0]
        grid = (n // bm, k // bk, m // bn)
        g_spec = pl.BlockSpec((bm, bn), lambda i, j, q, *_: (i, q))
        in_specs = [g_spec]
        operands = [g]
        if u is not None:
            in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, q, *_: (i, q)))
            operands.append(u)
            kernel = functools.partial(_mm_dgrad_gelu_kernel, **kw)
        else:
            kernel = functools.partial(_mm_dgrad_kernel, **kw)
        in_specs.append(pl.BlockSpec((bk, bn), lambda i, j, q, *_: (j, q)))
        operands.append(w)
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=in_specs,
                out_specs=pl.BlockSpec((bm, bk), lambda i, j, q, *_: (i, j)),
                scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((n, k), g.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(seed, *operands)

    def _wgrad_call(seed, x, g, u=None):
        n, k = x.shape
        m = g.shape[1]
        grid = (m // bn, k // bk, n // bm)  # j (m) outermost — see kernel note
        x_spec = pl.BlockSpec((bm, bk), lambda j, i, q, *_: (q, i))
        g_spec = pl.BlockSpec((bm, bn), lambda j, i, q, *_: (q, j))
        in_specs = [x_spec, g_spec]
        operands = [x, g]
        if u is not None:
            in_specs.append(pl.BlockSpec((bm, bn), lambda j, i, q, *_: (q, j)))
            operands.append(u)
            kernel = functools.partial(_mm_wgrad_gelu_kernel, **kw)
        else:
            kernel = functools.partial(_mm_wgrad_plain_kernel, **kw)
        dw, db = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=in_specs,
                out_specs=[
                    pl.BlockSpec((bk, bn), lambda j, i, q, *_: (i, j)),
                    pl.BlockSpec((1, bn), lambda j, i, q, *_: (0, j)),
                ],
                scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((k, m), x.dtype),
                jax.ShapeDtypeStruct((1, m), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(seed, *operands)
        return dw, db

    if kind == "resid":

        @jax.custom_vjp
        def fused(x, w, b, r, seed):
            return _fwd_call(seed, x, w, b, r)

        def fused_fwd(x, w, b, r, seed):
            # No u residual: the mask regenerates from (seed, coords) alone.
            return _fwd_call(seed, x, w, b, r), (x, w, b, seed)

        def fused_bwd(res, g):
            x, w, b, seed = res
            dx = _dgrad_call(seed, g, w)
            dw, db = _wgrad_call(seed, x, g)
            return dx, dw, db.reshape(-1).astype(b.dtype), g, None

    elif kind == "gelu":

        @jax.custom_vjp
        def fused(x, w, b, seed):
            y, _u = _fwd_call(seed, x, w, b)
            return y

        def fused_fwd(x, w, b, seed):
            y, u = _fwd_call(seed, x, w, b)
            return y, (x, w, b, u, seed)

        def fused_bwd(res, g):
            x, w, b, u, seed = res
            dx = _dgrad_call(seed, g, w, u)
            dw, db = _wgrad_call(seed, x, g, u)
            return dx, dw, db.reshape(-1).astype(b.dtype), None

    else:

        @jax.custom_vjp
        def fused(x, w, b, seed):
            return _fwd_call(seed, x, w, b)

        def fused_fwd(x, w, b, seed):
            return _fwd_call(seed, x, w, b), (x, w, b, seed)

        def fused_bwd(res, g):
            x, w, b, seed = res
            dx = _dgrad_call(seed, g, w)
            dw, db = _wgrad_call(seed, x, g)
            return dx, dw, db.reshape(-1).astype(b.dtype), None

    fused.defvjp(fused_fwd, fused_bwd)
    return fused


# ---------------------------------------------------------------------------
# Public entry points ([..., K] activations; leading dims flattened to rows)
# ---------------------------------------------------------------------------


def _reference(kind: str, x, w, b, r, rate: float, rng):
    """The exact unfused composition the model runs without --fused_matmul."""
    y = x @ w + b
    if kind == "bias":
        return y
    if kind == "gelu":
        return unfused_dropout(gelu_tanh(y), rate, rng, deterministic=rate == 0.0)
    return r + unfused_dropout(y, rate, rng, deterministic=rate == 0.0)


def _dispatch(kind: str, x, w, b, r, rate, rng, deterministic, interpret,
              salt: int):
    rate_eff, seed, interpret = _resolve(rate, rng, deterministic, interpret)
    k = x.shape[-1]
    m = w.shape[1]
    n = x.size // k
    mesh, b_axes = _mesh_axes(x.shape[0])
    if b_axes is None:
        record_fused_fallback(f"matmul_{kind}", "sp/tensor-sharded mesh")
        return _reference(kind, x, w, b, r, rate_eff, rng)
    shards = 1
    for a in b_axes:
        shards *= mesh.shape[a]
    plan = plan_tiles(n // shards, k, m, interpret)
    if plan is None:
        record_fused_fallback(f"matmul_{kind}", "shape won't tile")
        return _reference(kind, x, w, b, r, rate_eff, rng)
    bm, bk, bn = plan
    fn = _build_matmul(kind, rate_eff, bm, bk, bn, salt, interpret)
    out_shape = x.shape[:-1] + (m,)

    def _call(x, w, b, r, seed):
        # Shape from the x actually passed in: under shard_map this runs on
        # the SHARD-local view, whose leading dim is 1/shards of the global.
        x2 = x.reshape(-1, k)
        if kind == "resid":
            y = fn(x2, w, b, r.reshape(-1, m), seed)
        else:
            y = fn(x2, w, b, seed)
        return y.reshape(x.shape[:-1] + (m,))

    if b_axes:
        xspec = P(b_axes, *([None] * (x.ndim - 1)))
        wspec = P(*([None] * w.ndim))

        def _local(x, w, b, r, seed):
            return _call(x, w, b, r, _shard_seed(seed, mesh, b_axes, rate_eff))

        if kind == "resid":
            rspec = P(b_axes, *([None] * (r.ndim - 1)))
            return jax.shard_map(
                _local, mesh=mesh,
                in_specs=(xspec, wspec, P(None), rspec, P(None)),
                out_specs=rspec,
                check_vma=False,
            )(x, w, b, r, seed)

        def _local3(x, w, b, seed):
            return _local(x, w, b, None, seed)

        ospec = P(b_axes, *([None] * (len(out_shape) - 1)))
        return jax.shard_map(
            _local3, mesh=mesh,
            in_specs=(xspec, wspec, P(None), P(None)),
            out_specs=ospec,
            check_vma=False,
        )(x, w, b, seed)
    return _call(x, w, b, r, seed)


def matmul_bias(
    x: jnp.ndarray,  # [..., K] activations, compute dtype
    w: jnp.ndarray,  # [K, M]
    b: jnp.ndarray,  # [M]
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``x @ w + b`` through the tiled kernel (fp32 accumulation) — the qkv
    leg, where there is no epilogue to fuse but the fp32-accumulate tiled
    form still beats XLA's default bf16 accumulation and keeps the leg on
    the same custom_vjp machinery as the fused legs."""
    return _dispatch("bias", x, w, b, None, 0.0, None, True, interpret, 0)


def matmul_bias_gelu_dropout(
    x: jnp.ndarray,  # [..., K] post-ln2 activations
    w: jnp.ndarray,  # [K, M] fc weight (M = 4C)
    b: jnp.ndarray,  # [M]
    *,
    rate: float = 0.0,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    interpret: bool | None = None,
    salt: int = SALT_MM_GELU,
) -> jnp.ndarray:
    """``dropout(gelu_tanh(x @ w + b))`` — the MLP fc leg in one kernel.

    The GELU runs in fp32 on the accumulator tile; the [*, 4C] pre-GELU
    tensor is written once (as the backward residual ``u``) instead of the
    unfused path's write + read + write."""
    return _dispatch("gelu", x, w, b, None, rate, rng, deterministic,
                     interpret, salt)


def matmul_bias_residual_dropout(
    x: jnp.ndarray,      # [..., K] sublayer activations
    w: jnp.ndarray,      # [K, M] proj weight
    b: jnp.ndarray,      # [M]
    resid: jnp.ndarray,  # [..., M] residual stream
    *,
    rate: float = 0.0,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    interpret: bool | None = None,
    salt: int = SALT_MM_ATTN_PROJ,
) -> jnp.ndarray:
    """``resid + dropout(x @ w + b)`` — the proj legs (attention proj and MLP
    proj) with the residual add folded into the accumulator write-back. The
    two call sites pass distinct salts (SALT_MM_ATTN_PROJ / SALT_MM_MLP_PROJ)
    so their dropout streams never correlate within a layer application."""
    return _dispatch("resid", x, w, b, resid, rate, rng, deterministic,
                     interpret, salt)
