"""Fused Pallas layer-epilogue kernels: LN+residual+dropout and bias+GELU+dropout.

Round-5 roofline work (PERF_ANALYSIS.md §9) showed every matmul shape this
model runs sustains 187-196 TF/s in isolation while the whole step sits at
~50% MFU — the missing ~15 points to the 68% isolated-parts bound live
*between* the matmuls: layernorm, residual adds, dropout and the GELU are
bandwidth passes that XLA fuses only partially, so each block makes several
round trips over the [B, T, C] (and worse, [B, T, 4C]) activations. This
module collapses those passes into single Pallas kernels:

* ``fused_ln_residual_dropout`` — ``r = x + dropout(o); y = LN(r)`` in one
  read of (x, o) and one write of (r, y). This is the junction between the
  attention sublayer and the MLP sublayer (proj-dropout + residual + ln2).
* ``fused_residual_dropout`` — ``r = x + dropout(o)`` for the block-closing
  residual (the next LN belongs to the *next* block across the scan
  boundary, so it cannot be fused in).
* ``fused_bias_gelu_dropout`` — ``out = dropout(gelu(h + b))`` over the
  [*, 4C] MLP activation, the single largest between-matmul tensor.

Each op is a ``jax.custom_vjp`` whose backward *recomputes* the cheap
intermediates (rhat from the saved per-row mean/rstd; the GELU tanh from the
saved matmul output) instead of materializing them in the forward, and
regenerates dropout masks by rehashing the same absolute (row, col)
coordinates through ``ops.spmd.dropout_hash_bits`` — the counter-hash scheme
proven in ``ops/flash_attention.py`` — so masks never touch HBM in either
direction. Per-op streams are separated by a small integer ``salt`` in the
head coordinate of the shared hash.

Numerics: LN statistics and the GELU run in fp32 regardless of compute dtype,
exactly mirroring ``ops.layers.layer_norm`` (torch-autocast semantics) — fp32
inputs reproduce the unfused forward bit-for-bit, and gradients agree to
autodiff round-off (~1e-7 relative; the backward uses the standard analytic
LN gradient rather than replaying XLA's autodiff graph). The dropout *stream*
differs from ``ops.layers.hash_random_bits`` (different coordinate mixing),
which is within the dropout contract — determinism holds per seed per
implementation, the same stance ``flash_attention`` takes vs dense attention.

SPMD: like the flash kernel, Mosaic custom calls cannot be GSPMD-partitioned,
so under an active multi-device mesh (``parallel.mesh.activate_mesh``) the
entry points wrap the kernel in shard_map over the batch-like axes
(rows are embarrassingly parallel; each shard mixes its linear index into the
dropout seed). Meshes that shard the sequence ('sp') or feature (tensor-
parallel) dims — which these row-local kernels cannot honor — fall back to
the unfused reference path, degraded-not-wrong. Shapes whose flattened row
count or feature width don't tile (e.g. the 1.5B C=1600, 1600 % 128 != 0, or
decode's T=1 rows) take the same fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from gpt_2_distributed_tpu.ops.activations import gelu_tanh
from gpt_2_distributed_tpu.ops.layers import dropout as unfused_dropout
from gpt_2_distributed_tpu.ops.layers import layer_norm
from gpt_2_distributed_tpu.ops.spmd import (
    BATCH_AXIS_NAMES,
    HEAD_AXIS_NAMES,
    dividing_axes,
    dropout_hash_bits,
    record_fused_fallback,
)

# Per-op dropout stream salts, mixed in as the hash's head coordinate so the
# three fused sites (and flash attention, which hashes real head indices but
# a different seed) never share bits within one layer application.
SALT_LN_RESID = 1
SALT_RESID = 2
SALT_GELU = 3

# tanh-GELU constants (ops/activations.py): sqrt(2/pi) and the cubic coeff.
_GELU_C0 = 0.7978845608028654
_GELU_A = 0.044715

# Cap on rows*cols elements per block: several [bn, c] operands + fp32 temps
# must fit VMEM alongside double buffering. 512K elements = 2 MB bf16 / 4 MB
# fp32 per operand — comfortable within 64 MB VMEM for <= 6 operands.
_MAX_BLOCK_ELEMS = 512 * 1024


def fold_seed(rng: jax.Array) -> jnp.ndarray:
    """Fold a jax PRNG key down to the [1] int32 kernel seed (flash idiom)."""
    return jax.random.randint(rng, (1,), 0, jnp.iinfo(jnp.int32).max, jnp.int32)


def _threshold(rate: float) -> jnp.ndarray:
    return jnp.uint32(int(rate * (2**32)))


def _tile_bits(seed, salt: int, row_off, col_off, shape):
    """uint32 bits for one [rows, cols] tile of the salted epilogue stream.

    [rows, 1] x [1, cols] broadcasted iotas (not full tiles) keep the hash's
    pre-finalizer mixing at vector width — see flash_attention._dropout_bits.
    Coordinates are absolute, so the backward (and any blocking) regenerates
    the forward's exact mask by construction."""
    row = jnp.asarray(row_off).astype(jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, (shape[0], 1), 0
    )
    col = jnp.asarray(col_off).astype(jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, (1, shape[1]), 1
    )
    return dropout_hash_bits(seed, jnp.uint32(0), jnp.uint32(salt), row, col)


def epilogue_dropout_mask(
    seed: jnp.ndarray, salt: int, shape: tuple[int, int], rate: float
) -> jnp.ndarray:
    """The exact keep-mask a fused kernel applies, regenerated at full width.

    Exposed so tests (and the pure-JAX residual backward below) can
    reconstruct the fused ops' dropout decisions outside the kernel: the
    kernels hash absolute coordinates, so a full-[n, c] rehash with offsets
    (0, 0) reproduces every block's bits."""
    seed = jnp.asarray(seed).reshape(-1)[0]
    return _tile_bits(seed, salt, 0, 0, shape) >= _threshold(rate)


def _pick_block_rows(n: int, c: int, interpret: bool) -> int | None:
    """Largest viable row-block size for a [n, c] kernel, or None when the
    shape can't tile (callers fall back to the unfused path).

    On real TPUs the lane dim must be a multiple of 128 (Mosaic tiling) and
    row blocks a multiple of the fp32 sublane count (8); interpret mode has
    no such constraints, so CPU tests can run tiny shapes."""
    if not interpret and c % 128 != 0:
        return None
    cands = (1024, 512, 256, 128, 64, 32, 16, 8)
    if interpret:
        cands = cands + (4, 2, 1)
    for bn in cands:
        if bn <= n and n % bn == 0 and bn * c <= _MAX_BLOCK_ELEMS:
            return bn
    return None


def _ambient_mesh():
    """The framework's active mesh (``parallel.mesh.activate_mesh``), or None
    for no mesh / size-1 — same first-party discovery as flash attention."""
    from gpt_2_distributed_tpu.parallel.mesh import active_mesh

    m = active_mesh()
    return None if (m is None or m.size == 1) else m


def _mesh_axes(batch_dim: int):
    """(mesh, batch_axes) for sharding rows, or (mesh, None) = must fall back.

    These kernels are row-local over the flattened [N, C] view: a mesh that
    shards the sequence ('sp') or the feature dim (tensor-parallel axes)
    would either break the per-row LN reduction or force shard_map to
    re-gather what GSPMD deliberately sharded — fall back to the unfused XLA
    path there (degraded-not-wrong). A multi-device mesh whose batch-like
    axes don't divide the batch dim also falls back: the operands may be
    sharded, and an unwrapped Mosaic call would fail to partition.

    Callers wrap the kernel in ``jax.shard_map(..., check_vma=False)``: the
    replication structure is plain batch splitting, and ``_shard_seed``
    makes the dropout seed differ per shard on purpose."""
    mesh = _ambient_mesh()
    if mesh is None:
        return None, ()
    for a in mesh.axis_names:
        if mesh.shape[a] > 1 and (a in HEAD_AXIS_NAMES or a == "sp"):
            return mesh, None
    b_axes = dividing_axes(mesh, BATCH_AXIS_NAMES, batch_dim)
    if not b_axes:
        return mesh, None
    return mesh, b_axes


def _shard_seed(seed, mesh, b_axes, rate: float):
    """Distinct dropout stream per shard: kernels hash LOCAL row coordinates,
    identical on every shard — mix the linear shard index into the seed
    (flash attention's scheme)."""
    if rate <= 0.0:
        return seed
    idx = jnp.uint32(0)
    for a in b_axes:
        idx = idx * jnp.uint32(mesh.shape[a]) + jax.lax.axis_index(a).astype(
            jnp.uint32
        )
    return (seed.astype(jnp.uint32) ^ (idx * jnp.uint32(0x9E3779B1))).astype(
        jnp.int32
    )


def _resolve(rate, rng, deterministic, interpret):
    """(effective_rate, seed, interpret) shared by the three entry points."""
    rate = float(rate) if (not deterministic and rng is not None) else 0.0
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    seed = fold_seed(rng) if rate > 0.0 else jnp.zeros((1,), jnp.int32)
    return rate, seed, interpret


# ---------------------------------------------------------------------------
# Kernel 1: r = x + dropout(o); y = LN(r)  (attention->MLP junction)
# ---------------------------------------------------------------------------


def _ln_res_fwd_kernel(
    seed_ref,   # scalar prefetch: [1] int32
    x_ref,      # [bn, c] compute dtype
    o_ref,      # [bn, c]
    scale_ref,  # [1, c] param dtype
    bias_ref,   # [1, c]
    r_ref,      # [bn, c] out: residual stream
    y_ref,      # [bn, c] out: LN(r)
    mean_ref,   # [bn, 1] f32 out: saved for backward
    rstd_ref,   # [bn, 1] f32 out
    *,
    block_rows: int,
    rate: float,
    eps: float,
    salt: int,
):
    i = pl.program_id(0)
    o = o_ref[...]
    if rate > 0.0:
        bits = _tile_bits(seed_ref[0], salt, i * block_rows, 0, o.shape)
        o = jnp.where(bits >= _threshold(rate), o / (1.0 - rate), 0.0).astype(
            o.dtype
        )
    r = x_ref[...] + o
    r_ref[...] = r
    # fp32 statistics exactly as ops.layers.layer_norm computes them.
    r32 = r.astype(jnp.float32)
    mean = jnp.mean(r32, axis=-1, keepdims=True)
    cent = r32 - mean
    var = jnp.mean(jnp.square(cent), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    mean_ref[...] = mean
    rstd_ref[...] = rstd
    y = cent * rstd
    y = y * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _ln_res_bwd_kernel(
    seed_ref,    # scalar prefetch: [1] int32
    r_ref,       # [bn, c] saved residual
    mean_ref,    # [bn, 1] f32
    rstd_ref,    # [bn, 1] f32
    scale_ref,   # [1, c]
    dr_in_ref,   # [bn, c] cotangent w.r.t. the r output
    dy_ref,      # [bn, c] cotangent w.r.t. the y output
    dx_ref,      # [bn, c] out
    do_ref,      # [bn, c] out
    dscale_ref,  # [1, c] f32 accumulator (revisited across grid steps)
    dbias_ref,   # [1, c] f32 accumulator
    *,
    block_rows: int,
    rate: float,
    salt: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    rstd = rstd_ref[...]
    rhat = (r_ref[...].astype(jnp.float32) - mean_ref[...]) * rstd
    dy = dy_ref[...].astype(jnp.float32)
    dscale_ref[...] += jnp.sum(dy * rhat, axis=0, keepdims=True)
    dbias_ref[...] += jnp.sum(dy, axis=0, keepdims=True)
    # Standard analytic LN input gradient:
    #   dr_ln = rstd * (dxhat - mean_C(dxhat) - rhat * mean_C(dxhat * rhat))
    dxhat = dy * scale_ref[...].astype(jnp.float32)
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * rhat, axis=-1, keepdims=True)
    dr_tot = dr_in_ref[...].astype(jnp.float32) + rstd * (dxhat - m1 - rhat * m2)
    dx_ref[...] = dr_tot.astype(dx_ref.dtype)
    if rate > 0.0:
        bits = _tile_bits(seed_ref[0], salt, i * block_rows, 0, dr_tot.shape)
        do = jnp.where(bits >= _threshold(rate), dr_tot / (1.0 - rate), 0.0)
    else:
        do = dr_tot
    do_ref[...] = do.astype(do_ref.dtype)


@functools.lru_cache(maxsize=None)
def _build_ln_res_drop(
    rate: float, eps: float, block_rows: int, c: int, salt: int, interpret: bool
):
    """custom-VJP fused (x, o, scale, bias, seed) -> (r, y) over [n, c] rows."""
    bn = block_rows

    def _row_spec():
        return pl.BlockSpec((bn, c), lambda i, *_: (i, 0))

    def _vec_spec():
        return pl.BlockSpec((1, c), lambda i, *_: (0, 0))

    def _stat_spec():
        return pl.BlockSpec((bn, 1), lambda i, *_: (i, 0))

    def _raw_fwd(seed, x, o, scale, bias):
        n = x.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn,),
            in_specs=[_row_spec(), _row_spec(), _vec_spec(), _vec_spec()],
            out_specs=[_row_spec(), _row_spec(), _stat_spec(), _stat_spec()],
        )
        return pl.pallas_call(
            functools.partial(
                _ln_res_fwd_kernel,
                block_rows=bn, rate=rate, eps=eps, salt=salt,
            ),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
            ),
            interpret=interpret,
        )(seed, x, o, scale.reshape(1, c), bias.reshape(1, c))

    @jax.custom_vjp
    def fused(x, o, scale, bias, seed):
        r, y, _, _ = _raw_fwd(seed, x, o, scale, bias)
        return r, y

    def fused_fwd(x, o, scale, bias, seed):
        r, y, mean, rstd = _raw_fwd(seed, x, o, scale, bias)
        # x and o are NOT residuals: dx is the total dr directly, and do is
        # its mask-rescale — both recoverable from (r, mean, rstd) + rehash.
        return (r, y), (r, mean, rstd, scale, bias, seed)

    def fused_bwd(res, cts):
        r, mean, rstd, scale, bias, seed = res
        dr_in, dy = cts
        n = r.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn,),
            in_specs=[
                _row_spec(), _stat_spec(), _stat_spec(), _vec_spec(),
                _row_spec(), _row_spec(),
            ],
            # dscale/dbias are revisited [1, c] accumulators spanning every
            # grid step — the grid must stay "arbitrary" (sequential) so
            # Mosaic keeps them resident instead of flushing per block.
            out_specs=[_row_spec(), _row_spec(), _vec_spec(), _vec_spec()],
        )
        dx, do, dscale, dbias = pl.pallas_call(
            functools.partial(
                _ln_res_bwd_kernel, block_rows=bn, rate=rate, salt=salt,
            ),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(r.shape, r.dtype),
                jax.ShapeDtypeStruct(r.shape, r.dtype),
                jax.ShapeDtypeStruct((1, c), jnp.float32),
                jax.ShapeDtypeStruct((1, c), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
        )(seed, r, mean, rstd, scale.reshape(1, c), dr_in, dy)
        return (
            dx,
            do,
            dscale.reshape(c).astype(scale.dtype),
            dbias.reshape(c).astype(bias.dtype),
            None,
        )

    fused.defvjp(fused_fwd, fused_bwd)
    return fused


# ---------------------------------------------------------------------------
# Kernel 2: r = x + dropout(o)  (block-closing residual; no LN to fuse — the
# next layer norm lives across the scan boundary in the next block)
# ---------------------------------------------------------------------------


def _res_drop_fwd_kernel(
    seed_ref, x_ref, o_ref, r_ref, *, block_rows: int, rate: float, salt: int
):
    i = pl.program_id(0)
    o = o_ref[...]
    bits = _tile_bits(seed_ref[0], salt, i * block_rows, 0, o.shape)
    o = jnp.where(bits >= _threshold(rate), o / (1.0 - rate), 0.0).astype(o.dtype)
    r_ref[...] = x_ref[...] + o


@functools.lru_cache(maxsize=None)
def _build_res_drop(rate: float, block_rows: int, c: int, salt: int, interpret: bool):
    """custom-VJP fused (x, o, seed) -> x + dropout(o) over [n, c] rows.

    Only built for rate > 0 — at rate 0 the op is a bare add and the entry
    point short-circuits to plain ``x + o``. The backward is pure JAX: it is
    elementwise only (dx = dr; do = mask-rescaled dr via the same absolute-
    coordinate rehash), so XLA fuses it into the surrounding backward graph
    without needing a Mosaic kernel."""
    bn = block_rows

    def _raw_fwd(seed, x, o):
        n = x.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec((bn, c), lambda i, *_: (i, 0)),
                pl.BlockSpec((bn, c), lambda i, *_: (i, 0)),
            ],
            out_specs=pl.BlockSpec((bn, c), lambda i, *_: (i, 0)),
        )
        return pl.pallas_call(
            functools.partial(
                _res_drop_fwd_kernel, block_rows=bn, rate=rate, salt=salt,
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
            ),
            interpret=interpret,
        )(seed, x, o)

    @jax.custom_vjp
    def fused(x, o, seed):
        return _raw_fwd(seed, x, o)

    def fused_fwd(x, o, seed):
        return _raw_fwd(seed, x, o), (seed,)

    def fused_bwd(res, dr):
        (seed,) = res
        keep = epilogue_dropout_mask(seed, salt, dr.shape, rate)
        do = jnp.where(keep, dr / (1.0 - rate), 0.0).astype(dr.dtype)
        return dr, do, None

    fused.defvjp(fused_fwd, fused_bwd)
    return fused


# ---------------------------------------------------------------------------
# Kernel 3: out = dropout(gelu(h + b))  (MLP epilogue over the [*, 4C] tensor)
# ---------------------------------------------------------------------------


def _gelu_core(u):
    """tanh-GELU on fp32 ``u``; returns (g, t) with t = tanh(inner) so the
    backward can reuse it."""
    t = jnp.tanh(_GELU_C0 * (u + _GELU_A * u * u * u))
    return 0.5 * u * (1.0 + t), t


def _bias_gelu_fwd_kernel(
    seed_ref, h_ref, b_ref, out_ref, *, block_rows: int, rate: float, salt: int
):
    i = pl.program_id(0)
    u = (h_ref[...] + b_ref[...]).astype(jnp.float32)
    g, _ = _gelu_core(u)
    if rate > 0.0:
        bits = _tile_bits(seed_ref[0], salt, i * block_rows, 0, g.shape)
        g = jnp.where(bits >= _threshold(rate), g / (1.0 - rate), 0.0)
    out_ref[...] = g.astype(out_ref.dtype)


def _bias_gelu_bwd_kernel(
    seed_ref,  # scalar prefetch: [1] int32
    h_ref,     # [bn, f] saved matmul output
    b_ref,     # [1, f]
    dout_ref,  # [bn, f]
    dh_ref,    # [bn, f] out
    db_ref,    # [1, f] f32 accumulator (revisited across grid steps)
    *,
    block_rows: int,
    rate: float,
    salt: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        db_ref[...] = jnp.zeros_like(db_ref)

    u = (h_ref[...] + b_ref[...]).astype(jnp.float32)
    _, t = _gelu_core(u)
    # d/du [0.5*u*(1+tanh(c0*(u + a*u^3)))]
    #   = 0.5*(1+t) + 0.5*u*(1-t^2)*c0*(1+3a*u^2)
    gp = 0.5 * (1.0 + t) + 0.5 * u * (1.0 - t * t) * _GELU_C0 * (
        1.0 + 3.0 * _GELU_A * u * u
    )
    dg = dout_ref[...].astype(jnp.float32)
    if rate > 0.0:
        bits = _tile_bits(seed_ref[0], salt, i * block_rows, 0, dg.shape)
        dg = jnp.where(bits >= _threshold(rate), dg / (1.0 - rate), 0.0)
    du = dg * gp
    dh_ref[...] = du.astype(dh_ref.dtype)
    db_ref[...] += jnp.sum(du, axis=0, keepdims=True)


@functools.lru_cache(maxsize=None)
def _build_bias_gelu_drop(
    rate: float, block_rows: int, f: int, salt: int, interpret: bool
):
    """custom-VJP fused (h, b, seed) -> dropout(gelu(h + b)) over [n, f]."""
    bn = block_rows

    def _row_spec():
        return pl.BlockSpec((bn, f), lambda i, *_: (i, 0))

    def _vec_spec():
        return pl.BlockSpec((1, f), lambda i, *_: (0, 0))

    def _raw_fwd(seed, h, b):
        n = h.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn,),
            in_specs=[_row_spec(), _vec_spec()],
            out_specs=_row_spec(),
        )
        return pl.pallas_call(
            functools.partial(
                _bias_gelu_fwd_kernel, block_rows=bn, rate=rate, salt=salt,
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
            ),
            interpret=interpret,
        )(seed, h, b.reshape(1, f))

    @jax.custom_vjp
    def fused(h, b, seed):
        return _raw_fwd(seed, h, b)

    def fused_fwd(h, b, seed):
        # The only residuals are the kernel's own INPUTS (h is the matmul
        # output XLA already materialized) — u, tanh and the mask are all
        # recomputed in backward.
        return _raw_fwd(seed, h, b), (h, b, seed)

    def fused_bwd(res, dout):
        h, b, seed = res
        n = h.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn,),
            in_specs=[_row_spec(), _vec_spec(), _row_spec()],
            out_specs=[_row_spec(), _vec_spec()],
        )
        dh, db = pl.pallas_call(
            functools.partial(
                _bias_gelu_bwd_kernel, block_rows=bn, rate=rate, salt=salt,
            ),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(h.shape, h.dtype),
                jax.ShapeDtypeStruct((1, f), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
        )(seed, h, b.reshape(1, f), dout)
        return dh, db.reshape(f).astype(b.dtype), None

    fused.defvjp(fused_fwd, fused_bwd)
    return fused


# ---------------------------------------------------------------------------
# Public entry points ([..., C] operands; leading dims flattened to rows)
# ---------------------------------------------------------------------------


def _reference_ln_residual_dropout(x, o, scale, bias, eps, rate, rng):
    o = unfused_dropout(o, rate, rng, deterministic=rate == 0.0)
    r = x + o
    return r, layer_norm(r, scale, bias, eps)


def fused_ln_residual_dropout(
    x: jnp.ndarray,       # [..., C] residual stream
    o: jnp.ndarray,       # [..., C] sublayer output (pre-dropout)
    scale: jnp.ndarray,   # [C]
    bias: jnp.ndarray,    # [C]
    *,
    eps: float = 1e-5,
    rate: float = 0.0,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    interpret: bool | None = None,
    salt: int = SALT_LN_RESID,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``r = x + dropout(o); y = layer_norm(r, scale, bias)`` in one pass.

    Returns ``(r, y)`` — the updated residual stream and the normalized
    input to the next sublayer. Falls back to the unfused ops (identical
    semantics, ``hash_random_bits`` dropout stream) when the shape or the
    active mesh can't host the kernel."""
    rate_eff, seed, interpret = _resolve(rate, rng, deterministic, interpret)
    c = x.shape[-1]
    n = x.size // c
    mesh, b_axes = _mesh_axes(x.shape[0])
    if b_axes is None:
        record_fused_fallback("ln_residual_dropout", "sp/tensor-sharded mesh")
        return _reference_ln_residual_dropout(x, o, scale, bias, eps, rate_eff, rng)
    shards = 1
    for a in b_axes:
        shards *= mesh.shape[a]
    bn = _pick_block_rows(n // shards, c, interpret)
    if bn is None:
        record_fused_fallback("ln_residual_dropout", "shape won't tile")
        return _reference_ln_residual_dropout(x, o, scale, bias, eps, rate_eff, rng)
    fn = _build_ln_res_drop(rate_eff, float(eps), bn, c, salt, interpret)

    def _call(x, o, scale, bias, seed):
        r, y = fn(x.reshape(-1, c), o.reshape(-1, c), scale, bias, seed)
        return r.reshape(x.shape), y.reshape(x.shape)

    if b_axes:
        spec = P(b_axes, *([None] * (x.ndim - 1)))

        def _local(x, o, scale, bias, seed):
            return _call(x, o, scale, bias, _shard_seed(seed, mesh, b_axes, rate_eff))

        return jax.shard_map(
            _local, mesh=mesh,
            in_specs=(spec, spec, P(None), P(None), P(None)),
            out_specs=(spec, spec),
            check_vma=False,
        )(x, o, scale, bias, seed)
    return _call(x, o, scale, bias, seed)


def fused_residual_dropout(
    x: jnp.ndarray,  # [..., C] residual stream
    o: jnp.ndarray,  # [..., C] sublayer output (pre-dropout)
    *,
    rate: float = 0.0,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    interpret: bool | None = None,
    salt: int = SALT_RESID,
) -> jnp.ndarray:
    """``x + dropout(o)`` with the in-kernel counter-hash mask.

    With dropout inactive this is a bare add — returned directly (XLA fuses
    a lone add better than any custom call)."""
    rate_eff, seed, interpret = _resolve(rate, rng, deterministic, interpret)
    if rate_eff == 0.0:
        return x + o
    c = x.shape[-1]
    n = x.size // c
    mesh, b_axes = _mesh_axes(x.shape[0])
    if b_axes is None:
        record_fused_fallback("residual_dropout", "sp/tensor-sharded mesh")
        return x + unfused_dropout(o, rate_eff, rng, deterministic=False)
    shards = 1
    for a in b_axes:
        shards *= mesh.shape[a]
    bn = _pick_block_rows(n // shards, c, interpret)
    if bn is None:
        record_fused_fallback("residual_dropout", "shape won't tile")
        return x + unfused_dropout(o, rate_eff, rng, deterministic=False)
    fn = _build_res_drop(rate_eff, bn, c, salt, interpret)

    def _call(x, o, seed):
        return fn(x.reshape(-1, c), o.reshape(-1, c), seed).reshape(x.shape)

    if b_axes:
        spec = P(b_axes, *([None] * (x.ndim - 1)))

        def _local(x, o, seed):
            return _call(x, o, _shard_seed(seed, mesh, b_axes, rate_eff))

        return jax.shard_map(
            _local, mesh=mesh,
            in_specs=(spec, spec, P(None)),
            out_specs=spec,
            check_vma=False,
        )(x, o, seed)
    return _call(x, o, seed)


def _reference_bias_gelu_dropout(h, b, rate, rng):
    y = gelu_tanh(h + b)
    return unfused_dropout(y, rate, rng, deterministic=rate == 0.0)


def fused_bias_gelu_dropout(
    h: jnp.ndarray,  # [..., F] matmul output (no bias)
    b: jnp.ndarray,  # [F] bias, compute dtype
    *,
    rate: float = 0.0,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    interpret: bool | None = None,
    salt: int = SALT_GELU,
) -> jnp.ndarray:
    """``dropout(gelu_tanh(h + b))`` — the MLP activation epilogue.

    The GELU runs in fp32 inside the kernel (the unfused ``gelu_tanh``
    computes in the input dtype, so bf16 results track rather than match —
    fp32 is bit-compatible). Falls back to the unfused ops when the shape or
    mesh can't host the kernel."""
    rate_eff, seed, interpret = _resolve(rate, rng, deterministic, interpret)
    f = h.shape[-1]
    n = h.size // f
    mesh, b_axes = _mesh_axes(h.shape[0])
    if b_axes is None:
        record_fused_fallback("bias_gelu_dropout", "sp/tensor-sharded mesh")
        return _reference_bias_gelu_dropout(h, b, rate_eff, rng)
    shards = 1
    for a in b_axes:
        shards *= mesh.shape[a]
    bn = _pick_block_rows(n // shards, f, interpret)
    if bn is None:
        record_fused_fallback("bias_gelu_dropout", "shape won't tile")
        return _reference_bias_gelu_dropout(h, b, rate_eff, rng)
    fn = _build_bias_gelu_drop(rate_eff, bn, f, salt, interpret)

    def _call(h, b, seed):
        return fn(h.reshape(-1, f), b, seed).reshape(h.shape)

    if b_axes:
        spec = P(b_axes, *([None] * (h.ndim - 1)))

        def _local(h, b, seed):
            return _call(h, b, _shard_seed(seed, mesh, b_axes, rate_eff))

        return jax.shard_map(
            _local, mesh=mesh,
            in_specs=(spec, P(None), P(None)),
            out_specs=spec,
            check_vma=False,
        )(h, b, seed)
    return _call(h, b, seed)
