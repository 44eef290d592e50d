"""Shared SPMD helpers for attention kernels running under ``shard_map``.

The flash kernel and ring attention both split work over whatever mesh axes
divide their operand dims: batch over data-like axes, heads over tensor-like
axes (ring additionally owns the sequence dim via the 'sp' axis). The axis
vocabularies and the greedy divisibility scan live here so the two kernels
cannot drift apart.
"""

from __future__ import annotations

import sys

import jax.numpy as jnp
from jax.sharding import Mesh

# Mesh axis names treated as batch-like (data parallel) / head-like (tensor
# parallel) by the attention kernels. Our mesh uses ('data', 'fsdp', 'sp',
# 'tp'); the extra names keep the kernels usable under user-supplied meshes.
BATCH_AXIS_NAMES = ("data", "fsdp", "dp", "batch", "replica")
HEAD_AXIS_NAMES = ("tp", "model", "tensor")


def dividing_axes(mesh: Mesh, names: tuple[str, ...], dim: int) -> tuple[str, ...]:
    """Greedy prefix of mesh axes from ``names`` whose product divides ``dim``.

    Axes that don't divide are dropped — that slice of the mesh executes the
    kernel replicated rather than hitting Mosaic's unpartitionable-custom-call
    error with a sharded operand."""
    axes: list[str] = []
    prod = 1
    for a in mesh.axis_names:
        if a in names and mesh.shape[a] > 1 and dim % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
    return tuple(axes)


# --- fused-path fallback visibility -----------------------------------------
#
# The fused kernels (ops/fused_layer.py, ops/fused_matmul.py) silently degrade
# to their unfused XLA compositions when the active mesh shards an axis they
# can't honor (sp / tensor-parallel) or the shape won't tile (e.g. the 1.5B
# C=1600 preset, decode's T=1 rows). Degraded-not-wrong — but a user
# benchmarking `--fused_matmul all` on such a config would measure nothing.
# Every fallback site records itself here: first occurrence per (site, reason)
# warns once on stderr-visible stdout, and train.py surfaces the running count
# as the `fused_fallback` metric. Counts tick at TRACE time (once per compiled
# shape, not per step) — a nonzero value means "some requested fused path is
# not actually fused", which is the signal that matters.

_FUSED_FALLBACKS: dict[tuple[str, str], int] = {}


def record_fused_fallback(site: str, reason: str) -> None:
    """Note that the fused op at ``site`` degraded to its unfused path."""
    from gpt_2_distributed_tpu.utils.operating_point import warn_once

    _FUSED_FALLBACKS[(site, reason)] = _FUSED_FALLBACKS.get((site, reason), 0) + 1
    warn_once(
        f"fused_fallback:{site}:{reason}",
        f"fused op '{site}' fell back to the unfused path ({reason}); "
        "the requested fusion is not running for this shape/mesh",
    )


def fused_fallback_count() -> int:
    """Total recorded fallbacks (all sites) since process start / last reset."""
    return sum(_FUSED_FALLBACKS.values())


def fused_fallback_events() -> dict[tuple[str, str], int]:
    """Per-(site, reason) fallback counts — for tests and diagnostics."""
    return dict(_FUSED_FALLBACKS)


def reset_fused_fallbacks() -> None:
    _FUSED_FALLBACKS.clear()


# --- resolved-implementation visibility --------------------------------------
#
# Attention picks its implementation at TRACE time from what it can observe:
# the platform (Pallas compiled by Mosaic on a TPU, interpret mode or plain
# XLA elsewhere), the shape, the active mesh. Each choice is right for its
# platform and silent by nature, so a run that was meant for the chip and
# landed on the dense path, the XLA gather or the interpreter would look
# healthy. Every dispatch site says once which way it went; `chip_smoke.py`
# reads these lines and fails a chip run that resolved to anything else.

_RESOLVED_IMPLS: set[tuple[str, str]] = set()


def record_resolved_impl(site: str, impl: str) -> None:
    """Print, once per process and (site, impl), the implementation ``site``
    resolved to. stderr: several CLIs keep stdout for machine-read records."""
    if (site, impl) in _RESOLVED_IMPLS:
        return
    _RESOLVED_IMPLS.add((site, impl))
    print(f"[kernels] {site}: {impl}", file=sys.stderr, flush=True)


def pallas_mode(interpret: bool) -> str:
    """How a Pallas kernel runs: compiled for the chip, or interpreted."""
    return "interpret" if interpret else "mosaic"


def dropout_hash_bits(seed, b, h, row, col):
    """uint32 random bits from a murmur3-finalizer hash of absolute
    (batch, head, row, col) coordinates mixed with ``seed``.

    The ONE dropout stream both attention kernels share: stateless and
    blocking-independent, so the flash kernel's backward regenerates the
    forward's exact mask by construction, and the ring schedule produces the
    same mask regardless of the sp degree. All operands must be uint32
    BEFORE any arithmetic — a stray int32 promotes the expression and turns
    ``>>`` into an arithmetic shift on negative values, silently changing
    the stream."""
    u = jnp.uint32
    x = seed.astype(jnp.uint32) ^ (b * u(0x9E3779B1)) ^ (h * u(0x85EBCA77))
    x = x ^ (row * u(0xC2B2AE3D)) ^ (col * u(0x27D4EB2F))
    x = x ^ (x >> 16)
    x = x * u(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * u(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x
