"""Device introspection: the operator's first debugging tool on a new pod.

TPU-native equivalent of the reference's ``print_device_info`` /
``get_memory_info`` (``/root/reference/train_gpt2_distributed.py:168-191``),
which print CUDA device properties and allocator counters. Here the facts an
operator needs on a TPU-VM are: platform, device kind, global/local device
counts, process topology, per-device HBM limit/usage, coordinates on the ICI
mesh, and the peak-FLOPs figure MFU is measured against.
"""

from __future__ import annotations

import jax

from gpt_2_distributed_tpu.utils.flops import device_peak_flops

GB = 1024**3


def device_banner() -> str:
    """One line naming what JAX runs on — platform, kind, count. The serving
    entry points print it at start: when a TPU fails to initialise JAX warns
    and carries on on the CPU, and rc 0 alone would not show it."""
    d0 = jax.devices()[0]
    return (
        f"device: platform={d0.platform} kind={d0.device_kind!r} "
        f"count={jax.device_count()}"
    )


def device_memory_lines() -> list[str]:
    """One line per LOCAL device: kind, mesh coordinates, HBM in use / limit /
    peak as the allocator reports them now. Printed at start and again once
    state is placed: a sharded run shows its share on every device, and one
    that put everything on device 0 shows that instead."""
    lines = []
    for d in jax.local_devices():
        attrs = [f"  device {d.id}: {d.device_kind}"]
        coords = getattr(d, "coords", None)
        if coords is not None:
            attrs.append(f"coords={tuple(coords)}")
        core = getattr(d, "core_on_chip", None)
        if core is not None:
            attrs.append(f"core={core}")
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        if stats:
            limit = stats.get("bytes_limit", 0)
            in_use = stats.get("bytes_in_use", 0)
            peak_use = stats.get("peak_bytes_in_use", in_use)
            attrs.append(
                f"hbm {in_use/GB:.3f}/{limit/GB:.2f} GB (peak {peak_use/GB:.3f})"
            )
        lines.append(" ".join(attrs))
    return lines


def device_info_lines() -> list[str]:
    """Full device report, one string per line (testable; print separately)."""
    devices = jax.devices()
    d0 = devices[0]
    lines = [
        f"platform: {d0.platform}",
        f"device kind: {d0.device_kind}",
        f"global device count: {jax.device_count()}",
        f"local device count: {jax.local_device_count()}",
        f"process: {jax.process_index()} of {jax.process_count()}",
    ]
    peak = device_peak_flops(d0)
    if peak:
        lines.append(f"peak bf16 FLOP/s per chip: {peak/1e12:.0f}T")
    return lines + device_memory_lines()


def print_device_info() -> None:
    """Parity with the reference's ``print_device_info``
    (``/root/reference/train_gpt2_distributed.py:168-176``)."""
    for line in device_info_lines():
        print(line)


def get_memory_info(device=None) -> tuple[float, float]:
    """(allocated_gb, limit_gb) of one device — the reference returns CUDA
    (allocated, reserved) GB (``train_gpt2_distributed.py:179-191``); XLA
    plans HBM at compile time, so the allocator's bytes_limit is the analogue
    of 'reserved'. Returns (0.0, 0.0) when stats are unavailable (CPU)."""
    if device is None:
        device = jax.local_devices()[0]
    try:
        stats = device.memory_stats() or {}
    except Exception:
        return (0.0, 0.0)
    return (
        stats.get("bytes_in_use", 0) / GB,
        stats.get("bytes_limit", 0) / GB,
    )
