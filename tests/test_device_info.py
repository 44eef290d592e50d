"""Device introspection (C16 parity: the reference's print_device_info /
get_memory_info, /root/reference/train_gpt2_distributed.py:168-191)."""

import types

import pytest

from gpt_2_distributed_tpu.utils.device_info import (
    device_banner,
    device_info_lines,
    device_memory_lines,
    get_memory_info,
    print_device_info,
)
from gpt_2_distributed_tpu.utils.flops import device_peak_flops


def test_device_info_lines_content():
    lines = device_info_lines()
    text = "\n".join(lines)
    assert "platform: cpu" in text
    assert "global device count: 8" in text  # the virtual test mesh
    assert "process: 0 of 1" in text
    # one line per local device
    assert sum(1 for ln in lines if ln.startswith("  device ")) == 8


def test_print_device_info(capsys):
    print_device_info()
    out = capsys.readouterr().out
    assert "device kind" in out


def test_get_memory_info_shape():
    alloc, limit = get_memory_info()
    assert alloc >= 0.0 and limit >= 0.0  # CPU backend reports zeros


def test_device_banner_names_platform_kind_count():
    assert device_banner() == "device: platform=cpu kind='cpu' count=8"


def test_device_memory_lines_cover_every_local_device():
    lines = device_memory_lines()
    assert [ln.split(":")[0] for ln in lines] == [
        f"  device {i}" for i in range(8)
    ]
    assert device_info_lines()[-8:] == lines


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v5", 459e12),
    ("TPU v4", 275e12), ("TPU v6 lite", 918e12),
])
def test_peak_flops_listed_kind(kind, peak):
    assert device_peak_flops(_device("tpu", kind)) == peak


@pytest.mark.parametrize("kind", ["TPU v5x", "TPU v5 lite pod", "TPU v9", ""])
def test_peak_flops_unlisted_tpu_kind_raises(kind):
    """A prefix match used to hand "TPU v5x" the 459 TF/s of "TPU v5"."""
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        device_peak_flops(_device("tpu", kind))


def test_peak_flops_off_tpu_is_none():
    assert device_peak_flops(_device("cpu", "cpu")) is None
    assert device_peak_flops(_device("gpu", "NVIDIA H100")) is None
    assert device_peak_flops() is None   # this suite runs on the CPU
