from gpt_2_distributed_tpu.utils.device_info import (
    device_banner,
    device_info_lines,
    device_memory_lines,
    get_memory_info,
    print_device_info,
)
from gpt_2_distributed_tpu.utils.flops import (
    device_peak_flops,
    flops_per_token,
    mfu,
)

__all__ = [
    "device_banner",
    "device_info_lines",
    "device_memory_lines",
    "device_peak_flops",
    "flops_per_token",
    "get_memory_info",
    "mfu",
    "print_device_info",
]
