"""Kernels: the paged-decode attention kernel's share of its roofline over
the traced window. Bytes are the K and V actually attended plus q and o,
from the rows and lengths of each decode dispatch in the window, once per
layer; the kernel is bandwidth-bound. The time is the sum of the kernel
events' device durations; the Pallas call carries no ``name=``, so the
events are found by what the trace does show (looked at by hand, PR 24): the
Mosaic custom call named ``closed_call`` inside the decode step's layer
loop, the serving programs' only Pallas kernel."""

from benchmark import work

KERNEL = ("%closed_call", 'custom_call_target="tpu_custom_call"')


def read(ctx):
    decodes = ctx["decodes"]
    if not decodes:
        return None
    trace, (lo, hi) = ctx["trace"], ctx["trace_window_ns"]
    sizes = ctx["sizes"]
    heads = sizes["n_head"]
    spent, _ = trace.kernel_seconds(KERNEL, lo, hi)
    least = 0.0
    for _, rows, attended in decodes:
        flops, nbytes = work.paged_attention_work(
            attended, rows, heads, sizes["n_embd"] // heads)
        least += sizes["n_layer"] * work.roofline_seconds(
            flops, nbytes, ctx["peaks"])[0]
    return 100.0 * least / spent
