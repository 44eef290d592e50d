"""Kernels: the paged-decode attention kernel's share of its roofline over
the traced window. Bytes are the K and V actually attended plus q and o,
from the engine's own counters over the traced part of the window
(``decode_rows``, ``decode_attended``), once per layer that holds a KV cache
(the family's ``attention_shapes``); the kernel is bandwidth-bound, so the
least time of the window's dispatches is that of their sum. The time is the
sum of the kernel events' device durations; the Pallas call carries no
``name=``, so the events are found by what the trace does show (looked at by
hand, PR 24): the Mosaic custom call named ``closed_call`` inside the decode
step's layer loop, the serving programs' only Pallas kernel. A program
without the counters reports nothing."""

from benchmark import work

KERNEL = ("%closed_call", 'custom_call_target="tpu_custom_call"')


def read(ctx):
    traced = ctx["traced_stats"]
    if not traced.get("decode_rows"):
        return None
    trace, (lo, hi) = ctx["trace"], ctx["trace_window_ns"]
    shapes = ctx["cell"]["reference"].attention_shapes(ctx["sizes"])
    spent, _ = trace.kernel_seconds(KERNEL, lo, hi)
    flops, nbytes = work.paged_attention_work(
        traced["decode_attended"], traced["decode_rows"], shapes["heads"],
        shapes["head_dim"], shapes["kv_heads"])
    least = shapes["kv_layers"] * work.roofline_seconds(
        flops, nbytes, ctx["peaks"])[0]
    return 100.0 * least / spent
