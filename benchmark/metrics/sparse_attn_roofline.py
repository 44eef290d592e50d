"""Kernels: the decode step's attention over the selected blocks, as a share
of its roofline over the traced window. Bytes are the K and V of the selected
keys plus q and o, once per layer that holds a KV cache, from the engine's
counters over the traced part of the window (``decode_rows``,
``decode_attended``: the keys of the selected blocks) and the family's
``attention_shapes`` (16 query heads share a KV head); the time is the device
time of the decode program's operations under the scope
``sala/sparse_attend`` (``benchmark/scopes.py``). Prefill rows are left out:
a chunk's queries share one read of the keys, so a bound by the row does not
hold for them. A program without the counters, or a run whose trace was not
kept, reports nothing; a kept trace without an operation of the scope fails
the run."""

import os

from benchmark import scopes, work
from benchmark.reduce_trace import NoKernelEvent

SCOPE, PROGRAM = "sala/sparse_attend", "jit(decode_step)"


def read(ctx):
    traced = ctx["traced_stats"]
    path = scopes.kept_path()
    if not traced.get("decode_rows") or not os.path.exists(path):
        return None
    lo, hi = ctx["trace_window_ns"]
    spent = scopes.scope_seconds(path, lo, hi, (SCOPE,), PROGRAM)
    if spent <= 0:
        raise NoKernelEvent(f"no device operation under {SCOPE!r} in {PROGRAM}")
    shapes = ctx["cell"]["reference"].attention_shapes(ctx["sizes"])
    flops, nbytes = work.paged_attention_work(
        traced["decode_attended"], traced["decode_rows"], shapes["heads"],
        shapes["head_dim"], shapes["kv_heads"])
    least = shapes["kv_layers"] * work.roofline_seconds(flops, nbytes, ctx["peaks"])[0]
    return 100.0 * least / spent
