"""Kernels: the decode steps' latent attention, as a share of its roofline
over the traced window. The least time is that of the family's
``mla_decode_work`` over the engine's counters ``decode_attended`` and
``decode_rows`` (over the traced part of the window): each attended key's
latent row read once a layer for all the heads, a row's absorbed query read
and its ``u`` written, and every head's two products over the keys - the
count is the same whatever implements the attention. The time is the device
time of the decode program's operations under the scope
``deepseek_v3/mla_decode`` (``benchmark/scopes.py``): the row written into
the pool and the attention over it. A program without the counters, or a run
whose trace was not kept, reports nothing; a kept trace without an operation
of the scope fails the run."""

import os

from benchmark import scopes, work
from benchmark.reduce_trace import NoKernelEvent

SCOPE, PROGRAM = "deepseek_v3/mla_decode", "jit(decode_step)"


def read(ctx):
    traced = ctx["traced_stats"]
    path = scopes.kept_path()
    if not traced or not traced.get("decode_rows") or not os.path.exists(path):
        return None
    lo, hi = ctx["trace_window_ns"]
    spent = scopes.scope_seconds(path, lo, hi, (SCOPE,), PROGRAM)
    if spent <= 0:
        raise NoKernelEvent(f"no device operation under {SCOPE!r} in {PROGRAM}")
    flops, nbytes = ctx["cell"]["reference"].mla_decode_work(
        ctx["sizes"], traced["decode_attended"], traced["decode_rows"])
    least, _ = work.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * least / spent
