"""Kernels: the prefill chunks' selective scan, as a share of its roofline
over the traced window. The work is the family's ``selective_scan_work`` over
the engine's counter ``sscan_tokens`` (a chunk's real tokens times Mamba
layers, over the traced part of the window) in chunks of the cell's
``prefill_chunk``: the state updates' operations, and the least bytes any
form must move - so whatever implements the scan is read on the same
yardstick, and none can pass 100 %. The time is the device time of the chunk
program's operations under the scope ``jamba/sscan``
(``benchmark/scopes.py``): the convolution, the inner norms, ``dt``, the scan,
the skip and the gate. Decode steps are left out: a one-token update reads and
writes the whole state for one token. A program without the counter, or a run
whose trace was not kept, reports nothing; a kept trace without an operation
of the scope fails the run."""

import os

from benchmark import scopes, work
from benchmark.reduce_trace import NoKernelEvent

SCOPE, PROGRAM = "jamba/sscan", "jit(chunk_prefill)"


def read(ctx):
    traced = ctx["traced_stats"]
    path = scopes.kept_path()
    if not traced or not traced.get("sscan_tokens") or not os.path.exists(path):
        return None
    lo, hi = ctx["trace_window_ns"]
    spent = scopes.scope_seconds(path, lo, hi, (SCOPE,), PROGRAM)
    if spent <= 0:
        raise NoKernelEvent(f"no device operation under {SCOPE!r} in {PROGRAM}")
    cell = ctx["cell"]
    flops, nbytes = cell["reference"].selective_scan_work(
        ctx["sizes"], traced["sscan_tokens"],
        int(cell["config_file"]["serve"]["prefill_chunk"]))
    least, _ = work.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * least / spent
