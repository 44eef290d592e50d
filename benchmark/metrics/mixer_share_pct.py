"""Kernels: the share of the device's busy time, over the traced window,
that lies inside the family's three mixer scopes - block selection, the
attention over the selected blocks, the linear-attention scan and update
(``sala/select``, ``sala/sparse_attend``, ``sala/lightning``:
``benchmark/scopes.py``) - prefill and decode programs alike: whether the
mechanisms are where the time goes, or the matmuls around them. A run whose
trace was not kept reports nothing; a kept trace without an operation of any
of the scopes fails the run."""

import os

from benchmark import scopes
from benchmark.reduce_trace import NoKernelEvent

SCOPES = ("sala/select", "sala/sparse_attend", "sala/lightning")


def read(ctx):
    path = scopes.kept_path()
    if not os.path.exists(path) or not ctx["device"].get("busy_s"):
        return None
    lo, hi = ctx["trace_window_ns"]
    spent = scopes.scope_seconds(path, lo, hi, SCOPES)
    if spent <= 0:
        raise NoKernelEvent(f"no device operation under any of {SCOPES}")
    return 100.0 * spent / ctx["device"]["busy_s"]
