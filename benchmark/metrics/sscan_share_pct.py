"""Kernels: the share of the device's busy time, over the traced window,
that lies inside the family's selective-scan scope - the causal convolution,
the three inner norms, ``dt``, the scan of a prefill chunk or the one-token
update of a decode step, the skip and the gate (``jamba/sscan``:
``benchmark/scopes.py``) - prefill and decode programs alike: whether the
recurrence is where the time goes, or the matmuls around it. The four
projections of a Mamba mixer lie under ``jamba/mamba_proj`` and are not
counted. A run whose trace was not kept reports nothing; a kept trace without
an operation of the scope fails the run (as ``moe_share_pct``, whose
arithmetic this is)."""

from benchmark.harness import load_module

SCOPES = ("jamba/sscan",)


def read(ctx):
    return load_module("metrics", "moe_share_pct").share_of_busy(ctx, SCOPES)
