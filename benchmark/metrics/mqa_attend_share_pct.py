"""Kernels: the share of the device's busy time, over the traced window,
that lies inside the family's attention scope - the K/V written into the
paged pools and the grouped-query attention over them at ONE KV head
(``jamba/attend``: ``benchmark/scopes.py``) - prefill and decode programs
alike: two layers of twenty-eight; the decode form gathers every row's whole
block table. A run whose trace was not kept reports nothing; a kept trace
without an operation of the scope fails the run (as ``moe_share_pct``, whose
arithmetic this is)."""

from benchmark.harness import load_module

SCOPES = ("jamba/attend",)


def read(ctx):
    return load_module("metrics", "moe_share_pct").share_of_busy(ctx, SCOPES)
