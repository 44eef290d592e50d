"""Kernels: the share of the device's busy time, over the traced window,
that lies inside the family's four latent-attention scopes - the projections
(the norm, the query and latent, RoPE, the absorbed query), the chunk's
expanded attention over the latent pool with its write, the decode step's
absorbed attention with its write, and the output projection
(``deepseek_v3/mla_project``, ``deepseek_v3/mla_prefill``,
``deepseek_v3/mla_decode``, ``deepseek_v3/mla_out``: ``benchmark/scopes.py``)
- prefill and decode programs alike: whether the latent attention is where
the time goes. A run whose trace was not kept reports nothing; a kept trace
without an operation of any of the scopes fails the run (as
``moe_share_pct``, whose arithmetic this is)."""

from benchmark.harness import load_module

SCOPES = ("deepseek_v3/mla_project", "deepseek_v3/mla_prefill",
          "deepseek_v3/mla_decode", "deepseek_v3/mla_out")


def read(ctx):
    return load_module("metrics", "moe_share_pct").share_of_busy(ctx, SCOPES)
