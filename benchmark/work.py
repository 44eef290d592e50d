"""Operations and bytes from shapes: what the algorithm needs, never what an
implementation happens to do. Recomputed operations do not count.

``sizes`` is a configuration file's object (``n_embd``, ``n_layer``,
``n_head``, ``vocab_size``).
"""

from __future__ import annotations


def matmul_params(sizes: dict) -> int:
    """Parameters that take part in matmuls: per block qkv (3C^2), attention
    projection (C^2) and MLP (8C^2), plus the tied head's [C, V] projection.
    Embedding lookups are gathers, not operations."""
    c, l, v = sizes["n_embd"], sizes["n_layer"], sizes["vocab_size"]
    return l * 12 * c * c + c * v


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    """Forward and backward: 6 per matmul parameter, and the attention
    score and value matmuls (2 * 2*C*T forward, twice that backward) in
    each layer, counted over the full square as the usual convention does."""
    c, l = sizes["n_embd"], sizes["n_layer"]
    return 6.0 * matmul_params(sizes) + 12.0 * l * c * seq_len


def forward_flops_per_token(sizes: dict, context: float) -> float:
    """One forward pass of one token that attends over ``context`` keys."""
    c, l = sizes["n_embd"], sizes["n_layer"]
    return 2.0 * matmul_params(sizes) + 4.0 * l * c * context


def flash_attention_work(batch: int, heads: int, seq: int, head_dim: int,
                         backward: bool, bytes_per_el: int = 2):
    """(flops, bytes) of one causal attention call over [B, H, T, D].
    Forward: q.k^T and p.v over the causal half. Backward: the four
    products dv, dp, dq, dk (the recomputed scores do not count). Bytes:
    q, k, v, o once forward; q, k, v, o, do read and dq, dk, dv written
    backward."""
    pair = 2.0 * batch * heads * seq * seq * head_dim / 2.0   # one causal matmul
    tensor = batch * heads * seq * head_dim * bytes_per_el
    if backward:
        return 4.0 * pair, 8.0 * tensor
    return 2.0 * pair, 4.0 * tensor


def paged_attention_work(attended_tokens: float, rows: float, heads: int,
                         head_dim: int, bytes_per_el: int = 2):
    """(flops, bytes) of one layer's decode attention: ``attended_tokens``
    is the sum over rows of the keys each row attends to. K and V of every
    attended position are read once; q in and o out per row."""
    kv = attended_tokens * heads * head_dim
    return 4.0 * kv, (2.0 * kv + 2.0 * rows * heads * head_dim) * bytes_per_el


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peaks["flops_per_s_bf16"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
