"""Operations and bytes of a kernel from its shapes: what the algorithm
needs, never what an implementation happens to do. Recomputed operations do
not count. What is a kernel's and no model's lives here; a model's own
arithmetic (operations per token, which layers hold a KV cache, heads and
head width) is its family's, in ``reference/<family>.py``.
"""

from __future__ import annotations


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
                         head_dim: int, kv_heads: int, bytes_per_el: int = 2):
    """(flops, bytes) of one layer's decode attention: ``attended_tokens``
    is the sum over rows of the keys each row attends to. Every query head
    takes its two products over them; K and V of every attended position
    are read once, in ``kv_heads`` heads (``heads`` of them unless the
    model groups its queries); q in and o out per row."""
    per_head = attended_tokens * head_dim
    moved = 2.0 * per_head * kv_heads + 2.0 * rows * heads * head_dim
    return 4.0 * per_head * heads, moved * bytes_per_el


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peaks["flops_per_s_bf16"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
