"""Continuous-batching decode engine over the paged KV cache.

The one-shot path (``models/decode.py::generate_cached``) compiles a whole
(batch, prompt, total) signature and runs it to completion — fine for eval,
wrong for traffic: every request shape recompiles, and a batch finishes at
the speed of its longest member while finished rows burn flops. This engine
is the serving-shaped alternative:

* **Prefill/decode split per request.** Each admitted request runs its
  prompt through a prefill step (whole-prompt by default, jitted per
  prompt-length *bucket*; or fixed-width chunks — see below), samples its
  first token, and lands its K/V in pool blocks. From then on it only ever
  costs one row of the decode step.
* **One decode step, compiled once.** The step's signature is fixed by
  ``ServeConfig`` — ``[max_batch]`` token/position/key rows, the
  ``[num_blocks, ...]`` pools, the ``[max_batch, M]`` block table — so
  admissions and evictions are pure *data* changes. ``tests/test_serving.py``
  asserts ``_cache_size() == 1`` across a full churn of arrivals and exits.
* **Chunked prefill** (``ServeConfig.prefill_chunk > 0``): prompts advance
  one fixed-width chunk per engine step, interleaved with decode steps, so
  a long prompt no longer freezes every in-flight stream's inter-token
  latency. The chunk writes its K/V into the request's pool blocks at
  position granularity and attends over the partially-built table
  (``ops/paged_attention.py::paged_prefill_attention``); the fixed chunk
  width makes it ONE compile regardless of prompt lengths.
* **Prefix caching** (``ServeConfig.prefix_cache``): full prompt blocks are
  hash-consed by token-prefix (``paged_cache.PrefixCache``) with refcounted
  pool blocks, so requests sharing a system prompt skip prefill for the
  cached span — admission retains the cached blocks into the request's
  table and prefill starts at the first uncached position. A prompt ending
  exactly on a cached block boundary copy-on-writes that block (the last
  prompt position must be recomputed for its logits, and the recompute
  scatters into the request's private copy, never the shared block).
* **Admission at step boundaries.** A FIFO queue feeds free slots. Policy
  ``"reserve"`` (default) grants the *worst-case* block need
  (``ceil((P + max_new - 1) / block_size)``) all-or-nothing, so an
  in-flight request can never OOM mid-decode. Policy ``"watermark"``
  grants only what the prompt needs now (keeping ``watermark_blocks``
  free as growth headroom), grows tables lazily each decode step, and on
  pool exhaustion **preempts** the newest-admitted request — its blocks
  are freed and it requeues at the head with its generated tokens as a
  recompute-prefill — instead of head-of-line blocking. The oldest
  request is never preempted, so the engine always makes forward
  progress. Head-of-line order is preserved in both policies: if the
  head doesn't fit, nothing behind it jumps the queue.
* **Eviction on EOS / max-len** releases the request's blocks (shared
  blocks just drop a reference; the prefix cache keeps them) and zeroes
  its block-table row, leaving the slot free for the next admission. Idle
  rows keep flowing through the compiled step with ``length 0`` — the
  paged-attention mask makes them exact no-ops.
* **Streaming**: every sampled token is pushed through the request's
  ``on_token`` callback as soon as the host has read it. A preempted
  request's resume never re-emits: its last sampled token is carried as the
  pending decode input, so TTFT reflects first emission, not re-admission.
* **The decode loop is pipelined one deep.** ``step`` dispatches decode
  step N+1 BEFORE it reads step N's tokens back: the sampled tokens and the
  advanced keys stay on the device and feed the next step there (rows that
  a prefill has just opened are patched in by row, ``_feed_impl``), so the
  read-back, the emit loop, admission and the next step's arguments all run
  while the device computes, and none of the host's time lies between two
  device steps. What does not hang on a token's value is settled at
  dispatch: a row's position advances, and a row whose token in flight is
  its ``max_new_tokens``-th leaves its slot and blocks at once. What does
  hang on it is read first or thrown away: with ``eos_id`` set a row may
  run one step past its EOS, and that step's token is dropped (the position
  it wrote lies in the row's own blocks). A prefill chunk goes the same way:
  it is dispatched behind the step in flight, the next decode step behind
  it, and its first token is read after that step's read-back, so the row
  it opens joins the decode step after (under watermark admission, whose
  growth may preempt the chunk's row, it is settled at once). Whatever needs
  the host to hold every sampled token - preemption, deadline eviction,
  migration, ``decode_keys``, ``clear_prefix_cache``, ``close`` - reads the
  unread step back first (``collect``); the speculative round decides on
  values and never leaves a step unread. ``has_work`` is true while a step
  is unread. The emitted ids are those of an engine that collects after
  every dispatch, greedy or sampled: ``stats["decode_overlapped"]`` of
  ``stats["decode_steps"]`` were dispatched over an unread step.

* **Multi-chip serving** (``ServeConfig.mesh``, e.g. ``"data:4"`` or
  ``"data:2,tp:2"``): the engine builds a data×tp mesh
  (``parallel/mesh.py``) and runs the SAME compiled programs sharded under
  it — the KV pools split their block axis over 'data' and their head axis
  over 'tp', the decode step's ``max_batch`` rows split over 'data', and
  the qkv projections head-shard over 'tp'
  (``parallel.sharding.serve_param_pspecs``). Only reduction-preserving
  dims are sharded (GSPMD partitions them without re-associating any fp32
  sum), so streams stay bit-identical to the single-device engine for any
  mesh shape. The scheduler stays host-side and host-global, but becomes
  shard-aware: each data shard owns ``max_batch/data`` slot rows and
  ``num_blocks/data`` pool blocks (``BlockAllocator`` per-shard free
  lists), admission/watermark/grow/preempt account per shard, and
  prefix-cache hits truncate at the first foreign-shard block.
* **Batched multi-row prefill admission** (``ServeConfig.prefill_batch``):
  in chunked mode, up to ``prefill_batch`` in-progress prefills advance in
  ONE batched chunk dispatch per engine step (row count padded to
  ``prefill_batch`` so the program still compiles once) — single-row
  admission was the step-rate bottleneck once 'data' multiplied the
  concurrent slots.

Exactness contract: with ``attn_impl="xla"`` on CPU, each request's token
stream is bit-identical to ``generate_cached(batch=1, prompt, rng=request
key)`` — greedy AND seeded sampling — for ANY interleaving of other
requests, ANY ``prefill_chunk``, prefix-cache hits, and preemptions. The
decode step mirrors ``decode.decode_step`` op-for-op and the chunked
prefill mirrors the dense prefill op-for-op on the attendable region; rows
are independent in every op, each slot carries its own PRNG chain in the
exact split order of the one-shot scan, preemption saves the chain head
and recompute-prefill restores it without resampling, and cached K/V
blocks hold exactly the bits prefill would have recomputed (K/V at
position i is a pure function of tokens[0..i]). ``tests/test_serving.py``
enforces all of it.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import math
import sys
import time
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from gpt_2_distributed_tpu.config import GPT2Config, ServeConfig
from gpt_2_distributed_tpu.models import decode, gpt2
from gpt_2_distributed_tpu.obs import compile_watch
from gpt_2_distributed_tpu.obs.trace import get_tracer
from gpt_2_distributed_tpu.models.generate import (
    check_generation_args,
    sample_token,
)
from gpt_2_distributed_tpu.ops.layers import layer_norm
from gpt_2_distributed_tpu.ops.paged_attention import (
    paged_attention,
    paged_prefill_attention,
    spec_verify_attention,
)
from gpt_2_distributed_tpu.serving.paged_cache import (
    BlockAllocator,
    PrefixCache,
    as_blocks,
    copy_block,
    draft_serve_view,
    init_pools,
    make_pool_jits,
    pool_bytes,
    scatter_prefill,
    write_chunk,
    write_rows,
)
from gpt_2_distributed_tpu.serving.families import dense_attended, family_of
from gpt_2_distributed_tpu.serving.step_clocks import step_clocks


# Whoever builds the engine's programs has the compile watch first.
compile_watch.install()

# What an engine computes in unless told otherwise, and so what the CLIs'
# loaders (`serve.load_model`) cast a tree to before they hand it over.
DEFAULT_COMPUTE_DTYPE = jnp.bfloat16


def _program(name: str, impl: Callable, **static) -> Callable:
    """``impl`` with its static arguments bound, under a name: a bare
    ``functools.partial`` has none, and its program shows as
    ``jit__unknown`` in profiles and in the compile watch's log lines."""
    fn = functools.partial(impl, **static)
    fn.__name__ = name
    return fn


class _Phase:
    """One boundary of the step, timed once: the tracer's span around it
    and the host clock whose milliseconds go to each of ``keys`` in
    ``ServingEngine.stats`` are entered and left together."""

    __slots__ = ("_stats", "_keys", "_span", "_t0")

    def __init__(self, stats: dict, keys: tuple[str, ...], span):
        self._stats = stats
        self._keys = keys
        self._span = span
        self._t0 = 0.0

    def __enter__(self) -> "_Phase":
        self._span.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc: object) -> bool:
        ms = (time.monotonic() - self._t0) * 1e3
        for key in self._keys:
            self._stats[key] += ms
        return self._span.__exit__(*exc)


class _Unread(NamedTuple):
    """A decode step that was dispatched and whose tokens the host has not
    read yet."""

    tokens: jax.Array      # [B + counters] the sampled rows, then the family's counters
    keys: jax.Array        # [B, 2] the advanced chains - both still on the device
    rows: np.ndarray       # [B] bool - the rows it advanced
    reqs: list             # who held each slot when it was dispatched
    last: np.ndarray       # [B] bool - rows whose token in it is their
                           # max_new_tokens-th: they left their slots there


class _Chunk(NamedTuple):
    """A prefill dispatch whose first tokens the host has not read yet."""

    first: jax.Array       # [R + counters] the rows' sampled first tokens
    keys: jax.Array        # [R, 2] their advanced chains
    slots: list            # the slots it advanced, row by row
    lens: list             # the real tokens of each row's chunk
    at: float              # time.monotonic() as it was dispatched


# Version tag of the serialized request form (`RequestHandle.to_wire`).
# Bump on any field-semantics change; `from_wire` rejects unknown versions
# so a stale worker can never adopt a payload it would misinterpret.
REQUEST_WIRE_VERSION = 1


class RequestHandle:
    """One submitted request: its prompt, its growing output, and the
    accounting the bench and the serving CLI read (timestamps, queue wait,
    preemption/resume counts, prefix-cache hits)."""

    def __init__(
        self,
        rid: int,
        prompt: list[int],
        max_new_tokens: int,
        on_token: Callable[["RequestHandle", int], None] | None = None,
    ):
        self.id = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.on_token = on_token
        self.generated: list[int] = []
        self.done = False
        # "eos" | "length" | "timeout" (deadline exceeded) | "failed"
        # (replica lost with no healthy replica to migrate to)
        self.finish_reason: str | None = None
        self.deadline: float | None = None   # monotonic; None = no deadline
        self.submit_time: float | None = None
        self.first_token_time: float | None = None
        self.finish_time: float | None = None
        self.queue_wait_ms = 0.0     # cumulative: every (re)queue -> admit gap
        self.preemptions = 0         # times swapped out for pool pressure
        self.resumes = 0             # re-admissions after a preemption
        self.prefix_cached_tokens = 0  # prompt tokens skipped at 1st admission
        self.replica: int | None = None  # set by the replica router on route
        self._key = None        # [2] uint32 PRNG chain head
        self._slot: int | None = None
        self._blocks: list[int] | None = None
        self._enqueue_time: float | None = None
        self._admit_order = -1       # monotone per admission; newest = victim
        self._work: np.ndarray | None = None  # tokens this admission prefills
        self._prefill_pos: int | None = None  # next work position; None = done
        self._pending_token: int | None = None  # resume: decode input, no emit

    @property
    def tokens(self) -> list[int]:
        """Prompt + generated so far."""
        return list(self.prompt) + list(self.generated)

    def _emit(self, tok: int) -> None:
        if self.first_token_time is None:
            self.first_token_time = time.monotonic()
            # ts is the handle's OWN stamp (monotonic == perf_counter's
            # CLOCK_MONOTONIC on Linux), so a trace-derived TTFT equals the
            # engine's first_token_time - submit_time accounting exactly.
            get_tracer().event(
                "first_token", ts=self.first_token_time, rid=self.id
            )
        if self.on_token is not None:
            self.on_token(self, tok)

    def _finish(self, reason: str) -> None:
        self.done = True
        self.finish_reason = reason
        self.finish_time = time.monotonic()
        get_tracer().event(
            "finish", ts=self.finish_time, rid=self.id, reason=reason,
            n_generated=len(self.generated),
        )

    def to_wire(self) -> dict:
        """Serialize the exact migration state ``extract_inflight``
        captures — generated tokens, PRNG chain head, pending decode
        input — so a request can cross a process boundary and resume
        bit-identically with zero re-emitted tokens. Timestamps are
        CLOCK_MONOTONIC, which is machine-wide on Linux, so deadlines and
        queue-wait accounting stay valid across processes on one host."""
        return {
            "v": REQUEST_WIRE_VERSION,
            "rid": self.id,
            "prompt": list(self.prompt),
            "max_new_tokens": self.max_new_tokens,
            "generated": list(self.generated),
            "key": [int(k) for k in self._key]
            if self._key is not None else None,
            "pending_token": self._pending_token,
            "deadline": self.deadline,
            "submit_time": self.submit_time,
            "first_token_time": self.first_token_time,
            "queue_wait_ms": self.queue_wait_ms,
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "prefix_cached_tokens": self.prefix_cached_tokens,
        }

    @classmethod
    def from_wire(
        cls,
        d: dict,
        on_token: Callable[["RequestHandle", int], None] | None = None,
    ) -> "RequestHandle":
        """Rebuild a handle from :meth:`to_wire` output. Raises
        ValueError on an unknown version tag — adopting a payload whose
        fields we might misread would silently corrupt a stream."""
        v = d.get("v")
        if v != REQUEST_WIRE_VERSION:
            raise ValueError(
                f"unknown request wire version {v!r} "
                f"(this build speaks {REQUEST_WIRE_VERSION})"
            )
        req = cls(
            int(d["rid"]), [int(t) for t in d["prompt"]],
            int(d["max_new_tokens"]), on_token,
        )
        req.generated = [int(t) for t in d["generated"]]
        if d["key"] is not None:
            req._key = np.asarray(d["key"], np.uint32)
        if d["pending_token"] is not None:
            req._pending_token = int(d["pending_token"])
        req.deadline = d["deadline"]
        req.submit_time = d["submit_time"]
        req.first_token_time = d["first_token_time"]
        req.queue_wait_ms = float(d["queue_wait_ms"])
        req.preemptions = int(d["preemptions"])
        req.resumes = int(d["resumes"])
        req.prefix_cached_tokens = int(d["prefix_cached_tokens"])
        return req


def _prefill_impl(
    params,
    prompt: jnp.ndarray,   # [1, Pf] int32, right-padded to the bucket
    p_real: jnp.ndarray,   # scalar int32 — true prompt length (traced!)
    key: jnp.ndarray,      # [2] uint32
    pad_to: int,           # static (positional: pjit in_shardings bars kwargs)
    *,
    config: GPT2Config,
    temperature: float,
    top_k: int | None,
    compute_dtype,
):
    """Whole-prompt forward + first-token sample for one request.

    Compiles once per (Pf, pad_to) bucket, NOT per prompt length: the true
    length arrives as a traced scalar and only feeds a dynamic_slice. The
    right-padding is causally inert — K/V and hidden states at positions
    < p_real are bit-identical to an unpadded run (padded columns are
    masked out of every softmax row we read; see tests/test_serving.py).

    Returns (first_token scalar, advanced key, k, v ``[L, H, pad_to, D]``)
    with the PRNG split order of ``generate_cached``: split once, sample
    with the sub, carry the main — so a request's whole chain matches the
    one-shot path's.
    """
    h, cache = decode.prefill(
        params, config, prompt, prompt.shape[1], compute_dtype
    )
    h_last = jax.lax.dynamic_slice_in_dim(h, p_real - 1, 1, axis=1)[:, 0]
    logits0 = jnp.einsum(
        "bc,vc->bv", h_last, params["wte"].astype(h_last.dtype),
        preferred_element_type=jnp.float32,
    )
    key, sub = jax.random.split(key)
    first = sample_token(logits0, sub, temperature, top_k)[0]
    k, v = cache.k[:, 0], cache.v[:, 0]   # [L, H, Pf, D]
    if pad_to > k.shape[2]:
        # The last block straddles n_positions: the forward can't run past
        # the position table, but the scatter writes whole blocks. Zero-pad
        # — the tail is overwritten by decode before it's ever attendable.
        pad = ((0, 0), (0, 0), (0, pad_to - k.shape[2]), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    return first, key, k, v


def _paged_layers(
    config: GPT2Config,
    params,
    x: jnp.ndarray,            # [rows, T, C]
    k_pool: jnp.ndarray,       # as stored (`paged_cache.pool_shape`)
    v_pool: jnp.ndarray,
    write: Callable,           # (kp, vp, layer, k, v) -> (kp, vp)
    attend: Callable,          # (q, kp, vp, layer) -> o, rows*T*H*D elements
    data_rows: bool = False,
):
    """``x`` through every block, the step programs' one layer loop: each
    layer projects q/k/v ``[rows, T, H, D]``, ``write``s its K/V into the
    pools, ``attend``s over them, and runs the out-projection, residual and
    MLP — every op as ``decode.decode_step`` / the dense prefill has it.

    The pools ride the scan's CARRY, whole, as ``[L, N, H, bs, D]``
    (``write`` and ``attend`` get that view), and the layer's index rides
    ``xs`` beside its weights: carried, a pool stays one buffer that the
    writes update in place; as ``xs``/``ys`` each layer's slice was cut
    out, re-laid out and copied into a second pool-sized buffer.

    Returns (x, k_pool, v_pool), the pools in the shape they came in."""
    rows, t, c = x.shape
    stored = k_pool.shape

    def body(carry, layer):
        x, kp, vp = carry
        bp, l = layer
        y = layer_norm(x, bp["ln1_scale"], bp["ln1_bias"], config.layer_norm_eps)
        q, k, v = gpt2.qkv_proj(config, y, bp)              # [rows, T, H, D]
        kp, vp = write(kp, vp, l, k, v)
        o = gpt2.gather_attn_heads(attend(q, kp, vp, l), data_rows=data_rows)
        o = o.reshape(rows, t, c)
        o = o @ bp["attn_proj_w"].astype(x.dtype) + bp["attn_proj_b"].astype(x.dtype)
        x = x + o
        x = gpt2._mlp_sublayer(config, x, bp, None, True)
        return (x, kp, vp), None

    layers = jax.lax.iota(jnp.int32, config.n_layer)
    (x, k_pool, v_pool), _ = jax.lax.scan(
        body, (x, as_blocks(k_pool), as_blocks(v_pool)),
        (params["block"], layers),
    )
    return x, k_pool.reshape(stored), v_pool.reshape(stored)


def _chunk_prefill_impl(
    params,
    k_pool: jnp.ndarray,       # [L, N, H, bs, D], or as stored — donated
    v_pool: jnp.ndarray,
    bt: jnp.ndarray,           # [R, M] int32 — one block-table row per request
    chunk: jnp.ndarray,        # [R, C] int32 tokens, right-padded per row
    start: jnp.ndarray,        # [R] int32 — work position of chunk[r, 0]
    clen: jnp.ndarray,         # [R] int32 — real tokens per row (0 = pad row)
    keys: jnp.ndarray,         # [R, 2] uint32 per-row PRNG chains
    *,
    config: GPT2Config,
    temperature: float,
    top_k: int | None,
):
    """R prefill chunks straight into the pool in one dispatch: compute
    each row's K/V for positions ``[start_r, start_r + clen_r)``, write
    them into that request's blocks at position granularity
    (``paged_cache.write_chunk``), attend over the partially-built tables.

    Compiles once per (R, C) (shape-keyed) — in chunked mode R is
    ``ServeConfig.prefill_batch`` and C is ``ServeConfig.prefill_chunk``
    for every dispatch, so one compile total (short rounds pad with
    ``clen=0`` rows). The whole-prompt continuation path
    (``prefill_chunk=0``) runs R=1 and buckets C to a block multiple like
    ``_prefill_impl`` does for prefix-cache hits (remainder bounded by the
    prompt), and uses the full table width ``M * bs`` for preemption
    resumes (remainder grows with generation — one program covers every
    resume length).

    Bit-parity: every op mirrors the dense prefill path
    (``decode.prefill`` → ``causal_attention_bthd``) per position —
    identical embedding gathers, sublayer math, einsum forms, masked fp32
    softmax — and rows are independent in every op (per-row gathers,
    per-row attention via ``paged_prefill_attention``'s batch axis,
    per-row PRNG chains in the vmapped sampler), so any chunk split AND
    any row batching reproduces whole-prompt prefill bit-for-bit. Padded
    positions (``i >= clen_r``) are never written (the pool keeps what it
    holds there) and causally masked out of every row we read; an all-pad
    row (``clen_r = 0``) writes nothing and its sampled token/advanced
    key are discarded by the host. Every row samples a token with its
    request key — one compiled program — and the host discards it on
    non-final chunks, leaving the PRNG chain's one split exactly where
    ``generate_cached`` puts it.

    Returns ([R] sampled tokens at each row's start+clen-1, advanced
    [R, 2] keys, pools).
    """
    c = chunk.shape[1]
    dtype = k_pool.dtype
    start = jnp.asarray(start, jnp.int32)
    clen = jnp.asarray(clen, jnp.int32)

    tok = params["wte"].astype(dtype).at[chunk].get(mode="clip")  # [R, C, E]
    pos_ids = start[:, None] + jax.lax.iota(jnp.int32, c)[None]   # [R, C]
    # Gather (not dynamic_slice): a straddling final chunk has pos_ids past
    # n_positions-1 on its padded rows; clip freezes THOSE rows only, where
    # dynamic_slice would clamp the start and shift every real position.
    wpe = params["wpe"].astype(dtype).at[pos_ids].get(mode="clip")  # [R, C, E]
    x = tok + wpe

    valid = jax.lax.iota(jnp.int32, c)[None] < clen[:, None]      # [R, C]
    x, k_pool, v_pool = _paged_layers(
        config, params, x, k_pool, v_pool,
        write=lambda kp, vp, l, k, v: write_chunk(
            kp, vp, l, bt, start, valid, k, v),
        attend=lambda q, kp, vp, l: paged_prefill_attention(
            q, kp, vp, bt, start, l),
    )
    x = layer_norm(x, params["ln_f_scale"], params["ln_f_bias"], config.layer_norm_eps)
    last = jnp.maximum(clen - 1, 0)                               # [R]
    h_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    logits = jnp.einsum(
        "bc,vc->bv", h_last, params["wte"].astype(h_last.dtype),
        preferred_element_type=jnp.float32,
    )                                                             # [R, V] fp32

    def row_sample(logits_row, key):
        key, sub = jax.random.split(key)
        tok = sample_token(logits_row[None], sub, temperature, top_k)[0]
        return tok, key

    first, keys = jax.vmap(row_sample)(logits, keys)
    return first.astype(jnp.int32), keys, k_pool, v_pool


def _decode_step_impl(
    params,
    k_pool: jnp.ndarray,       # [L, N, H, bs, D], or as stored
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, M] int32
    tokens: jnp.ndarray,       # [B] int32 — token to process, at `pos`
    pos: jnp.ndarray,          # [B] int32
    active: jnp.ndarray,       # [B] bool
    keys: jnp.ndarray,         # [B, 2] uint32 per-slot PRNG chains
    *,
    config: GPT2Config,
    temperature: float,
    top_k: int | None,
    attn_impl: str,
):
    """One continuous-batching decode step: write each active row's K/V at
    its own position, attend over its paged prefix, sample its next token.

    Mirrors ``decode.decode_step`` op-for-op (same embedding gathers, same
    einsum forms, per-position sublayers) with two generalizations: `pos`
    is per-row instead of a shared scalar, and the cache indexing goes
    through the block table. Inactive rows (idle slots AND slots still in
    chunked prefill) are steered to the null block and a zero attention
    length — their lanes compute garbage that nothing reads.
    """
    bsz = tokens.shape[0]
    dtype = k_pool.dtype
    bs = k_pool.shape[-2]

    tok = params["wte"].astype(dtype).at[tokens].get(mode="clip")
    wpe = params["wpe"].astype(dtype).at[pos].get(mode="clip")   # [B, C]
    x = (tok + wpe)[:, None]                                     # [B, 1, C]

    lengths = jnp.where(active, pos + 1, 0).astype(jnp.int32)
    blk = block_table[jnp.arange(bsz), pos // bs]
    blk = jnp.where(active, blk, 0)   # idle rows scribble on the null block
    off = pos % bs

    x, k_pool, v_pool = _paged_layers(
        config, params, x, k_pool, v_pool,
        write=lambda kp, vp, l, k, v: write_rows(
            kp, vp, l, blk, off, k[:, 0], v[:, 0]),
        attend=lambda q, kp, vp, l: paged_attention(
            q[:, 0], kp, vp, block_table, lengths, l, impl=attn_impl),
        data_rows=True,
    )
    x = layer_norm(x, params["ln_f_scale"], params["ln_f_bias"], config.layer_norm_eps)
    logits = jnp.einsum(
        "btc,vc->btv", x, params["wte"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )[:, 0]                                                      # [B, V] fp32

    # Per-row PRNG chains: each slot samples with ITS key on a [1, V] row —
    # the threefry bits are identical to a batch-1 generate_cached step, so
    # a request's tokens don't depend on who shares the batch with it.
    def row_sample(logits_row, key):
        key, sub = jax.random.split(key)
        tok = sample_token(logits_row[None], sub, temperature, top_k)[0]
        return tok, key

    next_tokens, keys = jax.vmap(row_sample)(logits, keys)
    return next_tokens.astype(jnp.int32), keys, k_pool, v_pool


def _feed_impl(
    sampled: jnp.ndarray,      # [B + counters] int32 - the last decode step's tokens
    chains: jnp.ndarray,       # [B, 2] uint32 - and its advanced keys
    tokens: jnp.ndarray,       # [B] int32 - the host's rows
    keys: jnp.ndarray,         # [B, 2] uint32
    fresh: jnp.ndarray,        # [B] bool - rows whose host values are newer
):
    """The next decode step's ``tokens`` and ``keys``: what the step before
    sampled, as it lies on the device (without the counters a family puts
    behind the rows), with the rows a prefill has opened since - first token
    or pending token, and the request's key - patched in from the host."""
    rows = tokens.shape[0]
    return (jnp.where(fresh, tokens, sampled[:rows]),
            jnp.where(fresh[:, None], keys, chains))


def _draft_step_impl(
    params,
    k_pool: jnp.ndarray,       # [L, N, H, bs, D], or as stored — DRAFT pool
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, M] int32 — draft block table
    tokens: jnp.ndarray,       # [B] int32 — token to process, at `pos`
    pos: jnp.ndarray,          # [B] int32
    active: jnp.ndarray,       # [B] bool
    *,
    config: GPT2Config,
    attn_impl: str,
):
    """One draft-model decode step for speculative decoding: identical to
    ``_decode_step_impl`` — same embedding gathers, same paged write, same
    attention — but over the DRAFT pool/params, and returning the fp32
    logits instead of sampling: the host owns draft-token selection
    (argmax for greedy engines; inverse-CDF from the masked/tempered
    draft distribution for sampled ones, whose probabilities the
    acceptance rule needs anyway). No PRNG chain enters or leaves — draft
    randomness comes from the per-round uniforms the engine derives from
    each slot's chain head."""
    bsz = tokens.shape[0]
    dtype = k_pool.dtype
    bs = k_pool.shape[-2]

    tok = params["wte"].astype(dtype).at[tokens].get(mode="clip")
    wpe = params["wpe"].astype(dtype).at[pos].get(mode="clip")   # [B, C]
    x = (tok + wpe)[:, None]                                     # [B, 1, C]

    lengths = jnp.where(active, pos + 1, 0).astype(jnp.int32)
    blk = block_table[jnp.arange(bsz), jnp.minimum(pos // bs,
                                                   block_table.shape[1] - 1)]
    blk = jnp.where(active, blk, 0)   # idle rows scribble on the null block
    off = pos % bs

    x, k_pool, v_pool = _paged_layers(
        config, params, x, k_pool, v_pool,
        write=lambda kp, vp, l, k, v: write_rows(
            kp, vp, l, blk, off, k[:, 0], v[:, 0]),
        attend=lambda q, kp, vp, l: paged_attention(
            q[:, 0], kp, vp, block_table, lengths, l, impl=attn_impl),
        data_rows=True,
    )
    x = layer_norm(x, params["ln_f_scale"], params["ln_f_bias"], config.layer_norm_eps)
    logits = jnp.einsum(
        "btc,vc->btv", x, params["wte"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )[:, 0]                                                      # [B, V] fp32
    return logits, k_pool, v_pool


def _spec_verify_impl(
    params,
    k_pool: jnp.ndarray,       # [L, N, H, bs, D], or as stored — donated
    v_pool: jnp.ndarray,
    bt: jnp.ndarray,           # [R, M] int32 block-table rows
    chunk: jnp.ndarray,        # [R, T] int32 tokens, right-padded per row
    start: jnp.ndarray,        # [R] int32 — absolute position of chunk[r, 0]
    clen: jnp.ndarray,         # [R] int32 — real tokens per row (0 = pad row)
    *,
    config: GPT2Config,
    return_logits: bool,
):
    """The speculative two-model engine's shared forward: a T-token window
    through the model, K/V written into the pool at position
    granularity, attention over the partially-built table via
    ``spec_verify_attention``.

    Two partials, two jobs:

    * ``return_logits=True`` — the target VERIFY pass: chunk row r holds
      ``[committed_token, d_1, .., d_K]`` (T = K+1) at positions
      ``start_r ..``, and the fp32 logits at ALL T positions come back
      (``"btc,vc->btv"`` instead of the last-position gather) — logits[i]
      is the target distribution for position ``start_r + i + 1``, which
      the host's acceptance rule scores the draft against. Every op
      mirrors ``_chunk_prefill_impl`` (which is pinned bit-identical to
      the dense path), so greedy argmaxes equal sequential decode's.
    * ``return_logits=False`` — the DRAFT CATCH-UP pass: after admission,
      preemption-resume or cross-engine adoption the draft pool holds
      nothing (draft KV is disposable), so the engine re-drafts by
      running the committed tokens through the draft model to rebuild
      its KV; the logits (a ``[R, T, V]`` buffer at full window width)
      are never formed.

    Unlike ``_chunk_prefill_impl``, positions at or past
    ``config.n_positions`` are masked out of the write: a verify
    window straddling the context end must not wrap into (and corrupt)
    the last real block's valid rows — masked writes land nowhere, and
    the host never emits past the context anyway."""
    t = chunk.shape[1]
    dtype = k_pool.dtype
    start = jnp.asarray(start, jnp.int32)
    clen = jnp.asarray(clen, jnp.int32)

    tok = params["wte"].astype(dtype).at[chunk].get(mode="clip")  # [R, T, E]
    pos_ids = start[:, None] + jax.lax.iota(jnp.int32, t)[None]   # [R, T]
    wpe = params["wpe"].astype(dtype).at[pos_ids].get(mode="clip")
    x = tok + wpe

    valid = jax.lax.iota(jnp.int32, t)[None] < clen[:, None]      # [R, T]
    valid = valid & (pos_ids < config.n_positions)
    x, k_pool, v_pool = _paged_layers(
        config, params, x, k_pool, v_pool,
        write=lambda kp, vp, l, k, v: write_chunk(
            kp, vp, l, bt, start, valid, k, v),
        attend=lambda q, kp, vp, l: spec_verify_attention(
            q, kp, vp, bt, start, l),
    )
    if not return_logits:
        return k_pool, v_pool
    x = layer_norm(x, params["ln_f_scale"], params["ln_f_bias"], config.layer_norm_eps)
    logits = jnp.einsum(
        "btc,vc->btv", x, params["wte"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )                                                             # [R, T, V]
    return logits, k_pool, v_pool


def _spec_probs(logits, temperature: float, top_k: int | None) -> np.ndarray:
    """fp64 next-token distribution(s) from fp32 logits, mirroring
    ``sample_token``'s semantics exactly: kth-largest threshold with a
    strict-less mask (``lax.top_k`` keeps ties at the threshold, so does
    ``np.partition``), then temperature. Host-side because the
    speculative acceptance rule (``_spec_round``) needs the draft and
    target probabilities of specific tokens — fp64 so the accept/residual
    arithmetic carries no meaningful rounding of its own, which is what
    the target-distribution contract is tested against."""
    l = np.asarray(logits, np.float64)
    if top_k is not None:
        kth = np.partition(l, -top_k, axis=-1)[..., -top_k][..., None]
        l = np.where(l < kth, -np.inf, l)
    l = l / temperature
    l = l - l.max(axis=-1, keepdims=True)
    e = np.exp(l)
    return e / e.sum(axis=-1, keepdims=True)


def _spec_cdf_sample(probs: np.ndarray, u: float) -> int:
    """Inverse-CDF draw from one fp64 distribution with uniform ``u``.
    ``u`` scales by the actual mass (fp64 sums are not exactly 1.0) and
    the index clamps to the vocab — both guards are distribution-neutral."""
    c = np.cumsum(probs)
    return min(int(np.searchsorted(c, u * c[-1], side="right")), len(c) - 1)


def _spec_accept(
    vlogits: np.ndarray,            # [K+1, V] fp32 target verify logits
    d_toks: np.ndarray,             # [K] int32 draft proposals
    q_dists: list[np.ndarray] | None,  # K fp64 draft dists (None = greedy)
    unis: np.ndarray | None,        # [3K+1] fp64 round uniforms (None = greedy)
    temperature: float,
    top_k: int | None,
) -> tuple[list[int], int]:
    """One slot's acceptance/resample rule -> (emitted tokens, accepted).

    Greedy: accept while the draft token equals the verify argmax; the
    first mismatch emits the argmax itself (the correction), a clean
    sweep emits the bonus argmax — every emitted token is a target
    argmax, which is the bit-equality argument in one line.

    Sampled (the Leviathan/Chen rule): accept draft token ``d`` with
    probability ``min(1, p(d)/q(d))``; on rejection resample from the
    residual ``max(p - q, 0)`` renormalized; after a clean sweep the
    bonus token comes straight from the last target distribution. Each
    decision consumes the round uniform reserved for it (accept coins at
    ``[K, 2K)``, residual draws at ``[2K, 3K)``, the bonus at ``3K``), so
    the emitted prefix is provably distributed as sequential target
    sampling — the property the fp64 Monte-Carlo test pins."""
    k = len(d_toks)
    emit: list[int] = []
    accepted = 0
    if q_dists is None:
        for i in range(k):
            g = int(vlogits[i].argmax())
            emit.append(g)
            if g != int(d_toks[i]):
                return emit, accepted
            accepted += 1
        emit.append(int(vlogits[k].argmax()))
        return emit, accepted
    for i in range(k):
        p = _spec_probs(vlogits[i], temperature, top_k)
        d = int(d_toks[i])
        if unis[k + i] * q_dists[i][d] < p[d]:
            emit.append(d)
            accepted += 1
            continue
        r = np.maximum(p - q_dists[i], 0.0)
        z = float(r.sum())
        # z == 0 only when q dominates p everywhere it lost — an
        # fp64-measure-zero corner; falling back to p keeps the draw
        # inside the target support.
        r = r / z if z > 0.0 else p
        emit.append(_spec_cdf_sample(r, unis[2 * k + i]))
        return emit, accepted
    p = _spec_probs(vlogits[k], temperature, top_k)
    emit.append(_spec_cdf_sample(p, unis[3 * k]))
    return emit, accepted


class ServingEngine:
    """Continuous-batching serving engine. See the module docstring.

    What it holds: ``params`` (and ``draft_params``) of a GPT-2 model are
    ``gpt2.serving_weights(params, compute_dtype)`` - the embeddings and
    every matmul leaf in ``compute_dtype``, cast once here, the LayerNorm
    leaves float32 - and never the float32 tree passed in, which stays its
    caller's, whole, to keep or drop. The step programs still take either
    (their ``.astype`` is a no-op on a leaf already cast); the engine hands
    them only what it holds. Another family's tree (``serving/families.py``)
    is held as given.

    Typical loop::

        eng = ServingEngine(params, config, ServeConfig(max_batch=8))
        h = eng.submit(prompt_ids, max_new_tokens=64, rng=0,
                       on_token=lambda req, t: print(t))
        eng.run_until_idle()
        print(h.generated)
    """

    def __init__(
        self,
        params,
        config: GPT2Config,
        serve: ServeConfig | None = None,
        *,
        temperature: float = 0.0,
        top_k: int | None = None,
        compute_dtype=DEFAULT_COMPUTE_DTYPE,
        draft_params=None,
        draft_config: GPT2Config | None = None,
    ):
        serve = serve if serve is not None else ServeConfig()
        # The family seam (`serving/families.py`): a model configuration
        # that is not GPT-2's brings ONE object that says what it refuses,
        # whatever it keeps beside the K/V pools (`self.state`), its two step
        # programs (`_chunk_fn`, `_decode_fn`) and what its rows count;
        # admission, slots, block tables, the prefill tick, growth, the emit
        # loop, the counters and the spans below are one scheduler for every
        # family. GPT-2 is the default branch (`self._family is None`). What
        # a family cannot do yet is refused here, by name, not run wrong.
        self._family = family_of(config)
        if self._family is not None:
            why = self._family.flags.refuse(
                config, serve,
                draft_params is not None or draft_config is not None)
            if why is not None:
                raise ValueError(
                    f"ServingEngine cannot serve {self._family.name} with {why}")
        # Sampling params are engine-level (static in the compiled step);
        # validate top_k once here with the shared check so a bad engine
        # config fails like a bad request would.
        check_generation_args(config, 1, 1, top_k, batch=serve.max_batch)
        # Speculative decoding (ServeConfig.spec) — default off, opt-in per
        # engine. The draft model arrives as explicit params/config (the
        # CLIs map --draft_preset to MODEL_PRESETS; tests pass a shrunken
        # config directly), validated here with the same rules the jax-free
        # flag check enforces at parse time.
        self._draft_preset, self._spec_k = serve.spec_axes()
        if self._spec_k:
            if draft_params is None or draft_config is None:
                raise ValueError(
                    f"spec={serve.spec!r} enables speculative decoding but "
                    f"no draft model was provided "
                    f"(draft_params= / draft_config=)"
                )
            if draft_config.num_params() >= config.num_params():
                raise ValueError(
                    f"draft model ({draft_config.num_params():,} params) "
                    f"must be smaller than the target "
                    f"({config.num_params():,} params)"
                )
            if draft_config.vocab_size != config.vocab_size:
                raise ValueError(
                    f"draft vocab_size={draft_config.vocab_size} must match "
                    f"the target's {config.vocab_size}: acceptance compares "
                    f"distributions over one token space"
                )
            if draft_config.n_positions < config.n_positions:
                raise ValueError(
                    f"draft n_positions={draft_config.n_positions} must "
                    f"cover the target's {config.n_positions}: the draft "
                    f"re-encodes the full committed prefix"
                )
        elif draft_params is not None or draft_config is not None:
            raise ValueError(
                "draft model provided but serve.spec is empty — "
                "speculation is opt-in via ServeConfig.spec "
                "('draft:<preset>,k:<K>')"
            )
        self.draft_config = draft_config
        self.config = config
        self.serve = serve
        self.temperature = float(temperature)
        self.top_k = top_k
        self.compute_dtype = compute_dtype
        # What the engine holds is what its programs multiply by. Another
        # family's tree arrives so (bfloat16 among float32 norms) and is
        # kept as given; a GPT-2 tree is cast here, once, and the tree passed
        # in is its caller's to drop.
        self.params, self.draft_params = params, draft_params
        if self._family is None:
            self.params = self._serving_weights(params, "target")
            if draft_params is not None:
                self.draft_params = self._serving_weights(draft_params, "draft")

        self._m = serve.max_blocks_per_seq(config.n_positions)
        self._seq_limit = serve.seq_limit(config.n_positions)
        # --- serving mesh (ServeConfig.mesh): data × tp, or None -----------
        self._dp, self._tp = serve.mesh_axes()
        self.mesh = None
        self._pool_sharding = None
        self._scatter_fn, self._copy_fn = scatter_prefill, copy_block
        pool_sharding = None
        decode_kw: dict = {}
        feed_kw: dict = {}
        chunk_kw: dict = {}
        prefill_kw: dict = {}
        spec_draft_kw: dict = {}
        spec_catchup_kw: dict = {}
        spec_verify_kw: dict = {}
        if self._dp * self._tp > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from gpt_2_distributed_tpu.parallel.mesh import (
                DATA_AXIS,
                MeshSpec,
                TP_AXIS,
                create_mesh,
            )
            from gpt_2_distributed_tpu.parallel.sharding import (
                serve_param_pspecs,
            )

            if jax.device_count() < self._dp * self._tp:
                raise ValueError(
                    f"mesh={serve.mesh!r} wants {self._dp * self._tp} "
                    f"devices but only {jax.device_count()} are visible"
                )
            self.mesh = create_mesh(MeshSpec(data=self._dp, tp=self._tp))

            def sh(*spec):
                return NamedSharding(self.mesh, P(*spec))

            # Pools: block axis over 'data' (each shard owns its run of
            # blocks — matching the allocator's per-shard free lists), head
            # axis over 'tp'.
            pool_sharding = sh(None, DATA_AXIS, TP_AXIS, None, None)
            self._pool_sharding = pool_sharding
            # Params: tp head-shards the qkv leaves ONLY — the Megatron
            # row/col placements would psum partial matmuls and break the
            # bit-exactness contract (see serve_param_pspecs).
            param_sh = jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec),
                serve_param_pspecs(self.params, self.mesh),
                is_leaf=lambda x: isinstance(x, P),
            )
            self.params = jax.device_put(self.params, param_sh)
            row_sh, vec_sh, rep_sh = sh(DATA_AXIS), sh(DATA_AXIS, None), sh()
            # Explicit in/out shardings: jit commits the host numpy
            # scheduler arrays straight to their row placements, and
            # donation only elides the pool copy when the output sharding
            # matches the (donated) input's — without the pin GSPMD may
            # replicate outputs, silently un-sharding the engine.
            decode_kw = dict(
                in_shardings=(param_sh, pool_sharding, pool_sharding,
                              vec_sh, row_sh, row_sh, row_sh, vec_sh),
                out_shardings=(row_sh, vec_sh, pool_sharding, pool_sharding),
            )
            feed_kw = dict(out_shardings=(row_sh, vec_sh))
            # Chunk-prefill rows are replicated over 'data' (R is small and
            # unconstrained by the mesh; the matmuls still shard over 'tp'
            # and the pool writes land data-sharded).
            chunk_kw = dict(
                in_shardings=(param_sh, pool_sharding, pool_sharding,
                              rep_sh, rep_sh, rep_sh, rep_sh, rep_sh),
                out_shardings=(rep_sh, rep_sh, pool_sharding, pool_sharding),
            )
            kv_sh = sh(None, TP_AXIS, None, None)
            prefill_kw = dict(
                in_shardings=(param_sh, rep_sh, rep_sh, rep_sh),
                out_shardings=(rep_sh, rep_sh, kv_sh, kv_sh),
            )
            if self._spec_k:
                if draft_config.n_head % self._tp != 0:
                    raise ValueError(
                        f"draft n_head={draft_config.n_head} must be "
                        f"divisible by the tp degree {self._tp} (the draft "
                        f"pool head-shards like the target pool)"
                    )
                draft_param_sh = jax.tree_util.tree_map(
                    lambda spec: NamedSharding(self.mesh, spec),
                    serve_param_pspecs(self.draft_params, self.mesh),
                    is_leaf=lambda x: isinstance(x, P),
                )
                self.draft_params = jax.device_put(
                    self.draft_params, draft_param_sh
                )
                # Draft decode rows shard like target decode rows; the
                # verify window and draft catch-up rows replicate like
                # chunked prefill (same [R, T] row shapes, same write).
                spec_draft_kw = dict(
                    in_shardings=(draft_param_sh, pool_sharding,
                                  pool_sharding, vec_sh, row_sh, row_sh,
                                  row_sh),
                    out_shardings=(vec_sh, pool_sharding, pool_sharding),
                )
                spec_catchup_kw = dict(
                    in_shardings=(draft_param_sh, pool_sharding,
                                  pool_sharding, rep_sh, rep_sh, rep_sh,
                                  rep_sh),
                    out_shardings=(pool_sharding, pool_sharding),
                )
                spec_verify_kw = dict(
                    in_shardings=(param_sh, pool_sharding, pool_sharding,
                                  rep_sh, rep_sh, rep_sh, rep_sh),
                    out_shardings=(rep_sh, pool_sharding, pool_sharding),
                )
            self._scatter_fn, self._copy_fn = make_pool_jits(pool_sharding)
        # Per-device bytes of the weights held, as placed (under a mesh the
        # head-sharded qkv leaves count one shard): `metrics_snapshot`.
        self.weight_bytes = sum(
            math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
            if isinstance(a, jax.Array) else a.nbytes
            for a in jax.tree_util.tree_leaves(self.params)
        )
        # Which layers hold K/V, and in how many heads: GPT-2's all do, in
        # every head.
        self._pool_view = config if self._family is None else config.kv_pool_view
        self.k_pool, self.v_pool = init_pools(
            self._pool_view, serve, compute_dtype, sharding=pool_sharding,
        )
        # The family's cache beside the pools (None: GPT-2 keeps none),
        # carried through its step programs and donated like them.
        self.state = (
            None if self._family is None
            else self._family.init_state(config, serve, compute_dtype)
        )
        self.allocator = BlockAllocator(serve.num_blocks, num_shards=self._dp)
        self._slots_per_shard = serve.max_batch // self._dp
        self._cache = PrefixCache(serve.block_size) if serve.prefix_cache else None
        # Scheduler state lives on the HOST as numpy: admission/eviction
        # mutate it in place for free, and the arrays ship to the compiled
        # step with each call (a few hundred bytes). jnp `.at[].set` outside
        # jit costs ~1-2 ms PER UPDATE in op-by-op dispatch — doing the
        # bookkeeping device-side made admission 6x slower than the prefill
        # it wraps.
        self.block_table = np.zeros((serve.max_batch, self._m), np.int32)
        self.pos = np.zeros((serve.max_batch,), np.int32)
        self.tokens = np.zeros((serve.max_batch,), np.int32)
        self.active = np.zeros((serve.max_batch,), bool)
        self.keys = np.zeros((serve.max_batch, 2), np.uint32)
        # The one-deep pipeline of the decode loop (module docstring).
        # `tokens` and `keys` above are the HOST's rows: a decoding row's
        # `keys` is its chain after its last token read back, its `tokens`
        # is what a prefill (or a speculative round) put there; what the
        # next decode step takes lies on the device, `_sampled` - the last
        # decode step's two outputs - with the rows marked `_fresh` patched
        # in from here (`_feed_fn`). `_left`: tokens a row has yet to
        # sample once the dispatched steps are in; `_unread`: the step
        # dispatched and not read back; `_parting`: requests that left
        # their slot with their last token in a step still unread, by id.
        behind = len(self._family.counters) if self._family is not None else 0
        sampled_sh, chains_sh = feed_kw.get("out_shardings", (None, None))
        self._sampled = (
            jax.device_put(
                np.zeros((serve.max_batch + behind,), np.int32), sampled_sh),
            jax.device_put(
                np.zeros((serve.max_batch, 2), np.uint32), chains_sh),
        )
        self._fresh = np.zeros((serve.max_batch,), bool)
        self._left = np.zeros((serve.max_batch,), np.int64)
        self._unread: _Unread | None = None
        self._turned_at = 0.0   # when the last decode turn had dispatched and read
        self._parting: dict[int, RequestHandle] = {}
        self._feed_fn = jax.jit(_program("decode_feed", _feed_impl), **feed_kw)

        # --- draft-model state (speculative decoding) ---------------------
        # The draft pool pairs slot-for-slot with the target pool but is
        # sized for full per-slot capacity (draft_serve_view), so its
        # allocator can never fail mid-round. Draft KV is DISPOSABLE: it
        # is rebuilt from the committed tokens (catch-up pass) after
        # admission, preemption-resume and cross-engine adoption, and
        # never serialized — migration wire format is unchanged.
        if self._spec_k:
            self._draft_serve = draft_serve_view(serve, config.n_positions)
            self._draft_m = self._draft_serve.max_blocks_per_seq(
                config.n_positions
            )
            self.dk_pool, self.dv_pool = init_pools(
                draft_config, self._draft_serve, compute_dtype,
                sharding=pool_sharding,
            )
            self._draft_alloc = BlockAllocator(
                self._draft_serve.num_blocks, num_shards=self._dp
            )
            self.draft_table = np.zeros(
                (serve.max_batch, self._draft_m), np.int32
            )
            self._draft_blocks: list[list[int] | None] = (
                [None] * serve.max_batch
            )
            # Valid draft-KV frontier per slot: positions [0, _draft_pos)
            # hold K/V consistent with the committed token stream. The
            # round invariant (_spec_round) keeps it equal to `pos` after
            # every spec round; 0 = no draft KV (catch-up required).
            self._draft_pos = np.zeros((serve.max_batch,), np.int32)

        self._slots: list[RequestHandle | None] = [None] * serve.max_batch
        self._queue: collections.deque[RequestHandle] = collections.deque()
        self._next_id = 0
        self._admit_seq = 0
        self._deadlines = False   # any live request carries a deadline
        self.stats = {
            "admitted": 0, "finished": 0, "prefills": 0, "prefill_chunks": 0,
            "prefill_dispatches": 0, "prefill_batched": 0,
            "decode_steps": 0, "decode_overlapped": 0, "tokens_out": 0,
            "preemptions": 0, "resumes": 0, "timeouts": 0,
            "prefix_hit_tokens": 0, "cow_copies": 0,
            "prefill_ms": 0.0, "decode_ms": 0.0, "queue_wait_ms": 0.0,
            "spec_draft_tokens": 0, "spec_accepted_tokens": 0,
            "spec_rollbacks": 0, "draft_ms": 0.0, "verify_ms": 0.0,
            # The step's own clocks (`_phase`), host time.monotonic in ms;
            # `metrics_snapshot` shows each per step. steps/step_ms: calls
            # of step() that found work, whole wall time (and a collect()
            # outside any). admit_ms: deadline evictions + admission less
            # the prefill dispatches admission made. decode_ms: a decode
            # turn - the dispatch of one step and the read-back of the step
            # BEFORE it (decode_overlapped of decode_steps were dispatched
            # over an unread step; a turn that only collects has no
            # dispatch, the first after one no read-back).
            # decode_dispatch_ms: the part of decode_ms from the feed's and
            # the decode program's calls to their return (argument transfer
            # and enqueue; the verify pass's in a speculative round); the
            # rest of decode_ms is the wait for the token read-back, and
            # draft_ms. emit_ms: the emit loop over the tokens read back.
            # decode_rows/decode_attended: per decode step, active rows and
            # the keys they attend, sum of pos + 1. decode_blocks_live/
            # decode_blocks_table: of those rows' block-table slots, the
            # ones that hold keys (ceil((pos + 1) / block_size) a row) and
            # all of them; the rest is the tail the paged kernel skips.
            "steps": 0, "step_ms": 0.0, "admit_ms": 0.0, "grow_ms": 0.0,
            "decode_dispatch_ms": 0.0, "emit_ms": 0.0,
            "decode_rows": 0, "decode_attended": 0,
            "decode_blocks_live": 0, "decode_blocks_table": 0,
            # Chunked prefill's twin of the two above (tokens a dispatch
            # takes, keys they attend), and - zero unless a layer selects
            # its blocks - rows through the selection (prefill and decode
            # alike), the blocks they attend and see; requests started from
            # a zero recurrent state (`step_clocks` shows each per step).
            "prefill_tokens": 0, "prefill_attended": 0,
            "sparse_rows": 0, "sparse_selected": 0, "sparse_visible": 0,
            "state_resets": 0,
            # Zero unless a layer routes its tokens to experts: per dispatch,
            # token-expert pairs that fell to the experts held here and held
            # experts that got a row (both from the device, behind the
            # tokens in their read-back), and held experts, each summed over
            # the expert layers. ssm_rows: a decode step's live rows through
            # a state-space update, summed over those layers. sscan_tokens /
            # sscan_rows: a chunk's real tokens and a decode step's live rows
            # through a selective scan, summed over those layers.
            "moe_rows": 0, "moe_experts_touched": 0, "moe_expert_slots": 0,
            "ssm_rows": 0, "sscan_tokens": 0, "sscan_rows": 0,
        }

        # Per-engine jits so tests can count THIS engine's compilations:
        # the no-retrace contract is `_decode_fn._cache_size() == 1` across
        # arbitrary admission/eviction churn, and `_chunk_fn._cache_size()
        # == 1` in chunked mode (the chunk width is fixed).
        if self._family is not None:
            donate = ("k_pool", "v_pool", "state")
            sampling = dict(config=config, temperature=self.temperature, top_k=top_k)
            self._decode_fn = jax.jit(
                _program("decode_step", self._family.decode_impl, **sampling),
                donate_argnames=donate,
            )
            self._chunk_fn = jax.jit(
                _program("chunk_prefill", self._family.chunk_impl, **sampling),
                donate_argnames=donate,
            )
            get_tracer().event("engine_mesh", mesh="single", devices=1, data=1, tp=1)
            return
        self._decode_fn = jax.jit(
            _program(
                "decode_step", _decode_step_impl, config=config,
                temperature=self.temperature, top_k=top_k,
                attn_impl=serve.attn_impl,
            ),
            donate_argnames=("k_pool", "v_pool"),
            **decode_kw,
        )
        self._prefill_fn = jax.jit(
            _program(
                "prefill", _prefill_impl, config=config,
                temperature=self.temperature, top_k=top_k,
                compute_dtype=compute_dtype,
            ),
            static_argnums=(4,),   # pad_to
            **prefill_kw,
        )
        self._chunk_fn = jax.jit(
            _program(
                "chunk_prefill", _chunk_prefill_impl, config=config,
                temperature=self.temperature, top_k=top_k,
            ),
            donate_argnames=("k_pool", "v_pool"),
            **chunk_kw,
        )
        if self._spec_k:
            # All three spec programs are shape-stable: the draft step at
            # [max_batch] rows, the catch-up at the full draft window, the
            # verify at T = spec_k + 1 — one compile each, preserving the
            # engine's compile-once discipline.
            self._draft_fn = jax.jit(
                _program(
                    "draft_step", _draft_step_impl, config=draft_config,
                    attn_impl=serve.attn_impl,
                ),
                donate_argnames=("k_pool", "v_pool"),
                **spec_draft_kw,
            )
            self._draft_prefill_fn = jax.jit(
                _program(
                    "draft_catch_up", _spec_verify_impl, config=draft_config,
                    return_logits=False,
                ),
                donate_argnames=("k_pool", "v_pool"),
                **spec_catchup_kw,
            )
            self._verify_fn = jax.jit(
                _program(
                    "spec_verify", _spec_verify_impl, config=config,
                    return_logits=True,
                ),
                donate_argnames=("k_pool", "v_pool"),
                **spec_verify_kw,
            )
            if self.temperature > 0:
                # One chain split per spec ROUND, and every uniform the
                # round can consume (K draft samples, K acceptance coins,
                # K residual samples, 1 bonus) derived from the sub in one
                # dispatch. Sampled speculation relaxes bit-equality to
                # distribution-equality, so the per-emitted-token split
                # cadence of the sequential path is not replicated here.
                spec_k = self._spec_k

                def _round_entropy(keys):
                    def one(key):
                        key, sub = jax.random.split(key)
                        return key, jax.random.uniform(sub, (3 * spec_k + 1,))
                    return jax.vmap(one)(keys)

                _round_entropy.__name__ = "spec_round_entropy"
                self._spec_keys_fn = jax.jit(_round_entropy)
        get_tracer().event(
            "engine_mesh", mesh=serve.mesh or "single",
            devices=self._dp * self._tp, data=self._dp, tp=self._tp,
        )

    def _serving_weights(self, params, model: str):
        """``gpt2.serving_weights`` of a GPT-2 tree at the engine's dtype.
        Under a tracer, an ``engine_weights`` event says what the cast did:
        ``bytes_given`` / ``bytes_held`` are the whole tree's before and
        after, ``cast_leaves`` how many leaves changed dtype (0: the engine
        holds the caller's arrays), ``ms`` the host's wait for the cast -
        waited for only then, so that an untraced start-up goes on building
        its programs while the device still makes the weights."""
        tracer = get_tracer()
        t0 = time.monotonic()
        held = gpt2.serving_weights(params, self.compute_dtype)
        if tracer.enabled:
            jax.block_until_ready(held)
            given, kept = (jax.tree_util.tree_leaves(t) for t in (params, held))
            tracer.event(
                "engine_weights", model=model,
                dtype=jnp.dtype(self.compute_dtype).name,
                cast_leaves=sum(a is not b for a, b in zip(given, kept)),
                bytes_given=sum(a.nbytes for a in given),
                bytes_held=sum(a.nbytes for a in kept),
                ms=(time.monotonic() - t0) * 1e3,
            )
        return held

    def _mesh_scope(self):
        """Context every device dispatch runs under: activates the serving
        mesh so trace-time mesh discovery (``gpt2.qkv_proj``'s tp branch,
        ``paged_attention``'s auto→xla degrade) sees it. Free no-op on the
        single-device engine."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from gpt_2_distributed_tpu.parallel.mesh import activate_mesh

        return activate_mesh(self.mesh)

    def _slot_shard(self, slot: int) -> int:
        """Data shard owning decode slot ``slot`` (0 on a 1-device engine)."""
        return slot // self._slots_per_shard

    @property
    def kv_pool_bytes_per_device(self) -> int:
        """Per-device bytes of the two KV pools under the serving mesh
        ('data' splits the block axis, 'tp' the head axis), and of whatever
        the family keeps beside them."""
        itemsize = jnp.dtype(self.compute_dtype).itemsize
        return pool_bytes(
            self._pool_view, self.serve, itemsize
        ) // (self._dp * self._tp) + sum(
            a.nbytes for a in jax.tree_util.tree_leaves(self.state))

    # ------------------------------------------------------------- intake

    def _blocks_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        # Positions 0 .. P+max_new-2 get written (the last sampled token is
        # emitted but never processed); worst case ignores early EOS. The
        # formula is invariant under preemption: a resumed request's work
        # prompt plus its remaining tokens end at the same last position.
        return -(-(prompt_len + max_new_tokens - 1) // self.serve.block_size)

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        *,
        rng: jax.Array | int = 0,
        on_token: Callable[[RequestHandle, int], None] | None = None,
        rid: int | None = None,
        timeout_s: float | None = None,
    ) -> RequestHandle:
        """Queue a request. Validation happens HERE (the admission gate),
        with the same ``check_generation_args`` ValueErrors as both decode
        paths — a request the one-shot sampler would reject never enqueues.

        ``rid`` overrides the engine-local id counter: the replica router
        assigns FLEET-unique ids so trace events and API response ids from
        different replicas can never collide. Single-engine callers leave
        it None and get the engine counter (0, 1, 2, ... in submit order).

        ``timeout_s`` sets a wall-clock deadline counted from submission
        (queue wait included). An overdue request is evicted at the next
        step boundary with finish reason ``"timeout"`` and its blocks
        freed — generated-so-far tokens stay on the handle.
        """
        prompt = [int(t) for t in prompt]
        check_generation_args(
            self.config, len(prompt), max_new_tokens, self.top_k, batch=1
        )
        if len(prompt) + max_new_tokens > self._seq_limit:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len ({self._seq_limit})"
            )
        need = self._blocks_needed(len(prompt), max_new_tokens)
        # A request must fit in the SMALLEST data shard (shard 0 also hosts
        # the null block) so admission can always place the queue head once
        # the engine drains; dp=1 reduces to the whole-pool check.
        usable = self.serve.num_blocks // self._dp - 1
        if need > usable:
            raise ValueError(
                f"request needs {need} KV blocks but each data shard only "
                f"has {usable} allocatable (num_blocks="
                f"{self.serve.num_blocks}, block_size={self.serve.block_size}"
                f", data={self._dp}) — it could never be admitted"
            )
        if isinstance(rng, int):
            rng = jax.random.PRNGKey(rng)
        if rid is None:
            rid = self._next_id
            self._next_id += 1
        if timeout_s is not None and timeout_s < 0:
            raise ValueError(f"timeout_s must be >= 0, got {timeout_s}")
        req = RequestHandle(rid, prompt, max_new_tokens, on_token)
        req._key = np.asarray(rng, np.uint32)
        req.submit_time = time.monotonic()
        if timeout_s is not None:
            req.deadline = req.submit_time + timeout_s
            self._deadlines = True
        req._enqueue_time = req.submit_time
        self._queue.append(req)
        get_tracer().event(
            "submit", ts=req.submit_time, rid=req.id,
            prompt_len=len(prompt), max_new_tokens=max_new_tokens,
        )
        return req

    def _alloc_blocks(self, n: int, floor: int, shard: int = 0) -> list[int] | None:
        """n blocks from one data shard's free list while leaving `floor`
        of that shard free, evicting unpinned prefix-cache entries (LRU,
        restricted to that shard's blocks) under pressure."""
        while True:
            if self.allocator.available_in(shard) >= n + floor:
                return self.allocator.alloc(n, shard) if n else []
            if self._cache is None or not self._cache.evict_one(
                self.allocator, shard
            ):
                return None

    def _admit_one(self, slot: int, req: RequestHandle) -> bool:
        """Try to place the queue head into `slot`: prefix-cache lookup,
        block grant (reserve or watermark policy), COW of an
        aligned-cached tail, then prefill (inline for whole-prompt mode,
        deferred to ``_prefill_tick`` for chunked mode)."""
        bs = self.serve.block_size
        shard = self._slot_shard(slot)
        resuming = req._pending_token is not None
        work = np.asarray(
            req.prompt + (req.generated[:-1] if req.generated else []),
            np.int32,
        )
        p_work = len(work)
        need_total = self._blocks_needed(len(req.prompt), req.max_new_tokens)

        shared: list[int] = []
        cow_src: int | None = None
        s0 = 0
        if self._cache is not None:
            hits = self._cache.lookup(work)
            if self._dp > 1:
                # A slot's table only references blocks its own data shard
                # owns (admission capacity, watermark floors and
                # grow/preempt all account per shard) — truncate the hit
                # run at the first foreign-shard block. The run stays a
                # valid prefix: K/V bits are placement-independent.
                keep = 0
                for b in hits:
                    if self.allocator.shard_of(b) != shard:
                        break
                    keep += 1
                del hits[keep:]
            if hits and len(hits) * bs == p_work:
                # Whole prompt cached and block-aligned: the final block
                # must be private (position p_work-1 is recomputed for its
                # logits and scattered back) — copy-on-write it.
                cow_src = hits.pop()
                s0 = p_work - 1
            else:
                s0 = len(hits) * bs
            shared = hits
            # Pin everything we plan to reuse BEFORE allocating: the
            # allocator may evict cache entries under pressure, and an
            # unpinned hit (refcount 1) is exactly what it would take.
            for b in shared:
                self.allocator.retain(b)
            if cow_src is not None:
                self.allocator.retain(cow_src)

        n_shared = len(shared)
        if self.serve.admission == "watermark":
            now_blocks = min(-(-(p_work + 1) // bs), need_total)
            n_alloc = now_blocks - n_shared
            floor = (
                self.serve.watermark_blocks
                if self._has_active_in(shard) else 0
            )
        else:
            n_alloc = need_total - n_shared
            floor = 0
        ids = self._alloc_blocks(max(n_alloc, 0), floor, shard)
        if ids is None:
            for b in shared:        # unwind the pins; head waits its turn
                self.allocator.release([b])
            if cow_src is not None:
                self.allocator.release([cow_src])
            return False

        if cow_src is not None:
            dst = ids[0]            # block index n_shared — the prompt tail
            with self._mesh_scope():
                self.k_pool, self.v_pool = self._copy_fn(
                    self.k_pool, self.v_pool, np.int32(cow_src), np.int32(dst)
                )
            self.allocator.release([cow_src])   # drop the copy-window pin
            self.stats["cow_copies"] += 1
            get_tracer().event("cow", rid=req.id, src=cow_src, dst=dst)

        now = time.monotonic()
        req.queue_wait_ms += (now - req._enqueue_time) * 1e3
        self.stats["queue_wait_ms"] += (now - req._enqueue_time) * 1e3
        req._admit_order = self._admit_seq
        self._admit_seq += 1
        self.stats["admitted"] += 1
        tracer = get_tracer()
        tracer.event(
            "admit", ts=now, rid=req.id, slot=slot,
            queue_wait_ms=(now - req._enqueue_time) * 1e3,
        )
        if resuming or (req.generated and req._pending_token is None):
            req.resumes += 1
            self.stats["resumes"] += 1
            tracer.event("resume", ts=now, rid=req.id, slot=slot)
        if s0:
            self.stats["prefix_hit_tokens"] += s0
            if not req.generated:
                req.prefix_cached_tokens = s0
            tracer.event("prefix_hit", ts=now, rid=req.id, tokens=s0)

        blocks = shared + ids
        req._slot, req._blocks = slot, blocks
        req._work, req._prefill_pos = work, s0
        self._slots[slot] = req
        self.block_table[slot, :] = 0
        self.block_table[slot, :len(blocks)] = blocks
        self.pos[slot] = 0
        self.active[slot] = False

        if self.serve.prefill_chunk == 0:
            # Whole-prompt mode: prefill completes inside admission (the
            # PR 7 contract — TTFT pays the full prompt forward here).
            if s0 == 0 and not resuming:
                self._prefill_whole(slot, req)
            else:
                while self._slots[slot] is req and req._prefill_pos is not None:
                    self._prefill_step(slot, req)
        return True

    def _try_admit(self) -> int:
        """Admit queued requests into free slots, FIFO, while blocks last.

        Sharded engine: a slot's shard fixes which block pool run the
        request lands in, so the head gets one placement attempt PER data
        shard (first free slot of each) before it blocks the queue —
        shard 1 may have room when shard 0 is full. dp=1 reduces to the
        old first-free-slot behavior exactly."""
        admitted = 0
        while self._queue:
            placed = False
            tried: set[int] = set()
            for slot, s in enumerate(self._slots):
                if s is not None:
                    continue
                shard = self._slot_shard(slot)
                if shard in tried:
                    continue
                tried.add(shard)
                if self._admit_one(slot, self._queue[0]):
                    self._queue.popleft()
                    admitted += 1
                    placed = True
                    break
            if not placed:
                break   # head waits for evictions; nothing jumps the queue
        return admitted

    # ------------------------------------------------------------ prefill

    def _prefill_whole(self, slot: int, req: RequestHandle) -> int:
        """PR 7 whole-prompt prefill: bucketed dense forward + block
        scatter. Only for fresh, cache-miss admissions — continuations
        (cache hits, resumes) go through the chunk path, which can start
        mid-sequence."""
        bs = self.serve.block_size
        p = len(req._work)
        nb = -(-p // bs)                       # blocks prefill fills
        pb = nb * bs                           # scatter width
        pf = min(pb, self.config.n_positions)  # forward width
        prompt_arr = np.zeros((1, pf), np.int32)
        prompt_arr[0, :p] = req._work
        tracer = get_tracer()
        t0 = time.monotonic()
        with self._mesh_scope():
            first, key, k, v = self._prefill_fn(
                self.params, prompt_arr, np.int32(p), req._key, pb,
            )
            scatter_span = (
                tracer.span("shard_scatter", blocks=nb)
                if self.mesh is not None else contextlib.nullcontext()
            )
            with scatter_span:
                self.k_pool, self.v_pool = self._scatter_fn(
                    self.k_pool, self.v_pool, k, v,
                    np.asarray(req._blocks[:nb], np.int32),
                )
        first.block_until_ready()
        dur_ms = (time.monotonic() - t0) * 1e3
        self.stats["prefill_ms"] += dur_ms
        self.stats["prefills"] += 1
        self.stats["prefill_dispatches"] += 1
        get_tracer().event(
            "prefill_chunk", rid=req.id, n_tokens=p, dur_ms=dur_ms,
            whole=True,
        )
        req._prefill_pos = None
        self._register_prefix(req)
        return self._activate(slot, req, p, first, key)

    def _prefill_step(self, slot: int, req: RequestHandle) -> int:
        """Advance one prefill chunk for one request; on the final chunk,
        activate the decode row. Returns tokens emitted (1 when a fresh
        request's first token fires)."""
        s = req._prefill_pos
        p_work = len(req._work)
        if self.serve.prefill_chunk:
            width = self.serve.prefill_chunk
        elif req.generated:
            # Preemption resume: the work prompt grows with every generated
            # token, so bucketing its remainder would compile a fresh width
            # per resume length. One full-width program covers them all.
            width = self._m * self.serve.block_size
        else:
            # Fresh-admission cache-hit continuation: the remainder is
            # bounded by the prompt, so these share the same block-multiple
            # buckets the whole-prompt path compiles anyway.
            bs = self.serve.block_size
            width = min(-(-(p_work - s) // bs) * bs, self._m * bs)
        return self._prefill_rows([slot], width, 1)

    def _prefill_rows(self, slots: list[int], width: int,
                      pad_rows: int) -> int:
        """Advance one prefill chunk for each slot in ``slots`` in ONE
        batched dispatch and wait for it. Returns tokens emitted."""
        return self._settle_chunk(self._dispatch_chunk(slots, width, pad_rows))

    def _dispatch_chunk(self, slots: list[int], width: int,
                        pad_rows: int) -> _Chunk:
        """Dispatch one prefill chunk for each slot in ``slots`` in ONE
        batched program call (rows padded to ``pad_rows`` with ``clen=0`` so
        the program's shape — and so its compile — is independent of how
        many prefills happen to be in flight). The call returns async:
        ``_settle_chunk`` reads its first tokens."""
        r = max(pad_rows, len(slots))
        bt = np.zeros((r, self._m), np.int32)
        chunk = np.zeros((r, width), np.int32)
        start = np.zeros((r,), np.int32)
        clen = np.zeros((r,), np.int32)
        keys = np.zeros((r, 2), np.uint32)
        for i, slot in enumerate(slots):
            req = self._slots[slot]
            s = req._prefill_pos
            cl = min(width, len(req._work) - s)
            bt[i] = self.block_table[slot]
            chunk[i, :cl] = req._work[s:s + cl]
            start[i] = s
            clen[i] = cl
            keys[i] = req._key
        self._count_prefill(start, clen)
        t0 = time.monotonic()
        with self._mesh_scope():
            if self.state is None:
                first, out_keys, self.k_pool, self.v_pool = self._chunk_fn(
                    self.params, self.k_pool, self.v_pool,
                    bt, chunk, start, clen, keys,
                )
            else:
                (first, out_keys, self.k_pool, self.v_pool,
                 self.state) = self._chunk_fn(
                    self.params, self.k_pool, self.v_pool, self.state,
                    bt, chunk, start, clen, keys,
                    np.asarray(slots, np.int32),
                )
        return _Chunk(first, out_keys, slots, clen[:len(slots)].tolist(), t0)

    def _settle_chunk(self, chunk: _Chunk) -> int:
        """Wait for a dispatched chunk and settle its rows: a prompt with
        tokens left moves on, one that is through opens its decode row with
        the first token sampled. Returns tokens emitted.

        prefill_ms holds the chunk and nothing else: its clock starts where
        the device does, at the dispatch or - where a decode step was still
        running then - at that step's read-back, the end of the decode
        turn's own clock (``_turned_at``). The turn's emit loop runs beside
        the chunk and is on both clocks."""
        chunk.first.block_until_ready()
        dur_ms = (time.monotonic() - max(chunk.at, self._turned_at)) * 1e3
        r = chunk.keys.shape[0]
        first_host = self._count_behind(np.asarray(chunk.first), r)
        keys_host = np.asarray(chunk.keys)
        self.stats["prefill_ms"] += dur_ms
        self.stats["prefill_dispatches"] += 1
        self.stats["prefill_batched"] += max(len(chunk.slots) - 1, 0)
        tracer = get_tracer()
        emitted = 0
        for i, slot in enumerate(chunk.slots):
            req = self._slots[slot]
            cl = chunk.lens[i]
            self.stats["prefill_chunks"] += 1
            tracer.event(
                "prefill_chunk", rid=req.id, n_tokens=cl, dur_ms=dur_ms,
                whole=False,
            )
            s = req._prefill_pos + cl
            if s < len(req._work):
                req._prefill_pos = s
                continue
            self.stats["prefills"] += 1
            req._prefill_pos = None
            self._register_prefix(req)
            emitted += self._activate(
                slot, req, len(req._work), first_host[i], keys_host[i]
            )
        return emitted

    def _activate(self, slot: int, req: RequestHandle, p_work: int,
                  first, key) -> int:
        """Prefill done: emit the sampled first token (fresh requests) or
        restore the carried pending token (resumes — no re-emit, no
        resample), then open the decode row."""
        emitted = 0
        if req._pending_token is None:
            first_i = int(first)
            req.generated.append(first_i)
            self.stats["tokens_out"] += 1
            emitted = 1
            req._emit(first_i)
            if self.serve.eos_id is not None and first_i == self.serve.eos_id:
                self._evict(slot, "eos")
                return emitted
            if len(req.generated) >= req.max_new_tokens:
                self._evict(slot, "length")
                return emitted
            self.tokens[slot] = first_i
            self.keys[slot] = np.asarray(key)
        else:
            # Resume: the preempted request's last sampled token was
            # already emitted and already passed the EOS/length gates —
            # it becomes the decode input, and the chunk fn's sampled
            # token/advanced key are discarded in favor of the saved
            # chain head (bit-parity: one split per sampled token).
            self.tokens[slot] = req._pending_token
            req._pending_token = None
            self.keys[slot] = np.asarray(req._key)
        self.pos[slot] = p_work
        self.active[slot] = True
        self._fresh[slot] = True
        self._left[slot] = req.max_new_tokens - len(req.generated)
        return emitted

    def _register_prefix(self, req: RequestHandle) -> None:
        """Hash-cons every full work-prompt block into the prefix cache
        (first writer wins; hits re-register as no-ops). Valid for resumed
        work prompts too: K/V at position i is a pure function of
        tokens[0..i], so a block is reusable by ANY request whose prompt
        starts with the same tokens — whether they came from a prompt or
        from generation."""
        if self._cache is None:
            return
        w = req._work
        for j in range(len(w) // self.serve.block_size):
            self._cache.insert(w, j, req._blocks[j], self.allocator)

    def _prefill_slots(self) -> list[int]:
        """Chunked mode: the slots this step's prefill dispatch advances -
        up to ``ServeConfig.prefill_batch`` in-progress prefills, oldest
        first; empty when there is none (or in whole-prompt mode)."""
        if self.serve.prefill_chunk == 0:
            return []
        cands = sorted(
            (self._slots[s]._admit_order, s)
            for s in range(self.serve.max_batch)
            if self._slots[s] is not None
            and self._slots[s]._prefill_pos is not None
        )
        return [s for _, s in cands[:self.serve.prefill_batch]]

    def _prefill_tick(self, slots: list[int]) -> _Chunk | None:
        """Chunked mode: dispatch one chunk for each of ``slots``, in ONE
        batched dispatch per engine step; decode steps interleave between
        chunks, which is the whole point. Rows pad to ``prefill_batch`` so
        the dispatch compiles once regardless of how many prefills are in
        flight (``prefill_batch=1`` is exactly the old one-row tick). The
        step settles what this returns (``_settle_chunk``)."""
        if not slots:
            return None
        return self._dispatch_chunk(
            slots, self.serve.prefill_chunk, self.serve.prefill_batch
        )

    # -------------------------------------------------------------- churn

    def _release_slot(self, slot: int) -> None:
        req = self._slots[slot]
        self.allocator.release(req._blocks)
        req._slot, req._blocks = None, None
        req._work, req._prefill_pos = None, None
        self._slots[slot] = None
        # Table row back to the null block; the slot decodes as a no-op
        # (length 0) until the next admission overwrites it.
        self.block_table[slot, :] = 0
        self.pos[slot] = 0
        self.active[slot] = False
        self._left[slot] = 0
        if self._spec_k and self._draft_blocks[slot] is not None:
            # Draft KV dies with the slot — it is disposable state, never
            # carried through preemption or migration (the next occupant
            # re-drafts via the catch-up pass).
            self._draft_alloc.release(self._draft_blocks[slot])
            self._draft_blocks[slot] = None
            self.draft_table[slot, :] = 0
            self._draft_pos[slot] = 0

    def _evict(self, slot: int, reason: str) -> None:
        req = self._slots[slot]
        req._finish(reason)
        self._release_slot(slot)
        self.stats["finished"] += 1

    def _preempt(self, slot: int) -> None:
        """Swap a request out: free its blocks, requeue it at the head
        with its generated tokens as recompute-prefill. The last sampled
        token (already emitted) is carried as the pending decode input so
        the resume neither re-emits nor resamples."""
        req = self._slots[slot]
        req.preemptions += 1
        self.stats["preemptions"] += 1
        if req._prefill_pos is None:
            # Decoding: the slot key is the live chain head. (A request
            # preempted mid-prefill never advanced its chain — req._key
            # already holds the head.)
            req._key = np.array(self.keys[slot])
        req._pending_token = req.generated[-1] if req.generated else None
        self._release_slot(slot)
        req._enqueue_time = time.monotonic()
        get_tracer().event(
            "preempt", ts=req._enqueue_time, rid=req.id, slot=slot,
            n_generated=len(req.generated),
        )
        self._queue.appendleft(req)

    def _overdue_slots(self, now: float) -> list[int]:
        return [slot for slot, req in enumerate(self._slots)
                if req is not None and req.deadline is not None
                and now >= req.deadline]

    def _evict_overdue(self, now: float) -> int:
        """Evict every request past its deadline at ``now`` — slotted rows
        via ``_evict`` (blocks freed, slot reopened), queued requests by
        removal. Runs at step boundaries only when some live request
        actually carries a deadline, so deadline-free deployments pay
        nothing."""
        if not self._deadlines:
            return 0
        evicted = 0
        for slot in self._overdue_slots(now):
            self._evict(slot, "timeout")
            self.stats["timeouts"] += 1
            evicted += 1
        overdue = [r for r in self._queue
                   if r.deadline is not None and now >= r.deadline]
        for req in overdue:
            self._queue.remove(req)
            req._finish("timeout")
            self.stats["finished"] += 1
            self.stats["timeouts"] += 1
            evicted += 1
        self._deadlines = any(
            r is not None and r.deadline is not None
            for r in list(self._slots) + list(self._queue)
        )
        return evicted

    # ---------------------------------------------------------- migration

    def extract_inflight(self) -> list[RequestHandle]:
        """Detach every live request from this engine for migration to
        another replica, in admission order (slotted rows first, then the
        queue). Captures exactly the preemption state ``_preempt`` saves —
        generated tokens plus the per-slot PRNG chain head — so a healthy
        engine's ``adopt`` resumes each stream bit-identically with zero
        re-emitted tokens. Block release is best-effort: the engine is
        presumed failed and its pools are abandoned with it.

        The unread decode step is read back first, so that the state
        captured is the state after every token sampled; where the device
        no longer answers, that step is lost with the engine, and a request
        that had left its slot with its last token in it goes along with
        the slotted ones to sample it again."""
        try:
            self.collect()
        except Exception as e:
            self._unread = None
            print(f"[serve] extract_inflight: the unread decode step is lost "
                  f"with the engine ({type(e).__name__}: {e})",
                  file=sys.stderr, flush=True)
        out = sorted(
            (req for req in self._parting.values() if not req.done),
            key=lambda req: req._admit_order,
        )
        self._parting.clear()
        for req in out:
            req._pending_token = req.generated[-1]
        slotted = sorted(
            (s for s in range(self.serve.max_batch)
             if self._slots[s] is not None),
            key=lambda s: self._slots[s]._admit_order,
        )
        for slot in slotted:
            req = self._slots[slot]
            if req._prefill_pos is None:
                # Decoding: the slot key is the live chain head (mid-
                # prefill requests never advanced theirs — req._key
                # already holds it). Same capture as _preempt.
                req._key = np.array(self.keys[slot])
            req._pending_token = req.generated[-1] if req.generated else None
            try:
                self._release_slot(slot)
            except Exception:
                # A failed engine's allocator may be inconsistent; the
                # request state above is host-side and already safe.
                self._slots[slot] = None
            out.append(req)
        out.extend(self._queue)
        self._queue.clear()
        return out

    def decode_keys(self) -> dict[int, list[int]]:
        """Post-step PRNG chain heads for every decode-active slotted
        request, keyed by rid. The worker RPC sends this after each step
        so the frontend's request mirrors always hold the same chain head
        ``extract_inflight`` would capture — a worker SIGKILLed between
        steps migrates from the mirrors with zero re-emission. Requests
        queued or mid-prefill are absent: their chain never advanced, the
        mirror's last-known key is already the head. The unread decode
        step is read back first: a chain head goes with the tokens it has
        sampled."""
        self.collect()
        return {
            req.id: [int(k) for k in self.keys[slot]]
            for slot, req in enumerate(self._slots)
            if req is not None and req._prefill_pos is None
        }

    def adopt(self, req: RequestHandle) -> None:
        """Enqueue a request extracted from another replica. No
        validation — it already passed ``submit``'s gates on an engine
        with an identical ``ServeConfig`` — and no new trace event id:
        the handle (and its rid, callbacks, emitted tokens) carries over
        whole."""
        req._enqueue_time = time.monotonic()
        if req.deadline is not None:
            self._deadlines = True
        self._queue.append(req)

    def _grow_tables(self) -> None:
        """Watermark mode, before each decode step: every active row about
        to write into an unallocated block gets one. On pool exhaustion,
        preempt the NEWEST-admitted request (possibly a prefilling one)
        and retry — oldest-first iteration means an old request steals
        from newer ones, never the reverse, so the oldest always runs to
        completion and the engine cannot livelock.

        False: the pool is exhausted with a decode step unread. A victim
        carries its last sampled token and its chain head away with it, so
        the caller reads that step back (which may free blocks by itself)
        and calls again."""
        bs = self.serve.block_size
        order = sorted(
            (s for s in range(self.serve.max_batch)
             if self._slots[s] is not None and self.active[s]),
            key=lambda s: self._slots[s]._admit_order,
        )
        for slot in order:
            req = self._slots[slot]
            if req is None or not self.active[slot]:
                continue    # preempted by an older row's growth below
            shard = self._slot_shard(slot)
            # A speculative round writes up to ``spec_k`` positions past
            # ``pos`` (the verify window) before the next grow pass runs, so
            # pre-grow to cover the whole window — clamped to the last
            # position the request can ever legally write (``hard``), which
            # keeps the final block count identical to the non-spec engine.
            hard = len(req.prompt) + req.max_new_tokens - 2
            last = min(int(self.pos[slot]) + (self._spec_k or 0), hard)
            while last // bs >= len(req._blocks):
                ids = self._alloc_blocks(1, 0, shard)
                if ids is not None:
                    req._blocks.append(ids[0])
                    self.block_table[slot, len(req._blocks) - 1] = ids[0]
                    continue
                # Preemption frees blocks on the starved SHARD — a foreign
                # shard's newest request can't help (never empty: `slot`
                # itself is a candidate).
                victim = max(
                    (s for s in range(self.serve.max_batch)
                     if self._slots[s] is not None
                     and self._slot_shard(s) == shard),
                    key=lambda s: self._slots[s]._admit_order,
                )
                if self._unread is not None:
                    return False
                self._preempt(victim)
                if victim == slot:
                    break   # preempted ourselves: row is gone (safety net —
                            # submit() guarantees one request always fits)
        return True

    def _has_active(self) -> bool:
        return any(s is not None for s in self._slots)

    def _has_active_in(self, shard: int) -> bool:
        """Any occupied slot on one data shard — the watermark floor is
        per shard (each shard's pool run grows independently)."""
        lo = shard * self._slots_per_shard
        return any(
            s is not None for s in self._slots[lo:lo + self._slots_per_shard]
        )

    def has_work(self) -> bool:
        """Anything queued, in a slot, or sampled and not yet read back —
        the driver's step/skip gate."""
        return (bool(self._queue) or self._has_active()
                or self._unread is not None)

    @property
    def queue_depth(self) -> int:
        """Requests accepted but not yet admitted to a slot."""
        return len(self._queue)

    @property
    def occupancy(self) -> int:
        """Occupied decode slots (prefilling rows included)."""
        return sum(s is not None for s in self._slots)

    @property
    def prefix_cache(self) -> PrefixCache | None:
        """The engine's prefix cache (None when ``serve.prefix_cache`` is
        off) — the router's affinity probe reads it, never writes."""
        return self._cache

    def step(self) -> int:
        """One engine step: admit what fits, dispatch one prefill chunk
        (chunked mode), grow/preempt block tables (watermark mode), then
        one turn of the decode loop: dispatch the compiled decode step for
        every active row, and read back and emit the tokens of the step
        dispatched the turn BEFORE, while this one runs; last, read the
        chunk's first token. Returns tokens emitted this step (the samples
        read back + prefill first-tokens)."""
        if not self.has_work():
            return 0
        tracer = get_tracer()
        self.stats["steps"] += 1
        with self._phase(tracer, "engine_step", "step_ms",
                         n=self.stats["decode_steps"]):
            return self._step_impl(tracer)

    def collect(self) -> int:
        """Read the unread decode step back and emit its tokens, if there
        is one: afterwards the host holds every token sampled and every
        chain head. Whatever must see those calls this first - inside a
        step the loop does it itself. Returns tokens emitted."""
        if self._unread is None:
            return 0
        tracer = get_tracer()
        with self._phase(tracer, "engine_step", "step_ms",
                         n=self.stats["decode_steps"]):
            return self._decode_turn(tracer, dispatch=False)

    def close(self) -> None:
        """Leave nothing unread (``EngineDriver.close``): a token sampled
        is a token delivered."""
        self.collect()

    def _phase(self, tracer, name: str, *keys: str, **attrs) -> _Phase:
        """Span ``name`` and the clock of the counters ``keys``, as one."""
        return _Phase(self.stats, keys, tracer.span(name, **attrs))

    def _step_impl(self, tracer) -> int:
        # Where the step is about to do something that must see every token
        # sampled so far, the unread decode step is read back first (a turn
        # of the loop that dispatches nothing); everything else runs while
        # the device computes it.
        emitted = 0
        now = time.monotonic() if self._deadlines else 0.0
        if self._unread is not None and (
            # an overdue row's stream ends with its last sampled token
            (self._deadlines and self._overdue_slots(now))
            # whole-prompt mode prefills inside admission (see below)
            or (self.serve.prefill_chunk == 0 and self._queue
                and None in self._slots)
        ):
            emitted += self._decode_turn(tracer, dispatch=False)
        prefill_ms = self.stats["prefill_ms"]
        with self._phase(tracer, "admit", "admit_ms"):
            self._evict_overdue(now)
            self._try_admit()
        # Whole-prompt mode prefills inside admission: that is prefill_ms.
        self.stats["admit_ms"] -= self.stats["prefill_ms"] - prefill_ms
        # A prefill chunk is dispatched behind the decode step in flight and
        # settled after this step's decode turn: the device goes from that
        # step to the chunk to the next decode step while the host reads,
        # emits and builds. The chunk's row joins the decode step after its
        # first token is read. Where something below acts on the chunk's row
        # before that - a speculative round, a growth that may preempt it -
        # the chunk is settled at once, behind a read-back of the step in
        # flight so that prefill_ms still holds the chunk and nothing else.
        slots = self._prefill_slots()
        at_once = bool(self._spec_k) or self.serve.admission == "watermark"
        if slots and at_once and self._unread is not None:
            emitted += self._decode_turn(tracer, dispatch=False)
        with tracer.span("prefill"):
            chunk = self._prefill_tick(slots)
            if chunk is not None and at_once:
                emitted += self._settle_chunk(chunk)
                chunk = None
        if self.serve.admission == "watermark" and self.active.any():
            with self._phase(tracer, "grow", "grow_ms"):
                grown = self._grow_tables()
            if not grown:
                emitted += self._decode_turn(tracer, dispatch=False)
                with self._phase(tracer, "grow", "grow_ms"):
                    self._grow_tables()
        dispatch = bool(self.active.any())
        if dispatch and self._spec_k:
            # Two-model step: draft k tokens, verify them in one target
            # pass, emit the accepted prefix (plus a bonus token when the
            # whole draft survives). Replaces the single decode dispatch;
            # acceptance is decided on token values, so nothing stays unread.
            return emitted + self._spec_round(tracer)
        if dispatch or self._unread is not None:
            emitted += self._decode_turn(tracer, dispatch)
        if chunk is not None:
            with tracer.span("prefill"):
                emitted += self._settle_chunk(chunk)
        return emitted

    def _decode_turn(self, tracer, dispatch: bool) -> int:
        """One turn of the decode loop: dispatch the decode step of the
        active rows (``dispatch``; else the turn only collects), THEN read
        back the step dispatched the turn before, which the device has
        finished or is about to while it already holds the new one, and
        emit its tokens. Returns tokens emitted.

        `decode` is decode_ms: the dispatch, which returns async, and the
        token read-back the scheduler blocks on - of the step BEFORE - each
        a child span. In the sharded engine the read-back is the cross-shard
        all-gather of the row-sharded sampled tokens, and is named for it."""
        unread = self._unread
        rows = self.active if dispatch else unread.rows
        with self._phase(tracer, "decode", "decode_ms", rows=int(rows.sum())):
            self._unread = self._dispatch_decode(tracer) if dispatch else None
            if unread is not None:
                with tracer.span(
                    "readback" if self.mesh is None else "token_allgather",
                    rows=int(unread.rows.sum()),
                ):
                    toks_host = self._count_behind(
                        np.asarray(unread.tokens), self.serve.max_batch)
        self._turned_at = time.monotonic()
        if unread is None:
            return 0
        with self._phase(tracer, "emit", "emit_ms"):
            return self._emit_step(unread, toks_host)

    def _dispatch_decode(self, tracer) -> _Unread:
        """Dispatch one decode step for every active row and settle what
        does not hang on a token's value: positions advance, and a row whose
        token in this step is its ``max_new_tokens``-th leaves its slot and
        its blocks now (the device runs this step, then whatever is
        admitted into them, in order).

        The step's ``tokens`` and ``keys`` are the step before's outputs, on
        the device (``_feed_fn``). The host arrays handed over are private
        copies: a program may read a host argument after its call returns,
        and the scheduler writes its own in place."""
        rows = self.active.copy()
        self._count_decode(rows)
        with self._phase(tracer, "dispatch", "decode_dispatch_ms"), \
                self._mesh_scope():
            tokens, keys = self._feed_fn(
                *self._sampled, self.tokens.copy(), self.keys.copy(),
                self._fresh)
            block_table = self.block_table.copy()
            if self.state is None:
                sampled, chains, self.k_pool, self.v_pool = self._decode_fn(
                    self.params, self.k_pool, self.v_pool,
                    block_table, tokens, self.pos, rows, keys,
                )
            else:
                (sampled, chains, self.k_pool, self.v_pool,
                 self.state) = self._decode_fn(
                    self.params, self.k_pool, self.v_pool, self.state,
                    block_table, tokens, self.pos, rows, keys,
                )
        sampled.copy_to_host_async()
        chains.copy_to_host_async()
        self._sampled = (sampled, chains)
        self._fresh = np.zeros_like(self._fresh)
        self.stats["decode_steps"] += 1
        self.stats["decode_overlapped"] += self._unread is not None
        self.pos = np.where(rows, self.pos + 1, self.pos)
        self._left[rows] -= 1
        reqs = list(self._slots)
        last = rows & (self._left == 0)
        for slot in np.flatnonzero(last).tolist():
            req = reqs[slot]
            req._key = np.array(self.keys[slot])
            self._parting[req.id] = req
            self._release_slot(slot)
        return _Unread(sampled, chains, rows, reqs, last)

    def _emit_step(self, step: _Unread, toks_host: np.ndarray) -> int:
        """The engine's one emit loop, over a decode step's tokens as read
        back: append and stream each row's token, end the streams that end
        (EOS by value; length as settled at dispatch), keep the chain heads."""
        keys_host = np.asarray(step.keys)
        eos_id = self.serve.eos_id
        decoded = 0
        for slot in np.flatnonzero(step.rows).tolist():
            req = step.reqs[slot]
            if req.done:
                continue    # ran a step past its EOS: that token is dropped
            t = int(toks_host[slot])
            req.generated.append(t)
            decoded += 1
            req._emit(t)
            eos = eos_id is not None and t == eos_id
            if self._slots[slot] is req:
                self.keys[slot] = keys_host[slot]
                if eos:
                    self._evict(slot, "eos")
                continue
            # The request left its slot when its last step was dispatched:
            # this one, or the one still unread (its chain head stays with
            # it, should that step be lost).
            req._key = keys_host[slot]
            if eos or step.last[slot]:
                del self._parting[req.id]
                req._finish("eos" if eos else "length")
                self.stats["finished"] += 1
        self.stats["tokens_out"] += decoded  # prefill firsts counted at emit
        return decoded

    def _count_decode(self, was_active: np.ndarray) -> int:
        """Rows of this decode step, counted with the keys they attend and
        with the table slots that hold keys, of all the rows' slots."""
        rows = int(was_active.sum())
        positions = self.pos[was_active]
        self.stats["decode_rows"] += rows
        self.stats["decode_attended"] += self._count_attended(positions, True)
        self.stats["decode_blocks_live"] += rows + int(
            (positions // self.serve.block_size).sum())
        self.stats["decode_blocks_table"] += rows * self._m
        return rows

    def _count_attended(self, positions: np.ndarray, decode: bool = False) -> int:
        """Keys that queries at ``positions`` attend in one layer: all up to
        their own, unless the family says otherwise (one that selects
        blocks); a family's own host-side counters count the dispatch's rows
        here too."""
        if self._family is None or self._family.count_rows is None:
            return dense_attended(positions)
        return self._family.count_rows(
            self.stats, self.config, self.serve.block_size, positions, decode)

    def _count_behind(self, tokens_host: np.ndarray, rows: int) -> np.ndarray:
        """A program's tokens, read back: the ``rows`` sampled tokens, with
        what a family's program put behind them (``Family.counters``) added
        to the counters of those names."""
        if self._family is not None:
            for name, value in zip(self._family.counters, tokens_host[rows:]):
                self.stats[name] += int(value)
        return tokens_host[:rows]

    def _count_prefill(self, start: np.ndarray, clen: np.ndarray) -> None:
        """Tokens of this chunk dispatch, counted with the keys they attend;
        a chunk that opens a request starts its recurrent state from zero."""
        for s, n in zip(start.tolist(), clen.tolist()):
            self.stats["prefill_tokens"] += n
            self.stats["prefill_attended"] += self._count_attended(
                np.arange(s, s + n))
            if self.state is not None and n and s == 0:
                self.stats["state_resets"] += 1

    def _spec_round(self, tracer) -> int:
        """One speculative two-model step for every active row.

        Shape of a round (K = ``spec_k``):

        1. Draft catch-up (only when some row's draft-KV frontier trails
           ``pos``: fresh admissions, preemption resumes, adoptions) —
           one chunked pass of the committed tokens through the draft
           model rebuilds its disposable KV.
        2. K+1 draft decode steps: step i processes the token at position
           ``pos + i`` (step 0 the committed pending token, then each
           proposal) and proposes ``d_{i+1}``. The (K+1)-th step is
           KV-only — it writes ``d_K``'s draft KV so the frontier lands
           exactly on the new ``pos`` whatever the acceptance outcome,
           and the steady state never needs catch-up.
        3. ONE target verify pass: a (K+1)-token window
           ``[pending, d_1 .. d_K]`` at positions ``pos ..`` through
           ``spec_verify_attention`` — logits[i] is the target
           distribution for position ``pos + i + 1``.
        4. Host acceptance. Greedy: accept while the draft token equals
           the verify argmax; every emitted token IS a verify argmax, so
           streams are bit-equal to sequential decode for any K. Sampled
           (Leviathan/Chen): accept ``d`` with prob ``min(1, p(d)/q(d))``,
           resample rejections from ``max(p-q, 0)`` normalized, bonus
           token from ``p_K`` after a clean sweep — emitted tokens are
           exactly target-distributed. All uniforms for the round come
           from ONE split of each slot's threefry chain head
           (``_spec_keys_fn``).

        Rolled-back target KV (positions past the accepted prefix) stays
        in the pool as garbage that the per-sequence length masks already
        make invisible — the same invariance the non-spec engine relies
        on for preemption."""
        K = self._spec_k
        B = self.serve.max_batch
        was_active = self.active.copy()
        rows = self._count_decode(was_active)

        # Lazy draft-block grant: full per-slot capacity (draft_serve_view)
        # means this can never fail, so there is no draft preemption path.
        for slot in range(B):
            if was_active[slot] and self._draft_blocks[slot] is None:
                ids = self._draft_alloc.alloc(
                    self._draft_m, self._slot_shard(slot)
                )
                self._draft_blocks[slot] = ids
                self.draft_table[slot, :len(ids)] = ids

        sampled = self.temperature > 0
        if sampled:
            new_keys, unis = self._spec_keys_fn(self.keys)
            unis = np.asarray(unis, np.float64)    # [B, 3K+1]
            self.keys = np.where(
                was_active[:, None], np.array(new_keys), self.keys
            )

        with self._phase(tracer, "draft", "draft_ms", "decode_ms",
                         rows=rows, k=K), self._mesh_scope():
            clen_cu = np.where(
                was_active, self.pos - self._draft_pos, 0
            ).astype(np.int32)
            if clen_cu.any():
                width = self._draft_m * self._draft_serve.block_size
                chunk = np.zeros((B, width), np.int32)
                for slot in range(B):
                    n = int(clen_cu[slot])
                    if not n:
                        continue
                    req = self._slots[slot]
                    seq = req.prompt + req.generated
                    d0 = int(self._draft_pos[slot])
                    chunk[slot, :n] = seq[d0:d0 + n]
                self.dk_pool, self.dv_pool = self._draft_prefill_fn(
                    self.draft_params, self.dk_pool, self.dv_pool,
                    self.draft_table, chunk,
                    self._draft_pos.astype(np.int32), clen_cu,
                )
            cur_tok = self.tokens.astype(np.int32)
            cur_pos = self.pos.astype(np.int32)
            d_toks = np.zeros((B, K), np.int32)
            q_list: list[np.ndarray] = []
            for i in range(K + 1):
                logits, self.dk_pool, self.dv_pool = self._draft_fn(
                    self.draft_params, self.dk_pool, self.dv_pool,
                    self.draft_table, cur_tok, cur_pos, was_active,
                )
                if i == K:
                    break    # KV-only step: its proposal is never used
                dl = np.asarray(logits)                    # [B, V] fp32
                if sampled:
                    q = _spec_probs(dl, self.temperature, self.top_k)
                    q_list.append(q)
                    d = np.array(
                        [_spec_cdf_sample(q[s], unis[s, i]) for s in range(B)],
                        np.int32,
                    )
                else:
                    d = dl.argmax(axis=-1).astype(np.int32)
                d_toks[:, i] = d
                cur_tok = d
                cur_pos = cur_pos + 1
        self.stats["spec_draft_tokens"] += K * rows

        with self._phase(tracer, "verify", "verify_ms", "decode_ms",
                         rows=rows, k=K):
            with self._phase(tracer, "dispatch", "decode_dispatch_ms"):
                vtoks = np.zeros((B, K + 1), np.int32)
                vtoks[:, 0] = self.tokens
                vtoks[:, 1:] = d_toks
                vclen = np.where(was_active, K + 1, 0).astype(np.int32)
                with self._mesh_scope():
                    vlogits, self.k_pool, self.v_pool = self._verify_fn(
                        self.params, self.k_pool, self.v_pool,
                        self.block_table, vtoks, self.pos.astype(np.int32),
                        vclen,
                    )
            with tracer.span("readback"):
                vlogits = np.asarray(vlogits)    # [B, K+1, V] — the device sync
        self.stats["decode_steps"] += 1

        with self._phase(tracer, "emit", "emit_ms"):
            decoded = 0
            now = time.monotonic()
            for slot in range(B):
                req = self._slots[slot]
                if req is None or not was_active[slot]:
                    continue
                emit, accepted = _spec_accept(
                    vlogits[slot], d_toks[slot],
                    [q[slot] for q in q_list] if sampled else None,
                    unis[slot] if sampled else None,
                    self.temperature, self.top_k,
                )
                self.stats["spec_accepted_tokens"] += accepted
                if accepted < K:
                    self.stats["spec_rollbacks"] += 1
                tracer.event(
                    "spec_accept", ts=now, rid=req.id,
                    drafted=K, accepted=accepted,
                )
                done = None
                n_emitted = 0
                for t in emit:
                    req.generated.append(t)
                    decoded += 1
                    n_emitted += 1
                    req._emit(t)
                    if self.serve.eos_id is not None and t == self.serve.eos_id:
                        done = "eos"     # later emissions are dropped whole —
                        break            # sequential decode never produces them
                    if len(req.generated) >= req.max_new_tokens:
                        done = "length"
                        break
                if done is not None:
                    self._evict(slot, done)
                    continue
                self.pos[slot] += n_emitted
                self.tokens[slot] = emit[n_emitted - 1]
                # Round invariant: the K+1 draft steps covered positions
                # pos .. pos+K with tokens matching every committed prefix
                # outcome, so the draft frontier lands exactly on the new pos.
                self._draft_pos[slot] = self.pos[slot]
            self.stats["tokens_out"] += decoded
        return decoded

    def run_until_idle(self, max_steps: int | None = None) -> int:
        """Drive ``step`` until the queue and every slot drain. Returns
        total tokens emitted. ``submit``'s block-need check guarantees the
        queue head can always be admitted once the engine is empty (the
        watermark floor is waived for an empty engine), so this
        terminates."""
        total = 0
        steps = 0
        while self.has_work():
            total += self.step()
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError(
                    f"run_until_idle: exceeded max_steps={max_steps} with "
                    f"{len(self._queue)} queued / "
                    f"{sum(s is not None for s in self._slots)} in flight"
                )
        return total

    # ------------------------------------------------------------ metrics

    def metrics_snapshot(self) -> dict[str, float]:
        """Current serving-load metrics, named for the TB sink
        (``metrics/builtin.py`` registers each under ``serve/``)."""
        adm = max(self.stats["admitted"], 1)
        return {
            "queue_wait_ms": self.stats["queue_wait_ms"] / adm,
            "preempted": float(self.stats["preemptions"]),
            "prefix_cached_tokens": float(self.stats["prefix_hit_tokens"]),
            "serve_queue_depth": float(len(self._queue)),
            "serve_occupancy": float(
                sum(s is not None for s in self._slots)
            ),
            "serve_mesh_devices": float(self._dp * self._tp),
            "kv_pool_bytes_per_device": float(self.kv_pool_bytes_per_device),
            "weight_bytes": float(self.weight_bytes),
            "prefill_batched": float(self.stats["prefill_batched"]),
            "spec_draft_tokens": float(self.stats["spec_draft_tokens"]),
            "spec_accepted_tokens": float(
                self.stats["spec_accepted_tokens"]
            ),
            "spec_rollbacks": float(self.stats["spec_rollbacks"]),
            "draft_ms": float(self.stats["draft_ms"]),
            "verify_ms": float(self.stats["verify_ms"]),
            **step_clocks([self.stats]),
        }

    def clear_prefix_cache(self) -> None:
        """Drop every unpinned prefix-cache entry and return its blocks
        (bench isolation between warmup and the measured run); whatever
        was sampled before is read back and emitted first."""
        self.collect()
        if self._cache is not None:
            self._cache.clear(self.allocator)
