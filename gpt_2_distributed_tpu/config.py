"""Model configuration.

Mirrors the reference's frozen ``GPT2Config`` dataclass surface
(``/root/reference/model.py:26-57``): same field meanings and same defaults
(GPT-2 124M: vocab 50257, 1024 positions, 768 width, 12 layers, 12 heads,
0.1 dropouts, LN eps 1e-5, init std 0.02). Extends it with the 345M/774M/1.5B
presets that BASELINE.json's configs require but the reference hard-codes out
(``/root/reference/train_gpt2_distributed.py:42-44`` only ever builds 124M).

TPU-first additions: ``remat`` (activation checkpointing for the 774M/1.5B
configs) and ``scan_layers`` (stack per-layer params on a leading axis and run
the block stack as one ``lax.scan`` — constant-size HLO regardless of depth,
which keeps XLA compile time flat from 12 to 48 layers).
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass
from typing import Callable, ClassVar, NamedTuple

# Row-chunk default for the blocked CE (ops/losses.py imports it back from
# here). Defined in config — NOT in ops — so this module stays importable
# without jax: serve.py and frontend/server.py refuse bad flags before jax
# loads, and the parent of a worker fleet (--placement subprocess|remote)
# builds its ServeConfig and never loads it.
DEFAULT_BLOCK_ROWS = 1024


@dataclass(frozen=True)
class GPT2Config:
    """Architecture hyperparameters for a GPT-2 style decoder-only LM.

    Defaults are GPT-2 124M, matching the reference's defaults field-for-field
    (``/root/reference/model.py:26-57``).
    """

    vocab_size: int = 50257        # GPT-2 BPE vocab (50,000 merges + 256 bytes + EOT)
    n_positions: int = 1024        # maximum sequence length (learned positional table)
    n_embd: int = 768              # residual stream width C
    n_layer: int = 12              # transformer blocks
    n_head: int = 12               # attention heads; head_dim = n_embd // n_head
    embd_dropout: float = 0.1      # dropout on wte+wpe sum
    attn_dropout: float = 0.1      # dropout on attention probabilities
    resid_dropout: float = 0.1     # dropout on attn out-proj, MLP activation and MLP out-proj
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02  # N(0, 0.02) for Linear/Embedding weights
    # --- TPU-build extensions (not in the reference) ---
    # Activation checkpointing: False = save everything; True/"block" = remat
    # the whole block (lax.scan body) — lowest memory, one full extra forward
    # in backward, needed for the 1.5B config; "mlp" = remat only the MLP
    # sublayer — saves the flash-attention forward from running twice while
    # still dropping the 4C-wide MLP activations (the memory bulk). "mlp" is
    # the throughput sweet spot for models that fit.
    remat: bool | str = False
    scan_layers: bool = True       # stacked-layer params + lax.scan over blocks
    # Attention kernel: "dense" = XLA O(T^2) parity baseline (reference
    # semantics, model.py:137-151); "flash" = Pallas fused kernel (VMEM
    # score stripes, in-kernel dropout); "ring" = sequence-parallel ring
    # attention over the mesh's 'sp' axis (ops/ring_attention.py); "auto" =
    # ring when the active mesh has sp>1, else flash on TPU when the
    # sequence length allows it, dense otherwise.
    attention_impl: str = "auto"
    # Training-loss path: "blocked" = logit-free chunked CE (ops/losses.py),
    # O(rows*V) HBM — required for large micro-batches; "dense" = materialize
    # [B*T, V] fp32 logits and let XLA autodiff (measured slightly faster at
    # micro-batch <= 8 where the 1.6 GB logits fit — the win is one fewer
    # logits recompute in backward at the cost of storing them).
    # bf16 numerics differ between the two by design: "blocked" emits bf16
    # chunk logits (torch-autocast's own lm_head dtype — the parity choice,
    # and the change that crossed the 50%-MFU line, PERF_ANALYSIS.md §7)
    # while "dense" keeps fp32-accumulated logits, so bf16 losses agree only
    # to ~2e-3 (pinned in tests/test_losses.py). fp32 inputs are
    # bit-identical on both paths.
    loss_impl: str = "blocked"
    # Fused Pallas layer-epilogue kernels (ops/fused_layer.py), attacking the
    # between-matmul bandwidth gap PERF_ANALYSIS.md §9 measured: "ln" fuses
    # the attention->MLP junction (proj-dropout + residual + ln2, plus the
    # block-closing residual+dropout); "gelu" fuses the MLP's bias + tanh-GELU
    # + activation-dropout epilogue over the [*, 4C] tensor; "all" = both.
    # Default "off" until a benchmark cell proves the win on-chip (ROADMAP
    # S4). Shapes/meshes the kernels can't host (C not 128-aligned,
    # sp/tp-sharded activations, decode's T=1 rows) fall back to the unfused
    # path automatically — same math, different dropout stream.
    fused_layers: str = "off"
    # Fused matmul+epilogue Pallas kernels (ops/fused_matmul.py) — the v2
    # step beyond fused_layers: the matmul itself runs in a tiled MXU kernel
    # and the epilogue is applied to the fp32 accumulator tile before
    # write-back. "mlp" fuses the MLP fc leg (matmul+bias+GELU+dropout);
    # "proj" fuses the two proj legs (matmul+bias+residual+dropout, folding
    # the residual add); "all" = both plus the qkv leg (plain matmul+bias;
    # only when tensor parallelism is inactive — the tp path keeps the
    # head-explicit einsum GSPMD shards). Composable with fused_layers: on a
    # leg both cover, fused_matmul wins (it subsumes the v1 epilogue; the v1
    # kernels keep the junctions fused_matmul doesn't reach, e.g. the
    # attn->MLP LN). Default "off" until a benchmark cell proves the win
    # on-chip (ROADMAP S4). Unhostable shapes/meshes (K or M not
    # 128-aligned — the 1.5B C=1600 — sp/tp-sharded activations, decode's
    # T=1 rows) fall back to the unfused composition, recorded via the
    # `fused_fallback` metric.
    fused_matmul: str = "off"
    # Row-chunk size of the blocked CE ([rows, V] transient logits per
    # chunk). The default (DEFAULT_BLOCK_ROWS above — single source of
    # truth) is the measured v5e throughput optimum at 124M/345M
    # (PERF_ANALYSIS.md §7 — larger chunks pipeline worse); smaller values
    # trade a little throughput for peak-HBM headroom on memory-edge
    # configs (each halving cuts the fp32+bf16 chunk transients roughly in
    # half, ~75 MB at 1024 rows and GPT-2 vocab).
    loss_block_rows: int = DEFAULT_BLOCK_ROWS

    def __post_init__(self) -> None:
        if self.n_embd % self.n_head != 0:
            raise ValueError(
                f"n_embd={self.n_embd} must be divisible by n_head={self.n_head}"
            )
        if self.attention_impl not in ("auto", "dense", "flash", "ring"):
            raise ValueError(
                f"attention_impl={self.attention_impl!r}: expected "
                "'auto', 'dense', 'flash' or 'ring'"
            )
        if self.fused_layers not in ("off", "ln", "gelu", "all"):
            raise ValueError(
                f"fused_layers={self.fused_layers!r}: expected "
                "'off', 'ln', 'gelu' or 'all'"
            )
        if self.fused_matmul not in ("off", "mlp", "proj", "all"):
            raise ValueError(
                f"fused_matmul={self.fused_matmul!r}: expected "
                "'off', 'mlp', 'proj' or 'all'"
            )
        if self.loss_impl not in ("blocked", "dense"):
            raise ValueError(
                f"loss_impl={self.loss_impl!r}: expected 'blocked' or 'dense'"
            )
        if self.loss_block_rows < 1:
            raise ValueError(
                f"loss_block_rows={self.loss_block_rows} must be >= 1"
            )
        if self.remat not in (False, True, "block", "mlp", "attn", "dots"):
            raise ValueError(
                f"remat={self.remat!r}: expected False, True, 'block', "
                f"'mlp', 'attn' or 'dots'"
            )

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def max_seq_len(self) -> int:
        """Alias matching the reference's ``GPT2Backbone.max_seq_len`` property
        (``/root/reference/model.py:271-273``)."""
        return self.n_positions

    def replace(self, **kwargs) -> "GPT2Config":
        return dataclasses.replace(self, **kwargs)

    def num_params(self, include_embeddings: bool = True) -> int:
        """Exact parameter count (lm_head is tied to wte, so it adds nothing)."""
        c, l, v, p = self.n_embd, self.n_layer, self.vocab_size, self.n_positions
        per_block = (
            2 * (2 * c)                 # ln1, ln2 (scale + bias)
            + c * 3 * c + 3 * c         # fused qkv projection
            + c * c + c                 # attention out-projection
            + c * 4 * c + 4 * c         # MLP fc1
            + 4 * c * c + c             # MLP fc2
        )
        n = l * per_block + 2 * c       # blocks + final LN
        if include_embeddings:
            n += v * c + p * c          # wte + wpe (lm_head tied)
        return n


@dataclass(frozen=True)
class CheckpointPolicy:
    """Checkpoint-lifecycle policy (``checkpoint.CheckpointSaver``).

    Separate from :class:`GPT2Config` because it describes the *run*, not the
    model: two runs of the same architecture can save with different policies,
    and the policy never participates in jit/compile caching.

    * ``async_save`` — periodic saves snapshot device arrays (blocking
      device->host copy only) and write/commit in the background, so the step
      loop never stalls for the sharded OCDBT write (ROADMAP resilience
      follow-up a). Emergency/final saves always finish synchronously.
    * ``keep_last_n`` — retention GC: keep only the newest N *committed*
      checkpoints (0 = keep everything). The newest committed checkpoint is
      never deleted regardless of N; uncommitted/failed save dirs are always
      pruned.
    * ``save_retries`` / ``retry_backoff_s`` — transient save failures are
      retried this many times with exponential backoff (delay doubles per
      attempt). A save that exhausts its retries degrades to a warning +
      ``save_failures`` metric instead of killing a multi-hour run — the next
      periodic save is a fresh chance, and restore falls back past the gap.
    """

    async_save: bool = True
    keep_last_n: int = 0
    save_retries: int = 2
    retry_backoff_s: float = 0.5

    def __post_init__(self) -> None:
        if self.keep_last_n < 0:
            raise ValueError(f"keep_last_n={self.keep_last_n} must be >= 0")
        if self.save_retries < 0:
            raise ValueError(f"save_retries={self.save_retries} must be >= 0")
        if self.retry_backoff_s < 0:
            raise ValueError(
                f"retry_backoff_s={self.retry_backoff_s} must be >= 0"
            )


@dataclass(frozen=True)
class CoordinationPolicy:
    """Multi-host control-plane policy (``coordination.py``).

    Run-level like :class:`CheckpointPolicy` — never participates in
    jit/compile caching. All knobs are inert on a single process (consensus
    and fingerprint checks are identity there), so defaults keep single-host
    runs bit-identical to a build without the control plane.

    * ``desync_check_every`` — allgather-and-compare a device-side parameter
      fingerprint every N optimizer steps (0 = never). A mismatch names the
      drifted ranks and routes into the rollback-to-last-verified path.
    * ``hang_timeout_s`` — if no optimizer step completes within this window
      the hang watchdog dumps stacks, attempts a bounded emergency save, and
      exits ``resilience.HANG_EXIT_CODE`` for a supervised full-job restart
      (0 = watchdog disabled, the default: timeouts must be sized to the
      measured step time, which only the operator knows).
    * ``consensus_every`` — run the pod-wide control-word exchange every K
      optimizer steps instead of every step (1 = per-step, the default).
      Fault flags (preempt, worker death, rollback demand, failed saves)
      latch host-locally between exchanges and ride the next one; actions
      only ever fire at exchange boundaries, so rollback/abort decisions
      stay pod-consistent at any K. The trade is action latency: worst case
      K-1 extra steps between a host noticing a fault and the pod acting on
      it (see README multi-host section).
    """

    desync_check_every: int = 0
    hang_timeout_s: float = 0.0
    consensus_every: int = 1

    def __post_init__(self) -> None:
        if self.desync_check_every < 0:
            raise ValueError(
                f"desync_check_every={self.desync_check_every} must be >= 0"
            )
        if self.hang_timeout_s < 0:
            raise ValueError(
                f"hang_timeout_s={self.hang_timeout_s} must be >= 0"
            )
        if self.consensus_every < 1:
            raise ValueError(
                f"consensus_every={self.consensus_every} must be >= 1"
            )


@dataclass(frozen=True)
class TracePolicy:
    """Structured-tracing policy (``gpt_2_distributed_tpu/obs/trace.py``).

    Run-level like :class:`CheckpointPolicy` — never participates in
    jit/compile caching. Default disabled: the tracer is then a pure no-op
    (shared null span, no file ever opened), so instrumented hot paths cost
    one branch per call site.

    * ``trace_dir`` — where per-process ``trace-p{rank}.jsonl`` files land
      (None = tracing off). Read back with ``scripts/obs_report.py``.
    * ``max_file_bytes`` — rotation bound per process: the live file plus
      one ``.1`` generation, so disk use is capped at twice this.
    * ``xla_profile_at`` — on-demand device profiler window,
      ``STEP[:NSTEPS]`` (None = no capture); host spans bridge into the
      device timeline via ``jax.profiler.TraceAnnotation`` while active.
    """

    trace_dir: str | None = None
    max_file_bytes: int = 64 * 1024 * 1024
    xla_profile_at: str | None = None

    @property
    def enabled(self) -> bool:
        return self.trace_dir is not None

    def __post_init__(self) -> None:
        if self.max_file_bytes < 4096:
            raise ValueError(
                f"max_file_bytes={self.max_file_bytes} must be >= 4096 "
                f"(one meta record + headroom)"
            )


@dataclass(frozen=True)
class ServeConfig:
    """Serving-engine shape signature + scheduler policy
    (``gpt_2_distributed_tpu/serving/engine.py``).

    Run-level like :class:`CheckpointPolicy` — it describes a serving
    deployment, not the model. The triple ``(max_batch, num_blocks,
    block_size)`` IS the decode step's compile signature: every admission,
    eviction and block-table rewrite changes array *contents* only, so the
    engine's decode step compiles exactly once per ServeConfig (asserted by
    jit cache-miss counting in tests/test_serving.py).

    * ``max_batch`` — in-flight decode slots; the continuous-batching
      scheduler admits queued requests into free slots at step boundaries.
    * ``block_size`` — KV positions per pool block. Smaller blocks waste
      less capacity on short sequences (internal fragmentation is at most
      ``block_size - 1`` positions/sequence) but widen the block table; on
      real TPUs a multiple of 8 keeps the Pallas kernel's [bs, D] tiles
      sublane-aligned (128 is the MXU-friendly choice).
    * ``num_blocks`` — pool capacity. Block 0 is reserved as the null
      block: idle slots and table tails park there, so the paged kernels
      never index out of bounds. Usable KV capacity is
      ``(num_blocks - 1) * block_size`` positions.
    * ``attn_impl`` — paged_attention dispatch: "auto" (Pallas on TPU, XLA
      gather elsewhere), or forced "xla"/"pallas".
    * ``eos_id`` — generation stops (and the slot + blocks are reclaimed)
      when this token is sampled; None = run every request to its
      max_new_tokens.

    Scheduler policy knobs (all default to the PR 7 behavior):

    * ``prefill_chunk`` — 0 = whole-prompt prefill at admission (one compile
      per prompt-length bucket). > 0 = chunked prefill: prompts advance one
      ``prefill_chunk``-token slice per engine step, interleaved with decode
      steps, so a long prompt no longer freezes every in-flight stream's
      inter-token latency. The chunk width is part of the compile
      signature — one chunk-prefill compile total, regardless of prompt
      lengths.
    * ``prefix_cache`` — hash-cons full KV blocks by token-prefix so
      requests sharing a system prompt skip prefill for the cached span.
      Entries are refcounted in the BlockAllocator; the partial tail block
      is copy-on-write.
    * ``admission`` — block-grant policy. ``"reserve"`` (PR 7): admission
      allocates the worst-case ``ceil((P + max_new - 1) / block_size)``
      blocks up front, all-or-nothing. ``"watermark"``: admission grants
      only the blocks the prompt needs now, as long as ``watermark_blocks``
      blocks stay free; decode grows tables lazily and, on pool
      exhaustion, preempts the newest-admitted request (blocks freed,
      request requeued with its generated tokens as recompute-prefill)
      instead of head-of-line blocking.
    * ``watermark_blocks`` — free-block floor the watermark admission
      keeps as decode-growth headroom.

    Multi-chip knobs:

    * ``mesh`` — serving mesh spec, ``"data:N[,tp:M]"`` (``=`` also accepted
      as the separator; ``""`` = single-device engine, the default). ``data``
      shards the ``max_batch`` decode rows and the KV block pool over N
      devices (each shard owns ``max_batch/N`` slot rows and
      ``num_blocks/N`` blocks); ``tp`` shards the qkv-projection heads and
      the pool's head axis over M devices. Only reduction-preserving dims
      are sharded, so streams stay bit-identical to the single-device
      engine for any mesh shape. The mesh shape is part of the compile
      signature: one decode compile per (ServeConfig, mesh shape).
    * ``prefill_batch`` — max queued prompts admitted into ONE chunked
      prefill dispatch per engine step (multi-row admission). 1 = the
      one-chunk-per-step behavior. Only meaningful with
      ``prefill_chunk > 0``; the row count is padded to ``prefill_batch``
      so the batched chunk program still compiles exactly once.

    * ``max_seq_len`` — the longest prompt + output a request may have
      (0 = the model's ``n_positions``). It sets the block table's width,
      so a deployment of a model whose position range is far beyond what
      its pool can hold (512k positions, a 32k pool row) compiles tables
      and selectors as wide as the traffic and no wider.

    Speculative decoding knob:

    * ``spec`` — speculative-decoding spec, ``"draft:<preset>,k:<K>"``
      (``=`` also accepted as the separator; ``""`` = speculation off, the
      default). ``draft`` names the smaller drafting model (a
      :data:`MODEL_PRESETS` key — the engine may substitute an explicit
      draft config, e.g. the shrunken CPU test config drafting for 124M);
      ``k`` is the draft run length per verify pass. The draft model gets
      its own KV block pool (same allocator machinery, independent block
      size/count) and its KV is disposable: preemption and cross-engine
      migration discard it and re-draft, so the request wire format is
      unchanged. Greedy streams stay bit-equal to the non-speculative
      engine for any k; sampled streams are target-distributed via the
      standard acceptance/resample rule.
    """

    max_batch: int = 8
    block_size: int = 16
    num_blocks: int = 256
    attn_impl: str = "auto"
    eos_id: int | None = None
    prefill_chunk: int = 0
    prefix_cache: bool = False
    admission: str = "reserve"
    watermark_blocks: int = 1
    mesh: str = ""
    prefill_batch: int = 1
    spec: str = ""
    max_seq_len: int = 0

    def __post_init__(self) -> None:
        if self.max_seq_len < 0:
            raise ValueError(f"max_seq_len={self.max_seq_len} must be >= 0")
        if self.max_batch < 1:
            raise ValueError(f"max_batch={self.max_batch} must be >= 1")
        if self.block_size < 1:
            raise ValueError(f"block_size={self.block_size} must be >= 1")
        if self.num_blocks < 2:
            raise ValueError(
                f"num_blocks={self.num_blocks} must be >= 2 (block 0 is the "
                f"reserved null block)"
            )
        if self.attn_impl not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"attn_impl={self.attn_impl!r}: expected 'auto', 'xla' or "
                f"'pallas'"
            )
        if self.eos_id is not None and self.eos_id < 0:
            raise ValueError(f"eos_id={self.eos_id} must be >= 0")
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} must be >= 0 "
                f"(0 disables chunking)"
            )
        if self.admission not in ("reserve", "watermark"):
            raise ValueError(
                f"admission={self.admission!r}: expected 'reserve' or "
                f"'watermark'"
            )
        if self.watermark_blocks < 0:
            raise ValueError(
                f"watermark_blocks={self.watermark_blocks} must be >= 0"
            )
        data, tp = self.mesh_axes()  # raises on a malformed spec
        if self.max_batch % data != 0:
            raise ValueError(
                f"mesh={self.mesh!r}: max_batch={self.max_batch} must be "
                f"divisible by the data degree {data} (each shard owns "
                f"max_batch/data slot rows)"
            )
        if self.num_blocks % data != 0:
            raise ValueError(
                f"mesh={self.mesh!r}: num_blocks={self.num_blocks} must be "
                f"divisible by the data degree {data} (each shard owns "
                f"num_blocks/data pool blocks)"
            )
        if data > 1 and self.num_blocks // data < 2:
            raise ValueError(
                f"mesh={self.mesh!r}: num_blocks={self.num_blocks} leaves "
                f"shard 0 no usable blocks (it also hosts the reserved null "
                f"block 0); need num_blocks/data >= 2"
            )
        if not 1 <= self.prefill_batch <= self.max_batch:
            raise ValueError(
                f"prefill_batch={self.prefill_batch} must be in "
                f"[1, max_batch={self.max_batch}]"
            )
        self.spec_axes()  # raises on a malformed spec

    def mesh_axes(self) -> tuple[int, int]:
        """Parse ``mesh`` into ``(data, tp)`` degrees (``""`` -> (1, 1));
        see :func:`parse_serve_mesh`."""
        return parse_serve_mesh(self.mesh)

    @property
    def mesh_devices(self) -> int:
        """Total devices the mesh spec asks for (1 = unsharded engine)."""
        data, tp = self.mesh_axes()
        return data * tp

    def spec_axes(self) -> tuple[str | None, int]:
        """Parse ``spec`` into ``(draft_preset, k)`` (``""`` -> (None, 0));
        see :func:`parse_serve_spec`."""
        return parse_serve_spec(self.spec)

    @property
    def spec_k(self) -> int:
        """Draft run length per verify pass (0 = speculation off)."""
        return self.spec_axes()[1]

    def max_blocks_per_seq(self, n_positions: int) -> int:
        """Static block-table width: enough blocks for a full-context
        sequence, or for one of ``max_seq_len`` where that is set."""
        return -(-self.seq_limit(n_positions) // self.block_size)

    def seq_limit(self, n_positions: int) -> int:
        """The longest prompt + output the engine takes."""
        return min(n_positions, self.max_seq_len or n_positions)


def parse_serve_mesh(mesh: str) -> tuple[int, int]:
    """Parse a serving mesh spec into ``(data, tp)`` degrees (``""`` ->
    (1, 1)).

    Accepts ``"data:N[,tp:M]"`` (CLI form) and ``"data=N[,tp=M]"``
    (parallel/mesh.py MeshSpec form). Self-contained on purpose: config.py
    stays importable without jax or the parallel package, so the parent of
    a worker fleet, which never loads jax, can refuse a bad mesh flag.
    """
    degrees = {"data": 1, "tp": 1}
    if not mesh:
        return 1, 1
    seen: set[str] = set()
    for part in mesh.split(","):
        name, _, deg = part.replace("=", ":").partition(":")
        name = name.strip()
        if name not in degrees:
            raise ValueError(
                f"mesh={mesh!r}: unknown axis {name!r} (serving "
                f"meshes use 'data' and 'tp' only)"
            )
        if name in seen:
            raise ValueError(f"mesh={mesh!r}: duplicate axis {name!r}")
        seen.add(name)
        try:
            n = int(deg.strip())
        except ValueError:
            raise ValueError(
                f"mesh={mesh!r}: axis {name!r} needs an integer "
                f"degree, got {deg.strip()!r}"
            ) from None
        if n < 1:
            raise ValueError(
                f"mesh={mesh!r}: axis {name!r} degree must be >= 1"
            )
        degrees[name] = n
    return degrees["data"], degrees["tp"]


def parse_serve_spec(spec: str) -> tuple[str | None, int]:
    """Parse a speculative-decoding spec into ``(draft_preset, k)``
    (``""`` -> (None, 0) — speculation off).

    Accepts ``"draft:<preset>,k:<K>"`` (``=`` also accepted as the
    separator, mirroring :func:`parse_serve_mesh`). Both keys are
    required when the spec is non-empty: a draft model with no run
    length (or vice versa) is a configuration bug, not a default.
    Self-contained on purpose: config.py stays importable without jax,
    so serve.py and frontend/server.py refuse a bad ``--spec_k`` or
    ``--draft_preset`` before jax loads.

    The preset name is validated against :data:`MODEL_PRESETS` here; the
    draft-smaller-than-target check needs the *target* config and lives
    in :func:`validate_worker_flags` / the engine constructor.
    """
    if not spec:
        return None, 0
    draft: str | None = None
    k: int | None = None
    seen: set[str] = set()
    for part in spec.split(","):
        name, _, val = part.replace("=", ":").partition(":")
        name = name.strip()
        val = val.strip()
        if name not in ("draft", "k"):
            raise ValueError(
                f"spec={spec!r}: unknown key {name!r} (speculation specs "
                f"use 'draft' and 'k' only)"
            )
        if name in seen:
            raise ValueError(f"spec={spec!r}: duplicate key {name!r}")
        seen.add(name)
        if name == "draft":
            if val not in MODEL_PRESETS:
                raise ValueError(
                    f"spec={spec!r}: unknown draft preset {val!r} "
                    f"(expected one of {', '.join(MODEL_PRESETS)})"
                )
            draft = val
        else:
            try:
                k = int(val)
            except ValueError:
                raise ValueError(
                    f"spec={spec!r}: key 'k' needs an integer, got {val!r}"
                ) from None
            if k < 1:
                raise ValueError(
                    f"spec={spec!r}: k={k} must be >= 1 (use spec='' to "
                    f"disable speculation)"
                )
    if draft is None or k is None:
        raise ValueError(
            f"spec={spec!r}: both 'draft' and 'k' are required "
            f"(e.g. 'draft:124M,k:4')"
        )
    return draft, k


# Replica placement modes for the serving frontend: `inprocess` builds
# every ServingEngine inside the frontend process (the default — zero RPC
# overhead, shared fate); `subprocess` hosts one engine per worker process
# behind the RPC supervision plane (process-level blast radius); `remote`
# adopts pre-started workers listening on tcp://host:port (named by a
# --worker_pool file), extending the blast radius story to whole hosts.
PLACEMENTS = ("inprocess", "subprocess", "remote")


def validate_worker_flags(p, args) -> None:
    """Parse-time validation of the ``--placement``/``--worker_*`` flag
    family, shared by serve.py and frontend/server.py. jax-free on
    purpose (mirrors ``parse_serve_mesh``): a bad worker flag must be
    rejected before any CLI pays the jax import."""
    if args.placement not in PLACEMENTS:
        p.error(
            f"--placement must be one of {'|'.join(PLACEMENTS)}, "
            f"got {args.placement!r}"
        )
    if args.worker_max_respawns < 0:
        p.error(
            f"--worker_max_respawns must be >= 0, "
            f"got {args.worker_max_respawns}"
        )
    if args.worker_respawn_backoff_s < 0:
        p.error(
            f"--worker_respawn_backoff_s must be >= 0, "
            f"got {args.worker_respawn_backoff_s}"
        )
    if args.worker_rpc_timeout_s <= 0:
        p.error(
            f"--worker_rpc_timeout_s must be > 0, "
            f"got {args.worker_rpc_timeout_s}"
        )
    if args.worker_heartbeat_s <= 0:
        p.error(
            f"--worker_heartbeat_s must be > 0, "
            f"got {args.worker_heartbeat_s}"
        )
    if args.worker_connect_timeout_s <= 0:
        p.error(
            f"--worker_connect_timeout_s must be > 0, "
            f"got {args.worker_connect_timeout_s}"
        )
    # The cross-host flags arrived after the subprocess family; getattr
    # keeps this helper usable on namespaces that predate them (embedders
    # building their own argparse.Namespace).
    hb_timeout = getattr(args, "worker_heartbeat_timeout_s", None)
    if hb_timeout is not None and hb_timeout <= 0:
        p.error(
            f"--worker_heartbeat_timeout_s must be > 0, "
            f"got {hb_timeout}"
        )
    if getattr(args, "worker_auth_token_file", None) is not None:
        # Refuse a bad token file at parse time (rpc.py is jax-free): a
        # fleet that cannot authenticate must not get as far as spawning.
        from gpt_2_distributed_tpu.serving.frontend.rpc import (
            load_auth_token,
        )

        try:
            load_auth_token(args.worker_auth_token_file)
        except (OSError, ValueError) as e:
            p.error(f"--worker_auth_token_file: {e}")
    # Speculative-decoding flags (getattr-guarded like the cross-host
    # family: embedder namespaces may predate them). Everything here is
    # computable jax-free — GPT2Config.num_params() is pure python — so a
    # bad speculation flag is refused before the jax import, same as a bad
    # mesh spec.
    spec_k = getattr(args, "spec_k", None)
    if spec_k is not None and spec_k < 1:
        p.error(f"--spec_k must be >= 1, got {spec_k}")
    draft = getattr(args, "draft_preset", None)
    if draft is None:
        if spec_k is not None:
            p.error("--spec_k needs --draft_preset (speculation is opt-in "
                    "via the draft model)")
        if getattr(args, "draft_ckpt", None):
            p.error("--draft_ckpt needs --draft_preset")
    if draft is not None:
        if draft not in MODEL_PRESETS:
            p.error(
                f"--draft_preset must be one of "
                f"{'|'.join(MODEL_PRESETS)}, got {draft!r}"
            )
        target = MODEL_PRESETS.get(getattr(args, "model", None))
        if target is not None:
            overrides = {}
            for flag, field in (
                ("n_layer", "n_layer"),
                ("n_embd", "n_embd"),
                ("n_head", "n_head"),
                ("vocab_size", "vocab_size"),
                ("seq_len", "n_positions"),
            ):
                v = getattr(args, flag, None)
                if v is not None:
                    overrides[field] = v
            try:
                target = target.replace(**overrides)
            except ValueError:
                target = None  # malformed model flags fail elsewhere
        if (
            target is not None
            and MODEL_PRESETS[draft].num_params() >= target.num_params()
        ):
            p.error(
                f"--draft_preset {draft} "
                f"({MODEL_PRESETS[draft].num_params():,} params) must be "
                f"smaller than the target model "
                f"({target.num_params():,} params): a draft at least as "
                f"large as the target cannot speed up verification"
            )
    pool = getattr(args, "worker_pool", None)
    if args.placement == "remote":
        if not pool:
            p.error(
                "--placement remote needs --worker_pool (a file of "
                "'host_id address' lines naming the fleet; workers "
                "append themselves with gpt2-tpu-worker --advertise)"
            )
        if not os.path.exists(pool):
            p.error(
                f"--worker_pool {pool!r}: file not found"
            )
    elif pool:
        p.error(
            f"--worker_pool only makes sense with --placement remote, "
            f"not {args.placement!r}"
        )
    validate_model_flags(p, args)


# BASELINE.json configs 1-5 require these four sizes; the standard GPT-2 family.
MODEL_PRESETS: dict[str, GPT2Config] = {
    "124M": GPT2Config(n_layer=12, n_embd=768, n_head=12),
    "345M": GPT2Config(n_layer=24, n_embd=1024, n_head=16),
    "774M": GPT2Config(n_layer=36, n_embd=1280, n_head=20),
    "1.5B": GPT2Config(n_layer=48, n_embd=1600, n_head=25),
}


SPARSE_MIXER, LIGHTNING_MIXER = "minicpm4", "lightning-attn"


@dataclass(frozen=True)
class SparseAttentionConfig:
    """Sizes of the InfLLM-V2 block selection (``ops/sparse_select.py``):
    compressed keys are means over ``window`` tokens every ``stride``; a
    query attends the first ``init_blocks`` blocks, the ``local_window /
    block`` blocks that end with its own, and the ``topk`` best-scoring of
    the rest; below ``dense_below`` tokens of context it attends them all."""

    window: int = 32
    stride: int = 16
    block: int = 64
    init_blocks: int = 1
    local_window: int = 2048
    topk: int = 64
    dense_below: int = 8192

    def __post_init__(self) -> None:
        if self.window % self.stride or self.block % self.stride \
                or self.local_window % self.block:
            raise ValueError(
                f"sparse sizes must nest: stride={self.stride} divides "
                f"window={self.window} and block={self.block}, block divides "
                f"local_window={self.local_window}"
            )

    @property
    def local_blocks(self) -> int:
        return self.local_window // self.block

    @property
    def list_width(self) -> int:
        """Blocks a query attends at most: the forced and the picked ones,
        or every block of a context that is still dense."""
        return max(self.init_blocks + self.local_blocks + self.topk,
                   -(-self.dense_below // self.block))

    def selected_blocks(self, pos):
        """How many blocks the query at position ``pos`` (scalar or numpy
        array) attends, and how many it sees."""
        import numpy as np

        visible = np.asarray(pos) // self.block + 1
        sparse = np.minimum(
            visible, self.init_blocks + self.local_blocks + self.topk)
        return np.where(np.asarray(pos) < self.dense_below, visible, sparse), visible


@dataclass(frozen=True)
class SalaConfig:
    """MiniCPM-SALA (``models/minicpm_sala.py``): RMSNorm, no biases,
    SwiGLU, untied head, muP-style scalings, and a PER-LAYER mixer list —
    ``minicpm4`` layers (grouped-query attention with InfLLM-V2 block
    selection, no rotary) among ``lightning-attn`` layers (linear attention
    with per-head decay, rotary, output norm). Field names follow the
    published ``config.json``. ``num_hidden_layers`` is the PUBLISHED depth:
    it stays under the root in ``scale_depth / sqrt(num_hidden_layers)`` when
    ``mixer_types`` holds a cut of the stack."""

    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    mixer_types: tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    max_position_embeddings: int = 524288
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    initializer_range: float = 0.02
    sparse: SparseAttentionConfig = SparseAttentionConfig()

    # This family's words in `refuse_for_state_family`.
    recurrent_state: ClassVar[str] = "linear-attention state"
    unsharded: ClassVar[str] = "grouped-query pools and the per-slot state"

    def __post_init__(self) -> None:
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        bad = set(self.mixer_types) - {SPARSE_MIXER, LIGHTNING_MIXER}
        if bad or not self.mixer_types:
            raise ValueError(
                f"mixer_types must name {SPARSE_MIXER!r} or {LIGHTNING_MIXER!r} "
                f"for every layer, got {sorted(bad) or 'none'}"
            )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads={self.num_attention_heads} must be a "
                f"multiple of num_key_value_heads={self.num_key_value_heads}"
            )

    # What the engine and the generation checks read off any model config.
    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def n_layer(self) -> int:
        return len(self.mixer_types)

    @property
    def sparse_layers(self) -> tuple[int, ...]:
        return tuple(i for i, m in enumerate(self.mixer_types) if m == SPARSE_MIXER)

    @property
    def lightning_layers(self) -> tuple[int, ...]:
        return tuple(i for i, m in enumerate(self.mixer_types) if m == LIGHTNING_MIXER)

    @property
    def pool_block(self) -> int:
        """One pool block is one selection block: the only block size the
        engine may serve this family with."""
        return self.sparse.block

    @property
    def kv_pool_view(self):
        """What ``paged_cache.init_pools`` reads of a model: only the sparse
        layers hold K/V, in ``num_key_value_heads`` heads."""
        import types

        return types.SimpleNamespace(
            n_layer=len(self.sparse_layers), n_head=self.num_key_value_heads,
            head_dim=self.head_dim)

    def replace(self, **kwargs) -> "SalaConfig":
        return dataclasses.replace(self, **kwargs)

    def cut(self, n_layer: int, first: int = 0) -> "SalaConfig":
        """``n_layer`` consecutive layers of this stack from layer ``first``
        on (of the published 32, ``cut(16, 9)`` is the one run of 16 that
        keeps the published ratio of 1 sparse layer to 3 linear)."""
        total = len(self.mixer_types)
        if n_layer < 1 or first < 0 or first + n_layer > total:
            raise ValueError(
                f"layers [{first}, {first + n_layer}) are not within the "
                f"stack's {total}"
            )
        return self.replace(mixer_types=self.mixer_types[first:first + n_layer])

    def num_params(self, include_embeddings: bool = True) -> int:
        c, f = self.hidden_size, self.intermediate_size
        a = self.num_attention_heads * self.head_dim
        kv = self.num_key_value_heads * self.head_dim
        la = self.lightning_nh * self.lightning_head_dim
        n = c
        for m in self.mixer_types:
            n += 2 * c + 3 * c * f
            if m == SPARSE_MIXER:
                n += 2 * c * a + 2 * c * kv + a * c + 2 * self.head_dim
            else:
                n += 4 * c * la + la * c + 2 * self.lightning_head_dim + la
        if include_embeddings:
            n += 2 * self.vocab_size * c
        return n


_S, _L = SPARSE_MIXER, LIGHTNING_MIXER
SALA_PRESETS: dict[str, SalaConfig] = {
    # openbmb/MiniCPM-SALA config.json, the 32 published layers.
    "minicpm-sala-9b": SalaConfig(mixer_types=(
        _S, _L, _L, _L, _L, _L, _L, _L, _L, _S, _L, _L, _L, _L, _L, _L,
        _S, _S, _L, _L, _L, _L, _S, _L, _L, _L, _L, _L, _L, _S, _S, _S)),
    # The CPU tests' size: selection is live within 100 tokens.
    "minicpm-sala-tiny": SalaConfig(
        vocab_size=257, hidden_size=64, intermediate_size=128,
        num_hidden_layers=8, mixer_types=(_S, _L, _L, _L, _S, _L, _L, _L),
        num_attention_heads=4, num_key_value_heads=1, head_dim=16,
        lightning_nh=4, lightning_head_dim=16, max_position_embeddings=4096,
        dim_model_base=32,
        sparse=SparseAttentionConfig(
            window=4, stride=2, block=8, init_blocks=1, local_window=16,
            topk=2, dense_below=32)),
}


MAMBA_LAYER, ATTENTION_LAYER, EXPERT_LAYER = "M", "*", "E"


@dataclass(frozen=True)
class NemotronHConfig:
    """Nemotron-H (``models/nemotron_h.py``): RMSNorm, no biases, no
    positions, an untied head, and ONE mixer a layer, named by a character of
    ``hybrid_override_pattern`` - ``M`` a Mamba-2 state-space mixer, ``*``
    grouped-query attention, ``E`` sparse experts beside a shared one. Field
    names follow the published ``config.json``.

    ``n_routed_experts`` is the ROUTER's width, the published count;
    ``experts_held`` is the range of them whose weights live here (all of
    them unless an expert-parallel deployment gives this chip a share:
    ``share``). The router scores all, the layer computes what the held
    experts give and leaves out what the others would add. ``vocab_size``
    counts the rows of the vocabulary held here."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    n_routed_experts: int = 128
    experts_held: tuple[int, int] = (0, 128)
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4

    # This family's words in `refuse_for_state_family`.
    recurrent_state: ClassVar[str] = "state-space and convolution state"
    unsharded: ClassVar[str] = ("the per-slot state and the expert layer (no "
                                "expert-parallel axis, no exchange)")

    def __post_init__(self) -> None:
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        bad = set(self.hybrid_override_pattern) - {
            MAMBA_LAYER, ATTENTION_LAYER, EXPERT_LAYER}
        if bad or not self.hybrid_override_pattern:
            raise ValueError(
                f"hybrid_override_pattern names a layer by {MAMBA_LAYER!r}, "
                f"{ATTENTION_LAYER!r} or {EXPERT_LAYER!r}, got "
                f"{sorted(bad) or 'none'}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads={self.num_attention_heads} must be a "
                f"multiple of num_key_value_heads={self.num_key_value_heads}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(
                f"mamba_num_heads={self.mamba_num_heads} must be a multiple "
                f"of n_groups={self.n_groups}")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held={self.experts_held} is no range of the "
                f"{self.n_routed_experts} routed experts")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError(
                f"num_experts_per_tok={self.num_experts_per_tok} of "
                f"{self.n_routed_experts} experts")

    # What the engine and the generation checks read off any model config.
    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def n_layer(self) -> int:
        return len(self.hybrid_override_pattern)

    def layers_of(self, kind: str) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.hybrid_override_pattern)
                     if k == kind)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Width of what the causal convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def scan_chunk(self) -> int:
        """Tokens of one sub-chunk of the chunked scan (``ops/ssd.py``)."""
        return self.chunk_size

    @property
    def kv_pool_view(self):
        """What ``paged_cache.init_pools`` reads of a model: only the
        attention layers hold K/V, in ``num_key_value_heads`` heads."""
        import types

        return types.SimpleNamespace(
            n_layer=len(self.layers_of(ATTENTION_LAYER)),
            n_head=self.num_key_value_heads, head_dim=self.head_dim)

    def replace(self, **kwargs) -> "NemotronHConfig":
        return dataclasses.replace(self, **kwargs)

    def cut(self, n_layer: int, first: int = 0) -> "NemotronHConfig":
        """``n_layer`` consecutive layers of this stack from layer ``first``
        on (of the published 52, ``cut(14, 6)`` is two whole runs of the
        7-layer run that the pattern repeats)."""
        total = len(self.hybrid_override_pattern)
        if n_layer < 1 or first < 0 or first + n_layer > total:
            raise ValueError(
                f"layers [{first}, {first + n_layer}) are not within the "
                f"stack's {total}")
        return self.replace(hybrid_override_pattern=
                            self.hybrid_override_pattern[first:first + n_layer])

    def share(self, experts: tuple[int, int], vocab_size: int | None = None
              ) -> "NemotronHConfig":
        """One chip's share of an expert-parallel deployment: the routed
        experts ``[lo, hi)`` and the first ``vocab_size`` rows of the
        vocabulary; the router's width and every mixer stay whole."""
        return self.replace(experts_held=tuple(experts),
                            vocab_size=vocab_size or self.vocab_size)

    def layer_params(self, kind: str) -> int:
        """Parameters of one layer of ``kind`` as held here, its norm
        included."""
        c = self.hidden_size
        if kind == MAMBA_LAYER:
            h = self.mamba_num_heads
            return (c + c * (self.d_inner + self.conv_dim + h)
                    + self.conv_dim * (self.conv_kernel + 1) + 3 * h
                    + self.d_inner + self.d_inner * c)
        if kind == ATTENTION_LAYER:
            a = self.num_attention_heads * self.head_dim
            kv = self.num_key_value_heads * self.head_dim
            return c + 2 * c * a + 2 * c * kv
        return (c + c * self.n_routed_experts + self.n_routed_experts
                + self.n_held * 2 * c * self.moe_intermediate_size
                + 2 * c * self.moe_shared_expert_intermediate_size)

    def num_params(self, include_embeddings: bool = True) -> int:
        n = self.hidden_size + sum(
            self.layer_params(k) for k in self.hybrid_override_pattern)
        if include_embeddings:
            n += 2 * self.vocab_size * self.hidden_size
        return n


NEMOTRON_PRESETS: dict[str, NemotronHConfig] = {
    # nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json, the 52
    # published layers, every expert: a schema, 63 GB of weights.
    "nemotron3-nano-30b": NemotronHConfig(),
    # One chip of eight: layers 6-19, routed experts 0-63 of 128 and half
    # the vocabulary (two chips share each layer, four pipeline stages).
    "nemotron3-nano-l14": NemotronHConfig().cut(14, 6).share((0, 64), 65536),
    # The CPU tests' size, every expert held; a test takes a share of it.
    "nemotron-h-tiny": NemotronHConfig(
        vocab_size=257, hidden_size=64, hybrid_override_pattern="EM*EM",
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
        chunk_size=8, n_routed_experts=8, experts_held=(0, 8),
        num_experts_per_tok=2, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=48, max_position_embeddings=4096),
}


@dataclass(frozen=True)
class JambaConfig:
    """Jamba (``models/jamba.py``): RMSNorm, no positions, a tied head, and in
    every layer a mixer and a gated MLP - the mixer grouped-query attention
    where ``i % attn_layer_period == attn_layer_offset``, a Mamba-1
    selective-scan mixer (``ops/selective_scan.py``) otherwise. Field names
    follow the published ``config.json``; ``num_experts`` is 1 (the dense
    feed-forward in every layer: sparse experts of this family are not
    written). ``first_layer``, the published index of the first layer held
    (``cut``), is no published key."""

    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_experts: int = 1
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    head_dim: int = 128
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    first_layer: int = 0

    # This family's words in `refuse_for_state_family`.
    recurrent_state: ClassVar[str] = "state-space and convolution state"
    unsharded: ClassVar[str] = "grouped-query pools and the per-slot state"

    def __post_init__(self) -> None:
        if self.num_experts != 1:
            raise ValueError(
                f"num_experts={self.num_experts}: only the dense feed-forward "
                f"(num_experts=1) of this family is written")
        if not self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings=False: the head is the embedding")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads={self.num_attention_heads} must be a "
                f"multiple of num_key_value_heads={self.num_key_value_heads}")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError(
                f"attn_layer_offset={self.attn_layer_offset} is no layer of a "
                f"period of {self.attn_layer_period}")
        if self.num_hidden_layers < 1 or self.first_layer < 0:
            raise ValueError(
                f"num_hidden_layers={self.num_hidden_layers} from layer "
                f"{self.first_layer} on is no stack")

    # What the engine and the generation checks read off any model config.
    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def layer_kinds(self) -> str:
        """One character a layer held, ``*`` attention and ``M`` Mamba."""
        return "".join(
            ATTENTION_LAYER if i % self.attn_layer_period == self.attn_layer_offset
            else MAMBA_LAYER
            for i in range(self.first_layer, self.first_layer + self.num_hidden_layers))

    def layers_of(self, kind: str) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def kv_pool_view(self):
        """What ``paged_cache.init_pools`` reads of a model: only the
        attention layers hold K/V, in ``num_key_value_heads`` heads."""
        import types

        return types.SimpleNamespace(
            n_layer=len(self.layers_of(ATTENTION_LAYER)),
            n_head=self.num_key_value_heads, head_dim=self.head_dim)

    def replace(self, **kwargs) -> "JambaConfig":
        return dataclasses.replace(self, **kwargs)

    def cut(self, n_layer: int, first: int = 0) -> "JambaConfig":
        """``n_layer`` consecutive layers of this stack from layer ``first``
        on; each keeps the kind its published index gives it."""
        if n_layer < 1 or first < 0 or first + n_layer > self.num_hidden_layers:
            raise ValueError(
                f"layers [{first}, {first + n_layer}) are not within the "
                f"stack's {self.num_hidden_layers}")
        return self.replace(num_hidden_layers=n_layer,
                            first_layer=self.first_layer + first)

    def layer_params(self, kind: str) -> int:
        """Parameters of one layer of ``kind``: its mixer, its gated MLP and
        its two norms."""
        c, d, n, r = self.hidden_size, self.d_inner, self.mamba_d_state, self.mamba_dt_rank
        mlp = 2 * c + 3 * c * self.intermediate_size
        if kind == MAMBA_LAYER:
            return (mlp + c * 2 * d + d * (self.mamba_d_conv + 1) + d * (r + 2 * n)
                    + r + 2 * n + r * d + d + d * n + d + d * c)
        a = self.num_attention_heads * self.head_dim
        kv = self.num_key_value_heads * self.head_dim
        return mlp + 2 * c * a + 2 * c * kv

    def num_params(self, include_embeddings: bool = True) -> int:
        n = self.hidden_size + sum(self.layer_params(k) for k in self.layer_kinds)
        if include_embeddings:
            n += self.vocab_size * self.hidden_size       # tied: counted once
        return n


JAMBA_PRESETS: dict[str, JambaConfig] = {
    # ai21labs/AI21-Jamba2-3B config.json: 28 layers, attention at 7 and 21.
    "jamba2-3b": JambaConfig(),
    # The CPU tests' size: M M * M, one KV head under four query heads.
    "jamba-tiny": JambaConfig(
        vocab_size=257, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, attn_layer_period=4, attn_layer_offset=2,
        num_attention_heads=4, num_key_value_heads=1, head_dim=16,
        mamba_d_state=16, mamba_dt_rank=8, max_position_embeddings=4096),
}


def refuse_for_state_family(config, serve: "ServeConfig",
                            speculative: bool = False) -> str | None:
    """What the serving engine cannot do yet for a family that keeps a
    recurrent state beside the paged pools (every row of ``FAMILY_FLAGS``), as
    the sentence to refuse it with (None = it can serve). jax-free: the CLIs
    call it at parse time, the engine at construction. ``serve`` is read by
    attribute (``prefix_cache``, ``spec``, ``prefill_chunk``, ``mesh_devices``,
    ``prefill_batch``, ``block_size``); of ``config`` its family's own words
    (``recurrent_state``, ``unsharded``) and, where it has them, the one block
    size its pools take (``pool_block``) and the tokens of a sub-chunk of its
    scan (``scan_chunk``)."""
    if serve.prefix_cache:
        return (f"prefix_cache: a hit would need a snapshot of the "
                f"{config.recurrent_state} at the block boundary")
    if speculative or serve.spec:
        return "speculative decoding: the two-model round is written for GPT-2"
    if serve.prefill_chunk == 0:
        return ("whole-prompt prefill (prefill_chunk=0): this family "
                "prefills in chunks through the pools and the state")
    if serve.mesh_devices > 1:
        return (f"a serving mesh (tp or data over 1): {config.unsharded} "
                f"have no sharding yet")
    if serve.prefill_batch != 1:
        return "prefill_batch over 1: a chunk dispatch carries one slot's state"
    block = getattr(config, "pool_block", None)
    if block is not None and serve.block_size != block:
        return (f"block_size={serve.block_size}: one pool block is one "
                f"selection block of {block} keys")
    if serve.prefill_chunk % serve.block_size:
        return (f"prefill_chunk={serve.prefill_chunk}: a chunk covers whole "
                f"blocks of {serve.block_size}")
    sub = getattr(config, "scan_chunk", None)
    if sub is not None and serve.prefill_chunk % sub:
        return (f"prefill_chunk={serve.prefill_chunk}: a chunk covers whole "
                f"sub-chunks of the scan, {sub} tokens each")
    return None


class FamilyFlags(NamedTuple):
    """A family beside GPT-2 as a CLI meets it, jax-free: its schema, its
    presets by ``--model`` name, and what the engine refuses for it
    (``serving/families.py`` points here: one ``refuse`` for parse time and
    for construction). Every such family is a layer-pattern model: ``--n_layer``
    and ``--first_layer`` cut its stack (``config.cut``)."""

    config_type: type
    presets: dict
    refuse: Callable


FAMILY_FLAGS = (
    FamilyFlags(SalaConfig, SALA_PRESETS, refuse_for_state_family),
    FamilyFlags(NemotronHConfig, NEMOTRON_PRESETS, refuse_for_state_family),
    FamilyFlags(JambaConfig, JAMBA_PRESETS, refuse_for_state_family),
)
FAMILY_MODELS = tuple(m for row in FAMILY_FLAGS for m in sorted(row.presets))


def family_flags_of(model) -> FamilyFlags | None:
    """The ``FAMILY_FLAGS`` row of a ``--model`` name or of a model
    configuration (None: GPT-2's)."""
    return next((row for row in FAMILY_FLAGS
                 if isinstance(model, row.config_type)
                 or (isinstance(model, str) and model in row.presets)), None)


def family_config_from_flags(args):
    """``--model <a FAMILY_MODELS name> [--n_layer N --first_layer F]``: the
    preset, or ``N`` consecutive layers of it from layer ``F`` on (``cut``)."""
    config = family_flags_of(args.model).presets[args.model]
    n_layer = getattr(args, "n_layer", None)
    first = getattr(args, "first_layer", 0) or 0
    if n_layer is None:
        if first:
            raise ValueError("--first_layer needs --n_layer")
        return config
    return config.cut(n_layer, first)


def validate_model_flags(p, args) -> None:
    """Parse-time check of the model flags for a model of a family beside
    GPT-2 (:data:`FAMILY_FLAGS`), jax-free like the rest of this file: sizes
    that are GPT-2's, a checkpoint (these families have no trainer, so no
    checkpoint format), and every engine option that ``refuse_for_state_family``
    names are refused before any CLI pays the jax import."""
    import types

    model = getattr(args, "model", None)
    family = family_flags_of(model)
    if family is None:
        if getattr(args, "first_layer", 0):
            p.error(f"--first_layer cuts a layer-pattern model's stack "
                    f"({'|'.join(FAMILY_MODELS)}), not {model}")
        return
    for flag in ("n_embd", "n_head", "vocab_size", "seq_len"):
        if getattr(args, flag, None) is not None:
            p.error(f"--{flag} is a GPT-2 size; --model {model} takes "
                    f"--n_layer and --first_layer")
    if getattr(args, "ckpt", None):
        p.error(f"--ckpt: --model {model} has no checkpoint format yet; "
                f"serve it with --init_random")
    try:
        config = family_config_from_flags(args)
    except ValueError as e:
        p.error(str(e))
    if not hasattr(args, "prefill_chunk"):
        return                      # a CLI without the engine flags (sample.py)
    try:
        data, tp = parse_serve_mesh(getattr(args, "serve_mesh", "") or "")
    except ValueError:
        return                      # a malformed mesh fails where meshes are parsed
    why = family.refuse(config, types.SimpleNamespace(
        prefix_cache=args.prefix_cache, spec="", prefill_chunk=args.prefill_chunk,
        mesh_devices=data * tp, prefill_batch=getattr(args, "prefill_batch", 1),
        block_size=args.block_size,
    ), speculative=bool(getattr(args, "draft_preset", None)
                        or getattr(args, "spec_k", None)))
    if why is not None:
        p.error(f"--model {model} cannot be served with {why}")
