"""Training driver CLI.

Flag surface mirrors the reference's argparse CLI
(``/root/reference/train_gpt2_distributed.py:282-310``) so its launch scripts
translate 1:1 — ``--data_dir --training_mode --seq_len --batch
--grad_accum_steps --epochs --lr --save_every --save_dir --log_dir --workers``
— extended with what the reference hard-codes or lacks: ``--model`` size
presets (124M..1.5B, SURVEY.md §5.6), ``--mesh`` for explicit
data/fsdp mesh shapes, ``--resume`` (the reference's load_checkpoint is an
empty stub, ``:104-111``), ``--lr_schedule/--warmup_steps`` (its LR scheduler
is a TODO, ``:354``), ``--profile`` (jax.profiler traces into the same
TensorBoard log dir), and ``--max_steps`` for smoke runs.

Execution model (one jitted step, every mode a sharding):
    batches [grad_accum, micro_batch, seq] -> train_step (lax.scan grad accum,
    AdamW, bf16 compute / fp32 params) -> StatsTracker -> periodic sharded
    checkpoint. Loop structure follows the reference driver
    (``:194-473``): epoch loop, set_epoch, per-optimizer-step metrics update,
    save every ``--save_every`` steps plus a final save.
"""

from __future__ import annotations

import argparse
import os
import signal
import time
from typing import Any

import numpy as np

from gpt_2_distributed_tpu.config import MODEL_PRESETS, CoordinationPolicy
from gpt_2_distributed_tpu.ops.losses import DEFAULT_BLOCK_ROWS
from gpt_2_distributed_tpu.data.dataloader import (
    DEFAULT_BATCH_SIZE,
    DEFAULT_CONTEXT_LENGTH,
    DEFAULT_NUM_WORKERS,
    DEFAULT_PREFETCH_FACTOR,
    TokenShardDataset,
    create_dataloader,
    cursor_plan_digest,
    get_shard_paths,
    replay_cursor_history,
)

DEFAULT_SEED = 42  # reference global seed, /root/reference/train_gpt2_distributed.py:39


def _claim_one_shot(save_dir: str | None, name: str, fired: set) -> bool:
    """True exactly once per (resumable) run for a named fault injection.

    Marker file in ``save_dir`` when given — it survives supervised
    relaunches, so an injection fires once across the whole supervise
    lifecycle (the ``--inject_fail_at`` pattern) — otherwise an in-process
    set, good enough for single-invocation tests without a save dir.
    """
    if save_dir:
        os.makedirs(save_dir, exist_ok=True)
        marker = os.path.join(save_dir, f".{name}")
        if os.path.exists(marker):
            return False
        with open(marker, "w") as f:
            f.write("1")
        return True
    if name in fired:
        return False
    fired.add(name)
    return True


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="gpt_2_distributed_tpu.train",
        description="GPT-2 pretraining on TPU (JAX/XLA); capability parity "
        "with dpickem/gpt_2_distributed's train_gpt2_distributed.py",
    )
    p.add_argument("--data_dir", required=True, help="directory of uint16 .bin token shards")
    p.add_argument("--split", default="train")
    p.add_argument(
        "--training_mode", default="local", choices=["local", "dp", "ddp", "fsdp"],
        help="execution mode; all modes are sharding configs of one jitted step",
    )
    p.add_argument(
        "--mesh", default=None,
        help="explicit mesh shape 'data=K,fsdp=N[,sp=S][,tp=T]' (overrides "
        "--training_mode); sp>1 shards the sequence (ring attention), tp>1 "
        "shards weights Megatron-style",
    )
    p.add_argument(
        "--attention_impl", default=None,
        choices=["auto", "dense", "flash", "ring"],
        help="attention kernel (default: the preset's 'auto' policy — ring "
        "when the mesh has sp>1, flash on TPU, dense otherwise)",
    )
    p.add_argument(
        "--shard_update", default="auto", choices=["off", "on", "auto"],
        help="ZeRO-2-style cross-replica sharded weight update "
        "(parallel/sharding.py update_pspecs): reduce-scatter the "
        "accumulated gradient over the 'data' axis, keep the AdamW moments "
        "and the update sharded (~1/data optimizer memory and update "
        "flops), all-gather the fresh params — same comms volume as the "
        "grad all-reduce. 'auto' (default) enables it on meshes with "
        "data>1 and fsdp==1, where the update is otherwise fully "
        "replicated; 'on' forces it on any data>1 mesh (composes with "
        "fsdp); numerics match the replicated update to fp32 roundoff",
    )
    p.add_argument(
        "--device_prefetch", default="on", choices=["on", "off"],
        help="device-side double-buffered batch prefetch: issue the H2D "
        "transfer (shard_batch) for optimizer step i+1 right after "
        "dispatching step i, before blocking on step i-1's metrics, so the "
        "host->device copy hides behind device compute. Identical batches "
        "in identical order — numerics unchanged",
    )
    p.add_argument("--model", default="124M", choices=sorted(MODEL_PRESETS))
    # Architecture overrides on top of the preset (smoke tests / ablations);
    # the reference exposes no size control at all (SURVEY.md §5.6).
    p.add_argument("--n_layer", type=int, default=None)
    p.add_argument("--n_embd", type=int, default=None)
    p.add_argument("--n_head", type=int, default=None)
    p.add_argument("--vocab_size", type=int, default=None)
    p.add_argument("--seq_len", type=int, default=DEFAULT_CONTEXT_LENGTH)
    p.add_argument(
        "--batch", type=int, default=DEFAULT_BATCH_SIZE,
        help="per-DEVICE micro-batch size (the reference's --batch is "
        "per-GPU, /root/reference/train_gpt2_distributed.py:297; the global "
        "micro-batch is batch x mesh devices)",
    )
    p.add_argument("--grad_accum_steps", type=int, default=4)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--lr_schedule", default="constant", choices=["constant", "cosine"])
    p.add_argument("--warmup_steps", type=int, default=0)
    p.add_argument("--max_steps", type=int, default=0, help="stop after N optimizer steps (0 = no cap)")
    p.add_argument("--weight_decay", type=float, default=0.1)
    p.add_argument(
        "--eval_every", type=int, default=0,
        help="evaluate on the val split every N optimizer steps (0 = off)",
    )
    p.add_argument(
        "--eval_batches", type=int, default=16,
        help="number of val batches per evaluation",
    )
    p.add_argument("--save_every", type=int, default=1000)
    p.add_argument("--save_dir", default=None)
    p.add_argument(
        "--async_save", default="on", choices=["on", "off"],
        help="non-blocking periodic checkpoints (ROADMAP resilience item a): "
        "the step loop pays only the device->host snapshot; the sharded "
        "write, manifest+CRC verification, COMMITTED sentinel and retention "
        "GC run on a background thread. 'off' restores fully synchronous "
        "saves. Emergency/final saves are always synchronous and committed.",
    )
    p.add_argument(
        "--keep_last_n", type=int, default=0,
        help="retention GC: keep only the newest N committed checkpoints "
        "(0 = keep all). The newest committed checkpoint is never deleted; "
        "uncommitted/failed save dirs are always pruned.",
    )
    p.add_argument(
        "--save_retries", type=int, default=2,
        help="retry a transiently failing checkpoint save this many times "
        "(exponential backoff); exhausted retries degrade to a warning + "
        "the save_failures metric instead of killing the run",
    )
    p.add_argument(
        "--save_retry_backoff", type=float, default=0.5,
        help="initial save-retry backoff in seconds (doubles per attempt)",
    )
    p.add_argument(
        "--preempt_poll_url", default=None,
        help="poll this preemption-notice URL (e.g. the GCE metadata "
        "endpoint, resilience.GCE_METADATA_PREEMPTED_URL) on a background "
        "thread; a TRUE response triggers the same emergency-save + rc 143 "
        "path as SIGTERM, usually with more grace time. Default: off.",
    )
    p.add_argument(
        "--preempt_poll_interval", type=float, default=5.0,
        help="seconds between preemption-notice polls",
    )
    p.add_argument("--log_dir", default=None)
    p.add_argument("--workers", type=int, default=DEFAULT_NUM_WORKERS)
    p.add_argument("--prefetch_factor", type=int, default=DEFAULT_PREFETCH_FACTOR)
    p.add_argument("--seed", type=int, default=DEFAULT_SEED)
    p.add_argument("--resume", action="store_true", help="resume from latest checkpoint in --save_dir")
    p.add_argument(
        "--inject_fail_at", type=int, default=0,
        help="fault injection for elastic-restart testing (SURVEY.md §5.3 — "
        "the reference has none): hard-exit rc 13 the first time optimizer "
        "step N completes. One-shot via a marker file in --save_dir, so a "
        "supervised relaunch (scripts/supervise.sh) proves resume-after-"
        "crash end-to-end. 0 = off; requires --save_dir.",
    )
    p.add_argument(
        "--step_guard", default="on", choices=["on", "off"],
        help="non-finite step guard (resilience layer 1): lax.cond-gate the "
        "optimizer update on isfinite(loss) & isfinite(grad_norm) — a bad "
        "step applies the identity update (params/opt-state unchanged) and "
        "is counted in the skipped_steps metric; 'off' restores the "
        "unguarded step exactly",
    )
    p.add_argument(
        "--guard_max_grad_norm", type=float, default=0.0,
        help="per-layer clip fallback (resilience, ROADMAP item c): when the "
        "guard sees a FINITE gradient whose global norm exceeds this, clip "
        "each layer to --guard_clip_norm and apply instead of skipping the "
        "step; non-finite values still skip. Counted in the clipped_steps "
        "metric. 0 = off; requires --step_guard on.",
    )
    p.add_argument(
        "--guard_clip_norm", type=float, default=1.0,
        help="per-layer L2 norm each gradient leaf is clipped to when the "
        "--guard_max_grad_norm fallback engages",
    )
    p.add_argument(
        "--spike_sigma", type=float, default=6.0,
        help="loss-spike threshold in EMA standard deviations (one-sided, "
        "upward); spiking and guard-skipped steps count toward the "
        "rollback policy",
    )
    p.add_argument(
        "--max_consecutive_skips", type=int, default=3,
        help="after this many consecutive skipped/spiking steps, restore "
        "the last verified checkpoint and fast-forward the dataloader "
        "past the offending batches",
    )
    p.add_argument(
        "--max_rollbacks", type=int, default=3,
        help="abort the run after this many spike rollbacks (a loss that "
        "keeps diverging needs a human, not a loop)",
    )
    p.add_argument(
        "--inject_nan_at", type=int, default=0,
        help="fault injection: poison one micro-batch's loss with NaN on "
        "the optimizer step that would complete as step N (one-shot via a "
        "marker file when --save_dir is set, so supervised relaunches "
        "don't re-fire). Requires --step_guard on. 0 = off.",
    )
    p.add_argument(
        "--inject_preempt_at", type=int, default=0,
        help="fault injection: SIGTERM this process after optimizer step N "
        "completes (one-shot marker in --save_dir), exercising the "
        "preemption handler end-to-end: emergency save, exit rc 143, "
        "supervised resume. 0 = off; requires --save_dir.",
    )
    p.add_argument(
        "--inject_save_fail_at", type=int, default=0,
        help="fault injection: the first --inject_save_fail_count attempts "
        "of the checkpoint save at step N raise, exercising the retry/"
        "backoff path (and, when retries are exhausted, the degrade-to-"
        "warning path) on CPU. One-shot marker in --save_dir. 0 = off; "
        "requires --save_dir.",
    )
    p.add_argument(
        "--inject_save_fail_count", type=int, default=1,
        help="how many attempts of the injected save failure raise before "
        "the save is allowed to succeed",
    )
    p.add_argument(
        "--inject_preempt_notice_at", type=int, default=0,
        help="fault injection: the preemption POLLER (not SIGTERM) sees a "
        "cloud preemption notice once optimizer step N completes — a "
        "file:// notice endpoint in --save_dir flips to TRUE, exercising "
        "PreemptionPoller -> emergency save -> rc 143 end-to-end on CPU. "
        "One-shot marker in --save_dir. 0 = off; requires --save_dir.",
    )
    p.add_argument(
        "--desync_check_every", type=int, default=0,
        help="multi-host control plane (coordination.py): every N optimizer "
        "steps, allgather and compare a cheap device-side parameter "
        "fingerprint across hosts; a mismatch names the drifted ranks, "
        "counts in the desync_detected metric, and rolls the whole pod back "
        "to the last verified checkpoint. 0 = off. Identity single-process.",
    )
    p.add_argument(
        "--consensus_every", type=int, default=1,
        help="multi-host control plane: run the pod-wide control-word "
        "allgather every K optimizer steps instead of every step (default "
        "1). Fault flags (preempt, worker death, rollback demand, failed "
        "saves) latch host-locally between exchanges and ride the next one; "
        "actions fire only at exchange boundaries, so decisions stay "
        "pod-consistent at any K at the cost of up to K-1 steps of extra "
        "action latency (README multi-host section). Identity "
        "single-process.",
    )
    p.add_argument(
        "--hang_timeout_s", type=float, default=0.0,
        help="hang watchdog (coordination.py): if no optimizer step "
        "completes within this many seconds, dump all-thread stacks, "
        "attempt a bounded best-effort emergency save, and exit rc 170 for "
        "a supervised FULL-JOB restart (burns a restart attempt, unlike "
        "preemption's rc 143). Size it well above the worst-case step time; "
        "the watchdog arms only once the first step completes, so initial "
        "compilation is excluded. 0 = off (default).",
    )
    p.add_argument(
        "--data_read_retries", type=int, default=2,
        help="retry transient shard-I/O errors (OSError on memmap open/read "
        "— GCS-FUSE/NFS flake) this many times with doubling backoff before "
        "failing the epoch; counted in the data_read_retries metric. "
        "Corrupt-token errors are never retried.",
    )
    p.add_argument(
        "--inject_desync_at", type=int, default=0,
        help="fault injection: multiply the LAST rank's params by 1.001 "
        "just before optimizer step N (symmetric dispatch, rank-conditional "
        "value — the injection cannot itself deadlock the collectives it "
        "tests), exercising the --desync_check_every detector end-to-end "
        "on CPU. One-shot marker in --save_dir when set. 0 = off; requires "
        "--desync_check_every.",
    )
    p.add_argument(
        "--inject_hang_at", type=int, default=0,
        help="fault injection: rank 0 sleeps inside the step loop just "
        "before optimizer step N, exercising the --hang_timeout_s watchdog "
        "(every rank exits rc 170 — the hung rank from its own sleep, its "
        "peers from the collective it never joins). One-shot. 0 = off; "
        "requires --hang_timeout_s > 0.",
    )
    p.add_argument(
        "--inject_world_size", type=int, default=0,
        help="fault injection for the elastic path: pretend the observed "
        "world has N devices at resume, so the re-mesh + grad-accum rescale "
        "+ cursor migration run on a single CPU host without a pod. The "
        "checkpoint's saved world record is compared against N instead of "
        "the real device count. 0 = off; requires --resume and --save_dir.",
    )
    p.add_argument(
        "--dropout", type=float, default=None,
        help="override every dropout rate (embedding, attention, residual) "
        "with one value; default keeps the preset's rates. --dropout 0 "
        "makes runs deterministic across batch arrangements — required for "
        "cross-world trajectory comparisons, since dropout masks are drawn "
        "per position in the [accum, batch, seq] layout",
    )
    p.add_argument(
        "--inject_worker_fail_at", type=int, default=0,
        help="fault injection: data worker 0 on rank 0 raises after "
        "producing N batches, exercising worker-error propagation (single-"
        "process: loud RuntimeError, unchanged; multi-host: pod-wide "
        "coordinated abort rc 171 instead of N-1 hosts deadlocked). "
        "One-shot. 0 = off.",
    )
    p.add_argument(
        "--remat", nargs="?", const="block", default=False,
        choices=["block", "mlp", "attn", "dots"],
        help="activation checkpointing: 'block' (full, lowest memory; the "
        "bare flag means this), 'mlp' (remat only the MLP sublayer — "
        "attention runs once; the throughput sweet spot when memory allows) "
        "or 'dots' (checkpoint-policy: save matmul outputs, replay only "
        "elementwise ops — measured slower than both at 124M, situational)",
    )
    p.add_argument(
        "--accum_dtype", default="fp32", choices=["fp32", "bf16"],
        help="gradient-accumulator carry dtype: fp32 (torch-autocast "
        "parity, default) or bf16 (halves the carry — the knob that admits "
        "accum>1 for 774M on one 16G chip; mirrors the reference FSDP's "
        "bf16 gradient reduction, "
        "/root/reference/train_gpt2_distributed.py:151-155)",
    )
    p.add_argument(
        "--loss_impl", default="blocked", choices=["blocked", "dense"],
        help="training loss: 'blocked' logit-free chunked CE (O(rows*V) HBM) "
        "or 'dense' full-logits XLA autodiff (only viable at small "
        "micro-batches; see PERF_ANALYSIS.md)",
    )
    p.add_argument(
        "--fused_layers", default="off", choices=["off", "ln", "gelu", "all"],
        help="fused Pallas layer-epilogue kernels (ops/fused_layer.py): 'ln' "
        "fuses residual+dropout+layernorm at the sublayer junctions, 'gelu' "
        "fuses the MLP's bias+GELU+dropout epilogue, 'all' both. Default "
        "'off' until a benchmark cell confirms the win on-chip; unsupported "
        "shapes/meshes fall back to the unfused path automatically",
    )
    p.add_argument(
        "--fused_matmul", default="off", choices=["off", "mlp", "proj", "all"],
        help="fused matmul+epilogue Pallas kernels (ops/fused_matmul.py, "
        "v2): the matmul runs in a tiled MXU kernel with the epilogue "
        "applied to the fp32 accumulator tile before write-back. 'mlp' "
        "fuses the fc leg (matmul+bias+GELU+dropout), 'proj' the two proj "
        "legs (matmul+bias+residual+dropout), 'all' both plus the qkv leg. "
        "Composable with --fused_layers (fused_matmul wins on shared legs). "
        "Default 'off' until a benchmark cell confirms the win "
        "on-chip; unsupported shapes/meshes fall back to the unfused path, "
        "counted in the fused_fallback metric",
    )
    p.add_argument(
        "--loss_block_rows", type=int, default=0,
        help="blocked-CE chunk rows (0 = preset default "
        f"{DEFAULT_BLOCK_ROWS}; smaller trades throughput for peak-HBM "
        "headroom)",
    )
    p.add_argument(
        "--scan_layers", default="auto", choices=["auto", "on", "off"],
        help="block stack as one lax.scan ('on': constant-size HLO, fast "
        "compile — needed for 774M/1.5B) or unrolled ('off': ~11%% faster "
        "steps, XLA schedules across layer boundaries — see "
        "PERF_ANALYSIS.md). 'auto' unrolls 124M/345M, scans larger presets.",
    )
    p.add_argument(
        "--device", default=None, choices=["tpu", "cpu", "gpu"],
        help="JAX platform to run on (parity with the reference's --device, "
        "/root/reference/train_gpt2_distributed.py:292-294); overrides the "
        "JAX_PLATFORMS env var; default = JAX's own platform selection",
    )
    p.add_argument("--profile", action="store_true", help="jax.profiler trace into --log_dir")
    p.add_argument(
        "--xla_profile_at", default=None, metavar="STEP[:NSTEPS]",
        help="on-demand XLA profiler window: capture NSTEPS (default 1) "
        "optimizer steps starting at STEP into <log_dir>/xla_profile; host "
        "spans bridge into the device timeline via TraceAnnotation. Unlike "
        "--profile this skips compile/warmup noise and bounds trace size.",
    )
    p.add_argument(
        "--trace_dir", default=None,
        help="enable structured span tracing: per-process trace-p{rank}.jsonl "
        "written here (obs/trace.py); analyze with scripts/obs_report.py. "
        "Default off — the tracer is then a pure no-op.",
    )
    p.add_argument(
        "--trace_max_file_bytes", type=int, default=64 * 1024 * 1024,
        help="rotation bound per trace file (live file + one .1 generation)",
    )
    p.add_argument("--cli_every", type=int, default=20)
    p.add_argument("--tb_every", type=int, default=1)
    p.add_argument("--coordinator_address", default=None)
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)
    return p


def validate_mesh_for_config(spec, config, model_name: str, seq_len: int) -> None:
    """Parse-time mesh x model validation (round-3 VERDICT weak-point #6).

    Catches at the CLI boundary what would otherwise surface as a mid-run
    warning (tp leaving qkv replicated, ``parallel/sharding.py``) or a
    compile error (sp not dividing the sequence): a ``tp`` degree must divide
    the preset's ``n_head`` (head-explicit qkv sharding splits the head
    axis), and an ``sp`` degree must divide ``--seq_len`` (ring attention
    assigns each device a contiguous T/sp chunk)."""
    if spec.tp > 1 and config.n_head % spec.tp != 0:
        valid = [d for d in range(2, config.n_head + 1) if config.n_head % d == 0]
        raise ValueError(
            f"tp={spec.tp} does not divide n_head={config.n_head} of model "
            f"{model_name!r}: qkv/attention weights would stay replicated "
            f"across 'tp' (wasted flops). Valid tp degrees for this model: "
            f"{valid}"
        )
    if spec.sp > 1 and seq_len % spec.sp != 0:
        raise ValueError(
            f"sp={spec.sp} does not divide seq_len={seq_len}: ring attention "
            f"needs a whole T/sp sequence chunk per device"
        )


def elastic_rescale_accum(
    saved_global_batch: int, batch: int, n_devices: int
) -> int:
    """The grad-accum count that holds the global batch constant across an
    elastic world resize: ``global_batch = batch x n_devices x grad_accum``.

    Raises ValueError when no integer rescale exists, naming the offending
    values and the nearest valid operating points — exact
    ``--batch``/``--grad_accum_steps`` pairs when the device count divides
    the saved global batch, the nearest achievable global batches otherwise
    (satellite: never a bare divisibility failure).
    """
    per_step = batch * n_devices
    if saved_global_batch % per_step == 0:
        return saved_global_batch // per_step
    if saved_global_batch % n_devices == 0:
        # The world can hold the global batch — just not with this --batch.
        q = saved_global_batch // n_devices
        pairs = sorted(
            ((b, q // b) for b in range(1, q + 1) if q % b == 0),
            key=lambda p: (abs(p[0] - batch), p[0]),
        )
        near = ", ".join(
            f"--batch {b} --grad_accum_steps {a}" for b, a in pairs[:3]
        )
        raise ValueError(
            f"global batch {saved_global_batch} (saved in the checkpoint) is "
            f"not reconstructible with --batch {batch} at {n_devices} "
            f"device(s): {saved_global_batch} / ({batch} x {n_devices}) = "
            f"{saved_global_batch / per_step:.4g} grad-accum steps. Nearest "
            f"valid operating points at {n_devices} device(s): {near}"
        )
    a_lo = max(1, saved_global_batch // per_step)
    raise ValueError(
        f"no --batch/--grad_accum_steps pair reproduces global batch "
        f"{saved_global_batch} (saved in the checkpoint) at {n_devices} "
        f"device(s) — {saved_global_batch} is not divisible by {n_devices}. "
        f"Nearest achievable with --batch {batch}: --grad_accum_steps "
        f"{a_lo} (global {a_lo * per_step}) or --grad_accum_steps "
        f"{a_lo + 1} (global {(a_lo + 1) * per_step})"
    )


def _common_min(value: int) -> int:
    """Cross-process minimum of a host scalar (identity single-process).

    Every quantity that bounds a loop of collective steps — batches per
    epoch, eval batch count, the LR-schedule horizon — must be identical on
    all processes, or hosts dispatch different collective sequences and the
    job deadlocks / parameters silently diverge. The dataloader's round-robin
    shard assignment makes per-process batch counts unequal (shard-count
    remainders), so the common value is the minimum.
    """
    import jax

    if jax.process_count() == 1:
        return int(value)
    import numpy as np
    from jax.experimental import multihost_utils

    return int(np.min(multihost_utils.process_allgather(
        np.asarray(value, np.int64))))


def make_lr_schedule(args, steps_per_epoch: int):
    import optax

    total = args.max_steps or max(1, steps_per_epoch * args.epochs)
    if args.lr_schedule == "cosine":
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=args.lr,
            warmup_steps=args.warmup_steps,
            decay_steps=total,
            end_value=args.lr * 0.1,
        )
    if args.warmup_steps:
        return optax.linear_schedule(0.0, args.lr, args.warmup_steps)
    return args.lr


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    if args.inject_fail_at and not args.save_dir:
        build_parser().error("--inject_fail_at needs --save_dir (one-shot marker + resume target)")
    if args.inject_preempt_at and not args.save_dir:
        build_parser().error("--inject_preempt_at needs --save_dir (one-shot marker + resume target)")
    if args.inject_nan_at and args.step_guard != "on":
        build_parser().error("--inject_nan_at requires --step_guard on (an unguarded NaN update poisons the params permanently)")
    if args.inject_save_fail_at and not args.save_dir:
        build_parser().error("--inject_save_fail_at needs --save_dir (one-shot marker + save target)")
    if args.inject_preempt_notice_at and not args.save_dir:
        build_parser().error("--inject_preempt_notice_at needs --save_dir (notice file + one-shot marker)")
    if args.guard_max_grad_norm and args.step_guard != "on":
        build_parser().error("--guard_max_grad_norm requires --step_guard on (the clip fallback lives inside the guarded step)")
    if args.inject_hang_at and args.hang_timeout_s <= 0:
        build_parser().error("--inject_hang_at requires --hang_timeout_s > 0 (otherwise the injected hang sleeps unwatched)")
    if args.inject_desync_at and not args.desync_check_every:
        build_parser().error("--inject_desync_at requires --desync_check_every > 0 (nothing would ever detect the injected divergence)")
    if args.inject_world_size and not (args.resume and args.save_dir):
        build_parser().error("--inject_world_size needs --resume and --save_dir (it overrides the observed world at resume; there is nothing to resize without a checkpoint)")
    if args.inject_world_size < 0:
        build_parser().error(f"--inject_world_size must be >= 1 device, got {args.inject_world_size}")
    if args.dropout is not None and not (0.0 <= args.dropout < 1.0):
        build_parser().error(f"--dropout must be in [0, 1), got {args.dropout}")
    try:
        coord_policy = CoordinationPolicy(
            desync_check_every=args.desync_check_every,
            hang_timeout_s=args.hang_timeout_s,
            consensus_every=args.consensus_every,
        )
    except ValueError as e:
        build_parser().error(str(e))

    from gpt_2_distributed_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()

    # --device (highest priority), then JAX_PLATFORMS. The config update
    # also covers in-process callers that imported jax before main() ran,
    # where the env var alone would come too late.
    platform = args.device or os.environ.get("JAX_PLATFORMS")
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)

    from gpt_2_distributed_tpu.parallel.mesh import (
        MeshSpec,
        activate_mesh,
        create_mesh,
        elastic_respec,
        init_distributed,
        is_primary,
    )

    init_distributed(args.coordinator_address, args.num_processes, args.process_id)

    import jax

    from gpt_2_distributed_tpu import checkpoint as ckpt
    from gpt_2_distributed_tpu.config import CheckpointPolicy
    from gpt_2_distributed_tpu.resilience import (
        DATA_ABORT_EXIT_CODE,
        PREEMPTED_EXIT_CODE,
        SKIP_REASON_NAMES,
        PreemptionHandler,
        PreemptionPoller,
        SpikeMonitor,
        init_guard_state,
    )
    from gpt_2_distributed_tpu.coordination import (
        ConsensusBus,
        HangWatchdog,
        assert_pod_agreement,
        check_fingerprints,
        decode_control_word,
        encode_control_word,
        fingerprint_params,
        perturb_params,
    )
    from gpt_2_distributed_tpu.metrics.tracker import StatsTracker
    from gpt_2_distributed_tpu.models import gpt2
    from gpt_2_distributed_tpu.ops.spmd import fused_fallback_count
    from gpt_2_distributed_tpu.parallel.sharding import (
        resolve_shard_update,
        shard_batch,
        shard_params_and_opt_state,
        sharded_update_spec,
    )
    from gpt_2_distributed_tpu.parallel.train_step import (
        make_eval_step,
        make_optimizer,
        make_train_step,
    )
    from gpt_2_distributed_tpu.utils.flops import device_peak_flops, flops_per_token
    from gpt_2_distributed_tpu.obs import compile_watch
    from gpt_2_distributed_tpu.obs.trace import (
        XlaCapture,
        configure_tracing,
        get_tracer,
        parse_profile_at,
    )

    # --- observability ------------------------------------------------------
    # Tracing defaults off; when off, get_tracer() hands out a no-op and no
    # trace file is ever created (asserted by tests/test_obs.py).
    if args.trace_dir:
        configure_tracing(
            args.trace_dir,
            process_index=jax.process_index(),
            max_file_bytes=args.trace_max_file_bytes,
        )
    tracer = get_tracer()
    try:
        xla_profile_spec = parse_profile_at(args.xla_profile_at)
    except ValueError as e:
        raise SystemExit(f"error: {e}") from None
    if xla_profile_spec and not args.log_dir:
        raise SystemExit(
            "error: --xla_profile_at needs --log_dir (the capture lands in "
            "<log_dir>/xla_profile)"
        )
    if xla_profile_spec and args.profile:
        raise SystemExit(
            "error: --xla_profile_at and --profile both drive "
            "jax.profiler.start_trace; profiler sessions cannot nest — "
            "pick one"
        )
    xla_capture = XlaCapture(xla_profile_spec, args.log_dir)

    # --- config ------------------------------------------------------------
    overrides = {
        k: getattr(args, k)
        for k in ("n_layer", "n_embd", "n_head", "vocab_size")
        if getattr(args, k) is not None
    }
    if args.scan_layers == "auto":
        scan_layers = args.model not in ("124M", "345M")
    else:
        scan_layers = args.scan_layers == "on"
    config = MODEL_PRESETS[args.model].replace(
        n_positions=args.seq_len, remat=args.remat, scan_layers=scan_layers,
        loss_impl=args.loss_impl, **overrides
    )
    if args.attention_impl:
        config = config.replace(attention_impl=args.attention_impl)
    if args.loss_block_rows:
        config = config.replace(loss_block_rows=args.loss_block_rows)
    if args.fused_layers != "off":
        config = config.replace(fused_layers=args.fused_layers)
    if args.fused_matmul != "off":
        config = config.replace(fused_matmul=args.fused_matmul)
    if args.dropout is not None:
        config = config.replace(
            embd_dropout=args.dropout,
            attn_dropout=args.dropout,
            resid_dropout=args.dropout,
        )

    # --- mesh ---------------------------------------------------------------
    try:
        spec = MeshSpec.parse(args.mesh) if args.mesh else MeshSpec.for_mode(args.training_mode)
        validate_mesh_for_config(spec, config, args.model, args.seq_len)
    except ValueError as e:
        raise SystemExit(f"error: {e}") from None

    # --- elastic resume: survive a world resize ------------------------------
    # When --resume finds a checkpoint saved at a different world size (a
    # host lost to preemption, or --inject_world_size faking one), re-derive
    # the mesh from the SAVED spec — only the data axis moves; fsdp/sp/tp are
    # baked into the model layout — and rescale --grad_accum_steps so the
    # global batch the optimizer sees is unchanged. The restored arrays
    # reshard onto the new mesh for free: global shapes are unchanged, so the
    # sharding-annotated restore targets re-place every leaf (including
    # --shard_update's data-sharded moments, whose shard count follows the
    # new data degree). Observable via elastic_resizes / resume_world_delta.
    elastic_delta = 0
    saved_world: dict | None = None
    if args.resume and args.save_dir:
        peeked = ckpt.peek_latest_meta(args.save_dir)
        saved_world = peeked.world if peeked is not None else None
    if saved_world:
        saved_devices = int(saved_world["device_count"])
        capacity = args.inject_world_size or jax.device_count()
        respec_from = None
        if args.inject_world_size and args.inject_world_size != saved_devices:
            respec_from = capacity
        elif spec.n_devices > capacity:
            # The requested mesh no longer fits (a real host loss under an
            # explicit --mesh); rebuild from the saved spec on what is left.
            respec_from = capacity
        if respec_from is not None:
            try:
                spec = elastic_respec(
                    MeshSpec.parse(saved_world["mesh"]), respec_from
                )
                validate_mesh_for_config(spec, config, args.model, args.seq_len)
            except ValueError as e:
                raise SystemExit(f"error: elastic resume: {e}") from None
        if spec.n_devices != saved_devices:
            old_accum = args.grad_accum_steps
            try:
                args.grad_accum_steps = elastic_rescale_accum(
                    int(saved_world["global_batch"]), args.batch, spec.n_devices
                )
            except ValueError as e:
                raise SystemExit(f"error: elastic resume: {e}") from None
            elastic_delta = spec.n_devices - saved_devices
            tracer.event(
                "elastic_resize",
                old_devices=saved_devices, new_devices=spec.n_devices,
            )
            if is_primary():
                print(
                    f"[elastic] world resized: {saved_devices} -> "
                    f"{spec.n_devices} device(s) (saved mesh "
                    f"{saved_world['mesh']} -> {spec.to_str()}); "
                    f"--grad_accum_steps {old_accum} -> "
                    f"{args.grad_accum_steps} holds the global batch at "
                    f"{int(saved_world['global_batch'])}"
                )
        # Startup barrier: every host independently peeked the checkpoint and
        # derived the new world — a rank reading a stale save_dir replica (or
        # launched with drifted flags) must fail HERE, loudly, not desync the
        # pod at the first training collective. Doubles as a rendezvous of
        # the (possibly smaller) surviving world.
        assert_pod_agreement("elastic device count", float(spec.n_devices))
        assert_pod_agreement(
            "elastic grad_accum_steps", float(args.grad_accum_steps)
        )
    mesh = create_mesh(spec)
    use_shard_update = resolve_shard_update(args.shard_update, mesh)
    # --batch is per device (DDP parity: the reference's --batch is per GPU
    # process); each host's loader assembles the slice its local devices own.
    devices_per_process = max(1, spec.n_devices // jax.process_count())
    local_batch = args.batch * devices_per_process

    # --- data --------------------------------------------------------------
    shard_paths = get_shard_paths(args.data_dir, args.split)
    dataset = TokenShardDataset(
        shard_paths,
        seq_len=args.seq_len,
        num_workers=args.workers,
        vocab_size=config.vocab_size,
        data_read_retries=args.data_read_retries,
    )
    # One optimizer step consumes grad_accum local micro-batches. The count
    # feeds the cosine schedule's decay horizon, so it must be the
    # cross-process common value — per-process counts differ (see _common_min).
    steps_per_epoch = (
        _common_min(dataset.batches_per_epoch(local_batch))
        // args.grad_accum_steps
    )
    from gpt_2_distributed_tpu.utils.device_info import (
        device_memory_lines,
        print_device_info,
    )

    if is_primary():
        print_device_info()
        extra = ""
        if spec.sp > 1 or spec.tp > 1:
            extra = f", sp={spec.sp}, tp={spec.tp}"
        if use_shard_update:
            extra += ", shard_update"
        print(
            f"mesh: data={spec.data}, fsdp={spec.fsdp}{extra} | "
            f"model: {args.model} "
            f"({config.num_params()/1e6:.1f}M params) | "
            f"steps/epoch: {steps_per_epoch}"
        )
        from gpt_2_distributed_tpu import native

        print(f"dataloader window gather: {native.describe()}")
        from gpt_2_distributed_tpu.utils.operating_point import (
            accum_cliff_message,
            warn_once,
        )

        cliff = accum_cliff_message(
            args.seq_len, args.grad_accum_steps, config.scan_layers
        )
        if cliff:
            warn_once("accum_cliff", cliff)

    schedule = make_lr_schedule(args, steps_per_epoch)
    optimizer = make_optimizer(schedule, weight_decay=args.weight_decay)
    params = gpt2.init_params(config, seed=args.seed)

    with activate_mesh(mesh):
        params, opt_state, param_shardings, opt_shardings = (
            shard_params_and_opt_state(
                params, optimizer, mesh, shard_update=use_shard_update
            )
        )
        import jax.numpy as jnp

        use_guard = args.step_guard == "on"
        device_prefetch = args.device_prefetch == "on"
        train_step = make_train_step(
            config, optimizer,
            accum_dtype=jnp.bfloat16 if args.accum_dtype == "bf16" else None,
            guard=use_guard,
            clip_threshold=args.guard_max_grad_norm or None,
            layer_clip_norm=args.guard_clip_norm,
            sharded_update=(
                sharded_update_spec(params, optimizer, mesh)
                if use_shard_update else None
            ),
        )
        guard_state = init_guard_state() if use_guard else None
        monitor = (
            SpikeMonitor(
                sigma=args.spike_sigma,
                max_consecutive=args.max_consecutive_skips,
            )
            if use_guard else None
        )
        # loss_scale is all-ones in production; --inject_nan_at swaps in
        # nan_scale for one step (same shape/dtype, so no retrace). Its NaN
        # is written on the host, and only when asked for: made on the
        # device it trips jax_debug_nans on a run that injects nothing.
        ones_scale = (
            jnp.ones((args.grad_accum_steps,), jnp.float32) if use_guard else None
        )
        nan_scale = None
        if args.inject_nan_at:
            poisoned = np.ones((args.grad_accum_steps,), np.float32)
            poisoned[0] = np.nan
            nan_scale = jnp.asarray(poisoned)

        # --- checkpoint lifecycle -------------------------------------------
        # One saver per run: async writes + commit protocol + retries + GC
        # (checkpoint.CheckpointSaver). Fault injection for the retry path is
        # one-shot across supervised relaunches, like --inject_fail_at.
        saver = None
        if args.save_dir:
            saver = ckpt.CheckpointSaver(
                args.save_dir,
                CheckpointPolicy(
                    async_save=args.async_save == "on",
                    keep_last_n=args.keep_last_n,
                    save_retries=args.save_retries,
                    retry_backoff_s=args.save_retry_backoff,
                ),
            )
            if args.inject_save_fail_at and _claim_one_shot(
                args.save_dir,
                f"save_fail_injected_{args.inject_save_fail_at}",
                set(),
            ):
                saver.inject_fail_at = args.inject_save_fail_at
                saver.inject_fail_count = args.inject_save_fail_count

        # --- resume ---------------------------------------------------------
        start_epoch, skip_steps, global_step, total_tokens = 0, 0, 0, 0
        # Cursor-migration state: when a world resize re-partitions the
        # loader, the old world's consumption is excluded via a consumed-
        # window plan instead of the arithmetic prefix skip. cursor_base is
        # the optimizer-step count that plan already accounts for in epoch
        # cursor_epoch — the loader skips only steps taken SINCE the resize.
        cursor_base, cursor_epoch, cursor_record = 0, -1, None
        if args.resume and args.save_dir:
            # Prune stale uncommitted dirs (a crash mid-async-save leaves one)
            # and apply retention before picking a restore candidate.
            removed = ckpt.gc_checkpoints(args.save_dir, args.keep_last_n)
            if removed and is_primary():
                print(
                    "[ckpt] pruned on resume: "
                    + ", ".join(os.path.basename(p) for p in removed)
                )
            restored = ckpt.restore_latest_verified(
                args.save_dir, params, opt_state, param_shardings, opt_shardings
            )
            if restored is not None:
                params, opt_state, meta, latest = restored
                start_epoch = meta.epoch
                skip_steps = meta.batches_in_epoch
                global_step = meta.step
                total_tokens = meta.total_tokens
                if meta.rng_seed != args.seed and is_primary():
                    print(
                        f"warning: --seed {args.seed} differs from the "
                        f"checkpoint's seed {meta.rng_seed}; using the "
                        f"checkpoint's so dropout streams resume exactly"
                    )
                args.seed = meta.rng_seed
                if monitor is not None and meta.spike_monitor:
                    # Resume the EMA loss baseline (follow-up b): the monitor
                    # is armed immediately instead of sitting out a fresh
                    # warmup window blind to spikes.
                    monitor.load_state_dict(meta.spike_monitor)
                mw = meta.world or {}
                if elastic_delta and mw and int(
                    mw.get("global_batch", saved_world["global_batch"])
                ) != int(saved_world["global_batch"]):
                    # Restore fell back past a corrupt newest checkpoint onto
                    # one saved at yet another world — the mesh/accum derived
                    # from the peeked meta no longer match what was restored.
                    raise SystemExit(
                        f"error: elastic resume: restored {latest} was saved "
                        f"at global batch {mw.get('global_batch')} but the "
                        f"newest checkpoint's world record said "
                        f"{saved_world['global_batch']} (restore fell back "
                        f"past a corrupt checkpoint); delete the corrupt "
                        f"newest step dir and relaunch"
                    )
                # Data-cursor migration: the loader's (process, worker)
                # partitioning — shard ownership AND the epoch^rank^worker
                # offset-shuffle seeds — changed with the world, so the
                # arithmetic prefix skip would re-read some windows and drop
                # others. Reconstruct exactly which windows the old world
                # consumed this epoch and exclude them instead.
                needed = (
                    "process_count", "workers", "local_batch",
                    "grad_accum_steps",
                )
                prior = getattr(meta, "cursor_plan", None)
                if prior and int(prior.get("epoch", -1)) != meta.epoch:
                    # The partially-consumed epoch finished; its history
                    # is settled and carries nothing into this one.
                    prior = None
                if skip_steps > 0 and all(k in mw for k in needed):
                    old_shape = (
                        int(mw["process_count"]), int(mw["workers"]),
                        int(mw["local_batch"]),
                    )
                    new_shape = (
                        jax.process_count(), dataset.num_workers, local_batch,
                    )
                    # A prior record forces the migration path even at an
                    # unchanged shape: the restored world trained on a
                    # plan's complement, so the arithmetic prefix skip
                    # would replay the wrong stream.
                    if old_shape != new_shape or prior is not None:
                        resizes = list(prior["resizes"]) if prior else []
                        resizes.append({
                            "process_count": old_shape[0],
                            "workers": old_shape[1],
                            "local_batch": old_shape[2],
                            "grad_accum_steps": int(mw["grad_accum_steps"]),
                            "steps": skip_steps,
                        })
                        if prior is not None:
                            # Second same-epoch resize: recompute the plan
                            # the previous resume persisted and verify the
                            # digest — exactness proven, or fail loudly.
                            base = replay_cursor_history(
                                shard_paths, seq_len=args.seq_len,
                                epoch=meta.epoch, resizes=resizes[:-1],
                            )
                            got = cursor_plan_digest(base)
                            if got != prior["digest"]:
                                raise SystemExit(
                                    f"error: elastic resume: the consumed-"
                                    f"window plan persisted at the previous "
                                    f"same-epoch resize (digest "
                                    f"{prior['digest'][:12]}..., "
                                    f"{prior.get('windows')} windows) does "
                                    f"not reproduce from the current shards "
                                    f"(digest {got[:12]}...) — the data "
                                    f"files changed under a half-consumed "
                                    f"epoch, so the exact resume cursor is "
                                    f"unrecoverable; restart the epoch or "
                                    f"restore the original shards"
                                )
                            if is_primary():
                                print(
                                    f"[elastic] prior cursor plan verified "
                                    f"(digest {got[:12]}..., "
                                    f"{len(resizes) - 1} earlier resize(s) "
                                    f"this epoch)"
                                )
                        plan = replay_cursor_history(
                            shard_paths, seq_len=args.seq_len,
                            epoch=meta.epoch, resizes=resizes,
                        )
                        dataset.set_consumed(plan, epoch=meta.epoch)
                        cursor_base, cursor_epoch = skip_steps, meta.epoch
                        n_win = sum(len(v) for v in plan.values())
                        cursor_record = {
                            "epoch": meta.epoch,
                            "digest": cursor_plan_digest(plan),
                            "windows": n_win,
                            "resizes": resizes,
                        }
                        if is_primary():
                            print(
                                f"[elastic] data cursor migrated: old world "
                                f"(processes={old_shape[0]}, "
                                f"workers={old_shape[1]}, "
                                f"local_batch={old_shape[2]}) consumed "
                                f"{n_win} windows over {len(plan)} shard(s) "
                                f"this epoch; the new world resumes on the "
                                f"complement"
                            )
                if is_primary():
                    print(
                        f"resumed from {latest}: step {global_step}, epoch "
                        f"{start_epoch}, {skip_steps} steps into the epoch"
                    )
            elif is_primary():
                print(f"--resume: no checkpoint found in {args.save_dir}; starting fresh")

        # --- tracker ---------------------------------------------------------
        global_batch = args.batch * spec.n_devices * args.grad_accum_steps
        tracker = StatsTracker(
            args.log_dir,
            batch_size=global_batch,
            seq_len=args.seq_len,
            tb_every=args.tb_every,
            cli_every=args.cli_every,
            flops_per_token=flops_per_token(config, args.seq_len),
            peak_flops_per_chip=device_peak_flops(),
        )
        tracker.total_tokens = total_tokens

        # The world every checkpoint of this run is saved at — what a future
        # elastic resume needs to re-mesh (mesh/device_count), hold the global
        # batch (global_batch/batch/grad_accum_steps), and migrate the data
        # cursor (process_count/workers/local_batch).
        world_record = {
            "process_count": jax.process_count(),
            "device_count": spec.n_devices,
            "mesh": spec.to_str(),
            "global_batch": global_batch,
            "grad_accum_steps": args.grad_accum_steps,
            "batch": args.batch,
            "local_batch": local_batch,
            "workers": dataset.num_workers,
        }

        def make_meta(step: int, ep: int, batches: int) -> "ckpt.CheckpointMeta":
            return ckpt.CheckpointMeta(
                step=step, epoch=ep, batches_in_epoch=batches,
                rng_seed=args.seed,
                total_tokens=tracker.total_tokens,
                spike_monitor=monitor.state_dict() if monitor else None,
                world=world_record,
                # The same-epoch resize history travels with every
                # checkpoint of the partially-consumed epoch; once a new
                # epoch starts the stream is virgin again and the record
                # is dropped.
                cursor_plan=(cursor_record if ep == cursor_epoch else None),
            )

        # --- evaluation -------------------------------------------------------
        # Consumes the val split (shard 0 by the tokenizer's convention) the
        # reference reserves but never reads. Deterministic: epoch-0
        # permutation every time, so successive evals see the same batches.
        run_eval = None
        if args.eval_every:
            val_paths = get_shard_paths(args.data_dir, "val")
            # All processes must agree on whether eval runs at all — a host
            # with a partially-synced data_dir skipping eval while others run
            # its collectives would desynchronize the whole job.
            if not _common_min(int(bool(val_paths))):
                if is_primary():
                    print(
                        f"--eval_every: no 'val' shards in {args.data_dir} on "
                        f"every process; eval disabled"
                    )
                val_paths = []
            if val_paths:
                # Window-strided across processes (shard_windows=True): the
                # pipeline's convention is a single val shard (shard 0), so
                # shard-striding would give every host but one zero batches —
                # instead each host reads a disjoint 1/processes slice of the
                # windows and the hosts' slices assemble into one GLOBAL
                # batch (shard_batch's make_array_from_process_local_data
                # path), so eval cost is O(1/hosts) per host and the
                # eval_step's loss is already the global mean.
                eval_dataset = TokenShardDataset(
                    val_paths, seq_len=args.seq_len, num_workers=1,
                    vocab_size=config.vocab_size, shard_windows=True,
                    data_read_retries=args.data_read_retries,
                )
                eval_dataset.set_epoch(0)
                eval_step = make_eval_step(config)
                n_eval = min(
                    args.eval_batches,
                    _common_min(eval_dataset.batches_per_epoch(local_batch)),
                )
                if n_eval == 0:
                    if is_primary():
                        print(
                            "--eval_every: val split has fewer tokens than "
                            f"one batch ({local_batch}x{args.seq_len}); "
                            "eval disabled"
                        )
                else:
                    # One loader for the whole run; each eval re-iterates it
                    # (deterministic: the epoch-0 permutation every time, so
                    # successive evals score the same global batches).
                    eval_loader = create_dataloader(
                        eval_dataset, batch_size=local_batch,
                        prefetch_factor=args.prefetch_factor,
                    )

                    def run_eval(cur_params) -> float:
                        losses = []
                        for i, (xb, yb) in enumerate(eval_loader):
                            if i >= n_eval:
                                break
                            xs, ys = shard_batch((xb, yb), mesh,
                                                 leading_accum_axis=False)
                            losses.append(float(eval_step(cur_params, xs, ys)))
                        return float(np.mean(losses))

        if args.profile and args.log_dir:
            jax.profiler.start_trace(os.path.join(args.log_dir, "profile"))

        rng = jax.random.PRNGKey(args.seed)
        lr_of = schedule if callable(schedule) else (lambda _s: args.lr)

        # Preemption contract (resilience layer 4): SIGTERM only sets a flag;
        # the loop checks it at each optimizer-step boundary, saves one
        # emergency checkpoint, and exits rc 143 for a supervised --resume.
        preempt = PreemptionHandler().install()

        # Cloud-notice poller (ROADMAP item d): same flag, second source.
        # --inject_preempt_notice_at points it at a file:// endpoint in
        # --save_dir that the step loop flips to TRUE — the whole poller ->
        # emergency-save -> rc 143 path runs on CPU with no cloud in sight.
        poller = None
        notice_path = None
        if args.inject_preempt_notice_at:
            notice_path = os.path.join(
                os.path.abspath(args.save_dir), "preempt_notice.txt"
            )
            # Reset to FALSE on every launch: a relaunch after the injected
            # preemption must not re-read last run's TRUE and exit again.
            os.makedirs(os.path.dirname(notice_path), exist_ok=True)
            with open(notice_path, "w") as f:
                f.write("FALSE")
        if args.preempt_poll_url or notice_path:
            poller = PreemptionPoller(
                url=args.preempt_poll_url or f"file://{notice_path}",
                interval_s=(
                    min(args.preempt_poll_interval, 0.05)
                    if notice_path else args.preempt_poll_interval
                ),
                handler=preempt,
            ).start()

        # --- multi-host control plane (coordination.py) ---------------------
        # Fault DECISIONS must be as symmetric as the collectives they gate:
        # each step every process contributes a control word (preempt,
        # rollback, skip, worker-error, save-now) to an OR-reduce, and the
        # pod acts on the AGREED word — same action, same step, every host.
        # Identity fast path single-process: bus.exchange never allgathers,
        # and every multihost-only branch below is skipped outright.
        bus = ConsensusBus()
        multihost = bus.process_count > 1
        desync_count = 0
        skip_observed_last = False
        # --consensus_every K: the control-word exchange runs only at step
        # boundaries where global_step % K == 0 (plus the first iteration of
        # every epoch, so a worker death before any step of an epoch still
        # reaches an exchange). Fault flags latch host-locally in between —
        # preempt/worker_error/rollback_requested are already persistent;
        # skip_observed_last becomes a latch below — and actions fire only at
        # exchange boundaries, keeping decisions pod-consistent at any K with
        # up to K-1 steps of extra action latency.
        consensus_k = coord_policy.consensus_every

        watchdog = None
        if coord_policy.hang_timeout_s > 0:

            def _watchdog_emergency_save() -> None:
                # Process-local best effort: the pod is presumed wedged, so
                # an orbax save whose write spans processes may never finish
                # — the watchdog abandons it after its grace window.
                if saver is not None:
                    saver.ensure_committed_sync(
                        global_step, params, opt_state,
                        make_meta(global_step, epoch, step_in_epoch),
                    )

            watchdog = HangWatchdog(
                coord_policy.hang_timeout_s, on_hang=_watchdog_emergency_save,
            ).start()

        def stop_aux() -> None:
            """Quiesce the background machinery at every exit path."""
            if watchdog is not None:
                watchdog.stop()
            if poller is not None:
                poller.stop()
            if saver is not None:
                saver.close()
            xla_capture.stop_if_active()
            tracer.close()

        # --- epoch/step loop --------------------------------------------------
        # Metrics are consumed with a one-step lag: step N+1 is dispatched
        # (async) before step N's loss is read back, so the host->device
        # pipeline never drains on the device-to-host sync — the reference
        # pays that sync every step via loss.item(). The logged step index is
        # exact; only the wall-clock moment of logging shifts. The same lag
        # applies to the guard/spike bookkeeping below: a skip is noticed one
        # step later, which the rollback policy absorbs (its data cursor
        # already sits past the offending batches).
        pending: tuple[int, int, int, Any] | None = None
        compile_log = compile_watch.CompileLog(compile_watch.get_watch())
        rollback_requested = False
        last_skip_reason_host = 0

        def flush_pending() -> None:
            nonlocal pending, rollback_requested, last_skip_reason_host
            nonlocal skip_observed_last
            if pending is None:
                return
            p_step, p_epoch, p_batch, p_m = pending
            pending = None
            # The first host read of p_m below blocks until the dispatched
            # step's device work completes — that wait IS the device_sync
            # phase (everything after the first read is host arithmetic).
            _sync_span = tracer.span("device_sync", step=p_step).__enter__()
            extra = {}
            if use_guard:
                reason = int(p_m.skip_reason)
                # Fed to the next consensus exchange: the guard's decision is
                # computed from globally-reduced values, so hosts disagreeing
                # on it is itself a desync signal (warned on below). Latched
                # (OR) rather than overwritten: with --consensus_every > 1
                # several flushes can pass between exchanges, and a skip in
                # any of them must ride the next exchange.
                skip_observed_last = skip_observed_last or bool(reason)
                if reason:
                    last_skip_reason_host = reason
                    tracer.event("guard_skip", step=p_step, reason=reason)
                    if is_primary():
                        print(
                            f"[guard] step {p_step} skipped "
                            f"({SKIP_REASON_NAMES.get(reason, reason)}); "
                            f"params/opt-state unchanged (total skipped: "
                            f"{int(p_m.skipped_steps)})",
                            flush=True,
                        )
                if int(p_m.skipped_steps) or last_skip_reason_host:
                    # Pushed only once a skip has happened: a steady
                    # "skipped: 0" on every CLI line would be noise.
                    extra = {
                        "skipped_steps": int(p_m.skipped_steps),
                        "last_skip_reason": last_skip_reason_host,
                    }
                if int(p_m.clipped):
                    if is_primary():
                        print(
                            f"[guard] step {p_step} grad norm "
                            f"{float(p_m.grad_norm):.2f} exceeded "
                            f"--guard_max_grad_norm "
                            f"{args.guard_max_grad_norm:g}; clipped "
                            f"per-layer to {args.guard_clip_norm:g} and "
                            f"applied (total clipped: "
                            f"{int(p_m.clipped_steps)})",
                            flush=True,
                        )
                if int(p_m.clipped_steps):
                    extra["clipped_steps"] = int(p_m.clipped_steps)
                verdict = monitor.observe(float(p_m.loss), skipped=bool(reason))
                if verdict == "rollback":
                    rollback_requested = True
                elif verdict == "anomaly" and not reason and is_primary():
                    print(
                        f"[guard] step {p_step} loss spike: "
                        f"{float(p_m.loss):.4f} (EMA {monitor.mean:.4f}, "
                        f"{monitor.consecutive} consecutive anomalies)",
                        flush=True,
                    )
            if saver is not None and saver.failed_saves:
                extra["save_failures"] = saver.failed_saves
            if desync_count:
                extra["desync_detected"] = desync_count
            if dataset.read_retry_count:
                extra["data_read_retries"] = dataset.read_retry_count
            if fused_fallback_count():
                # Nonzero only when a requested --fused_layers/--fused_matmul
                # path degraded to unfused ops (trace-time count — once per
                # compiled shape, not per step). The warn-once fires at the
                # fallback site; this keeps the signal on the metrics record.
                extra["fused_fallback"] = fused_fallback_count()
            if elastic_delta:
                # This run resumed at a different world size than its
                # checkpoint was saved at; constant for the run, so the TB
                # series makes resizes (and their direction) visible.
                extra["elastic_resizes"] = 1
                extra["resume_world_delta"] = elastic_delta
            # p_step is the post-increment global step; optax evaluated the
            # schedule at count p_step - 1 for that update, so log that one.
            # A skipped step's loss/grad_norm are the REJECTED values (the
            # guard applied the identity update instead): keep them out of the
            # tracker, whose windowed AVERAGE a single NaN would poison for
            # the next 50 steps — the [guard] line above already reports them.
            values = dict(
                lr=float(lr_of(p_step - 1)), epoch=p_epoch, batch=p_batch,
            )
            if not (use_guard and int(p_m.skip_reason)):
                values["loss"] = float(p_m.loss)
                values["grad_norm"] = float(p_m.grad_norm)
            _sync_span.__exit__(None, None, None)
            with tracer.span("collector", step=p_step):
                tracker.update(p_step, **values, **extra)
            # The first step is done: set-up's compile summary; from then
            # on, every program that compiles names itself and the step.
            for line in compile_log.lines(p_step):
                if is_primary():
                    print(f"[compile] {line}", flush=True)

        def emergency_preempt_exit() -> None:
            """Preemption endgame (single-host: SIGTERM/poller flag at the
            step boundary; multi-host: the pod-AGREED preempt bit): flush,
            commit one emergency checkpoint, quiesce, exit rc 143 — the rc
            supervise.sh relaunches without burning a restart attempt."""
            flush_pending()
            end_step_span()
            tracer.event("preempt_exit", step=global_step)
            xla_capture.stop_if_active()
            if args.profile and args.log_dir:
                jax.profiler.stop_trace()
            if watchdog is not None:
                watchdog.disarm()
            if saver is not None:
                # wait-or-supersede: drains any in-flight async
                # save first; never two writers in one step dir.
                saver.ensure_committed_sync(
                    global_step, params, opt_state,
                    make_meta(global_step, epoch, step_in_epoch),
                )
            tracker.close()
            stop_aux()
            preempt.uninstall()
            if is_primary():
                print(
                    f"[preempt] emergency checkpoint at step "
                    f"{global_step}; exiting rc "
                    f"{PREEMPTED_EXIT_CODE} for a supervised resume",
                    flush=True,
                )
            raise SystemExit(PREEMPTED_EXIT_CODE)

        def coordinated_worker_abort(exc: BaseException | None) -> None:
            """Pod-agreed abort: a data worker died on some host. Every
            process reaches this from the SAME step's consensus exchange, so
            the emergency save's collectives line up; then exit a distinct
            rc that supervise.sh treats as a fault (burns an attempt —
            a worker death is not scheduled churn)."""
            flush_pending()
            end_step_span()
            tracer.event("worker_abort", step=global_step)
            xla_capture.stop_if_active()
            if args.profile and args.log_dir:
                jax.profiler.stop_trace()
            if watchdog is not None:
                watchdog.disarm()
            if saver is not None:
                saver.ensure_committed_sync(
                    global_step, params, opt_state,
                    make_meta(global_step, epoch, step_in_epoch),
                )
            tracker.close()
            stop_aux()
            preempt.uninstall()
            detail = f" ({exc})" if exc is not None else " (on a peer host)"
            print(
                f"[coord] data worker failed{detail}; pod-wide coordinated "
                f"abort at step {global_step}, exiting rc "
                f"{DATA_ABORT_EXIT_CODE}",
                flush=True,
            )
            raise SystemExit(DATA_ABORT_EXIT_CODE)

        done = False
        state_reported = False
        rollbacks_done = 0
        fired: set = set()  # in-process one-shot injections (no --save_dir)

        # One "step" span per loop iteration, managed manually: the body has
        # a dozen break/raise exits and a `with` would reindent all of them.
        # begin() closes any span a break path left open, so nesting can
        # never corrupt; the explicit end() calls sit on the paths that leave
        # the loop (epoch end, emergency exits).
        step_span = None

        def begin_step_span() -> None:
            nonlocal step_span
            end_step_span()
            step_span = tracer.span("step", n=global_step + 1)
            step_span.__enter__()

        def end_step_span() -> None:
            nonlocal step_span
            if step_span is not None:
                step_span.__exit__(None, None, None)
                step_span = None
        epoch, step_in_epoch = start_epoch, skip_steps
        # Multi-host periodic saves happen at the step boundary AFTER the
        # consensus exchange (so the decision to save is pod-agreed); this
        # guards against re-saving the step a resume/rollback restored.
        last_saved_step = global_step
        while True:
            rollback_requested = False
            for epoch in range(start_epoch, args.epochs):
                dataset.set_epoch(epoch)
                tracker.start_epoch(epoch)
                loader = create_dataloader(
                    dataset,
                    batch_size=local_batch,
                    prefetch_factor=args.prefetch_factor,
                    skip_batches=(
                        (skip_steps - (cursor_base if epoch == cursor_epoch else 0))
                        * args.grad_accum_steps
                    ) if epoch == start_epoch else 0,
                    inject_worker_fail_after=(
                        args.inject_worker_fail_at
                        if (
                            args.inject_worker_fail_at
                            and jax.process_index() == 0
                            and _claim_one_shot(
                                args.save_dir,
                                f"worker_fail_injected_"
                                f"{args.inject_worker_fail_at}",
                                fired,
                            )
                        )
                        else 0
                    ),
                )
                step_in_epoch = skip_steps if epoch == start_epoch else 0

                # Every optimizer step is a collective: a process whose local
                # loader yields more batches than another's would dispatch an
                # extra train_step and block forever on its psum. Bound the
                # epoch by the cross-process MINIMUM step count — the drop-to-
                # common-length behavior torch's DistributedSampler gives the
                # reference implicitly (round-robin shard remainders make
                # per-process batch counts unequal here).
                epoch_opt_steps = (
                    _common_min(dataset.batches_per_epoch(local_batch))
                    // args.grad_accum_steps
                )
                if epoch == cursor_epoch:
                    # batches_per_epoch counted only the complement of the
                    # migrated (consumed) windows; the old world's steps are
                    # still part of this epoch's step ledger.
                    epoch_opt_steps += cursor_base

                micro: list[tuple[np.ndarray, np.ndarray]] = []
                last_micro: list[tuple[np.ndarray, np.ndarray]] = []
                loader_iter = iter(loader)
                worker_error: BaseException | None = None
                first_inner_iter = True
                # Double-buffer slot for --device_prefetch: the NEXT step's
                # batch, already sharded onto devices (H2D issued while the
                # previous step computes). Host-side `micro` stays the source
                # of truth for last_micro replay.
                prefetched_dev = None
                while step_in_epoch < epoch_opt_steps:
                    begin_step_span()
                    # (1) Host-local fetch of one optimizer step's
                    # micro-batches. Deliberately NOT a collective: a host
                    # whose data worker just died still reaches the consensus
                    # exchange below, so the pod agrees to abort together
                    # instead of leaving the other N-1 hosts wedged forever
                    # in the train step's psum.
                    if worker_error is None:
                        try:
                            with tracer.span("data_fetch"):
                                while len(micro) < args.grad_accum_steps:
                                    xb, yb = next(loader_iter)
                                    micro.append((xb, yb))
                        except StopIteration:
                            break
                        except RuntimeError as exc:
                            if not multihost:
                                raise  # single-process: fail loudly, unchanged
                            worker_error = exc
                            # Surface the chained root cause: the loader wraps
                            # worker deaths in a generic "data worker N failed"
                            # and the actionable error rides on __cause__.
                            cause = exc.__cause__
                            detail = f"{exc}: {cause}" if cause else str(exc)
                            print(
                                f"[coord] local data worker failed ({detail}); "
                                f"requesting pod-wide abort",
                                flush=True,
                            )
                    if (
                        multihost
                        and worker_error is not None
                        and len(micro) < args.grad_accum_steps
                        and last_micro
                    ):
                        # --consensus_every > 1 and the worker died between
                        # exchange boundaries: the pod can only act at the
                        # next boundary, and every host must keep dispatching
                        # symmetric train steps until then. Replay the last
                        # full micro-batch set (params stay pod-identical —
                        # gradients still psum) for the <= K-1 steps before
                        # the agreed abort.
                        micro = [
                            last_micro[i % len(last_micro)]
                            for i in range(args.grad_accum_steps)
                        ]

                    # (2) Desync detector: symmetric by construction (every
                    # host agrees on global_step), so the allgather inside
                    # always pairs up — even when this host is carrying a
                    # worker error to the exchange below.
                    if (
                        multihost
                        and coord_policy.desync_check_every
                        and global_step > 0
                        and global_step % coord_policy.desync_check_every == 0
                    ):
                        t_fp = time.perf_counter()
                        with tracer.span("desync_check", step=global_step):
                            bad_ranks = check_fingerprints(
                                fingerprint_params(params)
                            )
                        if bad_ranks:
                            desync_count += 1
                            rollback_requested = True
                            if is_primary():
                                print(
                                    f"[coord] DESYNC at step {global_step}: "
                                    f"rank(s) {bad_ranks} disagree with the "
                                    f"pod's parameter fingerprint (check "
                                    f"took "
                                    f"{(time.perf_counter() - t_fp) * 1e3:.1f}"
                                    f" ms); rolling back to the last "
                                    f"verified checkpoint",
                                    flush=True,
                                )

                    # (3) Consensus exchange: OR-reduce the per-host control
                    # words and act on the AGREED word — the only place fault
                    # flags turn into actions on a pod. With --consensus_every
                    # K > 1 it runs only at K-step boundaries (plus each
                    # epoch's first iteration — symmetric: hosts enter epochs
                    # in lockstep); flags latch in between.
                    exchange_now = multihost and (
                        first_inner_iter
                        or global_step % consensus_k == 0
                    )
                    first_inner_iter = False
                    if exchange_now:
                        agreed = decode_control_word(bus.exchange(
                            encode_control_word(
                                preempt=preempt.preempted(),
                                rollback=rollback_requested,
                                skip=skip_observed_last,
                                worker_error=worker_error is not None,
                                save_now=bool(
                                    saver is not None and saver.failed_saves
                                ),
                            )
                        ))
                        if agreed.worker_error:
                            coordinated_worker_abort(worker_error)
                        if agreed.preempt:
                            emergency_preempt_exit()
                        if agreed.skip and not skip_observed_last:
                            print(
                                f"[coord] step {global_step}: another host "
                                f"observed a guard skip this host did not — "
                                f"guard inputs may have diverged",
                                flush=True,
                            )
                        # The exchange consumed the latched skip flag; re-arm
                        # the latch for the next interval.
                        skip_observed_last = False
                        if agreed.rollback:
                            rollback_requested = True
                            if is_primary():
                                print(
                                    f"[coord] pod-agreed rollback before "
                                    f"step {global_step + 1}",
                                    flush=True,
                                )
                            break
                        # Pod-agreed periodic/make-up save at this boundary
                        # (params here are identical to post-dispatch of the
                        # previous step). Single-process keeps its original
                        # post-dispatch save block below, bit-identical.
                        if (
                            saver is not None
                            and global_step > 0
                            and global_step != last_saved_step
                            and (
                                agreed.save_now
                                or (
                                    args.save_every
                                    and global_step % args.save_every == 0
                                )
                                # K>1 boundaries can straddle the % cadence;
                                # save whenever a full interval has elapsed
                                # (no-op at K=1 — kept bit-identical).
                                or (
                                    consensus_k > 1
                                    and args.save_every
                                    and global_step - last_saved_step
                                    >= args.save_every
                                )
                            )
                        ):
                            saver.save(
                                global_step, params, opt_state,
                                make_meta(global_step, epoch, step_in_epoch),
                            )
                            last_saved_step = global_step

                    # Fault injections for the control plane itself.
                    if (
                        args.inject_desync_at
                        and global_step + 1 == args.inject_desync_at
                        and _claim_one_shot(
                            args.save_dir,
                            f"desync_injected_{args.inject_desync_at}",
                            fired,
                        )
                    ):
                        factor = np.float32(
                            1.001
                            if jax.process_index() == jax.process_count() - 1
                            else 1.0
                        )
                        params = perturb_params(params, factor)
                        print(
                            f"[inject] desync perturbation x{float(factor):g} "
                            f"on rank {jax.process_index()} before step "
                            f"{global_step + 1}",
                            flush=True,
                        )
                    if (
                        args.inject_hang_at
                        and global_step + 1 == args.inject_hang_at
                        and jax.process_index() == 0
                        and _claim_one_shot(
                            args.save_dir,
                            f"hang_injected_{args.inject_hang_at}",
                            fired,
                        )
                    ):
                        print(
                            f"[inject] simulated hang before step "
                            f"{global_step + 1}; the watchdog should fire "
                            f"within {coord_policy.hang_timeout_s:g}s",
                            flush=True,
                        )
                        # The watchdog's os._exit cuts this sleep short; the
                        # horizon only matters if the watchdog is broken.
                        time.sleep(coord_policy.hang_timeout_s * 20 + 30)

                    last_micro = micro  # replay source if a worker dies mid-interval
                    if prefetched_dev is not None:
                        # --device_prefetch issued this batch's H2D during the
                        # previous step's compute; consume it as-is.
                        x, y = prefetched_dev
                        prefetched_dev = None
                    else:
                        with tracer.span("h2d"):
                            x = np.stack([m[0] for m in micro])
                            y = np.stack([m[1] for m in micro])
                            x, y = shard_batch((x, y), mesh)
                    micro = []
                    xla_capture.maybe_start(global_step + 1)
                    if use_guard:
                        loss_scale = ones_scale
                        if (
                            args.inject_nan_at
                            and global_step + 1 == args.inject_nan_at
                            and _claim_one_shot(
                                args.save_dir,
                                f"nan_injected_{args.inject_nan_at}",
                                fired,
                            )
                        ):
                            loss_scale = nan_scale
                            print(
                                f"[inject] poisoning micro-batch 0 loss with "
                                f"NaN at step {global_step + 1}",
                                flush=True,
                            )
                        with tracer.span("step_dispatch", step=global_step + 1):
                            params, opt_state, guard_state, m = train_step(
                                params, opt_state, guard_state, x, y, rng,
                                global_step, loss_scale,
                            )
                    else:
                        with tracer.span("step_dispatch", step=global_step + 1):
                            params, opt_state, m = train_step(
                                params, opt_state, x, y, rng, global_step
                            )
                    global_step += 1
                    step_in_epoch += 1
                    if not state_reported:
                        # The start-up report ran before any state was
                        # placed. Once, on the step that paid the compile
                        # anyway, wait for the device and report again: this
                        # is where a sharded run shows each device's share.
                        state_reported = True
                        jax.block_until_ready(m)
                        if is_primary():
                            print(
                                f"device memory after step {global_step}:",
                                *device_memory_lines(), sep="\n", flush=True,
                            )
                    # Device-side double-buffered prefetch (--device_prefetch):
                    # step i was just dispatched and the host is about to
                    # block on step i-1's metrics in flush_pending — fetch
                    # step i+1's micro-batches and issue their H2D transfer
                    # NOW, so the copy overlaps device compute instead of
                    # serializing after the metrics wait. Failures route
                    # exactly like the top-of-loop fetch: StopIteration
                    # leaves the partial tail for the top of the next
                    # iteration to re-raise (generators keep raising), a dead
                    # worker raises single-host and latches worker_error for
                    # the consensus exchange multi-host. Skipped when the
                    # loop is about to exit — no batch is pulled past the
                    # epoch/max_steps boundary.
                    if (
                        device_prefetch
                        and worker_error is None
                        and step_in_epoch < epoch_opt_steps
                        and not (
                            args.max_steps and global_step >= args.max_steps
                        )
                    ):
                        try:
                            with tracer.span("h2d_prefetch"):
                                while len(micro) < args.grad_accum_steps:
                                    xb, yb = next(loader_iter)
                                    micro.append((xb, yb))
                                prefetched_dev = shard_batch(
                                    (
                                        np.stack([m[0] for m in micro]),
                                        np.stack([m[1] for m in micro]),
                                    ),
                                    mesh,
                                )
                        except StopIteration:
                            pass
                        except RuntimeError as exc:
                            if not multihost:
                                raise
                            worker_error = exc
                            cause = exc.__cause__
                            detail = f"{exc}: {cause}" if cause else str(exc)
                            print(
                                f"[coord] local data worker failed during "
                                f"prefetch ({detail}); requesting pod-wide "
                                f"abort",
                                flush=True,
                            )
                    flush_pending()
                    pending = (global_step, epoch, step_in_epoch, m)
                    # Stop the on-demand capture once the window's last step
                    # has been FLUSHED (flush_pending blocked on its metrics,
                    # so its device work is in the trace, not just queued).
                    xla_capture.maybe_stop(global_step - 1)
                    if watchdog is not None:
                        # Arm-as-beat: the deadline extends only when a step
                        # completes, and the watchdog goes live only after the
                        # FIRST completed step — initial compilation is
                        # excluded from the hang budget.
                        watchdog.arm()
                    # Multi-host defers every local fault decision below to
                    # the next step's consensus exchange, so all hosts act
                    # identically on the identical step (one-step lag).
                    if rollback_requested and not multihost:
                        break

                    if run_eval is not None and global_step % args.eval_every == 0:
                        flush_pending()
                        if watchdog is not None:
                            watchdog.disarm()  # eval has no step cadence
                        # count_tokens=False: this step's training update
                        # already counted its tokens; eval is out-of-band.
                        with tracer.span("eval", step=global_step):
                            tracker.update(
                                global_step, count_tokens=False,
                                eval_loss=run_eval(params),
                            )
                        if watchdog is not None:
                            watchdog.arm()
                    if (
                        not multihost
                        and args.save_dir and args.save_every
                        and global_step % args.save_every == 0
                    ):
                        flush_pending()
                    if (
                        not multihost
                        and args.save_dir and args.save_every
                        and global_step % args.save_every == 0
                        # re-checked AFTER the flush: never checkpoint a step
                        # the spike monitor just flagged for rollback — the
                        # rollback would restore this very checkpoint.
                        and not rollback_requested
                    ):
                        saver.save(
                            global_step, params, opt_state,
                            make_meta(global_step, epoch, step_in_epoch),
                        )
                    if rollback_requested and not multihost:
                        break
                    if args.inject_fail_at and global_step >= args.inject_fail_at:
                        marker = os.path.join(
                            args.save_dir, f".fail_injected_{args.inject_fail_at}"
                        )
                        if not os.path.exists(marker):
                            flush_pending()
                            tracker.close()
                            if saver is not None:
                                # Quiesce in-flight async commits first: the
                                # injected crash models "process dies between
                                # steps", and the resume-from-cursor contract
                                # it tests predates async saves. The commit
                                # race itself (crash between write and commit)
                                # is covered by its own checkpoint tests.
                                saver.wait()
                            os.makedirs(args.save_dir, exist_ok=True)
                            with open(marker, "w") as f:
                                f.write(str(global_step))
                            print(
                                f"[inject] simulated failure after step {global_step}",
                                flush=True,
                            )
                            # Hard exit, no teardown/final-save: model a real crash.
                            os._exit(13)
                    if (
                        args.inject_preempt_at
                        and global_step >= args.inject_preempt_at
                        and _claim_one_shot(
                            args.save_dir,
                            f"preempt_injected_{args.inject_preempt_at}",
                            fired,
                        )
                    ):
                        print(
                            f"[inject] simulated preemption (SIGTERM) after "
                            f"step {global_step}",
                            flush=True,
                        )
                        os.kill(os.getpid(), signal.SIGTERM)
                    if (
                        args.inject_preempt_notice_at
                        and global_step >= args.inject_preempt_notice_at
                        and _claim_one_shot(
                            args.save_dir,
                            f"preempt_notice_injected_{args.inject_preempt_notice_at}",
                            fired,
                        )
                    ):
                        print(
                            f"[inject] cloud preemption notice after step "
                            f"{global_step}",
                            flush=True,
                        )
                        with open(notice_path, "w") as f:
                            f.write("TRUE")
                        # Wait for the poller (interval <= 50ms here) to see
                        # it, so the emergency save lands deterministically at
                        # THIS step boundary rather than a test-flaky later one.
                        deadline = time.monotonic() + 2.0
                        while (
                            not preempt.preempted()
                            and time.monotonic() < deadline
                        ):
                            time.sleep(0.01)
                    if not multihost and preempt.preempted():
                        emergency_preempt_exit()
                    if args.max_steps and global_step >= args.max_steps:
                        done = True
                        break
                end_step_span()
                loader_iter.close()  # stop worker threads promptly
                if multihost:
                    # Epoch/run boundary barrier: a fault flag raised by the
                    # very last step's flush would otherwise be consumed
                    # asymmetrically (one host entering the rollback path's
                    # collectives while another starts the next epoch). Every
                    # while-exit above is symmetric, so this exchange always
                    # pairs up.
                    agreed = decode_control_word(bus.exchange(
                        encode_control_word(rollback=rollback_requested)
                    ))
                    rollback_requested = agreed.rollback
                if done or rollback_requested:
                    break
                skip_steps = 0  # later epochs start from batch 0

            if rollback_requested and not done:
                # Layer 2: consecutive anomalies — restore the last verified
                # checkpoint, keep the data cursor where it is (past the
                # offending batches, via the loader's O(1) skip), reset the
                # guard counters and spike baseline, and go again.
                pending = None
                if watchdog is not None:
                    watchdog.disarm()  # restore has no step cadence
                if monitor is not None:
                    # A desync-triggered rollback can arrive with the spike
                    # monitor disabled (--step_guard off).
                    monitor.reset()
                guard_state = init_guard_state()
                rollbacks_done += 1
                tracer.event(
                    "rollback", step=global_step, count=rollbacks_done
                )
                if rollbacks_done > args.max_rollbacks:
                    tracker.close()
                    stop_aux()
                    preempt.uninstall()
                    raise SystemExit(
                        f"error: loss diverged through {rollbacks_done} "
                        f"rollbacks (--max_rollbacks {args.max_rollbacks}); "
                        f"stopping"
                    )
                if saver is not None:
                    # An in-flight async save may be about to commit the very
                    # checkpoint we want to restore — drain it first (also
                    # keeps its GC from racing the restore's directory scan).
                    saver.wait()
                restored = (
                    ckpt.restore_latest_verified(
                        args.save_dir, params, opt_state,
                        param_shardings, opt_shardings,
                    )
                    if args.save_dir else None
                )
                start_epoch = epoch
                skip_steps = step_in_epoch
                if restored is None:
                    if is_primary():
                        print(
                            "[resilience] rollback requested but no verified "
                            "checkpoint is available; continuing in place "
                            "with a reset spike baseline",
                            flush=True,
                        )
                    continue
                params, opt_state, meta, rpath = restored
                global_step = meta.step
                last_saved_step = global_step  # never re-save the restored step
                tracker.total_tokens = meta.total_tokens
                if is_primary():
                    print(
                        f"[resilience] rollback #{rollbacks_done}: restored "
                        f"{rpath} (step {meta.step}); data cursor kept at "
                        f"epoch {epoch}, {step_in_epoch} opt steps in — the "
                        f"offending batches are skipped",
                        flush=True,
                    )
                continue
            break

        # --- teardown ---------------------------------------------------------
        flush_pending()
        if watchdog is not None:
            watchdog.disarm()  # the final sync save has no step cadence
        preempt.uninstall()
        if args.profile and args.log_dir:
            jax.profiler.stop_trace()
        if saver is not None:
            # ensure_committed_sync covers every ending: nothing saved this
            # step -> sync save now; async save of this step still in flight
            # -> drain it; already committed -> no-op. Either way the run
            # ends with a committed checkpoint at the final step.
            saver.ensure_committed_sync(
                global_step, params, opt_state,
                make_meta(
                    global_step,
                    min(epoch, args.epochs - 1) if args.epochs else 0,
                    step_in_epoch,
                ),
            )
        tracker.close()
        stop_aux()
        if is_primary():
            print(f"training done: {global_step} optimizer steps")


if __name__ == "__main__":
    main()
