"""Built-in metrics: the reference's 13 plus TPU-native MFU accounting.

Mirrors the observable metric surface of ``/root/reference/stats_tracker.py``:

* freq-1 ``train/``: loss (avg, distributed), lr (current), grad_norm (avg,
  distributed), epoch (current), batch (current, int) — ``:142-206``
* freq-1 ``perf/``: tokens_per_second (collector), total_tokens, epoch_time —
  ``:237-274``
* freq-20 ``mem/``: device alloc/peak/utilization + host CPU RSS — ``:302-364``,
  with the CUDA allocator stats replaced by ``jax.local_devices()[i]
  .memory_stats()`` (XLA's HBM accounting; there is no reserved-vs-allocated
  split on TPU — HBM is planned at compile time — so ``gpu_reserved_gb`` maps
  to the allocator's bytes_limit).

TPU-native additions (BASELINE.md's headline metrics, absent in the
reference): ``perf/tokens_per_second_per_chip`` and ``perf/mfu``.
"""

from __future__ import annotations

import os
import time
from typing import TYPE_CHECKING

from gpt_2_distributed_tpu.metrics.registry import (
    METRIC_REGISTRY,
    ReductionStrategy,
)

if TYPE_CHECKING:
    from gpt_2_distributed_tpu.metrics.tracker import StatsTracker

GB = 1024**3
MB = 1024**2


# --- freq-1 training metrics (pushed by the driver through update()) -------

METRIC_REGISTRY.metric(
    "loss", reduction=ReductionStrategy.AVERAGE, distributed=True,
    cli_format="loss: {value:.4f}",
)(float)

METRIC_REGISTRY.metric(
    "lr", reduction=ReductionStrategy.CURRENT, cli_format="lr: {value:.2e}",
)(float)

METRIC_REGISTRY.metric(
    "grad_norm", reduction=ReductionStrategy.AVERAGE, distributed=True,
    cli_format="grad_norm: {value:.4f}",
)(float)

METRIC_REGISTRY.metric(
    "epoch", reduction=ReductionStrategy.CURRENT, cli_format="epoch: {value:.0f}",
)(float)

METRIC_REGISTRY.metric(
    "batch", reduction=ReductionStrategy.CURRENT, cli_format="batch: {value:.0f}",
)(lambda v: float(int(v)))

# Resilience (train.py --step_guard): cumulative count of optimizer steps the
# non-finite guard skipped, and the SKIP_* reason code of the latest skip
# (resilience.SKIP_REASON_NAMES; 0 = never skipped). skipped_steps shows on
# the CLI line only once a skip happened (a steady "skipped: 0" would be
# noise); the reason code is TB-only.
#
# Counter metrics below declare dist_reduce="sum": across a pod the total is
# the number that means something, not the per-host mean. They stay
# distributed=False because they are pushed *conditionally* (only once
# nonzero) — the cross-process allgather needs every host to push the same
# key set in the same update() call, which host-local counters can't
# guarantee. The declaration makes the strategy explicit for any reduce path
# that does see them (custom reduce_fn, or a future symmetric-push cadence).
METRIC_REGISTRY.metric(
    "skipped_steps", reduction=ReductionStrategy.CURRENT,
    dist_reduce="sum", cli_format="skipped: {value:.0f}",
)(lambda v: float(int(v)))

METRIC_REGISTRY.metric(
    "last_skip_reason", reduction=ReductionStrategy.CURRENT, cli_format=None,
)(lambda v: float(int(v)))

# Resilience (train.py --guard_max_grad_norm): cumulative count of steps whose
# finite-but-huge gradient was per-layer-clipped and applied instead of
# skipped. Like skipped_steps, pushed only once the first clip happens.
METRIC_REGISTRY.metric(
    "clipped_steps", reduction=ReductionStrategy.CURRENT, dist_reduce="sum",
    cli_format="clipped: {value:.0f}",
)(lambda v: float(int(v)))

# Resilience (checkpoint.CheckpointSaver): cumulative count of checkpoint
# saves that failed permanently (retries exhausted, or the async background
# write died after the source buffers were donated away). Non-zero means the
# run is progressing but its on-disk save cadence has gaps.
METRIC_REGISTRY.metric(
    "save_failures", reduction=ReductionStrategy.CURRENT, dist_reduce="sum",
    cli_format="save_fail: {value:.0f}",
)(lambda v: float(int(v)))

# Multi-host control plane (coordination.py): cumulative count of desync
# detections — fingerprint-allgather rounds where at least one host's
# parameter fingerprint disagreed with the pod. Each detection routes into
# the rollback-to-last-verified path; pushed only once nonzero.
METRIC_REGISTRY.metric(
    "desync_detected", reduction=ReductionStrategy.CURRENT, dist_reduce="max",
    cli_format="desync: {value:.0f}",
)(lambda v: float(int(v)))

# Data pipeline (data/dataloader.py): cumulative count of transient shard-I/O
# retries (OSError on memmap open/read, re-read succeeded or is about to be
# re-attempted). Non-zero means the storage layer is flaky but survivable;
# pushed only once nonzero.
METRIC_REGISTRY.metric(
    "data_read_retries", reduction=ReductionStrategy.CURRENT, dist_reduce="sum",
    cli_format="io_retry: {value:.0f}",
)(lambda v: float(int(v)))

# Fused-path degradation (ops/spmd.py fused_fallback_count): trace-time count
# of requested --fused_layers/--fused_matmul sites that degraded to unfused
# ops (once per compiled shape, not per step). train.py has pushed this since
# the fused-ops PR, but it was never registered — the tracker silently
# dropped every push (the exact bug class StatsTracker.strict and
# tests/test_metric_registration.py now kill). TB-only: the warn-once at the
# fallback site already narrates it.
METRIC_REGISTRY.metric(
    "fused_fallback", reduction=ReductionStrategy.CURRENT, dist_reduce="max",
    cli_format=None,
)(lambda v: float(int(v)))

# Elastic resume (train.py elastic hook): pushed only by runs that resumed at
# a different world size than their checkpoint was saved at. elastic_resizes
# is 1 for the life of such a run (summing across a supervised lifecycle's TB
# series counts the resizes); resume_world_delta is new minus old device
# count, so a shrink plots negative. TB-only — the [elastic] CLI line already
# narrates the resize once.
METRIC_REGISTRY.metric(
    "elastic_resizes", reduction=ReductionStrategy.CURRENT,
    cli_format=None,
)(lambda v: float(int(v)))
METRIC_REGISTRY.metric(
    "resume_world_delta", reduction=ReductionStrategy.CURRENT,
    cli_format=None,
)(lambda v: float(int(v)))

# Periodic validation loss over the held-out shard (shard 0 is reserved as
# "val" by the tokenizer pipeline, notebook cell 13 convention). The reference
# reserves the split but never consumes it; the TPU build's --eval_every wires
# it up (VERDICT round-1 gap #4).
METRIC_REGISTRY.metric(
    "eval_loss", reduction=ReductionStrategy.CURRENT, distributed=True,
    tb_prefix="eval/", cli_format="eval_loss: {value:.4f}",
)(float)


# --- freq-1 performance collector ------------------------------------------


def collect_performance(tracker: "StatsTracker") -> dict[str, float]:
    """Windowed throughput + totals, pulled each step
    (``/root/reference/stats_tracker.py:209-234``): tokens accumulated since
    the last CLI tick divided by elapsed wall-clock, plus run totals. Extends
    the reference with per-chip throughput and MFU."""
    now = time.perf_counter()
    dt = max(now - tracker.window_start_time, 1e-9)
    tok_s = tracker.window_tokens / dt
    out = {
        "tokens_per_second": tok_s,
        "total_tokens": float(tracker.total_tokens),
        "epoch_time": now - tracker.epoch_start_time,
        "tokens_per_second_per_chip": tok_s / max(tracker.n_chips, 1),
    }
    if tracker.flops_per_token and tracker.peak_flops_per_chip:
        out["mfu"] = (
            out["tokens_per_second_per_chip"]
            * tracker.flops_per_token
            / tracker.peak_flops_per_chip
        )
    return out


for _name, _red, _fmt in (
    # tokens_per_second is a collector metric: it never crosses processes.
    # It reports true GLOBAL system throughput because the driver constructs
    # the tracker with the global effective batch (micro-batch x grad_accum x
    # data-parallel degree — train.py StatsTracker(batch_size=global_batch)),
    # so tokens_per_step already counts every process's tokens. The reference
    # instead declares SUM but mean-reduces across ranks (SURVEY.md C21),
    # publishing mean per-worker throughput under a "total system" docstring;
    # this build fixes that without per-step host synchronization. Pinned by
    # tests/test_multihost.py::test_tokens_per_second_is_global_not_per_host.
    ("tokens_per_second", ReductionStrategy.CURRENT, "tok/s: {value:,.0f}"),
    ("total_tokens", ReductionStrategy.CURRENT, "total_tok: {value:,.0f}"),
    ("epoch_time", ReductionStrategy.CURRENT, "epoch_s: {value:.1f}"),
    ("tokens_per_second_per_chip", ReductionStrategy.CURRENT, "tok/s/chip: {value:,.0f}"),
    ("mfu", ReductionStrategy.CURRENT, "mfu: {value:.1%}"),
):
    METRIC_REGISTRY.metric(
        _name, reduction=_red, tb_prefix="perf/", cli_format=_fmt, collector=True,
    )(collect_performance)


# --- freq-20 memory collector ----------------------------------------------


def collect_memory(tracker: "StatsTracker") -> dict[str, float]:
    """Device HBM + host RSS (``/root/reference/stats_tracker.py:277-299``),
    via XLA's per-device allocator stats instead of the CUDA caching
    allocator."""
    out: dict[str, float] = {}
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        stats = None
    if stats:
        in_use = stats.get("bytes_in_use", 0)
        limit = stats.get("bytes_limit", 0)
        peak = stats.get("peak_bytes_in_use", in_use)
        out["device_alloc_gb"] = in_use / GB
        out["device_limit_gb"] = limit / GB
        out["device_peak_alloc_gb"] = peak / GB
        if limit:
            out["device_utilization_pct"] = 100.0 * in_use / limit
    try:
        import psutil

        out["cpu_mb"] = psutil.Process(os.getpid()).memory_info().rss / MB
    except Exception:
        pass
    return out


# --- serving-load metrics (pushed by the serving --tb_dir sink) ------------
# TB-only (cli_format None): the serving CLI's stderr summary already
# narrates totals; these exist so a deployment's TensorBoard sees load —
# queue depth/wait and occupancy size the deployment, preemption count and
# prefix-hit volume judge the ServeConfig scheduler knobs. All CURRENT:
# each flush pushes the fleet's metrics_snapshot() as-of-now (wait is a
# running mean, preempted/prefix tokens are cumulative counters). Both
# entry points (gpt2-tpu-serve, gpt2-tpu-frontend) emit through the same
# EngineDriver, so one replica or a routed fleet writes the same names;
# the last four are fleet-level (serving/frontend/router.py).

for _name, _dist in (
    ("queue_wait_ms", "mean"),         # mean enqueue->admission gap per admission
    ("preempted", "sum"),              # cumulative pool-pressure swap-outs
    ("prefix_cached_tokens", "sum"),   # cumulative prompt tokens served from cache
    ("serve_queue_depth", "sum"),      # requests waiting for a slot, as of the flush
    ("serve_occupancy", "sum"),        # occupied decode slots, as of the flush
    ("serve_replicas", "sum"),         # active engine replicas, as of the flush
    ("serve_shed", "sum"),             # cumulative SLO-admission refusals (503s)
    ("route_affinity_hits", "sum"),    # cumulative prefix-affinity route decisions
    ("slo_violations", "sum"),         # cumulative finished requests over TTFT SLO
    ("replica_failures", "sum"),       # cumulative replicas marked FAILED
    ("requests_migrated", "sum"),      # cumulative requests moved off failed replicas
    ("requests_timed_out", "sum"),     # cumulative deadline evictions (504s)
    ("watchdog_trips", "sum"),         # cumulative step-watchdog firings
    ("serve_mesh_devices", "max"),     # devices across the fleet's serving meshes
    ("kv_pool_bytes_per_device", "max"),  # largest per-device KV pool footprint
    ("weight_bytes", "max"),           # per-device bytes of the weights an engine holds
    ("prefill_batched", "sum"),        # cumulative extra rows batched into prefills
    ("worker_restarts", "sum"),        # cumulative replacement worker respawns
    ("host_failures", "sum"),          # cumulative whole-host domains lost
    ("hosts_active", "max"),           # remote fleet hosts not quarantined
    ("spec_draft_tokens", "sum"),      # cumulative draft-model proposals
    ("spec_accepted_tokens", "sum"),   # cumulative proposals the target accepted
    ("spec_rollbacks", "sum"),         # cumulative verify passes with a rejection
    ("draft_ms", "sum"),               # cumulative draft-pass wall time
    ("verify_ms", "sum"),              # cumulative target-verify wall time
    # serving/step_clocks.py: the engine's own clocks and counters, per step
    ("engine_host_ms", "mean"),        # step time outside prefill and decode
    ("admit_ms", "mean"),              # of it: deadline evictions + admission
    ("grow_ms", "mean"),               # of it: watermark block-table growth
    ("emit_ms", "mean"),               # of it: after the read-back to the end
    ("decode_dispatch_ms", "mean"),    # decode program's call to its return
    ("decode_wait_ms", "mean"),        # from there to the read-back of the step before
    ("decode_overlapped", "mean"),     # share of decode steps dispatched over an unread one
    ("decode_rows", "mean"),           # rows one decode step advances
    ("decode_attended", "mean"),       # keys those rows attend
    ("decode_blocks_live", "mean"),    # table slots of those rows that hold keys
    ("decode_blocks_table", "mean"),   # all table slots of those rows
    ("prefill_tokens", "mean"),        # tokens one chunked prefill dispatch takes
    ("prefill_attended", "mean"),      # keys those tokens attend
    ("sparse_rows", "mean"),           # rows a step puts through block selection
    ("sparse_selected", "mean"),       # blocks those rows attend
    ("sparse_visible", "mean"),        # blocks those rows see
    ("state_resets", "mean"),          # requests a step starts from a zero state
    ("moe_rows", "mean"),              # token-expert pairs a dispatch gives held experts
    ("moe_experts_touched", "mean"),   # held experts a dispatch gives a row, over layers
    ("moe_expert_slots", "mean"),      # held experts a dispatch asks, over layers
    ("ssm_rows", "mean"),              # live rows a decode step's state updates take
    ("sscan_tokens", "mean"),          # tokens a prefill dispatch's selective scans take
    ("sscan_rows", "mean"),            # live rows a decode step's selective-scan updates take
):
    METRIC_REGISTRY.metric(
        _name, reduction=ReductionStrategy.CURRENT, tb_prefix="serve/",
        dist_reduce=_dist, cli_format=None,
    )(float)


for _name, _red, _fmt in (
    ("device_alloc_gb", ReductionStrategy.AVERAGE, "hbm: {value:.2f}GB"),
    ("device_limit_gb", ReductionStrategy.CURRENT, None),
    ("device_peak_alloc_gb", ReductionStrategy.MAX, "hbm_peak: {value:.2f}GB"),
    ("device_utilization_pct", ReductionStrategy.AVERAGE, "hbm_util: {value:.0f}%"),
    ("cpu_mb", ReductionStrategy.SUM, "cpu: {value:.0f}MB"),
):
    METRIC_REGISTRY.metric(
        _name, frequency=20, reduction=_red, tb_prefix="mem/",
        cli_format=_fmt, collector=True,
    )(collect_memory)
