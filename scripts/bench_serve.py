"""Serving bench: the production scheduler vs the PR 7 engine vs one-shot
decode, on seeded Poisson traces with an optional shared prompt prefix.

Drives ``gpt_2_distributed_tpu/serving/`` with SEEDED offline request
traces — Poisson arrivals, uniform prompt/new-token lengths, and (in the
``shared_prefix`` trace) a fraction of requests opening with a common
system-prompt prefix — and reports the numbers a serving deployment is
judged on:

* **tok/s and tok/s/chip** — generated-token throughput over the trace.
* **TTFT p50/p99** — time from a request's *arrival* (not its admission) to
  its first streamed token, so queueing delay is counted honestly.
* **Inter-token latency p50/p99** — gaps between consecutive streamed
  tokens, pooled across all requests.
* **Per-phase breakdown** — cumulative prefill vs decode device time,
  queue-wait p50/p99, preemption count, prefix-cache hit rate.

Each trace runs through THREE configurations:

1. ``engine`` — the scheduler under test (``--prefill_chunk``,
   ``--prefix_cache``, ``--admission`` flags; defaults exercise chunked
   prefill + prefix caching + watermark admission).
2. ``engine_pr7`` — the same engine with every scheduler feature off
   (whole-prompt prefill, no cache, reserve admission): the PR 7 baseline
   replayed on the same trace. Skipped by ``--no_pr7``.
3. ``oneshot_baseline`` — sequential ``generate_cached`` calls, batch 1
   per request, compile-warmed — what serving this repo meant before the
   engine existed. Skipped by ``--no_baseline``.

The bench also asserts per-request streams are IDENTICAL between the two
engine configurations (``streams_bit_identical`` in the record): chunked
prefill, prefix hits and preemption must not change a single token.

Results go to stdout AND ``--json`` (default ``BENCH_SERVE.json``) — the
same record discipline as scripts/bench_fused.py. ``--traces both`` (the
committed-record mode) nests an ``original`` and a ``shared_prefix``
section under ``"traces"``.

``--serve_mesh data:N[,tp:M]`` runs the multi-chip comparison instead:
the same seeded trace through a single-device engine and a mesh-sharded
engine at matched per-device KV pool bytes (the sharded pool scales with
the device count). The run asserts the token streams bit-identical and
merges a ``sharded`` record — concurrent-slot capacity, per-device pool
bytes, tok/s for both engines — into ``--json``. When fewer devices are
visible than the mesh needs, the bench re-execs itself on the forced
virtual-CPU-device platform the test suite uses.

Usage (the committed-record invocation)::

    JAX_PLATFORMS=cpu python scripts/bench_serve.py --model 124M \
        --n_layer 2 --n_embd 64 --n_head 2 --vocab_size 257 \
        --seq_len 128 --traces both --max_batch 16 \
        --num_blocks_shared 36 --repeats 5

Recorded (tiny 2-layer config above, CPU, 2026-08-06 — BENCH_SERVE.json):
original trace 2.16x vs one-shot (the PR 7 record was 2.06x) with the
scheduler features adding ~8% over the PR 7 replay at a full pool; on the
shared-prefix trace with the pool squeezed to 36 blocks the new scheduler
is 1.88x the PR 7 replay (occupancy 11.6 vs 5.8 of 16 slots — reserve
admission strands capacity that watermark + prefix sharing reclaim; 92%
of prompt tokens served from cache, 4 preemptions absorbed) and both
engines' token streams are bit-identical. The CPU win comes from batching
fixed per-op overhead; on TPU the same structure amortizes weight reads
across rows, which is the real prize.

``--spec`` runs the speculative-decoding A/B instead: the same seeded
closed trace through one engine configuration with speculation off and
on (``ServeConfig.spec``), asserting the greedy token streams
bit-identical — speculation must be invisible in tokens — and merging a
``spec`` record (ITL p50/p99 and tok/s for both runs, acceptance rate,
tokens per verify pass) into ``--json``. ``--draft_preset`` drafts with
a real (randomly initialized) preset; without it the draft is the
SELF-SLICE: the target's upper blocks get their output projections
zeroed — exact bitwise identities — and the draft is the first
``--spec_draft_layers`` of the stacked block params, so it computes the
target function exactly (acceptance 1.0, the mechanism's upper bound)
while the target still pays full depth per verify. Exits nonzero on
divergence; any re-emitted or dropped token fails the replay's
token-count assertion.

``--placement subprocess --chaos`` is the process-isolation proof: the
same seeded trace through per-device worker PROCESSES, with replica 0
killed by ``--chaos_kill {exception,sigkill,sigstop}`` mid-decode — real
signals, real corpses, supervision detecting them out-of-band. Merges a
``chaos_proc`` record (keyed by kill mechanism) carrying the RPC-hop
A/B (in-process vs subprocess clean replays) and bit-parity verdicts
for greedy and sampled decoding; exits nonzero on no-fire, divergence,
or any re-emitted token.

``--chaos --chaos_net {partition,torn,slow,blackhole}`` is the
cross-host proof: the bench provisions its OWN remote fleet — real TCP
worker processes with authenticated hellos, two host failure domains,
and an in-path chaos proxy on every link — then injures the victim
host's links mid-decode (hard partition, torn frame mid-header,
injected latency, one-way blackhole). The supervision plane must
contain the whole host as ONE batch (``fail_host``), migrate every
stream with zero re-emission, and re-admit the host after ``heal()``.
Merges a ``chaos_net`` record (keyed by injury mode) carrying the
TCP-hop A/B and bit-parity verdicts for greedy and sampled decoding;
exits nonzero on no-fire, divergence, re-emission, or any failed
stream.

Flag combos the bench can't honor are refused at parse time (mirroring
bench.py's --suite rejection), before any jax import.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# Armed by main() from --xla_profile_at (one capture window per bench
# process; the first replay that reaches the armed step wins). None until
# then: obs.trace must NOT be imported at module scope — the package
# __init__ pulls in jax, and the CLI contract (tested with a poisoned jax
# on PYTHONPATH) is that --help and flag validation never touch jax.
_XLA_CAPTURE = None


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--model", default="124M")
    p.add_argument("--n_layer", type=int, default=None)
    p.add_argument("--n_embd", type=int, default=None)
    p.add_argument("--n_head", type=int, default=None)
    p.add_argument("--vocab_size", type=int, default=None)
    p.add_argument("--seq_len", type=int, default=None,
                   help="n_positions override (bounds prompt+new)")
    # Trace shape. The default rate saturates the engine (queue builds up,
    # occupancy ~max_batch) so the throughput number is a capacity figure;
    # drop --rate to ~the engine's req/s to measure TTFT under light load.
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--rate", type=float, default=1000.0,
                   help="Poisson arrival rate, requests/s")
    p.add_argument("--trace_seed", type=int, default=0)
    p.add_argument("--prompt_min", type=int, default=4)
    p.add_argument("--prompt_max", type=int, default=24)
    p.add_argument("--new_min", type=int, default=16)
    p.add_argument("--new_max", type=int, default=48)
    p.add_argument("--traces", default="original",
                   choices=["original", "shared_prefix", "both"],
                   help="which trace shapes to run (both = committed record)")
    p.add_argument("--shared_prefix_frac", type=float, default=0.75,
                   help="fraction of shared_prefix-trace requests opening "
                   "with the common prefix")
    p.add_argument("--shared_prefix_len", type=int, default=48,
                   help="length of the common prefix, tokens; prompts drawn "
                   "shorter than prefix+1 are lengthened to fit it")
    # Engine shape.
    p.add_argument("--max_batch", type=int, default=8)
    p.add_argument("--block_size", type=int, default=16)
    p.add_argument("--num_blocks", type=int, default=0,
                   help="KV pool blocks; 0 = enough for max_batch worst-case "
                   "sequences")
    p.add_argument("--num_blocks_shared", type=int, default=0,
                   help="KV pool override for the shared_prefix trace; 0 = "
                   "same as --num_blocks. The shared trace exists to probe "
                   "the memory-constrained regime (prefix sharing and "
                   "preemption change CAPACITY, not per-call speed), so the "
                   "committed record squeezes its pool")
    p.add_argument("--attn_impl", default="auto",
                   choices=["auto", "xla", "pallas"])
    # Scheduler under test (engine_pr7 always runs with all three off).
    p.add_argument("--prefill_chunk", type=int, default=0,
                   help="chunked-prefill width for the engine under test; "
                   "0 = whole-prompt prefill (the throughput-record mode — "
                   "chunking trades peak tok/s for bounded decode stalls)")
    p.add_argument("--prefix_cache", default="on", choices=["on", "off"])
    p.add_argument("--admission", default="watermark",
                   choices=["reserve", "watermark"])
    p.add_argument("--watermark_blocks", type=int, default=3)
    p.add_argument("--prefill_batch", type=int, default=1,
                   help="queued prompts folded into ONE chunked-prefill "
                   "dispatch per engine step (multi-row admission; only "
                   "batches when --prefill_chunk > 0)")
    p.add_argument("--serve_mesh", default="", metavar="data:N[,tp:M]",
                   help="sharded mode: replay the seeded trace on a "
                   "single-device engine AND a mesh-sharded engine at "
                   "matched per-device KV pool bytes, assert the token "
                   "streams bit-identical, and merge a 'sharded' record "
                   "into --json. Re-execs itself with forced virtual host "
                   "devices when too few are visible")
    p.add_argument("--spec", action="store_true",
                   help="speculative-decoding A/B: replay the closed trace "
                   "with speculation off and on, assert the greedy streams "
                   "bit-identical, and merge a 'spec' record into --json. "
                   "Drafts with --draft_preset when given, else with the "
                   "self-slice draft (see --spec_draft_layers)")
    p.add_argument("--draft_preset", default=None,
                   help="draft model preset for --spec (vocab/positions "
                   "inherited from the target; randomly initialized here, "
                   "so expect near-zero acceptance — machinery-honest, "
                   "not a speedup demo)")
    p.add_argument("--spec_k", type=int, default=None,
                   help="draft tokens per verify pass (default 4)")
    p.add_argument("--spec_draft_layers", type=int, default=None,
                   help="self-slice draft depth for --spec without "
                   "--draft_preset: the target's blocks past this depth "
                   "get their output projections zeroed (exact identities) "
                   "and the draft is the first N stacked blocks, computing "
                   "the target function exactly (default n_layer//4, "
                   "min 1)")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top_k", type=int, default=None)
    p.add_argument("--repeats", type=int, default=3,
                   help="replay each measurement this many times and keep "
                   "the best (wall-clock jitter only ever slows a run)")
    p.add_argument("--no_baseline", action="store_true",
                   help="skip the one-shot generate_cached comparison")
    p.add_argument("--no_pr7", action="store_true",
                   help="skip the features-off engine replay")
    p.add_argument("--baseline_only", action="store_true",
                   help="run only the one-shot comparison (engine debug)")
    # Front-door mode (scripts/bench_serve.py --duration): open-loop load
    # against the replica router + autoscaler instead of the closed traces.
    p.add_argument("--duration", type=float, default=0.0,
                   help="front-door mode: offer Poisson arrivals for this "
                   "many seconds against the replica router (open loop — "
                   "arrivals never wait for completions), then drain. 0 "
                   "keeps the classic closed-trace bench")
    p.add_argument("--ramp", type=float, default=None,
                   help="ramp the arrival rate linearly from --rate to this "
                   "over --duration (the autoscaler probe); default holds "
                   "--rate constant")
    p.add_argument("--replicas", type=int, default=2,
                   help="front-door mode: engine replicas to start with")
    p.add_argument("--max_replicas", type=int, default=None,
                   help="front-door mode: fleet ceiling; > --replicas "
                   "attaches the autoscaler (closed loop: queue depth and "
                   "SLO pressure grow the fleet, idle shrinks it)")
    p.add_argument("--route", default="affinity",
                   choices=["affinity", "least_loaded", "round_robin"],
                   help="front-door mode: routing policy for the measured "
                   "run (a round_robin control runs either way)")
    p.add_argument("--ttft_slo_ms", type=float, default=None,
                   help="front-door mode: TTFT target; violations counted "
                   "and fed to the autoscaler")
    p.add_argument("--queue_slo_ms", type=float, default=None,
                   help="front-door mode: shed arrivals whose predicted "
                   "queue wait exceeds this")
    # Chaos mode + fault injection (PR 16). Mirrors gpt2-tpu-serve's
    # add_fault_flags — duplicated rather than imported because pulling in
    # serving.serve drags jax through the package __init__, and this CLI's
    # contract is that --help and flag validation never touch jax.
    p.add_argument("--chaos", action="store_true",
                   help="chaos mode: replay the closed trace on a replica "
                   "fleet, kill one replica mid-run (default "
                   "--inject_replica_fail_at 20:0), and verify every "
                   "stream is bit-identical to an unfailed reference "
                   "replay; merges a 'chaos' record into --json")
    p.add_argument("--request_timeout_s", type=float, default=None,
                   help="per-request deadline from submission; overdue "
                   "requests finish with reason 'timeout'")
    p.add_argument("--watchdog_timeout_s", type=float, default=None,
                   help="fail a replica whose single step() exceeds this")
    p.add_argument("--inject_replica_fail_at", default=None,
                   metavar="STEP[:REPLICA]",
                   help="raise inside the given replica's step (default "
                   "replica 0) at fleet step STEP")
    p.add_argument("--inject_replica_hang_at", default=None,
                   metavar="STEP[:REPLICA]",
                   help="hang the given replica's step at fleet step STEP "
                   "until the watchdog trips (needs --watchdog_timeout_s)")
    p.add_argument("--inject_step_exception", type=int, default=None,
                   metavar="STEP",
                   help="raise in whichever replica steps first at fleet "
                   "step STEP")
    # Process-isolated chaos (PR 18). The placement/worker flags are the
    # same ones gpt2-tpu-serve and gpt2-tpu-frontend take; serving.serve
    # is importable jax-free (the serving package exports lazily), so
    # sharing them keeps the three CLIs from drifting without breaking
    # this CLI's poisoned-jax --help contract.
    from gpt_2_distributed_tpu.serving.serve import add_placement_flags

    add_placement_flags(p)
    p.add_argument("--chaos_kill", default="exception",
                   choices=["exception", "sigkill", "sigstop"],
                   help="chaos failure mechanism: 'exception' raises in "
                   "the replica's step (any placement); 'sigkill'/"
                   "'sigstop' send the REAL signal to a subprocess "
                   "worker's pid (needs --placement subprocess) — "
                   "supervision must detect the corpse/stall itself")
    # Network chaos (PR 19): the bench provisions its OWN remote fleet —
    # real TCP workers behind per-link chaos proxies — so no --placement
    # or --worker_pool is needed (or accepted) here.
    p.add_argument("--chaos_net", default=None,
                   choices=["partition", "torn", "slow", "blackhole"],
                   help="network-chaos mode (needs --chaos): replay the "
                   "seeded trace through authenticated TCP workers behind "
                   "in-path chaos proxies, injure the victim HOST's links "
                   "mid-decode (hard partition / torn frame mid-header / "
                   "injected latency / one-way blackhole), and verify "
                   "host-death batch migration kept every stream "
                   "bit-identical to the in-process reference with zero "
                   "re-emitted tokens; merges a 'chaos_net' record keyed "
                   "by mode into --json")
    p.add_argument("--json", default="BENCH_SERVE.json", metavar="PATH",
                   help="result file ('' disables the write); front-door "
                   "and chaos modes merge their record into an existing "
                   "file")
    p.add_argument("--trace_dir", default=None,
                   help="write span/event trace JSONL here (obs/trace.py)")
    p.add_argument("--xla_profile_at", default=None, metavar="STEP[:NSTEPS]",
                   help="capture an XLA profiler trace covering NSTEPS "
                        "(default 1) engine steps starting at STEP of the "
                        "first measured replay; needs --trace_dir")
    return p


def validate_args(p: argparse.ArgumentParser, args: argparse.Namespace) -> None:
    """Parse-time refusals for combos the bench can't honor — before any
    jax import, like bench.py's --suite rejection."""
    if args.baseline_only and args.no_baseline:
        p.error("--baseline_only contradicts --no_baseline; pick one")
    if args.requests < 1:
        p.error(f"--requests {args.requests}: a trace needs at least one "
                "request")
    if args.rate <= 0:
        p.error(f"--rate {args.rate}: arrival rate must be positive")
    if args.prompt_min < 1 or args.prompt_min > args.prompt_max:
        p.error("--prompt_min/--prompt_max must satisfy 1 <= min <= max")
    if args.new_min < 1 or args.new_min > args.new_max:
        p.error("--new_min/--new_max must satisfy 1 <= min <= max")
    if not 0.0 <= args.shared_prefix_frac <= 1.0:
        p.error(f"--shared_prefix_frac {args.shared_prefix_frac}: must be "
                "in [0, 1]")
    if args.traces in ("shared_prefix", "both"):
        if args.shared_prefix_len < 1:
            p.error(f"--shared_prefix_len {args.shared_prefix_len}: the "
                    "shared_prefix trace needs a prefix of >= 1 token")
    if args.num_blocks_shared < 0:
        p.error(f"--num_blocks_shared {args.num_blocks_shared}: must be >= 0")
    if args.prefill_chunk < 0:
        p.error(f"--prefill_chunk {args.prefill_chunk}: must be >= 0")
    if args.watermark_blocks < 0:
        p.error(f"--watermark_blocks {args.watermark_blocks}: must be >= 0")
    if args.repeats < 1:
        p.error(f"--repeats {args.repeats}: need at least one measurement")
    if args.prefill_batch < 1:
        p.error(f"--prefill_batch {args.prefill_batch}: must be >= 1")
    if args.serve_mesh:
        # jax-free on purpose: config.py (and the package __init__ it
        # pulls in) import no jax, so mesh specs are refused at parse time
        # like every other unhonorable flag.
        from gpt_2_distributed_tpu.config import parse_serve_mesh

        try:
            data, tp = parse_serve_mesh(args.serve_mesh)
        except ValueError as e:
            p.error(f"--serve_mesh: {e}")
        if data * tp < 2:
            p.error(f"--serve_mesh {args.serve_mesh!r}: the sharded "
                    "comparison needs a mesh of >= 2 devices")
        if args.duration > 0 or args.chaos or args.baseline_only:
            p.error("--serve_mesh runs the closed-trace sharded "
                    "comparison; drop --duration/--chaos/--baseline_only")
    # Speculative-decoding A/B (jax-free: the draft-flag family is
    # validated by config.validate_worker_flags below; these are the
    # bench-mode combos).
    if args.spec:
        if args.serve_mesh or args.duration > 0 or args.chaos \
                or args.baseline_only:
            p.error("--spec runs the closed-trace speculation A/B; drop "
                    "--serve_mesh/--duration/--chaos/--baseline_only")
        if args.temperature != 0.0:
            p.error("--spec asserts greedy bit-equality, so --temperature "
                    "must be 0 (sampled-speculation exactness is covered "
                    "by the engine's distribution tests)")
    if args.spec_draft_layers is not None:
        if not args.spec or args.draft_preset:
            p.error("--spec_draft_layers shapes the self-slice draft: it "
                    "needs --spec and contradicts --draft_preset")
        if args.spec_draft_layers < 1:
            p.error(f"--spec_draft_layers {args.spec_draft_layers}: "
                    "must be >= 1")
        from gpt_2_distributed_tpu.config import MODEL_PRESETS

        tgt_layers = args.n_layer if args.n_layer is not None else (
            MODEL_PRESETS[args.model].n_layer
            if args.model in MODEL_PRESETS else None
        )
        if tgt_layers is not None and args.spec_draft_layers >= tgt_layers:
            p.error(f"--spec_draft_layers {args.spec_draft_layers}: the "
                    f"self-slice draft must be shallower than the "
                    f"{tgt_layers}-layer target")
    if args.duration < 0:
        p.error(f"--duration {args.duration}: must be >= 0")
    if args.ramp is not None:
        if args.duration <= 0:
            p.error("--ramp only makes sense with --duration")
        if args.ramp <= 0:
            p.error(f"--ramp {args.ramp}: target rate must be positive")
    if args.duration > 0:
        if args.baseline_only or args.no_pr7 or args.no_baseline:
            p.error("--duration (front-door mode) does not run the "
                    "closed-trace comparisons; drop the baseline flags")
        if args.replicas < 1:
            p.error(f"--replicas {args.replicas}: must be >= 1")
        if args.max_replicas is not None and args.max_replicas < args.replicas:
            p.error(f"--max_replicas {args.max_replicas} < --replicas "
                    f"{args.replicas}")
    # Fault injection / chaos (parsed here, jax-free, mirroring
    # resilience.parse_fault_spec; the injector itself is built in main
    # after the jax import).
    def _fault_spec(flag, spec):
        if spec is None:
            return None
        parts = str(spec).split(":")
        try:
            step = int(parts[0])
            replica = int(parts[1]) if len(parts) > 1 else None
            if len(parts) > 2 or step < 1 or (replica is not None
                                              and replica < 0):
                raise ValueError
        except ValueError:
            p.error(f"{flag}={spec!r}: expected STEP[:REPLICA] with "
                    "STEP >= 1 and REPLICA >= 0")
        return step, replica

    args.fail_spec = _fault_spec("--inject_replica_fail_at",
                                 args.inject_replica_fail_at)
    args.hang_spec = _fault_spec("--inject_replica_hang_at",
                                 args.inject_replica_hang_at)
    if args.inject_step_exception is not None and args.inject_step_exception < 1:
        p.error(f"--inject_step_exception={args.inject_step_exception}: "
                "must be >= 1")
    if args.request_timeout_s is not None and args.request_timeout_s < 0:
        p.error(f"--request_timeout_s={args.request_timeout_s}: must be >= 0")
    if args.watchdog_timeout_s is not None and args.watchdog_timeout_s <= 0:
        p.error(f"--watchdog_timeout_s={args.watchdog_timeout_s}: "
                "must be > 0")
    if args.hang_spec is not None and args.watchdog_timeout_s is None:
        p.error("--inject_replica_hang_at needs --watchdog_timeout_s "
                "(nothing else ever detects the hang)")
    # Placement + worker supervision (jax-free: config.py imports no jax).
    from gpt_2_distributed_tpu.config import validate_worker_flags

    validate_worker_flags(p, args)
    if args.chaos_kill != "exception" and args.placement != "subprocess":
        p.error(f"--chaos_kill {args.chaos_kill}: real signals need "
                "--placement subprocess (an in-process replica has no pid "
                "of its own to kill)")
    if args.placement == "subprocess":
        if not args.chaos:
            p.error("--placement subprocess: the bench wires subprocess "
                    "workers through --chaos only (the closed-trace and "
                    "front-door paths reach into engine internals no RPC "
                    "surface exposes)")
        if (args.hang_spec is not None
                or args.inject_step_exception is not None):
            p.error("--placement subprocess chaos is driven by "
                    "--chaos_kill (+ optional --inject_replica_fail_at "
                    "for the trigger step); drop --inject_replica_hang_at"
                    "/--inject_step_exception")
        if args.chaos_net is not None:
            p.error("--chaos_net provisions its own TCP fleet behind "
                    "chaos proxies; drop --placement subprocess")
    if args.placement == "remote":
        p.error("--placement remote: the bench reaches remote TCP workers "
                "through --chaos_net, which provisions its own fleet "
                "(workers + chaos proxies + pool file); drop --placement")
    if args.chaos_net is not None:
        if not args.chaos:
            p.error("--chaos_net replays the closed chaos trace; it needs "
                    "--chaos")
        if args.chaos_kill != "exception":
            p.error(f"--chaos_kill {args.chaos_kill} signals a LOCAL "
                    "process; --chaos_net injures the network — pick one")
        if (args.hang_spec is not None
                or args.inject_step_exception is not None):
            p.error("--chaos_net is driven by the network injury "
                    "(+ optional --inject_replica_fail_at for the trigger "
                    "step); drop --inject_replica_hang_at/"
                    "--inject_step_exception")
    any_inject = (args.fail_spec is not None or args.hang_spec is not None
                  or args.inject_step_exception is not None)
    if args.chaos:
        if args.duration > 0:
            p.error("--chaos replays the closed trace; drop --duration")
        if args.baseline_only or args.no_pr7 or args.no_baseline:
            p.error("--chaos does not run the closed-trace comparisons; "
                    "drop the baseline flags")
        if args.replicas < 2:
            p.error(f"--chaos needs --replicas >= 2, got {args.replicas} "
                    "(a one-replica fleet has nowhere to migrate)")
    elif any_inject and args.duration == 0:
        p.error("fault injection needs --chaos or --duration (front-door "
                "mode): the single-engine closed-trace bench has no "
                "driver to contain failures")
    if args.xla_profile_at is not None:
        from gpt_2_distributed_tpu.obs.trace import parse_profile_at

        try:
            parse_profile_at(args.xla_profile_at)
        except ValueError as e:
            p.error(str(e))
        if not args.trace_dir:
            p.error("--xla_profile_at needs --trace_dir for output")


def percentiles(xs, np):
    if not xs:
        return None, None
    return (round(float(np.percentile(xs, 50)) * 1e3, 2),
            round(float(np.percentile(xs, 99)) * 1e3, 2))


def make_trace(args, np, vocab_size: int, shared: bool):
    """Seeded trace: arrivals, prompts, new-token budgets, request keys.
    With ``shared``, ~shared_prefix_frac of prompts open with one common
    prefix (lengths bumped to fit prefix + >= 1 distinct token)."""
    rng = np.random.default_rng(args.trace_seed)
    n = args.requests
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, n))
    plens = rng.integers(args.prompt_min, args.prompt_max + 1, n)
    news = rng.integers(args.new_min, args.new_max + 1, n)
    pfx = (rng.integers(0, vocab_size, args.shared_prefix_len).tolist()
           if shared else [])
    prompts = []
    n_shared = 0
    for pl in plens:
        pl = int(pl)
        if shared and rng.random() < args.shared_prefix_frac:
            pl = max(pl, args.shared_prefix_len + 1)
            prompts.append(
                pfx + rng.integers(
                    0, vocab_size, pl - args.shared_prefix_len
                ).tolist()
            )
            n_shared += 1
        else:
            prompts.append(rng.integers(0, vocab_size, pl).tolist())
    meta = {
        "requests": n, "rate_req_s": args.rate, "seed": args.trace_seed,
        "prompt_len": [args.prompt_min, args.prompt_max],
        "new_tokens": [args.new_min, args.new_max],
        "total_prompt_tokens": sum(len(pr) for pr in prompts),
        "total_new_tokens": int(news.sum()),
    }
    if shared:
        meta["shared_prefix_len"] = args.shared_prefix_len
        meta["shared_prefix_frac"] = args.shared_prefix_frac
        meta["shared_requests"] = n_shared
    return arrivals, prompts, news, meta


def run_engine(args, params, config, serve, trace, jax, np, make_engine):
    """Replay one trace through one engine configuration; return the
    result record plus the per-request streams (for the bit-parity
    cross-check)."""
    arrivals, prompts, news, _ = trace
    n = len(prompts)
    eng = make_engine(serve)
    # Warm every compile the trace will hit, then reset stats and drop any
    # warmup-registered cache entries. Chunked mode compiles once (any one
    # prompt warms it); whole-prompt mode compiles per prompt-length
    # bucket, PLUS — with the prefix cache on — per continuation width:
    # a cache hit resumes prefill through the chunk path at the bucketed
    # remaining width, so a second warmup pass submits prompts that hit a
    # warmup-registered block with every bucketed remainder the trace can
    # produce. (Resume-after-preemption can hit wider continuations than
    # any prompt; a preemption-heavy measured run may still compile.)
    bs = serve.block_size
    cap = config.n_positions - 2
    buckets = sorted({-(-int(len(p)) // bs) for p in prompts})
    if serve.prefill_chunk:
        buckets = buckets[-1:]
    for nb in buckets:
        # Distinct head token per bucket: with the cache on, shared-prefix
        # warmup prompts would hit each other and skip the whole-prefill
        # compile for every bucket past the first.
        eng.submit([3 + nb] * min(nb * bs, cap), 2, rng=0)
    eng.run_until_idle()
    if serve.prefix_cache and not serve.prefill_chunk:
        eng.submit([1] * bs, 2, rng=0)      # registers a 1-block hit anchor
        eng.run_until_idle()
        for nb in range(1, buckets[-1] + 1):
            pl = bs + nb * bs - 1     # 1-block hit + remainder in bucket nb
            if pl <= cap:             # distinct tails: always a 1-block hit
                eng.submit([1] * bs + [100 + nb] * (pl - bs), 2, rng=0)
        eng.run_until_idle()
    if serve.admission == "watermark" and not serve.prefill_chunk:
        # Preemption resumes prefill at the full table width (one compile
        # for any resume length) — unreachable from submit() without
        # engineering pool exhaustion, so warm the program directly. The
        # 1-token write lands on the null block (block-table row of an
        # empty slot), which the engine already uses as the sanctioned
        # scribble target for idle decode rows.
        with eng._mesh_scope():
            _f, _k, eng.k_pool, eng.v_pool = eng._chunk_fn(
                eng.params, eng.k_pool, eng.v_pool,
                np.ascontiguousarray(eng.block_table[:1]),
                np.zeros((1, eng._m * bs), np.int32),
                np.zeros((1,), np.int32), np.ones((1,), np.int32),
                np.zeros((1, 2), np.uint32),
            )
        _f.block_until_ready()
    keys = [jax.random.PRNGKey(args.trace_seed * 100_000 + i)
            for i in range(n)]
    prompt_tokens = sum(len(p) for p in prompts)

    def one_replay():
        """One cold-cache replay of the trace; returns (record, streams)."""
        eng.clear_prefix_cache()
        eng.stats = {k: type(v)() for k, v in eng.stats.items()}
        token_times: dict[int, list[float]] = {}

        def on_token(req, _tok, _tt=token_times):
            _tt.setdefault(req.id, []).append(time.monotonic())

        t0 = time.monotonic()
        handles = []
        nxt = 0
        step_no = 0
        while nxt < n or eng._queue or eng._has_active():
            now = time.monotonic() - t0
            while nxt < n and arrivals[nxt] <= now:
                handles.append(eng.submit(
                    prompts[nxt], int(news[nxt]), rng=keys[nxt],
                    on_token=on_token,
                ))
                nxt += 1
            if _XLA_CAPTURE is not None:
                _XLA_CAPTURE.maybe_start(step_no + 1)
            stepped = eng.step()
            step_no += 1
            if _XLA_CAPTURE is not None:
                _XLA_CAPTURE.maybe_stop(step_no)
            if (stepped == 0 and not eng._has_active() and not eng._queue
                    and nxt < n):
                # Truly idle: nothing in flight, nothing queued — wait for
                # the next arrival. (A 0-token step can still be chunk-
                # prefill progress; never sleep through those.)
                time.sleep(min(0.001, max(0.0, arrivals[nxt] - now)))
        wall = time.monotonic() - t0

        assert all(h.done for h in handles)
        emitted = sum(len(h.generated) for h in handles)
        assert emitted == int(news.sum())   # no EOS: all run to max_new
        ttfts = [h.first_token_time - (t0 + arrivals[i])
                 for i, h in enumerate(handles)]
        itls = [dt for ts in token_times.values()
                for dt in np.diff(ts).tolist()]
        qwaits = [h.queue_wait_ms / 1e3 for h in handles]
        ttft_p50, ttft_p99 = percentiles(ttfts, np)
        itl_p50, itl_p99 = percentiles(itls, np)
        qw_p50, qw_p99 = percentiles(qwaits, np)
        steps = max(eng.stats["decode_steps"], 1)
        rec = {
            "wall_s": round(wall, 4),
            "tok_s": round(emitted / wall, 1),
            "tok_s_per_chip": round(emitted / wall / jax.device_count(), 1),
            "ttft_p50_ms": ttft_p50, "ttft_p99_ms": ttft_p99,
            "itl_p50_ms": itl_p50, "itl_p99_ms": itl_p99,
            "queue_wait_p50_ms": qw_p50, "queue_wait_p99_ms": qw_p99,
            "prefill_ms": round(eng.stats["prefill_ms"], 1),
            "decode_ms": round(eng.stats["decode_ms"], 1),
            "decode_steps": eng.stats["decode_steps"],
            "prefill_calls": eng.stats["prefills"],
            "prefill_chunks": eng.stats["prefill_chunks"],
            "preemptions": eng.stats["preemptions"],
            "prefix_cache_hit_rate": round(
                eng.stats["prefix_hit_tokens"] / max(prompt_tokens, 1), 4
            ),
            "cow_copies": eng.stats["cow_copies"],
            "mean_batch_occupancy": round(
                (emitted - len(handles)) / steps, 2
            ),
        }
        if serve.spec:
            # Per-slot speculation rounds: drafted accumulates k per
            # active slot per round, so rounds = drafted/k, and each
            # round emits its accepted run + one verify-sourced token.
            k = serve.spec_k
            drafted = eng.stats["spec_draft_tokens"]
            accepted = eng.stats["spec_accepted_tokens"]
            rounds = drafted // max(k, 1)
            rec["spec"] = {
                "k": k,
                "draft_tokens": drafted,
                "accepted_tokens": accepted,
                "acceptance_rate": round(accepted / max(drafted, 1), 4),
                "rollbacks": eng.stats["spec_rollbacks"],
                "tokens_per_verify": round(
                    (accepted + rounds) / max(rounds, 1), 2),
                "draft_ms": round(eng.stats["draft_ms"], 1),
                "verify_ms": round(eng.stats["verify_ms"], 1),
            }
        return rec, [list(h.generated) for h in handles]

    # Best-of-N replays: the streams are deterministic (asserted), only the
    # clock varies, and interference only ever slows a run down.
    best = None
    for _ in range(args.repeats):
        rec, streams = one_replay()
        if best is None:
            best = (rec, streams)
        else:
            assert streams == best[1], "replay changed the token streams"
            if rec["tok_s"] > best[0]["tok_s"]:
                best = (rec, streams)
    return best


def run_sharded(args, params, config, jax, np, make_engine):
    """Same seeded trace through a single-device engine and a
    ``--serve_mesh``-sharded engine at MATCHED per-device KV pool bytes:
    the sharded pool and slot count scale with the mesh, so each chip
    holds exactly the bytes it would hold serving alone. The sharded
    engine must (a) stream every request bit-identically — the mesh is
    invisible in tokens — and (b) offer ``data``× the concurrent decode
    slots, which is the capacity multi-chip serving exists to buy."""
    from gpt_2_distributed_tpu.config import ServeConfig, parse_serve_mesh
    from gpt_2_distributed_tpu.serving.paged_cache import pool_bytes

    dp, tp = parse_serve_mesh(args.serve_mesh)
    base = dict(block_size=args.block_size, attn_impl=args.attn_impl,
                prefill_chunk=args.prefill_chunk,
                prefix_cache=args.prefix_cache == "on",
                admission=args.admission,
                watermark_blocks=args.watermark_blocks,
                prefill_batch=args.prefill_batch)
    probe = ServeConfig(max_batch=args.max_batch,
                        block_size=args.block_size)
    single_blocks = args.num_blocks or (
        1 + args.max_batch * probe.max_blocks_per_seq(config.n_positions)
    )
    serve_single = ServeConfig(max_batch=args.max_batch,
                               num_blocks=single_blocks, **base)
    # data*tp times the pool over data*tp devices = the same bytes per
    # device ('data' splits the block axis, 'tp' the head axis); data
    # times the slot rows (block tables shard over 'data' only).
    serve_sharded = ServeConfig(max_batch=args.max_batch * dp,
                                num_blocks=single_blocks * dp * tp,
                                mesh=args.serve_mesh, **base)
    trace = make_trace(args, np, config.vocab_size,
                       shared=args.traces != "original")
    itemsize = 2  # bf16 pools
    single_rec, single_streams = run_engine(
        args, params, config, serve_single, trace, jax, np, make_engine
    )
    sharded_rec, sharded_streams = run_engine(
        args, params, config, serve_sharded, trace, jax, np, make_engine
    )
    return {
        "mesh": args.serve_mesh, "data": dp, "tp": tp, "devices": dp * tp,
        "trace": trace[3],
        "serve": {"block_size": args.block_size,
                  "prefill_chunk": args.prefill_chunk,
                  "prefill_batch": args.prefill_batch,
                  "prefix_cache": args.prefix_cache == "on",
                  "admission": args.admission},
        "single": {
            **single_rec,
            "concurrent_slots": serve_single.max_batch,
            "num_blocks": serve_single.num_blocks,
            "kv_pool_bytes_per_device": pool_bytes(
                config, serve_single, itemsize),
        },
        "sharded": {
            **sharded_rec,
            "concurrent_slots": serve_sharded.max_batch,
            "num_blocks": serve_sharded.num_blocks,
            "kv_pool_bytes_per_device": pool_bytes(
                config, serve_sharded, itemsize) // (dp * tp),
        },
        "slot_capacity_ratio": round(
            serve_sharded.max_batch / serve_single.max_batch, 2),
        "sharded_tok_s_ratio": round(
            sharded_rec["tok_s"] / single_rec["tok_s"], 2),
        "streams_bit_identical": sharded_streams == single_streams,
    }


def run_spec(args, params, config, jax, np):
    """Speculative-decoding A/B: the same seeded closed trace through ONE
    engine configuration with speculation off and on. Greedy speculation
    is exact — every emitted token is a verify-pass argmax, rejected
    drafts roll back invisibly — so the two runs must stream every
    request bit-identically; the record carries ITL/throughput for both
    plus the acceptance telemetry any improvement is explained by.

    The draft model: ``--draft_preset`` when given (randomly initialized
    — exercises the honest two-model path, near-zero acceptance), else
    the SELF-SLICE: the target's blocks past ``--spec_draft_layers`` get
    ``attn_proj``/``mlp_proj`` weights and biases zeroed, turning them
    into exact bitwise identities (the residual adds 0), and the draft
    is the first N stacked blocks sharing wte/wpe/ln_f. The sliced draft
    then computes the target function EXACTLY — greedy acceptance is 1.0
    by construction — while the target still pays its full depth per
    verify dispatch, so the measured ITL win is honest wall-clock, just
    at the mechanism's acceptance upper bound."""
    from gpt_2_distributed_tpu.config import MODEL_PRESETS, ServeConfig
    from gpt_2_distributed_tpu.models import gpt2
    from gpt_2_distributed_tpu.serving import ServingEngine

    k = args.spec_k or 4
    if args.draft_preset:
        draft_config = MODEL_PRESETS[args.draft_preset].replace(
            vocab_size=config.vocab_size, n_positions=config.n_positions
        )
        draft_params = gpt2.init_params(draft_config)
        draft_rec = {"preset": args.draft_preset, "self_sliced": False,
                     "n_layer": draft_config.n_layer}
        spec = f"draft:{args.draft_preset},k:{k}"
    else:
        ld = args.spec_draft_layers or max(1, config.n_layer // 4)
        zero_out = {"attn_proj_w", "attn_proj_b", "mlp_proj_w",
                    "mlp_proj_b"}
        params = dict(params)
        params["block"] = {
            name: (leaf.at[ld:].set(0) if name in zero_out else leaf)
            for name, leaf in params["block"].items()
        }
        draft_params = dict(params)
        draft_params["block"] = {
            name: leaf[:ld] for name, leaf in params["block"].items()
        }
        draft_config = config.replace(n_layer=ld)
        draft_rec = {"preset": None, "self_sliced": True, "n_layer": ld}
        # The spec string's preset field names what a CLI would load; the
        # bench hands the engine explicit draft params, so reuse the
        # target's preset name to keep the string parseable.
        spec = f"draft:{args.model},k:{k}"

    probe = ServeConfig(max_batch=args.max_batch,
                        block_size=args.block_size)
    base = dict(
        max_batch=args.max_batch, block_size=args.block_size,
        num_blocks=args.num_blocks or (
            1 + args.max_batch * probe.max_blocks_per_seq(config.n_positions)
        ),
        attn_impl=args.attn_impl, prefill_chunk=args.prefill_chunk,
        prefix_cache=args.prefix_cache == "on", admission=args.admission,
        watermark_blocks=args.watermark_blocks,
        prefill_batch=args.prefill_batch,
    )
    serve_off = ServeConfig(**base)
    serve_on = ServeConfig(**base, spec=spec)

    def make_off(serve):
        return ServingEngine(params, config, serve,
                             temperature=args.temperature, top_k=args.top_k)

    def make_on(serve):
        return ServingEngine(params, config, serve,
                             temperature=args.temperature, top_k=args.top_k,
                             draft_params=draft_params,
                             draft_config=draft_config)

    rec = {
        "k": k, "draft": draft_rec,
        "serve": {"max_batch": serve_on.max_batch,
                  "block_size": serve_on.block_size,
                  "num_blocks": serve_on.num_blocks,
                  "prefill_chunk": serve_on.prefill_chunk,
                  "prefix_cache": serve_on.prefix_cache,
                  "admission": serve_on.admission},
        "traces": {},
    }
    names = (["original", "shared_prefix"] if args.traces == "both"
             else [args.traces])
    for name in names:
        trace = make_trace(args, np, config.vocab_size,
                           shared=name == "shared_prefix")
        off_rec, off_streams = run_engine(
            args, params, config, serve_off, trace, jax, np, make_off
        )
        on_rec, on_streams = run_engine(
            args, params, config, serve_on, trace, jax, np, make_on
        )
        sec = {
            "trace": trace[3],
            "off": off_rec,
            "on": on_rec,
            "streams_bit_identical": on_streams == off_streams,
            "acceptance_rate": on_rec["spec"]["acceptance_rate"],
            "tokens_per_verify": on_rec["spec"]["tokens_per_verify"],
            "tok_s_ratio": round(on_rec["tok_s"] / off_rec["tok_s"], 2),
        }
        if (off_rec["itl_p50_ms"] is not None
                and on_rec["itl_p50_ms"] is not None):
            # >1 means speculation tightened the median inter-token gap.
            # An accepted run emits as a burst, so the on-side median gap
            # can be ~0; floor the denominator at 10us to keep the ratio
            # finite rather than dropping the field.
            sec["itl_p50_improvement"] = round(
                off_rec["itl_p50_ms"] / max(on_rec["itl_p50_ms"], 0.01), 2
            )
        rec["traces"][name] = sec
    return rec


def run_frontend(args, config, serve, jax, np, make_engine, policy,
                 injector=None):
    """Open-loop Poisson load for --duration seconds against the replica
    router (optionally autoscaled), then drain; returns the record.

    Open loop means arrivals are generated by the clock, never gated on
    completions — the regime where queues actually build. The rate ramps
    linearly from --rate to --ramp across the window. ~--shared_prefix_frac
    of prompts open with a common prefix so prefix-affinity routing has
    structure to exploit; compiles triggered by autoscaler growth happen
    in-run, exactly as they would in production lazy growth.
    """
    from gpt_2_distributed_tpu.serving.frontend.autoscale import Autoscaler
    from gpt_2_distributed_tpu.serving.frontend.driver import EngineDriver
    from gpt_2_distributed_tpu.serving.frontend.router import (
        ReplicaRouter,
        ShedError,
    )

    max_replicas = args.max_replicas or args.replicas
    router = ReplicaRouter(
        lambda: make_engine(serve), replicas=args.replicas,
        max_replicas=max_replicas, policy=policy,
        ttft_slo_ms=args.ttft_slo_ms, queue_slo_ms=args.queue_slo_ms,
        # distinct rid namespaces per policy: the measured run and the
        # round_robin control share one --trace_dir
        rid_start={"affinity": 0, "least_loaded": 1_000_000,
                   "round_robin": 2_000_000}[policy],
    )
    scaler = (Autoscaler(router, min_replicas=args.replicas,
                         max_replicas=max_replicas)
              if max_replicas > args.replicas else None)
    driver = EngineDriver(router, autoscaler=scaler, autoscale_every=8,
                          request_timeout_s=args.request_timeout_s,
                          watchdog_timeout_s=args.watchdog_timeout_s,
                          injector=injector)

    # Warm the initial replicas' prompt-length buckets directly (bypassing
    # the router so its counters stay clean), then reset engine stats.
    bs = serve.block_size
    cap = config.n_positions - 2
    longest = max(args.prompt_max, args.shared_prefix_len + 1)
    buckets = ({-(-longest // bs)} if serve.prefill_chunk else
               set(range(-(-args.prompt_min // bs), -(-longest // bs) + 1)))
    for eng in router.engines:
        for nb in sorted(buckets):
            eng.submit([3 + nb] * min(nb * bs, cap), 2, rng=0)
        eng.run_until_idle()
        eng.clear_prefix_cache()
        eng.stats = {k: type(v)() for k, v in eng.stats.items()}

    rng = np.random.default_rng(args.trace_seed)
    pfx = rng.integers(0, config.vocab_size, args.shared_prefix_len).tolist()

    def draw_prompt():
        pl = int(rng.integers(args.prompt_min, args.prompt_max + 1))
        if rng.random() < args.shared_prefix_frac:
            pl = max(pl, args.shared_prefix_len + 1)
            return pfx + rng.integers(
                0, config.vocab_size, pl - args.shared_prefix_len
            ).tolist()
        return rng.integers(0, config.vocab_size, pl).tolist()

    r0 = args.rate
    r1 = args.ramp if args.ramp is not None else args.rate
    dur = args.duration
    arrivals: dict[int, float] = {}     # rid -> offered wall time
    handles = []
    offered = sheds = 0
    max_active = router.n_active
    t0 = time.monotonic()
    t_next = float(rng.exponential(1.0 / r0))
    while True:
        now = time.monotonic() - t0
        while t_next <= now and t_next < dur:
            prompt = draw_prompt()
            new = int(rng.integers(args.new_min, args.new_max + 1))
            offered += 1
            try:
                h = driver.submit(
                    prompt, new,
                    rng=jax.random.PRNGKey(args.trace_seed * 100_000
                                           + offered),
                )
                arrivals[h.id] = t0 + t_next
                handles.append(h)
            except ShedError:
                sheds += 1
            rate = r0 + (r1 - r0) * min(t_next / dur, 1.0)
            t_next += float(rng.exponential(1.0 / rate))
        if driver.has_work():
            driver.step()
            max_active = max(max_active, router.n_active)
        elif t_next < dur:
            time.sleep(min(0.001, max(0.0, t_next - now)))
        else:
            break
    wall = time.monotonic() - t0
    driver.close()

    assert all(h.done for h in handles)
    emitted = sum(len(h.generated) for h in handles)
    # A request can finish by timeout/replica-failure before its first
    # token when deadlines or fault injection are armed.
    ttfts = [h.first_token_time - arrivals[h.id] for h in handles
             if h.first_token_time is not None]
    ttft_p50, ttft_p99 = percentiles(ttfts, np)
    per_replica = [len([h for h in handles if h.replica == i])
                   for i in range(len(router.engines))]
    rec = {
        "policy": policy,
        "wall_s": round(wall, 4),
        "offered": offered,
        "completed": len(handles),
        "shed": sheds,
        "shed_rate": round(sheds / max(offered, 1), 4),
        "tok_s": round(emitted / wall, 1),
        "ttft_p50_ms": ttft_p50, "ttft_p99_ms": ttft_p99,
        "slo_violations": router.slo_violations,
        "prefix_cache_hit_rate": round(router.aggregate_hit_rate(), 4),
        "affinity_hits": router.affinity_hits,
        "requests_per_replica": per_replica,
        "replicas_final": router.n_active,
        "replicas_max": max_active,
    }
    if scaler is not None:
        rec["scale_ups"] = scaler.scale_ups
        rec["scale_downs"] = scaler.scale_downs
    if injector is not None or args.request_timeout_s is not None:
        rec["replica_failures"] = router.replica_failures
        rec["requests_migrated"] = router.migrated
        rec["watchdog_trips"] = driver.watchdog_trips
        rec["timeouts"] = sum(h.finish_reason == "timeout" for h in handles)
    return rec


def run_chaos(args, config, serve, jax, np, make_engine, make_inj):
    """Closed-trace replay on a replica fleet, twice: once clean (the
    reference) and once with the configured fault injected mid-run. Every
    request must stream the exact same tokens in both runs — replica
    failure, migration and watchdog trips may cost time, never tokens.
    Returns the chaos record: recovery time, migrated-stream count, and
    the bit-parity verdict."""
    from gpt_2_distributed_tpu.serving.frontend.driver import EngineDriver
    from gpt_2_distributed_tpu.serving.frontend.router import ReplicaRouter

    shared = args.traces != "original"
    trace = make_trace(args, np, config.vocab_size, shared=shared)
    arrivals, prompts, news, meta = trace
    n = len(prompts)
    keys = [jax.random.PRNGKey(args.trace_seed * 100_000 + i)
            for i in range(n)]

    def replay(injector):
        router = ReplicaRouter(lambda: make_engine(serve),
                               replicas=args.replicas,
                               max_replicas=args.replicas, policy=args.route)
        driver = EngineDriver(
            router, request_timeout_s=args.request_timeout_s,
            watchdog_timeout_s=args.watchdog_timeout_s, injector=injector,
        )
        # Same per-replica compile warmup as the front-door mode.
        bs = serve.block_size
        cap = config.n_positions - 2
        longest = max(len(pr) for pr in prompts)
        buckets = ({-(-longest // bs)} if serve.prefill_chunk else
                   {-(-len(pr) // bs) for pr in prompts})
        for eng in router.engines:
            for nb in sorted(buckets):
                eng.submit([3 + nb] * min(nb * bs, cap), 2, rng=0)
            eng.run_until_idle()
            eng.clear_prefix_cache()
            eng.stats = {k: type(v)() for k, v in eng.stats.items()}

        tok_times: dict[int, list[float]] = {}

        def on_token(req, _tok, _tt=tok_times):
            _tt.setdefault(req.id, []).append(time.monotonic())

        handles = []
        placed: dict[int, int] = {}    # rid -> replica routed to at submit
        t_fail = None
        nxt = 0
        t0 = time.monotonic()
        while nxt < n or driver.has_work():
            now = time.monotonic() - t0
            while nxt < n and arrivals[nxt] <= now:
                h = driver.submit(prompts[nxt], int(news[nxt]),
                                  rng=keys[nxt], on_token=on_token)
                placed[h.id] = h.replica
                handles.append(h)
                nxt += 1
            if driver.has_work():
                driver.step()
                if t_fail is None and router.replica_failures:
                    t_fail = time.monotonic()
            elif nxt < n:
                time.sleep(min(0.001, max(0.0, arrivals[nxt] - now)))
        wall = time.monotonic() - t0
        driver.close()
        assert all(h.done for h in handles)

        migrated = [h for h in handles if h.replica != placed[h.id]]
        recovery = None
        if t_fail is not None and migrated:
            # Failure detection -> every migrated stream has resumed
            # (emitted its first post-failure token).
            resumed = [min((t for t in tok_times.get(h.id, [])
                            if t > t_fail), default=None) for h in migrated]
            if all(r is not None for r in resumed):
                recovery = max(resumed) - t_fail
        emitted = sum(len(h.generated) for h in handles)
        rec = {
            "wall_s": round(wall, 4),
            "tok_s": round(emitted / wall, 1),
            "completed": sum(h.finish_reason in ("eos", "length")
                             for h in handles),
            "replica_failures": router.replica_failures,
            "migrated_streams": router.migrated,
            "watchdog_trips": driver.watchdog_trips,
            "timeouts": sum(h.finish_reason == "timeout" for h in handles),
            "failed_streams": sum(h.finish_reason == "failed"
                                  for h in handles),
            # on_token calls beyond len(generated) would be re-emits; the
            # migration contract is zero
            "re_emitted_tokens": sum(
                len(tok_times.get(h.id, [])) - len(h.generated)
                for h in handles
            ),
            "recovery_s": (round(recovery, 4) if recovery is not None
                           else None),
        }
        return rec, [list(h.generated) for h in handles]

    ref_rec, ref_streams = replay(None)
    chaos_rec, chaos_streams = replay(make_inj())
    chaos_rec["streams_bit_identical"] = chaos_streams == ref_streams
    return {
        "trace": meta,
        "replicas": args.replicas,
        "policy": args.route,
        "fail_at": args.inject_replica_fail_at,
        "hang_at": args.inject_replica_hang_at,
        "step_exception_at": args.inject_step_exception,
        "serve": {"max_batch": serve.max_batch,
                  "block_size": serve.block_size,
                  "num_blocks": serve.num_blocks,
                  "prefill_chunk": serve.prefill_chunk,
                  "prefix_cache": serve.prefix_cache,
                  "admission": serve.admission},
        "reference": ref_rec,
        "chaos": chaos_rec,
    }


def run_chaos_proc(args, params, config, serve, jax, np):
    """Process-isolation chaos (``--placement subprocess``): the seeded
    closed trace replayed through per-device worker PROCESSES, with the
    victim killed by ``--chaos_kill`` mid-decode.

    Six replays of the one trace — for greedy and sampled decoding each:

    1. ``inprocess`` — the PR 16 in-process fleet: the reference streams
       and the RPC-overhead baseline.
    2. ``subprocess`` — a clean worker fleet: same tokens, slower by the
       RPC hop (the A/B that prices process isolation; PERF_ANALYSIS §19).
    3. ``subprocess_kill`` — replica 0 takes the real signal (or an
       injected step exception) mid-run; the supervision plane must
       detect it out-of-band, migrate every in-flight stream off the
       corpse via the serialized wire form, and respawn a replacement
       through the autoscaler's below-min path.

    Every stream in every replay must match the in-process reference
    bit-for-bit, and the kill replay must re-emit nothing — main() exits
    nonzero otherwise, so a committed ``chaos_proc`` record IS the proof.
    """
    import copy
    import signal as _sig

    from gpt_2_distributed_tpu.resilience import FaultInjector
    from gpt_2_distributed_tpu.serving import ServingEngine
    from gpt_2_distributed_tpu.serving.frontend.autoscale import Autoscaler
    from gpt_2_distributed_tpu.serving.frontend.driver import EngineDriver
    from gpt_2_distributed_tpu.serving.frontend.router import ReplicaRouter
    from gpt_2_distributed_tpu.serving.frontend.worker import (
        spawner_from_args,
    )

    shared = args.traces != "original"
    trace = make_trace(args, np, config.vocab_size, shared=shared)
    arrivals, prompts, news, meta = trace
    n = len(prompts)
    keys = [jax.random.PRNGKey(args.trace_seed * 100_000 + i)
            for i in range(n)]
    kill_step, kill_replica = args.fail_spec
    kill_replica = kill_replica if kill_replica is not None else 0
    kill_sig = {"sigkill": _sig.SIGKILL,
                "sigstop": _sig.SIGSTOP}.get(args.chaos_kill)

    def replay(temp, placement, kill=False):
        spawner = None
        if placement == "subprocess":
            a = copy.copy(args)
            a.temperature = temp
            a.ckpt, a.init_random = None, True  # same seeded init weights
            spawner = spawner_from_args(a, serve,
                                        initial_replicas=args.replicas)
            factory = spawner
        else:
            def factory():
                return ServingEngine(params, config, serve,
                                     temperature=temp, top_k=args.top_k)
        router = ReplicaRouter(
            factory, replicas=args.replicas,
            # +1 headroom on the kill run only: a FAILED replica keeps its
            # index and counts against the ceiling, and the replacement
            # worker needs a free slot to spawn into.
            max_replicas=args.replicas + (1 if kill else 0),
            policy=args.route,
        )
        if spawner is not None:
            spawner.router = router
        injector = scaler = None
        if kill:
            # Supervision under test: the autoscaler's below-min
            # replacement path respawns the victim. The first tick lands
            # AFTER the kill step, so migration (immediate, inside
            # fail_replica) always precedes the respawn.
            scaler = Autoscaler(router, min_replicas=args.replicas,
                                max_replicas=args.replicas + 1)
            if kill_sig is not None:
                injector = FaultInjector(
                    kill_at=(kill_step, kill_replica),
                    kill_fn=lambda r: router.engines[r].kill(kill_sig),
                )
            else:
                injector = FaultInjector(fail_at=(kill_step, kill_replica))
        driver = EngineDriver(
            router, autoscaler=scaler,
            autoscale_every=max(25, kill_step + 1),
            request_timeout_s=args.request_timeout_s,
            watchdog_timeout_s=args.watchdog_timeout_s, injector=injector,
        )
        # Same per-replica compile warmup as run_chaos — for subprocess
        # placement every call here is an RPC and the compiles happen in
        # the worker processes.
        bs = serve.block_size
        cap = config.n_positions - 2
        buckets = ({-(-max(len(pr) for pr in prompts) // bs)}
                   if serve.prefill_chunk else
                   {-(-len(pr) // bs) for pr in prompts})
        for eng in router.engines:
            for nb in sorted(buckets):
                eng.submit([3 + nb] * min(nb * bs, cap), 2, rng=0)
            eng.run_until_idle()
            eng.clear_prefix_cache()
            eng.stats = {k: type(v)() for k, v in eng.stats.items()}
        if kill and args.chaos_kill == "sigstop":
            # A SIGSTOPped worker answers nothing: detection IS the step
            # RPC timing out. Cap the victim's patience once warmup is
            # done (the respawned replacement keeps the spawner's full
            # budget for its own lazy compiles).
            victim = router.engines[kill_replica]
            victim.rpc_timeout_s = min(victim.rpc_timeout_s, 10.0)

        tok_times: dict[int, list[float]] = {}

        def on_token(req, _tok, _tt=tok_times):
            _tt.setdefault(req.id, []).append(time.monotonic())

        handles = []
        placed: dict[int, int] = {}
        t_fail = None
        nxt = 0
        t0 = time.monotonic()
        while nxt < n or driver.has_work():
            now = time.monotonic() - t0
            while nxt < n and arrivals[nxt] <= now:
                h = driver.submit(prompts[nxt], int(news[nxt]),
                                  rng=keys[nxt], on_token=on_token)
                placed[h.id] = h.replica
                handles.append(h)
                nxt += 1
            if driver.has_work():
                driver.step()
                if t_fail is None and router.replica_failures:
                    t_fail = time.monotonic()
            elif nxt < n:
                time.sleep(min(0.001, max(0.0, arrivals[nxt] - now)))
        wall = time.monotonic() - t0
        driver.close()
        assert all(h.done for h in handles)

        migrated = [h for h in handles if h.replica != placed[h.id]]
        recovery = None
        if t_fail is not None and migrated:
            resumed = [min((t for t in tok_times.get(h.id, [])
                            if t > t_fail), default=None) for h in migrated]
            if all(r is not None for r in resumed):
                recovery = max(resumed) - t_fail
        emitted = sum(len(h.generated) for h in handles)
        rec = {
            "wall_s": round(wall, 4),
            "tok_s": round(emitted / wall, 1),
            "completed": sum(h.finish_reason in ("eos", "length")
                             for h in handles),
            "replica_failures": router.replica_failures,
            "migrated_streams": router.migrated,
            "watchdog_trips": driver.watchdog_trips,
            "timeouts": sum(h.finish_reason == "timeout" for h in handles),
            "failed_streams": sum(h.finish_reason == "failed"
                                  for h in handles),
            "re_emitted_tokens": sum(
                len(tok_times.get(h.id, [])) - len(h.generated)
                for h in handles
            ),
            "recovery_s": (round(recovery, 4) if recovery is not None
                           else None),
        }
        if spawner is not None:
            rec["worker_restarts"] = spawner.respawns
        return rec, [list(h.generated) for h in handles]

    out = {
        "kill": args.chaos_kill,
        "trace": meta,
        "replicas": args.replicas,
        "policy": args.route,
        "fail_at": f"{kill_step}:{kill_replica}",
        "serve": {"max_batch": serve.max_batch,
                  "block_size": serve.block_size,
                  "num_blocks": serve.num_blocks,
                  "prefill_chunk": serve.prefill_chunk,
                  "prefix_cache": serve.prefix_cache,
                  "admission": serve.admission},
        "worker": {"max_respawns": args.worker_max_respawns,
                   "respawn_backoff_s": args.worker_respawn_backoff_s,
                   "rpc_timeout_s": args.worker_rpc_timeout_s,
                   "heartbeat_s": args.worker_heartbeat_s},
    }
    for mode, temp in (("greedy", 0.0), ("sampled", 1.0)):
        ref_rec, ref_streams = replay(temp, "inprocess")
        sub_rec, sub_streams = replay(temp, "subprocess")
        kill_rec, kill_streams = replay(temp, "subprocess", kill=True)
        out[mode] = {
            "inprocess": ref_rec,
            "subprocess": sub_rec,
            "subprocess_kill": kill_rec,
            "streams_bit_identical": (sub_streams == ref_streams
                                      and kill_streams == ref_streams),
        }
    g = out["greedy"]
    out["rpc_overhead"] = {
        "inprocess_tok_s": g["inprocess"]["tok_s"],
        "subprocess_tok_s": g["subprocess"]["tok_s"],
        # Per-token cost of the hop: difference of the clean replays'
        # seconds-per-token. Positive = the RPC plane costs time.
        "per_token_overhead_us": round(
            (1.0 / g["subprocess"]["tok_s"]
             - 1.0 / g["inprocess"]["tok_s"]) * 1e6, 1),
    }
    return out


def run_chaos_net(args, params, config, serve, jax, np):
    """Cross-host network chaos (``--chaos_net``): the seeded closed trace
    replayed through REAL TCP workers — authenticated hello, host_ids,
    pool-file adoption — with every link routed through an in-path
    :class:`ChaosProxy` and the victim HOST's links injured mid-decode.

    Per temperature (greedy and sampled) the bench provisions one fleet of
    ``2 * replicas`` worker processes — ``replicas`` on victim host ``h0``,
    ``replicas`` spares on survivor ``h1`` — and runs three replays:

    1. ``inprocess`` — the PR 16 reference streams.
    2. ``remote`` — a clean TCP fleet adopted from a direct pool file:
       the TCP-vs-in-process RPC A/B (PERF_ANALYSIS §20 prices the hop
       against chaos_proc's Unix-socket number).
    3. ``remote_chaos`` — the same workers behind chaos proxies; at the
       trigger step BOTH of h0's links take the injury at once, so the
       health sweep sees every worker on the host fail inside one window
       and must contain the whole failure domain as a batch
       (``fail_host``): one extract->adopt wave onto h1, zero re-emitted
       tokens, and — once the links heal — a dial-probe re-admission of
       h0 (``host_joined``).

    Every stream in every replay must match the in-process reference
    bit-for-bit; main() exits nonzero on no-fire, divergence, any
    re-emission, or any failed stream — a committed ``chaos_net`` record
    IS the proof.
    """
    import copy
    import shutil
    import subprocess
    import tempfile

    from gpt_2_distributed_tpu.resilience import forced_host_device_env
    from gpt_2_distributed_tpu.serving import ServingEngine
    from gpt_2_distributed_tpu.serving.frontend.autoscale import Autoscaler
    from gpt_2_distributed_tpu.serving.frontend.driver import EngineDriver
    from gpt_2_distributed_tpu.serving.frontend.netchaos import ChaosProxy
    from gpt_2_distributed_tpu.serving.frontend.router import ReplicaRouter
    from gpt_2_distributed_tpu.serving.frontend.rpc import (
        WireError,
        client_hello,
        dial,
        load_auth_token,
    )
    from gpt_2_distributed_tpu.serving.frontend.worker import (
        read_worker_pool,
        remote_spawner_from_args,
        worker_argv,
    )

    shared = args.traces != "original"
    trace = make_trace(args, np, config.vocab_size, shared=shared)
    arrivals, prompts, news, meta = trace
    n = len(prompts)
    keys = [jax.random.PRNGKey(args.trace_seed * 100_000 + i)
            for i in range(n)]
    kill_step, _ = args.fail_spec
    mode = args.chaos_net

    def fleet_args(temp):
        """Frontend/worker flag set shared by every replay of one fleet:
        seeded init weights, tight heartbeat cadence so failure detection
        happens in the health sweep (where host-death classification
        lives), and the PR 19 satellite knob exercised for real."""
        a = copy.copy(args)
        a.temperature = temp
        a.ckpt, a.init_random = None, True
        a.worker_heartbeat_s = 0.05
        a.worker_heartbeat_timeout_s = 1.0
        a.worker_respawn_backoff_s = 0.5
        return a

    def wait_ready(addr, token, timeout_s=180.0):
        """Full authenticated hello round-trip: returns once the worker's
        engine is built and answering (TCP workers bind before the jax
        import, so connect alone proves nothing)."""
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                s = dial(addr, timeout=10.0)
                try:
                    client_hello(s, token, peer=addr)
                finally:
                    s.close()
                return
            except (OSError, WireError) as e:
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        f"worker at {addr} never became ready: {e}"
                    ) from e
                time.sleep(0.2)

    def start_fleet(temp, tmp):
        """2*replicas authenticated TCP workers: replicas on victim host
        h0, replicas spares on h1, each advertising its bound port into a
        registration ledger the bench then sorts into pool files."""
        token_path = os.path.join(tmp, "token")
        with open(token_path, "w") as f:
            f.write("bench-chaos-net-secret\n")
        a = fleet_args(temp)
        a.worker_auth_token_file = token_path
        adv = os.path.join(tmp, "advertised")
        open(adv, "w").close()
        env = None
        if (os.environ.get("JAX_PLATFORMS") or "").startswith("cpu"):
            env = forced_host_device_env(serve.mesh_devices)
        procs = []
        n_workers = 2 * args.replicas
        for i in range(n_workers):
            host = "h0" if i < args.replicas else "h1"
            argv = worker_argv(a, serve) + [
                "--socket", "tcp://127.0.0.1:0",
                "--host_id", host, "--advertise", adv,
            ]
            procs.append(subprocess.Popen(argv, env=env))
        deadline = time.monotonic() + 180.0
        while True:
            try:
                entries = read_worker_pool(adv)
            except ValueError:
                entries = []
            if len(entries) == n_workers:
                break
            dead = [pr.pid for pr in procs if pr.poll() is not None]
            if dead or time.monotonic() >= deadline:
                for pr in procs:
                    pr.kill()
                raise RuntimeError(
                    f"worker fleet failed to register: "
                    f"{len(entries)}/{n_workers} advertised"
                    + (f", pids {dead} exited" if dead else "")
                )
            time.sleep(0.2)
        # Pool order decides initial adoption: victims (h0) first, so the
        # chaos replay provably starts with every replica on the victim
        # host. The advertise file's order is registration-racy — sort.
        entries.sort(key=lambda e: (e["host_id"], e["addr"]))
        token = load_auth_token(token_path)
        for e in entries:
            wait_ready(e["addr"], token)
        direct = os.path.join(tmp, "pool_direct")
        with open(direct, "w") as f:
            for e in entries:
                f.write(f"{e['host_id']} {e['addr']}\n")
        proxies = [ChaosProxy(e["addr"]) for e in entries]
        proxied = os.path.join(tmp, "pool_proxied")
        with open(proxied, "w") as f:
            for e, px in zip(entries, proxies):
                f.write(f"{e['host_id']} {px.addr}\n")
        victims = [px for e, px in zip(entries, proxies)
                   if e["host_id"] == "h0"]
        return procs, proxies, victims, token_path, direct, proxied

    def injure(victims):
        for px in victims:
            if mode == "partition":
                px.partition()
            elif mode == "torn":
                # 2 bytes into the next reply frame's 4-byte length
                # prefix: a mid-header truncation the framing layer must
                # turn into a loud WireError, never a desync.
                px.tear(after_bytes=2)
            elif mode == "slow":
                px.set_latency(10.0)    # >> heartbeat timeout: slow = dead
            else:                       # blackhole
                px.blackhole("down")

    def replay(temp, placement, pool=None, token_path=None, victims=None):
        chaos = victims is not None
        spawner = None
        if placement == "remote":
            a = fleet_args(temp)
            a.worker_pool = pool
            a.worker_auth_token_file = token_path
            if chaos:
                # Adoption probes through an injured link must fail fast,
                # not burn the 120s default (every engine is already
                # built, so a healthy hello is instant).
                a.worker_connect_timeout_s = 3.0
            spawner = remote_spawner_from_args(
                a, serve, initial_replicas=args.replicas)
            factory = spawner
        else:
            def factory():
                return ServingEngine(params, config, serve,
                                     temperature=temp, top_k=args.top_k)
        router = ReplicaRouter(
            factory, replicas=args.replicas,
            # Chaos headroom: every victim-host replica keeps its FAILED
            # index and needs a replacement slot on the survivor host.
            max_replicas=args.replicas * (2 if chaos else 1),
            policy=args.route,
        )
        if spawner is not None:
            spawner.router = router
        scaler = None
        if chaos:
            scaler = Autoscaler(router, min_replicas=args.replicas,
                                max_replicas=args.replicas * 2)
        driver = EngineDriver(
            router, autoscaler=scaler,
            autoscale_every=max(25, kill_step + 1),
            request_timeout_s=args.request_timeout_s,
            watchdog_timeout_s=args.watchdog_timeout_s,
        )
        bs = serve.block_size
        cap = config.n_positions - 2
        buckets = ({-(-max(len(pr) for pr in prompts) // bs)}
                   if serve.prefill_chunk else
                   {-(-len(pr) // bs) for pr in prompts})
        for eng in router.engines:
            for nb in sorted(buckets):
                eng.submit([3 + nb] * min(nb * bs, cap), 2, rng=0)
            eng.run_until_idle()
            eng.clear_prefix_cache()
            eng.stats = {k: type(v)() for k, v in eng.stats.items()}

        tok_times: dict[int, list[float]] = {}

        def on_token(req, _tok, _tt=tok_times):
            _tt.setdefault(req.id, []).append(time.monotonic())

        handles = []
        placed: dict[int, int] = {}
        t_fail = None
        fired = False
        nxt = 0
        t0 = time.monotonic()
        while nxt < n or driver.has_work():
            now = time.monotonic() - t0
            while nxt < n and arrivals[nxt] <= now:
                h = driver.submit(prompts[nxt], int(news[nxt]),
                                  rng=keys[nxt], on_token=on_token)
                placed[h.id] = h.replica
                handles.append(h)
                nxt += 1
            if driver.has_work():
                if chaos and not fired and driver.steps >= kill_step:
                    fired = True
                    injure(victims)
                    # Let the heartbeat window lapse so the NEXT health
                    # sweep probes every worker and sees the whole host
                    # fail at once — detection through the supervision
                    # plane, as a real partition would be.
                    time.sleep(0.3)
                driver.step()
                if t_fail is None and router.replica_failures:
                    t_fail = time.monotonic()
            elif nxt < n:
                time.sleep(min(0.001, max(0.0, arrivals[nxt] - now)))
        wall = time.monotonic() - t0

        host_rejoined = None
        if chaos:
            # Heal the victim links and prove re-admission: the dial
            # probe reaches h0 again and lifts the quarantine
            # (host_joined). Non-partition injuries leave the listener
            # up, so h0 may have rejoined mid-replay already.
            for px in victims:
                px.heal()
            deadline = time.monotonic() + 15.0
            while ("h0" in spawner.dead_hosts
                   and time.monotonic() < deadline):
                router.poll_hosts()
                time.sleep(0.2)
            host_rejoined = "h0" not in spawner.dead_hosts
        driver.close()
        assert all(h.done for h in handles)

        migrated = [h for h in handles if h.replica != placed[h.id]]
        recovery = None
        if t_fail is not None and migrated:
            resumed = [min((t for t in tok_times.get(h.id, [])
                            if t > t_fail), default=None) for h in migrated]
            if all(r is not None for r in resumed):
                recovery = max(resumed) - t_fail
        emitted = sum(len(h.generated) for h in handles)
        rec = {
            "wall_s": round(wall, 4),
            "tok_s": round(emitted / wall, 1),
            "completed": sum(h.finish_reason in ("eos", "length")
                             for h in handles),
            "replica_failures": router.replica_failures,
            "migrated_streams": router.migrated,
            "watchdog_trips": driver.watchdog_trips,
            "timeouts": sum(h.finish_reason == "timeout" for h in handles),
            "failed_streams": sum(h.finish_reason == "failed"
                                  for h in handles),
            "re_emitted_tokens": sum(
                len(tok_times.get(h.id, [])) - len(h.generated)
                for h in handles
            ),
            "recovery_s": (round(recovery, 4) if recovery is not None
                           else None),
        }
        if spawner is not None:
            rec["worker_restarts"] = spawner.respawns
        if chaos:
            rec["host_failures"] = router.host_failures
            rec["hosts_active_after"] = spawner.hosts_active
            rec["host_rejoined"] = host_rejoined
        return rec, [list(h.generated) for h in handles]

    out = {
        "net": mode,
        "trace": meta,
        "replicas": args.replicas,
        "policy": args.route,
        "hosts": {"h0": args.replicas, "h1": args.replicas},
        "fire_at_step": kill_step,
        "serve": {"max_batch": serve.max_batch,
                  "block_size": serve.block_size,
                  "num_blocks": serve.num_blocks,
                  "prefill_chunk": serve.prefill_chunk,
                  "prefix_cache": serve.prefix_cache,
                  "admission": serve.admission},
        "worker": {"max_respawns": args.worker_max_respawns,
                   "respawn_backoff_s": 0.5,
                   "rpc_timeout_s": args.worker_rpc_timeout_s,
                   "heartbeat_s": 0.05,
                   "heartbeat_timeout_s": 1.0,
                   "authenticated": True},
    }
    for label, temp in (("greedy", 0.0), ("sampled", 1.0)):
        tmp = tempfile.mkdtemp(prefix="gpt2tpu-chaosnet-")
        procs, proxies, victims, token_path, direct, proxied = (
            start_fleet(temp, tmp))
        try:
            ref_rec, ref_streams = replay(temp, "inprocess")
            net_rec, net_streams = replay(
                temp, "remote", pool=direct, token_path=token_path)
            chaos_rec, chaos_streams = replay(
                temp, "remote", pool=proxied, token_path=token_path,
                victims=victims)
        finally:
            for px in proxies:
                px.close()
            for pr in procs:
                pr.terminate()
            for pr in procs:
                try:
                    pr.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pr.kill()
            shutil.rmtree(tmp, ignore_errors=True)
        out[label] = {
            "inprocess": ref_rec,
            "remote": net_rec,
            "remote_chaos": chaos_rec,
            "streams_bit_identical": (net_streams == ref_streams
                                      and chaos_streams == ref_streams),
        }
    g = out["greedy"]
    out["rpc_overhead"] = {
        "inprocess_tok_s": g["inprocess"]["tok_s"],
        "remote_tok_s": g["remote"]["tok_s"],
        # Per-token cost of the TCP hop vs the in-process fleet; §20
        # compares this against chaos_proc's Unix-socket number to price
        # TCP framing + loopback specifically.
        "per_token_overhead_us": round(
            (1.0 / g["remote"]["tok_s"]
             - 1.0 / g["inprocess"]["tok_s"]) * 1e6, 1),
    }
    return out


def main(argv=None) -> None:
    p = build_argparser()
    args = p.parse_args(argv)
    validate_args(p, args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from gpt_2_distributed_tpu.config import MODEL_PRESETS, ServeConfig
    from gpt_2_distributed_tpu.models import gpt2
    from gpt_2_distributed_tpu.models.decode import generate_cached
    from gpt_2_distributed_tpu.obs.trace import (
        XlaCapture,
        configure_tracing,
        get_tracer,
        parse_profile_at,
    )
    from gpt_2_distributed_tpu.serving import ServingEngine

    if args.serve_mesh:
        from gpt_2_distributed_tpu.config import parse_serve_mesh

        _dp, _tp = parse_serve_mesh(args.serve_mesh)
        need = _dp * _tp
        if (jax.device_count() < need and not
                (os.environ.get("JAX_PLATFORMS") or "").startswith("cpu")):
            # Never by itself: a run meant for chips that finds too few
            # must not come back with CPU numbers under device names.
            p.error(f"--serve_mesh {args.serve_mesh!r} needs {need} "
                    f"devices, found {jax.device_count()} "
                    f"({jax.devices()[0].platform}); set JAX_PLATFORMS=cpu "
                    f"to rehearse on forced virtual CPU devices")
        if (jax.device_count() < need
                and os.environ.get("_BENCH_SERVE_FORCED") != "1"):
            # Too few CPU devices, and the CPU was asked for: re-exec
            # against the forced virtual CPU platform (the test suite's
            # conftest pattern) so the sharded and single-device engines
            # run in ONE process and the stream comparison is
            # apples-to-apples. highest matmul precision pins both engines
            # to the same fp32 reductions the parity tests use.
            import re
            import subprocess

            env = dict(os.environ, _BENCH_SERVE_FORCED="1",
                       JAX_PLATFORMS="cpu",
                       JAX_DEFAULT_MATMUL_PRECISION="highest")
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "",
                env.get("XLA_FLAGS", ""),
            ).strip()
            env["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={need}"
            ).strip()
            sys.exit(subprocess.call(
                [sys.executable, os.path.abspath(__file__),
                 *(argv if argv is not None else sys.argv[1:])], env=env,
            ))
        if jax.device_count() < need:
            p.error(f"--serve_mesh {args.serve_mesh!r} needs {need} "
                    f"devices; the forced re-exec still sees only "
                    f"{jax.device_count()}")

    global _XLA_CAPTURE
    if args.trace_dir:
        configure_tracing(args.trace_dir)
    _XLA_CAPTURE = XlaCapture(parse_profile_at(args.xla_profile_at),
                              args.trace_dir)

    overrides = {
        k: getattr(args, k)
        for k in ("n_layer", "n_embd", "n_head", "vocab_size")
        if getattr(args, k) is not None
    }
    if args.seq_len is not None:
        overrides["n_positions"] = args.seq_len
    config = MODEL_PRESETS[args.model].replace(**overrides)
    longest = max(args.prompt_max,
                  args.shared_prefix_len + 1
                  if args.traces != "original" else 0)
    if longest + args.new_max > config.n_positions:
        p.error(
            f"longest possible prompt ({longest}) + --new_max "
            f"{args.new_max} exceeds n_positions {config.n_positions}; "
            f"shrink the trace or raise --seq_len"
        )

    serve_probe = ServeConfig(max_batch=args.max_batch,
                              block_size=args.block_size)
    full_pool = 1 + args.max_batch * serve_probe.max_blocks_per_seq(
        config.n_positions
    )

    def serve_pair(num_blocks):
        """(engine-under-test, PR 7 features-off replay) at one pool size."""
        base = dict(max_batch=args.max_batch, block_size=args.block_size,
                    num_blocks=num_blocks or full_pool,
                    attn_impl=args.attn_impl)
        new = ServeConfig(
            **base, prefill_chunk=args.prefill_chunk,
            prefix_cache=args.prefix_cache == "on",
            admission=args.admission, watermark_blocks=args.watermark_blocks,
            prefill_batch=args.prefill_batch,
        )
        return new, ServeConfig(**base)

    params = gpt2.init_params(config)

    def make_engine(serve):
        return ServingEngine(params, config, serve,
                             temperature=args.temperature, top_k=args.top_k)

    if args.serve_mesh:
        rec = run_sharded(args, params, config, jax, np, make_engine)
        _XLA_CAPTURE.stop_if_active()
        get_tracer().close()
        if args.json:
            out = {"bench": "serve",
                   "device": jax.devices()[0].device_kind,
                   "n_devices": jax.device_count(),
                   "model": {"preset": args.model, **overrides}}
            if os.path.exists(args.json):
                with open(args.json) as f:
                    out = json.load(f)
            out["sharded"] = rec
            with open(args.json, "w") as f:
                json.dump(out, f, indent=1)
                f.write("\n")
        print(json.dumps({"sharded": rec}))
        if not rec["streams_bit_identical"]:
            sys.exit("sharded: token streams diverged between the single-"
                     "device and mesh-sharded engines — sharding broke "
                     "bit-exactness")
        return

    if args.spec:
        rec = run_spec(args, params, config, jax, np)
        _XLA_CAPTURE.stop_if_active()
        get_tracer().close()
        if args.json:
            out = {"bench": "serve",
                   "device": jax.devices()[0].device_kind,
                   "n_devices": jax.device_count(),
                   "model": {"preset": args.model, **overrides}}
            if os.path.exists(args.json):
                with open(args.json) as f:
                    out = json.load(f)
            out["spec"] = rec
            with open(args.json, "w") as f:
                json.dump(out, f, indent=1)
                f.write("\n")
        print(json.dumps({"spec": rec}))
        for name, sec in rec["traces"].items():
            if not sec["streams_bit_identical"]:
                sys.exit(f"spec[{name}]: token streams diverged between "
                         "the speculative and plain engines — greedy "
                         "speculation must be exact")
        return

    if args.chaos and (args.fail_spec is None and args.hang_spec is None
                       and args.inject_step_exception is None):
        # Default chaos kill: replica 0, mid-run on the default trace.
        args.fail_spec = (20, 0)
        args.inject_replica_fail_at = "20:0"

    def make_inj():
        """Fresh injector per measured run (an injector fires once)."""
        from gpt_2_distributed_tpu.resilience import FaultInjector

        if (args.fail_spec is None and args.hang_spec is None
                and args.inject_step_exception is None):
            return None
        return FaultInjector(fail_at=args.fail_spec,
                             hang_at=args.hang_spec,
                             exception_at=args.inject_step_exception)

    if args.chaos and args.chaos_net is not None:
        serve_new, _ = serve_pair(
            args.num_blocks_shared or args.num_blocks
            if args.traces != "original" else args.num_blocks
        )
        rec = run_chaos_net(args, params, config, serve_new, jax, np)
        _XLA_CAPTURE.stop_if_active()
        get_tracer().close()
        if args.json:
            out = {"bench": "serve",
                   "device": jax.devices()[0].device_kind,
                   "n_devices": jax.device_count(),
                   "model": {"preset": args.model, **overrides}}
            if os.path.exists(args.json):
                with open(args.json) as f:
                    out = json.load(f)
            # Keyed by injury mode: one invocation per --chaos_net,
            # records accumulate in the same file.
            out.setdefault("chaos_net", {})[args.chaos_net] = rec
            with open(args.json, "w") as f:
                json.dump(out, f, indent=1)
                f.write("\n")
        print(json.dumps({"chaos_net": {args.chaos_net: rec}}))
        for mode in ("greedy", "sampled"):
            krec = rec[mode]["remote_chaos"]
            if krec["host_failures"] == 0:
                sys.exit(f"chaos_net[{mode}]: the {args.chaos_net} injury "
                         "never took the host down — either the run "
                         "finished before its trigger step or the failure "
                         "was not contained as a host domain")
            if not rec[mode]["streams_bit_identical"]:
                sys.exit(f"chaos_net[{mode}]: token streams diverged from "
                         "the in-process reference — the TCP boundary or "
                         "the host-death migration broke bit-exactness")
            if krec["re_emitted_tokens"] != 0:
                sys.exit(f"chaos_net[{mode}]: "
                         f"{krec['re_emitted_tokens']} token(s) were "
                         "re-emitted across the host migration — the "
                         "zero-re-emission contract is broken")
            if krec["failed_streams"] != 0:
                sys.exit(f"chaos_net[{mode}]: {krec['failed_streams']} "
                         "stream(s) died with the host instead of "
                         "migrating — containment is incomplete")
        return

    if args.chaos and args.placement == "subprocess":
        serve_new, _ = serve_pair(
            args.num_blocks_shared or args.num_blocks
            if args.traces != "original" else args.num_blocks
        )
        rec = run_chaos_proc(args, params, config, serve_new, jax, np)
        _XLA_CAPTURE.stop_if_active()
        get_tracer().close()
        if args.json:
            out = {"bench": "serve",
                   "device": jax.devices()[0].device_kind,
                   "n_devices": jax.device_count(),
                   "model": {"preset": args.model, **overrides}}
            if os.path.exists(args.json):
                with open(args.json) as f:
                    out = json.load(f)
            # Keyed by kill mechanism: one invocation per --chaos_kill,
            # records accumulate in the same file.
            out.setdefault("chaos_proc", {})[args.chaos_kill] = rec
            with open(args.json, "w") as f:
                json.dump(out, f, indent=1)
                f.write("\n")
        print(json.dumps({"chaos_proc": {args.chaos_kill: rec}}))
        for mode in ("greedy", "sampled"):
            krec = rec[mode]["subprocess_kill"]
            if krec["replica_failures"] == 0:
                sys.exit(f"chaos_proc[{mode}]: the {args.chaos_kill} kill "
                         "never fired — the run finished before its "
                         "trigger step; lower --inject_replica_fail_at")
            if not rec[mode]["streams_bit_identical"]:
                sys.exit(f"chaos_proc[{mode}]: token streams diverged "
                         "from the in-process reference — the process "
                         "boundary broke bit-exactness")
            if krec["re_emitted_tokens"] != 0:
                sys.exit(f"chaos_proc[{mode}]: "
                         f"{krec['re_emitted_tokens']} token(s) were "
                         "re-emitted across the migration — the "
                         "zero-re-emission contract is broken")
        return

    if args.chaos:
        serve_new, _ = serve_pair(
            args.num_blocks_shared or args.num_blocks
            if args.traces != "original" else args.num_blocks
        )
        rec = run_chaos(args, config, serve_new, jax, np, make_engine,
                        make_inj)
        _XLA_CAPTURE.stop_if_active()
        get_tracer().close()
        if args.json:
            out = {"bench": "serve",
                   "device": jax.devices()[0].device_kind,
                   "n_devices": jax.device_count(),
                   "model": {"preset": args.model, **overrides}}
            if os.path.exists(args.json):
                with open(args.json) as f:
                    out = json.load(f)
            out["chaos"] = rec
            with open(args.json, "w") as f:
                json.dump(out, f, indent=1)
                f.write("\n")
        print(json.dumps({"chaos": rec}))
        if rec["chaos"]["replica_failures"] == 0:
            sys.exit("chaos: the injected fault never fired — the run "
                     "finished before its trigger step; lower "
                     "--inject_replica_fail_at")
        if not rec["chaos"]["streams_bit_identical"]:
            sys.exit("chaos: token streams diverged from the unfailed "
                     "reference replay — migration broke bit-exactness")
        return

    if args.duration > 0:
        # Front-door mode: measured run under --route, plus a round_robin
        # control on the same seed — the affinity-vs-spray comparison the
        # router exists for. Merges into an existing --json file so the
        # closed-trace records survive.
        serve_new, _ = serve_pair(args.num_blocks)
        rec = {
            "duration_s": args.duration,
            "rate_req_s": [args.rate,
                           args.ramp if args.ramp is not None else args.rate],
            "replicas": args.replicas,
            "max_replicas": args.max_replicas or args.replicas,
            "ttft_slo_ms": args.ttft_slo_ms,
            "queue_slo_ms": args.queue_slo_ms,
            "shared_prefix_frac": args.shared_prefix_frac,
            "shared_prefix_len": args.shared_prefix_len,
            "serve": {"max_batch": serve_new.max_batch,
                      "block_size": serve_new.block_size,
                      "num_blocks": serve_new.num_blocks,
                      "prefix_cache": serve_new.prefix_cache,
                      "admission": serve_new.admission},
            args.route: run_frontend(args, config, serve_new, jax, np,
                                     make_engine, args.route,
                                     injector=make_inj()),
        }
        if args.route != "round_robin":
            rec["round_robin_control"] = run_frontend(
                args, config, serve_new, jax, np, make_engine,
                "round_robin", injector=make_inj(),
            )
        _XLA_CAPTURE.stop_if_active()
        get_tracer().close()
        if args.json:
            out = {"bench": "serve",
                   "device": jax.devices()[0].device_kind,
                   "n_devices": jax.device_count(),
                   "model": {"preset": args.model, **overrides}}
            if os.path.exists(args.json):
                with open(args.json) as f:
                    out = json.load(f)
            out["frontend"] = rec
            with open(args.json, "w") as f:
                json.dump(out, f, indent=1)
                f.write("\n")
        print(json.dumps({"frontend": rec}))
        return

    result = {
        "bench": "serve",
        "device": jax.devices()[0].device_kind,
        "n_devices": jax.device_count(),
        "model": {"preset": args.model, **overrides},
        "temperature": args.temperature,
        "top_k": args.top_k,
        "traces": {},
    }

    names = (["original", "shared_prefix"] if args.traces == "both"
             else [args.traces])
    for name in names:
        shared = name == "shared_prefix"
        serve_new, serve_pr7 = serve_pair(
            args.num_blocks_shared or args.num_blocks if shared
            else args.num_blocks
        )
        trace = make_trace(args, np, config.vocab_size, shared=shared)
        arrivals, prompts, news, meta = trace
        sec = {
            "trace": meta,
            "serve": {"max_batch": serve_new.max_batch,
                      "block_size": serve_new.block_size,
                      "num_blocks": serve_new.num_blocks,
                      "attn_impl": serve_new.attn_impl,
                      "prefill_chunk": serve_new.prefill_chunk,
                      "prefix_cache": serve_new.prefix_cache,
                      "admission": serve_new.admission,
                      "watermark_blocks": serve_new.watermark_blocks},
        }

        if not args.baseline_only:
            sec["engine"], streams_new = run_engine(
                args, params, config, serve_new, trace, jax, np, make_engine
            )
            if not args.no_pr7:
                sec["engine_pr7"], streams_pr7 = run_engine(
                    args, params, config, serve_pr7, trace, jax, np,
                    make_engine,
                )
                sec["streams_bit_identical"] = streams_new == streams_pr7
                sec["speedup_vs_pr7"] = round(
                    sec["engine"]["tok_s"] / sec["engine_pr7"]["tok_s"], 2
                )

        # One-shot baseline: same requests, served serially.
        if not args.no_baseline:
            keys = [jax.random.PRNGKey(args.trace_seed * 100_000 + i)
                    for i in range(len(prompts))]
            shapes = sorted({(len(pr), int(nw))
                             for pr, nw in zip(prompts, news)})
            for pl, nw in shapes:  # compile warmup, excluded from timing
                generate_cached(
                    params, config, jnp.asarray([[1] * pl], jnp.int32),
                    jax.random.PRNGKey(0), max_new_tokens=nw,
                    temperature=args.temperature, top_k=args.top_k,
                ).block_until_ready()
            base_wall = None
            for _ in range(args.repeats):
                t0 = time.monotonic()
                for pr, nw, key in zip(prompts, news, keys):
                    generate_cached(
                        params, config, jnp.asarray([pr], jnp.int32), key,
                        max_new_tokens=int(nw), temperature=args.temperature,
                        top_k=args.top_k,
                    ).block_until_ready()
                wall = time.monotonic() - t0
                base_wall = wall if base_wall is None else min(base_wall, wall)
            total_new = meta["total_new_tokens"]
            sec["oneshot_baseline"] = {
                "wall_s": round(base_wall, 4),
                "tok_s": round(total_new / base_wall, 1),
                "tok_s_per_chip": round(
                    total_new / base_wall / jax.device_count(), 1
                ),
                "distinct_shapes_warmed": len(shapes),
            }
            if "engine" in sec:
                sec["speedup_vs_oneshot"] = round(
                    sec["engine"]["tok_s"]
                    / sec["oneshot_baseline"]["tok_s"], 2
                )
        result["traces"][name] = sec

    _XLA_CAPTURE.stop_if_active()
    get_tracer().close()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
