"""Serving CLI: run a batch of requests through the continuous-batching
engine, streaming results as JSON lines.

Offline-first by design (no server socket — for the network front door see
``gpt2-tpu-frontend``, which wraps the same engine-driver in an HTTP/SSE
server): requests come from a JSONL file or stdin, one object per line::

    {"prompt_ids": [464, 3616], "new": 64, "seed": 7}
    {"prompt": "The meaning of life", "new": 32}

``prompt`` needs tiktoken's GPT-2 BPE (network-gated); ``prompt_ids`` works
fully offline. Per-line fields default to --new / --seed. Output is JSONL
on stdout: with ``--stream`` a ``{"id", "token"}`` line per token as it is
produced, and always a final ``{"id", ..., "generated", "ttft_ms",
"queue_wait_ms", "preempted", "prefix_cached_tokens", "finish_reason"}``
record per request. All requests are in flight together up to
``--max_batch`` — submission order is admission order (FIFO), but
completions interleave.

Scheduler knobs pass straight through to ``ServeConfig``:
``--prefill_chunk N`` interleaves N-token prompt chunks with decode steps,
``--prefix_cache`` reuses KV blocks across requests sharing a prompt
prefix, and ``--admission watermark`` (with ``--watermark_blocks``)
switches from worst-case block reservation to lazy growth with
preempt-and-recompute under pool pressure. ``--tb_dir`` streams serving
load (queue depth/wait, occupancy, preemptions, prefix hits) to
TensorBoard through the shared StatsTracker every ``--metrics_every``
engine steps.

The step loop itself lives in ``serving/frontend/driver.py`` — ONE
submit/step/drain loop shared with the HTTP front end, so the two entry
points cannot drift. SIGTERM drains: in-flight requests run to
completion (reusing the resilience preemption flag), then the process
exits 0 — kill -9 is the only way to drop a stream.

Usage::

    gpt2-tpu-serve --ckpt runs/ckpt --requests reqs.jsonl --stream
    echo '{"prompt_ids": [1,2,3], "new": 8}' | gpt2-tpu-serve \
        --ckpt runs/ckpt --requests -

``--init_random`` swaps the checkpoint for seeded-init weights (smoke tests
and benchmarking the serving path without training first).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def add_model_flags(p: argparse.ArgumentParser) -> None:
    """Model/checkpoint selection flags, shared verbatim with
    ``gpt2-tpu-frontend`` (serving/frontend/server.py)."""
    from gpt_2_distributed_tpu.config import FAMILY_MODELS, MODEL_PRESETS

    p.add_argument("--ckpt", default=None,
                   help="checkpoint dir (step_NNNNNNN) or save dir (latest)")
    p.add_argument("--init_random", action="store_true",
                   help="serve seeded-init weights instead of a checkpoint")
    p.add_argument("--model", default="124M",
                   choices=sorted(MODEL_PRESETS) + list(FAMILY_MODELS))
    p.add_argument("--n_layer", type=int, default=None)
    p.add_argument("--first_layer", type=int, default=0,
                   help="with --n_layer and a layer-pattern model "
                        "(minicpm-sala-*, nemotron*, jamba*, kanana2*): serve --n_layer "
                        "consecutive layers of the preset's stack from this "
                        "one on (minicpm-sala-9b: 16 from 9 keeps the "
                        "published ratio of kinds)")
    p.add_argument("--n_embd", type=int, default=None)
    p.add_argument("--n_head", type=int, default=None)
    p.add_argument("--vocab_size", type=int, default=None)
    p.add_argument("--seq_len", type=int, default=None)


def add_engine_flags(p: argparse.ArgumentParser) -> None:
    """ServeConfig + sampling flags, shared with the front end."""
    p.add_argument("--new", type=int, default=64,
                   help="default max_new_tokens for requests without one")
    p.add_argument("--seed", type=int, default=0,
                   help="default sampling seed for requests without one")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top_k", type=int, default=None)
    p.add_argument("--eos", type=int, default=None,
                   help="token id that finishes a request early")
    p.add_argument("--max_batch", type=int, default=8)
    p.add_argument("--block_size", type=int, default=16)
    p.add_argument("--num_blocks", type=int, default=0,
                   help="KV pool blocks; 0 = max_batch worst-case sequences")
    p.add_argument("--max_seq_len", type=int, default=0,
                   help="longest prompt + output a request may have; sets the "
                        "block table's width (0 = the model's positions)")
    p.add_argument("--attn_impl", default="auto",
                   choices=["auto", "xla", "pallas"])
    p.add_argument("--prefill_chunk", type=int, default=0,
                   help="prefill chunk width; 0 = whole-prompt prefill")
    p.add_argument("--prefill_batch", type=int, default=1,
                   help="chunked mode: in-progress prefills advanced per "
                        "engine step, in ONE batched dispatch")
    p.add_argument("--serve_mesh", default="",
                   help="serving mesh spec 'data:N[,tp:M]' — shard the KV "
                        "pool and decode rows over N data shards and the "
                        "attention heads over M tp shards (streams stay "
                        "bit-identical to single-device)")
    p.add_argument("--prefix_cache", action="store_true",
                   help="reuse KV blocks across shared prompt prefixes")
    p.add_argument("--admission", default="reserve",
                   choices=["reserve", "watermark"],
                   help="block grant policy: worst-case reservation, or "
                   "lazy growth with preemption under pool pressure")
    p.add_argument("--watermark_blocks", type=int, default=1,
                   help="free-block floor for --admission watermark")
    p.add_argument("--draft_preset", default=None,
                   help="speculative decoding: draft-model preset (must be "
                        "smaller than --model); greedy streams stay "
                        "bit-identical, sampled streams stay "
                        "target-distributed")
    p.add_argument("--spec_k", type=int, default=None,
                   help="draft tokens per verify pass (default 4; needs "
                        "--draft_preset)")
    p.add_argument("--draft_ckpt", default=None,
                   help="draft-model checkpoint dir; seeded init when "
                        "omitted (a random draft is correct, just "
                        "rarely accepted)")


def add_obs_flags(p: argparse.ArgumentParser) -> None:
    """Metrics/tracing/profiling flags, shared with the front end."""
    p.add_argument("--tb_dir", default=None,
                   help="TensorBoard dir for serving-load metrics")
    p.add_argument("--metrics_every", type=int, default=20,
                   help="engine steps between --tb_dir metric flushes")
    p.add_argument("--trace_dir", default=None,
                   help="write span/event trace JSONL here (obs/trace.py)")
    p.add_argument("--trace_max_file_bytes", type=int, default=64 * 1024 * 1024,
                   help="rotate trace-p*.jsonl past this size")
    p.add_argument("--xla_profile_at", default=None, metavar="STEP[:NSTEPS]",
                   help="capture an XLA profiler trace covering NSTEPS "
                        "(default 1) engine steps starting at STEP; written "
                        "under --trace_dir (or --tb_dir)/xla_profile")
    p.add_argument("--device", default=None,
                   help="jax platform override (cpu|tpu)")


def add_placement_flags(p: argparse.ArgumentParser) -> None:
    """Replica placement + worker supervision flags, shared by the JSONL
    CLI and the HTTP front end. Validated jax-free via
    ``config.validate_worker_flags``."""
    from gpt_2_distributed_tpu.config import PLACEMENTS

    p.add_argument("--placement", default="inprocess",
                   choices=list(PLACEMENTS),
                   help="replica placement: engines inside this process "
                        "(default), one worker process per replica behind "
                        "the RPC supervision plane, or remote workers "
                        "adopted over authenticated TCP from a "
                        "--worker_pool fleet")
    p.add_argument("--worker_max_respawns", type=int, default=3,
                   help="replacement workers spawned after failures before "
                        "the fleet degrades loudly (supervise.sh "
                        "MAX_RESTARTS semantics)")
    p.add_argument("--worker_respawn_backoff_s", type=float, default=2.0,
                   help="base respawn backoff; doubles per respawn "
                        "(supervise.sh RESTART_DELAY semantics)")
    p.add_argument("--worker_rpc_timeout_s", type=float, default=300.0,
                   help="per-RPC reply deadline; a worker that blows it "
                        "is failed and its requests migrated (generous "
                        "default: cold XLA compiles ride the step RPC)")
    p.add_argument("--worker_heartbeat_s", type=float, default=1.0,
                   help="idle gap after which the driver heartbeats a "
                        "worker; heartbeat loss fails the replica")
    p.add_argument("--worker_connect_timeout_s", type=float, default=120.0,
                   help="worker spawn-to-hello deadline (covers the "
                        "child's jax import + engine build)")
    p.add_argument("--worker_heartbeat_timeout_s", type=float, default=None,
                   help="per-attempt heartbeat reply deadline; default "
                        "derives max(5 x --worker_heartbeat_s, 2.0) — set "
                        "explicitly for cross-host fleets, where the "
                        "heartbeat budget should not be derived from the "
                        "local-socket cadence")
    p.add_argument("--worker_auth_token_file", default=None,
                   help="shared-secret file for the worker hello's mutual "
                        "HMAC challenge-response; unauthenticated or "
                        "wrong-token peers are refused before any engine "
                        "state moves (give workers the same file via "
                        "--auth_token_file)")
    p.add_argument("--worker_pool", default=None,
                   help="--placement remote: file of 'host_id address' "
                        "lines naming the worker fleet (workers append "
                        "themselves with gpt2-tpu-worker --advertise)")


def add_fault_flags(p: argparse.ArgumentParser) -> None:
    """Fault-tolerance + fault-injection flags, shared with the front end."""
    p.add_argument("--request_timeout_s", type=float, default=None,
                   help="per-request deadline from submission (queue wait "
                        "included); overdue requests are evicted with "
                        "finish reason 'timeout' (HTTP 504 on the front "
                        "end) and their KV blocks freed")
    p.add_argument("--watchdog_timeout_s", type=float, default=None,
                   help="fail a replica whose single step() exceeds this "
                        "(stacks + open trace spans dumped, in-flight "
                        "requests migrated to healthy replicas)")
    p.add_argument("--inject_replica_fail_at", default=None,
                   metavar="STEP[:REPLICA]",
                   help="fault injection: raise inside the given replica's "
                        "step (default replica 0) at fleet step STEP")
    p.add_argument("--inject_replica_hang_at", default=None,
                   metavar="STEP[:REPLICA]",
                   help="fault injection: hang the given replica's step at "
                        "fleet step STEP until the watchdog trips")
    p.add_argument("--inject_step_exception", type=int, default=None,
                   metavar="STEP",
                   help="fault injection: raise in whichever replica steps "
                        "first at fleet step STEP")


def make_injector(p: argparse.ArgumentParser, args: argparse.Namespace):
    """Validate the fault flags; return a :class:`resilience.FaultInjector`
    or None when no injection was asked for. Import-light (no jax): a bad
    fault flag is refused before jax loads."""
    from gpt_2_distributed_tpu.resilience import (
        FaultInjector,
        parse_fault_spec,
    )

    if args.request_timeout_s is not None and args.request_timeout_s < 0:
        p.error(f"--request_timeout_s={args.request_timeout_s} must be >= 0")
    if args.watchdog_timeout_s is not None and args.watchdog_timeout_s <= 0:
        p.error(f"--watchdog_timeout_s={args.watchdog_timeout_s} "
                f"must be > 0")
    try:
        fail_at = (parse_fault_spec(args.inject_replica_fail_at,
                                    "--inject_replica_fail_at")
                   if args.inject_replica_fail_at else None)
        hang_at = (parse_fault_spec(args.inject_replica_hang_at,
                                    "--inject_replica_hang_at")
                   if args.inject_replica_hang_at else None)
    except ValueError as e:
        p.error(str(e))
    exc_at = args.inject_step_exception
    if exc_at is not None and exc_at < 1:
        p.error(f"--inject_step_exception={exc_at} must be >= 1")
    if hang_at is not None and args.watchdog_timeout_s is None:
        p.error("--inject_replica_hang_at needs --watchdog_timeout_s "
                "(nothing else ever detects the hang)")
    if fail_at is None and hang_at is None and exc_at is None:
        return None
    return FaultInjector(fail_at=fail_at, hang_at=hang_at,
                         exception_at=exc_at)


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    add_model_flags(p)
    p.add_argument("--requests", required=True,
                   help="JSONL request file, or '-' for stdin")
    add_engine_flags(p)
    p.add_argument("--stream", action="store_true",
                   help="emit a JSON line per token as it is generated")
    add_obs_flags(p)
    add_placement_flags(p)
    add_fault_flags(p)
    return p


def setup_observability(p: argparse.ArgumentParser, args: argparse.Namespace):
    """Tracing + XLA-capture wiring shared by serve and the front end.
    Returns the armed :class:`XlaCapture` (inert when unconfigured)."""
    from gpt_2_distributed_tpu.obs.trace import (
        XlaCapture,
        configure_tracing,
        parse_profile_at,
    )

    if args.trace_dir:
        configure_tracing(args.trace_dir,
                          max_file_bytes=args.trace_max_file_bytes)
    try:
        xla_profile_spec = parse_profile_at(args.xla_profile_at)
    except ValueError as e:
        p.error(str(e))
    profile_root = args.trace_dir or args.tb_dir
    if xla_profile_spec and not profile_root:
        p.error("--xla_profile_at needs --trace_dir or --tb_dir for output")
    return XlaCapture(xla_profile_spec, profile_root)


def model_config_from_args(args: argparse.Namespace):
    """GPT2Config from --model + overrides, WITHOUT touching params or
    jax — subprocess placement needs the config (pool sizing, prompt
    validation) while the weights load only inside the workers."""
    from gpt_2_distributed_tpu.config import (
        MODEL_PRESETS,
        family_config_from_flags,
        family_flags_of,
    )

    if family_flags_of(args.model) is not None:
        return family_config_from_flags(args)
    overrides = {
        k: getattr(args, k)
        for k in ("n_layer", "n_embd", "n_head", "vocab_size")
        if getattr(args, k) is not None
    }
    if args.seq_len is not None:
        overrides["n_positions"] = args.seq_len
    return MODEL_PRESETS[args.model].replace(**overrides)


def load_model(args: argparse.Namespace):
    """(config, params) from --model overrides + checkpoint/--init_random,
    the params as an engine holds them (``gpt2.serving_weights`` at the
    engine's dtype: matmul and embedding leaves cast once, LayerNorm float32),
    so that no float32 copy outlives this call beside the engine's tree.
    Call after the jax platform is pinned."""
    import jax

    from gpt_2_distributed_tpu.checkpoint import latest_checkpoint, restore_params
    from gpt_2_distributed_tpu.models import gpt2
    from gpt_2_distributed_tpu.serving.engine import DEFAULT_COMPUTE_DTYPE
    from gpt_2_distributed_tpu.utils.device_info import device_banner

    config = model_config_from_args(args)
    # Every process that loads weights says what it loads them onto (the
    # JSONL CLI, the front door in-process, each worker): rc 0 alone does
    # not tell a chip from the CPU JAX falls back to.
    print(device_banner(), file=sys.stderr, flush=True)

    from gpt_2_distributed_tpu.serving.families import family_of

    family = family_of(config)
    if family is not None:
        # --init_random, by `validate_model_flags`: bfloat16, held as such.
        return config, family.init_params(
            config, jax.random.PRNGKey(getattr(args, "seed", 0)))
    if args.init_random:
        params = gpt2.init_params(config)
    else:
        path = os.path.abspath(args.ckpt)  # orbax rejects relative paths
        if not os.path.exists(os.path.join(path, "meta.json")):
            latest = latest_checkpoint(path)
            if latest is None:
                sys.exit(f"no checkpoint found under {path!r}")
            path = latest
        template = jax.eval_shape(lambda: gpt2.init_params(config))
        one_device = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        shardings = jax.tree_util.tree_map(lambda _: one_device, template)
        params, meta = restore_params(path, template, shardings)
        print(f"checkpoint: {path} (step {meta.step})", file=sys.stderr)
    return config, gpt2.serving_weights(params, DEFAULT_COMPUTE_DTYPE)


def load_draft_model(args: argparse.Namespace, config):
    """(draft_config, draft_params) from ``--draft_preset``, or
    ``(None, None)`` when speculation is off. The draft inherits the
    target's vocab and context window (acceptance compares distributions
    over one token space; the draft re-encodes the full committed
    prefix), keeping the preset's depth/width. Weights come from
    ``--draft_ckpt`` when given, seeded init otherwise — a random draft
    is still *correct* (verification guarantees the output distribution),
    it just accepts little. Returned as ``load_model`` returns the target.
    Call after the jax platform is pinned."""
    draft = getattr(args, "draft_preset", None)
    if draft is None:
        return None, None
    from gpt_2_distributed_tpu.config import MODEL_PRESETS
    from gpt_2_distributed_tpu.models import gpt2
    from gpt_2_distributed_tpu.serving.engine import DEFAULT_COMPUTE_DTYPE

    draft_config = MODEL_PRESETS[draft].replace(
        vocab_size=config.vocab_size, n_positions=config.n_positions
    )
    ckpt = getattr(args, "draft_ckpt", None)
    if ckpt:
        import jax

        from gpt_2_distributed_tpu.checkpoint import (
            latest_checkpoint,
            restore_params,
        )

        path = os.path.abspath(ckpt)
        if not os.path.exists(os.path.join(path, "meta.json")):
            latest = latest_checkpoint(path)
            if latest is None:
                sys.exit(f"no draft checkpoint found under {path!r}")
            path = latest
        template = jax.eval_shape(lambda: gpt2.init_params(draft_config))
        one_device = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        shardings = jax.tree_util.tree_map(lambda _: one_device, template)
        draft_params, meta = restore_params(path, template, shardings)
        print(f"draft checkpoint: {path} (step {meta.step})",
              file=sys.stderr)
    else:
        draft_params = gpt2.init_params(draft_config)
    return draft_config, gpt2.serving_weights(draft_params, DEFAULT_COMPUTE_DTYPE)


def build_serve_config(args: argparse.Namespace, config):
    """ServeConfig from the shared engine flags (0 blocks = worst case)."""
    from gpt_2_distributed_tpu.config import ServeConfig

    mesh = getattr(args, "serve_mesh", "") or ""
    num_blocks = args.num_blocks
    max_seq_len = getattr(args, "max_seq_len", 0)
    probe = ServeConfig(max_batch=args.max_batch, block_size=args.block_size,
                        max_seq_len=max_seq_len)
    if num_blocks == 0:
        num_blocks = 1 + args.max_batch * probe.max_blocks_per_seq(
            config.n_positions
        )
        if mesh:
            # Sharded pool: the block count must split evenly over 'data'.
            from gpt_2_distributed_tpu.config import parse_serve_mesh

            data, _ = parse_serve_mesh(mesh)
            num_blocks = -(-num_blocks // data) * data
    draft = getattr(args, "draft_preset", None)
    spec = f"draft:{draft},k:{getattr(args, 'spec_k', None) or 4}" \
        if draft else ""
    return ServeConfig(
        max_batch=args.max_batch, block_size=args.block_size,
        num_blocks=num_blocks, attn_impl=args.attn_impl, eos_id=args.eos,
        prefill_chunk=args.prefill_chunk, prefix_cache=args.prefix_cache,
        admission=args.admission, watermark_blocks=args.watermark_blocks,
        mesh=mesh, prefill_batch=getattr(args, "prefill_batch", 1),
        spec=spec, max_seq_len=max_seq_len,
    )


def make_tracker(args: argparse.Namespace):
    """The --tb_dir serving sink, or None."""
    if not args.tb_dir:
        return None
    from gpt_2_distributed_tpu.metrics.tracker import StatsTracker

    # batch/seq 0: the serving sink never counts training tokens —
    # every update is out-of-band (count_tokens=False), TB-only.
    return StatsTracker(
        args.tb_dir, batch_size=0, seq_len=0,
        print_fn=lambda s: print(s, file=sys.stderr),
    )


DRAIN_NOTICE = ("draining: in-flight requests will complete, new submits "
                "are refused, then exit 0")


def main(argv: list[str] | None = None) -> None:
    p = build_argparser()
    args = p.parse_args(argv)
    if (args.ckpt is None) == (not args.init_random):
        p.error("exactly one of --ckpt / --init_random is required")
    from gpt_2_distributed_tpu.config import validate_worker_flags

    validate_worker_flags(p, args)
    injector = make_injector(p, args)
    from gpt_2_distributed_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    if args.device:
        os.environ["JAX_PLATFORMS"] = args.device

    from gpt_2_distributed_tpu.obs.trace import get_tracer
    from gpt_2_distributed_tpu.resilience import PreemptionHandler
    from gpt_2_distributed_tpu.serving.frontend.driver import EngineDriver
    from gpt_2_distributed_tpu.serving.frontend.router import ReplicaRouter

    xla_capture = setup_observability(p, args)
    if args.placement in ("subprocess", "remote"):
        # The frontend stays off the device: weights load inside the
        # worker processes; the parent only needs the model SHAPE for
        # pool sizing and prompt validation.
        config = model_config_from_args(args)
        params = None
    else:
        config, params = load_model(args)

    lines = (sys.stdin if args.requests == "-"
             else open(args.requests, encoding="utf-8"))
    specs = []
    enc = None
    with lines:
        for ln, line in enumerate(lines, 1):
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError as e:
                sys.exit(f"--requests line {ln}: bad JSON ({e})")
            if ("prompt_ids" in obj) == ("prompt" in obj):
                sys.exit(f"--requests line {ln}: exactly one of "
                         f"'prompt_ids' / 'prompt' is required")
            if "prompt" in obj:
                if enc is None:
                    try:
                        import tiktoken
                        enc = tiktoken.get_encoding("gpt2")
                    except Exception as e:  # noqa: BLE001 — network-gated
                        sys.exit(f"'prompt' needs tiktoken's GPT-2 BPE ({e});"
                                 " use 'prompt_ids' offline")
                ids = enc.encode_ordinary(obj["prompt"])
            else:
                ids = [int(t) for t in obj["prompt_ids"]]
            timeout_s = obj.get("timeout_s")
            if timeout_s is not None:
                try:
                    timeout_s = float(timeout_s)
                except (TypeError, ValueError):
                    sys.exit(f"--requests line {ln}: 'timeout_s' must be "
                             f"a number")
            specs.append((ids, int(obj.get("new", args.new)),
                          int(obj.get("seed", args.seed)), timeout_s))
    if not specs:
        sys.exit("--requests: no requests")

    serve = build_serve_config(args, config)
    if args.placement == "subprocess":
        from gpt_2_distributed_tpu.serving.frontend.worker import (
            spawner_from_args,
        )

        make_engine = spawner_from_args(args, serve, initial_replicas=1)
    elif args.placement == "remote":
        from gpt_2_distributed_tpu.serving.frontend.worker import (
            remote_spawner_from_args,
        )

        make_engine = remote_spawner_from_args(args, serve,
                                               initial_replicas=1)
    else:
        from gpt_2_distributed_tpu.serving import ServingEngine

        draft_config, draft_params = load_draft_model(args, config)

        def make_engine():
            return ServingEngine(params, config, serve,
                                 temperature=args.temperature,
                                 top_k=args.top_k,
                                 draft_params=draft_params,
                                 draft_config=draft_config)
    router = ReplicaRouter(make_engine, replicas=1)
    if args.placement in ("subprocess", "remote"):
        make_engine.router = router  # respawn-vs-scale-up attribution
    tracker = make_tracker(args)
    # SIGTERM = finish what was accepted, exit 0. Every request below is
    # submitted before the loop starts, so the flag can only ever shorten
    # the idle tail — it exists so a supervisor's TERM during a long batch
    # drains instead of dropping streams mid-token.
    handler = PreemptionHandler(notice=DRAIN_NOTICE).install()
    driver = EngineDriver(router, tracker=tracker,
                          metrics_every=args.metrics_every,
                          xla_capture=xla_capture, preemption=handler,
                          request_timeout_s=args.request_timeout_s,
                          watchdog_timeout_s=args.watchdog_timeout_s,
                          injector=injector)

    def on_token(req, tok):
        if args.stream:
            print(json.dumps({"id": req.id, "token": tok}), flush=True)

    t0 = time.monotonic()
    handles = []
    for ids, new, seed, timeout_s in specs:
        # ValueError here (prompt too long, new<1, ...) is a bad REQUEST:
        # report and fail loudly rather than serving the rest silently.
        try:
            handles.append(driver.submit(ids, new, rng=seed,
                                         on_token=on_token,
                                         timeout_s=timeout_s))
        except ValueError as e:
            sys.exit(f"request {len(handles)}: {e}")
    driver.drain()
    driver.close()
    if tracker is not None:
        tracker.close()
    get_tracer().close()
    handler.uninstall()
    wall = time.monotonic() - t0

    eng = router.engines[0]
    for h in handles:
        print(json.dumps({
            "id": h.id,
            "generated": h.generated,
            "text": enc.decode(h.generated) if enc is not None else None,
            "finish_reason": h.finish_reason,
            # A request can time out (or lose its replica) before its
            # first token: no TTFT to report then.
            "ttft_ms": (round((h.first_token_time - h.submit_time) * 1e3, 2)
                        if h.first_token_time is not None else None),
            "queue_wait_ms": round(h.queue_wait_ms, 2),
            "preempted": h.preemptions,
            "prefix_cached_tokens": h.prefix_cached_tokens,
        }), flush=True)
    toks = sum(len(h.generated) for h in handles)
    print(f"{len(handles)} requests, {toks} tokens, {wall:.3f}s "
          f"({toks / wall:.0f} tok/s), {eng.stats['decode_steps']} decode "
          f"steps, {eng.stats['preemptions']} preemptions, "
          f"{eng.stats['prefix_hit_tokens']} prefix-cached tokens",
          file=sys.stderr)


if __name__ == "__main__":
    main()
