#!/usr/bin/env python3
"""The quickest proof that gpt2-tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip: device, kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: fsdp / dp+sharded-update vs one chip
    python chip_smoke.py --rehearse  # control flow at tiny size on the CPU; never passes

Drives the main path once through the entry points a user would type —
``python -m gpt_2_distributed_tpu.train``, ``...serving.serve``,
``...serving.frontend.server`` — at the published width and depth of GPT-2
124M (12 layers, C=768, 12 heads, V=50257, T=1024), on random weights and
synthetic data made from a seed inside the run. No network, no git.

It fails loudly when anything on the way is not the chip: every child is
told to use the TPU, and the script reads the device banners and the
``[kernels]`` lines the program prints instead of trusting rc 0. No phase
is retried, skipped or downgraded. The last line of stdout is the verdict:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

This parent never imports jax, nor anything of the package that does: a
chip belongs to one process at a time, so each phase is a child process
that exits before the next starts. Times printed here are set-up
observations of a smoke run (compile included), not benchmark figures.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import random
import re
import shutil
import signal
import struct
import subprocess
import sys
import tempfile
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
VOCAB = 50257            # config.MODEL_PRESETS["124M"].vocab_size
LN_VOCAB = math.log(VOCAB)   # 10.82: the loss of a uniform guess

# Kernel-vs-reference tolerance. Both sides compute bf16 products with fp32
# accumulation; they differ in blocking and in where probabilities are
# rounded to bf16 (eps 2^-8 = 3.9e-3). On unit-scale inputs that is a few
# 1e-3 on outputs of magnitude ~1, so 2e-2 per unit of the reference's
# largest magnitude is loose enough for rounding and far too tight for a
# wrong mask, a wrong block or a dropped term.
KERNEL_TOL = 2e-2
# Per-step loss agreement across sharding modes: bf16 reduction order alone
# drifts this far over ten steps (.claude/skills/verify/SKILL.md, recipe 3).
MODE_TOL = 3e-4

# Found on the chip: without warm-up a 124M model climbs first at 1e-3 and
# wobbles in place at 6e-4; at 3e-4 the per-step loss falls ~0.03 in eight
# steps against a batch-to-batch noise of ~0.004, and twenty steps put the
# fall beyond doubt. The synthetic runs are learnable, but at V=50257 slowly.
TRAIN_LR = "3e-4"
TRAIN_STEPS = 20
RESUME_STEPS = 2

CHILD_TIMEOUT_S = 900    # any one child; the whole run must fit 1200 s


# ============================================================================
# children — each runs in its own process; jax is imported only in here
# ============================================================================


def child_data(out_dir: str, tokens_per_shard: int) -> int:
    """Synthetic uint16 shards at the real vocabulary (no jax involved)."""
    from gpt_2_distributed_tpu.data.synthetic import write_synthetic_shards

    paths = write_synthetic_shards(
        out_dir, num_shards=9, tokens_per_shard=tokens_per_shard,
        vocab_size=VOCAB, seed=SEED,
    )
    print(f"wrote {len(paths)} shards x {tokens_per_shard} tokens to {out_dir}")
    return 0


def child_device() -> int:
    """What this process runs on, as JAX reports it. The environment and the
    versions go out first, so they are on record even if the backend does
    not come up."""
    from importlib import metadata

    for name in sorted(os.environ):
        if name.startswith(("TPU_", "JAX_", "XLA_", "LIBTPU")):
            print(f"env {name}={os.environ[name]}")
    for dist in ("jax", "jaxlib", "libtpu", "numpy", "optax", "orbax-checkpoint"):
        try:
            print(f"version {dist} {metadata.version(dist)}")
        except metadata.PackageNotFoundError:
            print(f"version {dist} not installed")
    print(f"python {sys.version.split()[0]}", flush=True)

    from gpt_2_distributed_tpu import native
    from gpt_2_distributed_tpu.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    print(f"dataloader window gather: {native.describe()}; "
          f"built as {native.so_path()}")

    import jax

    devices = jax.devices()
    print("REPORT " + json.dumps({
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "cache_dir": cache_dir,
    }), flush=True)
    return 0


def child_kernels(tiny: bool) -> int:
    """The kernels the main path picks by itself on a TPU, each against its
    plain XLA reference on the same inputs. Prints one KERNEL line per pair;
    the parent holds them to KERNEL_TOL."""
    from gpt_2_distributed_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gpt_2_distributed_tpu.ops.attention import causal_attention
    from gpt_2_distributed_tpu.ops.flash_attention import flash_attention
    from gpt_2_distributed_tpu.ops.paged_attention import paged_attention

    def report(name: str, got, ref) -> None:
        got = np.asarray(got.astype(jnp.float32))
        ref = np.asarray(ref.astype(jnp.float32))
        ok_values = bool(np.isfinite(got).all()) and got.shape == ref.shape
        err = float(np.max(np.abs(got - ref))) if ok_values else float("inf")
        print(f"KERNEL {name} max_abs_err={err:.3e} "
              f"ref_max_abs={float(np.max(np.abs(ref))):.3e}", flush=True)

    rng = np.random.default_rng(SEED)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    # --- training attention: flash vs dense, forward and gradients ----------
    b, h, t, d = (2, 2, 256, 64) if tiny else (4, 12, 1024, 64)
    q, k, v, g = (normal(b, h, t, d) for _ in range(4))

    def pulled(attn):
        def f(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32)), o
        return jax.jit(jax.value_and_grad(f, (0, 1, 2), has_aux=True))

    (_, o_flash), grads_flash = pulled(flash_attention)(q, k, v)
    (_, o_dense), grads_dense = pulled(causal_attention)(q, k, v)
    report(f"flash_attention fwd ({b},{h},{t},{d}) bf16", o_flash, o_dense)
    for name, gf, gd in zip(("dq", "dk", "dv"), grads_flash, grads_dense):
        report(f"flash_attention {name}", gf, gd)

    # --- serving attention: paged decode, Pallas vs XLA gather --------------
    heads, bs = (2, 16) if tiny else (12, 16)
    m = 256 // bs if tiny else 1024 // bs
    # Ragged on purpose: one token; exactly one block (no cached block
    # beyond the first); one past a block edge; ending mid-block; an idle
    # slot; a short one; half and (almost) full context.
    lengths = [1, bs, bs + 1, 6 * bs + 4, 0, 5, m * bs // 2, m * bs - 1]
    n_blocks = 1 + len(lengths) * m
    free = list(rng.permutation(np.arange(1, n_blocks)))
    table = np.zeros((len(lengths), m), np.int32)   # tails park on null block 0
    for row, n in enumerate(lengths):
        for j in range(-(-n // bs)):
            table[row, j] = free.pop()
    qd = normal(len(lengths), heads, d)
    k_pool, v_pool = normal(n_blocks, heads, bs, d), normal(n_blocks, heads, bs, d)
    args = (qd, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lengths, jnp.int32))
    pallas = jax.jit(lambda *a: paged_attention(*a, impl="pallas"))
    report(
        f"paged_attention pallas-vs-xla H={heads} D={d} block={bs} "
        f"lengths={lengths}",
        pallas(*args), jax.jit(lambda *a: paged_attention(*a, impl="xla"))(*args),
    )
    held = "tpu_custom_call" in pallas.lower(*args).compile().as_text()
    print(f"COMPILED paged_attention holds_tpu_custom_call={held}", flush=True)
    return 0


# ============================================================================
# parent — stdlib only
# ============================================================================


class Child:
    """One child process: its lines with the time each arrived, and how it
    ended. Both pipes are pumped into lists (and log files) by threads, so a
    caller may read ``err`` while the child still runs."""

    def __init__(self, name: str, argv: list[str], env: dict, log_dir: str):
        self.name = name
        self.rc: int | None = None
        self.wall_s = 0.0
        self.out: list[tuple[float, str]] = []   # (seconds since start, line)
        self.err: list[tuple[float, str]] = []
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            argv, cwd=HERE, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        self.pumps = [
            threading.Thread(
                target=self._pump, daemon=True,
                args=(pipe, rows, os.path.join(log_dir, f"{name}.{suffix}")),
            )
            for pipe, rows, suffix in ((self.proc.stdout, self.out, "out"),
                                       (self.proc.stderr, self.err, "err"))
        ]
        for pump in self.pumps:
            pump.start()

    def _pump(self, pipe, rows, log_path) -> None:
        with open(log_path, "w") as log:
            for line in pipe:
                rows.append((time.monotonic() - self.t0, line.rstrip("\n")))
                log.write(line)
                log.flush()

    def wait(self, timeout: float) -> bool:
        """Wait for the exit (killing at ``timeout``) and for both pipes to
        drain. False when the child had to be killed."""
        in_time = True
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            in_time = False
            self.proc.kill()
            self.proc.wait()
        for pump in self.pumps:
            pump.join()
        self.rc = self.proc.returncode
        self.wall_s = time.monotonic() - self.t0
        return in_time

    def lines(self, stream="both") -> list[str]:
        rows = {"out": self.out, "err": self.err,
                "both": self.out + self.err}[stream]
        return [line for _, line in rows]

    def first_seen(self, pattern: str) -> float | None:
        hits = [t for t, line in self.out + self.err if re.search(pattern, line)]
        return min(hits) if hits else None


class Smoke:
    def __init__(self, args):
        self.args = args
        self.rehearse = args.rehearse
        self.failures: list[str] = []
        self.device = {"platform": None, "kind": None, "count": 0}
        self.cache_dir: str | None = None
        self.live: list[Child] = []
        self.work = tempfile.mkdtemp(prefix="chip_smoke_")
        # Full child logs go where the chip tool brings files back from.
        self.log_dir = os.path.join(os.getcwd(), "chiprun_out", "chip_smoke")
        os.makedirs(self.log_dir, exist_ok=True)
        self.env = dict(os.environ, PYTHONUNBUFFERED="1")
        if not self.rehearse:
            # Asked for, not hoped for: with this set JAX fails at start-up
            # when the TPU does not come up, instead of warning and carrying
            # on on the CPU.
            self.env["JAX_PLATFORMS"] = "tpu"

    # ---------------------------------------------------------------- plumbing

    def say(self, text: str = "") -> None:
        print(text, flush=True)

    def check(self, ok: bool, what: str) -> bool:
        self.say(f"  {'ok  ' if ok else 'FAIL'}  {what}")
        if not ok:
            self.failures.append(what)
        return bool(ok)

    def spawn(self, name: str, argv: list[str]) -> Child:
        child = Child(name, argv, self.env, self.log_dir)
        self.live.append(child)
        return child

    def finish(self, child: Child, timeout: float = CHILD_TIMEOUT_S) -> Child:
        if not child.wait(timeout):
            self.say(f"  child {child.name} killed after {timeout}s")
        self.say(f"  [{child.name}] rc={child.rc} wall={child.wall_s:.1f}s "
                 f"(logs: {os.path.relpath(self.log_dir)}/{child.name}.out|.err)")
        if child.rc != 0:
            for line in child.lines("err")[-25:]:
                self.say(f"    | {line}")
        return child

    def run(self, name: str, argv: list[str]) -> Child:
        return self.finish(self.spawn(name, argv))

    def me(self, *child_args: str) -> list[str]:
        return [sys.executable, os.path.abspath(__file__), "--child", *child_args]

    def cache_entries(self) -> int:
        if not self.cache_dir or not os.path.isdir(self.cache_dir):
            return 0
        return sum(
            1 for _root, _dirs, files in os.walk(self.cache_dir)
            for f in files if not f.endswith("-atime")
        )

    def kernel_lines(self, child: Child, site: str) -> set[str]:
        """What ``site`` resolved to, from the program's own [kernels] lines."""
        prefix = f"[kernels] {site}: "
        return {line[len(prefix):] for line in child.lines()
                if line.startswith(prefix)}

    def cleanup(self) -> None:
        for child in self.live:
            if child.proc.poll() is None:
                child.wait(timeout=0)
        shutil.rmtree(self.work, ignore_errors=True)

    # ------------------------------------------------------------------ phases

    def phase_device(self, want_chips: int) -> bool:
        self.say("== device ==")
        child = self.run("device", self.me("device"))
        for line in child.lines("out"):
            if not line.startswith("REPORT "):
                self.say(f"  {line}")
        reports = [json.loads(line[7:]) for line in child.lines("out")
                   if line.startswith("REPORT ")]
        if child.rc != 0 or not reports:
            return self.check(False, "the device child reported a JAX device")
        report = reports[-1]
        self.cache_dir = report.pop("cache_dir")
        self.device = report
        self.say(f"  platform={report['platform']} kind={report['kind']!r} "
                 f"count={report['count']}; compile cache at {self.cache_dir} "
                 f"({self.cache_entries()} entries)")
        ok = self.check(report["platform"] == "tpu",
                        f"platform is tpu (got {report['platform']})")
        if want_chips > 1:
            ok &= self.check(report["count"] == want_chips,
                             f"{want_chips} chips visible (got {report['count']})")
        return ok

    def phase_kernels(self) -> None:
        self.say("== kernels on the chip ==")
        child = self.run("kernels", self.me("kernels", *(["--tiny"] if self.rehearse else [])))
        self.check(child.rc == 0, "kernel child exited 0")
        rows = [re.match(r"KERNEL (.*) max_abs_err=(\S+) ref_max_abs=(\S+)", line)
                for line in child.lines("out")]
        rows = [r for r in rows if r]
        self.check(len(rows) == 5, f"five kernel/reference pairs reported (got {len(rows)})")
        for r in rows:
            err, ref = float(r.group(2)), float(r.group(3))
            tol = KERNEL_TOL * max(1.0, ref)
            self.check(err <= tol, f"{r.group(1)}: max-abs err {err:.3e} <= {tol:.3e} "
                                   f"({KERNEL_TOL:g} x max(1, |ref|max={ref:.2f}))")
        self.check(self.kernel_lines(child, "attention") == {"flash (mosaic)"},
                   f"flash attention ran compiled by Mosaic "
                   f"(resolved: {sorted(self.kernel_lines(child, 'attention'))})")
        self.check("pallas (mosaic)" in self.kernel_lines(child, "paged_attention")
                   and "pallas (interpret)" not in self.kernel_lines(child, "paged_attention"),
                   f"paged decode kernel ran compiled by Mosaic "
                   f"(resolved: {sorted(self.kernel_lines(child, 'paged_attention'))})")
        self.check("COMPILED paged_attention holds_tpu_custom_call=True" in child.lines("out"),
                   "the compiled paged-decode program holds a tpu_custom_call")

    def make_data(self, tokens_per_shard: int) -> str:
        data_dir = os.path.join(self.work, "data")
        child = self.run("data", self.me("data", data_dir, str(tokens_per_shard)))
        self.check(child.rc == 0, "synthetic shards written")
        return data_dir

    def model_flags(self) -> list[str]:
        """--model 124M as published; the rehearsal shrinks it to run on a CPU."""
        if not self.rehearse:
            return ["--model", "124M"]
        return ["--model", "124M", "--n_layer", "2", "--n_embd", "64", "--n_head", "2"]

    def train_argv(self, data_dir, *extra: str) -> list[str]:
        seq = "128" if self.rehearse else "1024"
        return [sys.executable, "-m", "gpt_2_distributed_tpu.train",
                "--data_dir", data_dir, *self.model_flags(),
                "--seq_len", seq, "--cli_every", "1", *extra]

    @staticmethod
    def logged_losses(child: Child) -> list[float]:
        return [float(m.group(1)) for line in child.lines("out")
                if (m := re.match(r"step\s+\d+ \| loss: (\S+)", line))]

    def phase_train(self) -> str | None:
        """train, then --resume: the shape of scripts/run_training_local.sh
        (batch 4, seq 1024, grad-accum 4, bf16, dropout on, attention left to
        choose). Returns the save dir holding the checkpoint to serve."""
        self.say("== train ==")
        data_dir = self.make_data(65_536)
        save_dir = os.path.join(self.work, "ckpt")
        common = ["--training_mode", "local", "--batch", "4",
                  "--grad_accum_steps", "4", "--lr", TRAIN_LR,
                  "--save_every", str(TRAIN_STEPS // 2), "--save_dir", save_dir]
        entries0 = self.cache_entries()
        first = self.run("train", self.train_argv(
            data_dir, *common, "--max_steps", str(TRAIN_STEPS)))
        entries1 = self.cache_entries()
        again = self.run("train_resume", self.train_argv(
            data_dir, *common, "--max_steps", str(TRAIN_STEPS + RESUME_STEPS), "--resume"))
        entries2 = self.cache_entries()

        for child in (first, again):
            self.check(child.rc == 0, f"{child.name}: rc 0")
            self.check("platform: tpu" in child.lines("out"),
                       f"{child.name}: the trainer's banner says platform: tpu")
            self.check(self.kernel_lines(child, "attention") == {"flash (mosaic)"},
                       f"{child.name}: attention resolved to the flash kernel compiled "
                       f"by Mosaic (resolved: {sorted(self.kernel_lines(child, 'attention'))})")
            for line in child.lines("out"):
                if line.startswith(("step ", "dataloader window gather", "mesh:",
                                    "resumed from", "training done")):
                    self.say(f"    {line[:110]}")
        losses = self.logged_losses(first)
        self.check(len(losses) == TRAIN_STEPS,
                   f"{TRAIN_STEPS} optimizer steps logged (got {len(losses)})")
        self.check(all(math.isfinite(x) for x in losses + self.logged_losses(again))
                   and len(self.logged_losses(again)) == RESUME_STEPS,
                   f"every logged loss is finite, {RESUME_STEPS} more steps after the resume")
        if losses:
            self.check(abs(losses[0] - LN_VOCAB) <= 0.3,
                       f"first loss {losses[0]:.4f} within 0.3 of ln({VOCAB}) = {LN_VOCAB:.2f}")
            self.check(losses[-1] < losses[0],
                       f"loss fell: windowed mean {losses[0]:.4f} -> {losses[-1]:.4f}")
        steps = sorted(d for d in os.listdir(save_dir) if d.startswith("step_")) \
            if os.path.isdir(save_dir) else []
        committed = [d for d in steps
                     if os.path.exists(os.path.join(save_dir, d, "COMMITTED"))]
        self.check(bool(committed), f"COMMITTED checkpoint(s) on disk: {committed}")
        self.check(any(line.startswith("resumed from") and f": step {TRAIN_STEPS}," in line
                       for line in again.lines("out")),
                   f"the second run resumed from step {TRAIN_STEPS}")

        cold = first.first_seen(r"^device memory after step")
        warm = again.first_seen(r"^device memory after step")
        self.check(cold is not None and warm is not None,
                   "both runs reported device memory after their first step")
        self.say(f"  wall: train {first.wall_s:.1f}s, resume {again.wall_s:.1f}s; time to the "
                 f"first step (process start to step done; set-up, not a benchmark): "
                 f"first run {cold and round(cold, 1)}s, resumed run {warm and round(warm, 1)}s")
        self.say(f"  compile cache entries: {entries0} before, {entries1} after train, "
                 f"{entries2} after resume")
        self.check(entries1 >= 1, "the train run left compiled programs in the cache")
        # Printed, not judged: a cache that came warm with the machine makes
        # the first run warm too, and the resumed run also pays a restore.
        if cold is not None and warm is not None:
            self.say(f"  the resumed run reached its first step {cold - warm:+.1f}s "
                     f"sooner than the first run")
        return save_dir

    def write_requests(self) -> tuple[str, list[dict], list[bool]]:
        """8 requests of mixed prompt lengths; four share a 32-token prefix.
        Returns (path, requests, which of them share the prefix)."""
        rnd = random.Random(SEED)
        prefix = [rnd.randrange(VOCAB) for _ in range(32)]
        plan = [(True, 8, 16), (False, 5, 24), (True, 40, 8), (False, 64, 12),
                (True, 68, 16), (False, 200, 8), (True, 3, 20), (False, 17, 10)]
        if self.rehearse:
            plan = [(s, min(n, 40), new) for s, n, new in plan]
        reqs = [{"prompt_ids": (prefix if share else [])
                 + [rnd.randrange(VOCAB) for _ in range(n)], "new": new}
                for share, n, new in plan]
        path = os.path.join(self.work, "requests.jsonl")
        with open(path, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in reqs)
        return path, reqs, [share for share, _, _ in plan]

    def serve_argv(self, module: str, ckpt: str, *extra: str) -> list[str]:
        seq = ["--seq_len", "128"] if self.rehearse else []
        return [sys.executable, "-m", f"gpt_2_distributed_tpu.serving.{module}",
                "--ckpt", ckpt, *self.model_flags(), *seq,
                "--temperature", "0", *extra]

    def check_on_chip_serving(self, child: Child) -> None:
        self.check(any(line.startswith("device: platform=tpu") for line in child.lines("err")),
                   f"{child.name}: device banner says platform=tpu")
        resolved = self.kernel_lines(child, "paged_attention")
        self.check(resolved == {"pallas (mosaic)"},
                   f"{child.name}: the decode program holds the Pallas paged kernel, not "
                   f"the XLA gather nor the interpreter (resolved: {sorted(resolved)}; "
                   f"prefill attention: {sorted(self.kernel_lines(child, 'attention'))})")

    def phase_serve(self, ckpt: str) -> None:
        self.say("== serve ==")
        path, reqs, shares = self.write_requests()
        entries0 = self.cache_entries()
        child = self.run("serve", self.serve_argv(
            "serve", ckpt, "--requests", path, "--prefix_cache"))
        self.check(child.rc == 0, "serve: rc 0")
        self.check_on_chip_serving(child)
        records = [json.loads(line) for line in child.lines("out") if line.startswith("{")]
        self.check(len(records) == len(reqs), f"{len(reqs)} final records (got {len(records)})")
        for rec, req in zip(records, reqs):
            gen = rec["generated"]
            self.check(
                len(gen) == req["new"] and rec["finish_reason"] == "length"
                and all(0 <= tok < VOCAB for tok in gen),
                f"request {rec['id']}: prompt {len(req['prompt_ids'])} -> {len(gen)}/"
                f"{req['new']} tokens in vocab, prefix_cached_tokens="
                f"{rec['prefix_cached_tokens']}, ttft {rec['ttft_ms']} ms")
        sharers = [rec for rec, share in zip(records, shares) if share]
        self.check(bool(sharers) and all(r["prefix_cached_tokens"] > 0 for r in sharers[1:]),
                   "every sharer after the first reports prefix_cached_tokens > 0")
        for line in child.lines("err")[-1:]:
            self.say(f"    {line}")

        # --- the front door -------------------------------------------------
        self.say("== front door ==")
        rnd = random.Random(SEED + 1)
        prompt = [rnd.randrange(VOCAB) for _ in range(48)]
        new = 24
        alone = os.path.join(self.work, "alone.jsonl")
        with open(alone, "w") as f:
            f.write(json.dumps({"prompt_ids": prompt, "new": new}) + "\n")
        cli = self.run("serve_alone", self.serve_argv("serve", ckpt, "--requests", alone))
        self.check(cli.rc == 0, "serve CLI, the front door's prompt sent alone: rc 0")
        cli_ids = [json.loads(line)["generated"] for line in cli.lines("out")
                   if line.startswith("{")]
        cli_ids = cli_ids[0] if cli_ids else []

        entries1 = self.cache_entries()
        child = self.spawn("frontend", self.serve_argv(
            "frontend.server", ckpt, "--port", "0"))
        port = None
        while (port is None and child.proc.poll() is None
               and time.monotonic() - child.t0 < CHILD_TIMEOUT_S):
            for line in child.lines("err"):
                if m := re.match(r"frontend: http://[^:]+:(\d+)", line):
                    port = int(m.group(1))
            time.sleep(0.2)
        ready_s = time.monotonic() - child.t0
        plain, stream, done = None, [], False
        if self.check(port is not None, f"front door is listening (after {ready_s:.1f}s)"):
            body = {"prompt_ids": prompt, "max_tokens": new}
            plain = self.post(port, body)
            stream, done = self.post_sse(port, body)
        if child.proc.poll() is None:
            child.proc.send_signal(signal.SIGTERM)
        self.finish(child, timeout=120)
        entries2 = self.cache_entries()
        self.check(child.rc == 0, "front door: rc 0 after SIGTERM")
        self.check_on_chip_serving(child)
        self.check(plain is not None and len(plain) == new,
                   f"non-streaming completion returned {new} token_ids")
        self.check(plain is not None and plain == stream,
                   f"non-streaming and SSE token_ids are equal: {plain} vs {stream}")
        self.check(done, "the SSE stream ended with data: [DONE]")
        same = sum(a == b for a, b in zip(plain or [], cli_ids))
        self.say(f"  agreement with the serve CLI on the same prompt sent alone: "
                 f"{same}/{new} tokens equal ({100.0 * same / new:.0f}%) — reported, "
                 f"not judged: other batch shapes, bf16-class matmuls, flat logits")
        self.say(f"  compile cache entries: {entries0} before serve, {entries1} before the "
                 f"front door, {entries2} after")

    def post(self, port: int, body: dict) -> list[int] | None:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=CHILD_TIMEOUT_S)
        try:
            conn.request("POST", "/v1/completions", json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
        finally:
            conn.close()
        if resp.status != 200:
            self.say(f"  POST /v1/completions -> {resp.status}: {raw[:300]!r}")
            return None
        return json.loads(raw)["choices"][0]["token_ids"]

    def post_sse(self, port: int, body: dict) -> tuple[list[int], bool]:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=CHILD_TIMEOUT_S)
        tokens, done = [], False
        try:
            conn.request("POST", "/v1/completions", json.dumps({**body, "stream": True}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            for raw in resp:
                line = raw.decode().rstrip("\r\n")
                if line == "data: [DONE]":
                    done = True
                elif line.startswith("data: "):
                    tok = json.loads(line[6:])["choices"][0]["token"]
                    if tok is not None:
                        tokens.append(tok)
        finally:
            conn.close()
        return tokens, done

    # --------------------------------------------------------------- four chips

    @staticmethod
    def tb_series(log_dir: str, tag: str) -> dict[int, float]:
        """step -> value of one scalar from a TensorBoard event file. The CLI
        prints the loss to four decimals; the event file keeps float32."""
        from tensorboardX.proto import event_pb2   # protobuf only, no jax

        series: dict[int, float] = {}
        for name in sorted(os.listdir(log_dir)):
            if "tfevents" not in name:
                continue
            with open(os.path.join(log_dir, name), "rb") as f:
                data = f.read()
            pos = 0
            while pos + 12 <= len(data):
                (length,) = struct.unpack("<Q", data[pos:pos + 8])
                event = event_pb2.Event.FromString(data[pos + 12:pos + 12 + length])
                pos += 12 + length + 4
                for value in event.summary.value:
                    if value.tag == tag:
                        series[event.step] = value.simple_value
        return series

    @staticmethod
    def memory_after_first_step(child: Child) -> dict[int, float]:
        """device id -> GB in use, from the trainer's second device report."""
        lines = child.lines("out")
        found: dict[int, float] = {}
        if "device memory after step 1:" in lines:
            for line in lines[lines.index("device memory after step 1:") + 1:]:
                m = re.match(r"  device (\d+): .* hbm (\S+)/\S+ GB", line)
                if not m:
                    break
                found[int(m.group(1))] = float(m.group(2))
        return found

    def phase_multichip(self) -> None:
        """dp/fsdp training is the multi-chip path users depend on: 124M,
        6 steps, same global batch (16 x 1024 tokens) and seed, dropout 0,
        under fsdp-4, dp-4 with the sharded update, and one chip."""
        self.say("== four chips: fsdp-4, dp-4 + sharded update, one chip ==")
        data_dir = self.make_data(65_536)
        modes = {
            "fsdp4": ["--training_mode", "fsdp", "--batch", "2"],
            "dp4_shard_update": ["--training_mode", "dp", "--shard_update", "on",
                                 "--batch", "2"],
            "one_chip": ["--mesh", "data=1,fsdp=1", "--batch", "8"],
        }
        banners = {"fsdp4": "mesh: data=1, fsdp=4 ",
                   "dp4_shard_update": "mesh: data=4, fsdp=1, shard_update ",
                   "one_chip": "mesh: data=1, fsdp=1 "}
        losses: dict[str, list[float]] = {}
        memory: dict[str, dict[int, float]] = {}
        for name, flags in modes.items():
            tb = os.path.join(self.work, f"tb_{name}")
            child = self.run(name, self.train_argv(
                data_dir, *flags, "--grad_accum_steps", "2", "--dropout", "0",
                "--max_steps", "6", "--log_dir", tb))
            self.check(child.rc == 0, f"{name}: rc 0")
            self.check("platform: tpu" in child.lines("out")
                       and "global device count: 4" in child.lines("out"),
                       f"{name}: banner says platform: tpu, 4 devices")
            self.check(any(line.startswith(banners[name]) for line in child.lines("out")),
                       f"{name}: mesh banner starts {banners[name]!r}")
            self.check(self.kernel_lines(child, "attention") == {"flash (mosaic)"},
                       f"{name}: attention resolved to flash (mosaic) "
                       f"(resolved: {sorted(self.kernel_lines(child, 'attention'))})")
            mean = self.tb_series(tb, "train/loss") if os.path.isdir(tb) else {}
            # The tracker logs the running mean p_n of the losses so far;
            # the n-th loss is n*p_n - (n-1)*p_(n-1).
            losses[name] = [
                n * mean[n] - (n - 1) * mean.get(n - 1, 0.0) for n in sorted(mean)
            ]
            memory[name] = self.memory_after_first_step(child)
            self.say(f"    {name}: per-step loss "
                     f"{' '.join(f'{x:.5f}' for x in losses[name])}")
            self.say(f"    {name}: GB in use per device after step 1: {memory[name]}")
            self.check(len(losses[name]) == 6 and all(map(math.isfinite, losses[name])),
                       f"{name}: 6 finite per-step losses")
        ref = losses["one_chip"]
        for name in ("fsdp4", "dp4_shard_update"):
            gaps = [abs(a - b) for a, b in zip(losses[name], ref)]
            self.check(len(gaps) == 6 and max(gaps) <= MODE_TOL,
                       f"{name} vs one chip: per-step loss gap max "
                       f"{max(gaps, default=float('nan')):.2e} <= {MODE_TOL:g}")
        one = memory["one_chip"].get(0, 0.0)
        self.check(one > 0 and len(memory["one_chip"]) == 4,
                   f"one chip: state on device 0 ({one:.3f} GB), all four devices reported")
        # Bounds are wide on purpose: bytes in use also count the loaded
        # program (~0.1 GB on one chip), which sharding does not shrink.
        # State left on device 0, or replicated, would read 1.0.
        shares = {d: gb / one for d, gb in memory["fsdp4"].items()} if one else {}
        self.check(len(shares) == 4 and all(0.2 <= s <= 0.45 for s in shares.values()),
                   f"fsdp-4: every device holds about a quarter of the one-chip state "
                   f"(shares {({d: round(s, 3) for d, s in shares.items()})})")
        shares = {d: gb / one for d, gb in memory["dp4_shard_update"].items()} if one else {}
        # Replicated fp32 params (1/3 of the state) + a quarter of the two
        # AdamW moments (2/3 / 4) = one half.
        self.check(len(shares) == 4 and all(0.4 <= s <= 0.7 for s in shares.values()),
                   f"dp-4 + sharded update: every device holds about half "
                   f"(shares {({d: round(s, 3) for d, s in shares.items()})})")

    # -------------------------------------------------------------------- whole

    def run_all(self) -> bool:
        chips = self.args.chips
        if self.rehearse:
            self.say("REHEARSAL: tiny sizes, whatever platform JAX finds, every check "
                     "printed — control flow only, and never a pass")
        if not self.phase_device(chips) and not self.rehearse:
            return False
        if chips == 4:
            self.phase_multichip()
        else:
            self.phase_kernels()
            save_dir = self.phase_train()
            if self.check(bool(save_dir) and os.path.isdir(save_dir),
                          "a checkpoint directory exists to serve from"):
                self.phase_serve(save_dir)
        return not self.failures


def verdict(ok: bool, device: dict, rehearse: bool) -> tuple[str, int]:
    """(last line of stdout, exit code). A rehearsal proves control flow and
    nothing about the chip: it can never print ``"ok": true`` nor exit 0."""
    ok = bool(ok) and not rehearse and device.get("platform") == "tpu"
    line = json.dumps({"ok": ok, "device": {
        "platform": device.get("platform"), "kind": device.get("kind"),
        "count": device.get("count", 0),
    }})
    return line, 0 if ok else 1


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4],
                   help="4 runs only the multi-chip phase and what it is compared with")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on whatever JAX finds; exercises the control "
                        "flow, always ends \"ok\": false with a non-zero exit")
    p.add_argument("--child", nargs="+", default=None, help=argparse.SUPPRESS)
    p.add_argument("--tiny", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.child:
        sys.path.insert(0, HERE)
        kind, *rest = args.child
        if kind == "data":
            return child_data(rest[0], int(rest[1]))
        if kind == "device":
            return child_device()
        if kind == "kernels":
            return child_kernels(args.tiny)
        p.error(f"unknown child {kind!r}")

    t0 = time.monotonic()
    smoke = Smoke(args)
    ok = False
    try:
        ok = smoke.run_all()
    except Exception:  # noqa: BLE001 — a crash of the smoke itself is a failed smoke
        traceback.print_exc()
        smoke.failures.append("chip_smoke.py itself raised")
    finally:
        smoke.cleanup()
    print(f"== {'PASS' if ok and not args.rehearse else 'FAIL'} in "
          f"{time.monotonic() - t0:.0f}s; failed checks: {len(smoke.failures)} ==")
    for what in smoke.failures:
        print(f"  FAIL  {what[:200]}")
    line, code = verdict(ok, smoke.device, args.rehearse)
    print(line, flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
