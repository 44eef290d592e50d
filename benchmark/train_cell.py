"""A training cell: the trainer's own loader, ``shard_batch`` and guarded
step, driven for ``--seconds`` from the benchmark's loop.

``train.main`` is one function and cannot be driven for N seconds, so the
loop here repeats its order (fetch a step's micro-batches, place them,
dispatch, prefetch the next step's while the device computes, wait for the
previous step's metrics) around the program's own pieces. The flags and the
model configuration come from the family's program module
(``cell["program"].trainer_flags`` through ``train.build_parser``, then
``train_model_config``, which repeats the trainer's inline flags-to-config
lines); a test pins both to ``train.main`` step by step. The weights, the
reference's steps and its view of a parameter tree are the family's
reference module's (``cell["reference"]``).

Set-up builds ONE object - the compiled step with its state - drives it
from the seed through the first ``reference_steps`` steps through the
window's own call and feed, and hands the same object to the window. Those
steps' losses, the first gradient (read from Adam's first moment after one
step) and the parameters' change are what the reference is compared with,
once the window has closed and the program's state is freed.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark import check, harness, traffic


class Trainer:
    """The compiled step with its state and its feed: one object, stepped by
    set-up and by the window alike."""

    def __init__(self, cell: dict, seed: int, spans: harness.Spans):
        import jax
        import jax.numpy as jnp

        from gpt_2_distributed_tpu import train as trainer
        from gpt_2_distributed_tpu.data.dataloader import (
            TokenShardDataset, get_shard_paths)
        from gpt_2_distributed_tpu.parallel.mesh import (
            MeshSpec, activate_mesh, create_mesh)
        from gpt_2_distributed_tpu.parallel.sharding import (
            shard_params_and_opt_state)
        from gpt_2_distributed_tpu.parallel.train_step import (
            make_optimizer, make_train_step)
        from gpt_2_distributed_tpu.resilience import init_guard_state

        self.spans = spans
        self.reference = cell["reference"]
        self.sizes = cell["sizes"]
        self.data_dir = os.path.join(harness.WORK_DIR, cell["name"], "shards")
        self.shard_paths = traffic.write_shards(
            self.data_dir, cell["mix"], self.sizes["vocab_size"], seed)
        self.args = args = trainer.build_parser().parse_args(
            cell["program"].trainer_flags(
                cell["config_file"], cell["mix"], self.data_dir, seed))
        if args.step_guard != "on" or args.training_mode != "local":
            raise harness.RunFailed("the loop here drives the guarded one-chip step")
        self.config = cell["program"].train_model_config(args)
        self.mesh = create_mesh(MeshSpec.for_mode(args.training_mode))
        self.accum = args.grad_accum_steps
        self.tokens_per_step = self.accum * args.batch * args.seq_len

        self.dataset = TokenShardDataset(
            get_shard_paths(self.data_dir, args.split), seq_len=args.seq_len,
            num_workers=args.workers, vocab_size=self.config.vocab_size,
            data_read_retries=args.data_read_retries)
        steps_per_epoch = (
            self.dataset.batches_per_epoch(args.batch) // self.accum)
        if steps_per_epoch < 1:
            raise harness.RunFailed("the mix's shards hold less than one step")
        self.lr = trainer.make_lr_schedule(args, steps_per_epoch)
        self.optimizer = make_optimizer(self.lr, weight_decay=args.weight_decay)
        self._feed = self._batches()

        params = self.reference.make_weights(self.sizes, seed)
        self._activate = activate_mesh(self.mesh)
        self._activate.__enter__()
        self.params, self.opt_state, _, _ = shard_params_and_opt_state(
            params, self.optimizer, self.mesh)
        self.step_fn = make_train_step(
            self.config, self.optimizer,
            accum_dtype=jnp.bfloat16 if args.accum_dtype == "bf16" else None,
            guard=True, clip_threshold=args.guard_max_grad_norm or None,
            layer_clip_norm=args.guard_clip_norm)
        self.guard_state = init_guard_state()
        self.loss_scale = jnp.ones((self.accum,), jnp.float32)
        self.rng = jax.random.PRNGKey(args.seed)
        self.steps = 0
        self._placed = None
        self.fed: list[tuple[np.ndarray, np.ndarray]] = []
        self.record_next = 0    # how many of the next host batches to keep

    def _batches(self):
        """Host batches [accum, micro_batch, T], epoch after epoch."""
        from gpt_2_distributed_tpu.data.dataloader import create_dataloader

        epoch = 0
        while True:
            self.dataset.set_epoch(epoch)
            loader = iter(create_dataloader(
                self.dataset, batch_size=self.args.batch,
                prefetch_factor=self.args.prefetch_factor))
            try:
                while True:
                    micro = [b for _, b in zip(range(self.accum), loader)]
                    if len(micro) < self.accum:
                        break       # the epoch's tail is dropped
                    yield (np.stack([m[0] for m in micro]),
                           np.stack([m[1] for m in micro]))
            finally:
                loader.close()      # stops the loader's worker threads
            epoch += 1

    def _place_next(self):
        from gpt_2_distributed_tpu.parallel.sharding import shard_batch

        with self.spans("data_fetch"):
            x, y = next(self._feed)
        if self.record_next > 0:
            self.fed.append((x, y))
            self.record_next -= 1
        with self.spans("h2d"):
            self._placed = shard_batch((x, y), self.mesh)

    def dispatch(self):
        """One optimizer step, as the trainer dispatches it, then the next
        step's batch fetched and placed while the device computes.
        Returns the step's metrics, not yet waited for."""
        if self._placed is None:
            self._place_next()
        x, y = self._placed
        with self.spans("step_dispatch"):
            self.params, self.opt_state, self.guard_state, metrics = self.step_fn(
                self.params, self.opt_state, self.guard_state, x, y, self.rng,
                self.steps, self.loss_scale)
        self.steps += 1
        self._place_next()
        return metrics

    def close(self):
        self._feed.close()
        self._activate.__exit__(None, None, None)

    def free(self):
        """Drop every device array the program holds."""
        self.params = self.opt_state = self.guard_state = None
        self._placed = self.loss_scale = self.rng = None


def first_steps(trainer: Trainer, n_steps: int) -> dict:
    """Drive the trainer through its first steps and keep what the
    reference is compared with."""
    import jax

    ref = trainer.reference
    moments = jax.jit(lambda mu: jax.tree_util.tree_map(
        lambda m: m / (1.0 - ref.ADAM_B1), mu))
    observed = {"losses": [], "skipped": 0}
    trainer.record_next = n_steps
    for i in range(n_steps):
        metrics = trainer.dispatch()
        with trainer.spans("device_sync"):
            jax.block_until_ready(metrics)
        observed["losses"].append(float(metrics.loss))
        observed["skipped"] = int(metrics.skipped_steps)
        if i == 0:
            # Adam's first moment after one step is (1 - b1) x the gradient
            # as the optimizer got it.
            first_grad = moments(trainer.opt_state[0].mu)
            observed["grad"] = ref.leaf_norms(first_grad)
            # Kept on the host (not on the chip, whose peak is being read)
            # for the norm of its difference from the reference's.
            observed["grad_tree"] = jax.device_get(first_grad)
            del first_grad
    seed_weights = ref.make_weights(trainer.sizes, trainer.args.seed)
    observed["moved"] = ref.leaf_norms(jax.jit(
        lambda p, w: jax.tree_util.tree_map(lambda a, b: a - b, p, w)
    )(trainer.params, seed_weights))
    del seed_weights
    observed["batches"] = [
        (x.reshape(-1, x.shape[-1]), y.reshape(-1, y.shape[-1]))
        for x, y in trainer.fed
    ]
    observed["shards"] = (trainer.shard_paths, trainer.args.seq_len,
                          trainer.accum * trainer.args.batch)
    trainer.fed = []
    return observed


def reference_numbers(cell: dict, seed: int, observed: dict, lr: float,
                      weight_decay: float, control: bool = False) -> dict:
    """The reference's first steps (``control``: computed with the family's
    ``control_matmul``). It is fed from the shard files, not by the
    program's loader: each row the loader fed is looked up there, and a row
    that the files do not hold as fed counts in ``data_rows_wrong``."""
    ref = cell["reference"]
    sizes = cell["sizes"]
    rows = cell["config_file"]["reference"]["rows_per_block"]
    precision = {}
    if control:
        precision["matmul"] = ref.control_matmul
        rows = max(1, rows // 2)    # the control's backward keeps more alive
    batches, wrong = traffic.rows_in_shards(*observed["shards"], observed["batches"])
    losses, grad, moved = ref.train_steps(
        sizes, seed, batches, lr=lr, weight_decay=weight_decay,
        rows_per_block=rows, **precision)
    return {"losses": losses, "grad": ref.leaf_norms(grad),
            "moved": ref.leaf_norms(moved), "grad_tree": grad,
            "data_rows_wrong": wrong}


def run(cell: dict, seed: int, seconds: float, trace: bool, device: dict,
        started: float, compiles: harness.CompileCounter) -> dict:
    import jax

    spans = harness.Spans()
    profiler = harness.ProfilerWindow(cell["name"], spans, trace)
    trainer = Trainer(cell, seed, spans)
    try:
        observed = first_steps(trainer, int(cell["mix"]["reference_steps"]))
        if not isinstance(trainer.lr, float):
            raise harness.RunFailed("the reference follows a constant learning rate")

        # --- the measured window ---------------------------------------
        compiled_before = compiles.count
        profiler.start()
        t0 = time.monotonic()
        setup_s = t0 - started
        steps_before = trainer.steps
        pending, sync_ends, window_losses = None, [], []
        while time.monotonic() - t0 < seconds:
            metrics = trainer.dispatch()
            if pending is not None:
                with spans("device_sync"):
                    jax.block_until_ready(pending)
                sync_ends.append(time.monotonic())
                window_losses.append(pending.loss)
            pending = metrics
            if profiler.active and time.monotonic() - profiler.started_at >= harness.TRACE_SECONDS:
                jax.block_until_ready(pending)
                profiler.maybe_stop()
        with spans("device_sync"):
            jax.block_until_ready(pending)
        t1 = time.monotonic()
        sync_ends.append(t1)
        profiler.maybe_stop(force=True)
        compiled_in_window = compiles.count - compiled_before
        window_losses.append(pending.loss)
        skipped = int(pending.skipped_steps)
        window_losses = [float(v) for v in window_losses]
        steps = trainer.steps - steps_before
        memory_peak = harness.memory_peak_bytes(cell["chips"])
    finally:
        trainer.close()
    lr, weight_decay = trainer.lr, trainer.args.weight_decay
    tokens_per_step = trainer.tokens_per_step
    trainer.free()
    if compiled_in_window:
        raise harness.RunFailed(
            f"{compiled_in_window} trace/lower/compile events inside the window")

    # --- correct: the first steps against the reference ------------------
    reference = reference_numbers(cell, seed, observed, lr, weight_decay)
    numbers = check.training_numbers(
        observed, reference, cell["reference"].leaf_norms)["numbers"]
    correct, compared = check.judge(numbers, cell["limits"])
    finite = all(np.isfinite(v) for v in window_losses)
    correct = correct and finite and skipped == 0 and observed["skipped"] == 0

    window_s = t1 - t0
    tok_s = steps * tokens_per_step / window_s / cell["chips"]
    gaps = [b - a for a, b in zip(sync_ends, sync_ends[1:])]
    print(f"window: {steps} steps in {window_s:.3f} s; first step done after "
          f"{sync_ends[0] - t0:.3f} s, then gaps of {min(gaps):.3f}..{max(gaps):.3f} s",
          flush=True)
    device = dict(device, memory_peak_bytes=memory_peak)
    values = {"train_tok_s_per_chip": tok_s, "setup_s": setup_s}
    metrics, breakdown = harness.metrics_of(cell, values, device, profiler, spans, {
        "window": (t0, t1), "steps": steps, "sync_ends": sync_ends,
        "tokens_per_step": tokens_per_step,
    })
    return {"correct": correct, "attempted": steps,
            "failed": skipped + (0 if finite else 1), "metrics": metrics,
            "device": device, "compared": compared, "breakdown": breakdown}
