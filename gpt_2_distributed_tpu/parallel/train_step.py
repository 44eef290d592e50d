"""The jitted training step: loss -> grad -> accumulate -> AdamW update.

One function covers every execution mode of the reference
(``/root/reference/train_gpt2_distributed.py:396-425``): under jit, the mode
is determined entirely by how params and batch are sharded (see
``parallel/sharding.py``). Design decisions vs. the reference, each
deliberate:

* **Gradient accumulation is a ``lax.scan`` over the micro-batch axis inside
  the step** — gradients cross the network once per optimizer step. The
  reference all-reduces every micro-batch because it never calls DDP's
  ``no_sync()`` (SURVEY.md §3.2), wasting 3 of 4 reductions at
  grad_accum=4; that is a defect, not a behavior to match.
* **Grad-norm is measured, not clipped**, matching the reference's
  ``clip_grad_norm_(params, inf)`` measurement-only call
  (``/root/reference/train_gpt2_distributed.py:419-421``).
* **AdamW** = optax.adamw(lr, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
  applied to ALL params — torch ``AdamW(model.parameters(), wd=0.1)`` has no
  param groups in the reference (``:356-362``), so LN/bias weights decay
  there too; optax's decoupled decay matches torch's. The fused-kernel flag
  has no analogue: XLA fuses the update automatically.
* **Loss reported is the mean over micro-batches.** The reference logs only
  the last micro-batch's loss re-scaled (``:434-436``); the mean is the
  quantity the gradient actually descends, so we log that and document the
  difference here for the parity record.
* **Mixed precision**: params/opt-state fp32, compute bf16 (casts inside the
  model), loss/grads fp32 — the autocast-bf16 + fp32-master-weights scheme of
  the reference (``:404``, SURVEY.md §2.2).

The fused layer-epilogue kernels (``ops/fused_layer.py``, selected by
``GPT2Config.fused_layers``) need no wiring here: the flag rides inside the
config that ``make_train_step`` closes over, and the fused paths carry their
own ``jax.custom_vjp`` rules, so grad/accumulate/update are oblivious to
whether the model ran fused or unfused epilogues.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from gpt_2_distributed_tpu.config import GPT2Config
from gpt_2_distributed_tpu.models import gpt2
from gpt_2_distributed_tpu.obs import compile_watch

# Whoever builds a training program has the compile watch first.
compile_watch.install()

# Reference AdamW hyperparameters, /root/reference/train_gpt2_distributed.py:356-362.
DEFAULT_WEIGHT_DECAY = 0.1
DEFAULT_BETAS = (0.9, 0.95)
DEFAULT_EPS = 1e-8


def make_optimizer(
    learning_rate: float | optax.Schedule,
    weight_decay: float = DEFAULT_WEIGHT_DECAY,
    b1: float = DEFAULT_BETAS[0],
    b2: float = DEFAULT_BETAS[1],
    eps: float = DEFAULT_EPS,
) -> optax.GradientTransformation:
    return optax.adamw(
        learning_rate, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay
    )


class StepMetrics(NamedTuple):
    loss: jnp.ndarray       # scalar fp32, mean over micro-batches (global across devices)
    grad_norm: jnp.ndarray  # scalar fp32, global L2 norm of the accumulated grad


class GuardedStepMetrics(NamedTuple):
    """StepMetrics plus the anomaly-guard telemetry (guard=True steps)."""

    loss: jnp.ndarray
    grad_norm: jnp.ndarray
    skipped_steps: jnp.ndarray  # int32, cumulative updates skipped (post-step)
    skip_reason: jnp.ndarray    # int32 SKIP_* code for THIS step; 0 = applied
    clipped_steps: jnp.ndarray  # int32, cumulative clipped-then-applied steps
    clipped: jnp.ndarray        # int32, 1 iff THIS step was clip-applied


def _make_accumulate_grads(
    config: GPT2Config,
    compute_dtype: jnp.dtype,
    unroll_accum: bool,
    accum_dtype: jnp.dtype | None,
    grad_shardings: Any = None,
) -> Callable:
    """Build the loss->grad->accumulate closure shared by the train and
    accum-only steps. ``grad_shardings`` (a param-shaped NamedSharding tree)
    constrains the post-scan accumulated gradient — the ``--shard_update``
    hook: with the data-sharded update placement, GSPMD turns the gradient
    all-reduce into a reduce-scatter and ``optax.global_norm`` below it into
    per-shard partial square-sums plus one scalar psum. The constraint sits
    OUTSIDE the micro-batch scan on purpose: gradients still cross the
    network once per optimizer step, never per micro-batch."""

    def accumulate_grads(params, x, y, rng, step_idx, loss_scale=None):
        step_rng = jax.random.fold_in(rng, step_idx)
        accum = x.shape[0]

        # Pre-scale the loss by 1/accum INSIDE the differentiated function —
        # the reference's `loss = loss / grad_accum_steps` before backward
        # (/root/reference/train_gpt2_distributed.py:409) — so accumulated
        # grads are Σ(g_i/accum) in torch's accumulation order, and no
        # separate full-tree division pass runs after the scan (a 124M-param
        # read+write per step). The backward seed scalar absorbs the scale
        # for free.
        inv_accum = 1.0 / accum

        def loss_fn(params, x, y, rng, scale):
            _, loss = gpt2.forward(
                params, config, x, labels=y,
                rng=rng, deterministic=False, compute_dtype=compute_dtype,
            )
            if scale is not None:
                # Guard-mode fault-injection hook: all-ones in production, so
                # the multiply is a no-op the guard pays for its testability.
                loss = loss * scale
            return loss * inv_accum

        grad_fn = jax.value_and_grad(loss_fn)

        def micro_step(carry, inp):
            grad_acc, loss_acc = carry
            if loss_scale is None:
                xb, yb, i = inp
                scale = None
            else:
                xb, yb, i, scale = inp
            micro_rng = jax.random.fold_in(step_rng, i)
            loss, grads = grad_fn(params, xb, yb, micro_rng, scale)
            grad_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(a.dtype), grad_acc, grads
            )
            return (grad_acc, loss_acc + loss), None

        # The accumulator seeds with a zeros tree rather than peeling
        # micro-batch 0 out of the loop: peeling was measured 2% SLOWER
        # whole-step at 124M b8a8 on v5e — duplicating the micro-step HLO
        # outside the scan costs more in scheduling than the skipped
        # zeros-init round-trip saves.
        zero_grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, accum_dtype or p.dtype), params
        )
        carry = (zero_grads, jnp.zeros((), jnp.float32))
        if unroll_accum:
            # Unrolled micro-batch loop: XLA can overlap micro-batch i's
            # loss/backward tail with micro-batch i+1's forward — the same
            # cross-boundary scheduling win as unrolling the layer scan
            # (PERF_ANALYSIS.md §3). HLO grows linearly in accum; use for
            # small accum counts on the perf path.
            for i in range(accum):
                inp = (x[i], y[i], jnp.asarray(i))
                if loss_scale is not None:
                    inp += (loss_scale[i],)
                carry, _ = micro_step(carry, inp)
        else:
            xs = (x, y, jnp.arange(accum))
            if loss_scale is not None:
                xs += (loss_scale,)
            carry, _ = jax.lax.scan(micro_step, carry, xs)
        grads, loss = carry
        # Upcast a reduced-precision carry before the norm and the AdamW
        # math — the rounding happened in accumulation; the update is fp32.
        grads = jax.tree_util.tree_map(
            lambda g, p: g.astype(p.dtype), grads, params
        )
        if grad_shardings is not None:
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        grad_norm = optax.global_norm(grads)
        return grads, loss, grad_norm

    return accumulate_grads


def make_train_step(
    config: GPT2Config,
    optimizer: optax.GradientTransformation,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    donate: bool = True,
    unroll_accum: bool = False,
    accum_dtype: jnp.dtype | None = None,
    guard: bool = False,
    clip_threshold: float | None = None,
    layer_clip_norm: float = 1.0,
    sharded_update: Any = None,
) -> Callable:
    """Build the jitted train step.

    Signature of the returned function::

        new_params, new_opt_state, metrics = step(
            params, opt_state, x, y, rng, step_idx)

    where ``x, y`` are int32 ``[grad_accum, micro_batch, seq_len]`` and ``rng``
    is a per-run PRNG key (per-step dropout keys are derived by folding in
    ``step_idx`` and the micro-batch index, so resume from a checkpoint
    reproduces the same dropout masks).

    Works under any sharding: batch sharded over the mesh makes the loss/grads
    global automatically (XLA inserts the psum), params sharded over 'fsdp'
    makes this the ZeRO-3 schedule. Params and opt_state buffers are donated —
    the update is in-place in HBM, like the reference's fused optimizer.

    ``accum_dtype`` sets the cross-micro-batch gradient accumulator's dtype
    (None = the params' fp32 — torch-autocast parity, where ``.grad`` stays
    fp32). ``jnp.bfloat16`` halves the accumulator carry — the knob that
    gives single-chip 774M any accum > 1 at all (the fp32 carry alone is
    3.1 GiB, PRESETS_MEMORY.md) — similar in spirit to (not the same
    rounding as) the reference FSDP's bf16 gradient handling: torch's
    ``MixedPrecision(reduce_dtype=bf16)``
    (``/root/reference/train_gpt2_distributed.py:151-155``) is a ONE-SHOT
    cross-rank reduction of each backward's grads, whereas this carry is a
    *sequential running bf16 sum* over up to ``accum`` micro-steps of
    1/accum-scaled grads — later addends lose low-order bits against a
    growing carry, so the rounding compounds with depth (and accum counts
    deeper than the measured 8 widen the bound further). Opt-in (CLI/bench
    ``--accum_dtype bf16``): expect ~1e-2-relative gradient rounding
    (pinned by ``test_bf16_accum_tracks_fp32_accum``); the AdamW update
    itself still runs on fp32 (the carry is upcast before
    ``optimizer.update``).

    ``guard=True`` builds the resilient production step (``resilience.py``
    layer 1): signature becomes ::

        new_params, new_opt_state, new_guard_state, metrics = step(
            params, opt_state, guard_state, x, y, rng, step_idx, loss_scale)

    where ``guard_state`` is a :class:`resilience.GuardState` and
    ``loss_scale`` is a ``[grad_accum]`` fp32 vector multiplied into each
    micro-batch's loss (all-ones in production; ``--inject_nan_at`` poisons
    one entry to fault-inject a non-finite step). The optimizer update is
    ``lax.cond``-gated on ``isfinite(loss) & isfinite(grad_norm)``: a
    non-finite step returns params/opt-state *bit-unchanged* (identity
    update), bumps ``skipped_steps`` and records the SKIP_* reason code —
    both also mirrored into :class:`GuardedStepMetrics` so the host can read
    them with the usual one-step lag without touching the donated state.

    ``clip_threshold`` (guard mode only) adds the middle response between
    "apply as-is" and "skip outright" (ROADMAP resilience item c): a step
    whose gradient is *finite* but whose global norm exceeds the threshold
    is not discarded — each gradient leaf ("layer") is clipped to L2 norm
    ``layer_clip_norm`` and the update applies. Per-layer rather than global
    rescale: a single exploding layer (the common case — one attention block
    hitting a bad batch) is tamed without crushing every other layer's
    signal by the shared global factor. Non-finite values still skip — no
    amount of rescaling repairs a NaN. Clipped steps count in
    ``clipped_steps`` (GuardState + metrics), not ``skipped_steps``.

    ``sharded_update`` (a ``sharding.ShardedUpdateSpec``) enables the
    ZeRO-2-style cross-replica sharded weight update (``--shard_update``):
    the accumulated gradient is constrained to the data-sharded update
    placement (reduce-scatter), AdamW runs on 1/data-sized gradient/moment
    shards (weight decay slices the replicated params for free), and the
    fresh params are constrained back to the steady-state placement
    (all-gather) — applied AFTER the guard's ``lax.switch``, so all three
    branches unify under one constraint and the identity (skip) branch stays
    a bit-identical no-op (its inputs already carry exactly these
    shardings). Composes with ``accum_dtype`` (the constraint sits after the
    fp32 upcast) and with per-layer clip (clip_leaf's per-leaf norm becomes
    a sharded partial-sum + psum, same value).
    """

    grad_shardings = (
        sharded_update.grads if sharded_update is not None else None
    )
    accumulate_grads = _make_accumulate_grads(
        config, compute_dtype, unroll_accum, accum_dtype, grad_shardings
    )

    def constrain_state(new_params, new_opt_state):
        if sharded_update is None:
            return new_params, new_opt_state
        return (
            jax.lax.with_sharding_constraint(
                new_params, sharded_update.params
            ),
            jax.lax.with_sharding_constraint(
                new_opt_state, sharded_update.opt_state
            ),
        )

    if not guard:

        def train_step(params, opt_state, x, y, rng, step_idx):
            grads, loss, grad_norm = accumulate_grads(params, x, y, rng, step_idx)
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            new_params, new_opt_state = constrain_state(
                new_params, new_opt_state
            )
            return new_params, new_opt_state, StepMetrics(
                loss=loss, grad_norm=grad_norm
            )

        return jax.jit(train_step, donate_argnums=(0, 1) if donate else ())

    from gpt_2_distributed_tpu.resilience import (
        GuardState,
        SKIP_NONFINITE_GRAD,
        SKIP_NONFINITE_LOSS,
    )

    def guarded_train_step(
        params, opt_state, guard_state, x, y, rng, step_idx, loss_scale
    ):
        grads, loss, grad_norm = accumulate_grads(
            params, x, y, rng, step_idx, loss_scale
        )
        loss_ok = jnp.isfinite(loss)
        finite = jnp.logical_and(loss_ok, jnp.isfinite(grad_norm))
        if clip_threshold is not None:
            huge = jnp.logical_and(finite, grad_norm > clip_threshold)
        else:
            huge = jnp.zeros((), bool)
        ok = jnp.logical_and(finite, jnp.logical_not(huge))

        def apply_update(_):
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_opt_state

        def clip_apply_update(_):
            # Finite-but-huge gradient: clip each leaf to L2 norm
            # `layer_clip_norm` and apply. eps in the denominator guards the
            # all-zero leaf (norm 0 -> scale capped at 1 anyway, but 0/0
            # would poison it with NaN).
            def clip_leaf(g):
                norm = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                scale = jnp.minimum(
                    1.0, layer_clip_norm / jnp.maximum(norm, 1e-12)
                )
                return (g * scale.astype(g.dtype))

            clipped = jax.tree_util.tree_map(clip_leaf, grads)
            updates, new_opt_state = optimizer.update(
                clipped, opt_state, params
            )
            return optax.apply_updates(params, updates), new_opt_state

        def identity_update(_):
            # Skipped step: params AND opt-state bit-unchanged — optax's
            # internal step count does not advance either, so the skipped
            # step is invisible to moment bias-correction and schedules.
            return params, opt_state

        # branch 0 = apply, 1 = clip+apply, 2 = skip. lax.switch (not nested
        # cond) so only the selected update's HLO runs.
        branch = jnp.where(ok, 0, jnp.where(huge, 1, 2)).astype(jnp.int32)
        new_params, new_opt_state = jax.lax.switch(
            branch,
            [apply_update, clip_apply_update, identity_update],
            None,
        )
        new_params, new_opt_state = constrain_state(new_params, new_opt_state)
        skipped = (branch == 2).astype(jnp.int32)
        clipped_now = (branch == 1).astype(jnp.int32)
        # A non-finite grad_norm under a finite loss (0*inf in the backward)
        # is distinguished from a non-finite loss itself.
        reason = jnp.where(
            branch != 2,
            0,
            jnp.where(loss_ok, SKIP_NONFINITE_GRAD, SKIP_NONFINITE_LOSS),
        ).astype(jnp.int32)
        new_guard = GuardState(
            skipped_steps=guard_state.skipped_steps + skipped,
            last_skip_reason=jnp.where(
                branch != 2, guard_state.last_skip_reason, reason
            ).astype(jnp.int32),
            clipped_steps=guard_state.clipped_steps + clipped_now,
        )
        # Counters are duplicated into the metrics: guard_state is donated
        # into the NEXT step before the host reads metrics (one-step lag), so
        # the metrics copy is the only safely-readable one.
        metrics = GuardedStepMetrics(
            loss=loss,
            grad_norm=grad_norm,
            skipped_steps=new_guard.skipped_steps,
            skip_reason=reason,
            clipped_steps=new_guard.clipped_steps,
            clipped=clipped_now,
        )
        return new_params, new_opt_state, new_guard, metrics

    return jax.jit(
        guarded_train_step, donate_argnums=(0, 1, 2) if donate else ()
    )


def make_eval_step(
    config: GPT2Config, compute_dtype: jnp.dtype = jnp.bfloat16
) -> Callable:
    """Jitted eval loss on a [B, T] batch (no dropout, no update)."""

    def eval_step(params, x, y):
        _, loss = gpt2.forward(
            params, config, x, labels=y, deterministic=True,
            compute_dtype=compute_dtype,
        )
        return loss

    return jax.jit(eval_step)
