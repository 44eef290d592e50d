"""Component-level step-time breakdown on the attached device.

Times the full train step and ablations (dense vs flash attention, dropout
on/off, fwd-only) to locate where the MFU gap lives. Round-2 follow-up to
round 1's 30.1% MFU finding (VERDICT.md weak-point #1).

Usage: python scripts/profile_breakdown.py [--batch 8] [--steps 20]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from gpt_2_distributed_tpu.config import MODEL_PRESETS
from gpt_2_distributed_tpu.models import gpt2
from gpt_2_distributed_tpu.ops.attention import causal_attention
from gpt_2_distributed_tpu.ops.flash_attention import flash_attention
from gpt_2_distributed_tpu.parallel.train_step import make_optimizer, make_train_step
from gpt_2_distributed_tpu.utils.flops import device_peak_flops, flops_per_token


def _sync(out):
    """Force completion of everything enqueued: a device->host read of one
    element of the last output (the TPU stream is in-order, so this transitively
    waits on all prior dispatches) — the same sync bench.py uses."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(jnp.sum(leaf))


def timeit(fn, *args, steps=20, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / steps


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="124M")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq_len", type=int, default=1024)
    p.add_argument("--steps", type=int, default=20)
    args = p.parse_args()

    config = MODEL_PRESETS[args.model]
    b, t = args.batch, args.seq_len
    c, h, d = config.n_embd, config.n_head, config.head_dim
    rng = np.random.default_rng(0)
    peak = device_peak_flops() or float("nan")
    fpt = flops_per_token(config, t)

    def report(name, dt, tokens=b * t, flops=None):
        flops = flops if flops is not None else tokens * fpt
        print(f"{name:<42} {dt*1e3:8.2f} ms   {flops/dt/1e12:7.1f} TF/s "
              f"({flops/dt/peak*100:5.1f}% of peak)")

    # --- full train step variants -----------------------------------------
    x = jnp.asarray(rng.integers(0, config.vocab_size, (1, b, t), dtype=np.int32))
    y = jnp.asarray(rng.integers(0, config.vocab_size, (1, b, t), dtype=np.int32))
    key = jax.random.PRNGKey(0)

    for name, cfg in [
        ("step flash+dropout (prod)", config),
        ("step flash no-dropout", config.replace(
            embd_dropout=0.0, attn_dropout=0.0, resid_dropout=0.0)),
        ("step dense+dropout", config.replace(attention_impl="dense")),
        ("step flash+dropout remat", config.replace(remat=True)),
    ]:
        try:
            params = gpt2.init_params(cfg)
            opt = make_optimizer(1e-4)
            opt_state = opt.init(params)
            step = make_train_step(cfg, opt, donate=False)
            dt = timeit(lambda: step(params, opt_state, x, y, key, 0),
                        steps=args.steps)
            report(name, dt)
        except Exception as e:  # noqa: BLE001 — OOM on some variants is expected
            print(f"{name:<42} FAILED: {type(e).__name__} (likely HBM OOM)")
        finally:
            params = opt_state = step = None

    # --- forward only ------------------------------------------------------
    params = gpt2.init_params(config)
    fwd = jax.jit(lambda p, xx, yy: gpt2.forward(
        p, config, xx, labels=yy, deterministic=True)[1])
    dt = timeit(lambda: fwd(params, x[0], y[0]), steps=args.steps)
    report("fwd only (no dropout, flash)", dt, flops=b * t * fpt / 3)

    # --- attention kernels in isolation ------------------------------------
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.bfloat16)
    # attention matmul flops per layer: 2 matmuls fwd (qk^T, pv) = 2*2*B*H*T^2*D
    attn_fwd_flops = 2 * 2 * b * h * t * t * d
    key2 = jax.random.PRNGKey(1)

    flash_f = jax.jit(lambda q, k, v: flash_attention(q, k, v))
    dt = timeit(lambda: flash_f(q, k, v), steps=args.steps)
    report("flash fwd (1 layer, no drop)", dt, flops=attn_fwd_flops)

    dense_f = jax.jit(lambda q, k, v: causal_attention(q, k, v))
    dt = timeit(lambda: dense_f(q, k, v), steps=args.steps)
    report("dense fwd (1 layer, no drop)", dt, flops=attn_fwd_flops)

    def flash_vjp(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v), q, k, v)
        return vjp(out)

    dt = timeit(jax.jit(flash_vjp), q, k, v, steps=args.steps)
    report("flash fwd+bwd (1 layer)", dt, flops=3 * attn_fwd_flops)

    def flash_drop(q, k, v):
        return flash_attention(q, k, v, dropout_rate=0.1,
                               rng=key2, deterministic=False)

    dt = timeit(jax.jit(flash_drop), q, k, v, steps=args.steps)
    report("flash fwd dropout (1 layer)", dt, flops=attn_fwd_flops)

    # --- matmul roofline sanity -------------------------------------------
    a_ = jnp.asarray(rng.standard_normal((8192, 8192)), jnp.bfloat16)
    b_ = jnp.asarray(rng.standard_normal((8192, 8192)), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    dt = timeit(lambda: mm(a_, b_), steps=args.steps)
    report("bf16 8k matmul roofline", dt, flops=2 * 8192**3)


if __name__ == "__main__":
    main()
