"""Isolated flash-attention kernel microbenchmark (dispatch-free).

Chains N kernel applications inside one jitted lax.scan so per-dispatch
latency cannot pollute the measurement.
Reports achieved TF/s against the causal-useful FLOPs.

Usage: python scripts/bench_attention.py [--batch 8] [--block_q 512] [--bwd]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from gpt_2_distributed_tpu.ops.attention import causal_attention
from gpt_2_distributed_tpu.ops.flash_attention import flash_attention


def chained(fn, q, k, v, n):
    """q_{i+1} = normalize(fn(q_i, k, v)): every iteration depends on the
    last, so the device executes n sequential kernel calls inside one jit."""

    def body(qc, _):
        o = fn(qc, k, v)
        qc = (o * 0.5 + qc * 0.5).astype(qc.dtype)
        return qc, ()

    out, _ = jax.lax.scan(body, q, length=n)
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--head_dim", type=int, default=64)
    p.add_argument("--iters", type=int, default=50)
    p.add_argument("--block_q", type=int, default=None)
    p.add_argument("--block_k", type=int, default=None)
    p.add_argument("--dropout", type=float, default=0.0)
    p.add_argument("--impl", default="flash", choices=["flash", "dense", "block"])
    p.add_argument("--bwd", action="store_true", help="time fwd+bwd")
    args = p.parse_args()

    B, H, T, D = args.batch, args.heads, args.seq, args.head_dim
    r = np.random.default_rng(0)
    q = jnp.asarray(r.standard_normal((B, H, T, D)), jnp.bfloat16)
    k = jnp.asarray(r.standard_normal((B, H, T, D)), jnp.bfloat16)
    v = jnp.asarray(r.standard_normal((B, H, T, D)), jnp.bfloat16)
    key = jax.random.PRNGKey(0)

    if args.impl == "block":
        # Ring-step microbench (round-4 VERDICT item 4 "done" criterion):
        # one ring step = one flash_block call at local shapes. Time the
        # fully-unmasked off-diagonal case (the common ring step, FULL TxT
        # work) and report TF/s against those dense-useful flops — compare
        # with --impl flash at the same T (causal-useful accounting).
        from gpt_2_distributed_tpu.ops.flash_block import flash_block

        def base(q, k, v):
            o, lse = flash_block(
                q, k, v, jnp.int32(T), jnp.int32(0),
                seed=jax.random.randint(key, (1,), 0, 2**31 - 1, jnp.int32),
                dropout_rate=args.dropout,
                block_q=args.block_q, block_k=args.block_k,
            )
            # Depend on both outputs, BOUNDEDLY: lse is linear in |q|, so
            # feeding it raw into the chained q update diverges to inf/NaN
            # within ~50 iterations and the bench would time NaN operands.
            return o + (jnp.tanh(lse) * 1e-3).astype(o.dtype)

    elif args.impl == "flash":
        det = args.dropout == 0.0
        base = lambda q, k, v: flash_attention(
            q, k, v, dropout_rate=args.dropout, rng=key,
            deterministic=det, block_q=args.block_q, block_k=args.block_k)
    else:
        base = lambda q, k, v: causal_attention(q, k, v)

    if args.bwd:
        def fn(q, k, v):
            out, vjp = jax.vjp(base, q, k, v)
            dq, dk, dv = vjp(out)
            return dq
        n_mm = 3  # fwd 2 dots counted once; bwd ~4 dots => report vs 3x fwd
    else:
        fn = base
        n_mm = 1

    # Marginal timing: run n and 2n chained iterations and difference them,
    # cancelling the fixed dispatch+sync cost per run().
    def timed(n):
        run = jax.jit(lambda q: chained(fn, q, k, v, n))
        out = run(q)
        float(jnp.sum(out.astype(jnp.float32)))  # warm + full sync
        t0 = time.perf_counter()
        out = run(q)
        float(jnp.sum(out.astype(jnp.float32)))
        return time.perf_counter() - t0

    t1 = timed(args.iters)
    t2 = timed(args.iters * 2)
    dt = (t2 - t1) / args.iters

    useful = 1.0 if args.impl == "block" else 0.5  # block: full TxT work
    causal_flops = n_mm * 2 * 2 * B * H * T * T * D * useful
    print(
        f"{args.impl} block_q={args.block_q} block_k={args.block_k} "
        f"dropout={args.dropout} "
        f"bwd={args.bwd}: {dt*1e3:.3f} ms/call (marginal)  "
        f"{causal_flops/dt/1e12:.1f} TF/s causal-useful"
    )


if __name__ == "__main__":
    main()
