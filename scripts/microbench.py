"""Dispatch-amortized per-component microbenchmarks of the train step.

Each timed call is CHAINED on its predecessor's output (y = f(y, ...)), so the
host enqueues far ahead of the device and per-dispatch latency does not
floor the measurement (single-shot timings of a component are
dispatch-bound).

Two conventions encoded here:
* big arrays are passed as jit ARGUMENTS, never closures — closed-over arrays
  are baked into the HLO as constants;
* sync is a device->host ``float()`` read, the same as bench.py.

Usage: PYTHONPATH=.:$PYTHONPATH python -u scripts/microbench.py [--batch 8]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from gpt_2_distributed_tpu.config import MODEL_PRESETS
from gpt_2_distributed_tpu.models import gpt2
from gpt_2_distributed_tpu.ops.flash_attention import flash_attention
from gpt_2_distributed_tpu.ops.losses import blocked_cross_entropy
from gpt_2_distributed_tpu.parallel.train_step import make_optimizer
from gpt_2_distributed_tpu.utils.flops import device_peak_flops


def chain_time(fn, y0, steps=15, warmup=3):
    """Time y = fn(y) chained so the device stays busy; returns sec/call."""
    y = y0
    for _ in range(warmup):
        y = fn(y)
    float(jnp.sum(jax.tree_util.tree_leaves(y)[0]))
    t0 = time.perf_counter()
    for _ in range(steps):
        y = fn(y)
    float(jnp.sum(jax.tree_util.tree_leaves(y)[0]))
    return (time.perf_counter() - t0) / steps


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="124M")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq_len", type=int, default=1024)
    p.add_argument("--steps", type=int, default=15)
    args = p.parse_args()

    config = MODEL_PRESETS[args.model]
    b, t, c = args.batch, args.seq_len, config.n_embd
    h, d, v = config.n_head, config.head_dim, config.vocab_size
    n = b * t
    rng = np.random.default_rng(0)
    peak = device_peak_flops() or float("nan")

    def report(name, dt, flops=None, bytes_=None):
        line = f"{name:<40} {dt*1e3:8.3f} ms"
        if flops:
            line += f"  {flops/dt/1e12:7.1f} TF/s ({flops/dt/peak*100:5.1f}% peak)"
        if bytes_:
            line += f"  {bytes_/dt/1e9:6.0f} GB/s"
        print(line, flush=True)

    key = jax.random.PRNGKey(0)
    params = gpt2.init_params(config)
    block_params = jax.device_put(params["block"])
    lrngs = jax.random.split(key, config.n_layer)

    # ---- layer stack only (no embed, no CE): fwd and fwd+bwd ----------------
    xin = jnp.asarray(rng.standard_normal((b, t, c)), jnp.bfloat16)

    def stack_fwd(x, bp, deterministic, cfg):
        def body(carry, layer):
            lp, lr = layer
            return gpt2._block(cfg, carry, lp, lr, deterministic), None
        out, _ = jax.lax.scan(body, x, (bp, lrngs))
        return out

    lin_f = 2 * n * 12 * c * c * config.n_layer
    att_f = 4 * b * h * t * t * d * config.n_layer

    import functools
    fwd_drop = jax.jit(functools.partial(
        stack_fwd, deterministic=False, cfg=config))
    report("stack fwd (drop on)",
           chain_time(lambda x: fwd_drop(x, block_params), xin, args.steps),
           lin_f + att_f)

    def stack_grad(x, bp, deterministic, cfg):
        return jax.grad(lambda xx: jnp.sum(
            stack_fwd(xx, bp, deterministic, cfg).astype(jnp.float32)))(x)

    bwd_drop = jax.jit(functools.partial(
        stack_grad, deterministic=False, cfg=config))
    report("stack fwd+bwd (drop on)",
           chain_time(lambda x: bwd_drop(x, block_params), xin, args.steps),
           3 * (lin_f + att_f))

    cfg_nod = config.replace(attn_dropout=0.0, resid_dropout=0.0, embd_dropout=0.0)
    fwd_nod = jax.jit(functools.partial(
        stack_fwd, deterministic=True, cfg=cfg_nod))
    report("stack fwd (drop off)",
           chain_time(lambda x: fwd_nod(x, block_params), xin, args.steps),
           lin_f + att_f)
    bwd_nod = jax.jit(functools.partial(
        stack_grad, deterministic=True, cfg=cfg_nod))
    report("stack fwd+bwd (drop off)",
           chain_time(lambda x: bwd_nod(x, block_params), xin, args.steps),
           3 * (lin_f + att_f))

    # ---- blocked CE ---------------------------------------------------------
    xce = jnp.asarray(rng.standard_normal((n, c)), jnp.bfloat16)
    wte = jax.device_put(params["wte"].astype(jnp.bfloat16))
    labels = jnp.asarray(rng.integers(0, v, (n,), np.int32))
    ce_f = 2 * n * c * v

    ce_fwd = jax.jit(lambda x, w, lb: x * (
        1 + 0 * blocked_cross_entropy(x, w, lb)).astype(x.dtype))
    report("blocked CE fwd",
           chain_time(lambda x: ce_fwd(x, wte, labels), xce, args.steps), ce_f)

    def ce_bwd(x, w, lb):
        l, gr = jax.value_and_grad(
            lambda xx: blocked_cross_entropy(xx, w, lb))(x)
        return x + gr.astype(x.dtype) * 0 + 0 * l.astype(x.dtype)

    ce_bwd_j = jax.jit(ce_bwd)
    report("blocked CE fwd+bwd (dx only)",
           chain_time(lambda x: ce_bwd_j(x, wte, labels), xce, args.steps),
           4 * ce_f)

    def ce_bwd_full(x, w, lb):
        l, (gx, gw) = jax.value_and_grad(
            lambda xx, ww: blocked_cross_entropy(xx, ww, lb), (0, 1))(x, w)
        return x + gx.astype(x.dtype) * 0 + 0 * l.astype(x.dtype)

    ce_bwdf_j = jax.jit(ce_bwd_full)
    report("blocked CE fwd+bwd (dx+dwte)",
           chain_time(lambda x: ce_bwdf_j(x, wte, labels), xce, args.steps),
           4 * ce_f)

    # ---- flash attention, chained -------------------------------------------
    qkv_shape = (b, h, t, d)
    q0 = jnp.asarray(rng.standard_normal(qkv_shape), jnp.bfloat16)
    k0 = jnp.asarray(rng.standard_normal(qkv_shape), jnp.bfloat16)
    v0 = jnp.asarray(rng.standard_normal(qkv_shape), jnp.bfloat16)
    afwd = 4 * b * h * t * t * d  # full-square count (causal skips ~half)

    fa = jax.jit(lambda q, k, vv: flash_attention(q, k, vv))
    report("flash fwd (1 layer)",
           chain_time(lambda q: fa(q, k0, v0), q0, args.steps), afwd)

    def fa_bwd(q, k, vv):
        o, vjp = jax.vjp(lambda qq: flash_attention(qq, k, vv), q)
        return vjp(o)[0]

    fab = jax.jit(fa_bwd)
    report("flash fwd+bwd (1 layer)",
           chain_time(lambda q: fab(q, k0, v0), q0, args.steps), 3 * afwd)

    fad = jax.jit(lambda q, k, vv: flash_attention(
        q, k, vv, dropout_rate=0.1, rng=key, deterministic=False))
    report("flash fwd dropout (1 layer)",
           chain_time(lambda q: fad(q, k0, v0), q0, args.steps), afwd)

    # ---- embedding gather fwd + scatter-add bwd -----------------------------
    idx = jnp.asarray(rng.integers(0, v, (b, t), np.int32))

    def embed_roundtrip(w, ix):
        e = w.astype(jnp.bfloat16).at[ix].get(mode="clip")
        gr = jax.grad(lambda ww: jnp.sum(
            ww.astype(jnp.bfloat16).at[ix].get(mode="clip").astype(jnp.float32)
            * e.astype(jnp.float32)))(w)
        return w + 0 * gr

    emb = jax.jit(embed_roundtrip)
    report("embed gather + scatter-add bwd",
           chain_time(lambda w: emb(w, idx), params["wte"], args.steps),
           bytes_=3 * v * c * 4)

    # ---- AdamW update -------------------------------------------------------
    opt = make_optimizer(1e-4)
    opt_state = jax.device_put(opt.init(params))
    grads = jax.device_put(jax.tree_util.tree_map(
        lambda a: jnp.full_like(a, 1e-6), params))
    nparams = gpt2.count_params(params)

    def adamw(carry, g):
        ps, st = carry
        upd, st2 = opt.update(g, st, ps)
        return optax.apply_updates(ps, upd), st2

    ad = jax.jit(adamw)
    report("adamw update (fp32, full model)",
           chain_time(lambda cy: ad(cy, grads),
                      (jax.device_put(params), opt_state), args.steps),
           bytes_=nparams * 4 * 7)

    # ---- fp32 -> bf16 cast of all params ------------------------------------
    cast = jax.jit(lambda ps: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), ps))
    report("param fp32->bf16->fp32 roundtrip",
           chain_time(cast, jax.device_put(params), args.steps),
           bytes_=nparams * (4 + 2 + 2 + 4))

    # ---- big matmul roofline, chained ---------------------------------------
    a0 = jnp.asarray(rng.standard_normal((8192, 8192)), jnp.bfloat16)
    w0 = jnp.asarray(rng.standard_normal((8192, 8192)), jnp.bfloat16)
    mm = jax.jit(lambda a, w: (a @ w) * jnp.bfloat16(1e-2))
    report("bf16 8k matmul (chained)",
           chain_time(lambda a: mm(a, w0), a0, args.steps), 2 * 8192 ** 3)


if __name__ == "__main__":
    main()
