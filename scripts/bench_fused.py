"""Marginal microbenchmark: fused layer-epilogue kernels vs unfused JAX.

The fused kernels (``ops/fused_layer.py``) attack the between-matmul
bandwidth gap PERF_ANALYSIS.md identified: each LN/residual/dropout junction
and the MLP bias+GELU+dropout epilogue re-reads its activations from HBM per
elementwise op when XLA fails to fuse across the custom_vjp boundary. This
script measures whether the Pallas fusions actually beat the unfused
composition, per op, using the roofline marginal method (scripts/roofline.py
§ timing methodology):

* the iteration loop runs INSIDE one jit via ``lax.fori_loop`` with the
  output fed back as input (data-dependent, nothing collapses);
* ``outer`` calls issue back-to-back with ONE final sync;
* the whole procedure runs at ``inner`` and ``2*inner`` applications and the
  two times are differenced, cancelling every constant per-run cost
  (dispatch floor, final sync);
* leg order alternates across ``repeats`` pairs and the median is taken.

Each op is timed fused and unfused at identical shapes/dtypes, forward-only
and forward+backward (grad of a sum), and the per-application marginal time
is converted to effective GB/s under the op's minimal-traffic model
(LN+resid reads x,o and writes r,y -> 4·N·C·itemsize; resid reads x,o writes
r -> 3·; bias+GELU reads h writes out -> 2·, bias negligible).

The matmul+epilogue kernels (``ops/fused_matmul.py``) are timed the same
way: qkv (x[N,C]@[C,3C]+b), fc (matmul+bias+GELU+dropout, [C,4C]) and proj
(matmul+bias+residual+dropout, [C,C]). Their minimal traffic is
(N·K + K·M + N·M)·itemsize, plus N·M·itemsize for the proj op's residual
read and N·M·4 for the fc op's fp32 pre-activation stash; matmul legs
additionally report TF/s (2·N·K·M flops over the fwd marginal), the number
that says whether the fused kernel keeps the MXU fed.

On CPU this runs the kernels in ``interpret=True`` mode — the numbers there
say nothing about TPU bandwidth (interpret mode is a Python-level emulation,
orders of magnitude slower than the XLA unfused path) but prove the
measurement harness end-to-end; ``--assert_ran`` exits nonzero unless every
op produced a timing. Sub-resolution marginals (possible for tiny CPU
shapes) record ``null`` GB/s rather than failing. On a real chip, run with
the defaults (rows 8192 = bench operating point, width 768 = 124M C) and
paste the table into PERF_ANALYSIS.md § fused epilogues.

Usage: python scripts/bench_fused.py [--out FUSED_BENCH.json]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=None, help="also write full JSON here")
    p.add_argument("--rows", type=int, default=None,
                   help="row count N (default: 8192 on TPU, 256 on CPU)")
    p.add_argument("--width", type=int, default=None,
                   help="feature width C (default: 768 on TPU, 256 on CPU; "
                   "the GELU op runs at 4x this width)")
    p.add_argument("--dtype", default=None, choices=["bf16", "fp32"],
                   help="activation dtype (default: bf16 on TPU, fp32 on CPU)")
    p.add_argument("--rate", type=float, default=0.1, help="dropout rate")
    p.add_argument("--outer", type=int, default=4)
    p.add_argument("--inner", type=int, default=8)
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--assert_ran", action="store_true",
                   help="exit nonzero unless every op produced a timing")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from gpt_2_distributed_tpu.ops.activations import gelu_tanh
    from gpt_2_distributed_tpu.ops.fused_layer import (
        fused_bias_gelu_dropout,
        fused_ln_residual_dropout,
        fused_residual_dropout,
    )
    from gpt_2_distributed_tpu.ops.fused_matmul import (
        matmul_bias,
        matmul_bias_gelu_dropout,
        matmul_bias_residual_dropout,
    )
    from gpt_2_distributed_tpu.ops.layers import dropout, layer_norm

    on_tpu = jax.devices()[0].platform == "tpu"
    rows = args.rows or (8192 if on_tpu else 256)
    width = args.width or (768 if on_tpu else 256)
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[
        args.dtype or ("bf16" if on_tpu else "fp32")
    ]
    rate = args.rate
    itemsize = jnp.dtype(dtype).itemsize

    rng_np = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)

    def arr(*shape):
        return jnp.asarray(rng_np.normal(size=shape) * 0.1, dtype)

    def time_marginal(jitted, operands, rewrap):
        """Median marginal seconds per application, or None when the pair
        differences to <= 0 (sub-resolution op; expected for tiny CPU
        shapes on the unfused leg)."""

        def run_once(inner):
            ops = operands[:-1] + (inner,)
            y = jitted(*ops)  # compile (cached after first pair) + warm
            jax.block_until_ready(y)
            t0 = time.perf_counter()
            for _ in range(args.outer):
                ops = rewrap(y, ops)
                y = jitted(*ops)
            jax.block_until_ready(y)
            return time.perf_counter() - t0

        marginals = []
        for r in range(args.repeats):
            if r % 2 == 0:
                t1 = run_once(args.inner)
                t2 = run_once(2 * args.inner)
            else:
                t2 = run_once(2 * args.inner)
                t1 = run_once(args.inner)
            marginals.append((t2 - t1) / (args.outer * args.inner))
        dt = float(np.median(marginals))
        return dt if dt > 0 else None

    # Each op entry: (label, traffic_bytes, fused_fn, unfused_fn, operands).
    # The functions map their FIRST operand through to an output of the same
    # shape/dtype (chainable); the rest are captured parameters. Dropout runs
    # non-deterministic so the mask generation is part of what's timed.
    C, F = width, 4 * width
    scale = jnp.ones((C,), dtype)
    bias = jnp.zeros((C,), dtype)
    gbias = arr(F)

    def fused_ln(x, o):
        r, y = fused_ln_residual_dropout(
            x, o, scale, bias, rate=rate, rng=key, deterministic=False,
        )
        return r + y * jnp.asarray(0.5, dtype)

    def unfused_ln(x, o):
        r = x + dropout(o, rate, key, deterministic=False)
        y = layer_norm(r, scale, bias)
        return r + y * jnp.asarray(0.5, dtype)

    def fused_resid(x, o):
        return fused_residual_dropout(
            x, o, rate=rate, rng=key, deterministic=False,
        )

    def unfused_resid(x, o):
        return x + dropout(o, rate, key, deterministic=False)

    def fused_gelu(h):
        return fused_bias_gelu_dropout(
            h, gbias, rate=rate, rng=key, deterministic=False,
        )

    def unfused_gelu(h):
        u = h + gbias
        c0, a = 0.7978845608028654, 0.044715
        u32 = u.astype(jnp.float32)
        g = 0.5 * u32 * (1.0 + jnp.tanh(c0 * (u32 + a * u32**3)))
        return dropout(g.astype(h.dtype), rate, key, deterministic=False)

    # Matmul+epilogue operands. Widths follow the model legs at feature
    # width C (qkv C->3C, fc C->4C, proj C->C); all are multiples of 128 at
    # the defaults so the tiled kernels engage rather than falling back.
    # Each chained fn maps [N,C] -> [N,C] (wide outputs sliced back to C) so
    # the feedback loop stays data-dependent at a fixed shape.
    w_qkv, b_qkv = arr(C, 3 * C), arr(3 * C)
    w_fc, b_fc = arr(C, F), arr(F)
    w_pr, b_pr = arr(C, C), arr(C)
    r0 = arr(rows, C)

    def fused_mm_qkv(x):
        return matmul_bias(x, w_qkv, b_qkv)[:, :C]

    def unfused_mm_qkv(x):
        return (x @ w_qkv + b_qkv)[:, :C]

    def fused_mm_fc(x):
        return matmul_bias_gelu_dropout(
            x, w_fc, b_fc, rate=rate, rng=key, deterministic=False,
        )[:, :C]

    def unfused_mm_fc(x):
        return dropout(
            gelu_tanh(x @ w_fc + b_fc), rate, key, deterministic=False,
        )[:, :C]

    def fused_mm_proj(x):
        return matmul_bias_residual_dropout(
            x, w_pr, b_pr, r0, rate=rate, rng=key, deterministic=False,
        )

    def unfused_mm_proj(x):
        return r0 + dropout(x @ w_pr + b_pr, rate, key, deterministic=False)

    def mm_traffic(k, m, extra=0):
        return (rows * k + k * m + rows * m + extra) * itemsize

    two = jnp.asarray(2.0, dtype)
    ops = {
        # y feeds x, o stays fixed: chainable and data-dependent.
        "ln_residual_dropout": dict(
            traffic=4 * rows * C * itemsize,
            fused=fused_ln, unfused=unfused_ln,
            operands=(arr(rows, C), arr(rows, C)),
            chain=lambda fn: (lambda x, o: fn(x, o) * jnp.asarray(0.5, dtype)),
        ),
        "residual_dropout": dict(
            traffic=3 * rows * C * itemsize,
            fused=fused_resid, unfused=unfused_resid,
            operands=(arr(rows, C), arr(rows, C)),
            chain=lambda fn: (lambda x, o: fn(x, o) * jnp.asarray(0.5, dtype)),
        ),
        "bias_gelu_dropout": dict(
            traffic=2 * rows * F * itemsize,
            fused=fused_gelu, unfused=unfused_gelu,
            # GELU saturates: double the (rate-rescaled, ~half-magnitude)
            # output to keep the chained values in the active region.
            operands=(arr(rows, F),),
            chain=lambda fn: (lambda h: fn(h) * two),
        ),
        "matmul_bias_qkv": dict(
            traffic=mm_traffic(C, 3 * C),
            flops=2 * rows * C * (3 * C),
            fused=fused_mm_qkv, unfused=unfused_mm_qkv,
            operands=(arr(rows, C),),
            chain=lambda fn: (lambda x: fn(x) * two),
        ),
        "matmul_bias_gelu_dropout_fc": dict(
            # + rows*F*4: the fused forward stashes the fp32 pre-activation
            # for the backward's in-kernel GELU-derivative recompute.
            traffic=mm_traffic(C, F, extra=0) + rows * F * 4,
            flops=2 * rows * C * F,
            fused=fused_mm_fc, unfused=unfused_mm_fc,
            operands=(arr(rows, C),),
            chain=lambda fn: (lambda x: fn(x) * two),
        ),
        "matmul_bias_residual_dropout_proj": dict(
            # + rows*C: the residual-stream read.
            traffic=mm_traffic(C, C, extra=rows * C),
            flops=2 * rows * C * C,
            fused=fused_mm_proj, unfused=unfused_mm_proj,
            operands=(arr(rows, C),),
            chain=lambda fn: (lambda x: fn(x) * two),
        ),
    }

    result = {
        "platform": jax.devices()[0].platform,
        "rows": rows, "width": C, "gelu_width": F,
        "dtype": str(jnp.dtype(dtype)), "dropout_rate": rate,
        "method": "marginal",
        "inner": args.inner, "outer": args.outer, "repeats": args.repeats,
        "note": (
            "interpret-mode kernel emulation; TPU-irrelevant timings"
            if not on_tpu else "on-chip"
        ),
        "measurements": {},
    }

    ran = missing = 0
    for name, spec in ops.items():
        entry = {}
        for variant in ("fused", "unfused"):
            chained = spec["chain"](spec[variant])
            n_ops = len(spec["operands"])

            @functools.partial(jax.jit, static_argnums=(n_ops,))
            def fwd_loop(*a, _fn=chained, _n=n_ops):
                ops_, inner = a[:_n], a[_n]
                def body(_, y):
                    return _fn(y, *ops_[1:])
                return jax.lax.fori_loop(0, inner, body, ops_[0])

            grad_fn = jax.grad(
                lambda *a, _fn=chained: jnp.sum(_fn(*a).astype(jnp.float32))
            )

            @functools.partial(jax.jit, static_argnums=(n_ops,))
            def fwdbwd_loop(*a, _g=grad_fn, _n=n_ops):
                ops_, inner = a[:_n], a[_n]
                def body(_, y):
                    return _g(y, *ops_[1:]).astype(y.dtype)
                return jax.lax.fori_loop(0, inner, body, ops_[0])

            rewrap = lambda y, ops_: (y,) + tuple(ops_[1:])
            for leg, jitted in (("fwd", fwd_loop), ("fwd_bwd", fwdbwd_loop)):
                dt = time_marginal(
                    jitted, spec["operands"] + (args.inner,), rewrap)
                ran += 1
                if dt is None:
                    missing += 1
                    entry[f"{variant}_{leg}"] = {"us": None, "gb_per_s": None}
                else:
                    # fwd+bwd moves ~2x the forward traffic (cotangents in,
                    # gradients out) — report raw time only; GB/s is the
                    # forward-traffic model and only quoted for fwd.
                    entry[f"{variant}_{leg}"] = {
                        "us": round(dt * 1e6, 2),
                        "gb_per_s": (
                            round(spec["traffic"] / dt / 1e9, 2)
                            if leg == "fwd" else None
                        ),
                    }
                    if "flops" in spec and leg == "fwd":
                        entry[f"{variant}_{leg}"]["tf_per_s"] = round(
                            spec["flops"] / dt / 1e12, 3
                        )
        f_us = entry["fused_fwd"]["us"]
        u_us = entry["unfused_fwd"]["us"]
        entry["fwd_speedup"] = (
            round(u_us / f_us, 3) if f_us and u_us else None
        )
        result["measurements"][name] = entry

    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result))
    if args.assert_ran and any(
        entry[k]["us"] is None
        for entry in result["measurements"].values()
        for k in entry if k != "fwd_speedup"
    ) and on_tpu:
        raise SystemExit("some on-chip timings came back sub-resolution")
    if args.assert_ran and ran == 0:
        raise SystemExit("no timings ran")


if __name__ == "__main__":
    main()
