"""Inference throughput: KV-cache decode vs the re-forward sampler.

Measures greedy generation wall-clock on the attached device for
``models/generate.py`` (full re-forward per token, O(T^2) attention each
step) and ``models/decode.py`` (static-cache prefill+decode, O(T) per
step). Each generate call is ONE jit dispatch (the whole decode loop is a
``lax.scan`` inside the jit), so dispatch and sync are paid once per call,
not per token — the same pipelined-measurement rule as bench.py.

Usage: python scripts/bench_decode.py [--model 124M]
       [--batch 8] [--prompt 128] [--new 256]

Recorded (124M, TPU v5 lite, 2026-07-30):
  b8  prompt128 new256:  cached 698 tok/s  vs re-forward 1364 (0.51x)
  b8  prompt128 new896:  cached 431 tok/s  vs re-forward  442 (0.97x)
  b32 prompt128 new256:  cached 1741 tok/s vs re-forward 1287 (1.35x)
Single-token decode steps are latency/bandwidth-bound on this chip (every
step reads all weights for [B,1,C] rows), so the cache path needs batch to
amortize — it wins from b~16 up, while the re-forward path's full-sequence
matmuls stay MXU-efficient at small batch. Both paths are exact (tested
equal); pick by serving shape.
"""

from __future__ import annotations

import argparse
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
    p.add_argument("--model", default="124M")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt", type=int, default=128)
    p.add_argument("--new", type=int, default=256)
    p.add_argument("--iters", type=int, default=3)
    p.add_argument(
        "--skip_reforward", action="store_true",
        help="only bench the cached path (the re-forward baseline is slow "
        "at large --new)",
    )
    p.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write the result dict to PATH (same record discipline "
        "as scripts/bench_fused.py -> BENCH_FUSED.json)",
    )
    # Tiny-model overrides so CI can exercise the full CLI on CPU without
    # paying for a preset-sized model (mirrors train.py/sample.py).
    p.add_argument("--n_layer", type=int, default=None)
    p.add_argument("--n_embd", type=int, default=None)
    p.add_argument("--n_head", type=int, default=None)
    p.add_argument("--vocab_size", type=int, default=None)
    p.add_argument("--seq_len", type=int, default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from gpt_2_distributed_tpu.config import MODEL_PRESETS
    from gpt_2_distributed_tpu.models import gpt2
    from gpt_2_distributed_tpu.models.decode import generate_cached
    from gpt_2_distributed_tpu.models.generate import generate

    overrides = {
        k: getattr(args, k)
        for k in ("n_layer", "n_embd", "n_head", "vocab_size")
        if getattr(args, k) is not None
    }
    if args.seq_len is not None:
        overrides["n_positions"] = args.seq_len
    config = MODEL_PRESETS[args.model].replace(**overrides)
    params = gpt2.init_params(config)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, config.vocab_size, (args.batch, args.prompt)),
        jnp.int32,
    )
    key = jax.random.PRNGKey(0)

    def timeit(fn):
        out = fn()  # compile + run
        out.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn()
        # device->host read forces completion
        int(out[0, -1])
        return (time.perf_counter() - t0) / args.iters

    results = {
        "model": args.model,
        "batch": args.batch,
        "prompt_len": args.prompt,
        "new_tokens": args.new,
        "device": jax.devices()[0].device_kind,
    }

    dt_c = timeit(lambda: generate_cached(
        params, config, prompt, key, max_new_tokens=args.new, temperature=0.0
    ))
    results["cached_s"] = round(dt_c, 4)
    results["cached_tok_s"] = round(args.batch * args.new / dt_c, 1)

    if not args.skip_reforward:
        dt_r = timeit(lambda: generate(
            params, config, prompt, key, max_new_tokens=args.new,
            temperature=0.0,
        ))
        results["reforward_s"] = round(dt_r, 4)
        results["reforward_tok_s"] = round(args.batch * args.new / dt_r, 1)
        results["speedup"] = round(dt_r / dt_c, 2)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
            f.write("\n")
    print(json.dumps(results))


if __name__ == "__main__":
    main()
