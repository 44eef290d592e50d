"""Sampling CLI: generate text from a trained checkpoint.

Closes the train -> checkpoint -> sample loop (the reference is train-only;
its ``load_checkpoint`` is an empty stub,
``/root/reference/train_gpt2_distributed.py:104-111``, and it has no
inference entry point at all). Usage::

    gpt2-tpu-sample --ckpt runs/ckpt --prompt "The meaning of life" --new 64
    gpt2-tpu-sample --ckpt runs/ckpt/step_0001000 --prompt_ids 464,3616 \
        --temperature 0 --decode_path cached

``--ckpt`` accepts either one checkpoint directory (``step_NNNNNNN``) or a
save dir, in which case the latest checkpoint is used. Model architecture
comes from ``--model`` + override flags exactly like ``train.py`` (the
checkpoint stores arrays, not architecture — matching the reference's
code-specifies-model convention, SURVEY.md §5.6).

Text prompts/continuations need tiktoken's GPT-2 BPE (network-gated on
first fetch); ``--prompt_ids`` works fully offline and prints token ids.
``--stream`` prints each token the moment the serving engine produces it
(paged-KV decode; identical output to ``--decode_path cached`` per seed).
"""

from __future__ import annotations

import argparse
import os
import sys


def build_argparser() -> argparse.ArgumentParser:
    from gpt_2_distributed_tpu.config import MODEL_PRESETS, SALA_PRESETS

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--ckpt", default=None,
                   help="checkpoint dir (step_NNNNNNN) or save dir (uses latest)")
    p.add_argument("--init_random", action="store_true",
                   help="random weights from --seed instead of a checkpoint "
                        "(the minicpm-sala-* models have no checkpoint format)")
    p.add_argument("--model", default="124M",
                   choices=sorted(MODEL_PRESETS) + sorted(SALA_PRESETS))
    p.add_argument("--n_layer", type=int, default=None)
    p.add_argument("--first_layer", type=int, default=0,
                   help="with --n_layer and a minicpm-sala-* model: that many "
                        "consecutive layers of the published stack from this one on")
    p.add_argument("--n_embd", type=int, default=None)
    p.add_argument("--n_head", type=int, default=None)
    p.add_argument("--vocab_size", type=int, default=None)
    p.add_argument(
        "--seq_len", type=int, default=None,
        help="n_positions the checkpoint was trained with, when it differs "
        "from the preset (train.py --seq_len resizes wpe)",
    )
    p.add_argument("--prompt", default=None, help="text prompt (needs tiktoken BPE)")
    p.add_argument("--prompt_ids", default=None,
                   help="comma-separated token ids (offline alternative)")
    p.add_argument("--new", type=int, default=64, help="tokens to generate")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top_k", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--decode_path", default="auto", choices=["auto", "cached", "reforward"],
        help="'cached' = KV-cache prefill+decode (wins at batch>=16 on v5e), "
        "'reforward' = full re-forward per token; 'auto' picks reforward "
        "because this CLI always generates batch=1, below the batch size "
        "at which the cache path overtakes re-forwarding",
    )
    p.add_argument(
        "--stream", action="store_true",
        help="print tokens as they are generated, via the serving engine's "
        "paged-KV decode (gpt_2_distributed_tpu/serving/); same tokens as "
        "--decode_path cached for the same --seed",
    )
    p.add_argument("--device", default=None,
                   help="jax platform override (cpu|tpu), like train.py --device")
    return p


def _sample_sala(args, jax) -> None:
    """A layer-pattern model keeps a paged cache and a recurrent state, so it
    is sampled the way it is served: one request through a one-slot
    ``ServingEngine`` (chunked prefill, then the decode step)."""
    from gpt_2_distributed_tpu.config import ServeConfig, sala_config_from_flags
    from gpt_2_distributed_tpu.models import minicpm_sala
    from gpt_2_distributed_tpu.serving import ServingEngine
    from gpt_2_distributed_tpu.utils.device_info import device_banner

    if args.prompt_ids is None:
        sys.exit("--model minicpm-sala-* takes --prompt_ids (no tokenizer is shipped)")
    config = sala_config_from_flags(args)
    ids = [int(t) for t in args.prompt_ids.split(",")]
    bad = [t for t in ids if not 0 <= t < config.vocab_size]
    if not ids or bad:
        sys.exit(f"prompt ids empty or out of vocab range: {bad[:5]}")
    print(device_banner(), file=sys.stderr)
    block = config.sparse.block
    total = len(ids) + args.new
    serve = ServeConfig(
        max_batch=1, block_size=block, num_blocks=-(-total // block) + 1,
        prefill_chunk=block * max(1, min(2048, total) // block), max_seq_len=total)
    eng = ServingEngine(
        minicpm_sala.init_params(config, jax.random.PRNGKey(args.seed)), config,
        serve, temperature=args.temperature, top_k=args.top_k)
    print(",".join(str(t) for t in ids), end="", flush=True)
    eng.submit(ids, args.new, rng=jax.random.PRNGKey(args.seed),
               on_token=lambda _req, tok: print(f",{tok}", end="", flush=True))
    eng.run_until_idle()
    print(flush=True)


def main(argv: list[str] | None = None) -> None:
    p = build_argparser()
    args = p.parse_args(argv)
    if (args.ckpt is None) == (not args.init_random):
        p.error("exactly one of --ckpt / --init_random is required")
    from gpt_2_distributed_tpu.config import SALA_PRESETS, validate_model_flags

    validate_model_flags(p, args)
    if args.init_random and args.model not in SALA_PRESETS:
        p.error("--init_random samples a minicpm-sala-* model; GPT-2 presets "
                "are sampled from a checkpoint")
    from gpt_2_distributed_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    if args.device:
        os.environ["JAX_PLATFORMS"] = args.device

    import jax
    import jax.numpy as jnp

    if args.device:
        jax.config.update("jax_platforms", args.device)
    if args.model in SALA_PRESETS:
        return _sample_sala(args, jax)

    from gpt_2_distributed_tpu.checkpoint import latest_checkpoint, restore_params
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

    path = os.path.abspath(args.ckpt)  # orbax rejects relative paths
    if not os.path.exists(os.path.join(path, "meta.json")):
        latest = latest_checkpoint(path)
        if latest is None:
            sys.exit(f"no checkpoint found under {path!r}")
        path = latest

    if (args.prompt is None) == (args.prompt_ids is None):
        sys.exit("exactly one of --prompt / --prompt_ids is required")

    enc = None
    if args.prompt is not None:
        try:
            import tiktoken

            enc = tiktoken.get_encoding("gpt2")
        except Exception as e:  # noqa: BLE001 — network-gated BPE fetch
            sys.exit(f"--prompt needs tiktoken's GPT-2 BPE ({e}); "
                     "use --prompt_ids offline")
        ids = enc.encode_ordinary(args.prompt)
    else:
        ids = [int(t) for t in args.prompt_ids.split(",")]
    if not ids:
        sys.exit("empty prompt")
    bad = [t for t in ids if not 0 <= t < config.vocab_size]
    if bad:
        sys.exit(f"prompt ids out of vocab range: {bad[:5]}")

    from gpt_2_distributed_tpu.utils.device_info import device_banner

    print(device_banner(), file=sys.stderr)
    template = jax.eval_shape(lambda: gpt2.init_params(config))
    # Explicit single-device shardings: without them orbax re-applies the
    # shardings recorded in the checkpoint files — exactly the path it warns
    # is unsafe when restoring on a different topology, and sampling a
    # pod-trained checkpoint on one host/chip IS that case (round-3 ADVICE).
    one_device = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    shardings = jax.tree_util.tree_map(lambda _: one_device, template)
    params, meta = restore_params(path, template, shardings)
    print(f"checkpoint: {path} (step {meta.step}, "
          f"{meta.total_tokens:,} tokens trained)", file=sys.stderr)

    if args.stream:
        if args.decode_path == "reforward":
            sys.exit("--stream decodes through the serving engine's paged KV "
                     "path; drop --decode_path reforward")
        from gpt_2_distributed_tpu.config import ServeConfig
        from gpt_2_distributed_tpu.serving import ServingEngine

        block_size = 16
        need = -(-(len(ids) + args.new - 1) // block_size)
        serve = ServeConfig(
            max_batch=1, block_size=block_size, num_blocks=need + 1,
        )
        eng = ServingEngine(
            params, config, serve,
            temperature=args.temperature, top_k=args.top_k,
        )
        if enc is not None:
            print(args.prompt, end="", flush=True)

            def on_token(_req, tok):
                print(enc.decode([tok]), end="", flush=True)
        else:
            print(",".join(str(t) for t in ids), end="", flush=True)

            def on_token(_req, tok):
                print(f",{tok}", end="", flush=True)

        eng.submit(ids, args.new, rng=jax.random.PRNGKey(args.seed),
                   on_token=on_token)
        eng.run_until_idle()
        print(flush=True)
        return

    prompt = jnp.asarray([ids], jnp.int32)
    fn = generate_cached if args.decode_path == "cached" else generate
    out = fn(
        params, config, prompt, jax.random.PRNGKey(args.seed),
        max_new_tokens=args.new, temperature=args.temperature,
        top_k=args.top_k,
    )
    out_ids = [int(t) for t in out[0]]
    if enc is not None:
        print(enc.decode(out_ids))
    else:
        print(",".join(str(t) for t in out_ids))


if __name__ == "__main__":
    main()
