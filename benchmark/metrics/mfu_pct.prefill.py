"""Model step, prompt: the forward operations of the tokens prefilled over
the window (each attending its own context, by the family's arithmetic; the
head is not asked of a prompt token but the last, and is left out where the
family says how: ``prefill_flops_per_token``) over the time the prefill
dispatches took and the chip's peak. Tokens and the keys they attend are the
engine's own counters (``prefill_tokens``, ``prefill_attended``); the time is
``prefill_ms``, host clock closed by ``block_until_ready``. A program without
the counters reports nothing."""


def read(ctx):
    stats = ctx["stats"]
    tokens = stats.get("prefill_tokens")
    if not tokens or not stats.get("prefill_ms"):
        return None
    family = ctx["cell"]["reference"]
    per_token = getattr(family, "prefill_flops_per_token", family.forward_flops_per_token)
    flops = tokens * per_token(ctx["sizes"], stats["prefill_attended"] / tokens)
    return 100.0 * flops / (stats["prefill_ms"] / 1e3) / ctx["peaks"]["flops_per_s_bf16"]
