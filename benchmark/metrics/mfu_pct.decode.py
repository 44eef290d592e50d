"""Model step, whole step: the forward operations of the rows decoded in a
step (each attending over its own context) over the mean decode step time
and the chip's peak. Rows and contexts are those of the decode dispatches
the traced window held."""

from benchmark import work


def read(ctx):
    stats, decodes = ctx["stats"], ctx["decodes"]
    if not stats["decode_steps"] or not decodes:
        return None
    rows = sum(d[1] for d in decodes)
    attended = sum(d[2] for d in decodes)
    if not rows:
        return None
    flops_per_step = (
        rows * work.forward_flops_per_token(ctx["sizes"], attended / rows)
        / len(decodes))
    step_s = stats["decode_ms"] / stats["decode_steps"] / 1e3
    return 100.0 * flops_per_step / step_s / ctx["peaks"]["flops_per_s_bf16"]
