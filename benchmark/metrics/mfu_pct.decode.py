"""Model step, whole step: the forward operations of the rows decoded in a
step (each attending over its own context, by the family's arithmetic) over
the mean decode step time and the chip's peak. Rows and contexts are the
engine's own counters (``decode_rows``, ``decode_attended``, ``decode_steps``)
over the traced part of the window; the step time is the whole window's. A
program without the counters reports nothing."""


def read(ctx):
    stats, traced = ctx["stats"], ctx["traced_stats"]
    steps, rows = traced.get("decode_steps"), traced.get("decode_rows")
    if not stats["decode_steps"] or not steps or not rows:
        return None
    flops_per_step = rows * ctx["cell"]["reference"].forward_flops_per_token(
        ctx["sizes"], traced["decode_attended"] / rows) / steps
    step_s = stats["decode_ms"] / stats["decode_steps"] / 1e3
    return 100.0 * flops_per_step / step_s / ctx["peaks"]["flops_per_s_bf16"]
