"""Step program, whole step: tokens/s/chip x the forward and backward
operations a token requires (recomputation not counted) over the chip's
peak: the operations by the family's arithmetic, the peak from the
benchmark's table. The rate is a step's tokens over the median step time of
the traced run: stopping the profiler stalls that run's window for seconds,
so the window's own rate would read low."""

import statistics


def read(ctx):
    ends = ctx["sync_ends"]
    gaps = [b - a for a, b in zip(ends, ends[1:])]
    if not gaps:
        return None
    chips = ctx["cell"]["chips"]
    tok_s = ctx["tokens_per_step"] / statistics.median(gaps) / chips
    flops = ctx["cell"]["reference"].train_flops_per_token(
        ctx["sizes"], ctx["cell"]["mix"]["seq_len"])
    return 100.0 * tok_s * flops / ctx["peaks"]["flops_per_s_bf16"]
