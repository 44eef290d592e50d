"""Step program: median host time of one optimizer step, read as the gap
between the ends of consecutive ``block_until_ready`` waits (the loop keeps
one step in flight, as the trainer does)."""

import statistics


def read(ctx):
    ends = ctx["sync_ends"]
    gaps = [b - a for a, b in zip(ends, ends[1:])]
    if not gaps:
        return None
    return statistics.median(gaps) * 1e3
