"""Train loop: host time spent fetching a step's micro-batches from the
loader and placing them on the device (spans ``data_fetch`` + ``h2d``),
mean per optimizer step of the window."""


def read(ctx):
    if not ctx["steps"]:
        return None
    t0, t1 = ctx["window"]
    total = sum(e - s for name, s, e in ctx["spans"]
                if name in ("data_fetch", "h2d") and t0 <= s < t1)
    return total / ctx["steps"] * 1e3
