"""Model step, generation: mean host time of one decode step over the
window, closed by the token read-back (engine counters ``decode_ms`` /
``decode_steps``)."""


def read(ctx):
    stats = ctx["stats"]
    if not stats["decode_steps"]:
        return None
    return stats["decode_ms"] / stats["decode_steps"]
