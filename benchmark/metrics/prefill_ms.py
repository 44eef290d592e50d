"""Model step, prompt: mean host time of one prefill dispatch over the
window, closed by ``block_until_ready`` (engine counters ``prefill_ms`` /
``prefill_dispatches``)."""


def read(ctx):
    stats = ctx["stats"]
    if not stats["prefill_dispatches"]:
        return None
    return stats["prefill_ms"] / stats["prefill_dispatches"]
