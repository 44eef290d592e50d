"""Model step, generation: mean host time from the call of the decode
program to its return - the transfer of its host arguments and the enqueue,
before the device's work is waited for (engine counters
``decode_dispatch_ms`` / ``decode_steps``). A program without the counter
reports nothing."""


def read(ctx):
    stats = ctx["stats"]
    if not stats["decode_steps"] or not stats.get("decode_dispatch_ms"):
        return None
    return stats["decode_dispatch_ms"] / stats["decode_steps"]
