"""Model step, prompt: mean host time from the start of a prefill chunk's
argument building to the return of its program's call - ``decode_dispatch_ms``'
twin, before the chunk's first tokens are waited for (engine counters
``prefill_dispatch_ms`` / ``prefill_dispatches``). A program without the
counter reports nothing."""


def read(ctx):
    stats = ctx["stats"]
    if not stats.get("prefill_dispatches") or "prefill_dispatch_ms" not in stats:
        return None
    return stats["prefill_dispatch_ms"] / stats["prefill_dispatches"]
