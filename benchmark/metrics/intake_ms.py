"""Serving scheduler: mean host time a request spends in the engine's intake
over the window - ``ServingEngine.submit`` from its call to its return, the
wait for the request's key on the device included (engine counters
``intake_ms`` / ``submits``). The counter holds refused calls' time too and
``submits`` only the accepted; the cells' traffic refuses none. A program
without the counters reports nothing."""


def read(ctx):
    stats = ctx["stats"]
    if not stats.get("submits") or "intake_ms" not in stats:
        return None
    return stats["intake_ms"] / stats["submits"]
