"""Serving scheduler: mean host time of one engine step that is neither a
prefill dispatch nor the decode step - admission, block tables, building
the dispatches' arguments, the per-slot emit loop, evictions (engine
counters (``step_ms`` - ``prefill_ms`` - ``decode_ms``) / ``steps``, the
engine's own clocks). A program without those counters reports nothing."""


def read(ctx):
    stats = ctx["stats"]
    if not stats.get("steps"):
        return None
    return (stats["step_ms"] - stats["prefill_ms"] - stats["decode_ms"]) / stats["steps"]
