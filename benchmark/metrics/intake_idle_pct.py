"""Serving scheduler: the device's idle time that began while the program's
``intake`` span was open (``ServingEngine.submit``, whatever span lay inside
it), as a share of the traced window. It is ``Trace.idle_gaps``' entries for
``intake`` and its one child, ``request_key``: the first chip's gaps, each
counted whole toward the innermost span open when it began. A program that
opens no ``intake`` span, or a trace with no device plane, reports nothing;
``intake`` spans with no gap under them read 0."""

SPANS = ("intake", "request_key")


def read(ctx):
    trace = ctx["trace"]
    lo, hi = ctx["trace_window_ns"]
    if (not any(name == SPANS[0] for name, _, _ in trace.host_spans)
            or not trace.device_ops or hi <= lo):
        return None
    gaps = dict(trace.idle_gaps(lo, hi, n=10**6))
    return 100.0 * sum(gaps.get(name, 0.0) for name in SPANS) * 1e9 / (hi - lo)
