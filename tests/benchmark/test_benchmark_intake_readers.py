"""The readers of the engine's request intake and of a prefill chunk's
dispatch, each on a hand-built ``ctx``: what it computes, and that it
reports nothing where the program has no such span or counter, as a
program older than these readers has not."""

import pytest

from benchmark import harness
from benchmark.reduce_trace import Trace

MS = 1_000_000    # ns
WINDOW = (0, 100 * MS)
CHIP = "/device:TPU:0"


def _trace(spans, ops=((0, 10), (20, 30), (32, 40), (60, 100))):
    """Device operations in ms of one chip (idle 10-20, 30-32, 40-60) and
    host spans ``(name, start_ms, end_ms)``; a second chip that is never
    idle, which the readers leave out as ``idle_gaps`` does."""
    return Trace({CHIP: [("%fusion.1", s * MS, e * MS) for s, e in ops],
                  "/device:TPU:1": [("%fusion.1", 0, 100 * MS)]},
                 [(name, s * MS, e * MS) for name, s, e in spans])


def _idle_ctx(trace):
    return {"trace": trace, "trace_window_ns": WINDOW}


read_idle = harness.load_reader("intake_idle_pct")


def test_a_gap_that_begins_in_the_key_wait_inside_intake_counts():
    # the gap at 10 ms begins inside request_key inside intake inside the
    # harness's submit; it counts whole, though it outlasts the intake
    trace = _trace([("window", 0, 100), ("submit", 5, 19), ("intake", 6, 15),
                    ("request_key", 8, 14), ("step", 19, 100)])
    assert dict(trace.idle_gaps(*WINDOW)) == pytest.approx(
        {"request_key": 0.010, "step": 0.022})
    assert read_idle(_idle_ctx(trace)) == pytest.approx(10.0)


def test_a_gap_under_the_harness_submit_alone_does_not_count():
    # idle at 40 ms begins in submit between two intakes; 10 ms begins in
    # intake itself (no key wait): 10 of 100 ms
    trace = _trace([("window", 0, 100), ("submit", 5, 50), ("intake", 6, 12),
                    ("request_key", 7, 9), ("intake", 41, 45)])
    assert read_idle(_idle_ctx(trace)) == pytest.approx(10.0)
    # a span that opens as the gap begins holds it; one that closes then does not
    trace = _trace([("submit", 5, 50), ("intake", 10, 11), ("intake", 38, 40)])
    assert read_idle(_idle_ctx(trace)) == pytest.approx(10.0)


def test_intake_spans_with_no_idle_under_them_read_nought():
    trace = _trace([("window", 0, 100), ("submit", 0, 9), ("intake", 1, 8),
                    ("request_key", 2, 7), ("step", 9, 100)])
    assert read_idle(_idle_ctx(trace)) == 0.0


def test_a_parent_without_the_span_gives_nothing():
    trace = _trace([("window", 0, 100), ("submit", 5, 50), ("step", 50, 100)])
    assert read_idle(_idle_ctx(trace)) is None
    # nor does a trace with no device plane
    no_device = Trace({}, [("intake", 5 * MS, 15 * MS)])
    assert read_idle(_idle_ctx(no_device)) is None


def test_the_window_clips_the_gaps():
    trace = _trace([("intake", 0, 100)], ops=((20, 30),))
    ctx = {"trace": trace, "trace_window_ns": (15 * MS, 35 * MS)}
    # idle 15-20 and 30-35 of a 20 ms window, both under the intake
    assert read_idle(ctx) == pytest.approx(50.0)


# metric: [(ctx["stats"], expected)], the parent's stats among them
COUNTERS = {
    "intake_ms": [
        ({"submits": 40, "intake_ms": 100.0, "steps": 9}, 2.5),
        ({"steps": 9, "decode_steps": 8}, None),
        ({"submits": 0, "intake_ms": 0.0}, None),
    ],
    "prefill_dispatch_ms": [
        ({"prefill_dispatches": 8, "prefill_dispatch_ms": 12.0, "prefill_ms": 120.0}, 1.5),
        ({"prefill_dispatches": 8, "prefill_ms": 120.0}, None),
        ({"prefill_dispatches": 0, "prefill_dispatch_ms": 0.0}, None),
    ],
}


@pytest.mark.parametrize("metric", COUNTERS)
def test_counter_reader_on_a_hand_built_ctx(metric):
    read = harness.load_reader(metric)
    for stats, expected in COUNTERS[metric]:
        value = read({"stats": stats, "window": (10.0, 60.0)})
        assert value == (None if expected is None else pytest.approx(expected))


@pytest.mark.parametrize("metric", ["intake_ms", "intake_idle_pct", "prefill_dispatch_ms"])
def test_the_manifest_names_each_reader_for_three_serving_cells(metric):
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == metric)
    assert entry["moves"] == "serve_tok_s" and entry["better"] == "lower"
    assert entry["workloads"] == [
        "serve-xl-backlog", "serve-sala-longdoc", "serve-nemotron-reasoning"]
    for cell in entry["workloads"]:
        assert metric in {m["name"] for m in harness.load_cell(cell)["per_layer"]}
