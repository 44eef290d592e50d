"""``reduce_trace`` on two small traces recorded on the chip (a v5e; the
training one in PR 24, the serving one again in PR 27, with the program's own
``gpt2/...`` spans in it; ``record_small_trace.py`` beside this file says
how): a training window and a serving window of 50 ms each, through the real
kernels."""

import os

import pytest

from benchmark import reduce_trace
from benchmark.reduce_trace import NoKernelEvent, Trace, union_seconds

HERE = os.path.dirname(os.path.abspath(__file__))
MOSAIC = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def train():
    return Trace.from_file(os.path.join(HERE, "small_train.xplane.pb"))


@pytest.fixture(scope="module")
def serve():
    return Trace.from_file(os.path.join(HERE, "small_serve.xplane.pb"))


def test_union_of_intervals():
    assert union_seconds([(0, 4e9), (2e9, 6e9), (10e9, 11e9), (10e9, 10.5e9)]) == 7.0
    assert union_seconds([]) == 0.0


@pytest.mark.parametrize("which", ["train", "serve"])
def test_busy_union_and_idle_share(which, request):
    trace = request.getfixturevalue(which)
    lo, hi = trace.window_ns()
    window_s = (hi - lo) / 1e9
    busy = trace.busy_seconds(lo, hi)
    assert 0.04 < window_s < 0.2               # the 50 ms the recording asked for
    assert 0 < busy < window_s                 # a tiny model leaves the chip idle
    # nested operations are not counted twice: the union is no more than
    # the programs' own run time, and half a window is half the busy time
    plane = next(iter(trace.device_ops.values()))
    assert busy <= sum(e - s for _, s, e in plane) / 1e9
    mid = (lo + hi) / 2
    assert trace.busy_seconds(lo, mid) + trace.busy_seconds(mid, hi) == pytest.approx(busy)


def test_flash_kernel_sum_counts_one_forward_and_one_backward_a_layer(train):
    lo, hi = train.window_ns()
    fwd_s, fwd_n = train.kernel_seconds(("%jvp__", MOSAIC), lo, hi)
    bwd_s, bwd_n = train.kernel_seconds(("%transpose_jvp__", MOSAIC), lo, hi)
    assert fwd_n == bwd_n > 0 and fwd_n % 2 == 0       # two layers
    assert 0 < fwd_s < train.busy_seconds(lo, hi)
    assert 0 < bwd_s < train.busy_seconds(lo, hi)


def test_paged_kernel_is_found_in_the_serving_trace(serve):
    lo, hi = serve.window_ns()
    seconds, calls = serve.kernel_seconds(("%closed_call", MOSAIC), lo, hi)
    assert calls > 0 and 0 < seconds < serve.busy_seconds(lo, hi)


def test_a_reader_that_finds_no_kernel_event_raises(train, serve):
    lo, hi = train.window_ns()
    with pytest.raises(NoKernelEvent):
        train.kernel_seconds(("%closed_call", MOSAIC), lo, hi)   # no paged kernel in training
    with pytest.raises(NoKernelEvent):
        serve.kernel_seconds(("%jvp__", MOSAIC), *serve.window_ns())


def test_top_operations_are_self_times(train):
    lo, hi = train.window_ns()
    top = train.top_ops(lo, hi)
    assert 1 <= len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    # self times partition the busy time; the enclosing ``while`` is not on top
    assert sum(s for _, s in train.top_ops(lo, hi, n=10**6)) == pytest.approx(
        train.busy_seconds(lo, hi), rel=1e-6)
    assert any(name.endswith("(tpu_custom_call)") for name, _ in top)


HARNESS_SPANS = {"data_fetch", "h2d", "step_dispatch", "device_sync", "submit", "step"}
PROGRAM_SPANS = {"engine_step", "admit", "prefill", "decode", "dispatch",
                 "readback", "emit"}


@pytest.mark.parametrize("which", ["train", "serve"])
def test_idle_gaps_are_attributed_to_the_spans(which, request):
    trace = request.getfixturevalue(which)
    lo, hi = trace.window_ns()
    gaps = trace.idle_gaps(lo, hi)
    idle = (hi - lo) / 1e9 - trace.busy_seconds(lo, hi)
    assert sum(s for _, s in trace.idle_gaps(lo, hi, n=10**6)) == pytest.approx(idle)
    names = {name for name, _ in gaps}
    assert names <= HARNESS_SPANS | PROGRAM_SPANS | {"(none)"}
    assert names - {"(none)"}                  # the spans are in the trace


def test_an_idle_gap_is_named_by_the_innermost_span_of_the_program(serve):
    """The serving trace holds the engine's own ``gpt2/...`` spans inside the
    harness's ``bench/step``: a gap that opens under one of them is that
    phase's, and ``step`` keeps only what lies outside ``ServingEngine.step``
    (nothing, here)."""
    assert {"bench/", "gpt2/"} == set(reduce_trace.SPAN_PREFIXES)
    kept = {name for name, _, _ in serve.host_spans}
    assert PROGRAM_SPANS <= kept and {"submit", "step", "window"} <= kept
    lo, hi = serve.window_ns()
    by_span = dict(serve.idle_gaps(lo, hi, n=10**6))
    assert {"prefill", "dispatch", "readback", "emit"} <= set(by_span)
    assert "step" not in by_span
    # every phase span lies inside an engine step, and that inside a step
    steps = [(s, e) for n, s, e in serve.host_spans if n == "step"]
    for name, s, e in serve.host_spans:
        if name in PROGRAM_SPANS:
            assert any(a <= s and e <= b for a, b in steps), name
    # by hand on a two-span trace: the gap belongs to the inner, later span
    toy = Trace({"/device:TPU:0": [("%op", 0, 10), ("%op", 30, 40)]},
                [("step", 0, 40), ("emit", 8, 20)])
    assert toy.idle_gaps(0, 40) == [["emit", 2e-8]]


def test_span_names_lose_their_prefix_and_foreign_events_are_dropped():
    assert reduce_trace.span_name("bench/step") == "step"
    assert reduce_trace.span_name("gpt2/readback") == "readback"
    assert reduce_trace.span_name("PjitFunction(decode_step)") is None


def test_summary_lists_planes_and_lines(train):
    text = reduce_trace.summary(os.path.join(HERE, "small_train.xplane.pb"), top=3)
    assert "PLANE /device:TPU:0" in text and "LINE XLA Ops" in text
