"""The per-layer metrics that read the program's own counters: each reader
on a hand-built ``ctx`` (what it computes, and that it reports nothing where
the program has no such counter, as the parent of the PR that added them has
not), the engine's decode counters against what the harness's wrapper
intercepts on the tiny backlog cell, and the compile watch in place before a
cell's first compile."""

import os
import subprocess
import sys
import time

import pytest

from bench_tiny import tiny_cell
from benchmark import harness, serve_cell

ROOT = harness.ROOT

# what a parent without the counters hands the readers
OLD_STATS = {"decode_steps": 40, "decode_ms": 4000.0, "prefill_dispatches": 5,
             "prefill_ms": 300.0}
STATS = dict(OLD_STATS, steps=42, step_ms=4426.0, decode_dispatch_ms=60.0,
             decode_rows=150, decode_attended=30000)


class FakeWatch:
    def __init__(self, seconds, programs):
        self.answer = {"seconds": seconds, "programs": programs}
        self.asked = []

    def summary(self, **window):
        self.asked.append(window)
        return self.answer


READERS = {
    # metric: (ctx["stats"], expected)
    "engine_host_ms": [(STATS, (4426.0 - 300.0 - 4000.0) / 42), (OLD_STATS, None),
                       (dict(STATS, steps=0), None)],
    "decode_dispatch_ms": [(STATS, 1.5), (OLD_STATS, None),
                           (dict(STATS, decode_steps=0), None),
                           (dict(STATS, decode_dispatch_ms=0.0), None)],
}


@pytest.mark.parametrize("metric", READERS)
def test_counter_reader_on_a_hand_built_ctx(metric):
    read = harness.load_reader(metric)
    for stats, expected in READERS[metric]:
        value = read({"stats": stats, "window": (10.0, 60.0)})
        assert value == (None if expected is None else pytest.approx(expected))


@pytest.mark.parametrize("metric, key", [("setup_compile_s", "seconds"),
                                         ("setup_programs", "programs")])
def test_set_up_reader_asks_the_watch_for_what_came_before_the_window(
        metric, key, monkeypatch):
    from gpt_2_distributed_tpu.obs import compile_watch

    read = harness.load_reader(metric)
    watch = FakeWatch(seconds=7.25, programs=11)
    monkeypatch.setattr(compile_watch, "get_watch", lambda: watch)
    assert read({"window": (123.5, 173.5)}) == watch.answer[key]
    assert watch.asked == [{"before": 123.5}]
    # nothing counted: the metric is left out, not reported as 0
    watch.answer = {"seconds": 0.0, "programs": 0}
    assert read({"window": (123.5, 173.5)}) is None
    # a program without the watch (the parent): nothing, and no raise
    import gpt_2_distributed_tpu.obs as obs

    watch.answer = {"seconds": 7.25, "programs": 11}
    monkeypatch.delattr(obs, "compile_watch")
    monkeypatch.setitem(sys.modules, "gpt_2_distributed_tpu.obs.compile_watch", None)
    with pytest.raises(ImportError):
        from gpt_2_distributed_tpu.obs import compile_watch  # noqa: F401,F811
    assert read({"window": (123.5, 173.5)}) is None


def test_set_up_readers_read_the_real_watch():
    import jax
    import jax.numpy as jnp

    from gpt_2_distributed_tpu.obs import compile_watch

    compile_watch.install()

    def probe(x):
        return x - 7
    jax.jit(probe)(jnp.ones((3,))).block_until_ready()
    ctx = {"window": (time.monotonic(), time.monotonic() + 1)}
    seconds = harness.load_reader("setup_compile_s")(ctx)
    programs = harness.load_reader("setup_programs")(ctx)
    assert programs >= 1 and 0 < seconds < time.monotonic()
    # and nothing of it lies before a window that opened long ago
    assert harness.load_reader("setup_programs")({"window": (0.0, 1.0)}) is None


def test_decode_counters_equal_what_the_harness_intercepts():
    """The pin the follow-up needs: on the tiny backlog cell, step for step,
    ``decode_rows`` / ``decode_attended`` are the ``decodes`` that
    ``annotate_engine`` reads off ``_decode_fn``'s arguments, so a
    ``benchmark`` PR can point ``mfu_pct.decode`` and ``paged_attn_roofline``
    at the counters and delete the wrapper."""
    from benchmark import traffic

    cell = tiny_cell("backlog")
    engine, driver = serve_cell.build_engine(cell, seed=11)
    try:
        decodes = serve_cell.annotate_engine(engine, harness.Spans())
        source = traffic.requests(cell["mix"], cell["config_file"]["vocab_size"], 11)
        per_step, last = [], (0, 0)
        for _ in range(60):
            while engine.queue_depth < engine.serve.max_batch:
                request = next(source)
                driver.submit(request.prompt, request.max_new_tokens,
                              rng=request.index)
            driver.step()
            now = (engine.stats["decode_rows"], engine.stats["decode_attended"])
            if now != last:
                per_step.append((now[0] - last[0], now[1] - last[1]))
                last = now
    finally:
        driver.close()
    assert len(decodes) == engine.stats["decode_steps"] > 30
    assert per_step == [(rows, attended) for _, rows, attended in decodes]
    assert max(rows for rows, _ in per_step) == engine.serve.max_batch


BEFORE_FIRST_COMPILE = """
import sys
sys.path.insert(0, {tests!r})
import jax
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, seconds, **kw: compiles.append(kw.get("fun_name"))
    if event.endswith("backend_compile_duration") else None)
from bench_tiny import tiny_cell
from benchmark import harness, serve_cell, train_cell
kind = {kind!r}
cell = tiny_cell(kind)
if kind == "train":
    train_cell.Trainer(cell, 3, harness.Spans())
else:
    serve_cell.build_engine(cell, 3)
from gpt_2_distributed_tpu.obs import compile_watch
watched = [p[0] for p in compile_watch.get_watch().programs()]
assert compiles and watched == compiles, (watched, compiles)
print("WATCHED", len(watched), watched[0])
"""


@pytest.mark.parametrize("kind", ["train", "backlog"])
def test_cell_has_the_compile_watch_before_its_first_compile(kind):
    """A listener of the test's own, registered before anything of the
    program is imported, sees every backend compile of the cell's set-up;
    the watch, which the program installs as it is imported, has to hold the
    same list from the first one on (``make_weights``)."""
    code = BEFORE_FIRST_COMPILE.format(
        tests=os.path.join(ROOT, "tests", "benchmark"), kind=kind)
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
        timeout=80, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    assert "WATCHED" in done.stdout
