"""The per-layer metrics that read the program's own counters: each reader
on a hand-built ``ctx`` (what it computes, and that it reports nothing where
the program has no such counter, as the parent of the PR that added them has
not), a traced tiny run that reads the engine through its counters alone and
leaves its methods as they are, and the compile watch in place before a
cell's first compile."""

import os
import subprocess
import sys
import time

import pytest

from bench_tiny import CPU_DEVICE, tiny_cell
from benchmark import harness, serve_cell, work
from benchmark.reduce_trace import Trace

ROOT = harness.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))

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


# rows and keys attended of the decode dispatches of a traced window, one
# pair a step, as the engine counts them into ``decode_rows`` and
# ``decode_attended``
DISPATCHES = [(4, 700), (4, 704), (3, 650), (4, 910), (2, 75)]
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}


class FakeTrace:
    """15 ms in the paged kernel's events over the traced window."""

    def kernel_seconds(self, needles, lo, hi):
        assert needles[0] == "%closed_call"
        return 0.015, 48 * len(DISPATCHES)


def _decode_ctx(traced_stats):
    ref = harness.load_module("reference", "gpt2")
    sizes = ref.sizes_of(harness.load_json(ROOT, "benchmark/configs/gpt2-xl.json"))
    return {"stats": STATS, "traced_stats": traced_stats,
            "cell": {"reference": ref}, "sizes": sizes, "peaks": PEAKS, "trace": FakeTrace(),
            "trace_window_ns": (0, 4e9)}, ref, sizes


def _mfu_by_dispatch(ref, sizes):
    """As the reader had it while the harness kept one record a dispatch."""
    rows = sum(r for r, _ in DISPATCHES)
    attended = sum(a for _, a in DISPATCHES)
    per_step = rows * ref.forward_flops_per_token(sizes, attended / rows) / len(DISPATCHES)
    step_s = STATS["decode_ms"] / STATS["decode_steps"] / 1e3
    return 100.0 * per_step / step_s / PEAKS["flops_per_s_bf16"]


def _roofline_by_dispatch(ref, sizes):
    shapes = ref.attention_shapes(sizes)
    least = 0.0
    for rows, attended in DISPATCHES:
        flops, nbytes = work.paged_attention_work(
            attended, rows, shapes["heads"], shapes["head_dim"], shapes["kv_heads"])
        least += shapes["kv_layers"] * work.roofline_seconds(flops, nbytes, PEAKS)[0]
    return 100.0 * least / 0.015


@pytest.mark.parametrize("metric, by_dispatch", [
    ("mfu_pct.decode", _mfu_by_dispatch),
    ("paged_attn_roofline", _roofline_by_dispatch)])
def test_decode_reader_gives_the_per_dispatch_value_from_the_counters_alone(
        metric, by_dispatch):
    traced = {"decode_steps": len(DISPATCHES),
              "decode_rows": sum(r for r, _ in DISPATCHES),
              "decode_attended": sum(a for _, a in DISPATCHES)}
    ctx, ref, sizes = _decode_ctx(traced)
    read = harness.load_reader(metric)
    assert read(ctx) == pytest.approx(by_dispatch(ref, sizes), rel=1e-12)
    assert 0 < read(ctx) < 100
    # a program without the counters, or a traced part that decoded nothing:
    # the metric is left out, never 0
    assert read(dict(ctx, traced_stats={})) is None
    assert read(dict(ctx, traced_stats=dict(traced, decode_rows=0))) is None


def test_traced_run_reads_the_engine_through_its_counters_and_wraps_nothing(
        monkeypatch):
    """A traced tiny backlog run whose engine's decode program takes one
    argument more than today's: the harness names no method of the engine
    and spells out no signature, so both decode metrics still read, and the
    engine's methods are the objects they were. (The device's side of the
    trace is the chip's recording beside this file: the CPU has no device
    plane and no kernel event.)"""
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    cell = tiny_cell("backlog")
    cell["per_layer"] = [m for m in manifest["per_layer"]
                         if m["name"] in ("mfu_pct.decode", "paged_attn_roofline")]
    built, build = {}, serve_cell.build_engine

    def build_with_a_second_state(cell, seed):
        engine, driver = build(cell, seed)
        inner = engine._decode_fn

        def decode(params, k_pool, v_pool, block_table, tokens, pos, active,
                   keys, recurrent_state=None):
            return inner(params, k_pool, v_pool, block_table, tokens, pos,
                         active, keys)

        engine._decode_fn = decode
        built.update(engine=engine, methods={
            n: getattr(engine, n) for n in ("_decode_fn", "_try_admit", "_prefill_tick")})
        return engine, driver

    monkeypatch.setattr(serve_cell, "build_engine", build_with_a_second_state)
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.3)
    monkeypatch.setattr(
        harness.ProfilerWindow, "read",
        lambda self: Trace.from_file(os.path.join(HERE, "small_serve.xplane.pb")))
    device = dict(CPU_DEVICE, kind="TPU v5 lite")     # whose peaks the readers take
    result = serve_cell.run(cell, 13, 1.0, True, device, time.monotonic(),
                            harness.CompileCounter())
    assert result["correct"] is True
    assert set(result["metrics"]) == {"mfu_pct.decode", "paged_attn_roofline"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    for name, method in built["methods"].items():
        assert getattr(built["engine"], name) == method, f"{name} was wrapped"


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
