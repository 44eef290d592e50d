"""The compile watch: what it counts, how it splits set-up from what comes
after, that it installs once, and the two log lines a step loop prints."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from gpt_2_distributed_tpu.obs import compile_watch
from gpt_2_distributed_tpu.obs.compile_watch import (
    CompileLog,
    CompileWatch,
    _union_seconds,
)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


def _fresh_program(tag):
    """A jitted function no other test has compiled, under a name."""
    def fn(x):
        return x * 3 + len(tag)
    fn.__name__ = f"watch_probe_{tag}"
    return jax.jit(fn)


def _named(watch, name, **window):
    return [e for e in watch.events(**window) if e[3] and name in e[3]]


def test_fresh_jit_is_counted_once_with_its_name():
    watch = compile_watch.install()
    assert watch is compile_watch.get_watch() and watch.installed
    fn = _fresh_program("once")
    # Counted from a mark on the clock, not over the whole list: the watch is
    # the process's, and in a worker that has compiled MAX_EVENTS things the
    # oldest fall off the list between two whole-list summaries.
    mark = time.monotonic()
    fn(jnp.ones((5,), jnp.float32)).block_until_ready()
    after_first = watch.summary(after=mark)
    kinds = [e[1] for e in _named(watch, "watch_probe_once")]
    assert sorted(kinds) == ["compile", "lower", "trace"]
    compiled = [e for e in _named(watch, "watch_probe_once") if e[1] == "compile"]
    assert compiled[0][3] == "jit(watch_probe_once)" and compiled[0][2] > 0
    assert after_first["programs"] >= 1
    assert after_first["seconds"] > 0
    # the second call runs the program it has: nothing is counted
    fn(jnp.ones((5,), jnp.float32)).block_until_ready()
    assert len(_named(watch, "watch_probe_once")) == 3
    assert watch.summary(after=mark)["programs"] == after_first["programs"]


def test_before_and_after_split_on_the_clock():
    watch = compile_watch.install()
    _fresh_program("early")(jnp.ones((3,))).block_until_ready()
    time.sleep(0.002)
    mark = time.monotonic()
    _fresh_program("late")(jnp.ones((3,))).block_until_ready()
    assert _named(watch, "watch_probe_early", before=mark)
    assert not _named(watch, "watch_probe_early", after=mark)
    assert _named(watch, "watch_probe_late", after=mark)
    assert not _named(watch, "watch_probe_late", before=mark)
    whole, early, late = (watch.summary(), watch.summary(before=mark),
                          watch.summary(after=mark))
    assert early["programs"] + late["programs"] == whole["programs"]
    assert late["programs"] >= 1 and late["seconds"] > 0
    assert [p[0] for p in watch.programs(after=mark)].count(
        "jit(watch_probe_late)") == 1


def test_installing_again_registers_no_second_listener():
    watch = compile_watch.install()
    assert compile_watch.install() is watch and watch.install() is watch
    _fresh_program("again")(jnp.ones((2,))).block_until_ready()
    # a second listener would record every event twice
    assert sorted(e[1] for e in _named(watch, "watch_probe_again")) == [
        "compile", "lower", "trace"]


def test_nested_traces_are_not_counted_twice():
    assert _union_seconds([(0.0, 4.0), (1.0, 2.0), (3.0, 5.0), (7.0, 8.0)]) == 6.0
    assert _union_seconds([]) == 0.0
    watch = CompileWatch()
    watch.on_duration(TRACE, 0.010, fun_name="inner")   # ended inside ...
    watch.on_duration(TRACE, 1.0, fun_name="outer")     # ... this one
    summary = watch.summary()
    assert 1.0 <= summary["trace_s"] < 1.005
    assert summary["seconds"] == summary["trace_s"]
    assert summary["programs"] == 0
    # ... and the outer event stands for those it spans: thousands of `add`
    # inside a step program's trace or lowering do not push its compile out
    assert [e[3] for e in watch.events()] == ["outer"]
    time.sleep(0.002)
    watch.on_duration(TRACE, 0.0005, fun_name="add")   # called by a lowering rule
    watch.on_duration(LOWER, 0.001, fun_name="jit(outer)")
    watch.on_duration(TRACE, 0.0001, fun_name="next")  # after it: kept
    assert [e[3] for e in watch.events()] == ["outer", "jit(outer)", "next"]


def test_summary_counts_hits_misses_and_ignores_other_events(monkeypatch):
    monkeypatch.setattr(compile_watch, "MAX_EVENTS", 8)
    watch = CompileWatch()
    watch.on_event(HIT)
    watch.on_duration(COMPILE, 0.25, fun_name="jit(read_back)")
    watch.on_event(MISS)
    watch.on_duration(COMPILE, 0.5, fun_name="jit(compiled)")
    watch.on_duration("/jax/some/other_duration", 9.0)
    watch.on_event("/jax/compilation_cache/compile_requests_use_cache")
    summary = watch.summary()
    assert (summary["programs"], summary["hits"], summary["misses"]) == (2, 1, 1)
    assert watch.compiles == 2
    assert watch.programs() == [("jit(read_back)", 0.25, True),
                                ("jit(compiled)", 0.5, False)]
    text = compile_watch.describe(summary)
    assert text.startswith("2 programs, 0.0 s tracing, 0.0 s lowering, ")
    assert text.endswith("compiling or reading back, 1 hits, 1 misses")
    # bounded: the oldest events go first
    for i in range(20):
        watch.on_duration(LOWER, 0.001, fun_name=f"f{i}")
    assert len(watch.events()) == 8 and watch.compiles == 2


def test_compile_log_closes_set_up_then_names_late_programs():
    watch = CompileWatch()
    log = CompileLog(watch)
    watch.on_duration(TRACE, 0.001, fun_name="train_step")
    watch.on_duration(COMPILE, 0.002, fun_name="jit(train_step)")
    assert not log.ready
    assert log.lines(1) == [
        "set-up: 1 programs, 0.0 s tracing, 0.0 s lowering, 0.0 s compiling "
        "or reading back, 0 hits, 0 misses"]
    assert log.ready and log.lines(2) == []
    time.sleep(0.002)
    watch.on_duration(COMPILE, 1.5, fun_name="jit(eval_step)")
    watch.on_event(HIT)
    watch.on_duration(COMPILE, 0.25, fun_name="jit(train_step)")
    assert log.lines(4812) == [
        "warning: step 4812 compiled jit(eval_step) after set-up (1.50 s)",
        "warning: step 4812 read back jit(train_step) after set-up (0.25 s)"]
    assert log.lines(4813) == []
    # a program that compiles once per shape by design is no warning
    watch.on_duration(COMPILE, 0.5, fun_name="jit(prefill)")
    watch.on_duration(COMPILE, 0.5, fun_name="jit(decode_step)")
    assert log.lines(4814, per_shape=("jit(prefill)",)) == [
        "step 4814 compiled jit(prefill) for a new shape (0.50 s)",
        "warning: step 4814 compiled jit(decode_step) after set-up (0.50 s)"]
