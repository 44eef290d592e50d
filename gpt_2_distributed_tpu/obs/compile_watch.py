"""The program's compile counter: what of a process's set-up went into
tracing, lowering and compiling (or reading programs back from the
persistent cache), how many programs there were, and which program compiled
after set-up was over.

One process-wide :class:`CompileWatch` (``get_watch()``) listens to
``jax.monitoring``. The modules that build the package's programs
(``parallel/train_step.py``, ``serving/engine.py``) install it as they are
imported, so a process has it before its first compile; this module itself
imports without jax, and a process that never installs it (a front-door
parent that stays off the device) reads an empty watch.

It keeps a bounded list of ``(time.monotonic(), kind, seconds, fun_name)``.
The kinds, and the ``jax.monitoring`` events they come from (jax 0.9.0
passes ``fun_name=`` on the first three, e.g. ``jit(train_step)``):

=============  ==========================================================
``trace``      ``/jax/core/compile/jaxpr_trace_duration``
``lower``      ``/jax/core/compile/jaxpr_to_mlir_module_duration``
``compile``    ``/jax/core/compile/backend_compile_duration``: the backend
               compile *or* the read-back of a cached program, one event
               per program either way
``hit``        ``/jax/compilation_cache/cache_hits``
``miss``       ``/jax/compilation_cache/cache_misses`` (an entry written)
=============  ==========================================================

An event is stamped when it ends and spans ``[t - seconds, t]``. A jitted
function called while another is being traced reports its own trace inside
the outer one's, and so does one that a lowering rule calls (a step
program's trace and lowering hold thousands of ``add`` and ``less``): the
outer ``trace`` or ``lower`` event, which ends last, takes the place of the
trace events it spans, so the list holds outermost ones only, and seconds
are the union of the intervals, never their sum.
"""

from __future__ import annotations

import collections
import threading
import time

MAX_EVENTS = 4096

DURATION_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
COUNT_KINDS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


def _union_seconds(intervals) -> float:
    total, end = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        if hi > end:
            total += hi - max(lo, end)
            end = hi
    return total


class CompileWatch:
    """The listener and its list. ``install()`` is idempotent; the two
    ``on_*`` methods are what ``jax.monitoring`` calls."""

    def __init__(self):
        self._events: collections.deque = collections.deque(maxlen=MAX_EVENTS)
        self._lock = threading.Lock()
        self.installed = False
        self.compiles = 0   # `compile` events ever seen: cheap to poll

    def install(self) -> "CompileWatch":
        with self._lock:
            if self.installed:
                return self
            self.installed = True
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def on_duration(self, event: str, seconds: float, **kwargs) -> None:
        kind = DURATION_KINDS.get(event)
        if kind is not None:
            self._add(kind, float(seconds), kwargs.get("fun_name"))

    def on_event(self, event: str, **kwargs) -> None:
        kind = COUNT_KINDS.get(event)
        if kind is not None:
            self._add(kind, 0.0, None)

    def _add(self, kind: str, seconds: float, fun_name) -> None:
        with self._lock:
            now, events = time.monotonic(), self._events
            if kind in ("trace", "lower"):
                while (events and events[-1][1] == "trace"
                       and events[-1][0] - events[-1][2] >= now - seconds):
                    events.pop()
            events.append((now, kind, seconds, fun_name))
            if kind == "compile":
                self.compiles += 1

    def events(self, *, after: float | None = None,
               before: float | None = None) -> list[tuple]:
        """Events that ended in ``(after, before]`` on the monotonic clock,
        oldest first."""
        with self._lock:
            events = list(self._events)
        return [e for e in events
                if (after is None or e[0] > after)
                and (before is None or e[0] <= before)]

    def summary(self, *, after: float | None = None,
                before: float | None = None) -> dict:
        """Programs (``compile`` events), seconds by kind and in all, and
        the persistent cache's hits and misses, over ``(after, before]``."""
        events = self.events(after=after, before=before)

        def seconds(*kinds):
            return _union_seconds(
                (t - s, t) for t, kind, s, _ in events if kind in kinds)

        def count(kind):
            return sum(1 for e in events if e[1] == kind)

        return {
            "programs": count("compile"),
            "trace_s": seconds("trace"),
            "lower_s": seconds("lower"),
            "compile_s": seconds("compile"),
            "seconds": seconds("trace", "lower", "compile"),
            "hits": count("hit"),
            "misses": count("miss"),
        }

    def programs(self, *, after: float | None = None,
                 before: float | None = None) -> list[tuple]:
        """``(fun_name, seconds, read_back)`` of each program compiled, or
        read back from the cache, in ``(after, before]``."""
        out, read_back = [], False
        for _, kind, seconds, fun_name in self.events(after=after, before=before):
            if kind == "hit":
                read_back = True
            elif kind == "compile":
                out.append((fun_name or "<unnamed>", seconds, read_back))
                read_back = False
        return out


def describe(summary: dict) -> str:
    """The set-up log line's text."""
    return (f"{summary['programs']} programs, {summary['trace_s']:.1f} s tracing, "
            f"{summary['lower_s']:.1f} s lowering, {summary['compile_s']:.1f} s "
            f"compiling or reading back, {summary['hits']} hits, "
            f"{summary['misses']} misses")


class CompileLog:
    """A step loop's two log lines. The first ``lines(step)`` closes
    set-up and returns its summary; each later one names the programs that
    compiled since the one before (none, as a rule: one integer compared)."""

    def __init__(self, watch: CompileWatch):
        self.watch = watch
        self._mark: float | None = None
        self._seen = 0

    @property
    def ready(self) -> bool:
        """Set-up has been closed."""
        return self._mark is not None

    def lines(self, step: int, per_shape: tuple[str, ...] = ()) -> list[str]:
        """``per_shape`` names the programs that compile once for each shape
        of their input by design: one of those after set-up is a line, any
        other program a warning."""
        seen = self.watch.compiles   # read before the clock: a compile that
        if self.ready and seen == self._seen:   # lands between is not lost
            return []
        mark, self._mark, self._seen = self._mark, time.monotonic(), seen
        if mark is None:
            return ["set-up: " + describe(self.watch.summary(before=self._mark))]
        return [
            f"step {step} {'read back' if read_back else 'compiled'} {name} "
            f"for a new shape ({seconds:.2f} s)" if name in per_shape else
            f"warning: step {step} {'read back' if read_back else 'compiled'} "
            f"{name} after set-up ({seconds:.2f} s)"
            for name, seconds, read_back in self.watch.programs(
                after=mark, before=self._mark)
        ]


_WATCH = CompileWatch()


def get_watch() -> CompileWatch:
    """The process-wide watch; empty until something installs it."""
    return _WATCH


def install() -> CompileWatch:
    return _WATCH.install()
