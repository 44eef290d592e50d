"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, a kernel's time, the device operations that took most time, and the
longest idle gaps by what the host was doing.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. What a
TPU trace holds (looked at by hand, PR 24): one plane ``/device:TPU:<n>``
per chip whose line ``XLA Ops`` carries one event per executed HLO
operation, named by the instruction's whole text, a line ``XLA Modules``
with one event per program run, and a plane ``/host:CPU`` with one line per
host thread, where the harness's own ``jax.profiler.TraceAnnotation`` spans
(named ``bench/<span>``) and the program's (``gpt2/<span>``: every span of
its tracer entered while a capture records) land. All planes share one
clock, in nanoseconds.
Two things a reader has to know. Operations nest: a ``while`` or a
conditional is an event that spans the events of its body, so time by
operation is self time. And a Pallas kernel shows as ``%<hlo name> = ...
custom-call(...), custom_call_target="tpu_custom_call"``: the kernel
function's name is nowhere in it (the ``pallas_call``s carry no ``name=``),
only the instruction name that JAX derived from the name stack (``jvp__``,
``transpose_jvp___``, ``closed_call``).

``python -m benchmark.reduce_trace <file-or-dir>`` prints planes, lines and
the heaviest events: look at a trace by hand before writing a reader.
"""

from __future__ import annotations

import bisect
import glob
import os
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIXES = ("bench/", "gpt2/")   # the harness's spans, the program's


class NoKernelEvent(LookupError):
    """A kernel reader found no event of its kernel in the trace."""


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def union_seconds(intervals) -> float:
    """Total length of the union of ``(start_ns, end_ns)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


class Trace:
    """Device operations per chip, and the host spans of the harness and of
    the program."""

    def __init__(self, device_ops: dict[str, list], host_spans: list):
        # plane name -> [(name, start_ns, end_ns)], sorted by start
        self.device_ops = {
            # an enclosing operation before those nested in it
            k: sorted(v, key=lambda e: (e[1], -e[2])) for k, v in device_ops.items()
        }
        # [(span name without its prefix, start_ns, end_ns)], an enclosing
        # span before those nested in it
        self.host_spans = sorted(host_spans, key=lambda e: (e[1], -e[2]))

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(find_xplane(path))
        device_ops: dict[str, list] = {}
        host_spans = []
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PLANE_PREFIX):
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    device_ops.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                    )
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for ev in line.events:
                        name = span_name(ev.name)
                        if name is not None:
                            host_spans.append((
                                name, ev.start_ns, ev.start_ns + ev.duration_ns))
        return cls(device_ops, host_spans)

    # -- the traced window: the harness's outermost span, or all device ops --

    def window_ns(self, span: str = "window") -> tuple[float, float]:
        spans = [(s, e) for n, s, e in self.host_spans if n == span]
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
        ops = [e for v in self.device_ops.values() for e in v]
        if not ops:
            raise LookupError("the trace holds no device operation and no window span")
        return min(e[1] for e in ops), max(e[2] for e in ops)

    def _clipped(self, plane: str, lo: float, hi: float):
        for name, s, e in self.device_ops[plane]:
            if e > lo and s < hi:
                yield name, max(s, lo), min(e, hi)

    def busy_seconds(self, lo: float, hi: float) -> float:
        """Seconds in which an operation ran on the device, averaged over
        the chips that the trace holds."""
        if not self.device_ops:
            return 0.0
        per_chip = [
            union_seconds((s, e) for _, s, e in self._clipped(p, lo, hi))
            for p in self.device_ops
        ]
        return sum(per_chip) / len(per_chip)

    def kernel_seconds(self, needles, lo: float, hi: float):
        """(seconds, events) of the operations whose name starts with
        ``needles[0]`` and contains every further needle, summed over the
        window and averaged over chips. Raises :class:`NoKernelEvent` where
        there is none."""
        head, rest = needles[0], needles[1:]
        total, count = 0.0, 0
        for p in self.device_ops:
            for name, s, e in self._clipped(p, lo, hi):
                if name.startswith(head) and all(n in name for n in rest):
                    total += (e - s) / 1e9
                    count += 1
        if count == 0:
            raise NoKernelEvent(
                f"no device operation named like {needles!r} in the traced window"
            )
        chips = len(self.device_ops)
        return total / chips, count // chips

    def top_ops(self, lo: float, hi: float, n: int = 10):
        """The ``n`` operations that took most device time of their own
        (an operation's time less that of the operations nested in it), by
        instruction name with its numeric suffix and operands cut off, so
        that the same operation of every layer counts as one."""
        by_name: dict[str, float] = {}
        for p in self.device_ops:
            stack = []     # open events: [name, end_ns, self_ns]

            def close(upto):
                while stack and stack[-1][1] <= upto:
                    name, _, own = stack.pop()
                    by_name[name] = by_name.get(name, 0.0) + own / 1e9

            for name, s, e in self._clipped(p, lo, hi):
                close(s)
                if stack:
                    stack[-1][2] -= e - s
                stack.append([short_name(name), e, e - s])
            close(float("inf"))
        chips = max(len(self.device_ops), 1)
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[name, sec / chips] for name, sec in ranked]

    def idle_gaps(self, lo: float, hi: float, n: int = 10):
        """Idle time of the first chip by the innermost span open when each
        gap began - the program's own where it has one there (``admit``,
        ``prefill``, ``dispatch``, ``readback``, ``emit``...), else the
        harness's (``submit``, ``step``...), ``(none)`` where no span was
        open - the ``n`` largest sums."""
        if not self.device_ops:
            return []
        plane = sorted(self.device_ops)[0]
        gaps, cursor = [], lo
        for _, s, e in self._clipped(plane, lo, hi):
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if hi > cursor:
            gaps.append((cursor, hi))
        # The innermost open span at any time, from one sweep over the
        # spans' ends (they nest: one thread, context managers).
        edges = []
        for name, s, e in self.host_spans:
            if name != "window":
                edges += [(s, 1, name), (e, 0, name)]
        times, innermost, stack = [], [], []
        for t, opens, name in sorted(edges, key=lambda x: (x[0], x[1])):
            if opens:
                stack.append(name)
            elif name in stack:
                stack.remove(name)
            times.append(t)
            innermost.append(stack[-1] if stack else "(none)")
        by_span: dict[str, float] = {}
        for g0, g1 in gaps:
            i = bisect.bisect_right(times, g0) - 1
            name = innermost[i] if i >= 0 else "(none)"
            by_span[name] = by_span.get(name, 0.0) + (g1 - g0) / 1e9
        ranked = sorted(by_span.items(), key=lambda kv: -kv[1])[:n]
        return [[name, sec] for name, sec in ranked]


def span_name(event_name: str) -> str | None:
    """``bench/step`` -> ``step``, ``gpt2/admit`` -> ``admit``; None for
    what is no span of the harness or the program."""
    for prefix in SPAN_PREFIXES:
        if event_name.startswith(prefix):
            return event_name[len(prefix):]
    return None


def short_name(hlo_text: str) -> str:
    """``%fusion.2166 = (f32[...]) fusion(...)`` -> ``fusion``; a Pallas
    kernel keeps its mark: ``jvp__ (tpu_custom_call)``."""
    name = hlo_text.split(" = ", 1)[0].lstrip("%").rstrip("0123456789").rstrip(".")
    if 'custom_call_target="tpu_custom_call"' in hlo_text:
        name += " (tpu_custom_call)"
    return name


def summary(path: str, top: int = 25) -> str:
    """Planes, lines and heaviest events of a trace, for reading by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            by_name: dict[str, list] = {}
            n = 0
            for ev in line.events:
                n += 1
                rec = by_name.setdefault(ev.name, [0, 0.0])
                rec[0] += 1
                rec[1] += ev.duration_ns
            out.append(f"  LINE {line.name}: {n} events, {len(by_name)} names")
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:top]
            for name, (count, dur) in ranked:
                out.append(f"    {dur / 1e6:12.3f} ms  x{count:<6d} {name[:150]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(summary(sys.argv[1]))
