"""What every cell's run shares: reading ``BENCHMARK.json`` and the cell's
data files, the device check, the compile counter, host spans, the profiler
window, the per-layer readers and the result line.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one family of models is a file of its own, found by the
name in ``BENCHMARK.json``: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``limits/<workload>.json``,
``metrics/<metric>.py`` - and, by the ``family`` that the configuration file
names, ``reference/<family>.py`` (the plain reference and the family's shape
arithmetic; imports nothing of the program) and ``program/<family>.py`` (the
one file that names the package's model schema for that family). Nothing
else under ``benchmark/`` knows a model's keys.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WORK_DIR = os.path.join(ROOT, ".bench_work")   # shards and traces; git-ignored
TRACE_SECONDS = 4.0   # how much of a traced run's window the profiler records


class RunFailed(Exception):
    """The run can report nothing: exit non-zero, print no result."""


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# The one place that says which runner drives a kind of traffic, and what the
# two modules of a family export for that runner, whatever the architecture.
# A family that does not train leaves ``train_cell``'s names out; a mix of a
# kind that no runner drives is refused when its cell is loaded.
RUNNER_OF_KIND = {"train": "train_cell", "backlog": "serve_cell"}
FAMILY_CONTRACT = {
    "reference": {
        "always": ("sizes_of", "make_weights", "control_matmul", "attention_shapes"),
        "serve_cell": ("serving_reference", "forward_flops_per_token"),
        "train_cell": ("train_steps", "leaf_norms", "train_flops_per_token", "ADAM_B1"),
    },
    "program": {
        "always": (),
        "serve_cell": ("model_config", "serve_config"),
        "train_cell": ("trainer_flags", "train_model_config"),
    },
}


def runner_name(cell: dict) -> str:
    """``serve_cell`` or ``train_cell``: the module of the benchmark whose
    ``run`` drives the cell's kind of traffic."""
    kind = cell["mix"].get("kind")
    if kind not in RUNNER_OF_KIND:
        raise RunFailed(f"traffic mix {cell['traffic']!r}: kind {kind!r} is none "
                        f"of {sorted(RUNNER_OF_KIND)}")
    return RUNNER_OF_KIND[kind]


def runner_of(cell: dict):
    return importlib.import_module("benchmark." + runner_name(cell))


@functools.cache
def _module_at(path: str):
    if not os.path.exists(path):
        raise RunFailed(f"no {os.path.relpath(path, ROOT)}")
    stem = os.path.relpath(path, BENCH_DIR)[:-len(".py")]
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + "".join(c if c.isalnum() else "_" for c in stem), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` of the benchmark, loaded by path, once (a
    metric's name may hold dots, and a later PR adds files, not ``import``
    lines)."""
    return _module_at(os.path.join(BENCH_DIR, kind, name + ".py"))


def attach_family(cell: dict) -> dict:
    """Put the configuration's ``family`` on the cell: its two modules as
    ``cell["reference"]`` and ``cell["program"]``, held to the contract's
    names for the cell's runner, and ``cell["sizes"]``, the model's sizes as
    the family's reference reads them from the configuration file. Whatever
    else the sizes hold, they hold ``vocab_size``: the traffic draws its
    token ids from it."""
    family = cell["config_file"].get("family")
    if not family:
        raise RunFailed(f"configuration {cell['config']!r} names no \"family\"")
    runner = runner_name(cell)
    for kind, names in FAMILY_CONTRACT.items():
        module = load_module(kind, family)
        missing = [n for n in names["always"] + names[runner]
                   if not hasattr(module, n)]
        if missing:
            raise RunFailed(f"benchmark/{kind}/{family}.py lacks {missing}")
        cell[kind] = module
    cell["sizes"] = cell["reference"].sizes_of(cell["config_file"])
    return cell


def load_cell(workload: str) -> dict:
    """The cell's entry with its configuration, its family's two modules and
    sizes, its traffic mix, limits and the names of the metrics it reports."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise RunFailed(f"no workload {workload!r} in BENCHMARK.json "
                        f"(has: {', '.join(cells)})")
    cell = dict(cells[workload])
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cell["config_file"] = load_json(ROOT, entry["file"])
    from benchmark import traffic

    cell["mix"] = traffic.load_mix(cell["traffic"], os.path.join(BENCH_DIR, "traffic"))
    attach_family(cell)
    limits_path = os.path.join(BENCH_DIR, "limits", f"{workload}.json")
    cell["limits"] = load_json(limits_path) if os.path.exists(limits_path) else {}

    def reported(metric):
        return workload in metric.get("workloads", [workload])

    cell["end_to_end"] = [m for m in manifest["end_to_end"] if reported(m)]
    cell["per_layer"] = [m for m in manifest["per_layer"] if reported(m)]
    return cell


# --- the device ---------------------------------------------------------------


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; refuses anything but ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise RunFailed(f"JAX found no TPU (platform {platform!r}): the "
                        f"benchmark measures nothing off the chip")
    if len(devices) < chips:
        raise RunFailed(f"the cell asks for {chips} chip(s), JAX found {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind, "count": chips}


def load_peaks(kind: str) -> dict:
    peaks = load_json(BENCH_DIR, "peaks.json")
    if kind not in peaks:
        raise RunFailed(f"no peaks on record for device kind {kind!r} in "
                        f"benchmark/peaks.json: a share of a guessed peak is no number")
    return peaks[kind]


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts traces, lowerings and backend compiles through
    ``jax.monitoring``; a window in which the count moved compiled."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event in self.EVENTS:
            self.count += 1


# --- spans and the profiler window -------------------------------------------


class Spans:
    """Host spans kept in memory: ``(name, start_s, end_s)`` on the
    monotonic clock. While the profiler records, each span is also a
    ``TraceAnnotation`` named ``bench/<name>``, so that the device trace
    and the spans share the profiler's clock."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        note = None
        if self.annotate:
            import jax

            note = jax.profiler.TraceAnnotation("bench/" + name)
            note.__enter__()
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.records.append((name, t0, time.monotonic()))
            if note is not None:
                note.__exit__(None, None, None)


class ProfilerWindow:
    """Records the first ``TRACE_SECONDS`` of a traced run's window."""

    def __init__(self, workload: str, spans: Spans, enabled: bool):
        self.dir = os.path.join(WORK_DIR, workload, "trace")
        self.spans = spans
        self.enabled = enabled
        self.active = False
        self._window = None
        self.started_at = self.stopped_at = 0.0

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # host spans come from Spans
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.active = True
        self.spans.annotate = True
        self._window = jax.profiler.TraceAnnotation("bench/window")
        self._window.__enter__()
        self.started_at = time.monotonic()

    def maybe_stop(self, force: bool = False) -> bool:
        """Call between steps, with the device drained. True when this call
        stopped the recording: the moment to read the program's counters
        for the traced part of the window."""
        if not self.active:
            return False
        if not force and time.monotonic() - self.started_at < TRACE_SECONDS:
            return False
        import jax

        self._window.__exit__(None, None, None)
        self.spans.annotate = False
        jax.profiler.stop_trace()
        self.stopped_at = time.monotonic()
        self.active = False
        return True

    def read(self):
        from benchmark.reduce_trace import Trace

        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:   # for looking at a trace by hand; the result is the same
            from benchmark.reduce_trace import find_xplane

            os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
            shutil.copyfile(find_xplane(self.dir), keep)
        trace = Trace.from_file(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace


# --- per-layer readers --------------------------------------------------------


def load_reader(name: str):
    """The ``read(ctx)`` of ``metrics/<name>.py``."""
    return load_module("metrics", name).read


def read_layer_metrics(cell: dict, ctx: dict) -> dict:
    """Run the reader of each per-layer metric of the cell. A reader that
    finds nothing to read returns None and its metric is left out; a
    kernel reader that finds no event of its kernel raises, which fails the
    traced run."""
    out = {}
    for metric in cell["per_layer"]:
        value = load_reader(metric["name"])(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def metrics_of(cell: dict, values: dict, device: dict, profiler: "ProfilerWindow",
               spans: Spans, ctx: dict):
    """(metrics, breakdown) of a run. Untraced: the cell's end-to-end
    metrics out of ``values``. Traced: the trace is reduced, ``busy_s`` and
    ``window_s`` go into ``device``, and the cell's per-layer readers get
    ``ctx`` with the cell (its family's reference, for the shape arithmetic,
    is ``ctx["cell"]["reference"]``), the trace, the spans, the peaks and the
    sizes added."""
    if not profiler.enabled:
        missing = [m["name"] for m in cell["end_to_end"] if m["name"] not in values]
        if missing:
            raise RunFailed(f"the window gave no {missing}")
        return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in cell["end_to_end"]}, None
    trace = profiler.read()
    lo, hi = trace.window_ns()
    device["busy_s"] = trace.busy_seconds(lo, hi)
    device["window_s"] = (hi - lo) / 1e9
    breakdown = {"device_ops": trace.top_ops(lo, hi),
                 "idle_gaps": trace.idle_gaps(lo, hi)}
    ctx = dict(ctx, cell=cell, sizes=cell["sizes"],
               peaks=load_peaks(device["kind"]), spans=spans.records,
               values=values, trace=trace, trace_window_ns=(lo, hi), device=device)
    return read_layer_metrics(cell, ctx), breakdown


# --- the result ----------------------------------------------------------------


def print_result(*, correct: bool, attempted: int, failed: int, metrics: dict,
                 device: dict, compared: list, breakdown: dict | None) -> None:
    """Each number compared beside its limit as the last lines on standard
    error; the one JSON object as the last line on standard output, with
    the numbers compared as its last key."""
    sys.stdout.flush()
    for row in compared:
        verdict = "not compared" if row["limit"] is None else (
            "ok" if row["ok"] else "OVER")
        print(f"compared {row['name']}: {row['value']!r} limit {row['limit']!r} "
              f"[{verdict}]", file=sys.stderr)
    sys.stderr.flush()
    result = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {
        row["name"]: {"value": row["value"], "limit": row["limit"]}
        for row in compared
    }
    print(json.dumps(result), flush=True)
