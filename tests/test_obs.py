"""Observability subsystem: tracer span semantics, disabled fast path,
rotation bounds, multi-process report merging, watchdog span dumps, and
serving-trace fidelity (TTFT parity + bit-parity with tracing on).

The tracer's contract is tested at the JSONL layer — records are the
public interface ``scripts/obs_report.py`` consumes, so every assertion
here reads them back the way the report tool would.
"""

from __future__ import annotations

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from gpt_2_distributed_tpu.coordination import HangWatchdog
from gpt_2_distributed_tpu.obs.trace import (
    _NULL_SPAN,
    Tracer,
    XlaCapture,
    get_tracer,
    parse_profile_at,
)
from scripts.obs_report import (
    build_report,
    load_trace_dir,
    request_waterfall,
    step_breakdown,
)


@pytest.fixture(autouse=True)
def _reset_global_tracer():
    """Every test leaves the process-wide tracer the way train/serve runs
    start: disabled. Tests that enable it do so through configure()."""
    yield
    get_tracer().configure(None, enabled=False)


def read_records(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def host_annotations(profile_dir):
    """``{name: [(thread line, start_ns, end_ns), ...]}`` of the tracer's
    and the benchmark's annotations on the host plane of the capture under
    ``profile_dir``."""
    from benchmark.reduce_trace import HOST_PLANE, find_xplane

    data = jax.profiler.ProfileData.from_file(find_xplane(profile_dir))
    found: dict[str, list] = {}
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("gpt2/", "bench/")):
                    found.setdefault(ev.name, []).append(
                        (line.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return found


# --- span runtime -----------------------------------------------------------


class TestTracerCore:
    def test_disabled_is_shared_noop(self, tmp_path):
        tr = Tracer()  # default construction: disabled
        assert not tr.enabled
        s1 = tr.span("a", attr=1)
        s2 = tr.span("b")
        assert s1 is _NULL_SPAN and s2 is _NULL_SPAN  # no per-call alloc
        with s1 as s:
            s.set(more=2)  # no-op, no raise
        tr.event("ev", x=1)
        assert tr.open_spans() == {}
        # and the disabled tracer never touched the filesystem
        assert list(tmp_path.iterdir()) == []

    def test_nesting_parent_links_and_ordering(self, tmp_path):
        tr = Tracer(str(tmp_path), enabled=True)
        with tr.span("outer", step=1):
            time.sleep(0.002)
            with tr.span("inner"):
                time.sleep(0.002)
        tr.close()
        recs = read_records(tr.trace_path)
        assert recs[0]["ph"] == "meta"
        assert "wall" in recs[0] and "perf" in recs[0]
        spans = {r["name"]: r for r in recs if r["ph"] == "span"}
        inner, outer = spans["inner"], spans["outer"]
        # written on close: inner closes first
        assert [r["name"] for r in recs if r["ph"] == "span"] == [
            "inner", "outer",
        ]
        assert outer["parent"] is None
        assert inner["parent"] == outer["sid"]
        assert outer["ts"] <= inner["ts"]
        assert inner["dur"] <= outer["dur"]
        assert outer["attrs"] == {"step": 1}

    def test_events_counters_and_set(self, tmp_path):
        tr = Tracer(str(tmp_path), enabled=True)
        with tr.span("phase") as sp:
            sp.set(batch=4)
        tr.event("boom", reason="test")
        tr.event("stamped", ts=123.456, rid=7)
        tr.close()
        recs = read_records(tr.trace_path)
        by_name = {r["name"]: r for r in recs if r["ph"] != "meta"}
        assert by_name["phase"]["attrs"] == {"batch": 4}
        assert by_name["boom"]["ph"] == "event"
        assert by_name["stamped"]["ts"] == 123.456  # explicit ts honored

    def test_sibling_spans_share_parent(self, tmp_path):
        tr = Tracer(str(tmp_path), enabled=True)
        with tr.span("step"):
            with tr.span("a"):
                pass
            with tr.span("b"):
                pass
        tr.close()
        spans = {r["name"]: r for r in read_records(tr.trace_path)
                 if r["ph"] == "span"}
        assert spans["a"]["parent"] == spans["step"]["sid"]
        assert spans["b"]["parent"] == spans["step"]["sid"]
        assert spans["a"]["sid"] != spans["b"]["sid"]

    def test_open_spans_per_thread(self, tmp_path):
        tr = Tracer(str(tmp_path), enabled=True)
        entered = threading.Event()
        release = threading.Event()

        def worker():
            with tr.span("bg_commit"):
                entered.set()
                release.wait(5)

        t = threading.Thread(target=worker, daemon=True)
        with tr.span("step"):
            with tr.span("device_sync"):
                t.start()
                assert entered.wait(5)
                snap = tr.open_spans()
                txt = tr.format_open_spans()
        release.set()
        t.join(5)
        tr.close()
        stacks = sorted(snap.values(), key=len)
        assert ["bg_commit"] in stacks
        assert ["step", "device_sync"] in stacks
        assert "step > device_sync" in txt

    def test_rotation_bounds_disk(self, tmp_path):
        limit = 4096
        tr = Tracer(str(tmp_path), enabled=True, max_file_bytes=limit)
        for i in range(400):
            tr.event("filler", i=i, pad="x" * 64)
        tr.close()
        live = tr.trace_path
        rotated = live + ".1"
        assert os.path.exists(rotated), "rotation never happened"
        # one generation kept: bounded at ~2x the limit, never unbounded
        slack = 512  # one record past the threshold triggers the roll
        assert os.path.getsize(live) <= limit + slack
        assert os.path.getsize(rotated) <= limit + slack
        assert set(os.listdir(tmp_path)) == {
            os.path.basename(live), os.path.basename(rotated),
        }
        # both generations stay parseable JSONL
        for p in (live, rotated):
            assert read_records(p)

    def test_configure_reuses_instance(self, tmp_path):
        tr = get_tracer()
        assert not tr.enabled
        same = tr.configure(str(tmp_path), process_index=3)
        assert same is tr and tr.enabled
        assert tr.trace_path.endswith("trace-p3.jsonl")
        with tr.span("s"):
            pass
        tr.configure(None, enabled=False)
        assert not tr.enabled
        recs = read_records(os.path.join(str(tmp_path), "trace-p3.jsonl"))
        assert [r["name"] for r in recs if r["ph"] == "span"] == ["s"]


# --- XLA capture window -----------------------------------------------------


class TestXlaCapture:
    def test_parse_profile_at(self):
        assert parse_profile_at(None) is None
        assert parse_profile_at("") is None
        assert parse_profile_at("200") == (200, 1)
        assert parse_profile_at("200:5") == (200, 5)
        for bad in ("-1", "5:0", "abc", "5:-2"):
            with pytest.raises(ValueError):
                parse_profile_at(bad)

    def test_inert_without_spec(self, tmp_path):
        xc = XlaCapture(None, str(tmp_path))
        assert not xc.maybe_start(10**9)
        assert not xc.maybe_stop(10**9)
        xc.stop_if_active()  # no-op, no raise
        assert not os.path.exists(os.path.join(str(tmp_path), "xla_profile"))

    def test_window_start_stop(self, tmp_path):
        tr = get_tracer().configure(str(tmp_path))
        xc = XlaCapture((3, 2), str(tmp_path))
        assert not xc.maybe_start(2)
        with tr.span("before_window"):
            pass
        assert xc.maybe_start(3)        # window opens at step 3
        with tr.span("in_window", step=3):
            time.sleep(0.001)
        assert not xc.maybe_stop(3)     # covers steps 3-4
        assert xc.maybe_stop(4)
        with tr.span("after_window"):
            pass
        assert xc.done and not xc.maybe_start(5)  # one-shot
        tr.close()
        assert os.path.isdir(xc.profile_dir)
        names = [r.get("name") for r in read_records(tr.trace_path)]
        assert "xla_profile_start" in names and "xla_profile_stop" in names
        # every span has its JSONL record; the capture holds the one that
        # was entered inside the window, and neither of the others
        assert {"before_window", "in_window", "after_window"} <= set(names)
        captured = host_annotations(xc.profile_dir)
        assert "gpt2/in_window" in captured
        assert not {"gpt2/before_window", "gpt2/after_window"} & set(captured)


# --- watchdog integration ---------------------------------------------------


def test_watchdog_dump_names_open_spans(tmp_path, capsys):
    tr = get_tracer().configure(str(tmp_path))
    wd = HangWatchdog(timeout_s=60.0, _exit=lambda code: None)
    with tr.span("step", n=7):
        with tr.span("consensus_exchange"):
            wd._fire()
    tr.close()
    out = capsys.readouterr().out
    assert "[watchdog] open spans" in out
    assert "step > consensus_exchange" in out
    names = [r.get("name") for r in read_records(tr.trace_path)]
    assert "hang_watchdog_fired" in names


# --- report tool ------------------------------------------------------------


class TestObsReport:
    def _emit_steps(self, tr, n, phase_s=0.002):
        for i in range(n):
            with tr.span("step", n=i + 1):
                with tr.span("data_fetch"):
                    time.sleep(phase_s)
                with tr.span("step_dispatch", step=i + 1):
                    time.sleep(phase_s)
                with tr.span("device_sync", step=i + 1):
                    time.sleep(phase_s)

    def test_step_breakdown_attribution(self, tmp_path):
        tr = Tracer(str(tmp_path), enabled=True)
        self._emit_steps(tr, 5)
        tr.close()
        bd = step_breakdown(load_trace_dir(str(tmp_path)))
        assert bd["n_steps"] == 5
        assert set(bd["phases"]) == {
            "data_fetch", "step_dispatch", "device_sync",
        }
        for ph in bd["phases"].values():
            assert ph["n"] == 5
            assert ph["p50_ms"] <= ph["p99_ms"]
        assert bd["residual"]["mean_ms"] >= 0
        assert 0 < bd["attributed_pct"] <= 100
        # pure-sleep phases under a tight loop: residual is overhead only
        assert bd["attributed_pct"] > 90

    def test_nested_children_not_double_counted(self, tmp_path):
        tr = Tracer(str(tmp_path), enabled=True)
        with tr.span("step", n=1):
            with tr.span("consensus_exchange"):
                with tr.span("pod_barrier"):  # grandchild of step
                    time.sleep(0.002)
        tr.close()
        bd = step_breakdown(load_trace_dir(str(tmp_path)))
        assert "consensus_exchange" in bd["phases"]
        assert "pod_barrier" not in bd["phases"]  # only DIRECT children sum

    def test_multi_process_merge(self, tmp_path):
        for rank in range(2):
            tr = Tracer(str(tmp_path), enabled=True, process_index=rank)
            self._emit_steps(tr, 3, phase_s=0.001)
            tr.close()
        assert sorted(os.listdir(tmp_path)) == [
            "trace-p0.jsonl", "trace-p1.jsonl",
        ]
        records = load_trace_dir(str(tmp_path))
        bd = step_breakdown(records)
        assert bd["processes"] == [0, 1]
        assert bd["n_steps"] == 6  # both ranks' steps in one breakdown
        report = build_report(str(tmp_path))
        assert report["train_steps"]["n_steps"] == 6

    def test_tolerates_torn_tail_line(self, tmp_path):
        tr = Tracer(str(tmp_path), enabled=True)
        self._emit_steps(tr, 2, phase_s=0.0)
        tr.close()
        with open(tr.trace_path, "a", encoding="utf-8") as f:
            f.write('{"ph": "span", "name": "torn')  # crash mid-write
        bd = step_breakdown(load_trace_dir(str(tmp_path)))
        assert bd["n_steps"] == 2


# --- serving trace fidelity -------------------------------------------------


@pytest.fixture(scope="module")
def tiny_params(tiny_config):
    from gpt_2_distributed_tpu.models import gpt2

    return gpt2.init_params(tiny_config, seed=0)


def _traced_engine_run(tiny_params, tiny_config, trace_dir):
    from gpt_2_distributed_tpu.config import ServeConfig
    from gpt_2_distributed_tpu.serving import ServingEngine

    get_tracer().configure(str(trace_dir))
    eng = ServingEngine(
        tiny_params, tiny_config,
        ServeConfig(max_batch=2, block_size=8, num_blocks=32,
                    attn_impl="xla", prefill_chunk=4, prefix_cache=True),
        temperature=0.0,
    )
    handles = [
        eng.submit([1, 2, 3, 4, 5], 6, rng=0),
        eng.submit([1, 2, 3, 4, 5, 6, 7, 8, 9], 4, rng=1),
    ]
    eng.run_until_idle()
    get_tracer().configure(None, enabled=False)
    return eng, handles


def test_serving_trace_ttft_parity_and_bit_parity(
    tmp_path, tiny_params, tiny_config
):
    """The two serving acceptance checks in one engine run: trace-derived
    TTFT must match the engine's own accounting (same clock, same stamps —
    the bar is 1 ms, the mechanism makes it exact), and tracing must not
    perturb a single generated token vs generate_cached(batch=1)."""
    import jax.numpy as jnp

    from gpt_2_distributed_tpu.models.decode import generate_cached

    eng, handles = _traced_engine_run(tiny_params, tiny_config, tmp_path)

    records = load_trace_dir(str(tmp_path))
    wf = request_waterfall(records)
    assert wf is not None and wf["n_requests"] == 2
    rows = {row["rid"]: row for row in wf["requests"]}
    for h in handles:
        engine_ttft_ms = (h.first_token_time - h.submit_time) * 1e3
        trace_ttft_ms = rows[h.id]["first_token_ms"]
        assert abs(trace_ttft_ms - engine_ttft_ms) < 1.0  # acceptance bar
        assert trace_ttft_ms == pytest.approx(engine_ttft_ms, abs=1e-6)
        assert rows[h.id]["n_generated"] == len(h.generated)
        assert rows[h.id]["events"]["submit"] == 1
        assert rows[h.id]["events"]["admit"] >= 1
        assert rows[h.id]["events"]["finish"] == 1

    # bit-parity vs the one-shot reference, with tracing having been ON
    for h in handles:
        ref = generate_cached(
            tiny_params, tiny_config,
            jnp.asarray([h.prompt], jnp.int32),
            jax.random.PRNGKey(h.id),  # rng=0 / rng=1 above
            max_new_tokens=h.max_new_tokens, temperature=0.0,
        )
        assert h.generated == np.asarray(ref)[0, len(h.prompt):].tolist()

    # engine_step spans made it out, with their phase children
    bd = step_breakdown(records, step_name="engine_step")
    assert bd is not None and bd["n_steps"] >= 1
    assert "decode" in bd["phases"] or "prefill" in bd["phases"]


def test_engine_default_run_writes_no_trace(tmp_path, tiny_params, tiny_config):
    """Tracing off (the default): the engine runs, emits tokens, and the
    filesystem stays untouched — no trace-p*.jsonl anywhere."""
    from gpt_2_distributed_tpu.config import ServeConfig
    from gpt_2_distributed_tpu.serving import ServingEngine

    assert not get_tracer().enabled
    eng = ServingEngine(
        tiny_params, tiny_config,
        ServeConfig(max_batch=2, block_size=8, num_blocks=32,
                    attn_impl="xla"),
        temperature=0.0,
    )
    h = eng.submit([1, 2, 3], 4, rng=0)
    eng.run_until_idle()
    assert h.done and len(h.generated) == 4
    assert list(tmp_path.iterdir()) == []


def test_sharded_engine_trace_mesh_and_cross_shard_spans(
    tmp_path, tiny_params, tiny_config
):
    """A mesh-sharded engine run leaves its shape in the trace: the
    engine_mesh construction event (what obs_report's mesh_summary and the
    --frontend mesh line read), a shard_scatter span per whole-prompt
    prefill and a token_allgather span per decode step — the two
    cross-shard transfers a capacity model has to price."""
    from gpt_2_distributed_tpu.config import ServeConfig
    from gpt_2_distributed_tpu.serving import ServingEngine
    from scripts.obs_report import mesh_summary

    get_tracer().configure(str(tmp_path))
    eng = ServingEngine(
        tiny_params, tiny_config,
        ServeConfig(max_batch=2, block_size=8, num_blocks=32,
                    attn_impl="xla", mesh="data:2"),
        temperature=0.0,
    )
    hs = [eng.submit([1, 2, 3, 4, 5], 4, rng=0),
          eng.submit([9, 8, 7], 4, rng=1)]
    eng.run_until_idle()
    get_tracer().configure(None, enabled=False)
    assert all(h.done for h in hs)

    records = load_trace_dir(str(tmp_path))
    mesh_evs = [r for r in records
                if r.get("ph") == "event" and r["name"] == "engine_mesh"]
    assert len(mesh_evs) == 1
    assert mesh_evs[0]["attrs"] == {
        "mesh": "data:2", "devices": 2, "data": 2, "tp": 1,
    }
    spans = {r["name"] for r in records if r.get("ph") == "span"}
    assert "shard_scatter" in spans     # one per whole-prompt prefill
    assert "token_allgather" in spans   # one per decode step

    ms = mesh_summary(records)
    assert ms == {
        "n_engines": 1,
        "shapes": {"data:2": 1},
        "devices_per_engine": 2,
        "replica_meshes": None,   # single engine, no router scale_up
    }
    assert build_report(str(tmp_path))["meshes"] == ms
