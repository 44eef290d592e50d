"""The serving engine times itself: the phase clocks and work counters of
``ServingEngine.step`` (one construct feeds the span and the counter), and
the tracer's spans inside a profiler capture that nobody told the tracer
about. Times are of the CPU and mean nothing; the relations between them
are what is held."""

from __future__ import annotations

import time

import jax
import numpy as np
import pytest

from gpt_2_distributed_tpu.config import ServeConfig
from gpt_2_distributed_tpu.models import gpt2
from gpt_2_distributed_tpu.obs import compile_watch
from gpt_2_distributed_tpu.obs.trace import _NULL_SPAN, get_tracer
from gpt_2_distributed_tpu.metrics import METRIC_REGISTRY
from gpt_2_distributed_tpu.serving import ServingEngine
from gpt_2_distributed_tpu.serving.step_clocks import step_clocks
from test_obs import host_annotations

CLOCKS = ("step_ms", "admit_ms", "grow_ms", "prefill_ms", "decode_ms",
          "decode_dispatch_ms", "emit_ms", "draft_ms", "verify_ms")
COUNTS = ("steps", "decode_steps", "decode_rows", "decode_attended",
          "decode_blocks_live", "decode_blocks_table")

MODES = {
    # chunked prefill, worst-case reservation: the benchmark cell's mode
    "chunked": dict(prefill_chunk=4),
    # whole-prompt prefill inside admission, watermark growth
    "whole-watermark": dict(admission="watermark", watermark_blocks=1),
    "speculative": dict(prefill_chunk=4, spec="draft:124M,k:2"),
}


def _clocks_partition(s) -> bool:
    """The host's phases of a step lie side by side, and so do the two waits
    for the device. ``prefill_ms`` runs from the read-back that let the chunk
    start to the chunk's first token, beside the turn's emit loop: the emit
    is on both clocks, so the two sums are held apart."""
    host = s["admit_ms"] + s["grow_ms"] + s["decode_ms"] + s["emit_ms"]
    waits = s["admit_ms"] + s["grow_ms"] + s["decode_ms"] + s["prefill_ms"]
    return s["step_ms"] >= host > 0 and s["step_ms"] >= waits > 0


@pytest.fixture(scope="module")
def tiny_params(tiny_config):
    return gpt2.init_params(tiny_config, seed=0)


def _engine(mode, tiny_params, tiny_config):
    serve = ServeConfig(max_batch=3, block_size=8, num_blocks=32,
                        attn_impl="xla", **MODES[mode])
    kw = {}
    if mode == "speculative":
        draft_config = tiny_config.replace(n_layer=1)
        kw = dict(draft_params=gpt2.init_params(draft_config, seed=1),
                  draft_config=draft_config)
    return ServingEngine(tiny_params, tiny_config, serve, temperature=0.0, **kw)


@pytest.mark.parametrize("mode", MODES)
def test_step_clocks_nest_add_up_and_only_grow(mode, tiny_params, tiny_config):
    eng = _engine(mode, tiny_params, tiny_config)
    assert eng.step() == 0 and eng.stats["steps"] == 0   # found no work
    rng = np.random.default_rng(1)
    for i, (p, new) in enumerate([(5, 6), (11, 4), (17, 7), (3, 5)]):
        eng.submit(rng.integers(1, 256, p).tolist(), new, rng=i)
    last = dict(eng.stats)
    expected_rows = expected_attended = 0
    while eng.has_work():
        active = eng.active.copy()
        pos = eng.pos.copy()
        steps_before = eng.stats["decode_steps"]
        eng.step()
        now = dict(eng.stats)
        assert all(now[k] >= last[k] for k in CLOCKS + COUNTS), (last, now)
        if now["decode_steps"] > steps_before and mode == "chunked":
            # admission and the prefill tick only ever add rows at pos = the
            # prompt's length before the decode dispatch; rows active at the
            # step's start decode at the pos they had
            assert now["decode_rows"] - last["decode_rows"] >= int(active.sum())
            assert (now["decode_attended"] - last["decode_attended"]
                    >= int((pos[active] + 1).sum()))
        last = now
    s = eng.stats
    assert s["steps"] > s["decode_steps"] > 0 or mode != "chunked"
    assert _clocks_partition(s)
    assert s["admit_ms"] >= 0 and s["emit_ms"] > 0
    assert 0 < s["decode_dispatch_ms"] < s["decode_ms"] - s["draft_ms"]
    if mode == "speculative":
        assert s["decode_ms"] == pytest.approx(s["draft_ms"] + s["verify_ms"], rel=1e-9)
    else:
        assert s["draft_ms"] == s["verify_ms"] == 0
    assert (s["grow_ms"] > 0) == (mode == "whole-watermark")
    assert s["decode_rows"] >= s["decode_steps"] and s["decode_attended"] > s["decode_rows"]
    # how often a decode step went out over the step before, still unread:
    # never in a speculative round, which decides on token values
    if mode == "speculative":
        assert s["decode_overlapped"] == 0
    else:
        assert 0 < s["decode_overlapped"] < s["decode_steps"]
    assert s["decode_rows"] <= s["decode_blocks_live"] <= s["decode_blocks_table"]
    # what /metrics and the --tb_dir sink show of them: means per step,
    # every one registered, and the fleet's the same as its one engine's
    snap = eng.metrics_snapshot()
    per_step = {"engine_host_ms": s["step_ms"] - s["prefill_ms"] - s["decode_ms"],
                "admit_ms": s["admit_ms"], "grow_ms": s["grow_ms"],
                "emit_ms": s["emit_ms"]}
    per_other = {"prefill_dispatch_ms": "prefill_dispatches", "intake_ms": "submits"}
    for key, count in per_other.items():
        assert snap[key] == pytest.approx(s[key] / max(s[count], 1)), key
    assert s["submits"] == 4 and snap["intake_ms"] > 0
    assert (snap["prefill_dispatch_ms"] > 0) == (mode != "whole-watermark")
    per_decode = {"decode_dispatch_ms": s["decode_dispatch_ms"],
                  "decode_overlapped": s["decode_overlapped"],
                  "decode_wait_ms": s["decode_ms"] - s["draft_ms"] - s["decode_dispatch_ms"],
                  "decode_rows": s["decode_rows"], "decode_attended": s["decode_attended"],
                  "decode_blocks_live": s["decode_blocks_live"],
                  "decode_blocks_table": s["decode_blocks_table"]}
    for key, total in per_step.items():
        assert snap[key] == pytest.approx(total / s["steps"]), key
    for key, total in per_decode.items():
        assert snap[key] == pytest.approx(total / s["decode_steps"]), key
    # a chunk's prefill_ms runs beside the turn's emit (`_clocks_partition`),
    # so only with it added back do the host's phases fit in the step
    assert (snap["engine_host_ms"] + s["prefill_ms"] / s["steps"]
            >= snap["admit_ms"] + snap["grow_ms"] + snap["emit_ms"] > 0)
    assert snap["decode_wait_ms"] > 0
    assert step_clocks([s, s]) == pytest.approx(step_clocks([s]))
    assert all(key in METRIC_REGISTRY for key in snap)

def test_a_step_unread_is_work_and_the_clocks_still_partition(tiny_params, tiny_config):
    """Between two ``step()`` calls the decode loop leaves one step unread:
    ``has_work()`` says so until its tokens are out, the turn that reads the
    last one dispatches nothing, and with every turn counted ``decode`` holds
    one dispatch and one read-back a decode step."""
    eng = _engine("chunked", tiny_params, tiny_config)
    h = eng.submit([1, 2, 3, 4, 5], 4, rng=0)
    turns = []
    while eng.has_work():
        before = eng.stats["decode_steps"], len(h.generated)
        eng.step()
        turns.append((eng.stats["decode_steps"] - before[0],
                      len(h.generated) - before[1], eng.occupancy, h.done))
    # 2 chunks (the second is read at the end of its step, after that step's
    # decode turn: it emits the first token and opens the row for the step
    # after), then three decode steps: the first has nothing to read, the
    # last leaves its slot as it is dispatched, and one more turn reads its
    # token with nothing to dispatch
    assert turns == [(0, 0, 1, False), (0, 1, 1, False), (1, 0, 1, False),
                     (1, 1, 1, False), (1, 1, 0, False), (0, 1, 0, True)]
    s = eng.stats
    assert (s["decode_steps"], s["decode_overlapped"], s["steps"]) == (3, 2, 6)
    assert eng.collect() == 0 and s == eng.stats        # nothing left to read
    assert _clocks_partition(s)
    assert eng.metrics_snapshot()["decode_overlapped"] == pytest.approx(2 / 3)


@pytest.mark.parametrize("how", ["drain", "close"])
def test_the_driver_leaves_nothing_unread_and_loses_no_token(how, tiny_params, tiny_config):
    """``EngineDriver.drain()`` runs until the last token is out; ``close()``
    in mid-run reads the unread step back, so what was sampled is streamed -
    and a collect outside any step keeps the clocks a partition."""
    from gpt_2_distributed_tpu.serving.frontend.driver import EngineDriver
    from gpt_2_distributed_tpu.serving.frontend.router import ReplicaRouter

    eng = _engine("chunked", tiny_params, tiny_config)
    driver = EngineDriver(ReplicaRouter(lambda: eng, replicas=1))
    streamed, finished = [], []
    h = driver.submit(list(range(1, 8)), 6, rng=0,
                      on_token=lambda req, t: streamed.append(t),
                      on_finish=finished.append)
    if how == "drain":
        driver.drain()
        assert h.done and len(h.generated) == 6 and finished == [h]
    else:
        while eng.stats["decode_overlapped"] < 2:
            driver.step()
        sampled = 1 + eng.stats["decode_steps"]       # the prefill's, then one a step
        assert len(h.generated) == sampled - 1 and eng.has_work()
    driver.close()
    assert streamed == h.generated and eng.collect() == 0
    if how == "close":
        assert len(h.generated) == sampled
    s = eng.stats
    assert _clocks_partition(s)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_weight_bytes_and_the_engine_weights_event(
        dtype, tmp_path, tiny_params, tiny_config):
    """What the engine holds, as it says it: ``weight_bytes`` in the
    snapshot (registered) is the bytes of ``engine.params``, and one
    ``engine_weights`` event a model at construction gives the cast's two
    sides. A float32 tree held at bfloat16 is half of what was given but
    for the LayerNorm leaves; held at float32 nothing is cast or copied."""
    from scripts.obs_report import load_trace_dir

    draft_config = tiny_config.replace(n_layer=1)
    draft_params = gpt2.init_params(draft_config, seed=1)
    serve = ServeConfig(max_batch=3, block_size=8, num_blocks=32,
                        attn_impl="xla", **MODES["speculative"])
    get_tracer().configure(str(tmp_path))
    try:
        eng = ServingEngine(
            tiny_params, tiny_config, serve, temperature=0.0,
            compute_dtype=dtype, draft_params=draft_params,
            draft_config=draft_config)
    finally:
        get_tracer().configure(None, enabled=False)

    def nbytes(tree, keep=lambda name: True):
        return sum(a.nbytes for path, a in
                   jax.tree_util.tree_leaves_with_path(tree)
                   if keep(jax.tree_util.keystr(path)))

    snap = eng.metrics_snapshot()
    assert snap["weight_bytes"] == eng.weight_bytes == nbytes(eng.params)
    assert "weight_bytes" in METRIC_REGISTRY
    events = {r["attrs"]["model"]: r["attrs"]
              for r in load_trace_dir(str(tmp_path))
              if r.get("ph") == "event" and r["name"] == "engine_weights"}
    assert sorted(events) == ["draft", "target"]
    for model, given, held in (("target", tiny_params, eng.params),
                               ("draft", draft_params, eng.draft_params)):
        ev = events[model]
        norms = nbytes(given, lambda name: "ln" in name)
        assert ev["dtype"] == dtype and ev["ms"] >= 0
        assert ev["bytes_given"] == nbytes(given)
        assert ev["bytes_held"] == nbytes(held)
        if dtype == "bfloat16":
            assert ev["cast_leaves"] == 10      # wte, wpe, 4 weights, 4 biases
            assert ev["bytes_given"] - norms == 2 * (ev["bytes_held"] - norms)
        else:
            assert ev["cast_leaves"] == 0 and held is given


def test_fleet_snapshot_and_the_servers_compile_lines(tiny_params, tiny_config, capsys):
    """The router's snapshot (what ``/metrics`` and ``--tb_dir`` show)
    carries the step clocks over every engine's steps, each registered;
    and a whole-prompt server says ``set-up:`` once, then names a prefill
    program built for a new bucket of prompt length without a warning."""
    from gpt_2_distributed_tpu.serving.frontend.driver import EngineDriver
    from gpt_2_distributed_tpu.serving.frontend.router import ReplicaRouter

    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=32, attn_impl="xla")
    router = ReplicaRouter(
        lambda: ServingEngine(tiny_params, tiny_config, serve, temperature=0.0),
        replicas=2, policy="round_robin")
    driver = EngineDriver(router)
    try:
        for i, p in enumerate((5, 6)):
            driver.submit(list(range(1, p + 1)), 3, rng=i)
        driver.drain()
        driver.submit(list(range(1, 21)), 3, rng=9)   # a bucket not yet built
        driver.drain()
    finally:
        driver.close()
    snap = router.metrics_snapshot()
    assert all(key in METRIC_REGISTRY for key in snap)
    assert snap == pytest.approx(
        {**snap, **step_clocks(e.stats for e in router.engines)})
    assert snap["engine_host_ms"] > 0 and 1 <= snap["decode_rows"] <= 2
    width = serve.max_blocks_per_seq(tiny_config.n_positions)
    assert snap["decode_blocks_table"] == pytest.approx(snap["decode_rows"] * width)
    assert snap["decode_rows"] <= snap["decode_blocks_live"] <= snap["decode_blocks_table"]
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("[serve] ")]
    assert lines[0].startswith("[serve] set-up: ") and " programs, " in lines[0]
    late = [ln for ln in lines[1:] if "jit(prefill)" in ln]
    assert late and all(" for a new shape (" in ln and "warning" not in ln
                        for ln in late)
    assert not any("warning" in ln for ln in lines), lines


def test_decode_counters_are_the_dispatch_arguments(tiny_params, tiny_config):
    """``decode_rows`` / ``decode_attended`` are what the decode program is
    handed, read where the harness's wrapper reads them: off ``_decode_fn``'s
    positional arguments."""
    eng = _engine("chunked", tiny_params, tiny_config)
    inner, seen = eng._decode_fn, []

    def spy(params, k_pool, v_pool, block_table, tokens, pos, active, keys):
        active = np.asarray(active)
        seen.append((int(active.sum()), int((np.asarray(pos)[active] + 1).sum())))
        return inner(params, k_pool, v_pool, block_table, tokens, pos, active, keys)

    eng._decode_fn = spy
    for i, p in enumerate((9, 4, 13)):
        eng.submit(list(range(1, p + 1)), 5, rng=i)
    per_step, last = [], (0, 0)
    while eng.has_work():
        eng.step()
        now = (eng.stats["decode_rows"], eng.stats["decode_attended"])
        if now != last:
            per_step.append((now[0] - last[0], now[1] - last[1]))
            last = now
    assert per_step == seen and len(seen) == eng.stats["decode_steps"] > 3


@pytest.mark.parametrize("mode", ["chunked", "whole-watermark"])
def test_decode_block_counters_replay_the_positions(mode, tiny_params, tiny_config):
    """``decode_blocks_live`` / ``decode_blocks_table``: of the block-table
    slots of the rows a decode step advances, those that hold keys
    (``ceil((pos + 1) / block_size)`` a row) and all of them - what a
    replay of the positions the decode program is handed gives, step for
    step; the rest is the tail the paged kernel neither fetches nor attends."""
    eng = _engine(mode, tiny_params, tiny_config)
    bs = eng.serve.block_size
    inner, seen = eng._decode_fn, []

    def spy(params, k_pool, v_pool, block_table, tokens, pos, active, keys):
        active = np.asarray(active)
        at = np.asarray(pos)[active]
        seen.append((int(-(-(at + 1) // bs).sum()),
                     int(active.sum()) * np.asarray(block_table).shape[1]))
        return inner(params, k_pool, v_pool, block_table, tokens, pos, active, keys)

    eng._decode_fn = spy
    for i, p in enumerate((9, 4, 23, 16)):     # 16: a row that starts a block
        eng.submit(list(range(1, p + 1)), 9, rng=i)
    per_step, last = [], (0, 0)
    while eng.has_work():
        eng.step()
        now = (eng.stats["decode_blocks_live"], eng.stats["decode_blocks_table"])
        if now != last:
            per_step.append((now[0] - last[0], now[1] - last[1]))
            last = now
    assert per_step == seen and len(seen) == eng.stats["decode_steps"] > 3
    width = eng.block_table.shape[1]
    assert all(table // width <= live <= table for live, table in seen)
    assert 0 < last[0] < last[1]               # this traffic leaves a tail


@pytest.mark.parametrize("mode", ["chunked", "whole-watermark"])
def test_engine_programs_compile_under_their_names(mode, tiny_params, tiny_config):
    mark = time.monotonic()
    eng = _engine(mode, tiny_params, tiny_config)
    eng.submit([1, 2, 3, 4, 5], 3, rng=0)
    eng.run_until_idle()
    names = {p[0] for p in compile_watch.get_watch().programs(after=mark)}
    prefill = "jit(chunk_prefill)" if mode == "chunked" else "jit(prefill)"
    assert {"jit(decode_step)", prefill} <= names
    assert "jit(_unknown)" not in names and "jit(<unnamed function>)" not in names


@pytest.mark.parametrize("mode", ["chunked", "speculative", "sharded"])
def test_span_durations_are_the_counters(mode, tmp_path, tiny_params, tiny_config):
    """A ``--trace_dir`` waterfall agrees with the engine's accounting: the
    ``decode`` spans of a run add up to ``decode_ms`` (``draft`` + ``verify``
    in a speculative round, and ``verify`` to ``verify_ms``), the
    ``dispatch`` spans inside them to ``decode_dispatch_ms``, and
    ``obs_report`` lists dispatch and read-back under the phase. The same of
    a prefill chunk (``chunk_dispatch`` to ``prefill_dispatch_ms``, and
    ``chunk_readback`` beside it, both inside ``prefill``) and of the
    request intake (``intake`` to ``intake_ms``, a refused request's too,
    with ``request_key`` inside it)."""
    from scripts.obs_report import load_trace_dir, step_breakdown

    tracer = get_tracer()
    tracer.configure(str(tmp_path))
    try:
        if mode == "sharded":
            serve = ServeConfig(max_batch=2, block_size=8, num_blocks=32,
                                attn_impl="xla", mesh="data:2")
            eng = ServingEngine(tiny_params, tiny_config, serve, temperature=0.0)
        else:
            eng = _engine(mode, tiny_params, tiny_config)
        for i, p in enumerate((7, 12)):
            eng.submit(list(range(1, p + 1)), 6, rng=i)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit([1, 2, 3], 0)
        eng.run_until_idle()
    finally:
        tracer.configure(None, enabled=False)
    records = load_trace_dir(str(tmp_path))
    spans = [r for r in records if r.get("ph") == "span"]
    by_sid = {r["sid"]: r for r in spans}

    def total_ms(name):
        return sum(r["dur"] for r in spans if r["name"] == name) * 1e3

    s = eng.stats
    # a span encloses its counter's clock: longer, by microseconds a step
    def agrees(span_ms, counter_ms):
        return counter_ms <= span_ms <= counter_ms * 1.02 + 0.05 * s["steps"]

    outer = "verify" if mode == "speculative" else "decode"
    readback = "token_allgather" if mode == "sharded" else "readback"
    if mode == "speculative":
        assert agrees(total_ms("verify"), s["verify_ms"])
        assert agrees(total_ms("draft"), s["draft_ms"])
        assert agrees(total_ms("draft") + total_ms("verify"), s["decode_ms"])
    else:
        assert agrees(total_ms("decode"), s["decode_ms"])
    assert agrees(total_ms("dispatch"), s["decode_dispatch_ms"])
    assert agrees(total_ms("engine_step"), s["step_ms"])
    assert agrees(total_ms("emit"), s["emit_ms"])
    halves = [r for r in spans if r["name"] in ("dispatch", readback)]
    assert len(halves) == 2 * s["decode_steps"]
    assert all(by_sid[r["parent"]]["name"] == outer for r in halves)
    assert all(by_sid[r["parent"]]["name"] == "engine_step"
               for r in spans if r["name"] in (outer, "admit", "prefill", "emit"))

    # intake: every call, the refused one too; the key inside the accepted
    intakes = [r for r in spans if r["name"] == "intake"]
    assert len(intakes) == 3 and s["submits"] == 2
    assert agrees(total_ms("intake"), s["intake_ms"])
    assert sorted(r["attrs"].get("rid", -1) for r in intakes) == [-1, 0, 1]
    assert sorted(r["attrs"]["prompt_len"] for r in intakes) == [3, 7, 12]
    keys = [r for r in spans if r["name"] == "request_key"]
    assert len(keys) == 2 and all(by_sid[r["parent"]]["name"] == "intake" for r in keys)
    assert all(r["parent"] is None for r in intakes)

    # a prefill chunk's two halves, inside the two `prefill` spans of a step
    chunks = [r for r in spans if r["name"] in ("chunk_dispatch", "chunk_readback")]
    assert len(chunks) == 2 * s["prefill_dispatches"] or mode == "sharded"
    assert all(by_sid[r["parent"]]["name"] == "prefill" for r in chunks)
    assert agrees(total_ms("chunk_dispatch"), s["prefill_dispatch_ms"])

    phases = step_breakdown(records, step_name="engine_step")["phases"]
    assert set(phases[outer]["parts"]) == {"dispatch", readback}
    assert phases[outer]["parts"][readback]["n"] == s["decode_steps"]
    assert "parts" not in phases["emit"]
    if mode == "sharded":      # whole-prompt prefill: no chunk
        assert not chunks and s["prefill_dispatch_ms"] == 0
    else:
        assert set(phases["prefill"]["parts"]) == {"chunk_dispatch", "chunk_readback"}
        assert phases["prefill"]["parts"]["chunk_readback"]["n"] == s["prefill_dispatches"]


def _inside(inner, outer):
    return any(line == o_line and o_lo <= lo and hi <= o_hi
               for o_line, o_lo, o_hi in outer
               for line, lo, hi in [inner])


def test_spans_reach_a_capture_nobody_told_the_tracer_about(
        tmp_path, tiny_params, tiny_config):
    """``jax.profiler.start_trace`` with no trace directory: the engine's
    spans are on the host plane, nested under ``gpt2/engine_step``, on the
    thread that steps the engine, and ``gpt2/intake`` inside the caller's
    own span around ``submit``. With no capture either: no file, no span
    object."""
    tracer = get_tracer()
    assert not tracer.enabled
    eng = _engine("chunked", tiny_params, tiny_config)
    eng.submit([1, 2, 3, 4, 5, 6], 6, rng=0)
    for _ in range(3):      # compile outside the capture: two chunks, a decode step
        eng.step()

    capture = tmp_path / "capture"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(capture), profiler_options=options)
    try:
        assert tracer.span("probe") is not _NULL_SPAN
        with jax.profiler.TraceAnnotation("bench/submit"):
            eng.submit([7, 8, 9, 10, 11], 2, rng=1)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/step"):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    assert not tracer.enabled and tracer.trace_path is None

    found = host_annotations(str(capture))
    steps = found["gpt2/engine_step"]
    assert len(steps) == 3 and len(found["bench/step"]) == 3
    assert all(_inside(step, found["bench/step"]) for step in steps)
    for child in ("gpt2/admit", "gpt2/decode", "gpt2/dispatch", "gpt2/readback",
                  "gpt2/emit"):
        assert len(found[child]) == 3, child
        assert all(_inside(ev, steps) for ev in found[child]), child
    # the 5-token prompt is two chunks of 4; a step with a chunk opens
    # `prefill` twice (its dispatch, then its first token's read-back)
    for half in ("gpt2/chunk_dispatch", "gpt2/chunk_readback"):
        assert len(found[half]) == 2, half
    assert len(found["gpt2/prefill"]) == 3 + len(found["gpt2/chunk_readback"])
    assert all(_inside(ev, steps) for ev in found["gpt2/prefill"])
    # the decode step's two halves lie inside it, a chunk's inside `prefill`
    for half in ("gpt2/dispatch", "gpt2/readback"):
        assert all(_inside(ev, found["gpt2/decode"]) for ev in found[half]), half
    for half in ("gpt2/chunk_dispatch", "gpt2/chunk_readback"):
        assert all(_inside(ev, found["gpt2/prefill"]) for ev in found[half]), half
    # the intake inside the caller's span, the key's wait inside the intake
    (intake,) = found["gpt2/intake"]
    assert _inside(intake, found["bench/submit"])
    (key,) = found["gpt2/request_key"]
    assert _inside(key, [intake])

    # nothing attached: the shared no-op, and no file anywhere
    assert tracer.span("engine_step", n=1) is _NULL_SPAN
    eng.run_until_idle()
    assert [p.name for p in tmp_path.iterdir()] == ["capture"]
