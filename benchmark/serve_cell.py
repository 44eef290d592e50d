"""A serving cell: one ``ServingEngine`` behind one ``EngineDriver``, fed
by the mix's generator for ``--seconds``.

``backlog`` (closed loop, the one kind of serving mix so far): the generator
keeps ``min_queue`` requests waiting, so a freed slot is refilled at the
next step; the window's metric is the tokens completed per second. At the
close the unfinished requests are abandoned with the engine: a closed loop
has no request that was due.

``correct``: once the window has closed, ``memory_peak_bytes`` has been read
and the engine is dropped, the reference runs once over each sampled
request's prompt with its served tokens (the longest finished request
always among them), and the widest gap by which a served token's logit lies
below the reference's best is held to the cell's limit.

The model is found by name: the configuration's ``family`` gives
``cell["reference"]`` (weights from the seed, the serving reference, the
control's matmul) and ``cell["program"]`` (the package's model and serving
configurations). The engine is read only through what it publishes: its
``stats`` counters, ``occupancy`` and ``queue_depth``, and - in a traced run
- its own ``gpt2/...`` spans in the profiler's trace.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from benchmark import check, harness, traffic

PROGRESS_SECONDS = 5.0   # the log's tokens-per-slice line, for a run that reads far off


class Served:
    """One request as the harness saw it."""

    __slots__ = ("request", "token_times", "handle", "refused")

    def __init__(self, request):
        self.request = request
        self.token_times: list[float] = []
        self.handle = None
        self.refused = False

    def on_token(self, handle, token):
        self.token_times.append(time.monotonic())

    @property
    def done(self):
        return self.handle is not None and self.handle.done


def build_engine(cell: dict, seed: int):
    """(engine, driver): weights from the seed, placed as
    ``serve.load_model --init_random`` places them (fp32, default device)."""
    from gpt_2_distributed_tpu.serving.engine import ServingEngine
    from gpt_2_distributed_tpu.serving.frontend.driver import EngineDriver
    from gpt_2_distributed_tpu.serving.frontend.router import ReplicaRouter

    cfg, program = cell["config_file"], cell["program"]
    params = cell["reference"].make_weights(cell["sizes"], seed)
    engine = ServingEngine(
        params, program.model_config(cfg), program.serve_config(cfg, cell["mix"]),
        temperature=float(cfg["serve"]["temperature"]))
    router = ReplicaRouter(lambda: engine, replicas=1)
    return engine, EngineDriver(router)


def warm_up(cell: dict, engine, driver) -> None:
    """Run every program the window will: the chunked prefill, the decode
    step and the host-side helpers, on requests of the mix's own shapes."""
    rng = np.random.default_rng(0)
    vocab_size = cell["sizes"]["vocab_size"]
    pool = traffic.length_pool(cell["mix"])
    longest = max(p for p, _ in pool)
    for p in (longest, 1 + longest // 2, min(p for p, _ in pool)):
        driver.submit(rng.integers(0, vocab_size, p).tolist(), 3, rng=0)
    driver.drain()


def run_window(cell: dict, seed: int, seconds: float, engine, driver,
               spans: harness.Spans, profiler: harness.ProfilerWindow):
    """Drive the mix through the driver for ``seconds``. Returns the
    requests seen, the per-step occupancy samples, the window's ends and -
    in a traced run - the engine's counters as the profiler stopped."""
    mix = cell["mix"]
    source = traffic.requests(mix, cell["sizes"]["vocab_size"], seed)
    min_queue = math.ceil(engine.serve.max_batch * float(mix.get("min_queue_slots", 0)))
    seen: list[Served] = []
    occupancy: list[int] = []
    traced_stats = None
    upcoming = next(source)

    def submit(request):
        served = Served(request)
        seen.append(served)
        try:
            served.handle = driver.submit(
                request.prompt, request.max_new_tokens, rng=request.index,
                on_token=served.on_token)
        except Exception as exc:   # a refusal is a failed request
            served.refused = True
            print(f"request {request.index} refused: {exc!r}", flush=True)

    profiler.start()
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        with spans("submit"):
            while engine.queue_depth < min_queue:
                submit(upcoming)
                upcoming = next(source)
        with spans("step"):
            driver.step()
        occupancy.append(engine.occupancy)
        if profiler.maybe_stop():
            traced_stats = dict(engine.stats)
    t1 = time.monotonic()
    if profiler.maybe_stop(force=True):
        traced_stats = dict(engine.stats)
    return seen, occupancy, t0, t1, traced_stats


def sample_for_check(cell: dict, finished: list[Served], seed: int) -> list[Served]:
    """The longest finished request and further ones drawn from the seed,
    until the sample holds the mix's ``check_tokens`` served tokens."""
    if not finished:
        return []
    want = int(cell["mix"]["check_tokens"])
    longest = max(finished, key=lambda s: len(s.handle.generated))
    rest = [s for s in finished if s is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    sample, tokens = [longest], len(longest.handle.generated)
    for i in order:
        if tokens >= want:
            break
        sample.append(rest[i])
        tokens += len(rest[i].handle.generated)
    return sample


def logit_gaps(cell: dict, seed: int, sample: list[Served],
               control: bool = False, logits=None) -> np.ndarray:
    """Per served token of the sample, the gap below the reference's best
    logit, or - for the control - the gap of the token that the family's
    ``control_matmul`` precision puts first at each of the same positions.
    Every request is padded to the mix's ``max_total`` (one compiled
    reference, as wide as the traffic and no wider); ``logits`` is the
    family's ``serving_reference`` for the seed, made here unless given."""
    family = cell["reference"]
    if logits is None:
        logits = family.serving_reference(cell["sizes"], seed)
    width = int(cell["mix"]["max_total"])
    gaps = []
    for served in sample:
        prompt, tokens = served.request.prompt, list(served.handle.generated)
        ids = np.zeros((1, width), np.int32)
        seq = (prompt + tokens)[:width]
        ids[0, :len(seq)] = seq
        exact = np.asarray(logits(ids))[0]
        if control:
            rough = np.asarray(logits(ids, family.control_matmul))[0]
            pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
            tokens = rough[pos].argmax(axis=-1).tolist()
        gaps.append(check.token_logit_gaps(exact, len(prompt), tokens))
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def window_report(seen: list[Served], stats: dict, steps: list, t0: float,
                  t1: float) -> str:
    """An earlier line of the log, for whoever looks for the cause of a run
    that reads far off: the tokens of each slice of the window, the
    engine's own counters over it, and the longest ``(start, end)`` of
    ``steps`` (a stall of seconds in one step is the machine's)."""
    n = max(1, math.ceil((t1 - t0) / PROGRESS_SECONDS))
    slices = [0] * n
    for s in seen:
        for t in s.token_times:
            if t <= t1:
                slices[min(n - 1, int((t - t0) / PROGRESS_SECONDS))] += 1
    per = {k: stats[f"{k}_ms"] / max(stats[n_], 1) for k, n_ in (
        ("decode", "decode_steps"), ("prefill", "prefill_dispatches"))}
    host_s = (t1 - t0) - (stats["decode_ms"] + stats["prefill_ms"]) / 1e3
    longest = max(steps, key=lambda se: se[1] - se[0], default=(t0, t0))
    return (f"window: {t1 - t0:.3f} s; tokens per {PROGRESS_SECONDS:g} s {slices}; "
            f"{stats['decode_steps']} decode steps of {per['decode']:.2f} ms, "
            f"{stats['prefill_dispatches']} prefill dispatches of "
            f"{per['prefill']:.2f} ms, {host_s:.3f} s outside both; longest step "
            f"{(longest[1] - longest[0]) * 1e3:.0f} ms, {longest[0] - t0:.1f} s in")


def run(cell: dict, seed: int, seconds: float, trace: bool, device: dict,
        started: float, compiles: harness.CompileCounter) -> dict:
    spans = harness.Spans()
    profiler = harness.ProfilerWindow(cell["name"], spans, trace)
    engine, driver = build_engine(cell, seed)
    try:
        warm_up(cell, engine, driver)
        stats_before = dict(engine.stats)
        compiled_before = compiles.count
        setup_s = time.monotonic() - started
        seen, occupancy, t0, t1, traced_stats = run_window(
            cell, seed, seconds, engine, driver, spans, profiler)

        def since_warm_up(now):
            return {k: now[k] - stats_before[k] for k in stats_before}

        stats = since_warm_up(engine.stats)
        if traced_stats is not None:
            traced_stats = since_warm_up(traced_stats)
        compiled_in_window = compiles.count - compiled_before
        memory_peak = harness.memory_peak_bytes(cell["chips"])
    finally:
        driver.close()
    if compiled_in_window:
        raise harness.RunFailed(
            f"{compiled_in_window} trace/lower/compile events inside the window")
    steps = [(a, b) for name, a, b in spans.records if name == "step" and a >= t0]
    print(window_report(seen, stats, steps, t0, t1), flush=True)

    # A closed loop has no request that was due: what the window finished
    # was attempted, and only a refusal has failed.
    finished = [s for s in seen if s.done and s.handle.finish_reason == "length"]
    attempted = len(finished)
    failed = sum(1 for s in seen if s.refused)
    sample = sample_for_check(cell, finished, seed)
    del engine, driver   # the pools and the weights go with them
    gc.collect()

    tokens_in_window = sum(1 for s in seen for t in s.token_times if t <= t1)
    values = {"setup_s": setup_s, "serve_tok_s": tokens_in_window / (t1 - t0)}

    # --- correct: served tokens against the reference --------------------
    numbers = {}
    if sample:
        gaps = logit_gaps(cell, seed, sample)
        numbers["token_logit_gap"] = float(gaps.max())
        print(f"checked {len(gaps)} served tokens of {len(sample)} requests",
              flush=True)
    correct, compared = check.judge(numbers, cell["limits"])
    correct = correct and failed == 0 and bool(sample)

    device = dict(device, memory_peak_bytes=memory_peak)
    metrics, breakdown = harness.metrics_of(cell, values, device, profiler, spans, {
        "window": (t0, t1), "stats": stats, "traced_stats": traced_stats,
        "occupancy": occupancy,
    })
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, "compared": compared,
            "breakdown": breakdown}
