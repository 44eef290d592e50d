"""Front-door subsystem: router placement + SLO admission, autoscaler
hysteresis, the shared engine-driver, and the HTTP/SSE server end-to-end.

The exactness bar carries over from test_serving unchanged: routing picks
WHICH replica computes a stream, never WHAT — so SSE token streams must be
BIT-identical to ``generate_cached(batch=1)``, greedy and sampled, no
matter how many replicas the fleet runs. The affinity claim is also
absolute, not statistical: on a grouped shared-prefix trace, prefix-
affinity routing must land a strictly higher fleet cache-hit rate than the
round_robin control on the SAME trace.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.models import gpt2
from gpt_2_distributed_tpu.models.decode import generate_cached
from gpt_2_distributed_tpu.resilience import PreemptionHandler
from gpt_2_distributed_tpu.config import ServeConfig
from gpt_2_distributed_tpu.serving import ServingEngine
from gpt_2_distributed_tpu.serving.frontend import (
    Autoscaler,
    DrainingError,
    EngineDriver,
    ReplicaRouter,
    ShedError,
)
from gpt_2_distributed_tpu.serving.frontend.server import FrontendServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_params(tiny_config):
    return gpt2.init_params(tiny_config, seed=0)


@pytest.fixture(autouse=True)
def _tier1_runtime_budget(request):
    """Same default-tier guard as test_serving: non-slow tests must stay
    far inside the suite timeout."""
    t0 = time.perf_counter()
    yield
    if request.node.get_closest_marker("slow") is None:
        elapsed = time.perf_counter() - t0
        assert elapsed < 90, (
            f"{request.node.name} took {elapsed:.1f}s — default-tier tests "
            "must stay under 90s; size the config down or mark it slow"
        )


def _serve(**kw):
    base = dict(max_batch=4, block_size=8, num_blocks=32, attn_impl="xla")
    base.update(kw)
    return ServeConfig(**base)


def _oneshot(params, config, prompt, key, new, **kw):
    out = generate_cached(
        params, config, jnp.asarray([prompt], jnp.int32), key,
        max_new_tokens=new, **kw,
    )
    return np.asarray(out)[0, len(prompt):].tolist()


def _make_router(params, config, *, replicas=2, serve=None, **kw):
    serve = serve or _serve(prefix_cache=True)
    return ReplicaRouter(
        lambda: ServingEngine(params, config, serve, temperature=0.0),
        replicas=replicas, **kw,
    )


# ------------------------------------------------------------- HTTP helpers


def _http(port, method, path, payload=None, timeout=120):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    body = json.dumps(payload) if payload is not None else None
    c.request(method, path, body,
              {"Content-Type": "application/json"} if body else {})
    r = c.getresponse()
    raw = r.read()
    headers = dict(r.getheaders())
    c.close()
    return r.status, (json.loads(raw) if raw else None), headers


def _sse(port, payload, timeout=120, on_first=None):
    """POST a streaming completion; returns (status, chunk dicts, saw_done).

    ``on_first`` (if given) fires as soon as the first data: chunk arrives
    — i.e. the request is admitted and generating — while the stream is
    still open.
    """
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("POST", "/v1/completions", json.dumps({**payload, "stream": True}),
              {"Content-Type": "application/json"})
    r = c.getresponse()
    status = r.status
    chunks, saw_done = [], False
    for raw_line in r:
        line = raw_line.decode().rstrip("\r\n")
        if line == "data: [DONE]":
            saw_done = True
        elif line.startswith("data: "):
            chunks.append(json.loads(line[len("data: "):]))
            if on_first is not None:
                on_first()
                on_first = None
    c.close()
    return status, chunks, saw_done


class _Server:
    """FrontendServer over a fresh fleet, run()ning on a daemon thread."""

    def __init__(self, params, config, *, replicas=2, serve=None,
                 temperature=0.0, top_k=None, default_new=8,
                 preemption=None, **router_kw):
        serve = serve or _serve(prefix_cache=True)
        self.router = ReplicaRouter(
            lambda: ServingEngine(params, config, serve,
                                  temperature=temperature, top_k=top_k),
            replicas=replicas, **router_kw,
        )
        self.driver = EngineDriver(self.router, preemption=preemption)
        self.srv = FrontendServer(self.driver, port=0, model_name="tiny",
                                  default_new=default_new)
        self.thread = threading.Thread(target=self.srv.run, daemon=True)

    def __enter__(self):
        self.thread.start()
        assert self.srv.ready.wait(60), "server never bound"
        return self

    @property
    def port(self):
        return self.srv.port

    def __exit__(self, *exc):
        if self.thread.is_alive():
            self.srv.shutdown()
            self.thread.join(60)
        assert not self.thread.is_alive(), "server thread leaked"


# ------------------------------------------------------- SSE stream parity


def test_sse_stream_greedy_parity_two_replicas(tiny_params, tiny_config):
    # The 2-replica acceptance bar: SSE streams off the routed fleet are
    # bit-identical to generate_cached(batch=1) — and the non-stream
    # response body for the same request carries the same tokens.
    prompts = [[1, 2, 3], [7] * 10, [5, 4, 3, 2, 1], [9, 8, 7, 6]]
    news = [6, 4, 5, 7]
    with _Server(tiny_params, tiny_config, replicas=2) as s:
        for i, (p, n) in enumerate(zip(prompts, news)):
            ref = _oneshot(tiny_params, tiny_config, p,
                           jax.random.PRNGKey(i), n, temperature=0.0)
            status, chunks, done = _sse(
                s.port, {"prompt_ids": p, "max_tokens": n, "seed": i})
            assert status == 200 and done
            toks = [c["choices"][0]["token"] for c in chunks
                    if c["choices"][0]["token"] is not None]
            assert toks == ref, f"request {i}"
            final = chunks[-1]["choices"][0]
            assert final["finish_reason"] == "length"
            assert chunks[-1]["usage"]["completion_tokens"] == n
            status2, body, _ = _http(s.port, "POST", "/v1/completions",
                                     {"prompt_ids": p, "max_tokens": n,
                                      "seed": i})
            assert status2 == 200
            assert body["choices"][0]["token_ids"] == ref
        # Both replicas actually served traffic (router spread the load).
        status, m, _ = _http(s.port, "GET", "/metrics")
        assert status == 200 and m["serve_replicas"] == 2
        assert m["requests_routed"] == 2 * len(prompts)


def test_sse_stream_sampled_parity(tiny_params, tiny_config):
    # temperature>0 + top_k over the fleet: per-request PRNG chains must
    # replay generate_cached's exact split order regardless of replica.
    prompts = [[1, 2, 3, 4], [6] * 9, [2, 4, 6, 8, 10]]
    news = [5, 6, 4]
    with _Server(tiny_params, tiny_config, replicas=2,
                 temperature=0.9, top_k=40) as s:
        for i, (p, n) in enumerate(zip(prompts, news)):
            ref = _oneshot(tiny_params, tiny_config, p,
                           jax.random.PRNGKey(i + 10), n,
                           temperature=0.9, top_k=40)
            status, chunks, done = _sse(
                s.port, {"prompt_ids": p, "max_tokens": n, "seed": i + 10})
            assert status == 200 and done
            toks = [c["choices"][0]["token"] for c in chunks
                    if c["choices"][0]["token"] is not None]
            assert toks == ref, f"request {i}"


def test_http_request_validation(tiny_params, tiny_config):
    with _Server(tiny_params, tiny_config, replicas=1) as s:
        for payload, frag in (
            ({"prompt_ids": [1], "prompt": "x"}, "exactly one"),
            ({}, "exactly one"),
            ({"prompt_ids": []}, "non-empty"),
            ({"prompt_ids": [1, 2], "max_tokens": "lots"}, "integers"),
            ({"prompt_ids": [1] * 200, "max_tokens": 4}, None),  # too long
        ):
            status, body, _ = _http(s.port, "POST", "/v1/completions",
                                    payload)
            assert status == 400, payload
            assert body["error"]["type"] == "invalid_request_error"
            if frag:
                assert frag in body["error"]["message"], payload
        status, body, _ = _http(s.port, "GET", "/nope")
        assert status == 404
        status, body, _ = _http(s.port, "DELETE", "/v1/completions")
        assert status == 405
        status, body, _ = _http(s.port, "GET", "/healthz")
        assert status == 200 and body["status"] == "ok"


# -------------------------------------------------- affinity vs round_robin


def _grouped_trace(block_size=8, groups=3, per_group=4, tail=3, seed=0):
    """Interleaved shared-prefix trace: `groups` distinct 2-block prefixes,
    visited round-robin (A B C A B C ...) so a 2-replica round_robin
    spray keeps re-missing prefixes the other replica already cached."""
    rng = np.random.default_rng(seed)
    pfx = [rng.integers(0, 257, 2 * block_size).tolist()
           for _ in range(groups)]
    prompts = []
    for i in range(groups * per_group):
        g = i % groups
        prompts.append(pfx[g] + rng.integers(0, 257, tail).tolist())
    return prompts


def _routed_hit_rate(params, config, policy, prompts):
    router = _make_router(params, config, replicas=2, policy=policy)
    driver = EngineDriver(router)
    for i, p in enumerate(prompts):
        driver.submit(p, 3, rng=i)
        driver.drain()   # sequential: blocks registered before next route
    assert all(not e.has_work() for e in router.engines)
    return router


def test_affinity_beats_round_robin_on_shared_prefixes(
        tiny_params, tiny_config):
    prompts = _grouped_trace()
    rr = _routed_hit_rate(tiny_params, tiny_config, "round_robin", prompts)

    # Affinity run, keeping handles to check placement too.
    router = _make_router(tiny_params, tiny_config, replicas=2,
                          policy="affinity")
    driver = EngineDriver(router)
    handles = []
    for i, p in enumerate(prompts):
        handles.append(driver.submit(p, 3, rng=i))
        driver.drain()

    # STRICTLY higher — the whole point of the router. Affinity pays one
    # cold miss per prefix group; round_robin re-misses whenever the
    # 3-group cycle lands a group on the replica that didn't cache it.
    assert router.aggregate_hit_rate() > rr.aggregate_hit_rate(), (
        router.aggregate_hit_rate(), rr.aggregate_hit_rate())
    assert router.affinity_hits > 0
    # Placement converges per group: past the cold miss, every request of
    # a group lands on the replica that holds its prefix blocks.
    groups = 3
    for g in range(groups):
        placed = {handles[i].replica for i in range(len(prompts))
                  if i % groups == g and i >= groups}
        assert len(placed) == 1, f"group {g} spread across replicas"


def test_sticky_map_colocates_when_cache_off(tiny_params, tiny_config):
    # prefix_cache off: no blocks to probe, but the sticky map must still
    # co-locate shared-prefix traffic (covers the cache-off deployment and
    # the first-carrier-still-prefilling race).
    router = _make_router(tiny_params, tiny_config, replicas=2,
                          policy="affinity", serve=_serve())
    driver = EngineDriver(router)
    shared = [11] * 8   # exactly one block: the sticky key
    handles = []
    for i in range(4):
        handles.append(driver.submit(shared + [50 + i], 3, rng=i))
        driver.drain()
    assert len({h.replica for h in handles}) == 1
    assert router.affinity_hits == 3       # all but the first (sticky routes)


# ----------------------------------------------------------- SLO admission


def test_queue_slo_sheds_before_enqueue(tiny_params, tiny_config):
    router = _make_router(tiny_params, tiny_config, replicas=1,
                          queue_slo_ms=1.0)
    driver = EngineDriver(router)
    driver.submit([1, 2, 3], 4, rng=0)     # queue empty: admitted
    with pytest.raises(ShedError, match="queue wait"):
        driver.submit([4, 5, 6], 4, rng=1)  # predicted wait 25ms > 1ms
    assert router.shed_count == 1
    assert router.metrics_snapshot()["serve_shed"] == 1.0
    driver.drain()                          # the admitted request completes
    assert router.routed == 1
    # Queue drained: admission opens again.
    h = driver.submit([7, 8, 9], 3, rng=2)
    driver.drain()
    assert h.done


def test_http_shed_maps_to_503(tiny_params, tiny_config):
    with _Server(tiny_params, tiny_config, replicas=1,
                 serve=_serve(max_batch=1, prefix_cache=True),
                 queue_slo_ms=1.0) as s:
        # A long stream occupies the single slot...
        got_first = threading.Event()
        result = {}

        def run_a():
            result["a"] = _sse(s.port, {"prompt_ids": [1, 2, 3],
                                        "max_tokens": 24, "seed": 0},
                               on_first=got_first.set)

        # Start A and wait for its first token.  Polling occupancy is not
        # enough: during whole-prompt admission the engine holds A in a
        # slot AND at the queue head, so a queue-depth poll could fire on
        # A's own transient and let C's submit overtake B's.  A token on
        # the wire means A is admitted and popped — the queue is stably
        # empty until B joins it.
        ta = threading.Thread(target=run_a)
        ta.start()
        assert got_first.wait(60), "A never started streaming"
        # B joins the (empty) queue behind A: admitted, parked in queue
        # until A's slot frees. C would wait behind B: shed.
        def run_b():
            result["b"] = _http(s.port, "POST", "/v1/completions",
                                {"prompt_ids": [4, 5, 6], "max_tokens": 4,
                                 "seed": 1})

        tb = threading.Thread(target=run_b)
        tb.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            _, m, _ = _http(s.port, "GET", "/metrics")
            if m["serve_queue_depth"] >= 1:
                break
            time.sleep(0.01)
        sc, body, headers = _http(s.port, "POST", "/v1/completions",
                                  {"prompt_ids": [7, 8, 9], "max_tokens": 4,
                                   "seed": 2})
        ta.join(120)
        tb.join(120)
        assert result["b"][0] == 200
        assert sc == 503
        assert body["error"]["type"] == "overloaded"
        assert headers.get("Retry-After") == "1"
        status_a, chunks_a, done_a = result["a"]
        assert status_a == 200 and done_a
        assert len([c for c in chunks_a
                    if c["choices"][0]["token"] is not None]) == 24


def test_ttft_slo_violations_counted(tiny_params, tiny_config):
    router = _make_router(tiny_params, tiny_config, replicas=1,
                          ttft_slo_ms=0.001)   # everything violates
    driver = EngineDriver(router)
    for i in range(3):
        driver.submit([1, 2, 3 + i], 3, rng=i)
    driver.drain()
    assert router.slo_violations == 3
    assert router.metrics_snapshot()["slo_violations"] == 3.0


# -------------------------------------------------------- graceful shutdown


def test_drain_refuses_submits_and_completes_inflight(
        tiny_params, tiny_config):
    # The in-process SIGTERM path: the resilience flag flips the driver
    # into draining at a step boundary; accepted work runs to completion.
    handler = PreemptionHandler(signals=())
    router = _make_router(tiny_params, tiny_config, replicas=2)
    driver = EngineDriver(router, preemption=handler)
    handles = [driver.submit([1, 2, 3, i], 8, rng=i) for i in range(4)]
    driver.step()                      # work in flight
    handler.trigger("test SIGTERM")    # what the real signal does
    driver.step()                      # boundary poll flips to draining
    assert driver.draining
    with pytest.raises(DrainingError):
        driver.submit([9, 9], 2, rng=0)
    fut = driver.submit_threadsafe([9, 9], 2, rng=0)
    driver.drain()
    with pytest.raises(DrainingError):
        fut.result(timeout=5)
    assert all(h.done and len(h.generated) == 8 for h in handles)


def test_server_sigterm_drains_streams_then_exits(tiny_params, tiny_config):
    # e2e over HTTP: trigger the handler mid-stream; the stream must run
    # to its final token + [DONE], new requests must get 503, and run()
    # must return (exit 0 in the real process).
    handler = PreemptionHandler(signals=())
    ref = _oneshot(tiny_params, tiny_config, [1, 2, 3],
                   jax.random.PRNGKey(0), 24, temperature=0.0)
    with _Server(tiny_params, tiny_config, replicas=2,
                 preemption=handler) as s:
        result = {}

        def run_a():
            result["a"] = _sse(s.port, {"prompt_ids": [1, 2, 3],
                                        "max_tokens": 24, "seed": 0})

        ta = threading.Thread(target=run_a)
        ta.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            _, m, _ = _http(s.port, "GET", "/metrics")
            if m["serve_occupancy"] >= 1:
                break
            time.sleep(0.01)
        handler.trigger("supervisor TERM")
        # The driver drains; the server keeps sockets open until done.
        ta.join(120)
        status, chunks, done = result["a"]
        assert status == 200 and done
        toks = [c["choices"][0]["token"] for c in chunks
                if c["choices"][0]["token"] is not None]
        assert toks == ref                  # not one token dropped
        s.thread.join(60)
        assert not s.thread.is_alive()      # run() returned on its own


# ----------------------------------------------------------- autoscaler


class FakeRouter:
    """Scripted signal surface for autoscaler units."""

    def __init__(self, n_active=1, max_batch=4):
        self.n_active = n_active
        self.max_batch = max_batch
        self.max_replicas = 8
        self.shed_count = 0
        self.slo_violations = 0
        self.queue = 0
        self.occupancy = 0
        self.grown = 0
        self.retired = 0

    def total_queue_depth(self):
        return self.queue

    def total_occupancy(self):
        return self.occupancy

    def grow(self):
        self.n_active += 1
        self.grown += 1

    def retire(self):
        self.n_active -= 1
        self.retired += 1


def test_autoscaler_grows_after_streak_and_cooldown_holds():
    r = FakeRouter(n_active=1)
    a = Autoscaler(r, max_replicas=3, grow_queue_depth=4.0, grow_after=2,
                   shrink_after=2, cooldown=3)
    r.queue = 8                        # 8 per replica: pressure
    assert a.tick() is None            # streak 1 of 2
    assert a.tick() == "grow"
    assert r.n_active == 2
    for _ in range(3):
        assert a.tick() is None        # cooldown holds even under pressure
    assert a.tick() is None            # post-cooldown: streak rebuilds...
    assert a.tick() == "grow"          # ...over grow_after fresh ticks
    assert r.n_active == 3
    r.queue = 24
    for _ in range(10):
        a.tick()
    assert r.n_active == 3             # max_replicas is a hard ceiling


def test_autoscaler_shed_delta_is_pressure_even_at_low_depth():
    r = FakeRouter(n_active=1)
    a = Autoscaler(r, max_replicas=2, grow_after=1, cooldown=0)
    r.queue = 0
    assert a.tick() is None            # no signal at all... but occupancy 0
    r.shed_count = 1                   # one NEW shed since last tick
    assert a.tick() == "grow"
    # The same cumulative count is not new pressure next tick.
    r.occupancy = r.max_batch * 2      # not idle either
    assert a.tick() is None


def test_autoscaler_shrinks_only_when_fleet_fits_smaller():
    r = FakeRouter(n_active=2)
    a = Autoscaler(r, min_replicas=1, max_replicas=4, shrink_after=2,
                   cooldown=0)
    r.queue, r.occupancy = 0, 7        # 7 > 1 replica's 4 slots: keep both
    for _ in range(5):
        assert a.tick() is None
    r.occupancy = 3                    # fits in one replica now
    assert a.tick() is None            # streak 1 of 2
    assert a.tick() == "shrink"
    assert r.n_active == 1
    for _ in range(5):                 # min_replicas floor
        a.tick()
    assert r.n_active == 1


def test_autoscaler_closed_loop_grows_real_fleet(tiny_params, tiny_config):
    # Real router + engines: a backlog on 1 active replica grows to 2, the
    # grown replica serves traffic, and the idle tail shrinks back.
    router = _make_router(tiny_params, tiny_config, replicas=1,
                          max_replicas=2)
    scaler = Autoscaler(router, min_replicas=1, max_replicas=2,
                        grow_queue_depth=1.0, grow_after=1, shrink_after=2,
                        cooldown=0)
    driver = EngineDriver(router, autoscaler=scaler, autoscale_every=1)
    for i in range(8):
        driver.submit([1, 2, 3, i], 4, rng=i)
    driver.drain()
    assert scaler.scale_ups >= 1
    assert router.engines[1].stats["admitted"] >= 0   # replica exists
    assert scaler.scale_downs >= 1                    # idle tail shrank
    assert router.n_active == 1


def test_router_retire_drains_parked_replica(tiny_params, tiny_config):
    router = _make_router(tiny_params, tiny_config, replicas=2)
    driver = EngineDriver(router)
    hs = [driver.submit([5, 5, 5, i], 6, rng=i) for i in range(4)]
    driver.step()
    victim = router.retire()
    assert victim is not None and router.n_active == 1
    driver.drain()                     # parked replica still steps to idle
    assert all(h.done for h in hs)
    # grow() revives the parked replica rather than building a third.
    assert router.grow() == victim
    assert len(router.engines) == 2


# ------------------------------------------------------------ server CLI


@pytest.mark.parametrize("flags, named", [
    (("--replicas", "0"), "replicas=0"),
    (("--replicas", "3", "--max_replicas", "2"), "max_replicas=2"),
    (("--queue_slo_ms", "0"), "queue_slo_ms"),
], ids=" ".join)
def test_server_cli_refuses_fleet_shape_jax_free(run_cli_jax_free, flags,
                                                 named):
    # The router's own refusals reach the user as argparse errors, and the
    # parent of a worker fleet gets to them without jax and before it
    # spawns a worker.
    r = run_cli_jax_free("frontend", "--placement", "subprocess", *flags)
    assert r.returncode == 2, r.stderr[-300:]
    assert named in r.stderr, r.stderr[-300:]


@pytest.mark.slow
def test_frontend_process_sigterm_exits_zero(tiny_config, tmp_path):
    # The real thing: a gpt2-tpu-frontend process, a live SSE stream, a
    # real SIGTERM — the stream completes and the process exits 0.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "gpt_2_distributed_tpu.serving.frontend.server",
         "--init_random",
         "--n_layer", "2", "--n_embd", "32", "--n_head", "2",
         "--vocab_size", "257", "--seq_len", "64",
         "--max_batch", "4", "--block_size", "8", "--temperature", "0",
         "--replicas", "2", "--prefix_cache", "--port", "0"],
        cwd=REPO, env=env, stderr=subprocess.PIPE, text=True,
    )
    try:
        port = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            line = proc.stderr.readline()
            if "frontend: http://" in line:
                port = int(line.rsplit(":", 1)[1].split()[0])
                break
        assert port, "server never announced its port"
        result = {}

        def run_a():
            result["a"] = _sse(port, {"prompt_ids": [1, 2, 3],
                                      "max_tokens": 32, "seed": 0},
                               timeout=300)

        ta = threading.Thread(target=run_a)
        ta.start()
        # Wait until the request is actually in flight, then TERM.
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            _, m, _ = _http(port, "GET", "/metrics", timeout=60)
            if m["serve_occupancy"] >= 1:
                break
            time.sleep(0.05)
        proc.send_signal(signal.SIGTERM)
        ta.join(300)
        status, chunks, done = result["a"]
        assert status == 200 and done
        assert len([c for c in chunks
                    if c["choices"][0]["token"] is not None]) == 32
        assert proc.wait(timeout=120) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.stderr.close()


# --------------------------------- subprocess placement off the CPU refuses


def _subprocess_args(*extra):
    from gpt_2_distributed_tpu.serving.frontend.server import build_argparser

    return build_argparser().parse_args([
        "--init_random", "--n_layer", "2", "--n_embd", "32", "--n_head", "2",
        "--vocab_size", "257", "--seq_len", "64", "--placement", "subprocess",
        *extra,
    ])


@pytest.mark.parametrize("flags,platform_env,refused", [
    (["--replicas", "2"], None, "cannot be pinned"),
    (["--replicas", "1", "--max_replicas", "3"], None, "cannot be pinned"),
    (["--replicas", "2"], "tpu", "cannot be pinned"),
    (["--replicas", "2", "--device", "tpu"], "cpu", "cannot be pinned"),
    (["--replicas", "2"], "cpu", None),              # CPU: slices are pinned
    (["--replicas", "2", "--device", "cpu"], None, None),
    (["--replicas", "1"], None, None),               # one worker may take the chips
], ids=["two-workers", "autoscale-headroom", "env-tpu", "flag-tpu-beats-env",
        "env-cpu", "flag-cpu", "one-worker"])
def test_unpinnable_subprocess_placement_refused_at_start(
        monkeypatch, flags, platform_env, refused):
    """Off the CPU every worker would take all chips and the second would
    hang in backend init; the spawner refuses before spawning anything."""
    from gpt_2_distributed_tpu.serving.frontend.worker import spawner_from_args
    from gpt_2_distributed_tpu.serving.serve import (
        build_serve_config,
        model_config_from_args,
    )

    if platform_env is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platform_env)
    args = _subprocess_args(*flags)
    serve = build_serve_config(args, model_config_from_args(args))
    if refused is None:
        spawner_from_args(args, serve, initial_replicas=args.replicas)
    else:
        with pytest.raises(SystemExit, match=refused):
            spawner_from_args(args, serve, initial_replicas=args.replicas)


def test_workers_refused_from_a_parent_that_holds_the_chip(monkeypatch):
    from gpt_2_distributed_tpu.serving.frontend.worker import (
        refuse_unpinned_workers,
    )

    jax.devices()                       # this process's (CPU) backend is live
    refuse_unpinned_workers(1)          # a CPU parent holds no chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit, match="already holds the tpu backend"):
        refuse_unpinned_workers(1)
